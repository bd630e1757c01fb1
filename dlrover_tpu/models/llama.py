"""TpuLM — the flagship decoder-only transformer (Llama-family shape:
RMSNorm + RoPE + GQA + SwiGLU; optionally MoE every layer).

Pure-functional: ``init_params`` returns (params, logical_axes) twin
pytrees; ``forward`` is jit/pjit-safe with static shapes and scan-over-
layers. Parallelism is declared, not coded: logical axes map to the
(dp, ep, pp, sp, tp) mesh via parallel/sharding.py rules, giving FSDP
(embed over dp), tensor parallel (heads/mlp/vocab over tp), pipeline
(stage over pp via trainer/pipeline.py), sequence parallel (ring
attention over sp), and expert parallel (expert over ep) from one model
definition.

The reference delegates all of this to torch frameworks (SURVEY.md
section 2.9); here the model layer is first-class so the elastic/ckpt
machinery has a real workload to supervise.
"""

import dataclasses
import functools
import math
import os
from typing import Any, ClassVar, Dict, Tuple

import jax
import jax.numpy as jnp

from dlrover_tpu.models import moe as moe_lib
from dlrover_tpu.ops.attention import dot_product_attention
from dlrover_tpu.ops.norms import rms_norm
from dlrover_tpu.ops.rope import apply_rope
from dlrover_tpu.parallel.sharding import with_logical_constraint


@dataclasses.dataclass(frozen=True)
class TpuLMConfig:
    kind: ClassVar[str] = "llama"    # models.model_for: which module
    vocab_size: int = 32000
    embed_dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 128
    mlp_dim: int = 11008
    rope_theta: float = 10000.0
    dtype: str = "bfloat16"          # compute dtype (params stay f32)
    # MoE (n_experts > 0 makes every layer's MLP an expert layer)
    n_experts: int = 0
    moe_top_k: int = 2
    capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # "gshard": one-hot dispatch with capacity drop (works under ep
    # meshes); "dropless": megablox grouped matmul, zero drops (ep == 1
    # only); "auto": dropless when the mesh has no ep axis.
    moe_impl: str = "auto"
    # pipeline: layer stack is stored [stages, layers_per_stage, ...]
    pp_stages: int = 1
    num_microbatches: int = 1
    remat: bool = True
    # "mlp_only": the attention half of each layer is NOT rematerialized
    #   (its Pallas flash kernel would otherwise re-run in the backward —
    #   a measured ~1ms/layer/step on v5e) while the MLP half keeps the
    #   "dots" policy. Costs ~+130MB/layer of saved attention residuals.
    # "attn_save": the long-context middle ground — the attention call
    #   still escapes remat (at 32k tokens re-running flash attention is
    #   the dominant remat cost) but BOTH flanks recompute fully, so the
    #   saved state stays O(s*d)/layer where "mlp_only"'s dots flanks
    #   would pin the [s, mlp_dim] hiddens (the 32k OOM).
    # "dots": selective rematerialization — matmul outputs are saved,
    #   only elementwise work recomputes in the backward (measured +2 MFU
    #   points over full remat on v5e at the bench config).
    # "full": recompute everything (lowest memory; the hyperparam
    #   strategy escalates to this on OOM evidence).
    remat_policy: str = "mlp_only"

    def __post_init__(self):
        if self.remat_policy not in (
            "mlp_only", "attn_save", "dots", "full"
        ):
            raise ValueError(
                f"remat_policy {self.remat_policy!r} not in ('mlp_only', "
                f"'attn_save', 'dots', 'full') — a typo here silently "
                f"costs MFU"
            )
        if self.moe_impl not in ("auto", "gshard", "dropless"):
            raise ValueError(
                f"moe_impl {self.moe_impl!r} not in ('auto', 'gshard', "
                f"'dropless')"
            )

    @property
    def layers_per_stage(self) -> int:
        if self.n_layers % self.pp_stages:
            raise ValueError(
                f"n_layers {self.n_layers} % pp_stages {self.pp_stages} != 0"
            )
        return self.n_layers // self.pp_stages

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    def flops_per_token(self) -> float:
        """Approximate training FLOPs per token (fwd+bwd ~= 6 * params)."""
        return 6.0 * self.count_params()

    def attention_flops_per_token(self, seq: int, causal: bool = True):
        """Training attention-matmul FLOPs per token at sequence ``seq``:
        3 (fwd + bwd) x 2 matmuls (QK^T, AV) x 2 FLOPs/MAC x seq x
        n_heads x head_dim per layer, halved for causal masking. Excluded
        from the 6N model-FLOPs basis; at long context they dominate, so
        honest MFU there is (6N + attention) — the basis the longctx
        bench reports."""
        f = 12.0 * self.n_layers * self.n_heads * self.head_dim * seq
        return f / 2 if causal else f

    def count_params(self) -> int:
        d, hd = self.embed_dim, self.head_dim
        attn = d * (self.n_heads + 2 * self.n_kv_heads) * hd
        attn += self.n_heads * hd * d
        if self.n_experts > 0:
            mlp = 3 * d * self.mlp_dim * self.n_experts + d * self.n_experts
        else:
            mlp = 3 * d * self.mlp_dim
        per_layer = attn + mlp + 2 * d
        return (
            self.n_layers * per_layer
            + 2 * self.vocab_size * d
            + d
        )

    def count_active_params(self) -> int:
        """Params a single token actually touches — for MoE, top_k
        experts instead of all of them (the honest 6N basis for MoE
        MFU; equals count_params() for dense configs)."""
        if self.n_experts == 0:
            return self.count_params()
        d = self.embed_dim
        dense_mlp = 3 * d * self.mlp_dim
        all_mlp = dense_mlp * self.n_experts
        active_mlp = dense_mlp * self.moe_top_k
        return self.count_params() - self.n_layers * (
            all_mlp - active_mlp
        )


def tiny_config(**overrides) -> TpuLMConfig:
    """A config small enough for CPU tests yet exercising every axis."""
    defaults = dict(
        vocab_size=256,
        embed_dim=64,
        n_layers=4,
        n_heads=4,
        n_kv_heads=2,
        head_dim=16,
        mlp_dim=128,
        dtype="float32",
    )
    defaults.update(overrides)
    return TpuLMConfig(**defaults)


def flagship_config(**overrides) -> TpuLMConfig:
    """The 334M-param dense model the bench, the example and
    chip_smoke.py all mean by "flagship": the largest whose train state
    (f32 weights + Adam, ~4 GB) and activations fit one 16 GB chip."""
    defaults = dict(
        vocab_size=32000,
        embed_dim=1024,
        n_layers=16,
        n_heads=8,
        n_kv_heads=8,
        head_dim=128,
        mlp_dim=4096,
        dtype="bfloat16",
    )
    defaults.update(overrides)
    return TpuLMConfig(**defaults)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _layer_leading(config) -> Tuple[Tuple[int, ...], Tuple[str, ...]]:
    """Leading dims/axes of stacked layer params."""
    if config.pp_stages > 1:
        return (
            (config.pp_stages, config.layers_per_stage),
            ("stage", "layer"),
        )
    return ((config.n_layers,), ("layer",))


def param_axes(config: TpuLMConfig) -> Dict[str, Any]:
    """Logical-axis names per param leaf (static; no tracing needed)."""
    lead_ax = _layer_leading(config)[1]
    layer_axes = {
        "attn_norm": lead_ax + ("norm",),
        "wq": lead_ax + ("embed", "heads", "head_dim"),
        "wk": lead_ax + ("embed", "kv_heads", "head_dim"),
        "wv": lead_ax + ("embed", "kv_heads", "head_dim"),
        "wo": lead_ax + ("heads", "head_dim", "embed"),
        "mlp_norm": lead_ax + ("norm",),
    }
    if config.n_experts > 0:
        layer_axes.update(
            router=lead_ax + ("embed", "expert"),
            w_gate=lead_ax + ("expert", "embed", "mlp"),
            w_up=lead_ax + ("expert", "embed", "mlp"),
            w_down=lead_ax + ("expert", "mlp", "embed"),
        )
    else:
        layer_axes.update(
            w_gate=lead_ax + ("embed", "mlp"),
            w_up=lead_ax + ("embed", "mlp"),
            w_down=lead_ax + ("mlp", "embed"),
        )
    return {
        "embed": ("vocab", "embed"),
        "layers": layer_axes,
        "final_norm": ("norm",),
        "lm_head": ("embed", "vocab"),
    }


def init_params(
    config: TpuLMConfig, rng: jax.Array
) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Returns (params, logical_axes): twin pytrees.

    Simple init: normal(0, 1/sqrt(fan_in)); norm scales zero (the
    (1+scale) parameterization makes zero the identity).
    """
    d, hd = config.embed_dim, config.head_dim
    h, kv = config.n_heads, config.n_kv_heads
    f, v = config.mlp_dim, config.vocab_size
    lead, _ = _layer_leading(config)

    keys = jax.random.split(rng, 16)

    def dense(key, shape, fan_in):
        return (
            jax.random.normal(key, shape, dtype=jnp.float32)
            / math.sqrt(fan_in)
        )

    layers = {
        "attn_norm": jnp.zeros(lead + (d,), jnp.float32),
        "wq": dense(keys[0], lead + (d, h, hd), d),
        "wk": dense(keys[1], lead + (d, kv, hd), d),
        "wv": dense(keys[2], lead + (d, kv, hd), d),
        "wo": dense(keys[3], lead + (h, hd, d), h * hd),
        "mlp_norm": jnp.zeros(lead + (d,), jnp.float32),
    }
    if config.n_experts > 0:
        e = config.n_experts
        layers.update(
            router=dense(keys[4], lead + (d, e), d),
            w_gate=dense(keys[5], lead + (e, d, f), d),
            w_up=dense(keys[6], lead + (e, d, f), d),
            w_down=dense(keys[7], lead + (e, f, d), f),
        )
    else:
        layers.update(
            w_gate=dense(keys[5], lead + (d, f), d),
            w_up=dense(keys[6], lead + (d, f), d),
            w_down=dense(keys[7], lead + (f, d), f),
        )

    params = {
        "embed": dense(keys[8], (v, d), 1.0),  # ~N(0,1) embedding
        "layers": layers,
        "final_norm": jnp.zeros((d,), jnp.float32),
        "lm_head": dense(keys[9], (d, v), d),
    }
    return params, param_axes(config)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


_ATTN_CACHE: Dict[str, Any] = {}


def default_attention_fn():
    """Best attention impl for contiguous-position causal attention on the
    current backend: the Pallas flash kernel (ops/pallas_attention.py) on
    TPU, the XLA reference op elsewhere (``None`` → transformer_layer's
    ``dot_product_attention`` fallback). Decided once, by the backend
    alone; a caller that wants another attention passes
    ``attention_fn=``.
    """
    if "fn" not in _ATTN_CACHE:
        if jax.default_backend() == "tpu":
            from dlrover_tpu.ops.pallas_attention import make_flash_attention

            _ATTN_CACHE["fn"] = make_flash_attention()
        else:
            _ATTN_CACHE["fn"] = None
    return _ATTN_CACHE["fn"]


def attention_qkv(config: TpuLMConfig, p, x, positions):
    """Pre-attention block: norm + QKV projections + RoPE.

    Shared by the training layer and the KV-cache decode path
    (models/generate.py) so the two can never drift."""
    cdt = config.compute_dtype
    hx = rms_norm(x, p["attn_norm"]).astype(cdt)
    q = jnp.einsum("bsd,dhk->bshk", hx, p["wq"].astype(cdt))
    k = jnp.einsum("bsd,dhk->bshk", hx, p["wk"].astype(cdt))
    v = jnp.einsum("bsd,dhk->bshk", hx, p["wv"].astype(cdt))
    q = with_logical_constraint(q, ("batch", "seq", "heads", "head_dim"))
    k = with_logical_constraint(k, ("batch", "seq", "kv_heads", "head_dim"))
    q = apply_rope(q, positions, config.rope_theta)
    k = apply_rope(k, positions, config.rope_theta)
    return q, k, v


def attention_out(config: TpuLMConfig, p, attn, residual):
    """Post-attention projection + residual add (shared with decode)."""
    cdt = config.compute_dtype
    out = jnp.einsum("bshk,hkd->bsd", attn, p["wo"].astype(cdt))
    x = residual + out.astype(residual.dtype)
    return with_logical_constraint(x, ("batch", "seq", "embed"))


def _moe_resolve_impl(config) -> str:
    """Which MoE path runs: "gshard" | "dropless" | "dropless_sharded"
    | "dropless_ep".

    Explicit ``moe_impl="dropless"`` maps to the mesh-appropriate
    dropless variant: the single-device core, the shard_map-per-shard
    form on multi-device meshes without ep (the global-argsort core has
    data-dependent group sizes GSPMD cannot lower soundly — it must
    never see a sharded batch directly), or the ragged-all-to-all ep
    form. "auto" follows the measured crossover (r05,
    v5e, 2026-08-01, 334M): gshard wins at the default capacity
    factor (1.25: e.g. 9.3 vs 12.9 ms/layer at 8 experts), dropless
    wins once the capacity budget reaches ~2.0 — and at that point it
    is also drop-free, so auto picks it there. Multi-device auto stays
    on the GSPMD-proven gshard path."""
    from dlrover_tpu.parallel.sharding import current_mesh

    mesh = current_mesh()
    multi = mesh is not None and mesh.size > 1
    has_ep = mesh is not None and dict(mesh.shape).get("ep", 1) > 1
    if config.moe_impl == "gshard":
        return "gshard"
    if config.moe_impl == "dropless":
        if has_ep:
            return "dropless_ep"
        return "dropless_sharded" if multi else "dropless"
    if not multi and config.capacity_factor >= 2.0:
        return "dropless"
    return "gshard"


def mlp_block(config: TpuLMConfig, p, x):
    """Residual MLP (dense or MoE). Returns (x, aux). Shared with the
    decode path."""
    with jax.named_scope("mlp"):
        return _mlp_block_inner(config, p, x)


def _mlp_block_inner(config: TpuLMConfig, p, x):
    cdt = config.compute_dtype
    residual = x
    hx = rms_norm(x, p["mlp_norm"]).astype(cdt)
    if config.n_experts > 0:
        impl = _moe_resolve_impl(config)
        experts = (p["router"], p["w_gate"], p["w_up"], p["w_down"])
        if impl == "dropless":
            out, metrics = moe_lib.moe_mlp_dropless(
                hx, *experts, top_k=config.moe_top_k
            )
        elif impl in ("dropless_sharded", "dropless_ep"):
            from dlrover_tpu.parallel.sharding import current_mesh

            fn = (
                moe_lib.moe_mlp_dropless_ep
                if impl == "dropless_ep"
                else moe_lib.moe_mlp_dropless_sharded
            )
            out, metrics = fn(
                hx, *experts, mesh=current_mesh(),
                top_k=config.moe_top_k,
            )
        else:
            out, metrics = moe_lib.moe_mlp(
                hx,
                *experts,
                top_k=config.moe_top_k,
                capacity_factor=config.capacity_factor,
            )
        aux = metrics.aux_loss + 0.001 * metrics.router_z_loss
    else:
        g = jnp.einsum("bsd,df->bsf", hx, p["w_gate"].astype(cdt))
        u = jnp.einsum("bsd,df->bsf", hx, p["w_up"].astype(cdt))
        g = with_logical_constraint(g, ("batch", "seq", "mlp"))
        out = jnp.einsum(
            "bsf,fd->bsd", jax.nn.silu(g) * u, p["w_down"].astype(cdt)
        )
        aux = jnp.zeros((), jnp.float32)
    x = residual + out.astype(x.dtype)
    x = with_logical_constraint(x, ("batch", "seq", "embed"))
    return x, aux


def transformer_layer(
    config: TpuLMConfig,
    layer_params: Dict[str, jnp.ndarray],
    x,
    positions,
    attention_fn=None,
):
    """One decoder block. x: [b, s, d]; positions: [b, s] global indices.

    Returns (x, moe_aux_losses or None).
    """
    p = layer_params
    attn_fn = attention_fn or dot_product_attention

    residual = x
    # named_scope: the scope lands in the compiled HLO's op_name of
    # every op, forward AND backward; benchmark/trace_reduce.py buckets
    # device time by it (scopes_from_hlo — this JAX's trace events
    # carry no tf_op).
    with jax.named_scope("attn"):
        q, k, v = attention_qkv(config, p, x, positions)
        attn = attn_fn(q, k, v, causal=True,
                       q_positions=positions, kv_positions=positions)
        x = attention_out(config, p, attn, residual)
    return mlp_block(config, p, x)


def embed_tokens(config, params, tokens):
    # Release the table's FSDP (embed-over-dp) sharding BEFORE the
    # gather: the [vocab, d] all-gather is cheap, while letting GSPMD
    # reshard the [b, s, d] gather output (which inherits the table's
    # embed sharding) triggers involuntary full rematerialization on
    # meshes where batch/seq/embed axes all move (observed on sp).
    table = with_logical_constraint(params["embed"], ("vocab", None))
    x = jnp.take(table, tokens, axis=0).astype(config.compute_dtype)
    return with_logical_constraint(x, ("batch", "seq", "embed"))


def final_hidden(config, params, x):
    """Final-norm + compute-dtype cast — the single head path shared by
    ``unembed`` and the fused-CE loss so they can never diverge."""
    return rms_norm(x, params["final_norm"]).astype(config.compute_dtype)


def unembed(config, params, x):
    with jax.named_scope("vocab"):
        x = final_hidden(config, params, x)
        # bf16 einsum + separate f32 cast measures ~2ms/step better
        # than a preferred_element_type=f32 matmul here: XLA fuses the
        # convert into the loss consumers, so the bf16 intermediate
        # halves the HBM write.
        logits = jnp.einsum(
            "bsd,dv->bsv",
            x, params["lm_head"].astype(config.compute_dtype),
        )
        return with_logical_constraint(
            logits.astype(jnp.float32), ("batch", "seq", "vocab")
        )


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _attn_block_lite(config, p, x, positions):
    """Norm + qkv projection + rope + flash attention as ONE
    differentiable unit whose backward residuals are (p, x, out,
    compact lse) — NOT (q, k, v, out, lse).

    This is what lets the ``attn_save`` remat policy fit at 64k
    tokens: the plain escape pins q/k/v/out per layer (512MB/layer at
    64k, 8GB across 16 layers — a compile-time HBM OOM on 16GB v5e),
    while this block re-derives q/k/v from the saved layer input in
    the backward (cheap projections, the same recompute the flanks
    already pay) and still never re-runs the flash FORWARD (out/lse
    are saved — re-running it is what makes plain "full" remat slow
    at long context). ~258MB/layer saved at 64k."""
    from dlrover_tpu.ops.pallas_attention import flash_attention

    q, k, v = attention_qkv(config, p, x, positions)
    return flash_attention(q, k, v, True)


def _attn_block_lite_fwd(config, p, x, positions):
    from dlrover_tpu.ops.pallas_attention import flash_forward

    q, k, v = attention_qkv(config, p, x, positions)
    interpret = jax.default_backend() != "tpu"
    out, lse_c = flash_forward(q, k, v, True, None, interpret)
    return out, (p, x, positions, out, lse_c)


def _attn_block_lite_bwd(config, res, g):
    import numpy as np

    from dlrover_tpu.ops.pallas_attention import flash_backward

    p, x, positions, out, lse_c = res
    (q, k, v), qkv_vjp = jax.vjp(
        lambda p_, x_: attention_qkv(config, p_, x_, positions), p, x
    )
    interpret = jax.default_backend() != "tpu"
    dq, dk, dv = flash_backward(
        q, k, v, out, lse_c, g, True, None, interpret
    )
    dp, dx = qkv_vjp((dq, dk, dv))
    dpos = np.zeros(positions.shape, jax.dtypes.float0)
    return dp, dx, dpos


_attn_block_lite.defvjp(_attn_block_lite_fwd, _attn_block_lite_bwd)


def run_layer_stack(
    config: TpuLMConfig,
    layer_params,
    x,
    positions,
    attention_fn=None,
):
    """scan over a [L, ...] stacked layer pytree (single pipeline stage)."""

    # Cast the stacked MATMUL params to the compute dtype ONCE, outside
    # the scan: the scan's per-layer dynamic-slice then moves half the
    # bytes (f32 master params slice+convert measured ~0.8ms/layer/step
    # on v5e, in both the forward and the backward's recompute).
    # Gradients still reach the optimizer in f32 — the convert's
    # transpose upcasts the bf16 layer cotangents automatically. Norm
    # scales and the MoE router stay f32: rms_norm and moe_mlp
    # deliberately compute those in f32, and rounding the master values
    # here would silently flip near-boundary top-k routing decisions.
    cdt = config.compute_dtype
    if cdt != jnp.float32:
        keep_f32 = {"attn_norm", "mlp_norm", "router"}
        layer_params = {
            k: (v if k in keep_f32 else v.astype(cdt))
            for k, v in layer_params.items()
        }

    dots_policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable

    # "mlp_only" exempts the attention call from remat on the premise
    # that its saved residuals are O(s*d) — true for the flash kernel
    # (custom VJP: q/k/v/out + compact lse) but NOT for plain XLA
    # attention or other impls, whose backward would pin O(s^2) softmax
    # intermediates per layer. Impls that keep O(s*d) residuals declare
    # it via a ``saveable_residuals`` attribute; everything else demotes
    # to the "dots" policy.
    attn_escapes = (
        config.remat
        and config.remat_policy in ("mlp_only", "attn_save")
        and getattr(attention_fn, "saveable_residuals", False)
    )
    if attn_escapes:
        # Only the flash-attention call itself escapes rematerialization
        # (re-running its Pallas forward in the backward costs a measured
        # ~1ms/layer/step at 2k and dominates the remat bill at 32k).
        # "mlp_only": flanks keep the dots policy — the extra saved
        # state is just (q_roped, k_roped, v, attn_out) plus the compact
        # lse; the pre-rope projections DCE away because rope's backward
        # only needs the (recomputed) sin/cos. "attn_save": flanks
        # recompute fully — the long-context memory budget.
        flank_policy = (
            dots_policy if config.remat_policy == "mlp_only" else None
        )

        def out_mlp(p, attn, residual):
            with jax.named_scope("attn"):
                y = attention_out(config, p, attn, residual)
            return mlp_block(config, p, y)

        ckpt_out_mlp = jax.checkpoint(out_mlp, policy=flank_policy)

        if config.remat_policy == "attn_save" and getattr(
            attention_fn, "is_plain_flash", False
        ):
            # The memory-tight policy uses the lite block: residuals
            # are (x, out, lse) instead of (q, k, v, out, lse) — what
            # makes 64k-token training compile on one 16GB chip (see
            # _attn_block_lite). Independent of the passed
            # attention_fn by construction: is_plain_flash asserts the
            # fn IS the default flash kernel.
            def body(carry, pl):
                with jax.named_scope("attn"):
                    attn = _attn_block_lite(config, pl, carry, positions)
                return ckpt_out_mlp(pl, attn, carry)

        else:
            attn_fn = attention_fn or dot_product_attention
            ckpt_qkv = jax.checkpoint(
                functools.partial(attention_qkv, config),
                policy=flank_policy,
            )

            def body(carry, pl):
                with jax.named_scope("attn"):
                    q, k, v = ckpt_qkv(pl, carry, positions)
                    attn = attn_fn(
                        q, k, v, causal=True,
                        q_positions=positions, kv_positions=positions,
                    )
                return ckpt_out_mlp(pl, attn, carry)

    else:
        def body(carry, pl):
            y, aux = transformer_layer(
                config, pl, carry, positions, attention_fn
            )
            return y, aux

        if config.remat:
            policy = (
                dots_policy
                if config.remat_policy in ("dots", "mlp_only")
                else None
            )
            body = jax.checkpoint(body, policy=policy)
    x, auxes = jax.lax.scan(body, x, layer_params)
    return x, jnp.sum(auxes)


def forward_hidden(
    config: TpuLMConfig,
    params,
    tokens,                      # [b, s] int32
    positions=None,              # [b, s] global positions
    attention_fn=None,
):
    """Forward up to (but excluding) the final norm + unembedding.

    Returns (hidden [b, s, d], aux_loss scalar). pp_stages must be 1 —
    the pipelined path owns its own unembed placement.
    """
    if attention_fn is None and positions is None:
        attention_fn = default_attention_fn()
    b, s = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x = embed_tokens(config, params, tokens)
    return run_layer_stack(
        config, params["layers"], x, positions, attention_fn
    )


def forward(
    config: TpuLMConfig,
    params,
    tokens,                      # [b, s] int32
    positions=None,              # [b, s] global positions
    attention_fn=None,
):
    """Full forward. Dispatches to trainer/pipeline.py when
    pp_stages > 1. Returns (logits [b, s, vocab] f32, aux_loss scalar).

    When the caller passes no explicit ``attention_fn`` and no explicit
    ``positions`` (i.e. positions are the contiguous [0..s) default), the
    attention impl is resolved by ``default_attention_fn`` — the Pallas
    flash kernel on TPU. Callers with sharded/packed positions (ring
    attention, SP meshes) pass their own ``attention_fn``.
    """
    if config.pp_stages > 1:
        if attention_fn is None and positions is None:
            attention_fn = default_attention_fn()
        from dlrover_tpu.trainer.pipeline import pipelined_forward

        return pipelined_forward(
            config, params, tokens, positions, attention_fn
        )
    x, aux = forward_hidden(config, params, tokens, positions, attention_fn)
    return unembed(config, params, x), aux


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def cross_entropy(logits, targets, mask=None, z_weight: float = 1e-4):
    """Token-mean CE + z-loss. logits f32 [b,s,v]; targets int [b,s]."""
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    target_logit = jnp.take_along_axis(
        logits, targets[..., None], axis=-1
    )[..., 0]
    nll = logz - target_logit
    zloss = z_weight * jnp.square(logz)
    per_tok = nll + zloss
    if mask is None:
        return jnp.mean(per_tok)
    mask = mask.astype(jnp.float32)
    return jnp.sum(per_tok * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def _fused_ce_mode() -> str:
    """Parse DLROVER_TPU_FUSED_CE once: "on" | "off" | "auto".

    Unrecognized values warn and fall back to auto instead of silently
    flipping the CE path."""
    raw = os.environ.get("DLROVER_TPU_FUSED_CE", "auto").lower()
    if raw in ("on", "1", "fused", "true"):
        return "on"
    if raw in ("off", "0", "unfused", "false"):
        return "off"
    if raw != "auto":
        import logging

        logging.getLogger(__name__).warning(
            "DLROVER_TPU_FUSED_CE=%r not in (on, off, auto); using auto",
            raw,
        )
    return "auto"


def _fused_ce_applicable(config) -> bool:
    """Fused CE handles pp == 1 and vocab-unsharded meshes. Under tensor
    parallelism the vocab dim of lm_head is sharded — there the unfused
    path is the right one anyway (GSPMD shards the logits matmul and
    inserts the logsumexp psum); a blockwise dynamic-slice over a sharded
    vocab would force per-block collectives instead."""
    if config.pp_stages > 1:
        return False
    from dlrover_tpu.parallel.sharding import current_mesh, logical_to_spec

    mesh = current_mesh()
    if mesh is None:
        return True
    vocab_spec = logical_to_spec(("embed", "vocab"))[1]
    if vocab_spec is None:
        return True
    axes = (vocab_spec,) if isinstance(vocab_spec, str) else vocab_spec
    return all(dict(mesh.shape).get(a, 1) == 1 for a in axes)


def resolve_ce_path(config, n_tokens: int) -> str:
    """"fused" | "dense" — the CE decision ``loss_fn`` makes for a
    batch of ``n_tokens`` tokens, exposed so the driver dryrun
    (__graft_entry__.py) can LOG which CE path each certified mesh
    executed (VERDICT r4 #8). Mesh-dependent: call under the same
    ``with mesh:`` the step runs in.

    The chunked fused CE runs at ~0.99-1.07x dense on v5e (same three
    matmuls; gradients computed in the forward, see ops/fused_ce.py)
    while never materializing the [N, V] logits. "auto" engages it
    only ABOVE the measured N*V crossover
    (ops/fused_ce.AUTO_FUSED_MIN_NV ≈ 2 GiB of f32 logits): r05 (v5e)
    measured the chunked path at 1.042x dense at the flagship shape
    just below the line, while above it the memory freed is what lets
    the attn_save remat policy fit at 32k tokens and the time cost is
    a wash. Below the line dense keeps its measured edge on the
    flagship MFU path."""
    from dlrover_tpu.ops.fused_ce import auto_prefers_dense

    mode = _fused_ce_mode()
    use_fused = mode == "on" or (
        mode == "auto"
        and not auto_prefers_dense(n_tokens, config.vocab_size)
    )
    if use_fused and _fused_ce_applicable(config):
        return "fused"
    return "dense"


def head_loss(config, params, x, targets, mask=None):
    """Token-mean CE (+ z-loss) of the stack's output ``x [b, s, d]``:
    final norm, then the fused blockwise CE (ops/fused_ce.py) whenever
    applicable so the [b, s, vocab] f32 logits never materialize, else
    the head and ``cross_entropy`` (see ``resolve_ce_path``). Shared by
    every model whose stack ends in this head (models/hybrid.py)."""
    if resolve_ce_path(config, targets.size) == "fused":
        from dlrover_tpu.ops.fused_ce import fused_cross_entropy

        with jax.named_scope("vocab"):
            h = final_hidden(config, params, x)
            # Long sequences cap the CE row chunk at 4096: the 8192-row
            # tile pushed the whole-program TPU compile over the edge
            # when combined with the attn_save remat policy (measured
            # v5e: compile-helper failure at 32k tokens; 4096 compiles
            # and times identically there, and at long context the CE is
            # ~2% of the step). Short-sequence large-batch runs keep the
            # measured-fastest auto chunk.
            return fused_cross_entropy(
                h,
                params["lm_head"].astype(config.compute_dtype),
                targets,
                mask,
                block_rows=4096 if targets.shape[1] >= 32768 else None,
            )
    logits = unembed(config, params, x)
    with jax.named_scope("vocab"):
        return cross_entropy(logits, targets, mask)


def loss_fn(config, params, batch, attention_fn=None):
    """batch: {"tokens": [b,s+1]} — next-token LM loss through
    ``head_loss``; pipelined runs take ``forward`` + ``cross_entropy``
    (the pipeline owns its unembed placement). Set
    DLROVER_TPU_FUSED_CE=off to force the unfused path.
    """
    tokens = batch["tokens"][:, :-1]
    targets = batch["tokens"][:, 1:]
    if config.pp_stages > 1:
        logits, aux = forward(
            config, params, tokens, attention_fn=attention_fn
        )
        with jax.named_scope("vocab"):
            ce = cross_entropy(logits, targets, batch.get("mask"))
    else:
        x, aux = forward_hidden(
            config, params, tokens, attention_fn=attention_fn
        )
        ce = head_loss(config, params, x, targets, batch.get("mask"))
    loss = ce + config.moe_aux_weight * aux
    return loss, {"ce": ce, "aux": aux}
