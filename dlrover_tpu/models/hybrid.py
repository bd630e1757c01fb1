"""Layer-pattern language models: a stack that is NOT one layer repeated.

A model here is a few leading layers, unrolled, and then a period of
``(mixer kind, ffn kind)`` pairs scanned over its repeats. A kind is one
entry of ``MIXERS`` or ``FFNS``: how to make its parameters, their
logical axes, and the function itself. What ships:

- mixer ``kda``: gated delta-rule linear attention (short causal
  convolutions, L2-normed q and k, a per-channel decay, the delta-rule
  state of ``ops/kda.py``, a gated per-head norm on the way out);
- mixer ``mla``: latent attention without rotary (NoPE): q of
  ``qk_nope + qk_rope`` per head, a shared low-rank K/V latent, values of
  their own head size, through the flash kernel;
- mixer ``mla_rope``: the same layer with its positional slices ROTATED
  (``ops/rope.apply_rope`` at ``rope_theta``, on the queries' slice of
  every head and on the one key slice the heads share) and, where
  ``q_lora_rank`` is set, queries through a bottleneck (``w_qa`` ->
  RMSNorm -> ``w_qb``, :func:`mla_bottleneck_queries`);
- ffn ``dense``: SwiGLU; ffn ``moe``: a shared expert plus this chip's
  share of a sigmoid-routed expert layer (``moe.moe_mlp_share``).

Every block is pre-norm residual: ``x += mixer(norm(x))``,
``x += ffn(norm(x))``. Embedding, final norm, head, cross-entropy and
the (1 + scale) RMSNorm are ``models/llama.py``'s. The dense model is
the one-kind pattern by design but still runs ``llama.run_layer_stack``;
so do ``generate.py`` and the serving engines (ROADMAP D1).

Parameters: ``{"embed", "leading": [layer, ...], "period": [layer with a
leading repeat axis, ...], "final_norm", "lm_head"}``, a layer being
``{"mixer_norm", "mixer": {...}, "ffn_norm", "ffn": {...}}``. What is
state but not trained (the routers' score-correction bias) lives in a
twin tree of BUFFERS (``init_buffers``), which the train step carries
beside the parameters and gives no gradient and no optimizer state.

With ``mtp_depth`` 1 a multi-token-prediction MODULE sits beside the
stack (DeepSeek-V3, arXiv:2412.19437 section 2.2): ``params["mtp"] =
{"norm_h", "norm_e", "w_eh", "block": layer, "norm"}`` and
``buffers["mtp"] = {"block": ...}``. It joins the stack's output at
position ``i`` (before the final norm) with the embedding of token
``i + 1``, runs one more block of the period's last kind and predicts
token ``i + 2`` through the model's OWN embedding and head, which so
collect gradient from both losses (:func:`mtp_loss`); a sequence is then
``seq_len + 2`` tokens. Depth 0 has neither key and traces to the same
program as before the module existed.
"""

import dataclasses
import functools
import math
from typing import Any, Callable, ClassVar, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from dlrover_tpu.common.log import logger
from dlrover_tpu.models import llama
from dlrover_tpu.models import moe as moe_lib
from dlrover_tpu.ops import kda as kda_ops
from dlrover_tpu.ops import kda_tail
from dlrover_tpu.ops import rope
from dlrover_tpu.ops.attention import dot_product_attention
from dlrover_tpu.ops.norms import rms_norm
from dlrover_tpu.parallel.sharding import with_logical_constraint

Pattern = Tuple[Tuple[str, str], ...]
COUNTERS = ("moe_rows_held", "moe_rows_max", "moe_rows_dropped",
            "moe_rows_full_path")
MTP_COUNTER = "mtp_moe_rows_held"    # the module's block, apart
REMAT_KEEP = ("dots", "attention")


@dataclasses.dataclass(frozen=True)
class HybridLMConfig:
    kind: ClassVar[str] = "hybrid"
    pp_stages: ClassVar[int] = 1         # llama's head asks
    moe_aux_weight: ClassVar[float] = 0.0

    vocab_size: int = 256                # rows of the vocabulary HELD
    embed_dim: int = 64
    leading: Pattern = (("kda", "dense"),)
    period: Pattern = (
        ("kda", "moe"), ("kda", "moe"), ("mla", "moe"), ("kda", "moe"),
    )
    n_periods: int = 1
    # kda
    kda_heads: int = 4
    kda_head_dim: int = 16               # keys and values
    kda_conv: int = 4
    kda_gate_rank: int = 16
    # mla, mla_rope
    n_heads: int = 4
    kv_lora_rank: int = 32
    qk_nope_dim: int = 16
    qk_rope_dim: int = 8
    v_head_dim: int = 16
    q_lora_rank: int = 0                 # mla_rope: 0 is one plain wq
    rope_theta: float = 1e4
    # ffn
    mlp_dim: int = 128                   # the dense SwiGLU
    moe_mlp_dim: int = 32                # each expert, and the shared one
    n_experts: int = 16                  # the router's width
    moe_top_k: int = 4
    experts_held: Tuple[int, int] = (0, 16)   # (first, count) living here
    n_shared_experts: int = 1
    routed_scaling: float = 1.0
    # A multi-token-prediction module beside the stack (0: none, 1: one
    # block predicting the token after next) and its loss's weight.
    mtp_depth: int = 0
    mtp_weight: float = 0.3
    # What a block keeps for its backward: "dots" (its projection
    # matmuls' outputs and a KDA scan's) or "attention" (the flash
    # kernels' outputs alone: the projections run again).
    remat_keep: str = "dots"
    dtype: str = "bfloat16"              # compute dtype (params stay f32)

    def __post_init__(self):
        if self.mtp_depth not in (0, 1) or self.remat_keep not in REMAT_KEEP:
            raise ValueError(
                f"mtp_depth {self.mtp_depth} (0 or 1), remat_keep "
                f"{self.remat_keep!r} (one of {REMAT_KEEP})"
            )
        for mixer, ffn in self.leading + self.period:
            if mixer not in MIXERS or ffn not in FFNS:
                raise ValueError(f"no layer kind ({mixer!r}, {ffn!r})")
        first, count = self.experts_held
        if not (0 <= first and 1 <= count and first + count <= self.n_experts):
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"block of the {self.n_experts} experts")

    @property
    def n_layers(self) -> int:
        return len(self.leading) + self.n_periods * len(self.period)

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def mtp_kinds(self) -> Tuple[str, str]:
        """The kind of the prediction module's block: the period's last."""
        return self.period[-1]

    @property
    def positional(self) -> bool:
        """Whether any mixer of the model reads positions."""
        kinds = self.leading + self.period
        return any(MIXERS[mixer].positional for mixer, _ in kinds)


def tiny_config(**overrides) -> HybridLMConfig:
    """One dense-FFN KDA layer and one period, CPU-test sized."""
    return HybridLMConfig(**dict({"dtype": "float32"}, **overrides))


class Kind(NamedTuple):
    init: Callable      # (config, key) -> params
    axes: Callable      # (config) -> logical axes, same tree
    apply: Callable     # mixer: (config, p, h) -> y, or with
    #                     ``positional``: (config, p, h, positions) -> y
    #                     ffn: (config, p, buffers, h) -> (y, counters)
    buffers: Callable = lambda config, key: {}
    positional: bool = False


def _dense(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)


def _proj(spec, x, w):
    return jnp.einsum(spec, x, w.astype(x.dtype))


# -- mixer: KDA ---------------------------------------------------------------


def _kda_init(config, key):
    d, h, hd = config.embed_dim, config.kda_heads, config.kda_head_dim
    r, width = config.kda_gate_rank, config.kda_conv
    ks = jax.random.split(key, 14)
    dt = jnp.exp(jax.random.uniform(
        ks[11], (h, hd), jnp.float32, math.log(1e-3), math.log(1e-1)
    ))
    return {
        "wq": _dense(ks[0], (d, h, hd), d),
        "wk": _dense(ks[1], (d, h, hd), d),
        "wv": _dense(ks[2], (d, h, hd), d),
        "conv_q": _dense(ks[3], (width, h, hd), width),
        "conv_k": _dense(ks[4], (width, h, hd), width),
        "conv_v": _dense(ks[5], (width, h, hd), width),
        "w_a1": _dense(ks[6], (d, r), d),
        "w_a2": _dense(ks[7], (r, h, hd), r),
        "w_g1": _dense(ks[8], (d, r), d),
        "w_g2": _dense(ks[9], (r, h, hd), r),
        "w_beta": _dense(ks[10], (d, h), d),
        # decay rate exp(a_log) in [1, 16], softplus(dt_bias) in
        # [1e-3, 1e-1]: the public implementation's start.
        "a_log": jnp.log(jax.random.uniform(
            ks[12], (h,), jnp.float32, 1.0, 16.0
        )),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "o_norm": jnp.zeros((hd,), jnp.float32),
        "wo": _dense(ks[13], (h, hd, d), h * hd),
    }


def _kda_axes(config):
    head = ("heads", "head_dim")
    return {
        "wq": ("embed",) + head, "wk": ("embed",) + head,
        "wv": ("embed",) + head,
        "conv_q": (None,) + head, "conv_k": (None,) + head,
        "conv_v": (None,) + head,
        "w_a1": ("embed", None), "w_a2": (None,) + head,
        "w_g1": ("embed", None), "w_g2": (None,) + head,
        "w_beta": ("embed", "heads"),
        "a_log": ("heads",), "dt_bias": head, "o_norm": ("norm",),
        "wo": head + ("embed",),
    }


def _short_conv(x, w):
    """Causal depthwise convolution over time, one filter a channel,
    zeros to the left: ``y_t = sum_j w[j] x[t - (K-1) + j]``, float32.
    x ``[b, h, s, k]``, w ``[K, h, k]``."""
    width, s = w.shape[0], x.shape[2]
    x = jnp.pad(
        x.astype(jnp.float32), ((0, 0), (0, 0), (width - 1, 0), (0, 0))
    )
    return sum(
        w[j][:, None, :] * x[:, :, j:j + s] for j in range(width)
    )


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)


def _kda_apply(config, p, h):
    """Heads-major ``[b, heads, s, head_dim]`` from the projections on:
    a chunk of the scan is then a reshape, and no transpose is paid.
    The per-token work on either side of the scan has two forms, as the
    scan has, and ``kda_scan_kind`` chooses both: where the scan is the
    Pallas kernels so is this (``ops/kda_tail.py``: one fused float32
    pass a tensor forward and one backward); anywhere else the
    ``jax.numpy`` lines below, which are the definition."""
    with jax.named_scope("kda"):
        hd = config.kda_head_dim
        fused = kda_ops.kda_scan_kind(hd, hd) == "pallas"

        def heads(w):
            return _proj("bsd,dhk->bhsk", h, w)

        def branch(w, conv):
            return jax.nn.silu(_short_conv(heads(w), conv))

        if fused:
            q = kda_tail.branch(heads(p["wq"]), p["conv_q"], hd ** -0.5)
            k = kda_tail.branch(heads(p["wk"]), p["conv_k"], 1.0)
            v = kda_tail.branch(heads(p["wv"]), p["conv_v"])
        else:
            q = _l2norm(branch(p["wq"], p["conv_q"])) * hd ** -0.5
            k = _l2norm(branch(p["wk"], p["conv_k"]))
            v = branch(p["wv"], p["conv_v"])
        low = _proj("bsd,dr->bsr", h, p["w_a1"])
        if fused:
            g = kda_tail.decay_gate(
                _proj("bsr,rhk->bhsk", low, p["w_a2"]),
                p["a_log"], p["dt_bias"],
            )
        else:
            g = -jnp.exp(p["a_log"])[:, None, None] * jax.nn.softplus(
                _proj("bsr,rhk->bhsk", low, p["w_a2"]).astype(jnp.float32)
                + p["dt_bias"][:, None, :]
            )
        beta = jax.nn.sigmoid(
            _proj("bsd,dh->bhs", h, p["w_beta"]).astype(jnp.float32)
        )
        with jax.named_scope("kda_scan"):
            o = kda_ops.kda_chunked(q, k, v, g, beta)
        # Kept across the layer's rematerialisation (``run_pattern``'s
        # policy): the backward then runs the scan once more, for its own
        # pullback (the ``jax.numpy`` form re-forms its residuals there,
        # the kernels walk back over the kept ``kda_states``), and not a
        # second time for what follows it.
        o = checkpoint_name(o, "kda_out")
        gate = _proj(
            "bsr,rhk->bhsk", _proj("bsd,dr->bsr", h, p["w_g1"]), p["w_g2"]
        )
        if fused:
            o = kda_tail.gated_norm(o, gate, p["o_norm"], h.dtype)
        else:
            gate = jax.nn.sigmoid(gate.astype(jnp.float32))
            o = (rms_norm(o, p["o_norm"]) * gate).astype(h.dtype)
        return _proj("bhsk,hkd->bsd", o, p["wo"])


# -- mixers: MLA, without rotary and with --------------------------------------


def _mla_init(config, key, q_rank=0):
    d, h, r = config.embed_dim, config.n_heads, config.kv_lora_rank
    dq = config.qk_nope_dim + config.qk_rope_dim
    ks = jax.random.split(key, 4)
    if q_rank:
        k_a, k_b = jax.random.split(ks[0])
        queries = {
            "w_qa": _dense(k_a, (d, q_rank), d),
            "q_norm": jnp.zeros((q_rank,), jnp.float32),
            "w_qb": _dense(k_b, (q_rank, h, dq), q_rank),
        }
    else:
        queries = {"wq": _dense(ks[0], (d, h, dq), d)}
    return {
        **queries,
        "w_kva": _dense(ks[1], (d, r + config.qk_rope_dim), d),
        "kv_norm": jnp.zeros((r,), jnp.float32),
        "w_kvb": _dense(
            ks[2], (r, h, config.qk_nope_dim + config.v_head_dim), r
        ),
        "wo": _dense(ks[3], (h, config.v_head_dim, d), h * config.v_head_dim),
    }


def _mla_axes(config, q_rank=0):
    if q_rank:
        queries = {
            "w_qa": ("embed", None), "q_norm": ("norm",),
            "w_qb": (None, "heads", "head_dim"),
        }
    else:
        queries = {"wq": ("embed", "heads", "head_dim")}
    return {
        **queries,
        "w_kva": ("embed", None),
        "kv_norm": ("norm",),
        "w_kvb": (None, "heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }


def mla_bottleneck_queries(p, h):
    """A latent layer's queries through their low-rank bottleneck:
    ``h [b, s, d]`` -> ``w_qa`` -> RMSNorm -> ``w_qb`` -> ``[b, s, heads,
    nope + rope]``, not yet rotated. The one such function of the
    package: the ``mla_rope`` mixer's and ``models/latent_lm.py``'s
    (``latent_inputs``)."""
    c_q = rms_norm(_proj("bsd,dr->bsr", h, p["w_qa"]), p["q_norm"])
    return _proj("bsr,rhk->bshk", c_q, p["w_qb"])


def mla_keys_values(config, kva, latent, w_kvb, rotate=None):
    """The up-projection of a latent layer: the normed ``latent [b, s,
    r]`` and ``kva [b, s, r + rope]``, whose last ``rope`` numbers are
    the positional key the heads share (turned by ``rotate`` when
    given) -> keys ``[b, s, h, nope + rope]`` and values ``[b, s, h,
    v]``. The one place the package turns a latent into per-head keys
    and values: this model's mixer, and ``models/latent_lm.py``'s
    definition over cache rows (already rotated)."""
    r, nope = config.kv_lora_rank, config.qk_nope_dim
    kvb = _proj("bsr,rhk->bshk", latent, w_kvb)
    shared = kva[..., None, r:]
    if rotate is not None:
        shared = rotate(shared)
    shared = jnp.broadcast_to(
        shared, kvb.shape[:3] + (config.qk_rope_dim,)
    )
    k = jnp.concatenate([kvb[..., :nope], shared], axis=-1)
    return k, kvb[..., nope:]


def _mla_apply(config, p, h, rotate=None):
    """``rotate`` (None: the positional slices stay as projected, this
    model's ``mla_use_nope``): what turns the queries' positional slice
    ``[b, s, h, rope]`` and the shared key's ``[b, s, 1, rope]`` by
    their positions."""
    with jax.named_scope("mla"):
        r, nope = config.kv_lora_rank, config.qk_nope_dim
        if "w_qa" in p:
            q = mla_bottleneck_queries(p, h)
        else:
            q = _proj("bsd,dhk->bshk", h, p["wq"])
        if rotate is not None:
            q = jnp.concatenate(
                [q[..., :nope], rotate(q[..., nope:])], axis=-1
            )
        kva = _proj("bsd,dr->bsr", h, p["w_kva"])
        latent = rms_norm(kva[..., :r], p["kv_norm"])
        # The positional slice of a key is one vector shared by the
        # heads and, without ``rotate``, NOT rotated (mla_use_nope).
        k, v = mla_keys_values(config, kva, latent, p["w_kvb"], rotate)
        q = with_logical_constraint(q, ("batch", "seq", "heads", "head_dim"))
        attend = llama.default_attention_fn() or dot_product_attention
        out = attend(q, k, v, causal=True)     # scaled by 1/sqrt(nope + rope)
        return _proj("bshk,hkd->bsd", out, p["wo"])


def _mla_rope_apply(config, p, h, positions):
    """The latent layer with its positional slices turned by plain RoPE
    (``rope_theta``, all ``qk_rope_dim`` channels, the half-split
    pairing of ``ops/rope.apply_rope``) at ``positions [b, s]``."""
    return _mla_apply(config, p, h, functools.partial(
        rope.apply_rope, positions=positions, theta=config.rope_theta
    ))


# -- ffns ---------------------------------------------------------------------


def _swiglu_init(key, d, f):
    ks = jax.random.split(key, 3)
    return {
        "w_gate": _dense(ks[0], (d, f), d),
        "w_up": _dense(ks[1], (d, f), d),
        "w_down": _dense(ks[2], (f, d), f),
    }


_SWIGLU_AXES = {
    "w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"),
    "w_down": ("mlp", "embed"),
}


def _swiglu(p, h):
    g = _proj("bsd,df->bsf", h, p["w_gate"])
    u = _proj("bsd,df->bsf", h, p["w_up"])
    g = with_logical_constraint(g, ("batch", "seq", "mlp"))
    return _proj("bsf,fd->bsd", jax.nn.silu(g) * u, p["w_down"])


def _no_counters():
    return jnp.zeros((len(COUNTERS),), jnp.int32)


def _dense_apply(config, p, buffers, h):
    with jax.named_scope("dense"):
        return _swiglu(p, h), _no_counters()


def _moe_init(config, key):
    d, f, held = config.embed_dim, config.moe_mlp_dim, config.experts_held[1]
    ks = jax.random.split(key, 5)
    return {
        "router": _dense(ks[0], (d, config.n_experts), d),
        "w_gate": _dense(ks[1], (held, d, f), d),
        "w_up": _dense(ks[2], (held, d, f), d),
        "w_down": _dense(ks[3], (held, f, d), f),
        "shared": _swiglu_init(ks[4], d, f * config.n_shared_experts),
    }


def _moe_axes(config):
    return {
        "router": ("embed", None),
        "w_gate": ("expert", "embed", "mlp"),
        "w_up": ("expert", "embed", "mlp"),
        "w_down": ("expert", "mlp", "embed"),
        "shared": dict(_SWIGLU_AXES),
    }


def _moe_buffers(config, key):
    # Seeded and constant: nothing updates it while training. (A
    # trainer that balances load would step it against each expert's
    # load; the benchmark sets a balanced one before its first step.)
    return {"router_bias": 0.01 * jax.random.normal(
        key, (config.n_experts,), jnp.float32
    )}


def _moe_apply(config, p, buffers, h):
    routed, c = moe_lib.moe_mlp_share(
        h, p["router"], buffers["router_bias"],
        p["w_gate"], p["w_up"], p["w_down"],
        first=config.experts_held[0], top_k=config.moe_top_k,
        scaling=config.routed_scaling,
    )
    with jax.named_scope("shared"):
        out = routed + _swiglu(p["shared"], h)
    return out, jnp.stack(
        [c.rows_held, c.rows_max, c.rows_dropped, c.rows_full_path]
    )


MIXERS: Dict[str, Kind] = {
    "kda": Kind(_kda_init, _kda_axes, _kda_apply),
    "mla": Kind(_mla_init, _mla_axes, _mla_apply),
    "mla_rope": Kind(
        lambda c, k: _mla_init(c, k, c.q_lora_rank),
        lambda c: _mla_axes(c, c.q_lora_rank),
        _mla_rope_apply, positional=True,
    ),
}
FFNS: Dict[str, Kind] = {
    "dense": Kind(
        lambda c, k: _swiglu_init(k, c.embed_dim, c.mlp_dim),
        lambda c: dict(_SWIGLU_AXES), _dense_apply,
    ),
    "moe": Kind(_moe_init, _moe_axes, _moe_apply, _moe_buffers),
}


# -- the stack ----------------------------------------------------------------


def _layer_init(config, kinds, key):
    mixer, ffn = kinds
    k_mixer, k_ffn = jax.random.split(key)
    d = config.embed_dim
    return {
        "mixer_norm": jnp.zeros((d,), jnp.float32),
        "mixer": MIXERS[mixer].init(config, k_mixer),
        "ffn_norm": jnp.zeros((d,), jnp.float32),
        "ffn": FFNS[ffn].init(config, k_ffn),
    }


def _layer_axes(config, kinds, lead=()):
    mixer, ffn = kinds
    tree = {
        "mixer_norm": ("norm",), "mixer": MIXERS[mixer].axes(config),
        "ffn_norm": ("norm",), "ffn": FFNS[ffn].axes(config),
    }
    return jax.tree_util.tree_map(
        lambda axes: lead + axes, tree, is_leaf=lambda x: isinstance(x, tuple)
    )


def _per_layer(config, key, make):
    """``{"leading": [...], "period": [...]}`` of ``make(kinds, key)``,
    a period position stacked over the repeats."""
    keys = jax.random.split(key, config.n_layers)
    n_lead, span = len(config.leading), len(config.period)
    period = []
    for i, kinds in enumerate(config.period):
        repeats = [
            make(kinds, keys[n_lead + r * span + i])
            for r in range(config.n_periods)
        ]
        period.append(jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *repeats
        ))
    return {
        "leading": [
            make(kinds, keys[i]) for i, kinds in enumerate(config.leading)
        ],
        "period": period,
    }


def param_axes(config: HybridLMConfig) -> Dict[str, Any]:
    axes = {
        "embed": ("vocab", "embed"),
        "leading": [_layer_axes(config, kinds) for kinds in config.leading],
        "period": [
            _layer_axes(config, kinds, ("layer",)) for kinds in config.period
        ],
        "final_norm": ("norm",),
        "lm_head": ("embed", "vocab"),
    }
    if config.mtp_depth:
        axes["mtp"] = {
            "norm_h": ("norm",), "norm_e": ("norm",),
            "w_eh": (None, "embed"),
            "block": _layer_axes(config, config.mtp_kinds),
            "norm": ("norm",),
        }
    return axes


def init_params(config: HybridLMConfig, rng: jax.Array):
    """(params, logical axes): normal(0, 1/sqrt(fan_in)) matrices, an
    ~N(0, 1) embedding, zero norm scales (the identity under (1 + scale))."""
    k_embed, k_head, k_layers = jax.random.split(rng, 3)
    d, v = config.embed_dim, config.vocab_size
    params = {
        "embed": _dense(k_embed, (v, d), 1.0),
        **_per_layer(config, k_layers, functools.partial(_layer_init, config)),
        "final_norm": jnp.zeros((d,), jnp.float32),
        "lm_head": _dense(k_head, (d, v), d),
    }
    if config.mtp_depth:
        # A stream of its own: the stack's weights are the same at any depth.
        k_join, k_block = jax.random.split(jax.random.fold_in(rng, 2))
        params["mtp"] = {
            "norm_h": jnp.zeros((d,), jnp.float32),
            "norm_e": jnp.zeros((d,), jnp.float32),
            "w_eh": _dense(k_join, (2 * d, d), 2 * d),
            "block": _layer_init(config, config.mtp_kinds, k_block),
            "norm": jnp.zeros((d,), jnp.float32),
        }
    return params, param_axes(config)


def init_buffers(config: HybridLMConfig, rng: jax.Array):
    """State that is not trained, shaped like ``params``' layers: the
    expert layers' score-correction bias (the prediction module's block
    has one too). Seeded from another stream than the parameters."""
    make = lambda kinds, key: FFNS[kinds[1]].buffers(config, key)  # noqa: E731
    buffers = _per_layer(config, jax.random.fold_in(rng, 1), make)
    if config.mtp_depth:
        buffers["mtp"] = {
            "block": make(config.mtp_kinds, jax.random.fold_in(rng, 3))
        }
    return buffers


def buffer_axes(config: HybridLMConfig):
    return jax.tree_util.tree_map(
        lambda x: (None,) * x.ndim,
        jax.eval_shape(lambda: init_buffers(config, jax.random.key(0))),
    )


def _mix(config, mixer, positions, p, x):
    with jax.named_scope("attn"):
        h = rms_norm(x, p["mixer_norm"]).astype(config.compute_dtype)
        kind = MIXERS[mixer]
        where = (positions,) if kind.positional else ()
        x = x + kind.apply(config, p["mixer"], h, *where).astype(x.dtype)
        return with_logical_constraint(x, ("batch", "seq", "embed"))


def _feed(config, ffn, p, buffers, x):
    with jax.named_scope("mlp"):
        h = rms_norm(x, p["ffn_norm"]).astype(config.compute_dtype)
        y, counters = FFNS[ffn].apply(config, p["ffn"], buffers, h)
        x = x + y.astype(x.dtype)
        return with_logical_constraint(x, ("batch", "seq", "embed")), counters


def _layer(config, kinds, positions, p, buffers, x):
    """One block. The scopes land in every op's ``op_name``, forward and
    backward, and nest under the ``attn`` / ``mlp`` the dense model has,
    so ``benchmark/trace_reduce.py`` buckets them as it does those.
    ``positions [b, s]`` (None where no mixer of the model reads them)
    is closed over, not an argument: a block that reads none is the
    same program with and without."""
    mixer, ffn = kinds
    return _feed(config, ffn, p, buffers, _mix(config, mixer, positions, p, x))


def _block(config, kinds, positions):
    """The layer, rematerialised in the backward. ``remat_keep``
    ``"dots"`` keeps its projection matmuls' outputs, a KDA scan's
    output and, where the scan is the kernels, the state entering each
    chunk (the backward kernel's residual: with both kept, the layer's
    re-forward runs no scan at all); ``"attention"`` keeps only what the
    flash kernels left for their backward (``ops/pallas_attention``'s
    ``flash_out`` / ``flash_lse``), so the projections and the experts
    run again and a block holds its input and one attention output. A
    policy object a layer, not one for all: with a shared one the
    compiled step read 12,398 tokens/s where this reads 12,470 (my chip
    runs, PR 31; the layers' remat bodies are then laid out
    differently)."""
    return jax.checkpoint(
        functools.partial(_layer, config, kinds, positions),
        policy=remat_policy(config.remat_keep),
    )


def remat_policy(remat_keep: str):
    """What a layer keeps for its backward (:func:`_block`), a new
    policy object a call."""
    policies = jax.checkpoint_policies
    if remat_keep == "attention":
        return policies.save_only_these_names("flash_out", "flash_lse")
    return policies.save_from_both_policies(
        policies.save_only_these_names("kda_out", "kda_states"),
        policies.dots_with_no_batch_dims_saveable,
    )


def run_pattern(config: HybridLMConfig, params, buffers, x, positions=None):
    """Leading layers unrolled, then the period scanned over its
    repeats. Returns (hidden, summed counters ``[len(COUNTERS)]``)."""
    block = functools.partial(_block, config, positions=positions)
    if any(mixer == "kda" for mixer, _ in config.leading + config.period):
        # Once a trace of the step: which form the scan was built with.
        logger.info(
            "layer pattern: delta-rule scan of %d heads x %d is %s",
            config.kda_heads, config.kda_head_dim,
            kda_ops.kda_scan_kind(config.kda_head_dim, config.kda_head_dim),
        )
    counters = _no_counters()
    for kinds, p, b in zip(
        config.leading, params["leading"], buffers["leading"]
    ):
        x, c = block(kinds)(p, b, x)
        counters += c
    blocks = [block(kinds) for kinds in config.period]

    def period(x, layers):
        total = _no_counters()
        for fn, (p, b) in zip(blocks, layers):
            x, c = fn(p, b, x)
            total += c
        return x, total

    x, per_repeat = jax.lax.scan(
        period, x, list(zip(params["period"], buffers["period"]))
    )
    return x, counters + jnp.sum(per_repeat, axis=0)


def _positions(config, tokens):
    """``[b, s]`` of 0 .. s - 1 where a mixer of the model reads
    positions, else None."""
    if not config.positional:
        return None
    b, s = tokens.shape
    return jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))


def forward_hidden(config, params, buffers, tokens):
    x = llama.embed_tokens(config, params, tokens)
    return run_pattern(
        config, params, buffers, x, _positions(config, tokens)
    )


def mtp_loss(config, params, buffers, hidden, tokens, targets, mask=None):
    """The prediction module's loss: ``hidden [b, s, d]`` the stack's
    output BEFORE the final norm at positions 0 .. s - 1, ``tokens [b,
    s]`` the tokens one to the right of those the stack read, ``targets
    [b, s]`` two to the right. ``u_i = W_eh [norm_h(h_i) ; norm_e(
    Emb(t_{i+1}))]``, one block over the ``u`` (causal, position ``i``
    in its own rotation), then the model's own head behind the module's
    norm. Returns (token-mean CE + z-loss, the block's counters). Every
    op sits under the ``mtp`` scope: ``mtp/join``, ``mtp/attn/...``,
    ``mtp/mlp/...``, ``mtp/vocab``."""
    p, cdt = params["mtp"], config.compute_dtype
    with jax.named_scope("mtp"):
        with jax.named_scope("join"):
            e = llama.embed_tokens(config, params, tokens)
            joined = jnp.concatenate([
                rms_norm(hidden, p["norm_h"]).astype(cdt),
                rms_norm(e, p["norm_e"]).astype(cdt),
            ], axis=-1)
            u = _proj("bsd,de->bse", joined, p["w_eh"]).astype(hidden.dtype)
            u = with_logical_constraint(u, ("batch", "seq", "embed"))
        g, counters = _block(
            config, config.mtp_kinds, _positions(config, tokens)
        )(p["block"], buffers["mtp"]["block"], u)
        head = {"final_norm": p["norm"], "lm_head": params["lm_head"]}
        return llama.head_loss(config, head, g, targets, mask), counters


def loss_fn(config, params, batch, buffers=None, attention_fn=None):
    """batch: {"tokens": [b, s + 1 + mtp_depth]} -> (loss, {"ce", "aux",
    "counters"}). The stack reads ``tokens[:, :s]`` and is held to
    ``tokens[:, 1:s + 1]``; positions are 0 .. s - 1 (only the
    ``mla_rope`` mixer reads them), so there is no ``positions`` in the
    batch and no ``attention_fn`` to choose. With ``mtp_depth`` 1 the
    loss is ``ce + mtp_weight * ce_mtp``, the module held to
    ``tokens[:, 2:]``; ``ce_mtp`` comes back beside ``ce`` and the
    module's expert rows (``MTP_COUNTER``) beside the stack's counters."""
    del attention_fn
    depth = config.mtp_depth
    s = batch["tokens"].shape[1] - 1 - depth
    tokens, targets = batch["tokens"][:, :s], batch["tokens"][:, 1:s + 1]
    x, counters = forward_hidden(config, params, buffers, tokens)
    ce = llama.head_loss(config, params, x, targets, batch.get("mask"))
    aux = jnp.zeros((), jnp.float32)
    out = {"ce": ce, "aux": aux, "counters": dict(zip(COUNTERS, counters))}
    if not depth:
        return ce, out
    ce_mtp, c = mtp_loss(
        config, params, buffers, x, targets, batch["tokens"][:, 2:],
        batch.get("mask"),
    )
    out["ce_mtp"] = ce_mtp
    out["counters"][MTP_COUNTER] = c[0]
    return ce + config.mtp_weight * ce_mtp, out
