"""A decoder with a rotary LATENT attention (MLA), a multi-stream
residual and sigmoid-routed experts beside a shared one.

- **Latent attention.** Queries through a low-rank bottleneck
  (``w_qa`` -> RMSNorm -> ``w_qb``: ``qk_nope_dim + qk_rope_dim`` a
  head); keys and values from ONE ``kv_lora_rank``-wide latent a token
  (``w_kva`` -> RMSNorm) plus one ``qk_rope_dim``-wide positional key
  shared by the heads. Only the positional slices are rotated, by YaRN
  frequencies (``ops.rope.yarn_frequencies``), and the softmax scale
  carries YaRN's ``mscale`` squared. What a token leaves behind is its
  normed latent and its rotated positional key, :func:`latent_inputs`'
  ``row [kv_lora_rank + qk_rope_dim]``: the whole cache
  (:attr:`LatentLMConfig.cache_rows`). The up-projection ``w_kvb`` turns
  a latent into every head's keys and values (``hybrid.mla_keys_values``,
  the one latent layer of the package: ``hybrid._mla_apply`` is the same
  layer with no rotation), or is ABSORBED into the query and the output
  (:func:`absorb_queries`, :func:`values_out`) so that attention runs
  over the cached rows themselves (``serving/kvpool/latent.py``).
- **Residual (mHC, manifold-constrained hyper-connections).** The
  residual is ``hc_mult`` streams ``X [n, d]`` a token. Each sublayer
  reads ``u = H_pre X``, and writes ``X' = H_res X + H_post^T y``; the
  three maps are functions of the token's own streams
  (:func:`mhc_maps`), ``H_res`` doubly stochastic by Sinkhorn-Knopp. So
  NO function here adds a residual inside a sublayer: a sublayer maps
  ``h -> y`` and :func:`block` mixes.
- **MLP.** The first ``first_dense`` layers a SwiGLU of ``mlp_dim``;
  every other layer ``n_experts`` dropless experts of ``moe_mlp_dim``,
  sigmoid scores, the ``moe_top_k`` largest of score + bias, weights
  renormalised and scaled (``moe.sigmoid_route`` +
  ``moe.routed_experts``: all expert layers' experts in ONE stack of
  groups, as ``models/sparse_lm.py`` keeps them), beside a shared expert.

The model is SERVED: ``PagedServingEngine`` takes this config and builds
its programs from :func:`block` (``serving/kvpool/latent.py``).
:func:`forward` is the same layer over whole sequences with no cache
and no absorption, the definition the engine's logits are held to in
the package's tests. Nothing here trains it.
"""

import dataclasses
import math
from typing import ClassVar, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.models import hybrid
from dlrover_tpu.models import llama
from dlrover_tpu.models import moe as moe_lib
from dlrover_tpu.ops import rope
from dlrover_tpu.ops.norms import rms_norm


@dataclasses.dataclass(frozen=True)
class LatentLMConfig:
    kind: ClassVar[str] = "latent_lm"    # models.model_for: which module
    vocab_size: int = 131072
    embed_dim: int = 3584
    n_layers: int = 40
    first_dense: int = 2             # layers below it have a dense MLP
    n_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    mlp_dim: int = 9216              # the dense SwiGLU
    moe_mlp_dim: int = 1024          # each expert, and the shared one
    n_experts: int = 64
    moe_top_k: int = 4
    n_shared_experts: int = 1
    routed_scaling: float = 2.0
    hc_mult: int = 4                 # residual streams
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: float = 30.0
    rope_theta: float = 1e4
    rope_factor: float = 64.0        # YaRN; 1.0: plain RoPE
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 1.0
    dtype: str = "bfloat16"
    pp_stages: int = 1               # the engines ask; never staged

    def __post_init__(self):
        if not 0 <= self.first_dense <= self.n_layers:
            raise ValueError(
                f"first_dense {self.first_dense} of {self.n_layers} layers"
            )

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    # What the engines' dense builders read off any config they are
    # handed before the paged programs replace theirs; no layer of this
    # model has a K/V head.
    @property
    def n_kv_heads(self) -> int:
        return self.n_heads

    @property
    def head_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.first_dense

    @property
    def cache_width(self) -> int:
        """A token's cache row a layer: its latent and its rotated
        positional key."""
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def cache_rows(self):
        """What a token leaves in the cache, a layer: name -> shape (the
        pool's statement, ``serving/kvpool/layout.py``)."""
        return (("latent", (self.cache_width,)),)

    @property
    def softmax_scale(self) -> float:
        m = rope.yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return (self.qk_nope_dim + self.qk_rope_dim) ** -0.5 * m * m

    def count_params(self) -> int:
        d, h, n = self.embed_dim, self.n_heads, self.hc_mult
        attn = (
            d * self.q_lora_rank + self.q_lora_rank
            + self.q_lora_rank * h * (self.qk_nope_dim + self.qk_rope_dim)
            + d * self.cache_width + self.kv_lora_rank
            + self.kv_lora_rank * h * (self.qk_nope_dim + self.v_head_dim)
            + h * self.v_head_dim * d
        )
        maps = 2 * n + n * n
        mhc = n * d * maps + n * d + maps + 3
        shared = 3 * d * self.moe_mlp_dim * self.n_shared_experts
        moe = (
            d * self.n_experts + self.n_experts
            + self.n_experts * 3 * d * self.moe_mlp_dim + shared
        )
        return (
            self.n_layers * (attn + 2 * mhc + 2 * d)
            + self.first_dense * 3 * d * self.mlp_dim
            + self.n_moe_layers * moe
            + 2 * self.vocab_size * d + d
        )


def tiny_config(**overrides) -> LatentLMConfig:
    """Small enough for a CPU test; ``rope_original_max`` 16 so that a
    few dozen positions already lie past what YaRN stretches, and
    ``rope_beta_slow`` such that one of the four frequency pairs is
    blended (kept | blended | stretched | stretched)."""
    kw = dict(
        vocab_size=96, embed_dim=32, n_layers=3, first_dense=1, n_heads=4,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_dim=8, qk_rope_dim=8,
        v_head_dim=8, mlp_dim=48, moe_mlp_dim=16, n_experts=8, moe_top_k=2,
        hc_mult=4, rope_factor=8.0, rope_original_max=16,
        rope_beta_slow=0.05, dtype="float32",
    )
    kw.update(overrides)
    return LatentLMConfig(**kw)


def inv_frequencies(config: LatentLMConfig):
    """The rotated slice's inverse frequencies ``[qk_rope_dim // 2]``."""
    if config.rope_factor <= 1.0:
        return rope.rope_frequencies(config.qk_rope_dim, config.rope_theta)
    return rope.yarn_frequencies(
        config.qk_rope_dim, config.rope_theta, config.rope_factor,
        config.rope_original_max, config.rope_beta_fast,
        config.rope_beta_slow,
    )


# Leaves a server keeps in float32 whatever its compute dtype: norm
# scales, the router and its bias, every parameter of the residual maps.
FLOAT32_LEAVES = frozenset({
    "attn_norm", "mlp_norm", "q_norm", "kv_norm", "final_norm", "router",
    "router_bias", "phi", "bias", "alpha", "norm",
})


# Standard deviation of the residual maps' seeded ``bias`` and of ``x
# phi`` at seeded ``phi``: map logits of std 0.5, at which 20 Sinkhorn
# rounds leave every token's H_res within 1e-6 of doubly stochastic. At
# unit scale one 4 x 4 in 150 is still 1e-3 away after 20 rounds (the
# published round count presumes a trained, tame H_res).
MAP_INIT_SCALE = 0.35


def init_params(config: LatentLMConfig, rng: jax.Array, dtype=None):
    """Seeded weights, normal(0, 1/sqrt(fan_in)); norm scales zero (the
    ``1 + scale`` form). ``dtype``: what the matmul leaves are made in
    (float32 when None; a server passes its compute dtype, so that the
    float32 tree never exists); :data:`FLOAT32_LEAVES` stay float32.
    The residual maps' ``alpha`` are 1.0 and their ``bias`` and ``phi``
    seeded at :data:`MAP_INIT_SCALE` (at the small ``alpha`` a trainer
    starts from, the dynamic term would be rounding noise and no
    comparison could see it)."""
    c = config
    d, h, L, n = c.embed_dim, c.n_heads, c.n_layers, c.hc_mult
    Ld, Lm, E = c.first_dense, c.n_moe_layers, c.n_experts
    dtype = jnp.dtype(dtype or jnp.float32)
    keys = iter(jax.random.split(rng, 24))

    def dense(shape, fan_in, to=dtype):
        w = jax.random.normal(next(keys), shape, jnp.float32)
        return (w / math.sqrt(fan_in)).astype(to)

    def maps():
        width = 2 * n + n * n
        return {
            "norm": jnp.zeros((L, n * d), jnp.float32),
            "phi": MAP_INIT_SCALE * dense((L, n * d, width), n * d,
                                          jnp.float32),
            "bias": MAP_INIT_SCALE * dense((L, width), 1.0, jnp.float32),
            "alpha": jnp.ones((L, 3), jnp.float32),
        }

    dq = c.qk_nope_dim + c.qk_rope_dim
    layers = {
        "hc_attn": maps(),
        "attn_norm": jnp.zeros((L, d), jnp.float32),
        "w_qa": dense((L, d, c.q_lora_rank), d),
        "q_norm": jnp.zeros((L, c.q_lora_rank), jnp.float32),
        "w_qb": dense((L, c.q_lora_rank, h, dq), c.q_lora_rank),
        "w_kva": dense((L, d, c.cache_width), d),
        "kv_norm": jnp.zeros((L, c.kv_lora_rank), jnp.float32),
        "w_kvb": dense(
            (L, c.kv_lora_rank, h, c.qk_nope_dim + c.v_head_dim),
            c.kv_lora_rank,
        ),
        "wo": dense((L, h, c.v_head_dim, d), h * c.v_head_dim),
        "hc_mlp": maps(),
        "mlp_norm": jnp.zeros((L, d), jnp.float32),
    }
    f, fs = c.moe_mlp_dim, c.moe_mlp_dim * c.n_shared_experts
    return {
        "embed": dense((c.vocab_size, d), 1.0),
        "layers": layers,
        "dense": {
            "w_gu": dense((Ld, d, 2 * c.mlp_dim), d),
            "w_down": dense((Ld, c.mlp_dim, d), c.mlp_dim),
        },
        "moe": {
            "router": dense((Lm, d, E), d, jnp.float32),
            "router_bias": 0.01 * dense((Lm, E), 1.0, jnp.float32),
            "w_gu": dense((Lm * E, d, 2 * f), d),
            "w_down": dense((Lm * E, f, d), f),
            "shared_gu": dense((Lm, d, 2 * fs), d),
            "shared_down": dense((Lm, fs, d), fs),
        },
        "final_norm": jnp.zeros((d,), jnp.float32),
        "lm_head": dense((d, c.vocab_size), d),
    }


def prepare_decode_params(config: LatentLMConfig, params):
    """The tree as a server reads it: matmul leaves in the compute
    dtype, :data:`FLOAT32_LEAVES` as they are. Nothing is fused: the
    projections that share an input are stored side by side already."""
    cdt = config.compute_dtype

    def cast(path, leaf):
        name = getattr(path[-1], "key", None)
        return leaf if name in FLOAT32_LEAVES else leaf.astype(cdt)

    return jax.tree_util.tree_map_with_path(cast, params)


# -- the residual -------------------------------------------------------------


class ResidualMaps(NamedTuple):
    pre: jnp.ndarray     # [..., n]      H_pre  = sigmoid
    post: jnp.ndarray    # [..., n]      H_post = 2 sigmoid
    res: jnp.ndarray     # [..., n, n]   H_res, doubly stochastic


def sinkhorn(m, iters: int, eps: float):
    """``iters`` rounds of row- then column-normalisation of a positive
    ``[..., n, n]``, ``eps`` added to each denominator."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def mhc_maps(config: LatentLMConfig, hp, streams) -> ResidualMaps:
    """A sublayer's three maps from a token's own streams ``[..., n,
    d]``, all float32: ``x = RMSNorm(vec(X))`` over all ``n d`` entries,
    ``alpha * (x phi) + bias`` split into ``n`` pre, ``n`` post and ``n
    x n`` residual logits."""
    n = streams.shape[-2]
    flat = streams.astype(jnp.float32).reshape(streams.shape[:-2] + (-1,))
    raw = jnp.einsum(
        "...k,kw->...w", rms_norm(flat, hp["norm"]), hp["phi"],
        precision=jax.lax.Precision.HIGHEST,
    )
    alpha = jnp.repeat(hp["alpha"], np.array([n, n, n * n]), axis=-1)
    raw = raw * alpha + hp["bias"]
    logits = raw[..., 2 * n:].reshape(raw.shape[:-1] + (n, n))
    res = sinkhorn(
        jnp.exp(jnp.clip(logits, -config.hc_clamp, config.hc_clamp)),
        config.hc_sinkhorn_iters, config.hc_eps,
    )
    return ResidualMaps(
        pre=jax.nn.sigmoid(raw[..., :n]),
        post=2.0 * jax.nn.sigmoid(raw[..., n:2 * n]),
        res=res,
    )


def _mix(weights, streams):
    """``sum_j weights[..., j] streams[..., j, :]`` as float32 products
    and sums on the vector unit: n is 4, and as a matmul at the default
    precision the contraction goes through the MXU with operands rounded
    to bfloat16 (2.8e-2 of a mix's change on the chip, where this form
    reads 1.6e-6 and costs the decode step what the matmul at the highest
    precision did; my chip runs, PR 38, PERF.md section 6)."""
    return sum(
        weights[..., j, None] * streams[..., j, :]
        for j in range(streams.shape[-2])
    )


def mhc_read(maps: ResidualMaps, streams):
    """``u = H_pre X``: what the sublayer reads, ``[..., d]`` float32."""
    return _mix(maps.pre, streams)


def mhc_write(maps: ResidualMaps, streams, y):
    """``X' = H_res X + H_post^T y``, float32."""
    mixed = jnp.stack([
        _mix(maps.res[..., i, :], streams)
        for i in range(streams.shape[-2])
    ], axis=-2)
    return mixed + maps.post[..., None] * y.astype(jnp.float32)[..., None, :]


# -- latent attention ---------------------------------------------------------


def latent_inputs(config: LatentLMConfig, p, h, positions):
    """The projections of attention: ``h [b, s, d]`` -> the queries'
    un-rotated part ``q_nope [b, s, heads, qk_nope_dim]``, their rotated
    part ``q_rope [b, s, heads, qk_rope_dim]``, and the token's cache
    ``row [b, s, kv_lora_rank + qk_rope_dim]`` (the normed latent, then
    the rotated positional key), all in the compute dtype."""
    c, cdt = config, config.compute_dtype
    r, nope = c.kv_lora_rank, c.qk_nope_dim
    inv_freq = inv_frequencies(c)
    q = hybrid.mla_bottleneck_queries(p, h)
    q_rope = rope.apply_rope(q[..., nope:], positions, inv_freq=inv_freq)
    kva = jnp.einsum("bsd,dr->bsr", h, p["w_kva"].astype(cdt))
    latent = rms_norm(kva[..., :r], p["kv_norm"])
    k_rope = rope.apply_rope(
        kva[..., None, r:], positions, inv_freq=inv_freq
    )[..., 0, :]
    return q[..., :nope], q_rope, jnp.concatenate([latent, k_rope], axis=-1)


def absorb_queries(config: LatentLMConfig, p, q_nope, q_rope):
    """Queries over the CACHED rows: ``w_kvb``'s key half folded into
    ``q_nope`` (``[..., heads, kv_lora_rank]``), the rotated part beside
    it: ``[..., heads, kv_lora_rank + qk_rope_dim]``, so that a query's
    score against a token is its dot with the token's cache row."""
    w_k = p["w_kvb"][..., :config.qk_nope_dim].astype(q_nope.dtype)
    absorbed = jnp.einsum("...hn,rhn->...hr", q_nope, w_k)
    return jnp.concatenate([absorbed, q_rope], axis=-1)


def values_out(config: LatentLMConfig, p, mixed):
    """``w_kvb``'s value half applied AFTER attention: the softmax-
    weighted sum of latents ``[..., heads, kv_lora_rank]`` -> ``[...,
    heads, v_head_dim]``."""
    w_v = p["w_kvb"][..., config.qk_nope_dim:].astype(mixed.dtype)
    return jnp.einsum("...hr,rhv->...hv", mixed, w_v)


def definition_attention(config: LatentLMConfig, p, q_nope, q_rope, row):
    """Causal attention of one sequence as written: every key and value
    up-projected, float32 scores. ``[s, heads, ...]`` -> ``[s, heads,
    v_head_dim]``."""
    r = config.kv_lora_rank
    k, v = hybrid.mla_keys_values(
        config, row[None], row[None, :, :r], p["w_kvb"]
    )
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    scores = jnp.einsum(
        "shk,thk->hst", q, k[0], preferred_element_type=jnp.float32
    ) * config.softmax_scale
    s = q.shape[0]
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return jnp.einsum("hst,thv->shv", probs.astype(v.dtype), v[0])


# -- the MLP ------------------------------------------------------------------


def _swiglu(h, w_gu, w_down):
    f = w_down.shape[-2]
    gu = jnp.einsum("bsd,df->bsf", h, w_gu.astype(h.dtype))
    act = (jax.nn.silu(gu[..., :f]) * gu[..., f:]).astype(h.dtype)
    return jnp.einsum("bsf,fd->bsd", act, w_down.astype(h.dtype))


def route(config: LatentLMConfig, pm, h):
    """``h [n, d]`` -> (experts ``[n, k]``, weights ``[n, k]``): one
    expert layer's routing (``pm``: that layer's ``router`` and
    ``router_bias``)."""
    return moe_lib.sigmoid_route(
        h, pm["router"], pm["router_bias"], config.moe_top_k,
        config.routed_scaling,
    )


def feed(config: LatentLMConfig, params, layer, h, taps=None):
    """A layer's MLP on its normed input ``h [b, s, d]`` -> (``y``,
    :class:`moe.ShareCounters` or None). ``layer`` is a Python int below
    ``first_dense`` (the dense SwiGLU) and may be traced above it (the
    expert layer: its experts are groups ``(layer - first_dense) *
    n_experts ...`` of the one stack, read in place). ``taps``: see
    :func:`block`."""
    c = config
    with jax.named_scope("mlp"):
        if isinstance(layer, int) and layer < c.first_dense:
            with jax.named_scope("dense"):
                pd = params["dense"]
                return _swiglu(h, pd["w_gu"][layer], pd["w_down"][layer]), None
        pm, at = params["moe"], layer - c.first_dense
        with jax.named_scope("router"):
            experts, weights = route(
                c, {"router": pm["router"][at],
                    "router_bias": pm["router_bias"][at]},
                h.reshape(-1, h.shape[-1]),
            )
        if taps is not None:
            taps.update(experts=experts, weights=weights)
        out, counters = moe_lib.routed_experts(
            h, experts, weights, pm["w_gu"], pm["w_down"], c.n_experts,
            group_offset=at * c.n_experts,
        )
        with jax.named_scope("shared"):
            out = out + _swiglu(h, pm["shared_gu"][at], pm["shared_down"][at])
        return out, counters


# -- the block and the layer loop ---------------------------------------------


def block(config: LatentLMConfig, params, p, layer, streams, positions,
          attend, taps=None):
    """One decoder block over the streams ``[b, s, n, d]`` (float32)
    with attention behind ``attend(p, q_nope, q_rope, row) -> [b, s,
    heads, v_head_dim]``: (streams, the tokens' cache rows ``[b, s,
    cache_width]``, the expert layer's counters or None). ``taps``: a
    dict the block fills with what it otherwise keeps to itself (a
    check's probe reads them; the served programs pass none): the
    streams before, between and after the sublayers (``x_in``,
    ``x_mid``, ``x_out``), both sublayers' outputs (``y_attn``,
    ``y_mlp``) and ``H_res`` (``res_attn``, ``res_mlp``), attention's
    output before ``wo`` (``attn``), the MLP's normed input (``h_mlp``)
    and, of an expert layer, ``experts`` and ``weights``."""
    cdt = config.compute_dtype
    x_in = streams
    with jax.named_scope("resid"), jax.named_scope("mhc"):
        maps = mhc_maps(config, p["hc_attn"], streams)
        u = mhc_read(maps, streams)
    with jax.named_scope("attn"), jax.named_scope("mla"):
        h = rms_norm(u, p["attn_norm"]).astype(cdt)
        q_nope, q_rope, row = latent_inputs(config, p, h, positions)
        out = attend(p, q_nope, q_rope, row)
        y = jnp.einsum("bshv,hvd->bsd", out.astype(cdt), p["wo"].astype(cdt))
    # A sublayer's output is tapped AS THE MIX READS IT, one float32 value
    # with two readers: left to itself the compiler hands the mix the
    # matmul's float32 result (it may skip a rounding) and a bfloat16 tap
    # the rounded one, a bfloat16 place (1.7e-3) apart (my chip runs, PR
    # 38; PERF.md section 6).
    y = y.astype(jnp.float32)
    with jax.named_scope("resid"), jax.named_scope("mhc"):
        x_mid = mhc_write(maps, streams, y)
        maps_mlp = mhc_maps(config, p["hc_mlp"], x_mid)
        u = mhc_read(maps_mlp, x_mid)
    h = rms_norm(u, p["mlp_norm"]).astype(cdt)
    y_mlp, counters = feed(config, params, layer, h, taps)
    y_mlp = y_mlp.astype(jnp.float32)
    with jax.named_scope("resid"), jax.named_scope("mhc"):
        streams = mhc_write(maps_mlp, x_mid, y_mlp)
    if taps is not None:
        taps.update(
            x_in=x_in, res_attn=maps.res, attn=out, y_attn=y, x_mid=x_mid,
            res_mlp=maps_mlp.res, h_mlp=h, y_mlp=y_mlp, x_out=streams,
        )
    return streams, row, counters


def layer_params(params, layer):
    """Layer ``layer``'s own leaves of ``params["layers"]`` (``layer``
    may be traced: inside a loop this is the slice a scan would take)."""
    return jax.tree_util.tree_map(lambda a: a[layer], params["layers"])


def layer_loop(config: LatentLMConfig, params, body, carry):
    """``carry, out = body(carry, layer's leaves, layer)`` over the
    layers: the dense ones unrolled (``layer`` a Python int), the
    expert layers under ``lax.scan`` (``layer`` traced). ``out`` must be
    a tuple ``(per_layer, per_expert_layer)`` of pytrees (None: nothing):
    the first is stacked over all layers, the second over the expert
    layers."""
    c = config
    stack = lambda *a: jnp.stack(a)  # noqa: E731
    every = []
    for layer in range(c.first_dense):
        carry, (a, _) = body(carry, layer_params(params, layer), layer)
        every.append(a)
    every = jax.tree_util.tree_map(stack, *every) if every else None
    if not c.n_moe_layers:
        return carry, (every, None)

    def scanned(carry, layer):
        return body(carry, layer_params(params, layer), layer)

    carry, (rest, expert) = jax.lax.scan(
        scanned, carry,
        jnp.arange(c.first_dense, c.n_layers, dtype=jnp.int32),
    )
    if every is not None:
        rest = jax.tree_util.tree_map(
            lambda a, b: jnp.concatenate([a, b]), every, rest
        )
    return carry, (rest, expert)


def embed_streams(config: LatentLMConfig, params, tokens):
    """``X_0``: the embedding repeated over the streams, float32."""
    x = llama.embed_tokens(config, params, tokens).astype(jnp.float32)
    return jnp.repeat(x[..., None, :], config.hc_mult, axis=-2)


def unembed_streams(config: LatentLMConfig, params, streams):
    """The streams summed, the final norm and the untied head: float32
    logits."""
    return llama.unembed(config, params, jnp.sum(streams, axis=-2))


def forward(config: LatentLMConfig, params, tokens):
    """``tokens [b, s]`` -> float32 logits ``[b, s, vocab]`` and the
    expert rows dropped (0): the layer as the engines run it, but over
    whole sequences, with no cache and attention as written
    (:func:`definition_attention`)."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    def attend(p, q_nope, q_rope, row):
        return jax.vmap(
            lambda *a: definition_attention(config, p, *a)
        )(q_nope, q_rope, row)

    def body(streams, p, layer):
        streams, _, counters = block(
            config, params, p, layer, streams, positions, attend
        )
        dropped = None if counters is None else counters.rows_dropped
        return streams, (None, dropped)

    streams, (_, dropped) = layer_loop(
        config, params, body, embed_streams(config, params, tokens)
    )
    total = jnp.zeros((), jnp.int32) if dropped is None else jnp.sum(dropped)
    return unembed_streams(config, params, streams), total
