"""A decoder whose layers follow a PATTERN of two mixers: a gated short
convolution and grouped-query attention, over a dense SwiGLU or
sigmoid-routed experts with no shared one, under a tied head.

- **Conv (gated short convolution).** ``[B | C | X] = u W_in``; ``z_t =
  B_t * X_t``; ``c_t = sum_j w_j * z_{t - (taps - 1) + j}`` (one filter
  a channel, zeros before the sequence's start, no bias, no
  activation); ``y_t = (C_t * c_t) W_out``. What a sequence leaves
  behind in such a layer is NOT a row a token: it is the last ``taps -
  1`` gated inputs ``z``, ``[taps - 1, embed_dim]`` whatever the length
  (:attr:`ConvLMConfig.state_rows`: the per-SLOT arrays of
  ``serving/kvpool/layout.py``). :func:`conv_mix` takes that state in
  and hands back ``[state | z]``: the state as of ANY row of the call is
  a slice of it.
- **GQA.** ``q``, ``k``, ``v`` with no bias; RMSNorm over each head's
  channels of ``q`` and ``k`` (one scale shared by the heads); RoPE on
  all channels; ``n_heads / n_kv_heads`` query heads a KV head. Only
  these layers keep per-token rows (:attr:`ConvLMConfig.cache_rows`,
  over :attr:`ConvLMConfig.cache_layers` layers): a token's K (and V)
  of a layer as ONE flat row ``[n_kv_heads * head_dim]``, so that a
  64-wide head pads no 128-lane row on the device.
- **FFN.** The first ``n_dense`` layers a SwiGLU of ``mlp_dim``; every
  other layer ``n_experts`` dropless experts of ``moe_mlp_dim``, sigmoid
  scores, the ``moe_top_k`` largest of score + bias, weights
  renormalised (``moe.sigmoid_route`` + ``moe.routed_experts``: all
  expert layers' experts in ONE stack of groups, as
  ``models/latent_lm.py`` keeps them); nothing is added for a shared
  expert.

Every layer: ``x <- x + Op(norm(x))``, then ``x <- x + FFN(norm(x))``;
the residual is held in float32. The layers are walked in Python (the
pattern is static, every layer's index into its own stack of weights a
Python int). The model is SERVED: ``PagedServingEngine`` takes this
config and builds its programs from :func:`block`
(``serving/kvpool/conv.py``). :func:`forward` is the same layer over
whole sequences from a zero state, with no cache and attention as
written: the definition the engine's logits are held to in the
package's tests. Nothing here trains it.
"""

import dataclasses
import math
from typing import ClassVar, Tuple

import jax
import jax.numpy as jnp

from dlrover_tpu.models import llama
from dlrover_tpu.models import moe as moe_lib
from dlrover_tpu.ops import rope
from dlrover_tpu.ops.norms import rms_norm

CONV, ATTENTION = "conv", "full_attention"


@dataclasses.dataclass(frozen=True)
class ConvLMConfig:
    kind: ClassVar[str] = "conv_lm"      # models.model_for: which module
    vocab_size: int = 65536
    embed_dim: int = 2048
    layer_types: Tuple[str, ...] = (CONV, CONV, ATTENTION, CONV, CONV, CONV)
    n_dense: int = 2                 # layers below it have a dense MLP
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64
    conv_taps: int = 3
    mlp_dim: int = 11776             # the dense SwiGLU
    moe_mlp_dim: int = 1536          # each expert
    n_experts: int = 64
    moe_top_k: int = 4
    routed_scaling: float = 1.0
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    pp_stages: int = 1               # the engines ask; never staged

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        bad = set(self.layer_types) - {CONV, ATTENTION}
        if bad or not self.layer_types:
            raise ValueError(
                f"layer_types {self.layer_types}: each is {CONV!r} or "
                f"{ATTENTION!r}"
            )
        if not 0 <= self.n_dense <= self.n_layers:
            raise ValueError(
                f"n_dense {self.n_dense} of {self.n_layers} layers"
            )
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def conv_layers(self) -> Tuple[int, ...]:
        return tuple(
            i for i, t in enumerate(self.layer_types) if t == CONV
        )

    @property
    def attention_layers(self) -> Tuple[int, ...]:
        return tuple(
            i for i, t in enumerate(self.layer_types) if t == ATTENTION
        )

    def index_in_kind(self, layer: int) -> int:
        """Layer ``layer``'s index among the layers of its own kind: its
        row in that kind's stack of weights, of K/V layers or of state
        layers."""
        kinds = self.layer_types
        return sum(1 for t in kinds[:layer] if t == kinds[layer])

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense

    @property
    def kv_width(self) -> int:
        """A token's K (or V) row of one attention layer, held flat."""
        return self.n_kv_heads * self.head_dim

    # The pool's statement (``serving/kvpool/layout.py``): per-token rows
    # over the attention layers alone, and one per-SLOT array over the
    # convolution layers.
    @property
    def cache_layers(self) -> int:
        return len(self.attention_layers)

    @property
    def cache_rows(self):
        return (("k_rows", (self.kv_width,)), ("v_rows", (self.kv_width,)))

    @property
    def state_rows(self):
        """name -> (layers, a slot's shape)."""
        return ((
            "conv_state",
            (len(self.conv_layers), (self.conv_taps - 1, self.embed_dim)),
        ),)

    def count_params(self) -> int:
        d, h, kh, hd = (
            self.embed_dim, self.n_heads, self.n_kv_heads, self.head_dim
        )
        conv = 3 * d * d + d * d + self.conv_taps * d
        attn = 2 * d * h * hd + 2 * d * kh * hd + 2 * hd
        moe = (
            d * self.n_experts + self.n_experts
            + self.n_experts * 3 * d * self.moe_mlp_dim
        )
        return (
            len(self.conv_layers) * conv + self.cache_layers * attn
            + self.n_layers * 2 * d
            + self.n_dense * 3 * d * self.mlp_dim
            + self.n_moe_layers * moe
            + self.vocab_size * d + d
        )


def tiny_config(**overrides) -> ConvLMConfig:
    """Small enough for a CPU test: a dense conv layer, then attention
    and conv layers over experts."""
    kw = dict(
        vocab_size=96, embed_dim=32,
        layer_types=(CONV, ATTENTION, CONV, CONV, ATTENTION),
        n_dense=1, n_heads=4, n_kv_heads=2, head_dim=8, mlp_dim=48,
        moe_mlp_dim=16, n_experts=8, moe_top_k=2, rope_theta=1e4,
        dtype="float32",
    )
    kw.update(overrides)
    return ConvLMConfig(**kw)


# Leaves a server keeps in float32 whatever its compute dtype: norm
# scales, the filter's taps, the router and its bias.
FLOAT32_LEAVES = frozenset({
    "op_norm", "ffn_norm", "q_norm", "k_norm", "final_norm", "filter",
    "router", "router_bias",
})


def init_params(config: ConvLMConfig, rng: jax.Array, dtype=None):
    """Seeded weights, normal(0, 1/sqrt(fan_in)); norm scales zero (the
    ``1 + scale`` form); the filter's taps normal(0, 1/sqrt(taps)).
    ``dtype``: what the matmul leaves are made in (float32 when None; a
    server passes its compute dtype, so that the float32 tree never
    exists); :data:`FLOAT32_LEAVES` stay float32. ONE embedding array:
    the head reads it too, so it is drawn at ``1 / sqrt(embed_dim)``: at
    unit scale a token's own embedding, still a nineteenth of the final
    residual, meets itself in the head with a logit of ~470 against
    ~45 for every other row (my chip run, PR 48: every emitted token
    was the token fed, top-2 gap 450-480), and no check could see the
    layers through the logits."""
    c = config
    d, h, kh, hd, L = c.embed_dim, c.n_heads, c.n_kv_heads, c.head_dim, \
        c.n_layers
    Lc, La = len(c.conv_layers), c.cache_layers
    Ld, Lm, E, f = c.n_dense, c.n_moe_layers, c.n_experts, c.moe_mlp_dim
    dtype = jnp.dtype(dtype or jnp.float32)
    keys = iter(jax.random.split(rng, 16))

    def dense(shape, fan_in, to=dtype):
        w = jax.random.normal(next(keys), shape, jnp.float32)
        return (w / math.sqrt(fan_in)).astype(to)

    return {
        "embed": dense((c.vocab_size, d), d),
        "layers": {
            "op_norm": jnp.zeros((L, d), jnp.float32),
            "ffn_norm": jnp.zeros((L, d), jnp.float32),
        },
        "conv": {
            "w_in": dense((Lc, d, 3 * d), d),
            "filter": dense((Lc, c.conv_taps, d), c.conv_taps, jnp.float32),
            "w_out": dense((Lc, d, d), d),
        },
        "attn": {
            "wq": dense((La, d, h, hd), d),
            "wk": dense((La, d, kh, hd), d),
            "wv": dense((La, d, kh, hd), d),
            "q_norm": jnp.zeros((La, hd), jnp.float32),
            "k_norm": jnp.zeros((La, hd), jnp.float32),
            "wo": dense((La, h, hd, d), h * hd),
        },
        "dense": {
            "w_gu": dense((Ld, d, 2 * c.mlp_dim), d),
            "w_down": dense((Ld, c.mlp_dim, d), c.mlp_dim),
        },
        "moe": {
            "router": dense((Lm, d, E), d, jnp.float32),
            "router_bias": 0.01 * dense((Lm, E), 1.0, jnp.float32),
            "w_gu": dense((Lm * E, d, 2 * f), d),
            "w_down": dense((Lm * E, f, d), f),
        },
        "final_norm": jnp.zeros((d,), jnp.float32),
    }


def prepare_decode_params(config: ConvLMConfig, params):
    """The tree as a server reads it: matmul leaves in the compute
    dtype, :data:`FLOAT32_LEAVES` as they are. Nothing is fused: the
    projections that share an input are stored side by side already."""
    cdt = config.compute_dtype

    def cast(path, leaf):
        name = getattr(path[-1], "key", None)
        return leaf if name in FLOAT32_LEAVES else leaf.astype(cdt)

    return jax.tree_util.tree_map_with_path(cast, params)


def _norm(config: ConvLMConfig, x, scale):
    return rms_norm(x, scale, eps=config.norm_eps)


# -- the two mixers -----------------------------------------------------------


def zero_state(config: ConvLMConfig, batch: int):
    """A sequence's state before its first token: ``[batch, taps - 1,
    embed_dim]`` of zeros."""
    return jnp.zeros(
        (batch, config.conv_taps - 1, config.embed_dim),
        config.compute_dtype,
    )


def conv_mix(config: ConvLMConfig, pc, u, state, taps=None):
    """The gated short convolution on its normed input ``u [b, s, d]``
    from ``state [b, taps - 1, d]`` (the gated inputs of the rows before
    the call's first): (``y [b, s, d]``, ``zz [b, taps - 1 + s, d]``).
    ``zz`` is ``[state | z]``, so the state after ``n`` of the call's
    rows is ``zz[:, n:n + taps - 1]`` (``n = 0``: the state it came
    with), for every ``n`` at once and at no cost."""
    cdt, d, k = config.compute_dtype, config.embed_dim, config.conv_taps
    s = u.shape[1]
    with jax.named_scope("in"):
        bcx = jnp.einsum("bsd,de->bse", u, pc["w_in"].astype(cdt))
        gate_b, gate_c, x = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
        z = gate_b * x
    with jax.named_scope("filter"):
        zz = jnp.concatenate([state.astype(cdt), z], axis=1)
        w = pc["filter"].astype(jnp.float32)
        conv = sum(
            w[j] * zz[:, j:j + s].astype(jnp.float32) for j in range(k)
        )
        gated = (gate_c.astype(jnp.float32) * conv).astype(cdt)
    with jax.named_scope("out"):
        y = jnp.einsum("bsd,de->bse", gated, pc["w_out"].astype(cdt))
    if taps is not None:
        taps.update(conv_z=z, conv_gated=gated)
    return y, zz


def gqa_inputs(config: ConvLMConfig, pa, u, positions):
    """Attention's projections of ``u [b, s, d]``: ``q [b, s, heads,
    hd]``, ``k`` and ``v [b, s, kv_heads, hd]``; ``q`` and ``k`` normed
    over each head's channels and rotated (all channels, half-split
    pairing). ``k`` as returned is what the cache keeps."""
    cdt = config.compute_dtype
    q = jnp.einsum("bsd,dhk->bshk", u, pa["wq"].astype(cdt))
    k = jnp.einsum("bsd,dhk->bshk", u, pa["wk"].astype(cdt))
    v = jnp.einsum("bsd,dhk->bshk", u, pa["wv"].astype(cdt))
    q = rope.apply_rope(
        _norm(config, q, pa["q_norm"]), positions, config.rope_theta
    )
    k = rope.apply_rope(
        _norm(config, k, pa["k_norm"]), positions, config.rope_theta
    )
    return q, k, v


def softmax_scale(config: ConvLMConfig) -> float:
    return config.head_dim ** -0.5


def grouped(config: ConvLMConfig, q):
    """``q [..., heads, hd]`` -> ``[..., kv_heads, group, hd]``."""
    g = config.n_heads // config.n_kv_heads
    return q.reshape(q.shape[:-2] + (config.n_kv_heads, g, q.shape[-1]))


def definition_attention(config: ConvLMConfig, q, k, v):
    """Causal attention of one sequence as written, float32 scores:
    ``q [s, heads, hd]``, ``k`` / ``v [s, kv_heads, hd]`` -> ``[s,
    heads, hd]``."""
    s = q.shape[0]
    scores = jnp.einsum(
        "skgd,tkd->kgst", grouped(config, q), k,
        preferred_element_type=jnp.float32,
    ) * softmax_scale(config)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("kgst,tkd->skgd", probs.astype(v.dtype), v)
    return out.reshape(q.shape)


# -- the FFN ------------------------------------------------------------------


def _swiglu(h, w_gu, w_down):
    f = w_down.shape[-2]
    gu = jnp.einsum("bsd,df->bsf", h, w_gu.astype(h.dtype))
    act = (jax.nn.silu(gu[..., :f]) * gu[..., f:]).astype(h.dtype)
    return jnp.einsum("bsf,fd->bsd", act, w_down.astype(h.dtype))


def feed(config: ConvLMConfig, params, layer: int, h, taps=None):
    """Layer ``layer``'s FFN on its normed input ``h [b, s, d]`` ->
    (``y``, :class:`moe.ShareCounters` or None): the dense SwiGLU below
    ``n_dense``, else the expert layer, whose experts are groups
    ``(layer - n_dense) * n_experts ...`` of the one stack, read in
    place."""
    c = config
    with jax.named_scope("mlp"):
        if layer < c.n_dense:
            with jax.named_scope("dense"):
                pd = params["dense"]
                return _swiglu(h, pd["w_gu"][layer], pd["w_down"][layer]), None
        pm, at = params["moe"], layer - c.n_dense
        with jax.named_scope("router"):
            experts, weights = moe_lib.sigmoid_route(
                h.reshape(-1, h.shape[-1]), pm["router"][at],
                pm["router_bias"][at], c.moe_top_k, c.routed_scaling,
            )
        if taps is not None:
            taps.update(experts=experts, weights=weights)
        # ``routed_experts`` names its own scope (``experts``).
        return moe_lib.routed_experts(
            h, experts, weights, pm["w_gu"], pm["w_down"], c.n_experts,
            group_offset=at * c.n_experts,
        )


# -- the block and the layer loop ---------------------------------------------


def block(config: ConvLMConfig, params, layer: int, x, positions, mixer,
          taps=None):
    """Decoder block ``layer`` over the residual ``x [b, s, d]``
    (float32). ``mixer``: for a convolution layer the state it starts
    from, ``[b, taps - 1, d]``; for an attention layer ``attend(q, k,
    v) -> [b, s, heads, hd]``. Returns (``x``, what the layer leaves
    behind, the expert layer's counters or None): a convolution layer
    leaves ``zz`` (:func:`conv_mix`), an attention layer ``(k, v)``, the
    new tokens' ``[b, s, kv_heads, hd]``. ``taps``: a dict the block
    fills with what it otherwise keeps to itself (a check's probe reads
    them; the served programs pass none): ``x_in``, the mixer's output
    before the residual (``y_op``; of an attention layer also ``attn``,
    before ``wo``), ``x_mid``, the FFN's normed input ``h_mlp`` and
    output ``y_mlp``, ``x_out`` and, of an expert layer, ``experts`` and
    ``weights``."""
    c, cdt = config, config.compute_dtype
    pl = params["layers"]
    x_in = x
    u = _norm(c, x, pl["op_norm"][layer]).astype(cdt)
    at = c.index_in_kind(layer)
    if c.layer_types[layer] == CONV:
        pc = jax.tree_util.tree_map(lambda a: a[at], params["conv"])
        with jax.named_scope("attn"), jax.named_scope("conv"):
            y, left = conv_mix(c, pc, u, mixer, taps)
    else:
        pa = jax.tree_util.tree_map(lambda a: a[at], params["attn"])
        with jax.named_scope("attn"), jax.named_scope("gqa"):
            q, k, v = gqa_inputs(c, pa, u, positions)
            out = mixer(q, k, v)
            y = jnp.einsum(
                "bshk,hkd->bsd", out.astype(cdt), pa["wo"].astype(cdt)
            )
        left = (k, v)
        if taps is not None:
            taps.update(attn=out)
    x_mid = x + y.astype(jnp.float32)
    h = _norm(c, x_mid, pl["ffn_norm"][layer]).astype(cdt)
    y_mlp, counters = feed(c, params, layer, h, taps)
    x = x_mid + y_mlp.astype(jnp.float32)
    if taps is not None:
        taps.update(x_in=x_in, y_op=y, x_mid=x_mid, h_mlp=h, y_mlp=y_mlp,
                    x_out=x)
    return x, left, counters


def embed(config: ConvLMConfig, params, tokens):
    """The residual's start: the tokens' embeddings, float32."""
    return llama.embed_tokens(config, params, tokens).astype(jnp.float32)


def unembed(config: ConvLMConfig, params, x):
    """The final norm and the TIED head: float32 logits."""
    with jax.named_scope("vocab"):
        h = _norm(config, x, params["final_norm"]).astype(
            config.compute_dtype
        )
        return jnp.einsum(
            "bsd,vd->bsv", h, params["embed"].astype(config.compute_dtype)
        ).astype(jnp.float32)


def expert_counts(counters):
    """``[experts hit (mean over the expert layers), expert rows
    dropped]`` of one program's expert layers (float32 ``[2]``): what a
    decode step hands the host after its tokens."""
    if not counters:
        return jnp.zeros((2,), jnp.float32)
    return jnp.stack([
        jnp.mean(jnp.stack(
            [c.experts_hit for c in counters]
        ).astype(jnp.float32)),
        jnp.sum(jnp.stack(
            [c.rows_dropped for c in counters]
        )).astype(jnp.float32),
    ])


def forward(config: ConvLMConfig, params, tokens):
    """``tokens [b, s]`` -> float32 logits ``[b, s, vocab]`` and the
    expert rows dropped (0): the layer as the engines run it, but over
    whole sequences from a zero state, with no cache and attention as
    written (:func:`definition_attention`)."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    attend = jax.vmap(lambda *a: definition_attention(config, *a))
    x = embed(config, params, tokens)
    dropped = jnp.zeros((), jnp.int32)
    for layer, kind in enumerate(config.layer_types):
        mixer = zero_state(config, b) if kind == CONV else attend
        x, _, counters = block(config, params, layer, x, positions, mixer)
        if counters is not None:
            dropped = dropped + counters.rows_dropped
    return unembed(config, params, x), dropped
