"""A decoder whose attention layers follow a PATTERN of two reaches:
sliding-window layers, which see a token and the ``sliding_window - 1``
before it, and full layers, which see everything; every layer over
softmax-routed dropless experts, under an untied head.

- **GQA.** ``q``, ``k``, ``v`` with no bias and no per-head norm;
  ``n_heads / n_kv_heads`` query heads a KV head; scores ``q . k /
  sqrt(head_dim)``, causal. In a ``sliding_attention`` layer key ``j``
  is visible to query ``i`` iff ``0 <= i - j < sliding_window``.
- **Two rotations** (whole head, half-split pairing): a sliding layer
  rotates by ``rope_frequencies(head_dim, rope_theta)``; a full layer by
  ``yarn_frequencies`` (``rope_factor``, ``rope_original_max``,
  ``beta_fast``, ``beta_slow``) with cosine and sine both multiplied by
  ``attention_factor`` (``yarn_mscale(rope_factor)`` unless stated), so
  the logits of a full layer carry its square.
- **FFN.** Every layer ``n_experts`` dropless experts of
  ``moe_mlp_dim``: ``softmax`` over all experts in float32, the
  ``moe_top_k`` largest, weights renormalised over the chosen
  (``moe.softmax_route`` + ``moe.routed_experts``: all layers' experts
  in ONE stack of groups); no shared expert.

Every layer: ``x <- x + Attn(norm(x))``, then ``x <- x + FFN(norm(x))``;
the residual is held in float32. The layers are walked in Python (the
pattern is static). The model is SERVED: what a token leaves behind is
a K and a V row a layer, and the two reaches keep them in two GROUPS of
the paged pool (:attr:`WindowLMConfig.cache_groups`,
``serving/kvpool/layout.py``): the full layers' rows for as long as the
sequence lives, the sliding layers' only while a query can still see
them. ``PagedServingEngine`` takes this config and builds its programs
from :func:`block` (``serving/kvpool/window.py``). :func:`forward` is
the same layer over whole sequences with no cache and attention as
written: the definition the engine's logits are held to in the
package's tests. Nothing here trains it.
"""

import dataclasses
import math
from typing import ClassVar, Optional, Tuple

import jax
import jax.numpy as jnp

from dlrover_tpu.models import llama
from dlrover_tpu.models import moe as moe_lib
from dlrover_tpu.ops import rope
from dlrover_tpu.ops.norms import rms_norm

SLIDING, FULL = "sliding_attention", "full_attention"
# The pool's groups by name (``cache_groups``), and the device scopes the
# two reaches run under (``attn/full``, ``attn/window``).
GROUP_OF = {FULL: "full", SLIDING: "window"}


@dataclasses.dataclass(frozen=True)
class WindowLMConfig:
    kind: ClassVar[str] = "window_lm"    # models.model_for: which module
    vocab_size: int = 98304
    embed_dim: int = 2304
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL)
    sliding_window: int = 1024
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    moe_mlp_dim: int = 896           # each expert
    n_experts: int = 64
    moe_top_k: int = 8
    rope_theta: float = 5e5
    # YaRN, on the full layers alone.
    rope_factor: float = 16.0
    rope_original_max: int = 8192
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None    # None: yarn_mscale(factor)
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    pp_stages: int = 1               # the engines ask; never staged

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        bad = set(self.layer_types) - {SLIDING, FULL}
        if bad or not self.layer_types:
            raise ValueError(
                f"layer_types {self.layer_types}: each is {SLIDING!r} or "
                f"{FULL!r}"
            )
        if self.layer_types.count(FULL) == 0:
            raise ValueError(
                "a pattern with no full_attention layer has no group that "
                "keeps a sequence's every row: the engine sizes a slot by it"
            )
        if self.sliding_window < 1:
            raise ValueError(f"sliding_window {self.sliding_window}")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")
        if self.attention_factor is None:
            object.__setattr__(
                self, "attention_factor", rope.yarn_mscale(self.rope_factor)
            )

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types) if t == kind)

    def index_in_kind(self, layer: int) -> int:
        """Layer ``layer``'s index among the layers of its own reach: its
        layer of that group's pool arrays."""
        kinds = self.layer_types
        return sum(1 for t in kinds[:layer] if t == kinds[layer])

    # The pool's statement (``serving/kvpool/layout.py``): the same K and
    # V row a token in both groups; the full layers' group FIRST (the
    # group that sizes a slot), then the sliding layers', which keeps a
    # row only while a query can see it: ``sliding_window - 1`` rows
    # below the next one to be written.
    @property
    def cache_layers(self) -> int:
        return len(self.layers_of(FULL))

    @property
    def cache_groups(self):
        """name -> (layers, ``"all"`` or the rows below the next row that
        a query can still see)."""
        groups = [(GROUP_OF[FULL], (self.cache_layers, "all"))]
        if self.layers_of(SLIDING):
            groups.append((
                GROUP_OF[SLIDING],
                (len(self.layers_of(SLIDING)), self.sliding_window - 1),
            ))
        return tuple(groups)

    def count_params(self) -> int:
        d, h, kh, hd = (
            self.embed_dim, self.n_heads, self.n_kv_heads, self.head_dim
        )
        attn = 2 * d * h * hd + 2 * d * kh * hd
        moe = d * self.n_experts + self.n_experts * 3 * d * self.moe_mlp_dim
        return (
            self.n_layers * (attn + moe + 2 * d)
            + 2 * self.vocab_size * d + d
        )


def tiny_config(**overrides) -> WindowLMConfig:
    """Small enough for a CPU test: one period of (sliding, sliding,
    full) with a window of 24 rows."""
    kw = dict(
        vocab_size=96, embed_dim=32,
        layer_types=(SLIDING, SLIDING, FULL), sliding_window=24,
        n_heads=4, n_kv_heads=2, head_dim=8, moe_mlp_dim=16, n_experts=8,
        moe_top_k=2, rope_theta=1e4, rope_factor=4.0, rope_original_max=32,
        dtype="float32",
    )
    kw.update(overrides)
    return WindowLMConfig(**kw)


# Leaves a server keeps in float32 whatever its compute dtype: norm
# scales and the router.
FLOAT32_LEAVES = frozenset({"attn_norm", "ffn_norm", "final_norm", "router"})


def init_params(config: WindowLMConfig, rng: jax.Array, dtype=None):
    """Seeded weights, normal(0, 1/sqrt(fan_in)); norm scales zero (the
    ``1 + scale`` form). ``dtype``: what the matmul leaves are made in
    (float32 when None; a server passes its compute dtype, so that the
    float32 tree never exists); :data:`FLOAT32_LEAVES` stay float32."""
    c = config
    d, h, kh, hd, L = c.embed_dim, c.n_heads, c.n_kv_heads, c.head_dim, \
        c.n_layers
    E, f = c.n_experts, c.moe_mlp_dim
    dtype = jnp.dtype(dtype or jnp.float32)
    keys = iter(jax.random.split(rng, 16))

    def dense(shape, fan_in, to=dtype):
        w = jax.random.normal(next(keys), shape, jnp.float32)
        return (w / math.sqrt(fan_in)).astype(to)

    return {
        "embed": dense((c.vocab_size, d), 1.0),
        "layers": {
            "attn_norm": jnp.zeros((L, d), jnp.float32),
            "ffn_norm": jnp.zeros((L, d), jnp.float32),
            "wq": dense((L, d, h, hd), d),
            "wk": dense((L, d, kh, hd), d),
            "wv": dense((L, d, kh, hd), d),
            "wo": dense((L, h, hd, d), h * hd),
            "router": dense((L, d, E), d, jnp.float32),
        },
        "moe": {
            "w_gu": dense((L * E, d, 2 * f), d),
            "w_down": dense((L * E, f, d), f),
        },
        "final_norm": jnp.zeros((d,), jnp.float32),
        "lm_head": dense((d, c.vocab_size), d),
    }


def prepare_decode_params(config: WindowLMConfig, params):
    """The tree as a server reads it: matmul leaves in the compute
    dtype, :data:`FLOAT32_LEAVES` as they are. Nothing is fused: gate
    and up are stored side by side already."""
    cdt = config.compute_dtype

    def cast(path, leaf):
        name = getattr(path[-1], "key", None)
        return leaf if name in FLOAT32_LEAVES else leaf.astype(cdt)

    return jax.tree_util.tree_map_with_path(cast, params)


def _norm(config: WindowLMConfig, x, scale):
    return rms_norm(x, scale, eps=config.norm_eps)


# -- attention ----------------------------------------------------------------


def rotation(config: WindowLMConfig, kind: str):
    """(inverse frequencies ``[head_dim // 2]``, what cosine and sine
    are multiplied by) of a layer of reach ``kind``."""
    if kind == SLIDING:
        return rope.rope_frequencies(config.head_dim, config.rope_theta), 1.0
    return rope.yarn_frequencies(
        config.head_dim, config.rope_theta, config.rope_factor,
        config.rope_original_max, config.beta_fast, config.beta_slow,
    ), float(config.attention_factor)


def rotate(config: WindowLMConfig, kind: str, x, positions):
    """``x [b, s, heads, hd]`` rotated as a layer of reach ``kind``
    rotates its queries and keys."""
    inv_freq, factor = rotation(config, kind)
    out = rope.apply_rope(
        x.astype(jnp.float32), positions, inv_freq=inv_freq
    )
    return (out * factor if factor != 1.0 else out).astype(x.dtype)


def gqa_inputs(config: WindowLMConfig, pa, kind: str, u, positions):
    """Attention's projections of ``u [b, s, d]``: ``q [b, s, heads,
    hd]``, ``k`` and ``v [b, s, kv_heads, hd]``, ``q`` and ``k`` rotated
    by the layer's reach. ``k`` as returned is what the cache keeps."""
    cdt = config.compute_dtype
    q = jnp.einsum("bsd,dhk->bshk", u, pa["wq"].astype(cdt))
    k = jnp.einsum("bsd,dhk->bshk", u, pa["wk"].astype(cdt))
    v = jnp.einsum("bsd,dhk->bshk", u, pa["wv"].astype(cdt))
    return (rotate(config, kind, q, positions),
            rotate(config, kind, k, positions), v)


def softmax_scale(config: WindowLMConfig) -> float:
    return config.head_dim ** -0.5


def grouped(config: WindowLMConfig, q):
    """``q [..., heads, hd]`` -> ``[..., kv_heads, group, hd]``."""
    g = config.n_heads // config.n_kv_heads
    return q.reshape(q.shape[:-2] + (config.n_kv_heads, g, q.shape[-1]))


def definition_attention(config: WindowLMConfig, kind: str, q, k, v):
    """Attention of one sequence as written, float32 scores: ``q [s,
    heads, hd]``, ``k`` / ``v [s, kv_heads, hd]`` -> ``[s, heads, hd]``;
    causal, and in a sliding layer inside the window."""
    s = q.shape[0]
    scores = jnp.einsum(
        "skgd,tkd->kgst", grouped(config, q), k,
        preferred_element_type=jnp.float32,
    ) * softmax_scale(config)
    ahead = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]   # i - j
    visible = ahead >= 0
    if kind == SLIDING:
        visible &= ahead < config.sliding_window
    probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("kgst,tkd->skgd", probs.astype(v.dtype), v)
    return out.reshape(q.shape)


# -- the FFN ------------------------------------------------------------------


def feed(config: WindowLMConfig, params, layer: int, h, taps=None):
    """Layer ``layer``'s expert layer on its normed input ``h [b, s,
    d]`` -> (``y``, :class:`moe.ShareCounters`): its experts are groups
    ``layer * n_experts ...`` of the one stack, read in place."""
    c = config
    with jax.named_scope("mlp"):
        with jax.named_scope("router"):
            experts, weights = moe_lib.softmax_route(
                h.reshape(-1, h.shape[-1]),
                params["layers"]["router"][layer], c.moe_top_k,
            )
        if taps is not None:
            taps.update(experts=experts, weights=weights)
        # ``routed_experts`` names its own scope (``experts``).
        pm = params["moe"]
        return moe_lib.routed_experts(
            h, experts, weights, pm["w_gu"], pm["w_down"], c.n_experts,
            group_offset=layer * c.n_experts,
        )


# -- the block and the layer loop ---------------------------------------------


def block(config: WindowLMConfig, params, layer: int, x, positions, attend,
          taps=None):
    """Decoder block ``layer`` over the residual ``x [b, s, d]``
    (float32). ``attend(q, k, v) -> [b, s, heads, hd]`` is the layer's
    attention over whatever the caller keeps of the sequence. Returns
    (``x``, the new tokens' ``(k, v) [b, s, kv_heads, hd]``, the expert
    layer's counters). ``taps``: a dict the block fills with what it
    otherwise keeps to itself (a check's probe reads them; the served
    programs pass none): ``x_in``, ``q``, ``attn`` (before ``wo``),
    ``y_op``, ``x_mid``, the FFN's normed input ``h_mlp`` and output
    ``y_mlp``, ``x_out``, ``experts`` and ``weights``."""
    c, cdt = config, config.compute_dtype
    kind = c.layer_types[layer]
    pa = jax.tree_util.tree_map(lambda a: a[layer], params["layers"])
    x_in = x
    u = _norm(c, x, pa["attn_norm"]).astype(cdt)
    with jax.named_scope("attn"), jax.named_scope(GROUP_OF[kind]):
        q, k, v = gqa_inputs(c, pa, kind, u, positions)
        out = attend(q, k, v)
        y = jnp.einsum(
            "bshk,hkd->bsd", out.astype(cdt), pa["wo"].astype(cdt)
        )
    x_mid = x + y.astype(jnp.float32)
    h = _norm(c, x_mid, pa["ffn_norm"]).astype(cdt)
    y_mlp, counters = feed(c, params, layer, h, taps)
    x = x_mid + y_mlp.astype(jnp.float32)
    if taps is not None:
        taps.update(x_in=x_in, q=q, attn=out, y_op=y, x_mid=x_mid, h_mlp=h,
                    y_mlp=y_mlp, x_out=x)
    return x, (k, v), counters


def embed(config: WindowLMConfig, params, tokens):
    """The residual's start: the tokens' embeddings, float32."""
    return llama.embed_tokens(config, params, tokens).astype(jnp.float32)


def unembed(config: WindowLMConfig, params, x):
    """The final norm and the untied head: float32 logits."""
    with jax.named_scope("vocab"):
        h = _norm(config, x, params["final_norm"]).astype(
            config.compute_dtype
        )
        return jnp.einsum(
            "bsd,dv->bsv", h, params["lm_head"].astype(config.compute_dtype)
        ).astype(jnp.float32)


def expert_counts(counters):
    """``[experts hit (mean over the layers), expert rows dropped]`` of
    one program's expert layers (float32 ``[2]``): what a decode step
    hands the host after its tokens."""
    return jnp.stack([
        jnp.mean(jnp.stack(
            [c.experts_hit for c in counters]
        ).astype(jnp.float32)),
        jnp.sum(jnp.stack(
            [c.rows_dropped for c in counters]
        )).astype(jnp.float32),
    ])


def forward(config: WindowLMConfig, params, tokens):
    """``tokens [b, s]`` -> float32 logits ``[b, s, vocab]`` and the
    expert rows dropped (0): the layer as the engines run it, but over
    whole sequences with no cache and attention as written
    (:func:`definition_attention`)."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x = embed(config, params, tokens)
    dropped = jnp.zeros((), jnp.int32)
    for layer, kind in enumerate(config.layer_types):
        attend = jax.vmap(
            lambda *a, kind=kind: definition_attention(config, kind, *a)
        )
        x, _, counters = block(config, params, layer, x, positions, attend)
        dropped = dropped + counters.rows_dropped
    return unembed(config, params, x), dropped
