"""A decoder whose layers follow a LIST of two mixers: lightning linear
attention, whose whole memory of a sequence is one float32 matrix a head,
and block-sparse attention without rotation, which picks a few BLOCKS of
its cache from compressed keys (MiniCPM-SALA: Lightning Attention beside
InfLLM-v2), over a dense SwiGLU, under an untied head and the family's
depth, embedding and logit scales.

Every layer, with ``c = scale_depth / sqrt(published_layers)`` (the
PUBLISHED depth, whatever slice of it is held): ``x <- x + c * Mix(norm
(x))``, then ``x <- x + c * W_down(silu(W_gate h) * W_up h)``, ``h =
norm(x)``. ``x_0 = scale_emb * embed(token)``; logits ``= head(norm(x_L)
* dim_model_base / embed_dim)``. The residual is held in float32.

- **Lightning** (``mixer_types[l] == "lightning-attn"``). ``q, k, v =
  W_q u, W_k u, W_v u`` (``lightning_heads`` heads of
  ``lightning_head_dim`` each); ``q`` and ``k`` RMS-normed over a head's
  channels (a learned gain a layer), rotated (all channels, the repo's
  half-split pairing), ``q`` scaled by ``head_dim ** -0.5``. Per head
  ``h`` a CONSTANT decay ``lambda_h = exp(-slope_h)``
  (:func:`decay_slopes`: Lightning Attention's, by head and PUBLISHED
  layer index):

      S_t = lambda_h * S_{t-1} + k_t v_t^T      S in R^{d x d}, float32
      o_t = S_t^T q_t

  then ``o`` RMS-normed over all heads' channels at once, gated by
  ``sigmoid(W_g u)``, and ``W_o``. What a sequence leaves behind in such
  a layer is ``S``, ``[heads, d, d]`` float32 whatever the length
  (:attr:`LinearSparseLMConfig.state_rows`: the per-SLOT arrays of
  ``serving/kvpool/layout.py``, with their own dtype).
  :func:`lightning_chunk` is the same over a run of rows entering with a
  state (``O = (Q * Lambda_in) S + ((Q K^T) * D) V``), and
  :func:`lightning_state_after` the state after ANY number of its rows;
  every exponent is <= 0.
- **Sparse** (``"minicpm4"``). ``n_heads`` query heads over
  ``n_kv_heads`` KV heads, NO rotation. With ``k_s`` the cached keys:

      c_p   = mean(k_{stride (p - 1)} ... k_{stride (p + 1) - 1})
              a KV head, no weights (``kernel_size = 2 * kernel_stride``
              rows); it exists once its last row does, and is held at
              PLACE ``p`` of the sequence's compressed keys, ``p >= 1``
      p_thp = softmax over the visible p of (q_th . c_p / sqrt(d)), f32
      P_tgp = sum of p_thp over the heads h of KV group g
      B_tgb = max of P_tgp over the places whose rows overlap block b
              (``r b ... r b + r``, ``r = block / stride``)
      forced: the first ``init_blocks`` blocks and the ``window_size /
              block`` blocks ending with t's own: score +inf
      S_tg  = the ``topk`` blocks of largest B (ties to the lower b);
              every visible block if fewer
      o_th  = softmax over the rows s <= t of the blocks of S_t,g(h)

  and a query that sees at most ``dense_len`` rows attends to all of
  them. Then the gate ``sigmoid(W_g u)`` and ``W_o``. Only these layers
  keep per-token rows (:attr:`LinearSparseLMConfig.cache_rows`): K and V
  of one KV HEAD a pool layer (``cache_layers = sparse layers x KV
  heads``: a selected block of one head is then one page of one layer,
  read whole), and ``ckeys``, ``block / stride`` rows a block
  (:attr:`LinearSparseLMConfig.cache_strides`).

The layers are walked in Python (the list is static). The model is
SERVED: ``PagedServingEngine`` takes this config and builds its programs
from the functions here (``serving/kvpool/linear.py``). :func:`forward`
is the same layers over whole sequences from a zero state, with no cache:
the definition the engine's logits are held to. Nothing here trains it.
"""

import dataclasses
import math
from typing import ClassVar, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from dlrover_tpu.ops import rope
from dlrover_tpu.ops import sparse_attention as sparse_ops
from dlrover_tpu.ops.norms import rms_norm

LIGHTNING, SPARSE = "lightning-attn", "minicpm4"
HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class LinearSparseLMConfig:
    kind: ClassVar[str] = "linear_sparse_lm"   # models.model_for
    vocab_size: int = 73448
    embed_dim: int = 4096
    mixer_types: Tuple[str, ...] = (SPARSE, LIGHTNING, LIGHTNING, LIGHTNING)
    first_layer: int = 0             # PUBLISHED index of mixer_types[0]
    published_layers: int = 32
    n_heads: int = 32                # the sparse mixer's query heads
    n_kv_heads: int = 2
    head_dim: int = 128
    lightning_heads: int = 32
    lightning_head_dim: int = 128
    mlp_dim: int = 16384
    rope_theta: float = 1e4          # the lightning layers' alone
    norm_eps: float = 1e-6
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    kernel_size: int = 32            # rows a compressed key averages
    kernel_stride: int = 16
    sparse_block: int = 64           # rows a selected block holds
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    dense_len: int = 8192
    dtype: str = "bfloat16"
    pp_stages: int = 1               # the engines ask; never staged

    def __post_init__(self):
        object.__setattr__(self, "mixer_types", tuple(self.mixer_types))
        bad = set(self.mixer_types) - {LIGHTNING, SPARSE}
        if bad or not self.mixer_types:
            raise ValueError(
                f"mixer_types {self.mixer_types}: each is {LIGHTNING!r} or "
                f"{SPARSE!r}"
            )
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")
        if self.kernel_size != 2 * self.kernel_stride:
            raise ValueError(
                f"kernel_size {self.kernel_size} must be twice "
                f"kernel_stride {self.kernel_stride}: a compressed key "
                "is the mean of two strides of rows"
            )
        if (self.sparse_block % self.kernel_stride
                or self.window_size % self.sparse_block):
            raise ValueError(
                f"sparse_block {self.sparse_block} must be whole strides "
                f"of {self.kernel_stride} and window_size "
                f"{self.window_size} whole blocks"
            )
        if self.first_layer + self.n_layers > self.published_layers:
            raise ValueError(
                f"layers {self.first_layer}..."
                f"{self.first_layer + self.n_layers - 1} of "
                f"{self.published_layers}"
            )

    @property
    def n_layers(self) -> int:
        return len(self.mixer_types)

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def lightning_layers(self) -> Tuple[int, ...]:
        return tuple(
            i for i, t in enumerate(self.mixer_types) if t == LIGHTNING
        )

    @property
    def sparse_layers(self) -> Tuple[int, ...]:
        return tuple(
            i for i, t in enumerate(self.mixer_types) if t == SPARSE
        )

    def index_in_kind(self, layer: int) -> int:
        """Layer ``layer``'s index among the layers of its own kind."""
        kinds = self.mixer_types
        return sum(1 for t in kinds[:layer] if t == kinds[layer])

    @property
    def depth_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.published_layers)

    @property
    def logit_scale(self) -> float:
        return self.dim_model_base / self.embed_dim

    @property
    def group(self) -> int:
        """Query heads a KV head of the sparse mixer."""
        return self.n_heads // self.n_kv_heads

    @property
    def ckeys_per_block(self) -> int:
        return self.sparse_block // self.kernel_stride

    @property
    def window_blocks(self) -> int:
        return self.window_size // self.sparse_block

    @property
    def list_blocks(self) -> int:
        """Blocks a (query, KV group)'s list holds at most: the ``topk``
        selected, or every block of a query still under ``dense_len``."""
        return max(self.topk, -(-self.dense_len // self.sparse_block))

    # The pool's statement (``serving/kvpool/layout.py``): per-token rows
    # of ONE KV head a pool layer over the sparse layers, the compressed
    # keys at a stride, and one per-SLOT float32 array over the
    # lightning layers.
    @property
    def cache_layers(self) -> int:
        return len(self.sparse_layers) * self.n_kv_heads

    @property
    def cache_rows(self):
        row = (self.head_dim,)
        return (("k_pages", row), ("v_pages", row), ("ckeys", row))

    @property
    def cache_strides(self):
        """name -> tokens a row of that array stands for (1: every
        other)."""
        return {"ckeys": self.kernel_stride}

    @property
    def state_rows(self):
        """name -> (layers, a slot's shape, dtype)."""
        d = self.lightning_head_dim
        return ((
            "lightning",
            (len(self.lightning_layers), (self.lightning_heads, d, d),
             "float32"),
        ),)

    def pool_layer(self, layer: int, kv_head: int) -> int:
        """The pool layer that holds KV head ``kv_head`` of sparse layer
        ``layer``."""
        return self.index_in_kind(layer) * self.n_kv_heads + kv_head

    def count_params(self) -> int:
        d, f = self.embed_dim, self.mlp_dim
        lw = self.lightning_heads * self.lightning_head_dim
        qw, kw = self.n_heads * self.head_dim, self.n_kv_heads * self.head_dim
        lightning = 5 * d * lw + 2 * self.lightning_head_dim + lw
        sparse = 3 * d * qw + 2 * d * kw
        return (
            len(self.lightning_layers) * lightning
            + len(self.sparse_layers) * sparse
            + self.n_layers * (3 * d * f + 2 * d)
            + 2 * self.vocab_size * d + d
        )


def tiny_config(**overrides) -> LinearSparseLMConfig:
    """Small enough for a CPU test: blocks of 8 rows, a compressed key
    every 2, 5 blocks a list: the first, the last two and the 2 best of
    the rest."""
    kw = dict(
        vocab_size=96, embed_dim=32,
        mixer_types=(SPARSE, LIGHTNING, LIGHTNING, SPARSE, LIGHTNING),
        first_layer=2, published_layers=8, n_heads=4, n_kv_heads=2,
        head_dim=8, lightning_heads=4, lightning_head_dim=8, mlp_dim=48,
        dim_model_base=8, kernel_size=4, kernel_stride=2, sparse_block=8,
        topk=5, init_blocks=1, window_size=16, dense_len=16,
        dtype="float32",
    )
    kw.update(overrides)
    return LinearSparseLMConfig(**kw)


def decay_slopes(config: LinearSparseLMConfig, layer: int) -> np.ndarray:
    """``slope_h`` of HELD layer ``layer`` (``lambda_h = exp(-slope_h)``),
    float32 ``[lightning_heads]``: Lightning Attention's ``2 ** (-8 (h +
    1) / H) * (1 - l / (L - 1) + 1e-5)`` with ``l`` the PUBLISHED index
    of the layer and ``L`` the published depth."""
    n = config.lightning_heads
    base = 2.0 ** (-8.0 * (np.arange(n) + 1) / n)
    depth = 1.0 - (config.first_layer + layer) / max(
        config.published_layers - 1, 1
    ) + 1e-5
    return (base * depth).astype(np.float32)


# Leaves a server keeps in float32 whatever its compute dtype.
FLOAT32_LEAVES = frozenset({
    "mix_norm", "ffn_norm", "q_norm", "k_norm", "o_norm", "final_norm",
})


def init_params(config: LinearSparseLMConfig, rng: jax.Array, dtype=None):
    """Seeded weights, normal(0, 1 / sqrt(fan_in)); norm gains zero (the
    ``1 + scale`` form). The embedding is drawn at ``1 / scale_emb`` and
    the head at ``(embed_dim / dim_model_base) / sqrt(embed_dim)``, so
    that ``x_0`` and the logits are of unit scale under the family's two
    multipliers, as a trained model's are. ``dtype``: what the matmul
    leaves are made in (float32 when None; a server passes its compute
    dtype, so that the float32 tree never exists)."""
    c = config
    d, f, L = c.embed_dim, c.mlp_dim, c.n_layers
    Ll, Ls = len(c.lightning_layers), len(c.sparse_layers)
    lh, ld = c.lightning_heads, c.lightning_head_dim
    h, kh, hd = c.n_heads, c.n_kv_heads, c.head_dim
    dtype = jnp.dtype(dtype or jnp.float32)
    keys = iter(jax.random.split(rng, 16))

    def dense(shape, fan_in, gain=1.0):
        w = jax.random.normal(next(keys), shape, jnp.float32)
        return (w * (gain / math.sqrt(fan_in))).astype(dtype)

    return {
        "embed": dense((c.vocab_size, d), 1.0, 1.0 / c.scale_emb),
        "head": dense((d, c.vocab_size), d, 1.0 / c.logit_scale),
        "layers": {
            "mix_norm": jnp.zeros((L, d), jnp.float32),
            "ffn_norm": jnp.zeros((L, d), jnp.float32),
            "w_gu": dense((L, d, 2 * f), d),
            "w_down": dense((L, f, d), f),
        },
        "lightning": {
            "wq": dense((Ll, d, lh, ld), d),
            "wk": dense((Ll, d, lh, ld), d),
            "wv": dense((Ll, d, lh, ld), d),
            "wg": dense((Ll, d, lh * ld), d),
            "wo": dense((Ll, lh, ld, d), lh * ld),
            "q_norm": jnp.zeros((Ll, ld), jnp.float32),
            "k_norm": jnp.zeros((Ll, ld), jnp.float32),
            "o_norm": jnp.zeros((Ll, lh * ld), jnp.float32),
        },
        "sparse": {
            "wq": dense((Ls, d, h, hd), d),
            "wk": dense((Ls, d, kh, hd), d),
            "wv": dense((Ls, d, kh, hd), d),
            "wg": dense((Ls, d, h * hd), d),
            "wo": dense((Ls, h, hd, d), h * hd),
        },
        "final_norm": jnp.zeros((d,), jnp.float32),
    }


def prepare_decode_params(config: LinearSparseLMConfig, params):
    """The tree as a server reads it: matmul leaves in the compute
    dtype, :data:`FLOAT32_LEAVES` as they are."""
    cdt = config.compute_dtype

    def cast(path, leaf):
        name = getattr(path[-1], "key", None)
        return leaf if name in FLOAT32_LEAVES else leaf.astype(cdt)

    return jax.tree_util.tree_map_with_path(cast, params)


def _norm(config, x, scale):
    return rms_norm(x, scale, eps=config.norm_eps)


# -- the lightning mixer ------------------------------------------------------


def lightning_inputs(config: LinearSparseLMConfig, pl, u, positions):
    """The mixer's projections of ``u [b, s, d]``: ``q``, ``k``, ``v [b,
    s, heads, hd]`` (``q`` and ``k`` normed a head, rotated; ``q``
    scaled) and the gate ``[b, s, heads * hd]`` before its sigmoid."""
    cdt = config.compute_dtype
    proj = lambda w: jnp.einsum(  # noqa: E731
        "bsd,dhk->bshk", u, w.astype(cdt)
    )
    q = rope.apply_rope(
        _norm(config, proj(pl["wq"]), pl["q_norm"]), positions,
        config.rope_theta,
    )
    k = rope.apply_rope(
        _norm(config, proj(pl["wk"]), pl["k_norm"]), positions,
        config.rope_theta,
    )
    q = (q * config.lightning_head_dim ** -0.5).astype(cdt)
    gate = jnp.einsum("bsd,de->bse", u, pl["wg"].astype(cdt))
    return q, k, proj(pl["wv"]), gate


def lightning_chunk(q, k, v, state, slopes):
    """A run of ``s`` rows of one sequence entering with ``state [heads,
    d, d]`` (float32: ``S`` as of the row before the run's first): ``q``,
    ``k``, ``v [s, heads, d]`` -> ``o [s, heads, d]`` float32, ``o_t =
    S_t^T q_t``. Products accumulate in float32; the decayed scores meet
    ``v`` in its own dtype (rounded once, as attention's probabilities
    are); the state meets ``q`` in float32."""
    f32 = jnp.float32
    s = q.shape[0]
    idx = jnp.arange(s)
    slopes = jnp.asarray(slopes, f32)
    entry = jnp.exp(-slopes[None, :] * (idx[:, None] + 1).astype(f32))
    o = jnp.einsum(
        "thk,hkv->thv", q.astype(f32) * entry[:, :, None], state,
        precision=HIGHEST,
    )
    scores = jnp.einsum("thk,jhk->htj", q, k, preferred_element_type=f32)
    dist = (idx[:, None] - idx[None, :]).astype(f32)
    decay = jnp.exp(jnp.where(
        dist >= 0, -slopes[:, None, None] * dist[None], -jnp.inf
    ))
    return o + jnp.einsum(
        "htj,jhv->thv", (scores * decay).astype(v.dtype), v,
        preferred_element_type=f32,
    )


def lightning_state_after(k, v, state, slopes, n):
    """The state after the first ``n`` rows of the run (``n`` may be
    traced, 0 ... s; 0: ``state`` itself): ``lambda^n S + sum_{j < n}
    lambda^{n - 1 - j} k_j v_j^T``, float32."""
    f32 = jnp.float32
    idx = jnp.arange(k.shape[0])
    slopes = jnp.asarray(slopes, f32)
    n = jnp.asarray(n, jnp.int32)
    weight = jnp.exp(jnp.where(
        idx[None, :] < n,
        -slopes[:, None] * (n - 1 - idx[None, :]).astype(f32), -jnp.inf,
    ))                                               # [heads, s]
    return (
        jnp.exp(-slopes * n.astype(f32))[:, None, None] * state
        + jnp.einsum(
            "jhk,jhv->hkv", k.astype(f32) * weight.T[:, :, None],
            v.astype(f32), precision=HIGHEST,
        )
    )


def lightning_step(q, k, v, state, slopes):
    """One row a sequence: ``q``, ``k``, ``v [b, heads, d]``, ``state
    [b, heads, d, d]`` float32 -> (``o [b, heads, d]`` float32, the new
    state): one rank-1 update and one read."""
    f32 = jnp.float32
    lam = jnp.exp(-jnp.asarray(slopes, f32))[None, :, None, None]
    new = lam * state + (
        k.astype(f32)[..., :, None] * v.astype(f32)[..., None, :]
    )
    return jnp.sum(new * q.astype(f32)[..., :, None], axis=-2), new


def lightning_out(config: LinearSparseLMConfig, pl, o, gate):
    """``o [b, s, heads, d]`` float32 -> the mixer's output ``[b, s,
    embed_dim]``: the norm over all heads' channels, the gate, ``W_o``."""
    b, s = o.shape[:2]
    flat = _norm(config, o.reshape(b, s, -1), pl["o_norm"])
    return _gate_and_project(config, flat, gate, pl["wo"])


def _gate_and_project(config: LinearSparseLMConfig, mix, gate, wo):
    """``mix [b, s, heads * hd]`` gated by ``sigmoid(gate)`` and through
    ``wo [heads, hd, d]``: (the gated mix, the mixer's output)."""
    cdt = config.compute_dtype
    b, s = mix.shape[:2]
    gated = (
        mix.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))
    ).astype(cdt)
    return gated, jnp.einsum(
        "bshk,hkd->bsd", gated.reshape(b, s, wo.shape[0], -1), wo.astype(cdt)
    )


# -- the sparse mixer ---------------------------------------------------------


def sparse_inputs(config: LinearSparseLMConfig, ps, u):
    """``q [b, s, heads, hd]``, ``k``, ``v [b, s, kv_heads, hd]`` (no
    norm, no rotation) and the gate before its sigmoid."""
    cdt = config.compute_dtype
    proj = lambda w: jnp.einsum(  # noqa: E731
        "bsd,dhk->bshk", u, w.astype(cdt)
    )
    gate = jnp.einsum("bsd,de->bse", u, ps["wg"].astype(cdt))
    return proj(ps["wq"]), proj(ps["wk"]), proj(ps["wv"]), gate


def compressed_keys(k_rows, stride: int):
    """``k_rows [(n + 1) * stride, ...]``, rows ``stride (p0 - 1) ...``
    of a sequence -> the ``n`` compressed keys at places ``p0 ... p0 + n
    - 1``, each the float32 mean of ``2 * stride`` rows, in ``k_rows``'
    dtype."""
    halves = jnp.mean(
        k_rows.astype(jnp.float32).reshape(
            (-1, stride) + k_rows.shape[1:]
        ), axis=1,
    )
    return ((halves[:-1] + halves[1:]) * 0.5).astype(k_rows.dtype)


def ckeys_visible(config: LinearSparseLMConfig, positions, n_places: int):
    """``[len(positions), n_places]`` bool: place ``p``'s last row
    (``stride (p + 1) - 1``) is at or below the query's position; place
    0 holds nothing."""
    p = jnp.arange(n_places)
    last = config.kernel_stride * (p + 1) - 1
    return (p[None, :] >= 1) & (last[None, :] <= positions[:, None])


def block_scores(config: LinearSparseLMConfig, q, ckeys, positions):
    """``q [t, heads, hd]`` at ``positions [t]`` over one sequence's
    compressed keys by place ``ckeys [places, kv_heads, hd]`` (``places``
    whole blocks' worth) -> ``B [kv_heads, t, blocks]`` float32: per
    head a softmax over the visible places, summed over the group's
    heads, max-pooled to the blocks a place's rows overlap. A block no
    visible place overlaps scores 0."""
    c, f32 = config, jnp.float32
    places, r = ckeys.shape[0], c.ckeys_per_block
    qg = q.reshape(q.shape[0], c.n_kv_heads, c.group, c.head_dim)
    scores = jnp.einsum(
        "tkgd,pkd->kgtp", qg, ckeys, preferred_element_type=f32
    ) * c.head_dim ** -0.5
    seen = ckeys_visible(c, positions, places)[None, None]
    scores = jnp.where(seen, scores, -jnp.inf)
    top = jnp.max(scores, axis=-1, keepdims=True)
    top = jnp.where(jnp.isfinite(top), top, 0.0)
    e = jnp.exp(scores - top)                         # 0 where unseen
    total = jnp.sum(e, axis=-1, keepdims=True)
    probs = jnp.sum(e / jnp.where(total > 0, total, 1.0), axis=1)
    own = probs.reshape(probs.shape[:2] + (places // r, r))
    nxt = jnp.pad(own[..., 1:, 0], ((0, 0), (0, 0), (0, 1)))
    return jnp.maximum(jnp.max(own, axis=-1), nxt)


def block_scores_forced(config: LinearSparseLMConfig, scores, positions):
    """``scores [kv_heads, t, blocks]`` with the forced blocks at +inf,
    and which blocks a query at ``positions [t]`` sees at all ``[t,
    blocks]``."""
    c = config
    b = jnp.arange(scores.shape[-1])[None, :]
    own = (positions // c.sparse_block)[:, None]
    visible = b <= own
    forced = visible & ((b < c.init_blocks) | (own - b < c.window_blocks))
    return jnp.where(forced[None], jnp.inf, scores), visible


def select_block_mask(config: LinearSparseLMConfig, scores, positions):
    """The selection as a mask ``[kv_heads, t, blocks]``: the ``topk``
    visible blocks of largest score with the forced ones first (ties to
    the lower block), every visible block where the query sees at most
    ``dense_len`` rows."""
    forced, visible = block_scores_forced(config, scores, positions)
    mask = sparse_ops.select_mask(
        forced, jnp.broadcast_to(visible[None], forced.shape), config.topk
    )
    dense = (positions + 1 <= config.dense_len)[None, :, None]
    return jnp.where(dense, visible[None], mask)


def select_block_list(config: LinearSparseLMConfig, scores, positions):
    """The selection as a LIST, for one query a row: ``scores [kv_heads,
    t, blocks]`` -> (``blocks [kv_heads, t, list_blocks]`` int32, the
    unused places at the end and holding ``blocks``' count, ``count
    [kv_heads, t]``). The query's OWN block is the last one counted (the
    only one of which it sees a part: what reads the list masks the rows
    past a length, so every other listed block is whole); the others
    come in no order that matters (the forced first, then by score)."""
    c = config
    n_blocks, width = scores.shape[-1], c.list_blocks
    forced, visible = block_scores_forced(c, scores, positions)
    own = (positions // c.sparse_block)[None, :, None]
    # The own block is forced: the others are the topk - 1 best of the
    # rest (one sort, no second one to put the own block last).
    others = visible[None] & (jnp.arange(n_blocks)[None, None, :] != own)
    k = min(max(c.topk - 1, 0), n_blocks)
    idx, valid = sparse_ops.select_indices(forced, others, k)
    n_others = jnp.sum(valid, axis=-1, dtype=jnp.int32)
    picked = jnp.pad(
        jnp.where(valid, idx, n_blocks),
        ((0, 0), (0, 0), (0, width - k)), constant_values=n_blocks,
    )
    picked = jnp.where(
        jnp.arange(width)[None, None, :] == n_others[..., None], own, picked
    )
    every = jnp.arange(width)[None, :]
    every = jnp.where(
        every <= (positions // c.sparse_block)[:, None], every, n_blocks
    )
    dense = (positions + 1 <= c.dense_len)[None, :, None]
    blocks = jnp.where(dense, every[None], picked).astype(jnp.int32)
    return blocks, jnp.sum(blocks < n_blocks, axis=-1, dtype=jnp.int32)


def definition_sparse_attention(config: LinearSparseLMConfig, q, k, v):
    """The sparse mixer over one whole sequence as written: ``q [s,
    heads, hd]``, ``k`` / ``v [s, kv_heads, hd]`` -> ``[s, heads, hd]``
    (float32 scores; dense attention under the block mask)."""
    c = config
    s = q.shape[0]
    stride, bs = c.kernel_stride, c.sparse_block
    n_blocks = -(-s // bs)
    rows = n_blocks * bs
    positions = jnp.arange(s)
    # place p <- rows [stride (p - 1), stride (p + 1)); place 0 is empty
    padded = jnp.pad(k, ((stride, rows + stride - s), (0, 0), (0, 0)))
    ckeys = compressed_keys(padded, stride)[:rows // stride]
    mask = select_block_mask(
        c, block_scores(c, q, ckeys, positions), positions
    )                                                # [kh, s, blocks]
    rows_seen = jnp.repeat(mask, bs, axis=-1)[..., :s]
    rows_seen = rows_seen & (positions[None, :] <= positions[:, None])[None]
    qg = q.reshape(s, c.n_kv_heads, c.group, c.head_dim)
    scores = jnp.einsum(
        "skgd,tkd->kgst", qg, k, preferred_element_type=jnp.float32
    ) * c.head_dim ** -0.5
    probs = jax.nn.softmax(
        jnp.where(rows_seen[:, None], scores, -jnp.inf), axis=-1
    )
    out = jnp.einsum(
        "kgst,tkd->skgd", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(q.shape)


def sparse_out(config: LinearSparseLMConfig, ps, attn, gate):
    """``attn [b, s, heads, hd]`` -> the mixer's output: the gate and
    ``W_o``."""
    b, s = attn.shape[:2]
    return _gate_and_project(config, attn.reshape(b, s, -1), gate, ps["wo"])


# -- the block and the layer loop ---------------------------------------------


def feed(config: LinearSparseLMConfig, params, layer: int, h):
    """Layer ``layer``'s SwiGLU on its normed input ``h [b, s, d]``."""
    pl = params["layers"]
    with jax.named_scope("mlp"):
        f = config.mlp_dim
        gu = jnp.einsum("bsd,df->bsf", h, pl["w_gu"][layer].astype(h.dtype))
        act = (jax.nn.silu(gu[..., :f]) * gu[..., f:]).astype(h.dtype)
        return jnp.einsum(
            "bsf,fd->bsd", act, pl["w_down"][layer].astype(h.dtype)
        )


def block(config: LinearSparseLMConfig, params, layer: int, x, positions,
          mixer, taps=None):
    """Decoder block ``layer`` over the residual ``x [b, s, d]``
    (float32). ``mixer``: for a lightning layer ``mix(q, k, v) -> o [b,
    s, heads, d]`` float32 (the caller holds the state); for a sparse
    layer ``attend(q, k, v) -> [b, s, heads, hd]``. Returns (``x``, the
    new rows' ``(k, v)``). ``taps``: a dict the block fills with what it
    otherwise keeps to itself (a check's probe reads them): ``x_in``,
    ``q``, ``mix`` (the mixer's output before the norm and the gate),
    ``gated`` (before ``W_o``), ``x_mid``, ``x_out``."""
    c, cdt = config, config.compute_dtype
    pl = params["layers"]
    scale = c.depth_scale
    x_in = x
    u = _norm(c, x, pl["mix_norm"][layer]).astype(cdt)
    at = c.index_in_kind(layer)
    if c.mixer_types[layer] == LIGHTNING:
        pm = jax.tree_util.tree_map(lambda a: a[at], params["lightning"])
        with jax.named_scope("attn"):
            q, k, v, gate = lightning_inputs(c, pm, u, positions)
            with jax.named_scope("lightning"):
                mix = mixer(q, k, v)
            gated, y = lightning_out(c, pm, mix, gate)
    else:
        pm = jax.tree_util.tree_map(lambda a: a[at], params["sparse"])
        with jax.named_scope("attn"):
            q, k, v, gate = sparse_inputs(c, pm, u)
            mix = mixer(q, k, v)
            gated, y = sparse_out(c, pm, mix, gate)
    x_mid = x + scale * y.astype(jnp.float32)
    h = _norm(c, x_mid, pl["ffn_norm"][layer]).astype(cdt)
    x = x_mid + scale * feed(c, params, layer, h).astype(jnp.float32)
    if taps is not None:
        taps.update(x_in=x_in, q=q, mix=mix, gated=gated, x_mid=x_mid,
                    x_out=x)
    return x, (k, v)


def embed(config: LinearSparseLMConfig, params, tokens):
    """The residual's start: ``scale_emb`` times the tokens' embeddings,
    float32."""
    rows = jnp.take(params["embed"], tokens, axis=0)
    return config.scale_emb * rows.astype(jnp.float32)


def unembed(config: LinearSparseLMConfig, params, x):
    """The final norm, the logit scale and the untied head: float32
    logits."""
    with jax.named_scope("vocab"):
        h = (
            _norm(config, x, params["final_norm"]) * config.logit_scale
        ).astype(config.compute_dtype)
        return jnp.einsum(
            "bsd,dv->bsv", h, params["head"].astype(config.compute_dtype)
        ).astype(jnp.float32)


def forward(config: LinearSparseLMConfig, params, tokens):
    """``tokens [b, s]`` -> float32 logits ``[b, s, vocab]``: the layers
    as the engine runs them, but over whole sequences from a zero state
    and with no cache (:func:`lightning_chunk` over the whole sequence,
    :func:`definition_sparse_attention`)."""
    c = config
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    d = c.lightning_head_dim
    zero = jnp.zeros((c.lightning_heads, d, d), jnp.float32)
    attend = jax.vmap(lambda *a: definition_sparse_attention(c, *a))
    x = embed(c, params, tokens)
    for layer, kind in enumerate(c.mixer_types):
        if kind == LIGHTNING:
            slopes = decay_slopes(c, layer)
            mixer = jax.vmap(
                lambda q, k, v: lightning_chunk(q, k, v, zero, slopes)
            )
        else:
            mixer = attend
        x, _ = block(c, params, layer, x, positions, mixer)
    return unembed(c, params, x)
