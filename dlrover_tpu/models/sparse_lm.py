"""A decoder whose attention reads a learned selection of its cache and
whose every MLP is an expert layer: GQA main heads with per-head q/k
RMSNorm and rotary positions, an indexer beside them (``index_heads`` x
``index_dim`` index queries, ONE index key a token, a per-head weight)
whose scores pick the ``index_topk`` cached rows a query attends over
(``ops/sparse_attention.py``), and ``n_experts`` dropless experts,
softmax-routed top-k with renormalised weights, no shared expert
(``moe.softmax_route`` + ``moe.routed_experts``).

The model is SERVED: ``PagedServingEngine`` takes this config next to
``TpuLMConfig`` and builds its decode and prefill programs from
:func:`attention_inputs`, ``llama.attention_out`` and :func:`expert_mlp`
(``serving/kvpool/sparse.py``); the pool keeps the index keys as a third
per-token array. :func:`forward` is the same layer over a whole
sequence with no cache, what the engine's logits are held to in the
package's tests. Nothing here trains it: how the indexer is trained is
not part of the published forward pass.

Parameters are stored the way a decode step reads them, projections
that share an input side by side: ``wqkv [L, d, h + 2 kh, hd]``,
``w_idx [L, d, hi * di + di + hi]`` (index queries | index key | head
weights) and ``w_gu [L * E, d, 2 f]`` (gate | up), ``w_down [L * E, f,
d]``: the experts of all layers in ONE stack of groups, expert ``e`` of
layer ``l`` at ``l * E + e``, because the grouped matmul reads its
layer's experts out of that stack in place (:func:`expert_mlp`). There
is no second layout to fuse from.
"""

import dataclasses
import math
from typing import ClassVar

import jax
import jax.numpy as jnp

from dlrover_tpu.models import llama
from dlrover_tpu.models import moe as moe_lib
from dlrover_tpu.ops import sparse_attention as sa
from dlrover_tpu.ops.norms import rms_norm
from dlrover_tpu.ops.rope import apply_rope

@dataclasses.dataclass(frozen=True)
class SparseLMConfig:
    kind: ClassVar[str] = "sparse_lm"    # models.model_for: which module
    vocab_size: int = 151936
    embed_dim: int = 2048
    n_layers: int = 48
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    mlp_dim: int = 768               # one expert's width
    n_experts: int = 128
    moe_top_k: int = 8
    index_heads: int = 16
    index_dim: int = 64
    index_topk: int = 2048
    rope_theta: float = 1e7
    dtype: str = "bfloat16"
    pp_stages: int = 1               # the engines ask; never staged

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def index_width(self) -> int:
        """Columns of ``w_idx``: index queries, the index key, weights."""
        return self.index_heads * self.index_dim + self.index_dim \
            + self.index_heads

    @property
    def cache_rows(self):
        """What a token leaves in the cache, a layer: name -> shape (the
        pool's statement, ``serving/kvpool/layout.py``)."""
        head = (self.n_kv_heads, self.head_dim)
        return (("k", head), ("v", head), ("index_keys", (self.index_dim,)))

    def count_params(self) -> int:
        d, hd, f = self.embed_dim, self.head_dim, self.mlp_dim
        attn = d * (self.n_heads + 2 * self.n_kv_heads) * hd
        attn += self.n_heads * hd * d + 2 * hd
        index = d * self.index_width + 2 * self.index_dim
        experts = self.n_experts * 3 * d * f + d * self.n_experts
        return (
            self.n_layers * (attn + index + experts + 2 * d)
            + 2 * self.vocab_size * d + d
        )


def tiny_config(**overrides) -> SparseLMConfig:
    """Small enough for a CPU test, selection active past 8 rows."""
    kw = dict(
        vocab_size=96, embed_dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=8, mlp_dim=16, n_experts=8, moe_top_k=2, index_heads=2,
        index_dim=8, index_topk=8, rope_theta=1e4, dtype="float32",
    )
    kw.update(overrides)
    return SparseLMConfig(**kw)


def init_params(config: SparseLMConfig, rng: jax.Array, dtype=None):
    """Seeded weights, normal(0, 1/sqrt(fan_in)); norm scales zero (the
    ``1 + scale`` form). ``dtype``: what the matmul leaves are made in
    (float32 when None; a server passes its compute dtype, so that the
    float32 tree never exists); norm scales and the router stay
    float32, as ``generate.prepare_decode_params`` keeps them."""
    c = config
    d, hd, f, L = c.embed_dim, c.head_dim, c.mlp_dim, c.n_layers
    dtype = jnp.dtype(dtype or jnp.float32)
    keys = jax.random.split(rng, 8)

    def dense(key, shape, fan_in, to=dtype):
        w = jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)
        return w.astype(to)

    layers = {
        "attn_norm": jnp.zeros((L, d), jnp.float32),
        "wqkv": dense(keys[0], (L, d, c.n_heads + 2 * c.n_kv_heads, hd), d),
        "q_norm": jnp.zeros((L, hd), jnp.float32),
        "k_norm": jnp.zeros((L, hd), jnp.float32),
        "wo": dense(keys[1], (L, c.n_heads, hd, d), c.n_heads * hd),
        "w_idx": dense(keys[2], (L, d, c.index_width), d),
        "ik_norm_scale": jnp.ones((L, c.index_dim), jnp.float32),
        "ik_norm_bias": jnp.zeros((L, c.index_dim), jnp.float32),
        "mlp_norm": jnp.zeros((L, d), jnp.float32),
        "router": dense(keys[3], (L, d, c.n_experts), d, jnp.float32),
        "w_gu": dense(keys[4], (L * c.n_experts, d, 2 * f), d),
        "w_down": dense(keys[5], (L * c.n_experts, f, d), f),
    }
    return {
        "embed": dense(keys[6], (c.vocab_size, d), 1.0),
        "layers": layers,
        "final_norm": jnp.zeros((d,), jnp.float32),
        "lm_head": dense(keys[7], (d, c.vocab_size), d),
    }


def _layer_norm(x, scale, bias, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    return (x32 - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def attention_inputs(config: SparseLMConfig, p, x, positions):
    """Norm, the two fused projections, q/k norms and rotations: ``x
    [b, s, d]`` -> main ``q [b, s, h, hd]``, ``k`` / ``v [b, s, kh,
    hd]`` and the indexer's ``q_idx [b, s, hi, di]``, ``k_idx [b, s,
    di]``, ``w [b, s, hi]``."""
    c, cdt = config, config.compute_dtype
    h, kh, hi, di = c.n_heads, c.n_kv_heads, c.index_heads, c.index_dim
    hx = rms_norm(x, p["attn_norm"]).astype(cdt)
    qkv = jnp.einsum("bsd,dhk->bshk", hx, p["wqkv"].astype(cdt))
    q = rms_norm(qkv[:, :, :h], p["q_norm"])
    k = rms_norm(qkv[:, :, h:h + kh], p["k_norm"])
    v = qkv[:, :, h + kh:]
    q = apply_rope(q, positions, c.rope_theta)
    k = apply_rope(k, positions, c.rope_theta)
    with jax.named_scope("index"):
        idx = jnp.einsum("bsd,dn->bsn", hx, p["w_idx"].astype(cdt))
        q_idx = idx[..., :hi * di].reshape(idx.shape[:2] + (hi, di))
        k_idx = _layer_norm(
            idx[..., hi * di:hi * di + di], p["ik_norm_scale"],
            p["ik_norm_bias"],
        ).astype(cdt)
        w = idx[..., hi * di + di:]
        q_idx = apply_rope(q_idx, positions, c.rope_theta)
        k_idx = apply_rope(k_idx[:, :, None], positions, c.rope_theta)[:, :, 0]
    return q, k, v, q_idx, k_idx, w


EXPERT_WEIGHTS = ("w_gu", "w_down")


def layer_params(config: SparseLMConfig, params, i: int):
    """Layer ``i``'s own leaves, its experts cut out of the stack."""
    e = config.n_experts
    return {
        name: (
            leaf[i * e:(i + 1) * e] if name in EXPERT_WEIGHTS else leaf[i]
        )
        for name, leaf in params["layers"].items()
    }


def scan_layers(config: SparseLMConfig, params, body, x):
    """``lax.scan`` of ``body(x, (layer's leaves, layer index))`` over
    the layers. The expert weights stay out of the scanned inputs: the
    body hands :func:`expert_mlp` the whole stack and its index."""
    scanned = {
        name: leaf for name, leaf in params["layers"].items()
        if name not in EXPERT_WEIGHTS
    }
    return jax.lax.scan(
        body, x, (scanned, jnp.arange(config.n_layers, dtype=jnp.int32))
    )


def expert_mlp(config: SparseLMConfig, p, x, layers=None, layer=0):
    """The expert layer with its residual: ``x [b, s, d]`` -> (``x``,
    :class:`moe.ShareCounters`). Every expert is held, nothing has a
    capacity: a token's output does not depend on what else is in the
    call.

    ``layers``: the tree's ``layers`` with ALL layers' expert weights,
    and ``layer`` which one this is (traced in a layer loop), in place
    of expert weights of ``p``'s own: the grouped matmul runs over ``L *
    E`` groups of which only this layer's have rows. A layer loop that
    hands each layer its slice of the weights makes the compiler copy
    the slice out before the kernel can read it (1.2 GB a layer here:
    18 ms of either serving program on the chip; PERF.md §6, PR 33)."""
    c = config
    if layers is None:
        layers, layer = p, 0
    with jax.named_scope("mlp"):
        hx = rms_norm(x, p["mlp_norm"]).astype(c.compute_dtype)
        with jax.named_scope("router"):
            experts, weights = moe_lib.softmax_route(
                hx.reshape(-1, hx.shape[-1]), p["router"], c.moe_top_k
            )
        out, counters = moe_lib.routed_experts(
            hx, experts, weights, layers["w_gu"], layers["w_down"],
            c.n_experts, group_offset=layer * c.n_experts,
        )
        return x + out.astype(x.dtype), counters


def sparse_self_attention(config: SparseLMConfig, q, k, v, q_idx, k_idx, w):
    """One sequence, no cache: ``q [s, h, hd]`` ... -> ``[s, h, hd]``
    (score, select under the causal mask, attend under the selection)."""
    s = q.shape[0]
    with jax.named_scope("index"):
        scores = sa.index_scores(q_idx, w, k_idx)
    with jax.named_scope("select"):
        causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
        mask = sa.select_mask(scores, causal, min(config.index_topk, s))
    with jax.named_scope("sparse"):
        return sa.masked_attention(q, k, v, mask)


def forward(config: SparseLMConfig, params, tokens):
    """``tokens [b, s]`` -> float32 logits ``[b, s, vocab]`` and the
    expert rows dropped (0), the layer as the engines run it but over
    whole sequences."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x = llama.embed_tokens(config, params, tokens)

    def layer(x, layer_in):
        p, i = layer_in
        residual = x
        with jax.named_scope("attn"):
            q, k, v, q_idx, k_idx, w = attention_inputs(
                config, p, x, positions
            )
            attn = jax.vmap(
                lambda *a: sparse_self_attention(config, *a)
            )(q, k, v, q_idx, k_idx, w)
            x = llama.attention_out(config, p, attn, residual)
        x, counters = expert_mlp(config, p, x, params["layers"], i)
        return x, counters.rows_dropped

    x, dropped = scan_layers(config, params, layer, x)
    return llama.unembed(config, params, x), jnp.sum(dropped)
