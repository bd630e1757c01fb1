"""Attention ops (XLA path).

Layout convention throughout the framework: [batch, seq, heads, head_dim]
("BSHD"). GQA is supported by ``kv_heads <= heads``; KV heads are
broadcast by reshape, never materialized ``heads/kv_heads`` times — XLA
keeps the broadcast virtual inside the einsum.

The Pallas flash kernel (ops/pallas_attention.py) and the ring-attention
shard_map island (ops/ring_attention.py) share this op's semantics; tests
cross-check all three.
"""

from typing import Optional

import jax.numpy as jnp

NEG_INF = -2.0 ** 30  # large-but-finite: avoids NaN from (-inf) - (-inf)


def dot_product_attention(
    q,
    k,
    v,
    causal: bool = True,
    q_positions=None,
    kv_positions=None,
    softmax_scale: Optional[float] = None,
):
    """Multi-head attention with optional GQA and causal masking.

    q: [b, sq, h, d]; k, v: [b, skv, hkv, d]. Positions (global token
    indices, shape [sq]/[skv] or per-row [b, sq]/[b, skv]) drive the
    causal mask, so sequence-parallel / packed callers pass the true
    offsets of their shards. Query rows with no visible key (a shard
    entirely in the future) produce exactly zero output, which is what
    ring attention's combine step requires.
    """
    b, sq, h, d = q.shape
    _, skv, hkv, _ = k.shape
    if h % hkv:
        raise ValueError(f"heads {h} not a multiple of kv_heads {hkv}")
    groups = h // hkv
    scale = softmax_scale if softmax_scale is not None else d ** -0.5

    q32 = (q * scale).astype(jnp.float32)
    qg = q32.reshape(b, sq, hkv, groups, d)
    # [b, hkv, g, sq, skv]
    logits = jnp.einsum(
        "bqkgd,bskd->bkgqs", qg, k.astype(jnp.float32)
    )
    if causal:
        if q_positions is None:
            q_positions = jnp.arange(sq)
        if kv_positions is None:
            kv_positions = jnp.arange(skv)
        q_pos = jnp.broadcast_to(q_positions, (b, sq))
        kv_pos = jnp.broadcast_to(kv_positions, (b, skv))
        mask = q_pos[:, :, None] >= kv_pos[:, None, :]  # [b, sq, skv]
        logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    row_max = jnp.max(logits, axis=-1, keepdims=True)
    probs = jnp.exp(logits - row_max)
    probs = probs / (jnp.sum(probs, axis=-1, keepdims=True) + 1e-30)
    # fully-masked rows (row_max still at NEG_INF) must contribute zero,
    # not a uniform average of the illegal keys
    probs = jnp.where(row_max > NEG_INF / 2, probs, 0.0)
    out = jnp.einsum(
        "bkgqs,bskd->bqkgd", probs, v.astype(jnp.float32)
    )
    return out.reshape(b, sq, h, v.shape[-1]).astype(q.dtype)
