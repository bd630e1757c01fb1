"""Learned sparse attention: a light indexer scores every cached
position, the ``topk`` best are kept, and the main heads attend over
those rows alone (DeepSeek Sparse Attention's lightning indexer, here on
a GQA layer).

    I(t, s) = sum_j w[t, j] * relu(q_idx[t, j] . k_idx[s])   (x di^-1/2 hi^-1/2)
    S_t     = the topk positions s <= t of largest I(t, s), ties to the lower s
    o_t     = softmax_{s in S_t}(q_t . k_s / sqrt(d)) v_s

Plain ``jax.numpy``, no kernel. The selection is a THRESHOLD, not a
sort: the k-th largest score of a row is found by 32 bisection steps on
the scores' order-preserving integer keys (each a compare-and-count over
the row), and a row's selection is the mask ``score > threshold`` plus
as many of the ties at it as are missing, lowest positions first. A
prefill chunk keeps that mask and attends densely under it, since its
512 queries select 512 different sets. A decode step needs the rows
themselves, to gather them: there ``lax.top_k`` over the few query rows
gives the same set as indices (:func:`select_indices`). Below ``topk``
visible rows the selection is every visible row and both are plain
attention.
"""

import jax
import jax.numpy as jnp


def index_scores(q_idx, w, k_idx):
    """``q_idx [..., q, hi, di]``, ``w [..., q, hi]``, ``k_idx [..., s,
    di]`` -> ``[..., q, s]`` float32 index scores (no mask applied)."""
    hi, di = q_idx.shape[-2:]
    dots = jnp.einsum(
        "...qhd,...sd->...qhs", q_idx, k_idx,
        preferred_element_type=jnp.float32,
    )
    scores = jnp.einsum(
        "...qhs,...qh->...qs", jax.nn.relu(dots), w.astype(jnp.float32)
    )
    return scores * (di ** -0.5 * hi ** -0.5)


def _ordered_keys(scores):
    """float32 -> uint32 whose unsigned order is the floats' order
    (-0.0 read as 0.0: a row of dead relus must tie with itself)."""
    scores = jnp.where(scores == 0.0, 0.0, scores.astype(jnp.float32))
    bits = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    negative = (bits >> 31) == 1
    return jnp.where(negative, ~bits, bits | jnp.uint32(1 << 31))


def _kth_largest_key(keys, k: int):
    """Per row of ``keys [..., s]`` (uint32) the largest ``T`` with at
    least ``k`` keys ``>= T``: the k-th largest key, or 0 where the row
    has fewer than ``k`` keys above 0."""
    def bit(i, t):
        cand = t | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(
            keys >= cand[..., None], axis=-1, dtype=jnp.int32
        ) >= k
        return jnp.where(enough, cand, t)

    return jax.lax.fori_loop(
        0, 32, bit, jnp.zeros(keys.shape[:-1], jnp.uint32)
    )


def select_mask(scores, visible, topk: int):
    """The selection as a mask: ``scores [..., s]`` float32, ``visible
    [..., s]`` bool (``s <= t``) -> bool ``[..., s]`` with exactly
    ``min(topk, visible rows)`` True a row: the largest scores, ties at
    the threshold broken by lower position."""
    keys = jnp.where(visible, _ordered_keys(scores), jnp.uint32(0))
    thr = _kth_largest_key(keys, topk)[..., None]
    above = keys > thr
    tied = visible & (keys == thr)
    missing = topk - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    return above | (
        tied & (jnp.cumsum(tied, axis=-1, dtype=jnp.int32) <= missing)
    )


def select_indices(scores, visible, topk: int):
    """The selection as row indices, for one query a row: ``scores [...,
    s]``, ``visible [..., s]`` -> (``idx [..., topk]`` int32, ``valid
    [..., topk]``: a row with fewer than ``topk`` visible positions
    fills up with invisible ones, not valid). ``lax.top_k``: exact, and
    of equal scores the lower position comes first, which is
    :func:`select_mask`'s rule. On the v5e, 16 rows of 33,792 scores
    take it 0.70 ms; the threshold mask (0.34 ms) plus a running count
    and a binary search for each of the 2,048 places took 6.0 (PERF.md
    §6, PR 33)."""
    _, idx = jax.lax.top_k(jnp.where(visible, scores, -jnp.inf), topk)
    return idx.astype(jnp.int32), jnp.take_along_axis(visible, idx, axis=-1)


def gathered_attention(q, k_sel, v_sel, valid):
    """One query a row over its gathered rows: ``q [b, h, d]``, ``k_sel
    / v_sel [b, n, kh, d]``, ``valid [b, n]`` -> ``[b, h, d]``."""
    b, h, d = q.shape
    kh = k_sel.shape[2]
    qg = q.reshape(b, kh, h // kh, d)
    logits = jnp.einsum(
        "bkgd,bnkd->bkgn", qg, k_sel, preferred_element_type=jnp.float32
    ) * d ** -0.5
    logits = jnp.where(valid[:, None, None, :], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1).astype(v_sel.dtype)
    out = jnp.einsum(
        "bkgn,bnkd->bkgd", probs, v_sel,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, h, d).astype(q.dtype)


def masked_attention(q, k, v, mask):
    """Many queries over one sequence under a per-query mask: ``q [c,
    h, d]``, ``k / v [s, kh, d]``, ``mask [c, s]`` (every row has a True)
    -> ``[c, h, d]``. One KV head at a time, each head's keys SLICED out
    of ``k`` as stored: the live logits are ``[c * h / kh, s]`` float32
    and not all heads', and no operand asks for a head-major layout (a
    batched product over the heads made the compiler re-lay the whole
    pool the keys were gathered from: two 1.5 GB copies a chunk)."""
    c, h, d = q.shape
    kh = k.shape[1]
    qh = q.reshape(c, kh, h // kh, d)
    outs = []
    for j in range(kh):
        logits = jnp.einsum(
            "cgd,sd->cgs", qh[:, j], k[:, j],
            preferred_element_type=jnp.float32,
        ) * d ** -0.5
        logits = jnp.where(mask[:, None, :], logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        outs.append(jnp.einsum(
            "cgs,sd->cgd", probs, v[:, j],
            preferred_element_type=jnp.float32,
        ))
    return jnp.stack(outs, axis=1).reshape(c, h, d).astype(q.dtype)
