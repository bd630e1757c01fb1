"""Ring attention: exact causal attention over a sequence-parallel mesh
axis (long-context path).

A ``shard_map`` island inside the jitted program: Q/K/V are sharded on
the ``sp`` mesh axis along sequence; each device computes blockwise
attention of its local queries against the K/V block it currently holds,
accumulating with an online (flash-style) softmax, then rotates K/V one
hop around the ``sp`` ring via ``ppermute`` — compute and ICI transfer
overlap, HBM never holds the full sequence. Position-based causal
masking makes the result exact for any block arrival order.

This is the long-context capability the reference lacks entirely
(SURVEY.md §2.9: EP/CP/ring attention "absent"); the reference's
DeepSpeed-SP awareness (docs/design/elastic.md:23-29) stops at
checkpoint/rendezvous metadata.
"""

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from dlrover_tpu.ops.attention import NEG_INF
from dlrover_tpu.parallel.sharding import DEFAULT_RULES, logical_to_spec


def _block_attn(q, k, v, q_pos, kv_pos, causal, scale):
    """Partial attention of q against one K/V block.

    q: [b, sq, h, d]; k/v: [b, skv, hkv, d]. Returns (o, m, l) where
    o = sum(exp(logits - m) @ v), m = rowwise max logits, l = rowwise
    sum exp — the flash-attention partial triple, f32.

    Matmuls keep the input dtype (bf16 = full-rate MXU) and accumulate
    in f32; softmax math runs on the f32 logits with the scale applied
    there, so bf16 inputs lose nothing to a pre-scaled q.
    """
    b, sq, h, d = q.shape
    _, skv, hkv, _ = k.shape
    groups = h // hkv
    qg = q.reshape(b, sq, hkv, groups, d)
    logits = (
        jnp.einsum(
            "bqkgd,bskd->bkgqs",
            qg,
            k,
            preferred_element_type=jnp.float32,
        )
        * scale
    )
    if causal:
        mask = q_pos[:, :, None] >= kv_pos[:, None, :]  # [b, sq, skv]
        logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    m = jnp.max(logits, axis=-1)                        # [b, hkv, g, sq]
    p = jnp.exp(logits - m[..., None])
    p = jnp.where((m > NEG_INF / 2)[..., None], p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum(
        "bkgqs,bskd->bkgqd",
        p.astype(v.dtype),
        v,
        preferred_element_type=jnp.float32,
    ).astype(jnp.float32)
    return o, m, l


def _ring_overlap() -> bool:
    """Collective/compute overlap schedule (default on): each hop
    ISSUES the next chunk's ppermute before running the current
    chunk's attention block, so the collective-permute-start flows
    into the scheduler ahead of the matmuls it must hide behind, and
    the final hop elides the wasted wrap-around K/V permute entirely
    (n-1 rotations instead of n). DLROVER_TPU_RING_OVERLAP=0 restores
    the legacy compute-then-permute order for the bench A/B."""
    from dlrover_tpu.common.env_utils import get_env_bool

    return get_env_bool("DLROVER_TPU_RING_OVERLAP", True)


def ring_attention_local(
    q,
    k,
    v,
    q_positions,
    kv_positions,
    axis_name: str = "sp",
    causal: bool = True,
    softmax_scale: Optional[float] = None,
):
    """Per-shard body (call under shard_map). Shapes are LOCAL:
    q [b, sq_loc, h, d]; k/v [b, skv_loc, hkv, d]; positions are the
    GLOBAL token indices of the local rows ([b, sq_loc]/[b, skv_loc]).
    """
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    groups = h // hkv
    n = jax.lax.axis_size(axis_name)
    scale = softmax_scale if softmax_scale is not None else d ** -0.5

    o0 = jnp.zeros((b, hkv, groups, sq, d), jnp.float32)
    m0 = jnp.full((b, hkv, groups, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, groups, sq), jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def block_merge(o, m, l, k_cur, v_cur, kv_pos):
        bo, bm, bl = _block_attn(
            q, k_cur, v_cur, q_positions, kv_pos, causal, scale
        )
        m_new = jnp.maximum(m, bm)
        corr = jnp.exp(m - m_new)
        bcorr = jnp.exp(bm - m_new)
        o = o * corr[..., None] + bo * bcorr[..., None]
        l = l * corr + bl * bcorr
        return o, m_new, l

    if _ring_overlap():
        def step(i, carry):
            o, m, l, k_cur, v_cur, kv_pos = carry
            # Next chunk's rotation is issued BEFORE this chunk's
            # attention block: the permute depends only on the carry,
            # so its transfer hides behind the block's matmuls.
            k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
            v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
            p_nxt = jax.lax.ppermute(kv_pos, axis_name, perm)
            o, m, l = block_merge(o, m, l, k_cur, v_cur, kv_pos)
            return (o, m, l, k_nxt, v_nxt, p_nxt)

        o, m, l, k_l, v_l, p_l = jax.lax.fori_loop(
            0, n - 1, step, (o0, m0, l0, k, v, kv_positions)
        )
        # Final chunk: compute only — the wrap-around permute that the
        # legacy schedule paid (result discarded) is gone.
        o, m, l = block_merge(o, m, l, k_l, v_l, p_l)
    else:
        def step(i, carry):
            o, m, l, k_cur, v_cur, kv_pos = carry
            o, m, l = block_merge(o, m, l, k_cur, v_cur, kv_pos)
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
            kv_pos = jax.lax.ppermute(kv_pos, axis_name, perm)
            return (o, m, l, k_cur, v_cur, kv_pos)

        o, m, l, _, _, _ = jax.lax.fori_loop(
            0, n, step, (o0, m0, l0, k, v, kv_positions)
        )
    out = o / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.where((m > NEG_INF / 2)[..., None], out, 0.0)
    # [b, hkv, g, sq, d] -> [b, sq, h, d]
    out = jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(b, sq, h, d)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas ring attention: the flash kernel as the per-hop inner block
# ---------------------------------------------------------------------------
#
# The XLA path above materializes the full local [sq_loc, skv_loc] logits
# tensor on every ring hop — exactly the memory/bandwidth cost flash
# attention kills. This path instead calls the fused Pallas kernels
# (ops/pallas_attention.py) per hop and merges the (out, lse) partials:
#
# - forward: out_global = sum_b exp(lse_b - lse_global) * out_b, with
#   lse_global accumulated stably across hops;
# - backward (ring-level custom VJP): p_ij = exp(s_ij - lse_global)
#   globally, so each hop's (dq, dk, dv) is one flash-backward call fed
#   the FINAL lse and the global delta = rowsum(do * out); dk/dv
#   accumulators rotate around the ring alongside k/v and are home after
#   n hops.
#
# Requires each sp shard to hold a CONTIGUOUS chunk of the sequence (the
# layout make_ring_attention's shard_map produces): the per-hop causal
# relation then collapses to three static cases — fully-past block (no
# mask), diagonal block (relative causal mask), fully-future block
# (skipped) — so the kernels never need absolute positions.


def _flash_block(q, k, v, causal, scale):
    """One ring hop through the Pallas forward. Returns (out [b,sq,h,d]
    in q.dtype, lse [b, h, sq] f32)."""
    from dlrover_tpu.ops.pallas_attention import flash_forward_local

    interpret = jax.default_backend() != "tpu"
    return flash_forward_local(q, k, v, causal, scale, interpret)


def _merge(o, lse, out_b, lse_b):
    """Merge a block partial into the running (o f32 [b,sq,h,d],
    lse f32 [b,h,sq]) accumulator."""
    m = jnp.maximum(lse, lse_b)
    lse_new = m + jnp.log(jnp.exp(lse - m) + jnp.exp(lse_b - m))
    w_old = jnp.exp(lse - lse_new).transpose(0, 2, 1)[..., None]
    w_new = jnp.exp(lse_b - lse_new).transpose(0, 2, 1)[..., None]
    o = o * w_old + out_b.astype(jnp.float32) * w_new
    return o, lse_new


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def ring_flash_attention_local(
    q, k, v, q_positions, kv_positions,
    axis_name: str = "sp",
    causal: bool = True,
    softmax_scale: Optional[float] = None,
):
    out, _ = _ring_flash_fwd(
        q, k, v, q_positions, kv_positions, axis_name, causal,
        softmax_scale,
    )
    return out


def _contiguity_poison(q_pos, kv_pos):
    """NaN unless positions are what the pallas path assumes: every batch
    row identical and contiguous within the shard (the layout
    make_ring_attention's shard_map produces from global iota positions).
    Packed/per-batch positions then fail LOUDLY (NaN loss on step one)
    instead of training on silently wrong causal masks — such callers
    must use impl="xla"."""
    sq = q_pos.shape[1]
    skv = kv_pos.shape[1]
    ok_q = jnp.all(
        q_pos == q_pos[0, 0] + jnp.arange(sq, dtype=q_pos.dtype)[None, :]
    )
    ok_kv = jnp.all(
        kv_pos
        == kv_pos[0, 0] + jnp.arange(skv, dtype=kv_pos.dtype)[None, :]
    )
    return jnp.where(ok_q & ok_kv, 0.0, jnp.nan).astype(jnp.float32)


def _ring_flash_fwd(q, k, v, q_pos, kv_pos, axis_name, causal, scale):
    b, sq, h, d = q.shape
    n = jax.lax.axis_size(axis_name)
    scale = scale if scale is not None else d ** -0.5
    q_off = q_pos[0, 0]
    perm = [(i, (i + 1) % n) for i in range(n)]

    def skip():
        return (
            jnp.zeros((b, sq, h, d), q.dtype),
            jnp.full((b, h, sq), NEG_INF, jnp.float32),
        )

    def block_merge(o, lse, k_cur, v_cur, kvp):
        kv_off = kvp[0, 0]
        if causal:
            out_b, lse_b = jax.lax.cond(
                kv_off > q_off,
                skip,
                lambda: jax.lax.cond(
                    kv_off == q_off,
                    lambda: _flash_block(q, k_cur, v_cur, True, scale),
                    lambda: _flash_block(q, k_cur, v_cur, False, scale),
                ),
            )
        else:
            out_b, lse_b = _flash_block(q, k_cur, v_cur, False, scale)
        return _merge(o, lse, out_b, lse_b)

    o0 = jnp.zeros((b, sq, h, d), jnp.float32)
    lse0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    if _ring_overlap():
        def hop(i, carry):
            o, lse, k_cur, v_cur, kvp = carry
            # Rotation first: the ppermute-start is in flight while the
            # flash kernel chews the chunk it already holds (§33).
            k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
            v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
            kvp_nxt = jax.lax.ppermute(kvp, axis_name, perm)
            o, lse = block_merge(o, lse, k_cur, v_cur, kvp)
            return (o, lse, k_nxt, v_nxt, kvp_nxt)

        o, lse, k_l, v_l, kvp_l = jax.lax.fori_loop(
            0, n - 1, hop, (o0, lse0, k, v, kv_pos)
        )
        o, lse = block_merge(o, lse, k_l, v_l, kvp_l)
    else:
        def hop(i, carry):
            o, lse, k_cur, v_cur, kvp = carry
            o, lse = block_merge(o, lse, k_cur, v_cur, kvp)
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
            kvp = jax.lax.ppermute(kvp, axis_name, perm)
            return (o, lse, k_cur, v_cur, kvp)

        o, lse, _, _, _ = jax.lax.fori_loop(
            0, n, hop, (o0, lse0, k, v, kv_pos)
        )
    if causal:
        # Only causal masking consults positions; bidirectional ring
        # attention is position-free and needs no guard.
        o = o + _contiguity_poison(q_pos, kv_pos)
    return o.astype(q.dtype), lse


def _ring_fwd_rule(q, k, v, q_pos, kv_pos, axis_name, causal, scale):
    out, lse = _ring_flash_fwd(
        q, k, v, q_pos, kv_pos, axis_name, causal, scale
    )
    return out, (q, k, v, q_pos, kv_pos, out, lse)


def _ring_bwd_rule(axis_name, causal, scale, res, g):
    from dlrover_tpu.ops.pallas_attention import (
        LANES,
        flash_backward_T,
        flash_backward_delta,
    )

    q, k, v, q_pos, kv_pos, out, lse = res
    b, sq, h, d = q.shape
    n = jax.lax.axis_size(axis_name)
    scale_v = scale if scale is not None else d ** -0.5
    interpret = jax.default_backend() != "tpu"
    q_off = q_pos[0, 0]
    perm = [(i, (i + 1) % n) for i in range(n)]
    # Loop invariants, hoisted: final lse + global delta (from the FINAL
    # out/do — with p_ij = exp(s_ij - lse_final), each hop's grads are
    # exact partials of the global softmax), and the [b, h, s, d]
    # transposes the backward kernels want. k/v rotate around the ring
    # already transposed so no per-hop transpose remains.
    lse_lane = jnp.broadcast_to(
        lse.reshape(b * h, sq)[:, :, None], (b * h, sq, LANES)
    )
    di = flash_backward_delta(g, out)
    qT = q.transpose(0, 2, 1, 3)
    doT = g.transpose(0, 2, 1, 3)
    kT0 = k.transpose(0, 2, 1, 3)
    vT0 = v.transpose(0, 2, 1, 3)

    def skip(kT_cur, vT_cur):
        return (
            jnp.zeros_like(qT),
            jnp.zeros_like(kT_cur),
            jnp.zeros_like(vT_cur),
        )

    def block_grads(dqT, dkT_acc, dvT_acc, kT_cur, vT_cur, kvp):
        kv_off = kvp[0, 0]

        def run(causal_blk):
            return lambda: flash_backward_T(
                qT, kT_cur, vT_cur, doT, lse_lane, di, causal_blk,
                scale_v, interpret,
            )

        if causal:
            dqb, dkb, dvb = jax.lax.cond(
                kv_off > q_off,
                lambda: skip(kT_cur, vT_cur),
                lambda: jax.lax.cond(
                    kv_off == q_off, run(True), run(False)
                ),
            )
        else:
            dqb, dkb, dvb = run(False)()
        return (
            dqT + dqb.astype(jnp.float32),
            dkT_acc + dkb.astype(jnp.float32),
            dvT_acc + dvb.astype(jnp.float32),
        )

    dq0 = jnp.zeros(qT.shape, jnp.float32)
    dk0 = jnp.zeros(kT0.shape, jnp.float32)
    dv0 = jnp.zeros(vT0.shape, jnp.float32)
    if _ring_overlap():
        def hop(i, carry):
            dqT, dkT_acc, dvT_acc, kT_cur, vT_cur, kvp = carry
            # K/V rotation issued BEFORE the backward kernels (depends
            # only on the carry — hides behind the block compute). The
            # dk/dv accumulators can only move AFTER this hop's adds:
            # they ride the ring with the chunk, n permutes total, so
            # each shard's accumulated gradient lands back home.
            kT_nxt = jax.lax.ppermute(kT_cur, axis_name, perm)
            vT_nxt = jax.lax.ppermute(vT_cur, axis_name, perm)
            kvp_nxt = jax.lax.ppermute(kvp, axis_name, perm)
            dqT, dkT_acc, dvT_acc = block_grads(
                dqT, dkT_acc, dvT_acc, kT_cur, vT_cur, kvp
            )
            dkT_acc = jax.lax.ppermute(dkT_acc, axis_name, perm)
            dvT_acc = jax.lax.ppermute(dvT_acc, axis_name, perm)
            return (dqT, dkT_acc, dvT_acc, kT_nxt, vT_nxt, kvp_nxt)

        dqT, dkT, dvT, kT_l, vT_l, kvp_l = jax.lax.fori_loop(
            0, n - 1, hop, (dq0, dk0, dv0, kT0, vT0, kv_pos)
        )
        # Final chunk: grads computed without the wasted K/V rotation;
        # the accumulators take their n-th hop home.
        dqT, dkT, dvT = block_grads(dqT, dkT, dvT, kT_l, vT_l, kvp_l)
        dkT = jax.lax.ppermute(dkT, axis_name, perm)
        dvT = jax.lax.ppermute(dvT, axis_name, perm)
    else:
        def hop(i, carry):
            dqT, dkT_acc, dvT_acc, kT_cur, vT_cur, kvp = carry
            dqT, dkT_acc, dvT_acc = block_grads(
                dqT, dkT_acc, dvT_acc, kT_cur, vT_cur, kvp
            )
            # dk/dv accumulators ride the ring WITH k/v: after n hops
            # each shard's accumulated gradient is back on the shard
            # that owns it.
            kT_cur = jax.lax.ppermute(kT_cur, axis_name, perm)
            vT_cur = jax.lax.ppermute(vT_cur, axis_name, perm)
            kvp = jax.lax.ppermute(kvp, axis_name, perm)
            dkT_acc = jax.lax.ppermute(dkT_acc, axis_name, perm)
            dvT_acc = jax.lax.ppermute(dvT_acc, axis_name, perm)
            return (dqT, dkT_acc, dvT_acc, kT_cur, vT_cur, kvp)

        dqT, dkT, dvT, _, _, _ = jax.lax.fori_loop(
            0, n, hop, (dq0, dk0, dv0, kT0, vT0, kv_pos)
        )
    return (
        dqT.transpose(0, 2, 1, 3).astype(q.dtype),
        dkT.transpose(0, 2, 1, 3).astype(k.dtype),
        dvT.transpose(0, 2, 1, 3).astype(v.dtype),
        np.zeros(q_pos.shape, jax.dtypes.float0),
        np.zeros(kv_pos.shape, jax.dtypes.float0),
    )


ring_flash_attention_local.defvjp(_ring_fwd_rule, _ring_bwd_rule)


def _ring_impl(impl: Optional[str]) -> str:
    """pallas (flash inner block) on TPU, xla elsewhere; DLROVER_TPU_RING
    overrides. The pallas path assumes each sp shard holds a contiguous
    chunk of the sequence — callers with packed/arbitrary positions must
    pass impl="xla"."""
    if impl is None:
        impl = os.environ.get("DLROVER_TPU_RING", "auto")
    impl = impl.lower()
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl not in ("pallas", "xla"):
        raise ValueError(
            f"ring attention impl {impl!r} not in ('auto', 'pallas', "
            f"'xla') — refusing to silently fall back"
        )
    return impl


def make_ring_attention(
    mesh: Mesh,
    rules=DEFAULT_RULES,
    axis_name="sp",
    impl: Optional[str] = None,
):
    """Returns an ``attention_fn`` drop-in for ``dot_product_attention``
    that runs ring attention along ``axis_name`` via a shard_map island.
    Plug into ``llama.forward(..., attention_fn=...)``.
    """
    q_spec = logical_to_spec(("batch", "seq", "heads", "head_dim"), rules)
    kv_spec = logical_to_spec(("batch", "seq", "kv_heads", "head_dim"), rules)
    pos_spec = logical_to_spec(("batch", "seq"), rules)
    impl = _ring_impl(impl)
    local_fn = (
        ring_flash_attention_local
        if impl == "pallas"
        else ring_attention_local
    )

    def attention_fn(
        q, k, v, causal=True, q_positions=None, kv_positions=None,
        softmax_scale=None,
    ):
        b, sq = q.shape[0], q.shape[1]
        skv = k.shape[1]
        if q_positions is None:
            q_positions = jnp.broadcast_to(jnp.arange(sq), (b, sq))
        if kv_positions is None:
            kv_positions = jnp.broadcast_to(jnp.arange(skv), (b, skv))
        q_positions = jnp.broadcast_to(q_positions, (b, sq))
        kv_positions = jnp.broadcast_to(kv_positions, (b, skv))

        # Positional call: custom_vjp functions reject keyword args for
        # nondiff parameters.
        def body(q, k, v, qp, kp):
            return local_fn(
                q, k, v, qp, kp, axis_name, causal, softmax_scale
            )

        # check_vma off: the ring's manual collectives (ppermute hops)
        # carry no replication annotations.
        return jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(q_spec, kv_spec, kv_spec, pos_spec, pos_spec),
            out_specs=q_spec,
            check_vma=False,
        )(q, k, v, q_positions, kv_positions)

    # The pallas path's ring-level custom VJP keeps O(s*d) residuals
    # (q/k/v/out + lse), so mlp_only remat may exempt it (llama.py).
    attention_fn.saveable_residuals = impl == "pallas"
    return attention_fn
