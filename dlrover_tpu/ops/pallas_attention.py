"""Pallas TPU flash-attention: fused forward AND backward kernels.

Online-softmax tiling: grid (batch*heads, q_blocks, kv_blocks) with the
kv dimension innermost — TPU grids run sequentially, so the running
(acc, m, l) live in VMEM scratch across kv iterations and the output
block is written once on the last one. Q/K/V blocks stream HBM→VMEM via
BlockSpec; the [block_q, block_k] logits tile hits the MXU in the input
dtype (bf16 at full MXU rate) with f32 accumulation. GQA is handled in
the index maps (query head -> kv head), never materialized. The value
head size may differ from q/k's (latent attention: 192 / 128): scores
contract over q/k's, the accumulators and ``o`` / ``dO`` / ``dv`` carry v's.

Backward (FlashAttention-2 style): the forward additionally writes the
row log-sum-exp ``lse`` ([b*h, sq, 128] lane-broadcast, the layout trick
of the official jax pallas kernel); the backward recomputes P per tile
from (q, k, lse) and runs two kernels — one accumulating dq over kv
blocks, one accumulating dk/dv over (group, q-block) pairs so GQA
gradients sum across the query heads sharing a kv head. No O(s^2)
tensor ever hits HBM in either direction.

Used for the per-device block of full attention; ring attention
(ops/ring_attention.py) handles the sequence-parallel case.

Parity note: the reference delegates attention entirely to torch
frameworks (SURVEY.md §2.9); this kernel is the TPU-native compute path
its elastic machinery would supervise.
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from dlrover_tpu.ops.attention import NEG_INF
from dlrover_tpu.parallel.sharding import current_mesh, logical_to_spec

LANES = 128  # lane-broadcast width for per-row stats (lse, delta)


def _pick_block(s: int, target: int = 1024) -> int:
    for cand in (target, 512, 256, 128, 64, 32, 16, 8):
        if s % cand == 0 and cand <= s:
            return cand
    return s


# (q/k head size, value head size) -> {"fwd" | "bwd": (q target, kv
# target)}: block targets picked on the chip for ONE shape, in place of
# the defaults below (1024 forward; backward 1024 up to d = 128, else
# 512). A shape that is not listed keeps the defaults, so an entry moves
# no other model's kernels. ``tools/bench_flash_blocks.py`` times the
# three kernels under candidate entries. 256 / 256 (20 heads, 8,192
# tokens; my chip run, PR 43, one layer): the backward at 512 x 1024
# reads 19.6 ms against 21.4 at the default 512 x 512 (1024 x 512:
# 20.0; 256-blocks 25-34; 1024 x 1024 does not fit VMEM); the forward's
# default 1024 x 1024 reads 7.65 ms and won (1024 x 512: 8.35; 512 x
# 512: 10.15; 2048 x 1024 does not fit).
BLOCK_TARGETS = {(256, 256): {"bwd": (512, 1024)}}


def _block_targets(kind: str, d: int, dv: int, default: int):
    return BLOCK_TARGETS.get((d, dv), {}).get(kind, (default, default))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
    *, scale: float, causal: bool, block_q: int, block_k: int,
):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    qi = pl.program_id(1)
    q_start = qi * block_q
    k_start = ki * block_k

    def body(masked: bool):
        # Blocks are (1, bq, d) or (1, 1, bq, d) depending on the layout
        # path; normalize to 2D for the math. Matmuls keep the input
        # dtype (bf16 on TPU — full-rate MXU) and accumulate in f32;
        # softmax math happens on the f32 logits.
        q = q_ref[...].reshape(block_q, -1)
        k = k_ref[...].reshape(block_k, -1)
        v = v_ref[...].reshape(block_k, -1)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [block_q, block_k]
        if masked:
            rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            cols = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            logits = jnp.where(rows >= cols, logits, NEG_INF)

        m_prev = m_ref[:, :1]                       # [block_q, 1]
        l_prev = l_ref[:, :1]
        m_blk = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        # Fully-masked ROWS can exist in a diagonal tile when
        # block_q > block_k (rows q_start..k_start-1 see only future
        # columns). The invariant that makes this safe without a -inf
        # guard: the FIRST k-tile of every row's sweep contributes at
        # least one valid column (k_start=0 <= row), so m_prev is
        # finite by the time any fully-masked tile-row is processed,
        # and its exp(NEG_INF - m_new) underflows to exactly 0. Keep
        # that ordering (ki=0 first) if the grid or NEG_INF changes.
        p = jnp.exp(logits - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    if causal:
        # Three tile classes: fully past (no mask math — the iota/
        # compare/where VPU passes rival the tile's MXU time at d=128),
        # diagonal (masked), fully future (skipped).
        q_end = q_start + block_q - 1
        k_end = k_start + block_k - 1
        pl.when(k_end <= q_start)(lambda: body(False))
        pl.when((k_start <= q_end) & (k_end > q_start))(
            lambda: body(True)
        )
    else:
        body(False)

    @pl.when(ki == nk - 1)
    def _():
        m = m_ref[:, :1]
        l = l_ref[:, :1]
        out = acc_ref[:] / jnp.maximum(l, 1e-30)
        out = jnp.where(m > NEG_INF / 2, out, 0.0)
        o_ref[...] = out.astype(o_ref.dtype).reshape(o_ref.shape)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)


def _flash_forward(q, k, v, causal, softmax_scale, interpret):
    b, sq, h, d = q.shape
    _, skv, hkv, _ = k.shape
    dv = v.shape[-1]  # the value head size may differ from q/k's (MLA)
    groups = h // hkv
    scale = softmax_scale if softmax_scale is not None else d ** -0.5
    target_q, target_k = _block_targets("fwd", d, dv, 1024)
    block_q = _pick_block(sq, target_q)
    block_k = _pick_block(skv, target_k)
    grid = (b * h, sq // block_q, skv // block_k)

    # Mosaic requires the BLOCK's last two dims to be divisible by
    # (8, 128) or equal to the full array dims; a head-dim block of 1 in
    # the sublane position never qualifies. Two legal layouts:
    # - d % 128 == 0: fold heads into the minor axis ([b, s, h*d] is a
    #   FREE reshape of the contiguous layout) and block the per-head
    #   d-slice — zero data movement;
    # - otherwise (d=64 etc.): transpose to [b, h, s, d] so the minor
    #   block dim equals the full array d — costs one HBM copy per
    #   operand, still far cheaper than materialized s^2 logits.
    # NOTE: clamping kv/q block indices to the causal diagonal (so
    # compute-skipped future tiles revisit the resident block instead
    # of streaming one they never read, Mosaic eliding the copy on an
    # unchanged index) was swept on v5e at s in {8k, 32k} across all
    # three kernels and REJECTED: every apparent win (best 42 -> 37.5
    # ms fwd+bwd at 32k in one session) failed to reproduce across
    # fresh sessions — the deltas sat inside the ±8% session-to-session
    # spread, while the non-affine index maps measurably slowed the
    # forward (18.1 -> 19.1 ms). Simple affine maps win.
    if (d % 128 == 0 and dv % 128 == 0) or h == 1:
        operands = (
            q.reshape(b, sq, h * d),
            k.reshape(b, skv, hkv * d),
            v.reshape(b, skv, hkv * dv),
        )
        out_dims = (b, sq, h * dv)
        q_block, o_block = (1, block_q, d), (1, block_q, dv)
        k_block, v_block = (1, block_k, d), (1, block_k, dv)

        def q_map(bh, qi, ki):
            return (bh // h, qi, bh % h)

        def kv_map(bh, qi, ki):
            return (bh // h, ki, (bh % h) // groups)

        def post(out):
            return out.reshape(b, sq, h, dv)

    else:
        operands = (
            q.transpose(0, 2, 1, 3),
            k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3),
        )
        out_dims = (b, h, sq, dv)
        q_block, o_block = (1, 1, block_q, d), (1, 1, block_q, dv)
        k_block, v_block = (1, 1, block_k, d), (1, 1, block_k, dv)

        def q_map(bh, qi, ki):
            return (bh // h, bh % h, qi, 0)

        def kv_map(bh, qi, ki):
            return (bh // h, (bh % h) // groups, ki, 0)

        def post(out):
            return out.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
    )
    out, lse = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct(out_dims, q.dtype),
            jax.ShapeDtypeStruct((b * h, sq, LANES), jnp.float32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec(q_block, q_map),
            pl.BlockSpec(k_block, kv_map),
            pl.BlockSpec(v_block, kv_map),
        ],
        out_specs=(
            pl.BlockSpec(o_block, q_map),
            pl.BlockSpec((1, block_q, LANES), lambda bh, qi, ki: (bh, qi, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)
    return post(out), lse


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------
#
# Operands are pre-transposed to [b, h, s, d] (one HBM copy each — simple
# uniform layout for both d%128==0 and d=64). Per-row stats (lse, delta)
# ride as [b*h, sq, LANES] lane-broadcast f32.


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, dq_acc,
    *, scale: float, causal: bool, block_q: int, block_k: int,
):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    q_start = pl.program_id(1) * block_q
    k_start = ki * block_k

    @pl.when(ki == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def body(masked: bool):
        q = q_ref[...].reshape(block_q, -1)
        k = k_ref[...].reshape(block_k, -1)
        v = v_ref[...].reshape(block_k, -1)
        do = do_ref[...].reshape(block_q, -1)
        lse = lse_ref[...].reshape(block_q, LANES)[:, :1]
        di = di_ref[...].reshape(block_q, LANES)[:, :1]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if masked:
            rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            cols = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)                       # [bq, bk]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = (p * (dp - di) * scale).astype(q.dtype)
        dq_acc[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        # Mask math only on diagonal tiles (see _flash_kernel).
        pl.when(k_start + block_k - 1 <= q_start)(lambda: body(False))
        pl.when(
            (k_start <= q_start + block_q - 1)
            & (k_start + block_k - 1 > q_start)
        )(lambda: body(True))
    else:
        body(False)

    @pl.when(ki == nk - 1)
    def _():
        dq_ref[...] = dq_acc[:].astype(dq_ref.dtype).reshape(dq_ref.shape)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, scale: float, causal: bool, block_q: int, block_k: int, nq: int,
):
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    k_start = pl.program_id(1) * block_k
    q_start = (j % nq) * block_q

    @pl.when(j == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def body(masked: bool):
        q = q_ref[...].reshape(block_q, -1)
        k = k_ref[...].reshape(block_k, -1)
        v = v_ref[...].reshape(block_k, -1)
        do = do_ref[...].reshape(block_q, -1)
        lse = lse_ref[...].reshape(block_q, LANES)[:, :1]
        di = di_ref[...].reshape(block_q, LANES)[:, :1]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if masked:
            rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            cols = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)                       # [bq, bk]
        # dv += P^T @ dO
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = (p * (dp - di) * scale).astype(q.dtype)
        # dk += dS^T @ Q
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        # Mask math only on diagonal tiles; q blocks entirely before the
        # kv block contribute nothing and are skipped.
        pl.when(k_start + block_k - 1 <= q_start)(lambda: body(False))
        pl.when(
            (q_start + block_q - 1 >= k_start)
            & (k_start + block_k - 1 > q_start)
        )(lambda: body(True))
    else:
        body(False)

    @pl.when(j == nj - 1)
    def _():
        dk_ref[...] = dk_acc[:].astype(dk_ref.dtype).reshape(dk_ref.shape)
        dv_ref[...] = dv_acc[:].astype(dv_ref.dtype).reshape(dv_ref.shape)


def flash_backward_delta(g, out):
    """delta_i = rowsum(dO * O), lane-broadcast to the stats layout —
    loop-invariant for ring attention, so exposed separately."""
    b, sq, h, _ = g.shape
    di = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )  # [b, sq, h]
    return jnp.broadcast_to(
        di.transpose(0, 2, 1).reshape(b * h, sq, 1), (b * h, sq, LANES)
    )


def _flash_backward(q, k, v, out, lse, g, causal, softmax_scale, interpret):
    """Grad wrt (q, k, v) in the model's [b, s, h, d] layout."""
    di = flash_backward_delta(g, out)
    dqT, dkT, dvT = flash_backward_T(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        g.transpose(0, 2, 1, 3),
        lse,
        di,
        causal,
        softmax_scale,
        interpret,
    )
    return (
        dqT.transpose(0, 2, 1, 3),
        dkT.transpose(0, 2, 1, 3),
        dvT.transpose(0, 2, 1, 3),
    )


def flash_backward_T(qT, kT, vT, doT, lse, di, causal, softmax_scale,
                     interpret):
    """Backward core on PRE-TRANSPOSED [b, h, s, d] operands with a
    precomputed delta — ring attention hoists the transposes and delta
    out of its per-hop loop and calls this directly."""
    b, h, sq, d = qT.shape
    _, hkv, skv, _ = kT.shape
    dv = vT.shape[-1]
    groups = h // hkv
    scale = softmax_scale if softmax_scale is not None else d ** -0.5
    # 1024 blocks measure ~10% faster than 512 on v5e at d<=128 (same
    # sweep result as the forward: grid-step overhead dominates below
    # ~1024) and were verified to compile/run on hardware at d=128,
    # s=4096. The backward holds roughly twice the forward's live tiles
    # (s/p/dp f32 + two accumulators), so larger head dims — unverified
    # and with proportionally bigger blocks — keep the conservative 512
    # cap to stay inside VMEM.
    target_q, target_k = _block_targets(
        "bwd", d, dv, 1024 if d <= 128 else 512
    )
    block_q = _pick_block(sq, target=target_q)
    block_k = _pick_block(skv, target=target_k)
    nq = sq // block_q

    q_block, do_block = (1, 1, block_q, d), (1, 1, block_q, dv)
    k_block, v_block = (1, 1, block_k, d), (1, 1, block_k, dv)
    stat_block = (1, block_q, LANES)

    # ---- dq: grid (b*h, q_blocks, kv_blocks) --------------------------
    def q_map(bh, qi, ki):
        return (bh // h, bh % h, qi, 0)

    def kv_map(bh, qi, ki):
        return (bh // h, (bh % h) // groups, ki, 0)

    def stat_map(bh, qi, ki):
        return (bh, qi, 0)

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k,
        ),
        out_shape=jax.ShapeDtypeStruct(qT.shape, qT.dtype),
        grid=(b * h, nq, skv // block_k),
        in_specs=[
            pl.BlockSpec(q_block, q_map),
            pl.BlockSpec(k_block, kv_map),
            pl.BlockSpec(v_block, kv_map),
            pl.BlockSpec(do_block, q_map),
            pl.BlockSpec(stat_block, stat_map),
            pl.BlockSpec(stat_block, stat_map),
        ],
        out_specs=pl.BlockSpec(q_block, q_map),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(qT, kT, vT, doT, lse, di)

    # ---- dk/dv: grid (b*hkv, kv_blocks, groups*q_blocks) --------------
    # The innermost axis walks every query head in the kv head's group
    # and every q block, accumulating into one (dk, dv) tile — GQA
    # gradients need exactly this cross-head sum.
    def kv_map2(bkv, ki, j):
        return (bkv // hkv, bkv % hkv, ki, 0)

    def q_map2(bkv, ki, j):
        return (bkv // hkv, (bkv % hkv) * groups + j // nq, j % nq, 0)

    def stat_map2(bkv, ki, j):
        bh = (bkv // hkv) * h + (bkv % hkv) * groups + j // nq
        return (bh, j % nq, 0)

    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, nq=nq,
        ),
        out_shape=(
            jax.ShapeDtypeStruct(kT.shape, kT.dtype),
            jax.ShapeDtypeStruct(vT.shape, vT.dtype),
        ),
        grid=(b * hkv, skv // block_k, groups * nq),
        in_specs=[
            pl.BlockSpec(q_block, q_map2),
            pl.BlockSpec(k_block, kv_map2),
            pl.BlockSpec(v_block, kv_map2),
            pl.BlockSpec(do_block, q_map2),
            pl.BlockSpec(stat_block, stat_map2),
            pl.BlockSpec(stat_block, stat_map2),
        ],
        out_specs=(
            pl.BlockSpec(k_block, kv_map2),
            pl.BlockSpec(v_block, kv_map2),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, dv), jnp.float32),
        ],
        interpret=interpret,
    )(qT, kT, vT, doT, lse, di)

    return dq, dk, dv


# ---------------------------------------------------------------------------
# Mesh island
# ---------------------------------------------------------------------------
#
# GSPMD cannot partition a Mosaic kernel, so on a multi-device mesh the
# kernels run per shard inside a ``shard_map`` over the batch and head
# axes (attention is independent across both; the sequence stays whole —
# a sequence-sharded mesh uses ops/ring_attention.py instead). The row
# lse crosses the island compact, [b, h, sq]: the kernel's
# lane-broadcast [b*h, sq, LANES] layout would pin 128x the bytes
# (64MB/layer at the flagship shape) across the whole backward, and its
# merged b*h axis has no partition spec.


def _island_specs(q, k):
    """(mesh, q_spec, kv_spec, lse_spec) when a multi-device mesh is in
    scope, else None. A dim its mesh axes do not divide stays whole."""
    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return None
    batch_ax, q_ax = logical_to_spec(("batch", "heads"))
    kv_ax = logical_to_spec(("kv_heads",))[0]

    def n_shards(axes):
        if axes is None:
            return 1
        names = (axes,) if isinstance(axes, str) else axes
        return math.prod(mesh.shape[a] for a in names)

    b, _, h, _ = q.shape
    hkv = k.shape[2]
    if b % n_shards(batch_ax):
        batch_ax = None
    # q heads and kv heads split together or not at all: a shard's
    # query heads must find their kv head on the same shard.
    if q_ax != kv_ax or h % n_shards(q_ax) or hkv % n_shards(kv_ax):
        q_ax = kv_ax = None
    return (
        mesh,
        P(batch_ax, None, q_ax, None),
        P(batch_ax, None, kv_ax, None),
        P(batch_ax, q_ax, None),
    )


def flash_forward_local(q, k, v, causal, softmax_scale, interpret):
    """The forward on this shard's arrays (also one ring-attention
    hop): (out, compact lse [b, h, sq])."""
    b, sq, h, _ = q.shape
    out, lse = _flash_forward(q, k, v, causal, softmax_scale, interpret)
    return out, lse[:, :, 0].reshape(b, h, sq)


def _backward_local(
    q, k, v, out, lse_c, g, causal, softmax_scale, interpret
):
    b, sq, h, _ = q.shape
    lse = jnp.broadcast_to(
        lse_c.reshape(b * h, sq, 1), (b * h, sq, LANES)
    )
    return _flash_backward(
        q, k, v, out, lse, g, causal, softmax_scale, interpret
    )


def flash_forward(q, k, v, causal, softmax_scale, interpret):
    """(out [b, sq, h, d], compact lse [b, h, sq]) — per shard under the
    mesh in scope, directly on a single device."""
    local = functools.partial(
        flash_forward_local, causal=causal, softmax_scale=softmax_scale,
        interpret=interpret,
    )
    specs = _island_specs(q, k)
    if specs is None:
        return local(q, k, v)
    mesh, qs, kvs, ls = specs
    return jax.shard_map(
        local, mesh=mesh, in_specs=(qs, kvs, kvs), out_specs=(qs, ls),
        check_vma=False,
    )(q, k, v)


def flash_backward(
    q, k, v, out, lse_c, g, causal, softmax_scale, interpret
):
    """Grad wrt (q, k, v) from ``flash_forward``'s residuals."""
    local = functools.partial(
        _backward_local, causal=causal, softmax_scale=softmax_scale,
        interpret=interpret,
    )
    specs = _island_specs(q, k)
    if specs is None:
        return local(q, k, v, out, lse_c, g)
    mesh, qs, kvs, ls = specs
    return jax.shard_map(
        local, mesh=mesh, in_specs=(qs, kvs, kvs, qs, ls, qs),
        out_specs=(qs, kvs, kvs), check_vma=False,
    )(q, k, v, out, lse_c, g)


# ---------------------------------------------------------------------------
# Public op
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(
    q, k, v,
    causal: bool = True,
    softmax_scale: Optional[float] = None,
    interpret: Optional[bool] = None,
):
    """Drop-in for ``dot_product_attention`` with contiguous positions.

    q [b, sq, h, d]; k/v [b, skv, hkv, d]; h % hkv == 0. ``interpret``
    defaults to True off-TPU so tests run on CPU.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    out, _ = flash_forward(q, k, v, causal, softmax_scale, interpret)
    return out


def _fwd(q, k, v, causal, softmax_scale, interpret):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    out, lse_c = flash_forward(q, k, v, causal, softmax_scale, interpret)
    # Named (an identity that lowers to nothing) so that a remat policy
    # can keep exactly what the backward kernels need beside q, k, v and
    # never run the forward kernel twice: ``save_only_these_names(
    # "flash_out", "flash_lse")`` (models/hybrid.py, ``remat_keep``).
    out = checkpoint_name(out, "flash_out")
    lse_c = checkpoint_name(lse_c, "flash_lse")
    return out, (q, k, v, out, lse_c)


def _bwd(causal, softmax_scale, interpret, res, g):
    q, k, v, out, lse_c = res
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return flash_backward(
        q, k, v, out, lse_c, g, causal, softmax_scale, interpret
    )


flash_attention.defvjp(_fwd, _bwd)


def make_flash_attention(interpret: Optional[bool] = None):
    """attention_fn factory for ``llama.forward``. Ignores explicit
    positions (assumes contiguous [0..s) per call) — use ring attention
    when the sequence axis is sharded."""

    def attention_fn(
        q, k, v, causal=True, q_positions=None, kv_positions=None,
        softmax_scale=None,
    ):
        return flash_attention(
            q, k, v, causal, softmax_scale, interpret
        )

    # Backward residuals are O(s*d) (q/k/v/out + compact lse), so the
    # "mlp_only" remat policy may exempt this impl from rematerialization.
    attention_fn.saveable_residuals = True
    # Plain contiguous-position flash with DEFAULT interpret
    # resolution: eligible for llama's lite attention block (attn_save
    # saves only x/out/lse and re-derives q/k/v in the backward). An
    # explicit interpret override opts out — the lite block resolves
    # interpret from the backend and must not silently discard the
    # caller's choice. Ring attention sets saveable_residuals but not
    # this — its hop structure can't be re-derived from x.
    attention_fn.is_plain_flash = interpret is None
    return attention_fn
