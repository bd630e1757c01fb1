"""The block-sparse mixer's decode SELECTION scores over the compressed
keys IN PLACE (``models/linear_sparse_lm.block_scores`` is the definition;
``serving/kvpool/linear.decode_attend`` the caller).

The compressed keys live in a pool array at a stride, ``ckeys [pool
layers, num_blocks, r, d]``: a block's ``r`` places are one ``r x d``
piece (1 KB at 4 x 128 bf16). Gathered through a slot's table they move a
piece a copy, and pieces of 1 KB move at the rate of DMA descriptors, not
of bytes. The kernel here reads the pool through the table too, but walks
the table row in GROUPS of :data:`GROUP_BLOCKS` entries: a group whose
entries are consecutive block ids (the allocator hands ids out ascending,
and a request's blocks go back together, so a document prefilled alone is
one long run) is ONE copy of ``GROUP_BLOCKS`` KB; any other group (a run
broken inside it, the tail at the slot's fill) is a copy a block, as the
gather has it. Whether a group is a run is computed on the device from
the tables (:func:`group_runs`) and rides in scalar memory beside them.

One grid step is one slot; its KV heads' lists alternate between two VMEM
buffers, the next list's copies (of this slot or of the next) in flight
while this one is scored. With a list's places resident the kernel takes
``q [g, d] x keys^T`` on the MXU into float32, lays the step's own new
compressed key's scores over its place, masks by visibility, takes the
softmax a head, the sum over the group's heads and the maximum over a
block's places and the next block's first. Only ``[slots, kv_heads,
columns]`` float32 leaves the kernel (the blocks' scores at a stride of
columns: :func:`pool_block_scores` slices them out).

A bf16 pool of 128-wide keys is scored AS IT LIES: the device packs rows
``2i`` and ``2i + 1`` of a bf16 array into one 32-bit sublane, so a
block's ``[r, 128]`` piece is ``r / 2`` rows of words and a list's buffer
a ``[blocks * r / 2, 128]`` array of them with no byte moved; a shift and
a mask read the even and the odd places out (exact), each scored by its
own matmul. Any other pool (float32 in interpret mode) is scored a place
a row.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.decode_attention import NEG_INF

# Table entries a group holds: what one copy moves where the entries are
# consecutive ids. By timing at the ``sala-serve-docs-64k`` shape, one
# sparse layer a call (``tools/bench_sparse_attention.py --parts
# block_select``, my chip runs, PR 56; the gathered ``jax.numpy`` form
# 0.99 ms): over a table of consecutive ids 0.659 / 0.439 / 0.337 ms at
# 8 / 16 / 32 entries, over a shuffled one (no run: a copy a block) 2.21
# / 1.98 / 1.88. A group costs ~40 ns beside its copy, so fewer, larger
# groups win on both. 64 and up would leave any shorter run, and more of
# a slot's own blocks past its document, to single copies.
GROUP_BLOCKS = 32
# Groups one wait of the kernel covers at most, and the copies of single
# blocks one turn of their loop starts: all 32 of a group unrolled read
# 1.67 ms over the shuffled table for 1.88, and took the step's program
# 9.3 s to trace and lower where four take 5.3 (3.7 without the kernel).
WAIT_GROUPS = 8
SINGLES_UNROLL = 4
LANES = 128
# What the kernel may use of the core's VMEM (128 MiB on a v5e; the
# compiler's own default scope is 16 MB) and the scalar memory the
# prefetched tables may take (``ops/flat_decode_attention.py``'s reading).
VMEM_BYTES = 32 << 20
SMEM_TABLE_BYTES = 768 << 10


def _packed(pool_dtype, per_block: int, head_dim: int) -> bool:
    """Whether a list's buffer is scored as 32-bit words of two places."""
    return (
        jnp.dtype(pool_dtype) == jnp.bfloat16
        and head_dim == LANES and per_block % 2 == 0
    )


def _padded_blocks(max_blocks: int, cols_per_block: int,
                   group_blocks: int) -> int:
    """A list's buffer in blocks: whole groups, and whole 128-lane
    vectors of score columns."""
    lanes = LANES // math.gcd(LANES, cols_per_block)
    unit = group_blocks * lanes // math.gcd(group_blocks, lanes)
    return -(-max_blocks // unit) * unit


def _vmem_bytes(places: int, head_dim: int, group: int) -> int:
    """An upper reckoning of the kernel's VMEM at a bf16 pool whose list
    buffer holds ``places`` keys: the two buffers, the words read out as
    even and odd places (32-bit, then bf16) and the live score and
    probability tiles."""
    rows = -(-group // 8) * 8
    return (
        2 * places * head_dim * 2          # the buffers
        + places * head_dim * (2 + 4 + 2)  # words, halves, bf16 halves
        + 8 * rows * places * 4            # scores, exponentials, shares
    )


def select_kernel_supported(pool_dtype, per_block: int, head_dim: int,
                            group: int, slots: int,
                            max_blocks: int) -> bool:
    """Shapes :func:`pool_block_scores` lowers for on a TPU: a bf16 pool
    of 128-wide keys, an even number of places a block (two places a
    32-bit word), one list within the VMEM the kernel asks for and the
    tables within the scalar memory."""
    if not _packed(pool_dtype, per_block, head_dim):
        return False
    nbp = _padded_blocks(max_blocks, per_block // 2, GROUP_BLOCKS)
    return (
        _vmem_bytes(nbp * per_block, head_dim, group) <= VMEM_BYTES
        and slots * (nbp + nbp // GROUP_BLOCKS) * 4 <= SMEM_TABLE_BYTES
    )


def group_runs(tables, group_blocks: int = GROUP_BLOCKS):
    """``tables [slots, n]`` (``n`` whole groups; the device's or the
    host's) -> ``[slots, n / group_blocks]`` bool: the group's entries
    are consecutive ids, so its blocks are one contiguous piece of the
    pool."""
    slots, n = tables.shape
    t = tables.reshape(slots, n // group_blocks, group_blocks)
    return (t[..., 1:] == t[..., :-1] + 1).all(axis=-1)


def copy_groups(tables, blocks, group_blocks: int = GROUP_BLOCKS):
    """What the kernel copies for slots whose lists hold ``blocks [slots]``
    blocks each, from the HOST's ``tables`` (numpy): ``(groups, runs)``,
    the groups at or below the fills and those of them that are one
    copy."""
    pad = -tables.shape[1] % group_blocks
    t = np.pad(np.asarray(tables), ((0, 0), (0, pad)))
    blocks = np.asarray(blocks).reshape(-1, 1)
    first = np.arange(t.shape[1] // group_blocks)[None, :] * group_blocks
    full = first + group_blocks <= blocks
    runs = group_runs(t, group_blocks) & full
    return int(np.sum(first < blocks)), int(np.sum(runs))


def _kernel(
    tbl_ref, run_ref, vis_ref, at_ref,            # scalar prefetch
    q_ref, own_ref, ck_hbm,                       # inputs
    o_ref,                                        # output
    buf, sem,                                     # scratch
    *, layer: int, kv_heads: int, per_block: int, group_blocks: int,
    scale: float, packed: bool,
):
    """One call = one sparse layer's block scores for every slot; one
    grid step = one slot, its KV heads in turn. List ``n = slot *
    kv_heads + head`` lies in ``buf[n % 2]``."""
    slot = pl.program_id(0)
    slots = pl.num_programs(0)
    nbp = buf.shape[1]
    n_groups = nbp // group_blocks
    f32 = jnp.float32

    def blocks_of(slot):
        return (vis_ref[slot] + per_block - 1) // per_block

    def piece(src, dst, b, at, first, n: int):
        """The copy of ``n`` blocks from pool block ``at`` on to buffer
        block ``first`` on."""
        return pltpu.make_async_copy(
            src.at[pl.ds(at, n)], dst.at[pl.ds(first, n)], sem.at[b]
        )

    def start_copies(slot, head, b):
        """Start list ``(slot, head)``'s copies into ``buf[b]``: the
        groups at or below the slot's fill, a run as one piece, any
        other a block at a time."""
        nb = blocks_of(slot)
        whole = nb // group_blocks
        src, dst = ck_hbm.at[layer + head], buf.at[b]

        def one_block(i, carry):
            piece(src, dst, b, tbl_ref[slot * nbp + i], i, 1).start()
            return carry

        several = math.gcd(SINGLES_UNROLL, group_blocks)

        def one_group(g, carry):
            first = pl.multiple_of(g * group_blocks, group_blocks)
            at = slot * nbp + first

            def run():
                piece(src, dst, b, tbl_ref[at], first, group_blocks).start()

            def blocks():
                def some(k, carry):
                    for i in range(several):
                        one_block(first + k * several + i, 0)
                    return carry

                lax.fori_loop(0, group_blocks // several, some, 0)

            lax.cond(run_ref[slot * n_groups + g] > 0, run, blocks)
            return carry

        lax.fori_loop(0, whole, one_group, 0)
        lax.fori_loop(whole * group_blocks, nb, one_block, 0)

    def wait_copies(slot, b):
        """Wait for what :func:`start_copies` started: the semaphore
        counts bytes, so a whole group is one wait whether it came as a
        run or a block at a time, and :data:`WAIT_GROUPS` groups are one
        wait too."""
        nb = blocks_of(slot)
        whole = nb // group_blocks
        src, dst = ck_hbm.at[layer], buf.at[b]
        many = min(WAIT_GROUPS, n_groups)

        def wait(n: int):
            def body(i, carry):
                piece(src, dst, b, 0, 0, n).wait()
                return carry

            return body

        lax.fori_loop(0, whole // many, wait(many * group_blocks), 0)
        lax.fori_loop(0, whole % many, wait(group_blocks), 0)
        lax.fori_loop(whole * group_blocks, nb, wait(1), 0)

    @pl.when(slot == 0)
    def _():
        start_copies(0, 0, 0)

    def shifted(x, by: int):
        """``x[..., i + by]`` at ``i`` (circular: the columns that wrap
        hold place 0's share, which is 0)."""
        return pltpu.roll(x, x.shape[-1] - by, axis=x.ndim - 1)

    def scores(head, b):
        q = q_ref[head]                           # [g, d]
        nt = (((1,), (1,)), ((), ()))

        def dot(keys):
            if q.dtype == keys.dtype == jnp.bfloat16:
                return lax.dot_general(
                    q, keys, nt, preferred_element_type=f32
                ) * scale
            return lax.dot_general(
                q.astype(f32), keys.astype(f32), nt,
                precision=lax.Precision.HIGHEST,
                preferred_element_type=f32,
            ) * scale

        if packed:
            # Word row w holds place 2w in its low half, 2w + 1 in its
            # high one; a bf16 is the high half of its float32.
            words = buf.bitcast(jnp.uint32).reshape(
                2, nbp * per_block // 2, LANES
            )[b]
            halves = (words << 16, words & jnp.uint32(0xFFFF0000))
            parts = [
                dot(pltpu.bitcast(h, f32).astype(jnp.bfloat16))
                for h in halves
            ]
        else:
            parts = [dot(buf[b].reshape(nbp * per_block, -1))]
        n_parts = len(parts)
        n_vis, fresh_at = vis_ref[slot], at_ref[slot]
        own = own_ref[head]                       # [g, 1]
        col = lax.broadcasted_iota(jnp.int32, parts[0].shape, 1)
        for i in range(n_parts):
            place = col * n_parts + i
            s = jnp.where(place == fresh_at, own, parts[i])
            parts[i] = jnp.where(
                (place >= 1) & (place < n_vis), s, NEG_INF
            )
        top = functools.reduce(jnp.maximum, [
            jnp.max(s, axis=-1, keepdims=True) for s in parts
        ])
        top = jnp.where(top > NEG_INF / 2, top, 0.0)
        parts = [jnp.exp(s - top) for s in parts]    # 0 where unseen
        total = sum(jnp.sum(e, axis=-1, keepdims=True) for e in parts)
        total = jnp.where(total > 0, total, 1.0)
        parts = [
            jnp.sum(e / total, axis=0, keepdims=True) for e in parts
        ]                                         # [1, columns] a part
        # A block's own places and the next block's first.
        cols = per_block // n_parts
        both = functools.reduce(jnp.maximum, parts)
        best = both
        for i in range(1, cols):
            best = jnp.maximum(best, shifted(both, i))
        o_ref[pl.ds(head, 1), :] = jnp.maximum(
            best, shifted(parts[0], cols)
        )

    def one_head(head, carry):
        b = (slot * kv_heads + head) % 2
        last = head + 1 == kv_heads
        nxt_slot = jnp.where(last, slot + 1, slot)

        @pl.when(nxt_slot < slots)
        def _():
            start_copies(nxt_slot, jnp.where(last, 0, head + 1), 1 - b)

        wait_copies(slot, b)
        scores(head, b)
        return carry

    lax.fori_loop(0, kv_heads, one_head, 0)


def pool_block_scores(q, own_scores, ckeys, layer: int, tables, n_visible,
                      fresh_at, *, group_blocks: int = GROUP_BLOCKS,
                      interpret=None):
    """``linear_sparse_lm.block_scores`` of one query a slot over the
    slot's compressed keys read through its table, in place.

    ``q [slots, kv_heads, g, d]``; ``ckeys [pool layers, num_blocks, r,
    d]`` with KV head ``j`` of this sparse layer at pool layer ``layer +
    j``; ``tables [slots, max_blocks]``; ``n_visible [slots]`` the places
    ``1 ... n_visible - 1`` the slot's query sees (0: the slot reads
    nothing and scores 0 everywhere); ``fresh_at [slots]`` the place the
    step's own new compressed key takes (negative: none), whose scaled
    float32 scores a head are ``own_scores [slots, kv_heads, g]`` and
    stand in for what the pool holds there. Returns ``[slots, kv_heads,
    max_blocks]`` float32."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    slots, kh, g, d = q.shape
    _, _, per, _ = ckeys.shape
    max_blocks = tables.shape[1]
    packed = _packed(ckeys.dtype, per, d)
    cols = per // 2 if packed else per
    nbp = _padded_blocks(max_blocks, cols, group_blocks)
    tables = jnp.pad(
        jnp.asarray(tables, jnp.int32), ((0, 0), (0, nbp - max_blocks))
    )
    scalars = (
        tables.reshape(-1),
        group_runs(tables, group_blocks).astype(jnp.int32).reshape(-1),
        jnp.clip(jnp.asarray(n_visible, jnp.int32), 0, max_blocks * per),
        jnp.asarray(fresh_at, jnp.int32),
    )

    def a_slot(*dims):
        return pl.BlockSpec(
            (None,) + dims, lambda s, *_: (s,) + (0,) * len(dims)
        )

    out = pl.pallas_call(
        functools.partial(
            _kernel, layer=layer, kv_heads=kh, per_block=per,
            group_blocks=group_blocks, scale=d ** -0.5, packed=packed,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(slots,),
            in_specs=[
                a_slot(kh, g, d),
                a_slot(kh, g, 1),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=a_slot(kh, nbp * cols),
            scratch_shapes=[
                pltpu.VMEM((2, nbp, per, d), ckeys.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((slots, kh, nbp * cols), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_BYTES,
        ),
        interpret=interpret,
        name="paged_block_select_scores",
    )(*scalars, q, own_scores.astype(jnp.float32)[..., None], ckeys)
    return out[:, :, :max_blocks * cols:cols]
