"""Grouped-query attention over a pool of FLAT K/V rows, in place.

:func:`pool_flat_decode_attention` (Pallas; :func:`flat_kernel_supported`
says where it lowers) is the attention of ``serving/kvpool/conv.py``'s
decode step on a TPU, one query token a slot, and at the file's end
:func:`pool_flat_chunk_attention` (:func:`flat_chunk_kernel_supported`)
its prefill chunk's, a tile of tokens at a time (PR 50), against the two
arrays such a model's pool holds, ``[attention layers, num_blocks,
block_size, kv_width]``: a token's K (V) held FLAT, heads narrower than
a 128-lane row side by side in it (``conv.lane_pack``). The pools are
read where they lie: filled pages only, a page one contiguous DMA,
scores, softmax and the weighted sum in VMEM. The gathered views they
replace wrote the rows out and read them back, and walked float32
scores through HBM in the softmax's passes (PERF.md section 6, PR 49).

A flat row is never split into heads: a ``[.., 8, 64]`` view is another
tiling on the device (every head padded to 128 lanes). A page ``[block_size,
kv_width]`` lands in VMEM as stored, and lane row ``j`` of a chunk of
pages (a static, lane-aligned slice) meets the queries of that lane
row's heads, each laid into its own head's lanes with zeros in its
neighbour's (``conv._placed``), in one matmul: a column is a token, so
no head mask exists.

A module of its own, not ``ops/decode_attention.py``'s: that module's
kernels read pages of ``[block_size x kv_heads, head_dim]`` rows under a
head mask or a strided read, this one pages of ``[block_size, J x 128]``
lane rows under placed queries; the two share the exact products
(``_split_bf16`` / ``_dot_f32_by_stored``) and the double-buffering
idiom, and those kernels' programs stay byte for byte what they were
when this one changes. ``kvpool/conv.decode_attention_kind`` picks
between it and the gathered form from what it can see; nothing here
reads the environment.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.decode_attention import NEG_INF, _dot_f32_by_stored

# Lanes of a device row: the width of a lane row of a flat K (V) row.
LANES = 128
# Bytes of K (and of V) one inner step of the kernel copies and attends:
# a chunk of whole pages, double-buffered, so four such buffers in VMEM;
# 1 MB is 16 pages of 64 x 512, 1,024 tokens. On the v5e at the
# ``lfm2-serve-sessions-8k`` shape (32 slots x ~8.5k rows, one layer;
# ``tools/bench_paged_decode.py --parts conv``, my chip run, PR 49):
# 256 KB / 512 KB / 1 MB / 2 MB / 4 MB take 1.082 / 0.872 / 0.869 / 0.881
# / 0.911 ms a call where the page copies ALONE take 0.853 / 0.843 /
# 0.851 / 0.847 / 0.850 (658 GB/s of the visible rows) and the gathered
# form 2.866: from 512 KB up the kernel is bound by its DMAs, the
# arithmetic hidden under them to 2-3 %.
CHUNK_BYTES = 1 << 20
# What the kernel may use of the core's VMEM (128 MiB on a v5e; the
# compiler's own default scope is 16 MB).
VMEM_BYTES = 32 << 20
# Scalar memory the prefetched tables may take: every slot's table rides
# there whole (the next slot's first pages are asked for while this
# slot's last are attended), 18 KB at 32 slots x 144 pages. The core has
# 1 MB: 768 KB compiled for the described v5e, 1 MB did not
# (``ops/latent_decode_attention.py``'s reading).
SMEM_TABLE_BYTES = 768 << 10


def _chunk_pages(block_size: int, kv_width: int) -> int:
    """Pages of a bf16 pool in one VMEM chunk: as many whole pages as
    ``CHUNK_BYTES`` holds; 0 where not even one fits."""
    return CHUNK_BYTES // (block_size * kv_width * 2)


def _query_rows(rows: int) -> int:
    """A lane row's placed queries padded to whole bf16 tiles, so that
    the three addends of an exact product stack on tile edges."""
    return -(-rows // 16) * 16


def _vmem_bytes(block_size: int, kv_width: int, rows: int) -> int:
    """An upper reckoning of the kernel's VMEM at a bf16 pool: the four
    chunk buffers, a slot's pipelined blocks (queries, own logits, own
    V row, answer: two buffers each) and the live score tiles."""
    cols = _chunk_pages(block_size, kv_width) * block_size
    qrows = kv_width // LANES * _query_rows(rows)
    return (
        4 * cols * kv_width * 2                   # K, V double-buffered
        + 2 * qrows * LANES * 4 * 3               # q, own logits, answer
        + 2 * 8 * LANES * 4                       # own V row
        + 8 * qrows * cols * 4                    # scores, probabilities
    )


def flat_kernel_supported(pool_dtype, block_size: int, kv_width: int,
                          lane_row: int, rows: int, slots: int,
                          max_blocks: int) -> bool:
    """Shapes :func:`pool_flat_decode_attention` lowers for on a TPU: a
    bf16 pool whose page ``[block_size, kv_width]`` is whole (16, 128)
    tiles and one contiguous DMA and fits a VMEM chunk, a flat row of
    whole lane rows that each hold whole heads (``lane_row``, the lanes
    ``conv.lane_pack`` heads take, is 128), a chunk of whole pages that
    is whole 128-lane blocks of tokens (they are the lane dimension of
    the score tile), ``rows`` placed queries a lane row, buffers that
    fit the VMEM the kernel asks for, and tables that fit the scalar
    memory."""
    pages = _chunk_pages(block_size, kv_width)
    return bool(
        jnp.dtype(pool_dtype) == jnp.bfloat16
        and lane_row == LANES and kv_width % LANES == 0
        and block_size % 16 == 0
        and pages >= 1 and (pages * block_size) % LANES == 0
        and _vmem_bytes(block_size, kv_width, rows) <= VMEM_BYTES
        and slots * max_blocks * 4 <= SMEM_TABLE_BYTES
    )


_NT = (((1,), (1,)), ((), ()))      # [m, d] x [n, d] -> [m, n]
_NN = (((1,), (0,)), ((), ()))      # [m, n] x [n, d] -> [m, d]


def _attend(q_ref, kbuf, vbuf, buf, limit, carry, *, lane_rows: int,
            rows: int):
    """One online-softmax step over the chunk in ``kbuf[buf]`` /
    ``vbuf[buf]`` ``[cols, lane_rows * 128]`` as stored: ``carry`` =
    (running max ``[lane_rows * rows, 1]``, sum, accumulator
    ``[lane_rows * rows, 128]``), all float32; tokens at or past
    ``limit`` are not visible. Lane row ``j``'s queries (``q_ref`` rows
    ``j * rows ...``, float32, scaled, each in its own head's lanes)
    meet lanes ``j * 128 ...`` of every token, the float32 operands
    unrounded (``_dot_f32_by_stored``)."""
    m, l, acc = carry
    lanes = lambda ref, j: ref[buf, :, j * LANES:(j + 1) * LANES]  # noqa: E731
    mine = lambda x, j: x[j * rows:(j + 1) * rows]  # noqa: E731
    s = jnp.concatenate([
        _dot_f32_by_stored(mine(q_ref, j), lanes(kbuf, j), _NT)
        for j in range(lane_rows)
    ], axis=0)                                   # [lane_rows * rows, cols]
    col = lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(col < limit, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)              # masked: exp(-1e30 - m) == 0
    alpha = jnp.exp(m - m_new)
    l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc * alpha + jnp.concatenate([
        _dot_f32_by_stored(mine(p, j), lanes(vbuf, j), _NN)
        for j in range(lane_rows)
    ], axis=0)
    return m_new, l, acc


def _kernel(
    layer_ref, pages_ref, len_ref, tbl_ref,       # scalar prefetch
    q_ref, s_own_ref, v_own_ref, k_hbm, v_hbm,    # inputs
    o_ref,                                        # output
    kbuf, vbuf, sem, parity,                      # scratch
    *, chunk_pages: int, block_size: int, lane_rows: int, rows: int,
    max_blocks: int,
):
    """One call = one attention layer's decode attention for every slot;
    one grid step = one slot. The pools stay in HBM; a slot's filled
    pages are copied page by page (K and V, one contiguous DMA each)
    into double-buffered VMEM chunks of ``chunk_pages`` pages, the next
    chunk — of this slot or of the next — in flight while this one is
    attended (:func:`_attend`), as ``ops/decode_attention._pool_kernel``
    has it. The online softmax is opened by the new token's own term
    (``s_own_ref``, ``v_own_ref``), so its running max is a real logit
    from the start; a slot with no page to read (free, mid-prefill, an
    empty cache) copies and computes nothing and answers with its own
    V row."""
    slot = pl.program_id(0)
    slots = pl.num_programs(0)
    layer = layer_ref[0]
    cols = chunk_pages * block_size

    def pages_in(slot, chunk):
        return jnp.clip(pages_ref[slot] - chunk * chunk_pages,
                        0, chunk_pages)

    def page_copies(slot, chunk, b, i):
        blk = tbl_ref[slot * max_blocks + chunk * chunk_pages + i]
        dst = pl.ds(pl.multiple_of(i * block_size, block_size), block_size)
        return (
            pltpu.make_async_copy(
                k_hbm.at[layer, blk], kbuf.at[b, dst], sem.at[0, b]
            ),
            pltpu.make_async_copy(
                v_hbm.at[layer, blk], vbuf.at[b, dst], sem.at[1, b]
            ),
        )

    def start(slot, chunk, b):
        def body(i, carry):
            for cp in page_copies(slot, chunk, b, i):
                cp.start()
            return carry

        lax.fori_loop(0, pages_in(slot, chunk), body, 0)

    def wait(slot, chunk, b):
        def body(i, carry):
            for cp in page_copies(slot, chunk, b, i):
                cp.wait()
            return carry

        lax.fori_loop(0, pages_in(slot, chunk), body, 0)

    @pl.when(slot == 0)
    def _():
        # What a page copy has not filled must still be FINITE: a
        # masked token's probability is exactly 0, and 0 x NaN would
        # poison p.V.
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        parity[0] = 0
        start(0, 0, 0)

    # a slot with no page still takes one turn of the loop: it is there
    # that the next slot's first chunk is asked for
    n_mine = jnp.maximum(
        (pages_ref[slot] + chunk_pages - 1) // chunk_pages, 1
    )

    def chunk_body(chunk, carry):
        b, state = carry[0], carry[1:]
        last = chunk + 1 >= n_mine
        nxt_slot = jnp.where(last, slot + 1, slot)
        nxt_chunk = jnp.where(last, 0, chunk + 1)

        @pl.when(nxt_slot < slots)
        def _():
            start(nxt_slot, nxt_chunk, 1 - b)

        wait(slot, chunk, b)
        state = lax.cond(
            pages_in(slot, chunk) > 0,
            lambda: _attend(
                q_ref, kbuf, vbuf, b, len_ref[slot] - chunk * cols, state,
                lane_rows=lane_rows, rows=rows,
            ),
            lambda: state,
        )
        return (1 - b,) + tuple(state)

    # The new token's own term: probability exp(0) against its own V
    # row, whole lane rows of it under every query of the lane row.
    own = jnp.concatenate([
        jnp.broadcast_to(v_own_ref[j:j + 1, :], (rows, LANES))
        for j in range(lane_rows)
    ], axis=0)
    b, _, l, acc = lax.fori_loop(
        0, n_mine, chunk_body,
        (parity[0], s_own_ref[...], jnp.ones_like(s_own_ref[...]), own),
    )
    parity[0] = b
    o_ref[...] = acc / l


def pool_flat_decode_attention(
    q,             # [b, J, R, 128] — ONE token's placed queries a slot
    k_own,         # [b, J, 128] — that token's own K / V, flat, by lane
    v_own,         #   row; not yet in the pool (the append-free step)
    k_pool,        # [layers, num_blocks, block_size, J * 128]
    v_pool,
    layer,         # [] int32 — which attention layer of the stacked pool
    block_tables,  # [b, max_blocks] int32
    length,        # [b] int32 — filled logical rows per slot
    active=None,   # [b] bool — a slot that is not active reads nothing
    *,
    scale: float,
    interpret=None,
):
    """The decode step's attention of a model whose pool holds FLAT K/V
    rows (``serving/kvpool/conv.py``), read from the stacked pool IN
    PLACE: the softmax of every slot's queries over its cached rows
    below ``length`` and its own new row, and the probabilities' sum of
    the V rows, without the gathered ``[slots, max_len]`` views, without
    a score in HBM and without the rows past a slot's fill
    (:func:`_kernel`).

    ``q`` is laid over LANE ROWS (``conv._placed``): ``J`` lane rows of
    a flat row, ``R`` queries a lane row, each in its own head's lanes
    and zeros elsewhere, so its dot with a lane row of a key is its dot
    with its head's key; the answer ``[b, J, R, 128]`` float32 is every
    query's weighted sum of whole lane rows of V (``conv._own_lanes``
    reads each head's own lanes back). The pools go into the kernel
    whole (``memory_space=ANY``) as the device holds them; the layer,
    the per-slot page counts and fills and the flattened tables ride as
    scalar prefetch and pick the pages, each one contiguous DMA of
    ``block_size x kv_width``. A slot stops at its last filled page and
    an inactive one copies nothing; rows of that page past the fill are
    masked in VMEM.

    Arithmetic is no less than the gathered form's: K and V as stored,
    the scaled query, logits, running max, sum, probabilities and
    accumulator float32, and the float32 operands meet the bf16 ones
    unrounded (``ops/decode_attention._split_bf16``), where the gathered
    form rounds its probabilities to the rows' dtype before they meet V.
    What differs besides is the order of summation: an online softmax
    over chunks of ``CHUNK_BYTES`` of pages, opened by the new token's
    own term. An inactive slot's answer is its own V row (finite, and
    discarded by the caller)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, lane_rows, n_q, width = q.shape
    n_layers, nb_pool, block_size, kv_width = k_pool.shape
    if (width, lane_rows * width) != (LANES, kv_width):
        raise ValueError(
            f"queries over {lane_rows} lane rows of {width} against flat "
            f"rows of {kv_width}: a lane row is {LANES} lanes"
        )
    _, max_blocks = block_tables.shape
    # Off the chip (interpret mode, float32 pools) any page goes; on it
    # the caller asked flat_kernel_supported, so at least one page fits.
    chunk_pages = max(1, _chunk_pages(block_size, kv_width))
    f32 = jnp.float32
    # The gathered form's own term, to the letter: operands as they
    # come, products summed in float32, then the scale.
    s_own = jnp.einsum(
        "bjrw,bjw->bjr", q, k_own.astype(q.dtype),
        precision=lax.Precision.HIGHEST, preferred_element_type=f32,
    ) * scale
    rows = _query_rows(n_q)
    pad = ((0, 0), (0, 0), (0, rows - n_q), (0, 0))
    q32 = jnp.pad(q.astype(f32) * scale, pad).reshape(b, -1, width)
    s_own = jnp.pad(s_own[..., None], pad).reshape(b, -1, 1)
    fill = jnp.asarray(length, jnp.int32)
    if active is not None:
        fill = jnp.where(active, fill, 0)
    fill = jnp.clip(fill, 0, max_blocks * block_size)
    scalars = (
        jnp.asarray(layer, jnp.int32).reshape(1),
        (fill + block_size - 1) // block_size,
        fill,
        jnp.asarray(block_tables, jnp.int32).reshape(-1),
    )

    def a_slot(*dims):
        return pl.BlockSpec(
            (None,) + dims, lambda s, *_: (s,) + (0,) * len(dims)
        )

    cols = chunk_pages * block_size
    out = pl.pallas_call(
        functools.partial(
            _kernel, chunk_pages=chunk_pages, block_size=block_size,
            lane_rows=lane_rows, rows=rows, max_blocks=max_blocks,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(b,),
            in_specs=[
                a_slot(lane_rows * rows, width),
                a_slot(lane_rows * rows, 1),
                a_slot(lane_rows, width),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=a_slot(lane_rows * rows, width),
            scratch_shapes=[
                pltpu.VMEM((2, cols, kv_width), k_pool.dtype),
                pltpu.VMEM((2, cols, kv_width), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, lane_rows * rows, width), f32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_BYTES,
        ),
        interpret=interpret,
        name="paged_flat_decode_attention",
    )(*scalars, q32, s_own, v_own.astype(f32), k_pool, v_pool)
    return out.reshape(b, lane_rows, rows, width)[:, :, :n_q]


# ---- a prefill chunk's attention over the flat pool, in place -------------

# (imported down here: no line above the decode kernel's call site moves)
from dlrover_tpu.ops.decode_attention import _page_stream  # noqa: E402

# Bytes of K (and of V) of the prefix one inner step of the CHUNK kernel
# copies and attends: the score tile of a lane row is ``[keys of such a
# chunk, token tile x queries a token]`` float32, so the chunk is sized
# with the tile (``kvpool/conv.CHUNK_TOKEN_TILE``), not by its DMAs. On
# the v5e at the ``lfm2-serve-sessions-8k`` shape (512 launched rows
# over 8,512 cached, one layer, every row valid;
# ``tools/bench_paged_decode.py --parts conv``, my chip run, PR 50): at a
# tile of 128 tokens 256 KB / 512 KB / 1 MB take 0.830 / 0.812 / 0.846
# ms a call, at 64 tokens 0.924 / 0.876 / 0.879, where the gathered form
# takes 6.166 and the kernel's copies and launch alone 0.25-0.36.
CHUNK_PREFIX_BYTES = 512 << 10

_TN = (((0,), (0,)), ((), ()))      # [n, d] x [n, m] -> [d, m]


def _prefix_pages(block_size: int, kv_width: int) -> int:
    """Pages of a bf16 pool in one VMEM chunk of the chunk kernel: as
    many whole pages as ``CHUNK_PREFIX_BYTES`` holds; 0 where not even
    one fits."""
    return CHUNK_PREFIX_BYTES // (block_size * kv_width * 2)


def _chunk_vmem_bytes(block_size: int, kv_width: int, rows: int,
                      chunk: int, tile: int) -> int:
    """An upper reckoning of the chunk kernel's VMEM at a bf16 pool: the
    four page buffers, the chunk's own K and V (pipelined blocks: two
    buffers each), a tile's queries and answer, the running statistics
    and accumulator, and the live score tiles of one lane row."""
    lane_rows = kv_width // LANES
    cols = max(_prefix_pages(block_size, kv_width) * block_size, tile)
    q_rows = tile * rows
    return (
        4 * cols * kv_width * 2                   # K, V double-buffered
        + 2 * 2 * chunk * kv_width * 2            # own K, V
        + 2 * 2 * lane_rows * q_rows * LANES * 2  # q in, answer out
        + lane_rows * q_rows * (2 * 8 + LANES) * 4    # max, sum, acc
        + 4 * cols * q_rows * 4                   # scores, probabilities
    )


def flat_chunk_kernel_supported(pool_dtype, block_size: int, kv_width: int,
                                lane_row: int, rows: int, chunk: int,
                                tile: int, max_blocks: int) -> bool:
    """Shapes :func:`pool_flat_chunk_attention` lowers for on a TPU: a
    bf16 pool whose page ``[block_size, kv_width]`` is whole (16, 128)
    tiles, one contiguous DMA, and fits a VMEM chunk, a flat row of
    whole lane rows that each hold whole heads (``lane_row`` is 128), a
    ``chunk`` of whole token tiles of ``tile`` tokens whose ``rows``
    placed queries a token make whole 128-lane blocks of a score tile's
    columns (and whole (16, 128) tiles of the chunk's own rows), buffers
    that fit the VMEM the kernel asks for, and a table that fits the
    scalar memory."""
    return bool(
        jnp.dtype(pool_dtype) == jnp.bfloat16
        and lane_row == LANES and kv_width % LANES == 0
        and block_size % 16 == 0
        and _prefix_pages(block_size, kv_width) >= 1
        and tile > 0 and chunk % tile == 0 and tile % 16 == 0
        and (tile * rows) % LANES == 0
        and _chunk_vmem_bytes(block_size, kv_width, rows, chunk, tile)
        <= VMEM_BYTES
        and max_blocks * 4 <= SMEM_TABLE_BYTES
    )


def _dot_as_stored(a, b, dims):
    """bf16 by bf16 is exact in float32: one MXU pass. Anything else
    (interpret mode on float32 pools) is a float32 contraction."""
    if a.dtype == b.dtype == jnp.bfloat16:
        return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)
    return lax.dot_general(
        a.astype(jnp.float32), b.astype(jnp.float32), dims,
        precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _attend_keys(q_ref, m_ref, l_ref, acc_ref, j, k, v, visible, scale):
    """One online-softmax step of lane row ``j``'s placed queries
    (``q_ref[j]`` ``[rows, 128]``, as they come) over keys ``k`` /
    values ``v`` ``[n, 128]``, lane row ``j`` of ``n`` flat rows as
    stored; ``visible`` ``[n, rows]``, or None where every key is.
    Scores lie KEYS x QUERIES (``ops/decode_attention._online_softmax``'s
    reasons: the reductions run down the sublanes and the statistics
    ``m_ref`` / ``l_ref`` ``[J, 1, rows]`` are lane-dense rows; the
    accumulator ``acc_ref`` is ``[J, 128, rows]``), float32, scaled
    where the definition scales them; the probabilities are rounded to
    the rows' dtype once before they meet V, as the definition rounds
    them. A masked key's probability is exp(NEG_INF - m) == 0 once the
    running max is a real logit, and every caller shows every query a
    key in every call."""
    s = _dot_as_stored(k, q_ref[j], _NT) * scale       # [n, rows]
    if visible is not None:
        s = jnp.where(visible, s, NEG_INF)
    m_prev = m_ref[j]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[j] = alpha * l_ref[j] + jnp.sum(p, axis=0, keepdims=True)
    acc_ref[j] = acc_ref[j] * alpha + _dot_as_stored(
        v, p.astype(v.dtype), _TN
    )
    m_ref[j] = m_new


def _chunk_kernel(
    layer_ref, start_ref, valid_ref, tbl_ref,     # scalar prefetch
    q_ref, kn_ref, vn_ref, k_hbm, v_hbm,          # inputs
    o_ref,                                        # output
    kbuf, vbuf, sem, m_ref, l_ref, acc_ref,       # scratch
    *, chunk_pages: int, block_size: int, lane_rows: int, rows: int,
    tile: int, scale: float,
):
    """One call = one attention layer's attention for one slot's prefill
    chunk; one grid step = ``tile`` of its tokens, every lane row.

    A tile whose first token is at or past ``valid_ref`` (the padding of
    a short chunk) copies no page, runs no matmul and answers ZEROS: its
    rows are never read as queries' answers, but they ride on through
    the later layers and land in the pool, so nothing non-finite may
    leave here. Any other tile runs two key groups through one online
    softmax a (lane row, query row) (:func:`_attend_keys`):

    - the slot's rows ``[0, start)``, page by page from the pools in
      HBM (``ops/decode_attention._page_stream``, the chunk kernels'
      copies: a page one contiguous DMA into double-buffered VMEM
      chunks of ``chunk_pages`` pages, the next chunk in flight while
      this one is attended), every row visible to every query, the last
      chunk masked at ``start``;
    - the chunk's own rows up to the tile's last, from VMEM, the tiles
      before this one whole and this one causally.

    Query rows lie ``[lane_rows, tile * rows, 128]``, row ``r`` of a
    lane row is token ``r // rows``. Lane row ``j`` of the keys is a
    static, lane-aligned slice of a buffer as stored. A chunk of pages
    is attended only if it holds a row below ``start`` and a token sees
    itself, so the running max is a real logit after the first call."""
    step = pl.program_id(0)
    layer = layer_ref[0]
    start = start_ref[0]
    cols = chunk_pages * block_size
    q_rows = tile * rows
    n_pages = (start + block_size - 1) // block_size
    n_chunks = (n_pages + chunk_pages - 1) // chunk_pages
    lanes = lambda j: slice(j * LANES, (j + 1) * LANES)  # noqa: E731

    start_copies, wait_copies, _ = _page_stream(
        layer, tbl_ref, n_pages, k_hbm, v_hbm, kbuf, vbuf, sem,
        chunk_pages=chunk_pages, page_rows=block_size,
    )

    def attend(keys, values, visible):
        for j in range(lane_rows):
            _attend_keys(
                q_ref, m_ref, l_ref, acc_ref, j, keys(j), values(j),
                visible, scale,
            )

    @pl.when(step == 0)
    def _():
        # Finite wherever a page copy has not written (0 x NaN).
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

    @pl.when(step * tile >= valid_ref[0])
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(step * tile < valid_ref[0])
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(n_chunks > 0)
        def _():
            start_copies(0, 0)

        def chunk_body(chunk, b):
            @pl.when(chunk + 1 < n_chunks)
            def _():
                start_copies(chunk + 1, 1 - b)

            wait_copies(chunk, b)
            filled = start - chunk * cols       # rows of it below start
            stored = (
                lambda j: kbuf[b, 0, :, lanes(j)],
                lambda j: vbuf[b, 0, :, lanes(j)],
            )

            # Only the prefix's last chunk has rows to hide (what its
            # page copies left of an earlier chunk, and a last page's
            # rows past ``start``): every other one skips the mask.
            @pl.when(filled >= cols)
            def _():
                attend(*stored, None)

            @pl.when(filled < cols)
            def _():
                key = lax.broadcasted_iota(jnp.int32, (cols, q_rows), 0)
                attend(*stored, key < filled)

            return 1 - b

        lax.fori_loop(0, n_chunks, chunk_body, 0)

        def own(tile_at, visible):
            at = pl.ds(pl.multiple_of(tile_at * tile, tile), tile)
            attend(
                lambda j: kn_ref[at, lanes(j)], lambda j: vn_ref[at, lanes(j)],
                visible,
            )

        def before(tile_at, carry):
            own(tile_at, None)
            return carry

        lax.fori_loop(0, step, before, 0)
        # This tile's own keys: token u is visible to token t iff u <= t.
        token = lax.broadcasted_iota(jnp.int32, (tile, q_rows), 1) // rows
        own(step, lax.broadcasted_iota(jnp.int32, (tile, q_rows), 0) <= token)
        for j in range(lane_rows):
            o_ref[j] = (acc_ref[j] / l_ref[j]).T.astype(o_ref.dtype)


def pool_flat_chunk_attention(
    q,             # [T, J, R, 128] — one slot's chunk, placed queries
    k_own,         # [T, J * 128] — the chunk's own K / V rows, flat;
    v_own,         #   not yet in the pool
    k_pool,        # [layers, num_blocks, block_size, J * 128]
    v_pool,
    layer,         # [] int32 — which attention layer of the stacked pool
    table_row,     # [max_blocks] int32 — the slot's pages
    start,         # [] int32 — rows of the slot already filled: [0, start)
    n_valid=None,  # [] int32 — tokens at or past it are padding
    *,
    scale: float,
    tile: int,
    interpret=None,
):
    """A prefill chunk's attention of a model whose pool holds FLAT K/V
    rows (``serving/kvpool/conv.py``), the pool read IN PLACE: the
    softmax of the chunk's queries (positions ``start + t``) over the
    slot's rows below ``start`` and over the chunk's own rows causally,
    and the probabilities' sum of the V rows, without a gathered prefix,
    without a score in HBM, and only for the token tiles (``tile``
    tokens a grid step) that hold one of the chunk's ``n_valid`` tokens
    (None: all of them): a tile past them answers exact zeros
    (:func:`_chunk_kernel`).

    ``q`` is laid over LANE ROWS (``conv._placed``) as
    :func:`pool_flat_decode_attention` takes it, one token after the
    other; the answer ``[T, J, R, 128]`` in ``q.dtype`` is every query's
    weighted sum of whole lane rows of V (``conv._own_lanes`` reads each
    head's own lanes back). The pools go in whole (``memory_space=ANY``)
    as the device holds them; layer, ``start``, ``n_valid`` and the
    table ride as scalar prefetch and pick the pages below ``start``,
    each one contiguous DMA, once a tile.

    Arithmetic is the definition's (``conv.chunk_attend``'s gathered
    form): queries, K and V as they come, bf16 by bf16 into float32
    logits, the scale on the logits, float32 running max, sum and
    accumulator, the probabilities rounded to the rows' dtype once
    before they meet V, no row dropped. What differs is the order of
    summation: an online softmax over chunks of ``CHUNK_PREFIX_BYTES``
    of pages and then the chunk's own rows a tile at a time, where the
    definition walks blocks of ``conv.CHUNK_PREFIX_ROWS``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    t, lane_rows, n_q, width = q.shape
    _, _, block_size, kv_width = k_pool.shape
    if (width, lane_rows * width) != (LANES, kv_width):
        raise ValueError(
            f"queries over {lane_rows} lane rows of {width} against flat "
            f"rows of {kv_width}: a lane row is {LANES} lanes"
        )
    if t % tile:
        raise ValueError(f"a chunk of {t} tokens in tiles of {tile}")
    max_blocks = table_row.shape[0]
    # Off the chip (interpret mode, float32 pools) any page goes; on it
    # the caller asked flat_chunk_kernel_supported.
    chunk_pages = max(1, _prefix_pages(block_size, kv_width))
    cols = chunk_pages * block_size
    q_rows = tile * n_q
    # [T, J, R, 128] -> [J, T * R, 128]: a lane row's query rows
    # together, token-major, so that a token tile is a run of rows.
    qs = q.transpose(1, 0, 2, 3).reshape(lane_rows, t * n_q, width)
    i32 = jnp.int32
    scalars = (
        jnp.asarray(layer, i32).reshape(1),
        jnp.clip(jnp.asarray(start, i32), 0, max_blocks * block_size)
        .reshape(1),
        jnp.asarray(t if n_valid is None else n_valid, i32).reshape(1),
        jnp.asarray(table_row, i32),
    )
    tiled = pl.BlockSpec(
        (lane_rows, q_rows, width), lambda i, *_: (0, i, 0)
    )
    whole = pl.BlockSpec((t, kv_width), lambda i, *_: (0, 0))
    f32 = jnp.float32
    out = pl.pallas_call(
        functools.partial(
            _chunk_kernel, chunk_pages=chunk_pages, block_size=block_size,
            lane_rows=lane_rows, rows=n_q, tile=tile, scale=scale,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(t // tile,),
            in_specs=[
                tiled, whole, whole,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=tiled,
            scratch_shapes=[
                # (two buffers, ONE lane group: a page is copied whole)
                pltpu.VMEM((2, 1, cols, kv_width), k_pool.dtype),
                pltpu.VMEM((2, 1, cols, kv_width), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((lane_rows, 1, q_rows), f32),
                pltpu.VMEM((lane_rows, 1, q_rows), f32),
                pltpu.VMEM((lane_rows, width, q_rows), f32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qs.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_BYTES,
        ),
        interpret=interpret,
        name="paged_flat_chunk_attention",
    )(*scalars, qs, k_own, v_own, k_pool, v_pool)
    return out.reshape(lane_rows, t, n_q, width).transpose(1, 0, 2, 3)
