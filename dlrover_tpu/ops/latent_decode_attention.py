"""A latent model's decode attention over the PACKED pool, in place.

One function, :func:`pool_latent_decode_attention` (Pallas), and the
predicate that says where it lowers (:func:`latent_kernel_supported`):
the attention of ``serving/kvpool/latent.py``'s decode step on a TPU.
One query token a slot, absorbed (``latent_lm.absorb_queries``), against
the ONE array a latent model's pool holds, ``[layers, num_blocks,
page_rows, pack * cache_width]`` (``kvpool/index_pool.IndexKeyPool``:
``pack`` tokens to a device row), read where it lies: a slot's filled
pages only, a page a DMA, scores, softmax and the weighted sum of the
rows in VMEM. The gathered ``[slots, max_len]`` view it replaces wrote
the rows once and read them twice, and walked float32 scores through HBM
in five passes (PERF.md section 6, PRs 38 and 40).

A module of its own, not ``ops/decode_attention.py``'s: the latent page
shares no operand layout with the dense and sparse kernels there (no K /
V split, two tokens to a row, a query operand laid into lanes, an
accumulator as wide as the row, a softmax opened by a row that is not in
the pool), and those kernels' programs stay byte for byte what they were
when this one changes. ``kvpool/latent.decode_attention_kind`` picks
between it and the gathered form from what it can see; nothing here
reads the environment.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# A masked key's score: exp(NEG_INF - m) is exactly 0 and no inf - inf
# can arise (``ops/decode_attention.py``'s value).
NEG_INF = -1e30

# Device rows of the pool one inner step of the kernel copies and attends
# (double-buffered: two such buffers in VMEM). Sized in rows: they are
# the lane dimension of the step's score tile, so a tile is whole
# 128-lane blocks of them; at the ``xing-serve-sessions-16k`` shape
# (32-row pages of 1,152 lanes) 1,024 rows are 32 pages, 2.36 MB. On the
# v5e at 32 slots x ~16.6k tokens, one layer, absorb and ``w_kvb``'s
# value half around the call (``tools/bench_paged_decode.py --parts
# latent``, PR 39's builder's chip run): 128 / 256 / 512 / 1,024 / 2,048
# rows take 2.44 / 1.90 / 1.68 / 1.54 / 1.63 ms where the gathered form
# takes 7.91.
TILE_ROWS = 1024
# What the kernel may use of the core's VMEM (128 MiB on a v5e; the
# compiler's own default scope is 16 MB).
VMEM_BYTES = 32 << 20
# Scalar memory the prefetched tables may take: every slot's table rides
# there whole (the next slot's first pages are asked for while this
# slot's last are attended), 35 KB at the engine's 32 slots x 272 pages.
# The core has 1 MB: 768 KB compiled for the described v5e, 1 MB did
# not.
SMEM_TABLE_BYTES = 768 << 10


def _tile_pages(page_rows: int) -> int:
    """Pages of the packed pool in one VMEM tile: as many whole pages as
    ``TILE_ROWS`` device rows hold, one at least."""
    return max(1, TILE_ROWS // page_rows)


def _vmem_bytes(page_rows: int, lanes: int, heads: int, pack: int,
                itemsize: int) -> int:
    """An upper reckoning of the kernel's VMEM: the two tile buffers,
    the wide accumulator, a slot's pipelined blocks (placed queries, own
    row, answer: two buffers each) and the live score tiles."""
    rows = _tile_pages(page_rows) * page_rows
    hp = pack * heads
    return (
        2 * rows * lanes * itemsize               # the tile, double-buffered
        + hp * lanes * 4                          # accumulator
        + 2 * hp * lanes * itemsize               # [q | 0], [0 | q]
        + 2 * (8 * lanes * 4 + 2 * heads * lanes * 4)   # own row, answer
        + 8 * hp * rows * 4                       # scores, probabilities
    )


def latent_kernel_supported(pool_dtype, page_rows: int, lanes: int,
                            heads: int, pack: int, slots: int,
                            max_blocks: int) -> bool:
    """Shapes :func:`pool_latent_decode_attention` lowers for on a TPU:
    a bf16 pool whose page ``[page_rows, lanes]`` (``pack`` tokens to a
    row) is whole (16, 128) tiles and one contiguous DMA, a tile of
    whole pages that is whole 128-lane blocks of rows, query rows
    (``pack * heads``) of whole bf16 tiles whose halves are whole
    float32 ones, buffers that fit the VMEM the kernel asks for, and
    tables that fit the scalar memory."""
    return bool(
        jnp.dtype(pool_dtype) == jnp.bfloat16
        and lanes % 128 == 0
        and page_rows % 16 == 0
        and TILE_ROWS % page_rows == 0
        and heads % 8 == 0 and (pack * heads) % 16 == 0
        and _vmem_bytes(page_rows, lanes, heads, pack, 2) <= VMEM_BYTES
        and slots * max_blocks * 4 <= SMEM_TABLE_BYTES
    )


_NT = (((1,), (1,)), ((), ()))      # [m, d] x [n, d] -> [m, n]
_NN = (((1,), (0,)), ((), ()))      # [m, n] x [n, d] -> [m, d]


def _dot(a, b, dims):
    """Products summed in float32. bf16 by bf16 is exact in f32: one MXU
    pass. Anything else (interpret mode on an f32 pool) is an f32
    contraction."""
    if a.dtype == b.dtype == jnp.bfloat16:
        return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)
    return lax.dot_general(
        a.astype(jnp.float32), b.astype(jnp.float32), dims,
        precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _products(queries, rows):
    """The default ``scores`` of :func:`pool_latent_decode_attention`:
    ``queries [m, w]`` against ``rows [n, w]`` as stored, float32."""
    return _dot(queries, rows, _NT)


def _round_mantissa(x, *, exponent_bits: int, mantissa_bits: int):
    """``lax.reduce_precision`` of a float32 that keeps its 8 exponent
    bits: the mantissa rounded to nearest, ties to even, on the bits."""
    if x.dtype != jnp.float32 or exponent_bits != 8 or not (
        0 < mantissa_bits < 23
    ):
        raise NotImplementedError(
            f"reduce_precision({x.dtype}, {exponent_bits}, {mantissa_bits}) "
            "inside a TPU kernel"
        )
    drop = 23 - mantissa_bits
    bits = lax.bitcast_convert_type(x, jnp.uint32)
    bits = bits + ((bits >> drop) & 1) + ((1 << (drop - 1)) - 1)
    bits = bits & jnp.uint32(0xFFFFFFFF ^ ((1 << drop) - 1))
    return lax.bitcast_convert_type(bits, jnp.float32)


def _teach_mosaic_reduce_precision():
    """Pallas's TPU lowering (JAX 0.9) has no rule for
    ``lax.reduce_precision``, and ``scores`` is the caller's to state: a
    caller that states a LOWER precision with it would not lower. The
    harness's bfloat16-scores control does exactly that
    (``benchmark/controls_xing.py`` plants it in
    ``kvpool/latent._scores``), to show that the cell's check reads the
    scores the kernel attends with; so the rule is given here, where the
    one kernel that takes such a function is, and only if JAX has none."""
    from jax._src.pallas.mosaic import lowering

    rules = lowering.lowering_rules[lowering.tpu_core.KernelType.TC]
    if lax.reduce_precision_p in rules:
        return

    @lowering.register_lowering_rule(lax.reduce_precision_p)
    def _(ctx, x, **precision):
        return lowering.lower_fun(
            functools.partial(_round_mantissa, **precision),
            multiple_results=False,
        )(ctx, x)


_teach_mosaic_reduce_precision()


def _kernel(
    layer_ref, pages_ref, len_ref, tbl_ref,       # scalar prefetch
    q_ref, s_own_ref, own_ref, pool_hbm,          # inputs
    *rest,
    tile_pages: int, page_rows: int, heads: int, pack: int, width: int,
    rank: int, scale: float, max_blocks: int, n_tiles: int, scores,
    raw_scores: bool,
):
    """One call = one layer's decode attention of a latent model for
    every slot; one grid step = one slot. The pool stays in HBM; a
    slot's filled pages are copied page by page (one contiguous DMA
    each) into a double-buffered VMEM tile of ``tile_pages`` pages, the
    next tile — of this slot or of the next — in flight while this one
    is computed, as ``ops/decode_attention._pool_kernel`` has it.

    A tile is ``[rows, pack * width]`` AS STORED: ``pack`` tokens to a
    device row, token ``j`` of a row in lanes ``j * width ...``. The
    slot's queries come laid into each token's lanes of a row-wide
    operand (``[q | 0]``, ``[0 | q]``: ``q_ref [pack * heads, pack *
    width]``, row ``j * heads + h`` is head ``h`` against token ``j`` of
    every row), so scores are one matmul against the rows as they lie
    and no half of a row is cut out mid lane block; the probabilities
    meet the same tile in one more, ``[pack * heads, pack * width]``
    float32 accumulated, and a token's latent is read back from its own
    lanes once, after the slot's last tile. The softmax runs over a
    head's ``pack`` rows together and is opened by the query's own new
    row (``s_own_ref``, ``own_ref``), so its running max is a real
    logit from the start.

    ``raw_scores``: a second output ``[slots, pack * heads, n_tiles *
    rows]`` float32 in HBM takes each tile's scores before the scale,
    zero where a key is not visible or a tile was not read (a check's
    probe; the served program has no such output)."""
    if raw_scores:
        o_ref, raw_hbm, buf, sem, parity, acc_ref, raw_buf, raw_sem = rest
    else:
        o_ref, buf, sem, parity, acc_ref = rest
    slot = pl.program_id(0)
    slots = pl.num_programs(0)
    layer = layer_ref[0]
    rows = tile_pages * page_rows
    hp = pack * heads
    lanes = pack * width

    def pages_in(slot, tile):
        return jnp.clip(pages_ref[slot] - tile * tile_pages, 0, tile_pages)

    def page_copy(slot, tile, b, i):
        blk = tbl_ref[slot * max_blocks + tile * tile_pages + i]
        dst = pl.ds(pl.multiple_of(i * page_rows, page_rows), page_rows)
        return pltpu.make_async_copy(
            pool_hbm.at[layer, blk], buf.at[b, dst], sem.at[b]
        )

    def start(slot, tile, b):
        def body(i, carry):
            page_copy(slot, tile, b, i).start()
            return carry

        lax.fori_loop(0, pages_in(slot, tile), body, 0)

    def wait(slot, tile, b):
        def body(i, carry):
            page_copy(slot, tile, b, i).wait()
            return carry

        lax.fori_loop(0, pages_in(slot, tile), body, 0)

    @pl.when(slot == 0)
    def _():
        # What a page copy has not filled must still be FINITE: a
        # masked key's probability is exactly 0, and 0 x NaN would
        # poison the weighted sum.
        buf[...] = jnp.zeros_like(buf)
        parity[0] = 0
        start(0, 0, 0)

    # Column c of row j * heads + h of a score tile is token c * pack +
    # j of the tile.
    token = (
        lax.broadcasted_iota(jnp.int32, (hp, rows), 1) * pack
        + lax.broadcasted_iota(jnp.int32, (hp, rows), 0) // heads
    )

    def per_head(x, op):
        """``x [pack * heads, 1]`` -> ``[heads, 1]``: a head's ``pack``
        rows under ``op``."""
        return functools.reduce(
            op, [x[j * heads:(j + 1) * heads] for j in range(pack)]
        )

    def all_rows(x):
        """``x [heads, 1]`` -> ``[pack * heads, 1]``."""
        return jnp.concatenate([x] * pack, axis=0)

    def send_raw(tile, tile_scores):
        raw_buf[...] = tile_scores
        cp = pltpu.make_async_copy(
            raw_buf,
            raw_hbm.at[slot, :, pl.ds(pl.multiple_of(tile * rows, rows), rows)],
            raw_sem.at[0],
        )
        cp.start()
        cp.wait()

    def attend(tile, b, m, l):
        stored = buf[b]
        raw = scores(q_ref[...], stored)
        # Visibility: token < the slot's fill (a fill may end on the
        # first token of a row: its second is hidden).
        visible = token < len_ref[slot] - tile * rows * pack
        if raw_scores:
            send_raw(tile, jnp.where(visible, raw, 0.0))
        s = jnp.where(visible, raw * scale, NEG_INF)
        m_new = jnp.maximum(
            m, per_head(jnp.max(s, axis=-1, keepdims=True), jnp.maximum)
        )
        p = jnp.exp(s - all_rows(m_new))   # masked: exp(-1e30 - m) == 0
        alpha = jnp.exp(m - m_new)
        l = l * alpha + per_head(
            jnp.sum(p, axis=-1, keepdims=True), jnp.add
        )
        # a token's probability against its row AS STORED (rounded to
        # the rows' dtype once, as the gathered form rounds it)
        acc_ref[...] = acc_ref[...] * all_rows(alpha) + _dot(
            p.astype(stored.dtype), stored, _NN
        )
        return m_new, l

    n_read = (pages_ref[slot] + tile_pages - 1) // tile_pages
    # a slot with no page still takes one turn of the loop: it is there
    # that the next slot's first tile is asked for
    n_mine = jnp.maximum(n_read, 1)
    # The query's own row opens the online softmax with probability
    # exp(0) against its latent, in token 0's lanes of the first head
    # rows.
    acc_ref[:heads] = jnp.broadcast_to(own_ref[...], (heads, lanes))
    if pack > 1:
        acc_ref[heads:] = jnp.zeros((hp - heads, lanes), jnp.float32)

    def tile_body(tile, carry):
        b, m, l = carry
        last = tile + 1 >= n_mine
        nxt_slot = jnp.where(last, slot + 1, slot)
        nxt_tile = jnp.where(last, 0, tile + 1)

        @pl.when(nxt_slot < slots)
        def _():
            start(nxt_slot, nxt_tile, 1 - b)

        wait(slot, tile, b)
        m, l = lax.cond(
            pages_in(slot, tile) > 0,
            lambda: attend(tile, b, m, l),
            lambda: (m, l),
        )
        return 1 - b, m, l

    b, _, l = lax.fori_loop(
        0, n_mine, tile_body,
        (parity[0], s_own_ref[...], jnp.ones((heads, 1), jnp.float32)),
    )
    parity[0] = b
    acc = acc_ref[...]
    o_ref[...] = sum(
        acc[j * heads:(j + 1) * heads, j * width:j * width + rank]
        for j in range(pack)
    ) / l

    if raw_scores:
        # tiles of the slot's table that no page copy reached
        def blank(tile, carry):
            send_raw(tile, jnp.zeros((hp, rows), jnp.float32))
            return carry

        lax.fori_loop(n_read, n_tiles, blank, 0)


def pool_latent_decode_attention(
    q,             # [b, heads, width] — ONE absorbed query a slot
    own,           # [b, width] — that token's own row, not yet in the pool
    pool_rows,     # [layers, num_blocks, page_rows, pack * width]
    layer,         # [] int32 — which layer of the stacked pool
    block_tables,  # [b, max_blocks] int32
    length,        # [b] int32 — filled logical rows (tokens) per slot
    *,
    rank: int,     # leading lanes of a token's row that are its latent
    scale: float,
    scores=_products,
    raw_scores: bool = False,
    interpret=None,
):
    """A latent model's decode attention (``serving/kvpool/latent.py``)
    with the packed pool read IN PLACE: the softmax of every slot's
    absorbed queries over its cached rows below ``length`` and its own
    new row, and the probabilities' sum of the rows' latents (lanes
    ``[:rank]`` of a token's ``width``), without the gathered ``[slots,
    max_len]`` view, without a score in HBM and without the rows past a
    slot's fill (:func:`_kernel`).

    The pool goes into the kernel whole (``memory_space=ANY``) as the
    device holds it, ``pack`` tokens to a row; the layer, the per-slot
    page counts and fills and the flattened tables ride as scalar
    prefetch. A slot stops at its last filled page, rows of that page
    past the fill are masked in VMEM, and a slot with nothing to read
    copies nothing and answers with its own row's latent.

    Arithmetic is the gathered form's: rows and queries as stored,
    products summed in float32 (``scores(queries [m, w], rows [n, w]) ->
    float32 [m, n]`` forms a tile's scores and the own row's: the
    caller's statement of that precision), the scale, running max, sum
    and accumulator float32, the probabilities rounded to the rows'
    dtype once before they meet the rows. What differs is the order of
    summation: an online softmax over tiles of ``TILE_ROWS`` device
    rows, opened by the query's own row (whose probability stays
    float32). Returns float32 ``[b, heads, rank]``; with ``raw_scores``
    also the kernel's own scores ``[b, heads, max_len]`` float32 before
    the scale, zero where a key is not visible."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, heads, width = q.shape
    _, _, page_rows, lanes = pool_rows.shape
    pack = lanes // width
    _, max_blocks = block_tables.shape
    block_size = page_rows * pack
    tile_pages = _tile_pages(page_rows)
    rows = tile_pages * page_rows
    n_tiles = -(-max_blocks // tile_pages)
    hp = pack * heads
    q = q.astype(pool_rows.dtype)
    own = own.astype(pool_rows.dtype)
    # [q | 0], [0 | q]: the other lanes add exact zeros to a float32 sum
    placed = jnp.concatenate([
        jnp.pad(q, ((0, 0), (0, 0), (j * width, (pack - 1 - j) * width)))
        for j in range(pack)
    ], axis=1)
    s_own = jax.vmap(scores)(q, own[:, None]) * scale
    own_wide = jnp.pad(
        own.astype(jnp.float32), ((0, 0), (0, lanes - width))
    )[:, None]
    fill = jnp.clip(
        jnp.asarray(length, jnp.int32), 0, max_blocks * block_size
    )
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

    out_specs = [a_slot(heads, rank)]
    out_shape = [jax.ShapeDtypeStruct((b, heads, rank), jnp.float32)]
    scratch = [
        pltpu.VMEM((2, rows, lanes), pool_rows.dtype),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SMEM((1,), jnp.int32),
        pltpu.VMEM((hp, lanes), jnp.float32),
    ]
    if raw_scores:
        out_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        out_shape.append(
            jax.ShapeDtypeStruct((b, hp, n_tiles * rows), jnp.float32)
        )
        scratch += [
            pltpu.VMEM((hp, rows), jnp.float32),
            pltpu.SemaphoreType.DMA((1,)),
        ]
    out = pl.pallas_call(
        functools.partial(
            _kernel, tile_pages=tile_pages, page_rows=page_rows,
            heads=heads, pack=pack, width=width, rank=rank,
            scale=float(scale), max_blocks=max_blocks, n_tiles=n_tiles,
            scores=scores, raw_scores=raw_scores,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(b,),
            in_specs=[
                a_slot(hp, lanes), a_slot(heads, 1), a_slot(1, lanes),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_BYTES,
        ),
        interpret=interpret,
        name="paged_latent_decode_attention",
    )(*scalars, placed, s_own, own_wide, pool_rows)
    if not raw_scores:
        return out[0]
    mixed, raw = out
    # row j * heads + h, column c -> head h, token c * pack + j
    raw = raw.reshape(b, pack, heads, -1).transpose(0, 2, 3, 1)
    return mixed, raw.reshape(b, heads, -1)[..., :max_blocks * block_size]
