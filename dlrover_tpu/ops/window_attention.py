"""Attention over a paged pool under a LOWER bound as well: what a
sliding-window layer of ``models/window_lm.py`` runs over its group of
the pool (``serving/kvpool/window.py``).

A query at position ``i`` of such a layer sees key ``j`` iff ``0 <= i -
j <= reach`` (``reach`` = ``sliding_window - 1``: the rows below it a
query can still see). The pool's group keeps no row below that for long
(the engine releases a block once every row of it is out of reach, and
its table entry goes to the sentinel block), so a kernel here copies
only the pages that hold a visible row and masks the rest of the first
and the last of them:

- :func:`pool_window_decode_attention`: one query token a slot, pool
  rows ``[max(fill - reach, 0), fill)`` and the token's own K/V. It is
  ``ops.decode_attention._pool_kernel`` with each slot's page walk
  started at the band's first page and a second bound on the mask.
- :func:`pool_window_chunk_attention`: one slot's prefill chunk, token
  ``t`` over pool rows ``[start + t - reach, start)`` and the chunk's own
  rows ``u`` with ``0 <= t - u <= reach``. It is
  ``ops.decode_attention._chunk_kernel`` built from the same
  ``_page_stream`` and ``_online_softmax``, each token tile walking the
  pages of ITS band: a page wholly below every one of its queries' bands
  is not copied.

Both take the pool as ``[layers, num_blocks, block_size, kv_heads,
head_dim]`` and read it in place, as the kernels they are made from do;
the arithmetic is theirs (K and V as stored, float32 logits, running
max, sum and accumulator, the float32 operands unrounded). Off the chip
they run interpreted; :func:`window_reference` is the gathered
``jax.numpy`` form the tests hold them to, and
:func:`window_kernels_supported` says where they lower.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.decode_attention import (
    NEG_INF,
    _CHUNK_VMEM_BYTES,
    _chunk_scratch,
    _chunk_token_tile,
    _chunk_vmem_bytes,
    _dot_f32_by_stored,
    _head_rows,
    _online_softmax,
    _page_stream,
    _pool_chunk_pages,
)

# Scalar memory the prefetched tables may take (``ops/
# flat_decode_attention.py``'s reading: 768 KB compiled for the described
# v5e, 1 MB did not).
SMEM_TABLE_BYTES = 768 << 10
# What ``ops.decode_attention._chunk_vmem_bytes`` may reckon for a shape
# this module admits. That reckoning is an UPPER one (six live score
# tiles); at 8 query heads a KV head it reads 37 MB for the cell's chunk,
# whose kernels compile inside the 32 MB they ask for
# (``tests/test_tpu_compile.py`` holds both to that).
_VMEM_RECKONED_MAX = 40 << 20


def window_kernels_supported(pool_dtype, block_size: int, n_heads: int,
                             kv_heads: int, head_dim: int, chunk: int,
                             slots: int, max_blocks: int) -> bool:
    """Shapes both kernels of this module lower for on a TPU: a bf16
    pool whose page ``[block_size * kv_heads, head_dim]`` is whole (16,
    128) tiles and one contiguous DMA (8 KV heads a row, or 4: XLA lays
    ``[..., 4, 128]`` bf16 out in ``T(4,128)(2,1)`` tiles, two of which
    are one ``T(8,128)(2,1)`` tile of the collapsed rows, byte for byte:
    ``ops.decode_attention.sparse_chunk_kernel_supported``'s reading), a
    page no larger than a VMEM chunk, a chunk that splits into token
    tiles of whole sublanes, buffers that fit the VMEM the chunk kernel
    asks for, and tables that fit the scalar memory."""
    tile = _chunk_token_tile(chunk, n_heads)
    return bool(
        jnp.dtype(pool_dtype) == jnp.bfloat16
        and head_dim % 128 == 0
        and (kv_heads % 8 == 0 or kv_heads == 4)
        and n_heads % kv_heads == 0
        and (block_size * kv_heads) % 16 == 0
        and _pool_chunk_pages(block_size, kv_heads, head_dim, 1) == 1
        and tile
        and _chunk_vmem_bytes(
            block_size, n_heads, kv_heads, head_dim, chunk, tile, 2
        ) <= _VMEM_RECKONED_MAX
        and slots * max_blocks * 4 <= SMEM_TABLE_BYTES
    )


# ---- the definition ---------------------------------------------------------


def window_reference(q, k_new, v_new, k_view, v_view, q_pos, fill, reach):
    """The gathered form both kernels are held to: queries ``q [n, T,
    heads, d]`` at positions ``q_pos [n, T]`` over each row's logical
    cache ``k_view`` / ``v_view [n, S, kv_heads, d]`` (rows ``< fill
    [n]`` written) and over the ``T`` new tokens' own ``k_new`` / ``v_new
    [n, T, kv_heads, d]`` (positions ``q_pos``). Key at position ``j`` is
    visible to the query at ``i`` iff ``0 <= i - j <= reach``. The query
    is scaled in its own dtype, scores and softmax are float32, V meets
    the probabilities as stored. Returns ``[n, T, heads, d]`` in
    ``q.dtype``."""
    n, t, h, d = q.shape
    kh = k_view.shape[2]
    g = h // kh
    f32 = jnp.float32
    qg = (q * d ** -0.5).astype(f32).reshape(n, t, kh, g, d)
    rows = jnp.arange(k_view.shape[1])
    ahead = q_pos[:, :, None] - rows[None, None, :]           # [n, T, S]
    seen = (rows[None, None, :] < fill[:, None, None]) & (ahead <= reach)
    s_pool = jnp.einsum(
        "ntkgd,nskd->nkgts", qg, k_view.astype(f32),
        precision=jax.lax.Precision.HIGHEST,
    )
    s_pool = jnp.where(seen[:, None, None], s_pool, -jnp.inf)
    own = q_pos[:, :, None] - q_pos[:, None, :]               # [n, T, T]
    seen_own = (own >= 0) & (own <= reach)
    s_own = jnp.einsum(
        "ntkgd,nukd->nkgtu", qg, k_new.astype(f32),
        precision=jax.lax.Precision.HIGHEST,
    )
    s_own = jnp.where(seen_own[:, None, None], s_own, -jnp.inf)
    probs = jax.nn.softmax(jnp.concatenate([s_pool, s_own], -1), axis=-1)
    split = k_view.shape[1]
    out = jnp.einsum(
        "nkgts,nskd->ntkgd", probs[..., :split], v_view.astype(f32),
        precision=jax.lax.Precision.HIGHEST,
    ) + jnp.einsum(
        "nkgtu,nukd->ntkgd", probs[..., split:], v_new.astype(f32),
        precision=jax.lax.Precision.HIGHEST,
    )
    return out.reshape(n, t, h, d).astype(q.dtype)


# ---- the decode step --------------------------------------------------------


def _window_decode_kernel(
    layer_ref, page0_ref, pages_ref, lo_ref, len_ref, tbl_ref,  # prefetch
    q_ref, s_new_ref, v_new_ref, k_hbm, v_hbm,    # inputs
    o_ref,                                        # output
    kbuf, vbuf, sem,                              # scratch
    *, chunk_pages: int, page_rows: int, kv_heads: int, group: int,
    max_blocks: int,
):
    """``ops.decode_attention._pool_kernel`` over each slot's BAND: its
    page walk starts at table entry ``page0_ref[slot]`` (the page that
    holds the band's first row) and runs ``pages_ref[slot]`` pages; a
    row is visible iff ``lo_ref[slot] <= row < len_ref[slot]``."""
    slots, hp, _ = o_ref.shape
    layer = layer_ref[0]
    cols = chunk_pages * page_rows
    block_size = page_rows // kv_heads

    # What a page copy has not filled must still be FINITE: a masked
    # column's probability is exactly 0, and 0 x NaN would poison p.V.
    kbuf[...] = jnp.zeros_like(kbuf)
    vbuf[...] = jnp.zeros_like(vbuf)

    def pages_in(slot, chunk):
        return jnp.clip(pages_ref[slot] - chunk * chunk_pages,
                        0, chunk_pages)

    def page_copies(slot, chunk, buf, i):
        blk = tbl_ref[
            slot * max_blocks + page0_ref[slot] + chunk * chunk_pages + i
        ]
        dst = pl.ds(pl.multiple_of(i * page_rows, page_rows), page_rows)
        return (
            pltpu.make_async_copy(
                k_hbm.at[layer, blk], kbuf.at[buf, dst], sem.at[0, buf]
            ),
            pltpu.make_async_copy(
                v_hbm.at[layer, blk], vbuf.at[buf, dst], sem.at[1, buf]
            ),
        )

    def start(slot, chunk, buf):
        def body(i, carry):
            for cp in page_copies(slot, chunk, buf, i):
                cp.start()
            return carry

        jax.lax.fori_loop(0, pages_in(slot, chunk), body, 0)

    def wait(slot, chunk, buf):
        def body(i, carry):
            for cp in page_copies(slot, chunk, buf, i):
                cp.wait()
            return carry

        jax.lax.fori_loop(0, pages_in(slot, chunk), body, 0)

    # Column c of a chunk belongs to KV head c % kv_heads; query head r
    # reads KV head r // group.
    col = jax.lax.broadcasted_iota(jnp.int32, (hp, cols), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (hp, cols), 0)
    own_head = (col % kv_heads) == (row // group)
    nt = (((1,), (1,)), ((), ()))      # [m, d] x [n, d] -> [m, n]
    nn = (((1,), (0,)), ((), ()))      # [m, n] x [n, d] -> [m, d]

    def attend(slot, chunk, buf, m, l, acc):
        s = _dot_f32_by_stored(q_ref[slot], kbuf[buf], nt)
        # Visibility: lo <= cache row < fill, as columns of this chunk
        # (whose first row is the band's first page's, chunks on).
        base = (page0_ref[slot] + chunk * chunk_pages) * block_size
        low = (lo_ref[slot] - base) * kv_heads
        limit = (len_ref[slot] - base) * kv_heads
        s = jnp.where(own_head & (col >= low) & (col < limit), s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)          # masked: exp(-1e30 - m) == 0
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + _dot_f32_by_stored(p, vbuf[buf], nn)
        return m_new, l, acc

    start(0, 0, 0)

    def slot_body(slot, buf):
        # Every slot is visited for one chunk at least; one with no
        # page to read copies and computes nothing and answers with its
        # new token alone.
        n_chunks = jnp.maximum(
            (pages_ref[slot] + chunk_pages - 1) // chunk_pages, 1
        )

        def chunk_body(chunk, carry):
            buf, m, l, acc = carry
            last = chunk + 1 >= n_chunks
            nxt_slot = jnp.where(last, slot + 1, slot)
            nxt_chunk = jnp.where(last, 0, chunk + 1)

            @pl.when(nxt_slot < slots)
            def _():
                start(nxt_slot, nxt_chunk, 1 - buf)

            wait(slot, chunk, buf)
            m, l, acc = jax.lax.cond(
                pages_in(slot, chunk) > 0,
                lambda: attend(slot, chunk, buf, m, l, acc),
                lambda: (m, l, acc),
            )
            return 1 - buf, m, l, acc

        # The new token's own K/V open the online softmax: the running
        # max is a real logit from the start, so no -inf arithmetic.
        buf, _, l, acc = jax.lax.fori_loop(
            0, n_chunks, chunk_body,
            (buf, s_new_ref[slot], jnp.ones((hp, 1), jnp.float32),
             v_new_ref[slot]),
        )
        o_ref[slot] = acc / l
        return buf

    jax.lax.fori_loop(0, slots, slot_body, 0)


def pool_window_decode_attention(
    q,             # [b, n_heads, d] — ONE query token per slot
    k_new,         # [b, kv_heads, d] — that token's own K/V, not yet in
    v_new,         #   the pool
    k_pool,        # [layers, num_blocks, block_size, kv_heads, d]
    v_pool,
    layer,         # [] int32 — which layer of this group's pool
    block_tables,  # [b, max_blocks] int32 — the group's own table
    length,        # [b] int32 — filled logical rows per slot
    active,        # [b] bool — a slot that is not active reads nothing
    reach: int,    # rows below the query that it can still see
    interpret=None,
):
    """A sliding-window layer's decode attention, read from the pool IN
    PLACE: the query at position ``length`` over rows ``[max(length -
    reach, 0), length)`` and its own new row. Only the pages that hold a
    row of that band are copied: table entries below it are never read,
    so they may name the sentinel block (a released block's entry does).
    Returns ``[b, n_heads, d]`` in ``q.dtype``; an inactive slot's row is
    its own ``v_new`` (finite, and discarded by the caller)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, h, d = q.shape
    n_layers, nb_pool, block_size, kh, _ = k_pool.shape
    _, max_blocks = block_tables.shape
    if h % kh:
        raise ValueError(f"n_heads {h} not divisible by kv_heads {kh}")
    g = h // kh
    page_rows = block_size * kh
    chunk_pages = max(1, _pool_chunk_pages(block_size, kh, d, max_blocks))
    q32 = (q * d ** -0.5).astype(jnp.float32)
    s_new = jnp.einsum(
        "bkgd,bkd->bkg", q32.reshape(b, kh, g, d),
        k_new.astype(jnp.float32),
    ).reshape(b, h, 1)
    v_rows = jnp.repeat(v_new.astype(jnp.float32), g, axis=1)
    hp = -(-h // 16) * 16
    pad = ((0, 0), (0, hp - h), (0, 0))
    q32, s_new, v_rows = (jnp.pad(x, pad) for x in (q32, s_new, v_rows))
    fill = jnp.where(active, jnp.asarray(length, jnp.int32), 0)
    fill = jnp.minimum(fill, max_blocks * block_size)
    low = jnp.maximum(fill - reach, 0)
    page0 = low // block_size
    scalars = (
        jnp.asarray(layer, jnp.int32).reshape(1),
        page0,
        (fill + block_size - 1) // block_size - page0,
        low,
        fill,
        jnp.asarray(block_tables, jnp.int32).reshape(-1),
    )
    pooled = (n_layers, nb_pool, page_rows, d)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(
            _window_decode_kernel, chunk_pages=chunk_pages,
            page_rows=page_rows, kv_heads=kh, group=g,
            max_blocks=max_blocks,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(),
            in_specs=[
                vmem, vmem, vmem,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=vmem,
            scratch_shapes=[
                pltpu.VMEM((2, chunk_pages * page_rows, d), k_pool.dtype),
                pltpu.VMEM((2, chunk_pages * page_rows, d), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hp, d), jnp.float32),
        interpret=interpret,
        name="paged_window_decode_attention",
    )(
        *scalars, q32, s_new, v_rows,
        k_pool.reshape(pooled), v_pool.reshape(pooled),
    )
    return out[:, :h].astype(q.dtype)


# ---- the prefill chunk ------------------------------------------------------


class _TableFrom:
    """``tbl_ref`` read from entry ``first`` on (what ``_page_stream``
    indexes from 0)."""

    def __init__(self, tbl_ref, first):
        self._ref, self._first = tbl_ref, first

    def __getitem__(self, i):
        return self._ref[self._first + i]


def _window_chunk_kernel(
    layer_ref, start_ref, tbl_ref,                # scalar prefetch
    q_ref, kn_ref, vn_ref, k_hbm, v_hbm,          # inputs
    o_ref,                                        # output
    kbuf, vbuf, sem, m_ref, l_ref, acc_ref,       # scratch
    *, chunk_pages: int, page_rows: int, kv_heads: int, group: int,
    tile: int, reach: int, exact: bool,
):
    """``ops.decode_attention._chunk_kernel`` under a band: one grid
    step = ``tile`` of the chunk's tokens, all heads, over the pages
    that hold a row of ``[start + first token - reach, start)`` (the
    lowest row its first token sees, up to the chunk), each (key, query)
    pair masked to ``key >= query - reach``, then the chunk's own keys
    under the same band and causally."""
    step = pl.program_id(0)
    layer = layer_ref[0]
    start = start_ref[0]
    block_size = page_rows // kv_heads
    chunk_rows = chunk_pages * block_size
    t_own = kn_ref.shape[1]
    rows = tile * group
    first_pos = start + step * tile               # the tile's first query
    low = jnp.maximum(first_pos - reach, 0)
    page0 = low // block_size
    n_pages = jnp.where(
        low < start, (start + block_size - 1) // block_size - page0, 0
    )
    n_chunks = (n_pages + chunk_pages - 1) // chunk_pages

    @pl.when(step == 0)
    def _():
        # Finite wherever a page copy has not written (0 x NaN).
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    start_copies, wait_copies, _ = _page_stream(
        layer, _TableFrom(tbl_ref, page0), n_pages, k_hbm, v_hbm, kbuf,
        vbuf, sem, chunk_pages=chunk_pages, page_rows=page_rows,
    )
    attend = _online_softmax(
        q_ref, m_ref, l_ref, acc_ref, rows=rows, exact=exact
    )

    @pl.when(n_chunks > 0)
    def _():
        start_copies(0, 0)

    def chunk_body(chunk, buf):
        @pl.when(chunk + 1 < n_chunks)
        def _():
            start_copies(chunk + 1, 1 - buf)

        wait_copies(chunk, buf)
        # Query row r of a head is token r // group of the tile.
        q_pos = first_pos + jax.lax.broadcasted_iota(
            jnp.int32, (chunk_rows, rows), 1
        ) // group
        key = (page0 + chunk * chunk_pages) * block_size + (
            jax.lax.broadcasted_iota(jnp.int32, (chunk_rows, rows), 0)
        )
        visible = (key < start) & (key >= q_pos - reach)

        def head_body(head, carry):
            attend(
                head,
                _head_rows(kbuf.at[buf], head, kv_heads, chunk_rows),
                _head_rows(vbuf.at[buf], head, kv_heads, chunk_rows),
                visible,
            )
            return carry

        jax.lax.fori_loop(0, kv_heads, head_body, 0)
        return 1 - buf

    jax.lax.fori_loop(0, n_chunks, chunk_body, 0)

    # The chunk's own keys: token u is visible to token t iff 0 <= t - u
    # <= reach.
    token = step * tile + jax.lax.broadcasted_iota(
        jnp.int32, (t_own, rows), 1
    ) // group
    ahead = token - jax.lax.broadcasted_iota(jnp.int32, (t_own, rows), 0)
    banded = (ahead >= 0) & (ahead <= reach)

    def own_body(head, carry):
        attend(head, kn_ref[head], vn_ref[head], banded)
        o_ref[head] = (acc_ref[head] / l_ref[head]).T.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, kv_heads, own_body, 0)


def pool_window_chunk_attention(
    q,            # [T, n_heads, d] — one slot's prefill chunk
    k_new,        # [T, kv_heads, d] — the chunk's own K/V, not yet in
    v_new,        #   the pool
    k_pool,       # [layers, num_blocks, block_size, kv_heads, d]
    v_pool,
    layer,        # [] int32
    table_row,    # [max_blocks] int32 — the slot's pages in this group
    start,        # [] int32 — cache rows already filled: [0, start)
    reach: int,   # rows below a query that it can still see
    interpret=None,
    exact: bool = True,
):
    """A sliding-window layer's prefill chunk with the pool read IN
    PLACE: token ``t`` (position ``start + t``) over pool rows ``[start
    + t - reach, start)`` and the chunk's own rows ``u`` with ``0 <= t -
    u <= reach``. A token tile copies only the pages from the one that
    holds its FIRST token's lowest visible row up to ``start``: table
    entries below that are never read. Cost goes by ``reach``, not by
    ``start``. Returns ``[T, n_heads, d]`` in ``q.dtype``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    t, h, d = q.shape
    n_layers, nb_pool, block_size, kh, _ = k_pool.shape
    if h % kh:
        raise ValueError(f"n_heads {h} not divisible by kv_heads {kh}")
    g = h // kh
    page_rows = block_size * kh
    max_blocks = table_row.shape[0]
    chunk_pages = max(1, _pool_chunk_pages(block_size, kh, d, max_blocks))
    tile = _chunk_token_tile(t, h) or t
    rows = tile * g
    qs = (q * d ** -0.5).reshape(t, kh, g, d).transpose(1, 0, 2, 3)
    qs = qs.reshape(kh, t * g, d)
    own = [x.astype(k_pool.dtype).transpose(1, 0, 2) for x in (k_new, v_new)]
    scalars = (
        jnp.asarray(layer, jnp.int32).reshape(1),
        jnp.minimum(
            jnp.asarray(start, jnp.int32), max_blocks * block_size
        ).reshape(1),
        jnp.asarray(table_row, jnp.int32),
    )
    pooled = (n_layers, nb_pool, page_rows, d)
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    tiled = pl.BlockSpec((kh, rows, d), lambda i, *_: (0, i, 0))
    out = pl.pallas_call(
        functools.partial(
            _window_chunk_kernel, chunk_pages=chunk_pages,
            page_rows=page_rows, kv_heads=kh, group=g, tile=tile,
            reach=reach, exact=exact,
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
            scratch_shapes=_chunk_scratch(
                k_pool.dtype, chunk_pages * page_rows, kh, rows, d
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((kh, t * g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_CHUNK_VMEM_BYTES,
        ),
        interpret=interpret,
        name="paged_window_chunk_attention",
    )(
        *scalars, qs, *own,
        k_pool.reshape(pooled), v_pool.reshape(pooled),
    )
    return out.reshape(kh, t, g, d).transpose(1, 0, 2, 3).reshape(t, h, d)
