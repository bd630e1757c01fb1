"""Attention over a KV cache that is READ, not rewritten: what serving
runs over its paged pool, and the reference for what it runs next.

Four functions, and the predicates that say where the three kernels
lower:

- :func:`pool_decode_attention` (Pallas; :func:`pool_kernel_supported`),
  the attention of ``PagedServingEngine``'s decode program on a TPU: one
  query token a slot against the STACKED pool read in place, one call a
  layer, a page of all KV heads per DMA, only each decoding slot's
  filled pages, chunks of pages double-buffered across slots. The XLA
  gather it replaced moved the cache at full capacity four times a
  layer (PERF.md §5, PR 25).
- :func:`pool_chunk_attention` (Pallas;
  :func:`chunk_kernel_supported`), the attention of its prefill
  program: one slot's chunk of T tokens against the rows below the
  chunk, read from the same pool in place, then the chunk's own K/V
  causally, in one online softmax (PERF.md §5, PR 28).
- :func:`sparse_chunk_attention` (Pallas;
  :func:`sparse_chunk_kernel_supported`), the same chunk for a model
  whose queries attend a learned SELECTION of the cache
  (``serving/kvpool/sparse.py``): the chunk kernel's page copies and
  online softmax, the selection an operand applied to the scores in
  VMEM (PERF.md §6, PR 34).
- :func:`spec_verify_attention` (plain XLA), T query tokens a row
  against a read-only cache: the speculative verify step's math, and
  the reference for a T-query pool kernel over several slots
  (ROADMAP S4(c)).

``serving/kvpool/dense.pool_attention_kind`` picks between the two
dense kernels and the XLA gather, and
``kvpool/sparse.chunk_attention_kind`` between the sparse one and
``ops.sparse_attention.masked_attention``, from what they can see
(platform, pool dtype, page and chunk shapes); nothing here reads the
environment.

A one-token step over a SLAB cache (``generate()``, the flat
``ServingEngine``) has no kernel here: it runs
``models/generate._append_free_attention``, plain XLA (a Pallas kernel
on a ``(batch, kv_head, block)`` grid took 3.58-3.675 ms/token where
that takes 1.26-1.35, BENCH_r05: deleted in PR 29, not to be rebuilt).
The decode kernels over pools of another layout are modules of their
own: ``ops/latent_decode_attention.py`` (packed latent rows, PR 40) and
``ops/flat_decode_attention.py`` (flat rows of 64-wide heads a lane row
at a time, PR 49; it takes its exact products from :func:`_split_bf16`).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def spec_verify_attention(
    q,            # [b, T, h, d] — T = 1 fed token + K drafted tokens
    k_cache,      # [b, S, kh, d] read-only; rows >= cache_len unfilled
    v_cache,
    k_new,        # [b, T, kh, d] full-precision K/V of the T new tokens
    v_new,
    cache_len,    # [] or [b] int32 — committed (visible) cache rows
    k_scale=None,      # [b, S, kh] f32 — int8 caches (ops/kv_quant)
    v_scale=None,
    k_new_q=None,      # [b, T, kh, d] int8 — quantized new K rows
    k_new_scale=None,  # [b, T, kh] f32
    v_new_q=None,
    v_new_scale=None,
):
    """T-query generalization of the append-free decode attention —
    the speculative-decoding VERIFICATION step's core math.

    One batched call scores all T = K+1 tokens (the fed token plus K
    drafted continuations) against a READ-ONLY ragged cache, exactly
    what K+1 sequential ``_append_free_attention`` steps would compute
    if each drafted token's K/V had been appended before the next
    step. Three key groups, merged in one online softmax:

    - **Cache part** ([b, S]): rows visible iff ``< cache_len``, per
      row — the same visibility invariant as single-token decode.
    - **Intra-draft part** ([b, T]): query t sees drafted key u iff
      ``u < t`` (strict — the standard causal chain among the new
      tokens). Sequential decode would read these keys FROM THE CACHE,
      i.e. after the storage round trip; so for int8 caches the
      off-diagonal keys here are the QUANTIZED rows (``k_new_q`` with
      per-(row, head) ``k_new_scale`` folded post-reduction, the exact
      read-site math of the cache part) — bit-exact int8 parity with
      the non-speculative path.
    - **Self part**: each query always sees its own K/V at FULL
      precision (the write-once rule: a token's quantized row is what
      LATER tokens read, never itself).

    T=1 degenerates to ``_append_free_attention`` (the intra part is
    empty) — the parity test pins the two. Returns [b, T, h, d].
    """
    b, T, h, d = q.shape
    _, skv, kh, _ = k_cache.shape
    g = h // kh
    scale = d ** -0.5
    # [b, T, kh, g, d] f32 query groups.
    q32 = (q * scale).astype(jnp.float32).reshape(b, T, kh, g, d)
    # Cache part: [b, kh, g, T, S]; per-row visibility masking.
    logits = jnp.einsum(
        "btkgd,bskd->bkgts", q32, k_cache.astype(jnp.float32)
    )
    if k_scale is not None:
        logits = logits * k_scale.transpose(0, 2, 1)[:, :, None, None, :]
    lens = jnp.atleast_1d(jnp.asarray(cache_len, jnp.int32))
    visible = jnp.arange(skv)[None, :] < lens[:, None]       # [1|b, S]
    logits = jnp.where(visible[:, None, None, None, :], logits, NEG_INF)
    # Intra-draft part: [b, kh, g, T, T]; key u visible to query t iff
    # u < t. Off-diagonal keys go through the storage round trip (int8:
    # quantized values with the scale folded post-reduction, exactly
    # like the cache read above; fp: the cache dtype IS the compute
    # dtype, so the round trip is the identity and k_new serves as-is).
    intra_k = (k_new_q if k_new_q is not None else k_new).astype(
        jnp.float32
    )
    l_intra = jnp.einsum("btkgd,bukd->bkgtu", q32, intra_k)
    if k_new_scale is not None:
        l_intra = l_intra * k_new_scale.transpose(0, 2, 1)[
            :, :, None, None, :
        ]
    tq = jnp.arange(T)
    intra_mask = tq[None, :] < tq[:, None]                   # [T, T] u<t
    l_intra = jnp.where(intra_mask[None, None, None], l_intra, NEG_INF)
    # Self part: full-precision own K/V.
    l_self = jnp.einsum(
        "btkgd,btkd->bkgt", q32, k_new.astype(jnp.float32)
    )
    m = jnp.maximum(
        jnp.maximum(jnp.max(logits, axis=-1), jnp.max(l_intra, axis=-1)),
        l_self,
    )                                                        # [b,kh,g,T]
    p = jnp.exp(logits - m[..., None])
    p = jnp.where(visible[:, None, None, None, :], p, 0.0)
    p_intra = jnp.exp(l_intra - m[..., None])
    p_intra = jnp.where(intra_mask[None, None, None], p_intra, 0.0)
    p_self = jnp.exp(l_self - m)
    denom = (
        jnp.sum(p, axis=-1) + jnp.sum(p_intra, axis=-1) + p_self
    )                                                        # >= p_self
    pv = p if v_scale is None else (
        p * v_scale.transpose(0, 2, 1)[:, :, None, None, :]
    )
    intra_v = (v_new_q if v_new_q is not None else v_new).astype(
        jnp.float32
    )
    pv_intra = p_intra if v_new_scale is None else (
        p_intra * v_new_scale.transpose(0, 2, 1)[:, :, None, None, :]
    )
    out = (
        jnp.einsum("bkgts,bskd->bkgtd", pv, v_cache.astype(jnp.float32))
        + jnp.einsum("bkgtu,bukd->bkgtd", pv_intra, intra_v)
        + p_self[..., None] * v_new.astype(jnp.float32).transpose(
            0, 2, 1, 3
        )[:, :, None]
    ) / denom[..., None]
    # [b, kh, g, T, d] -> [b, T, h, d]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, T, h, d).astype(
        q.dtype
    )


# ---- paged decode attention over the STACKED pool, in place ---------------

# Bytes of cache one inner step of the pool kernel copies (K and V each,
# double-buffered: four such buffers in VMEM). 1 MB is 512 rows of 8 KV
# heads x 128, 32 pages of 16 rows. On the v5e at 16 slots x 12 layers
# of that shape (``tools/bench_paged_decode.py``, my chip runs, PR 25):
# 256 KB / 512 KB / 1 MB / 2 MB take 2.45 / 2.14 / 2.03 / 2.11 ms at chat
# fills (mean ~900 rows), 4.7 / 3.9 / 3.7 / 3.8 ms with every slot full,
# 0.92 / 0.99 / 1.09 / 1.52 ms at 128 rows a slot; at 32 KV heads 512 KB /
# 1 MB / 2 MB take 3.52 / 3.48 / 3.52, 7.6 / 7.2 / 7.3, 1.06 / 1.06 / 1.19. Sized in bytes, not rows: the buffers grow with
# ``kv_heads * head_dim``, and the kernel's scoped VMEM (16 MB on the
# v5e) holds four of them beside the score tiles — at 3 MB a buffer the
# compiler still takes the kernel, at 3.5 MB it refuses it.
_POOL_CHUNK_BYTES = 1 << 20


def _pool_chunk_pages(block_size: int, kv_heads: int, head_dim: int,
                      max_blocks: int) -> int:
    """Pages of a bf16 pool in one VMEM chunk: as many whole pages as
    ``_POOL_CHUNK_BYTES`` holds, at most a slot's table; 0 where not
    even one fits."""
    page_bytes = block_size * kv_heads * head_dim * 2
    return min(_POOL_CHUNK_BYTES // page_bytes, max_blocks)


def pool_kernel_supported(pool_dtype, block_size: int, kv_heads: int,
                          head_dim: int) -> bool:
    """Shapes the in-place pool kernel lowers for on a TPU: a bf16 pool
    whose page ``[block_size * kv_heads, head_dim]`` is a whole number
    of (16, 128) bf16 tiles, so that one page is one contiguous DMA and
    the collapse of ``[block_size, kv_heads]`` into rows moves no byte,
    and no larger than one VMEM chunk (``_POOL_CHUNK_BYTES``: a page is
    the unit of copy, so a larger one would size the four buffers past
    what the kernel's VMEM holds). ``kv_heads`` a multiple of 8 is what
    makes that collapse a bitcast of XLA's ``T(8,128)(2,1)`` layout of
    ``[..., kv_heads, head_dim]``."""
    return (
        jnp.dtype(pool_dtype) == jnp.bfloat16
        and head_dim % 128 == 0
        and kv_heads % 8 == 0
        and (block_size * kv_heads) % 16 == 0
        and _pool_chunk_pages(block_size, kv_heads, head_dim, 1) == 1
    )


def _split_bf16(x):
    """``x`` (f32) as three bf16 arrays whose sum is ``x`` exactly (8 +
    8 + 8 mantissa bits). A bf16 x bf16 product is exact in f32, so one
    MXU pass over the three stacked as extra rows gives the f32 product
    without rounding ``x`` (the MXU is bound by loading the OTHER
    operand's tiles; a few more rows ride free)."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    return [hi, mid, (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)]


def _dot_f32_by_stored(a, b, dims):
    """``a`` (f32, [rows, n]) contracted with ``b`` as it is stored.
    A bf16 ``b`` takes ``a`` as three bf16 addends stacked along rows
    (see :func:`_split_bf16`); any other dtype (interpret mode on f32
    pools) is a plain f32 contraction."""
    if b.dtype != jnp.bfloat16:
        return jax.lax.dot_general(
            a, b.astype(jnp.float32), dims,
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
    rows = a.shape[0]
    out = jax.lax.dot_general(
        jnp.concatenate(_split_bf16(a), 0), b, dims,
        preferred_element_type=jnp.float32,
    )
    return out[:rows] + out[rows:2 * rows] + out[2 * rows:]


def _pool_kernel(
    layer_ref, pages_ref, len_ref, tbl_ref,       # scalar prefetch
    q_ref, s_new_ref, v_new_ref, k_hbm, v_hbm,    # inputs
    o_ref,                                        # output
    kbuf, vbuf, sem,                              # scratch
    *, chunk_pages: int, page_rows: int, kv_heads: int, group: int,
    max_blocks: int,
):
    """One call = one layer's decode attention for every slot. The
    pools stay in HBM; each slot's filled pages are copied page by page
    (one contiguous DMA each) into a double-buffered VMEM chunk of
    ``chunk_pages`` pages, the next chunk — of this slot or of the next
    — in flight while this one is computed.

    A chunk is ``[chunk_pages * block_size * kv_heads, head_dim]``: row
    ``c`` is cache row ``c // kv_heads`` of KV head ``c % kv_heads``,
    exactly as the page lies in the pool. All query heads are scored
    against all rows in one matmul and a constant mask keeps each
    head's own KV head, so no byte of a page is moved to separate the
    heads: the MXU time is set by streaming the chunk through it once
    either way, the wasted columns cost only VPU time — ``kv_heads``
    times the useful softmax work, which per byte of cache copied goes
    by query heads / ``head_dim``. Measured on the v5e at 16 slots x
    2,304 rows (``tools/bench_paged_decode.py``, PR 25): at 32 query
    heads x 128 the kernel is bound by its page DMAs, with 8 KV heads
    and with 32 (MHA) alike; 64 query heads cost a quarter more time.
    A layout that scores each KV head apart has not been measured. The
    call is one sequential program (no grid): one TensorCore, which is
    all a v5e has."""
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
        blk = tbl_ref[slot * max_blocks + chunk * chunk_pages + i]
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
        # Visibility: cache row < the slot's fill, i.e. column <
        # (fill - first row of the chunk) * kv_heads.
        limit = (
            len_ref[slot] - chunk * chunk_pages * block_size
        ) * kv_heads
        s = jnp.where(own_head & (col < limit), s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)          # masked: exp(-1e30 - m) == 0
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + _dot_f32_by_stored(p, vbuf[buf], nn)
        return m_new, l, acc

    start(0, 0, 0)

    def slot_body(slot, buf):
        # Every slot is visited for one chunk at least; one with no
        # page to read (free, mid-prefill, or an empty cache) copies
        # and computes nothing and answers with its new token alone.
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


def pool_decode_attention(
    q,             # [b, n_heads, d] — ONE query token per slot
    k_new,         # [b, kv_heads, d] — that token's own K/V, not yet
    v_new,         #   in the pool (the append-free step)
    k_pool,        # [layers, num_blocks, block_size, kv_heads, d]
    v_pool,
    layer,         # [] int32 — which layer of the stacked pool
    block_tables,  # [b, max_blocks] int32
    length,        # [b] int32 — filled logical rows per slot
    active,        # [b] bool — a slot that is not active reads nothing
    interpret=None,
):
    """The paged decode step's attention, read from the stacked pool IN
    PLACE: ``_append_free_attention`` over ``k_pool[layer][tables]``
    without the slice, without the gathered ``[slots, max_len]`` view,
    and without the rows past each slot's fill.

    The pools go into the kernel whole (``memory_space=ANY``); the
    layer index, the per-slot page counts and fills and the flattened
    block tables ride as scalar prefetch and pick the pages, each one
    contiguous DMA of ``block_size x kv_heads x d``. A slot stops at
    its last filled page and an inactive one copies nothing; rows of
    that page past the fill are masked (visibility: row < length).

    Arithmetic is the reference's: K and V are used as stored, the
    scaled query, logits, running max, sum, probabilities and
    accumulator are f32, and the f32 operands meet the bf16 ones
    unrounded (:func:`_split_bf16`). What differs is the order of
    summation: an online softmax over chunks of
    ``_POOL_CHUNK_BYTES`` of pages, opened by the new token's own term. (On
    a TPU XLA runs the reference's f32 einsums at default matmul
    precision, one bf16 pass: there the gather path is ~3e-3 off the
    exact softmax and this kernel ~5e-7, ``tools/bench_paged_decode.py``
    on the v5e, PR 25.)
    Returns ``[b, n_heads, d]`` in ``q.dtype``; an inactive slot's row
    is its own ``v_new`` (finite, and discarded by the caller)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, h, d = q.shape
    n_layers, nb_pool, block_size, kh, _ = k_pool.shape
    _, max_blocks = block_tables.shape
    if h % kh:
        raise ValueError(f"n_heads {h} not divisible by kv_heads {kh}")
    g = h // kh
    page_rows = block_size * kh
    # Off the chip (interpret mode, f32 pools) any page goes; on it the
    # caller asked pool_kernel_supported, so at least one page fits.
    chunk_pages = max(1, _pool_chunk_pages(block_size, kh, d, max_blocks))
    # _append_free_attention's operands, to the letter: the query is
    # scaled in its own dtype, then everything is f32.
    q32 = (q * d ** -0.5).astype(jnp.float32)
    s_new = jnp.einsum(
        "bkgd,bkd->bkg", q32.reshape(b, kh, g, d),
        k_new.astype(jnp.float32),
    ).reshape(b, h, 1)
    v_rows = jnp.repeat(v_new.astype(jnp.float32), g, axis=1)
    # Query heads padded to whole bf16 tiles, so that the addends of
    # _split_bf16 stack on tile edges.
    hp = -(-h // 16) * 16
    pad = ((0, 0), (0, hp - h), (0, 0))
    q32, s_new, v_rows = (jnp.pad(x, pad) for x in (q32, s_new, v_rows))
    fill = jnp.where(active, jnp.asarray(length, jnp.int32), 0)
    fill = jnp.minimum(fill, max_blocks * block_size)
    scalars = (
        jnp.asarray(layer, jnp.int32).reshape(1),
        (fill + block_size - 1) // block_size,
        fill,
        jnp.asarray(block_tables, jnp.int32).reshape(-1),
    )
    pooled = (n_layers, nb_pool, page_rows, d)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(
            _pool_kernel, chunk_pages=chunk_pages, page_rows=page_rows,
            kv_heads=kh, group=g, max_blocks=max_blocks,
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
        name="paged_pool_decode_attention",
    )(
        *scalars, q32, s_new, v_rows,
        k_pool.reshape(pooled), v_pool.reshape(pooled),
    )
    return out[:, :h].astype(q.dtype)


# ---- a prefill chunk's attention over the STACKED pool, in place ----------

# Query rows (tokens x query heads) one grid step of the chunk kernel
# holds: their f32 accumulator and the pipelined query and output
# blocks come to ~1.5 KB a row at 128-wide heads, 6 MB at 4,096 rows
# beside the 4 MB of page buffers and the score tiles. Every grid step
# re-reads the prefix's pages and pays its own set-up, so fewer, larger
# steps are faster: on the v5e at the ``nemo12b-serve-chat`` shape
# 2,048 / 4,096 / 8,192 rows took 2.01 / 1.59 / 1.44 ms over 12 layers
# with 512 rows below the chunk, 3.89 / 2.83 / 2.54 with 2,048
# (``tools/bench_paged_decode.py --parts prefill``, PR 28); 8,192 would
# want ~32 MB of VMEM for a tenth of a millisecond a chunk.
_CHUNK_QUERY_ROWS = 4096
# What the chunk kernel may use of the core's VMEM (128 MiB on a v5e; the
# compiler's own default scope is 16 MB).
_CHUNK_VMEM_BYTES = 32 << 20


def _chunk_token_tile(chunk: int, n_heads: int) -> int:
    """Tokens of a chunk in one grid step of the chunk kernel: the
    largest power-of-two share of ``chunk`` whose query rows number at
    most ``_CHUNK_QUERY_ROWS``; 0 where no share of 8 tokens or more
    divides the chunk."""
    tile = chunk
    while tile * n_heads > _CHUNK_QUERY_ROWS and tile % 2 == 0:
        tile //= 2
    fits = tile * n_heads <= _CHUNK_QUERY_ROWS and tile % 8 == 0
    return tile if fits else 0


def _chunk_vmem_bytes(block_size, n_heads, kv_heads, head_dim, chunk,
                      tile, itemsize) -> int:
    """An upper reckoning of the chunk kernel's VMEM: page buffers,
    per-row state, pipelined blocks and the live score tiles."""
    rows = tile * n_heads
    group_rows = tile * (n_heads // kv_heads)
    page = block_size * kv_heads * head_dim * itemsize
    pages = max(1, _POOL_CHUNK_BYTES // page)
    cols = max(pages * block_size, chunk)
    return (
        4 * pages * page                          # K, V double-buffered
        + rows * (2 * 8 * 4 + head_dim * 4)       # max, sum, accumulator
        + 2 * rows * head_dim * 2 * itemsize      # q in, out: 2 buffers
        + 2 * 2 * chunk * kv_heads * head_dim * itemsize   # own K, V
        + 6 * group_rows * cols * 4               # scores, probabilities
    )


def chunk_kernel_supported(pool_dtype, block_size: int, n_heads: int,
                           kv_heads: int, head_dim: int,
                           chunk: int) -> bool:
    """Shapes :func:`pool_chunk_attention` lowers for on a TPU: what
    :func:`pool_kernel_supported` admits (the page is the same unit of
    copy), a chunk that splits into token tiles of whole sublanes, and
    buffers — sized in bytes from the shapes — that fit the VMEM the
    kernel asks for."""
    tile = _chunk_token_tile(chunk, n_heads)
    if not tile or not pool_kernel_supported(
        pool_dtype, block_size, kv_heads, head_dim
    ):
        return False
    return _chunk_vmem_bytes(
        block_size, n_heads, kv_heads, head_dim, chunk, tile, 2
    ) <= _CHUNK_VMEM_BYTES


def _head_rows(ref, head, kv_heads: int, rows: int):
    """Rows of one KV head out of a chunk that lies as its pages do:
    ``ref`` is ``[d // 128, rows * kv_heads, 128]`` (Mosaic's strided
    read wants a 128-lane buffer, so wider heads lie in lane groups),
    row ``c`` = cache row ``c // kv_heads`` of head ``c % kv_heads``.
    bf16 rows are packed in pairs into 32-bit sublanes, so there the
    read is of the words that hold the head (stride ``kv_heads // 2``)
    and a shift picks the half — exact. Returns ``[rows, d]``."""

    def lanes(ref):
        if ref.dtype != jnp.bfloat16:
            return ref[pl.ds(head, rows, stride=kv_heads), :]
        words = ref.bitcast(jnp.uint32)[
            pl.ds(head // 2, rows, stride=kv_heads // 2), :
        ]
        half = jnp.where(
            head % 2 == 0, words << 16, words & jnp.uint32(0xFFFF0000)
        )
        return pltpu.bitcast(half, jnp.float32).astype(jnp.bfloat16)

    return jnp.concatenate(
        [lanes(ref.at[j]) for j in range(ref.shape[0])], axis=-1
    )


def _page_stream(layer, tbl_ref, n_pages, k_hbm, v_hbm, kbuf, vbuf, sem,
                 *, chunk_pages: int, page_rows: int):
    """The chunk kernels' page copies: ``(start_copies, wait_copies,
    pages_in)`` over one slot's pages ``tbl_ref[0 .. n_pages)`` of
    ``layer``, in VMEM chunks of ``chunk_pages`` pages, K into
    ``kbuf[buf]`` and V into ``vbuf[buf]``, one contiguous DMA a page
    and a lane group."""
    _, lane_groups, _, lanes = kbuf.shape

    def pages_in(chunk):
        return jnp.clip(n_pages - chunk * chunk_pages, 0, chunk_pages)

    def page_copies(chunk, buf, i):
        blk = tbl_ref[chunk * chunk_pages + i]
        dst = pl.ds(pl.multiple_of(i * page_rows, page_rows), page_rows)
        return [
            pltpu.make_async_copy(
                hbm.at[layer, blk, :, pl.ds(j * lanes, lanes)],
                vmem.at[buf, j, dst], sem.at[which, buf],
            )
            for which, (hbm, vmem) in enumerate(
                ((k_hbm, kbuf), (v_hbm, vbuf))
            )
            for j in range(lane_groups)
        ]

    def start_copies(chunk, buf):
        def body(i, carry):
            for cp in page_copies(chunk, buf, i):
                cp.start()
            return carry

        jax.lax.fori_loop(0, pages_in(chunk), body, 0)

    def wait_copies(chunk, buf):
        def body(i, carry):
            for cp in page_copies(chunk, buf, i):
                cp.wait()
            return carry

        jax.lax.fori_loop(0, pages_in(chunk), body, 0)

    return start_copies, wait_copies, pages_in


def _online_softmax(q_ref, m_ref, l_ref, acc_ref, *, rows: int,
                    exact: bool):
    """The chunk kernels' one online softmax: ``attend(head, k, v,
    visible)`` over the per-(KV head, query row) statistics ``m_ref`` /
    ``l_ref`` ``[kv_heads, 1, rows]`` and the accumulator ``acc_ref``
    ``[kv_heads, d, rows]``, all float32."""
    nt = (((1,), (1,)), ((), ()))      # [n, d] x [m, d] -> [n, m]
    tn = (((0,), (0,)), ((), ()))      # [n, d] x [n, m] -> [d, m]

    def dot(a, b, dims):
        # bf16 by bf16 is exact in f32: one MXU pass. Anything else
        # (interpret mode on f32 pools, f32 queries from the parity
        # tool) is an f32 contraction.
        if a.dtype == b.dtype == jnp.bfloat16:
            return jax.lax.dot_general(
                a, b, dims, preferred_element_type=jnp.float32
            )
        return jax.lax.dot_general(
            a.astype(jnp.float32), b.astype(jnp.float32), dims,
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )

    def attend(head, k, v, visible=None):
        """One online-softmax step of ``head``'s query rows over keys
        ``k`` / values ``v`` ``[n, d]`` as stored; ``visible`` ``[n,
        rows]``, or None where every key is. Scores lie KEYS x QUERIES:
        the softmax's reductions run down the sublanes (elementwise
        over vregs, one short reduce at the end) and its per-query
        statistics are lane-dense ``[1, rows]`` rows, where queries x
        keys would reduce along the lanes and keep a ``[rows, 128]``
        tile a statistic. A ``visible`` narrower than ``rows`` (``[n,
        rows / r]``: one answer for the ``r`` query heads of a token,
        the rows lying head-major) is applied a lane block at a time,
        never widened. A masked key's probability is exp(NEG_INF - m)
        == 0 with no second select once the running max is a real
        logit; a query no key has been shown yet (every caller shows it
        one before it reads the answer) carries exp(0) a masked key in
        its sum and accumulator, all finite, and its first real logit
        scales them by exp(NEG_INF - m) == 0, exactly."""
        s = dot(k, q_ref[head], nt)
        if visible is not None:
            width = visible.shape[1]
            if width == rows:
                s = jnp.where(visible, s, NEG_INF)
            else:
                s = jnp.concatenate([
                    jnp.where(visible, s[:, j:j + width], NEG_INF)
                    for j in range(0, rows, width)
                ], axis=1)
        m_prev = m_ref[head]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[head] = alpha * l_ref[head] + jnp.sum(
            p, axis=0, keepdims=True
        )
        if v.dtype != jnp.bfloat16:
            pv = dot(v, p, tn)
        elif exact:
            # The f32 probabilities as three bf16 addends side by side
            # (:func:`_split_bf16`): exact products in one MXU pass.
            pv = dot(v, jnp.concatenate(_split_bf16(p), 1), tn)
            pv = pv[:, :rows] + pv[:, rows:2 * rows] + pv[:, 2 * rows:]
        else:
            pv = dot(v, p.astype(v.dtype), tn)
        acc_ref[head] = acc_ref[head] * alpha + pv
        m_ref[head] = m_new

    return attend


def _chunk_kernel(
    layer_ref, start_ref, tbl_ref,                # scalar prefetch
    q_ref, kn_ref, vn_ref, k_hbm, v_hbm,          # inputs
    o_ref,                                        # output
    kbuf, vbuf, sem, m_ref, l_ref, acc_ref,       # scratch
    *, chunk_pages: int, page_rows: int, kv_heads: int, group: int,
    tile: int, exact: bool,
):
    """One call = one layer's attention for one slot's prefill chunk;
    one grid step = ``tile`` of its tokens, all heads.

    Two key groups in one online softmax per (KV head, query row)
    (:func:`_online_softmax`):

    - the slot's cache rows ``[0, start)``, copied from the pool page
      by page as :func:`_pool_kernel` copies them (the next chunk of
      pages in flight while this one is computed), every row visible
      to every query, the last page masked at ``start``;
    - the chunk's own K/V, from VMEM, causally.

    Query rows lie ``[kv_heads, tile * group, d]``, row ``r`` of a head
    is token ``r // group``: a KV head's rows are read out of the page
    layout by :func:`_head_rows` and meet only their own queries, so
    nothing is scored to be masked away (the decode kernel's one
    matmul over all heads costs ``kv_heads`` x the softmax work, which
    for 32 query rows is nothing and for 8,192 would be the kernel's
    time). Every call of ``attend`` shows each query a key at least (a
    chunk of pages is attended only if it holds a row below ``start``,
    and a token sees itself), so the running max is a real logit after
    the first."""
    step = pl.program_id(0)
    layer = layer_ref[0]
    start = start_ref[0]
    block_size = page_rows // kv_heads
    chunk_rows = chunk_pages * block_size
    n_pages = (start + block_size - 1) // block_size
    n_chunks = (n_pages + chunk_pages - 1) // chunk_pages
    t_own = kn_ref.shape[1]
    rows = tile * group

    @pl.when(step == 0)
    def _():
        # Finite wherever a page copy has not written (0 x NaN).
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    start_copies, wait_copies, _ = _page_stream(
        layer, tbl_ref, n_pages, k_hbm, v_hbm, kbuf, vbuf, sem,
        chunk_pages=chunk_pages, page_rows=page_rows,
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
        filled = start - chunk * chunk_rows     # rows of it below start

        def heads(visible):
            def head_body(head, carry):
                attend(
                    head,
                    _head_rows(kbuf.at[buf], head, kv_heads, chunk_rows),
                    _head_rows(vbuf.at[buf], head, kv_heads, chunk_rows),
                    visible,
                )
                return carry

            jax.lax.fori_loop(0, kv_heads, head_body, 0)

        # Only the prefix's last chunk has rows to hide (what its page
        # copies left of an earlier chunk, and a last page's rows past
        # ``start``): every other one skips the mask's passes.
        @pl.when(filled >= chunk_rows)
        def _():
            heads(None)

        @pl.when(filled < chunk_rows)
        def _():
            key = jax.lax.broadcasted_iota(
                jnp.int32, (chunk_rows, rows), 0
            )
            heads(key < filled)

        return 1 - buf

    jax.lax.fori_loop(0, n_chunks, chunk_body, 0)

    # The chunk's own keys: token u is visible to token t iff u <= t.
    token = step * tile + jax.lax.broadcasted_iota(
        jnp.int32, (t_own, rows), 1
    ) // group
    causal = jax.lax.broadcasted_iota(
        jnp.int32, (t_own, rows), 0
    ) <= token

    def own_body(head, carry):
        attend(head, kn_ref[head], vn_ref[head], causal)
        o_ref[head] = (acc_ref[head] / l_ref[head]).T.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, kv_heads, own_body, 0)


def _chunk_scratch(pool_dtype, buf_rows: int, kv_heads: int, rows: int,
                   head_dim: int):
    """The chunk kernels' scratch: K and V page buffers (two each, a
    head wider than 128 in 128-lane groups; one group, off the chip,
    for a narrow one), their DMA semaphores, and the online softmax's
    running max, sum and accumulator a (KV head, query row)."""
    lane_groups = max(head_dim // 128, 1)
    chunk_buf = (2, lane_groups, buf_rows, head_dim // lane_groups)
    return [
        pltpu.VMEM(chunk_buf, pool_dtype),
        pltpu.VMEM(chunk_buf, pool_dtype),
        pltpu.SemaphoreType.DMA((2, 2)),
        pltpu.VMEM((kv_heads, 1, rows), jnp.float32),
        pltpu.VMEM((kv_heads, 1, rows), jnp.float32),
        pltpu.VMEM((kv_heads, head_dim, rows), jnp.float32),
    ]


def pool_chunk_attention(
    q,            # [T, n_heads, d] — one slot's prefill chunk
    k_new,        # [T, kv_heads, d] — the chunk's own K/V, not yet in
    v_new,        #   the pool
    k_pool,       # [layers, num_blocks, block_size, kv_heads, d]
    v_pool,
    layer,        # [] int32
    table_row,    # [max_blocks] int32 — the slot's pages
    start,        # [] int32 — cache rows already filled: [0, start)
    interpret=None,
    exact: bool = True,
):
    """A paged prefill chunk's attention with the pool read IN PLACE:
    ``dot_product_attention`` of the chunk's queries (positions ``start
    + t``) over the slot's logical cache with the chunk written at
    ``start``, without that view: rows below ``start`` come straight
    from the stacked pool through ``table_row``, only the pages that
    hold them, and the chunk's own K/V from the caller's hands. Cost
    goes by ``start + T``, not by the table's length.

    Arithmetic is the reference's: the query is scaled in its own
    dtype, K and V are used as stored, logits, running max, sum and
    accumulator are f32 and no row is dropped; the order of summation
    differs (an online softmax over chunks of ``_POOL_CHUNK_BYTES`` of
    pages, then the chunk itself). ``exact`` says how the f32
    probabilities meet a bf16 V: unrounded (:func:`_split_bf16`, three
    MXU passes' worth of rows) or rounded to bf16 once, which is what
    XLA's default matmul precision makes of the reference on a TPU.
    Returns ``[T, n_heads, d]`` in ``q.dtype``."""
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
    # Off the chip (interpret mode) any chunk goes in one tile; on it
    # the caller asked chunk_kernel_supported.
    tile = _chunk_token_tile(t, h) or t
    rows = tile * g
    # [T, kh, g, d] -> [kh, T * g, d]: a KV head's query rows together,
    # token-major, so that a token tile is a run of rows.
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
            _chunk_kernel, chunk_pages=chunk_pages, page_rows=page_rows,
            kv_heads=kh, group=g, tile=tile, exact=exact,
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
        name="paged_pool_chunk_attention",
    )(
        *scalars, qs, *own,
        k_pool.reshape(pooled), v_pool.reshape(pooled),
    )
    return out.reshape(kh, t, g, d).transpose(1, 0, 2, 3).reshape(t, h, d)


# ---- the same chunk under a per-query SELECTION of its keys ---------------

# What the sparse chunk kernel may use of the core's VMEM: the dense
# kernel's terms, the selection's two blocks (4.3 MB a buffer at 33,792
# rows a slot) and a score tile of 1,024 keys x 1,024 query rows at
# 64-row x 4-head pages. Its page chunk is the dense kernels'
# ``_POOL_CHUNK_BYTES``: on the v5e at the ``keye-serve-docqa-32k``
# shape 256 KB / 512 KB / 1 MB / 2 MB took 2.92 / 2.75 / 2.65 / 2.66 ms
# a layer over four 128-token tiles and 32,768 rows below the chunk
# (``tools/bench_sparse_attention.py --parts chunk_kernel``, PR 34).
_SPARSE_VMEM_BYTES = 48 << 20
# Lanes of a vreg: on the chip the selection is applied a lane block of
# one token tile at a time, so a token tile is a whole number of them.
_LANES = 128


def _sparse_chunk_vmem_bytes(block_size, n_heads, kv_heads, head_dim,
                             chunk, max_blocks, tile, itemsize) -> int:
    """An upper reckoning of the sparse chunk kernel's VMEM:
    :func:`_chunk_vmem_bytes`, and the two selection blocks (the pool's
    rows to whole chunks of pages, the chunk's own), int8, pipelined."""
    pages = max(1, _pool_chunk_pages(
        block_size, kv_heads, head_dim, max_blocks
    ))
    keys = -(-max_blocks // pages) * pages * block_size
    return _chunk_vmem_bytes(
        block_size, n_heads, kv_heads, head_dim, chunk, tile, itemsize
    ) + 2 * (keys + chunk) * tile


def sparse_chunk_kernel_supported(pool_dtype, block_size: int,
                                  n_heads: int, kv_heads: int,
                                  head_dim: int, chunk: int,
                                  max_blocks: int) -> bool:
    """Shapes :func:`sparse_chunk_attention` lowers for on a TPU: a bf16
    pool whose page ``[block_size * kv_heads, head_dim]`` is a whole
    number of (16, 128) tiles and one contiguous DMA (8 KV heads a row
    as :func:`pool_kernel_supported` has it, or 4: XLA lays ``[...,
    4, 128]`` bf16 out in ``T(4,128)(2,1)`` tiles, two of which are one
    ``T(8,128)(2,1)`` tile of the collapsed rows, byte for byte), a
    token tile of whole lane blocks, and buffers that fit the VMEM the
    kernel asks for."""
    tile = _chunk_token_tile(chunk, n_heads)
    return bool(
        jnp.dtype(pool_dtype) == jnp.bfloat16
        and head_dim % 128 == 0
        and (kv_heads % 8 == 0 or kv_heads == 4)
        and n_heads % kv_heads == 0
        and (block_size * kv_heads) % 16 == 0
        and _pool_chunk_pages(block_size, kv_heads, head_dim, 1) == 1
        and tile and tile % _LANES == 0
        and _sparse_chunk_vmem_bytes(
            block_size, n_heads, kv_heads, head_dim, chunk, max_blocks,
            tile, 2,
        ) <= _SPARSE_VMEM_BYTES
    )


def _sparse_chunk_kernel(
    layer_ref, start_ref, valid_ref, tbl_ref,     # scalar prefetch
    q_ref, kn_ref, vn_ref, sel_ref, own_ref, k_hbm, v_hbm,   # inputs
    o_ref,                                        # output
    kbuf, vbuf, sem, m_ref, l_ref, acc_ref,       # scratch
    *, chunk_pages: int, page_rows: int, kv_heads: int, group: int,
    tile: int,
):
    """:func:`_chunk_kernel` with each query attending the keys a
    selection marks, and those alone: the same page copies
    (:func:`_page_stream`) and the same online softmax
    (:func:`_online_softmax`), the visibility an operand.

    ``sel_ref`` ``[keys, tile]`` int8 is this token tile's selection
    over the slot's cache rows (nothing marked at or past ``start``:
    what a last page holds there is not the chunk), ``own_ref``
    ``[chunk, tile]`` over the chunk's own keys; both hold causality
    already, so no other mask is applied. Query rows lie ``[kv_heads,
    group * tile, d]``, row ``r`` of a head is token ``r % tile``: the
    ``group`` query heads of a token share its selection, and head-major
    the selection is one lane block wide and repeats. A token tile at or
    past ``valid_ref`` (padding) reads nothing and is left zero. A
    query may find no key of its own in a chunk of pages, or in all of
    them; it finds one before the end (a selection marks a key for
    every query), which is what :func:`_online_softmax` asks."""
    step = pl.program_id(0)
    layer = layer_ref[0]
    start = start_ref[0]
    block_size = page_rows // kv_heads
    chunk_rows = chunk_pages * block_size
    n_pages = (start + block_size - 1) // block_size
    n_chunks = (n_pages + chunk_pages - 1) // chunk_pages
    rows = tile * group

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
        start_copies, wait_copies, _ = _page_stream(
            layer, tbl_ref, n_pages, k_hbm, v_hbm, kbuf, vbuf, sem,
            chunk_pages=chunk_pages, page_rows=page_rows,
        )
        attend = _online_softmax(
            q_ref, m_ref, l_ref, acc_ref, rows=rows, exact=False
        )

        @pl.when(n_chunks > 0)
        def _():
            start_copies(0, 0)

        def chunk_body(chunk, buf):
            @pl.when(chunk + 1 < n_chunks)
            def _():
                start_copies(chunk + 1, 1 - buf)

            wait_copies(chunk, buf)
            keys = pl.ds(
                pl.multiple_of(chunk * chunk_rows, chunk_rows), chunk_rows
            )

            def head_body(head, carry):
                attend(
                    head,
                    _head_rows(kbuf.at[buf], head, kv_heads, chunk_rows),
                    _head_rows(vbuf.at[buf], head, kv_heads, chunk_rows),
                    sel_ref[keys, :].astype(jnp.int32) != 0,
                )
                return carry

            jax.lax.fori_loop(0, kv_heads, head_body, 0)
            return 1 - buf

        jax.lax.fori_loop(0, n_chunks, chunk_body, 0)

        def own_body(head, carry):
            attend(
                head, kn_ref[head], vn_ref[head],
                own_ref[...].astype(jnp.int32) != 0,
            )
            o_ref[head] = (
                acc_ref[head] / l_ref[head]
            ).T.astype(o_ref.dtype)
            return carry

        jax.lax.fori_loop(0, kv_heads, own_body, 0)


def sparse_chunk_attention(
    q,            # [T, n_heads, d] — one slot's prefill chunk
    k_new,        # [T, kv_heads, d] — the chunk's own K/V, not yet in
    v_new,        #   the pool
    k_pool,       # [layers, num_blocks, block_size, kv_heads, d]
    v_pool,
    layer,        # [] int32
    table_row,    # [max_blocks] int32 — the slot's pages
    start,        # [] int32 — cache rows already filled: [0, start)
    selection,    # [T, max_len] bool — query t's keys, by logical row
    n_valid=None,  # [] int32 — queries at or past it are padding
    interpret=None,
):
    """``ops.sparse_attention.masked_attention`` of the chunk's queries
    over the slot's logical cache with the chunk written at ``start``,
    under ``selection``, without that view and without a logit in HBM:
    rows below ``start`` come straight from the stacked pool through
    ``table_row``, only the pages that hold them, the chunk's own K/V
    from the caller's hands (``start + T <= max_len``), and the
    selection — which holds causality: it marks no row past its query —
    is the only mask (:func:`_sparse_chunk_kernel`).

    Arithmetic is ``masked_attention``'s: K and V as stored, float32
    logits, running max, sum and accumulator, the probabilities rounded
    to the pool's dtype once before they meet V, every selected key of
    every query attended and no other. The query is scaled in its own
    dtype (there the logits are), and the order of summation is an
    online softmax over chunks of ``_POOL_CHUNK_BYTES`` of pages, then
    the chunk itself. A token tile of nothing but padding is skipped
    and its rows are zero; padding rows of a tile with a valid query
    are attended like any other (their selection marks a key too).
    Returns ``[T, n_heads, d]`` in ``q.dtype``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    t, h, d = q.shape
    n_layers, nb_pool, block_size, kh, _ = k_pool.shape
    if h % kh:
        raise ValueError(f"n_heads {h} not divisible by kv_heads {kh}")
    g = h // kh
    page_rows = block_size * kh
    max_blocks = table_row.shape[0]
    max_len = max_blocks * block_size
    chunk_pages = max(1, _pool_chunk_pages(block_size, kh, d, max_blocks))
    # Off the chip (interpret mode) any chunk goes in one tile; on it
    # the caller asked sparse_chunk_kernel_supported.
    tile = _chunk_token_tile(t, h) or t
    n_tiles, rows = t // tile, tile * g
    start = jnp.minimum(jnp.asarray(start, jnp.int32), max_len)
    # [T, kh, g, d] -> [kh, tiles x g x tile, d]: a KV head's query rows
    # together, a token tile a run of rows, head-major inside it.
    qs = (q * d ** -0.5).reshape(n_tiles, tile, kh, g, d)
    qs = qs.transpose(2, 0, 3, 1, 4).reshape(kh, t * g, d)
    own = [x.astype(k_pool.dtype).transpose(1, 0, 2) for x in (k_new, v_new)]

    def by_tile(sel):
        # [T, keys] -> [tiles, keys, tile] int8: KEYS x QUERIES, as the
        # scores lie.
        sel = sel.reshape(n_tiles, tile, -1).transpose(0, 2, 1)
        return sel.astype(jnp.int8)

    # The pool's share of the selection (logical rows below start), to
    # whole chunks of pages; the chunk's own is the [T, T] at start.
    keys = -(-max_blocks // chunk_pages) * chunk_pages * block_size
    below = selection & (jnp.arange(max_len) < start)
    sel_pool = by_tile(jnp.pad(below, ((0, 0), (0, keys - max_len))))
    sel_own = by_tile(
        jax.lax.dynamic_slice_in_dim(selection, start, t, axis=1)
    )
    scalars = (
        jnp.asarray(layer, jnp.int32).reshape(1),
        start.reshape(1),
        jnp.asarray(t if n_valid is None else n_valid, jnp.int32).reshape(1),
        jnp.asarray(table_row, jnp.int32),
    )
    pooled = (n_layers, nb_pool, page_rows, d)
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    tiled = pl.BlockSpec((kh, rows, d), lambda i, *_: (0, i, 0))
    out = pl.pallas_call(
        functools.partial(
            _sparse_chunk_kernel, chunk_pages=chunk_pages,
            page_rows=page_rows, kv_heads=kh, group=g, tile=tile,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(n_tiles,),
            in_specs=[
                tiled, whole, whole,
                pl.BlockSpec((None, keys, tile), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec((None, t, tile), lambda i, *_: (i, 0, 0)),
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
            vmem_limit_bytes=_SPARSE_VMEM_BYTES,
        ),
        interpret=interpret,
        name="paged_pool_sparse_chunk_attention",
    )(
        *scalars, qs, *own, sel_pool, sel_own,
        k_pool.reshape(pooled), v_pool.reshape(pooled),
    )
    out = out.reshape(kh, n_tiles, g, tile, d).transpose(1, 3, 0, 2, 4)
    return out.reshape(t, h, d)
