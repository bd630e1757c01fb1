"""The paged decode and prefill programs of a dense GQA model
(``models/llama.py``): ``k`` and ``v [layers, num_blocks, block_size,
kv_heads, head_dim]`` read through the block tables; with them the
speculative verify / draft programs and every program's int8 twin (K and
V in int8 beside ``k_scale``, ``v_scale``: ``kv_dtype="int8"``), which no
other family has.

**Attention reads the pool in place** (``pool_attention``:
``"paged_kernel"``), in the decode step and the prefill chunk alike. On a
TPU with a bf16 pool whose page tiles and fits a VMEM chunk
(``ops.decode_attention.chunk_kernel_supported``; anything else takes the
gather, nothing fails to build) each layer's attention is one Pallas call
over the WHOLE stacked pool: the layer index, the tables and the fills
pick the pages, only the filled pages are copied (one contiguous DMA a
page), and the new tokens' own K/V come from the layer's hands, so both
programs are append-free: the decode step lands one row a slot after its
layer scan, the prefill chunk its ``[layers, chunk]`` rows (PERF.md §5,
PR 28: the chunk program used to gather one slot's whole ``[max_len]``
view, carry it through the scan and score all of it). The alternative
(``"xla_gather"``: everywhere else, and the plain reference of the parity
tests) gathers each slot's logical ``[max_len]`` view through the table
and runs the flat engine's ``models/generate._layer_decode_read_only`` on
it. On the chip that path moved the cache at its full CAPACITY four times
a layer — the scan's slice of the layer's pool, the gathered view, and
one read each for K and V — as many bytes as the weights at
``nemo12b-serve-chat`` (PERF.md §5, PR 25). Which one an engine's
programs were built with follows from the platform and the pool, not from
a knob (:func:`pool_attention_kind`); the engine logs it once at
construction and reports it in ``kv_stats()``. The verify / draft
programs and int8 pools still gather (no cell runs them).

What the family states (``kvpool/families.py``) is at the file's end.
"""

import jax
import jax.numpy as jnp

from dlrover_tpu.models import generate as gen_lib
from dlrover_tpu.models import llama
from dlrover_tpu.serving import spec_decode as spec_lib
from dlrover_tpu.serving.engine import _place_first
from dlrover_tpu.serving.kvpool import families
from dlrover_tpu.serving.kvpool.families import SENTINEL_BLOCK


def pool_attention_kind(config, block_size: int, kv_dtype: str,
                        chunk: int) -> str:
    """Which attention the plain decode program AND the prefill program
    are built with, one answer for both: ``"paged_kernel"`` (the pool
    read in place, filled pages only) where both kernels lower — a TPU,
    a bf16 pool, a page that tiles and fits a VMEM chunk, a prefill
    chunk whose query tile and buffers fit the VMEM the kernel asks for
    — and ``"xla_gather"`` otherwise. Decided by what the code can see;
    there is no option for it, and nothing falls back after it, so what
    it admits has to compile (``tests/test_tpu_compile.py`` holds it to
    that over GQA, MHA, wide heads and short caches). The cache's size
    is no part of it: on the v5e the decode kernel was ahead of the
    gather down to 4 slots x 576 rows and 16 slots x 128 rows (PERF.md
    §6, PR 25), the chunk kernel at every ``start`` (PR 28)."""
    if kv_dtype != "fp" or not families._on_tpu():
        return "xla_gather"
    # Pallas costs ~1.2 s to import: only a process that may run the
    # kernels pays it (the repo's idiom for ops/ kernels).
    from dlrover_tpu.ops.decode_attention import chunk_kernel_supported

    if chunk_kernel_supported(
        config.compute_dtype, block_size, config.n_heads,
        config.n_kv_heads, config.head_dim, chunk,
    ):
        return "paged_kernel"
    return "xla_gather"


def _layer_over_pool(config, p, x, positions, attend):
    """``generate._layer_decode_read_only`` with the cache behind
    ``attend(q, k_new, v_new)`` (``[b, s, heads, d]`` each) in place of
    a ``[b, max_len]`` slab: the paged programs' memory is a pool and
    tables, so their attention shares no logic with the slab's. Serves
    the decode step (``[slots, 1]``) and the prefill chunk (``[1,
    chunk]``) alike; the caller lands ``k_new`` / ``v_new`` in their
    pages after the layer scan."""
    residual = x
    if "wqkv" in p:
        q, k, v = gen_lib._fused_qkv(config, p, x, positions)
    else:
        q, k, v = llama.attention_qkv(config, p, x, positions)
    attn = attend(q, k, v)
    x = llama.attention_out(config, p, attn, residual)
    if "w_gu" in p:
        x = gen_lib._fused_mlp(config, p, x)
    else:
        x, _ = llama.mlp_block(config, p, x)
    return x, k, v


def _build_paged_decode(config, slots: int, max_blocks: int,
                        block_size: int, counts,
                        quantized: bool = False,
                        attn: str = "xla_gather"):
    """[slots] tokens -> one decoded token per slot, ragged lengths;
    ``first`` / ``first_slot`` as the flat decode step takes them
    (``serving.engine._place_first``).
    ``attn`` (:func:`pool_attention_kind`): ``"paged_kernel"`` reads
    each layer's K/V straight from the stacked pool through the block
    tables, filled pages only; ``"xla_gather"`` gathers the cache per
    layer into a ``[slots, max_len]`` view. ``quantized``: int8 pools +
    per-(row, head) scale pools — the gather streams half the KV bytes
    and the append quantizes each new row (ops/kv_quant);
    dequantization folds into the attention math."""
    max_len = max_blocks * block_size
    kh, hd = config.n_kv_heads, config.head_dim
    def _append_coords(tables, lengths, active):
        # Per-slot append through the table. Non-active slots are
        # redirected to the sentinel block: their garbage must never
        # land in a block another slot may SHARE (the flat engine's
        # own-row invisibility does not survive sharing). Active slots
        # write their privately-owned cursor block (host COW-ensured).
        write = jnp.minimum(lengths, max_len - 1)
        blk = jnp.take_along_axis(
            tables, (write // block_size)[:, None], axis=1
        )[:, 0]
        blk = jnp.where(active, blk, SENTINEL_BLOCK)
        off = jnp.where(active, write % block_size, 0)
        return blk, off

    def _finish(x, params, rng, step_idx, temps, active, tokens):
        logits = llama.unembed(config, params, x)[:, 0]   # [slots, V]
        sub = jax.random.fold_in(rng, step_idx * 2)
        nxt = gen_lib.sample_token(logits, sub, temps)
        return jnp.where(active, nxt, tokens)

    def step(k, v, params, tables, lengths, tokens, active, temps,
             rng, step_idx, first=0, first_slot=-1):
        counts["decode"] += 1  # traces only
        tokens = _place_first(tokens, first, first_slot)
        positions = lengths[:, None]                     # [slots, 1]
        x = llama.embed_tokens(config, params, tokens[:, None])

        def body(carry, layer_in):
            pl, k_c, v_c = layer_in                      # [nb, bs, kh, hd]
            k_view = k_c[tables].reshape(slots, max_len, kh, hd)
            v_view = v_c[tables].reshape(slots, max_len, kh, hd)
            y, k_new, v_new = gen_lib._layer_decode_read_only(
                config, pl, carry, positions, k_view, v_view, lengths
            )
            return y, (k_new, v_new)

        def body_in_place(carry, layer_in):
            # The pools are closed over WHOLE: as scanned inputs the
            # loop would slice a layer's pool out (a copy of all of
            # it) before the kernel could pick its pages.
            from dlrover_tpu.ops.decode_attention import (
                pool_decode_attention,
            )

            pl, layer = layer_in
            y, k_new, v_new = _layer_over_pool(
                config, pl, carry, positions,
                lambda q, k_new, v_new: pool_decode_attention(
                    q[:, 0], k_new[:, 0], v_new[:, 0], k, v, layer,
                    tables, lengths, active,
                )[:, None],
            )
            return y, (k_new, v_new)

        if attn == "paged_kernel":
            x, (k_news, v_news) = jax.lax.scan(
                body_in_place, x,
                (params["layers"],
                 jnp.arange(config.n_layers, dtype=jnp.int32)),
            )
        else:
            x, (k_news, v_news) = jax.lax.scan(
                body, x, (params["layers"], k, v)
            )
        blk, off = _append_coords(tables, lengths, active)
        k = k.at[:, blk, off].set(k_news[:, :, 0].astype(k.dtype))
        v = v.at[:, blk, off].set(v_news[:, :, 0].astype(v.dtype))
        nxt = _finish(x, params, rng, step_idx, temps, active, tokens)
        return k, v, nxt

    def step_q8(k, v, ks, vs, params, tables, lengths, tokens, active,
                temps, rng, step_idx, first=0, first_slot=-1):
        from dlrover_tpu.ops.kv_quant import quantize_kv

        counts["decode"] += 1  # traces only
        tokens = _place_first(tokens, first, first_slot)
        positions = lengths[:, None]
        x = llama.embed_tokens(config, params, tokens[:, None])

        def body(carry, layer_in):
            pl, k_c, v_c, ks_c, vs_c = layer_in
            k_view = k_c[tables].reshape(slots, max_len, kh, hd)
            v_view = v_c[tables].reshape(slots, max_len, kh, hd)
            ks_view = ks_c[tables].reshape(slots, max_len, kh)
            vs_view = vs_c[tables].reshape(slots, max_len, kh)
            y, k_new, v_new = gen_lib._layer_decode_read_only(
                config, pl, carry, positions, k_view, v_view, lengths,
                k_scale=ks_view, v_scale=vs_view,
            )
            return y, (k_new, v_new)

        x, (k_news, v_news) = jax.lax.scan(
            body, x, (params["layers"], k, v, ks, vs)
        )
        blk, off = _append_coords(tables, lengths, active)
        kq, ks_rows = quantize_kv(k_news[:, :, 0])   # [L, slots, kh, hd]
        vq, vs_rows = quantize_kv(v_news[:, :, 0])
        k = k.at[:, blk, off].set(kq)
        v = v.at[:, blk, off].set(vq)
        ks = ks.at[:, blk, off].set(ks_rows)
        vs = vs.at[:, blk, off].set(vs_rows)
        nxt = _finish(x, params, rng, step_idx, temps, active, tokens)
        return k, v, ks, vs, nxt

    return step_q8 if quantized else step


def _build_paged_prefill(config, max_blocks: int, block_size: int,
                         chunk: int, counts, quantized: bool = False,
                         attn: str = "xla_gather"):
    """One prompt chunk into ONE slot's blocks. ``attn``
    (:func:`pool_attention_kind`):

    - ``"paged_kernel"``: the chunk does only its chunk's work. Each
      layer's attention reads the slot's rows below ``start`` straight
      from the stacked pool through ``table_row``
      (``ops.decode_attention.pool_chunk_attention``) and the chunk's
      own K/V from the layer's hands; the pools are closed over whole,
      the layer scan carries nothing of the cache, and after it the
      chunk's rows of all layers land in their pages with one write.
    - ``"xla_gather"`` (everywhere else, and the reference): gather the
      slot's logical ``[max_len]`` cache through its table row, run the
      flat prefill body over it, scatter back only the touched blocks.

    Either way shared untouched blocks are never rewritten (the COW
    invariant), rows at or past ``n_valid`` are written, invisible and
    overwritten later, and the head runs under ``last`` only: the host
    reads the sampled token on a prompt's LAST chunk alone, so every
    other chunk skips the ``[d, vocab]`` matmul and returns token 0.
    ``quantized``: the slot view is dequantized for the
    (compute-bound) chunk forward and the touched span re-quantized on
    the way out — per-(row, head) round-to-nearest is IDEMPOTENT (the
    amax element always maps to ±127), so rows below the chunk inside a
    touched block keep their exact stored values."""
    L = config.n_layers
    kh, hd = config.n_kv_heads, config.head_dim
    max_len = max_blocks * block_size
    # Blocks a chunk can touch: chunk//bs full blocks when chunks are
    # block-multiples, else the single block containing the chunk
    # (init enforces one of chunk % bs == 0 / bs % chunk == 0).
    n_touch = max(chunk // block_size, 1)

    def _positions(start):
        return (start + jnp.arange(chunk, dtype=jnp.int32))[None, :]

    def _run_chunk(k_slot, v_slot, params, tokens, start):
        positions = _positions(start)
        x = llama.embed_tokens(config, params, tokens)

        def body(carry, layer_in):
            pl, k_c, v_c = layer_in
            y, k_c, v_c = gen_lib._layer_decode(
                config, pl, carry, positions, k_c, v_c, start
            )
            return y, (k_c, v_c)

        return jax.lax.scan(
            body, x, (params["layers"], k_slot, v_slot)
        )

    def _touched(arr, start, head_shape):
        # Slice the touched span [touched0*bs, +n_touch*bs) — it
        # covers [start, start+chunk) exactly (chunk-aligned starts;
        # see the divisibility contract), so shared UNtouched blocks
        # are never rewritten.
        touched0 = start // block_size
        seg = jax.lax.dynamic_slice(
            arr, (0, 0, touched0 * block_size) + (0,) * len(head_shape),
            (L, 1, n_touch * block_size) + head_shape,
        ).reshape((L, n_touch, block_size) + head_shape)
        return seg, touched0

    def _first_token(x, params, n_valid, temp, rng, step_idx, last):
        def head():
            h = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
            logits = llama.unembed(config, params, h)[0, 0]    # [V]
            sub = jax.random.fold_in(rng, step_idx * 2 + 1)
            return gen_lib.sample_token(logits, sub, temp)

        return jax.lax.cond(last, head, lambda: jnp.zeros((), jnp.int32))

    def _land_chunk(pool, rows, table_row, start):
        # ``rows`` [L, chunk, kh, hd] into their pages: whole blocks
        # when a chunk is a multiple of a block, else the chunk's span
        # inside the one block that holds it.
        rows = rows.astype(pool.dtype)
        if chunk % block_size == 0:
            ids = jax.lax.dynamic_slice(
                table_row, (start // block_size,), (n_touch,)
            )
            return pool.at[:, ids].set(
                rows.reshape(L, n_touch, block_size, kh, hd)
            )
        return jax.lax.dynamic_update_slice(
            pool, rows[:, None],
            (0, table_row[start // block_size], start % block_size, 0, 0),
        )

    def prefill_in_place(k, v, params, tokens, table_row, start, n_valid,
                         temp, rng, step_idx, last=True):
        from dlrover_tpu.ops.decode_attention import pool_chunk_attention

        counts["prefill"] += 1  # traces only
        positions = _positions(start)
        x = llama.embed_tokens(config, params, tokens)

        def body(carry, layer_in):
            # The pools are closed over WHOLE (see the decode step's
            # body_in_place); what the scan stacks is the chunk's own
            # K/V, [chunk, kh, hd] a layer.
            pl, layer = layer_in
            y, k_new, v_new = _layer_over_pool(
                config, pl, carry, positions,
                lambda q, k_new, v_new: pool_chunk_attention(
                    q[0], k_new[0], v_new[0], k, v, layer, table_row,
                    start,
                )[None],
            )
            return y, (k_new[0], v_new[0])

        x, (k_news, v_news) = jax.lax.scan(
            body, x,
            (params["layers"], jnp.arange(L, dtype=jnp.int32)),
        )
        k = _land_chunk(k, k_news, table_row, start)
        v = _land_chunk(v, v_news, table_row, start)
        first = _first_token(x, params, n_valid, temp, rng, step_idx, last)
        return k, v, first

    def prefill_gather(k, v, params, tokens, table_row, start, n_valid,
                       temp, rng, step_idx, last=True):
        counts["prefill"] += 1  # traces only
        k_slot = k[:, table_row].reshape(L, 1, max_len, kh, hd)
        v_slot = v[:, table_row].reshape(L, 1, max_len, kh, hd)
        x, (k_slot, v_slot) = _run_chunk(
            k_slot, v_slot, params, tokens, start
        )
        seg_k, touched0 = _touched(k_slot, start, (kh, hd))
        seg_v, _ = _touched(v_slot, start, (kh, hd))
        ids = jax.lax.dynamic_slice(table_row, (touched0,), (n_touch,))
        k = k.at[:, ids].set(seg_k.astype(k.dtype))
        v = v.at[:, ids].set(seg_v.astype(v.dtype))
        first = _first_token(x, params, n_valid, temp, rng, step_idx, last)
        return k, v, first

    def prefill_q8(k, v, ks, vs, params, tokens, table_row, start,
                   n_valid, temp, rng, step_idx, last=True):
        from dlrover_tpu.ops.kv_quant import dequantize_kv, quantize_kv

        counts["prefill"] += 1  # traces only
        k_q = k[:, table_row].reshape(L, 1, max_len, kh, hd)
        v_q = v[:, table_row].reshape(L, 1, max_len, kh, hd)
        ks_slot = ks[:, table_row].reshape(L, 1, max_len, kh)
        vs_slot = vs[:, table_row].reshape(L, 1, max_len, kh)
        # f32 view, not compute_dtype: q*scale is exact in f32, so the
        # round trip is idempotent and untouched rows inside touched
        # blocks re-quantize to their exact stored (values, scale).
        k_slot = dequantize_kv(k_q, ks_slot, jnp.float32)
        v_slot = dequantize_kv(v_q, vs_slot, jnp.float32)
        x, (k_slot, v_slot) = _run_chunk(
            k_slot, v_slot, params, tokens, start
        )
        kq_new, ks_new = quantize_kv(k_slot)
        vq_new, vs_new = quantize_kv(v_slot)
        seg_k, touched0 = _touched(kq_new, start, (kh, hd))
        seg_v, _ = _touched(vq_new, start, (kh, hd))
        seg_ks, _ = _touched(ks_new, start, (kh,))
        seg_vs, _ = _touched(vs_new, start, (kh,))
        ids = jax.lax.dynamic_slice(table_row, (touched0,), (n_touch,))
        k = k.at[:, ids].set(seg_k)
        v = v.at[:, ids].set(seg_v)
        ks = ks.at[:, ids].set(seg_ks)
        vs = vs.at[:, ids].set(seg_vs)
        first = _first_token(x, params, n_valid, temp, rng, step_idx, last)
        return k, v, ks, vs, first

    if quantized:
        return prefill_q8
    # Both plain programs go by ``prefill``: a trace names a device op
    # by its program (``jit_prefill:...``), and readers of traces find
    # the chunk program by that name whichever it is.
    prefill = prefill_in_place if attn == "paged_kernel" else prefill_gather
    prefill.__name__ = prefill.__qualname__ = "prefill"
    return prefill



def _build_paged_verify(config, slots: int, max_blocks: int,
                        block_size: int, K: int, counts,
                        quantized: bool = False):
    """Paged sibling of serving.engine._build_verify_step: the T = K+1
    verification queries gather each slot's logical cache through its
    block table and all T new rows land via one advanced-index scatter
    at block coordinates. Invalid writes (inactive slot, or a row at or
    past max_len) are redirected to the sentinel block — the paged
    engine's version of ``mode="drop"``; the host guarantees the rows
    that CAN become visible (fill..fill+accept) sit in allocated,
    privately-owned blocks (_spec_prepare_rows). ``quantized``: the
    layer quantizes its new rows IN-LAYER (per-row round-to-nearest, so
    intra-draft reads see exactly the values a sequential step would
    read back from the int8 cache — the bit-stability rule, §35) and
    the scatter appends the quantized rows + scales directly."""
    max_len = max_blocks * block_size
    kh, hd = config.n_kv_heads, config.head_dim
    T = K + 1

    def _verify_coords(tables, lengths, active):
        writes = (
            lengths[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
        )                                                # [slots, T]
        valid = active[:, None] & (writes < max_len)
        w = jnp.minimum(writes, max_len - 1)
        blk = jnp.take_along_axis(tables, w // block_size, axis=1)
        blk = jnp.where(valid, blk, SENTINEL_BLOCK)
        off = jnp.where(valid, w % block_size, 0)
        # Several invalid columns may collapse onto sentinel (0, 0);
        # duplicate scatter targets are fine — it is garbage writing
        # over garbage in a block that is never read.
        return blk, off

    def verify(k, v, params, tables, lengths, tokens, drafts,
               draft_len, active, temps, rng, step_idx):
        counts["verify"] += 1  # traces only
        toks = jnp.concatenate([tokens[:, None], drafts], axis=1)
        positions = (
            lengths[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
        )
        x = llama.embed_tokens(config, params, toks)

        def body(carry, layer_in):
            pl, k_c, v_c = layer_in
            k_view = k_c[tables].reshape(slots, max_len, kh, hd)
            v_view = v_c[tables].reshape(slots, max_len, kh, hd)
            y, k_new, v_new = gen_lib._layer_verify_read_only(
                config, pl, carry, positions, k_view, v_view, lengths
            )
            return y, (k_new, v_new)

        x, (k_news, v_news) = jax.lax.scan(
            body, x, (params["layers"], k, v)
        )
        blk, off = _verify_coords(tables, lengths, active)
        k = k.at[:, blk, off].set(k_news.astype(k.dtype))
        v = v.at[:, blk, off].set(v_news.astype(v.dtype))
        logits = llama.unembed(config, params, x)        # [slots, T, V]
        emitted, acc = spec_lib.spec_accept(
            logits, drafts, draft_len, temps, active, tokens,
            rng, step_idx,
        )
        return k, v, emitted, acc

    def verify_q8(k, v, ks, vs, params, tables, lengths, tokens,
                  drafts, draft_len, active, temps, rng, step_idx):
        counts["verify"] += 1  # traces only
        toks = jnp.concatenate([tokens[:, None], drafts], axis=1)
        positions = (
            lengths[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
        )
        x = llama.embed_tokens(config, params, toks)

        def body(carry, layer_in):
            pl, k_c, v_c, ks_c, vs_c = layer_in
            k_view = k_c[tables].reshape(slots, max_len, kh, hd)
            v_view = v_c[tables].reshape(slots, max_len, kh, hd)
            ks_view = ks_c[tables].reshape(slots, max_len, kh)
            vs_view = vs_c[tables].reshape(slots, max_len, kh)
            y, kq, ks_rows, vq, vs_rows = (
                gen_lib._layer_verify_read_only(
                    config, pl, carry, positions, k_view, v_view,
                    lengths, k_scale=ks_view, v_scale=vs_view,
                )
            )
            return y, (kq, ks_rows, vq, vs_rows)

        x, (kqs, ks_news, vqs, vs_news) = jax.lax.scan(
            body, x, (params["layers"], k, v, ks, vs)
        )
        blk, off = _verify_coords(tables, lengths, active)
        k = k.at[:, blk, off].set(kqs)
        v = v.at[:, blk, off].set(vqs)
        ks = ks.at[:, blk, off].set(ks_news)
        vs = vs.at[:, blk, off].set(vs_news)
        logits = llama.unembed(config, params, x)
        emitted, acc = spec_lib.spec_accept(
            logits, drafts, draft_len, temps, active, tokens,
            rng, step_idx,
        )
        return k, v, ks, vs, emitted, acc

    return verify_q8 if quantized else verify


def _build_paged_draft(config, slots: int, max_blocks: int,
                       block_size: int, K: int, draft_layers: int,
                       counts, quantized: bool = False):
    """Paged early-exit drafter: K sequential single-token partial
    forwards (first ``draft_layers`` blocks) through the block-table
    gather; each drafted row's partial-layer K/V is appended beyond
    the fill (sentinel-redirected when invalid) so the next draft can
    attend it. The verify pass rewrites all layers of those rows
    before any can become visible."""
    max_len = max_blocks * block_size
    kh, hd = config.n_kv_heads, config.head_dim
    d = draft_layers

    def _coords(tables, lens_i, active):
        valid = active & (lens_i < max_len)
        w = jnp.minimum(lens_i, max_len - 1)
        blk = jnp.take_along_axis(
            tables, (w // block_size)[:, None], axis=1
        )[:, 0]
        blk = jnp.where(valid, blk, SENTINEL_BLOCK)
        off = jnp.where(valid, w % block_size, 0)
        return blk, off

    def draft(k, v, params, tables, lengths, tokens, active):
        counts["draft"] += 1  # traces only
        layers_d = jax.tree_util.tree_map(
            lambda a: a[:d], params["layers"]
        )
        cur = tokens
        drafts = []
        for i in range(K):
            lens_i = lengths + i
            positions = lens_i[:, None]
            x = llama.embed_tokens(config, params, cur[:, None])

            def body(carry, layer_in):
                pl, k_c, v_c = layer_in
                k_view = k_c[tables].reshape(slots, max_len, kh, hd)
                v_view = v_c[tables].reshape(slots, max_len, kh, hd)
                y, k_new, v_new = gen_lib._layer_decode_read_only(
                    config, pl, carry, positions, k_view, v_view,
                    lens_i,
                )
                return y, (k_new, v_new)

            x, (k_news, v_news) = jax.lax.scan(
                body, x, (layers_d, k[:d], v[:d])
            )
            blk, off = _coords(tables, lens_i, active)
            k = k.at[:d, blk, off].set(k_news[:, :, 0].astype(k.dtype))
            v = v.at[:d, blk, off].set(v_news[:, :, 0].astype(v.dtype))
            logits = llama.unembed(config, params, x)[:, 0]
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            cur = jnp.where(active, nxt, cur)
            drafts.append(cur)
        return k, v, jnp.stack(drafts, axis=1)

    def draft_q8(k, v, ks, vs, params, tables, lengths, tokens,
                 active):
        from dlrover_tpu.ops.kv_quant import quantize_kv

        counts["draft"] += 1  # traces only
        layers_d = jax.tree_util.tree_map(
            lambda a: a[:d], params["layers"]
        )
        cur = tokens
        drafts = []
        for i in range(K):
            lens_i = lengths + i
            positions = lens_i[:, None]
            x = llama.embed_tokens(config, params, cur[:, None])

            def body(carry, layer_in):
                pl, k_c, v_c, ks_c, vs_c = layer_in
                k_view = k_c[tables].reshape(slots, max_len, kh, hd)
                v_view = v_c[tables].reshape(slots, max_len, kh, hd)
                ks_view = ks_c[tables].reshape(slots, max_len, kh)
                vs_view = vs_c[tables].reshape(slots, max_len, kh)
                y, k_new, v_new = gen_lib._layer_decode_read_only(
                    config, pl, carry, positions, k_view, v_view,
                    lens_i, k_scale=ks_view, v_scale=vs_view,
                )
                return y, (k_new, v_new)

            x, (k_news, v_news) = jax.lax.scan(
                body, x, (layers_d, k[:d], v[:d], ks[:d], vs[:d])
            )
            blk, off = _coords(tables, lens_i, active)
            kq, ks_rows = quantize_kv(k_news[:, :, 0])
            vq, vs_rows = quantize_kv(v_news[:, :, 0])
            k = k.at[:d, blk, off].set(kq)
            v = v.at[:d, blk, off].set(vq)
            ks = ks.at[:d, blk, off].set(ks_rows)
            vs = vs.at[:d, blk, off].set(vs_rows)
            logits = llama.unembed(config, params, x)[:, 0]
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            cur = jnp.where(active, nxt, cur)
            drafts.append(cur)
        return k, v, ks, vs, jnp.stack(drafts, axis=1)

    return draft_q8 if quantized else draft



# ---- what the family states (kvpool/families.py) ----------------------------

POOL_ATTENTION = "xla_gather"       # (the definition; ``kinds`` answers)


def kinds(config, pool_dtype, block_size: int, chunk: int, slots: int = 0,
          max_blocks: int = 0):
    return {"pool_attention": pool_attention_kind(
        config, block_size,
        "int8" if jnp.dtype(pool_dtype) == jnp.int8 else "fp", chunk,
    )}


def build_decode(config, slots: int, max_blocks: int, block_size: int,
                 counts, kinds=None, quantized: bool = False):
    return _build_paged_decode(
        config, slots, max_blocks, block_size, counts, quantized=quantized,
        attn=(kinds or {}).get("pool_attention", POOL_ATTENTION),
    )


def build_prefill(config, max_blocks: int, block_size: int, chunk: int,
                  counts, kinds=None, quantized: bool = False):
    return _build_paged_prefill(
        config, max_blocks, block_size, chunk, counts, quantized=quantized,
        attn=(kinds or {}).get("pool_attention", POOL_ATTENTION),
    )


# (config, slots, max_blocks, block_size, spec_k[, draft_layers], counts,
# quantized=False): the speculative programs, as the engine asks for them.
build_verify, build_draft = _build_paged_verify, _build_paged_draft
