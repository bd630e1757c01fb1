"""The paged decode and prefill programs of a latent-attention model
(``models/latent_lm.py``).

The pool holds ONE array, ``latent [layers, num_blocks, block_size,
kv_lora_rank + qk_rope_dim]``: a token's normed latent and its rotated
positional key, with no head axis and no V. A 576-wide row is 4.5 of a
TPU's 128-lane rows, and an array whose rows are not whole lane rows is
given a device layout that every gather and scatter re-tiles the WHOLE
pool for (the described-v5e compile of the bare array: two 4 GB copies a
decode step; PERF.md section 6, PRs 36 and 38), so the device holds two
tokens to a 1,152-lane row (``kvpool/index_pool.IndexKeyPool``, the
index keys' class at another width, which is what ``pool`` is in every
function here; a width that does not pack is one token a row through
the same code) and lands rows by ``(layer, block, row)`` coordinate. Neither program ever builds a key or a value of a
cached token:

- the decode step (``[slots, 1]`` queries) ABSORBS the up-projection:
  ``w_kvb``'s key half is folded into the query
  (``latent_lm.absorb_queries``), a score is the query's dot with the
  cached row, the softmax-weighted sum of the rows' latents comes out
  ``[heads, kv_lora_rank]`` and only then meets ``w_kvb``'s value half
  (``latent_lm.values_out``). It reads each visible row once a layer
  through the slot's table, AS STORED, never un-paired: the query is
  laid into each token's lanes of a row-wide operand, ``[q | 0]`` and
  ``[0 | q]``, and the weighted sum is read back from each token's
  lanes; the query's own row comes from the layer's hands. On a TPU
  that is a Pallas kernel over the pool in place, filled pages only
  (``ops/latent_decode_attention.py``); elsewhere, and as the
  definition of what the kernel computes, a gathered ``[slots,
  max_len]`` view and ``jax.numpy`` (:func:`decode_attention_kind` says
  which);
- the prefill chunk (``[1, chunk]`` queries whose own keys are causal
  and whose prefix lives in the pool) walks the slot's prefix in blocks
  of :data:`CHUNK_PREFIX_ROWS` rows (un-paired: 2.4 MB a block) under a
  running softmax, then its own rows, ABSORBED like the decode step
  (:data:`CHUNK_ATTENTION`), a TILE of queries at a time and only the
  tiles that hold a valid row (:func:`chunk_rows_scored`).

Both are append-free like the dense in-place programs: the new rows of
all layers land after the layer loop (one row a slot, or the chunk's
blocks). The programs keep the names ``step`` and ``prefill`` (a trace
names a device op by its program), and the decode step returns, after
the tokens, ``[experts hit (mean over the expert layers), expert rows
dropped]`` for the host to fetch with them, as ``kvpool/sparse.py``'s
does.
"""

import jax
import jax.numpy as jnp

from dlrover_tpu.models import generate as gen_lib
from dlrover_tpu.models import latent_lm
from dlrover_tpu.serving.engine import _place_first
from dlrover_tpu.serving.kvpool import families
from dlrover_tpu.serving.kvpool.families import SENTINEL_BLOCK
from dlrover_tpu.serving.kvpool.index_pool import tokens_per_row

# Prefix rows a prefill chunk scores at a time, and query rows a TILE of
# it holds (:func:`chunk_query_rows`): their float32 logits are 32 MB.
CHUNK_PREFIX_ROWS = 2048
CHUNK_QUERY_ROWS = 128


def _scores(spec: str, queries, rows):
    """Scores of ``queries`` against cache ``rows`` (an einsum ``spec``):
    bfloat16 operands, products summed in float32, handed on in float32
    (the softmax and its sum follow in float32)."""
    return jnp.einsum(
        spec, queries, rows, preferred_element_type=jnp.float32
    )


# How a prefill chunk scores a block of cached rows (``kv_stats()`` and
# the construction log line say it): the decode step's form, queries
# folded through ``w_kvb``, scores and the weighted sum over the rows
# themselves (``2 T heads R (2 kv_lora_rank + qk_rope_dim)`` FLOPs a
# layer). The other form, keys and values built from a block's latents
# (half the FLOPs at the cell's shape), read 47.0 ms a chunk against
# 47.2 on the chip (PR 38; PERF.md section 6). By the cell's trace at
# 512 query rows (ledger, PRs 40, 43; PERF.md section 5) a chunk waits
# ~22 ms for matmul fusions, at no less than ~82 % of the MXU's peak,
# and ~10.8 ms for float32 softmax passes: the matmuls are two thirds,
# and both scale with the query rows scored. One form was kept, the one
# that shares the decode step's code.
CHUNK_ATTENTION = "absorbed"


def decode_attention_kind(config, pool_dtype, block_size: int,
                          max_blocks: int, slots: int) -> str:
    """What the decode step reads its cached rows with
    (:func:`decode_attend`): ``"pool_kernel"``
    (``ops.latent_decode_attention.pool_latent_decode_attention``: the
    packed pool read in place, filled pages only, scores, softmax and
    the weighted sum of latents in VMEM) where that kernel lowers — a
    TPU, a bf16 pool whose page is whole (16, 128) tiles and one DMA, a
    row of ``pack * cache_width`` whole 128-lane blocks, query rows of
    whole tiles, buffers inside the VMEM it asks for, the ``slots``
    tables inside the scalar memory — and ``"gathered_view"``, the
    definition, over every slot's whole table gathered, everywhere else.
    Decided by what the code can see, like ``dense.pool_attention_kind``
    and for its reasons: no option, nothing falls back after it, so what
    it admits has to compile (``tests/test_tpu_compile.py`` holds it to
    the cell's shape). ``kv_stats()["latent_decode_attention"]`` and the
    engine's construction log line say which. The prefill chunk is not
    its business (:func:`chunk_attend` as it is)."""
    if not families._on_tpu():
        return "gathered_view"
    # Pallas costs ~1.2 s to import: only a process that may run the
    # kernel pays it (the repo's idiom for ops/ kernels).
    from dlrover_tpu.ops.latent_decode_attention import (
        latent_kernel_supported,
    )

    pack = tokens_per_row(config.cache_width, block_size)
    if latent_kernel_supported(
        pool_dtype, block_size // pack, pack * config.cache_width,
        config.n_heads, pack, slots, max_blocks,
    ):
        return "pool_kernel"
    return "gathered_view"


def _placed(q, pack: int):
    """``q [..., w]`` laid into each token's lanes of a row ``pack``
    tokens wide: ``[pack, ..., pack * w]`` (``[q | 0]``, ``[0 | q]``);
    the other lanes add exact zeros to a float32 sum."""
    w = q.shape[-1]
    lead = [(0, 0)] * (q.ndim - 1)
    return jnp.stack([
        jnp.pad(q, lead + [(j * w, (pack - 1 - j) * w)])
        for j in range(pack)
    ])


def decode_attend(config, pool, layer, tables, lengths, block_size: int,
                  taps=None, kind=None):
    """The decode step's ``attend`` for one layer: every slot's query
    (at position ``lengths``) over its pool rows below ``lengths`` and
    its own new row. ``taps``: a dict filled with what the step keeps to
    itself (a check's probe reads it; the served program passes none):
    the absorbed ``queries [slots, heads, cache_width]`` and their
    ``scores [slots, heads, max_len]`` against the slots' rows, float32,
    before the scale and the mask (the kernel's are its own, a second
    output, zero where a row is not visible).

    ``kind`` (:func:`decode_attention_kind`; None: asked here, of what
    this call can see) says what reads the rows: ``"pool_kernel"``, the
    Pallas kernel over the pool in place, or ``"gathered_view"``, the
    definition, over every slot's whole table gathered."""
    slots, max_blocks = tables.shape
    max_len = max_blocks * block_size
    r, width = config.kv_lora_rank, config.cache_width
    pack = pool.pack
    kind = kind or decode_attention_kind(
        config, pool.dtype, block_size, max_blocks, slots
    )

    def in_place(p, q, own):
        from dlrover_tpu.ops.latent_decode_attention import (
            pool_latent_decode_attention,
        )

        with jax.named_scope("scores"):
            # a tile's scores are formed by this module's _scores, as
            # the gathered form's and the chunk's are
            out = pool_latent_decode_attention(
                q, own, pool.rows, layer, tables, lengths, rank=r,
                scale=config.softmax_scale,
                scores=lambda qs, rows: _scores("qw,kw->qk", qs, rows),
                raw_scores=taps is not None,
            )
            if taps is None:
                mixed = out
            else:
                mixed, scores = out
                taps.update(queries=q, scores=scores)
        with jax.named_scope("values"):
            return latent_lm.values_out(
                config, p, mixed.astype(pool.dtype)
            )[:, None]

    def attend(p, q_nope, q_rope, row):
        with jax.named_scope("absorb"):
            q = latent_lm.absorb_queries(config, p, q_nope[:, 0], q_rope[:, 0])
        if kind == "pool_kernel":
            return in_place(p, q, row[:, 0])
        view = pool.blocks_at(layer, tables).reshape(
            slots, max_len // pack, pack * width
        )
        own = row[:, 0].astype(view.dtype)
        with jax.named_scope("scores"):
            scores = _scores(
                "pshw,stw->shtp", _placed(q, pack), view
            ).reshape(slots, -1, max_len)
            if taps is not None:
                taps.update(queries=q, scores=scores)
            visible = jnp.arange(max_len)[None, :] < lengths[:, None]
            scores = jnp.where(visible[:, None, :], scores, -jnp.inf)
            mine = _scores("shw,sw->sh", q, own)
            probs = jax.nn.softmax(
                jnp.concatenate([scores, mine[..., None]], axis=-1)
                * config.softmax_scale, axis=-1,
            ).astype(view.dtype)
        with jax.named_scope("values"):
            # a token's probability against its row AS STORED; its
            # latent is read back from its own lanes
            wide = jnp.einsum(
                "shtp,stw->pshw",
                probs[..., :-1].reshape(slots, -1, max_len // pack, pack),
                view, preferred_element_type=jnp.float32,
            )
            mixed = sum(
                wide[j, ..., j * width:j * width + r] for j in range(pack)
            ) + probs[..., -1:].astype(jnp.float32) * own[:, None, :r]
            return latent_lm.values_out(
                config, p, mixed.astype(view.dtype)
            )[:, None]

    return attend


def _softmax_start(heads: int, queries: int, width: int):
    """A running softmax over blocks of keys: (largest score, sum of
    exponentials, weighted values ``[queries, heads, width]``), all
    float32."""
    return (
        jnp.full((heads, queries), -jnp.inf, jnp.float32),
        jnp.zeros((heads, queries), jnp.float32),
        jnp.zeros((queries, heads, width), jnp.float32),
    )


def _softmax_add(carry, scores, values):
    """One more block: ``scores [heads, queries, keys]`` float32 (-inf
    where masked), ``values(probs) -> [queries, heads, width]``."""
    top, total, acc = carry
    new_top = jnp.maximum(top, jnp.max(scores, axis=-1))
    # A query that has seen nothing visible yet keeps -inf: its running
    # state stays finite (and zero).
    safe = jnp.where(jnp.isfinite(new_top), new_top, 0.0)
    scale = jnp.exp(top - safe)
    probs = jnp.exp(scores - safe[..., None])
    total = total * scale + jnp.sum(probs, axis=-1)
    acc = acc * scale.T[..., None] + values(probs)
    return new_top, total, acc


def _softmax_finish(carry):
    _, total, acc = carry
    return acc / total.T[..., None]


# The prefill chunk's query tiles. A short last chunk's rows past its
# ``n_valid`` tokens are padding: they come after every valid query, so
# causality hides them, and their own output is never read. The cell's
# turns fill ~215 of a chunk's 512 rows, so the chunk's attention walks
# its queries a tile at a time under a trip count taken from ``n_valid``,
# and a skipped tile's rows come out as exact ZEROS, not as whatever a
# softmax over nothing gives: they still ride through the residual
# mixes, the router and the experts of every later layer and are landed
# in the pool (written, invisible, overwritten), so they have to stay
# finite.
#
# The tile was chosen on the chip among 32 / 64 / 128 / 256, one layer at
# the cell's shape (``tools/bench_paged_decode.py --parts latent``; my
# chip run, PR 44; PERF.md section 6): a FULL chunk 6.01 / 5.10 / 4.59 /
# 4.55 ms against 6.53 as one tile of 512, and over the cell's turns
# (log-uniform 64-512 valid rows) ~3.06 / 2.81 / 2.77 / 3.28: a tile
# costs ~0.34 / 0.55 / 0.98 / 1.9 ms, so the smaller ones skip more rows
# and run each row slower. With the tiles OUTSIDE the prefix blocks'
# loop (a block re-gathered a tile) a layer reads within 0.1 ms of tiles
# inside it (the running state sliced and updated in place) either way
# round, ~2.77 against ~2.80 over the turns: the simpler order was kept.


def chunk_query_rows(chunk: int) -> int:
    """Query rows a tile of a ``chunk``-row prefill chunk holds:
    :data:`CHUNK_QUERY_ROWS`, or the whole chunk where that does not
    divide it (``kv_stats()["latent_chunk_query_rows"]``)."""
    return chunk if chunk % CHUNK_QUERY_ROWS else CHUNK_QUERY_ROWS


def chunk_rows_scored(n_valid, chunk: int, kinds=None):
    """Query rows a chunk of ``n_valid`` valid rows scores: its tiles up
    to the last that holds a valid row. The chunk program takes its trip
    count from this function and an account of a traced run takes its
    rows from it (over the step spans' ``prefill_tokens``), so the two
    cannot disagree. ``n_valid``: an int, an array or a traced scalar
    (``kinds``: the protocol's third argument; one form here)."""
    tile = chunk_query_rows(chunk)
    return -(-n_valid // tile) * tile


def _attend_block(q, rows, visible, carry, scale, rank: int):
    """One more block of cached ``rows [k, cache_width]`` for a tile's
    absorbed queries ``q [tile, heads, cache_width]`` under ``visible
    [tile, k]``: the running softmax ``carry`` (:func:`_softmax_add`)
    with the block's scores and its rows' latents added."""
    with jax.named_scope("scores"):
        scores = _scores("qhw,kw->hqk", q, rows) * scale
        scores = jnp.where(visible[None], scores, -jnp.inf)
    with jax.named_scope("values"):
        return _softmax_add(
            carry, scores,
            lambda probs: jnp.einsum(
                "hqk,kr->qhr", probs.astype(rows.dtype), rows[:, :rank],
                preferred_element_type=jnp.float32,
            ),
        )


def decode_forward(config, pool, params, tables, lengths, tokens,
                   block_size: int, kind=None):
    """All layers for one token a slot: float32 ``logits [slots,
    vocab]``, the new rows ``[L, slots, cache_width]`` and per expert
    layer the experts hit and the expert rows dropped. ``kind``: see
    :func:`decode_attend`."""
    positions = lengths[:, None]
    streams = latent_lm.embed_streams(config, params, tokens[:, None])

    def body(streams, p, layer):
        # The pool is closed over WHOLE, as in the dense in-place
        # programs: the gather picks its rows from all layers'.
        streams, row, c = latent_lm.block(
            config, params, p, layer, streams, positions,
            decode_attend(
                config, pool, layer, tables, lengths, block_size, kind=kind
            ),
        )
        counts = None if c is None else jnp.stack(
            [c.experts_hit, c.rows_dropped]
        )
        return streams, (row[:, 0], counts)

    streams, (rows, counts) = latent_lm.layer_loop(
        config, params, body, streams
    )
    logits = latent_lm.unembed_streams(config, params, streams)[:, 0]
    return logits, rows, counts


def chunk_forward(config, pool, params, tokens, table_row, start,
                  block_size: int, n_valid=None):
    """All layers for one slot's chunk ``tokens [1, chunk]`` at rows
    ``start ...`` (``n_valid``: :func:`chunk_attend`'s): the final streams
    ``[1, chunk, n, d]`` and the chunk's new rows ``[L, chunk, width]``."""
    positions = (start + jnp.arange(tokens.shape[1], dtype=jnp.int32))[None]
    streams = latent_lm.embed_streams(config, params, tokens)

    def body(streams, p, layer):
        streams, row, _ = latent_lm.block(
            config, params, p, layer, streams, positions,
            chunk_attend(
                config, pool, layer, table_row, start, block_size, n_valid
            ),
        )
        return streams, (row[0], None)

    streams, (rows, _) = latent_lm.layer_loop(config, params, body, streams)
    return streams, rows


def build_decode(config, slots: int, max_blocks: int, block_size: int,
                 counts, kinds=None):
    max_len = max_blocks * block_size

    def step(pool, params, tables, lengths, tokens, active, temps, rng,
             step_idx, first=0, first_slot=-1):
        counts["decode"] += 1  # traces only
        tokens = _place_first(tokens, first, first_slot)
        logits, rows, moe = decode_forward(
            config, pool, params, tables, lengths, tokens, block_size,
            kind=(kinds or {}).get("latent_decode_attention"),
        )
        write = jnp.minimum(lengths, max_len - 1)
        blk = jnp.take_along_axis(
            tables, (write // block_size)[:, None], axis=1
        )[:, 0]
        blk = jnp.where(active, blk, SENTINEL_BLOCK)
        off = jnp.where(active, write % block_size, 0)
        pool = pool.land_tokens(rows, blk, off)
        sub = jax.random.fold_in(rng, step_idx * 2)
        nxt = gen_lib.sample_token(logits, sub, temps)
        if moe is None:
            aux = jnp.zeros((2,), jnp.float32)
        else:
            aux = jnp.stack([
                jnp.mean(moe[:, 0].astype(jnp.float32)),
                jnp.sum(moe[:, 1]).astype(jnp.float32),
            ])
        return pool, jnp.where(active, nxt, tokens), aux

    return step


def chunk_attend(config, pool, layer, table_row, start, block_size: int,
                 n_valid=None):
    """The prefill chunk's ``attend`` for one layer: the chunk's queries
    (positions ``start ...``) over the slot's rows below ``start``, a
    block of :data:`CHUNK_PREFIX_ROWS` at a time, and over the chunk's
    own rows, causally, a tile of :func:`chunk_query_rows` queries at a
    time, and only the ``chunk_rows_scored(n_valid, chunk)`` rows of the
    tiles that hold a valid row (``n_valid`` a traced scalar: the trip
    count of the tiles' loop; None: every tile). Every valid row gets
    what the whole chunk would give it, bit for bit (a tile's arithmetic
    does not depend on how many tiles run). The rows of a skipped tile
    are exact ZEROS, never NaN or Inf: later layers and the pool still
    take them (the comment above :func:`chunk_query_rows`)."""
    r = config.kv_lora_rank
    per = max(CHUNK_PREFIX_ROWS // block_size, 1)      # table entries a block
    span = per * block_size
    n_table = -(-table_row.shape[0] // per) * per
    table = jnp.pad(
        table_row, (0, n_table - table_row.shape[0]),
        constant_values=SENTINEL_BLOCK,
    )
    scale = config.softmax_scale

    def attend(p, q_nope, q_rope, row):
        chunk, heads = q_nope.shape[1], q_nope.shape[2]
        tile = chunk_query_rows(chunk)
        cdt = row.dtype
        with jax.named_scope("absorb"):
            q_all = latent_lm.absorb_queries(config, p, q_nope[0], q_rope[0])

        def query_tile(t, out):
            first = t * tile
            q = jax.lax.dynamic_slice_in_dim(q_all, first, tile)

            def prefix_block(i, carry):
                ids = jax.lax.dynamic_slice_in_dim(table, i * per, per)
                rows = pool.blocks_at(layer, ids).reshape(span, -1)
                below = (i * span + jnp.arange(span)) < start
                return _attend_block(
                    q, rows.astype(cdt),
                    jnp.broadcast_to(below[None, :], (tile, span)),
                    carry, scale, r,
                )

            carry = jax.lax.fori_loop(
                0, (start + span - 1) // span, prefix_block,
                _softmax_start(heads, tile, r),
            )
            causal = (
                jnp.arange(chunk)[None, :]
                <= first + jnp.arange(tile)[:, None]
            )
            carry = _attend_block(q, row[0], causal, carry, scale, r)
            return jax.lax.dynamic_update_slice_in_dim(
                out, _softmax_finish(carry).astype(cdt), first, 0
            )

        scored = chunk_rows_scored(
            chunk if n_valid is None else n_valid, chunk
        )
        out = jax.lax.fori_loop(
            0, scored // tile, query_tile,
            jnp.zeros((chunk, heads, r), cdt),
        )
        with jax.named_scope("values"):
            return latent_lm.values_out(config, p, out)[None]

    return attend


def build_prefill(config, max_blocks: int, block_size: int, chunk: int,
                  counts, kinds=None):
    def prefill(pool, params, tokens, table_row, start, n_valid, temp,
                rng, step_idx, last=True):
        counts["prefill"] += 1  # traces only
        streams, rows = chunk_forward(
            config, pool, params, tokens, table_row, start, block_size,
            n_valid,
        )
        pool = pool.land_run(
            rows, table_row, start, block_size, SENTINEL_BLOCK
        )

        def head():
            h = jax.lax.dynamic_slice_in_dim(streams, n_valid - 1, 1, axis=1)
            logits = latent_lm.unembed_streams(config, params, h)[0, 0]
            sub = jax.random.fold_in(rng, step_idx * 2 + 1)
            return gen_lib.sample_token(logits, sub, temp)

        first = jax.lax.cond(last, head, lambda: jnp.zeros((), jnp.int32))
        return pool, first

    return prefill


# ---- what the family states (kvpool/families.py) ----------------------------

POOL_ATTENTION = "latent_absorbed"


def kinds(config, pool_dtype, block_size: int, chunk: int, slots: int = 0,
          max_blocks: int = 0):
    return {
        "latent_decode_attention": decode_attention_kind(
            config, pool_dtype, block_size, max_blocks, slots
        ),
        "latent_chunk_attention": CHUNK_ATTENTION,
    }


def pool_stats(engine):
    """One token's row of one layer in bytes, and the tile of queries a
    chunk scores by (``kv_stats()``)."""
    return {
        "latent_row_bytes": engine._array_block_bytes["latent"] // (
            engine.config.n_layers * engine.block_size
        ),
        "latent_chunk_query_rows": chunk_query_rows(engine.prefill_chunk),
    }
