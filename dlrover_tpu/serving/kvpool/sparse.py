"""The paged decode and prefill programs of a model whose attention
reads a learned selection of its cache (``models/sparse_lm.py``).

The pool holds a THIRD per-token array under the same block tables, the
index keys, logically ``[layers, num_blocks, block_size, index_dim]`` and
on the device ``pack`` tokens to a 128-lane row
(``kvpool/index_pool.IndexKeyPool``; a bare array of the logical shape
is the pool of one token a row, the same code at another row width):
each layer scores the slot's index keys through its table, selects, and
reads only the selected K/V rows (``ops/sparse_attention.py``):

- the decode step (``[slots, 1]`` queries) gathers a slot's index keys
  (one small row a token), finds its ``index_topk`` rows and gathers
  those K/V rows straight from the stacked pool: the K/V a step reads
  is ``topk`` rows a slot, whatever its fill;
- the prefill chunk (``[1, chunk]`` queries whose own keys are causal
  and whose prefix lives in the pool) gathers the slot's view of the
  index keys, lays the chunk's own into it, selects, and attends under
  the selection as a mask, its queries picking ``chunk`` different
  sets: on a TPU in a Pallas kernel that reads K and V from the pool in
  place (``ops.decode_attention.sparse_chunk_attention``), elsewhere by
  ``masked_attention``, the definition, over the gathered views of K
  and V (:func:`chunk_attention_kind` says which).

Both are append-free like the dense in-place programs: the new rows of
all layers land after the layer scan (one row a slot, or the chunk's
blocks), the index keys by ``(layer, block, row)`` coordinate. Below
``index_topk`` rows the selection is the causal mask and both equal full
attention. The programs keep the names ``step`` and ``prefill`` (a trace
names a device op by its program), and the decode step returns, after
the tokens, ``[experts hit (mean over layers), expert rows dropped]``
for the host to fetch with them.
"""

import jax
import jax.numpy as jnp

from dlrover_tpu.models import generate as gen_lib
from dlrover_tpu.models import llama
from dlrover_tpu.models import sparse_lm
from dlrover_tpu.ops import sparse_attention as sa
from dlrover_tpu.serving.engine import _place_first
from dlrover_tpu.serving.kvpool import families
from dlrover_tpu.serving.kvpool.families import SENTINEL_BLOCK
from dlrover_tpu.serving.kvpool.index_pool import (
    IndexKeyPool,
    gather_at_layer,
)


def _at_layer(pool, layer, *index):
    """``pool[layer, *index]`` (``index``: arrays of one shape) as ONE
    gather over the stacked pool (:func:`index_pool.gather_at_layer`).
    Of an :class:`IndexKeyPool`, by TOKEN coordinates: at ``(blocks,
    offsets)`` the tokens' keys ``[..., index_dim]``, at ``(blocks,)``
    the blocks' rows as stored, which flattened are the tokens in
    order."""
    if isinstance(pool, IndexKeyPool):
        if len(index) == 1:
            return pool.blocks_at(layer, *index)
        return pool.tokens_at(layer, *index)
    return gather_at_layer(pool, layer, *index)


def _land_rows(pool, rows, blocks, offsets):
    """``rows [L, n, ...]`` into K or V at ``(blocks [n], offsets [n])``
    of every layer: one scatter of rows, in place."""
    return pool.at[:, blocks, offsets].set(rows.astype(pool.dtype))


def _like(ki, pool: IndexKeyPool):
    """``pool`` as the caller holds its index keys: the pytree, or the
    bare array it came as."""
    return pool if isinstance(ki, IndexKeyPool) else pool.rows


def _scores_as_stored(q_idx, w, view, pack: int):
    """Index scores ``[..., q, rows * pack]`` of queries ``q_idx [..., q,
    hi, di]`` over a view AS STORED ``[..., rows, pack * di]``, the rows
    never un-paired: the queries are laid into each token's lanes of a
    row-wide operand (``[q | 0]``, ``[0 | q]``; the other lanes add exact
    zeros to the float32 sum), each placement scores every row
    (``sa.index_scores``), and the ``pack`` score rows are interleaved
    into token order."""
    if pack == 1:
        return sa.index_scores(q_idx, w, view)
    di = q_idx.shape[-1]
    lead = [(0, 0)] * (q_idx.ndim - 1)
    placed = jnp.stack([
        jnp.pad(q_idx, lead + [(j * di, (pack - 1 - j) * di)])
        for j in range(pack)
    ])
    scores = jax.vmap(lambda q: sa.index_scores(q, w, view))(placed)
    scores = jnp.moveaxis(scores, 0, -1)          # [..., q, rows, pack]
    # index_scores scaled by the operand's width, pack x the key's
    return scores.reshape(scores.shape[:-2] + (-1,)) * pack ** 0.5


def _layer(config, params, p, layer, x, positions, attend):
    """One decoder block with the cache behind ``attend(q, k, v, q_idx,
    k_idx, w)``; returns the new rows for the caller to land."""
    residual = x
    with jax.named_scope("attn"):
        q, k, v, q_idx, k_idx, w = sparse_lm.attention_inputs(
            config, p, x, positions
        )
        attn = attend(q, k, v, q_idx, k_idx, w)
        x = llama.attention_out(config, p, attn, residual)
    x, counters = sparse_lm.expert_mlp(
        config, p, x, params["layers"], layer
    )
    return x, (k, v, k_idx), counters


def decode_select(config, ki, layer, tables, lengths, block_size: int,
                  q_idx, k_idx, w):
    """Score a layer's index keys through the tables and select: every
    slot's query (at position ``lengths``) over its pool rows and its
    own new row -> (``idx [slots, topk]`` logical rows, ``valid [slots,
    topk]``)."""
    slots, max_blocks = tables.shape
    max_len = max_blocks * block_size
    topk = min(config.index_topk, max_len)
    at = jnp.minimum(lengths, max_len - 1)
    pool = IndexKeyPool.of(ki)
    with jax.named_scope("index"):
        view = pool.blocks_at(layer, tables).reshape(
            slots, max_len // pool.pack, -1
        )
        view = pool.lay_in(view, k_idx[:, 0], at)
        scores = _scores_as_stored(q_idx, w, view, pool.pack)[:, 0]
    with jax.named_scope("select"):
        visible = jnp.arange(max_len)[None, :] <= at[:, None]
        return sa.select_indices(scores, visible, topk)


def decode_attend(config, k, v, ki, layer, tables, lengths,
                  block_size: int):
    """The decode step's ``attend`` for one layer: select, gather the
    selected K/V rows from the stacked pool (the query's own row comes
    from the layer's hands) and attend over them."""
    max_len = tables.shape[1] * block_size
    at = jnp.minimum(lengths, max_len - 1)

    def attend(q, k_new, v_new, q_idx, k_idx, w):
        idx, valid = decode_select(
            config, ki, layer, tables, lengths, block_size, q_idx, k_idx, w
        )
        with jax.named_scope("sparse"):
            blk = jnp.take_along_axis(tables, idx // block_size, axis=1)
            off = idx % block_size
            own = (idx == at[:, None])[..., None, None]
            k_sel = jnp.where(
                own, k_new.astype(k.dtype), _at_layer(k, layer, blk, off)
            )
            v_sel = jnp.where(
                own, v_new.astype(v.dtype), _at_layer(v, layer, blk, off)
            )
            return sa.gathered_attention(q[:, 0], k_sel, v_sel, valid)[:, None]

    return attend


def _slot_view(pool, layer, table_row, new, start, block_size: int):
    """One slot's logical rows of ``pool`` with the chunk's own rows
    ``new [1, chunk, ...]`` laid in at ``start``. K and V are gathered
    ROW by row: gathered block by block, what reads the view next (a
    head's keys) hands its layout up through the gather, and the
    compiler re-lays the whole pool to suit it. The index keys' view is
    one key a row, ``[max_len, index_dim]``, whatever the pool's."""
    if pool.ndim == 4:                     # index keys: block by block
        pool = IndexKeyPool.of(pool)
        rows = pool.blocks_at(layer, table_row).reshape(-1, pool.index_dim)
    else:
        at = jnp.arange(table_row.shape[0] * block_size)
        rows = _at_layer(
            pool, layer, table_row[at // block_size], at % block_size
        )
    return jax.lax.dynamic_update_slice_in_dim(
        rows, new[0].astype(rows.dtype), start, axis=0
    )


# Queries a prefill chunk scores, selects and attends for at a time:
# a sub-block whose rows all lie at or past ``n_valid`` (the padding of
# a short last chunk) is skipped.
CHUNK_QUERY_BLOCK = 128


def chunk_select(config, ki_view, at, q_idx, w):
    """A block of chunk queries' selection as a mask ``[rows,
    max_len]``: queries at logical positions ``at [rows]`` over the
    slot's view of the index keys (the chunk's own laid in)."""
    max_len = ki_view.shape[0]
    with jax.named_scope("index"):
        scores = sa.index_scores(q_idx, w, ki_view)
    with jax.named_scope("select"):
        visible = jnp.arange(max_len)[None, :] <= at[:, None]
        return sa.select_mask(
            scores, visible, min(config.index_topk, max_len)
        )


def chunk_attend(config, k, v, ki, layer, table_row, start,
                 block_size: int, n_valid=None, kind=None):
    """The prefill chunk's ``attend`` for one layer: the chunk's queries
    (positions ``start ...``) over the slot's rows below ``start`` and
    the chunk's own, under each query's selection. The selection is made
    a block of queries at a time (:func:`chunk_select`); rows at or past
    ``n_valid`` (None: none) are padding: their output is never read,
    and a block of nothing else selects nothing and is left at zero.

    ``kind`` (:func:`chunk_attention_kind`; None: asked here,
    of what this call can see) says what attends under the selection:
    ``"chunk_kernel"``, ``ops.decode_attention.sparse_chunk_attention``
    once for the chunk with K and V read from the pool in place, or
    ``"masked_attention"``, the definition, a block at a time over the
    slot's gathered view."""
    def attend(q, k_new, v_new, q_idx, k_idx, w):
        chunk = q.shape[1]
        how = kind or chunk_attention_kind(
            config, k.dtype, block_size, chunk, table_row.shape[0]
        )
        sub = min(CHUNK_QUERY_BLOCK, chunk)
        if chunk % sub:
            sub = chunk
        ki_view = _slot_view(ki, layer, table_row, k_idx, start, block_size)

        def blocks(run, nothing):
            """``run(lo, take)`` for each block of queries ``lo ...``
            with a valid row, ``nothing`` for the others, stacked."""
            def block(lo):
                take = lambda a: jax.lax.dynamic_slice_in_dim(  # noqa: E731
                    a[0], lo, sub, axis=0
                )
                if n_valid is None:
                    return run(lo, take)
                return jax.lax.cond(
                    lo < n_valid, lambda: run(lo, take),
                    lambda: jnp.zeros(*nothing),
                )

            return jax.lax.map(block, jnp.arange(0, chunk, sub))

        def select(lo, take):
            return chunk_select(
                config, ki_view, start + lo + jnp.arange(sub),
                take(q_idx), take(w),
            )

        if how == "chunk_kernel":
            # Imported here: Pallas costs ~1.2 s, and only a process
            # that may run the kernel pays it.
            from dlrover_tpu.ops.decode_attention import (
                sparse_chunk_attention,
            )

            selection = blocks(select, ((sub, ki_view.shape[0]), bool))
            with jax.named_scope("sparse"):
                out = sparse_chunk_attention(
                    q[0], k_new[0], v_new[0], k, v, layer, table_row,
                    start, selection.reshape(chunk, -1), n_valid,
                )
            return out[None]

        k_view, v_view = (
            _slot_view(pool, layer, table_row, new, start, block_size)
            for pool, new in ((k, k_new), (v, v_new))
        )

        def run(lo, take):
            mask = select(lo, take)
            with jax.named_scope("sparse"):
                return sa.masked_attention(take(q), k_view, v_view, mask)

        out = blocks(run, ((sub,) + q.shape[2:], q.dtype))
        return out.reshape((1, chunk) + q.shape[2:])

    return attend


def decode_forward(config, k, v, ki, params, tables, lengths, tokens,
                   block_size: int):
    """All layers for one token a slot: float32 ``logits [slots,
    vocab]``, the new rows ``(k, v, k_idx)`` each ``[L, slots, 1, ...]``
    and per layer the experts hit and the expert rows dropped."""
    positions = lengths[:, None]
    x = llama.embed_tokens(config, params, tokens[:, None])

    def body(carry, layer_in):
        # The pools are closed over WHOLE, as in the dense in-place
        # programs: the gathers pick their rows from all layers'.
        pl, layer = layer_in
        y, new, c = _layer(
            config, params, pl, layer, carry, positions,
            decode_attend(config, k, v, ki, layer, tables, lengths,
                          block_size),
        )
        return y, (new, c.experts_hit, c.rows_dropped)

    x, (news, hit, dropped) = sparse_lm.scan_layers(config, params, body, x)
    return llama.unembed(config, params, x)[:, 0], news, hit, dropped


def chunk_forward(config, k, v, ki, params, tokens, table_row, start,
                  block_size: int, n_valid=None, kind=None):
    """All layers for one slot's chunk ``tokens [1, chunk]`` at rows
    ``start ...``: the final hidden states ``[1, chunk, d]`` and the
    chunk's new rows ``(k, v, k_idx)`` each ``[L, chunk, ...]``. Rows at
    or past ``n_valid`` are padding, and ``kind`` is what attends under
    the selection (:func:`chunk_attend`)."""
    positions = (
        start + jnp.arange(tokens.shape[1], dtype=jnp.int32)
    )[None, :]
    x = llama.embed_tokens(config, params, tokens)

    def body(carry, layer_in):
        pl, layer = layer_in
        y, (k_new, v_new, ki_new), _ = _layer(
            config, params, pl, layer, carry, positions,
            chunk_attend(config, k, v, ki, layer, table_row, start,
                         block_size, n_valid, kind),
        )
        return y, (k_new[0], v_new[0], ki_new[0])

    return sparse_lm.scan_layers(config, params, body, x)


def build_decode(config, slots: int, max_blocks: int, block_size: int,
                 counts, kinds=None):
    max_len = max_blocks * block_size

    def step(k, v, ki, params, tables, lengths, tokens, active, temps,
             rng, step_idx, first=0, first_slot=-1):
        counts["decode"] += 1  # traces only
        tokens = _place_first(tokens, first, first_slot)
        logits, (k_news, v_news, ki_news), hit, dropped = decode_forward(
            config, k, v, ki, params, tables, lengths, tokens, block_size
        )
        write = jnp.minimum(lengths, max_len - 1)
        blk = jnp.take_along_axis(
            tables, (write // block_size)[:, None], axis=1
        )[:, 0]
        blk = jnp.where(active, blk, SENTINEL_BLOCK)
        off = jnp.where(active, write % block_size, 0)
        k = _land_rows(k, k_news[:, :, 0], blk, off)
        v = _land_rows(v, v_news[:, :, 0], blk, off)
        ki = _like(ki, IndexKeyPool.of(ki).land_tokens(
            ki_news[:, :, 0], blk, off
        ))
        sub = jax.random.fold_in(rng, step_idx * 2)
        nxt = gen_lib.sample_token(logits, sub, temps)
        aux = jnp.stack([
            jnp.mean(hit.astype(jnp.float32)),
            jnp.sum(dropped).astype(jnp.float32),
        ])
        return k, v, ki, jnp.where(active, nxt, tokens), aux

    return step


def build_prefill(config, max_blocks: int, block_size: int, chunk: int,
                  counts, kinds=None):
    """``kinds``: :func:`kinds`' answers for this shape (None: asked when
    the chunk is traced)."""
    kind = (kinds or {}).get("sparse_chunk_attention")

    def land(pool, rows, table_row, start):
        # ``rows`` [L, chunk, ...] at the slot's logical rows start ...
        at = start + jnp.arange(chunk)
        return _land_rows(
            pool, rows, table_row[at // block_size], at % block_size
        )

    def prefill(k, v, ki, params, tokens, table_row, start, n_valid,
                temp, rng, step_idx, last=True):
        counts["prefill"] += 1  # traces only
        x, (k_news, v_news, ki_news) = chunk_forward(
            config, k, v, ki, params, tokens, table_row, start, block_size,
            n_valid, kind,
        )
        k = land(k, k_news, table_row, start)
        v = land(v, v_news, table_row, start)
        ki = _like(ki, IndexKeyPool.of(ki).land_run(
            ki_news, table_row, start, block_size, SENTINEL_BLOCK
        ))

        def head():
            h = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
            logits = llama.unembed(config, params, h)[0, 0]
            sub = jax.random.fold_in(rng, step_idx * 2 + 1)
            return gen_lib.sample_token(logits, sub, temp)

        first = jax.lax.cond(last, head, lambda: jnp.zeros((), jnp.int32))
        return k, v, ki, first

    return prefill


# ---- what the family states (kvpool/families.py) ----------------------------

POOL_ATTENTION = "sparse_gather"


def chunk_attention_kind(config, pool_dtype, block_size: int, chunk: int,
                         max_blocks: int) -> str:
    """What the prefill chunk attends with under its selection
    (:func:`chunk_attend`): ``"chunk_kernel"``
    (``ops.decode_attention.sparse_chunk_attention``: K and V read from
    the pool in place, the selection applied to the scores in VMEM)
    where that kernel lowers — a TPU, a bf16 pool, a page that is one
    DMA, a token tile of whole lane blocks, buffers inside the VMEM it
    asks for — and ``"masked_attention"``, the definition, over the
    slot's gathered views everywhere else. Decided by what the code can
    see, like ``dense.pool_attention_kind`` and for its reasons: no
    option, nothing falls back after it, so what it admits has to
    compile (``tests/test_tpu_compile.py`` holds it to the cell's
    shape). The decode step is not its business: that one gathers the
    selected rows whatever this says."""
    if not families._on_tpu():
        return "masked_attention"
    from dlrover_tpu.ops.decode_attention import (
        sparse_chunk_kernel_supported,
    )

    if sparse_chunk_kernel_supported(
        pool_dtype, block_size, config.n_heads, config.n_kv_heads,
        config.head_dim, chunk, max_blocks,
    ):
        return "chunk_kernel"
    return "masked_attention"


def kinds(config, pool_dtype, block_size: int, chunk: int, slots: int = 0,
          max_blocks: int = 0):
    return {"sparse_chunk_attention": chunk_attention_kind(
        config, pool_dtype, block_size, chunk, max_blocks
    )}
