"""The paged decode and prefill programs of a model whose layers are
lightning linear attention or block-sparse attention
(``models/linear_sparse_lm.py``).

Two kinds of cache, both the engine's (``kvpool/layout.py``):

- per-TOKEN rows in pages, for the sparse layers alone: ``k_pages`` and
  ``v_pages [sparse layers x KV heads, num_blocks, block_size,
  head_dim]``, ONE KV head a pool layer, so that a selected block of a
  head is one whole page; and ``ckeys [sparse layers x KV heads,
  num_blocks, block_size / stride, head_dim]``, the compressed keys, an
  array at a STRIDE. Place ``p`` of a sequence's compressed keys (the
  mean of rows ``[stride (p - 1), stride (p + 1))``) lives in the block
  that holds its LAST row, block ``p // r`` at offset ``p % r`` (``r =
  block_size / stride``): every array of a block is then a function of
  the tokens up to that block's end alone, which is what lets two
  requests share a document's blocks and differ after it (the key that
  straddles the boundary sits in each one's own first private block).
  The program that lands rows ``[a, b)`` lands every place whose last
  row falls in ``[a, b)``, reading the ``stride`` (a chunk: block-aligned
  start) or ``2 stride - 1`` (a decode step) rows before from the pool.
- per-SLOT state, for the lightning layers: ``lightning [layers, slots,
  heads, d, d]`` FLOAT32 (the model's config states the dtype), and
  beside it the snapshots the prefix cache's entries own.

Every program takes and hands back all five arrays. The decode step
updates the state of its ACTIVE slots (one rank-1 update and one read a
(slot, head): in place through VMEM where :func:`lightning_decode_kind`
answers ``state_kernel``, ``ops/lightning_attention.py``) and leaves the
others' alone; a prefill chunk starts from
its slot's state, leaves the state after its last VALID row and writes
the state as of row ``snap_at`` of the chunk into snapshot ``snap_id``
(sentinel 0: none) — both are :func:`lightning_state_after` of the same
operands. A sparse layer scores the slot's compressed keys through its
table (scope ``attn/select``; the decode step in place, a run of
consecutive blocks a copy, where :func:`select_kind` answers
``pool_kernel``, ``ops/block_select.py``), makes the LIST of blocks of
each (slot, KV head) and maps it to pages through the table: the decode
step then attends over the listed pages (``attn/sparse``; in place where
:func:`decode_attention_kind` answers ``pool_kernel``,
``ops/block_sparse_attention.py``), a chunk over the slot's rows under
the block mask in spans of :data:`CHUNK_SPAN_BLOCKS` blocks, a tile of
:data:`CHUNK_QUERY_ROWS` queries at a time and only the tiles that hold
a valid row (``masked_blocks``: 512 queries select 512 lists, whose union
at seeded weights is every block). Both programs are append-free: the new
rows and compressed keys land after the layer loop. Chunk starts are
block-aligned.

The ``*_kind`` functions decide from what they can see and say so in
``kv_stats()`` and the engine's construction log line; there is no
option for any of them.
"""

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.models import generate as gen_lib
from dlrover_tpu.models import linear_sparse_lm as lsm
from dlrover_tpu.ops import block_select
from dlrover_tpu.ops import block_sparse_attention as block_ops
from dlrover_tpu.serving.engine import _place_first
from dlrover_tpu.serving.kvpool import families
from dlrover_tpu.serving.kvpool.families import SENTINEL_BLOCK
from dlrover_tpu.serving.kvpool.latent import (
    _softmax_add,
    _softmax_finish,
    _softmax_start,
)

# Blocks of a slot's prefix a tile of a prefill chunk's queries scores
# at a time, and the queries a tile holds: a tile whose first token is at
# or past the chunk's ``n_valid`` scores nothing and answers zeros (this
# traffic's questions fill ~215 of a chunk's 512 rows, and at 65k rows a
# layer's attention under the mask took 21 ms a chunk for all 512: my
# chip run, PR 55).
CHUNK_SPAN_BLOCKS = 32
CHUNK_QUERY_ROWS = 128


def chunk_query_rows(chunk: int) -> int:
    """Queries a tile of a ``chunk``-token prefill chunk holds."""
    return chunk if chunk % CHUNK_QUERY_ROWS else CHUNK_QUERY_ROWS


def check_shapes(config, block_size: int, chunk: int) -> None:
    """What these programs are not built for, refused by name."""
    if block_size != config.sparse_block:
        raise ValueError(
            f"block_size {block_size} must be the model's sparse block of "
            f"{config.sparse_block} rows: a selected block is a page"
        )
    if chunk % block_size:
        raise ValueError(
            f"prefill_chunk {chunk} must be whole blocks of {block_size}: "
            "a chunk of this model starts at any block boundary"
        )


def lightning_chunk_kind(config) -> str:
    """What a prefill chunk's lightning layers run: ``"jnp"``
    (``linear_sparse_lm.lightning_chunk``, the chunk algebra in
    ``jax.numpy``: two batched matmuls a head and the state's term)."""
    return "jnp"


def lightning_decode_kind(config, state_dtype=jnp.float32) -> str:
    """What the decode step's lightning layers run: ``"state_kernel"``
    (``ops.lightning_attention.state_step``: a slot's state of a layer
    through VMEM once, updated and read there, written back in place)
    where that kernel lowers — a TPU, a float32 state of 128-wide heads
    whose one slot fits the kernel's VMEM — and ``"jnp"``
    (``linear_sparse_lm.lightning_step``: an elementwise rank-1 update
    and a reduction over the state), the definition, everywhere else."""
    if not families._on_tpu():
        return "jnp"
    from dlrover_tpu.ops.lightning_attention import state_kernel_supported

    if state_kernel_supported(
        state_dtype, config.lightning_heads, config.lightning_head_dim
    ):
        return "state_kernel"
    return "jnp"


def select_kind(config, pool_dtype=None, slots: int = 0,
                max_blocks: int = 0) -> str:
    """What scores the DECODE step's compressed keys: ``"pool_kernel"``
    (``ops.block_select.pool_block_scores``: the pool in place, read
    through the table in runs of blocks, scores and softmax in VMEM)
    where that kernel lowers — a TPU, a bf16 ``ckeys`` of 128-wide rows
    whose one list fits the kernel's VMEM and whose tables fit its
    scalar memory — and ``"jnp"`` (``linear_sparse_lm.block_scores``
    over the slot's places gathered through its table), the definition,
    everywhere else. The blocks are listed by ``lax.top_k`` either way,
    and a prefill chunk's selection (the threshold mask of
    ``ops/sparse_attention.py`` over the gathered view) is ``jnp``
    everywhere."""
    if families._on_tpu() and max_blocks and (
        block_select.select_kernel_supported(
            pool_dtype, config.ckeys_per_block, config.head_dim,
            config.group, slots, max_blocks,
        )
    ):
        return "pool_kernel"
    return "jnp"


def decode_attention_kind(config, pool_dtype, block_size: int) -> str:
    """What the decode step reads its listed pages with:
    ``"pool_kernel"`` (``ops.block_sparse_attention
    .list_decode_attention``: the pool in place, a listed page a DMA)
    where that kernel lowers — a TPU, a bf16 pool, a page of whole (16,
    128) tiles inside a VMEM chunk — and ``"gathered_pages"``, the
    definition, everywhere else."""
    if not families._on_tpu():
        return "gathered_pages"
    if block_ops.list_kernel_supported(
        pool_dtype, block_size, config.head_dim
    ):
        return "pool_kernel"
    return "gathered_pages"


def chunk_attention_kind(config) -> str:
    """What a prefill chunk's sparse layers attend with:
    ``"masked_blocks"``, dense attention under the block mask over the
    slot's rows in spans of :data:`CHUNK_SPAN_BLOCKS` blocks."""
    return "masked_blocks"


def kinds(config, pool_dtype, block_size: int, chunk: int = 0,
          slots: int = 0, max_blocks: int = 0):
    """The five, by name, for ``kv_stats()`` (``slots``, ``max_blocks``:
    the decode step's tables; 0: no step is asked about)."""
    return {
        "lightning_chunk": lightning_chunk_kind(config),
        "lightning_decode": lightning_decode_kind(config),
        "block_select": select_kind(config, pool_dtype, slots, max_blocks),
        "block_decode_attention": decode_attention_kind(
            config, pool_dtype, block_size
        ),
        "block_chunk_attention": chunk_attention_kind(config),
    }


def _places(ck, pool_layer: int, table):
    """A sequence's compressed keys by place through its table:
    ``table [..., max_blocks]`` -> ``[..., max_blocks * r, d]``."""
    view = ck[pool_layer, table]
    return view.reshape(table.shape[:-1] + (-1, view.shape[-1]))


def new_ckey_place(config, lengths):
    """The place a decode step at row ``lengths`` completes (its last
    row is the step's own), and whether it completes one at all."""
    stride = config.kernel_stride
    done = ((lengths + 1) % stride == 0) & (lengths + 1 >= 2 * stride)
    return (lengths + 1) // stride - 1, done


def decode_block_scores(config, q, fresh, ck, layer: int, tables, lengths,
                        active, select: str,
                        group_blocks: int = block_select.GROUP_BLOCKS):
    """The decode step's block scores ``[slots, kv_heads, max_blocks]``
    float32 for sparse layer ``layer``: ``q [slots, heads, d]`` at rows
    ``lengths`` over each slot's compressed keys through its table, the
    step's own new keys ``fresh [slots, kv_heads, d]`` laid over the
    place :func:`new_ckey_place` says (they land in the pool after the
    layer loop). ``select``: :func:`select_kind`'s answer. Both forms
    are ``linear_sparse_lm.block_scores``; the kernel's inactive slots
    read nothing and score 0. ``group_blocks``: the kernel's copy group
    (``tools/bench_sparse_attention.py`` sweeps it; nothing else passes
    it)."""
    c = config
    slots, max_blocks = tables.shape
    kh = c.n_kv_heads
    place, done = new_ckey_place(c, lengths)
    if select == "pool_kernel":
        qg = q.reshape(slots, kh, c.group, c.head_dim)
        own = jnp.einsum(
            "skgd,skd->skg", qg, fresh, preferred_element_type=jnp.float32
        ) * c.head_dim ** -0.5
        return block_select.pool_block_scores(
            qg, own, ck, c.pool_layer(layer, 0), tables,
            jnp.where(active, (lengths + 1) // c.kernel_stride, 0),
            jnp.where(done, place, -1), group_blocks=group_blocks,
        )
    here = (
        jnp.arange(max_blocks * c.ckeys_per_block)[None, :] == place[:, None]
    ) & done[:, None]
    views = jnp.stack([
        jnp.where(
            here[..., None], fresh[:, j, None, :],
            _places(ck, c.pool_layer(layer, j), tables),
        ) for j in range(kh)
    ], axis=2)                                    # [slots, P, kh, d]
    return jax.vmap(
        lambda q1, ck1, t: lsm.block_scores(c, q1[None], ck1, t[None])[:, 0]
    )(q, views, lengths)


def decode_block_lists(config, scores, lengths):
    """``select_block_list`` a slot: ``scores [slots, kv_heads, blocks]``
    of queries at rows ``lengths`` -> (``blocks [slots, kv_heads,
    width]``, ``count [slots, kv_heads]``)."""
    return jax.vmap(
        lambda s, t: tuple(
            x[:, 0] for x in lsm.select_block_list(
                config, s[:, None], t[None]
            )
        )
    )(scores, lengths)


def decode_attend(config, kp, vp, ck, layer: int, tables, lengths,
                  block_size: int, kind=None, active=None, taps=None,
                  left=None, select=None):
    """The decode step's ``attend`` for sparse layer ``layer``: one query
    a slot. ``left``: a dict the new compressed keys land in (``[slots,
    kv_heads, d]``, for the caller to write where
    :func:`new_ckey_place` says). ``taps``: the probes' (``scores``,
    ``blocks``, ``count``). ``kind``, ``select``:
    :func:`decode_attention_kind`'s and :func:`select_kind`'s answers
    (None: asked here)."""
    c = config
    slots, max_blocks = tables.shape
    n_pool = kp.shape[1]
    stride, kh = c.kernel_stride, c.n_kv_heads
    kind = kind or decode_attention_kind(c, kp.dtype, block_size)
    select = select or select_kind(c, ck.dtype, slots, max_blocks)
    if active is None:
        active = jnp.ones((slots,), bool)
    # The rows the completed place averages: the step's own and the 2 *
    # stride - 1 before it, through the table.
    rows = jnp.maximum(
        lengths[:, None] - (2 * stride - 1) + jnp.arange(2 * stride)[None],
        0,
    )
    row_blk = jnp.take_along_axis(tables, rows // block_size, axis=1)
    row_off = rows % block_size

    def attend(q, k_new, v_new):
        q, k_new, v_new = q[:, 0], k_new[:, 0], v_new[:, 0]
        with jax.named_scope("select"):
            fresh = []
            for j in range(kh):
                before = kp[
                    c.pool_layer(layer, j), row_blk, row_off
                ].astype(jnp.float32)
                mean = (
                    jnp.sum(before[:, :-1], axis=1)
                    + k_new[:, j].astype(jnp.float32)
                ) / (2 * stride)
                fresh.append(mean.astype(ck.dtype))
            fresh = jnp.stack(fresh, axis=1)          # [slots, kh, d]
            if left is not None:
                left["ckeys"] = fresh
            scores = decode_block_scores(
                c, q, fresh, ck, layer, tables, lengths, active, select
            )                                     # [slots, kh, blocks]
            blocks, count = decode_block_lists(c, scores, lengths)
            if taps is not None:
                taps.update(scores=scores, blocks=blocks, count=count)
            # The list as pages of this layer's KV heads, all pool
            # layers' pages on one axis.
            listed = jnp.take_along_axis(
                tables[:, None, :], jnp.minimum(blocks, max_blocks - 1),
                axis=2,
            )
            listed = jnp.where(blocks < max_blocks, listed, SENTINEL_BLOCK)
            first = jnp.asarray(
                [c.pool_layer(layer, j) * n_pool for j in range(kh)]
            )
            pages = (listed + first[None, :, None]).reshape(slots * kh, -1)
            seen = (
                (count - 1) * block_size + (lengths % block_size)[:, None]
            ).reshape(-1)
        with jax.named_scope("sparse"):
            args = (
                q.reshape(slots * kh, c.group, c.head_dim),
                k_new.reshape(slots * kh, -1), v_new.reshape(slots * kh, -1),
                kp.reshape((-1,) + kp.shape[2:]),
                vp.reshape((-1,) + vp.shape[2:]), pages, seen,
            )
            if kind == "pool_kernel":
                out = block_ops.list_decode_attention(
                    *args, jnp.repeat(active, kh)
                )
            else:
                out = block_ops.list_attention(*args)
        return out.reshape(slots, 1, c.n_heads, c.head_dim).astype(q.dtype)

    return attend


def chunk_attend(config, kp, vp, ck, layer: int, table_row, start,
                 block_size: int, taps=None, left=None, n_valid=None):
    """The prefill chunk's ``attend`` for sparse layer ``layer``: the
    chunk's queries (positions ``start ...``) over the slot's rows below
    ``start`` and over the chunk's own, under each query's block mask.
    ``left``: a dict the chunk's new compressed keys land in (``[chunk /
    stride, kv_heads, d]``, places ``start / stride ...``). ``n_valid``
    (a traced scalar; None: all): tokens at or past it are padding, and
    the query tiles (:func:`chunk_query_rows`) that hold none of the
    valid ones are not attended and answer exact ZEROS (they still ride
    through every later layer and land in the pool: written, invisible,
    overwritten)."""
    c = config
    stride, kh, bs = c.kernel_stride, c.n_kv_heads, block_size
    per = c.ckeys_per_block
    max_blocks = table_row.shape[0]
    span_blocks = min(CHUNK_SPAN_BLOCKS, max_blocks)
    span = span_blocks * bs
    n_table = -(-max_blocks // span_blocks) * span_blocks
    table = jnp.pad(table_row, (0, n_table - max_blocks),
                    constant_values=SENTINEL_BLOCK)
    f32 = jnp.float32
    scale = c.head_dim ** -0.5

    def attend(q, k_new, v_new):
        q, k_new, v_new = q[0], k_new[0], v_new[0]
        chunk = q.shape[0]
        positions = start + jnp.arange(chunk, dtype=jnp.int32)
        with jax.named_scope("select"):
            # The stride rows below the chunk (block-aligned start: the
            # last ones of the block before it).
            before_blk = table_row[jnp.maximum(start // bs - 1, 0)]
            before = jnp.stack([
                kp[c.pool_layer(layer, j), before_blk, bs - stride:]
                for j in range(kh)
            ], axis=1)                                 # [stride, kh, d]
            fresh = lsm.compressed_keys(
                jnp.concatenate([before, k_new.astype(kp.dtype)]), stride
            )                                   # [chunk / stride, kh, d]
            if left is not None:
                left["ckeys"] = fresh
            view = jnp.stack([
                _places(ck, c.pool_layer(layer, j), table) for j in range(kh)
            ], axis=1)                                 # [P, kh, d]
            view = jax.lax.dynamic_update_slice_in_dim(
                jnp.pad(view, ((0, fresh.shape[0]), (0, 0), (0, 0))),
                fresh, start // stride, axis=0,
            )[:n_table * per]
            scores = lsm.block_scores(c, q, view, positions)
            mask = lsm.select_block_mask(c, scores, positions)
            if taps is not None:
                taps.update(scores=scores, mask=mask)
        with jax.named_scope("sparse"):
            tile = chunk_query_rows(chunk)
            own = jax.lax.dynamic_slice_in_dim(
                jnp.pad(mask, ((0, 0), (0, 0), (0, chunk // bs))),
                start // bs, chunk // bs, axis=2,
            )

            def rows_of(pool, ids):
                return jnp.stack([
                    pool[c.pool_layer(layer, j), ids].reshape(
                        -1, c.head_dim
                    ) for j in range(kh)
                ])

            def one_tile(i, out):
                first = i * tile
                qg = jax.lax.dynamic_slice_in_dim(q, first, tile).reshape(
                    tile, kh, c.group, c.head_dim
                )
                picked = jax.lax.dynamic_slice_in_dim(mask, first, tile, 1)

                def add(carry, k_rows, v_rows, visible):
                    """One more span: ``k_rows`` / ``v_rows [kh, t, d]``,
                    ``visible [kh, tile, t]``."""
                    s = jnp.einsum(
                        "qkgd,ktd->kgqt", qg, k_rows,
                        preferred_element_type=f32,
                    ) * scale
                    s = jnp.where(visible[:, None], s, -jnp.inf)
                    s = s.reshape((c.n_heads,) + s.shape[2:])

                    def values(probs):
                        probs = probs.astype(v_rows.dtype).reshape(
                            (kh, c.group) + probs.shape[1:]
                        )
                        o = jnp.einsum(
                            "kgqt,ktd->qkgd", probs, v_rows,
                            preferred_element_type=f32,
                        )
                        return o.reshape(tile, c.n_heads, c.head_dim)

                    return _softmax_add(carry, s, values)

                def prefix_span(j, carry):
                    ids = jax.lax.dynamic_slice_in_dim(
                        table, j * span_blocks, span_blocks
                    )
                    seen = jax.lax.dynamic_slice_in_dim(
                        picked, j * span_blocks, span_blocks, axis=2
                    )
                    below = (j * span + jnp.arange(span)) < start
                    return add(
                        carry, rows_of(kp, ids), rows_of(vp, ids),
                        jnp.repeat(seen, bs, axis=-1) & below[None, None],
                    )

                carry = jax.lax.fori_loop(
                    0, (start + span - 1) // span, prefix_span,
                    _softmax_start(c.n_heads, tile, c.head_dim),
                )
                causal = (
                    jnp.arange(chunk)[None, :]
                    <= first + jnp.arange(tile)[:, None]
                )
                carry = add(
                    carry, jnp.moveaxis(k_new, 1, 0),
                    jnp.moveaxis(v_new, 1, 0),
                    jnp.repeat(
                        jax.lax.dynamic_slice_in_dim(own, first, tile, 1),
                        bs, axis=-1,
                    ) & causal[None],
                )
                return jax.lax.dynamic_update_slice_in_dim(
                    out, _softmax_finish(carry).astype(q.dtype), first, 0
                )

            n_tiles = chunk // tile if n_valid is None else (
                (jnp.minimum(n_valid, chunk) + tile - 1) // tile
            )
            out = jax.lax.fori_loop(
                0, n_tiles, one_tile, jnp.zeros(q.shape, q.dtype)
            )
            return out[None]

    return attend


def decode_forward(config, kp, vp, ck, state, params, tables, lengths,
                   tokens, block_size: int, taps=None, *, kind=None,
                   active=None, state_kind=None, select=None):
    """All layers for one token a slot: float32 ``logits [slots,
    vocab]``, the sparse layers' new rows ``(k, v, ckeys) [pool layers,
    slots, d]`` and the state with every lightning layer's update of the
    active slots in it (an inactive slot's is what it was): the state is
    THREADED through the layers, each writing its own layer's slice in
    place as soon as it has read it (landed after the loop the array is
    copied whole, there and back: the later layers read what the earlier
    ones have not yet written). ``taps``: a dict a layer's ``{layer:
    taps}`` land in (the checks' probes). ``kind``, ``state_kind``,
    ``select``: :func:`decode_attention_kind`'s,
    :func:`lightning_decode_kind`'s and :func:`select_kind`'s answers
    (None: asked here)."""
    c = config
    positions = lengths[:, None]
    slots = tokens.shape[0]
    live = jnp.ones((slots,), bool) if active is None else active
    keep = live[:, None, None, None]
    state_kind = state_kind or lightning_decode_kind(c, state.dtype)
    x = lsm.embed(c, params, tokens[:, None])
    box = {"state": state}
    k_news, v_news, c_news = [], [], []
    for layer, layer_kind in enumerate(c.mixer_types):
        at = c.index_in_kind(layer)
        seen = None if taps is None else taps.setdefault(layer, {})
        if layer_kind == lsm.LIGHTNING:
            slopes = lsm.decay_slopes(c, layer)

            def mix(q, k, v, at=at, slopes=slopes):
                if state_kind == "state_kernel":
                    from dlrover_tpu.ops.lightning_attention import (
                        state_step,
                    )

                    o, box["state"] = state_step(
                        q[:, 0], k[:, 0], v[:, 0], box["state"], at, slopes,
                        live,
                    )
                    return o[:, None]
                old = box["state"][at]
                o, new = lsm.lightning_step(
                    q[:, 0], k[:, 0], v[:, 0], old, slopes
                )
                box["state"] = box["state"].at[at].set(
                    jnp.where(keep, new, old)
                )
                return o[:, None]

            x, _ = lsm.block(c, params, layer, x, positions, mix, taps=seen)
        else:
            left = {}
            x, (k_new, v_new) = lsm.block(
                c, params, layer, x, positions,
                decode_attend(
                    c, kp, vp, ck, layer, tables, lengths, block_size,
                    kind, active, taps=seen, left=left, select=select,
                ),
                taps=seen,
            )
            for j in range(c.n_kv_heads):
                k_news.append(k_new[:, 0, j])
                v_news.append(v_new[:, 0, j])
                c_news.append(left["ckeys"][:, j])
    logits = lsm.unembed(c, params, x)[:, 0]
    rows = lambda new: (  # noqa: E731
        jnp.stack(new) if new else jnp.zeros((0, slots, c.head_dim))
    )
    return logits, (rows(k_news), rows(v_news), rows(c_news)), box["state"]


def chunk_forward(config, kp, vp, ck, state, params, tokens, table_row,
                  start, slot, block_size: int, taps=None, n_valid=None):
    """All layers for one slot's chunk ``tokens [1, chunk]`` at rows
    ``start ...`` from the slot's state: the final residual, the sparse
    layers' new rows ``(k, v) [pool layers, chunk, d]`` and compressed
    keys ``[pool layers, chunk / stride, d]``, and a lightning layer's
    ``(k, v, state it entered with, slopes)`` for
    :func:`linear_sparse_lm.lightning_state_after`."""
    c = config
    chunk = tokens.shape[1]
    positions = (start + jnp.arange(chunk, dtype=jnp.int32))[None]
    x = lsm.embed(c, params, tokens)
    runs, k_news, v_news, c_news = [], [], [], []
    for layer, layer_kind in enumerate(c.mixer_types):
        at = c.index_in_kind(layer)
        seen = None if taps is None else taps.setdefault(layer, {})
        if layer_kind == lsm.LIGHTNING:
            slopes = lsm.decay_slopes(c, layer)
            own = jax.lax.dynamic_index_in_dim(
                state[at], slot, axis=0, keepdims=False
            )

            def mix(q, k, v, own=own, slopes=slopes):
                runs.append((k[0], v[0], own, slopes))
                return lsm.lightning_chunk(q[0], k[0], v[0], own, slopes)[None]

            x, _ = lsm.block(c, params, layer, x, positions, mix, taps=seen)
        else:
            left = {}
            x, (k_new, v_new) = lsm.block(
                c, params, layer, x, positions,
                chunk_attend(
                    c, kp, vp, ck, layer, table_row, start, block_size,
                    taps=seen, left=left, n_valid=n_valid,
                ),
                taps=seen,
            )
            for j in range(c.n_kv_heads):
                k_news.append(k_new[0, :, j])
                v_news.append(v_new[0, :, j])
                c_news.append(left["ckeys"][:, j])
    return x, (k_news, v_news, c_news), runs


def build_decode(config, slots: int, max_blocks: int, block_size: int,
                 counts, kinds=None):
    """``kinds``: :func:`kinds`' answers for this shape (None: asked when
    the step is traced)."""
    max_len, kinds = max_blocks * block_size, kinds or {}
    kind, state_kind, select = (kinds.get(name) for name in (
        "block_decode_attention", "lightning_decode", "block_select",
    ))
    per = config.ckeys_per_block

    def step(kp, vp, ck, state, snaps, params, tables, lengths, tokens,
             active, temps, rng, step_idx, first=0, first_slot=-1):
        counts["decode"] += 1  # traces only
        tokens = _place_first(tokens, first, first_slot)
        logits, (k_new, v_new, c_new), state = decode_forward(
            config, kp, vp, ck, state, params, tables, lengths, tokens,
            block_size, kind=kind, active=active, state_kind=state_kind,
            select=select,
        )
        write = jnp.minimum(lengths, max_len - 1)
        blk = jnp.take_along_axis(
            tables, (write // block_size)[:, None], axis=1
        )[:, 0]
        blk = jnp.where(active, blk, SENTINEL_BLOCK)
        off = jnp.where(active, write % block_size, 0)
        if k_new.shape[0]:
            # The layer is a COORDINATE of the scatter (a window across
            # the layer axis makes the compiler re-lay the whole pool).
            at = (
                jnp.arange(k_new.shape[0])[:, None],
                jnp.broadcast_to(blk, k_new.shape[:2]),
                jnp.broadcast_to(off, k_new.shape[:2]),
            )
            kp = kp.at[at].set(k_new.astype(kp.dtype))
            vp = vp.at[at].set(v_new.astype(vp.dtype))
            # The place this row completes, if any, in the block that
            # holds the row.
            place, done = new_ckey_place(config, write)
            lands = active & done
            cblk = jnp.where(lands, blk, SENTINEL_BLOCK)
            coff = jnp.where(lands, place % per, 0)
            ck = ck.at[(
                jnp.arange(c_new.shape[0])[:, None],
                jnp.broadcast_to(cblk, c_new.shape[:2]),
                jnp.broadcast_to(coff, c_new.shape[:2]),
            )].set(c_new.astype(ck.dtype))
        sub = jax.random.fold_in(rng, step_idx * 2)
        nxt = gen_lib.sample_token(logits, sub, temps)
        return kp, vp, ck, state, snaps, jnp.where(active, nxt, tokens)

    return step


def build_prefill(config, max_blocks: int, block_size: int, chunk: int,
                  counts, kinds=None):
    check_shapes(config, block_size, chunk)
    n_touch = chunk // block_size

    def prefill(kp, vp, ck, state, snaps, params, tokens, table_row, start,
                n_valid, temp, rng, step_idx, last=True, slot=0, snap_at=0,
                snap_id=0):
        counts["prefill"] += 1  # traces only
        x, (k_new, v_new, c_new), runs = chunk_forward(
            config, kp, vp, ck, state, params, tokens, table_row, start,
            slot, block_size, n_valid=n_valid,
        )
        if k_new:
            # Whole blocks from a block-aligned start; a block past the
            # slot's allocation (or the table's end) is the sentinel.
            ids = jax.lax.dynamic_slice_in_dim(
                jnp.pad(table_row, (0, n_touch),
                        constant_values=SENTINEL_BLOCK),
                start // block_size, n_touch,
            )
            land = lambda pool, rows: pool.at[:, ids].set(  # noqa: E731
                jnp.stack(rows).astype(pool.dtype).reshape(
                    len(rows), n_touch, -1, rows[0].shape[-1]
                )
            )
            kp, vp, ck = land(kp, k_new), land(vp, v_new), land(ck, c_new)
        if runs:
            with jax.named_scope("state"):
                after = lambda n: jnp.stack([  # noqa: E731
                    lsm.lightning_state_after(k, v, own, slopes, n)
                    for k, v, own, slopes in runs
                ])
                state = state.at[:, slot].set(
                    after(n_valid).astype(state.dtype)
                )
                with jax.named_scope("snapshot"):
                    snaps = snaps.at[:, snap_id].set(
                        after(snap_at).astype(snaps.dtype)
                    )

        def head():
            h = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
            logits = lsm.unembed(config, params, h)[0, 0]
            sub = jax.random.fold_in(rng, step_idx * 2 + 1)
            return gen_lib.sample_token(logits, sub, temp)

        first = jax.lax.cond(last, head, lambda: jnp.zeros((), jnp.int32))
        return kp, vp, ck, state, snaps, first

    return prefill


def ckey_copy_stats(config, tables, fills):
    """How often the selection's run copies engage (``kv_stats()``; the
    host's ``tables [slots, max_blocks]`` and rows ``fills [slots]`` of
    the active slots): ``ckey_copy_groups``, the groups of
    ``ops.block_select.GROUP_BLOCKS`` table entries a decode step's
    selection reads a (sparse layer, KV head), and
    ``ckey_copy_groups_run_share``, the share of them that are
    consecutive ids and so one copy each (0.0 with no group)."""
    c = config
    places = (np.asarray(fills, np.int64).reshape(-1) + 1) // c.kernel_stride
    groups, runs = block_select.copy_groups(
        np.asarray(tables), -(-places // c.ckeys_per_block)
    )
    return {
        "ckey_copy_groups": groups,
        "ckey_copy_groups_run_share": runs / groups if groups else 0.0,
    }


def rows_listed(config, fill: int) -> int:
    """Rows ONE list of a query at row ``fill`` holds that the query sees
    (its own row among them): every row up to its own while it sees at
    most ``dense_len``, else its selected blocks' (whole but the last)."""
    c = config
    if fill + 1 <= c.dense_len:
        return fill + 1
    blocks = min(c.topk, fill // c.sparse_block + 1)
    return (blocks - 1) * c.sparse_block + fill % c.sparse_block + 1


def decode_counts(config, fills):
    """What a decode launch over slots at rows ``fills`` carries, for
    its ``serving.step`` span: ``ckey_rows``, the (sparse layer, place)
    pairs its queries score (a pair is every KV head's compressed key of
    that place); ``selected_rows``, the rows one list a slot attends
    (beside ``kv_rows``, the rows a dense layer would); ``state_slots``,
    the slots whose state the launch reads and writes."""
    c = config
    places = sum(
        max((f + 1) // c.kernel_stride - 1, 0) for f in fills
    )
    return {
        "ckey_rows": places * len(c.sparse_layers),
        "selected_rows": sum(rows_listed(c, f) for f in fills),
        "state_slots": len(fills),
    }


# ---- what the family states (kvpool/families.py) ----------------------------

POOL_ATTENTION = "linear_block_lists"


def pool_stats(engine):
    """The array at a stride: its share of the bytes in use and the whole
    array's size; and what the decode step's selection would copy for the
    slots now active, from the host's tables (never on the step path)."""
    per_block = engine._array_block_bytes["ckeys"]
    held = engine._allocator.stats(engine._live_block_ids())
    at = [r.slot for r in engine.scheduler.active()]
    return {
        "ckey_bytes_in_use": (held["used"] + held["cached"]) * per_block,
        "ckey_bytes": engine.num_blocks * per_block,
        **ckey_copy_stats(
            engine.config, engine._tables[at], engine._lengths[at]
        ),
    }
