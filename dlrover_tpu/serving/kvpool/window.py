"""The paged decode and prefill programs of a model whose attention
layers follow a pattern of two reaches, sliding-window and full
(``models/window_lm.py``).

The pool is in GROUPS (``kvpool/layout.py``): ``k`` and ``v [full
layers, num_blocks, block_size, kv_heads, head_dim]``, which keep every
row of a sequence, and ``k_window`` and ``v_window [sliding layers,
window_blocks, ...]``, which keep a row only while a query can still see
it. Each group has its own block ids and its own ``[slots, max_blocks]``
table; the programs take the tables stacked ``[groups, slots,
max_blocks]`` (a chunk: ``[groups, max_blocks]``), in the groups' order,
and index a group's by the LOGICAL block, as one table is: entry ``b``
of a slot's window table names the block that holds its rows ``[b *
block_size, (b + 1) * block_size)`` while the slot holds it, and the
sentinel block 0 before it is allocated and after it is released. A
window layer never reads an entry below its band (``ops/
window_attention.py``), so what a released entry names is never read.

Both programs are append-free: a layer's attention reads the group's
pool in place through the table and takes the new tokens' own K/V from
the layer's hands; the new rows of every layer land in their group after
the layer loop. Where :func:`kinds` answers ``pool_kernel`` (a TPU at
the cell's shape) the full layers call ``ops.decode_attention``'s
accepted kernels and the window layers ``ops.window_attention``'s;
everywhere else both
run the gathered ``jax.numpy`` form (``window_attention.
window_reference``), the definition. Chunk starts are BLOCK-aligned, not
chunk-aligned: a prefix hit resumes at its boundary.

The programs keep the names ``step`` and ``prefill`` (a trace names a
device op by its program), and the decode step returns, after the
tokens, ``[experts hit (mean over the layers), expert rows dropped]``
for the host to fetch with them, as ``kvpool/conv.py``'s does.
"""

import jax
import jax.numpy as jnp

from dlrover_tpu.models import generate as gen_lib
from dlrover_tpu.models import window_lm
from dlrover_tpu.ops.window_attention import window_reference
from dlrover_tpu.serving.engine import _place_first
from dlrover_tpu.serving.kvpool import families
from dlrover_tpu.serving.kvpool.families import SENTINEL_BLOCK


def group_index(config, kind: str) -> int:
    """Which of ``config.cache_groups`` the layers of reach ``kind``
    keep their rows in."""
    names = [name for name, _ in config.cache_groups]
    return names.index(window_lm.GROUP_OF[kind])


def reach_of(config, kind: str):
    """Rows below a query that a layer of reach ``kind`` can still see
    (None: all of them)."""
    if kind == window_lm.FULL:
        return None
    return config.sliding_window - 1


def _group_pools(config, pools, kind: str):
    g = group_index(config, kind)
    return pools[2 * g], pools[2 * g + 1]


def decode_attend(config, layer: int, pools, tables, lengths,
                  block_size: int, kind: str, active=None):
    """The decode step's ``attend`` for ``layer``: one query a slot (at
    position ``lengths``) over the rows of the layer's group that it can
    see and over its own new row."""
    reach_kind = config.layer_types[layer]
    at = config.index_in_kind(layer)
    reach = reach_of(config, reach_kind)
    k_pool, v_pool = _group_pools(config, pools, reach_kind)
    table = tables[group_index(config, reach_kind)]
    slots, max_blocks = table.shape
    if active is None:
        active = jnp.ones((slots,), bool)

    def attend(q, k_new, v_new):
        if kind == "pool_kernel" and reach is None:
            from dlrover_tpu.ops.decode_attention import (
                pool_decode_attention,
            )

            return pool_decode_attention(
                q[:, 0], k_new[:, 0], v_new[:, 0], k_pool, v_pool, at,
                table, lengths, active,
            )[:, None]
        if kind == "pool_kernel":
            from dlrover_tpu.ops.window_attention import (
                pool_window_decode_attention,
            )

            return pool_window_decode_attention(
                q[:, 0], k_new[:, 0], v_new[:, 0], k_pool, v_pool, at,
                table, lengths, active, reach,
            )[:, None]
        max_len = max_blocks * block_size
        view = lambda pool: pool[at, table].reshape(  # noqa: E731
            (slots, max_len) + pool.shape[3:]
        )
        return window_reference(
            q, k_new, v_new, view(k_pool), view(v_pool), lengths[:, None],
            lengths, max_len if reach is None else reach,
        )

    return attend


def chunk_attend(config, layer: int, pools, table_rows, start,
                 block_size: int, kind: str):
    """The prefill chunk's ``attend`` for ``layer``: the chunk's queries
    (positions ``start ...``) over the slot's rows below ``start`` in the
    layer's group that each can see, and over the chunk's own rows."""
    reach_kind = config.layer_types[layer]
    at = config.index_in_kind(layer)
    reach = reach_of(config, reach_kind)
    k_pool, v_pool = _group_pools(config, pools, reach_kind)
    table_row = table_rows[group_index(config, reach_kind)]
    max_len = table_row.shape[0] * block_size

    def attend(q, k_new, v_new):
        if kind == "pool_kernel" and reach is None:
            from dlrover_tpu.ops.decode_attention import pool_chunk_attention

            return pool_chunk_attention(
                q[0], k_new[0], v_new[0], k_pool, v_pool, at, table_row,
                start,
            )[None]
        if kind == "pool_kernel":
            from dlrover_tpu.ops.window_attention import (
                pool_window_chunk_attention,
            )

            return pool_window_chunk_attention(
                q[0], k_new[0], v_new[0], k_pool, v_pool, at, table_row,
                start, reach,
            )[None]
        view = lambda pool: pool[at, table_row].reshape(  # noqa: E731
            (1, max_len) + pool.shape[3:]
        )
        positions = (start + jnp.arange(q.shape[1], dtype=jnp.int32))[None]
        return window_reference(
            q, k_new, v_new, view(k_pool), view(v_pool), positions,
            jnp.reshape(start, (1,)), max_len if reach is None else reach,
        )

    return attend


def _by_group(config, per_layer):
    """``per_layer[layer]`` stacked by group, in the groups' order:
    ``[layers of the group, ...]`` each."""
    out = []
    for name, _ in config.cache_groups:
        layers = [
            i for i, t in enumerate(config.layer_types)
            if window_lm.GROUP_OF[t] == name
        ]
        out.append(jnp.stack([per_layer[i] for i in layers]))
    return out


def decode_forward(config, pools, params, tables, lengths, tokens,
                   block_size: int, taps=None, *, kind="gathered_view",
                   active=None):
    """All layers for one token a slot: float32 ``logits [slots,
    vocab]``, the new rows by group ``[(k, v) [Lg, slots, kv_heads,
    hd]]`` and the expert layers' counters. ``taps``: a dict a layer's
    ``{layer: block taps}`` land in (the checks' probes)."""
    positions = lengths[:, None]
    x = window_lm.embed(config, params, tokens[:, None])
    k_news, v_news, counters = [], [], []
    for layer in range(config.n_layers):
        seen = None if taps is None else taps.setdefault(layer, {})
        x, (k_new, v_new), c = window_lm.block(
            config, params, layer, x, positions,
            decode_attend(config, layer, pools, tables, lengths,
                          block_size, kind, active),
            taps=seen,
        )
        k_news.append(k_new[:, 0])
        v_news.append(v_new[:, 0])
        counters.append(c)
    logits = window_lm.unembed(config, params, x)[:, 0]
    return (logits, list(zip(_by_group(config, k_news),
                             _by_group(config, v_news))), counters)


def chunk_forward(config, pools, params, tokens, table_rows, start,
                  block_size: int, taps=None, *, kind="gathered_view"):
    """All layers for one slot's chunk ``tokens [1, chunk]`` at rows
    ``start ...``: the final residual and the new rows by group ``[(k,
    v) [Lg, chunk, kv_heads, hd]]``."""
    chunk = tokens.shape[1]
    positions = (start + jnp.arange(chunk, dtype=jnp.int32))[None]
    x = window_lm.embed(config, params, tokens)
    k_news, v_news = [], []
    for layer in range(config.n_layers):
        seen = None if taps is None else taps.setdefault(layer, {})
        x, (k_new, v_new), _ = window_lm.block(
            config, params, layer, x, positions,
            chunk_attend(config, layer, pools, table_rows, start,
                         block_size, kind),
            taps=seen,
        )
        k_news.append(k_new[0])
        v_news.append(v_new[0])
    return x, list(zip(_by_group(config, k_news),
                       _by_group(config, v_news)))


def build_decode(config, slots: int, max_blocks: int, block_size: int,
                 counts, kinds=None):
    """``kinds``: :func:`kinds`' answers for this shape (None: the
    definition's)."""
    max_len = max_blocks * block_size
    kind = (kinds or {}).get("window_decode_attention", "gathered_view")
    n_pools = 2 * len(config.cache_groups)

    def step(*args):
        counts["decode"] += 1  # traces only
        pools, rest = args[:n_pools], args[n_pools:]
        (params, tables, lengths, tokens, active, temps, rng, step_idx,
         first, first_slot) = rest
        tokens = _place_first(tokens, first, first_slot)
        logits, new_rows, counters = decode_forward(
            config, pools, params, tables, lengths, tokens, block_size,
            kind=kind, active=active,
        )
        write = jnp.minimum(lengths, max_len - 1)
        off = jnp.where(active, write % block_size, 0)
        out = []
        for g, (k_new, v_new) in enumerate(new_rows):
            # Non-active slots are redirected to the sentinel block.
            blk = jnp.take_along_axis(
                tables[g], (write // block_size)[:, None], axis=1
            )[:, 0]
            blk = jnp.where(active, blk, SENTINEL_BLOCK)
            # The layer is a COORDINATE of the scatter (kvpool/conv.py).
            at = (
                jnp.arange(k_new.shape[0])[:, None],
                jnp.broadcast_to(blk, k_new.shape[:2]),
                jnp.broadcast_to(off, k_new.shape[:2]),
            )
            k, v = pools[2 * g], pools[2 * g + 1]
            out.append(k.at[at].set(k_new.astype(k.dtype)))
            out.append(v.at[at].set(v_new.astype(v.dtype)))
        sub = jax.random.fold_in(rng, step_idx * 2)
        nxt = gen_lib.sample_token(logits, sub, temps)
        return (*out, jnp.where(active, nxt, tokens),
                window_lm.expert_counts(counters))

    return step


def build_prefill(config, max_blocks: int, block_size: int, chunk: int,
                  counts, kinds=None):
    """``kinds``: :func:`kinds`' answers for this shape (None: the
    definition's)."""
    kind = (kinds or {}).get("window_chunk_attention", "gathered_view")
    if chunk % block_size:
        raise ValueError(
            f"prefill_chunk {chunk} must be whole blocks of {block_size}: "
            "a chunk of this model starts at any block boundary"
        )
    n_touch = chunk // block_size
    n_pools = 2 * len(config.cache_groups)

    def prefill(*args):
        counts["prefill"] += 1  # traces only
        pools, rest = args[:n_pools], args[n_pools:]
        (params, tokens, table_rows, start, n_valid, temp, rng, step_idx,
         last) = rest
        x, new_rows = chunk_forward(
            config, pools, params, tokens, table_rows, start, block_size,
            kind=kind,
        )
        out = []
        # A row a token, at its (block, offset) through the group's
        # table (``kvpool/sparse.py``'s form: whole-block windows make
        # the compiler re-lay a pool of 4 KV heads, there and back); a
        # row past the slot's allocation, or the table's end, goes to
        # the sentinel.
        at = start + jnp.arange(chunk, dtype=jnp.int32)
        for g, (k_new, v_new) in enumerate(new_rows):
            blk = jnp.pad(
                table_rows[g], (0, n_touch), constant_values=SENTINEL_BLOCK
            )[at // block_size]
            for pool, rows in ((pools[2 * g], k_new), (pools[2 * g + 1],
                                                        v_new)):
                out.append(pool.at[:, blk, at % block_size].set(
                    rows.astype(pool.dtype)
                ))

        def head():
            h = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
            logits = window_lm.unembed(config, params, h)[0, 0]
            sub = jax.random.fold_in(rng, step_idx * 2 + 1)
            return gen_lib.sample_token(logits, sub, temp)

        first = jax.lax.cond(last, head, lambda: jnp.zeros((), jnp.int32))
        return (*out, first)

    return prefill


# ---- what the family states (kvpool/families.py) ----------------------------

POOL_ATTENTION = "window_groups"


def kinds(config, pool_dtype, block_size: int, chunk: int, slots: int = 0,
          max_blocks: int = 0):
    """What the decode step and the prefill chunk read their cached rows
    with, both reaches alike: ``"pool_kernel"`` (the full layers
    ``ops.decode_attention.pool_decode_attention`` /
    ``pool_chunk_attention``, the window layers ``ops.window_attention
    .pool_window_decode_attention`` / ``pool_window_chunk_attention``:
    the group's pool in place, only the pages that hold a visible row)
    where those kernels lower (``window_attention
    .window_kernels_supported``: a TPU, a bf16 pool of 4 or 8k KV heads x
    128 whose page is one DMA, tables inside the scalar memory) and
    ``"gathered_view"``, the definition, everywhere else. One predicate
    admits both programs' kernels, so the two answers agree today; they
    are named apart because they are reported apart. Decided by what the
    code can see: no option, nothing falls back after it, so what it
    admits has to compile (``tests/test_tpu_compile.py`` holds it to the
    cell's shape)."""
    kind = "gathered_view"
    if families._on_tpu():
        # Pallas costs ~1.2 s to import: only a process that may run the
        # kernels pays it (the repo's idiom for ops/ kernels).
        from dlrover_tpu.ops.window_attention import window_kernels_supported

        if window_kernels_supported(
            pool_dtype, block_size, config.n_heads, config.n_kv_heads,
            config.head_dim, chunk, slots, max_blocks,
        ):
            kind = "pool_kernel"
    return {"window_decode_attention": kind, "window_chunk_attention": kind}
