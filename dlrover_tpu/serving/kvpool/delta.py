"""The paged decode and prefill programs of a model whose layers are the
gated delta rule or un-grouped full attention (``models/delta_lm.py``).

Two kinds of cache, both the engine's (``kvpool/layout.py``):

- per-TOKEN rows in pages, for the full layers alone: ``k`` and ``v [full
  layers, num_blocks, block_size, kv_heads_held, head_dim]``, the dense
  model's arrays (the 30 KV heads held as 32, the last two zeros: what a
  ``[30, 128]`` bfloat16 row pads to on the device anyway), read by the
  dense model's kernels (``ops.decode_attention.pool_decode_attention`` /
  ``pool_chunk_attention``, group 1) where :func:`kinds` says
  ``pool_kernel``, and through the gathered view
  (``ops.window_attention.window_reference``, the definition) everywhere
  else;
- per-SLOT state, for the delta layers, in TWO arrays of two dtypes:
  ``delta [layers, slots, heads, dk, dv]`` FLOAT32, the rule's matrix
  state, and ``taps [layers, slots, K - 1, conv_width]`` in the compute
  dtype, the short convolutions' last inputs; beside each the snapshots
  the prefix cache's entries own. A slot's state is the PAIR: what
  restores, snapshots, gives up or migrates one moves both
  (``kvpool/engine.py`` walks ``layout.state_arrays``; nothing there
  knows there are two).

Every program takes and hands back all six arrays, aliased. The decode
step updates the state of its ACTIVE slots (the delta state of a layer
through VMEM once, ``ops.gated_delta.delta_step``, where
``gated_delta.step_kind`` answers ``state_kernel``; the definition
elsewhere) and leaves the others' alone; a prefill chunk starts from its
slot's state, leaves the state after its last VALID row and writes the
state as of row ``snap_at`` of the chunk into snapshot ``snap_id``
(sentinel 0: none): for the matrix state both are
``gated_delta.delta_chunk``'s, from its one solve; for the taps both are
a slice of ``[taps | projections]``. Both programs are append-free: the
new K and V rows land after the layer loop. Chunk starts are
block-aligned.

:func:`kinds` decides from what it can see and says so in ``kv_stats()``
and the engine's construction log line; there is no option for any of
them.
"""

import jax
import jax.numpy as jnp

from dlrover_tpu.models import delta_lm
from dlrover_tpu.models import generate as gen_lib
from dlrover_tpu.ops import gated_delta
from dlrover_tpu.ops.window_attention import SMEM_TABLE_BYTES, window_reference
from dlrover_tpu.serving.engine import _place_first
from dlrover_tpu.serving.kvpool import families
from dlrover_tpu.serving.kvpool.families import SENTINEL_BLOCK


def sub_chunk(chunk: int) -> int:
    """Rows a solve of the chunk form takes of a ``chunk``-row prefill
    chunk."""
    return chunk if chunk % gated_delta.CHUNK else gated_delta.CHUNK


def check_shapes(config, block_size: int, chunk: int) -> None:
    """What these programs are not built for, refused by name."""
    if chunk % block_size:
        raise ValueError(
            f"prefill_chunk {chunk} must be whole blocks of {block_size}: "
            "a chunk of this model starts at any block boundary"
        )


def kinds(config, pool_dtype, block_size: int, chunk: int, slots: int = 0,
          max_blocks: int = 0):
    """What each of the four parts runs, by name, for ``kv_stats()``:
    the delta layers' chunk (``jnp``: ``gated_delta.delta_chunk``) and
    decode step (``gated_delta.step_kind``), and what the full layers'
    decode step and chunk read their rows with: ``pool_kernel`` (the
    dense model's in-place kernels over ``kv_heads_held`` heads, group 1)
    where both lower (a TPU, a bfloat16 pool whose page tiles and fits a
    VMEM chunk, a chunk whose query tile and buffers fit the kernel's
    VMEM, tables inside the scalar memory) and ``gathered_view``, the
    definition, everywhere else."""
    c = config
    full = "gathered_view"
    if families._on_tpu():
        from dlrover_tpu.ops.decode_attention import chunk_kernel_supported

        held = c.kv_heads_held
        if chunk_kernel_supported(
            pool_dtype, block_size, held, held, c.head_dim, chunk
        ) and slots * max_blocks * 4 <= SMEM_TABLE_BYTES:
            full = "pool_kernel"
    return {
        "delta_chunk": "jnp",
        "delta_decode": gated_delta.step_kind(
            jnp.float32, c.linear_heads, c.linear_key_dim,
            c.linear_value_dim,
        ),
        "full_decode_attention": full,
        "full_chunk_attention": full,
    }


def _held(config, x):
    """``x [..., n_heads, hd]`` padded with zero heads to the pool's."""
    more = config.kv_heads_held - x.shape[-2]
    if not more:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, more), (0, 0)])


def decode_attend(config, k_pool, v_pool, layer: int, tables, lengths,
                  block_size: int, kind: str, active=None):
    """The decode step's ``attend`` for full layer ``layer``: one query a
    slot (at position ``lengths``) over the slot's rows and its own."""
    c = config
    at = c.index_in_kind(layer)
    slots, max_blocks = tables.shape
    if active is None:
        active = jnp.ones((slots,), bool)

    def attend(q, k_new, v_new):
        if kind == "pool_kernel":
            from dlrover_tpu.ops.decode_attention import (
                pool_decode_attention,
            )

            out = pool_decode_attention(
                _held(c, q[:, 0]), _held(c, k_new[:, 0]),
                _held(c, v_new[:, 0]), k_pool, v_pool, at, tables, lengths,
                active,
            )
            return out[:, None, :c.n_heads]
        max_len = max_blocks * block_size
        view = lambda pool: pool[at, tables].reshape(  # noqa: E731
            (slots, max_len) + pool.shape[3:]
        )[:, :, :c.n_kv_heads]
        return window_reference(
            q, k_new, v_new, view(k_pool), view(v_pool), lengths[:, None],
            lengths, max_len,
        )

    return attend


def chunk_attend(config, k_pool, v_pool, layer: int, table_row, start,
                 block_size: int, kind: str):
    """The prefill chunk's ``attend`` for full layer ``layer``: the
    chunk's queries (positions ``start ...``) over the slot's rows below
    ``start`` and over the chunk's own."""
    c = config
    at = c.index_in_kind(layer)
    max_len = table_row.shape[0] * block_size

    def attend(q, k_new, v_new):
        if kind == "pool_kernel":
            from dlrover_tpu.ops.decode_attention import pool_chunk_attention

            out = pool_chunk_attention(
                _held(c, q[0]), _held(c, k_new[0]), _held(c, v_new[0]),
                k_pool, v_pool, at, table_row, start,
            )
            return out[None, :, :c.n_heads]
        view = lambda pool: pool[at, table_row].reshape(  # noqa: E731
            (1, max_len) + pool.shape[3:]
        )[:, :, :c.n_kv_heads]
        positions = (start + jnp.arange(q.shape[1], dtype=jnp.int32))[None]
        return window_reference(
            q, k_new, v_new, view(k_pool), view(v_pool), positions,
            jnp.reshape(start, (1,)), max_len,
        )

    return attend


def decode_forward(config, k_pool, v_pool, delta, taps, params, tables,
                   lengths, tokens, block_size: int, probe=None, *,
                   kinds=None, active=None):
    """All layers for one token a slot: float32 ``logits [slots,
    vocab]``, the full layers' new rows ``(k, v) [full layers, slots,
    kv_heads_held, hd]``, the delta state with every delta layer's update
    of the active slots in it (THREADED through the layers, each writing
    its own layer's slice in place as soon as it has read it:
    ``kvpool/linear.decode_forward``'s reason) and every slot's taps
    after the token ``[delta layers, slots, K - 1, conv_width]`` (the
    caller keeps an idle slot's). ``probe``: a dict a layer's ``{layer:
    probe}`` land in (the checks' probes). ``kinds``: :func:`kinds`'
    answers (None: the definition's)."""
    c = config
    kinds = kinds or {}
    slots = tokens.shape[0]
    live = jnp.ones((slots,), bool) if active is None else active
    keep = live[:, None, None, None]
    x = delta_lm.embed(c, params, tokens[:, None])
    box = {"delta": delta}
    k_news, v_news, new_taps = [], [], []
    for layer, layer_kind in enumerate(c.layer_types):
        at = c.index_in_kind(layer)
        seen = None if probe is None else probe.setdefault(layer, {})
        if layer_kind == delta_lm.DELTA:

            def mix(q, k, v, g, beta, at=at):
                rows = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
                if kinds.get("delta_decode") == "state_kernel":
                    o, box["delta"] = gated_delta.delta_step(
                        *rows, box["delta"], at, live
                    )
                    return o[:, None]
                old = box["delta"][at]
                o, new = gated_delta.delta_step_reference(*rows, old)
                box["delta"] = box["delta"].at[at].set(
                    jnp.where(keep, new, old).astype(delta.dtype)
                )
                return o[:, None]

            x, zz = delta_lm.block(
                c, params, layer, x, mix, taps=taps[at], probe=seen
            )
            new_taps.append(zz[:, 1:])
        else:
            x, (k_new, v_new) = delta_lm.block(
                c, params, layer, x,
                decode_attend(
                    c, k_pool, v_pool, layer, tables, lengths, block_size,
                    kinds.get("full_decode_attention", "gathered_view"),
                    active,
                ),
                probe=seen,
            )
            k_news.append(_held(c, k_new[:, 0]))
            v_news.append(_held(c, v_new[:, 0]))
    logits = delta_lm.unembed(c, params, x)[:, 0]
    rows = lambda new: (  # noqa: E731
        jnp.stack(new) if new
        else jnp.zeros((0, slots, c.kv_heads_held, c.head_dim))
    )
    return (logits, (rows(k_news), rows(v_news)), box["delta"],
            jnp.stack(new_taps) if new_taps else taps)


def chunk_forward(config, k_pool, v_pool, delta, taps, params, tokens,
                  table_row, start, slot, block_size: int, n_valid=None,
                  snap_at=0, probe=None, *, kinds=None):
    """All layers for one slot's chunk ``tokens [1, chunk]`` at rows
    ``start ...`` from the slot's state: the final residual, the full
    layers' new rows ``(k, v) [full layers, chunk, kv_heads_held, hd]``,
    and a delta layer's ``(state after n_valid rows, state after snap_at
    rows, zz [K - 1 + chunk, conv_width])`` (``delta_lm.conv_inputs``:
    the taps after ``n`` rows are ``zz[n:n + K - 1]``)."""
    c = config
    kinds = kinds or {}
    chunk = tokens.shape[1]
    sub = sub_chunk(chunk)
    x = delta_lm.embed(c, params, tokens)
    runs, k_news, v_news = [], [], []
    for layer, layer_kind in enumerate(c.layer_types):
        at = c.index_in_kind(layer)
        seen = None if probe is None else probe.setdefault(layer, {})
        if layer_kind == delta_lm.DELTA:
            own = jax.lax.dynamic_index_in_dim(
                delta[at], slot, axis=0, keepdims=False
            )
            own_taps = jax.lax.dynamic_slice_in_dim(taps[at], slot, 1)
            states = {}

            def mix(q, k, v, g, beta, own=own, states=states):
                o, after, snap = gated_delta.delta_chunk(
                    q[0], k[0], v[0], g[0], beta[0], own, n_valid, snap_at,
                    chunk=sub,
                )
                states.update(after=after, snap=snap)
                return o[None]

            x, zz = delta_lm.block(
                c, params, layer, x, mix, taps=own_taps, probe=seen
            )
            runs.append((states["after"], states["snap"], zz[0]))
        else:
            x, (k_new, v_new) = delta_lm.block(
                c, params, layer, x,
                chunk_attend(
                    c, k_pool, v_pool, layer, table_row, start, block_size,
                    kinds.get("full_chunk_attention", "gathered_view"),
                ),
                probe=seen,
            )
            k_news.append(_held(c, k_new[0]))
            v_news.append(_held(c, v_new[0]))
    return x, (k_news, v_news), runs


def build_decode(config, slots: int, max_blocks: int, block_size: int,
                 counts, kinds=None):
    """``kinds``: :func:`kinds`' answers for this shape (a tuple of
    pairs or a dict; None: the definition's)."""
    max_len = max_blocks * block_size
    kinds = dict(kinds or {})

    def step(k, v, delta, taps, delta_snaps, taps_snaps, params, tables,
             lengths, tokens, active, temps, rng, step_idx, first=0,
             first_slot=-1):
        counts["decode"] += 1  # traces only
        tokens = _place_first(tokens, first, first_slot)
        logits, (k_new, v_new), delta, new_taps = decode_forward(
            config, k, v, delta, taps, params, tables, lengths, tokens,
            block_size, kinds=kinds, active=active,
        )
        with jax.named_scope("attn"), jax.named_scope("conv"):
            # A slot that is not decoding keeps its taps: a prompt still
            # prefilling holds its own there.
            taps = jnp.where(
                active[None, :, None, None], new_taps.astype(taps.dtype),
                taps,
            )
        if k_new.shape[0]:
            write = jnp.minimum(lengths, max_len - 1)
            blk = jnp.take_along_axis(
                tables, (write // block_size)[:, None], axis=1
            )[:, 0]
            blk = jnp.where(active, blk, SENTINEL_BLOCK)
            off = jnp.where(active, write % block_size, 0)
            k = k.at[:, blk, off].set(k_new.astype(k.dtype))
            v = v.at[:, blk, off].set(v_new.astype(v.dtype))
        sub = jax.random.fold_in(rng, step_idx * 2)
        nxt = gen_lib.sample_token(logits, sub, temps)
        return (k, v, delta, taps, delta_snaps, taps_snaps,
                jnp.where(active, nxt, tokens))

    return step


def build_prefill(config, max_blocks: int, block_size: int, chunk: int,
                  counts, kinds=None):
    check_shapes(config, block_size, chunk)
    n_touch = chunk // block_size
    keep = config.conv_kernel - 1
    kinds = dict(kinds or {})

    def prefill(k, v, delta, taps, delta_snaps, taps_snaps, params, tokens,
                table_row, start, n_valid, temp, rng, step_idx, last=True,
                slot=0, snap_at=0, snap_id=0):
        counts["prefill"] += 1  # traces only
        x, (k_new, v_new), runs = chunk_forward(
            config, k, v, delta, taps, params, tokens, table_row, start,
            slot, block_size, n_valid, snap_at, kinds=kinds,
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
                    (len(rows), n_touch, block_size) + rows[0].shape[1:]
                )
            )
            k, v = land(k, k_new), land(v, v_new)
        if runs:
            taps_at = lambda n: jnp.stack([  # noqa: E731
                jax.lax.dynamic_slice_in_dim(zz, n, keep, axis=0)
                for _, _, zz in runs
            ])
            with jax.named_scope("state"):
                delta = delta.at[:, slot].set(
                    jnp.stack([after for after, _, _ in runs]).astype(
                        delta.dtype
                    )
                )
                taps = taps.at[:, slot].set(
                    taps_at(n_valid).astype(taps.dtype)
                )
                with jax.named_scope("snapshot"):
                    delta_snaps = delta_snaps.at[:, snap_id].set(
                        jnp.stack([snap for _, snap, _ in runs]).astype(
                            delta_snaps.dtype
                        )
                    )
                    taps_snaps = taps_snaps.at[:, snap_id].set(
                        taps_at(snap_at).astype(taps_snaps.dtype)
                    )

        def head():
            h = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
            logits = delta_lm.unembed(config, params, h)[0, 0]
            sub = jax.random.fold_in(rng, step_idx * 2 + 1)
            return gen_lib.sample_token(logits, sub, temp)

        first = jax.lax.cond(last, head, lambda: jnp.zeros((), jnp.int32))
        return k, v, delta, taps, delta_snaps, taps_snaps, first

    return prefill


def decode_counts(config, fills):
    """What a decode launch over slots at rows ``fills`` carries, for
    its ``serving.step`` span: ``state_slots``, the slots whose state (of
    every delta layer) the launch reads and writes."""
    return {"state_slots": len(fills)}


# ---- what the family states (kvpool/families.py) ----------------------------

POOL_ATTENTION = "delta_state_and_pages"


def pool_stats(engine):
    """Each state array's bytes a slot (a snapshot): the pair is one
    state."""
    return {"state_array_bytes": {
        a.name: a.entry_bytes() for a in engine._state_layout
    }}
