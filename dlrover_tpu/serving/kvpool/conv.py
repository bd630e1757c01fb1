"""The paged decode and prefill programs of a model whose layers follow a
pattern of gated short convolutions and grouped-query attention
(``models/conv_lm.py``).

Two kinds of cache, both the engine's (``kvpool/layout.py``):

- per-TOKEN rows in pages, for the attention layers alone: ``k_rows`` and
  ``v_rows [attention layers, num_blocks, block_size, kv_heads *
  head_dim]``, a token's K (V) of a layer held FLAT, so that heads
  narrower than a 128-lane row pad nothing (8 heads of 64 are four whole
  lane rows); addressed by the slot's block table like every pool;
- per-SLOT state, for the convolution layers: ``conv_state [conv
  layers, slots, taps - 1, embed_dim]``, the last gated inputs of the
  slot's sequence, which no table addresses, and beside it the
  SNAPSHOTS ``[conv layers, snapshots, taps - 1, embed_dim]`` that the
  prefix cache's entries own (``kvpool/prefix_cache.py``): the state as
  of a block boundary, without which a cached run of blocks cannot be
  continued.

Every program takes and hands back all four arrays. The decode step
reads and writes the state of its ACTIVE slots and leaves the others'
alone; a prefill chunk starts from its slot's state, leaves the state
after its last VALID row (padding rows change nothing: the state after
``n`` rows is a slice of ``[state | z]``, ``conv_lm.conv_mix``), and
writes the state as of row ``snap_at`` of the chunk into snapshot
``snap_id`` (the host passes the sentinel snapshot 0 when it wants
none). Attention reads the slot's rows through its table and the new
tokens' own K/V from the layer's hands. Both programs read them IN
PLACE where :func:`decode_attention_kind` / :func:`chunk_attention_kind`
answer ``pool_kernel`` (a TPU at the cell's shape:
``ops/flat_decode_attention.py``, filled pages only, a page a DMA,
scores and softmax in VMEM; PRs 49, 50; the chunk a tile of tokens at a
time, and only the tiles below ``n_valid``), and by their definitions
everywhere else: a gathered ``[slots, max_len]`` view, and the prefix
in blocks of :data:`CHUNK_PREFIX_ROWS` under a running softmax. Either
way a flat row is read a 128-lane row at a time with the queries laid
into their heads' lanes (:func:`lane_pack`), never split into 64-wide
heads, which the device would pad and re-lay. Both programs are
append-free: the new rows land after the layer loop. Chunk starts are
BLOCK-aligned, not chunk-aligned: a hit resumes at its snapshot's row.

The programs keep the names ``step`` and ``prefill`` (a trace names a
device op by its program), and the decode step returns, after the
tokens, ``[experts hit (mean over the expert layers), expert rows
dropped]`` for the host to fetch with them, as ``kvpool/latent.py``'s
does.
"""

import jax
import jax.numpy as jnp

from dlrover_tpu.models import conv_lm
from dlrover_tpu.models import generate as gen_lib
from dlrover_tpu.serving.engine import _place_first
from dlrover_tpu.serving.kvpool import families
from dlrover_tpu.serving.kvpool.families import SENTINEL_BLOCK
from dlrover_tpu.serving.kvpool.latent import (
    _softmax_add,
    _softmax_finish,
    _softmax_start,
)

# Prefix rows a prefill chunk scores at a time: the float32 scores of a
# 512-row chunk's 32 heads against them are 134 MB.
CHUNK_PREFIX_ROWS = 2048


def lane_pack(config) -> int:
    """KV heads a LANE ROW of a flat K (V) row holds: as many whole heads
    as fit 128 lanes and divide the KV heads (2 of 64; 1 of 128).
    Attention reads a flat row a lane row at a time and never splits it
    into heads: a ``[..., kv_heads, 64]`` view pads every head to 128
    lanes on the device, twice the bytes, re-laid every step."""
    pack = max(128 // config.head_dim, 1)
    while config.n_kv_heads % pack:
        pack -= 1
    return pack


def _placed(config, q):
    """Queries over LANE ROWS: ``q [..., heads, hd]`` -> ``[..., J, pack
    * G, pack * hd]`` (``J`` lane rows of ``pack`` KV heads, ``G`` query
    heads a KV head), each query laid into its own KV head's lanes and
    zeros in its neighbours', so that its dot with a lane row is its dot
    with its head's key. Head order is kept."""
    pack, hd = lane_pack(config), config.head_dim
    g = config.n_heads // config.n_kv_heads
    lead = q.shape[:-2]
    q = q.reshape(lead + (config.n_kv_heads // pack, pack, g, 1, hd))
    eye = jnp.eye(pack, dtype=q.dtype)[:, None, :, None]    # [p, 1, p', 1]
    return (q * eye).reshape(lead + (-1, pack * g, pack * hd))


def _own_lanes(config, out):
    """The inverse on attention's output: ``out [..., J, pack * G, pack *
    hd]`` (every head's weighted sum of whole lane rows) -> ``[...,
    heads, hd]``, each head's own lanes."""
    pack, hd = lane_pack(config), config.head_dim
    g = config.n_heads // config.n_kv_heads
    lead = out.shape[:-3]
    out = out.reshape(lead + (-1, pack, g, pack, hd))
    own = jnp.eye(pack, dtype=out.dtype)
    return jnp.einsum("...jpgqd,pq->...jpgd", out, own).reshape(
        lead + (config.n_heads, hd)
    )


def decode_attention_kind(config, pool_dtype, block_size: int,
                          max_blocks: int, slots: int) -> str:
    """What the decode step reads its cached rows with
    (:func:`decode_attend`): ``"pool_kernel"``
    (``ops.flat_decode_attention.pool_flat_decode_attention``: K and V
    read from the stacked flat pool in place, filled pages only, scores,
    softmax and the weighted sum in VMEM) where that kernel lowers — a
    TPU, a bf16 pool whose page is whole (16, 128) tiles, one DMA, and
    fits a VMEM chunk, a flat row of whole lane rows with
    :func:`lane_pack` heads to each, the ``slots`` tables inside the
    scalar memory — and ``"gathered_view"``, the definition, over every
    slot's whole table gathered, everywhere else. Decided by what the
    code can see, like ``latent.decode_attention_kind`` and for its
    reasons: no option, nothing falls back after it, so what it admits
    has to compile (``tests/test_tpu_compile.py`` holds it to the cell's
    shape). ``kv_stats()["conv_decode_attention"]`` and the engine's
    construction log line say which. The prefill chunk is not its
    business but :func:`chunk_attention_kind`'s."""
    if not families._on_tpu():
        return "gathered_view"
    # Pallas costs ~1.2 s to import: only a process that may run the
    # kernel pays it (the repo's idiom for ops/ kernels).
    from dlrover_tpu.ops.flat_decode_attention import flat_kernel_supported

    pack = lane_pack(config)
    if flat_kernel_supported(
        pool_dtype, block_size, config.kv_width, pack * config.head_dim,
        pack * (config.n_heads // config.n_kv_heads), slots, max_blocks,
    ):
        return "pool_kernel"
    return "gathered_view"


def decode_attend(config, k_pool, v_pool, at: int, tables, lengths,
                  block_size: int, kind=None, active=None):
    """The decode step's ``attend`` for attention layer ``at`` of the
    pool: one query a slot over the slot's visible rows (``< lengths``)
    AS STORED (flat rows, read a lane row at a time: :func:`lane_pack`)
    and over its own new row.

    ``kind`` (:func:`decode_attention_kind`; None: asked here, of what
    this call can see) says what reads the rows: ``"pool_kernel"``, the
    Pallas kernel over the pool in place (a slot that is not ``active``
    reads nothing there), or ``"gathered_view"``, the definition, over
    every slot's whole table gathered."""
    slots, max_blocks = tables.shape
    max_len = max_blocks * block_size
    width = lane_pack(config) * config.head_dim
    n_rows = config.kv_width // width
    scale = conv_lm.softmax_scale(config)
    f32 = jnp.float32
    kind = kind or decode_attention_kind(
        config, k_pool.dtype, block_size, max_blocks, slots
    )

    def attend(q, k_new, v_new):
        qp = _placed(config, q[:, 0])                 # [s, J, pG, width]
        k_own = k_new[:, 0].reshape(slots, n_rows, width)
        v_own = v_new[:, 0].reshape(slots, n_rows, width)
        if kind == "pool_kernel":
            from dlrover_tpu.ops.flat_decode_attention import (
                pool_flat_decode_attention,
            )

            out = pool_flat_decode_attention(
                qp, k_own, v_own, k_pool, v_pool, at, tables, lengths,
                active, scale=scale,
            )
            return _own_lanes(config, out)[:, None].astype(q.dtype)
        k_view = k_pool[at, tables].reshape(slots, max_len, -1)
        v_view = v_pool[at, tables].reshape(slots, max_len, -1)
        lanes = lambda a, j: a[..., j * width:(j + 1) * width]  # noqa: E731
        scores = jnp.stack([
            jnp.einsum("sgw,stw->sgt", qp[:, j], lanes(k_view, j),
                       preferred_element_type=f32)
            for j in range(n_rows)
        ], axis=1)                                     # [s, J, pG, T]
        visible = jnp.arange(max_len)[None, :] < lengths[:, None]
        scores = jnp.where(visible[:, None, None, :], scores, -jnp.inf)
        mine = jnp.einsum(
            "sjgw,sjw->sjg", qp, k_own, preferred_element_type=f32
        )
        probs = jax.nn.softmax(
            jnp.concatenate([scores, mine[..., None]], axis=-1) * scale,
            axis=-1,
        )
        seen = probs[..., :-1].astype(v_view.dtype)
        out = jnp.stack([
            jnp.einsum("sgt,stw->sgw", seen[:, j], lanes(v_view, j),
                       preferred_element_type=f32)
            for j in range(n_rows)
        ], axis=1) + probs[..., -1:] * v_own[:, :, None, :].astype(f32)
        return _own_lanes(config, out)[:, None].astype(q.dtype)

    return attend


def chunk_attend(config, k_pool, v_pool, at: int, table_row, start,
                 block_size: int, n_valid=None, kind=None):
    """The prefill chunk's ``attend`` for attention layer ``at``: the
    chunk's queries (positions ``start ...``) over the slot's rows below
    ``start`` and over the chunk's own rows, causally, rows read flat a
    lane row at a time. ``kind`` ``"pool_kernel"``: in place. Else, here,
    the DEFINITION: blocks of :data:`CHUNK_PREFIX_ROWS`, every row scored."""
    if kind == "pool_kernel":
        return chunk_attend_in_place(config, k_pool, v_pool, at, table_row,
                                     start, n_valid)
    per = max(CHUNK_PREFIX_ROWS // block_size, 1)    # table entries a block
    span = per * block_size
    n_table = -(-table_row.shape[0] // per) * per
    table = jnp.pad(table_row, (0, n_table - table_row.shape[0]),
                    constant_values=SENTINEL_BLOCK)
    width = lane_pack(config) * config.head_dim
    n_rows = config.kv_width // width
    scale = conv_lm.softmax_scale(config)
    f32, heads = jnp.float32, config.n_heads

    def attend(q, k_new, v_new):
        chunk = q.shape[1]
        qp = _placed(config, q[0])                     # [c, J, pG, width]
        lanes = lambda a, j: a[..., j * width:(j + 1) * width]  # noqa: E731

        def add(carry, k_rows, v_rows, visible):
            """One more block of flat rows ``[t, kv_width]``."""
            scores = jnp.concatenate([
                jnp.einsum("qgw,tw->gqt", qp[:, j], lanes(k_rows, j),
                           preferred_element_type=f32)
                for j in range(n_rows)
            ], axis=0) * scale                         # [heads, c, t]
            scores = jnp.where(visible[None], scores, -jnp.inf)

            def values(probs):
                probs = probs.astype(v_rows.dtype).reshape(
                    (n_rows, -1) + probs.shape[1:]
                )
                out = jnp.stack([
                    jnp.einsum("gqt,tw->qgw", probs[j], lanes(v_rows, j),
                               preferred_element_type=f32)
                    for j in range(n_rows)
                ], axis=1)                             # [c, J, pG, width]
                return _own_lanes(config, out)

            return _softmax_add(carry, scores, values)

        def prefix_block(i, carry):
            ids = jax.lax.dynamic_slice_in_dim(table, i * per, per)
            below = (i * span + jnp.arange(span)) < start
            return add(
                carry,
                k_pool[at, ids].reshape(span, -1),
                v_pool[at, ids].reshape(span, -1),
                jnp.broadcast_to(below[None, :], (chunk, span)),
            )

        carry = jax.lax.fori_loop(
            0, (start + span - 1) // span, prefix_block,
            _softmax_start(heads, chunk, config.head_dim),
        )
        causal = jnp.arange(chunk)[None, :] <= jnp.arange(chunk)[:, None]
        carry = add(
            carry, k_new[0].reshape(chunk, -1), v_new[0].reshape(chunk, -1),
            causal,
        )
        return _softmax_finish(carry).astype(q.dtype)[None]

    return attend


def decode_forward(config, k_pool, v_pool, state, params, tables, lengths,
                   tokens, block_size: int, taps=None, *, kind=None,
                   active=None):
    """All layers for one token a slot: float32 ``logits [slots,
    vocab]``, the attention layers' new rows ``(k, v) [La, slots,
    kv_width]``, every slot's state after the token ``[Lc, slots, taps -
    1, d]`` and the expert layers' counters. ``taps``: a dict a layer's
    ``{layer: block taps}`` land in (the checks' probes). ``kind``,
    ``active``: :func:`decode_attend`'s (None: it decides for itself
    from its operands, and every slot reads its rows below
    ``lengths``)."""
    positions = lengths[:, None]
    slots = tokens.shape[0]
    x = conv_lm.embed(config, params, tokens[:, None])
    states, k_news, v_news, counters = [], [], [], []
    for layer, layer_kind in enumerate(config.layer_types):
        at = config.index_in_kind(layer)
        seen = None if taps is None else taps.setdefault(layer, {})
        if layer_kind == conv_lm.CONV:
            x, zz, c = conv_lm.block(
                config, params, layer, x, positions, state[at], taps=seen
            )
            states.append(zz[:, 1:])
        else:
            x, (k_new, v_new), c = conv_lm.block(
                config, params, layer, x, positions,
                decode_attend(
                    config, k_pool, v_pool, at, tables, lengths, block_size,
                    kind, active,
                ),
                taps=seen,
            )
            k_news.append(k_new[:, 0].reshape(slots, -1))
            v_news.append(v_new[:, 0].reshape(slots, -1))
        if c is not None:
            counters.append(c)
    logits = conv_lm.unembed(config, params, x)[:, 0]
    # (a pattern without one of the kinds stacks nothing of it)
    rows = lambda new: (  # noqa: E731
        jnp.stack(new) if new else jnp.zeros((0, slots, config.kv_width))
    )
    return (
        logits, (rows(k_news), rows(v_news)),
        jnp.stack(states) if states else state, counters,
    )


def chunk_forward(config, k_pool, v_pool, state, params, tokens, table_row,
                  start, slot, block_size: int, taps=None, n_valid=None,
                  kind=None):
    """All layers for one slot's chunk ``tokens [1, chunk]`` at rows
    ``start ...`` from the slot's state: the final residual, the attention
    layers' new rows ``(k, v) [La, chunk, kv_width]`` and the convolution
    layers' ``zz [Lc, taps - 1 + chunk, d]``. ``kind`` None: asked here."""
    chunk = tokens.shape[1]
    kind = kind or chunk_attention_kind(config, k_pool.dtype, block_size,
                                        table_row.shape[0], chunk)
    positions = (start + jnp.arange(chunk, dtype=jnp.int32))[None]
    x = conv_lm.embed(config, params, tokens)
    zzs, k_news, v_news = [], [], []
    for layer, layer_kind in enumerate(config.layer_types):
        at = config.index_in_kind(layer)
        seen = None if taps is None else taps.setdefault(layer, {})
        if layer_kind == conv_lm.CONV:
            own = jax.lax.dynamic_slice_in_dim(state[at], slot, 1)
            x, zz, _ = conv_lm.block(
                config, params, layer, x, positions, own, taps=seen
            )
            zzs.append(zz[0])
        else:
            x, (k_new, v_new), _ = conv_lm.block(
                config, params, layer, x, positions,
                chunk_attend(
                    config, k_pool, v_pool, at, table_row, start, block_size,
                    n_valid, kind,
                ),
                taps=seen,
            )
            k_news.append(k_new[0].reshape(chunk, -1))
            v_news.append(v_new[0].reshape(chunk, -1))
    return x, (k_news, v_news), zzs


def build_decode(config, slots: int, max_blocks: int, block_size: int,
                 counts, kinds=None):
    """``kinds``: :func:`kinds`' answers for this shape (None: asked when
    the step is traced)."""
    max_len, kinds = max_blocks * block_size, kinds or {}

    def step(k, v, state, snaps, params, tables, lengths, tokens, active,
             temps, rng, step_idx, first=0, first_slot=-1):
        counts["decode"] += 1  # traces only
        tokens = _place_first(tokens, first, first_slot)
        logits, (k_new, v_new), new_state, counters = decode_forward(
            config, k, v, state, params, tables, lengths, tokens, block_size,
            kind=kinds.get("conv_decode_attention"), active=active,
        )
        write = jnp.minimum(lengths, max_len - 1)
        blk = jnp.take_along_axis(
            tables, (write // block_size)[:, None], axis=1
        )[:, 0]
        blk = jnp.where(active, blk, SENTINEL_BLOCK)
        off = jnp.where(active, write % block_size, 0)
        if k_new.shape[0]:
            # The layer is a COORDINATE of the scatter: a window across
            # the layer axis makes the compiler re-lay the whole pool,
            # there and back (kvpool/index_pool.land_tokens).
            at = (
                jnp.arange(k_new.shape[0])[:, None],
                jnp.broadcast_to(blk, k_new.shape[:2]),
                jnp.broadcast_to(off, k_new.shape[:2]),
            )
            k = k.at[at].set(k_new.astype(k.dtype))
            v = v.at[at].set(v_new.astype(v.dtype))
        with jax.named_scope("state"):
            # A slot that is not decoding keeps its state: a prompt
            # between two of its chunks, a slot nobody holds.
            state = jnp.where(
                active[None, :, None, None], new_state.astype(state.dtype),
                state,
            )
        sub = jax.random.fold_in(rng, step_idx * 2)
        nxt = gen_lib.sample_token(logits, sub, temps)
        return (k, v, state, snaps, jnp.where(active, nxt, tokens),
                conv_lm.expert_counts(counters))

    return step


def build_prefill(config, max_blocks: int, block_size: int, chunk: int,
                  counts, kinds=None):
    """``kinds``: :func:`kinds`' answers for this shape (None: asked when
    the chunk is traced)."""
    kind = (kinds or {}).get("conv_chunk_attention")
    if chunk % block_size:
        raise ValueError(
            f"prefill_chunk {chunk} must be whole blocks of {block_size}: "
            "a chunk of this model starts at any block boundary"
        )
    n_touch = chunk // block_size
    keep = config.conv_taps - 1

    def prefill(k, v, state, snaps, params, tokens, table_row, start,
                n_valid, temp, rng, step_idx, last=True, slot=0, snap_at=0,
                snap_id=0):
        counts["prefill"] += 1  # traces only
        x, (k_new, v_new), zzs = chunk_forward(
            config, k, v, state, params, tokens, table_row, start, slot,
            block_size, n_valid=n_valid, kind=kind,
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
                    len(rows), n_touch, block_size, -1
                )
            )
            k, v = land(k, k_new), land(v, v_new)
        if zzs:
            zz = jnp.stack(zzs)                     # [Lc, keep + chunk, d]
            with jax.named_scope("state"):
                state = state.at[:, slot].set(
                    jax.lax.dynamic_slice_in_dim(
                        zz, n_valid, keep, axis=1
                    ).astype(state.dtype)
                )
                with jax.named_scope("snapshot"):
                    snaps = snaps.at[:, snap_id].set(
                        jax.lax.dynamic_slice_in_dim(
                            zz, snap_at, keep, axis=1
                        ).astype(snaps.dtype)
                    )

        def head():
            h = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
            logits = conv_lm.unembed(config, params, h)[0, 0]
            sub = jax.random.fold_in(rng, step_idx * 2 + 1)
            return gen_lib.sample_token(logits, sub, temp)

        first = jax.lax.cond(last, head, lambda: jnp.zeros((), jnp.int32))
        return k, v, state, snaps, first

    return prefill


# ---- the prefill chunk over the pool in place ------------------------------
#
# Down here, below every line the decode step's kernel is called from: a
# compiled kernel carries its call sites' line numbers (PERF.md section
# 6, PRs 35, 40), so the file is LINE-NEUTRAL down to ``build_decode``'s
# end whatever the chunk gains.

# Tokens of a chunk one grid step of the chunk kernel attends: a tile
# whose first token is at or past the chunk's ``n_valid`` reads nothing
# (this traffic's turns fill ~215 of a chunk's 512 rows). Chosen on the
# chip with the kernel's VMEM chunk (``tools/bench_paged_decode.py
# --parts conv``, my chip run, PR 50; PERF.md section 6): one layer over
# the cell's turns (64-512 valid rows, log-uniform) takes 0.498-0.530 ms
# in tiles of 64, 0.505-0.518 in tiles of 128 and 0.542 in tiles of 256
# (a tile costs 0.09 / 0.17 / 0.29 ms over ~0.15 a call): nothing to
# choose between 64 and 128, and 128 is half the grid steps.
CHUNK_TOKEN_TILE = 128


def chunk_token_tile(chunk: int) -> int:
    """Tokens a tile of a ``chunk``-token prefill chunk holds:
    :data:`CHUNK_TOKEN_TILE`, or the whole chunk where that does not
    divide it."""
    return chunk if chunk % CHUNK_TOKEN_TILE else CHUNK_TOKEN_TILE


def chunk_rows_scored(n_valid, chunk: int, kinds):
    """Query rows (tokens) a chunk of ``n_valid`` valid rows scores: all
    ``chunk`` of them under the gathered form, and under the kernel its
    tiles up to the last that holds a valid row (the kernel's own test,
    tile by tile: ``first token < n_valid``). ``kinds``: :func:`kinds`'."""
    if kinds["conv_chunk_attention"] != "pool_kernel":
        return chunk
    tile = chunk_token_tile(chunk)
    return -(-n_valid // tile) * tile


def chunk_attention_kind(config, pool_dtype, block_size: int,
                         max_blocks: int, chunk: int) -> str:
    """What a prefill chunk reads its slot's rows with
    (:func:`chunk_attend`): ``"pool_kernel"``
    (``ops.flat_decode_attention.pool_flat_chunk_attention``: the prefix
    read from the stacked flat pool in place, its pages below ``start``
    only, scores, softmax and the weighted sum in VMEM, a tile of
    :func:`chunk_token_tile` tokens at a time and only the tiles that
    hold a valid row) where that kernel lowers — a TPU, a bf16 pool whose
    page is whole (16, 128) tiles, one DMA, and fits a VMEM chunk, a flat
    row of whole lane rows with :func:`lane_pack` heads to each, a chunk
    of whole token tiles whose placed queries are whole lane blocks, a
    table inside the scalar memory — and ``"gathered_view"``, the
    definition, everywhere else. Decided by what the code can see, as
    :func:`decode_attention_kind` is and for its reasons: no option,
    nothing falls back after it, so what it admits has to compile
    (``tests/test_tpu_compile.py``). ``kv_stats()["conv_chunk_attention"]``
    and the engine's construction log line say which."""
    if not families._on_tpu():
        return "gathered_view"
    from dlrover_tpu.ops.flat_decode_attention import (
        flat_chunk_kernel_supported,
    )

    pack = lane_pack(config)
    if flat_chunk_kernel_supported(
        pool_dtype, block_size, config.kv_width, pack * config.head_dim,
        pack * (config.n_heads // config.n_kv_heads), chunk,
        chunk_token_tile(chunk), max_blocks,
    ):
        return "pool_kernel"
    return "gathered_view"


def chunk_attend_in_place(config, k_pool, v_pool, at: int, table_row, start,
                          n_valid=None):
    """:func:`chunk_attend` through the Pallas kernel over the pool in
    place (``ops.flat_decode_attention.pool_flat_chunk_attention``):
    the same queries, keys, values, mask and softmax, the prefix never
    gathered and no score in HBM. Tokens at or past ``n_valid`` (a traced
    scalar; None: none) are padding: the token tiles that hold none of
    the valid ones are not scored and answer exact ZEROS, never NaN or
    Inf (they still ride through every later layer and land in the
    pool: written, invisible, overwritten)."""
    from dlrover_tpu.ops.flat_decode_attention import (
        pool_flat_chunk_attention,
    )

    scale = conv_lm.softmax_scale(config)

    def attend(q, k_new, v_new):
        chunk = q.shape[1]
        out = pool_flat_chunk_attention(
            _placed(config, q[0]), k_new[0].reshape(chunk, -1),
            v_new[0].reshape(chunk, -1), k_pool, v_pool, at, table_row,
            start, n_valid, scale=scale, tile=chunk_token_tile(chunk),
        )
        return _own_lanes(config, out)[None].astype(q.dtype)

    return attend


# ---- what the family states (kvpool/families.py) ----------------------------

# The name of the programs' definition (two accepted benchmark files pin
# the string); what each reads the slot's rows with is :func:`kinds`'.
POOL_ATTENTION = "conv_gathered_view"


def kinds(config, pool_dtype, block_size: int, chunk: int, slots: int = 0,
          max_blocks: int = 0):
    return {
        "conv_decode_attention": decode_attention_kind(
            config, pool_dtype, block_size, max_blocks, slots
        ),
        "conv_chunk_attention": chunk_attention_kind(
            config, pool_dtype, block_size, max_blocks, chunk
        ),
    }


def pool_stats(engine):
    """Token rows the chunks launched carried, and those their attention
    scored (the kernel skips the tiles of padding)."""
    return {
        "conv_chunk_rows_launched": engine._chunk_rows_launched,
        "conv_chunk_rows_scored": engine._chunk_rows_scored,
    }
