"""The latent-attention model (``models/latent_lm.py``) through the paged
engine (``serving/kvpool/latent.py``), on a CPU at tiny size with seeded
random weights: chunked prefill + absorbed decode through the pool
against the plain reference's full forward (``benchmark/reference_xing``)
on LOGITS, at positions past ``rope_original_max`` so that the YaRN blend
is live; the absorbed decode against the unabsorbed definition; prefix
hits, copy-on-write, preemption and migration over the one latent array;
the residual maps; the expert layer in all three programs."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_xing
from dlrover_tpu.models import hybrid, latent_lm
from dlrover_tpu.ops import rope
from dlrover_tpu.serving.kvpool import (
    PagedServingEngine,
    export_request,
    import_request,
    latent,
    layout,
    release_exported,
)
from dlrover_tpu.serving.kvpool.index_pool import IndexKeyPool
from tests.benchmark import tiny_xing

BS, CHUNK = 4, 8


def seeded_params(cfg, seed):
    """One program a model, not one a leaf's shape."""
    return jax.jit(lambda key: latent_lm.init_params(cfg, key))(
        jax.random.key(seed)
    )


@pytest.fixture(scope="module")
def tiny():
    cfg = latent_lm.tiny_config()
    return cfg, seeded_params(cfg, 0)


def cfg_json_of(cfg):
    """The published keys that describe ``cfg`` (for the reference)."""
    out = dict(tiny_xing.CONFIG)
    out.update(
        hidden_size=cfg.embed_dim, vocab_size=cfg.vocab_size,
        num_hidden_layers=cfg.n_layers,
        first_k_dense_replace=cfg.first_dense,
        num_attention_heads=cfg.n_heads, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_dim, qk_rope_head_dim=cfg.qk_rope_dim,
        v_head_dim=cfg.v_head_dim, hc_mult=cfg.hc_mult,
        n_routed_experts=cfg.n_experts,
        num_experts_per_tok=cfg.moe_top_k,
        rope_scaling=dict(
            tiny_xing.CONFIG["rope_scaling"], factor=cfg.rope_factor,
            original_max_position_embeddings=cfg.rope_original_max,
            beta_fast=cfg.rope_beta_fast, beta_slow=cfg.rope_beta_slow,
        ),
    )
    return out


def prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).tolist() for n in lengths]


def engine(cfg, params, **kw):
    kw = dict(dict(slots=3, max_len=64, prefill_chunk=CHUNK, block_size=BS,
                   num_blocks=60), **kw)
    return PagedServingEngine(cfg, params, **kw)


def serve(eng, items):
    reqs = [eng.submit(p, n) for p, n in items]
    while eng.pending():
        eng.step()
    eng.check_block_invariants()
    return [list(r.tokens) for r in reqs]


def greedy_of_the_full_forward(cfg, params):
    """``want(prompt, out)``: the plain forward's greedy tokens at the
    positions that emitted ``out``, and the rows it dropped. ONE program
    a model: a sequence is padded to 64 tokens, which a causal model's
    earlier positions cannot see."""
    fwd = jax.jit(lambda t: latent_lm.forward(cfg, params, t))

    def want(prompt, out):
        tokens = np.zeros((1, 64), np.int32)
        tokens[0, :len(prompt) + len(out)] = prompt + out
        logits, dropped = fwd(jnp.asarray(tokens))
        rows = slice(len(prompt) - 1, len(prompt) + len(out) - 1)
        return np.asarray(logits)[0, rows].argmax(-1).tolist(), int(dropped)

    return want


@pytest.fixture(scope="module")
def full_forward(tiny):
    return greedy_of_the_full_forward(*tiny)


def reference_logits(cfg, params, seq, rows):
    tokens = np.zeros(-(-len(seq) // 64) * 64, np.int32)
    tokens[:len(seq)] = seq
    return np.asarray(reference_xing.forward_at(
        params, jnp.asarray(tokens), jnp.asarray(rows, jnp.int32),
        cfg_json_of(cfg),
    )["logits"])


def test_the_config_states_one_latent_row_and_the_pool_builds_it(tiny):
    cfg, params = tiny
    assert cfg.cache_rows == (("latent", (cfg.cache_width,)),)
    assert cfg.cache_width == cfg.kv_lora_rank + cfg.qk_rope_dim
    (a,) = layout.pool_arrays(cfg)
    assert (a.name, a.row_shape, a.raw) == ("latent", (24,), True)
    with pytest.raises(ValueError, match="int8 pool holds K and V alone"):
        layout.pool_arrays(cfg, "int8")
    eng = engine(cfg, params)
    (pool,) = eng._pools()
    assert pool.shape == (cfg.n_layers, 60, BS, 24)
    assert eng._k is None and eng._v is None and eng._latent is pool
    stats = eng.kv_stats()
    assert stats["pool_attention"] == "latent_absorbed"
    assert stats["latent_chunk_attention"] == "absorbed"
    assert stats["latent_row_bytes"] == 24 * 4
    assert stats["latent_pool_bytes"] == pool.nbytes
    assert eng._block_bytes * eng.num_blocks == pool.nbytes
    with pytest.raises(ValueError, match="speculative programs read K and V"):
        engine(cfg, params, spec_k=2)


def test_dense_int8_and_sparse_models_state_todays_arrays():
    from dlrover_tpu.models import llama, sparse_lm

    dense = llama.tiny_config()
    head = (dense.n_kv_heads, dense.head_dim)
    assert [(a.name, a.row_shape, jnp.dtype(a.dtype).name, a.raw)
            for a in layout.pool_arrays(dense)] == [
        ("k", head, "float32", False), ("v", head, "float32", False)]
    assert [(a.name, a.row_shape, jnp.dtype(a.dtype).name)
            for a in layout.pool_arrays(dense, "int8")] == [
        ("k", head, "int8"), ("v", head, "int8"),
        ("k_scale", head[:1], "float32"), ("v_scale", head[:1], "float32")]
    sparse = sparse_lm.tiny_config()
    names = [a.name for a in layout.pool_arrays(sparse)]
    assert names == ["k", "v", "index_keys"]
    assert layout.pool_arrays(sparse)[2].raw


def test_engine_logits_are_the_references_past_the_yarn_range(tiny):
    """Chunked prefill and absorbed decode through the pool, program by
    program, against the reference's full forward on LOGITS; the rows lie
    at positions 37 ... 45 of a model whose rotation was published for 16,
    where the blended and the stretched frequencies both turn."""
    cfg, params = tiny
    assert cfg.rope_original_max == 16 and cfg.rope_factor > 1
    inv = np.asarray(latent_lm.inv_frequencies(cfg))
    plain = np.asarray(rope.rope_frequencies(cfg.qk_rope_dim, cfg.rope_theta))
    assert inv[0] == plain[0] and np.isclose(inv[-1], plain[-1] / 8)
    assert ((inv < plain) & (inv > plain / 8)).any(), "no blended pair"
    (prompt,) = prompts(cfg, (37,))
    max_blocks = 16
    pool = layout.fresh(layout.pool_arrays(cfg)[0], cfg.n_layers, 40, BS)
    table = jnp.arange(1, max_blocks + 1, dtype=jnp.int32)
    served = latent_lm.prepare_decode_params(cfg, params)
    # Each program compiled once, as the engine has it: its chunks and
    # its steps differ in traced values alone.
    chunk_forward = jax.jit(lambda pool, tokens, start: latent.chunk_forward(
        cfg, pool, served, tokens, table, start, BS
    ))
    decode_forward = jax.jit(lambda pool, at, token: latent.decode_forward(
        cfg, pool, served, table[None], at, token, BS
    ))
    got = []
    for start in range(0, len(prompt), CHUNK):
        piece = prompt[start:start + CHUNK]
        tokens = np.zeros((1, CHUNK), np.int32)
        tokens[0, :len(piece)] = piece
        streams, rows = chunk_forward(
            pool, jnp.asarray(tokens), jnp.int32(start)
        )
        pool = pool.land_run(rows, table, start, BS, 0)
    got.append(np.asarray(latent_lm.unembed_streams(
        cfg, served, streams[:, len(piece) - 1:len(piece)]
    ))[0, 0])
    seq = list(prompt)
    for _ in range(8):
        seq.append(int(got[-1].argmax()))
        at = len(seq) - 1
        logits, rows, moe = decode_forward(
            pool, jnp.asarray([at], jnp.int32),
            jnp.asarray([seq[-1]], jnp.int32),
        )
        pool = pool.land_tokens(
            rows, table[at // BS][None], jnp.asarray([at % BS])
        )
        got.append(np.asarray(logits)[0])
        assert int(np.asarray(moe)[:, 1].sum()) == 0
    want = reference_logits(
        cfg, params, seq, range(len(prompt) - 1, len(seq))
    )
    np.testing.assert_allclose(np.stack(got), want, atol=2e-4, rtol=1e-4)
    assert (np.stack(got).argmax(-1) == want.argmax(-1)).all()


def own_programs(monkeypatch):
    """From here to the test's end, engines build their programs into a
    cache of the test's own: what a test compiles under a patched tile
    size or platform probe (no part of a program's key) serves no other
    test, and the programs the other tests share stay compiled."""
    import functools

    from dlrover_tpu.serving.kvpool import engine as paged

    monkeypatch.setattr(paged, "_steps_for", functools.lru_cache(
        maxsize=16
    )(paged._steps_for.__wrapped__))


@pytest.fixture
def take_the_pool_kernel(monkeypatch):
    """``take(tile_rows)``: from here on the decode program is built as
    on a TPU: the platform probe says so (the kernel then runs in
    interpret mode) and the predicate admits the tiny model's float32
    pool and narrow rows; ``tile_rows``: device rows a VMEM tile holds,
    so that a slot's pages are several tiles. The engines' programs
    built from here on are the test's own (``own_programs``)."""
    from dlrover_tpu.ops import latent_decode_attention as lda
    from dlrover_tpu.serving.kvpool import families

    def take(tile_rows):
        own_programs(monkeypatch)
        monkeypatch.setattr(families, "_on_tpu", lambda: True)
        monkeypatch.setattr(lda, "latent_kernel_supported", lambda *a: True)
        monkeypatch.setattr(lda, "TILE_ROWS", tile_rows)

    return take


KINDS = ("gathered_view", "pool_kernel")


@pytest.mark.parametrize("kind", KINDS)
def test_served_tokens_are_the_full_forwards(
        tiny, full_forward, kind, take_the_pool_kernel):
    """Once with the decode step's rows gathered (what a CPU builds),
    once read by the Pallas kernel over the pool in place (what a TPU
    builds; here interpreted, two pages a tile): the greedy tokens are
    the full forward's either way, so each other's."""
    if kind == "pool_kernel":
        take_the_pool_kernel(2 * BS)
    cfg, params = tiny
    items = list(zip(prompts(cfg, (37, 21, 30, 9)), (6, 5, 7, 4)))
    eng = engine(cfg, params)
    stats = eng.kv_stats()
    assert stats["latent_decode_attention"] == kind
    assert eng.kinds["latent_decode_attention"] == kind
    assert stats["pool_attention"] == "latent_absorbed"
    # a chunk of 8 is not whole tiles of the module's size: one tile
    assert stats["latent_chunk_query_rows"] == CHUNK
    eng.warmup()
    traced = dict(eng.trace_counts)
    tokens = serve(eng, items)
    assert dict(eng.trace_counts) == traced      # no retrace after warm-up
    for (prompt, n), out in zip(items, tokens):
        want, dropped = full_forward(prompt, out)
        assert out == want and len(out) == n
        assert dropped == 0
    assert eng.kv_stats()["moe_rows_dropped"] == 0


@pytest.mark.parametrize("kind", KINDS)
def test_the_absorbed_decode_is_the_unabsorbed_definition(
        tiny, kind, take_the_pool_kernel):
    """One layer, one slot: the decode step's attention over pool rows
    (gathered, and by the kernel over the pool in place) and the chunk's
    against attention as written over the same rows."""
    if kind == "pool_kernel":
        take_the_pool_kernel(2 * BS)
    cfg, params = tiny
    p = latent_lm.layer_params(params, 1)
    rng = np.random.default_rng(3)
    n = 27
    h = jnp.asarray(rng.normal(size=(1, n, cfg.embed_dim)), jnp.float32)
    positions = jnp.arange(n, dtype=jnp.int32)[None]
    q_nope, q_rope, row = latent_lm.latent_inputs(cfg, p, h, positions)
    want = latent_lm.definition_attention(
        cfg, p, q_nope[0], q_rope[0], row[0]
    )
    pool = layout.fresh(layout.pool_arrays(cfg)[0], cfg.n_layers, 12, BS)
    table = jnp.arange(1, 9, dtype=jnp.int32)
    rows = jnp.zeros((cfg.n_layers, n, cfg.cache_width)).at[1].set(row[0])
    pool = pool.land_run(rows[:, :n - 1], table, 0, BS, 0)
    got = latent.decode_attend(
        cfg, pool, 1, table[None], jnp.asarray([n - 1], jnp.int32), BS
    )(p, q_nope[:, -1:], q_rope[:, -1:], row[:, -1:])
    np.testing.assert_allclose(got[0, 0], want[-1], atol=1e-5, rtol=1e-5)
    start = 16
    got = latent.chunk_attend(cfg, pool, 1, table, start, BS)(
        p, q_nope[:, start:start + CHUNK], q_rope[:, start:start + CHUNK],
        row[:, start:start + CHUNK],
    )
    np.testing.assert_allclose(
        got[0], want[start:start + CHUNK], atol=1e-5, rtol=1e-5
    )


@pytest.fixture
def query_tiles_of(monkeypatch):
    """``tiles(rows, prefix_rows)``: from here on a chunk's queries are
    walked in tiles of ``rows`` and its prefix in blocks of
    ``prefix_rows`` (the module's constants are sized for 512-row chunks
    over 16k rows; a CPU test's chunk of 8 or 16 is one tile of them).
    The engines' programs built from here on are the test's own
    (``own_programs``)."""

    def tiles(rows, prefix_rows=latent.CHUNK_PREFIX_ROWS):
        own_programs(monkeypatch)
        monkeypatch.setattr(latent, "CHUNK_QUERY_ROWS", rows)
        monkeypatch.setattr(latent, "CHUNK_PREFIX_ROWS", prefix_rows)

    return tiles


@pytest.fixture(scope="module")
def one_layers_chunk(tiny):
    """One layer's inputs for 28 tokens, their rows landed in a pool of
    8 blocks, and attention as written over them."""
    cfg, params = tiny
    p = latent_lm.layer_params(params, 1)
    n = 28
    h = jnp.asarray(
        np.random.default_rng(11).normal(size=(1, n, cfg.embed_dim)),
        jnp.float32,
    )
    q_nope, q_rope, row = latent_lm.latent_inputs(
        cfg, p, h, jnp.arange(n, dtype=jnp.int32)[None]
    )
    want = latent_lm.definition_attention(
        cfg, p, q_nope[0], q_rope[0], row[0]
    )
    pool = layout.fresh(layout.pool_arrays(cfg)[0], cfg.n_layers, 12, BS)
    table = jnp.arange(1, 9, dtype=jnp.int32)
    rows = jnp.zeros((cfg.n_layers, n, cfg.cache_width)).at[1].set(row[0])
    pool = pool.land_run(rows, table, 0, BS, 0)

    programs = {}

    def attend(start, chunk, n_valid=None):
        """``chunk_attend`` of rows ``start .. start + chunk``, jitted
        with ``n_valid`` traced (as the prefill program has it): one
        program a (start, chunk, whole or not) under the tiles in
        force, whatever ``n_valid`` is."""
        at = slice(start, start + chunk)

        def run(pool, n_valid):
            return latent.chunk_attend(
                cfg, pool, 1, table, start, BS, n_valid
            )(p, q_nope[:, at], q_rope[:, at], row[:, at])[0]

        key = (start, chunk, n_valid is None, latent.CHUNK_QUERY_ROWS,
               latent.CHUNK_PREFIX_ROWS)
        if key not in programs:
            programs[key] = jax.jit(
                (lambda pool: run(pool, None)) if n_valid is None else run
            )
        if n_valid is None:
            return np.asarray(programs[key](pool))
        return np.asarray(programs[key](pool, jnp.int32(n_valid)))

    return attend, np.asarray(want)


_TILE, _TILED_CHUNK = 4, 16


@pytest.mark.parametrize("start", (0, 12))
@pytest.mark.parametrize(
    "n_valid", (1, _TILE - 1, _TILE, _TILE + 1, _TILED_CHUNK)
)
def test_a_chunk_scores_the_tiles_that_hold_a_valid_row(
        one_layers_chunk, query_tiles_of, n_valid, start):
    """A chunk of 16 queries in tiles of 4 over a prefix of 0 rows and
    of 12 (one and a half blocks of 8): every row below ``n_valid`` is
    BIT FOR BIT what the whole-chunk call gives (a tile's arithmetic
    does not depend on how many tiles run) and attention as written to
    float32 rounding; every row of a skipped tile is an exact zero;
    nothing anywhere is non-finite; and ``n_valid=None`` is
    ``n_valid=chunk``."""
    query_tiles_of(_TILE, 8)
    attend, want = one_layers_chunk
    whole = attend(start, _TILED_CHUNK)
    np.testing.assert_allclose(
        whole, want[start:start + _TILED_CHUNK], atol=1e-5, rtol=1e-5
    )
    got = attend(start, _TILED_CHUNK, n_valid)
    scored = latent.chunk_rows_scored(n_valid, _TILED_CHUNK)
    assert scored == -(-n_valid // _TILE) * _TILE
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got[:scored], whole[:scored])
    assert not got[scored:].any()
    assert np.abs(got[:scored]).min(axis=-1).max() > 0  # tiles that ran
    if n_valid == _TILED_CHUNK:
        np.testing.assert_array_equal(got, whole)


@pytest.mark.parametrize("n_valid", (1, 6))
def test_a_chunk_that_is_not_whole_tiles_is_one_tile(
        one_layers_chunk, query_tiles_of, n_valid):
    """6 queries under tiles of 4: one tile of 6, every row scored
    whatever ``n_valid`` says."""
    query_tiles_of(_TILE, 8)
    attend, want = one_layers_chunk
    assert latent.chunk_query_rows(6) == 6
    assert latent.chunk_rows_scored(n_valid, 6) == 6
    np.testing.assert_allclose(
        attend(12, 6, n_valid), want[12:18], atol=1e-5, rtol=1e-5
    )


@pytest.mark.parametrize("n_valid", range(1, _TILED_CHUNK + 1))
def test_the_account_of_rows_scored_is_the_programs_trip_count(
        one_layers_chunk, query_tiles_of, n_valid):
    """``chunk_rows_scored`` is the tile times the trips the program
    made: the rows that came out of it other than zero."""
    query_tiles_of(_TILE, 8)
    attend, _ = one_layers_chunk
    ran = np.abs(attend(12, _TILED_CHUNK, n_valid)).max(axis=(1, 2)) > 0
    assert int(ran.sum()) == latent.chunk_rows_scored(n_valid, _TILED_CHUNK)
    assert ran[:int(ran.sum())].all()


def test_a_short_last_chunk_serves_the_full_forwards_tokens(
        tiny, full_forward, query_tiles_of):
    """Chunks of 8 in tiles of 4: last chunks of 5, 5, 6 and 1 valid
    rows leave a tile out or run both, middle chunks run both, and the
    greedy tokens are the plain forward's."""
    query_tiles_of(4, 8)
    cfg, params = tiny
    items = list(zip(prompts(cfg, (37, 21, 30, 9)), (6, 5, 7, 4)))
    eng = engine(cfg, params)
    stats = eng.kv_stats()
    assert stats["latent_chunk_query_rows"] == 4
    assert stats["latent_chunk_attention"] == "absorbed"
    eng.warmup()
    traced = dict(eng.trace_counts)
    tokens = serve(eng, items)
    assert dict(eng.trace_counts) == traced      # n_valid is an input
    for (prompt, n), out in zip(items, tokens):
        assert out == full_forward(prompt, out)[0] and len(out) == n


# A decode batch's fills, a slot each (blocks of BS = 4 tokens, tables of
# 8 blocks, a tile of two pages = 8 tokens; slot 1's table is all the
# sentinel block: an inactive slot). Every batch is ragged, and every
# slot's table runs backwards through the pool.
_BATCHES = {
    # a slot with nothing cached answers with its own row's latent
    "a_fill_of_0": (0, 0, 19, 0, 7),
    "a_fill_of_1": (1, 1, 27, 1, 14),
    # with two tokens to a device row an odd fill ends on the first: the
    # row's second token is hidden
    "an_odd_fill": (5, 3, 13, 25, 9),
    "on_a_page_boundary": (4, 12, 20, 28, 4),
    "on_a_tile_boundary": (8, 16, 24, 8, 16),
    "a_table_longer_than_the_fill": (3, 0, 2, 6, 1),
    "a_full_table": (32, 32, 31, 29, 32),
}


def landing(cfg, sequence):
    def land(pool, table, fill):
        rows = jnp.where(
            jnp.arange(len(sequence))[:, None] < fill, sequence, 0.0
        )
        layers = jnp.zeros((cfg.n_layers,) + sequence.shape)
        return pool.land_run(layers.at[1].set(rows), table, 0, BS, 0)

    return land


@pytest.fixture(scope="module")
def latent_batches():
    """Per tokens-a-row: a config, one layer's weights, one sequence's
    attention inputs with attention as written over it, and ``land(pool,
    table, fill)``: the sequence's first ``fill`` rows in the pages of
    ``table`` (one program whatever the fill: all 32 rows a table holds
    are landed, those past the fill as the zeros a fresh pool has)."""
    out = {}
    for pack, kw in ((1, {}), (2, dict(kv_lora_rank=128, qk_rope_dim=64))):
        cfg = latent_lm.tiny_config(**kw)
        params = seeded_params(cfg, pack)
        p = latent_lm.layer_params(params, 1)
        n = 33
        h = jnp.asarray(
            np.random.default_rng(pack).normal(size=(1, n, cfg.embed_dim)),
            jnp.float32,
        )
        positions = jnp.arange(n, dtype=jnp.int32)[None]
        q_nope, q_rope, row = latent_lm.latent_inputs(cfg, p, h, positions)
        want = latent_lm.definition_attention(
            cfg, p, q_nope[0], q_rope[0], row[0]
        )
        out[pack] = (cfg, p, q_nope[0], q_rope[0], row[0], want,
                     jax.jit(landing(cfg, row[0, :32])))
    return out


@pytest.mark.parametrize("pack", [1, 2])
@pytest.mark.parametrize("batch", sorted(_BATCHES))
def test_the_pool_kernel_is_the_gathered_view_over_a_ragged_batch(
        latent_batches, batch, pack, take_the_pool_kernel):
    """Slot ``i`` holds the first ``fills[i]`` rows of one sequence in
    its own pages and asks with the next token's query: the kernel over
    the pool in place (a tile of two pages, so a slot's rows are several
    tiles and the last one part filled) gives what the gathered form
    gives and what attention as written gives at that token, and its own
    scores are the gathered form's over the visible rows, zero past
    them."""
    cfg, p, q_nope, q_rope, row, want, land = latent_batches[pack]
    fills = np.asarray(_BATCHES[batch])
    slots, max_blocks = len(fills), 8
    pool = layout.fresh(
        layout.pool_arrays(cfg)[0], cfg.n_layers, slots * max_blocks + 1, BS
    )
    assert pool.pack == pack
    tables = 1 + np.arange(slots * max_blocks, dtype=np.int32).reshape(
        slots, max_blocks
    )[:, ::-1]
    for i, fill in enumerate(fills):
        pool = land(pool, jnp.asarray(tables[i]), jnp.int32(fill))
    tables[1] = 0        # an inactive slot: the sentinel block's rows
    at = jnp.asarray(fills)
    args = (p, q_nope[at][:, None], q_rope[at][:, None], row[at][:, None])
    call = lambda **kw: latent.decode_attend(  # noqa: E731
        cfg, pool, 1, jnp.asarray(tables), at.astype(jnp.int32), BS, **kw
    )(*args)[:, 0]
    seen_view, seen_kernel = {}, {}
    view = call(kind="gathered_view", taps=seen_view)
    take_the_pool_kernel(2 * BS // pack)
    got = call()                  # the kind is asked of what it can see
    np.testing.assert_allclose(got, view, atol=2e-6, rtol=1e-5)
    np.testing.assert_allclose(
        call(taps=seen_kernel), got, atol=1e-7, rtol=1e-6
    )
    real = np.arange(slots) != 1
    np.testing.assert_allclose(
        got[real], want[fills][real], atol=1e-5, rtol=1e-5
    )
    visible = np.arange(max_blocks * BS)[None, :] < fills[:, None]
    scores = np.asarray(seen_kernel["scores"])
    assert scores.shape == (slots, cfg.n_heads, max_blocks * BS)
    np.testing.assert_allclose(
        scores, np.where(visible[:, None], seen_view["scores"], 0.0),
        atol=1e-5, rtol=1e-5,
    )
    assert (scores[~np.broadcast_to(visible[:, None], scores.shape)] == 0).all()
    np.testing.assert_array_equal(
        seen_kernel["queries"], seen_view["queries"]
    )


def test_the_kernels_scores_are_the_modules_own(
        latent_batches, take_the_pool_kernel, monkeypatch):
    """A tile's scores are formed by ``latent._scores``, the statement of
    their precision that the gathered form and the chunk share: scores
    rounded THERE (what the harness's bfloat16-scores control plants) are
    the scores the kernel hands out and attends with."""
    cfg, p, q_nope, q_rope, row, _, _ = latent_batches[2]
    fill, max_blocks = 21, 8
    pool = layout.fresh(
        layout.pool_arrays(cfg)[0], cfg.n_layers, max_blocks + 1, BS
    )
    table = jnp.arange(1, max_blocks + 1, dtype=jnp.int32)
    rows = jnp.zeros((cfg.n_layers, fill, cfg.cache_width))
    pool = pool.land_run(rows.at[1].set(row[:fill]), table, 0, BS, 0)
    take_the_pool_kernel(BS)

    def run():
        seen = {}
        out = latent.decode_attend(
            cfg, pool, 1, table[None], jnp.asarray([fill], jnp.int32), BS,
            taps=seen,
        )(p, q_nope[fill][None, None], q_rope[fill][None, None],
          row[fill][None, None])
        return np.asarray(out), np.asarray(seen["scores"])[..., :fill]

    out, exact = run()
    real = latent._scores
    monkeypatch.setattr(latent, "_scores", lambda *a: jax.lax.reduce_precision(
        real(*a), exponent_bits=8, mantissa_bits=7
    ))
    out_low, low = run()
    np.testing.assert_array_equal(
        low, np.asarray(jnp.asarray(exact).astype(jnp.bfloat16), np.float32)
    )
    assert 1e-4 < np.abs(low - exact).max() / np.abs(exact).max() < 1e-2
    assert np.abs(out_low - out).max() > 0


def test_a_prefix_hit_request_is_the_request_served_cold(tiny):
    cfg, params = tiny
    (context,), (turn,) = prompts(cfg, (32,), 5), prompts(cfg, (7,), 6)
    cold = engine(cfg, params)
    (want,) = serve(cold, [(context + turn, 6)])
    assert cold.kv_stats()["prefix_hits"] == 0
    warm = engine(cfg, params)
    serve(warm, [(context, 1)])
    (got,) = serve(warm, [(context + turn, 6)])
    stats = warm.kv_stats()
    assert got == want
    assert stats["prefix_hits"] == 1 and stats["prefix_hit_tokens"] == 32
    shared = warm._cache.lookup(np.asarray(context))
    assert len(shared) == 32 // BS
    own = cold._cache.lookup(np.asarray(context))
    np.testing.assert_array_equal(
        np.asarray(warm._latent[:, np.asarray(shared)]),
        np.asarray(cold._latent[:, np.asarray(own)]),
    )


def test_cow_and_preemption_keep_the_latent_rows_consistent(tiny):
    """A full-prompt hit re-runs its last chunk; with blocks longer than
    a chunk that chunk lies inside a SHARED block, which is copied first,
    latent rows and all. Then a pool too small for its slots: the
    youngest request is preempted and served again, and every answer is
    the unpressed engine's."""
    cfg, params = tiny
    (prompt,) = prompts(cfg, (32,), 7)
    long_blocks = engine(cfg, params, prefill_chunk=4, block_size=8,
                         num_blocks=40)
    (first,) = serve(long_blocks, [(prompt, 5)])
    shared = long_blocks._cache.lookup(np.asarray(prompt))
    before = np.asarray(long_blocks._latent[:, np.asarray(shared)])
    (again,) = serve(long_blocks, [(prompt, 5)])
    assert again == first
    assert long_blocks.kv_stats()["cow_copies"] >= 1
    np.testing.assert_array_equal(          # the shared chain is untouched
        np.asarray(long_blocks._latent[:, np.asarray(shared)]), before
    )
    assert np.abs(before).max() > 0
    (context,) = prompts(cfg, (30,), 7)
    turns = prompts(cfg, (5, 9, 3, 6), 8)
    items = [(context + t, 12) for t in turns]
    # (the unpressed engine: the shape the other tests have compiled)
    want = serve(engine(cfg, params), items)
    tight = engine(cfg, params, slots=4, num_blocks=24)
    serve(tight, [(context, 1)])
    got = serve(tight, items)
    assert got == want
    stats = tight.kv_stats()
    assert tight.metrics.kv_preemptions.value() > 0
    assert stats["used"] == 0 and stats["moe_rows_dropped"] == 0


def test_a_migrated_request_carries_its_latent_rows(tiny):
    cfg, params = tiny
    (prompt,) = prompts(cfg, (19,), 9)
    (want,) = serve(engine(cfg, params), [(prompt, 9)])
    src, dst = engine(cfg, params), engine(cfg, params)
    req = src.submit(prompt, 9)
    while len(req.tokens) < 3:
        src.step()
    payload = export_request(src, req)
    moved = import_request(dst, payload)
    release_exported(src, req)
    rows_src = np.asarray(src._latent[:, src._slot_blocks[req.slot]]) \
        if req.slot >= 0 else None
    assert rows_src is None        # the source let go of the slot
    while dst.pending():
        dst.step()
    assert list(moved.tokens) == want
    for eng in (src, dst):
        eng.check_block_invariants()
    dense = __import__("dlrover_tpu.models.llama", fromlist=["x"])
    other = PagedServingEngine(
        dense.tiny_config(), dense.init_params(
            dense.tiny_config(), jax.random.key(0))[0],
        slots=2, max_len=64, prefill_chunk=CHUNK, block_size=BS,
    )
    from dlrover_tpu.serving.kvpool import MigrationError

    with pytest.raises(MigrationError):
        import_request(other, payload)


def test_a_packed_pool_serves_what_a_bare_one_serves():
    """A 192-wide row is 1.5 lane rows: the device holds two tokens to a
    384-lane row, and everything (decode, chunk, landings, COW, prefix
    sharing) reads it by token coordinates."""
    cfg = latent_lm.tiny_config(kv_lora_rank=128, qk_rope_dim=64)
    params = seeded_params(cfg, 1)
    items = list(zip(prompts(cfg, (33, 18, 26), 11), (6, 7, 5)))
    packed = engine(cfg, params)
    assert packed._latent.pack == 2
    assert packed._latent.rows.shape[-2:] == (BS // 2, 384)
    assert packed._latent.shape == (cfg.n_layers, 60, BS, 192)
    got = serve(packed, items)
    want = greedy_of_the_full_forward(cfg, params)
    for (prompt, _), out in zip(items, got):
        assert out == want(prompt, out)[0]
    bare = IndexKeyPool.of(jnp.zeros((cfg.n_layers, 60, BS, 192)))
    assert bare.pack == 1


@pytest.mark.parametrize("width,block,want", [
    (576, 64, 2), (192, 4, 2), (576, 1, 1), (640, 64, 1), (64, 64, 2),
    (24, 4, 1), (256, 64, 1),
])
def test_tokens_a_device_row_holds(width, block, want):
    from dlrover_tpu.serving.kvpool.index_pool import tokens_per_row

    assert tokens_per_row(width, block) == want


def test_h_res_is_doubly_stochastic_and_one_stream_is_the_plain_residual(tiny):
    cfg, params = tiny
    hp = jax.tree_util.tree_map(lambda a: a[0], params["layers"]["hc_attn"])
    streams = jnp.asarray(
        np.random.default_rng(0).normal(size=(64, cfg.hc_mult, cfg.embed_dim)),
        jnp.float32,
    )
    maps = latent_lm.mhc_maps(cfg, hp, streams)
    assert np.abs(np.asarray(maps.res.sum(-1)) - 1).max() < 1e-5
    assert np.abs(np.asarray(maps.res.sum(-2)) - 1).max() < 1e-5
    assert (np.asarray(maps.res) > 0).all()
    assert np.asarray(maps.res).std() > 0.01          # not the uniform map
    assert ((np.asarray(maps.pre) > 0) & (np.asarray(maps.pre) < 1)).all()
    assert ((np.asarray(maps.post) > 0) & (np.asarray(maps.post) < 2)).all()
    # n = 1, the maps forced to 1: x + f(norm(x)), sublayer by sublayer
    one = dataclasses.replace(cfg, hc_mult=1, n_layers=2)
    p1 = seeded_params(one, 2)
    unit = latent_lm.ResidualMaps(
        pre=jnp.ones((1, 5, 1)), post=jnp.ones((1, 5, 1)),
        res=jnp.ones((1, 5, 1, 1)),
    )
    x = jnp.asarray(
        np.random.default_rng(1).normal(size=(1, 5, one.embed_dim)),
        jnp.float32,
    )
    y = 0.5 * x[..., ::-1]
    np.testing.assert_array_equal(
        latent_lm.mhc_read(unit, x[..., None, :]), x
    )
    np.testing.assert_allclose(
        latent_lm.mhc_write(unit, x[..., None, :], y)[..., 0, :], x + y
    )
    # and a one-stream Sinkhorn map IS 1 (to hc_eps)
    hp1 = jax.tree_util.tree_map(lambda a: a[0], p1["layers"]["hc_mlp"])
    res = latent_lm.mhc_maps(one, hp1, x[0, :, None, :]).res
    np.testing.assert_allclose(np.asarray(res), 1.0, atol=2e-6)


def test_the_expert_layer_gives_a_token_one_output_in_every_program(tiny):
    """The same normed input through the expert layer alone in a chunk of
    8, in a decode batch of 3 and in a whole sequence: no capacity, so the
    token's output is the same, and nothing is dropped."""
    cfg, params = tiny
    rng = np.random.default_rng(4)
    h = jnp.asarray(rng.normal(size=(40, cfg.embed_dim)), jnp.float32)
    feed = jax.jit(lambda h: latent_lm.feed(cfg, params, jnp.int32(2), h))
    whole, c = feed(h[None])
    assert int(c.rows_dropped) == 0
    chunk, c = feed(h[None, 16:24])
    np.testing.assert_allclose(chunk[0], whole[0, 16:24], atol=1e-6)
    step, c = feed(h[jnp.asarray([16, 3, 30])][:, None])
    np.testing.assert_allclose(step[:, 0], whole[0, [16, 3, 30]], atol=1e-6)
    assert int(c.rows_dropped) == 0 and 1 <= int(c.experts_hit) <= 6
    # against the reference's every-expert-computes-every-token form
    sh = reference_xing.shape_of(cfg_json_of(cfg))
    _, pf = reference_xing.layer_weights(params, 2, sh)
    with jax.default_matmul_precision("highest"):
        want, ids, _ = reference_xing.mlp(pf, h, sh)
    np.testing.assert_allclose(whole[0], want, atol=1e-5, rtol=1e-4)
    got_ids, _ = latent_lm.route(
        cfg, {k: params["moe"][k][1] for k in ("router", "router_bias")}, h
    )
    assert (np.sort(got_ids, -1) == np.sort(ids, -1)).all()


def test_the_mixer_of_the_trained_model_takes_the_rotation_as_an_option():
    """``hybrid._mla_apply`` with no rotation is the program it was (the
    lowered-text digest in tests/test_hybrid_model.py holds it to the
    byte); given one, queries' and the shared key's positional slices turn
    and a shift of every position leaves the output alone."""
    cfg = hybrid.tiny_config()
    p = hybrid._mla_init(cfg, jax.random.key(0))
    h = jnp.asarray(
        np.random.default_rng(0).normal(size=(1, 12, cfg.embed_dim)),
        jnp.float32,
    )
    nope = hybrid._mla_apply(cfg, p, h)

    def rotate_from(first):
        positions = first + jnp.arange(12, dtype=jnp.int32)[None]
        return lambda x: rope.apply_rope(x, positions, 1e4)

    turned = hybrid._mla_apply(cfg, p, h, rotate=rotate_from(0))
    assert float(jnp.abs(turned - nope).max()) > 1e-3
    np.testing.assert_allclose(
        hybrid._mla_apply(cfg, p, h, rotate=rotate_from(40)), turned,
        atol=2e-5,
    )


def run_the_tool(*args):
    """``tools/bench_paged_decode.py --tiny --parts latent`` with
    ``args`` on a CPU: the JSON lines it printed."""
    import json
    import os
    import subprocess
    import sys

    tool = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools", "bench_paged_decode.py",
    )
    out = subprocess.run(
        [sys.executable, tool, "--tiny", "--parts", "latent", *args],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, text=True,
        capture_output=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return [json.loads(x) for x in out.stdout.splitlines() if x[:1] == "{"]


def test_the_tools_latent_part_rehearses_off_a_tpu():
    """``tools/bench_paged_decode.py --parts latent --tiny``: the
    gathered form, then the kernel at each ``--tile-rows`` against it
    (interpret mode), a line each, then the prefill chunk's attention in
    one line: the whole chunk as one tile and tiles of
    ``--chunk-query-rows``, each at every count of valid rows, the tiled
    forms the whole-chunk form's on the valid rows; no time anywhere.
    The program's own tile sizes are back where they were afterwards
    (the tool sets them, no option of the program does)."""
    rows = run_the_tool("--tile-rows", "2,4", "--chunk-query-rows", "2,4")
    assert [(r["part"], r["form"]) for r in rows] == [
        ("latent", "gathered_view"), ("latent", "pool_kernel"),
        ("latent", "pool_kernel"), ("latent", "chunk"),
    ]
    assert [r["tile_rows"] for r in rows[1:3]] == [2, 4]
    assert all(r["rel_err_of_gathered"] < 1e-5 for r in rows[1:3])
    assert not [k for r in rows for k in r if k in ("ms", "rows_gb_s")]
    tiles = rows[3]["tiles"]
    assert list(tiles) == ["8", "2", "4"]        # the whole chunk first
    assert all(list(t) == ["1", "3", "8"] for t in tiles.values())
    assert [tiles[t]["3"]["rows_scored_share"] for t in tiles] == [
        1.0, 0.5, 0.5
    ]
    assert tiles["2"]["1"]["rows_scored_share"] == 0.25
    assert all(
        r["rel_err_of_whole_chunk"] < 1e-5 and "ms" not in r
        for t in ("2", "4") for r in tiles[t].values()
    )


def test_the_tools_chunk_account_reads_a_traced_runs_spans(tmp_path):
    """``--chunks-of traced.json``: rows scored over rows launched by
    the chunks the run's ``serving.step`` spans have on record, at the
    tile its ``kv_stats`` names (none: the whole chunk, as before the
    tiles), inside the profiler session and over all; nothing runs."""
    import json

    def step(ts, n):
        return {"name": "serving.step", "ts": ts, "dur_s": 0.5,
                "attrs": {"prefill_tokens": n}}

    facts = {
        "traced_window": [10.0, 20.0],
        "kv_stats": {"latent_chunk_query_rows": 4},
        "spans": [step(1.0, 8), step(2.0, 0), step(11.0, 3), step(12.0, 5),
                  {"name": "serving.queue_wait", "ts": 12.0, "dur_s": 0.1,
                   "attrs": {}}],
    }
    got = []
    for name in ("tiled", "whole"):
        path = tmp_path / (name + ".json")
        path.write_text(json.dumps(facts))
        (line,) = run_the_tool("--chunks-of", str(path))
        got.append(line)
        facts["kv_stats"].pop("latent_chunk_query_rows", None)
    tiled, whole = got
    assert tiled["query_rows"] == 4 and whole["query_rows"] == 8
    assert tiled["traced"] == {
        "chunks": 2, "full_chunks": 0, "rows_valid": 8, "rows_scored": 12,
        "rows_launched": 16, "scored_over_launched": 0.75,
    }
    assert tiled["all"]["scored_over_launched"] == round(20 / 24, 4)
    assert tiled["all"]["full_chunks"] == 1
    assert whole["traced"]["scored_over_launched"] == 1.0
    assert whole["all"]["rows_scored"] == whole["all"]["rows_launched"] == 24
