"""The index-key pool as the device holds it (``kvpool/index_pool.py``):
how many keys a 128-lane row takes follows from the shape alone; the
pytree answers block and token coordinates like the logical array;
keys land in rows shared with keys that stay; the programs of
``kvpool/sparse.py`` give the same selection, tokens and pool over
packed rows as over a bare array of the logical shape; and the
benchmark's probe chain (``runners/serve_sparse``) runs on a packing
engine's pool."""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import sparse_lm
from dlrover_tpu.serving.kvpool import SENTINEL_BLOCK, sparse
from dlrover_tpu.serving.kvpool.index_pool import (
    IndexKeyPool,
    tokens_per_row,
)

L, NB = 2, 10


@pytest.mark.parametrize("index_dim, block_size, want", [
    (64, 64, 2),        # keye-vl2 as served: [.., 32, 128]
    (64, 5, 1),         # two keys do not divide a 5-token block
    (8, 4, 1),          # the tests' tiny model: 16 do not divide 4
    (8, 16, 16), (8, 32, 16),
    (128, 16, 1), (256, 16, 1),
    (24, 16, 1),        # 24 does not divide 128
    (32, 6, 1), (32, 8, 4),
    (0, 16, 1),         # a model that keeps no index keys
])
def test_tokens_per_row_follows_from_the_shape(index_dim, block_size, want):
    assert tokens_per_row(index_dim, block_size) == want
    if index_dim:
        pool = IndexKeyPool.zeros(L, NB, block_size, index_dim, jnp.bfloat16)
        assert pool.pack == want
        assert pool.rows.shape == (
            L, NB, block_size // want, want * index_dim
        )
        assert pool.shape == (L, NB, block_size, index_dim)
        assert pool.nbytes == L * NB * block_size * index_dim * 2


def _filled(index_dim, block_size, seed=0):
    """A packed pool and the logical array it holds."""
    logical = np.random.default_rng(seed).normal(
        size=(L, NB, block_size, index_dim)
    ).astype(np.float32)
    pool = IndexKeyPool.zeros(L, NB, block_size, index_dim, jnp.float32)
    pool = pool.at[:, :].set(logical)
    return pool, logical


SHAPES = [(64, 4), (8, 16), (8, 4)]       # 2, 16 and 1 keys a row


@pytest.mark.parametrize("index_dim, block_size", SHAPES)
def test_the_pool_answers_like_the_logical_array(index_dim, block_size):
    """``[layer, block]`` reads and writes, ``shape`` and ``dtype`` are
    the logical array's; ``_at_layer`` gives a token's ``index_dim``
    lanes at ``(layer, block, offset)`` and at ``(layer, blocks)`` the
    rows as stored, which flattened are the tokens in order; token
    indexing of the pytree itself is refused."""
    pool, logical = _filled(index_dim, block_size)
    assert pool.dtype == jnp.float32 and pool.ndim == 4
    np.testing.assert_array_equal(np.asarray(pool[:, 3]), logical[:, 3])
    np.testing.assert_array_equal(np.asarray(pool[1, [4, 2]]),
                                  logical[1, [4, 2]])
    zeroed = pool.at[1, np.asarray([2, 5])].set(0)
    want = logical.copy()
    want[1, [2, 5]] = 0
    np.testing.assert_array_equal(np.asarray(zeroed[:, :]), want)
    moved = pool.at[:, 6].set(pool[:, 1])             # copy-on-write
    np.testing.assert_array_equal(np.asarray(moved[:, 6]), logical[:, 1])
    blk = jnp.asarray([[1, 5, 2], [6, 0, 3]])
    off = jnp.asarray([[0, block_size - 1, 1], [2, 3, block_size - 2]])
    for layer in range(L):
        got = sparse._at_layer(pool, jnp.int32(layer), blk, off)
        assert got.shape == blk.shape + (index_dim,)
        np.testing.assert_array_equal(
            np.asarray(got), logical[layer][np.asarray(blk), np.asarray(off)]
        )
        rows = sparse._at_layer(pool, jnp.int32(layer), blk)
        assert rows.shape == blk.shape + pool.rows.shape[2:]
        np.testing.assert_array_equal(
            np.asarray(rows.reshape(2, 3 * block_size, -1)),
            logical[layer][np.asarray(blk)].reshape(2, 3 * block_size, -1),
        )
    with pytest.raises(TypeError, match="_at_layer"):
        pool[0, 1, 2]
    # through jit, donated, with shapes in the leaves' place
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), pool
    )
    assert isinstance(shapes, IndexKeyPool) and shapes.shape == pool.shape
    bump = jax.jit(lambda p: p.at[:, 0].set(p[:, 1]), donate_argnums=0)
    assert bump.lower(shapes).out_info.rows.shape == pool.rows.shape
    np.testing.assert_array_equal(
        np.asarray(bump(pool)[:, 0]), logical[:, 1]
    )


@pytest.mark.parametrize("index_dim, block_size", SHAPES)
def test_tokens_land_in_rows_shared_with_tokens_that_stay(index_dim,
                                                          block_size):
    """One key a slot (the decode step) at odd and even offsets, the
    idle slots' at the sentinel: the named tokens change, no other."""
    pool, logical = _filled(index_dim, block_size, seed=1)
    keys = np.random.default_rng(2).normal(
        size=(L, 4, index_dim)
    ).astype(np.float32)
    blk = np.asarray([3, SENTINEL_BLOCK, 5, 1])
    off = np.asarray([block_size - 1, 0, 2, 1])
    got = jax.jit(IndexKeyPool.land_tokens)(
        pool, jnp.asarray(keys), jnp.asarray(blk), jnp.asarray(off)
    )
    want = logical.copy()
    want[:, blk, off] = keys
    np.testing.assert_array_equal(np.asarray(got[:, :]), want)


@pytest.mark.parametrize("start, n", [(0, 8), (8, 8), (3, 8), (5, 7),
                                      (17, 3), (24, 8), (29, 1), (27, 8)])
@pytest.mark.parametrize("index_dim, block_size", SHAPES)
def test_a_run_lands_whole_rows_and_merges_its_edges(index_dim, block_size,
                                                     start, n):
    """A chunk's keys at the slot's logical tokens ``start ...``: whole
    rows where both are multiples of ``pack``, the edge rows merged with
    what they held, whatever ``start`` and ``n`` are; what runs past the
    table goes to the sentinel block."""
    pool, logical = _filled(index_dim, block_size, seed=3)
    table = np.asarray([4, 2, 6, 1, 5, 3, 9, 7])[:32 // block_size]
    keys = np.random.default_rng(4).normal(
        size=(L, n, index_dim)
    ).astype(np.float32)
    land = jax.jit(IndexKeyPool.land_run, static_argnums=(4, 5))
    got = land(pool, jnp.asarray(keys), jnp.asarray(table),
               jnp.int32(start), block_size, SENTINEL_BLOCK)
    want = logical.copy()
    for i in range(n):
        t = start + i
        if t < 32:
            want[:, table[t // block_size], t % block_size] = keys[:, i]
    got = np.asarray(got[:, :])
    np.testing.assert_array_equal(got[:, 1:], want[:, 1:])
    if start + n <= 32:
        np.testing.assert_array_equal(got[:, 0], want[:, 0])


# (config, block_size) whose index-key pool packs: 2 and 16 keys a row
PACKING = pytest.mark.parametrize(
    "cfg, bs",
    [(sparse_lm.tiny_config(index_dim=64), 4), (sparse_lm.tiny_config(), 16)],
    ids=["two_a_row", "sixteen_a_row"],
)


def _programs_inputs(cfg, bs, fills, seed=5):
    """A pool of random K, V and index keys, tables and a tree of
    weights: (k, v, packed ki, bare ki, params, tables, lengths)."""
    rng = np.random.default_rng(seed)
    slots, mb = len(fills), 32 // bs
    nb = slots * mb + 1
    kv_shape = (cfg.n_layers, nb, bs, cfg.n_kv_heads, cfg.head_dim)
    k, v = (jnp.asarray(rng.normal(size=kv_shape).astype(np.float32))
            for _ in range(2))
    bare = rng.normal(
        size=(cfg.n_layers, nb, bs, cfg.index_dim)
    ).astype(np.float32)
    packed = IndexKeyPool.zeros(
        cfg.n_layers, nb, bs, cfg.index_dim, jnp.float32
    ).at[:, :].set(bare)
    assert packed.pack > 1
    tables = 1 + rng.permutation(slots * mb).reshape(slots, mb)
    params = sparse_lm.init_params(cfg, jax.random.key(seed))
    return (k, v, packed, jnp.asarray(bare), params,
            jnp.asarray(tables, jnp.int32), jnp.asarray(fills, jnp.int32))


@PACKING
def test_the_decode_step_is_the_same_program_over_packed_rows(cfg, bs):
    """``build_decode``'s step over the packed pool and over the bare
    array of the same keys (fills odd and even, past ``index_topk``, one
    slot idle): the same selection in every layer, the same tokens, and
    the same pool afterwards; a bare array comes back bare."""
    fills = [31, 12, 9, 0]
    k, v, packed, bare, params, tables, lengths = _programs_inputs(
        cfg, bs, fills
    )
    slots = len(fills)
    step = jax.jit(sparse.build_decode(
        cfg, slots, tables.shape[1], bs, {"decode": 0}
    ))
    rest = (
        params, tables, lengths, jnp.asarray([3, 5, 7, 0], jnp.int32),
        jnp.asarray([True, True, True, False]),
        jnp.zeros(slots, jnp.float32), jax.random.key(0), jnp.int32(0),
    )
    out_p = step(k, v, packed, *rest)
    out_b = step(k, v, bare, *rest)
    assert isinstance(out_p[2], IndexKeyPool)
    assert not isinstance(out_b[2], IndexKeyPool)
    np.testing.assert_array_equal(np.asarray(out_p[3]), np.asarray(out_b[3]))
    np.testing.assert_array_equal(
        np.asarray(out_p[2][:, 1:]), np.asarray(out_b[2][:, 1:])
    )
    for a, b in zip(out_p[:2], out_b[:2]):
        np.testing.assert_allclose(np.asarray(a[:, 1:]), np.asarray(b[:, 1:]),
                                   rtol=1e-6, atol=1e-6)
    # the new keys are in the pool, at their tokens
    changed = np.asarray(out_p[2][:, :]) != np.asarray(packed[:, :])
    at = {(int(tables[s, f // bs]), f % bs) for s, f in enumerate(fills[:3])}
    found = {(int(b), int(t)) for _, b, t, _ in zip(*np.nonzero(changed))
             if b != SENTINEL_BLOCK}
    assert found == at
    # and every layer's selection is the bare array's
    layer_p = sparse_lm.layer_params(cfg, params, 1)
    x = jnp.asarray(np.random.default_rng(6).normal(
        size=(slots, 1, cfg.embed_dim)
    ).astype(np.float32))
    _, _, _, q_idx, k_idx, w = sparse_lm.attention_inputs(
        cfg, layer_p, x, lengths[:, None]
    )
    sel = [
        sparse.decode_select(cfg, ki, jnp.int32(1), tables, lengths, bs,
                             q_idx, k_idx, w)
        for ki in (packed, bare)
    ]
    for got, want in zip(*sel):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("start, n_valid", [(8, 8), (5, 8), (11, 3), (16, 5)])
@PACKING
def test_the_prefill_chunk_is_the_same_program_over_packed_rows(
        cfg, bs, start, n_valid):
    """``build_prefill``'s chunk at a ``start`` and an ``n_valid`` that
    are no multiples of ``pack`` (a resume inside a row): the same first
    token and the same pool as over the bare array."""
    k, v, packed, bare, params, tables, _ = _programs_inputs(cfg, bs, [0])
    chunk = 8
    prefill = jax.jit(sparse.build_prefill(
        cfg, tables.shape[1], bs, chunk, {"prefill": 0}
    ))
    tokens = np.zeros((1, chunk), np.int32)
    tokens[0, :n_valid] = np.arange(1, n_valid + 1)
    rest = (
        params, jnp.asarray(tokens), tables[0], jnp.int32(start),
        jnp.int32(n_valid), jnp.float32(0), jax.random.key(0), jnp.int32(0),
        jnp.bool_(True),
    )
    out_p = prefill(k, v, packed, *rest)
    out_b = prefill(k, v, bare, *rest)
    assert int(out_p[3]) == int(out_b[3])
    np.testing.assert_array_equal(
        np.asarray(out_p[2][:, 1:]), np.asarray(out_b[2][:, 1:])
    )
    before, after = np.asarray(packed[:, :]), np.asarray(out_p[2][:, :])
    flat = lambda a: a[:, np.asarray(tables[0])].reshape(  # noqa: E731
        cfg.n_layers, -1, cfg.index_dim
    )
    np.testing.assert_array_equal(flat(after)[:, :start],
                                  flat(before)[:, :start])
    np.testing.assert_array_equal(flat(after)[:, start + chunk:],
                                  flat(before)[:, start + chunk:])
    assert (flat(after)[:, start:start + n_valid]
            != flat(before)[:, start:start + n_valid]).all()


@pytest.mark.parametrize("trace", [0, 1], ids=["timed", "traced"])
def test_the_benchmarks_probe_chain_runs_on_a_packing_engine(tmp_path, trace):
    """``runners/serve_sparse`` at tiny size with 16-token blocks, so
    that the engine's index-key pool PACKS (the benchmark's own tiny
    configuration, ``block_size`` 4, never does): ``build_probes`` reads
    ``engine._pools()`` by token coordinates through ``_at_layer``,
    ``program_scopes`` (the traced run) lowers the engine's programs
    from the pools' shapes, and the run is ``correct`` with the landed
    rows the chain's."""
    from benchmark import run as bench_run
    from tests.benchmark import tiny_keye

    runner = bench_run.load_module("runners", "serve_sparse")
    ctx = tiny_keye.context(tmp_path, trace=trace)
    ctx["config"] = copy.deepcopy(ctx["config"])
    ctx["config"]["serve_engine"].update(block_size=16, num_blocks=30)
    # WHICH requests the check replays hangs on which ones completed
    # inside the 2-second window, so on the host's speed: three answers
    # of the tiny traffic's 2-8 tokens can come to fewer than the
    # runner's ALIKE_ROWS_MIN (8) emitting rows, and a loaded host then
    # reads "too few emitting rows" (the six-worker run, PR 36). Four
    # requests (one a slot) emit at least 4 x 2 whatever the draw.
    ctx["traffic"] = dict(ctx["traffic"], reference_sample=4)
    assert tokens_per_row(
        ctx["config"]["sa_config"]["indexer_head_dim"], 16
    ) == 16
    facts = runner.run(ctx)
    assert facts["problems"] == [], facts["problems"]
    assert facts["kv_stats"]["index_tokens_per_row"] == 16
    assert facts["prefix"]["documents_cached_blocks"] == 3 * 32 // 16
    ref = facts["reference"]
    assert ref["tracked_share"] == 1.0 and ref["pool_err_by_layer"] == [0, 0]
    assert ref["keys_wrong"] == 0 and ref["keys_min"] == 8
    assert ref["share_wide_min"] == 1.0
    assert ref["score_err_max"] < 1e-5
