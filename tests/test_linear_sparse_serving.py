"""The lightning / block-sparse model (``models/linear_sparse_lm.py``)
through the paged engine (``serving/kvpool/linear.py``), on a CPU at tiny
size with seeded random weights (blocks of 8 rows, a compressed key every
2, 5 blocks a list: the first, the last two and the 2 best of the rest,
``dense_len`` 16, chunks of 16): the model's forward against the plain
reference (``benchmark/reference_sala``: the recurrence, a sort) on LOGITS;
hit + chunked prefill + decode through the per-slot float32 state, the
compressed-key array and the page lists against the full forward; the
state a snapshot holds; snapshots given up before blocks under a budget;
two requests that share a document and differ after it; conservation
through preemption, a reused slot, eviction; migration; what is
refused."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_sala
from dlrover_tpu.models import linear_sparse_lm as lsm
from dlrover_tpu.serving.kvpool import (
    PagedServingEngine,
    export_request,
    import_request,
    layout,
    release_exported,
)
from tests.benchmark import tiny_sala

BS, CHUNK = 8, 16


@pytest.fixture(scope="module")
def tiny():
    cfg = lsm.tiny_config()
    params = jax.jit(lambda key: lsm.init_params(cfg, key))(jax.random.key(0))
    return cfg, params


def cfg_json_of(cfg):
    """The published keys that describe ``cfg`` (for the reference)."""
    return dict(
        tiny_sala.CONFIG, hidden_size=cfg.embed_dim,
        vocab_size=cfg.vocab_size, num_hidden_layers=cfg.n_layers,
        mixer_types=list(cfg.mixer_types),
        first_published_layer=cfg.first_layer,
        published={"num_hidden_layers": cfg.published_layers},
    )


def prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).tolist() for n in lengths]


def engine(cfg, params, **kw):
    kw = dict(dict(slots=3, max_len=128, prefill_chunk=CHUNK, block_size=BS,
                   num_blocks=80), **kw)
    return PagedServingEngine(cfg, params, **kw)


def serve(eng, items):
    reqs = [eng.submit(p, n) for p, n in items]
    while eng.pending():
        eng.step()
    eng.check_block_invariants()
    return [list(r.tokens) for r in reqs]


@pytest.fixture(scope="module")
def want(tiny):
    """``want(prompt, out)``: the REFERENCE's greedy tokens at the
    positions that emitted ``out`` (its free-running full forward over
    prompt + out)."""
    cfg, params = tiny
    cfg_json = cfg_json_of(cfg)

    def greedy(prompt, out):
        tokens = np.zeros(128, np.int32)
        n = len(prompt) + len(out)
        tokens[:n] = prompt + out
        logits = reference_sala.forward(params, tokens, cfg_json)
        rows = np.arange(len(prompt) - 1, n - 1)
        return np.asarray(logits)[rows].argmax(-1).tolist()

    return greedy


@pytest.fixture(scope="module")
def warm(tiny):
    """One engine for the tests that only add requests to it."""
    return engine(*tiny)


def test_the_config_states_three_arrays_a_stride_and_a_float32_state(
    tiny, warm
):
    cfg, _ = tiny
    assert cfg.cache_layers == 4 and len(cfg.lightning_layers) == 3
    arrays = layout.pool_arrays(cfg)
    assert [(a.name, a.stride) for a in arrays] == [
        ("k_pages", 1), ("v_pages", 1), ("ckeys", 2)
    ]
    assert all(a.raw for a in arrays)
    assert arrays[2].block_rows(BS) == 4
    assert arrays[2].block_bytes(4, BS) * 2 == arrays[0].block_bytes(4, BS)
    (state,) = layout.state_arrays(cfg)
    assert (state.name, state.layers, state.shape, state.dtype) == (
        "lightning", 3, (4, 8, 8), jnp.dtype("float32")
    )
    kp, vp, ck, s, snaps = warm._pools()
    assert kp.shape == vp.shape == (4, 80, BS, 8)
    assert ck.shape == (4, 80, BS // 2, 8)
    assert s.shape == (3, 3, 4, 8, 8) and s.dtype == jnp.float32
    assert snaps.shape[1] == warm.state_snapshots + 1


def test_a_state_in_another_dtype_than_the_compute_dtype():
    cfg = lsm.tiny_config(dtype="bfloat16")
    (state,) = layout.state_arrays(cfg)
    assert state.dtype == jnp.float32
    assert layout.pool_arrays(cfg)[0].dtype == jnp.bfloat16


def test_forward_against_the_reference_on_logits(tiny):
    cfg, params = tiny
    (tokens,) = prompts(cfg, [77], seed=3)
    got = lsm.forward(cfg, params, jnp.asarray([tokens]))[0]
    want = reference_sala.forward(params, tokens, cfg_json_of(cfg))
    assert float(jnp.abs(got - want).max()) < 2e-5
    # ... and the reference in blocks of rows is the reference
    blocks = reference_sala.forward(params, tokens, cfg_json_of(cfg), rows=16)
    assert float(jnp.abs(blocks - want).max()) < 2e-5


@pytest.mark.parametrize("n", [5, 16, 17, 40, 77])
def test_chunked_prefill_then_decode_against_the_reference(
    tiny, warm, want, n
):
    """Prompts below, at and several times ``dense_len`` (16)."""
    cfg, _ = tiny
    (prompt,) = prompts(cfg, [n], seed=n)
    (out,) = serve(warm, [(prompt, 12)])
    assert out == want(prompt, out)


def test_the_selected_blocks_are_the_references_as_sets(tiny):
    cfg, _ = tiny
    sh = reference_sala.shape_of(cfg_json_of(cfg))
    # the program's own functions over one sparse layer's q and k
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(96, 4, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(96, 2, 8)), jnp.float32)
    positions = jnp.arange(96)
    padded = jnp.pad(k, ((2, 2), (0, 0), (0, 0)))
    ckeys = lsm.compressed_keys(padded, 2)[:48]
    mask = lsm.select_block_mask(
        cfg, lsm.block_scores(cfg, q, ckeys, positions), positions
    )
    ref_c, last = reference_sala.all_ckeys(sh, k)
    _, ref_mask = reference_sala.select_blocks(
        sh, q, ref_c, last, positions, 12
    )
    assert bool((mask == ref_mask).all())
    lists, count = lsm.select_block_list(
        cfg, lsm.block_scores(cfg, q, ckeys, positions), positions
    )
    for kh in range(2):
        for t in (0, 15, 16, 40, 95):
            got = set(np.asarray(lists[kh, t, :count[kh, t]]).tolist())
            assert got == set(np.nonzero(np.asarray(ref_mask[kh, t]))[0])
    # a query past dense_len lists topk = 5: block 0, its own two, and
    # TWO more by score
    assert int(count[0, 95]) == 5 and int(count[0, 16]) == 3


def test_a_snapshot_holds_the_recurrences_state_and_a_restore_continues(
    tiny, want
):
    cfg, params = tiny
    eng = engine(cfg, params)
    document, turn = prompts(cfg, [64, 11], seed=5)
    serve(eng, [(document, 1)])
    stats = eng.kv_stats()
    assert stats["state_snapshots_live"] == 1
    # the snapshot at the document's end is S_63 of the recurrence
    sh = reference_sala.shape_of(cfg_json_of(cfg))
    carry = reference_sala.new_carry(sh, 64 + 64)
    carry, _ = reference_sala.advance(
        params, carry, np.asarray(document, np.int32), 0, sh, query_rows=64
    )
    snap = next(e.snapshot for e in eng._cache._entries.values() if e.snapshot)
    got = np.asarray(eng._arrays["lightning_snapshots"][:, snap])
    assert np.abs(got - np.asarray(carry["state"])).max() < 1e-4
    # a hit restores it, and the slot continues as an unbroken one does
    (out,) = serve(eng, [(document + turn, 9)])
    after = eng.kv_stats()
    assert after["prefix_hit_tokens"] - stats["prefix_hit_tokens"] == 64
    assert after["state_restores_from_snapshot"] == 1
    (cold,) = serve(
        engine(cfg, params, prefix_cache=False), [(document + turn, 9)]
    )
    assert out == cold == want(document + turn, out)


def test_two_requests_share_a_document_and_each_has_its_straddling_key(
    tiny, want
):
    cfg, params = tiny
    eng = engine(cfg, params)
    document, a, b = prompts(cfg, [64, 9, 13], seed=6)
    serve(eng, [(document, 1)])
    ra, rb = eng.submit(document + a, 10), eng.submit(document + b, 10)
    for _ in range(6):
        eng.step()
    eng._drain("test")
    ta, tb = (eng._tables[r.slot].copy() for r in (ra, rb))
    assert (ta[:8] == tb[:8]).all() and ta[8] != tb[8]
    # place 32 (rows 62-65) straddles the boundary: block 8, offset 0,
    # of each request's OWN first private block, and they differ
    ck = np.asarray(eng._arrays["ckeys"])
    assert np.abs(ck[:, ta[8], 0] - ck[:, tb[8], 0]).max() > 1e-3
    while eng.pending():
        eng.step()
    eng.check_block_invariants()
    assert list(ra.tokens) == want(document + a, list(ra.tokens))
    assert list(rb.tokens) == want(document + b, list(rb.tokens))


def test_snapshots_are_given_up_before_blocks_under_a_budget_of_two(
    tiny, want
):
    cfg, params = tiny
    eng = engine(cfg, params, state_snapshots=2)
    assert eng.state_snapshots == 2
    first, second, third = prompts(cfg, [18, 20, 19], seed=7)
    serve(eng, [(first, 2)])
    serve(eng, [(second, 2)])
    cached = eng.kv_stats()["cached"]
    serve(eng, [(third, 2)])          # no id free: the oldest gives up
    stats = eng.kv_stats()
    assert stats["state_snapshots_given_up"] == 1
    assert stats["state_snapshots_live"] == 2
    assert stats["state_snapshots_denied"] == 0
    assert stats["cached"] > cached               # no block went for it
    # ``first``'s entries stay; its hit is rounded down to nothing
    before = eng.kv_stats()
    (out,) = serve(eng, [(first + [3, 4, 5], 4)])
    after = eng.kv_stats()
    assert after["prefix_hit_tokens"] == before["prefix_hit_tokens"]
    assert (after["prefix_rounded_down_blocks"]
            - before["prefix_rounded_down_blocks"]) == 2
    assert out == want(first + [3, 4, 5], out)
    eng.check_block_invariants()


def test_a_prompt_that_cannot_get_a_snapshot_runs_without_and_is_counted(
    tiny, want
):
    cfg, params = tiny
    eng = engine(cfg, params, state_snapshots=1, slots=2)
    a, b = prompts(cfg, [40, 41], seed=8)
    # both prefill at once over several chunks: one id, lent to the
    # first whose boundary chunk comes up
    (out_a, out_b) = serve(eng, [(a, 3), (b, 3)])
    stats = eng.kv_stats()
    assert stats["state_snapshots"] + stats["state_snapshots_denied"] >= 2
    assert out_a == want(a, out_a) and out_b == want(b, out_b)


def test_the_byte_budget_is_the_engines_own_arithmetic(tiny):
    cfg, params = tiny
    eng = engine(cfg, params)
    entry = sum(a.entry_bytes() for a in layout.state_arrays(cfg))
    block = sum(a.block_bytes(4, BS) for a in layout.pool_arrays(cfg))
    assert entry > block          # 3,072 B a snapshot, 2,560 B a block
    assert eng.state_snapshots == layout.budgeted_snapshots(
        entry, 80 * block, 3
    ) == max(4, int(0.5 * 80 * block) // entry)
    assert eng.state_snapshots < layout.default_snapshots(80, 3)
    with pytest.raises(ValueError, match="state_snapshots 0"):
        engine(cfg, params, state_snapshots=0)


def test_preempt_and_resume_equals_an_unpreempted_run(tiny, want):
    cfg, params = tiny
    eng = engine(cfg, params, slots=2, num_blocks=24, max_len=64)
    a, b = prompts(cfg, [21, 19], seed=10)
    ra, rb = eng.submit(a, 12), eng.submit(b, 12)
    for _ in range(6):
        eng.step()
    eng._drain("test")
    eng._preempt(rb)
    while eng.pending():
        eng.step()
    eng.check_block_invariants()
    assert list(ra.tokens) == want(a, list(ra.tokens))
    assert list(rb.tokens) == want(b, list(rb.tokens))


def test_a_released_slots_next_cold_tenant_starts_from_zeros(tiny, want):
    cfg, params = tiny
    eng = engine(cfg, params, slots=1, prefix_cache=False)
    first, second = prompts(cfg, [27, 6], seed=11)
    serve(eng, [(first, 5)])
    assert float(jnp.abs(eng._arrays["lightning"]).max()) > 0
    (out,) = serve(eng, [(second, 6)])
    assert out == want(second, out)


def test_eviction_frees_the_snapshot_with_its_block(tiny):
    cfg, params = tiny
    eng = engine(cfg, params)
    serve(eng, [(p, 2) for p in prompts(cfg, [9, 17, 25], seed=12)])
    cache = eng._cache
    assert eng.kv_stats()["state_snapshots_live"] == 3
    free = cache.snapshots_free
    cache.evict_lru(100)
    eng.check_block_invariants()
    assert eng.kv_stats()["state_snapshots_live"] == 0
    assert cache.snapshots_free == free + 3 == eng.state_snapshots


def test_export_then_import_carries_state_and_compressed_keys(tiny, want):
    cfg, params = tiny
    src, dst = engine(cfg, params), engine(cfg, params)
    (prompt,) = prompts(cfg, [27], seed=13)
    req = src.submit(prompt, 14)
    while len(req.tokens) < 4:
        src.step()
    payload = export_request(src, req)
    release_exported(src, req)
    src.check_block_invariants()
    moved = import_request(dst, payload)
    while dst.pending():
        dst.step()
    dst.check_block_invariants()
    assert list(moved.tokens) == want(prompt, list(moved.tokens))
    assert len(moved.tokens) == 14


@pytest.mark.parametrize("kw, match", [
    (dict(kv_cache_dtype="int8"), "int8 pool holds K and V alone"),
    (dict(spec_k=2), "per-slot state"),
    (dict(prefill_chunk=4), "whole blocks"),
    (dict(block_size=16, prefill_chunk=16), "sparse block of 8 rows"),
])
def test_what_is_not_carried_is_refused_by_name(tiny, kw, match):
    cfg, params = tiny
    with pytest.raises(ValueError, match=match):
        engine(cfg, params, **kw)


def test_the_slot_engine_is_refused_by_name(tiny):
    from dlrover_tpu.serving.engine import ServingEngine

    cfg, params = tiny
    with pytest.raises((ValueError, NotImplementedError),
                       match="paged|PagedServingEngine"):
        ServingEngine(cfg, params, slots=2, max_len=64, prefill_chunk=16)


def test_no_program_retraces_across_admissions(tiny, warm):
    cfg, _ = tiny
    before = dict(warm.trace_counts)
    serve(warm, [(p, 3) for p in prompts(cfg, [9, 12, 33, 5], seed=14)])
    assert dict(warm.trace_counts) == before


def test_the_selection_says_what_it_runs_and_how_often_its_runs_engage(
    tiny
):
    """Off a TPU the selection is its definition, and ``kv_stats()``
    carries the run copies' two counters: a document prefilled alone is
    all runs; one that takes blocks freed before it, out of order, is
    not."""
    from dlrover_tpu.ops import block_select
    from dlrover_tpu.serving.kvpool import linear

    cfg, params = tiny
    assert linear.select_kind(cfg, cfg.compute_dtype, 48, 1040) == "jnp"
    g = block_select.GROUP_BLOCKS
    document = prompts(cfg, [2 * g * BS - 6], seed=21)[0]

    def stats_at_the_documents_end(eng):
        """``kv_stats()`` with the document's request on its first
        decode rows: two whole groups of blocks visible."""
        r = eng.submit(document, 4)
        while eng._lengths[r.slot] < len(document) or r.slot < 0:
            eng.step()
        assert len(eng._slot_blocks[r.slot]) == 2 * g
        stats, table = eng.kv_stats(), eng._tables[r.slot, :2 * g].copy()
        while eng.pending():
            eng.step()
        return stats, table

    kw = dict(prefix_cache=False, max_len=2 * g * BS + CHUNK,
              num_blocks=2 * g + 8)
    alone, table = stats_at_the_documents_end(engine(cfg, params, **kw))
    assert alone["block_select"] == "jnp"
    assert (np.diff(table) == 1).all()
    assert alone["ckey_copy_groups"] == 2
    assert alone["ckey_copy_groups_run_share"] == 1.0
    eng = engine(cfg, params, **kw)
    serve(eng, [(prompts(cfg, [60], seed=22)[0], 2)])   # blocks 1 .. 8
    reused, table = stats_at_the_documents_end(eng)
    assert (np.diff(table[:g]) == 1).all()
    assert not (np.diff(table[g:]) == 1).all()      # ... 39, then 1 ...
    assert reused["ckey_copy_groups"] == 2
    assert reused["ckey_copy_groups_run_share"] == 0.5
    assert engine(cfg, params).kv_stats()["ckey_copy_groups"] == 0


def test_the_step_span_carries_the_new_counts(tiny):
    from dlrover_tpu.serving.kvpool import linear

    cfg, _ = tiny
    counts = linear.decode_counts(cfg, [5, 40])
    # row 5: places 1-2 seen (last rows 3, 5) x 2 sparse layers; row 40:
    # places 1-19; dense below 16 rows, then 5 blocks (4 whole + 1 row)
    assert counts == {
        "ckey_rows": (2 + 19) * 2, "selected_rows": 6 + 33,
        "state_slots": 2,
    }
    assert linear.rows_listed(cfg, 15) == 16
    assert linear.rows_listed(cfg, 16) == 17
    assert linear.rows_listed(cfg, 24) == 25


def test_trace_query_steps_shows_the_new_counts(tiny):
    """An armed engine's ``serving.step`` spans through ``tools/
    trace_query.py --steps``' table."""
    import importlib.util
    import os

    from dlrover_tpu.observability import tracing

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )), "tools", "trace_query.py")
    spec = importlib.util.spec_from_file_location("trace_query", path)
    trace_query = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(trace_query)
    cfg, params = tiny
    tracer = tracing.arm(tracing.Tracer(service="test", ring_capacity=4096))
    try:
        eng = engine(cfg, params)
        document, turn = prompts(cfg, [32, 9], seed=15)
        serve(eng, [(document, 1)])
        serve(eng, [(document + turn, 6)])
    finally:
        tracing.disarm()
    counts = trace_query.step_summary(tracer.finished())["counts"]
    assert counts["state_slots_mean"] == 1.0
    assert counts["ckey_rows_mean"] > 0 and counts["selected_rows_mean"] > 0
    assert counts["state_snapshots"] == 2
    assert counts["state_restores_from_snapshot"] == 1
    assert counts["prefix_rounded_down_blocks"] == 0
