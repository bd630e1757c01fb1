"""The delta-rule / full-attention model (``models/delta_lm.py``) through
the paged engine (``serving/kvpool/delta.py``), on a CPU at tiny size with
seeded random weights (blocks of 8 rows, chunks of 16, 4 un-grouped heads
held as 8): the model's forward against the plain reference
(``benchmark/reference_olmo_hybrid``: the recurrence a token a step) on
LOGITS; chunked prefill + decode against it; a session grown over four
turns (hit, restore of BOTH state arrays, rows prefilled again) against a
cold pass over the whole conversation; snapshots given up under a budget
of two; preemption and migration carry both arrays; every loop over the
state arrays holds for two."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_olmo_hybrid as reference
from benchmark.runners import serve_delta
from dlrover_tpu.models import delta_lm
from dlrover_tpu.serving.kvpool import (
    PagedServingEngine,
    export_request,
    import_request,
    layout,
    release_exported,
)
from tests.benchmark import tiny_olmo_hybrid

BS, CHUNK = 8, 16
CFG_JSON = tiny_olmo_hybrid.CONFIG


@pytest.fixture(scope="module")
def tiny():
    cfg = serve_delta.delta_config(CFG_JSON)
    params = jax.jit(lambda key: delta_lm.init_params(cfg, key))(
        jax.random.key(0)
    )
    return cfg, params


def prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).tolist() for n in lengths]


def engine(cfg, params, **kw):
    kw = dict(dict(slots=3, max_len=192, prefill_chunk=CHUNK, block_size=BS,
                   num_blocks=100), **kw)
    return PagedServingEngine(cfg, params, **kw)


def serve(eng, items):
    reqs = [eng.submit(p, n) for p, n in items]
    while eng.pending():
        eng.step()
    eng.check_block_invariants()
    return [list(r.tokens) for r in reqs]


@pytest.fixture(scope="module")
def ref_logits(tiny):
    """``ref_logits(tokens)``: the REFERENCE's float32 logits of one whole
    sequence (padded to a fixed length: one program)."""
    _, params = tiny
    sh = reference.shape_of(CFG_JSON)

    def logits(tokens):
        padded = np.zeros(192, np.int32)
        padded[:len(tokens)] = tokens
        return np.asarray(reference.forward(params, padded, sh))[:len(tokens)]

    return logits


@pytest.fixture(scope="module")
def want(ref_logits):
    """``want(prompt, out)``: the reference's greedy tokens at the rows
    that emitted ``out``, and its smallest top-2 gap there."""

    def greedy(prompt, out):
        logits = ref_logits(list(prompt) + list(out))
        rows = logits[len(prompt) - 1:len(prompt) + len(out) - 1]
        return rows.argmax(-1).tolist()

    return greedy


@pytest.fixture(scope="module")
def warm(tiny):
    """One engine for the tests that only add requests to it."""
    return engine(*tiny)


def test_the_config_states_two_state_arrays_of_two_dtypes(tiny, warm):
    cfg, _ = tiny
    assert cfg.kind == "delta_lm" and cfg.cache_layers == 2
    assert cfg.kv_heads_held == 8 and cfg.n_kv_heads == 4
    arrays = layout.pool_arrays(cfg)
    assert [(a.name, a.row_shape) for a in arrays] == [
        ("k", (8, 8)), ("v", (8, 8))
    ]
    delta, taps = layout.state_arrays(cfg)
    assert (delta.name, delta.layers, delta.shape, delta.dtype) == (
        "delta", 4, (3, 8, 12), jnp.dtype("float32")
    )
    assert (taps.name, taps.layers, taps.shape, taps.dtype) == (
        "taps", 4, (3, 84), cfg.compute_dtype
    )
    k, v, d, t, ds, ts = warm._pools()
    assert k.shape == v.shape == (2, 100, BS, 8, 8)
    assert d.shape == (4, 3, 3, 8, 12) and t.shape == (4, 3, 3, 84)
    assert ds.shape[1] == ts.shape[1] == warm.state_snapshots + 1
    # two dtypes side by side where the compute dtype is not float32
    bf16 = delta_lm.tiny_config(dtype="bfloat16")
    assert [a.dtype for a in layout.state_arrays(bf16)] == [
        jnp.dtype("float32"), jnp.dtype("bfloat16")
    ]
    stats = warm.kv_stats()
    assert stats["pool_attention"] == "delta_state_and_pages"
    assert stats["delta_decode"] == stats["delta_chunk"] == "jnp"
    assert stats["full_decode_attention"] == "gathered_view"
    assert stats["state_array_bytes"] == {
        "delta": 4 * 3 * 8 * 12 * 4, "taps": 4 * 3 * 84 * 4
    }
    assert stats["state_bytes"] == 3 * sum(
        stats["state_array_bytes"].values()
    )


def test_the_parameter_count_is_the_published_keys(tiny):
    cfg, params = tiny
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert n == cfg.count_params() == reference.count_params(
        reference.shape_of(CFG_JSON)
    )


def test_forward_against_the_reference_on_logits(tiny, ref_logits):
    cfg, params = tiny
    (tokens,) = prompts(cfg, [77], seed=3)
    got = np.asarray(delta_lm.forward(cfg, params, jnp.asarray([tokens]))[0])
    want_logits = ref_logits(tokens)
    assert np.abs(got - want_logits).max() < 5e-5
    # ... and the reference in blocks of rows is the reference
    sh = reference.shape_of(CFG_JSON)
    blocks = np.asarray(reference.forward(
        params, np.asarray(tokens[:64]), sh, block_rows=16
    ))
    assert np.abs(blocks - want_logits[:64]).max() < 5e-5


@pytest.mark.parametrize("n", [5, 16, 17, 40, 77])
def test_chunked_prefill_then_decode_against_the_reference(
    tiny, warm, want, n
):
    cfg, _ = tiny
    (prompt,) = prompts(cfg, [n], seed=n)
    (out,) = serve(warm, [(prompt, 10)])
    assert out == want(prompt, out)


def test_the_engines_logits_are_the_references(tiny, ref_logits):
    """LOGITS, not tokens: the prefill chunk's last row and a decode
    step's, read by the probes (made of the programs' own functions) over
    the live pool and both state arrays after hit + restore + chunk."""
    cfg, params = tiny
    eng = engine(cfg, params)
    opening, turn = prompts(cfg, [45, 14], seed=9)
    (answer,) = serve(eng, [(opening, 5)])
    grown = opening + answer + turn
    req = eng.submit(grown, 40)
    while len(req.tokens) < 4:
        eng.step()
    eng._drain("test")
    assert req.prefix_hit_blocks == 5            # 45 // 8
    (reading,) = serve_delta.probe_program(eng, [req])
    seq = grown + [int(t) for t in req.tokens]
    want_logits = ref_logits(seq)
    # the chunk that resumed at the hit's boundary: every valid row
    start, n_valid = reading["hit_rows"], reading["n_valid"]
    assert start == 40 and n_valid == 16
    assert np.abs(
        reading["chunk_logits"][:n_valid]
        - want_logits[start:start + n_valid]
    ).max() < 1e-4
    # the step the slot would take next
    assert np.abs(
        reading["next_logits"] - want_logits[len(seq) - 1]
    ).max() < 1e-4
    while eng.pending():
        eng.step()


def test_a_session_grown_over_four_turns_equals_a_cold_pass(tiny, want):
    cfg, params = tiny
    eng = engine(cfg, params)
    cold = engine(cfg, params, prefix_cache=False)
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, cfg.vocab_size, 29).tolist()
    for turn in range(4):
        before = eng.kv_stats()
        (out,) = serve(eng, [(prompt, 6)])
        after = eng.kv_stats()
        (alone,) = serve(cold, [(prompt, 6)])
        assert out == alone == want(prompt, out)
        hit = after["prefix_hit_tokens"] - before["prefix_hit_tokens"]
        restored = (after["state_restores_from_snapshot"]
                    - before["state_restores_from_snapshot"])
        if turn:
            # the hit reaches the LAST prompt's last whole block: the
            # answer, the tail and the turn are prefilled again
            assert hit == last_len // BS * BS and restored == 1
        else:
            assert hit == 0 and restored == 0
        last_len = len(prompt)
        prompt = prompt + out + rng.integers(0, cfg.vocab_size, 11).tolist()
    # the snapshot the last turn wrote holds BOTH arrays as of its row:
    # the reference's S and its last three projections there
    sh = reference.shape_of(CFG_JSON)
    boundary = last_len // BS * BS
    tokens = np.zeros(192, np.int32)
    tokens[:last_len] = prompt[:last_len]
    _, ref = reference.advance(
        params, reference.new_carry(sh, 192), tokens, 0, sh,
        keep_rows=(boundary - 1, -1),
    )
    snap = serve_delta.snapshot_of(eng, prompt[:last_len], boundary // BS)
    assert snap
    got = np.asarray(eng._arrays["delta_snapshots"][:, snap])
    assert np.abs(got - np.asarray(ref["state_rows"][:, 0])).max() < 1e-4
    got = np.asarray(eng._arrays["taps_snapshots"][:, snap])
    assert np.abs(got - np.asarray(ref["taps_rows"][:, 0])).max() < 1e-4


def test_snapshots_given_up_under_a_budget_of_two_fall_back_and_agree(
    tiny, want
):
    cfg, params = tiny
    eng = engine(cfg, params, state_snapshots=2)
    first, second, third = prompts(cfg, [26, 20, 19], seed=7)
    (out1,) = serve(eng, [(first, 3)])
    # the session's second turn: a deeper boundary, its own snapshot
    grown = first + out1 + [5, 6, 7, 8, 9, 10]
    serve(eng, [(grown, 2)])
    serve(eng, [(third, 2)])          # no id free: the oldest gives up
    stats = eng.kv_stats()
    assert stats["state_snapshots_given_up"] == 1
    assert stats["state_snapshots_live"] == 2
    assert stats["state_snapshots_denied"] == 0
    # the first turn's boundary lost its snapshot; the second's has one:
    # a third turn resumes from the deeper one
    before = eng.kv_stats()
    (out,) = serve(eng, [(grown + [3, 4, 5], 4)])
    assert out == want(grown + [3, 4, 5], out)
    assert (eng.kv_stats()["prefix_hit_tokens"]
            - before["prefix_hit_tokens"]) == len(grown) // BS * BS
    serve(eng, [(second, 2)])         # ... gives up the oldest again
    # a prompt that matches the first turn alone falls back to zeros
    # (its boundary's snapshot is gone) and still agrees
    before = eng.kv_stats()
    (out,) = serve(eng, [(first + [1, 2], 4)])
    after = eng.kv_stats()
    assert out == want(first + [1, 2], out)
    assert after["prefix_hit_tokens"] == before["prefix_hit_tokens"]
    assert (after["prefix_rounded_down_blocks"]
            > before["prefix_rounded_down_blocks"])
    eng.check_block_invariants()


def test_preempt_and_resume_equals_an_unpreempted_run(tiny, want):
    cfg, params = tiny
    eng = engine(cfg, params, slots=2, num_blocks=30, max_len=64)
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
    assert float(jnp.abs(eng._arrays["delta"]).max()) > 0
    assert float(jnp.abs(eng._arrays["taps"]).max()) > 0
    (out,) = serve(eng, [(second, 6)])
    assert out == want(second, out)


def test_export_then_import_carries_both_state_arrays(tiny, want):
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


def test_every_walk_over_the_state_arrays_moves_the_pair(tiny):
    """Restore, get / put (migration), the sentinel: both arrays, each in
    its own dtype, by the programs ``_state_steps(2)`` builds."""
    cfg, params = tiny
    eng = engine(cfg, params)
    (prompt,) = prompts(cfg, [33], seed=15)
    serve(eng, [(prompt, 2)])
    snap = serve_delta.snapshot_of(eng, prompt, 4)
    assert snap
    eng._restore_state(1, snap)
    for name in ("delta", "taps"):
        got = np.asarray(eng._arrays[name][:, 1])
        held = np.asarray(eng._arrays[name + "_snapshots"][:, snap])
        assert np.abs(held).max() > 0
        np.testing.assert_array_equal(got, held)
    rows = eng._get_slot_state(1)
    assert [r.shape for r in rows] == [(4, 3, 8, 12), (4, 3, 84)]
    eng._put_slot_state(2, rows)
    for name, row in zip(("delta", "taps"), rows):
        np.testing.assert_array_equal(np.asarray(eng._arrays[name][:, 2]),
                                      row)
    eng._restore_state(1, 0)                      # the sentinel: zeros
    assert all(
        float(jnp.abs(eng._arrays[name][:, 1]).max()) == 0
        for name in ("delta", "taps")
    )


def test_the_step_span_carries_the_new_counts(tiny):
    from dlrover_tpu.observability import tracing

    cfg, params = tiny
    eng = engine(cfg, params, state_snapshots=1)
    tracer = tracing.arm(tracing.Tracer(service="test", ring_capacity=4096))
    try:
        a, b = prompts(cfg, [26, 21], seed=16)
        (out,) = serve(eng, [(a, 3)])
        serve(eng, [(b, 2)])                      # takes a's snapshot id
        serve(eng, [(a + out + [1, 2, 3], 2)])    # a's blocks, no state
    finally:
        tracing.disarm()
    steps = [s for s in tracer.finished() if s["name"] == "serving.step"]
    total = lambda name: sum(  # noqa: E731
        s["attrs"].get(name, 0) for s in steps
    )
    assert total("state_slots") > 0
    assert total("state_snapshots") == 3
    assert total("state_snapshots_given_up") == 2
    # every row of all three prompts: none of the hits had a snapshot
    assert total("prefill_rows_again") == 26 + 21 + 32
    assert total("state_restores") == 3
    assert total("state_restores_from_snapshot") == 0


@pytest.mark.parametrize("kw, match", [
    (dict(kv_cache_dtype="int8"), "per-slot state"),
    (dict(spec_k=2), "per-slot state"),
    (dict(prefill_chunk=4), "whole blocks"),
])
def test_what_is_not_carried_is_refused_by_name(tiny, kw, match):
    cfg, params = tiny
    with pytest.raises(ValueError, match=match):
        engine(cfg, params, **kw)


def test_no_program_retraces_across_admissions(tiny, warm):
    cfg, _ = tiny
    before = dict(warm.trace_counts)
    serve(warm, [(p, 3) for p in prompts(cfg, [9, 12, 33, 5], seed=14)])
    assert dict(warm.trace_counts) == before
