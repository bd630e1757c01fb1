"""One step in flight (docs/DESIGN.md §29): the engine launches
iteration n's programs before it fetches iteration n-1's tokens, feeds
the tokens back on the device and schedules on counts. Same tokens as
the serial loop it replaced; the launch really comes first; slots go at
the launch; every drain reason and a step error at either end lose and
duplicate nothing; no loop stops with tokens still on the device."""

import numpy as np
import pytest

import jax

from dlrover_tpu.fault import FaultRule, FaultSchedule
from dlrover_tpu.fault import arm as arm_faults
from dlrover_tpu.fault import disarm as disarm_faults
from dlrover_tpu.models import llama
from dlrover_tpu.observability import tracing
from dlrover_tpu.observability.registry import MetricsRegistry
from dlrover_tpu.observability.tracing import Tracer
from dlrover_tpu.serving.engine import ServingEngine
from dlrover_tpu.serving.fleet.replica import serve_step, serve_submit
from dlrover_tpu.serving.kvpool import (
    PagedServingEngine,
    export_request,
    import_request,
    release_exported,
)
from tests.test_serving import naive_greedy

KINDS = ("flat", "paged")
MAX_LEN, CHUNK = 32, 4
# (prompt length, max_new_tokens): one and two tokens, several chunks, a
# one-chunk prompt, and one the cache truncates (28 + 12 > 32 rows).
PLAN = ((5, 6), (3, 1), (9, 2), (4, 4), (13, 7), (28, 12), (2, 1), (7, 3))


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.tiny_config()
    params, _ = llama.init_params(cfg, jax.random.key(0))
    return cfg, params


def build(kind, tiny, slots=3, **kw):
    cfg, params = tiny
    kw = dict(slots=slots, max_len=MAX_LEN, prefill_chunk=CHUNK,
              registry=MetricsRegistry(), **kw)
    if kind == "flat":
        return ServingEngine(cfg, params, **kw)
    kw.setdefault("block_size", 4)
    return PagedServingEngine(cfg, params, **kw)


def prompts(cfg, plan=PLAN, seed=0):
    rs = np.random.RandomState(seed)
    return [
        rs.randint(0, cfg.vocab_size, size=n).astype(np.int32)
        for n, _ in plan
    ]


_REFERENCE = {}


def reference(tiny, prompt, n):
    """Teacher-forced greedy tokens, once per (prompt, length)."""
    key = (prompt.tobytes(), n)
    if key not in _REFERENCE:
        _REFERENCE[key] = naive_greedy(*tiny, prompt, n)
    return _REFERENCE[key]


def drains(eng, reason):
    return eng.metrics.pipeline_drains.value(reason=reason)


def assert_all_served_once(tiny, eng, reqs, done, plan=PLAN):
    """Every request came back exactly once with the reference's tokens
    (a truncated one: as many as its cache had rows for)."""
    assert sorted(r.rid for r in done) == sorted(r.rid for r in reqs)
    assert not eng.pending() and eng._flight is None
    for req, (n, new) in zip(reqs, plan):
        assert req.state == "done" and not req.failed and not req.inflight
        want = min(new, MAX_LEN - n + 1)
        assert req.truncated == (want < new)
        assert req.tokens == reference(tiny, req.prompt, want), req.rid
    if hasattr(eng, "check_block_invariants"):
        eng.check_block_invariants()
        assert eng.kv_stats()["used"] == 0


def serve(eng, tiny, plan=PLAN, per_step=2, each_step=None):
    """Submit the plan ``per_step`` requests an iteration and pump
    ``step()`` while anything is pending."""
    todo = list(zip(prompts(tiny[0], plan), plan))
    reqs, done = [], []
    for _ in range(1000):
        for prompt, (_, new) in todo[:per_step]:
            reqs.append(eng.submit(prompt, new))
        del todo[:per_step]
        if not todo and not eng.pending():
            return reqs, done
        done.extend(eng.step())
        if each_step is not None:
            each_step(reqs)
    raise AssertionError("the engine did not drain")


@pytest.fixture()
def tracer():
    t = tracing.arm(Tracer(service="test"))
    yield t
    tracing.disarm()


def step_attrs(tracer):
    return [
        s["attrs"] for s in tracer.finished()
        if s["name"] == "serving.step"
    ]


# ---- (a) the same tokens ----------------------------------------------------


@pytest.mark.parametrize("kind", KINDS)
def test_pipelined_tokens_equal_the_reference(kind, tiny):
    eng = build(kind, tiny)
    eng.warmup()
    base = dict(eng.trace_counts)
    reqs, done = serve(eng, tiny)
    assert_all_served_once(tiny, eng, reqs, done)
    assert [len(r.tokens) for r in reqs] == [6, 1, 2, 4, 7, 5, 1, 3]
    assert eng.trace_counts == base   # fed either way, one program
    assert drains(eng, "preempt") == drains(eng, "cancel") == 0


@pytest.mark.parametrize("kind", KINDS)
def test_a_last_chunk_rides_with_another_requests_last_launch(kind, tiny):
    """Request B's only chunk is launched in the iteration whose decode
    launch carries A's last token: the flight holds B's first token, A
    leaves its slot by count, and both come out right."""
    eng = build(kind, tiny, slots=2)
    pa, pb = prompts(tiny[0], ((3, 4), (4, 3)), seed=5)
    a = eng.submit(pa, 4)
    for _ in range(50):
        if len(a.tokens) + a.inflight == 3:
            break
        eng.step()
    assert len(a.tokens) + a.inflight == 3 and a.slot >= 0
    b = eng.submit(pb, 3)
    done = eng.step()
    flight = eng._flight
    assert flight.first_row[0] is b and flight.first_row[2] is None
    assert [(r.rid, end) for r, _, end in flight.rows] == [
        (a.rid, "finished"), (b.rid, None),
    ]
    assert a.slot == -1 and a.state == "decode" and b.inflight == 2
    assert a not in done and eng.pending() == 2
    done += eng.run_until_idle()
    assert_all_served_once(tiny, eng, [a, b], done, ((3, 4), (4, 3)))


# ---- (b) the order ----------------------------------------------------------


@pytest.mark.parametrize("kind", KINDS)
def test_a_steady_iteration_launches_before_it_fetches(
    kind, tiny, tracer, monkeypatch
):
    fetches = []
    real_get = jax.device_get
    monkeypatch.setattr(
        jax, "device_get", lambda x: fetches.append(x) or real_get(x)
    )
    eng = build(kind, tiny)
    reqs, done = serve(eng, tiny)
    assert_all_served_once(tiny, eng, reqs, done)
    attrs = step_attrs(tracer)
    # A decode launch finds the previous iteration's in flight, unless
    # that one launched none (the engine's first, or after a lull).
    for before, a in zip([{"n_decoding": 0}] + attrs, attrs):
        assert a["overlapped"] == int(
            a["n_decoding"] > 0 and before["n_decoding"] > 0
        )
    steady = [a for a in attrs if a["overlapped"]]
    assert len(steady) > 10
    for a in steady:
        names = [p[0] for p in a["phases"]]
        assert names.index("decode_launch") < names.index("decode_fetch")
        assert names[-3:] == ["decode_fetch", "commit", "account"]
        assert "prefill_fetch" not in names
        if a["prefill_tokens"]:
            assert names.index("prefill_launch") < names.index(
                "decode_launch"
            )
    # One fetch an iteration that had something in flight, and it takes
    # the decode launch's vector and the chunk's first token together.
    assert len(fetches) == sum(
        1 for a in attrs
        if {"decode_fetch", "prefill_fetch"} & {p[0] for p in a["phases"]}
    )
    assert all(isinstance(f, tuple) and len(f) == 2 for f in fetches)


def test_the_launch_is_fed_from_the_device_while_a_step_is_in_flight(tiny):
    eng = build("paged", tiny)
    (prompt,) = prompts(tiny[0], ((6, 8),))
    req = eng.submit(prompt, 8)
    while not req.inflight:
        eng.step()
    eng.step()
    assert eng._flight is not None and eng._flight.nxt is not None
    assert eng._fed_tokens() is eng._flight.nxt
    eng._drain("cancel")
    assert eng._flight is None and not req.inflight
    fed = eng._fed_tokens()
    assert int(fed[req.slot]) == req.tokens[-1] == eng._tokens[req.slot]
    eng.run_until_idle()
    assert req.tokens == reference(tiny, prompt, 8)


@pytest.mark.parametrize("kind", KINDS)
def test_a_slot_freed_by_count_is_reused_without_an_idle_iteration(
    kind, tiny, tracer
):
    """One slot, two requests: the iteration after A's last decode
    launch admits B and launches its chunk, and the same iteration
    hands A back: A's slot did not wait for A's tokens."""
    eng = build(kind, tiny, slots=1)
    pa, pb = prompts(tiny[0], ((3, 3), (3, 2)), seed=7)
    a, b = eng.submit(pa, 3), eng.submit(pb, 2)
    done = eng.run_until_idle()
    assert_all_served_once(tiny, eng, [a, b], done, ((3, 3), (3, 2)))
    attrs = step_attrs(tracer)
    # A: its chunk and second token, then its third and last token.
    assert [x["n_decoding"] for x in attrs[:2]] == [1, 1]
    assert [x["n_finished"] for x in attrs[:2]] == [0, 0]
    nxt = attrs[2]
    assert (nxt["n_admitted"], nxt["prefill_tokens"]) == (1, 3)
    assert nxt["n_finished"] == 1
    assert b.admit_ts < a.finish_ts


# ---- (c) every drain reason -------------------------------------------------


def test_drain_spec_k_stays_synchronous(tiny):
    cfg, params = tiny
    eng = ServingEngine(
        cfg, params, slots=3, max_len=MAX_LEN, prefill_chunk=CHUNK,
        registry=MetricsRegistry(), spec_k=2,
    )
    reqs, done = serve(eng, tiny)
    assert_all_served_once(tiny, eng, reqs, done)
    # A prompt's first token is committed before the path drafts from
    # it (one asked for a single token may ride to the next iteration's
    # fetch instead, if nothing decodes beside it).
    assert sum(1 for _, new in PLAN if new > 1) <= drains(
        eng, "spec_k"
    ) <= len(PLAN)


def test_drain_on_forced_preemption_with_a_tiny_pool(tiny):
    plan = ((9, 12), (10, 12), (11, 12), (5, 3), (6, 2), (3, 1))
    eng = build("paged", tiny, num_blocks=MAX_LEN // 4 + 3)
    reqs, done = serve(eng, tiny, plan, per_step=3)
    assert_all_served_once(tiny, eng, reqs, done, plan)
    assert sum(r.preemptions for r in reqs) > 0
    assert drains(eng, "preempt") > 0


@pytest.mark.parametrize("kind", KINDS)
def test_cancel_with_a_token_in_flight(kind, tiny):
    """Cancelling a request the device still owes a token commits it
    first: one that had more to come is evicted, one whose last token
    was in flight has finished and the next step() returns it."""
    eng = build(kind, tiny)
    plan = ((5, 6), (6, 3), (4, 5))
    ps = prompts(tiny[0], plan, seed=11)
    victim, last, stays = [eng.submit(p, n) for p, (_, n) in zip(ps, plan)]
    done = []
    for _ in range(50):
        if last.slot == -1 and last.inflight:   # left by count
            break
        done.extend(eng.step())
    assert last.state == "decode" and victim.inflight and not done
    held = len(victim.tokens)
    eng.cancel(victim)
    assert drains(eng, "cancel") == 1 and eng._flight is None
    assert victim.state == "done" and victim.slot == -1
    assert len(victim.tokens) == held + 1 and not victim.inflight
    eng.cancel(last)   # its last token arrived with that drain
    assert last.state == "done" and drains(eng, "cancel") == 1
    assert eng.pending() == 2   # ``last`` waits for step(), ``stays``
    done.extend(eng.run_until_idle())
    assert [r.rid for r in done] == [last.rid, stays.rid]
    assert last.tokens == reference(tiny, last.prompt, 3)
    assert stays.tokens == reference(tiny, stays.prompt, 5)
    if kind == "paged":
        eng.check_block_invariants()
        assert eng.kv_stats()["used"] == 0


def test_export_and_release_with_a_token_in_flight(tiny):
    src, dst = build("paged", tiny), build("paged", tiny)
    src.warmup()
    dst.warmup()
    plan = ((9, 8), (5, 6))
    ps = prompts(tiny[0], plan, seed=13)
    mover, other = [src.submit(p, n) for p, (_, n) in zip(ps, plan)]
    while not mover.tokens:
        src.step()
    assert mover.inflight   # the device owes it a token
    payload = export_request(src, mover)
    assert drains(src, "migrate") == 1 and not mover.inflight
    src.step()              # the source keeps serving it meanwhile
    assert mover.inflight
    filler = dst.submit(ps[1], 4)
    while not filler.inflight:
        dst.step()
    imported = import_request(dst, payload)
    assert drains(dst, "migrate") == 1 and not filler.inflight
    release_exported(src, mover)
    assert drains(src, "migrate") == 2 and mover.state == "done"
    done = src.run_until_idle()
    assert [r.rid for r in done] == [other.rid]
    assert other.tokens == reference(tiny, other.prompt, 6)
    dst.run_until_idle()
    assert imported.tokens == reference(tiny, mover.prompt, 8)
    assert filler.tokens == reference(tiny, filler.prompt, 4)
    for eng in (src, dst):
        eng.check_block_invariants()
        assert eng.kv_stats()["used"] == 0


@pytest.mark.parametrize("where", ["launch", "fetch"])
@pytest.mark.parametrize("kind", KINDS)
def test_a_step_error_drops_the_step_in_flight_and_requeues(
    kind, where, tiny, monkeypatch
):
    """An error at the launch (the fault point) or at the fetch (the
    device reports a launch's error when its result is read, an
    iteration later): the flight is dropped with the pool, requests in
    slots AND requests that had left theirs by count restart, and all
    finish exactly once."""
    eng = build(kind, tiny)
    reqs, done = [], []
    if where == "launch":
        arm_faults(FaultSchedule(
            [FaultRule("serving.step.error", nth=7)], seed=0
        ))
    else:
        real_get, calls = jax.device_get, []

        def flaky_get(x):
            calls.append(1)
            if len(calls) == 6:
                raise RuntimeError("device fault at the fetch")
            return real_get(x)

        monkeypatch.setattr(jax, "device_get", flaky_get)
    seen_leaving = []
    try:
        reqs, done = serve(
            eng, tiny,
            each_step=lambda _: seen_leaving.append(len(eng._leaving)),
        )
    finally:
        disarm_faults()
    assert eng.metrics.step_errors.value() == 1
    assert sum(r.requeues for r in reqs) > 0
    assert max(seen_leaving) > 0 and not eng._leaving
    assert_all_served_once(tiny, eng, reqs, done)


# ---- (d) nothing is left on the device --------------------------------------


@pytest.mark.parametrize("kind", KINDS)
def test_pending_counts_the_step_in_flight(kind, tiny):
    eng = build(kind, tiny, slots=2)
    (prompt,) = prompts(tiny[0], ((3, 2),), seed=17)
    req = eng.submit(prompt, 2)
    assert eng.step() == []
    # Both tokens are sampled, on the device; the slot is free already.
    assert eng.scheduler.active() == [] and not eng.scheduler.queue
    assert req.inflight == 2 and req.tokens == [] and req.slot == -1
    assert eng.pending() == 1 and req.first_token_ts is None
    assert eng.step() == [req]
    assert req.tokens == reference(tiny, prompt, 2)
    assert req.first_token_ts is not None and eng.pending() == 0
    assert eng.run_until_idle() == []


@pytest.mark.parametrize("kind", KINDS)
def test_serve_step_emits_every_completion_exactly_once(kind, tiny):
    eng = build(kind, tiny)
    by_rid, events = {}, []
    for i, (prompt, (_, new)) in enumerate(zip(prompts(tiny[0]), PLAN)):
        serve_submit(eng, by_rid, events.append, f"r{i}", 0,
                     prompt.tolist(), new, 0.0, None)
    for _ in range(500):
        if not eng.pending():
            break
        serve_step(eng, by_rid, events.append)
    assert not eng.pending() and not by_rid
    assert sorted(e["request_id"] for e in events) == sorted(
        f"r{i}" for i in range(len(PLAN))
    )
    assert all(e["ok"] and e["ttft_s"] > 0 for e in events)
    assert [len(e["tokens"]) for e in sorted(
        events, key=lambda e: int(e["request_id"][1:])
    )] == [6, 1, 2, 4, 7, 5, 1, 3]
