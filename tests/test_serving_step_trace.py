"""The serving engine times its own step (docs/DESIGN.md §29): armed,
every ``step()`` emits one ``local`` span ``serving.step`` whose phases
tile it and whose counts add up to the work done; disarmed, nothing is
emitted and no clock read is added."""

import json
import os
import sys
import time

import pytest

import jax

from dlrover_tpu.fault import FaultRule, FaultSchedule
from dlrover_tpu.fault import arm as arm_faults
from dlrover_tpu.fault import disarm as disarm_faults
from dlrover_tpu.models import llama
from dlrover_tpu.observability import tracing
from dlrover_tpu.observability.registry import MetricsRegistry
from dlrover_tpu.observability.tracing import Tracer
from dlrover_tpu.serving import engine as engine_mod
from dlrover_tpu.serving.engine import STEP_PHASES, ServingEngine
from dlrover_tpu.serving.kvpool import PagedServingEngine

pytestmark = pytest.mark.trace

KINDS = ("flat", "paged", "speculative")
# (prompt length, new tokens): three requests over two slots, so one
# waits for a slot, and prompts of one, two and three 8-token chunks.
PLAN = ((5, 6), (11, 4), (19, 5))


@pytest.fixture(scope="module")
def parts():
    cfg = llama.tiny_config()
    params, _ = llama.init_params(cfg, jax.random.key(0))
    return cfg, params


def build(kind, parts):
    cfg, params = parts
    kw = dict(slots=2, max_len=64, prefill_chunk=8,
              registry=MetricsRegistry())
    if kind == "paged":
        return PagedServingEngine(cfg, params, block_size=8, **kw)
    return ServingEngine(
        cfg, params, spec_k=3 if kind == "speculative" else 0, **kw
    )


def serve(eng):
    """The PLAN through ``eng``; returns the requests, all finished."""
    reqs = [
        eng.submit([(7 * i + j) % 50 + 1 for j in range(n)], new)
        for i, (n, new) in enumerate(PLAN)
    ]
    eng.run_until_idle(max_iters=500)
    assert all(r.state == "done" for r in reqs)
    return reqs


@pytest.fixture()
def tracer(tmp_path):
    t = tracing.arm(
        Tracer(service="test", sink_path=str(tmp_path / "spans.jsonl"))
    )
    yield t
    tracing.disarm()


def step_spans(tracer):
    return [s for s in tracer.finished() if s["name"] == "serving.step"]


@pytest.mark.parametrize("kind", KINDS)
def test_phases_tile_every_step_span(kind, parts, tracer):
    eng = build(kind, parts)
    serve(eng)
    spans = step_spans(tracer)
    assert len(spans) == eng._step_idx > 0
    assert [s["attrs"]["idx"] for s in spans] == list(range(len(spans)))
    seen = set()
    for s in spans:
        assert s["parent_id"] is None and s["status"] == "ok"
        phases = s["attrs"]["phases"]
        cursor = 0.0
        for name, offset, dur in phases:
            assert name in STEP_PHASES
            assert offset == pytest.approx(cursor, abs=1e-9)
            assert dur >= 0.0
            cursor = offset + dur
            seen.add(name)
        assert phases[0][0] == "admit" and phases[-1][0] == "account"
        assert sum(p[2] for p in phases) == pytest.approx(
            s["dur_s"], abs=1e-6
        )
    # The plain engines fetch a prompt's first token with the decode
    # launch it rides into, a step later; the speculative path drains
    # it (``prefill_fetch``) before it drafts.
    launches = (
        {"prefill_fetch", "spec_draft", "spec_verify"}
        if kind == "speculative"
        else {"decode_launch", "decode_fetch"}
    )
    assert seen == {
        "admit", "prefill_prep", "prefill_launch",
        "decode_prep", "commit", "account",
    } | launches


@pytest.mark.parametrize("kind", KINDS)
def test_a_two_chunk_step_tiles_and_counts_both_launches(
    kind, parts, tracer
):
    """Two prompts in PREFILL slots and the older one three chunks long:
    the first step launches its first two (``Scheduler.pick_prefills``).
    ``prefill_prep`` / ``prefill_launch`` pass twice and still tile the
    span; ``prefill_tokens`` / ``prefill_kv_rows`` are the LAST
    launch's, ``prefill_rows`` both launches' rows."""
    eng = build(kind, parts)
    reqs = [eng.submit(list(range(1, n + 1)), 3) for n in (19, 11)]
    eng.run_until_idle(max_iters=500)
    assert all(r.state == "done" and len(r.tokens) == 3 for r in reqs)
    first, *rest = [s["attrs"] for s in step_spans(tracer)]
    names = [p[0] for p in first["phases"]]
    assert names[:5] == ["admit", "prefill_prep", "prefill_launch",
                         "prefill_prep", "prefill_launch"]
    assert sum(p[2] for p in first["phases"]) == pytest.approx(
        step_spans(tracer)[0]["dur_s"], abs=1e-6
    )
    assert (first["prefill_chunks"], first["prefill_rows"]) == (2, 16)
    assert (first["prefill_tokens"], first["prefill_kv_rows"]) == (8, 16)
    # Then the older prompt's last chunk alone (nothing follows a last
    # chunk), and the younger one's two with nobody waiting behind it.
    assert [(a["prefill_chunks"], a["prefill_rows"]) for a in rest[:3]] == [
        (1, 3), (1, 8), (1, 3),
    ]
    assert sum(a["prefill_chunks"] for a in rest[3:]) == 0
    assert eng.metrics.two_chunk_steps.value() == 1


@pytest.mark.parametrize("kind", KINDS)
def test_counts_add_up_to_the_work_done(kind, parts, tracer):
    reqs = serve(build(kind, parts))
    attrs = [s["attrs"] for s in step_spans(tracer)]
    assert sum(a["prefill_rows"] for a in attrs) == sum(
        n for n, _ in PLAN
    )
    assert sum(a["prefill_chunks"] for a in attrs) == sum(
        -(-n // 8) for n, _ in PLAN
    )
    assert sum(a["n_admitted"] for a in attrs) == len(PLAN)
    assert sum(a["n_finished"] for a in attrs) == len(PLAN)
    decoded = sum(len(r.tokens) - 1 for r in reqs)
    slot_steps = sum(a["n_decoding"] for a in attrs)
    if kind == "speculative":
        # A verify step hands a slot one token or more.
        assert 0 < slot_steps <= decoded
    else:
        assert slot_steps == decoded
    assert all(
        set(a) - {"retraces", "kv_rows", "prefill_kv_rows"} == {
            "idx", "phases", "n_admitted", "n_decoding",
            "prefill_chunks", "prefill_rows", "prefill_tokens",
            "n_finished", "overlapped",
        } for a in attrs
    )
    assert all("retraces" not in a for a in attrs[3:])


@pytest.mark.parametrize("kind", KINDS)
def test_prefill_kv_rows_is_the_cache_a_chunk_launch_attends_to(
    kind, parts, tracer
):
    """``prefill_kv_rows`` rides on exactly the steps that launched a
    prefill chunk and is the slot's fill below the chunk plus the
    chunk's own valid tokens: over a prompt's chunks it climbs by the
    chunk to the prompt's length."""
    serve(build(kind, parts))
    attrs = [s["attrs"] for s in step_spans(tracer)]
    assert all(
        ("prefill_kv_rows" in a) == (a["prefill_tokens"] > 0)
        for a in attrs
    )
    chunk = 8
    want = sorted(
        min(start + chunk, n)
        for n, _ in PLAN for start in range(0, n, chunk)
    )
    assert sorted(
        a["prefill_kv_rows"] for a in attrs if a["prefill_tokens"]
    ) == want
    # The last chunk of a prompt sees all of it.
    assert {n for n, _ in PLAN} <= {
        a["prefill_kv_rows"] for a in attrs if a["prefill_tokens"]
    }


@pytest.mark.parametrize("kind", KINDS)
def test_kv_rows_is_the_cache_visible_to_each_decode_launch(
    kind, parts, tracer
):
    """``kv_rows`` rides on exactly the steps that launched a decode
    and is the sum of the decoding slots' fills: a request's first
    decode step sees its prompt, every later one a row more."""
    reqs = serve(build(kind, parts))
    attrs = [s["attrs"] for s in step_spans(tracer)]
    assert all(("kv_rows" in a) == (a["n_decoding"] > 0) for a in attrs)
    launched = [a for a in attrs if a["n_decoding"]]
    assert launched
    assert all(a["kv_rows"] >= a["n_decoding"] * PLAN[0][0]
               for a in launched)
    fed = [len(r.tokens) - 1 for r in reqs]
    by_request = sum(
        n * plen + n * (n - 1) // 2
        for n, (plen, _) in zip(fed, PLAN)
    )
    total = sum(a["kv_rows"] for a in launched)
    if kind == "speculative":
        # A verify step may commit several tokens: fewer launches see
        # the same prompts, so fewer rows in all.
        assert sum(
            a["n_decoding"] for a in launched
        ) * PLAN[0][0] <= total <= by_request
    else:
        assert total == by_request


@pytest.mark.parametrize("kind", KINDS)
def test_tokens_are_identical_armed_and_disarmed(kind, parts):
    plain = [r.tokens for r in serve(build(kind, parts))]
    tracing.arm(Tracer(service="test"))
    try:
        armed = [r.tokens for r in serve(build(kind, parts))]
    finally:
        tracing.disarm()
    assert armed == plain
    assert [len(t) for t in plain] == [new for _, new in PLAN]


class CountingClock:
    """``time`` for the engine module, counting its monotonic reads."""

    def __init__(self):
        self.reads = 0

    def monotonic(self):
        self.reads += 1
        return time.monotonic()


@pytest.mark.parametrize("kind", KINDS)
def test_disarmed_no_span_and_no_added_clock_read(
    kind, parts, monkeypatch
):
    clock = CountingClock()
    monkeypatch.setattr(engine_mod, "time", clock)
    tracer = tracing.arm(Tracer(service="test"))
    try:
        eng = build(kind, parts)
        armed_build_reads, clock.reads = clock.reads, 0
        serve(eng)
        steps = [s["attrs"] for s in step_spans(tracer)]
    finally:
        tracing.disarm()
    armed_reads, clock.reads = clock.reads, 0
    n_spans = len(tracer.finished())
    eng = build(kind, parts)
    # Construction marks its phases armed or not: the same few reads.
    assert clock.reads == armed_build_reads <= 12
    clock.reads = 0
    serve(eng)
    assert len(tracer.finished()) == n_spans and eng._step_trace is None
    # What step() read before it timed itself: its own start, the
    # token-latency observation of a step that emitted tokens, every
    # request's first-token stamp, and the speculative path's three
    # draft / verify marks.
    decoding = sum(1 for a in steps if a["n_decoding"])
    expected = len(steps) + decoding + len(PLAN)
    if kind == "speculative":
        expected += 3 * decoding
    assert clock.reads == expected
    assert armed_reads > expected


def test_a_local_span_stays_in_the_process(tmp_path):
    seen = []
    sink = tmp_path / "spans.jsonl"
    tracer = Tracer(service="t", sink_path=str(sink),
                    on_finish=seen.append)
    tracer.record_span("serving.step", 1.0, 2.0, local=True)
    # A local record rides in the sink's buffer; the next span that is
    # not local flushes both, in order.
    assert sink.read_text() == ""
    tracer.record_span("serving.request", 1.0, 3.0)
    assert len(sink.read_text().splitlines()) == 2
    tracer.close()
    assert [s["name"] for s in tracer.finished()] == [
        "serving.step", "serving.request",
    ]
    on_disk = [json.loads(line) for line in sink.read_text().splitlines()]
    assert [s["name"] for s in on_disk] == [
        "serving.step", "serving.request",
    ]
    assert [s["name"] for s in seen] == ["serving.request"]
    assert [s["name"] for s in tracer.drain_exports()] == [
        "serving.request"
    ]


def test_the_engine_exports_requests_and_never_steps(parts, tracer):
    serve(build("paged", parts))
    exported = {s["name"] for s in tracer.drain_exports(10 ** 6)}
    assert "serving.request" in exported
    assert "serving.step" not in exported
    assert step_spans(tracer)


@pytest.mark.parametrize("kind", KINDS)
def test_an_injected_step_error_yields_an_error_span(kind, parts, tracer):
    eng = build(kind, parts)
    arm_faults(FaultSchedule(
        [FaultRule("serving.step.error", nth=3)], seed=0
    ))
    try:
        reqs = serve(eng)
    finally:
        disarm_faults()
    spans = step_spans(tracer)
    assert [s["status"] for s in spans].count("error") == 1
    bad = next(s for s in spans if s["status"] == "error")
    assert bad["attrs"]["idx"] == 2
    assert [p[0] for p in bad["attrs"]["phases"]] == ["admit", "account"]
    assert sum(p[2] for p in bad["attrs"]["phases"]) == pytest.approx(
        bad["dur_s"], abs=1e-6
    )
    # The requeued requests restart: their prompts are prefilled twice.
    assert sum(s["attrs"]["prefill_rows"] for s in spans) > sum(
        n for n, _ in PLAN
    )
    assert [len(r.tokens) for r in reqs] == [new for _, new in PLAN]


def test_trace_query_renders_a_step_span(parts, tracer, tmp_path, capsys):
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools",
    ))
    import trace_query

    serve(build("flat", parts))
    tracer.close()
    sink = str(tmp_path / "spans.jsonl")
    step = step_spans(tracer)[1]
    assert trace_query.main(["--trace", step["trace_id"], sink]) == 0
    out = capsys.readouterr().out
    assert "serving.step" in out and "account" in out
    assert trace_query.main(["--serving", "--json", sink]) == 0
    rows = {r["name"] for r in json.loads(capsys.readouterr().out)}
    assert "step" not in rows and "decode" in rows
    assert trace_query.main(["--steps", "--json", sink]) == 0
    table = json.loads(capsys.readouterr().out)
    assert {r["name"] for r in table["phases"]} <= set(STEP_PHASES)
    assert sum(r["share_pct"] for r in table["phases"]) == pytest.approx(
        100.0, abs=0.1
    )
    spans = step_spans(tracer)
    assert table["counts"] == {
        "steps": len(spans), "errors": 0, "admitted": len(PLAN),
        "finished": len(PLAN),
        "prefill_tokens": sum(n for n, _ in PLAN),
        "decode_batch_mean": pytest.approx(
            table["counts"]["decode_batch_mean"]
        ),
        "kv_rows_mean": pytest.approx(
            sum(s["attrs"].get("kv_rows", 0) for s in spans)
            / sum("kv_rows" in s["attrs"] for s in spans)
        ),
        "prefill_kv_rows_mean": pytest.approx(
            sum(s["attrs"].get("prefill_kv_rows", 0) for s in spans)
            / sum("prefill_kv_rows" in s["attrs"] for s in spans)
        ),
        "overlapped_pct": pytest.approx(
            100.0 * sum(s["attrs"]["overlapped"] for s in spans)
            / sum(s["attrs"]["n_decoding"] > 0 for s in spans)
        ),
        "retraced_steps": [
            s["attrs"]["idx"] for s in spans if "retraces" in s["attrs"]
        ],
        # PLAN never meets the rule of the second chunk: its first
        # prompt's first chunk is its last, and the third prompt gets
        # its slot when the second one decodes.
        "prefill_chunks_mean": 1.0, "two_chunk_steps_pct": 0.0,
    }
    assert 1.0 <= table["counts"]["decode_batch_mean"] <= 2.0
    # A decode launch after one found it in flight (not the first, nor
    # the one after a lull in which only a chunk ran).
    assert 50.0 < table["counts"]["overlapped_pct"] < 100.0
    assert table["counts"]["prefill_kv_rows_mean"] > 8
    assert trace_query.main(["--steps", sink]) == 0
    printed = capsys.readouterr().out
    assert "retraced_steps=" in printed and "kv_rows_mean=" in printed
    assert " prefill_kv_rows_mean=" in printed
    assert " overlapped_pct=" in printed
