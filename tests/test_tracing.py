"""Cross-process distributed tracing + live straggler/hang diagnosis
(docs/DESIGN.md §29): span layer, RPC context propagation (incl. the
retried-RPC same-span contract), serving/fleet/trainer phase trees,
the master's straggler score and /api endpoints, the hang watchdog's
stack capture, /metrics quantile gauges, and the trace_query CLI."""

import http.client
import json
import os
import signal
import threading
import time

import numpy as np
import pytest

from dlrover_tpu.common import comm
from dlrover_tpu.master.monitor.perf_monitor import PerfMonitor
from dlrover_tpu.master.servicer import MasterServicer
from dlrover_tpu.observability import tracing
from dlrover_tpu.observability.registry import MetricsRegistry
from dlrover_tpu.observability.tracing import (
    TraceAggregator,
    Tracer,
    build_trees,
    load_spans,
)

pytestmark = pytest.mark.trace


@pytest.fixture()
def tracer(tmp_path):
    """An armed tracer with a JSONL sink; always disarmed afterwards so
    other tests keep the one-global-check disarmed state."""
    t = tracing.arm(
        Tracer(service="test", sink_path=str(tmp_path / "spans.jsonl"))
    )
    yield t
    tracing.disarm()


def _not_the_watchers(spans):
    """Without the ``host.*`` spans of the armed Tracer's own watcher
    (observability/host_watch.py; tests/test_host_watch.py)."""
    return [s for s in spans if not s["name"].startswith("host.")]


# ---------------------------------------------------------------------------
# Span layer basics
# ---------------------------------------------------------------------------


def test_span_nesting_propagation_and_sink(tracer, tmp_path):
    with tracing.span("outer", kind="server", a=1) as outer:
        carrier = tracing.current_carrier()
        assert carrier == {
            "trace_id": outer.trace_id, "span_id": outer.span_id,
        }
        with tracing.span("inner") as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
            inner.set_attr("bytes", 42)
    # Cross-process parenting: a child built from the carrier dict.
    child = tracing.record_span("remote", 1.0, 2.5, parent=carrier)
    assert child.trace_id == outer.trace_id
    assert child.parent_id == outer.span_id
    records = load_spans([str(tmp_path / "spans.jsonl")])
    assert records[0]["name"] == "host.watch"  # the Tracer is armed
    by_name = {r["name"]: r for r in _not_the_watchers(records)}
    assert set(by_name) == {"outer", "inner", "remote"}
    assert by_name["remote"]["dur_s"] == pytest.approx(1.5)
    assert by_name["inner"]["attrs"]["bytes"] == 42
    assert by_name["outer"]["service"] == "test"
    # Ring + trees: one coherent trace.
    trees = build_trees(tracer.finished())  # (leaves the watcher's out)
    assert len(trees) == 1
    root = trees[0]
    assert root["name"] == "outer"
    assert {c["name"] for c in root["children"]} == {"inner", "remote"}


def test_disarmed_span_sites_are_noops():
    assert tracing.active_tracer() is None
    sp = tracing.span("x", a=1)
    assert sp is tracing.NOOP_SPAN
    with sp as s:
        s.set_attr("k", "v")
        assert s.inc_attr("retry") == 0
        assert s.carrier() is None
    assert tracing.current_carrier() is None
    assert tracing.record_span("y", 0.0, 1.0) is None
    tracing.bump_current("retry")  # must not raise


def test_error_status_on_exception(tracer):
    with pytest.raises(RuntimeError):
        with tracing.span("boom"):
            raise RuntimeError("nope")
    (record,) = _not_the_watchers(tracer.finished())
    assert record["status"] == "error"
    assert record["attrs"]["error"] == "RuntimeError"


# ---------------------------------------------------------------------------
# RPC propagation: one span per logical RPC, retries bump the attr
# ---------------------------------------------------------------------------


def _http_master(servicer):
    from dlrover_tpu.rpc.transport import HttpMasterServer

    server = HttpMasterServer(0, servicer)
    server.start()
    return server


def test_retry_rpc_reuses_one_span_with_retry_attr(tracer):
    """Satellite: a fault-injected transport failure makes retry_rpc
    re-send — the trace shows ONE client span with retry=1, and the
    (single successful) server span joins the same trace."""
    from dlrover_tpu.agent.master_client import MasterClient
    from dlrover_tpu.fault import FaultRule, FaultSchedule, arm, disarm

    servicer = MasterServicer(rdzv_managers={})
    server = _http_master(servicer)
    client = MasterClient(
        f"localhost:{server.port}", node_id=0, kind="http"
    )
    arm(FaultSchedule([
        FaultRule("rpc.client.get", action="raise", nth=1, once=True,
                  match={"request": "KVStoreGetRequest"}),
    ], seed=0))
    try:
        client.kv_store_set("k", b"v")
        assert client.kv_store_get("k") == b"v"
    finally:
        disarm()
        client.close()
        server.stop()
    spans = tracer.finished()
    client_spans = [
        s for s in spans if s["name"] == "rpc.kv_store_get"
    ]
    assert len(client_spans) == 1, (
        "a retried RPC must reuse its span, not mint siblings"
    )
    assert client_spans[0]["attrs"]["retry"] == 1
    server_spans = [
        s for s in spans if s["name"] == "master.KVStoreGetRequest"
    ]
    # Attempt 1 died client-side (before the wire): exactly one server
    # span, in the client span's trace, parented to it.
    assert len(server_spans) == 1
    assert server_spans[0]["trace_id"] == client_spans[0]["trace_id"]
    assert server_spans[0]["parent_id"] == client_spans[0]["span_id"]


def test_http_stub_stale_keepalive_retry_bumps_same_span(tracer):
    """The stub's transparent stale-connection re-send increments the
    active span's retry attr (at-most-once stays one wire op)."""
    from dlrover_tpu.rpc.transport import HttpMasterStub

    servicer = MasterServicer(rdzv_managers={})
    server = _http_master(servicer)
    stub = HttpMasterStub(f"localhost:{server.port}")

    class _StaleConn:
        def request(self, *a, **k):
            raise http.client.RemoteDisconnected("stale keep-alive")

        def close(self):
            pass

    try:
        # Plant a poisoned "reused" connection: first attempt fails
        # with a stale-socket error, the retry runs on a fresh conn.
        stub._local.conn = _StaleConn()
        with tracing.span("rpc.probe", kind="client") as sp:
            stub.get(comm.Message(node_id=0))
        assert sp.attrs["retry"] == 1
    finally:
        stub.close()
        server.stop()


def test_message_trace_defaults_are_backward_safe():
    msg = comm.Message(node_id=1, data=b"")
    assert getattr(msg, "trace", None) is None
    round_tripped = comm.Message.deserialize(msg.serialize())
    assert round_tripped.trace is None


# ---------------------------------------------------------------------------
# Serving engine: phase spans sum to e2e
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_engine_parts():
    import jax

    from dlrover_tpu.models import llama

    cfg = llama.tiny_config()
    params, _ = llama.init_params(cfg, jax.random.key(0))
    return cfg, params


def test_engine_emits_contiguous_phase_spans(tracer, tiny_engine_parts):
    from dlrover_tpu.serving.engine import ServingEngine

    cfg, params = tiny_engine_parts
    eng = ServingEngine(
        cfg, params, slots=2, max_len=64, prefill_chunk=8,
        registry=MetricsRegistry(),
    )
    carrier = {"trace_id": "t" * 24, "span_id": "a" * 12}
    eng.submit([1, 2, 3, 4], 5, trace=carrier)
    eng.submit([5, 6, 7], 3)
    eng.run_until_idle()
    spans = tracer.finished()
    requests = [s for s in spans if s["name"] == "serving.request"]
    assert len(requests) == 2
    linked = [s for s in requests if s["trace_id"] == "t" * 24]
    assert len(linked) == 1 and linked[0]["parent_id"] == "a" * 12
    for root in requests:
        children = [
            s for s in spans if s["parent_id"] == root["span_id"]
        ]
        names = {s["name"] for s in children}
        assert names == {
            "serving.queue_wait", "serving.prefill", "serving.decode",
        }
        # The §29 invariant: contiguous phases partition the e2e
        # latency (within 10%, here float-exact by construction).
        phase_sum = sum(s["dur_s"] for s in children)
        assert phase_sum == pytest.approx(
            root["dur_s"], rel=0.1, abs=0.005
        )


# ---------------------------------------------------------------------------
# Fleet router: failed attempt + retry as sibling spans
# ---------------------------------------------------------------------------


def test_router_rerouted_request_has_sibling_attempt_spans(tracer):
    from tests.test_fleet import FakeClock, FakeReplica

    from dlrover_tpu.serving.fleet import (
        FleetRouter,
        HealthPolicy,
        RouterConfig,
    )

    clock = FakeClock()
    reps = [FakeReplica(i, clock) for i in range(2)]
    router = FleetRouter(
        reps,
        RouterConfig(
            retry_backoff_s=0.1, retry_jitter_frac=0.0,
            health=HealthPolicy(
                heartbeat_timeout_s=5.0, probe_cooldown_s=1.0,
                probe_successes=1,
            ),
        ),
        clock=clock,
        registry=MetricsRegistry(),
    )
    router.start()
    req = router.submit([1, 2, 3], 4, request_id="r1")
    router.step()
    victim = reps[0] if reps[0].inbox else reps[1]
    other = reps[1] if victim is reps[0] else reps[0]
    item = victim.take()
    victim.fail(item, reason="replica_error")
    router.step()                      # failure -> backoff
    clock.advance(0.2)
    router.step()                      # retry dispatches elsewhere
    item2 = other.take()
    assert item2.trace is not None     # context propagated to replica
    other.complete(item2, tokens=(7, 8))
    router.step()
    assert req.result is not None and req.result.ok
    trees = build_trees(tracer.finished())
    (root,) = [t for t in trees if t["name"] == "fleet.request"]
    attempts = [
        c for c in root["children"] if c["name"] == "fleet.attempt"
    ]
    assert len(attempts) == 2, "failed attempt and retry are siblings"
    statuses = sorted(a["status"] for a in attempts)
    assert statuses == ["error", "ok"]
    failed = next(a for a in attempts if a["status"] == "error")
    assert failed["attrs"]["failure_reason"] == "replica_error"
    # The replica-bound carrier was the winning attempt's span.
    won = next(a for a in attempts if a["status"] == "ok")
    assert item2.trace["span_id"] == won["span_id"]


# ---------------------------------------------------------------------------
# Trainer: per-step phase spans + straggler piggyback
# ---------------------------------------------------------------------------


def test_trainer_step_spans_and_step_time_report(tracer):
    from dlrover_tpu.trainer.elastic.trainer import (
        ElasticBatchConfig,
        ElasticTrainer,
    )

    reports = []

    class _Client:
        def report_global_step(self, step, elapsed_train_secs=0.0,
                               step_time_s=0.0):
            reports.append((step, step_time_s))

        def report_trace_spans(self, max_n=256):
            pass

    trainer = ElasticTrainer(
        ElasticBatchConfig(global_batch_size=8, micro_batch_per_device=1),
        dp_size=8,
        master_client=_Client(),
        report_interval_s=0.0,
    )
    trainer.start_training()
    time.sleep(0.02)
    trainer.step_completed(
        data_wait_s=0.004, ckpt_block_s=0.002, allreduce_wait_s=0.003
    )
    assert reports and reports[0][0] == 1
    assert reports[0][1] > 0
    spans = tracer.finished()
    (root,) = [s for s in spans if s["name"] == "train.step"]
    children = {
        s["name"]: s for s in spans if s["parent_id"] == root["span_id"]
    }
    assert set(children) == {
        "train.data_fetch", "train.step_compute",
        "train.allreduce_wait", "train.ckpt_persist",
    }
    assert children["train.data_fetch"]["dur_s"] == pytest.approx(
        0.004, abs=0.002
    )
    # Phases partition the step wall time.
    assert sum(c["dur_s"] for c in children.values()) == pytest.approx(
        root["dur_s"], rel=0.1, abs=0.002
    )


# ---------------------------------------------------------------------------
# Master: straggler score + /api endpoints + span push
# ---------------------------------------------------------------------------


class _FakeJobManager:
    def get_job_detail(self):
        raise NotImplementedError


def _dash_get(dash, path):
    conn = http.client.HTTPConnection("127.0.0.1", dash.port, timeout=5)
    conn.request("GET", path)
    body = conn.getresponse().read()
    conn.close()
    return json.loads(body)


def test_straggler_score_flags_exactly_the_delayed_rank():
    """Acceptance: a sim-cluster-style job with one artificially slow
    rank — reports flow through the real servicer RPC path — flags
    exactly that rank on /api/stragglers and the gauge."""
    from dlrover_tpu.master.dashboard import DashboardServer
    from dlrover_tpu.observability.registry import default_registry

    perf = PerfMonitor()
    servicer = MasterServicer(rdzv_managers={}, perf_monitor=perf)
    now = time.time()
    delayed_rank = 2
    for report_i in range(4):
        for rank in range(4):
            step_time = 2.5 if rank == delayed_rank else 0.5
            msg = comm.Message(
                node_id=rank,
                data=comm.GlobalStepReport(
                    node_id=rank,
                    step=report_i + 1,
                    timestamp=now + report_i,
                    step_time_s=step_time,
                ).serialize(),
            )
            servicer.report(msg)
    report = perf.straggler_report()
    assert report["stragglers"] == [delayed_rank]
    assert report["ranks"][delayed_rank]["score"] == pytest.approx(
        5.0, rel=0.05
    )
    assert not report["ranks"][0]["flagged"]
    # The per-report gauge path is an O(1) median estimator (§32);
    # force an exact resync to read the precise score.
    perf._update_straggler_gauges()
    gauge = default_registry().get("dlrover_straggler_score")
    assert gauge.value(rank=str(delayed_rank)) == pytest.approx(
        5.0, rel=0.05
    )
    dash = DashboardServer(_FakeJobManager(), perf, port=0)
    dash.start()
    try:
        data = _dash_get(dash, "/api/stragglers")
    finally:
        dash.stop()
    assert data["stragglers"] == [delayed_rank]
    assert data["ranks"][str(delayed_rank)]["flagged"] is True


def test_worker_span_push_reaches_api_traces(tracer):
    """Workers piggyback drained spans on the diagnosis verb; the
    master aggregates and serves trace trees at /api/traces."""
    from dlrover_tpu.agent.master_client import MasterClient
    from dlrover_tpu.master.dashboard import DashboardServer

    agg = TraceAggregator()
    servicer = MasterServicer(rdzv_managers={}, trace_aggregator=agg)
    server = _http_master(servicer)
    client = MasterClient(
        f"localhost:{server.port}", node_id=3, kind="http"
    )
    try:
        with tracing.span("worker.op", step=7):
            pass
        client.report_trace_spans()
    finally:
        client.close()
        server.stop()
    pushed = [
        tid for tid in agg.trace_ids()
        if any(s["name"] == "worker.op" for s in agg.spans(tid))
    ]
    assert len(pushed) == 1
    dash = DashboardServer(_FakeJobManager(), PerfMonitor(), port=0,
                           trace_aggregator=agg)
    dash.start()
    try:
        listing = _dash_get(dash, "/api/traces")
        assert listing["enabled"]
        assert any(
            t["trace_id"] == pushed[0] for t in listing["traces"]
        )
        tree = _dash_get(dash, f"/api/traces/{pushed[0]}")
        names = [n["name"] for n in tree["tree"]]
        assert "worker.op" in names
    finally:
        dash.stop()


# ---------------------------------------------------------------------------
# Hang watchdog + SIGUSR1 on-demand dump
# ---------------------------------------------------------------------------


def _blocked_in_test_frame(release: threading.Event):
    release.wait(30.0)


def test_hang_watchdog_dump_names_the_blocked_frame(tmp_path):
    """Acceptance: the watchdog's stack dump names the frame the
    blocked thread sits in."""
    from dlrover_tpu.observability.hang_watchdog import HangWatchdog

    release = threading.Event()
    blocker = threading.Thread(
        target=_blocked_in_test_frame, args=(release,),
        name="blocked-worker", daemon=True,
    )
    blocker.start()
    fake_now = [100.0]
    dump_file = tmp_path / "hang.json"
    hooks = []
    wd = HangWatchdog(
        name="step",
        dump_path=str(dump_file),
        deadline_factor=4.0,
        min_deadline_s=1.0,
        clock=lambda: fake_now[0],
        on_hang=hooks.append,
    )
    try:
        wd.beat()
        fake_now[0] += 0.5
        wd.beat()                       # EWMA gap ~0.5s, deadline 2s
        assert wd.check() is None       # fresh beat: no hang
        fake_now[0] += 3.0
        path = wd.check()
        assert path == str(dump_file)
        assert wd.check() is None       # fires once per hang episode
        wd.beat()
        fake_now[0] += 3.0
        assert wd.check() is not None   # re-armed by the beat
        dump = json.loads(dump_file.read_text())
        assert dump["kind"] == "stack_dump"
        assert dump["hang_for_s"] >= 2.0
        blocked = [
            label for label, frames in dump["stacks"].items()
            if any("_blocked_in_test_frame" in f for f in frames)
        ]
        assert blocked and "blocked-worker" in blocked[0]
        assert hooks and hooks[0]["name"] == "step"
    finally:
        release.set()
        blocker.join(timeout=5)


def test_sigusr1_dumps_ring_and_stacks_without_dying(tmp_path):
    """Satellite: SIGUSR1 = on-demand diagnostics (ring + all-thread
    stacks) and the process keeps running."""
    from dlrover_tpu.observability.flight_recorder import FlightRecorder

    rec = FlightRecorder(registry=MetricsRegistry())
    rec.record_step(1, step_time_s=0.5)
    rec.record_step(2, step_time_s=0.6)
    rec._dump_target = str(tmp_path / "flight.json")
    # Sibling path: a clean-exit atexit re-dump of the ring must never
    # clobber an operator's on-demand stacks capture.
    dump_file = tmp_path / "flight.ondemand.json"
    assert rec.on_demand_path() == str(dump_file)
    rec.install_on_demand_dump()
    os.kill(os.getpid(), signal.SIGUSR1)
    deadline = time.time() + 5
    while not dump_file.exists() and time.time() < deadline:
        time.sleep(0.01)
    dump = json.loads(dump_file.read_text())
    assert dump["on_demand"] is True
    assert [s["step"] for s in dump["steps"]] == [1, 2]
    assert dump["stacks"]  # every live thread captured
    assert any(
        "MainThread" in label for label in dump["stacks"]
    )
    # Still alive and functional (trivially true if we got here, but
    # record another step to prove the recorder survived too).
    rec.record_step(3)


def test_training_hang_escalation_names_blocked_frame():
    """The master-side diagnostician folds reported stack dumps into
    its hang escalation message."""
    from dlrover_tpu.diagnosis.actions import EventAction
    from dlrover_tpu.diagnosis.diagnosticians.training_hang import (
        TrainingHangDiagnostician,
    )

    class _Perf:
        global_step = 42

        def step_stagnated(self, timeout):
            return True

    dumps = [{
        "kind": "stack_dump",
        "meta": {"node_rank": 3},
        "stacks": {
            "MainThread-1": [
                "train.py:10 main",
                "ops.py:99 psum_wait",
            ],
        },
    }]
    clock = [1000.0]
    diag = TrainingHangDiagnostician(
        _Perf(), hang_timeout_s=10.0, restart_after_s=3600.0,
        clock=lambda: clock[0],
        stack_dump_provider=lambda: dumps,
    )
    ob = diag.observe()
    assert ob.observation == "training-hang"
    clock[0] += 100.0
    action = diag.resolve(ob)
    assert isinstance(action, EventAction)
    assert "psum_wait" in action.event_msg
    assert "rank 3" in action.event_msg


# ---------------------------------------------------------------------------
# /metrics quantile gauges
# ---------------------------------------------------------------------------


def test_metrics_exposition_precomputes_quantiles():
    from dlrover_tpu.diagnosis.collectors import parse_prometheus_text
    from dlrover_tpu.observability import prom

    reg = MetricsRegistry()
    h = reg.histogram(
        "lat_seconds", "latency", buckets=(0.01, 0.1, 1.0, 10.0)
    )
    for _ in range(90):
        h.observe(0.05)
    for _ in range(10):
        h.observe(5.0)
    assert h.quantile(0.5) == pytest.approx(0.06, abs=0.01)
    assert h.quantile(0.99) == pytest.approx(9.1, abs=0.2)
    assert h.quantile(0.5, ) is not None
    labelled = reg.histogram(
        "op_seconds", "ops", labelnames=("kind",), buckets=(1.0, 2.0)
    )
    labelled.observe(0.5, kind="read")
    text = prom.render_registry(reg)
    assert "# TYPE lat_seconds_p50 gauge" in text
    assert "# TYPE lat_seconds_p95 gauge" in text
    assert "# TYPE lat_seconds_p99 gauge" in text
    assert 'op_seconds_p50{kind="read"}' in text
    # Round-trips through the in-repo scraper like every other family.
    parsed = parse_prometheus_text(text)
    assert parsed["lat_seconds_p50"] == pytest.approx(0.06, abs=0.01)
    assert parsed["lat_seconds_p99"] == pytest.approx(9.1, abs=0.2)
    # Empty histograms expose no quantile samples (never a fake zero).
    empty = MetricsRegistry()
    empty.histogram("e_seconds", "empty")
    assert "_p50" not in prom.render_registry(empty)


# ---------------------------------------------------------------------------
# trace_query CLI
# ---------------------------------------------------------------------------


def _tools_on_path():
    import sys

    sys.path.insert(
        0,
        os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools",
        ),
    )


def test_trace_query_summary_and_critical_path(tmp_path, capsys):
    _tools_on_path()
    import trace_query

    sink = tmp_path / "spans.jsonl"
    t = tracing.arm(Tracer(service="cli", sink_path=str(sink)))
    try:
        root = t.start_span("fleet.request", kind="server")
        t.record_span(
            "serving.queue_wait", 10.0, 10.1, parent=root
        )
        slow = t.record_span("serving.decode", 10.1, 12.0, parent=root)
        t.record_span("decode.kernel", 10.2, 11.9, parent=slow)
        root.end(end_mono=root.start_mono + 2.0)
    finally:
        tracing.disarm()
    spans = _not_the_watchers(load_spans([str(sink)]))
    assert len(spans) == 4

    rows = trace_query.summarize(spans)
    assert rows[0]["name"] == "fleet.request"
    by_name = {r["name"]: r for r in rows}
    assert by_name["serving.decode"]["count"] == 1
    assert by_name["serving.decode"]["p95_s"] == pytest.approx(1.9)

    top = trace_query.slowest(spans, top=2)
    assert top[0]["name"] == "fleet.request"

    trace_id = spans[0]["trace_id"]
    path = trace_query.critical_path(spans, trace_id)
    assert [h["name"] for h in path] == [
        "fleet.request", "serving.decode", "decode.kernel",
    ]
    # Self time = own duration minus children's.
    assert path[1]["self_s"] == pytest.approx(1.9 - 1.7, abs=1e-6)

    rc = trace_query.main([
        str(sink), "--summary",
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert "fleet.request" in out
    assert "host.watch" not in out  # in the sink, and --stalls' to read
    rc = trace_query.main([str(sink), "--trace", trace_id])
    assert rc == 0
    out = capsys.readouterr().out
    assert "critical path" in out
