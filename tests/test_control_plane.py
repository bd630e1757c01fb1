"""Control-plane observability & saturation plane (docs/DESIGN.md §32).

Covers: per-verb RPC telemetry (bounded cardinality, exposition round
trip), the overload governor's shed-ordering law through the real
servicer, the O(1) straggler-gauge refactor (straggler_report output
identical), trace-aggregator drop accounting + eviction policy,
dashboard 503-per-panel degradation, /api/control_plane, the
trace_query --verbs table, and the sim load harness (64-worker smoke
fast-lane; the 1k-worker ramp is slow-lane).
"""

import http.client
import json
import threading
import time

import pytest

from dlrover_tpu.common import comm
from dlrover_tpu.master.monitor.perf_monitor import PerfMonitor
from dlrover_tpu.master.overload import (
    CLASS_CRITICAL,
    CLASS_DIAGNOSTIC,
    CLASS_TELEMETRY,
    OverloadGovernor,
    classify,
)
from dlrover_tpu.master.rpc_metrics import MAX_VERB_LABELS
from dlrover_tpu.master.servicer import MasterServicer
from dlrover_tpu.master.shard.task_manager import TaskManager
from dlrover_tpu.observability import tracing
from dlrover_tpu.observability.registry import default_registry

pytestmark = pytest.mark.control_plane


def _servicer(**kwargs) -> MasterServicer:
    return MasterServicer(rdzv_managers={}, **kwargs)


def _report(servicer, request, node_id=0):
    resp = servicer.report(
        comm.Message(node_id=node_id, data=request.serialize())
    )
    return comm.BaseResponse.deserialize(resp.data)


def _get(servicer, request, node_id=0):
    resp = servicer.get(
        comm.Message(node_id=node_id, data=request.serialize())
    )
    return comm.BaseResponse.deserialize(resp.data)


def _new_dataset(servicer, name="d", size=64, shard=16):
    _report(servicer, comm.DatasetShardParams(
        dataset_name=name, dataset_size=size, shard_size=shard,
        task_type="training", storage_type="text", num_epochs=1,
        shuffle=False,
    ))


# ---------------------------------------------------------------------------
# Per-verb telemetry: bounded cardinality + exposition round trip
# ---------------------------------------------------------------------------


def test_per_verb_families_round_trip_and_cardinality_bound():
    """Satellite: high-cardinality abuse collapses into the ``other``
    bucket; the exposition stays under the documented family cap and
    round-trips through parse_prometheus_text."""
    from dlrover_tpu.diagnosis.collectors import parse_prometheus_text
    from dlrover_tpu.observability.prom import master_metrics_text

    perf = PerfMonitor()
    tm = TaskManager(perf_monitor=perf)
    servicer = _servicer(task_manager=tm, perf_monitor=perf)
    _new_dataset(servicer)
    # The registry is process-global across tests: count by delta.
    count_before = servicer.telemetry.seconds.count(
        verb="MultiTaskRequest"
    )
    for _ in range(3):
        _get(servicer, comm.MultiTaskRequest(
            dataset_name="d", node_id=0, count=1))
    # A control-plane type with no registered handler lands in "other"
    # (unknown types can't even unpickle off the wire — the restricted
    # unpickler rejects them before the verb map is consulted).
    servicer.get(comm.Message(node_id=0))  # empty -> BaseRequest
    # Simulated verb flood far past the cap: normalization must never
    # mint labels for names outside the registered handler tables.
    telemetry = servicer.telemetry
    for i in range(4 * MAX_VERB_LABELS):
        assert telemetry.verb(f"MadeUpRequest{i}") == "other"

    parsed = parse_prometheus_text(master_metrics_text())
    verb_counts = {
        k: v for k, v in parsed.items()
        if k.startswith("master_rpc_seconds_count/")
    }
    verbs = {k.split("verb=", 1)[1] for k in verb_counts}
    assert "MultiTaskRequest" in verbs
    assert "other" in verbs
    assert not any(v.startswith("MadeUpRequest") for v in verbs)
    assert len(verbs) <= MAX_VERB_LABELS
    assert verb_counts[
        "master_rpc_seconds_count/verb=MultiTaskRequest"
    ] == count_before + 3.0
    # Precomputed quantiles round-trip too.
    assert any(
        k.startswith("master_rpc_seconds_p99/") for k in parsed
    )
    # Handler split stays three children regardless of verb count.
    phases = [
        k for k in parsed
        if k.startswith("master_rpc_phase_seconds_count/")
    ]
    assert len(phases) == 3


def test_handler_error_counted_with_kind():
    class _Wedged:
        def get_task(self, node_id, dataset_name):
            raise RuntimeError("boom")

    servicer = _servicer(task_manager=_Wedged())
    with pytest.raises(RuntimeError):
        _get(servicer, comm.TaskRequest(dataset_name="d", node_id=0))
    assert servicer.telemetry.errors.value(
        verb="TaskRequest", kind="RuntimeError"
    ) == 1.0
    # The inflight gauge must not leak on the exception path.
    assert servicer.telemetry.inflight_now() == 0


# ---------------------------------------------------------------------------
# Overload governor: classification + hysteresis + ordering law
# ---------------------------------------------------------------------------


def test_classification_defaults_to_critical():
    assert classify("DiagnosisDataReport") == CLASS_DIAGNOSTIC
    assert classify("ResourceStats") == CLASS_DIAGNOSTIC
    assert classify("GlobalStepReport") == CLASS_TELEMETRY
    assert classify("GoodputPhaseReport") == CLASS_TELEMETRY
    # Leases, rendezvous, kv, heartbeats, and anything FUTURE are
    # critical by default — verbs must opt INTO sheddability.
    for verb in ("TaskRequest", "MultiTaskRequest", "TaskDoneReport",
                 "JoinRendezvousRequest", "CommWorldRequest",
                 "HeartbeatReport", "KVStoreSetRequest",
                 "SomeFutureVerb"):
        assert classify(verb) == CLASS_CRITICAL


def test_governor_escalates_and_calms_with_hysteresis():
    clock = [0.0]
    gov = OverloadGovernor(
        latency_high_s=0.1, inflight_high=10, level2_factor=2.0,
        low_frac=0.5, calm_hold_s=2.0, ewma_alpha=1.0,
        clock=lambda: clock[0],
    )
    assert gov.level == 0
    gov.observe(0.15, 1)            # ewma 0.15 > 0.1 -> level 1
    assert gov.level == 1
    assert gov.admit("DiagnosisDataReport") == CLASS_DIAGNOSTIC
    assert gov.admit("GlobalStepReport") is None  # telemetry at L1
    gov.observe(0.25, 1)            # 2.5x watermark -> level 2
    assert gov.level == 2
    assert gov.admit("GlobalStepReport") == CLASS_TELEMETRY
    # Critical never shed, at any level.
    assert gov.admit("MultiTaskRequest") is None
    # Calm must HOLD before de-escalation (one step per hold).
    gov.observe(0.01, 0)
    assert gov.level == 2
    clock[0] += 2.1
    gov.observe(0.01, 0)
    assert gov.level == 1
    # Each step down opens a FRESH calm window: one observe to start
    # it, one past the hold to take the step.
    clock[0] += 2.1
    gov.observe(0.01, 0)
    assert gov.level == 1
    clock[0] += 2.1
    gov.observe(0.01, 0)
    assert gov.level == 0
    state = gov.state()
    assert state["shed_total"][CLASS_DIAGNOSTIC] == 1
    assert state["shed_total"][CLASS_TELEMETRY] == 1


def test_governor_relaxes_when_only_shed_traffic_flows():
    """De-escalation must not require handled traffic: a master whose
    remaining arrivals are ALL being shed (observe() never runs) still
    steps down one level per calm_hold of silence — no latched shed."""
    clock = [0.0]
    gov = OverloadGovernor(
        latency_high_s=0.1, calm_hold_s=2.0, ewma_alpha=1.0,
        clock=lambda: clock[0],
    )
    gov.observe(0.5, 1)  # factor 5x -> straight to level 2
    assert gov.level == 2
    clock[0] += 2.1  # silence: only shed-class arrivals from here on
    assert gov.admit("DiagnosisDataReport") == CLASS_DIAGNOSTIC
    assert gov.level == 1  # one step per hold of silence
    clock[0] += 2.1
    assert gov.admit("DiagnosisDataReport") is None
    assert gov.level == 0


def test_shed_rpcs_excluded_from_latency_family():
    """A shed RPC's microsecond fast-path must not collapse the verb's
    quantiles while its traffic is being dropped; it surfaces via the
    dropped counter (and still appears in the /api summary)."""
    servicer = _servicer(perf_monitor=PerfMonitor())
    servicer.overload_governor.set_thresholds(latency_high_s=1e-9)
    _report(servicer, comm.GlobalStepReport(
        node_id=0, step=1, timestamp=time.time()))
    count_before = servicer.telemetry.seconds.count(
        verb="DiagnosisDataReport"
    )
    _report(servicer, comm.DiagnosisDataReport(
        node_id=0, data_type="trace_spans", payload={"spans": []},
        timestamp=0.0))
    assert servicer.telemetry.seconds.count(
        verb="DiagnosisDataReport") == count_before
    assert servicer.telemetry.dropped.value(
        verb="DiagnosisDataReport") >= 1
    verbs = servicer.telemetry.summary()["verbs"]
    assert verbs["DiagnosisDataReport"]["dropped"] >= 1


def test_shed_law_through_real_servicer():
    """Diagnostics shed, leases flow, counters tick — the §32 law on
    the real dispatch path."""
    perf = PerfMonitor()
    tm = TaskManager(perf_monitor=perf)
    servicer = _servicer(task_manager=tm, perf_monitor=perf)
    _new_dataset(servicer)
    servicer.overload_governor.set_thresholds(latency_high_s=1e-9)
    # Any handled RPC observes a latency -> escalates.
    _report(servicer, comm.GlobalStepReport(
        node_id=0, step=1, timestamp=time.time()))
    assert servicer.overload_governor.level == 2
    diag = _report(servicer, comm.DiagnosisDataReport(
        node_id=0, data_type="trace_spans", payload={"spans": []},
        timestamp=0.0))
    assert diag.success is False and "shed" in diag.reason
    lease = _get(servicer, comm.MultiTaskRequest(
        dataset_name="d", node_id=0, count=2))
    assert [t.task_id for t in lease.tasks] == [0, 1]
    state = servicer.control_plane_state()
    assert state["overload"]["shed_total"]["diagnostic"] >= 1
    assert servicer.telemetry.dropped.value(
        verb="DiagnosisDataReport") >= 1
    assert servicer.telemetry.dropped.value(
        verb="MultiTaskRequest") == 0


# ---------------------------------------------------------------------------
# PerfMonitor: O(1) gauge refresh, straggler_report identical
# ---------------------------------------------------------------------------


def test_straggler_report_identical_and_gauge_o1():
    """Satellite: the incremental gauge path must not change
    straggler_report()'s flags/scores (regression), and the per-report
    gauge must separate the straggler without a full recompute."""
    perf = PerfMonitor()
    now = time.time()
    step_times = {0: 0.5, 1: 0.5, 2: 2.5, 3: 0.5}
    for i in range(8):
        for rank, st in step_times.items():
            perf.collect_global_step(
                i + 1, now + i, node_id=rank, step_time_s=st
            )
    report = perf.straggler_report()
    # Brute-force expectation: EWMAs converge to the constant inputs,
    # median of {0.5, 0.5, 2.5, 0.5} is 0.5, scores are ewma/median.
    assert report["median_step_time_s"] == pytest.approx(0.5)
    assert report["stragglers"] == [2]
    assert report["ranks"][2]["score"] == pytest.approx(5.0, rel=1e-6)
    assert report["ranks"][0]["score"] == pytest.approx(1.0, rel=1e-6)
    assert report["ranks"][2]["flagged"] is True
    assert report["ranks"][0]["flagged"] is False
    # The O(1) per-report gauge path (median ESTIMATOR) must already
    # separate the straggler from the healthy ranks.
    gauge = default_registry().get("dlrover_straggler_score")
    assert gauge.value(rank="2") > 2.0
    assert gauge.value(rank="0") < 1.6
    # Explicit exact resync lands the exact scores.
    perf._update_straggler_gauges()
    assert gauge.value(rank="2") == pytest.approx(5.0, rel=1e-6)
    assert gauge.value(rank="0") == pytest.approx(1.0, rel=1e-6)


def test_straggler_amortized_resync_keeps_gauge_exactish():
    """Past ~R reports the amortized exact resync must re-anchor the
    estimator: long-run gauge drift is bounded without any caller ever
    invoking the exact path."""
    perf = PerfMonitor()
    now = time.time()
    for i in range(40):  # > the 32-report resync floor
        for rank in range(4):
            st = 1.2 if rank == 1 else 0.4
            perf.collect_global_step(
                i + 1, now + i, node_id=rank, step_time_s=st
            )
    gauge = default_registry().get("dlrover_straggler_score")
    assert gauge.value(rank="1") == pytest.approx(3.0, rel=0.15)
    assert gauge.value(rank="0") == pytest.approx(1.0, rel=0.15)


def test_perf_buffer_stats():
    perf = PerfMonitor(max_phase_records=4)
    for i in range(6):
        perf.collect_phase(0, "train", float(i), float(i) + 0.5)
    stats = perf.buffer_stats()
    assert stats["occupancy"] == 4
    assert stats["capacity"] == 4
    assert stats["drops"] == 2


# ---------------------------------------------------------------------------
# TraceAggregator: drop accounting + eviction policy
# ---------------------------------------------------------------------------


def _span(trace_id, span_id="s0"):
    return {"trace_id": trace_id, "span_id": span_id, "name": "op",
            "mono": 0.0}


def test_trace_aggregator_eviction_preserves_newest_and_counts():
    agg = tracing.TraceAggregator(max_traces=4, max_spans_per_trace=2)
    before = default_registry().counter(
        "trace_ingest_dropped_total", labelnames=("reason",)
    )
    evicted_before = before.value(reason="trace_cap")
    span_before = before.value(reason="span_cap")
    for i in range(10):
        agg.ingest([_span(f"t{i}")])
    # Oldest-trace eviction preserves exactly the newest N.
    assert agg.trace_ids() == [f"t{i}" for i in range(6, 10)]
    stats = agg.stats()
    assert stats["dropped"]["trace_cap"] == 6
    assert before.value(reason="trace_cap") - evicted_before == 6
    # Span-cap overflow inside one trace is counted, not silent.
    agg.ingest([_span("t9", f"s{j}") for j in range(5)])
    stats = agg.stats()
    assert stats["dropped"]["span_cap"] == 4  # 1 existing + 2 fit
    assert before.value(reason="span_cap") - span_before == 4
    assert stats["occupancy"] == stats["spans"]
    assert "drops" in stats


def test_api_traces_summary_exposes_drop_totals():
    from dlrover_tpu.master.dashboard import DashboardServer

    agg = tracing.TraceAggregator(max_traces=2)
    for i in range(5):
        agg.ingest([_span(f"t{i}")])
    dash = DashboardServer(None, PerfMonitor(), port=0,
                           trace_aggregator=agg)
    dash.start()
    try:
        data = _http_json(dash.port, "/api/traces")
    finally:
        dash.stop()
    assert data["stats"]["dropped"]["trace_cap"] == 3
    assert data["stats"]["occupancy"] == 2


# ---------------------------------------------------------------------------
# Dashboard: per-panel 503 degradation + /api/control_plane
# ---------------------------------------------------------------------------


def _http_raw(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=5)
    conn.request("GET", path)
    resp = conn.getresponse()
    body = resp.read()
    conn.close()
    return resp.status, body


def _http_json(port, path):
    status, body = _http_raw(port, path)
    assert status == 200, body
    return json.loads(body)


class _WedgedPerf(PerfMonitor):
    def straggler_report(self, *a, **k):
        raise RuntimeError("perf subsystem wedged")


def test_dashboard_503_per_panel_not_whole_page():
    """Satellite: a raising provider answers ITS endpoint with a 503 +
    JSON error body; every other panel keeps serving."""
    from dlrover_tpu.master.dashboard import DashboardServer

    servicer = _servicer(perf_monitor=PerfMonitor())
    dash = DashboardServer(
        None, _WedgedPerf(), port=0, rdzv_managers={},
        control_plane=servicer.control_plane_state,
    )
    dash.start()
    try:
        status, body = _http_raw(dash.port, "/api/stragglers")
        assert status == 503
        err = json.loads(body)
        assert err["unavailable"] is True
        assert "perf subsystem wedged" in err["error"]
        # The wedged panel did not take down its neighbors.
        assert _http_json(dash.port, "/api/rdzv") == []
        cp = _http_json(dash.port, "/api/control_plane")
        assert cp["enabled"] is True
        assert cp["overload"]["level"] == 0
        assert "rpc" in cp and "buffers" in cp
    finally:
        dash.stop()


def test_control_plane_endpoint_reports_buffers():
    from dlrover_tpu.master.dashboard import DashboardServer

    perf = PerfMonitor()
    tm = TaskManager(perf_monitor=perf)
    agg = tracing.TraceAggregator()
    servicer = _servicer(
        task_manager=tm, perf_monitor=perf, trace_aggregator=agg
    )
    _new_dataset(servicer)
    _get(servicer, comm.MultiTaskRequest(
        dataset_name="d", node_id=0, count=1))
    dash = DashboardServer(
        None, perf, port=0,
        control_plane=servicer.control_plane_state,
    )
    dash.start()
    try:
        cp = _http_json(dash.port, "/api/control_plane")
    finally:
        dash.stop()
    for name, stats in cp["buffers"].items():
        assert "occupancy" in stats and "drops" in stats, name
    assert "MultiTaskRequest" in cp["rpc"]["verbs"]
    assert cp["rpc"]["verbs"]["MultiTaskRequest"]["p99_s"] is not None


# ---------------------------------------------------------------------------
# Queue-age / wait-depth self-instrumentation
# ---------------------------------------------------------------------------


def test_dispatch_latency_and_queue_age_observed():
    perf = PerfMonitor()
    tm = TaskManager(perf_monitor=perf)
    servicer = _servicer(task_manager=tm, perf_monitor=perf)
    _new_dataset(servicer)
    reg = default_registry()
    # Deltas: the registry is process-global across tests.
    dispatch_before = reg.get("shard_dispatch_seconds").count()
    age_before = reg.get("shard_task_queue_age_seconds").count()
    _get(servicer, comm.MultiTaskRequest(
        dataset_name="d", node_id=0, count=2))
    assert reg.get("shard_dispatch_seconds").count() - dispatch_before == 1
    assert (
        reg.get("shard_task_queue_age_seconds").count() - age_before == 2
    )
    assert reg.get("shard_todo_depth").value() == 2  # 4 shards - 2
    assert reg.get("shard_doing_depth").value() == 2
    stats = tm.queue_stats()
    assert stats["occupancy"] == 4
    assert stats["drops"] == 0
    assert stats["dispatch_p99_s"] is not None


def test_kv_and_sync_wait_depth_gauges():
    from dlrover_tpu.master.elastic_training.kv_store import (
        KVStoreService,
    )

    kv = KVStoreService()
    gauge = default_registry().get("kv_wait_depth")
    base = gauge.value()
    entered = threading.Event()

    def waiter():
        entered.set()
        kv.wait(["k"], timeout=10.0)

    t = threading.Thread(target=waiter, daemon=True)
    t.start()
    entered.wait(2.0)
    deadline = time.time() + 2.0
    while gauge.value() <= base and time.time() < deadline:
        time.sleep(0.005)
    assert gauge.value() == base + 1
    kv.set("k", b"v")
    t.join(timeout=5.0)
    assert gauge.value() == base
    assert kv.size() == 1


# ---------------------------------------------------------------------------
# trace_query --verbs
# ---------------------------------------------------------------------------


def test_trace_query_verbs_mode(tmp_path):
    import sys

    sys.path.insert(0, "tools")
    import trace_query

    spans = [
        {"trace_id": "t", "span_id": "a", "name": "master.TaskRequest",
         "kind": "server", "dur_s": 0.002},
        {"trace_id": "t", "span_id": "b", "name": "master.TaskRequest",
         "kind": "server", "dur_s": 0.004},
        {"trace_id": "t", "span_id": "c",
         "name": "master.KVStoreSetRequest", "kind": "server",
         "dur_s": 0.001},
        # Non-server / non-master spans must not appear in the table.
        {"trace_id": "t", "span_id": "d", "name": "rpc.get_task",
         "kind": "client", "dur_s": 0.5},
        {"trace_id": "t", "span_id": "e", "name": "master.TaskRequest",
         "kind": "internal", "dur_s": 0.5},
    ]
    path = tmp_path / "spans.jsonl"
    path.write_text("".join(json.dumps(s) + "\n" for s in spans))
    rows = trace_query.verb_summary(trace_query.load_spans([str(path)]))
    table = {r["name"]: r for r in rows}
    assert set(table) == {"TaskRequest", "KVStoreSetRequest"}
    assert table["TaskRequest"]["count"] == 2
    assert table["TaskRequest"]["mean_s"] == pytest.approx(0.003)


# ---------------------------------------------------------------------------
# The sim load harness
# ---------------------------------------------------------------------------


def _smoke_cfg(**overrides):
    from dlrover_tpu.testing.control_plane_soak import (
        ControlPlaneSoakConfig,
    )

    base = dict(
        workers=64, driver_threads=4, stage_duration_s=0.4,
        max_stages=2, quorum_worlds=(8, 64), shed_duration_s=0.4,
    )
    base.update(overrides)
    return ControlPlaneSoakConfig(**base)


def test_control_plane_soak_smoke_64_workers():
    """Fast lane: the full harness — ramp, quorum at {8, 64}, shed —
    with all three invariants, in seconds."""
    from dlrover_tpu.testing.control_plane_soak import (
        run_control_plane_soak,
    )

    rep = run_control_plane_soak(_smoke_cfg())
    assert rep["invariants"] == "pass"
    assert rep["max_sustainable_rps"] > 0
    assert rep["cpu_s_per_1k_rpcs"] > 0
    assert rep["quorum"]["8"]["time_to_quorum_s"] > 0
    assert rep["quorum"]["64"]["time_to_quorum_s"] > 0
    assert rep["shed"]["shed_diagnostic"] > 0
    assert rep["shed"]["lease_rpcs_during_shed"] > 0
    assert rep["shed"]["client_errors"] == 0
    for stats in rep["buffers"].values():
        assert "occupancy" in stats and "drops" in stats
    agree = rep["metric_span_agreement"]
    assert agree["verbs_checked"] >= 1
    assert agree["worst_rel_diff"] <= 0.15


@pytest.mark.slow
def test_control_plane_soak_1k_worker_ramp():
    """Slow lane: 1024 sim workers, quorum swept to world 1024, and the
    per-verb metric and span means within 15 % of each other."""
    from dlrover_tpu.testing.control_plane_soak import (
        run_control_plane_soak,
    )

    rep = run_control_plane_soak(_smoke_cfg(
        workers=1024, driver_threads=16, stage_duration_s=1.0,
        max_stages=5, quorum_worlds=(8, 64, 256, 1024),
        shed_duration_s=0.8,
    ))
    assert rep["invariants"] == "pass"
    agree = rep["metric_span_agreement"]
    assert agree["verbs_checked"] >= 1
    assert agree["worst_rel_diff"] <= 0.15
    assert rep["quorum"]["1024"]["time_to_quorum_s"] > 0
    # Quorum time grows with world size but stays bounded: the full
    # 1024-rank world must form well inside the join timeout.
    assert (
        rep["quorum"]["1024"]["time_to_quorum_s"]
        > rep["quorum"]["8"]["time_to_quorum_s"]
    )
    assert rep["quorum"]["1024"]["time_to_quorum_s"] < 30.0
