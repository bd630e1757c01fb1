"""Native tpu_timer tests: build, spans, metrics, daemon, hang watchdog,
timeline dump, and the agent-side Prometheus collector."""

import http.client
import json
import os
import threading
import time

import pytest

from dlrover_tpu.diagnosis.collectors import (
    TpuTimerMetricCollector,
    parse_prometheus_text,
)
from dlrover_tpu.tpu_timer import SpanKind, get_timer


@pytest.fixture(scope="module")
def timer():
    t = get_timer()
    t.start_server(0)
    return t


def test_span_records_metrics(timer):
    with timer.span("unit_span", SpanKind.CUSTOM, flops=2e9):
        time.sleep(0.01)
    text = timer.metrics_text()
    assert 'tpu_timer_span_count{name="unit_span"} 1' in text
    assert 'tpu_timer_tflops{name="unit_span"}' in text
    metrics = parse_prometheus_text(text)
    # ~10ms sleep: avg between 5ms and 500ms
    avg = metrics["tpu_timer_span_avg_us/unit_span"]
    assert 5_000 < avg < 500_000


def test_gauges_and_counters(timer):
    timer.set_gauge("goodput", 95.5)
    timer.counter_add("steps", 3)
    timer.counter_add("steps", 2)
    metrics = parse_prometheus_text(timer.metrics_text())
    assert metrics["tpu_timer_gauge/goodput"] == pytest.approx(95.5)
    assert metrics["tpu_timer_counter/steps"] == pytest.approx(5.0)


def test_http_daemon_serves_metrics(timer):
    conn = http.client.HTTPConnection("127.0.0.1", timer.port, timeout=5)
    conn.request("GET", "/metrics")
    resp = conn.getresponse()
    assert resp.status == 200
    body = resp.read().decode()
    assert "tpu_timer_hang_spans" in body
    conn.close()

    conn = http.client.HTTPConnection("127.0.0.1", timer.port, timeout=5)
    conn.request("GET", "/healthz")
    assert conn.getresponse().status == 200
    conn.close()


def test_hang_watchdog_counts_stuck_spans(timer):
    # Private timer config: spans older than the timeout count as hung.
    timer._lib.tt_init(50)  # 50ms hang timeout
    sid = timer._lib.tt_begin(b"stuck_span", SpanKind.STEP)
    time.sleep(0.15)
    assert timer.hang_count() >= 1
    timer._lib.tt_end(sid, 0.0)
    assert timer.hang_count() == 0
    timer._lib.tt_init(600000)  # restore


def test_timeline_dump_chrome_trace(timer, tmp_path):
    with timer.span("timeline_span"):
        time.sleep(0.001)
    path = str(tmp_path / "timeline.json")
    assert timer.dump_timeline(path)
    with open(path) as f:
        trace = json.load(f)
    names = [e["name"] for e in trace["traceEvents"]]
    assert "timeline_span" in names
    ev = [e for e in trace["traceEvents"] if e["name"] == "timeline_span"][0]
    assert ev["ph"] == "X" and ev["dur"] > 0


def test_timed_step_wrapper(timer):
    import jax.numpy as jnp

    def step(x):
        return x * 2

    wrapped = timer.timed_step(step, name="wrapped_step", flops_per_step=100)
    out = wrapped(jnp.ones(4))
    assert float(out[0]) == 2.0
    metrics = parse_prometheus_text(timer.metrics_text())
    assert metrics["tpu_timer_span_count/wrapped_step"] >= 1


def test_concurrent_spans(timer):
    def worker(i):
        for _ in range(50):
            with timer.span(f"thread_span_{i % 4}"):
                pass

    threads = [
        threading.Thread(target=worker, args=(i,)) for i in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    metrics = parse_prometheus_text(timer.metrics_text())
    total = sum(
        v
        for k, v in metrics.items()
        if k.startswith("tpu_timer_span_count/thread_span_")
    )
    assert total == 400


def test_collector_scrape_and_parse(timer):
    collector = TpuTimerMetricCollector(port=timer.port)
    metrics = collector.scrape()
    assert metrics is not None
    assert "tpu_timer_hang_spans" in metrics


def test_collector_reports_to_client(timer):
    class FakeClient:
        def __init__(self):
            self.reports = []

        def report_diagnosis_data(self, data_type, payload):
            self.reports.append((data_type, payload))

    client = FakeClient()
    collector = TpuTimerMetricCollector(
        master_client=client, node_id=3, port=timer.port
    )
    assert collector.collect_once()
    data_type, payload = client.reports[0]
    assert "metrics" in payload and payload["node_rank"] == 3


def test_span_name_sanitized_for_json(timer, tmp_path):
    # Quotes/backslashes in user-supplied span names must not break the
    # chrome-trace JSON or Prometheus label values.
    with timer.span('restore "ckpt\\shard0"'):
        pass
    path = str(tmp_path / "sanitized.json")
    assert timer.dump_timeline(path)
    with open(path) as f:
        trace = json.load(f)  # must parse
    assert any("restore" in e["name"] for e in trace["traceEvents"])
    parse_prometheus_text(timer.metrics_text())  # must not blow up


def test_gc_tracing_records_spans(timer):
    import gc

    from dlrover_tpu.tpu_timer.py_tracing import trace_gc, untrace_gc

    trace_gc()
    try:
        gc.collect()
    finally:
        untrace_gc()
    metrics = parse_prometheus_text(timer.metrics_text())
    gc_spans = [k for k in metrics if "py_gc_gen" in k]
    assert gc_spans, metrics.keys()


def test_traced_decorator(timer):
    from dlrover_tpu.tpu_timer.py_tracing import traced

    @traced(name="fetch_batch")
    def fetch():
        return 42

    assert fetch() == 42
    metrics = parse_prometheus_text(timer.metrics_text())
    assert metrics["tpu_timer_span_count/fetch_batch"] >= 1


def test_stack_dump_to_file(tmp_path):
    from dlrover_tpu.tpu_timer.py_tracing import dump_stacks

    path = tmp_path / "stacks.txt"
    with open(path, "w") as f:
        dump_stacks(f)
    text = path.read_text()
    assert "test_stack_dump_to_file" in text


def test_sigusr2_dumps_and_does_not_kill(tmp_path):
    import os
    import signal
    import subprocess
    import sys
    import time as _time

    script = tmp_path / "w.py"
    script.write_text(
        "import sys, time\n"
        "from dlrover_tpu.tpu_timer.py_tracing import "
        "install_stack_dump_handler\n"
        "install_stack_dump_handler()\n"
        "print('ready', flush=True)\n"
        "time.sleep(30)\n"
    )
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [sys.executable, str(script)],
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        env=env,
    )
    assert proc.stdout.readline().strip() == b"ready"
    os.kill(proc.pid, signal.SIGUSR2)
    _time.sleep(0.5)
    assert proc.poll() is None  # survived the dump signal
    proc.terminate()
    _, err = proc.communicate(timeout=10)
    assert b"Thread" in err or b"File" in err  # traceback was dumped


def test_native_binary_is_rebuilt_when_its_sources_change(
    tmp_path, monkeypatch
):
    """The binaries are git-ignored: a fresh checkout has none and must
    build them on first use, and a copied tree may hold one built from
    OTHER sources — that one must be rebuilt, whatever its mtime."""
    import shutil

    from dlrover_tpu.tpu_timer import bridge

    native = tmp_path / "tpu_timer"
    native.mkdir()
    for name in os.listdir(bridge._NATIVE_DIR):
        if name == "Makefile" or name.endswith((".cc", ".h")):
            shutil.copy(os.path.join(bridge._NATIVE_DIR, name), native)
    monkeypatch.setattr(bridge, "_NATIVE_DIR", str(native))

    path = bridge.ensure_native_built("stack_sampler")
    assert os.access(path, os.X_OK)  # built from nothing
    built = os.stat(path).st_mtime_ns
    assert bridge.ensure_native_built("stack_sampler") == path
    assert os.stat(path).st_mtime_ns == built  # up to date: untouched

    with open(native / "stack_sampler.cc", "a") as f:
        f.write("\n// changed\n")
    future = time.time() + 3600
    os.utime(path, (future, future))  # a "newer" stale binary
    bridge.ensure_native_built("stack_sampler")
    assert os.stat(path).st_mtime_ns != int(future * 1e9)  # rebuilt


def test_unbuildable_native_binary_loads_if_present_else_raises(
    tmp_path, monkeypatch
):
    """A read-only install or a host without a compiler: a binary that
    is there is used with a warning (it cannot be shown to match its
    sources); with none there, the failure is raised."""
    import subprocess

    from dlrover_tpu.tpu_timer import bridge

    native = tmp_path / "tpu_timer"
    native.mkdir()
    (native / "Makefile").write_text("libx.so:\n\tfalse\n")
    monkeypatch.setattr(bridge, "_NATIVE_DIR", str(native))

    with pytest.raises(subprocess.CalledProcessError):
        bridge.ensure_native_built("libx.so")
    (native / "libx.so").write_bytes(b"prebuilt, unstamped")
    warned = []
    monkeypatch.setattr(
        bridge.logger, "warning", lambda msg, *a: warned.append(msg % a)
    )
    assert bridge.ensure_native_built("libx.so") == str(native / "libx.so")
    assert len(warned) == 1 and "may not match" in warned[0]
