"""XLA/PJRT-level trace acquisition tests (tpu_timer/xla_capture.py):
chrome-trace parsing, live capture of runtime events on the CPU
backend, the agent trigger file, and the hang-watchdog coupling.

Mirrors the role of reference xpu_timer's hook-layer tests: kernels
must appear in the timeline with NO Python span feeding them.
"""

import gzip
import json
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from dlrover_tpu.tpu_timer import get_timer
from dlrover_tpu.tpu_timer.xla_capture import (
    XlaCaptureListener,
    capture_device_events,
    parse_chrome_trace,
    record_events,
    request_xla_capture,
)


def test_parse_chrome_trace(tmp_path):
    trace = {
        "traceEvents": [
            {"ph": "M", "pid": 3, "name": "process_name",
             "args": {"name": "/device:TPU:0"}},
            {"ph": "M", "pid": 7, "name": "process_name",
             "args": {"name": "/host:CPU"}},
            {"ph": "X", "pid": 3, "name": "jit_matmul(123)",
             "ts": 10.0, "dur": 5.5},
            {"ph": "X", "pid": 3, "name": "all-reduce.1",
             "ts": 20.0, "dur": 2.0},
            {"ph": "X", "pid": 7, "name": "$frame.py:1 f",
             "ts": 0.0, "dur": 1.0},
            {"ph": "X", "pid": 7, "name": "PjRtCpuClient::Compile",
             "ts": 1.0, "dur": 3.0},
        ]
    }
    path = tmp_path / "t.trace.json.gz"
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)
    events = parse_chrome_trace(str(path))
    names = {e[0] for e in events}
    assert "jit_matmul(123)" in names
    assert "all-reduce.1" in names
    assert "PjRtCpuClient::Compile" in names
    assert all(not n.startswith("$") for n in names)  # python frames out
    by_name = {e[0]: e for e in events}
    assert by_name["jit_matmul(123)"][1] is True  # device plane
    assert by_name["PjRtCpuClient::Compile"][1] is False


def _churn(stop):
    x = jnp.ones((128, 128))
    while not stop.is_set():
        x = jnp.tanh(x @ x / 100.0)
        float(jnp.sum(x))


def test_capture_records_runtime_events_without_python_spans():
    """A live capture during jit churn lands named runtime events in
    the native timeline — none of them fed by a Python span."""
    timer = get_timer()
    stop = threading.Event()
    t = threading.Thread(target=_churn, args=(stop,), daemon=True)
    t.start()
    try:
        start_ns = timer.now_ns()
        events = capture_device_events(capture_s=1.0)
        assert events, "no runtime events captured"
        n = record_events(events, start_ns)
        assert n > 0
    finally:
        stop.set()
        t.join(timeout=10)


def test_trigger_file_drives_capture(tmp_path, monkeypatch):
    monkeypatch.setenv("DLROVER_TPU_JOB_NAME", "xlacap")
    listener = XlaCaptureListener(
        local_rank=0, interval_s=3600.0, capture_s=0.2
    )
    stop = threading.Event()
    t = threading.Thread(target=_churn, args=(stop,), daemon=True)
    t.start()
    listener.start()
    try:
        request_xla_capture(0)
        # A cap, not a budget: the loop ends at the first capture, and a
        # profiler session on a host whose every core is busy has taken
        # more than 30 s (PRs 40, 45).
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline and listener.captures == 0:
            time.sleep(0.1)
        assert listener.captures >= 1
    finally:
        stop.set()
        listener.stop()
        t.join(timeout=10)


def test_stalled_capture_trips_native_watchdog(monkeypatch):
    """A capture wedged behind a stuck device trips the C++ hang
    watchdog even though Python never returns from the step."""
    import dlrover_tpu.tpu_timer.xla_capture as xc

    timer = get_timer()
    timer._lib.tt_init(50)  # 50ms hang timeout
    try:
        listener = XlaCaptureListener(local_rank=0, capture_s=0.01)

        def stuck(*a, **k):
            time.sleep(0.3)  # well past the watchdog timeout
            return []

        monkeypatch.setattr(xc, "capture_device_events", stuck)
        done = threading.Event()

        def run():
            listener.capture_once()
            done.set()

        threading.Thread(target=run, daemon=True).start()
        deadline = time.time() + 5
        tripped = False
        while time.time() < deadline:
            if timer.hang_count() >= 1:
                tripped = True
                break
            time.sleep(0.02)
        assert tripped, "watchdog did not flag the stalled capture"
        done.wait(5)
    finally:
        timer._lib.tt_init(600_000)  # restore default
