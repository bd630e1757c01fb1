"""XLA/PJRT-level trace acquisition feeding the native tpu_timer ring.

Parity: reference xpu_timer/nvidia/hook.cc:53-580 (dlsym interception of
CUDA kernel launches + NCCL collectives) + common/manager.h:106-195
(event poller). On TPU there is nothing to dlsym — the runtime's own
profiler (PJRT/libtpu, surfaced as ``jax.profiler``) is the kernel-level
source of truth. This listener periodically (or on agent request via a
trigger file) captures a short device trace, parses the chrome-trace the
runtime emits, and records every device-plane event — named XLA
executables, fusions, collectives — into the native ring: per-kernel
visibility with NO cooperation from the training script beyond runtime
init (``_maybe_start_tpu_timer``), the same contract as LD_PRELOADing
the reference's hook library.

Sub-step hang detection rides the existing native watchdog: each
capture runs inside a native ``xla_capture`` span, and a capture that
stalls — profiler teardown blocks behind a wedged device/collective —
exceeds the hang timeout so the C++ watchdog fires even though Python
never returned from the step.
"""

import glob
import gzip
import json
import os
import re
import shutil
import tempfile
import threading
import time
from typing import Dict, List, Optional, Tuple

from dlrover_tpu.common.constants import NodeEnv
from dlrover_tpu.common.log import logger
from dlrover_tpu.tpu_timer.bridge import SpanKind, get_timer

# Runtime-level host events worth recording even off-TPU (PJRT client,
# XLA modules/thunks); device-plane events are always recorded.
_RUNTIME_NAME_RE = re.compile(
    r"jit_|PjRt|Xla|XLA|thunk|fusion|convolution|dot_general"
    r"|all[-_]?reduce|all[-_]?gather|reduce[-_]?scatter"
    r"|all[-_]?to[-_]?all|collective|ppermute",
    re.IGNORECASE,
)
_COLLECTIVE_RE = re.compile(
    r"all[-_]?reduce|all[-_]?gather|reduce[-_]?scatter"
    r"|all[-_]?to[-_]?all|collective|ppermute",
    re.IGNORECASE,
)


def trigger_path(local_rank: int) -> str:
    """Touch this file to request an immediate capture (the agent-side
    knob; no signal or RPC into the training process needed)."""
    job = os.getenv(NodeEnv.JOB_NAME, "job")
    return os.path.join(
        tempfile.gettempdir(),
        f"dlrover_tpu_timer_{job}_{local_rank}.capture",
    )


def request_xla_capture(local_rank: int = 0):
    with open(trigger_path(local_rank), "w") as f:
        f.write(str(time.time()))


def parse_chrome_trace(path: str) -> List[Tuple[str, bool, float, float]]:
    """(name, is_device_plane, start_us, dur_us) for complete events."""
    with gzip.open(path, "rt") as f:
        data = json.load(f)
    events = data.get("traceEvents", [])
    plane: Dict[int, str] = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            plane[e["pid"]] = e.get("args", {}).get("name", "")
    out = []
    for e in events:
        if e.get("ph") != "X":
            continue
        name = str(e.get("name", ""))
        if name.startswith("$"):
            continue  # python frames: the py_tracing layer covers those
        is_device = plane.get(e.get("pid"), "").startswith("/device:")
        out.append(
            (name, is_device, float(e.get("ts", 0)), float(e.get("dur", 0)))
        )
    return out


def _capture_trace(parse_fn, capture_s: float):
    """Open a trace session for ``capture_s``, close it ON ANY EXIT (a
    leaked active session breaks every later capture in the process),
    and apply ``parse_fn`` to the newest trace file."""
    import jax

    tmpdir = tempfile.mkdtemp(prefix="dlrover_tpu_xla_cap_")
    try:
        jax.profiler.start_trace(tmpdir)
        try:
            time.sleep(capture_s)
        finally:
            jax.profiler.stop_trace()
        traces = sorted(
            glob.glob(
                os.path.join(
                    tmpdir, "plugins", "profile", "*", "*.trace.json.gz"
                )
            )
        )
        if not traces:
            return []
        return parse_fn(traces[-1])
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)


def capture_device_events(
    capture_s: float = 1.0, keep_host_runtime: bool = True
) -> List[Tuple[str, bool, float, float]]:
    """Capture a trace window and return its runtime/device events.

    The profiler samples whatever the process is executing on device
    during the window — this thread only opens/closes the session.
    """

    def parse(path):
        events = parse_chrome_trace(path)
        if keep_host_runtime:
            return [
                ev
                for ev in events
                if ev[1] or _RUNTIME_NAME_RE.search(ev[0])
            ]
        return [ev for ev in events if ev[1]]

    return _capture_trace(parse, capture_s)


def _base_name(name: str) -> str:
    """jit_matmul(12345...) -> jit_matmul — aggregate across executions."""
    return name.split("(", 1)[0].strip()[:120]


def record_events(
    events: List[Tuple[str, bool, float, float]],
    capture_start_ns: int,
    min_dur_us: float = 1.0,
    max_events: int = 4096,
) -> int:
    """Feed captured events into the native ring/histograms. Event
    timestamps are µs relative to the trace session; they are mapped
    onto the native clock via the capture-start anchor."""
    timer = get_timer()
    recorded = 0
    for name, is_device, ts_us, dur_us in events:
        if dur_us < min_dur_us:
            continue
        if recorded >= max_events:
            logger.info(
                "xla capture truncated at %d events (of %d)",
                max_events,
                len(events),
            )
            break
        kind = (
            SpanKind.COLLECTIVE
            if _COLLECTIVE_RE.search(name)
            else SpanKind.CUSTOM
        )
        prefix = "xla/" if is_device else "xla_host/"
        timer.record(
            prefix + _base_name(name),
            kind,
            capture_start_ns + int(ts_us * 1000),
            int(dur_us * 1000),
        )
        recorded += 1
    timer.set_gauge("xla_capture_events", float(recorded))
    return recorded


class XlaCaptureListener:
    """Background acquisition thread living inside the worker process
    (installed by runtime init when DLROVER_TPU_TIMER_XLA=1)."""

    def __init__(
        self,
        local_rank: int = 0,
        interval_s: float = 60.0,
        capture_s: float = 1.0,
    ):
        self._trigger = trigger_path(local_rank)
        self._interval_s = interval_s
        self._capture_s = capture_s
        self._stopped = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.captures = 0

    def start(self):
        self._thread = threading.Thread(
            target=self._loop, name="xla-capture", daemon=True
        )
        self._thread.start()
        # A capture in flight while the interpreter tears down aborts
        # the process from C++ ("FATAL: exception not rethrown" in the
        # profiler session) — drain cleanly at exit.
        import atexit

        atexit.register(self.stop)

    def stop(self):
        self._stopped.set()
        if self._thread is not None:
            self._thread.join(timeout=self._capture_s + 30)

    def capture_once(self):
        timer = get_timer()
        start_ns = timer.now_ns()
        # The native watchdog turns a stalled capture (wedged device)
        # into a hang report even though Python never returns.
        with timer.span("xla_capture", SpanKind.CUSTOM):
            events = capture_device_events(self._capture_s)
        n = record_events(events, start_ns)
        self.captures += 1
        logger.info(
            "xla capture #%d: %d runtime events recorded",
            self.captures,
            n,
        )

    def _loop(self):
        next_auto = time.time() + self._interval_s
        while not self._stopped.is_set():
            triggered = os.path.exists(self._trigger)
            if triggered or time.time() >= next_auto:
                if triggered:
                    try:
                        os.unlink(self._trigger)
                    except OSError:
                        pass
                try:
                    self.capture_once()
                except Exception:
                    logger.warning("xla capture failed", exc_info=True)
                next_auto = time.time() + self._interval_s
            self._stopped.wait(0.5)


_started_listener: Optional[XlaCaptureListener] = None


def maybe_start_listener(local_rank: int = 0) -> Optional[XlaCaptureListener]:
    """Idempotent per process: an instrumented script under the agent's
    sitecustomize injection would otherwise arm TWO listeners (startup +
    runtime init) whose overlapping jax.profiler windows collide."""
    global _started_listener
    from dlrover_tpu.common.env_utils import get_env_bool

    if not get_env_bool("DLROVER_TPU_TIMER_XLA"):
        return None
    if _started_listener is not None:
        return _started_listener
    interval = float(os.getenv("DLROVER_TPU_TIMER_XLA_INTERVAL", "60"))
    window = float(os.getenv("DLROVER_TPU_TIMER_XLA_WINDOW", "1.0"))
    listener = XlaCaptureListener(local_rank, interval, window)
    _started_listener = listener
    listener.start()
    logger.info(
        "xla capture listener on (every %.0fs, %.1fs windows)",
        interval,
        window,
    )
    return listener
