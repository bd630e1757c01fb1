"""ctypes bridge to the native tpu_timer runtime (libtpu_timer.so).

Parity: reference xpu_timer's py side (py_xpu_timer) + the
LD_PRELOAD hook layer (nvidia/hook.cc). On TPU there is no dlsym-able
NCCL: spans are fed explicitly from Python at the natural sync points
(jitted step dispatch, XLA compiles, checkpoint phases, collective
probes), while everything that must survive a wedged Python runtime —
trace ring, aggregation, Prometheus daemon, hang watchdog — is native.

The library is built from its sources on first use and cached next to
them (see ``ensure_native_built``).
"""

import ctypes
import fcntl
import hashlib
import os
import subprocess
import tempfile
import threading
import time
from contextlib import contextmanager
from typing import Optional

from dlrover_tpu.common.constants import NodeEnv
from dlrover_tpu.common.log import logger

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "native",
    "tpu_timer",
)


class SpanKind:
    STEP = 0
    COMPILE = 1
    CHECKPOINT = 2
    COLLECTIVE = 3
    DATA = 4
    CUSTOM = 9


def port_file_path(local_rank: int) -> str:
    """Where a worker publishes its daemon's actually-bound port (the
    launcher-side collector re-reads it before each scrape)."""
    job = os.getenv(NodeEnv.JOB_NAME, "job")
    return os.path.join(
        tempfile.gettempdir(), f"dlrover_tpu_timer_{job}_{local_rank}.port"
    )


def publish_port(local_rank: int, port: int):
    path = port_file_path(local_rank)
    tmp = f"{path}.{os.getpid()}"
    with open(tmp, "w") as f:
        f.write(str(port))
    os.rename(tmp, path)


def _sources_digest() -> str:
    """Hash of everything ``make`` reads in the native dir."""
    h = hashlib.sha256()
    for name in sorted(os.listdir(_NATIVE_DIR)):
        if name == "Makefile" or name.endswith((".cc", ".h")):
            h.update(name.encode())
            with open(os.path.join(_NATIVE_DIR, name), "rb") as f:
                h.update(f.read())
    return h.hexdigest()


def ensure_native_built(target: str, timeout: float = 120.0) -> str:
    """Path of ``native/tpu_timer/<target>``, built with make unless it
    was built from the sources now on disk.

    The binaries are git-ignored and built on first use (one g++
    invocation, no third-party deps). "Up to date" is a digest of the
    sources stamped next to the binary, not an mtime: a copied tree
    keeps prebuilt binaries next to sources that changed since and no
    trustworthy timestamps, and a stale binary silently preferred over
    its sources is the worst outcome.

    Everything is BOUNDED: this also runs on the agent's hang-recovery
    path (native_stack), where an unbounded flock or make would let the
    hang diagnostic hang the recovery itself. A lock held past the
    deadline raises TimeoutError; so does a failed or wedged build
    (CalledProcessError / TimeoutExpired / OSError) when no binary is
    there — one that is there is then loaded with a warning."""
    path = os.path.join(_NATIVE_DIR, target)
    stamp = path + ".srcdigest"
    want = _sources_digest()

    def fresh() -> bool:
        try:
            with open(stamp) as f:
                return os.path.exists(path) and f.read() == want
        except OSError:
            return False

    if fresh():
        return path
    # Serialize builds across worker processes: make writes the binary
    # in place, and a sibling must not load a half-written ELF.
    lock_path = os.path.join(
        tempfile.gettempdir(), "dlrover_tpu_timer_build.lock"
    )
    deadline = time.time() + timeout
    with open(lock_path, "w") as lock:
        while True:
            try:
                fcntl.flock(lock, fcntl.LOCK_EX | fcntl.LOCK_NB)
                break
            except OSError:
                if time.time() > deadline:
                    raise TimeoutError(
                        f"build lock {lock_path} held past {timeout}s"
                    )
                time.sleep(0.2)
        try:
            if not fresh():
                logger.info("building %s from its sources", target)
                try:
                    subprocess.run(
                        ["make", "-B", "-C", _NATIVE_DIR, target],
                        check=True,
                        capture_output=True,
                        timeout=max(deadline - time.time(), 10.0),
                    )
                    with open(stamp, "w") as f:
                        f.write(want)
                except (OSError, subprocess.SubprocessError) as e:
                    # A read-only install, or no compiler: a binary
                    # that is there is used, LOUDLY — it cannot be
                    # shown to match the sources.
                    if not os.path.exists(path):
                        raise
                    logger.warning(
                        "could not rebuild or stamp %s (%s); loading "
                        "the binary found there, which may not match "
                        "its sources", target, e,
                    )
        finally:
            fcntl.flock(lock, fcntl.LOCK_UN)
    return path


def _load_lib() -> ctypes.CDLL:
    lib = ctypes.CDLL(ensure_native_built("libtpu_timer.so"))
    lib.tt_init.argtypes = [ctypes.c_int64]
    lib.tt_start_server.argtypes = [ctypes.c_int]
    lib.tt_start_server.restype = ctypes.c_int
    lib.tt_begin.argtypes = [ctypes.c_char_p, ctypes.c_int]
    lib.tt_begin.restype = ctypes.c_int64
    lib.tt_end.argtypes = [ctypes.c_int64, ctypes.c_double]
    lib.tt_record.argtypes = [
        ctypes.c_char_p,
        ctypes.c_int,
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.c_double,
    ]
    lib.tt_set_gauge.argtypes = [ctypes.c_char_p, ctypes.c_double]
    lib.tt_counter_add.argtypes = [ctypes.c_char_p, ctypes.c_double]
    lib.tt_hang_count.restype = ctypes.c_int
    lib.tt_now_ns.restype = ctypes.c_int64
    lib.tt_dump_timeline.argtypes = [ctypes.c_char_p]
    lib.tt_metrics_text.argtypes = [ctypes.c_char_p, ctypes.c_int]
    lib.tt_metrics_text.restype = ctypes.c_int
    return lib


class TpuTimer:
    """Process-wide profiler handle (native singleton underneath)."""

    _instance: Optional["TpuTimer"] = None
    _lock = threading.Lock()

    def __init__(self, hang_timeout_s: float = 600.0):
        self._lib = _load_lib()
        self._lib.tt_init(int(hang_timeout_s * 1000))
        self.port = 0

    @classmethod
    def get(cls) -> "TpuTimer":
        with cls._lock:
            if cls._instance is None:
                cls._instance = cls()
            return cls._instance

    # ---- daemon -------------------------------------------------------------

    def start_server(self, port: int = 0) -> int:
        """Start the metrics/timeline HTTP daemon; returns the bound port
        (reference xpu_timer daemon :18889)."""
        self.port = self._lib.tt_start_server(port)
        if self.port:
            logger.info("tpu_timer daemon on port %d", self.port)
        return self.port

    # ---- spans --------------------------------------------------------------

    @contextmanager
    def span(self, name: str, kind: int = SpanKind.CUSTOM, flops: float = 0.0):
        sid = self._lib.tt_begin(name.encode(), kind)
        try:
            yield
        finally:
            self._lib.tt_end(sid, flops)

    def record(
        self,
        name: str,
        kind: int,
        start_ns: int,
        dur_ns: int,
        flops: float = 0.0,
    ):
        self._lib.tt_record(name.encode(), kind, start_ns, dur_ns, flops)

    def timed_step(self, step_fn, name: str = "train_step",
                   flops_per_step: float = 0.0):
        """Wrap a jitted step: blocks on the result so the span covers
        device execution (the TPU analogue of CUDA-event timing)."""
        import jax

        def wrapped(*args, **kwargs):
            sid = self._lib.tt_begin(name.encode(), SpanKind.STEP)
            try:
                out = step_fn(*args, **kwargs)
                out = jax.block_until_ready(out)
                return out
            finally:
                self._lib.tt_end(sid, flops_per_step)

        return wrapped

    # ---- metrics ------------------------------------------------------------

    def set_gauge(self, name: str, value: float):
        self._lib.tt_set_gauge(name.encode(), value)

    def counter_add(self, name: str, delta: float = 1.0):
        self._lib.tt_counter_add(name.encode(), delta)

    def hang_count(self) -> int:
        return self._lib.tt_hang_count()

    def now_ns(self) -> int:
        return self._lib.tt_now_ns()

    def metrics_text(self) -> str:
        cap = 1 << 16
        while True:
            buf = ctypes.create_string_buffer(cap)
            n = self._lib.tt_metrics_text(buf, cap)
            if n >= 0:
                return buf.value.decode()
            cap = -n + 1

    def dump_timeline(self, path: str) -> bool:
        return self._lib.tt_dump_timeline(path.encode()) == 0


def get_timer() -> TpuTimer:
    return TpuTimer.get()


def active_timer() -> Optional[TpuTimer]:
    """The timer IF something already initialized it, else None — for
    callers (tracing decorators, GC hooks) that must never trigger the
    first-use native build as a side effect."""
    return TpuTimer._instance
