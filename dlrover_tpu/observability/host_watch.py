"""Armed-only host watcher: ``host.pause`` and ``host.gc`` spans.

What a step span cannot say of itself is why it took five times its
neighbours': the one-chip machine stands still for 0.09-0.12 s some
0-6 times a 30 s window (every process at once: PERF.md, PR 48), a
full collection holds the interpreter, a program recompiles. This
module records the first two from inside, with the tracing that is
there (docs/DESIGN.md §29); ``observability/stalls.py`` lays them over
the step spans and names each over-long step's cause.

- ``host.pause``: a thread that waits ``PERIOD_S`` at a time and, when
  a wake-up comes ``MIN_LATE_S`` late or more, records the span from
  where it fell asleep to where it woke, attrs ``late_s``,
  ``process_cpu_s`` (``time.process_time()`` over the same interval: a
  machine that stood still burned none, a thread that held the
  interpreter burned about the interval). Between the two the clock
  cannot tell: the host of the benchmark's v5e ticks ``process_time``
  at 10 ms and charges a standstill to whatever threads were running
  (0.00-0.08 s of ~0.11: PERF.md, PR 53), so ``stalls.pause_cause``
  calls such a pause ``unattributed``.
- ``host.gc``: a ``gc.callbacks`` hook, one span a generation-2
  collection (attrs ``generation``, ``collected``). The hook runs on
  whichever thread allocated, possibly inside ``Tracer._finish`` under
  the Tracer's lock, so it only appends to a deque; the watcher thread
  emits the span at its next wake-up.
- ``host.watch``: one zero-length span when the watcher starts (attrs
  ``period_s``, ``min_late_s``), so that a ring or a sink without a
  pause says "watched, none" and not "not watched".

All three are ``local`` (ring and sink, never exported), and
``tracing.build_trees`` leaves them out (``NAMES``): they belong to no
request's tree. The watcher's
life is its Tracer's: ``tracing.arm()`` starts it, ``disarm()`` /
``Tracer.close()`` / an ``arm()`` of another Tracer end it. A disarmed
process has no thread, no callback and reads no clock. The thread is
``HangWatchdog``'s kind: daemon, never raises, joins on stop.
"""

import gc
import threading
import time
from collections import deque
from typing import Callable, Optional

from dlrover_tpu.common.log import logger

PAUSE = "host.pause"
GC = "host.gc"
WATCH = "host.watch"
NAMES = (PAUSE, GC, WATCH)
# The two numbers the benchmark's own watcher settled on the chip's
# host (runners/serve_conv.py, PR 48): a 5 ms wait wakes within a
# millisecond or two there, and the machine's pauses start at 0.07 s.
PERIOD_S = 0.005
MIN_LATE_S = 0.06


class HostWatch:
    """The watcher of one armed Tracer. ``wait`` (``period_s ->
    stopped?``) and the two clocks are injectable, so a test drives a
    late wake-up without sleeping."""

    def __init__(
        self,
        tracer,
        period_s: float = PERIOD_S,
        min_late_s: float = MIN_LATE_S,
        wait: Optional[Callable[[float], bool]] = None,
        clock: Callable[[], float] = time.monotonic,
        cpu_clock: Callable[[], float] = time.process_time,
    ):
        self._tracer = tracer
        self._period_s = float(period_s)
        self._min_late_s = float(min_late_s)
        self._stop = threading.Event()
        self._wait = wait or self._stop.wait
        self._clock = clock
        self._cpu_clock = cpu_clock
        self._thread: Optional[threading.Thread] = None
        self._gc_start: Optional[float] = None
        self._collections: "deque[tuple]" = deque()

    def start(self):
        if self._thread is not None:
            return
        now = self._clock()
        self._tracer.record_span(
            WATCH, now, now, local=True,
            attrs={"period_s": self._period_s,
                   "min_late_s": self._min_late_s},
        )
        gc.callbacks.append(self._on_gc)
        self._thread = threading.Thread(
            target=self._run, name="host-watch", daemon=True,
        )
        self._thread.start()

    def stop(self):
        """End the thread, take the hook out, emit what it still holds."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
        try:
            gc.callbacks.remove(self._on_gc)
        except ValueError:
            pass
        if not self.alive():  # one that outlived the join still pops the deque
            self._emit_collections()
        self._thread = None

    def alive(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def _on_gc(self, phase, info):
        # On the collecting thread, the interpreter held: no lock, no
        # span, and never an exception.
        if info.get("generation") != 2:
            return
        if phase == "start":
            self._gc_start = self._clock()
        elif self._gc_start is not None:
            self._collections.append(
                (self._gc_start, self._clock(), info.get("collected", 0))
            )
            self._gc_start = None

    def _emit_collections(self):
        while self._collections:
            start, end, collected = self._collections.popleft()
            self._tracer.record_span(
                GC, start, end, local=True,
                attrs={"generation": 2, "collected": collected},
            )

    def _run(self):
        try:
            last, cpu = self._clock(), self._cpu_clock()
            while not self._wait(self._period_s):
                now, cpu_now = self._clock(), self._cpu_clock()
                late = now - last - self._period_s
                if late >= self._min_late_s:
                    self._tracer.record_span(
                        PAUSE, last, now, local=True,
                        attrs={"late_s": late, "process_cpu_s": cpu_now - cpu},
                    )
                self._emit_collections()
                last, cpu = now, cpu_now
        except Exception:  # noqa: BLE001 — a watcher must not end the job
            logger.debug("host watch ended", exc_info=True)
