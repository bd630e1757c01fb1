"""The armed Tracer's host watcher (``observability/host_watch.py``):
``host.pause`` / ``host.gc`` / ``host.watch`` spans, and a life that is
its Tracer's. The watcher's wait and both its clocks are scripted, so no
case sleeps for a pause."""

import gc
import threading

import pytest

from dlrover_tpu.observability import host_watch, stalls, tracing
from dlrover_tpu.observability.tracing import Tracer

PERIOD = host_watch.PERIOD_S


class Script:
    """A scripted ``wait`` and the two clocks it moves: each entry of
    ``wakes`` is (seconds the wait took, CPU seconds burned meanwhile);
    past the script the wait says "stopped"."""

    def __init__(self, wakes):
        self.wakes = list(wakes)
        self.now, self.cpu = 100.0, 5.0

    def wait(self, _period_s):
        if not self.wakes:
            return True
        slept, burned = self.wakes.pop(0)
        self.now += slept
        self.cpu += burned
        return False

    def clock(self):
        return self.now

    def cpu_clock(self):
        return self.cpu


def run_script(wakes):
    """The spans a watcher records over the scripted wake-ups."""
    tracer, script = Tracer(service="test"), Script(wakes)
    watch = host_watch.HostWatch(
        tracer, wait=script.wait, clock=script.clock,
        cpu_clock=script.cpu_clock,
    )
    watch.start()
    watch._thread.join(timeout=5.0)
    assert not watch.alive()
    watch.stop()
    return tracer.finished()


def named(spans, name):
    return [s for s in spans if s["name"] == name]


def watchers():
    return [t for t in threading.enumerate() if t.name == "host-watch"]


@pytest.fixture(autouse=True)
def disarmed():
    tracing.disarm()
    yield
    tracing.disarm()


def test_a_late_wake_up_is_a_pause_with_its_lateness():
    spans = run_script([(PERIOD, 0.0), (PERIOD + 0.11, 0.001),
                        (PERIOD, 0.0)])
    (pause,) = named(spans, "host.pause")
    assert pause["dur_s"] == pytest.approx(PERIOD + 0.11)
    assert pause["mono"] == pytest.approx(100.0 + PERIOD)
    assert pause["attrs"]["late_s"] == pytest.approx(0.11)
    assert pause["attrs"]["process_cpu_s"] == pytest.approx(0.001)


def test_a_quiet_stretch_records_no_pause():
    # 59 ms late is under the threshold, 1 ms is an ordinary wake-up.
    spans = run_script([(PERIOD + 0.001, 0.0)] * 20
                       + [(PERIOD + 0.059, 0.0)])
    assert named(spans, "host.pause") == []


def test_host_watch_is_recorded_once_at_the_start():
    spans = run_script([(PERIOD, 0.0), (PERIOD + 0.2, 0.0)])
    assert spans[0]["name"] == "host.watch"
    (watch,) = named(spans, "host.watch")
    assert watch["dur_s"] == 0.0
    assert watch["attrs"] == {
        "period_s": host_watch.PERIOD_S,
        "min_late_s": host_watch.MIN_LATE_S,
    }


@pytest.mark.parametrize("burned, cause", [
    (0.0, "machine"), (0.04, "machine"),
    # Neither about none nor about the interval: the v5e's host charges
    # a standstill 0.06-0.08 s of ~0.11 one time in four.
    (0.06, "unattributed"), (0.08, "unattributed"),
    (0.09, "interpreter"), (0.105, "interpreter"), (0.2, "interpreter"),
])
def test_process_cpu_tells_the_machine_from_the_interpreter(burned, cause):
    (pause,) = named(
        run_script([(PERIOD + 0.1, burned)]), "host.pause"
    )
    assert stalls.pause_cause(pause) == cause


def test_the_real_clocks_raise_nothing():
    tracer = tracing.arm(Tracer(service="test"))
    tracing.disarm()
    (watch,) = named(tracer.finished(), "host.watch")
    assert set(watch["attrs"]) == {"period_s", "min_late_s"}


def test_stop_leaves_the_collections_to_a_thread_that_outlives_the_join():
    """``stop()`` joins for two seconds at most; a thread still inside
    its round then owns the deque, and ``stop()`` pops nothing beside
    it."""
    tracer = Tracer(service="test")
    watch = host_watch.HostWatch(tracer)

    class Stuck:
        def join(self, timeout=None):
            pass

        def is_alive(self):
            return True

    watch._thread = Stuck()
    watch._collections.append((1.0, 1.1, 0))
    watch.stop()
    assert named(tracer.finished(), "host.gc") == []
    assert len(watch._collections) == 1


def test_the_watchers_spans_are_local():
    tracer = Tracer(service="test")
    script = Script([(PERIOD + 0.3, 0.0)])
    watch = host_watch.HostWatch(
        tracer, wait=script.wait, clock=script.clock,
        cpu_clock=script.cpu_clock,
    )
    watch.start()
    watch._thread.join(timeout=5.0)
    watch.stop()
    assert {s["name"] for s in tracer.finished()} == {
        "host.watch", "host.pause",
    }
    assert tracer.drain_exports() == []


def test_a_full_collection_is_a_span_and_a_young_one_is_not():
    gc.disable()  # no collection but the three asked for below
    try:
        tracer = tracing.arm(Tracer(service="test"))
        gc.collect(0)
        gc.collect(1)
        assert named(tracer.finished(), "host.gc") == []
        gc.collect()
        # What the thread had not emitted yet, its stop does.
        tracing.disarm()
    finally:
        gc.enable()
    found = named(tracer.finished(), "host.gc")
    assert len(found) == 1
    assert found[0]["attrs"]["generation"] == 2
    assert found[0]["attrs"]["collected"] >= 0
    assert found[0]["dur_s"] >= 0.0


def test_disarmed_there_is_no_thread_and_no_callback():
    threads, callbacks = len(watchers()), len(gc.callbacks)
    assert threads == 0
    tracing.span("anything").end()
    assert tracing.record_span("x", 0.0, 1.0) is None
    assert len(watchers()) == 0 and len(gc.callbacks) == callbacks


def test_disarm_ends_the_thread_and_removes_the_callback():
    threads, callbacks = threading.active_count(), len(gc.callbacks)
    tracer = tracing.arm(Tracer(service="test"))
    assert len(watchers()) == 1
    assert threading.active_count() == threads + 1
    assert len(gc.callbacks) == callbacks + 1
    tracing.disarm()
    assert watchers() == []
    assert threading.active_count() == threads
    assert len(gc.callbacks) == callbacks
    assert len(named(tracer.finished(), "host.watch")) == 1


def test_close_ends_the_watcher_of_its_tracer():
    callbacks = len(gc.callbacks)
    tracer = tracing.arm(Tracer(service="test"))
    tracer.close()
    assert watchers() == [] and len(gc.callbacks) == callbacks


def test_arming_over_an_armed_tracer_ends_the_first_ones_watcher():
    callbacks = len(gc.callbacks)
    first = tracing.arm(Tracer(service="first"))
    (thread,) = watchers()
    second = tracing.arm(Tracer(service="second"))
    assert not thread.is_alive()
    assert len(watchers()) == 1 and len(gc.callbacks) == callbacks + 1
    assert first._host_watch is None and second._host_watch.alive()
    # The first is armed again, as the soaks re-arm the Tracer they
    # found: it watches again, and says so.
    tracing.arm(first)
    assert len(watchers()) == 1 and second._host_watch is None
    assert len(named(first.finished(), "host.watch")) == 2


def test_arming_the_armed_tracer_again_starts_no_second_watcher():
    tracer = tracing.arm(Tracer(service="test"))
    tracing.arm(tracer)
    assert len(watchers()) == 1
    assert len(named(tracer.finished(), "host.watch")) == 1


def test_arm_from_env_starts_the_watcher(tmp_path, monkeypatch):
    sink = tmp_path / "spans.jsonl"
    monkeypatch.setenv(tracing.TRACE_FILE_ENV, str(sink))
    assert tracing.arm_from_env(service="worker") is not None
    assert len(watchers()) == 1
    tracing.disarm()
    names = [r["name"] for r in tracing.load_spans([str(sink)])]
    assert names[0] == "host.watch" and names.count("host.watch") == 1


def test_a_collection_inside_the_tracers_lock_cannot_deadlock():
    """The hook runs on whichever thread allocated; under the Tracer's
    (non-reentrant) lock it must not record."""
    tracer = tracing.arm(Tracer(service="test"))
    done = []

    def collect_under_the_lock():
        with tracer._lock:
            gc.collect()
        done.append(True)

    worker = threading.Thread(target=collect_under_the_lock)
    worker.start()
    worker.join(timeout=5.0)
    assert done == [True] and not worker.is_alive()
    tracing.disarm()
    assert len(named(tracer.finished(), "host.gc")) >= 1
