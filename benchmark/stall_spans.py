"""What the readers of the program's account of its own stalls share.

While a ``Tracer`` is armed the program watches its host
(``dlrover_tpu/observability/host_watch.py``): a ``host.pause`` span for
every wake-up of a 5 ms wait that came 60 ms late or more (attrs
``late_s``, ``process_cpu_s``), a ``host.gc`` span a generation-2
collection, and one zero-length ``host.watch`` span when the watcher
starts. ``dlrover_tpu/observability/stalls.py`` lays them over the
``serving.step`` spans and puts every step whose period exceeds its
kind's median by ``max(0.05 s, median)`` down to a cause: ``machine``,
``gc``, ``interpreter``, ``unattributed`` (a pause whose CPU reading
says neither), ``compile``, ``device_wait``, ``caller`` or
``host:<phase>``. The rule is the program's; what it gives on a fixed
set of spans is pinned by ``tests/benchmark/test_stall_metrics.py``, so
a change to it there fails a benchmark test.

The window is ``step_spans.window``'s: the spans that END inside the
timed seconds. Facts without a ``host.watch`` span come from a program
that does not watch (the parent of the PR that added the watcher), and
every reader here then returns ``None``: a watched window without a
pause reads 0.
"""

from benchmark import step_spans

# The program's names, kept here too: these readers also run over a
# program that has neither module (its parent), and return ``None``.
WATCH = "host.watch"
PAUSE = "host.pause"
GC = "host.gc"
# Not the program's to mend: the machine stood still, or (where the
# host's CPU clock cannot tell: on the v5e's it charges a standstill to
# the threads that were running) a pause that no collection covers and
# that did not burn its length in CPU. Every ``unattributed`` pause met
# in a window so far was the machine's (PERF.md, PR 53).
HOST = ("machine", "unattributed")


def watched(facts):
    return any(s["name"] == WATCH for s in facts.get("spans") or ())


def host_pauses(facts):
    """The window's ``host.pause`` spans whose cause
    (``stalls.pause_cause``) is one of ``HOST``, or ``None``."""
    if not watched(facts):
        return None
    from dlrover_tpu.observability import stalls

    collections = [s for s in facts["spans"] if s["name"] == GC]
    return [
        p for p in step_spans.ending_in_window(facts, PAUSE)
        if stalls.pause_cause(p, collections) in HOST
    ]


def summary(facts):
    """``stalls.summary`` of the window's steps, or ``None``."""
    if not watched(facts):
        return None
    from dlrover_tpu.observability import stalls

    lo, hi = step_spans.window(facts)
    return stalls.summary(facts["spans"], lo=lo, hi=hi)


def stall_share_pct(facts, but=()):
    """The stalled steps' excess seconds, the causes ``but`` left out,
    over the window's seconds."""
    table = summary(facts)
    if table is None or not table["window_s"]:
        return None
    excess = sum(
        s for cause, s in table["excess_s"].items() if cause not in but
    )
    return 100.0 * excess / table["window_s"]
