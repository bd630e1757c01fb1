"""Host machine: seconds the host stood still inside the timed window,
by the program's own watcher: the summed lengths (fell asleep -> woke)
of the ``host.pause`` spans that end in the window, that no collection
covers and over which the process did not burn about their length in
CPU (``process_cpu_s``: causes ``machine`` and ``unattributed`` of
``stalls.pause_cause``). 0 in a watched window without one; ``None``
from a program that does not watch."""

from benchmark import stall_spans


def read(facts):
    pauses = stall_spans.host_pauses(facts)
    return None if pauses is None else sum(p["dur_s"] for p in pauses)
