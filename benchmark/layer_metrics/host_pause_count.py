"""Host machine: how many times the host stood still inside the timed
window: the ``host.pause`` spans ``host_pause_s`` sums."""

from benchmark import stall_spans


def read(facts):
    pauses = stall_spans.host_pauses(facts)
    return None if pauses is None else len(pauses)
