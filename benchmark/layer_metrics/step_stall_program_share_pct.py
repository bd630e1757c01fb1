"""Serving engine: ``step_stall_share_pct`` without the steps put down
to the host (``stall_spans.HOST``: the machine, or a pause no signal
attributes): what is the program's to mend (a collection, a compile,
the interpreter held, the device, a host phase, the caller)."""

from benchmark import stall_spans


def read(facts):
    return stall_spans.stall_share_pct(facts, but=stall_spans.HOST)
