"""Prefix cache: state snapshots written (``state_snapshots`` of the step
spans: a prompt's chunk that holds its last whole-block boundary writes
one) over requests admitted (``state_restores``: every admission of a
model with per-slot state restores or zeroes its slot), over the
window's steps. A program without the counters gives nothing to read."""

from benchmark import step_spans


def read(facts):
    steps = step_spans.steps(facts)
    admitted = sum(s["attrs"].get("state_restores", 0) for s in steps)
    if not admitted:
        return None
    return sum(s["attrs"].get("state_snapshots", 0) for s in steps) / admitted
