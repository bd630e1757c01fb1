"""Prefix cache: snapshots the cache gave up to lend their id to a new
prompt (``state_snapshots_given_up`` of the step spans: the least recently
used entry's, its blocks staying) over requests admitted
(``state_restores``), over the window's steps: how hard the byte budget
of the snapshots is pressed. A program without the counter gives nothing
to read."""

from benchmark import delta_scopes, step_spans


def read(facts):
    if not delta_scopes.is_cell(facts):
        return None
    steps = step_spans.steps(facts)
    admitted = sum(s["attrs"].get("state_restores", 0) for s in steps)
    if not admitted:
        return None
    return sum(
        s["attrs"].get("state_snapshots_given_up", 0) for s in steps
    ) / admitted
