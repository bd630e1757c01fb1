"""Serving engine: ``idle_attributed_pct``'s quantity (of the seconds
the device ran nothing in the traced window, the share that falls inside
a named phase of a ``serving.step`` span or between two steps) for a
program of lightning and block-sparse layers; that reader's list is
pinned by an accepted test (PERF.md section 7), so this one calls its
function."""

from benchmark import sala_scopes
from benchmark.layer_metrics import idle_attributed_pct


def read(facts):
    if not sala_scopes.is_cell(facts):
        return None
    return idle_attributed_pct.read(facts)
