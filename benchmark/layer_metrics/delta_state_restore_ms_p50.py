"""Serving engine: ``state_restore_ms_p50``'s quantity (the host's time
for an admission's state restore: one compiled copy of a snapshot's two
arrays, 13.7 MB, into the slot's state, launched on the device) for a
program of delta-rule and full layers; that reader's list is pinned to
one cell (PERF.md section 7), so this one calls its function."""

from benchmark import delta_scopes
from benchmark.layer_metrics import state_restore_ms_p50


def read(facts):
    if not delta_scopes.is_cell(facts):
        return None
    return state_restore_ms_p50.read(facts)
