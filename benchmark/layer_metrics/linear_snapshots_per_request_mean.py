"""Prefix cache: ``state_snapshots_per_request_mean``'s quantity (state
snapshots written over requests admitted, over the window's steps) for a
program of lightning and block-sparse layers; that reader's list is
pinned to one cell (PERF.md section 7), so this one calls its
function."""

from benchmark import sala_scopes
from benchmark.layer_metrics import state_snapshots_per_request_mean


def read(facts):
    if not sala_scopes.is_cell(facts):
        return None
    return state_snapshots_per_request_mean.read(facts)
