"""Serving engine: ``decode_unscoped_ms_per_step``'s quantity (device-op
time of the decode program under NONE of its scopes per traced decode
launch: what the step does besides the model's arithmetic, such as
re-laying a pool or a state array) for a program of lightning and
block-sparse layers, off ``sala_scopes.reduce``'s table; that reader's
list is pinned to one cell (PERF.md section 7), so this one calls its
function."""

from benchmark import sala_scopes, sparse_scopes


def read(facts):
    if not sala_scopes.is_cell(facts):
        return None
    s = sparse_scopes.per_decode_step_s(facts, ("unscoped",))
    return None if s is None else 1e3 * s
