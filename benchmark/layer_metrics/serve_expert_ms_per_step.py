"""Expert layer: device time under ``mlp/experts`` (the sort of the
step's (token, expert) pairs, the row gathers and the two grouped
matmuls) per traced decode step, all layers."""

from benchmark import sparse_scopes


def read(facts):
    s = sparse_scopes.per_decode_step_s(facts, ("experts",))
    return None if s is None else 1e3 * s
