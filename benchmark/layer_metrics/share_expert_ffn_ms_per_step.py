"""Expert layer: device time of everything under ``mlp/experts`` and
``mtp/mlp/experts`` (the share's sort, gathers and grouped matmuls,
forward, re-forward and backward, the stack's expert layers and the
prediction module's block) per traced step."""

from benchmark import mtp_scopes


def read(facts):
    s = mtp_scopes.per_step_s(facts, "experts")
    return None if s is None else 1e3 * s
