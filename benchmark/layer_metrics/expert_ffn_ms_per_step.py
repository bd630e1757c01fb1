"""Expert layer: device time of the ops under ``mlp/experts`` (the
gather of the held experts' rows, the grouped matmuls, the way back to
token order; forward and backward, all expert layers) per traced step."""

from benchmark import hybrid_scopes


def read(facts):
    s = hybrid_scopes.per_step_s(facts, "experts")
    return None if s is None else 1e3 * s
