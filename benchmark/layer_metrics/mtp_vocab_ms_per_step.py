"""Train step: device time under ``mtp/vocab`` (the prediction module's
norm, the second pass through the model's head and the second
cross-entropy, forward and backward) per traced step."""

from benchmark import mtp_scopes


def read(facts):
    s = mtp_scopes.per_step_s(facts, "vocab", stack=False)
    return None if s is None else 1e3 * s
