"""Kernels: device time of the ops under ``attn/kda/kda_scan`` (the
chunked delta rule, forward and backward, all KDA layers) per traced
step."""

from benchmark import hybrid_scopes


def read(facts):
    s = hybrid_scopes.per_step_s(facts, "kda_scan")
    return None if s is None else 1e3 * s
