"""Kernels: device time under ``attn/sparse`` (the gather of the
selected K/V rows and the attention over them) per traced decode step,
all layers."""

from benchmark import sparse_scopes


def read(facts):
    s = sparse_scopes.per_decode_step_s(facts, ("sparse",))
    return None if s is None else 1e3 * s
