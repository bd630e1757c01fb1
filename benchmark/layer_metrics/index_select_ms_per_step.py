"""Kernels: device time under ``attn/index`` (the index projections,
the gather of a slot's index keys, the scores) and ``attn/select`` (the
threshold search and the mask-to-rows compaction) per traced decode
step, all layers."""

from benchmark import sparse_scopes


def read(facts):
    s = sparse_scopes.per_decode_step_s(facts, ("index", "select"))
    return None if s is None else 1e3 * s
