"""Kernels: device time under ``attn/select`` and ``attn/sparse`` in the
prefill program per traced chunk: the sparse layers' scoring, selection
and attention under the block mask. A program without the scopes gives
nothing to read."""

from benchmark import sala_scopes


def read(facts):
    s = sala_scopes.per_launch_s(
        facts, sala_scopes.PREFILL, ("sparse", "select")
    )
    return None if s is None else 1e3 * s
