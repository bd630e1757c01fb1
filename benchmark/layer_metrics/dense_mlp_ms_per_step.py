"""Serving engine: device time under ``mlp`` in the decode program per
traced decode launch, for a program of lightning and block-sparse layers
(a dense SwiGLU of 16,384 in every layer: most of the weights a step
reads)."""

from benchmark import sala_scopes


def read(facts):
    if not sala_scopes.is_cell(facts):
        return None
    s = sala_scopes.per_launch_s(facts, sala_scopes.STEP, ("mlp",))
    return None if s is None else 1e3 * s
