"""Kernels: device time under ``attn/sparse`` in the decode program per
traced decode launch: attention over the listed pages of every (slot, KV
head) of the sparse layers. A program without the scope gives nothing to
read."""

from benchmark import sala_scopes


def read(facts):
    s = sala_scopes.per_launch_s(facts, sala_scopes.STEP, ("sparse",))
    return None if s is None else 1e3 * s
