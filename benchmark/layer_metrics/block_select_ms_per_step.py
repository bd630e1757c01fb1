"""Kernels: device time under ``attn/select`` in the decode program per
traced decode launch: the new compressed key, the slot's compressed keys
through its table, the heads' softmax, the group's sum, the max over a
block's keys, the forced blocks, the top 64 and the list's pages. A
program without the scope gives nothing to read."""

from benchmark import sala_scopes


def read(facts):
    s = sala_scopes.per_launch_s(facts, sala_scopes.STEP, ("select",))
    return None if s is None else 1e3 * s
