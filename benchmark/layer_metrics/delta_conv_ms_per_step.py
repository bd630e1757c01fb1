"""Kernels: device time under ``attn/conv`` in the decode program
(``jit_step``) per traced decode launch: the three short convolutions of
every delta layer and the landing of their taps. A program without the
scope gives nothing to read."""

from benchmark import delta_scopes


def read(facts):
    s = delta_scopes.per_launch_s(facts, delta_scopes.STEP, ("conv",))
    return None if s is None else 1e3 * s
