"""Kernels: device time under ``attn/full`` in the decode program
(``jit_step``) per traced decode launch: the full layers' paged attention
over every decoding slot's K and V rows (30 un-grouped heads). A program
without the scope gives nothing to read."""

from benchmark import delta_scopes


def read(facts):
    s = delta_scopes.per_launch_s(facts, delta_scopes.STEP, ("full",))
    return None if s is None else 1e3 * s
