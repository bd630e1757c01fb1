"""Kernels: device time under ``attn/delta`` and ``state`` in the decode
program (``jit_step``) per traced decode launch: the delta layers' decay,
reads and rank-1 update of every decoding slot's float32 state. A program
without the scope gives nothing to read."""

from benchmark import delta_scopes


def read(facts):
    s = delta_scopes.per_launch_s(
        facts, delta_scopes.STEP, ("delta", "state")
    )
    return None if s is None else 1e3 * s
