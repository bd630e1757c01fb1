"""Kernels: device time under ``attn/lightning`` and ``state`` in the
decode program (``jit_step``) per traced decode launch: the lightning
layers' rank-1 update and read of every decoding slot's float32 state,
and its landing. A program without the scope gives nothing to read."""

from benchmark import sala_scopes


def read(facts):
    s = sala_scopes.per_launch_s(
        facts, sala_scopes.STEP, ("lightning", "state")
    )
    return None if s is None else 1e3 * s
