"""Kernels: device time under ``attn/delta``, ``state`` and
``state/snapshot`` in the prefill program (``jit_prefill``) per traced
chunk launch: the chunk form from the slot's state (a triangular solve a
sub-chunk of 64 rows), the state after ``n_valid`` rows and the snapshot.
A program without the scope gives nothing to read."""

from benchmark import delta_scopes


def read(facts):
    s = delta_scopes.per_launch_s(
        facts, delta_scopes.PREFILL, ("delta", "state", "snapshot")
    )
    return None if s is None else 1e3 * s
