"""Kernels: device time under ``attn/lightning`` and ``state`` in the
prefill program (``jit_prefill``) per traced chunk: the lightning layers'
intra-chunk products, the state's term, the state after the chunk's
valid rows and the snapshot. A program without the scope gives nothing
to read."""

from benchmark import sala_scopes


def read(facts):
    s = sala_scopes.per_launch_s(
        facts, sala_scopes.PREFILL, ("lightning", "state", "snapshot")
    )
    return None if s is None else 1e3 * s
