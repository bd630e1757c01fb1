"""Kernels: device time of the flash-attention Pallas kernels under
``attn/mla`` (forward, dq, dk/dv at q/k 192 and v 128, all latent
attention layers) per traced step. The trace names a custom call after
the innermost scope it sits in, ``mla.N`` here, so
``flash_attn_ms_per_step`` (which looks for ``attn.N``) finds none of
them in this program."""

from benchmark import hybrid_scopes


def read(facts):
    s = hybrid_scopes.per_step_s(facts, "mla", kernels_only=True)
    return None if s is None else 1e3 * s
