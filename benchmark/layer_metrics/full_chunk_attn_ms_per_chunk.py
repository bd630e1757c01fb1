"""Kernels: device time under ``attn/full`` in the PREFILL program
(``jit_prefill``) per traced chunk launch: the full-attention layers'
attention of one 512-token chunk over every row below its start. A
program without the scope gives nothing to read."""

from benchmark import latent_scopes


def read(facts):
    s = latent_scopes.per_launch_s(facts, latent_scopes.PREFILL, "full")
    return None if s is None else 1e3 * s
