"""Kernels: device time under ``attn/window`` in the PREFILL program
(``jit_prefill``) per traced chunk launch: the sliding-window layers'
attention of one 512-token chunk (projections, rotation, each token
tile's band of the window group's rows, the chunk's own rows, the output
projection). A program without the scope gives nothing to read."""

from benchmark import latent_scopes


def read(facts):
    s = latent_scopes.per_launch_s(facts, latent_scopes.PREFILL, "window")
    return None if s is None else 1e3 * s
