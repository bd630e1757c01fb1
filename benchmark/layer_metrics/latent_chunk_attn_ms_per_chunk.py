"""Kernels: device time under ``attn/mla`` in the PREFILL program
(``jit_prefill``) per traced chunk launch, all layers: the chunk's
projections, the walk over the slot's prefix rows in blocks under a
running softmax (absorbed or up-projected: ``kv_stats()``'s
``latent_chunk_attention`` says which), the chunk's own causal block and
the output projection. A program without the scope gives nothing to
read."""

from benchmark import latent_scopes


def read(facts):
    s = latent_scopes.per_launch_s(facts, latent_scopes.PREFILL, "mla")
    return None if s is None else 1e3 * s
