"""Kernels: device time under ``attn/gqa`` in the PREFILL program
(``jit_prefill``) per traced chunk launch, the attention layers of a
convolution / attention pattern model (64-wide heads, K and V held
flat): the chunk's three projections, the per-head norms and the
rotation, the read of the slot's prefix (gathered in blocks under a
running softmax, or the chunk kernel over the pool in place:
``kv_stats()["conv_chunk_attention"]`` says which, where the program has
the counter), the chunk's own causal rows and the output projection. A
program without the scope gives nothing to read."""

from benchmark import latent_scopes


def read(facts):
    s = latent_scopes.per_launch_s(facts, latent_scopes.PREFILL, "gqa")
    return None if s is None else 1e3 * s
