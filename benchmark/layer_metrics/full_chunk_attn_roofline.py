"""Kernels: the least time the chip could take for the full-attention
layers' attention of a mean traced chunk (``flops_mellum2.
attention_chunk``: pairs = ``n_valid`` x (``start`` + the causal half of
the chunk), 4 x 128 x 32 FLOP each, against the K and V bytes of the
rows below ``start``) over the time under ``attn/full`` in the prefill
program."""

from benchmark import flops_mellum2, latent_scopes, sparse_scopes
from benchmark import window_scopes


def read(facts):
    s = latent_scopes.per_launch_s(facts, latent_scopes.PREFILL, "full")
    chunks = window_scopes.traced_chunks(facts)
    if s is None or not chunks:
        return None
    work = flops_mellum2.attention_chunk(
        facts["ctx"]["config"], flops_mellum2.FULL, chunks
    )
    return sparse_scopes.roofline_pct(facts, work, s)
