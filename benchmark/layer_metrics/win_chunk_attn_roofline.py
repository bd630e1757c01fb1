"""Kernels: the least time the chip could take for the sliding-window
layers' attention of a mean traced chunk (``flops_mellum2.
attention_chunk``: the larger of its visible (query, key) pairs x 4 x
128 x 32 FLOP over the bf16 peak and the K and V bytes of the pool rows
its band needs over the HBM peak) over the time under ``attn/window`` in
the prefill program."""

from benchmark import flops_mellum2, latent_scopes, sparse_scopes
from benchmark import window_scopes


def read(facts):
    s = latent_scopes.per_launch_s(facts, latent_scopes.PREFILL, "window")
    chunks = window_scopes.traced_chunks(facts)
    if s is None or not chunks:
        return None
    work = flops_mellum2.attention_chunk(
        facts["ctx"]["config"], flops_mellum2.SLIDING, chunks
    )
    return sparse_scopes.roofline_pct(facts, work, s)
