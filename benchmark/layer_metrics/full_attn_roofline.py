"""Kernels: the least time the chip could take to read the visible rows' K
and V once a full-attention layer (``flops_mellum2.attention_step`` at
the traced steps' mean ``kv_rows``: the decoding slots' fills;
memory-bound) over the time under ``attn/full`` in the decode program:
the projections around attention and everything else the scope holds
count against it."""

from benchmark import flops_mellum2, latent_scopes, sparse_scopes


def read(facts):
    s = latent_scopes.per_launch_s(facts, latent_scopes.STEP, "full")
    rows = sparse_scopes.traced_decode_mean(facts, "kv_rows")
    if s is None or rows is None:
        return None
    work = flops_mellum2.attention_step(
        facts["ctx"]["config"], flops_mellum2.FULL, rows
    )
    return sparse_scopes.roofline_pct(facts, work, s)
