"""Kernels: the least time the chip could take to read the VISIBLE rows' K
and V once a sliding-window layer (``flops_mellum2.attention_step`` at
the traced steps' mean ``window_rows``: ``min(fill, 1,023)`` a decoding
slot; memory-bound) over the time under ``attn/window`` in the decode
program: the projections around attention and everything else the scope
holds count against it."""

from benchmark import flops_mellum2, latent_scopes, sparse_scopes


def read(facts):
    s = latent_scopes.per_launch_s(facts, latent_scopes.STEP, "window")
    rows = sparse_scopes.traced_decode_mean(facts, "window_rows")
    if s is None or rows is None:
        return None
    work = flops_mellum2.attention_step(
        facts["ctx"]["config"], flops_mellum2.SLIDING, rows
    )
    return sparse_scopes.roofline_pct(facts, work, s)
