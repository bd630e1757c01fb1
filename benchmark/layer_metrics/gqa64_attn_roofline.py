"""Kernels: the least time the chip could take to read the visible rows'
K and V once an attention layer (``flops_lfm2.gqa_attention_step`` at
the traced steps' mean ``kv_rows``; memory-bound) over the time under
``attn/gqa`` in the decode program: the projections around attention, a
gathered copy of the rows and everything else the scope holds count
against it."""

from benchmark import flops_lfm2, latent_scopes, sparse_scopes


def read(facts):
    s = latent_scopes.per_launch_s(facts, latent_scopes.STEP, "gqa")
    rows = sparse_scopes.traced_decode_mean(facts, "kv_rows")
    if s is None or rows is None:
        return None
    work = flops_lfm2.gqa_attention_step(facts["ctx"]["config"], rows)
    return sparse_scopes.roofline_pct(facts, work, s)
