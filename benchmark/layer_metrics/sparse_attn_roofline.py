"""Kernels: the least time the chip could take to read the selected
rows' K and V once a layer (``flops_keye.sparse_attention_step`` at the
traced steps' mean ``selected_rows``; memory-bound) over the time under
``attn/sparse``: the gathered copy is written and read again, and
counts against it."""

from benchmark import flops_keye, sparse_scopes


def read(facts):
    s = sparse_scopes.per_decode_step_s(facts, ("sparse",))
    rows = sparse_scopes.traced_decode_mean(facts, "selected_rows")
    if s is None or rows is None:
        return None
    work = flops_keye.sparse_attention_step(facts["ctx"]["config"], rows)
    return sparse_scopes.roofline_pct(facts, work, s)
