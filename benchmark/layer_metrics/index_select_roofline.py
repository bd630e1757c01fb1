"""Kernels: the least time the chip could take to read one index key a
visible row a layer (``flops_keye.index_select_step`` at the traced
steps' mean ``kv_rows``; memory-bound) over the time under
``attn/index`` + ``attn/select``: the view's gather, the score tensor
and the 32 passes of the threshold search all count against it."""

from benchmark import flops_keye, sparse_scopes


def read(facts):
    s = sparse_scopes.per_decode_step_s(facts, ("index", "select"))
    rows = sparse_scopes.traced_decode_mean(facts, "kv_rows")
    if s is None or rows is None:
        return None
    work = flops_keye.index_select_step(facts["ctx"]["config"], rows)
    return sparse_scopes.roofline_pct(facts, work, s)
