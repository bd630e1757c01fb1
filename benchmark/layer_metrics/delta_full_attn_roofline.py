"""Kernels: the least time the chip could take to read the K and V rows
of the active slots once a full layer
(``flops_olmo_hybrid.full_attention_step`` at the traced steps' mean
``kv_rows``: 30 heads x 128 x 2 B a row, K and V; memory-bound) over the
time under ``attn/full`` in the decode program."""

from benchmark import delta_scopes, flops_olmo_hybrid, sparse_scopes


def read(facts):
    s = delta_scopes.per_launch_s(facts, delta_scopes.STEP, ("full",))
    rows = sparse_scopes.traced_decode_mean(facts, "kv_rows")
    if s is None or rows is None:
        return None
    work = flops_olmo_hybrid.full_attention_step(
        facts["ctx"]["config"], rows
    )
    return sparse_scopes.roofline_pct(facts, work, s)
