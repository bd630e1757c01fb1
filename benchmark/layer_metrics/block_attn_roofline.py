"""Kernels: the least time the chip could take to read the K and V rows
of every list once (``flops_sala.block_attention_step`` at the traced
steps' mean ``selected_rows``: ONE KV head's rows a list, a list a KV
head, in every sparse layer) over the time under ``attn/sparse`` in the
decode program."""

from benchmark import flops_sala, sala_scopes, sparse_scopes


def read(facts):
    s = sala_scopes.per_launch_s(facts, sala_scopes.STEP, ("sparse",))
    rows = sparse_scopes.traced_decode_mean(facts, "selected_rows")
    if s is None or rows is None:
        return None
    work = flops_sala.block_attention_step(facts["ctx"]["config"], rows)
    return sparse_scopes.roofline_pct(facts, work, s)
