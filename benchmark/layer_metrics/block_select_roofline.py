"""Kernels: the least time the chip could take to read the decoding
slots' visible compressed keys once a sparse layer
(``flops_sala.block_select_step`` at the traced steps' mean
``ckey_rows``) over the time under ``attn/select`` in the decode
program."""

from benchmark import flops_sala, sala_scopes, sparse_scopes


def read(facts):
    s = sala_scopes.per_launch_s(facts, sala_scopes.STEP, ("select",))
    rows = sparse_scopes.traced_decode_mean(facts, "ckey_rows")
    if s is None or rows is None:
        return None
    work = flops_sala.block_select_step(facts["ctx"]["config"], rows)
    return sparse_scopes.roofline_pct(facts, work, s)
