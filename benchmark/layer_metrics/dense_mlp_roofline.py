"""Serving engine: the least time the chip could take to read every
layer's gate, up and down weights once (``flops_sala.dense_mlp_step``;
memory-bound at 48 rows) over the time under ``mlp`` in the decode
program."""

from benchmark import flops_sala, sala_scopes, sparse_scopes


def read(facts):
    if not sala_scopes.is_cell(facts):
        return None
    s = sala_scopes.per_launch_s(facts, sala_scopes.STEP, ("mlp",))
    tokens = sparse_scopes.traced_decode_mean(facts, "n_decoding")
    if s is None or tokens is None:
        return None
    work = flops_sala.dense_mlp_step(facts["ctx"]["config"], tokens)
    return sparse_scopes.roofline_pct(facts, work, s)
