"""Kernels: the least time the chip could take for the sparse layers of
a mean traced chunk (``flops_sala.block_chunk``: the selected (query,
key) pairs and the scoring against the bytes of the pages some query
selected) over the time under ``attn/select`` and ``attn/sparse`` in the
prefill program."""

from benchmark import flops_sala, sala_scopes, sparse_scopes


def read(facts):
    s = sala_scopes.per_launch_s(
        facts, sala_scopes.PREFILL, ("sparse", "select")
    )
    chunks = sala_scopes.traced_chunks(facts)
    if s is None or not chunks:
        return None
    work = flops_sala.block_chunk(facts["ctx"]["config"], chunks)
    return sparse_scopes.roofline_pct(facts, work, s)
