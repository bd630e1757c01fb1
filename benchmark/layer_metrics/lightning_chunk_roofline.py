"""Kernels: the least time the chip could take for the lightning layers
of a mean traced chunk (``flops_sala.lightning_chunk`` at the chunks'
mean ``n_valid``: the larger of its FLOP over the bf16 peak and its
state bytes over the HBM peak) over the time under ``attn/lightning``,
``state`` and ``state/snapshot`` in the prefill program."""

from benchmark import flops_sala, sala_scopes, sparse_scopes


def read(facts):
    s = sala_scopes.per_launch_s(
        facts, sala_scopes.PREFILL, ("lightning", "state", "snapshot")
    )
    chunks = sala_scopes.traced_chunks(facts)
    if s is None or not chunks:
        return None
    n_valid = sum(n for _, n in chunks) / len(chunks)
    work = flops_sala.lightning_chunk(facts["ctx"]["config"], n_valid)
    return sparse_scopes.roofline_pct(facts, work, s)
