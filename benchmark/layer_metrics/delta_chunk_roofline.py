"""Kernels: the least time the chip could take for the delta layers of a
mean traced chunk (``flops_olmo_hybrid.delta_chunk`` at the chunks' mean
``n_valid``, by ``flops_kimi_linear``'s convention for the same solve: the
larger of its FLOP over the bf16 peak and its state bytes over the HBM
peak) over the time under ``attn/delta``, ``state`` and ``state/snapshot``
in the prefill program."""

from benchmark import delta_scopes, flops_olmo_hybrid, sparse_scopes


def read(facts):
    s = delta_scopes.per_launch_s(
        facts, delta_scopes.PREFILL, ("delta", "state", "snapshot")
    )
    chunks = delta_scopes.traced_chunks(facts)
    if s is None or not chunks:
        return None
    n_valid = sum(n for _, n in chunks) / len(chunks)
    work = flops_olmo_hybrid.delta_chunk(facts["ctx"]["config"], n_valid)
    return sparse_scopes.roofline_pct(facts, work, s)
