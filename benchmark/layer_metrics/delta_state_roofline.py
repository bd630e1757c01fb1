"""Kernels: the least time the chip could take to read AND write the
decoding slots' delta state once a delta layer
(``flops_olmo_hybrid.delta_state_step`` at the traced steps' mean
``state_slots``: ``2 x 30 x 96 x 192 x 4`` bytes a slot and layer;
memory-bound) over the time under ``attn/delta`` and ``state`` in the
decode program."""

from benchmark import delta_scopes, flops_olmo_hybrid, sparse_scopes


def read(facts):
    s = delta_scopes.per_launch_s(
        facts, delta_scopes.STEP, ("delta", "state")
    )
    slots = sparse_scopes.traced_decode_mean(facts, "state_slots")
    if s is None or slots is None:
        return None
    work = flops_olmo_hybrid.delta_state_step(facts["ctx"]["config"], slots)
    return sparse_scopes.roofline_pct(facts, work, s)
