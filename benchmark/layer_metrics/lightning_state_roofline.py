"""Kernels: the least time the chip could take to read AND write the
decoding slots' lightning state once a lightning layer
(``flops_sala.lightning_state_step`` at the traced steps' mean
``state_slots``; memory-bound) over the time under ``attn/lightning`` and
``state`` in the decode program."""

from benchmark import flops_sala, sala_scopes, sparse_scopes


def read(facts):
    s = sala_scopes.per_launch_s(
        facts, sala_scopes.STEP, ("lightning", "state")
    )
    slots = sparse_scopes.traced_decode_mean(facts, "state_slots")
    if s is None or slots is None:
        return None
    work = flops_sala.lightning_state_step(facts["ctx"]["config"], slots)
    return sparse_scopes.roofline_pct(facts, work, s)
