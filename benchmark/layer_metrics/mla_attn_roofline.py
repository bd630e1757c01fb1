"""Kernels: the least time the chip could take for one step's latent-
attention flash kernels (``flops_kimi_linear.mla_flash_step``) over the
time they took (``mla_attn_ms_per_step``)."""

from benchmark import flops_kimi_linear, hybrid_scopes


def read(facts):
    s = hybrid_scopes.per_step_s(facts, "mla", kernels_only=True)
    window = facts.get("window")
    if s is None or not window:
        return None
    work = flops_kimi_linear.mla_flash_step(
        facts["ctx"]["config"], window["micro_batch"], window["seq_len"]
    )
    return hybrid_scopes.roofline_pct(facts, work, s)
