"""Kernels: the least time the chip could take for one step's rotary
latent-attention flash kernels, all blocks
(``flops_glm.mla_flash_step``), over the time they took
(``rope_mla_attn_ms_per_step``)."""

from benchmark import flops_glm, hybrid_scopes, mtp_scopes


def read(facts):
    s = mtp_scopes.per_step_s(facts, "mla", kernels_only=True)
    window = facts.get("window")
    if s is None or not window:
        return None
    work = flops_glm.mla_flash_step(
        facts["ctx"]["config"], window["micro_batch"], window["seq_len"]
    )
    return hybrid_scopes.roofline_pct(facts, work, s)
