"""Kernels: the least time the chip could take for one step's delta-rule
scans (``flops_kimi_linear.kda_scan_step``: the chunk algebra's FLOPs
over the bf16 peak or its float32 bytes over the HBM peak, whichever is
larger) over the time the ops under ``kda_scan`` took."""

from benchmark import flops_kimi_linear, hybrid_scopes


def read(facts):
    s = hybrid_scopes.per_step_s(facts, "kda_scan")
    window = facts.get("window")
    if s is None or not window:
        return None
    work = flops_kimi_linear.kda_scan_step(
        facts["ctx"]["config"], window["micro_batch"], window["seq_len"]
    )
    return hybrid_scopes.roofline_pct(facts, work, s)
