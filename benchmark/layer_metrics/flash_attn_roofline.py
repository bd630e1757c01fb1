"""Kernels: the least time the chip could take for one step's
flash-attention kernels (``flops.flash_attention_step``: causal FLOPs
over the bf16 peak, or bytes over the HBM peak, whichever is larger)
over the time they took in the trace (``flash_attn_ms_per_step``)."""

from benchmark import flops


def read(facts):
    trace, window, ctx = facts.get("trace"), facts.get("window"), facts["ctx"]
    if not trace or not window or not trace.get("kernel_s", {}).get("attn"):
        return None
    peaks = flops.peaks_for(facts["device"]["kind"], ctx["peaks_table"])
    work = flops.flash_attention_step(
        ctx["config"], window["micro_batch"], window["seq_len"]
    )
    least_s, _ = flops.roofline_s(work, peaks)
    return 100.0 * least_s / (trace["kernel_s"]["attn"] / trace["steps"])
