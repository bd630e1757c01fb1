"""Model FLOP/s utilization of the train step over the window: the
benchmark's own FLOPs per token (``flops.train_flops_per_token``) x the
window's tokens per second, over chips x the device kind's bf16 peak."""

from benchmark import flops


def read(facts):
    window, ctx = facts.get("window"), facts["ctx"]
    if not window or not window.get("tokens_per_s"):
        return None
    peaks = flops.peaks_for(facts["device"]["kind"], ctx["peaks_table"])
    per_token = flops.train_flops_per_token(
        ctx["config"], window["seq_len"]
    )
    return 100.0 * per_token * window["tokens_per_s"] / (
        ctx["chips"] * peaks["bf16_flops_per_s"]
    )
