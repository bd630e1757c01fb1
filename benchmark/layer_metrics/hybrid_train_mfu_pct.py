"""Model FLOP/s utilization of the layer-pattern step over the window:
``flops_kimi_linear.train_flops_per_token`` (with the held experts at
the rows the window's steps counted) x the window's tokens per second,
over chips x the device kind's bf16 peak."""

from benchmark import flops, flops_kimi_linear, hybrid_scopes


def read(facts):
    window, ctx = facts.get("window"), facts["ctx"]
    rows = hybrid_scopes.counter_mean(facts, "moe_rows_held", "window_steps")
    if not window or not window.get("tokens_per_s") or rows is None:
        return None
    peaks = flops.peaks_for(facts["device"]["kind"], ctx["peaks_table"])
    per_token = flops_kimi_linear.train_flops_per_token(
        ctx["config"], window["seq_len"], rows / window["tokens_per_step"]
    )
    return 100.0 * per_token * window["tokens_per_s"] / (
        ctx["chips"] * peaks["bf16_flops_per_s"]
    )
