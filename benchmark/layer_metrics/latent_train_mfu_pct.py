"""Model FLOP/s utilization of the latent-attention step with its
prediction module over the window -- the cell's share of the whole
step's peak: ``flops_glm.train_flops_per_token`` (with the held experts
at the rows the window's steps counted, the stack's and the module's) x
the window's tokens per second, over chips x the device kind's bf16
peak."""

from benchmark import flops, flops_glm, mtp_scopes


def read(facts):
    window, ctx = facts.get("window"), facts["ctx"]
    rows = mtp_scopes.rows_mean(facts, "window_steps")
    if not window or not window.get("tokens_per_s") or rows is None:
        return None
    peaks = flops.peaks_for(facts["device"]["kind"], ctx["peaks_table"])
    per_token = flops_glm.train_flops_per_token(
        ctx["config"], window["seq_len"], rows / window["tokens_per_step"]
    )
    return 100.0 * per_token * window["tokens_per_s"] / (
        ctx["chips"] * peaks["bf16_flops_per_s"]
    )
