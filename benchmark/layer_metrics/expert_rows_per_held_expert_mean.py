"""Expert layer: rows (token, k pairs) a held expert computes in a step,
the mean over the window's steps of ``moe_rows_held`` / (expert layers x
experts held)."""

from benchmark import flops_kimi_linear, hybrid_scopes


def read(facts):
    rows = hybrid_scopes.counter_mean(facts, "moe_rows_held", "window_steps")
    if rows is None:
        return None
    cfg = facts["ctx"]["config"]
    layers = sum(f == "moe" for _, f in flops_kimi_linear.layer_kinds(cfg))
    return rows / (layers * cfg["num_experts"])
