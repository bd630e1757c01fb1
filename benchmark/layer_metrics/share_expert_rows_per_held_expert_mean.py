"""Expert layer: rows (token, k pairs) a held expert computes in a step,
the mean over the window's steps of (``moe_rows_held`` +
``mtp_moe_rows_held``) / (expert blocks x experts held), the prediction
module's block counted with the stack's expert layers."""

from benchmark import flops_glm, mtp_scopes


def read(facts):
    rows = mtp_scopes.rows_mean(facts, "window_steps")
    if rows is None:
        return None
    cfg = facts["ctx"]["config"]
    return rows / (
        flops_glm.n_expert_blocks(cfg) * cfg["n_routed_experts"]
    )
