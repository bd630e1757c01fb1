"""Expert layer: the least time the chip could take for one step's
grouped expert matmuls at the rows the traced steps counted
(``flops_kimi_linear.expert_gmm_step``) over the time of everything
under ``mlp/experts``: the gathers around the matmuls count against
it."""

from benchmark import flops_kimi_linear, hybrid_scopes


def read(facts):
    s = hybrid_scopes.per_step_s(facts, "experts")
    rows = hybrid_scopes.counter_mean(facts, "moe_rows_held", "traced_steps")
    if s is None or rows is None:
        return None
    work = flops_kimi_linear.expert_gmm_step(facts["ctx"]["config"], rows)
    return hybrid_scopes.roofline_pct(facts, work, s)
