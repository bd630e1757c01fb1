"""Expert layer: the least time the chip could take for one step's
grouped expert matmuls at the rows the traced steps counted, the
stack's and the module's (``flops_glm.expert_gmm_step``), over the time
of everything under the ``experts`` scopes: the gathers around the
matmuls and the re-forward count against it."""

from benchmark import flops_glm, hybrid_scopes, mtp_scopes


def read(facts):
    s = mtp_scopes.per_step_s(facts, "experts")
    rows = mtp_scopes.rows_mean(facts, "traced_steps")
    if s is None or rows is None:
        return None
    work = flops_glm.expert_gmm_step(facts["ctx"]["config"], rows)
    return hybrid_scopes.roofline_pct(facts, work, s)
