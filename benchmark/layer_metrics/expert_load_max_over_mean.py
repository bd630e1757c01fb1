"""Expert layer: the busiest held expert's rows over the mean held
expert's, over the window's steps (both summed over the expert layers):
1.0 is a perfectly even load."""

from benchmark import hybrid_scopes


def read(facts):
    held = hybrid_scopes.counter_mean(facts, "moe_rows_held", "window_steps")
    busiest = hybrid_scopes.counter_mean(
        facts, "moe_rows_max", "window_steps"
    )
    if not held or busiest is None:
        return None
    return busiest * facts["ctx"]["config"]["num_experts"] / held
