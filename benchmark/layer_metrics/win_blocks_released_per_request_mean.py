"""Serving engine: blocks of the window group released by live slots
(``window_blocks_released`` of the step spans: a slot drops a block once
every row of it is below its next query's reach) summed over the
window's steps, over the requests that finished in them. A program
without the counter gives nothing to read."""

from benchmark import step_spans


def read(facts):
    steps = step_spans.steps(facts)
    if not any("window_blocks_released" in s["attrs"] for s in steps):
        return None
    finished = sum(s["attrs"].get("n_finished", 0) for s in steps)
    if not finished:
        return None
    return sum(
        s["attrs"].get("window_blocks_released", 0) for s in steps
    ) / finished
