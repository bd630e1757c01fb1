"""Expert layer: distinct experts a decode launch's tokens reach, the
mean over layers (the program's own count, fetched with the tokens),
averaged over the window's decode launches: the weights a step must
read. 16 tokens x top-8 over 128 experts reach ~81 when routing is
even."""

import statistics

from benchmark import step_spans


def read(facts):
    hit = [
        s["attrs"]["experts_hit"] for s in step_spans.steps(facts)
        if "experts_hit" in s["attrs"]
    ]
    return statistics.fmean(hit) if hit else None
