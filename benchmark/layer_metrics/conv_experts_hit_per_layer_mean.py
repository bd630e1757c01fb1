"""Expert layer: distinct experts a decode launch's tokens reach, the
mean over the expert layers (the program's own count, fetched with the
tokens), averaged over the window's decode launches, for the convolution
/ attention pattern model: 32 tokens x top-4 over 64 experts reach ~56
when routing is even (``experts_hit_per_layer_mean``'s quantity; that
reader's list is pinned to one cell: PERF.md section 7). Only a program
with per-slot state reports here."""

import statistics

from benchmark import step_spans


def read(facts):
    if not (facts.get("kv_stats") or {}).get("state_layers"):
        return None
    hit = [
        s["attrs"]["experts_hit"] for s in step_spans.steps(facts)
        if "experts_hit" in s["attrs"]
    ]
    return statistics.fmean(hit) if hit else None
