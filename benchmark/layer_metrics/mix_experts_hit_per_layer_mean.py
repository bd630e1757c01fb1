"""Expert layer: distinct experts a decode launch's tokens reach, the
mean over the 8 expert layers (the program's own count, fetched with the
tokens), averaged over the window's decode launches, for the window /
full attention pattern model: up to 31 tokens x top-8 over 64 experts
reach ~62 when routing is even (``experts_hit_per_layer_mean``'s
quantity; that reader's list is pinned to one cell: PERF.md section 7).
Only a program whose pool is in layer groups reports here."""

import statistics

from benchmark import step_spans


def read(facts):
    if "window_decode_attention" not in (facts.get("kv_stats") or {}):
        return None
    hit = [
        s["attrs"]["experts_hit"] for s in step_spans.steps(facts)
        if "experts_hit" in s["attrs"]
    ]
    return statistics.fmean(hit) if hit else None
