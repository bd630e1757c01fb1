"""Prefix cache: of the prompt tokens of the requests admitted in the
window, the share the prefix cache supplied FROM A BOUNDARY THAT HELD A
STATE SNAPSHOT (``prefix_hit_tokens`` on the admitting steps: a model
with per-slot state adopts a hit only as deep as its deepest snapshot)
against what prefill chunks ran (``prefill_tokens``)
(``prefix_hit_token_share_pct``'s quantity; that reader's list is pinned
to one cell: PERF.md section 7). Only a program with per-slot state
reports here."""

from benchmark import step_spans


def read(facts):
    if not (facts.get("kv_stats") or {}).get("state_layers"):
        return None
    steps = step_spans.ending_in_window(facts, step_spans.STEP)
    hit = sum(s["attrs"].get("prefix_hit_tokens", 0) for s in steps)
    ran = sum(s["attrs"].get("prefill_tokens", 0) for s in steps)
    if not hit:
        return None
    return 100.0 * hit / (hit + ran)
