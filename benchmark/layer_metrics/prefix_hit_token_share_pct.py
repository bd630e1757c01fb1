"""Serving engine: of the prompt tokens of the requests admitted in the
window, the share the prefix cache supplied (``prefix_hit_tokens`` on
the admitting steps) against what prefill chunks ran
(``prefill_tokens``)."""

from benchmark import step_spans


def read(facts):
    steps = step_spans.ending_in_window(facts, step_spans.STEP)
    hit = sum(s["attrs"].get("prefix_hit_tokens", 0) for s in steps)
    ran = sum(s["attrs"].get("prefill_tokens", 0) for s in steps)
    if not hit:
        return None
    return 100.0 * hit / (hit + ran)
