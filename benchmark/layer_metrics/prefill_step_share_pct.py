"""Serving engine: the share of the window's decoding steps on which a
prefill chunk rides (``prefill_tokens`` > 0): how often the decoding
requests wait for somebody's prompt."""

from benchmark import step_spans


def read(facts):
    decoding = [
        s["attrs"] for s in step_spans.steps(facts)
        if s["attrs"]["n_decoding"]
    ]
    if not decoding:
        return None
    with_chunk = sum(1 for a in decoding if a["prefill_tokens"])
    return 100.0 * with_chunk / len(decoding)
