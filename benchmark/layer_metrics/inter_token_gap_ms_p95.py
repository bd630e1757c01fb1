"""Serving engine: the time between two tokens of one request, p95.
Every decoding request gets one token a step, so the gap is the
start-to-start period of two consecutive steps, counted once for each
of the ``n_decoding`` requests of the later step."""

from benchmark import step_spans


def read(facts):
    gap_s = step_spans.weighted_quantile(
        [
            (b["mono"] - a["mono"], b["attrs"]["n_decoding"])
            for a, b in step_spans.neighbours(facts)
        ],
        0.95,
    )
    return None if gap_s is None else 1e3 * gap_s
