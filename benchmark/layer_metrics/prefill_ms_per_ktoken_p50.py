"""Serving engine: per request, the engine's own ``serving.prefill``
span (admitted to a slot -> first token) per thousand prompt tokens; the
median over the requests that finished while spans were recorded.
Chunks of other requests and the decode steps interleaved with them are
inside the span: it is what a prompt's owner waits, not kernel time."""

import statistics


def read(facts):
    per_k = [
        1e3 * s["dur_s"] / (s["attrs"]["prompt_len"] / 1e3)
        for s in facts.get("spans", [])
        if s["name"] == "serving.prefill" and s.get("dur_s")
        and s["attrs"].get("prompt_len")
    ]
    return statistics.median(per_k) if per_k else None
