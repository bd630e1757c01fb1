"""Serving engine: per request, the engine's own ``serving.decode`` span
(first token -> finish) over the tokens decoded in it; the median over
the requests that finished while spans were recorded."""

import statistics


def read(facts):
    per_token = [
        1e3 * s["dur_s"] / (s["attrs"]["new_tokens"] - 1)
        for s in facts.get("spans", [])
        if s["name"] == "serving.decode" and s.get("dur_s")
        and s["attrs"].get("new_tokens", 0) > 1
    ]
    return statistics.median(per_token) if per_token else None
