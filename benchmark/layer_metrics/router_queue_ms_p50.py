"""Fleet: how long the router holds a request before it first hands it
to a replica -- from the start of its ``fleet.request`` span to the
start of that span's first ``fleet.attempt`` child. Median over the
requests that completed inside the window."""

from benchmark import step_spans


def read(facts):
    first_attempt = {}
    for s in facts.get("spans") or ():
        if s["name"] == "fleet.attempt":
            seen = first_attempt.get(s["parent_id"])
            if seen is None or s["mono"] < seen:
                first_attempt[s["parent_id"]] = s["mono"]
    return step_spans.median_ms(
        first_attempt[s["span_id"]] - s["mono"]
        for s in step_spans.ending_in_window(facts, "fleet.request")
        if s["span_id"] in first_attempt
    )
