"""Fleet: what the replica's serve loop spends between two engine
steps -- from the end of step k to the start of step k+1
(``ThreadReplica._run``: heartbeat, inbox, the completion events of
``serve_step``, ``serve_exports``). A span cannot hold its own
emission, so that of step k's ``serving.step`` span is in here too
(most of the value on the v5e host: PERF.md, PR 24). Median over the
window's consecutive steps."""

from benchmark import step_spans


def read(facts):
    return step_spans.median_ms(
        b["mono"] - (a["mono"] + a["dur_s"])
        for a, b in step_spans.neighbours(facts)
    )
