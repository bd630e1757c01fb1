"""Prefix cache: rows of a prompt that its hit did not cover
(``prefill_rows_again`` of the step spans: the prompt's length less the
hit's rows, counted at admission; a grown session's are the last answer,
the tail below a block boundary and the new turn) over requests admitted
(``state_restores``), over the window's steps. A program without the
counter gives nothing to read."""

from benchmark import delta_scopes, step_spans


def read(facts):
    if not delta_scopes.is_cell(facts):
        return None
    steps = step_spans.steps(facts)
    admitted = sum(s["attrs"].get("state_restores", 0) for s in steps)
    if not admitted or not any(
        "prefill_rows_again" in s["attrs"] for s in steps
    ):
        return None
    return sum(
        s["attrs"].get("prefill_rows_again", 0) for s in steps
    ) / admitted
