"""Admission: how long a request waits for one of the engine's slots --
the engine's own ``serving.queue_wait`` span (submit -> admitted), the
median over the requests admitted inside the window."""

from benchmark import step_spans


def read(facts):
    return step_spans.median_ms(
        s["dur_s"]
        for s in step_spans.ending_in_window(facts, "serving.queue_wait")
        if s["status"] == "ok"
    )
