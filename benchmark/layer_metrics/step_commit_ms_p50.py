"""Serving engine: host time a step spends handing out its tokens --
the ``commit`` phase: the per-request token loop, ``_finish`` and the
request spans of those that finished. Median over the window's steps."""

from benchmark import step_spans


def read(facts):
    return step_spans.phase_ms_p50(facts, ("commit",))
