"""Serving engine: what a step's always-on bookkeeping costs -- the
``account`` phase: counters, gauges, the block pool's gauges
(``_sync_pool_metrics``), the retrace check and the per-token latency
observations. Median over the window's steps."""

from benchmark import step_spans


def read(facts):
    return step_spans.phase_ms_p50(facts, ("account",))
