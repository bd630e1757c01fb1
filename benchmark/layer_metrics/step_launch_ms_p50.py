"""Serving engine: host time a step spends enqueueing its programs --
``prefill_launch`` + ``decode_launch``, the small host-to-device
arguments (tables, lengths, tokens, active, temperatures) included.
Median over the window's steps."""

from benchmark import step_spans


def read(facts):
    return step_spans.phase_ms_p50(facts, step_spans.LAUNCHES)
