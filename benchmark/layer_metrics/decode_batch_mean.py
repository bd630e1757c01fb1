"""Serving engine: how many of the engine's slots a decode launch
carries -- the mean ``n_decoding`` over the window's steps that
decode."""

import statistics

from benchmark import step_spans


def read(facts):
    batches = [
        s["attrs"]["n_decoding"] for s in step_spans.steps(facts)
        if s["attrs"]["n_decoding"]
    ]
    return statistics.fmean(batches) if batches else None
