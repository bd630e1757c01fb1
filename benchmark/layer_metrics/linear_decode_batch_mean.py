"""Serving engine: ``decode_batch_mean``'s quantity (the mean
``n_decoding`` over the window's steps that decode) for a program of
lightning and block-sparse layers; that reader's list is pinned by
position (PERF.md section 7), so this one calls its function."""

from benchmark import sala_scopes
from benchmark.layer_metrics import decode_batch_mean


def read(facts):
    if not sala_scopes.is_cell(facts):
        return None
    return decode_batch_mean.read(facts)
