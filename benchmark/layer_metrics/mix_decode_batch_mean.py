"""Serving engine: ``decode_batch_mean``'s quantity (the mean
``n_decoding`` over the window's steps that decode) for a program whose
pool is in layer groups; that reader's list is pinned by position
(PERF.md section 7), so this one calls its function."""

from benchmark.layer_metrics import decode_batch_mean


def read(facts):
    if "window_decode_attention" not in (facts.get("kv_stats") or {}):
        return None
    return decode_batch_mean.read(facts)
