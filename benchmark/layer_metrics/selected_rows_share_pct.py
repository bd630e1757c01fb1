"""Serving engine: rows ONE list of a decoding slot attends
(``selected_rows``) over the rows the same slots hold (``kv_rows``: their
fills), the mean over the window's decode launches: what selecting 64
pages saves of dense attention, which would read 100 %. A program
without the counter (or one whose counter means selected KEYS of a
learned indexer) gives nothing to read."""

import statistics

from benchmark import sala_scopes, step_spans


def read(facts):
    if not sala_scopes.is_cell(facts):
        return None
    shares = [
        100.0 * s["attrs"]["selected_rows"] / s["attrs"]["kv_rows"]
        for s in step_spans.steps(facts)
        if "selected_rows" in s["attrs"] and s["attrs"].get("kv_rows")
    ]
    return statistics.fmean(shares) if shares else None
