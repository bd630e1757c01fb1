"""Serving engine: rows of the window group that the decoding slots can
see (``window_rows``: ``min(fill, 1,023)`` a slot) over the rows the
same slots hold in the full group (``kv_rows``: their fills), the mean
over the window's decode launches: what keeping the sliding layers' rows
only while a query can see them saves of a one-table pool, which would
hold 100 %. A program without the counter gives nothing to read."""

import statistics

from benchmark import step_spans


def read(facts):
    shares = [
        100.0 * s["attrs"]["window_rows"] / s["attrs"]["kv_rows"]
        for s in step_spans.steps(facts)
        if "window_rows" in s["attrs"] and s["attrs"].get("kv_rows")
    ]
    return statistics.fmean(shares) if shares else None
