"""Serving engine: of the cache rows visible to the window's decode
launches (``kv_rows``), the share their attention reads
(``selected_rows``): ~6 % at 33k rows and top-2,048, 100 % below 2,048."""

from benchmark import step_spans


def read(facts):
    decoding = [
        s["attrs"] for s in step_spans.steps(facts)
        if s["attrs"].get("n_decoding") and "selected_rows" in s["attrs"]
    ]
    visible = sum(a["kv_rows"] for a in decoding)
    if not visible:
        return None
    return 100.0 * sum(a["selected_rows"] for a in decoding) / visible
