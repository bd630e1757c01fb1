"""Worker runtime: ``setup_compile_s``'s quantity (seconds compiling
before the timed window opened, each hit's cache retrieval taken out)
for a program whose pool is in layer groups; that reader's list is
pinned by position (PERF.md section 7), so this one calls its functions,
and like it leaves the set-up table (``setup_spans.table``) among the
run's events, so in this cell's ``traced.json``."""

from benchmark import setup_spans


def read(facts):
    if "window_decode_attention" not in (facts.get("kv_stats") or {}):
        return None
    setup_spans.leave_table(facts)
    return setup_spans.compile_s(facts)
