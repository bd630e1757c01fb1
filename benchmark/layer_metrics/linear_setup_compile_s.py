"""Worker runtime: ``setup_compile_s``'s quantity (seconds compiling
before the timed window opened, each hit's cache retrieval taken out)
for a program of lightning and block-sparse layers; that reader's list
is pinned by position (PERF.md section 7), so this one calls its
functions, and like it leaves the set-up table (``setup_spans.table``)
among the run's events."""

from benchmark import sala_scopes, setup_spans


def read(facts):
    if not sala_scopes.is_cell(facts):
        return None
    setup_spans.leave_table(facts)
    return setup_spans.compile_s(facts)
