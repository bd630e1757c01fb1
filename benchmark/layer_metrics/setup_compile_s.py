"""Worker runtime: seconds the program spent compiling before the
timed window opened -- the sum of JAX's ``backend_compile_duration``
over the compiles that began by then, each hit's cache retrieval taken
out (``setup_cache_load_s`` counts those). From the program's own
compile log (``dlrover_tpu/common/compile_cache.py``), which listens
from that module's import on.

This reader is also the ONE place that leaves the set-up table
(``setup_spans.table``) among the run's events, so in ``traced.json``:
``run.py`` calls readers and nothing else of this PR's."""

from benchmark import setup_spans


def read(facts):
    setup_spans.leave_table(facts)
    return setup_spans.compile_s(facts)
