"""Serving engine: seconds its construction and warm-up took (the
eager fused copy of the weights, the pools, then every program compiled
or loaded and run once), as the engine timed them itself: the two
floats ``engine_build_s`` + ``warmup_s`` of its ``kv_stats()``, which a
timed run's ``result.json`` carries too. Its ``serving.engine_build`` /
``serving.warmup`` spans hold the same seconds by phase. The compiles
inside are also in ``setup_compile_s`` / ``setup_cache_load_s``."""

from benchmark import setup_spans


def read(facts):
    return setup_spans.engine_build_s(facts)
