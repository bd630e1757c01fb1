"""Worker runtime: of the compiles that asked the persistent cache
before the timed window opened, the share it answered. Not reported
when none asked (the cache switched off). By count, so a warm run reads
the share of its programs that JAX keeps at all (``setup_spans``)."""

from benchmark import setup_spans


def read(facts):
    return setup_spans.cache_hit_pct(facts)
