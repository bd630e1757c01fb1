"""Serving engine: ``engine_build_s``'s quantity (construction plus
warm-up as the engine timed them: the pools, the per-slot state and its
snapshots, every program compiled or loaded and run once) for a program
with per-slot state; that reader's list is pinned by position (PERF.md
section 7), so this one calls its function."""

from benchmark import setup_spans


def read(facts):
    if not (facts.get("kv_stats") or {}).get("state_layers"):
        return None
    return setup_spans.engine_build_s(facts)
