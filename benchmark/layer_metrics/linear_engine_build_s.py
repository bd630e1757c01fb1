"""Serving engine: ``engine_build_s``'s quantity for a program of
lightning and block-sparse layers; that reader's list is pinned by
position (PERF.md section 7), so this one calls its function."""

from benchmark import sala_scopes
from benchmark.layer_metrics import engine_build_s


def read(facts):
    if not sala_scopes.is_cell(facts):
        return None
    return engine_build_s.read(facts)
