"""Serving engine: ``idle_attributed_pct``'s quantity (of the seconds
the device ran nothing in the traced window, the share that falls inside
a named phase of a ``serving.step`` span or between two steps) for a
program with per-slot state. That reader's list ends with another cell
by an accepted test's pin and only appends are admitted (PERF.md section
7), so this one calls its function: ``idle_by_phase`` is the table
behind both, and

    python3 benchmark/layer_metrics/idle_attributed_pct.py lfm2-serve-sessions-8k

prints it for this cell's last traced run."""

from benchmark.layer_metrics import idle_attributed_pct


def read(facts):
    if not (facts.get("kv_stats") or {}).get("state_layers"):
        return None
    return idle_attributed_pct.read(facts)
