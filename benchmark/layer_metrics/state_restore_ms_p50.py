"""Serving engine: the host's time for an admission's state restore (the
slot's per-slot state set from a prefix hit's snapshot, or zeroed: one
compiled copy launched on the device, no host round trip), the median
over the window's steps that admitted a request of a model with per-slot
state (``state_restore_s`` over ``state_restores`` of the step span). A
program without the counters gives nothing to read."""

import statistics

from benchmark import step_spans


def read(facts):
    per = [
        1e3 * s["attrs"]["state_restore_s"] / s["attrs"]["state_restores"]
        for s in step_spans.steps(facts)
        if s["attrs"].get("state_restores")
        and "state_restore_s" in s["attrs"]
    ]
    return statistics.median(per) if per else None
