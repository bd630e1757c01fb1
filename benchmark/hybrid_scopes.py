"""The layer-pattern step's device time by its own scopes, from the
event dump a traced run keeps (``trace_reduce.dump_xplane``): pure
arithmetic, as ``trace_reduce.reduce`` is for its four scopes, which
these nest under (``attn/kda``, ``attn/kda/kda_scan``, ``attn/mla``,
``mlp/router``, ``mlp/experts``, ``mlp/shared``, ``mlp/dense``).

An op is booked to the INNERMOST of these scopes that its ``op_name``
path names (forward and backward alike: ``jvp(attn)/kda/...`` and
``transpose(jvp(attn))/kda/...`` both say ``kda``), so ``kda`` here is
the KDA layers' time outside the scan. A program without these scopes
books nothing and ``reduce`` returns None.
"""

import re

from benchmark import trace_reduce

# Innermost first.
SCOPES = ("kda_scan", "kda", "mla", "router", "experts", "shared", "dense")
_RE = {
    s: re.compile(r"(?:^|[/(])" + s + r"(?:[/)]|$)") for s in SCOPES
}


def scope_of(op_name):
    for s in SCOPES:
        if _RE[s].search(op_name):
            return s
    return None


def reduce(dump):
    """Seconds of device-op time under each scope, the Pallas kernels'
    share of each, and all device-op time, averaged over the device
    planes that ran anything; None where no op carries these scopes."""
    scope_s, kernel_s, all_s, planes = {}, {}, 0.0, 0
    for lines in dump.get("planes", {}).values():
        rows = lines.get(trace_reduce.OPS_LINE) or []
        if not rows:
            continue
        planes += 1
        for _, _, dur, op_name, category in rows:
            if category in trace_reduce.ENVELOPES:
                continue
            all_s += dur / 1e9
            s = scope_of(op_name)
            if s is None:
                continue
            scope_s[s] = scope_s.get(s, 0.0) + dur / 1e9
            if category == trace_reduce.KERNEL:
                kernel_s[s] = kernel_s.get(s, 0.0) + dur / 1e9
    if not scope_s:
        return None
    return {
        "scope_s": {k: v / planes for k, v in scope_s.items()},
        "kernel_s": {k: v / planes for k, v in kernel_s.items()},
        "device_op_s": all_s / planes,
    }


# -- what the layer_metrics readers share -------------------------------------


def per_step_s(facts, scope, kernels_only=False):
    """Seconds a traced step spends under ``scope`` (its Pallas kernels
    alone when asked), or None where the run has nothing to read."""
    scopes, trace = facts.get("hybrid_scopes"), facts.get("trace")
    if not scopes or not trace or not trace.get("steps"):
        return None
    table = scopes["kernel_s" if kernels_only else "scope_s"]
    if not table.get(scope):
        return None
    return table[scope] / trace["steps"]


def counter_mean(facts, name, steps_key):
    """Mean of the step counter ``name`` over the steps
    ``facts[steps_key]`` names (``traced_steps`` / ``window_steps``)."""
    values = (facts.get("counters") or {}).get(name)
    span = facts.get(steps_key)
    if not values or not span:
        return None
    values = values[span[0]:span[1]]
    return sum(values) / len(values) if values else None


def roofline_pct(facts, work, seconds):
    """``work``'s least time on this device over ``seconds``, in %."""
    from benchmark import flops

    peaks = flops.peaks_for(
        facts["device"]["kind"], facts["ctx"]["peaks_table"]
    )
    return 100.0 * flops.roofline_s(work, peaks)[0] / seconds
