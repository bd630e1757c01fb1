"""A traced serve run's device time by the window / full attention
pattern model's own scopes (``attn/full``, ``attn/window``,
``mlp/router``, ``mlp/experts``, ``vocab``), per program:
``benchmark/conv_scopes.py``'s arithmetic over this model's scope names
(``conv_scopes.SCOPES`` and ``latent_scopes.SCOPES`` are fixed lists and
cannot name ``full`` and ``window``). Its result goes by the same shape,
so the accepted readers of a serve cell's scope table read it unchanged,
and ``latent_scopes.per_launch_s`` serves this model's readers. A
program without these scopes (the parent's, another model's) books
nothing: :func:`reduce` returns None and every reader returns None.

Beside it, what the chunk readers need of the step spans:
:func:`traced_chunks`, the ``(start, n_valid)`` of every prefill chunk
that ended inside the profiler session.
"""

import re

from benchmark import sparse_scopes, trace_reduce

# Innermost first. ``attn/full`` and ``attn/window`` hold ALL of a
# layer's attention: projections, rotation, the read of the group's rows
# (a pool kernel or a gathered view), scores, values and the output
# projection.
SCOPES = ("window", "full", "router", "experts", "vocab")
_RE = {
    s: re.compile(r"(?:^|[/(])" + s + r"(?:[/)]|$)") for s in SCOPES
}
STEP, PREFILL = sparse_scopes.STEP, sparse_scopes.PREFILL


def scope_of(op_name):
    for s in SCOPES:
        if _RE[s].search(op_name):
            return s
    return None


def reduce(dump):
    """Per program: launches in the dump, seconds of device-op time
    under each scope, and all its device-op time, averaged over the
    device planes that ran anything. None where no op carries one of the
    two attention scopes."""
    out, planes = {}, 0
    for lines in dump.get("planes", {}).values():
        rows = lines.get(trace_reduce.OPS_LINE) or []
        modules, program_at = sparse_scopes._programs(lines)
        if not rows or not modules:
            continue
        planes += 1
        for m in modules:
            prog = out.setdefault(
                trace_reduce.module_name(m[0]),
                {"launches": 0, "scope_s": {}, "device_op_s": 0.0},
            )
            prog["launches"] += 1
        for _, start, dur, op_name, category in rows:
            prog = out.get(program_at(start))
            if category in trace_reduce.ENVELOPES or prog is None:
                continue
            prog["device_op_s"] += dur / 1e9
            s = scope_of(op_name) or trace_reduce.scope_of(op_name)
            prog["scope_s"][s] = prog["scope_s"].get(s, 0.0) + dur / 1e9
    if not planes or not any(
        s in prog["scope_s"] for prog in out.values()
        for s in ("window", "full")
    ):
        return None
    for prog in out.values():
        prog["launches"] /= planes
        prog["device_op_s"] /= planes
        prog["scope_s"] = {k: v / planes for k, v in prog["scope_s"].items()}
    return out


def traced_chunks(facts):
    """``(start, n_valid)`` of every prefill chunk whose step ended
    inside the profiler session: the chunks whose device time
    ``latent_scopes.per_launch_s(facts, PREFILL, ...)`` reads."""
    lo, hi = facts.get("traced_window") or (None, None)
    if lo is None:
        return []
    return [
        (s["attrs"]["prefill_kv_rows"] - s["attrs"]["prefill_tokens"],
         s["attrs"]["prefill_tokens"])
        for s in facts.get("spans") or ()
        if s["name"] == "serving.step" and s.get("dur_s") is not None
        and lo <= s["ts"] + s["dur_s"] <= hi
        and s["attrs"].get("prefill_tokens")
        and "prefill_kv_rows" in s["attrs"]
    ]
