"""A traced serve run's device time by the sparse model's own scopes
(``attn/index``, ``attn/select``, ``attn/sparse``, ``mlp/router``,
``mlp/experts``), per program: the decode step (``jit_step``) and the
prefill chunk (``jit_prefill``) both carry them.

``trace_reduce.dump_xplane`` names an op by its instruction and looks
its scope up in ONE table, but two programs share instruction names
(``fusion.12`` is in both). ``label`` therefore takes a dump made with
no table and fills each op's scope from the table of the program it ran
in, found by the "XLA Modules" event that encloses it in time;
``reduce`` is then pure arithmetic, as ``hybrid_scopes.reduce`` is. A
program without these scopes (the parent's, a dense model's) books
nothing: ``reduce`` returns None and every reader returns None.
"""

import bisect
import re

from benchmark import trace_reduce

# Innermost first.
SCOPES = ("index", "select", "sparse", "router", "experts")
_RE = {
    s: re.compile(r"(?:^|[/(])" + s + r"(?:[/)]|$)") for s in SCOPES
}
STEP, PREFILL = "jit_step", "jit_prefill"


def scope_of(op_name):
    for s in SCOPES:
        if _RE[s].search(op_name):
            return s
    return None


def _programs(lines):
    """A plane's "XLA Modules" events by start, and ``program_at(t)``:
    the name of the program whose event encloses time ``t`` (or None)."""
    modules = sorted(
        lines.get(trace_reduce.MODULES_LINE) or [], key=lambda r: r[1]
    )
    starts = [m[1] for m in modules]

    def program_at(t):
        i = bisect.bisect_right(starts, t) - 1
        if i < 0 or t >= modules[i][1] + modules[i][2]:
            return None
        return trace_reduce.module_name(modules[i][0])

    return modules, program_at


def label(dump, tables):
    """Fill the scope column of ``dump``'s op rows in place: ``tables``
    is program name (``jit_step``) -> ``scopes_from_hlo`` of its text."""
    for lines in dump.get("planes", {}).values():
        _, program_at = _programs(lines)
        for row in lines.get(trace_reduce.OPS_LINE) or []:
            table = tables.get(program_at(row[1]))
            if table:
                row[3] = table.get(row[0], "")
    return dump


def reduce(dump):
    """Per program: launches in the dump, seconds of device-op time
    under each scope, and all its device-op time, averaged over the
    device planes that ran anything. None where no op carries a scope."""
    out, planes = {}, 0
    for lines in dump.get("planes", {}).values():
        rows = lines.get(trace_reduce.OPS_LINE) or []
        modules, program_at = _programs(lines)
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
        s in prog["scope_s"] for prog in out.values() for s in SCOPES
    ):
        return None
    for prog in out.values():
        prog["launches"] /= planes
        prog["device_op_s"] /= planes
        prog["scope_s"] = {k: v / planes for k, v in prog["scope_s"].items()}
    return out


# -- what the layer_metrics readers share -------------------------------------


def per_decode_step_s(facts, scopes):
    """Seconds a traced decode step spends under ``scopes`` together."""
    step = (facts.get("sparse_scopes") or {}).get(STEP)
    if not step or not step["launches"]:
        return None
    total = sum(step["scope_s"].get(s, 0.0) for s in scopes)
    return total / step["launches"] if total else None


def traced_decode_mean(facts, count):
    """Mean of a ``serving.step`` count over the decoding steps that
    ended inside the profiler session (``traced_window``, epoch
    seconds): the steps whose device time ``per_decode_step_s`` reads."""
    lo, hi = facts.get("traced_window") or (None, None)
    if lo is None:
        return None
    values = [
        s["attrs"][count] for s in facts.get("spans") or ()
        if s["name"] == "serving.step" and s.get("dur_s") is not None
        and lo <= s["ts"] + s["dur_s"] <= hi
        and s["attrs"].get("n_decoding") and count in s["attrs"]
    ]
    return sum(values) / len(values) if values else None


def roofline_pct(facts, work, seconds):
    from benchmark import flops

    peaks = flops.peaks_for(
        facts["device"]["kind"], facts["ctx"]["peaks_table"]
    )
    return 100.0 * flops.roofline_s(work, peaks)[0] / seconds
