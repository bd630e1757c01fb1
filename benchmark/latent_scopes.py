"""A traced serve run's device time by the latent-attention model's own
scopes (``attn/mla``, ``resid/mhc``, ``mlp/router``, ``mlp/experts``,
``mlp/shared``, ``mlp/dense``), per program: the decode step
(``jit_step``) and the prefill chunk (``jit_prefill``) both carry them.

The dump is labelled by ``sparse_scopes.label`` (two programs share
instruction names; each op's scope comes from the table of the program
it ran in), and :func:`reduce` is that module's arithmetic over this
model's scopes. Its result goes by the same shape, so the accepted
readers of a serve cell's scope table (``serve_expert_ms_per_step``:
``experts``; ``decode_unscoped_ms_per_step``: ``unscoped``) read it
unchanged. A program without these scopes (the parent's, another
model's) books nothing: ``reduce`` returns None and every reader returns
None.
"""

import re

from benchmark import sparse_scopes, trace_reduce

# Innermost first. ``attn/mla`` holds ALL of attention: the query and
# latent projections, the rotation, ``absorb`` / ``scores`` / ``values``
# beneath it, and the output projection.
SCOPES = ("mla", "mhc", "router", "experts", "shared", "dense")
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
    device planes that ran anything. None where no op carries a scope."""
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
        s in prog["scope_s"] for prog in out.values() for s in SCOPES
    ):
        return None
    for prog in out.values():
        prog["launches"] /= planes
        prog["device_op_s"] /= planes
        prog["scope_s"] = {k: v / planes for k, v in prog["scope_s"].items()}
    return out


# -- what the layer_metrics readers share -------------------------------------


def per_launch_s(facts, program, scope):
    """Seconds a traced launch of ``program`` spends under ``scope``."""
    prog = (facts.get("sparse_scopes") or {}).get(program)
    if not prog or not prog.get("launches"):
        return None
    s = (prog.get("scope_s") or {}).get(scope)
    return s / prog["launches"] if s else None


def traced_prefill_mean(facts, count):
    """Mean of a ``serving.step`` count over the steps that ran a prefill
    chunk and ended inside the profiler session: the chunks whose device
    time ``per_launch_s(facts, PREFILL, ...)`` reads."""
    lo, hi = facts.get("traced_window") or (None, None)
    if lo is None:
        return None
    values = [
        s["attrs"][count] for s in facts.get("spans") or ()
        if s["name"] == "serving.step" and s.get("dur_s") is not None
        and lo <= s["ts"] + s["dur_s"] <= hi
        and s["attrs"].get("prefill_tokens") and count in s["attrs"]
    ]
    return sum(values) / len(values) if values else None
