"""A traced serve run's device time by the lightning / block-sparse
model's own scopes (``attn/lightning``, ``attn/select``, ``attn/sparse``,
``state``, ``state/snapshot``, ``state/restore``, beside ``attn``,
``mlp`` and ``vocab``), per program: ``benchmark/conv_scopes.py``'s
arithmetic over this model's scope names (``conv_scopes.SCOPES`` and
``window_scopes.SCOPES`` are fixed lists and cannot name them). Its
result goes by the same shape, so the accepted readers of a serve cell's
scope table read it unchanged, and ``latent_scopes.per_launch_s`` serves
this model's readers. A program without these scopes (the parent's,
another model's) books nothing: :func:`reduce` returns None and every
reader returns None.
"""

import re

from benchmark import sparse_scopes, trace_reduce, window_scopes

# Innermost first. ``attn/lightning`` holds the mixer's core (the state's
# update and read, or the chunk's products); the projections, norms,
# rotation and gate around it stay under ``attn``. ``state`` holds what
# lands a slot's state after the layer loop.
SCOPES = ("restore", "snapshot", "state", "lightning", "select", "sparse")
OWN = ("lightning", "select", "sparse")
_RE = {
    s: re.compile(r"(?:^|[/(])" + s + r"(?:[/)]|$)") for s in SCOPES
}
STEP, PREFILL = sparse_scopes.STEP, sparse_scopes.PREFILL
traced_chunks = window_scopes.traced_chunks


def scope_of(op_name):
    for s in SCOPES:
        if _RE[s].search(op_name):
            return s
    return None


def reduce(dump):
    """Per program: launches in the dump, seconds of device-op time
    under each scope, and all its device-op time, averaged over the
    device planes that ran anything. None where no op carries one of the
    model's own scopes."""
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
        s in prog["scope_s"] for prog in out.values() for s in OWN
    ):
        return None
    for prog in out.values():
        prog["launches"] /= planes
        prog["device_op_s"] /= planes
        prog["scope_s"] = {k: v / planes for k, v in prog["scope_s"].items()}
    return out


def per_launch_s(facts, program, scopes):
    """Seconds a traced launch of ``program`` spends under ``scopes``
    together (None where the first of them booked nothing)."""
    prog = (facts.get("sparse_scopes") or {}).get(program)
    if not prog or not prog.get("launches"):
        return None
    booked = prog.get("scope_s") or {}
    if not booked.get(scopes[0]):
        return None
    return sum(booked.get(s, 0.0) for s in scopes) / prog["launches"]


def is_cell(facts):
    """A run of this model's programs (the readers that call an accepted
    reader's function say so first)."""
    return "lightning_decode" in (facts.get("kv_stats") or {})
