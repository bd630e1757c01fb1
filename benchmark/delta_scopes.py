"""A traced serve run's device time by the delta-rule / full-attention
model's own scopes (``attn/delta``, ``attn/conv``, ``attn/full``,
``state``, ``state/snapshot``, ``state/restore``, beside ``attn``, ``mlp``
and ``vocab``), per program: ``benchmark/sala_scopes.py``'s arithmetic
over this model's scope names (the accepted scope lists are fixed and
cannot name them). Its result goes by the same shape, so the accepted
readers of a serve cell's scope table read it unchanged. A program
without these scopes (the parent's, another model's) books nothing:
:func:`reduce` returns None and every reader returns None.
"""

import re

from benchmark import sala_scopes, sparse_scopes, trace_reduce

# Innermost first. ``attn/delta`` holds the rule's core alone (the chunk
# algebra, or the state's one-token update and reads); ``attn/conv`` the
# three short convolutions and their taps; ``attn/full`` the paged
# attention; the projections, norms and gates around them stay under
# ``attn``. ``state`` holds what lands a slot's state after the layer
# loop.
SCOPES = ("restore", "snapshot", "state", "delta", "conv", "full")
OWN = ("delta", "conv", "full")
_RE = {
    s: re.compile(r"(?:^|[/(])" + s + r"(?:[/)]|$)") for s in SCOPES
}
STEP, PREFILL = sparse_scopes.STEP, sparse_scopes.PREFILL
traced_chunks = sala_scopes.traced_chunks
per_launch_s = sala_scopes.per_launch_s


def scope_of(op_name):
    for s in SCOPES:
        if _RE[s].search(op_name):
            return s
    return None


def reduce(dump):
    """``sala_scopes.reduce`` under this model's scopes: per program,
    launches in the dump, seconds of device-op time under each scope, and
    all its device-op time, averaged over the device planes that ran
    anything. None where no op carries one of the model's own scopes."""
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


def is_cell(facts):
    """A run of this model's programs (the readers that call an accepted
    reader's function say so first)."""
    return "delta_decode" in (facts.get("kv_stats") or {})
