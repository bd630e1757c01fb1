"""A layer-pattern step WITH a prediction module, by its own scopes: the
table ``hybrid_scopes`` keeps for the stack (``attn/mla``,
``mlp/router``, ``mlp/experts``, ``mlp/shared``, ``mlp/dense``), the
head (``vocab``) and, apart, everything the module runs, which sits
under ``mtp`` (``mtp/join``, ``mtp/attn/mla``, ``mtp/mlp/experts``,
``mtp/mlp/shared``, ``mtp/mlp/router``, ``mtp/vocab``).
``hybrid_scopes.SCOPES`` cannot name ``mtp``: its table books an op to
the innermost scope alone, and the module's innermost scopes are the
stack's. Pure arithmetic over the event dump a traced run keeps
(``trace_reduce.dump_xplane``).

An op is booked to ``[mtp/]<innermost scope>`` (forward and backward
alike: ``jvp(mtp)/attn/mla/...`` and ``transpose(jvp(mtp))/...`` both
say ``mtp``); an op of the module under none of the inner scopes is
booked to ``mtp`` itself. A program without these scopes books nothing
and ``reduce`` returns None.
"""

import re

from benchmark import trace_reduce

MODULE = "mtp"
# Innermost first.
SCOPES = ("mla", "router", "experts", "shared", "dense", "join", "vocab")
_RE = {
    s: re.compile(r"(?:^|[/(])" + s + r"(?:[/)]|$)")
    for s in SCOPES + (MODULE,)
}


def scope_of(op_name):
    inner = next((s for s in SCOPES if _RE[s].search(op_name)), None)
    if _RE[MODULE].search(op_name):
        return MODULE + "/" + inner if inner else MODULE
    return inner


def reduce(dump, top=10):
    """Seconds of device-op time under each scope, the Pallas kernels'
    share of each, everything under ``mtp`` together, all device-op
    time, and the ``top`` ops by time named ``<scope>:<op>`` (so that a
    breakdown tells the module's ops from the stack's), averaged over
    the device planes that ran anything; None where no op carries these
    scopes."""
    scope_s, kernel_s, op_s, all_s, planes = {}, {}, {}, 0.0, 0
    for lines in dump.get("planes", {}).values():
        rows = lines.get(trace_reduce.OPS_LINE) or []
        if not rows:
            continue
        planes += 1
        for name, _, dur, op_name, category in rows:
            if category in trace_reduce.ENVELOPES:
                continue
            all_s += dur / 1e9
            s = scope_of(op_name)
            key = (s or trace_reduce.scope_of(op_name)) + ":" + (
                trace_reduce.base_name(name)
            )
            op_s[key] = op_s.get(key, 0.0) + dur / 1e9
            if s is None:
                continue
            scope_s[s] = scope_s.get(s, 0.0) + dur / 1e9
            if category == trace_reduce.KERNEL:
                kernel_s[s] = kernel_s.get(s, 0.0) + dur / 1e9
    if not scope_s:
        return None
    ranked = sorted(op_s.items(), key=lambda kv: -kv[1])[:top]
    return {
        "scope_s": {k: v / planes for k, v in scope_s.items()},
        "kernel_s": {k: v / planes for k, v in kernel_s.items()},
        "module_s": sum(
            v for k, v in scope_s.items() if k.split("/")[0] == MODULE
        ) / planes,
        "device_op_s": all_s / planes,
        "device_ops": [[k, v / planes] for k, v in ranked],
    }


# -- what the layer_metrics readers share -------------------------------------


def per_step_s(facts, scope, kernels_only=False, stack=True):
    """Seconds a traced step spends under ``scope`` in the module and,
    unless ``stack`` is False, in the stack (its Pallas kernels alone
    when asked), or None where the run has nothing to read."""
    scopes, trace = facts.get("mtp_scopes"), facts.get("trace")
    if not scopes or not trace or not trace.get("steps"):
        return None
    table = scopes["kernel_s" if kernels_only else "scope_s"]
    total = table.get(MODULE + "/" + scope, 0.0)
    if stack:
        total += table.get(scope, 0.0)
    return total / trace["steps"] if total else None


def rows_mean(facts, steps_key):
    """Mean over the steps ``facts[steps_key]`` names of the rows the
    held experts computed, the stack's and the module's together."""
    from benchmark import hybrid_scopes

    stack = hybrid_scopes.counter_mean(facts, "moe_rows_held", steps_key)
    module = hybrid_scopes.counter_mean(
        facts, "mtp_moe_rows_held", steps_key
    )
    return None if stack is None or module is None else stack + module
