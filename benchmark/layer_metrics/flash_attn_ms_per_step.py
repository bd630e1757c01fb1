"""Kernels: summed device time of the flash-attention Pallas kernels
(forward, dq, dk/dv, all layers) per traced step. In the trace the three
are custom calls with target ``tpu_custom_call`` named ``attn.N`` after
the scope they sit in; nothing else in the dense step is such a call,
but the three cannot be told from each other by name (PERF.md, Open
questions)."""


def read(facts):
    trace = facts.get("trace")
    if not trace or "attn" not in trace.get("kernel_s", {}):
        return None
    return 1e3 * trace["kernel_s"]["attn"] / trace["steps"]
