"""Serving engine: the prefill-chunk program's share of the device time
of all the engine's programs in the traced seconds (the rest is the
decode step): how far prompts compete with decoding for the chip."""


def read(facts):
    trace = facts.get("trace")
    modules = (trace or {}).get("module_s") or {}
    total = sum(modules.values())
    prefill = sum(v for k, v in modules.items() if "prefill" in k)
    if not total or not prefill:
        return None
    return 100.0 * prefill / total
