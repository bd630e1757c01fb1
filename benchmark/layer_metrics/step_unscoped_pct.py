"""Share of the traced steps' device-op time under none of the program's
named scopes (attn, mlp, vocab, optimizer): what a scope table cannot
yet account for."""


def read(facts):
    trace = facts.get("trace")
    if not trace or trace.get("unscoped_share") is None:
        return None
    return 100.0 * trace["unscoped_share"]
