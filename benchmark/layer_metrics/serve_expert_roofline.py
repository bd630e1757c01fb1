"""Expert layer: the least time the chip could take to read the
weights of the experts a decode step hits (``flops_keye.expert_step``
at the traced steps' mean ``experts_hit`` and ``n_decoding``;
memory-bound: a handful of rows an expert) over the time under
``mlp/experts``."""

from benchmark import flops_keye, sparse_scopes


def read(facts):
    s = sparse_scopes.per_decode_step_s(facts, ("experts",))
    hit = sparse_scopes.traced_decode_mean(facts, "experts_hit")
    tokens = sparse_scopes.traced_decode_mean(facts, "n_decoding")
    if s is None or hit is None or tokens is None:
        return None
    work = flops_keye.expert_step(facts["ctx"]["config"], hit, tokens)
    return sparse_scopes.roofline_pct(facts, work, s)
