"""Expert layer: the least time the chip could take to read the weights
of the experts a decode step hits in its 8 expert layers
(``flops_mellum2.expert_step`` at the traced steps' mean ``experts_hit``
and ``n_decoding``; memory-bound: a handful of rows an expert) over the
time under ``mlp/experts`` in the decode program."""

from benchmark import flops_mellum2, latent_scopes, sparse_scopes


def read(facts):
    s = latent_scopes.per_launch_s(facts, latent_scopes.STEP, "experts")
    hit = sparse_scopes.traced_decode_mean(facts, "experts_hit")
    tokens = sparse_scopes.traced_decode_mean(facts, "n_decoding")
    if s is None or hit is None or tokens is None:
        return None
    work = flops_mellum2.expert_step(facts["ctx"]["config"], hit, tokens)
    return sparse_scopes.roofline_pct(facts, work, s)
