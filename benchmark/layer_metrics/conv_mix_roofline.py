"""Kernels: the least time the chip could take to read the convolution
mixers' two projections and the decoding slots' state once a convolution
layer (``flops_lfm2.conv_mix_step`` at the traced steps' mean
``n_decoding``; memory-bound on the weights) over the time under
``attn/conv`` in the decode program."""

from benchmark import flops_lfm2, latent_scopes, sparse_scopes


def read(facts):
    s = latent_scopes.per_launch_s(facts, latent_scopes.STEP, "conv")
    tokens = sparse_scopes.traced_decode_mean(facts, "n_decoding")
    if s is None or tokens is None:
        return None
    work = flops_lfm2.conv_mix_step(facts["ctx"]["config"], tokens)
    return sparse_scopes.roofline_pct(facts, work, s)
