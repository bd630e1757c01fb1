"""Kernels: device time under ``attn/gqa`` in the decode program
(``jit_step``) per traced decode launch, the attention layers of a
convolution / attention pattern model (64-wide heads, K and V held
flat). The scope holds all of attention: the three projections, the
per-head norms and the rotation, the read of the slots' rows (a gathered
view, or a pool kernel: ``kv_stats()["pool_attention"]`` says which),
scores, softmax, the weighted sum and the output projection. A program
without the scope gives nothing to read."""

from benchmark import latent_scopes


def read(facts):
    s = latent_scopes.per_launch_s(facts, latent_scopes.STEP, "gqa")
    return None if s is None else 1e3 * s
