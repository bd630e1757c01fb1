"""Kernels: device time under ``attn/conv`` in the decode program
(``jit_step``) per traced decode launch, the convolution layers: the
input projection and the gate ``B * X`` (``in``), the three-tap filter
over the slot's state and the gate ``C`` (``filter``), the output
projection (``out``). A program without the scope gives nothing to
read."""

from benchmark import latent_scopes


def read(facts):
    s = latent_scopes.per_launch_s(facts, latent_scopes.STEP, "conv")
    return None if s is None else 1e3 * s
