"""Kernels: device time under ``attn/full`` in the decode program
(``jit_step``) per traced decode launch: the full-attention layers of a
window / full attention pattern model (YaRN's rotation, every row of the
full group below the slot's fill). The scope holds all of those layers'
attention, projections and output projection included. A program
without the scope gives nothing to read."""

from benchmark import latent_scopes


def read(facts):
    s = latent_scopes.per_launch_s(facts, latent_scopes.STEP, "full")
    return None if s is None else 1e3 * s
