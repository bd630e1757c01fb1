"""Kernels: device time under ``attn/mla`` in the decode program
(``jit_step``) per traced decode launch, all layers. The scope holds all
of attention: the query and latent projections (``w_qa``, ``w_qb``,
``w_kva``), their norms and the rotation, the absorbed queries, the
gather of the slots' rows, scores, softmax and the weighted sum of
latents, ``w_kvb``'s value half and the output projection ``wo``. A
program without the scope (the parent of the cell, another model) gives
nothing to read."""

from benchmark import latent_scopes


def read(facts):
    s = latent_scopes.per_launch_s(facts, latent_scopes.STEP, "mla")
    return None if s is None else 1e3 * s
