"""Expert layer: device time under ``mlp/experts`` in the PREFILL program
(``jit_prefill``) per traced chunk launch, the 8 expert layers: a
512-row chunk reaches all 64 experts of a layer (~64 rows an expert). A
program without the window scopes gives nothing to read."""

from benchmark import latent_scopes


def read(facts):
    s = latent_scopes.per_launch_s(facts, latent_scopes.PREFILL, "experts")
    return None if s is None else 1e3 * s
