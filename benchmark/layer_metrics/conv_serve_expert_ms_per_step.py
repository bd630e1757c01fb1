"""Expert layer: device time under ``mlp/experts`` in the decode program
(``jit_step``) per traced decode launch, the 8 expert layers of
``lfm2-24b-a2b`` (``serve_expert_ms_per_step``'s quantity for the
convolution / attention pattern model, whose scope table
``benchmark/conv_scopes.py`` makes; the accepted reader's list is pinned
to one cell: PERF.md section 7). A program without the scope gives
nothing to read."""

from benchmark import latent_scopes


def read(facts):
    s = latent_scopes.per_launch_s(facts, latent_scopes.STEP, "experts")
    return None if s is None else 1e3 * s
