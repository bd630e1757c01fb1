"""Expert layer: device time under ``mlp/experts`` in the decode program
(``jit_step``) per traced decode launch, the 8 expert layers of the
window / full attention pattern model (64 softmax top-8 experts of width
896 a layer). A program without the window scopes gives nothing to
read."""

from benchmark import latent_scopes


def read(facts):
    s = latent_scopes.per_launch_s(facts, latent_scopes.STEP, "experts")
    return None if s is None else 1e3 * s
