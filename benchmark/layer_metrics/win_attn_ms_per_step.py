"""Kernels: device time under ``attn/window`` in the decode program
(``jit_step``) per traced decode launch: the sliding-window layers of a
window / full attention pattern model (``models/window_lm.py``). The
scope holds all of those layers' attention: the three projections, the
rotation, the read of the band of the window group's rows (a pool
kernel, or a gathered view: ``kv_stats()["window_decode_attention"]``
says which), scores, softmax, the weighted sum and the output
projection. A program without the scope gives nothing to read."""

from benchmark import latent_scopes


def read(facts):
    s = latent_scopes.per_launch_s(facts, latent_scopes.STEP, "window")
    return None if s is None else 1e3 * s
