"""Residual: device time under ``resid/mhc`` in the decode program per
traced decode launch, all layers: two sublayers a layer, each the norm
over the 4 x 3,584 streams, the ``[14,336, 24]`` map projection, 20
Sinkhorn rounds on ``[slots, 4, 4]``, the read ``H_pre X`` and the write
``H_res X + H_post^T y``. Latency, not bytes (the streams of 32 tokens
are 1.8 MB): no roofline share."""

from benchmark import latent_scopes


def read(facts):
    s = latent_scopes.per_launch_s(facts, latent_scopes.STEP, "mhc")
    return None if s is None else 1e3 * s
