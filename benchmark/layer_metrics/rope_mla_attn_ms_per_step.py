"""Kernels: device time of the flash-attention Pallas kernels under
``attn/mla`` and ``mtp/attn/mla`` (forward, dq, dk/dv at q/k 256 and v
256, 20 heads: every block's rotary latent attention, the prediction
module's among them) per traced step."""

from benchmark import mtp_scopes


def read(facts):
    s = mtp_scopes.per_step_s(facts, "mla", kernels_only=True)
    return None if s is None else 1e3 * s
