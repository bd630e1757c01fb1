"""Rotary position embeddings (RoPE).

Takes explicit global position indices so sequence-parallel shards (each
holding ``seq/sp`` tokens) rotate with their true positions — required by
ring attention where the local sequence index is not the global one.
"""

import math

import jax.numpy as jnp


def rope_frequencies(head_dim: int, theta: float = 10000.0):
    """Inverse frequencies, shape [head_dim // 2], float32."""
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponents)


def yarn_frequencies(head_dim: int, theta: float, factor: float,
                     original_max: int, beta_fast: float = 32.0,
                     beta_slow: float = 1.0):
    """YaRN's inverse frequencies, shape [head_dim // 2], float32: each
    of :func:`rope_frequencies` blended between itself (a pair that
    turns more than ``beta_fast`` times within ``original_max``
    positions keeps its frequency) and itself / ``factor`` (a pair that
    turns less than ``beta_slow`` times is stretched in full), linearly
    in the pair's index between the two pairs where exactly
    ``beta_fast`` and ``beta_slow`` turns fit."""
    def pair_with(turns):
        return head_dim * math.log(
            original_max / (turns * 2 * math.pi)
        ) / (2 * math.log(theta))

    low = max(math.floor(pair_with(beta_fast)), 0)
    high = min(math.ceil(pair_with(beta_slow)), head_dim // 2 - 1)
    ramp = jnp.clip(
        (jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
        / max(high - low, 1e-3), 0.0, 1.0,
    )
    inv_freq = rope_frequencies(head_dim, theta)
    return inv_freq / factor * ramp + inv_freq * (1.0 - ramp)


def yarn_mscale(factor: float, mscale_all_dim: float = 1.0) -> float:
    """``0.1 * mscale_all_dim * ln(factor) + 1``: what YaRN multiplies
    the softmax scale by, squared (1.4159 at factor 64)."""
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale_all_dim * math.log(factor) + 1.0


def apply_rope(x, positions, theta: float = 10000.0, inv_freq=None):
    """Rotate x: [..., seq, heads, head_dim] by positions: [..., seq].

    Uses the half-split convention (first half paired with second half),
    which keeps the op a pair of multiplies + one concat — friendlier to
    XLA fusion than interleaved lanes. ``inv_freq`` [head_dim // 2]: the
    frequencies to rotate by in place of ``theta``'s own (a slice of a
    head rotated by :func:`yarn_frequencies` is ``apply_rope(slice,
    positions, inv_freq=...)``).
    """
    head_dim = x.shape[-1]
    if inv_freq is None:
        inv_freq = rope_frequencies(head_dim, theta)
    # [..., seq, head_dim//2]
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    # broadcast over the heads axis: [..., seq, 1, head_dim//2]
    angles = angles[..., None, :]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    rotated = jnp.concatenate(
        (x1 * cos - x2 * sin, x2 * cos + x1 * sin), axis=-1
    )
    return rotated.astype(x.dtype)
