"""The fused kernels around a KDA layer's scan (ops/kda_tail.py), run in
interpret mode on the CPU at the published head size, against the
``jax.numpy`` lines of ``models/hybrid._kda_apply`` they replace on a
TPU: forward and every gradient, the shared parameters' (``d_taps``,
``d_a_log``, ``d_dt_bias``, ``d_o_norm``) included."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import hybrid
from dlrover_tpu.ops import kda_tail
from dlrover_tpu.ops.norms import rms_norm

DIM = kda_tail.LANES
HEADS = 2
BF16 = jnp.bfloat16


def _normal(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def _branch(scale):
    """(kernels, jax.numpy lines, inputs' makers, output dtype)."""
    def lines(x, taps):
        y = jax.nn.silu(hybrid._short_conv(x, taps))
        return y if scale is None else hybrid._l2norm(y) * scale

    def args(keys, shape):
        return (_normal(keys[0], shape, BF16),
                _normal(keys[1], (4, HEADS, DIM)) / 2)

    return (
        lambda x, taps: kda_tail.branch(x, taps, scale, interpret=True),
        lines, args, jnp.float32,
    )


def _decay_gate():
    def lines(z, a_log, dt_bias):
        return -jnp.exp(a_log)[:, None, None] * jax.nn.softplus(
            z.astype(jnp.float32) + dt_bias[:, None, :]
        )

    def args(keys, shape):
        return (2 * _normal(keys[0], shape, BF16),
                jnp.log(jax.random.uniform(keys[1], (HEADS,), minval=1.0,
                                           maxval=16.0)),
                _normal(keys[2], (HEADS, DIM)))

    return (
        lambda *a: kda_tail.decay_gate(*a, interpret=True), lines, args,
        jnp.float32,
    )


def _gated_norm():
    def lines(o, z, o_norm):
        gate = jax.nn.sigmoid(z.astype(jnp.float32))
        return (rms_norm(o, o_norm) * gate).astype(BF16)

    def args(keys, shape):
        return (3 * _normal(keys[0], shape), 2 * _normal(keys[1], shape, BF16),
                0.1 * _normal(keys[2], (DIM,)))

    return (
        lambda *a: kda_tail.gated_norm(*a, BF16, interpret=True), lines,
        args, BF16,
    )


CASES = {
    "branch": _branch(None),                    # v: no norm
    "branch_normed": _branch(DIM ** -0.5),      # q (k: the scale is 1)
    "decay_gate": _decay_gate(),
    "gated_norm": _gated_norm(),
}
# (rows, batch) at a tile of 128 rows walked 32 at a time: two whole
# tiles (the convolution's three rows cross the tile's edge, its
# transpose crosses it the other way); not a whole number of tiles, nor
# of passes (padded); shorter than a tile, a batch of two.
SHAPES = [(256, 1), (300, 1), (70, 2)]


@pytest.mark.parametrize("rows,batch", SHAPES)
@pytest.mark.parametrize("case", sorted(CASES))
def test_a_kernel_matches_the_jax_numpy_lines(monkeypatch, case, rows, batch):
    monkeypatch.setattr(kda_tail, "TILE", 128)
    monkeypatch.setattr(kda_tail, "ROWS", 32)
    kernels, lines, make, out_dtype = CASES[case]
    keys = jax.random.split(jax.random.key(rows), 4)
    shape = (batch, HEADS, rows, DIM)
    args = make(keys, shape)
    cot = _normal(keys[3], shape, out_dtype)

    def pulled(fn):
        out, pull = jax.vjp(fn, *args)
        return (out,) + pull(cot)

    got, want = jax.jit(pulled, static_argnums=0)(kernels), pulled(lines)
    assert len(got) == len(want) == 1 + len(args)
    for a, r in zip(got, want):
        assert a.shape == r.shape and a.dtype == r.dtype
        # float32 rounding by the tensor's scale; where the lines round
        # to bfloat16 so do the kernels, and a last place may differ.
        last_place = 2.0 ** -7 if a.dtype == BF16 else 3e-5
        a, r = (np.asarray(x.astype(jnp.float32)) for x in (a, r))
        assert np.all(np.isfinite(a))
        scale = max(1.0, float(np.max(np.abs(r))))
        np.testing.assert_allclose(a, r, rtol=0, atol=last_place * scale)
        assert np.linalg.norm(a - r) <= 1e-3 * np.linalg.norm(r)
