"""Fused blockwise cross-entropy vs the dense reference loss.

Ground truth is ``llama.cross_entropy`` over explicitly materialized
logits — loss AND grads (dx, dw) must match for both fused
implementations (the vocab-scan "xla" and the row-"chunked" one), in
float32 and in the bfloat16 the chip feeds them, including ragged
vocab sizes (padding blocks), masks, and the z-loss term.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import llama
from dlrover_tpu.ops.fused_ce import fused_cross_entropy


def _dense_loss(x, w, targets, mask=None, z_weight=1e-4):
    logits = jnp.matmul(x, w, preferred_element_type=jnp.float32)
    return llama.cross_entropy(logits, targets, mask, z_weight=z_weight)


def _rand(key, b=2, s=12, d=32, v=300):
    kx, kw, kt, km = jax.random.split(key, 4)
    x = jax.random.normal(kx, (b, s, d), jnp.float32)
    w = jax.random.normal(kw, (d, v), jnp.float32) / np.sqrt(d)
    targets = jax.random.randint(kt, (b, s), 0, v)
    mask = (jax.random.uniform(km, (b, s)) > 0.3).astype(jnp.int32)
    return x, w, targets, mask


# Gradients leave the fused paths through a cast of d(loss)/d(logits)
# to the input dtype: in bfloat16 they carry its 8 bits.
_GRAD_TOL = {
    "float32": dict(rtol=1e-4, atol=1e-6),
    "bfloat16": dict(rtol=2e-2, atol=2e-4),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("impl", ["xla", "chunked"])
@pytest.mark.parametrize("mask_on", [False, True])
def test_loss_and_grads_match_dense(impl, mask_on, dtype):
    x, w, targets, mask = _rand(jax.random.key(0))
    x, w = x.astype(dtype), w.astype(dtype)
    mask = mask if mask_on else None

    ref_loss, (ref_dx, ref_dw) = jax.value_and_grad(
        _dense_loss, argnums=(0, 1)
    )(x, w, targets, mask)

    def fused(x, w):
        return fused_cross_entropy(
            x, w, targets, mask, block_v=128, block_rows=8, impl=impl
        )

    loss, (dx, dw) = jax.value_and_grad(fused, argnums=(0, 1))(x, w)

    assert dx.dtype == x.dtype and dw.dtype == w.dtype
    f32 = lambda a: np.asarray(a, np.float32)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    np.testing.assert_allclose(f32(dx), f32(ref_dx), **_GRAD_TOL[dtype])
    np.testing.assert_allclose(f32(dw), f32(ref_dw), **_GRAD_TOL[dtype])


def test_unknown_impl_is_refused():
    """The Pallas CE is gone (30.61 ms against dense 20.56 on the v5e):
    its name, like any other unknown one, raises; it does not run the
    vocab scan under another label."""
    x, w, targets, _ = _rand(jax.random.key(0))
    with pytest.raises(ValueError, match="pallas"):
        fused_cross_entropy(x, w, targets, impl="pallas")


@pytest.mark.parametrize("impl", ["xla", "chunked"])
def test_ragged_vocab_and_tokens(impl):
    # v=300 is not a multiple of block_v=128 (pad block) and b*s=21 is
    # not a multiple of 8 (pad rows) — both must be invisible.
    x, w, targets, _ = _rand(jax.random.key(1), b=3, s=7, d=16, v=300)
    ref = _dense_loss(x, w, targets)
    got = fused_cross_entropy(
        x, w, targets, block_v=128, block_rows=8, impl=impl
    )
    np.testing.assert_allclose(got, ref, rtol=1e-5)


@pytest.mark.parametrize("impl", ["xla", "chunked"])
def test_zero_mask_is_finite(impl):
    x, w, targets, _ = _rand(jax.random.key(2))
    mask = jnp.zeros(targets.shape, jnp.int32)
    loss = fused_cross_entropy(x, w, targets, mask, impl=impl)
    assert bool(jnp.isfinite(loss))
    assert float(loss) == 0.0


def test_loss_fn_uses_fused_and_matches_unfused(monkeypatch):
    config = llama.tiny_config()
    params, _ = llama.init_params(config, jax.random.key(0))
    tokens = jax.random.randint(
        jax.random.key(3), (2, 17), 0, config.vocab_size
    )
    batch = {"tokens": tokens}

    monkeypatch.setenv("DLROVER_TPU_FUSED_CE", "on")
    fused_loss, fused_m = llama.loss_fn(config, params, batch)
    monkeypatch.setenv("DLROVER_TPU_FUSED_CE", "off")
    ref_loss, ref_m = llama.loss_fn(config, params, batch)
    np.testing.assert_allclose(fused_loss, ref_loss, rtol=1e-5)
    np.testing.assert_allclose(fused_m["ce"], ref_m["ce"], rtol=1e-5)

    unfused_grads = jax.grad(
        lambda p: llama.loss_fn(config, p, batch)[0]
    )(params)
    monkeypatch.setenv("DLROVER_TPU_FUSED_CE", "on")
    fused_grads = jax.grad(
        lambda p: llama.loss_fn(config, p, batch)[0]
    )(params)
    # lm_head grads must agree between paths
    np.testing.assert_allclose(
        fused_grads["lm_head"], unfused_grads["lm_head"], rtol=1e-4,
        atol=1e-6,
    )


def test_fused_gate_respects_tp_mesh():
    # Under a tp>1 mesh (vocab sharded), loss_fn must choose unfused.
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh

    config = llama.tiny_config()
    mesh = build_mesh(MeshConfig(dp=2, tp=4))
    with mesh:
        assert not llama._fused_ce_applicable(config)
    mesh2 = build_mesh(MeshConfig(dp=8))
    with mesh2:
        assert llama._fused_ce_applicable(config)
    assert llama._fused_ce_applicable(config)
