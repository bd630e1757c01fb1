"""Dropless (grouped-matmul) MoE vs exact references.

Ground truth is a straightforward per-token dense computation: every
token runs its top-k experts' FFNs in full, no capacity, no drops.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import llama
from dlrover_tpu.models import moe as moe_lib


def _weights(key, d=16, f=32, e=4):
    kr, kg, ku, kd = jax.random.split(key, 4)
    router = jax.random.normal(kr, (d, e), jnp.float32)
    w_gate = jax.random.normal(kg, (e, d, f), jnp.float32) / np.sqrt(d)
    w_up = jax.random.normal(ku, (e, d, f), jnp.float32) / np.sqrt(d)
    w_down = jax.random.normal(kd, (e, f, d), jnp.float32) / np.sqrt(f)
    return router, w_gate, w_up, w_down


def _dense_reference(x, router, w_gate, w_up, w_down, top_k):
    """Every token through its top-k experts, full FFN, no capacity."""
    b, s, d = x.shape
    logits = jnp.einsum("bsd,de->bse", x, router)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    # run all experts densely, then select
    h = jnp.einsum("bsd,edf->bsef", x, w_gate)
    u = jnp.einsum("bsd,edf->bsef", x, w_up)
    ffn = jnp.einsum("bsef,efd->bsed", jax.nn.silu(h) * u, w_down)
    out = jnp.zeros_like(x)
    for k in range(top_k):
        sel = jnp.take_along_axis(
            ffn, experts[..., k][..., None, None], axis=2
        )[:, :, 0]
        out = out + gates[..., k][..., None] * sel
    return out


@pytest.mark.parametrize("top_k", [1, 2])
def test_dropless_matches_dense_reference(top_k):
    x = jax.random.normal(jax.random.key(0), (2, 12, 16), jnp.float32)
    router, wg, wu, wd = _weights(jax.random.key(1))
    ref = _dense_reference(x, router, wg, wu, wd, top_k)
    out, metrics = moe_lib.moe_mlp_dropless(
        x, router, wg, wu, wd, top_k=top_k
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5
    )
    assert float(metrics.dropped_fraction) == 0.0


def test_dropless_grads_match_dense_reference():
    x = jax.random.normal(jax.random.key(2), (2, 8, 16), jnp.float32)
    router, wg, wu, wd = _weights(jax.random.key(3))

    def loss_ref(wg, wd):
        return jnp.sum(
            jnp.square(_dense_reference(x, router, wg, wu, wd, 2))
        )

    def loss_drop(wg, wd):
        out, _ = moe_lib.moe_mlp_dropless(x, router, wg, wu, wd, top_k=2)
        return jnp.sum(jnp.square(out))

    g_ref = jax.grad(loss_ref, argnums=(0, 1))(wg, wd)
    g_drop = jax.grad(loss_drop, argnums=(0, 1))(wg, wd)
    for a, b in zip(g_ref, g_drop):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5
        )


def test_gshard_at_infinite_capacity_matches_dropless():
    """With capacity -> inf, GShard drops nothing and both paths compute
    the same renormalized top-k mixture."""
    x = jax.random.normal(jax.random.key(4), (2, 10, 16), jnp.float32)
    router, wg, wu, wd = _weights(jax.random.key(5))
    out_g, m_g = moe_lib.moe_mlp(
        x, router, wg, wu, wd, top_k=2, capacity_factor=100.0
    )
    out_d, _ = moe_lib.moe_mlp_dropless(x, router, wg, wu, wd, top_k=2)
    assert float(m_g.dropped_fraction) == 0.0
    np.testing.assert_allclose(
        np.asarray(out_g), np.asarray(out_d), rtol=2e-4, atol=2e-5
    )


def test_model_moe_impl_resolution():
    """auto follows the measured crossover: gshard at the default
    capacity factor, dropless at capacity >= 2.0 on a single device
    (ADVICE r3: the global-argsort core must never see a GSPMD-sharded
    batch); explicit dropless maps to the mesh-appropriate variant."""
    cfg = llama.tiny_config(n_experts=4)
    assert llama._moe_resolve_impl(cfg) == "gshard"  # cap 1.25 default
    hi_cap = llama.tiny_config(n_experts=4, capacity_factor=2.0)
    assert llama._moe_resolve_impl(hi_cap) == "dropless"  # no mesh
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh

    with build_mesh(MeshConfig(ep=2, dp=4)):
        assert llama._moe_resolve_impl(cfg) == "gshard"
        assert llama._moe_resolve_impl(hi_cap) == "gshard"
    with build_mesh(MeshConfig(dp=8)):
        assert llama._moe_resolve_impl(cfg) == "gshard"
    exp = llama.tiny_config(n_experts=4, moe_impl="dropless")
    with build_mesh(MeshConfig(ep=2, dp=4)):
        assert llama._moe_resolve_impl(exp) == "dropless_ep"
    with build_mesh(MeshConfig(dp=8)):
        assert llama._moe_resolve_impl(exp) == "dropless_sharded"
    assert llama._moe_resolve_impl(exp) == "dropless"
    assert llama._moe_resolve_impl(
        llama.tiny_config(n_experts=4, moe_impl="gshard")
    ) == "gshard"


def test_dropless_ep_matches_dense_reference():
    """The ragged-all-to-all expert-parallel dropless path computes the
    same mixture as the dense reference, on a real ep mesh."""
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh

    x = jax.random.normal(jax.random.key(6), (8, 8, 16), jnp.float32)
    router, wg, wu, wd = _weights(jax.random.key(7))
    ref = _dense_reference(x, router, wg, wu, wd, 2)
    mesh = build_mesh(MeshConfig(dp=2, ep=4))
    with mesh:
        out, metrics = jax.jit(
            lambda x: moe_lib.moe_mlp_dropless_ep(
                x, router, wg, wu, wd, mesh, top_k=2
            )
        )(x)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5
    )
    assert float(metrics.dropped_fraction) == 0.0


def test_dropless_sharded_matches_dense_reference():
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh

    x = jax.random.normal(jax.random.key(8), (8, 6, 16), jnp.float32)
    router, wg, wu, wd = _weights(jax.random.key(9))
    ref = _dense_reference(x, router, wg, wu, wd, 2)
    mesh = build_mesh(MeshConfig(dp=8))
    with mesh:
        out, _ = jax.jit(
            lambda x: moe_lib.moe_mlp_dropless_sharded(
                x, router, wg, wu, wd, mesh, top_k=2
            )
        )(x)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5
    )


def test_moe_model_trains_dropless_ep_mesh():
    """Full model training with moe_impl=dropless on an ep mesh: the
    dropless property survives expert parallelism (VERDICT r3 #3)."""
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.trainer import train_step as ts

    mesh = build_mesh(MeshConfig(ep=2, dp=4))
    cfg = llama.tiny_config(
        n_layers=2, n_experts=4, moe_impl="dropless"
    )
    tc = ts.TrainConfig(learning_rate=5e-3, warmup_steps=2)
    opt = ts.make_optimizer(tc)
    state, _ = ts.init_train_state(cfg, opt, mesh, jax.random.key(0))
    step, _ = ts.make_train_step(cfg, tc, opt, mesh)
    tokens = jax.random.randint(
        jax.random.key(1), (8, 33), 0, cfg.vocab_size
    ).astype(jnp.int32)
    losses = []
    for _ in range(6):
        state, metrics = step(state, {"tokens": tokens})
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] - 0.2, losses


def test_moe_model_trains_dropless():
    cfg = llama.tiny_config(n_layers=2, n_experts=4, moe_impl="dropless")
    params, _ = llama.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(
        jax.random.key(1), (2, 17), 0, cfg.vocab_size
    ).astype(jnp.int32)
    import optax

    opt = optax.adam(5e-3)
    ostate = opt.init(params)
    losses = []
    step = jax.jit(
        lambda p, o: _step(cfg, opt, p, o, {"tokens": tokens})
    )
    for _ in range(8):
        params, ostate, loss = step(params, ostate)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] - 0.2, losses


def _step(cfg, opt, params, ostate, batch):
    (loss, _), grads = jax.value_and_grad(
        lambda p: llama.loss_fn(cfg, p, batch), has_aux=True
    )(params)
    upd, ostate = opt.update(grads, ostate)
    import optax

    return optax.apply_updates(params, upd), ostate, loss


def test_dispatch_ab_probe_runs_and_agrees():
    """tools/bench_moe_dispatch.py (the on-chip gmm-vs-fused A/B that
    ROADMAP S3 decides from) at a tiny shape in interpret mode: both
    dispatches run fwd+bwd and agree."""
    import os
    import sys

    sys.path.insert(
        0, os.path.join(os.path.dirname(__file__), "..", "tools")
    )
    import bench_moe_dispatch

    out = bench_moe_dispatch.run(
        batch=2, seq=64, d=128, f=128, experts=4, repeats=1
    )
    assert out["ok"], out
    assert out["fused_over_gmm"] > 0
