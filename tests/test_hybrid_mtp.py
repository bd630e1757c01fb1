"""The layer pattern's rotary latent mixer (``mla_rope``, with its query
bottleneck) and its multi-token-prediction module (models/hybrid.py) on
the CPU at tiny sizes, seeded weights: against the plain reference the
benchmark keeps (benchmark/reference_glm.py, which shares no code with
the program) for the main loss, the module's loss and every gradient
leaf; the rotation against an explicit per-position one; depth 0 against
the model without the module; the expert shares against the uncut layer,
for a stack block and for the module's; and what the train step reports."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_glm as reference
from dlrover_tpu.models import hybrid, latent_lm
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.trainer import train_step as ts

PATTERN = dict(
    leading=(("mla_rope", "dense"),), period=(("mla_rope", "moe"),),
    n_periods=2, q_lora_rank=24, rope_theta=1e6, routed_scaling=1.8,
)


def _spec(cfg):
    return {"top_k": cfg.moe_top_k, "first_expert": cfg.experts_held[0],
            "routed_scaling": cfg.routed_scaling,
            "rope_theta": cfg.rope_theta, "mtp_weight": cfg.mtp_weight}


def _state(cfg, seq=45, batch=2):
    params, _ = hybrid.init_params(cfg, jax.random.key(0))
    buffers = hybrid.init_buffers(cfg, jax.random.key(0))
    tokens = jax.random.randint(
        jax.random.key(1), (batch, seq + 1 + cfg.mtp_depth), 0,
        cfg.vocab_size,
    )
    return params, buffers, tokens


@pytest.fixture(scope="module")
def tiny():
    """One dense block, two expert blocks and the module, 4 of 16
    experts held, float32."""
    cfg = hybrid.tiny_config(mtp_depth=1, experts_held=(4, 4), **PATTERN)
    return (cfg,) + _state(cfg)


@pytest.fixture(scope="module")
def tiny_grads(tiny):
    """``tiny``'s loss, its parts and every gradient leaf, compiled once
    for the tests that hold something against them."""
    cfg, params, buffers, tokens = tiny
    return jax.jit(jax.value_and_grad(
        lambda p: hybrid.loss_fn(cfg, p, {"tokens": tokens}, buffers),
        has_aux=True,
    ))(params)


@pytest.fixture(scope="module")
def reference_grads(tiny):
    """``jax.grad`` of the reference's whole graph at ``tiny``."""
    cfg, params, buffers, tokens = tiny
    return jax.jit(jax.grad(
        lambda p: reference.loss(p, buffers, tokens, _spec(cfg))
    ))(params)


def test_both_losses_and_every_gradient_match_the_reference(
        tiny, tiny_grads, reference_grads):
    cfg, params, buffers, tokens = tiny
    (loss, aux), grads = tiny_grads
    ref_main, ref_mtp = reference.batch_losses(
        params, buffers, np.asarray(tokens), _spec(cfg)
    )
    assert float(aux["ce"]) == pytest.approx(ref_main, rel=2e-6)
    assert float(aux["ce_mtp"]) == pytest.approx(ref_mtp, rel=2e-6)
    assert float(loss) == pytest.approx(
        ref_main + cfg.mtp_weight * ref_mtp, rel=2e-6
    )
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    ref_flat = jax.tree_util.tree_leaves(reference_grads)
    assert len(flat) == len(ref_flat) == 51
    names = [jax.tree_util.keystr(path) for path, _ in flat]
    # The module's own leaves, and the two arrays both losses reach.
    assert "['mtp']['w_eh']" in names and "['mtp']['norm']" in names
    assert "['mtp']['block']['mixer']['w_qb']" in names
    assert "['embed']" in names and "['lm_head']" in names
    for name, (_, g), r in zip(names, flat, ref_flat):
        scale = float(jnp.max(jnp.abs(r))) + 1e-8
        assert float(jnp.max(jnp.abs(r))) > 0, name
        err = float(jnp.max(jnp.abs(g - r)))
        assert err <= 2e-4 * scale + 1e-7, (name, err)
    c = aux["counters"]
    assert int(c["moe_rows_dropped"]) == 0
    assert 0 < int(c["mtp_moe_rows_held"]) < int(c["moe_rows_held"])


def test_the_layer_walk_gives_the_same_gradient_as_the_whole_graph(
        tiny, reference_grads):
    """``batch_loss_and_grads`` (a layer's pullback at a time, on the
    host: what the chip's ``correct`` reads) against ``jax.grad`` of the
    reference's whole graph."""
    cfg, params, buffers, tokens = tiny
    (main, mtp), walked = reference.batch_loss_and_grads(
        params, buffers, np.asarray(tokens), _spec(cfg)
    )
    want = reference.batch_losses(
        params, buffers, np.asarray(tokens), _spec(cfg)
    )
    assert (main, mtp) == pytest.approx(want, rel=1e-6)
    assert jax.tree_util.tree_structure(walked) == (
        jax.tree_util.tree_structure(reference_grads)
    )
    for a, b in zip(jax.tree_util.tree_leaves(walked),
                    jax.tree_util.tree_leaves(reference_grads)):
        np.testing.assert_allclose(
            a, b, rtol=0, atol=2e-5 * float(jnp.max(jnp.abs(b))) + 1e-9
        )


def test_the_mixer_rotates_by_each_tokens_own_position(tiny):
    """The mixer against the reference's layer, whose rotation is
    written out per position and per pair; then against itself with
    the positions shifted: the positional slices turn, relative
    distances do not, so the output is the same -- and is NOT the same
    when the rotation is left out."""
    cfg, params, _, _ = tiny
    p = params["leading"][0]["mixer"]
    h = jax.random.normal(jax.random.key(5), (2, 37, cfg.embed_dim))
    positions = jnp.broadcast_to(jnp.arange(37), (2, 37))
    out = hybrid._mla_rope_apply(cfg, p, h, positions)
    for row in range(2):
        want = reference.mla(p, h[row], _spec(cfg))
        np.testing.assert_allclose(out[row], want, rtol=0, atol=2e-5)
    shifted = hybrid._mla_rope_apply(cfg, p, h, positions + 1000)
    np.testing.assert_allclose(shifted, out, rtol=0, atol=2e-4)
    unrotated = hybrid._mla_apply(cfg, p, h)
    assert float(jnp.max(jnp.abs(unrotated - out))) > 1e-2
    # Position 0 is not turned at all: there the two agree.
    np.testing.assert_allclose(unrotated[:, 0], out[:, 0], atol=2e-5)


def test_the_reference_rotation_is_the_pairwise_one():
    x = jax.random.normal(jax.random.key(2), (5, 3, 8))
    got = reference.rotate(x, 100.0)
    for i in range(5):
        for j in range(4):
            angle = i * 100.0 ** (-2 * j / 8)
            a, b = x[i, :, j], x[i, :, j + 4]
            np.testing.assert_allclose(
                got[i, :, j], a * np.cos(angle) - b * np.sin(angle),
                atol=1e-5,
            )
            np.testing.assert_allclose(
                got[i, :, j + 4], b * np.cos(angle) + a * np.sin(angle),
                atol=1e-5,
            )


def test_the_bottleneck_is_the_latent_models_own():
    """One function for the two models that have the bottleneck."""
    cfg = latent_lm.tiny_config()
    params = jax.jit(lambda key: latent_lm.init_params(cfg, key))(
        jax.random.key(0)
    )
    p = latent_lm.layer_params(params, 0)
    h = jax.random.normal(
        jax.random.key(1), (1, 9, cfg.embed_dim), cfg.compute_dtype
    )
    q_nope, _, _ = latent_lm.latent_inputs(
        cfg, p, h, jnp.arange(9)[None]
    )
    want = hybrid.mla_bottleneck_queries(p, h)[..., :cfg.qk_nope_dim]
    np.testing.assert_array_equal(q_nope, want)


def test_depth_0_is_the_model_without_the_module(tiny):
    """No key of the module in any tree, the stack's weights the same at
    either depth, and the loss the main loss of depth 1 to the bit."""
    cfg, params, buffers, tokens = tiny
    cfg0 = hybrid.tiny_config(experts_held=(4, 4), **PATTERN)
    params0, axes0 = hybrid.init_params(cfg0, jax.random.key(0))
    buffers0 = hybrid.init_buffers(cfg0, jax.random.key(0))
    for tree in (params0, axes0, buffers0, hybrid.buffer_axes(cfg0)):
        assert "mtp" not in tree
    for tree in (params, hybrid.param_axes(cfg), buffers,
                 hybrid.buffer_axes(cfg)):
        assert "mtp" in tree
    rest = {k: v for k, v in params.items() if k != "mtp"}
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: bool(jnp.array_equal(a, b)), rest, params0
    ))
    loss0, aux0 = jax.jit(
        lambda p: hybrid.loss_fn(cfg0, p, {"tokens": tokens[:, :-1]},
                                 buffers0)
    )(params0)
    _, aux = jax.jit(
        lambda p: hybrid.loss_fn(cfg, p, {"tokens": tokens}, buffers)
    )(params)
    assert set(aux0) == {"ce", "aux", "counters"}
    assert set(aux0["counters"]) == set(hybrid.COUNTERS)
    assert float(loss0) == float(aux0["ce"]) == float(aux["ce"])
    # The kimi-style pattern reads no positions at all.
    assert not hybrid.tiny_config().positional and cfg0.positional


@pytest.mark.parametrize("block", ["stack", "module"])
def test_the_eight_shares_add_up_to_the_uncut_layer(block):
    """Over all 8 expert ranks (2 of 16 experts each), the routed parts
    plus the shared expert counted once add up to what the uncut
    reference gives for the whole expert layer."""
    full = hybrid.tiny_config(
        mtp_depth=1, n_experts=16, experts_held=(0, 16), **PATTERN
    )
    params, buffers, _ = _state(full)
    if block == "stack":
        p = jax.tree_util.tree_map(lambda a: a[1], params["period"][0])
        b = jax.tree_util.tree_map(lambda a: a[1], buffers["period"][0])
    else:
        p, b = params["mtp"]["block"], buffers["mtp"]["block"]
    x = jax.random.normal(jax.random.key(7), (2, 33, full.embed_dim))
    shared = hybrid._swiglu(p["ffn"]["shared"], x)
    total, rows = shared, 0
    for rank in range(8):
        cfg = hybrid.tiny_config(
            mtp_depth=1, n_experts=16, experts_held=(2 * rank, 2), **PATTERN
        )
        held = dict(p["ffn"], **{
            k: p["ffn"][k][2 * rank:2 * rank + 2]
            for k in ("w_gate", "w_up", "w_down")
        })
        out, counters = hybrid._moe_apply(cfg, held, b, x)
        total = total + (out - shared)
        rows += int(counters[0])
    assert rows == 2 * 33 * full.moe_top_k      # every pair, once
    for row in range(2):
        want = reference.experts(
            p["ffn"], b["router_bias"], x[row], _spec(full)
        )
        np.testing.assert_allclose(total[row], want, rtol=0, atol=2e-5)


@pytest.mark.parametrize("remat_keep", hybrid.REMAT_KEEP)
def test_what_a_block_keeps_does_not_change_the_gradient(
        tiny, tiny_grads, remat_keep):
    cfg, params, buffers, tokens = tiny
    import dataclasses

    other = dataclasses.replace(cfg, remat_keep=remat_keep)
    kept = jax.jit(jax.grad(
        lambda p: hybrid.loss_fn(other, p, {"tokens": tokens}, buffers)[0]
    ))(params)
    for a, b in zip(jax.tree_util.tree_leaves(tiny_grads[1]),
                    jax.tree_util.tree_leaves(kept)):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)
    with pytest.raises(ValueError):
        dataclasses.replace(cfg, remat_keep="everything")
    with pytest.raises(ValueError):
        dataclasses.replace(cfg, mtp_depth=2)


@pytest.fixture(scope="module")
def one_period():
    """A dense block, one expert block and the module, 14 (+2) tokens:
    the config, a batch, the one-axis mesh, a fresh train state (the
    steps below donate nothing) and the loss's parts as one program."""
    cfg = hybrid.tiny_config(
        mtp_depth=1, experts_held=(4, 4), **dict(PATTERN, n_periods=1)
    )
    mesh = build_mesh(MeshConfig(dp=1), jax.devices()[:1])
    opt = ts.make_optimizer(ts.TrainConfig(warmup_steps=1))
    state, _ = ts.init_train_state(cfg, opt, mesh, jax.random.key(0))
    parts = jax.jit(lambda p, b, t: hybrid.loss_fn(
        cfg, p, {"tokens": t}, b
    )[1])
    return cfg, _state(cfg, seq=14)[2], mesh, state, parts


@pytest.mark.parametrize("grad_accum", [1, 2])
def test_the_step_reports_the_two_losses_and_the_modules_rows(
        one_period, grad_accum):
    cfg, tokens, mesh, state, parts = one_period
    tc = ts.TrainConfig(warmup_steps=1, grad_accum=grad_accum)
    bias = jax.tree_util.tree_map(np.asarray, state["buffers"])
    step, _ = ts.make_train_step(
        cfg, tc, ts.make_optimizer(tc), mesh, donate=False
    )
    new, metrics = step(state, {"tokens": tokens})
    auxes = [parts(state["params"], state["buffers"], t) for t in (
        tokens.reshape(grad_accum, -1, tokens.shape[1])
    )]
    assert set(hybrid.COUNTERS) | {"ce", "ce_mtp", hybrid.MTP_COUNTER} <= (
        set(metrics)
    )
    for part in ("ce", "ce_mtp"):      # averaged over the microbatches
        assert float(metrics[part]) == pytest.approx(
            np.mean([float(a[part]) for a in auxes]), rel=1e-5
        )
    assert float(metrics["loss"]) == pytest.approx(
        float(metrics["ce"]) + cfg.mtp_weight * float(metrics["ce_mtp"]),
        rel=1e-6,
    )
    assert int(metrics[hybrid.MTP_COUNTER]) == sum(    # summed over them
        int(a["counters"][hybrid.MTP_COUNTER]) for a in auxes
    )
    # The module's bias is a buffer too: no gradient, no update.
    assert "mtp" in new["buffers"]
    for a, b in zip(jax.tree_util.tree_leaves(new["buffers"]),
                    jax.tree_util.tree_leaves(bias)):
        np.testing.assert_array_equal(a, b)


def test_a_loss_of_one_part_reports_no_parts():
    cfg = hybrid.tiny_config(
        leading=(), period=(("mla", "moe"),), experts_held=(4, 4)
    )
    mesh = build_mesh(MeshConfig(dp=1), jax.devices()[:1])
    tc = ts.TrainConfig(warmup_steps=1)
    opt = ts.make_optimizer(tc)
    state, _ = ts.init_train_state(cfg, opt, mesh, jax.random.key(0))
    step, _ = ts.make_train_step(cfg, tc, opt, mesh, donate=False)
    with mesh:      # what the step reports is in its trace: no compile
        _, metrics = jax.eval_shape(
            step.jitted, state, {"tokens": _state(cfg, seq=14)[2]}
        )
    assert "ce" not in metrics and hybrid.MTP_COUNTER not in metrics
    assert set(hybrid.COUNTERS) <= set(metrics)


def test_the_steps_span_carries_the_new_counters():
    """Where the loop has fetched the loss and a tracer is armed, the
    step's counters and both loss parts ride ``train.step`` as
    attributes (``ElasticTrainer.step_completed(metrics=...)``)."""
    from dlrover_tpu.observability import tracing
    from dlrover_tpu.trainer.elastic.trainer import (
        ElasticBatchConfig,
        ElasticTrainer,
    )

    tracer = tracing.Tracer(service="test")
    tracing.arm(tracer)
    try:
        trainer = ElasticTrainer(
            ElasticBatchConfig(global_batch_size=1, micro_batch_per_device=1),
            dp_size=1,
        )
        trainer.start_training()
        trainer.step_completed(metrics={
            "loss": jnp.float32(12.9), "ce": jnp.float32(9.9),
            "ce_mtp": jnp.float32(10.0), "grad_norm": jnp.float32(1.0),
            "moe_rows_held": jnp.int32(16384), "moe_rows_max": jnp.int32(600),
            "moe_rows_dropped": jnp.int32(0),
            "mtp_moe_rows_held": jnp.int32(4096),
        })
        (root,) = [
            s for s in tracer.finished() if s["name"] == "train.step"
        ]
    finally:
        tracing.disarm()
    attrs = root["attrs"]
    assert attrs["moe_rows_held"] == 16384
    assert attrs["mtp_moe_rows_held"] == 4096
    assert attrs["ce"] == pytest.approx(9.9) and "loss" not in attrs
    assert attrs["ce_mtp"] == pytest.approx(10.0)
