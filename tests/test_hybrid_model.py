"""The layer-pattern model (models/hybrid.py), its KDA scan (ops/kda.py)
and the expert share (models/moe.moe_mlp_share) on the CPU at tiny
sizes, seeded weights: against the plain reference the benchmark keeps
(benchmark/reference_kimi_linear.py, which shares no code with the
program) for loss AND gradients, the chunked scan against the token-by-
token recurrence, the shares against the uncut layer, a skewed router,
and the buffer the optimizer must not touch."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_kimi_linear as reference
from dlrover_tpu.models import hybrid, model_for, moe
from dlrover_tpu.ops import kda
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.trainer import train_step as ts


def _spec(cfg):
    return {"top_k": cfg.moe_top_k, "first_expert": cfg.experts_held[0],
            "routed_scaling": cfg.routed_scaling}


def _reference_loss(cfg, params, buffers, tokens):
    total, count = 0.0, 0
    for row in tokens:
        s, n = reference.sequence_loss_sums(params, buffers, row, _spec(cfg))
        total, count = total + s, count + n
    return total / count


@pytest.fixture(scope="module")
def tiny():
    """One dense-FFN KDA layer + one period (KDA, KDA, MLA, KDA with
    experts), 4 of 16 experts held, float32; a sequence length that is
    not a multiple of the chunk."""
    cfg = hybrid.tiny_config(
        experts_held=(4, 4), routed_scaling=2.446
    )
    params, _ = hybrid.init_params(cfg, jax.random.key(0))
    buffers = hybrid.init_buffers(cfg, jax.random.key(0))
    tokens = jax.random.randint(
        jax.random.key(1), (2, 81), 0, cfg.vocab_size
    )
    return cfg, params, buffers, tokens


@pytest.fixture(scope="module")
def tiny_step(tiny):
    """The train step of ``tiny`` at the default ``TrainConfig`` on the
    one-axis mesh, traced ONCE for the tests that run it, read its
    metrics or hash its text: ``(mesh, state, step, lowered)``. The step
    donates nothing, so ``state`` stays the initial one."""
    cfg, _, _, tokens = tiny
    mesh = build_mesh(MeshConfig(dp=1), jax.devices()[:1])
    tc = ts.TrainConfig(warmup_steps=2)
    opt = ts.make_optimizer(tc)
    state, _ = ts.init_train_state(cfg, opt, mesh, jax.random.key(0))
    step, _ = ts.make_train_step(cfg, tc, opt, mesh, donate=False)
    with mesh:
        lowered = step.jitted.lower(state, {"tokens": tokens})
    return mesh, state, step, lowered


def test_loss_and_gradients_match_the_reference(tiny):
    cfg, params, buffers, tokens = tiny
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: hybrid.loss_fn(cfg, p, {"tokens": tokens}, buffers),
        has_aux=True,
    ))(params)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: _reference_loss(cfg, p, buffers, tokens)
    ))(params)
    assert float(loss) == pytest.approx(float(ref_loss), rel=2e-6)
    assert int(aux["counters"]["moe_rows_dropped"]) == 0
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    ref_flat = jax.tree_util.tree_leaves(ref_grads)
    assert len(flat) == len(ref_flat) > 60
    for (path, g), r in zip(flat, ref_flat):
        scale = float(jnp.max(jnp.abs(r))) + 1e-8
        err = float(jnp.max(jnp.abs(g - r)))
        assert err <= 2e-4 * scale + 1e-7, (jax.tree_util.keystr(path), err)


def test_the_fused_kda_layer_matches_its_jax_numpy_lines(monkeypatch):
    """``_kda_apply`` with ``kda_scan_kind`` steered to ``"pallas"`` (every
    kernel interpreted: the scan's and ``ops/kda_tail.py``'s around it)
    against the ``jax.numpy`` form: loss and every gradient of
    ``tiny_config`` at the published head size, two KDA layers."""
    import functools

    from dlrover_tpu.ops import kda_tail

    cfg = hybrid.tiny_config(
        kda_heads=2, kda_head_dim=128, period=(("kda", "dense"),)
    )
    params, _ = hybrid.init_params(cfg, jax.random.key(0))
    buffers = hybrid.init_buffers(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 41), 0, cfg.vocab_size)

    def run():
        return jax.jit(jax.value_and_grad(lambda p: hybrid.loss_fn(
            cfg, p, {"tokens": tokens}, buffers
        )[0]))(params)

    want_loss, want = run()
    monkeypatch.setattr(kda, "kda_scan_kind", lambda dk, dv: "pallas")
    for module, name in [(kda, "kda_chunked_kernels")] + [
        (kda_tail, n) for n in ("branch", "decay_gate", "gated_norm")
    ]:
        monkeypatch.setattr(module, name, functools.partial(
            getattr(module, name), interpret=True
        ))
    ran = []
    monkeypatch.setattr(kda_tail, "_call", lambda *a, real=kda_tail._call,
                        **kw: ran.append(a[1]) or real(*a, **kw))
    loss, grads = run()
    assert {"kda_branch_fwd", "kda_branch_bwd", "kda_gate_fwd",
            "kda_gate_bwd", "kda_out_fwd", "kda_out_bwd"} == set(ran)
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-6)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), r in zip(flat, jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(r))) + 1e-8
        err = float(jnp.max(jnp.abs(g - r)))
        assert err <= 2e-4 * scale + 1e-7, (jax.tree_util.keystr(path), err)


def _kda_inputs(key, b, h, s, dk, dv, decay):
    ks = jax.random.split(key, 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (b, h, s, dk)))
    k = unit(jax.random.normal(ks[1], (b, h, s, dk)))
    v = jax.random.normal(ks[2], (b, h, s, dv))
    g = -jax.nn.softplus(2 * jax.random.normal(ks[3], (b, h, s, dk)))
    g = g * jnp.asarray(decay)[None, :, None, None]
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, h, s)))
    return (q, k, v, g, beta), jax.random.normal(ks[5], (b, h, s, dv))


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("length", [150, 64, 7])
def test_chunked_kda_matches_the_recurrence(length, groups, monkeypatch):
    """Forward and all five gradients, at a length that is not a
    multiple of the chunk; the fourth head forgets e^-12 a token, which
    a one-level chunk form overflows on."""
    args, w = _kda_inputs(
        jax.random.key(length), 2, 4, length, 16, 8, (0.05, 1.0, 0.3, 12.0)
    )
    # 4 heads: walked in one group, or in two.
    monkeypatch.setattr(kda, "GROUP_TOKENS", 4 * length // groups)
    assert kda.head_groups(4, length) == groups
    chunked = lambda *a: kda.kda_chunked(*a)  # noqa: E731
    out = jax.jit(chunked)(*args)
    ref = kda.kda_recurrent(*args)
    np.testing.assert_allclose(out, ref, rtol=0, atol=2e-5)
    every = (0, 1, 2, 3, 4)
    g_out = jax.jit(jax.grad(
        lambda *a: jnp.sum(chunked(*a) * w), argnums=every
    ))(*args)
    g_ref = jax.grad(
        lambda *a: jnp.sum(kda.kda_recurrent(*a) * w), argnums=every
    )(*args)
    for a, r in zip(g_out, g_ref):
        np.testing.assert_allclose(a, r, rtol=0, atol=1e-4)


def test_unit_lower_inverse_is_the_inverse_and_has_its_gradient():
    n = jnp.tril(jax.random.normal(jax.random.key(0), (3, 5, 64, 64)), -1)
    inv = kda.unit_lower_inverse(0.3 * n)
    eye = jnp.eye(64)
    np.testing.assert_allclose(
        jnp.einsum("...ij,...jk->...ik", eye + 0.3 * n, inv,
                   precision="highest"),
        jnp.broadcast_to(eye, inv.shape), atol=2e-4,
    )
    w = jax.random.normal(jax.random.key(1), (64, 64))
    small = 0.1 * n[0, 0]
    grad = jax.grad(lambda m: jnp.sum(kda.unit_lower_inverse(m) * w))(small)
    ref = jax.grad(lambda m: jnp.sum(jnp.linalg.inv(eye + m) * w))(small)
    np.testing.assert_allclose(grad, jnp.tril(ref, -1), atol=2e-4)


def _expert_layer(key, n_experts=32, d=16, f=8):
    ks = jax.random.split(key, 5)
    return {
        "router": jax.random.normal(ks[0], (d, n_experts)) / d ** 0.5,
        "bias": 0.01 * jax.random.normal(ks[1], (n_experts,)),
        "w_gate": jax.random.normal(ks[2], (n_experts, d, f)) / d ** 0.5,
        "w_up": jax.random.normal(ks[3], (n_experts, d, f)) / d ** 0.5,
        "w_down": jax.random.normal(ks[4], (n_experts, f, d)) / f ** 0.5,
    }


def _share(layer, x, first, held, bias=None, top_k=4):
    block = slice(first, first + held)
    return moe.moe_mlp_share(
        x, layer["router"], layer["bias"] if bias is None else bias,
        layer["w_gate"][block], layer["w_up"][block],
        layer["w_down"][block], first=first, top_k=top_k, scaling=2.446,
    )


def _uncut_reference(layer, x, top_k=4):
    """The whole routed sum, all experts, in plain float32."""
    p = {k: layer[k] for k in ("router", "w_gate", "w_up", "w_down")}
    p["shared"] = {"w_gate": jnp.zeros((x.shape[-1], 1)),
                   "w_up": jnp.zeros((x.shape[-1], 1)),
                   "w_down": jnp.zeros((1, x.shape[-1]))}
    return reference.experts(p, layer["bias"], x, {
        "top_k": top_k, "routed_scaling": 2.446, "first_expert": 0,
    })


def test_all_shares_add_up_to_the_uncut_layer():
    """Guide section 4's share test: the parts the 8 shares of 4 experts
    give add up to what the uncut reference gives for the whole routed
    layer (the shared expert, which every chip computes alike, is
    counted once: here, left out of both sides)."""
    layer = _expert_layer(jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 128, 16))
    total, rows = 0.0, 0
    for first in range(0, 32, 4):
        out, counters = _share(layer, x, first, 4)
        total, rows = total + out, rows + int(counters.rows_held)
        assert int(counters.rows_dropped) == 0
    assert rows == 2 * 128 * 4          # every (token, k) pair, once
    whole = _uncut_reference(layer, x.reshape(-1, 16)).reshape(x.shape)
    np.testing.assert_allclose(total, whole, atol=2e-5)


@pytest.mark.parametrize("hot", [False, True])
def test_a_skewed_router_drops_nothing(hot):
    """Three held experts take a row of EVERY token (a bias of +100):
    more rows than the usual buffer (four even shares) holds, so the
    step takes the full one -- and still matches, forward and backward."""
    layer = _expert_layer(jax.random.key(2))
    x = jax.random.normal(jax.random.key(3), (1, 256, 16))
    bias = layer["bias"].at[4:7].set(100.0) if hot else layer["bias"]
    out, counters = _share(layer, x, 4, 4, bias=bias)
    usual = 4 * (256 * 4 // 32) * 4
    assert (int(counters.rows_held) > usual) == hot
    assert int(counters.rows_dropped) == 0
    assert int(counters.rows_max) == (256 if hot else counters.rows_max)
    skewed = dict(layer, bias=bias)
    held = {k: skewed[k][4:8] for k in ("w_gate", "w_up", "w_down")}

    def plain(x, held):
        p = dict(held, router=layer["router"], shared={
            "w_gate": jnp.zeros((16, 1)), "w_up": jnp.zeros((16, 1)),
            "w_down": jnp.zeros((1, 16)),
        })
        return reference.experts(p, bias, x.reshape(-1, 16), {
            "top_k": 4, "routed_scaling": 2.446, "first_expert": 4,
        }).reshape(x.shape)

    np.testing.assert_allclose(out, plain(x, held), atol=2e-5)
    w = jax.random.normal(jax.random.key(4), x.shape)
    grad = jax.grad(lambda x, h: jnp.sum(moe.moe_mlp_share(
        x, layer["router"], bias, h["w_gate"], h["w_up"], h["w_down"],
        first=4, top_k=4, scaling=2.446,
    )[0] * w), argnums=(0, 1))(x, held)
    ref = jax.grad(lambda x, h: jnp.sum(plain(x, h) * w), argnums=(0, 1))(
        x, held
    )
    for a, r in zip(jax.tree_util.tree_leaves(grad),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(a, r, atol=5e-5)


def test_train_step_carries_counters_and_leaves_the_bias_alone(
        tiny, tiny_step):
    """Through make_train_step / init_train_state on the one-axis mesh:
    the step's metrics hold the model's counters, the score-correction
    bias gets no optimizer state and no update, the weights do move."""
    cfg, _, _, tokens = tiny
    assert model_for(cfg) is hybrid
    _, state, step, _ = tiny_step
    assert set(state) == {"params", "opt_state", "step", "buffers"}
    n_params = len(jax.tree_util.tree_leaves(state["params"]))
    moments = [
        x for x in jax.tree_util.tree_leaves(state["opt_state"])
        if x.ndim > 0
    ]
    assert len(moments) == 2 * n_params       # Adam's m and v, no more
    before = jax.device_get((state["buffers"], state["params"]["lm_head"]))
    for _ in range(3):
        state, metrics = step(state, {"tokens": tokens})
    assert set(metrics) == {"loss", "grad_norm", "step", *hybrid.COUNTERS}
    assert int(metrics["moe_rows_dropped"]) == 0
    assert 0 < int(metrics["moe_rows_max"]) <= int(metrics["moe_rows_held"])
    after = jax.device_get((state["buffers"], state["params"]["lm_head"]))
    for a, b in zip(jax.tree_util.tree_leaves(before[0]),
                    jax.tree_util.tree_leaves(after[0])):
        np.testing.assert_array_equal(a, b)
    assert np.abs(after[1] - before[1]).max() > 0


@pytest.mark.parametrize("heads, seq, groups", [
    (32, 8192, 4), (32, 2048, 1), (32, 4096, 2), (4, 81, 1), (3, 65536, 3),
])
def test_the_heads_are_walked_in_groups_that_fit(heads, seq, groups):
    assert kda.head_groups(heads, seq) == groups


def test_grad_accum_sums_the_counters(tiny, tiny_step):
    cfg, _, _, tokens = tiny
    mesh, state, step, _ = tiny_step
    one = step(state, {"tokens": tokens})[1]
    # The same initial state (the optimizer's is the same tree) through
    # the step that walks the batch in two halves.
    tc = ts.TrainConfig(warmup_steps=2, grad_accum=2)
    halves, _ = ts.make_train_step(
        cfg, tc, ts.make_optimizer(tc), mesh, donate=False
    )
    two = halves(state, {"tokens": tokens})[1]
    assert int(one["moe_rows_held"]) == int(two["moe_rows_held"])
    assert float(one["loss"]) == pytest.approx(float(two["loss"]), rel=1e-5)


def test_the_dense_model_keeps_its_state_and_metrics():
    """The dense model's train state and step metrics are what they
    were: no buffers, no counters."""
    from dlrover_tpu.models import llama

    cfg = llama.tiny_config()
    assert model_for(cfg) is llama
    mesh = build_mesh(MeshConfig(dp=1), jax.devices()[:1])
    tc = ts.TrainConfig(warmup_steps=2)
    opt = ts.make_optimizer(tc)
    state, _ = ts.init_train_state(cfg, opt, mesh, jax.random.key(0))
    assert set(state) == {"params", "opt_state", "step"}
    step, _ = ts.make_train_step(cfg, tc, opt, mesh, donate=False)
    tokens = jnp.zeros((2, 17), jnp.int32)
    _, metrics = step(state, {"tokens": tokens})
    assert set(metrics) == {"loss", "grad_norm", "step"}


def test_a_pattern_must_name_kinds_that_exist():
    with pytest.raises(ValueError):
        hybrid.tiny_config(period=(("softmax", "moe"),))
    with pytest.raises(ValueError):
        hybrid.tiny_config(experts_held=(12, 8))
    cfg = hybrid.tiny_config(n_periods=2)
    params, axes = hybrid.init_params(cfg, jax.random.key(0))
    assert cfg.n_layers == 9
    assert params["period"][2]["mixer"]["w_kva"].shape[0] == 2
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda x: 0, params)
    ) == jax.tree_util.tree_structure(jax.tree_util.tree_map(
        lambda x: 0, axes, is_leaf=lambda x: isinstance(x, tuple)
    ))


def test_the_train_step_lowers_to_the_text_it_had_before_expert_serving(
        tiny_step):
    """PR 33 split ``moe_mlp_share`` so that a served model routes for
    itself (``moe.routed_experts``); the trained share keeps its
    signature and, held here, its program: this step's lowered text is
    byte for byte what the commit before that PR lowers (sha256 of the
    text, taken there at this size). A PR that means to change the
    hybrid train step replaces the digest. PR 54 did (``8d3d698e...``
    until then): the step's metrics carry ``moe_rows_full_path``, the
    rows of the expert layers that left a share's fast path
    (``moe._share_rows_held``). At this size no layer has one (160
    tokens x top-4 of 16 experts, 4 held: four even shares' rows are all
    640 pairs), so every layer takes the path it took and counts its
    rows; ``tests/test_moe_share_rows.py`` holds the fast path, and
    ``tools/program_hashes.py`` the cells' own programs."""
    import hashlib

    text = tiny_step[3].as_text()      # lowered for int32 [2, 81] tokens
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        "cf0cf544049b6625"
    out, counters = moe.moe_mlp_share(
        jnp.zeros((1, 8, 32)), jnp.zeros((32, 16)), jnp.zeros((16,)),
        *(jnp.zeros(s) for s in ((4, 32, 8), (4, 32, 8), (4, 8, 32))),
        first=4, top_k=4,
    )
    assert counters.experts_hit is None      # the served layer's alone
