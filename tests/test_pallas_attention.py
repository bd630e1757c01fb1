"""Pallas flash attention (interpret mode on CPU) vs the XLA reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops.attention import dot_product_attention
from dlrover_tpu.ops.pallas_attention import (
    flash_attention,
    make_flash_attention,
)


def _qkv(key, b, s, h, hkv, d):
    kq, kk, kv = jax.random.split(key, 3)
    return (
        jax.random.normal(kq, (b, s, h, d), jnp.float32),
        jax.random.normal(kk, (b, s, hkv, d), jnp.float32),
        jax.random.normal(kv, (b, s, hkv, d), jnp.float32),
    )


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize(
    "h,hkv,d",
    [
        (4, 4, 16),   # MHA, transpose layout path
        (4, 2, 16),   # GQA, transpose layout path
        (4, 4, 128),  # MHA, fold-heads layout path (d % 128 == 0)
        (4, 2, 128),  # GQA, fold-heads layout path
        (1, 1, 16),   # single head, fold-heads path via h == 1
    ],
)
def test_flash_matches_dense(causal, h, hkv, d):
    q, k, v = _qkv(jax.random.key(0), 2, 64, h, hkv, d)
    ref = dot_product_attention(q, k, v, causal=causal)
    out = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, causal, None, True)
    )(q, k, v)
    np.testing.assert_allclose(
        np.asarray(ref), np.asarray(out), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize(
    "h,hkv,d,dv",
    [
        (4, 4, 192, 128),  # latent attention: q/k 128 + 64, values 128
        (4, 2, 24, 16),    # GQA with a smaller value head
        (1, 1, 24, 16),    # single head, fold-heads path via h == 1
    ],
)
def test_flash_with_its_own_value_head_size(h, hkv, d, dv):
    """q/k of one head size, v of another: forward and all three
    gradients against plain attention."""
    q, k, _ = _qkv(jax.random.key(2), 2, 64, h, hkv, d)
    v = jax.random.normal(jax.random.key(3), (2, 64, hkv, dv), jnp.float32)
    w = jax.random.normal(jax.random.key(4), (2, 64, h, dv), jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * w)

    flash = make_flash_attention(interpret=True)
    out = jax.jit(flash)(q, k, v)
    assert out.shape == (2, 64, h, dv)
    np.testing.assert_allclose(
        np.asarray(dot_product_attention(q, k, v)), np.asarray(out),
        rtol=2e-5, atol=2e-5,
    )
    g_ref = jax.grad(loss(dot_product_attention), argnums=(0, 1, 2))(q, k, v)
    g_out = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ref, g_out):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-5, atol=5e-5
        )


def test_flash_grad_matches_dense():
    q, k, v = _qkv(jax.random.key(1), 1, 32, 4, 2, 8)

    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.square(fn(q, k, v)))

    flash = make_flash_attention(interpret=True)
    g_ref = jax.grad(loss(dot_product_attention), argnums=(0, 1, 2))(q, k, v)
    g_out = jax.jit(
        jax.grad(loss(flash), argnums=(0, 1, 2))
    )(q, k, v)
    for a, b in zip(g_ref, g_out):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-5, atol=5e-5
        )


def test_flash_in_model():
    from dlrover_tpu.models import llama

    cfg = llama.tiny_config(n_layers=2)
    params, _ = llama.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(
        jax.random.key(2), (2, 32), 0, cfg.vocab_size
    ).astype(jnp.int32)
    ref, _ = llama.forward(cfg, params, tokens)
    out, _ = llama.forward(
        cfg, params, tokens, attention_fn=make_flash_attention(True)
    )
    np.testing.assert_allclose(
        np.asarray(ref), np.asarray(out), rtol=1e-4, atol=1e-4
    )


def test_default_attention_goes_by_the_backend_alone(monkeypatch):
    """The model's default attention is decided once, from the backend:
    the XLA reference (None) off a TPU, the flash kernel on one — the
    same function object on every call, since jit caches key on it. A
    caller that wants another attention passes ``attention_fn=``, as
    the test above does."""
    from dlrover_tpu.models import llama

    monkeypatch.setattr(llama, "_ATTN_CACHE", {})
    assert llama.default_attention_fn() is None
    assert len(llama._ATTN_CACHE) == 1
    monkeypatch.setattr(llama, "_ATTN_CACHE", {})
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn = llama.default_attention_fn()
    assert callable(fn) and llama.default_attention_fn() is fn
    assert len(llama._ATTN_CACHE) == 1


def test_mlp_only_remat_matches_dots():
    """The mlp_only scan body (attention exempt from remat) must produce
    the same loss and grads as the dots policy, and must silently demote
    to dots when the attention impl doesn't declare saveable residuals."""
    from dlrover_tpu.models import llama

    flash = make_flash_attention(True)
    assert flash.saveable_residuals
    tokens = {"tokens": jax.random.randint(
        jax.random.key(3), (2, 33), 0, 256
    ).astype(jnp.int32)}

    def grads(policy, attention_fn):
        cfg = llama.tiny_config(n_layers=2, remat_policy=policy)
        params, _ = llama.init_params(cfg, jax.random.key(0))
        return jax.grad(
            lambda p: llama.loss_fn(cfg, p, tokens, attention_fn)[0]
        )(params)

    g_dots = grads("dots", flash)
    g_mlp = grads("mlp_only", flash)
    # attn_save (long-context policy: attention escapes, flanks fully
    # recompute) must produce identical gradients too — via the LITE
    # block (x/out/lse residuals, projections re-derived in the
    # backward), which only engages for default-constructed flash
    # (is_plain_flash; an explicit interpret override opts out).
    flash_default = make_flash_attention()
    assert flash_default.is_plain_flash
    assert not flash.is_plain_flash  # explicit interpret opts out
    g_attn_save = grads("attn_save", flash_default)
    # The escape path with an explicit-interpret flash (lite bypassed)
    # must also match.
    g_attn_save_escape = grads("attn_save", flash)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
        ),
        g_attn_save,
        g_attn_save_escape,
    )
    for other in (g_mlp, g_attn_save):
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
            ),
            g_dots,
            other,
        )
    # XLA attention has no saveable_residuals attr -> mlp_only demotes to
    # dots rather than pinning O(s^2) residuals.
    g_xla = grads("mlp_only", dot_product_attention)
    assert jax.tree_util.tree_structure(g_xla) == (
        jax.tree_util.tree_structure(g_mlp)
    )
