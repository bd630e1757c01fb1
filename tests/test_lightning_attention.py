"""The lightning mixer of ``models/linear_sparse_lm.py``: the chunk
algebra (``lightning_chunk``, ``lightning_state_after``) and the one-row
update (``lightning_step``) against the RECURRENCE, a token a step, over
runs of 1 / a few / many rows, entering with a zero and a non-zero
state, states after 0 / some / all rows, in float32 and with bfloat16
operands; and the decay."""

import numpy as np
import pytest

import jax.numpy as jnp

from dlrover_tpu.models import linear_sparse_lm as lsm

H, D = 4, 8


def recurrence(q, k, v, state, slopes):
    lam = np.exp(-np.asarray(slopes, np.float64))[:, None, None]
    s = np.asarray(state, np.float64)
    outs, states = [], [s]
    for t in range(q.shape[0]):
        s = lam * s + np.einsum("hk,hv->hkv", k[t], v[t])
        outs.append(np.einsum("hkv,hk->hv", s, q[t]))
        states.append(s)
    return np.stack(outs), states


def operands(n, seed, scale_state=0.0):
    rng = np.random.default_rng(seed)
    q, k, v = (rng.normal(size=(n, H, D)) for _ in range(3))
    state = scale_state * rng.normal(size=(H, D, D))
    return q, k, v, state


@pytest.mark.parametrize("n, scale", [(1, 0.0), (5, 0.0), (5, 2.0),
                                      (64, 2.0), (200, 2.0)])
def test_the_chunk_is_the_recurrence(n, scale):
    cfg = lsm.tiny_config()
    slopes = lsm.decay_slopes(cfg, 1)
    q, k, v, state = operands(n, n, scale)
    want, states = recurrence(q, k, v, state, slopes)
    f = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    got = lsm.lightning_chunk(f(q), f(k), f(v), f(state), slopes)
    assert np.abs(np.asarray(got) - want).max() < 1e-3 * max(
        np.abs(want).max(), 1.0
    )
    for rows in sorted({0, 1, n // 2, n}):
        after = lsm.lightning_state_after(f(k), f(v), f(state), slopes, rows)
        assert np.abs(np.asarray(after) - states[rows]).max() < 1e-3 * max(
            np.abs(states[rows]).max(), 1.0
        )


def test_the_step_is_one_row_of_the_recurrence():
    cfg = lsm.tiny_config()
    slopes = lsm.decay_slopes(cfg, 2)
    q, k, v, state = operands(3, 7, 1.5)
    want, states = recurrence(q[:1], k[:1], v[:1], state, slopes)
    f = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    out, new = lsm.lightning_step(
        f(q[:1]), f(k[:1]), f(v[:1]), f(state)[None], slopes
    )
    assert np.abs(np.asarray(out)[0] - want[0]).max() < 1e-4
    assert np.abs(np.asarray(new)[0] - states[1]).max() < 1e-4
    assert new.dtype == jnp.float32


def test_bfloat16_operands_leave_the_state_float32():
    cfg = lsm.tiny_config()
    slopes = lsm.decay_slopes(cfg, 1)
    q, k, v, state = operands(48, 9, 1.0)
    b = lambda a: jnp.asarray(a, jnp.bfloat16)  # noqa: E731
    r = lambda a: np.asarray(b(a), np.float64)  # noqa: E731
    want, states = recurrence(r(q), r(k), r(v), state, slopes)
    s32 = jnp.asarray(state, jnp.float32)
    got = lsm.lightning_chunk(b(q), b(k), b(v), s32, slopes)
    after = lsm.lightning_state_after(b(k), b(v), s32, slopes, 48)
    assert got.dtype == jnp.float32 and after.dtype == jnp.float32
    # the state's products are exact in float32; the outputs round their
    # decayed scores to bfloat16 once
    assert np.abs(np.asarray(after) - states[48]).max() < 1e-4
    assert np.abs(np.asarray(got) - want).max() < 0.05 * np.abs(want).max()


def test_the_decay_goes_by_the_published_layer_and_head():
    cfg = lsm.tiny_config()           # held layers 2-6 of 8 published
    first = lsm.decay_slopes(cfg, 1)  # published layer 3
    assert first.shape == (4,) and first.dtype == np.float32
    want = 2.0 ** (-8.0 * np.arange(1, 5) / 4) * (1 - 3 / 7 + 1e-5)
    assert np.allclose(first, want, rtol=1e-6)
    deeper = lsm.decay_slopes(cfg, 4)
    assert (deeper < first).all() and (np.exp(-deeper) < 1).all()
    full = lsm.LinearSparseLMConfig(
        mixer_types=("lightning-attn",) * 12, first_layer=9
    )
    last = lsm.decay_slopes(full, 11)     # published layer 20 of 32
    assert np.allclose(last[0], 2.0 ** -0.25 * (1 - 20 / 31 + 1e-5))


@pytest.mark.parametrize("active", [(True, True, True), (True, False, True)])
def test_the_state_kernel_is_the_step_and_leaves_the_rest_alone(active):
    """``ops/lightning_attention.state_step`` interpreted: one layer of
    the active slots updated in place, every other layer and an inactive
    slot bit for bit as they were."""
    from dlrover_tpu.ops import lightning_attention as la

    heads, d, slots, layers = 2, 128, 3, 3
    rng = np.random.default_rng(4)
    q, k, v = (
        jnp.asarray(rng.normal(size=(slots, heads, d)), jnp.bfloat16)
        for _ in range(3)
    )
    state = jnp.asarray(rng.normal(size=(layers, slots, heads, d, d)),
                        jnp.float32)
    slopes = np.asarray([0.3, 0.01], np.float32)
    live = jnp.asarray(active)
    o, new = la.state_step(q, k, v, state, 1, slopes, live, interpret=True)
    want_o, want = lsm.lightning_step(q, k, v, state[1], slopes)
    assert new.dtype == jnp.float32 and new.shape == state.shape
    for s, on in enumerate(active):
        if on:
            assert np.abs(np.asarray(new[1, s] - want[s])).max() < 1e-5
            assert np.abs(np.asarray(o[s] - want_o[s])).max() < 1e-3
        else:
            assert bool((new[1, s] == state[1, s]).all())
    assert bool((new[0] == state[0]).all() and (new[2] == state[2]).all())
    assert la.state_kernel_supported(jnp.float32, 32, 128)
    assert not la.state_kernel_supported(jnp.bfloat16, 32, 128)
    assert not la.state_kernel_supported(jnp.float32, 32, 64)
