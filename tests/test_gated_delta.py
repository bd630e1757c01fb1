"""``ops/gated_delta.py``: the served delta rule's chunk form against the
token-a-step definition (its own one-row update walked over the rows) AND
against ``ops/kda.kda_recurrent`` with the gate broadcast over channels;
the in-place step kernel, interpreted, against the definition."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import gated_delta, kda

HEADS, DK, DV, ROWS, SUB = 3, 8, 12, 64, 16


def _inputs(seed: int, neg_eigval: bool, rows: int = ROWS):
    ks = jax.random.split(jax.random.key(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (rows, HEADS, DK))) * DK ** -0.5
    k = unit(jax.random.normal(ks[1], (rows, HEADS, DK)))
    v = jax.random.normal(ks[2], (rows, HEADS, DV))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (rows, HEADS)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (rows, HEADS)))
    beta = 2.0 * beta if neg_eigval else beta
    state = 0.5 * jax.random.normal(ks[5], (HEADS, DK, DV))
    return q, k, v, g, beta, state


@jax.jit
def _scan(q, k, v, g, beta, state):
    def step(s, row):
        o, s = gated_delta.delta_step_reference(
            *(x[None] for x in row), s
        )
        return s, o[0]

    state, outs = jax.lax.scan(step, state[None], (q, k, v, g, beta))
    return outs, state[0]


def _walk(q, k, v, g, beta, state, rows: int):
    """The definition: the one-row update, ``rows`` times."""
    if not rows:
        return None, state
    return _scan(q[:rows], k[:rows], v[:rows], g[:rows], beta[:rows], state)


def _by_kda(q, k, v, g, beta, state):
    """``kda_recurrent`` (heads-major, a batch of one) with the head's
    gate in every channel."""
    major = lambda x: jnp.moveaxis(x, 1, 0)[None]  # noqa: E731
    out, last = kda.kda_recurrent(
        major(q), major(k), major(v),
        jnp.broadcast_to(major(g)[..., None], (1, HEADS, q.shape[0], DK)),
        major(beta), state=state[None],
    )
    return jnp.moveaxis(out[0], 0, 1), last[0]


@pytest.mark.parametrize("neg_eigval", [True, False])
def test_the_chunk_form_is_the_recurrence_from_a_state(neg_eigval):
    q, k, v, g, beta, state = _inputs(0, neg_eigval)
    o, after, same = gated_delta.delta_chunk(
        q, k, v, g, beta, state, chunk=SUB
    )
    want_o, want_state = _walk(q, k, v, g, beta, state, ROWS)
    np.testing.assert_allclose(o, want_o, atol=2e-5)
    np.testing.assert_allclose(after, want_state, atol=2e-5)
    np.testing.assert_array_equal(same, state)        # snap_at 0
    kda_o, kda_state = _by_kda(q, k, v, g, beta, state)
    np.testing.assert_allclose(o, kda_o, atol=2e-5)
    np.testing.assert_allclose(after, kda_state, atol=2e-5)


def test_kda_recurrent_without_a_state_is_what_it_was():
    q, k, v, g, beta, state = _inputs(1, True, rows=8)
    major = lambda x: jnp.moveaxis(x, 1, 0)[None]  # noqa: E731
    gates = jnp.broadcast_to(major(g)[..., None], (1, HEADS, 8, DK))
    alone = kda.kda_recurrent(major(q), major(k), major(v), gates,
                              major(beta))
    out, _ = kda.kda_recurrent(
        major(q), major(k), major(v), gates, major(beta),
        state=jnp.zeros((1, HEADS, DK, DV)),
    )
    np.testing.assert_array_equal(alone, out)


@pytest.mark.parametrize("snap_at", [0, 16, 32, 37, 48, 64])
@pytest.mark.parametrize("n_valid", [64, 41])
def test_states_at_any_row_come_from_the_one_solve(snap_at, n_valid):
    """``n_valid`` short of the chunk (the rows past it are another
    sequence's, and must not matter) and ``snap_at`` at every block
    boundary and inside a block: each is the definition's state after
    that many rows."""
    snap_at = min(snap_at, n_valid)
    q, k, v, g, beta, state = _inputs(2, True)
    noise = _inputs(3, True)
    pad = lambda x, y: jnp.concatenate([x[:n_valid], y[n_valid:]])  # noqa: E731
    args = [pad(x, y) for x, y in zip((q, k, v, g, beta), noise[:5])]
    run = jax.jit(lambda n, s: gated_delta.delta_chunk(
        *args, state, n, s, chunk=SUB
    ))
    o, after, snap = run(n_valid, snap_at)
    want_o, want_after = _walk(q, k, v, g, beta, state, n_valid)
    _, want_snap = _walk(q, k, v, g, beta, state, snap_at)
    np.testing.assert_allclose(o[:n_valid], want_o, atol=2e-5)
    np.testing.assert_allclose(after, want_after, atol=2e-5)
    np.testing.assert_allclose(snap, want_snap, atol=2e-5)


def test_a_chunk_that_forgets_everything_stays_finite():
    """Gates of -60 a row (e^-3840 over a sub-chunk): every exponent is
    <= 0, nothing overflows, and the entering state is gone."""
    q, k, v, g, beta, state = _inputs(4, True)
    o, after, _ = gated_delta.delta_chunk(
        q, k, v, jnp.full_like(g, -60.0), beta, 1e3 * state, chunk=SUB
    )
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(after).all())
    want_o, want_after = _walk(
        q, k, v, jnp.full_like(g, -60.0), beta, 1e3 * state, ROWS
    )
    np.testing.assert_allclose(o, want_o, atol=2e-5)
    np.testing.assert_allclose(after, want_after, atol=2e-5)


def test_rows_that_are_not_whole_sub_chunks_are_refused():
    q, k, v, g, beta, state = _inputs(5, True, rows=24)
    with pytest.raises(ValueError, match="whole sub-chunks"):
        gated_delta.delta_chunk(q, k, v, g, beta, state, chunk=SUB)


@pytest.mark.parametrize("layer", [0, 1])
def test_the_step_kernel_updates_active_slots_in_place(layer):
    slots, layers = 4, 2
    ks = jax.random.split(jax.random.key(7), 7)
    q = jax.random.normal(ks[0], (slots, HEADS, DK))
    k = jax.random.normal(ks[1], (slots, HEADS, DK)) * DK ** -0.5
    v = jax.random.normal(ks[2], (slots, HEADS, DV))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (slots, HEADS)))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (slots, HEADS)))
    state = jax.random.normal(ks[5], (layers, slots, HEADS, DK, DV))
    active = jnp.asarray([True, False, True, True])
    o, new = gated_delta.delta_step(
        q, k, v, g, beta, state, layer, active, interpret=True
    )
    want_o, want = gated_delta.delta_step_reference(
        q, k, v, g, beta, state[layer]
    )
    live = np.asarray(active)
    np.testing.assert_allclose(o[live], want_o[live], atol=1e-5)
    np.testing.assert_allclose(new[layer][live], want[live], atol=1e-5)
    # an idle slot's state and the other layer's are what they were
    np.testing.assert_array_equal(new[layer][~live], state[layer][~live])
    np.testing.assert_array_equal(new[1 - layer], state[1 - layer])


def test_step_kind_reads_platform_dtype_and_sizes(monkeypatch):
    assert gated_delta.step_kind(jnp.float32, 30, 96, 192) == "jnp"  # a CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert gated_delta.step_kind(jnp.float32, 30, 96, 192) == "state_kernel"
    assert gated_delta.step_kind(jnp.bfloat16, 30, 96, 192) == "jnp"
    assert gated_delta.step_kind(jnp.float32, 30, 100, 192) == "jnp"
    assert gated_delta.step_kind(jnp.float32, 128, 128, 256) == "jnp"
