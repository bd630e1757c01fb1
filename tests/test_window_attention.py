"""``ops/window_attention.py``: the two pool kernels under a lower bound,
interpreted on the CPU, against the gathered form
(``window_reference``) and the exact softmax, over fills below, at and
many times the reach, a band that starts mid-block, an inactive slot,
and NaN in every page the band does not need (released blocks: the
kernels must not read them)."""

import numpy as np
import pytest

import jax.numpy as jnp

from dlrover_tpu.ops import window_attention as wa

L, NB, BS, KH, D, H = 2, 64, 8, 2, 8, 4
REACH = 23                      # a window of 24 rows
SLOTS, MB = 4, 14


@pytest.fixture(scope="module")
def pool():
    rng = np.random.default_rng(0)
    k = rng.normal(size=(L, NB, BS, KH, D)).astype(np.float32)
    v = rng.normal(size=(L, NB, BS, KH, D)).astype(np.float32)
    ids = rng.permutation(np.arange(1, NB))
    tables = np.stack([ids[s * MB:(s + 1) * MB] for s in range(SLOTS)])
    return k, v, tables.astype(np.int32)


def _exact(q, k_new, v_new, keys, values, q_pos, key_pos):
    """float64 softmax over the visible keys of ONE query: ``q [h, d]``,
    ``keys [T, kh, d]`` at positions ``key_pos`` plus its own."""
    g = H // KH
    keys = np.concatenate([keys, k_new[None]]).astype(np.float64)
    values = np.concatenate([values, v_new[None]]).astype(np.float64)
    pos = np.concatenate([key_pos, [q_pos]])
    seen = (q_pos - pos >= 0) & (q_pos - pos <= REACH)
    out = np.zeros((H, D))
    for head in range(H):
        s = keys[seen, head // g] @ (q[head].astype(np.float64) * D ** -0.5)
        p = np.exp(s - s.max())
        out[head] = (p / p.sum()) @ values[seen, head // g]
    return out


def _poisoned(k, v, tables, slot, low):
    """The pools with NaN in every block of ``slot`` wholly below row
    ``low``, and those entries of its table at the sentinel."""
    k, v, tables = k.copy(), v.copy(), tables.copy()
    for b in range(low // BS):
        k[:, tables[slot, b]] = np.nan
        v[:, tables[slot, b]] = np.nan
        tables[slot, b] = 0
    return k, v, tables


@pytest.mark.parametrize("fills", [
    (0, 1, REACH, REACH + 1), (REACH + 2, 4 * REACH + 11, 101, 37),
    (MB * BS, 60, 61, 8),
], ids=["to-the-reach", "many-windows", "full-table"])
def test_decode_kernel_against_the_gathered_form_and_exact(pool, fills):
    k, v, tables = pool
    rng = np.random.default_rng(sum(fills))
    fills = np.asarray(fills, np.int32)
    active = np.array([True, True, True, False])
    q = rng.normal(size=(SLOTS, H, D)).astype(np.float32)
    kn = rng.normal(size=(SLOTS, KH, D)).astype(np.float32)
    vn = rng.normal(size=(SLOTS, KH, D)).astype(np.float32)
    kp, vp, tb = k, v, tables
    for slot in range(SLOTS):       # released blocks: NaN, sentinel
        kp, vp, tb = _poisoned(kp, vp, tb, slot, max(fills[slot] - REACH, 0))
    out = np.asarray(wa.pool_window_decode_attention(
        jnp.asarray(q), jnp.asarray(kn), jnp.asarray(vn), jnp.asarray(kp),
        jnp.asarray(vp), 1, jnp.asarray(tb), jnp.asarray(fills),
        jnp.asarray(active), REACH,
    ))
    assert np.isfinite(out).all()
    view = lambda p: p[1][tables].reshape(SLOTS, MB * BS, KH, D)  # noqa: E731
    ref = np.asarray(wa.window_reference(
        jnp.asarray(q)[:, None], jnp.asarray(kn)[:, None],
        jnp.asarray(vn)[:, None], jnp.asarray(view(k)),
        jnp.asarray(view(v)), jnp.asarray(fills)[:, None],
        jnp.asarray(fills), REACH,
    ))[:, 0]
    for slot in range(SLOTS):
        if not active[slot]:        # reads nothing: its own V row
            np.testing.assert_allclose(
                out[slot], np.repeat(vn[slot], H // KH, axis=0), atol=1e-6
            )
            continue
        np.testing.assert_allclose(out[slot], ref[slot], atol=2e-6)
        fill = fills[slot]
        exact = _exact(
            q[slot], kn[slot], vn[slot], view(k)[slot, :fill],
            view(v)[slot, :fill], fill, np.arange(fill),
        )
        np.testing.assert_allclose(out[slot], exact, atol=5e-6)


@pytest.mark.parametrize("start", [0, 8, 24, 40, 96])
def test_chunk_kernel_against_the_gathered_form_and_exact(pool, start):
    k, v, tables = pool
    rng = np.random.default_rng(start)
    t = 16
    q = rng.normal(size=(t, H, D)).astype(np.float32)
    kn = rng.normal(size=(t, KH, D)).astype(np.float32)
    vn = rng.normal(size=(t, KH, D)).astype(np.float32)
    # what the engine has released before this chunk: blocks wholly
    # below its FIRST token's band
    kp, vp, tb = _poisoned(k, v, tables, 2, max(start - REACH, 0))
    out = np.asarray(wa.pool_window_chunk_attention(
        jnp.asarray(q), jnp.asarray(kn), jnp.asarray(vn), jnp.asarray(kp),
        jnp.asarray(vp), 0, jnp.asarray(tb[2]), start, REACH,
    ))
    assert np.isfinite(out).all()
    view = lambda p: p[0][tables[2]].reshape(MB * BS, KH, D)  # noqa: E731
    positions = start + np.arange(t)
    ref = np.asarray(wa.window_reference(
        jnp.asarray(q)[None], jnp.asarray(kn)[None], jnp.asarray(vn)[None],
        jnp.asarray(view(k))[None], jnp.asarray(view(v))[None],
        jnp.asarray(positions)[None], jnp.asarray([start]), REACH,
    ))[0]
    np.testing.assert_allclose(out, ref, atol=2e-6)
    for row in (0, 7, t - 1):
        keys = np.concatenate([view(k)[:start], kn[:row]])
        values = np.concatenate([view(v)[:start], vn[:row]])
        exact = _exact(
            q[row], kn[row], vn[row], keys, values, start + row,
            np.arange(start + row),
        )
        np.testing.assert_allclose(out[row], exact, atol=5e-6)


def test_the_kernels_lower_where_the_predicate_says():
    ok = wa.window_kernels_supported
    cell = dict(block_size=64, n_heads=32, kv_heads=4, head_dim=128,
                chunk=512, slots=32, max_blocks=264)
    assert ok(jnp.bfloat16, **cell)
    assert ok(jnp.bfloat16, **dict(cell, kv_heads=8))
    assert not ok(jnp.float32, **cell)
    assert not ok(jnp.bfloat16, **dict(cell, head_dim=64))
    assert not ok(jnp.bfloat16, **dict(cell, kv_heads=2))
    assert not ok(jnp.bfloat16, **dict(cell, chunk=12))
    assert not ok(jnp.bfloat16, **dict(cell, slots=4096))
