"""ops/sparse_attention.py against brute force and against the plain
reference (benchmark/reference_keye.py): index scores, the threshold
selection with its tie rule as a mask and as row indices, and both
attentions; below ``topk`` visible rows everything is dense attention."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_keye as ref
from dlrover_tpu.ops import sparse_attention as sa


def _brute_select(scores, visible, topk):
    """Row by row: sort by (-score, position), keep the first topk."""
    out = np.zeros(scores.shape, bool)
    for r in range(scores.shape[0]):
        seen = np.nonzero(visible[r])[0]
        order = sorted(seen, key=lambda i: (-scores[r, i], i))
        out[r, order[:topk]] = True
    return out


def _inputs(seed, t=40, h=4, kh=2, hd=8, hi=2, di=8):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32))  # noqa: E731
    return f(t, h, hd), f(t, kh, hd), f(t, kh, hd), f(t, hi, di), f(t, di), f(t, hi)


def test_index_scores_match_the_reference():
    _, _, _, q_idx, k_idx, w = _inputs(0)
    got = sa.index_scores(q_idx, w, k_idx)
    with jax.default_matmul_precision("highest"):
        want = ref.index_scores(q_idx, w, k_idx)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # batched leading axes, as the decode step calls it
    got_b = sa.index_scores(q_idx[None, :3], w[None, :3], k_idx[None])
    np.testing.assert_allclose(got_b[0], want[:3], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_select_mask_is_the_topk_by_score_then_position(seed):
    rng = np.random.default_rng(seed)
    s, topk = 70, 12
    scores = rng.normal(size=(6, s)).astype(np.float32)
    fills = np.array([69, 40, 12, 11, 3, 0])
    visible = np.arange(s)[None, :] <= fills[:, None]
    got = np.asarray(sa.select_mask(jnp.asarray(scores), jnp.asarray(visible), topk))
    np.testing.assert_array_equal(got, _brute_select(scores, visible, topk))
    assert (got.sum(-1) == np.minimum(fills + 1, topk)).all()
    assert not (got & ~visible).any()


def test_ties_at_the_threshold_go_to_the_lower_position():
    s, topk = 64, 8
    scores = np.full((4, s), -1.0, np.float32)
    scores[0, 10:30] = 0.5                 # 20 tied candidates for 8 places
    scores[1, [3, 50]] = 2.0               # 2 above, 6 of the tied zeros
    scores[1, 20:40] = 0.0
    scores[1, 25] = -0.0                   # -0.0 ties with 0.0
    scores[2, :] = 0.0                     # all tied (dead relus)
    scores[3, 5:9] = np.float32(1e-30)     # tiny positives beat zeros
    scores[3, 30:60] = 0.0
    visible = np.ones((4, s), bool)
    got = np.asarray(sa.select_mask(jnp.asarray(scores), jnp.asarray(visible), topk))
    assert np.nonzero(got[0])[0].tolist() == list(range(10, 18))
    assert np.nonzero(got[1])[0].tolist() == [3, 20, 21, 22, 23, 24, 25, 50]
    assert np.nonzero(got[2])[0].tolist() == list(range(8))
    assert np.nonzero(got[3])[0].tolist() == [5, 6, 7, 8, 30, 31, 32, 33]
    # the reference's explicit top-k mask has the same rule
    want = np.asarray(ref.topk_mask(jnp.asarray(scores), jnp.asarray(visible), topk))
    np.testing.assert_array_equal(got, want)


def test_select_indices_are_the_masks_rows():
    """The decode step's form of the selection: the same set as the
    mask, ties included, and places past a short row's count invalid."""
    rng = np.random.default_rng(3)
    s_len, topk = 64, 8
    scores = rng.normal(size=(5, s_len)).astype(np.float32)
    scores[1, 10:30] = 0.5
    scores[1, :10] = -1.0
    scores[1, 30:] = -1.0
    scores[2, :] = 0.0
    fills = np.array([63, 63, 40, 4, 0])
    visible = np.arange(s_len)[None, :] <= fills[:, None]
    mask = np.asarray(sa.select_mask(
        jnp.asarray(scores), jnp.asarray(visible), topk
    ))
    idx, valid = sa.select_indices(
        jnp.asarray(scores), jnp.asarray(visible), topk
    )
    idx, valid = np.asarray(idx), np.asarray(valid)
    for r in range(5):
        assert sorted(idx[r][valid[r]].tolist()) == \
            np.nonzero(mask[r])[0].tolist()
        assert valid[r].sum() == min(fills[r] + 1, topk)


def test_masked_and_gathered_attention_match_the_reference():
    q, k, v, q_idx, k_idx, w = _inputs(4)
    t, topk = q.shape[0], 8
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    with jax.default_matmul_precision("highest"):
        want = ref.sparse_attention(q, k, v, q_idx, k_idx, w, topk)
    mask = sa.select_mask(sa.index_scores(q_idx, w, k_idx), causal, topk)
    got = sa.masked_attention(q, k, v, mask)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    # the decode form: every query gathers its own rows
    idx, valid = sa.select_indices(
        sa.index_scores(q_idx, w, k_idx), causal, topk
    )
    got_g = sa.gathered_attention(q, k[idx], v[idx], valid)
    np.testing.assert_allclose(got_g, want, rtol=2e-5, atol=2e-6)


def test_below_topk_rows_the_selection_is_dense_attention():
    q, k, v, q_idx, k_idx, w = _inputs(5, t=24)
    t = q.shape[0]
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    mask = sa.select_mask(sa.index_scores(q_idx, w, k_idx), causal, 32)
    np.testing.assert_array_equal(np.asarray(mask), np.asarray(causal))
    with jax.default_matmul_precision("highest"):
        dense = ref.sparse_attention(q, k, v, q_idx, k_idx, w, 32, dense=True)
    np.testing.assert_allclose(
        sa.masked_attention(q, k, v, mask), dense, rtol=2e-5, atol=2e-6
    )
