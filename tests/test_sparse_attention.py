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


# Each case: one slot's prefill chunk of ``chunk`` tokens over a pool of
# ``bs``-row pages behind a shuffled table, ``start`` cache rows below
# it, each query selecting ``topk`` of the rows it may see. 8 query
# heads on 2 KV heads; a VMEM chunk holds 2 pages, a grid step 8 tokens.
_KERNEL_CASES = {
    # A prompt's first chunk: nothing below it, no page read; its first
    # queries see fewer rows than topk.
    "start_0": dict(start=0),
    # The prefix ends mid-page: that page's rows past ``start`` hold
    # something else than the chunk's own rows, which the selection marks.
    "start_mid_page": dict(start=20),
    "start_at_a_page_boundary": dict(start=32),
    # Many pages at a tiny page (the cell: 512 pages below the chunk).
    "many_pages": dict(start=232, bs=4, mb=64),
    # The last token tile is padding: skipped, left zero.
    "a_padded_tile_is_skipped": dict(start=24, n_valid=7),
    "a_tile_half_valid": dict(start=24, n_valid=12),
    # Fewer visible rows than topk: causal attention.
    "below_topk_is_causal_attention": dict(start=8, topk=64),
    # A row of tied index scores: which of them are attended is the
    # mask's tie rule (lower positions), none of the kernel's.
    "tied_scores": dict(start=40, tied=True),
    # Every selected key of the first tile's queries lies in the chunk
    # itself: chunk after chunk of pages shows them nothing.
    "nothing_selected_below_start": dict(start=48, own_only=True, topk=1),
}


@pytest.mark.parametrize("pool_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(_KERNEL_CASES))
def test_sparse_chunk_kernel_is_masked_attention_over_the_view(
    case, pool_dtype, monkeypatch,
):
    """``ops.decode_attention.sparse_chunk_attention`` (interpret mode
    on the CPU) against ``masked_attention`` over the slot's gathered
    view with the chunk laid in at ``start``, under one selection.
    Layer 1 of a two-layer pool; pages the table does not reach below
    ``start`` hold NaNs, which the kernel must never copy."""
    from dlrover_tpu.ops import decode_attention as da

    spec = dict(_KERNEL_CASES[case])
    t, h, kh, d = 16, 8, 2, 128
    bs, mb, start = spec.get("bs", 8), spec.get("mb", 12), spec["start"]
    topk, n_valid = spec.get("topk", 10), spec.get("n_valid")
    n_layers, layer, max_len = 2, 1, mb * bs
    monkeypatch.setattr(da, "_POOL_CHUNK_BYTES", 2 * bs * kh * d * 2)
    monkeypatch.setattr(da, "_CHUNK_QUERY_ROWS", 8 * h)
    rs = np.random.RandomState(3)
    table = (rs.permutation(2 * mb)[:mb] + 1).astype(np.int32)
    ks = jax.random.split(jax.random.key(5), 6)
    pool_shape = (n_layers, 2 * mb + 1, bs, kh, d)
    k_pool = jax.random.normal(ks[0], pool_shape).astype(pool_dtype)
    v_pool = jax.random.normal(ks[1], pool_shape).astype(pool_dtype)
    q = jax.random.normal(ks[2], (t, h, d)).astype(pool_dtype)
    k_new = jax.random.normal(ks[3], (t, kh, d)).astype(pool_dtype)
    v_new = jax.random.normal(ks[4], (t, kh, d)).astype(pool_dtype)
    scores = jax.random.normal(ks[5], (t, max_len))
    if spec.get("tied"):
        scores = scores.at[3].set(0.25).at[9, 5:50].set(7.0)
    if spec.get("own_only"):
        scores = scores.at[:8, start:].add(100.0)
    at = start + jnp.arange(t)
    visible = jnp.arange(max_len)[None, :] <= at[:, None]
    selection = sa.select_mask(scores, visible, topk)
    if spec.get("own_only"):
        assert not np.asarray(selection)[:8, :start].any()

    def view(pool, new):
        rows = pool[layer][table].reshape(max_len, kh, d)
        return jax.lax.dynamic_update_slice(rows, new, (start, 0, 0))

    want = np.asarray(sa.masked_attention(
        q, view(k_pool, k_new), view(v_pool, v_new), selection
    ), np.float32)
    if topk >= start + t:
        np.testing.assert_allclose(want, np.asarray(sa.masked_attention(
            q, view(k_pool, k_new), view(v_pool, v_new), visible
        ), np.float32))
    unread = table[-(-start // bs):]
    k_pool = k_pool.at[:, unread].set(jnp.nan)
    v_pool = v_pool.at[:, unread].set(jnp.nan)
    got = np.asarray(da.sparse_chunk_attention(
        q, k_new, v_new, k_pool, v_pool, jnp.int32(layer),
        jnp.asarray(table), jnp.int32(start), selection,
        None if n_valid is None else jnp.int32(n_valid),
    ), np.float32)
    assert np.isfinite(got).all()
    live = t if n_valid is None else -(-n_valid // 8) * 8
    assert not got[live:].any()
    # f32: the order of summation; bf16: the query's scale in its own
    # dtype, the probabilities' and the output's rounding.
    tol = dict(rtol=2e-5, atol=2e-6) if pool_dtype == "float32" else dict(
        rtol=2 ** -6, atol=2 ** -7
    )
    np.testing.assert_allclose(got[:live], want[:live], **tol)
    # A key the selection does not mark is not attended: another
    # selection is another answer.
    other = np.asarray(da.sparse_chunk_attention(
        q, k_new, v_new, k_pool, v_pool, jnp.int32(layer),
        jnp.asarray(table), jnp.int32(start),
        sa.select_mask(-scores, visible, topk),
    ), np.float32)
    if topk < start + t:
        assert np.abs(other - got)[t - 1].max() > 1e-2
