"""``ops/block_sparse_attention.py``: decode attention over a LIST of
pages, the kernel interpreted on the CPU against the gathered form
(``list_attention``) and the exact softmax, over lists of one page, many
pages, a last page of 0 / 1 / all-but-one rows, an inactive list, and NaN
in every page no list names (the kernel must not read them); and the
model's selection (``models/linear_sparse_lm.py``) against a sort, over
fills 0 / 1 / kernel - 1 / kernel / a block +- 1 / many blocks and
ties."""

import numpy as np
import pytest

import jax.numpy as jnp

from dlrover_tpu.models import linear_sparse_lm as lsm
from dlrover_tpu.ops import block_sparse_attention as bsa

PAGES, BS, D, G, WIDTH = 40, 16, 128, 4, 6


@pytest.fixture(scope="module")
def pool():
    rng = np.random.default_rng(0)
    k = rng.normal(size=(PAGES, BS, D)).astype(np.float32)
    v = rng.normal(size=(PAGES, BS, D)).astype(np.float32)
    return k, v


def _exact(q, k_new, v_new, keys, values):
    keys = np.concatenate([keys, k_new[None]]).astype(np.float64)
    values = np.concatenate([values, v_new[None]]).astype(np.float64)
    s = keys @ (q.astype(np.float64).T * D ** -0.5)          # [t, g]
    p = np.exp(s - s.max(0))
    return ((p / p.sum(0)).T @ values)


@pytest.mark.parametrize("lengths", [
    (0, 1, BS - 1, BS), (BS + 1, 3 * BS + 5, WIDTH * BS, 2 * BS),
], ids=["to-a-page", "many-pages"])
def test_the_list_kernel_against_the_gathered_form_and_the_softmax(
    pool, lengths
):
    k, v = pool
    rng = np.random.default_rng(1)
    b = len(lengths)
    q = rng.normal(size=(b, G, D)).astype(np.float32)
    k_new = rng.normal(size=(b, D)).astype(np.float32)
    v_new = rng.normal(size=(b, D)).astype(np.float32)
    pages = rng.permutation(np.arange(1, PAGES))[:b * WIDTH].reshape(
        b, WIDTH
    ).astype(np.int32)
    length = np.asarray(lengths, np.int32)
    # NaN in every page no list names, and in every listed page past the
    # last one the list's query sees
    k_bad, v_bad = k.copy(), v.copy()
    needed = set()
    for i in range(b):
        needed.update(pages[i, :-(-int(length[i]) // BS)].tolist())
    for page in range(PAGES):
        if page not in needed:
            k_bad[page] = v_bad[page] = np.nan
    args = [jnp.asarray(a) for a in (q, k_new, v_new)]
    got = np.asarray(bsa.list_decode_attention(
        *args, jnp.asarray(k_bad), jnp.asarray(v_bad), jnp.asarray(pages),
        jnp.asarray(length), jnp.ones((b,), bool), interpret=True,
    ))
    plain = np.asarray(bsa.list_attention(
        *args, jnp.asarray(k), jnp.asarray(v), jnp.asarray(pages),
        jnp.asarray(length),
    ))
    assert np.isfinite(got).all()
    for i in range(b):
        n = int(length[i])
        rows_k = k[pages[i]].reshape(-1, D)[:n]
        rows_v = v[pages[i]].reshape(-1, D)[:n]
        want = _exact(q[i], k_new[i], v_new[i], rows_k, rows_v)
        assert np.abs(got[i] - want).max() < 1e-4
        assert np.abs(plain[i] - want).max() < 1e-4


def test_an_inactive_list_reads_nothing_and_answers_its_own_row(pool):
    k, _ = pool
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(2, G, D)), jnp.float32)
    k_new = jnp.asarray(rng.normal(size=(2, D)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(2, D)), jnp.float32)
    pages = jnp.asarray([[3, 4, 5, 6, 7, 8], [0, 0, 0, 0, 0, 0]], jnp.int32)
    bad = jnp.full_like(jnp.asarray(k), jnp.nan)
    got = np.asarray(bsa.list_decode_attention(
        q, k_new, v_new, bad, bad, pages, jnp.asarray([40, 33]),
        jnp.asarray([False, False]), interpret=True,
    ))
    assert np.abs(got - np.asarray(v_new)[:, None, :]).max() < 1e-6


def test_what_the_list_kernel_lowers_for():
    assert bsa.list_kernel_supported(jnp.bfloat16, 64, 128)
    assert not bsa.list_kernel_supported(jnp.float32, 64, 128)
    assert not bsa.list_kernel_supported(jnp.bfloat16, 8, 128)
    assert not bsa.list_kernel_supported(jnp.bfloat16, 64, 64)
    assert not bsa.list_kernel_supported(jnp.bfloat16, 8192, 128)


def _sorted_selection(cfg, scores, t):
    """The selection of a query at row ``t`` by a SORT: forced blocks
    first, then by score, ties to the lower block."""
    own = t // cfg.sparse_block
    if t + 1 <= cfg.dense_len:
        return set(range(own + 1))
    forced = {b for b in range(own + 1)
              if b < cfg.init_blocks or own - b < cfg.window_blocks}
    rest = sorted(
        (b for b in range(own + 1) if b not in forced),
        key=lambda b: (-scores[b], b),
    )
    return forced | set(rest[:max(cfg.topk - len(forced), 0)])


@pytest.mark.parametrize("t", [0, 1, 2, 3, 7, 8, 9, 15, 16, 17, 63, 64, 95])
def test_the_selection_is_the_sorts_at_every_fill(t):
    """Rows 0 / 1 / kernel - 1 / kernel / a block +- 1 / dense_len +- 1 /
    many blocks, with TIES among the scores (whole runs of equal
    blocks)."""
    cfg = lsm.tiny_config(topk=5)          # 2 of a list's 5 go by score
    rng = np.random.default_rng(t)
    n_blocks = 12
    scores = np.round(rng.random((2, 1, n_blocks)), 1).astype(np.float32)
    positions = jnp.asarray([t])
    mask = np.asarray(
        lsm.select_block_mask(cfg, jnp.asarray(scores), positions)
    )
    lists, count = lsm.select_block_list(cfg, jnp.asarray(scores), positions)
    for kh in range(2):
        want = _sorted_selection(cfg, scores[kh, 0], t)
        assert set(np.nonzero(mask[kh, 0])[0].tolist()) == want
        got = np.asarray(lists[kh, 0, :int(count[kh, 0])]).tolist()
        assert set(got) == want and len(got) == len(want)
        assert got[-1] == t // cfg.sparse_block


def test_block_scores_see_only_finished_keys_and_pool_to_their_blocks():
    cfg = lsm.tiny_config()
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(3, 4, 8)), jnp.float32)
    ckeys = jnp.asarray(rng.normal(size=(16, 2, 8)), jnp.float32)
    positions = jnp.asarray([0, 3, 20])
    seen = np.asarray(lsm.ckeys_visible(cfg, positions, 16))
    assert seen[0].sum() == 0             # row 0: no key is finished
    assert seen[1].tolist()[:3] == [False, True, False]   # place 1 ends at 3
    assert seen[2].sum() == 9             # places 1-9 end at rows 3-19
    b = np.asarray(lsm.block_scores(cfg, q, ckeys, positions))
    assert b.shape == (2, 3, 4) and (b[:, 0] == 0).all()
    # a group's probabilities sum to its heads over the places; a block's
    # score is one place's
    assert (b[:, 2] <= 2.0 + 1e-6).all() and (b[:, 2, :3] > 0).all()
    assert (b[:, 2, 3] == 0).all()        # places 12-16: none finished
