"""The decode step's selection kernel (``ops/block_select.py``), interpreted
on a CPU, against ``linear_sparse_lm.block_scores`` over the table-gathered
view (``kvpool/linear.decode_block_scores``'s ``jnp`` form): scores to
float32 tolerance and ``select_block_list``'s lists IDENTICAL, over the
tables a pool can hold (one long run, no run at all, a run broken inside a
group, sentinels past a fill that ends inside a group), a step that
completes a place beside one that does not, an inactive slot and a fill
under ``dense_len``; with a float32 pool (a place a row) and a bf16 pool
of 128-wide keys (two places a 32-bit word, as the device packs them)."""

import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import linear_sparse_lm as lsm
from dlrover_tpu.ops import block_select
from dlrover_tpu.serving.kvpool import linear

SLOTS, MAX_BLOCKS, NUM_BLOCKS, BS = 3, 40, 160, 8


def consecutive():
    return np.stack([1 + 45 * s + np.arange(MAX_BLOCKS) for s in range(SLOTS)])


def shuffled():
    rng = np.random.default_rng(1)
    return np.stack([
        1 + rng.permutation(NUM_BLOCKS - 1)[:MAX_BLOCKS] for _ in range(SLOTS)
    ])


def broken():
    """A run broken inside a group (one foreign id), a run that restarts
    at a group's edge and one that restarts one entry past it."""
    t = consecutive()
    t[0, 20] = 150
    t[1, 16:] += 3
    t[2, 17:] += 2
    return t


def sentinel_tail(fills):
    t = consecutive()
    for s, fill in enumerate(fills):
        t[s, fill // BS + 1:] = 0
    return t


FULL = [MAX_BLOCKS * BS - 1, MAX_BLOCKS * BS - 20, MAX_BLOCKS * BS - 37]
MID_GROUP = [BS * 21 + 3, BS * 17 - 1, BS * 33]
CASES = {
    "consecutive": (consecutive(), FULL, None),
    "shuffled": (shuffled(), FULL, None),
    "run_broken_in_a_group": (broken(), FULL, None),
    "sentinels_and_a_fill_mid_group": (
        sentinel_tail(MID_GROUP), MID_GROUP, None),
    # rows 301 and 299 complete a place (row + 1 a whole stride), 300 not
    "a_step_completes_a_place_beside_one_that_does_not": (
        consecutive(), [301, 300, 299], None),
    "an_inactive_slot": (
        sentinel_tail([FULL[0], -BS, FULL[2]]), [FULL[0], 0, FULL[2]],
        [True, False, True]),
    "fills_under_dense_len": (sentinel_tail([11, 2, 14]), [11, 2, 14], None),
}


@pytest.mark.parametrize("dtype, head_dim", [
    ("float32", 8), ("bfloat16", 128),
])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_scores_and_lists_as_the_definition(case, dtype, head_dim):
    c = lsm.tiny_config(head_dim=head_dim, dtype=dtype)
    tables, lengths, active = CASES[case]
    rng = np.random.default_rng(0)
    f = lambda *s: jnp.asarray(rng.normal(size=s), c.compute_dtype)  # noqa: E731
    ck = f(c.cache_layers, NUM_BLOCKS, c.ckeys_per_block, head_dim)
    q, fresh = f(SLOTS, c.n_heads, head_dim), f(SLOTS, c.n_kv_heads, head_dim)
    tables = jnp.asarray(tables, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    live = jnp.ones((SLOTS,), bool) if active is None else jnp.asarray(active)
    layer = c.sparse_layers[-1]
    want, got = (
        np.asarray(linear.decode_block_scores(
            c, q, fresh, ck, layer, tables, lengths, live, select
        )) for select in ("jnp", "pool_kernel")
    )
    assert got.shape == want.shape == (SLOTS, c.n_kv_heads, MAX_BLOCKS)
    on = np.asarray(live)
    np.testing.assert_allclose(got[on], want[on], rtol=2e-6, atol=1e-7)
    assert (got[~on] == 0).all()
    assert want[on].max() > 0 or max(np.asarray(lengths)) < 4
    for a, b in zip(linear.decode_block_lists(c, jnp.asarray(got), lengths),
                    linear.decode_block_lists(c, jnp.asarray(want), lengths)):
        np.testing.assert_array_equal(np.asarray(a)[on], np.asarray(b)[on])


@pytest.mark.parametrize("group_blocks", [8, 16, 32])
def test_the_groups_size_moves_the_copies_and_not_the_scores(group_blocks):
    c = lsm.tiny_config()
    tables, lengths = broken(), np.asarray(FULL)
    rng = np.random.default_rng(2)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    ck = f(c.cache_layers, NUM_BLOCKS, c.ckeys_per_block, c.head_dim)
    q = f(SLOTS, c.n_kv_heads, c.group, c.head_dim)
    n_visible = (lengths + 1) // c.kernel_stride
    none = jnp.full((SLOTS,), -1, jnp.int32)
    own = jnp.zeros(q.shape[:3], jnp.float32)
    got = block_select.pool_block_scores(
        q, own, ck, 2, jnp.asarray(tables, jnp.int32), n_visible, none,
        group_blocks=group_blocks,
    )
    want = block_select.pool_block_scores(
        q, own, ck, 2, jnp.asarray(tables, jnp.int32), n_visible, none,
        group_blocks=1,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-7)
    blocks = -(-n_visible // c.ckeys_per_block)
    groups, runs = block_select.copy_groups(tables, blocks, group_blocks)
    assert groups == sum(-(-b // group_blocks) for b in blocks)
    # 40, 38 and 36 blocks: slot 0 holds a foreign id at entry 20, slot 1
    # restarts at entry 16 (an edge of 8 and of 16, inside a group of
    # 32), slot 2 at entry 17; a group past the fill's last whole one is
    # no run.
    assert runs == {8: 4 + 4 + 3, 16: 1 + 2 + 1, 32: 0}[group_blocks]


def test_copy_groups_counts_only_the_groups_at_or_below_the_fill():
    tables = consecutive()
    assert block_select.copy_groups(tables, [40, 17, 0], 16) == (3 + 2, 2 + 1)
    assert block_select.copy_groups(tables[:0], [], 16) == (0, 0)
