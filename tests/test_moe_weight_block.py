"""The weight block a grouped matmul streams (``moe._weight_block``) and
the layout of the rows it streams them over (``moe._aligned_rows``): the
two rules at the benchmark cells' shapes, read off the tilings and the
row buffers the expert layer hands megablox ``gmm``, and the served
expert layer under the large blocks against today's tiles and a dense
per-expert reference (CPU, kernels interpreted)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import moe

# (entry, tokens, top_k, embed, mlp, experts routed over, experts held,
# layers' experts in the stack): the serve cells' decode step and prefill
# chunk, and the two train cells' step (8 experts of the layer held).
_CELLS = {
    "xing_decode": ("routed", 32, 4, 3584, 1024, 64, 64, 5),
    "xing_chunk": ("routed", 512, 4, 3584, 1024, 64, 64, 5),
    "keye_decode": ("routed", 16, 8, 2048, 768, 128, 128, 5),
    "keye_chunk": ("routed", 512, 8, 2048, 768, 128, 128, 5),
    "kimilinear_train": ("share", 8192, 8, 2304, 1024, 256, 8, 1),
    "glm47flash_train": ("share", 8192, 4, 2048, 1536, 64, 8, 1),
}
_VMEM_LIMIT = 16 * 2**20
# ... and the two serve cells that came after the weight blocks' table
# (eight expert layers each): the row layout's table walks these too
_LAYOUT_CELLS = {
    **_CELLS,
    "lfm2_decode": ("routed", 32, 4, 2048, 1536, 64, 64, 8),
    "lfm2_chunk": ("routed", 512, 4, 2048, 1536, 64, 64, 8),
    "mellum2_decode": ("routed", 32, 8, 2304, 896, 64, 64, 8),
    "mellum2_chunk": ("routed", 512, 8, 2304, 896, 64, 64, 8),
}


@pytest.fixture
def tilings(monkeypatch):
    """The ``tiling`` of every ``gmm`` call traced while it is in force,
    with the shapes it was called on; nothing is computed."""
    from jax.experimental.pallas.ops.tpu import megablox

    class Seen(list):
        """The tilings, and ``rows``: of the buffers the matmuls ran over."""

    seen = Seen()
    seen.rows = set()

    def gmm(lhs, rhs, group_sizes, preferred_element_type=jnp.float32,
            tiling=None, **_):
        seen.append((lhs.shape[1], rhs.shape[2], tiling))
        seen.rows.add(lhs.shape[0])
        return jnp.zeros((lhs.shape[0], rhs.shape[2]), preferred_element_type)

    monkeypatch.setattr(megablox, "gmm", gmm)
    return seen


def _trace(cell):
    entry, n, top_k, d, f, e_all, held, layers = _LAYOUT_CELLS[cell]
    bf16 = jnp.bfloat16

    def sds(*shape, dtype=bf16):
        return jax.ShapeDtypeStruct(shape, dtype)

    if entry == "routed":
        return jax.eval_shape(
            lambda x, ex, w, w_gu, w_down: moe.routed_experts(
                x, ex, w, w_gu, w_down, e_all, group_offset=2 * e_all
            ),
            sds(1, n, d), sds(n, top_k, dtype=jnp.int32),
            sds(n, top_k, dtype=jnp.float32),
            sds(layers * held, d, 2 * f), sds(layers * held, f, d),
        )
    else:
        return jax.eval_shape(
            lambda x, r, b, wg, wu, wd: moe.moe_mlp_share(
                x, r, b, wg, wu, wd, first=held, top_k=top_k
            ),
            sds(1, n, d), sds(d, e_all, dtype=jnp.float32),
            sds(e_all, dtype=jnp.float32),
            sds(held, d, f), sds(held, d, f), sds(held, f, d),
        )


@pytest.mark.parametrize("cell", sorted(_CELLS))
def test_the_weight_block_follows_the_rows_a_group_holds(cell, tilings):
    _, n, top_k, d, f, e_all, _, _ = _CELLS[cell]
    _trace(cell)
    assert {(k, n_) for k, n_, _ in tilings} == {(d, 2 * f), (f, d)}
    today = {
        (d, 2 * f): (moe._tile(d), moe._tile(2 * f)),
        (f, d): (moe._tile(f), moe._tile(d)),
    }
    even = n * top_k // e_all
    if even >= moe.ROW_TILE:
        # a trained shape: letter for letter the tuples it had
        for k, n_, (tm, tk, tn) in tilings:
            assert tm == min(even, 512)
            assert (tk, tn) == today[k, n_]
        return
    steps = {}
    for k, n_, (tm, tk, tn) in tilings:
        assert tm == 128
        assert tk % 128 == 0 and k % tk == 0
        assert tn % 128 == 0 and n_ % tn == 0
        assert tk * tn * 2 >= 3e6 > 2 * today[k, n_][0] * today[k, n_][1]
        blocks = 2 * tk * tn * 2 + 2 * tm * (tk + tn) * 2
        assert blocks + 4 * tm * tn <= _VMEM_LIMIT        # gmm
        assert blocks + 4 * tk * tn <= _VMEM_LIMIT        # its pullback's tgmm
        steps[k, n_] = (k // tk) * (n_ // tn), (
            (k // today[k, n_][0]) * (n_ // today[k, n_][1])
        )
    new, old = (sum(s[i] for s in steps.values()) for i in (0, 1))
    assert 7 * new <= old     # xing: 6 grid steps a visit for 42; keye 3 for 24


# The cells whose rows are laid out expert-aligned: the rule's table (the
# chip's readings under it: PERF.md section 6, PR 52).
_ALIGNED = {"mellum2_chunk", "lfm2_chunk", "xing_chunk"}


@pytest.mark.parametrize("cell", sorted(_LAYOUT_CELLS))
def test_the_row_layout_follows_the_shape(cell, tilings):
    """Expert-aligned where a group holds less than a row tile and the
    weights the tile edges send through twice outweigh what the aligned
    buffer adds (its rows, and the matmul that lays them out); packed,
    today's buffer to the row, anywhere else: every decode step, the
    chunk over 128 experts, the trained shapes. From shapes alone:
    nothing is computed here."""
    entry, n, top_k, d, f, e_all, held, _ = _LAYOUT_CELLS[cell]
    _, counters = _trace(cell)
    pairs = n * min(top_k, held)
    even = n * top_k // e_all
    tm = min(max(even, moe.ROW_TILE), 512)
    packed = -(-pairs // tm) * tm if pairs >= tm else pairs
    if cell in _ALIGNED:
        assert tilings.rows == {packed + held * moe.ROW_TILE}
        assert counters.weight_visits.shape == ()
        assert moe._aligned_rows(even, tm, packed, held, n, d, f, 2)
    else:
        # (a share's usual buffer is four even loads; its full one rarely)
        assert tilings.rows <= {packed, 4 * even * held}
        assert packed in tilings.rows
        assert counters.weight_visits is None
        assert not moe._aligned_rows(even, tm, packed, held, n, d, f, 2)
    if cell == "keye_chunk":
        # the nearest miss: the same 31 edges as the chunk over 64
        # experts for twice the rows added; half the experts would do
        assert moe._aligned_rows(even, tm, packed, held // 2, n, d, f, 2)


def test_the_weight_block_of_a_small_dimension_is_the_tile():
    # nothing of 128 divides 64 or 96: the dimension keeps its tile
    assert moe._weight_block(2, 16, 64, 96, 2) == (64, 32)
    assert moe._weight_block(2, 16, 64, 256, 2) == (64, 256)
    # float32 operands halve what fits
    assert moe._weight_block(2, 128, 3584, 2048, 4) == (512, 2048)


# -- the served layer under the large blocks ----------------------------------

_D, _F, _E, _TOP_K, _OFFSET = 384, 896, 4, 2, 4      # k, n: 3, 7, 14 x 128


def _layer(dtype, seed=0):
    """96 tokens x top-2 over 4 experts, the second layer of a stack of
    two: expert 0 gets 70 rows, expert 1 none, expert 2 96 (rows 70-165
    of the sorted buffer: it straddles the row tiles' edge at 128),
    expert 3 26; the buffer's last 64 rows belong to nobody."""
    keys = jax.random.split(jax.random.key(seed), 4)
    x = jax.random.normal(keys[0], (1, 96, _D), jnp.float32)
    w_gu = jax.random.normal(keys[1], (2 * _E, _D, 2 * _F)) * _D ** -0.5
    w_down = jax.random.normal(keys[2], (2 * _E, _F, _D)) * _F ** -0.5
    experts = jnp.array([[0, 2]] * 70 + [[3, 2]] * 26, jnp.int32)
    weights = jax.nn.softmax(jax.random.normal(keys[3], (96, _TOP_K)))
    return (
        x.astype(dtype), experts, weights, w_gu.astype(dtype),
        w_down.astype(dtype),
    )


def _served(x, experts, weights, w_gu, w_down):
    return moe.routed_experts(
        x, experts, weights, w_gu, w_down, _E, group_offset=_OFFSET,
        interpret=True,
    )


def _under_todays_tiles(monkeypatch):
    monkeypatch.setattr(
        moe, "_weight_block",
        lambda even, tm, k, n, itemsize: (moe._tile(k), moe._tile(n)),
    )


def _dense(x, experts, weights, w_gu, w_down):
    x, w_gu, w_down = (
        np.asarray(a.astype(jnp.float32)) for a in (x, w_gu, w_down)
    )
    out = np.zeros_like(x[0])
    for t, (chosen, by) in enumerate(zip(np.asarray(experts),
                                         np.asarray(weights))):
        for e, w in zip(chosen, by):
            hu = x[0, t] @ w_gu[_OFFSET + e]
            gate, up = hu[:_F], hu[_F:]
            out[t] += w * ((gate / (1 + np.exp(-gate)) * up)
                           @ w_down[_OFFSET + e])
    return out[None]


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_the_served_layer_under_large_blocks_is_the_layer(
        dtype, monkeypatch):
    args = _layer(jnp.dtype(dtype))
    size = jnp.dtype(dtype).itemsize
    assert moe._weight_block(48, 128, _D, 2 * _F, size) == (_D, 2 * _F)
    assert moe._weight_block(48, 128, _F, _D, size) == (_F, _D)
    out, counters = _served(*args)
    assert int(counters.experts_hit) == 3 and int(counters.rows_held) == 192
    assert int(counters.rows_max) == 96
    _under_todays_tiles(monkeypatch)
    was, _ = _served(*args)
    out, was = (np.asarray(a.astype(jnp.float32)) for a in (out, was))
    ref = _dense(*args)
    scale = np.abs(ref).max()
    # the same products under another order of float32 partial sums: a
    # bfloat16 result may round the other way, nothing more
    ulp = 2.0 ** -7 if dtype == "bfloat16" else 2.0 ** -20
    assert np.abs(out - was).max() <= 2 * ulp * scale
    assert np.abs(out - ref).max() <= (
        (8 * ulp if dtype == "bfloat16" else 1e-4) * scale
    )


def test_the_served_layers_gradient_under_large_blocks(monkeypatch):
    """``gmm``'s pullback runs a transposed ``gmm`` and ``tgmm`` under
    the forward's tiling: the large blocks give the gradients today's
    tiles give (float32, so that only the order of sums differs)."""
    x, experts, weights, w_gu, w_down = _layer(jnp.float32)

    def grads():
        return jax.grad(
            lambda x, w_gu, w_down: jnp.sum(jnp.sin(
                _served(x, experts, weights, w_gu, w_down)[0]
            )), argnums=(0, 1, 2),
        )(x, w_gu, w_down)

    new = grads()
    _under_todays_tiles(monkeypatch)
    for name, a, b in zip(("x", "w_gu", "w_down"), new, grads()):
        assert float(jnp.abs(b).max()) > 0, name
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-4 * float(
            jnp.abs(b).max()
        ), err_msg=name)
    # groups that got no row got no gradient: the other layer's, and
    # this layer's expert 1
    assert not np.asarray(new[1][:_OFFSET]).any()
    assert not np.asarray(new[1][_OFFSET + 1]).any()


def test_the_tool_walks_its_table_of_blocks_at_toy_widths():
    """``tools/bench_moe_dispatch.py --serve --tiny``: one line a
    (shape, blocks), today's blocks first, the grid steps counted from
    the groups' sizes, and the rule back in its place afterwards."""
    import os
    import sys

    sys.path.insert(
        0, os.path.join(os.path.dirname(__file__), "..", "tools")
    )
    import bench_moe_dispatch

    rule = moe._weight_block
    today, halved = bench_moe_dispatch.run_serve(
        ["tiny"], repeats=1, tiny=True
    )
    assert moe._weight_block is rule
    assert today["today"] and today["chosen"] and not halved["today"]
    assert today["finite"] and halved["finite"]
    # 48 sorted rows in tiles of 16: 4 groups hit and 2 tile edges, each
    # inside a group that is then visited twice; a visit streams 1 + 1
    # of today's blocks, 2 + 2 of the halved ones
    assert today["experts_hit_mean"] == 4 and today["tm"] == 16
    assert today["grid_steps_per_layer"] == 6 * 2
    assert halved["grid_steps_per_layer"] == 6 * 4


def test_the_tool_walks_both_row_layouts_at_toy_widths():
    """``tools/bench_moe_dispatch.py --serve --layouts --valid-rows 10
    --tiny``: a line a layout under the rule's blocks, the visits counted
    by the layer itself, and both rules back in their places."""
    import os
    import sys

    sys.path.insert(
        0, os.path.join(os.path.dirname(__file__), "..", "tools")
    )
    import bench_moe_dispatch

    rules = moe._weight_block, moe._aligned_rows
    packed, aligned = bench_moe_dispatch.run_serve(
        ["tiny"], repeats=1, tiny=True, layouts=True, valid_rows=10
    )
    assert (moe._weight_block, moe._aligned_rows) == rules
    assert [packed["layout"], aligned["layout"]] == ["packed", "aligned"]
    assert packed["by_rule"] == aligned["by_rule"] == "packed"
    assert packed["chosen"] and aligned["chosen"]
    assert packed["valid_rows"] == 10 and packed["finite"]
    # 14 of 24 tokens are the eleventh repeated: two groups of 14 and more
    assert packed["rows_max"] >= 14
    # 48 rows packed in tiles of 16: two edges, each inside a group;
    # aligned, a whole tile of 128 a group and four more for the buffer
    assert packed["buffer_rows"] == 48 and aligned["buffer_rows"] == 5 * 128
    assert packed["weight_visits_mean"] > packed["experts_hit_mean"]
    assert aligned["weight_visits_mean"] == aligned["experts_hit_mean"]
    # the first layer's output: one rounding apart at most
    assert aligned["max_abs_diff_from_packed"] <= (
        2.0 ** -6 * aligned["max_abs"]
    )
