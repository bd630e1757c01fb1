"""A share's fast path (``moe._share_rows_held``, PR 54) against the
path it took before (``moe._share_rows``) through the same buffer: the
output and every gradient, over routings in which tokens hold 0 to
``min(top_k, held)`` of the held experts; the two ways out of it (more
pairs than the usual buffer, more many-row tokens than the tail holds);
the counter that says which path ran; and the fast branch's jaxpr, which
may hold nothing sized by the (token, k) pairs but one sort."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import moe

N, TOP_K, E_ALL, HELD, FIRST, D, F = 96, 4, 32, 4, 4, 16, 8
PAIRS = N * TOP_K
EVEN = PAIRS // E_ALL                  # 12 rows an evenly loaded expert
USUAL = 2 * moe.ROW_TILE               # four even shares' 192 rows, in tiles
MOST = N * min(TOP_K, HELD)            # 384: the buffer for any routing


def _routing(counts, seed=0):
    """``experts [N, TOP_K]`` in which token ``t`` holds ``counts[t %
    len(counts)]`` of the held experts (distinct experts a token, the
    held ones at seeded places among its ``k``), and seeded weights."""
    rng = np.random.default_rng(seed)
    held = np.arange(FIRST, FIRST + HELD)
    absent = np.setdiff1d(np.arange(E_ALL), held)
    experts = np.empty((N, TOP_K), np.int32)
    for t in range(N):
        c = counts[t % len(counts)]
        row = np.concatenate([
            rng.choice(held, c, replace=False),
            rng.choice(absent, TOP_K - c, replace=False),
        ])
        experts[t] = rng.permutation(row)
    weights = rng.uniform(0.1, 1.0, (N, TOP_K)).astype(np.float32)
    return jnp.asarray(experts), jnp.asarray(weights)


def _operands():
    ks = jax.random.split(jax.random.key(1), 4)
    return (
        jax.random.normal(ks[0], (N, D)),
        jax.random.normal(ks[1], (HELD, D, F)) / D ** 0.5,
        jax.random.normal(ks[2], (HELD, D, F)) / D ** 0.5,
        jax.random.normal(ks[3], (HELD, F, D)) / F ** 0.5,
    )


def _maps(experts):
    local = experts.reshape(PAIRS) - FIRST
    is_held = (local >= 0) & (local < HELD)
    local = jnp.where(is_held, local, HELD)
    sizes = jnp.bincount(local, length=HELD + 1)[:HELD].astype(jnp.int32)
    return local, is_held, sizes


@functools.lru_cache(maxsize=None)
def _before(rows):
    """The path a share took before, through ``rows`` rows."""
    def run(xf, weights, w_gate, w_up, w_down, experts):
        local, is_held, sizes = _maps(experts)
        order = jnp.argsort(local, stable=True)
        return moe._share_rows(
            xf, weights, w_gate, w_up, w_down, order, jnp.argsort(order),
            is_held, sizes, jnp.sum(sizes), rows, moe.ROW_TILE, True, None,
            EVEN,
        )
    return run


@functools.lru_cache(maxsize=None)
def _held(rows, tail):
    def run(xf, weights, w_gate, w_up, w_down, experts):
        local, _, sizes = _maps(experts)
        group_of = local.reshape(N, TOP_K)
        member = jnp.sum(
            group_of[:, :, None] == jnp.arange(HELD), axis=1, dtype=jnp.int32
        )
        return moe._share_rows_held(
            xf, weights, w_gate, w_up, w_down, None, group_of, member, sizes,
            jnp.sum(sizes), rows, tail, moe.ROW_TILE, True, EVEN,
        )
    return run


@functools.lru_cache(maxsize=None)
def _compiled(run):
    """``run``'s output and gradients as ONE compiled function of the
    operands and the routing: every routing of a shape shares it."""
    cot = jax.random.normal(jax.random.key(7), (N, D))

    def loss(xf, weights, w_gate, w_up, w_down, experts):
        out = run(xf, weights, w_gate, w_up, w_down, experts)
        return jnp.sum(out * cot), out

    return jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3, 4), has_aux=True
    ))


def _out_and_grads(run, experts, weights):
    xf, w_gate, w_up, w_down = _operands()
    (_, out), grads = _compiled(run)(
        xf, weights, w_gate, w_up, w_down, experts
    )
    return (out, *grads)


NAMES = ("out", "d_x", "d_routing_weights", "d_w_gate", "d_w_up", "d_w_down")


# counts a token holds, cycled over the tokens: none or one (most of a
# trained share's tokens), two (the second level), three (the tail), and
# min(top_k, held) = 4 (every pair of the token held)
ROUTINGS = {
    "none": (0,),
    "none_or_one": (0, 1, 0, 0),
    "up_to_two": (0, 1, 2, 0, 1),
    "up_to_three": (0, 1, 2, 3, 0, 0),
    "up_to_all": (0, 1, 2, 3, 4, 0, 0, 1),
    "all_three": (3,),
}


@functools.lru_cache(maxsize=None)
def _both_paths(routing):
    """(the path before, the held-rows path): output and gradients, once
    a routing for the cases that compare a part each."""
    experts, weights = _routing(ROUTINGS[routing])
    rows = 3 * moe.ROW_TILE                  # holds all_three's 288 pairs
    return (
        _out_and_grads(_before(rows), experts, weights),
        _out_and_grads(_held(rows, N), experts, weights),
    )


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_the_held_rows_path_is_the_path_before(routing, name):
    want, got = _both_paths(routing)
    at = NAMES.index(name)
    np.testing.assert_allclose(got[at], want[at], atol=2e-5, rtol=2e-5)
    if routing != "none":
        assert float(jnp.max(jnp.abs(want[at]))) > 0    # compared something


def test_a_small_tail_buffer_holds_exactly_its_tokens():
    """16 tokens hold three rows: a tail of 16 slots is enough, and the
    slots past the last such token stay free."""
    experts, weights = _routing(ROUTINGS["up_to_three"])
    rows = 3 * moe.ROW_TILE
    want = _out_and_grads(_before(rows), experts, weights)
    got = _out_and_grads(_held(rows, 16), experts, weights)
    more = _out_and_grads(_held(rows, 40), experts, weights)
    for a, b, c in zip(got, want, more):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(a, c, atol=1e-6, rtol=1e-6)


def _layer(experts, weights):
    """``moe._routed_share`` as ``moe_mlp_share`` calls it, with this
    file's routing in place of a router's."""
    xf, w_gate, w_up, w_down = _operands()
    out, sizes, n_held, _, full = moe._routed_share(
        xf, (1, N, D), experts, weights, E_ALL, w_down, w_gate=w_gate,
        w_up=w_up, first=FIRST, interpret=True,
    )
    return out.reshape(N, D), sizes, n_held, full


# (routing, tail buffer, the path it must take)
WAYS = {
    "fits": ("up_to_three", 16, "fast"),
    "tail_overflows": ("up_to_three", 15, "full"),
    "every_level": ("up_to_all", N, "fast"),          # 132 pairs <= 256
    "no_tail_at_all": ("up_to_two", 0, "fast"),
}


@pytest.mark.parametrize("way", sorted(WAYS))
def test_the_cond_takes_the_path_the_counts_say(way, monkeypatch):
    routing, tail, path = WAYS[way]
    monkeypatch.setattr(moe, "TAIL_TOKENS", tail)
    experts, weights = _routing(ROUTINGS[routing])
    out, sizes, n_held, full = _layer(experts, weights)
    assert int(n_held) == int(jnp.sum(sizes)) <= USUAL
    assert int(full) == (0 if path == "fast" else int(n_held))
    want = _out_and_grads(_before(MOST), experts, weights)[0]
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


@functools.lru_cache(maxsize=None)
def _through_the_cond(way):
    """``_routed_share`` under a tail of 15 tokens: (output and
    gradients, ``n_held``, the counter), and the path before's."""
    real, moe.TAIL_TOKENS = moe.TAIL_TOKENS, 15
    try:
        experts, weights = _routing(ROUTINGS[
            "up_to_three" if way == "tail_overflows" else "all_three"
        ])
        xf, w_gate, w_up, w_down = _operands()
        cot = jax.random.normal(jax.random.key(7), (N, D))

        def loss(xf, weights, w_gate, w_up, w_down):
            out, _, n_held, _, full = moe._routed_share(
                xf, (1, N, D), experts, weights, E_ALL, w_down,
                w_gate=w_gate, w_up=w_up, first=FIRST, interpret=True,
            )
            out = out.reshape(N, D)
            return jnp.sum(out * cot), (out, n_held, full)

        (_, (out, n_held, full)), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True
        )(xf, weights, w_gate, w_up, w_down)
    finally:
        moe.TAIL_TOKENS = real
    want = _out_and_grads(_before(MOST), experts, weights)
    return (out, *grads), int(n_held), int(full), want


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("way", ["tail_overflows", "more_than_usual"])
def test_both_ways_out_take_the_full_path_and_agree(way, name):
    """A tail that overflows its buffer, and ``n_held > usual`` (every
    token holds three of the four held experts: 288 pairs, the usual
    buffer 256 rows): the full path's output and gradients, the counter
    at ``rows_held``."""
    got, n_held, full, want = _through_the_cond(way)
    assert full == n_held > 0
    assert (n_held > USUAL) == (way == "more_than_usual")
    at = NAMES.index(name)
    np.testing.assert_allclose(got[at], want[at], atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("hot", [False, True])
def test_moe_mlp_share_counts_the_rows_that_left_the_fast_path(hot):
    """Through the router: an even routing stays on the fast path (0), a
    bias that sends every token to three held experts leaves it
    (``rows_held``); ``routed_experts`` has no fast path and no count."""
    ks = jax.random.split(jax.random.key(3), 6)
    d, e = D, 32
    bias = 0.01 * jax.random.normal(ks[0], (e,))
    if hot:
        bias = bias.at[FIRST:FIRST + 3].set(100.0)
    x = jax.random.normal(ks[1], (1, 256, d))
    _, w_gate, w_up, w_down = _operands()
    out, counters = moe.moe_mlp_share(
        x, jax.random.normal(ks[2], (d, e)) / d ** 0.5, bias, w_gate, w_up,
        w_down, first=FIRST, top_k=TOP_K, scaling=2.0,
    )
    assert int(counters.rows_dropped) == 0
    assert int(counters.rows_full_path) == (
        int(counters.rows_held) if hot else 0
    )
    assert (int(counters.rows_held) > 4 * (256 * TOP_K // e) * HELD) == hot
    experts, weights = _routing(ROUTINGS["up_to_two"])
    _, served = moe.routed_experts(
        x[:, :N], experts, weights, jnp.concatenate([w_gate, w_up], -1),
        w_down, HELD,
    )
    assert served.rows_full_path is None


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold
    (branches, custom derivative bodies, nested calls)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _indexed(jaxpr):
    """(primitive, index vectors of its index operand or sorted keys) of
    every gather, scatter and sort under ``jaxpr``."""
    found = []
    for eqn in _eqns(jaxpr):
        name = eqn.primitive.name
        if name == "gather" or name.startswith("scatter"):
            found.append((name, math.prod(eqn.invars[1].aval.shape[:-1])))
        elif name == "sort":
            found.append((name, eqn.invars[0].aval.size))
    return found


@pytest.mark.parametrize("e, rerun", [(128, True), (32, False)])
def test_the_fast_branch_holds_nothing_sized_by_the_pairs(e, rerun):
    """The jaxpr of ``jax.grad`` of ``moe_mlp_share`` at a share's shape:
    the fast branch of each of its ``cond``s holds no gather, sort or
    scatter with ``n * top_k`` index entries or more but ONE sort (the
    keys ``group * pairs + pair``, whose head is the buffer's row ->
    pair map), and no scatter of more than a value a group or a row tile. The full
    branch, walked the same way, shows the walk sees what it looks for.
    With 128 experts the full buffer is 8 usual ones (``RERUN_FROM`` 4):
    a branch keeps nothing but its operands and runs again in the
    backward, so there are two ``cond``s and neither hands out anything
    shaped by the buffer's 256 rows or by the pairs (a residual both
    branches would write). With 32 it is 2: the forward's, the layer's
    second forward's, were it under a remat, and the backward's ``cond``
    keep their residuals, as before PR 54."""
    n, top_k, held, d = 512, 4, 4, 16
    pairs = n * top_k
    ks = jax.random.split(jax.random.key(0), 3)
    _, w_gate, w_up, w_down = _operands()
    args = (
        jax.random.normal(ks[0], (1, n, d)),
        jax.random.normal(ks[1], (d, e)) / d ** 0.5,
        w_gate, w_up, w_down,
    )

    def loss(x, router, w_gate, w_up, w_down):
        out, _ = moe.moe_mlp_share(
            x, router, jnp.zeros((e,)), w_gate, w_up, w_down, first=FIRST,
            top_k=top_k, scaling=2.0, interpret=True,
        )
        return jnp.sum(out)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(*args)
    # (an interpreted kernel holds ``cond``s of its own: the share's are
    # the two whose full branch works by pair)
    conds = [
        q for q in _eqns(jaxpr.jaxpr) if q.primitive.name == "cond" and any(
            entries >= pairs
            for _, entries in _indexed(q.params["branches"][0].jaxpr)
        )
    ]
    assert len(conds) == 2                     # the forward's, the backward's
    for cond in conds:
        fast = _indexed(cond.params["branches"][1].jaxpr)
        assert len(fast) >= 3          # the dispatch and the levels' reads
        by_pairs = [f for f in fast if f[1] >= pairs]
        # (the forward's sort is the backward's only where it runs again)
        assert by_pairs in ([("sort", pairs)], [] if not rerun else None)
        # (the grouped matmuls' metadata scatters a value a group or a
        # row tile: 4 and at most 8 here)
        assert max(
            entries for name, entries in fast if name.startswith("scatter")
        ) <= 8 < moe.TAIL_TOKENS
        shaped = {v.aval.shape for v in cond.outvars} - {
            v.aval.shape for v in cond.invars
        }
        # out and the operands handed on, or the operands' gradients
        assert bool(shaped) != rerun


@pytest.mark.parametrize("skew", [False, True])
def test_the_tool_rehearses_the_share_at_toy_widths(skew):
    """``tools/bench_moe_dispatch.py --share --tiny [--skew]``: one line
    a shape, the layer under a cell's remat policy; the seeded router
    stays on the fast path, the skewed one leaves it with every pair."""
    import os
    import sys

    sys.path.insert(
        0, os.path.join(os.path.dirname(__file__), "..", "tools")
    )
    import bench_moe_dispatch

    (line,) = bench_moe_dispatch.run_share(
        ["tiny"], repeats=1, tiny=True, skew=skew
    )
    assert line["finite"] and line["rows_dropped"] == 0
    assert line["rows_full_path"] == (line["rows_held"] if skew else 0)
    assert (line["rows_held"] == line["pairs"]) == skew
