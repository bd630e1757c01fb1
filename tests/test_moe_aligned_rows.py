"""The served expert layer's two row layouts (``moe._share_rows``: sorted
rows packed end to end; ``moe._share_rows_aligned``: every expert's rows
from a row-tile boundary) give the same layer, routing by routing, and
``moe.weight_visits`` counts what megablox ``gmm`` visits under each
(CPU, kernels interpreted)."""

import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import moe

_N, _TOP_K, _D, _F, _E = 160, 2, 128, 128, 8
_TM = moe.ROW_TILE                      # 40 rows an expert: under a tile


def _random(key, n=_N, experts=_E):
    """``top_k`` distinct experts a token, every expert as likely."""
    return jnp.argsort(
        jax.random.uniform(key, (n, experts)), axis=-1
    )[:, :_TOP_K].astype(jnp.int32)


def _partial_chunk(key):
    """A prefill chunk 40 % full: the pad rows are one token, routed one
    way (two groups of 96 rows and more)."""
    experts = _random(key)
    return experts.at[64:].set(experts[64])


def _empty_expert(key):
    """Expert 3 gets no row: no tile, and its neighbours close ranks."""
    experts = _random(key, experts=_E - 1)
    return jnp.where(experts >= 3, experts + 1, experts)


def _heavy_expert(key):
    """150 tokens choose expert 5 first: a group over a row tile, which
    fills two."""
    experts = _empty_expert(key)        # (no 3: the shift below is free)
    second = jnp.where(experts[:, 1] == 5, 3, experts[:, 1])
    heavy = jnp.stack([jnp.full((_N,), 5, jnp.int32), second], axis=1)
    return experts.at[:150].set(heavy[:150])


# routing -> (experts of a seed, layers' experts in the weight stack,
# this layer's place in it)
_ROUTINGS = {
    "random": (_random, 1, None),
    "partial_chunk": (_partial_chunk, 1, None),
    "empty_expert": (_empty_expert, 1, None),
    "heavy_expert": (_heavy_expert, 1, None),
    "offset_into_a_stack": (_random, 3, 1),
}


def _layer(routing, dtype=jnp.bfloat16, seed=0):
    route, layers, at = _ROUTINGS[routing]
    keys = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(keys[0], (1, _N, _D), jnp.float32)
    w_gu = jax.random.normal(keys[1], (layers * _E, _D, 2 * _F)) * _D ** -0.5
    w_down = jax.random.normal(keys[2], (layers * _E, _F, _D)) * _F ** -0.5
    weights = jax.nn.softmax(jax.random.normal(keys[3], (_N, _TOP_K)))
    offset = None if at is None else jnp.int32(at * _E)
    return (
        x.astype(dtype), route(keys[4]), weights, w_gu.astype(dtype),
        w_down.astype(dtype),
    ), offset


def _served(aligned, offset, monkeypatch):
    """``routed_experts`` under one layout, whatever the rule says of
    the toy shape."""
    monkeypatch.setattr(moe, "_aligned_rows", lambda *shape: aligned)
    return jax.jit(lambda *args: moe.routed_experts(
        *args, _E, group_offset=offset, interpret=True
    ))


def _gmm_visits(group_sizes, rows):
    """``gmm``'s own count of the tiles it executes over ``rows`` rows."""
    gmm_module = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm"
    )
    _, num_tiles = gmm_module.make_group_metadata(
        group_sizes=group_sizes, m=rows, tm=_TM, start_group=jnp.int32(0),
        num_nonzero_groups=group_sizes.shape[0], visit_empty_groups=False,
    )
    return int(num_tiles)


@pytest.mark.parametrize("routing", sorted(_ROUTINGS))
def test_the_aligned_layer_is_the_packed_layer(routing, monkeypatch):
    args, offset = _layer(routing)
    x, experts, weights, w_gu, w_down = args
    sizes = np.bincount(np.asarray(experts).ravel(), minlength=_E)
    assert moe.ROW_TILE == _TM and _N * _TOP_K // _E < _TM
    if routing == "empty_expert":
        assert sizes[3] == 0
    if routing == "heavy_expert":
        assert sizes.max() > _TM
    if routing == "partial_chunk":
        assert np.sort(sizes)[-2] >= 96

    packed, was = _served(False, offset, monkeypatch)(*args)
    aligned, now = _served(True, offset, monkeypatch)(*args)
    assert was.weight_visits is None
    for name in ("rows_held", "rows_max", "rows_dropped", "experts_hit"):
        assert int(getattr(now, name)) == int(getattr(was, name)), name
    assert int(now.rows_held) == _N * _TOP_K and int(now.rows_dropped) == 0

    # the counter: a tile a FILLED tile aligned, gmm's own count packed
    assert int(now.weight_visits) == int(np.ceil(sizes / _TM).sum())
    assert int(now.weight_visits) == int(
        moe.weight_visits(jnp.asarray(sizes), _TM, True)
    )
    pairs = _N * _TOP_K
    rows = -(-pairs // _TM) * _TM
    packed_visits = int(moe.weight_visits(jnp.asarray(sizes), _TM, False))
    assert packed_visits == _gmm_visits(jnp.asarray(sizes, jnp.int32), rows)
    whole = jnp.asarray(-(-sizes // _TM) * _TM, jnp.int32)
    assert int(now.weight_visits) == _gmm_visits(whole, rows + _E * _TM)
    assert int(now.weight_visits) <= packed_visits
    if routing in ("random", "offset_into_a_stack"):
        # two tile edges, each inside a group
        assert packed_visits == int(now.weight_visits) + 2

    # the layer: within a rounding where the weighting moved to the
    # combine (float32 there, the compute dtype's row here) ...
    packed, aligned = (
        np.asarray(a.astype(jnp.float32)) for a in (packed, aligned)
    )
    scale = np.abs(packed).max()
    assert scale > 0.5
    assert np.abs(aligned - packed).max() <= 2 * 2.0 ** -7 * scale
    # ... and bit for bit where the weighting rounds nothing (a power
    # of two): a row's result is not in where the buffer holds it
    halves = (x, experts, jnp.full_like(weights, 0.5), w_gu, w_down)
    packed, _ = _served(False, offset, monkeypatch)(*halves)
    aligned, _ = _served(True, offset, monkeypatch)(*halves)
    assert np.array_equal(
        np.asarray(packed.astype(jnp.float32)),
        np.asarray(aligned.astype(jnp.float32)),
    )
    assert np.abs(np.asarray(packed.astype(jnp.float32))).max() > 0.25


@pytest.mark.parametrize("layout", ["aligned", "packed"])
def test_neither_layout_scatters_a_row(layout, monkeypatch):
    """Index arithmetic and gathers lay the rows out and read them back,
    forward and backward: the lowered text holds the scatters of
    ``bincount`` and of ``gmm``'s own metadata (``int32`` vectors a
    group or a tile long) and none over a row."""
    args, offset = _layer("random", jnp.float32)
    served = _served(layout == "aligned", offset, monkeypatch)

    def loss(x, experts, weights, w_gu, w_down):
        out, _ = served(x, experts, weights, w_gu, w_down)
        return jnp.sum(jnp.sin(out))

    text = jax.jit(jax.grad(loss, argnums=(0, 2, 3, 4))).lower(
        *args
    ).as_text()
    scattered = re.findall(
        r'"stablehlo\.scatter".*?\) -> (tensor<[^>]*>)', text, flags=re.S
    )
    assert scattered and "stablehlo.gather" in text
    for result in scattered:
        assert re.fullmatch(r"tensor<\d+x[if]32>", result), result


def test_the_aligned_layers_gradient_is_the_packed_layers(monkeypatch):
    """Every transpose of the aligned layout is a gather over the same
    maps read the other way: the gradients are the packed layout's
    (float32, so that only the order of sums differs)."""
    args, offset = _layer("heavy_expert", jnp.float32)
    x, experts, weights, w_gu, w_down = args

    def grads(aligned):
        served = _served(aligned, offset, monkeypatch)
        return jax.grad(
            lambda x, weights, w_gu, w_down: jnp.sum(jnp.sin(
                served(x, experts, weights, w_gu, w_down)[0]
            )), argnums=(0, 1, 2, 3),
        )(x, weights, w_gu, w_down)

    for name, a, b in zip(
        ("x", "weights", "w_gu", "w_down"), grads(True), grads(False)
    ):
        assert float(jnp.abs(b).max()) > 0, name
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-4 * float(
            jnp.abs(b).max()
        ), err_msg=name)


@pytest.mark.parametrize("sizes, packed, aligned", [
    ([60] * 8, 11, 8),               # 480 rows: 3 edges, each in a group
    ([128] * 4, 4, 4),               # groups that end on the edges
    ([0, 300, 0, 20], 4, 4),         # a heavy group fills its tiles
    ([100, 100, 100], 5, 3),
    ([0, 0, 0], 0, 0),
])
def test_weight_visits_counts_tiles_a_group_touches_or_fills(
        sizes, packed, aligned):
    sizes = jnp.asarray(sizes, jnp.int32)
    assert int(moe.weight_visits(sizes, 128, False)) == packed
    assert int(moe.weight_visits(sizes, 128, True)) == aligned
