"""The decode kernel over a pool of FLAT K/V rows
(``ops/flat_decode_attention.py``) on a CPU, interpreted: against
``kvpool/conv.decode_attend``'s gathered form and against the exact
float32 softmax on every edge of a slot's fill, and through the paged
engine of a tiny convolution / attention pattern model, whose tokens are
the gathered form's across a prefix hit, a preemption and a released
slot."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import conv_lm
from dlrover_tpu.ops import flat_decode_attention as fda
from dlrover_tpu.serving.kvpool import PagedServingEngine, conv
from dlrover_tpu.serving.kvpool import engine as paged, families
from tests.test_conv_serving import prompts, seeded_params, serve

BS, MB = 16, 6
# a slot's fill: nothing, one row, a page less one, a page, every page
# of its table, and two that end inside a page
FILLS = (0, 1, BS - 1, BS, MB * BS, 37, 50)
INACTIVE = 5


def _exact(cfg, q, k_new, v_new, k_pool, v_pool, at, tables, lengths):
    """The definition, float32 at the highest precision, by 64-wide
    heads (off the device nothing pads them)."""
    hi = jax.lax.Precision.HIGHEST
    slots, kh, hd = q.shape[0], cfg.n_kv_heads, cfg.head_dim
    view = lambda pool: np.asarray(pool, np.float32)[at][  # noqa: E731
        np.asarray(tables)
    ].reshape(slots, -1, kh, hd)
    qh = q[:, 0].astype(jnp.float32).reshape(slots, kh, -1, hd)
    s = jnp.einsum("skgd,stkd->skgt", qh, view(k_pool), precision=hi)
    visible = jnp.arange(s.shape[-1])[None, :] < lengths[:, None]
    s = jnp.where(visible[:, None, None], s, -jnp.inf)
    mine = jnp.einsum(
        "skgd,skd->skg", qh, k_new[:, 0].astype(jnp.float32), precision=hi
    )
    p = jax.nn.softmax(
        jnp.concatenate([s, mine[..., None]], -1)
        * conv_lm.softmax_scale(cfg), axis=-1,
    )
    out = jnp.einsum(
        "skgt,stkd->skgd", p[..., :-1], view(v_pool), precision=hi
    ) + p[..., -1:] * v_new[:, 0].astype(jnp.float32)[:, :, None]
    return np.asarray(out.reshape(slots, 1, cfg.n_heads, hd))


@pytest.mark.parametrize("dtype,chunk_pages", [
    ("float32", 1), ("float32", 2), ("float32", 64), ("bfloat16", 2),
])
def test_flat_pool_kernel_matches_the_gathered_form(
    dtype, chunk_pages, monkeypatch,
):
    """8 KV heads of 64 under 4 query heads each, held flat as four lane
    rows, layer 1 of a two-layer pool: the kernel in place against the
    gathered ``[slots, max_len]`` view and against the exact softmax, a
    chunk of one page, of two (a slot's pages are several chunks, and
    the next slot's first is asked for under this one's last) and of
    more than a table. Pages past a slot's fill hold NaNs, which the
    kernel must never copy (the sentinel block among them)."""
    cfg = conv_lm.tiny_config(
        n_heads=32, n_kv_heads=8, head_dim=64, dtype=dtype
    )
    dt = cfg.compute_dtype
    slots, layers, at = len(FILLS), 2, 1
    nb = slots * MB + 1
    monkeypatch.setattr(
        fda, "CHUNK_BYTES", chunk_pages * BS * cfg.kv_width * 2
    )
    ks = jax.random.split(jax.random.key(3), 5)
    normal = lambda k, *dims: jax.random.normal(  # noqa: E731
        k, dims
    ).astype(dt)
    k_pool = normal(ks[0], layers, nb, BS, cfg.kv_width)
    v_pool = normal(ks[1], layers, nb, BS, cfg.kv_width)
    q = normal(ks[2], slots, 1, cfg.n_heads, cfg.head_dim)
    k_new = normal(ks[3], slots, 1, cfg.n_kv_heads, cfg.head_dim)
    v_new = normal(ks[4], slots, 1, cfg.n_kv_heads, cfg.head_dim)
    tables = 1 + np.random.RandomState(0).permutation(nb - 1)[
        :slots * MB
    ].reshape(slots, MB).astype(np.int32)
    lengths = jnp.asarray(FILLS, jnp.int32)
    active = jnp.arange(slots) != INACTIVE
    args = (cfg, k_pool, v_pool, at, jnp.asarray(tables), lengths, BS)
    gathered = np.asarray(conv.decode_attend(
        *args, kind="gathered_view"
    )(q, k_new, v_new), np.float32)
    exact = _exact(cfg, q, k_new, v_new, *args[1:6])
    # what no slot may read: every page past its fill goes to the
    # sentinel in the table and to NaN in the pool, the sentinel too
    unread = np.ones(nb, bool)
    for row, fill in zip(tables, FILLS):
        unread[row[:-(-fill // BS)]] = False
        row[-(-fill // BS):] = families.SENTINEL_BLOCK
    k_pool = k_pool.at[:, unread].set(jnp.nan)
    v_pool = v_pool.at[:, unread].set(jnp.nan)
    got = np.asarray(conv.decode_attend(
        cfg, k_pool, v_pool, at, jnp.asarray(tables), lengths, BS,
        kind="pool_kernel", active=active,
    )(q, k_new, v_new), np.float32)
    assert np.isfinite(got).all()
    on = np.asarray(active)
    if dtype == "float32":
        # the order of summation alone
        np.testing.assert_allclose(got[on], exact[on], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            got[on], gathered[on], rtol=2e-5, atol=2e-6
        )
    else:
        # the output's last rounding; the gathered form also rounds its
        # probabilities, so the kernel is no further from the exact
        tol = dict(rtol=2 ** -7, atol=2 ** -8)
        np.testing.assert_allclose(got[on], exact[on], **tol)
        assert np.abs(got[on] - exact[on]).max() <= (
            np.abs(gathered[on] - exact[on]).max() + 2 ** -8
        )
    # a slot that is not active reads nothing: its own V row
    np.testing.assert_array_equal(
        got[INACTIVE, 0],
        np.repeat(
            np.asarray(v_new, np.float32)[INACTIVE, 0],
            cfg.n_heads // cfg.n_kv_heads, axis=0,
        ),
    )
    # another layer of the same pool is another answer
    other = np.asarray(conv.decode_attend(
        cfg, k_pool, v_pool, 0, jnp.asarray(tables), lengths, BS,
        kind="pool_kernel", active=active,
    )(q, k_new, v_new), np.float32)
    assert np.abs(other[4] - got[4]).max() > 1e-2


def test_one_head_to_a_lane_row_needs_no_placing(monkeypatch):
    """128-wide heads: one KV head a lane row (``lane_pack`` 1), the
    placed queries are the queries, and the kernel still is the
    gathered form."""
    cfg = conv_lm.tiny_config(n_heads=4, n_kv_heads=2, head_dim=128)
    assert conv.lane_pack(cfg) == 1
    monkeypatch.setattr(fda, "CHUNK_BYTES", 2 * BS * cfg.kv_width * 2)
    slots, nb = 3, 3 * MB + 1
    ks = jax.random.split(jax.random.key(5), 5)
    k_pool = jax.random.normal(ks[0], (1, nb, BS, cfg.kv_width))
    v_pool = jax.random.normal(ks[1], (1, nb, BS, cfg.kv_width))
    q = jax.random.normal(ks[2], (slots, 1, 4, 128))
    k_new = jax.random.normal(ks[3], (slots, 1, 2, 128))
    v_new = jax.random.normal(ks[4], (slots, 1, 2, 128))
    tables = jnp.arange(1, nb, dtype=jnp.int32).reshape(slots, MB)
    args = (cfg, k_pool, v_pool, 0, tables, jnp.asarray([70, 3, 96]), BS)
    want = conv.decode_attend(*args, kind="gathered_view")(q, k_new, v_new)
    got = conv.decode_attend(*args, kind="pool_kernel")(q, k_new, v_new)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_queries_that_are_not_lane_rows_are_refused():
    cfg = conv_lm.tiny_config()          # 2 KV heads of 8: 16-wide rows
    q = jnp.zeros((1, 1, 4, 16))
    with pytest.raises(ValueError, match="a lane row is 128 lanes"):
        fda.pool_flat_decode_attention(
            q, jnp.zeros((1, 1, 16)), jnp.zeros((1, 1, 16)),
            jnp.zeros((1, 4, 4, 16)), jnp.zeros((1, 4, 4, 16)), 0,
            jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32),
            scale=conv_lm.softmax_scale(cfg),
        )


# ---- the prefill chunk's kernel --------------------------------------------

CHUNK, TILE = 32, 8
# rows below the chunk: none, one block, blocks that are no multiple of
# the definition's CHUNK_PREFIX_ROWS (patched to two blocks) nor of the
# kernel's VMEM chunk, and a table's worth
STARTS = (0, BS, 5 * BS, 8 * BS)
# valid rows: one, a tile's edge, mid-tile, the whole chunk
VALID = (1, TILE, TILE + 3, CHUNK)


def _chunk_exact(cfg, q, k_new, v_new, k_pool, v_pool, at, table_row, start):
    """The chunk's definition, float32 at the highest precision, by
    64-wide heads over the slot's logical rows below ``start`` and the
    chunk's own."""
    hi = jax.lax.Precision.HIGHEST
    f32, kh, hd = jnp.float32, cfg.n_kv_heads, cfg.head_dim
    chunk = q.shape[1]
    rows = lambda pool, new: jnp.concatenate([  # noqa: E731
        pool.astype(f32)[at][table_row].reshape(-1, kh, hd),
        new[0].astype(f32),
    ])
    qh = q[0].astype(f32).reshape(chunk, kh, -1, hd)
    s = jnp.einsum("qkgd,tkd->kgqt", qh, rows(k_pool, k_new), precision=hi)
    cached = table_row.shape[0] * k_pool.shape[2]
    seen = jnp.concatenate([
        jnp.broadcast_to(jnp.arange(cached)[None, :] < start,
                         (chunk, cached)),
        jnp.arange(chunk)[None, :] <= jnp.arange(chunk)[:, None],
    ], axis=1)
    p = jax.nn.softmax(
        jnp.where(seen, s * conv_lm.softmax_scale(cfg), -jnp.inf), axis=-1
    )
    out = jnp.einsum("kgqt,tkd->qkgd", p, rows(v_pool, v_new), precision=hi)
    return out.reshape(1, chunk, cfg.n_heads, hd)


@pytest.mark.parametrize("dtype,prefix_pages", [
    ("float32", 1), ("float32", 3), ("float32", 64), ("bfloat16", 2),
])
def test_flat_chunk_kernel_matches_the_gathered_form(
    dtype, prefix_pages, monkeypatch,
):
    """A chunk of 32 tokens in tiles of 8 over layer 1 of a two-layer
    pool, 8 KV heads of 64 under 4 query heads each: the kernel in place
    against ``chunk_attend``'s ``jax.numpy`` form and the exact softmax
    at every ``start`` and every ``n_valid``. The table's entries past
    the prefix are the sentinel and every page the chunk may not read
    holds NaNs; the rows of the tiles past the last scored one are
    exactly zero and every output is finite."""
    cfg = conv_lm.tiny_config(
        n_heads=32, n_kv_heads=8, head_dim=64, dtype=dtype
    )
    dt = cfg.compute_dtype
    layers, at, nb = 2, 1, MB + 4
    monkeypatch.setattr(
        fda, "CHUNK_PREFIX_BYTES", prefix_pages * BS * cfg.kv_width * 2
    )
    monkeypatch.setattr(conv, "CHUNK_PREFIX_ROWS", 2 * BS)
    monkeypatch.setattr(conv, "CHUNK_TOKEN_TILE", TILE)
    ks = jax.random.split(jax.random.key(7), 5)
    normal = lambda k, *dims: jax.random.normal(  # noqa: E731
        k, dims
    ).astype(dt)
    k_pool = normal(ks[0], layers, nb, BS, cfg.kv_width)
    v_pool = normal(ks[1], layers, nb, BS, cfg.kv_width)
    q = normal(ks[2], 1, CHUNK, cfg.n_heads, cfg.head_dim)
    k_new = normal(ks[3], 1, CHUNK, cfg.n_kv_heads, cfg.head_dim)
    v_new = normal(ks[4], 1, CHUNK, cfg.n_kv_heads, cfg.head_dim)
    order = 1 + np.random.RandomState(1).permutation(nb - 1)

    def form(kind, layer=at):
        return jax.jit(lambda k, v, table, start, n_valid: conv.chunk_attend(
            cfg, k, v, layer, table, start, BS, n_valid, kind
        )(q, k_new, v_new).astype(jnp.float32))

    kernel, view = form("pool_kernel"), form("gathered_view")
    exact = jax.jit(lambda table, start: _chunk_exact(
        cfg, q, k_new, v_new, k_pool, v_pool, at, table, start
    ))
    for start in STARTS:
        pages = -(-start // BS)
        table = np.full(MB + 2, families.SENTINEL_BLOCK, np.int32)
        table[:pages] = order[:pages]
        unread = np.ones(nb, bool)
        unread[order[:pages]] = False
        table, start = jnp.asarray(table), jnp.int32(start)
        want = np.asarray(exact(table, start))
        # (a CPU has no bf16 x bf16 -> f32 matmul of the definition's
        # shape: the bf16 kernel is held to the exact softmax alone)
        gathered = want if dtype == "bfloat16" else np.asarray(
            view(k_pool, v_pool, table, start, CHUNK)
        )
        poisoned = (
            k_pool.at[:, unread].set(jnp.nan),
            v_pool.at[:, unread].set(jnp.nan), table, start,
        )
        for n_valid in VALID:
            got = np.asarray(kernel(*poisoned, jnp.int32(n_valid)))
            scored = conv.chunk_rows_scored(
                n_valid, CHUNK, {"conv_chunk_attention": "pool_kernel"}
            )
            assert scored == -(-n_valid // TILE) * TILE
            assert np.isfinite(got).all()
            assert not got[0, scored:].any()
            got, near = got[0, :scored], gathered[0, :scored]
            if dtype == "float32":
                # the order of summation alone
                tol = dict(rtol=2e-5, atol=2e-5)
                np.testing.assert_allclose(got, want[0, :scored], **tol)
                np.testing.assert_allclose(got, near, **tol)
            else:
                # its probabilities and its answer rounded once each
                np.testing.assert_allclose(
                    got, want[0, :scored], rtol=2 ** -6, atol=2 ** -6
                )
    assert conv.chunk_rows_scored(
        3, CHUNK, {"conv_chunk_attention": "gathered_view"}
    ) == CHUNK
    # another layer of the same pool is another answer (below start)
    other = np.asarray(form("pool_kernel", 0)(
        k_pool, v_pool, table, start, jnp.int32(CHUNK)
    ))
    assert np.abs(other[0] - got).max() > 1e-2


def test_a_chunk_that_is_not_whole_tiles_is_refused():
    cfg = conv_lm.tiny_config(n_heads=4, n_kv_heads=2, head_dim=64)
    with pytest.raises(ValueError, match="in tiles of 8"):
        fda.pool_flat_chunk_attention(
            jnp.zeros((12, 1, 4, 128)), jnp.zeros((12, 128)),
            jnp.zeros((12, 128)), jnp.zeros((1, 4, 4, 128)),
            jnp.zeros((1, 4, 4, 128)), 0, jnp.zeros((2,), jnp.int32), 0,
            scale=conv_lm.softmax_scale(cfg), tile=8,
        )


# ---- through the engine ----------------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    """Two KV heads of 64 to ONE lane row under two query heads each;
    float32, where the two forms differ by the order of summation
    alone."""
    cfg = conv_lm.tiny_config(n_heads=4, n_kv_heads=2, head_dim=64)
    return cfg, seeded_params(cfg, 0)


def _a_day_of_traffic(cfg, params):
    """One engine, warmed up: two requests decoding, the younger
    preempted mid-decode and resumed; then, in the slots they released,
    a cold prompt and a prefix hit on it that restores a snapshot off a
    chunk boundary. Every request's greedy tokens, and what read the
    decode step's rows."""
    eng = PagedServingEngine(
        cfg, params, slots=2, max_len=64, num_blocks=40, prefill_chunk=8,
        block_size=4,
    )
    eng.warmup()
    traced = dict(eng.trace_counts)
    stats = eng.kv_stats()
    assert stats["pool_attention"] == "conv_gathered_view"
    assert stats["conv_decode_attention"] == eng.kinds["conv_decode_attention"]
    assert "latent_decode_attention" not in eng.kinds
    a, b = prompts(cfg, [13, 27], seed=10)
    ra, rb = eng.submit(a, 12), eng.submit(b, 12)
    for _ in range(8):
        eng.step()
    eng._drain("test")
    eng._preempt(rb)
    while eng.pending():
        eng.step()
    eng.check_block_invariants()
    assert eng.metrics.kv_preemptions.value() >= 1
    tokens = [list(ra.tokens), list(rb.tokens)]
    first, other = prompts(cfg, [14, 9], seed=2)
    tokens += serve(eng, [(first, 3)])
    hits = eng.kv_stats()["prefix_hit_tokens"]
    tokens += serve(eng, [(first[:12] + other, 6)])
    assert eng.kv_stats()["prefix_hit_tokens"] - hits == 12
    assert dict(eng.trace_counts) == traced      # no retrace after warm-up
    return eng.kinds["conv_decode_attention"], tokens


def test_engine_tokens_are_the_same_through_the_flat_pool_kernel(
    tiny, monkeypatch,
):
    """The decode program built with the kernel — the platform probe
    patched, the predicate admitting the tiny model's float32 pool of
    4-row pages, two pages a VMEM chunk, the kernel interpreted — emits
    the greedy tokens of the program built over the gathered view across
    a preemption, a released slot and a prefix hit, and ``kv_stats()``
    names the kind."""
    cfg, params = tiny
    kind, want = _a_day_of_traffic(cfg, params)
    assert kind == "gathered_view"
    monkeypatch.setattr(families, "_on_tpu", lambda: True)
    monkeypatch.setattr(fda, "flat_kernel_supported", lambda *a: True)
    monkeypatch.setattr(fda, "CHUNK_BYTES", 2 * 4 * cfg.kv_width * 2)
    # the chunk size is no part of a program's key: programs of the
    # test's own
    monkeypatch.setattr(paged, "_steps_for", functools.lru_cache(
        maxsize=16
    )(paged._steps_for.__wrapped__))
    calls = []
    kernel = fda.pool_flat_decode_attention
    monkeypatch.setattr(
        fda, "pool_flat_decode_attention",
        lambda *a, **kw: calls.append(a[5]) or kernel(*a, **kw),
    )
    kind, got = _a_day_of_traffic(cfg, params)
    assert kind == "pool_kernel"
    assert calls == [0, 1]    # traced into the decode program, both layers
    assert got == want
    assert [len(t) for t in got] == [12, 12, 3, 6]


def test_the_tools_conv_part_rehearses_off_a_tpu():
    """``tools/bench_paged_decode.py --parts conv --tiny``: the gathered
    form, then at each ``--chunk-kb`` the kernel (interpret mode), no
    further from the exact softmax than the gathered form, and the
    kernel with its compute taken out, a line each; then the same three
    of the prefill chunk at each (``--token-tiles``, ``--prefix-kb``);
    no time anywhere."""
    import json
    import os
    import subprocess
    import sys

    tool = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools", "bench_paged_decode.py",
    )
    out = subprocess.run(
        [sys.executable, tool, "--tiny", "--parts", "conv",
         "--chunk-kb", "4", "--token-tiles", "8", "--prefix-kb", "4"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, text=True,
        capture_output=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rows = [json.loads(x) for x in out.stdout.splitlines() if x[:1] == "{"]
    forms = ["gathered_view", "pool_kernel", "pool_kernel_copies_alone"]
    assert [(r["part"], r["form"]) for r in rows] == [
        (part, form) for part in ("conv", "conv_chunk") for form in forms
    ]
    assert [r["chunk_kb"] for r in rows[1:3]] == [4, 4]
    view, kernel, copies = rows[:3]
    assert kernel["rel_err_of_exact"] <= view["rel_err_of_exact"] < 0.01
    # nothing attended: the answer is the new token's own V row
    assert copies["rel_err_of_exact"] > 0.5
    view, kernel, copies = rows[3:]
    assert (kernel["tile"], kernel["prefix_kb"]) == (8, 4)
    # float32 off the chip: the order of summation alone
    assert max(kernel["rel_err_of_exact"], view["rel_err_of_exact"]) < 1e-5
    assert copies["rel_err_of_exact"] == 1.0     # nothing attended: zeros
    assert not [
        k for r in rows for k in r if k in ("ms", "rows_gb_s", "turn_ms")
    ]
