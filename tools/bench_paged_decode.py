"""Paged attention A/B on the device at hand: pool kernels vs gather.

``serving/kvpool/dense.py`` builds the plain decode program and the
prefill program with one of two attentions (``pool_attention_kind``):
``paged_kernel`` reads each layer's K/V from the stacked pool in place,
filled pages only (``ops.decode_attention.pool_decode_attention`` for
the decode step, ``pool_chunk_attention`` for a prefill chunk);
``xla_gather`` slices the layer's pool, gathers a ``[slots, max_len]``
view through the tables and runs ``_append_free_attention`` on it (the
prefill chunk: one slot's view, the chunk written into it, plain
attention over all of it). This probe holds the two
against each other at the shapes of ``--preset``: ``nemo12b``, the
``nemo12b-serve-chat`` cell's (16 slots x 2,304 rows in 16-row pages, 32
heads / 8 KV x 128, 12 layers); ``flagship334m``, ``chip_smoke.py``'s
engine (4 slots x 576 rows, 8 / 8 x 128, 16 layers); ``llama2-7b``, an
MHA model at the cell's cache (32 / 32 x 128). ``--slots`` and
``--max-blocks`` move the cache's size, ``--heads`` the query heads:

    python tools/bench_paged_decode.py          # on the chip: chiprun -- ...

Parts (``--parts``), one JSON line each: ``parity`` (the decode kernel
against the gather reference on one layer of a random pool, fills on
every edge), ``attention`` (all layers' attention alone, at the chat
traffic's fills, a full and a short cache, and over four chunk sizes),
``decode`` (the whole decode program both ways, random weights), and
the prefill mode, ``prefill``: at ``--starts`` (cache rows below the
chunk) the chunk kernel against the exact softmax — probabilities kept
f32 and rounded to bf16 once, beside the gather's error — all layers'
chunk attention alone over ``--query-rows`` tile sizes, and the whole
prefill program both ways with the head on and off. Exits 1 if a kernel
is not finite or, fed f32 queries, more than 1e-5 off the reference at
the highest matmul precision. ``latent`` (asked for by name, whatever
the preset): one layer of a latent model's decode attention at the
``xing-serve-sessions-16k`` shape (32 slots, fills ~16.6k of 17,408
rows, the 9,216-block pool of two 576-wide rows to a device row), the
gathered ``[slots, max_len]`` view against
``pool_latent_decode_attention`` over the pool in place at
``--tile-rows`` device rows a VMEM tile: ms a call and the visible
rows' GB/s; then one layer of its prefill chunk's attention
(``latent.chunk_attend``: 512 queries x 32 heads over one slot's 16,384
cached rows) with 64 / 128 / 215 / 512 of the chunk's rows valid, its
queries walked in tiles of ``--chunk-query-rows`` rows and in one tile
(the whole-chunk form). ``--chunks-of TRACED_JSON`` (with ``latent``,
nothing else runs and no TPU is needed): rows scored over rows launched
by the prefill chunks a traced benchmark run of that cell has on record
(``latent.chunk_rows_scored`` over its ``serving.step`` spans'
``prefill_tokens``, at the tile its ``kv_stats`` names). ``conv`` (asked
for by name too): one attention layer of a convolution / attention
pattern model's decode step at the ``lfm2-serve-sessions-8k`` shape (32
slots, fills 8.3-8.8k of 9,216 rows, the 5,120-block pool of 64 x 512
pages of flat K and V rows, 32 query heads over 8 KV heads of 64), the
gathered ``[slots, max_len]`` views against
``pool_flat_decode_attention`` over the pools in place at ``--chunk-kb``
of pages a VMEM chunk, and with the kernel's compute taken out (its
page copies alone): ms a call, the visible rows' GB/s and each form's
error against the exact float32 softmax; then one attention layer of
its PREFILL CHUNK (``conv.chunk_attend``: 512 launched rows over one
slot's 8,512 cached rows), the gathered form against
``pool_flat_chunk_attention`` at each of ``--token-tiles`` tokens a grid
step and ``--prefix-kb`` of pages a VMEM chunk, whole and with its
compute taken out: ms a call with every row valid and with 215, and
``turn_ms``, the mean over the cell's turns (64-512 valid rows,
log-uniform) from the times at one, two, ... scored tiles. A smoke
reading, not a
benchmark: one process, host-clock timing around ``block_until_ready``.
Times mean something on a TPU only: anywhere else the tool refuses to
run, unless ``--tiny`` rehearses it (interpret mode, nothing timed).
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

BLOCK = 16
PRESETS = {
    "nemo12b": dict(
        slots=16, max_blocks=144, heads=32, kv_heads=8, head_dim=128,
        layers=12, vocab_size=131072, embed_dim=5120, mlp_dim=14336,
        chunk=256,
    ),
    "flagship334m": dict(
        slots=4, max_blocks=36, heads=8, kv_heads=8, head_dim=128,
        layers=16, vocab_size=32000, embed_dim=1024, mlp_dim=4096,
        chunk=64,
    ),
    "llama2-7b": dict(
        slots=16, max_blocks=144, heads=32, kv_heads=32, head_dim=128,
        layers=8, vocab_size=32000, embed_dim=4096, mlp_dim=11008,
        chunk=256,
    ),
}
TINY = dict(max_blocks=40, vocab_size=512, embed_dim=256, mlp_dim=512,
            chunk=64)


def _timed(fn, repeats):
    """Median ms of ``repeats`` calls of ``fn`` (which blocks), after
    one that is not timed; None where nothing is to be timed."""
    fn()
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return round(1e3 * statistics.median(times), 3) if times else None


def _put(row, key, value):
    if value is not None:
        row[key] = value


def _normal(key, dims):
    """bf16 standard normals of ``dims``, made on the device."""
    import jax
    import jax.numpy as jnp

    return jax.jit(
        lambda k: jax.random.normal(k, dims, jnp.bfloat16)
    )(key)


def _weights(cfg, key):
    """Random bf16 weights of ``cfg`` as the engines hold them."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import generate as gen_lib
    from dlrover_tpu.models import llama

    def make(key):
        params, _ = llama.init_params(cfg, key)
        cast = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16), params
        )
        return gen_lib.prepare_decode_params(cfg, cast)

    return jax.jit(make)(key)


def _fills(kind, rng, slots, max_len):
    """Per-slot fills: the chat traffic's (prompt 128-2,048 log-uniform
    plus part of an answer, as far as the cache goes), a full cache, or
    a short one."""
    import numpy as np

    if kind == "full":
        return np.full(slots, max_len - 1, np.int32)
    if kind == "short":
        return np.full(slots, min(128, max_len - 1), np.int32)
    longest = max(128, min(2048, max_len - 64))
    prompts = np.exp(rng.uniform(np.log(128), np.log(longest), slots))
    return np.minimum(
        prompts + rng.uniform(0, 200, slots), max_len - 1
    ).astype(np.int32)


def _reference(q, k_new, v_new, k_pool, v_pool, layer, tables, fills):
    import jax.numpy as jnp

    from dlrover_tpu.models.generate import _append_free_attention

    slots, max_blocks = tables.shape
    shape = (slots, max_blocks * BLOCK) + k_pool.shape[-2:]
    return _append_free_attention(
        q[:, None], k_pool[layer][tables].reshape(shape),
        v_pool[layer][tables].reshape(shape),
        k_new[:, None], v_new[:, None], jnp.asarray(fills),
    )[:, 0]


# The ``latent`` part's shape: ``xing-serve-sessions-16k``'s engine (32
# slots x 272 pages of 64 tokens over the 9,216-block pool, two 576-wide
# rows to a 1,152-lane device row, 32 heads) and its fills.
LATENT = dict(
    slots=32, max_blocks=272, num_blocks=9216, block=64, layers=6,
    fills=(16400, 16900), chunk=512, start=16384,
    n_valid=(64, 128, 215, 512), query_rows=(32, 64, 128),
    model=dict(n_heads=32, kv_lora_rank=512, qk_rope_dim=64,
               qk_nope_dim=128, v_head_dim=128, dtype="bfloat16"),
)
TINY_LATENT = dict(
    slots=3, max_blocks=6, num_blocks=24, block=4, layers=2,
    fills=(9, 23), chunk=8, start=12, n_valid=(1, 3, 8),
    query_rows=(2, 4), model=dict(kv_lora_rank=128, qk_rope_dim=64),
)


CONV = dict(
    slots=32, max_blocks=144, num_blocks=5120, block=64, layers=2,
    fills=(8300, 8800), calls=8,
    chunk=512, start=8512, valid=(64, 512), some_valid=215,
    model=dict(n_heads=32, n_kv_heads=8, head_dim=64, dtype="bfloat16"),
)
TINY_CONV = dict(
    slots=3, max_blocks=5, num_blocks=16, block=16, layers=2,
    fills=(20, 70), calls=2,
    chunk=32, start=40, valid=(4, 32), some_valid=11,
    model=dict(n_heads=8, n_kv_heads=4, head_dim=64, dtype="bfloat16"),
)


def _conv(chunk_kb, repeats, seed, tiny):
    """One attention layer of a convolution / attention pattern model's
    decode step (``kvpool/conv.decode_attend``: placed queries, the
    rows, each head's own lanes read back) at the
    ``lfm2-serve-sessions-8k`` shape, the gathered ``[slots, max_len]``
    views against the Pallas kernel over the flat pools in place at each
    of ``chunk_kb`` of pages a VMEM chunk, whole and with its compute
    taken out (the page copies alone): ms a call (``calls`` chained
    calls a timed launch, alternating layers, so that a launch's host
    cost is spread thin), the visible rows' GB/s, and the error against
    the exact float32 softmax, one JSON line each."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.models import conv_lm
    from dlrover_tpu.ops import flat_decode_attention as fda
    from dlrover_tpu.serving.kvpool import conv

    shape = TINY_CONV if tiny else CONV
    cfg = conv_lm.tiny_config(**shape["model"])
    slots, mb, bs = shape["slots"], shape["max_blocks"], shape["block"]
    rng = np.random.default_rng(seed)
    keys = jax.random.split(jax.random.key(seed), 5)
    pool_dims = (shape["layers"], shape["num_blocks"], bs, cfg.kv_width)
    k_pool, v_pool = _normal(keys[0], pool_dims), _normal(keys[1], pool_dims)
    tables = jnp.asarray(
        1 + rng.permutation(shape["num_blocks"] - 1)[:slots * mb]
        .reshape(slots, mb).astype(np.int32)
    )
    fills = jnp.asarray(rng.integers(*shape["fills"], slots), jnp.int32)
    q = _normal(keys[2], (slots, 1, cfg.n_heads, cfg.head_dim))
    k_new = _normal(keys[3], (slots, 1, cfg.n_kv_heads, cfg.head_dim))
    v_new = _normal(keys[4], (slots, 1, cfg.n_kv_heads, cfg.head_dim))
    # K and V of the visible rows, read once
    row_bytes = int(fills.sum()) * cfg.kv_width * 2 * 2
    calls = shape["calls"]

    def attend(kind):
        def one(k_pool, v_pool, at, tables, fills, q):
            return conv.decode_attend(
                cfg, k_pool, v_pool, at, tables, fills, bs, kind=kind
            )(q, k_new, v_new)

        def chained(k_pool, v_pool, tables, fills):
            def body(out, at):
                # each call's query hangs on the last one's answer
                return one(
                    k_pool, v_pool, at, tables, fills,
                    q + (out * 0).astype(q.dtype),
                ), None

            layers = jnp.arange(calls, dtype=jnp.int32) % shape["layers"]
            return jax.lax.scan(body, jnp.zeros_like(q), layers)[0]

        return jax.jit(one), jax.jit(chained)

    def exact(k_pool, v_pool, tables, fills, q, k_new, v_new):
        """The definition in float32 at the highest precision."""
        f32, hi = jnp.float32, jax.lax.Precision.HIGHEST
        g = cfg.n_heads // cfg.n_kv_heads
        view = lambda pool: pool[0][tables].reshape(  # noqa: E731
            slots, mb * bs, cfg.n_kv_heads, cfg.head_dim
        ).astype(f32)
        qh = q[:, 0].astype(f32).reshape(slots, cfg.n_kv_heads, g, -1)
        s = jnp.einsum("skgd,stkd->skgt", qh, view(k_pool), precision=hi)
        s = jnp.where(
            (jnp.arange(mb * bs)[None, :] < fills[:, None])[:, None, None],
            s, -jnp.inf,
        )
        mine = jnp.einsum(
            "skgd,skd->skg", qh, k_new[:, 0].astype(f32), precision=hi
        )
        p = jax.nn.softmax(
            jnp.concatenate([s, mine[..., None]], -1)
            * conv_lm.softmax_scale(cfg), axis=-1,
        )
        out = jnp.einsum(
            "skgt,stkd->skgd", p[..., :-1], view(v_pool), precision=hi
        ) + p[..., -1:] * v_new[:, 0].astype(f32)[:, :, None]
        return out.reshape(slots, 1, cfg.n_heads, cfg.head_dim)

    want = jax.jit(exact)(k_pool, v_pool, tables, fills, q, k_new, v_new)

    def report(form, kind, **more):
        one, chained = attend(kind)
        got = one(k_pool, v_pool, jnp.int32(0), tables, fills, q)
        got = got.astype(jnp.float32)
        err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
        ms = _timed(lambda: jax.block_until_ready(
            chained(k_pool, v_pool, tables, fills)
        ), repeats)
        line = {"part": "conv", "form": form, **more, "rel_err_of_exact": err}
        if ms is not None:
            line.update(ms=round(ms / calls, 4),
                        rows_gb_s=round(row_bytes * calls / ms / 1e6, 1))
        print(json.dumps(line), flush=True)
        return bool(jnp.isfinite(got).all()), err

    ok, view_err = report(
        "gathered_view", "gathered_view", slots=slots,
        rows_mean=float(fills.mean()),
    )
    shipped, attend_chunk = fda.CHUNK_BYTES, fda._attend
    try:
        for kb in chunk_kb:
            fda.CHUNK_BYTES = kb << 10
            more = dict(chunk_kb=kb, shipped=fda.CHUNK_BYTES == shipped)
            finite, err = report("pool_kernel", "pool_kernel", **more)
            # no less exact than the form it replaces
            ok = ok and finite and err <= max(view_err, 1e-5)
            fda._attend = lambda *a, **kw: a[5]
            report("pool_kernel_copies_alone", "pool_kernel", **more)
            fda._attend = attend_chunk
    finally:
        fda.CHUNK_BYTES, fda._attend = shipped, attend_chunk
    return ok


def _share_of_turns(lo, hi, tile, chunk):
    """P(a turn scores ``k`` tiles of ``tile`` tokens), ``k`` = 1 ..
    ``chunk // tile``, for valid rows log-uniform on ``[lo, hi]``."""
    import math

    span = math.log(hi) - math.log(lo)
    edges = [
        min(max(k * tile, lo), hi) for k in range(chunk // tile + 1)
    ]
    return [
        (math.log(b) - math.log(a)) / span for a, b in zip(edges, edges[1:])
    ]


def _conv_chunk(token_tiles, prefix_kb, repeats, seed, tiny):
    """One attention layer of a convolution / attention pattern model's
    PREFILL CHUNK (``kvpool/conv.chunk_attend``) at the
    ``lfm2-serve-sessions-8k`` shape: ``chunk`` launched rows over one
    slot's ``start`` cached rows. The gathered form (the definition:
    every row scored, whatever is valid), then at each (token tile, KB
    of pages a VMEM chunk) the Pallas kernel over the flat pools in
    place, whole and with its compute taken out (the page copies
    alone): the error against the exact float32 softmax, ms a call
    (``calls`` chained calls a timed launch, alternating layers) with
    every row valid and with ``some_valid``, and ``turn_ms``, the mean
    over the traffic's turns from the times at one, two, ... scored
    tiles; one JSON line each."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.models import conv_lm
    from dlrover_tpu.ops import flat_decode_attention as fda
    from dlrover_tpu.serving.kvpool import conv

    shape = TINY_CONV if tiny else CONV
    model = dict(shape["model"])
    if tiny:
        # (a CPU has no bf16 x bf16 -> f32 matmul of the definition's)
        model["dtype"] = "float32"
    cfg = conv_lm.tiny_config(**model)
    dt = cfg.compute_dtype
    mb, bs, chunk = shape["max_blocks"], shape["block"], shape["chunk"]
    start, calls = shape["start"], shape["calls"]
    lo, hi = shape["valid"]
    rng = np.random.default_rng(seed)
    keys = jax.random.split(jax.random.key(seed + 1), 5)
    pool_dims = (shape["layers"], shape["num_blocks"], bs, cfg.kv_width)
    k_pool = _normal(keys[0], pool_dims).astype(dt)
    v_pool = _normal(keys[1], pool_dims).astype(dt)
    table = jnp.asarray(
        1 + rng.permutation(shape["num_blocks"] - 1)[:mb].astype(np.int32)
    )
    q = _normal(keys[2], (1, chunk, cfg.n_heads, cfg.head_dim)).astype(dt)
    k_new = _normal(keys[3], (1, chunk, cfg.n_kv_heads, cfg.head_dim))
    v_new = _normal(keys[4], (1, chunk, cfg.n_kv_heads, cfg.head_dim))
    k_new, v_new = k_new.astype(dt), v_new.astype(dt)

    def attend(kind):
        def one(k_pool, v_pool, at, n_valid, q):
            return conv.chunk_attend(
                cfg, k_pool, v_pool, at, table, jnp.int32(start), bs,
                n_valid, kind,
            )(q, k_new, v_new)

        def chained(k_pool, v_pool, n_valid):
            def body(out, at):
                # each call's queries hang on the last one's answer
                return one(
                    k_pool, v_pool, at, n_valid,
                    q + (out * 0).astype(q.dtype),
                ), None

            layers = jnp.arange(calls, dtype=jnp.int32) % shape["layers"]
            return jax.lax.scan(body, jnp.zeros_like(q), layers)[0]

        return jax.jit(one), jax.jit(chained)

    def exact(k_pool, v_pool, q, k_new, v_new):
        """The definition in float32 at the highest precision."""
        f32, hi_p = jnp.float32, jax.lax.Precision.HIGHEST
        kh, hd = cfg.n_kv_heads, cfg.head_dim
        rows = lambda pool, new: jnp.concatenate([  # noqa: E731
            pool[0][table].reshape(mb * bs, kh, hd)[:start].astype(f32),
            new[0].astype(f32),
        ])
        qh = q[0].astype(f32).reshape(chunk, kh, -1, hd)
        s = jnp.einsum(
            "qkgd,tkd->kgqt", qh, rows(k_pool, k_new), precision=hi_p
        )
        seen = jnp.arange(start + chunk)[None, :] <= (
            start + jnp.arange(chunk)[:, None]
        )
        p = jax.nn.softmax(
            jnp.where(seen, s * conv_lm.softmax_scale(cfg), -jnp.inf), -1
        )
        out = jnp.einsum(
            "kgqt,tkd->qkgd", p, rows(v_pool, v_new), precision=hi_p
        )
        return out.reshape(1, chunk, cfg.n_heads, hd)

    want = jax.jit(exact)(k_pool, v_pool, q, k_new, v_new)

    def report(form, kind, **more):
        tile = more.get("tile")
        one, chained = attend(kind)
        got = one(k_pool, v_pool, jnp.int32(0), jnp.int32(chunk), q)
        got = got.astype(jnp.float32)
        err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
        ms = lambda n: _timed(lambda: jax.block_until_ready(  # noqa: E731
            chained(k_pool, v_pool, jnp.int32(n))
        ), repeats)
        line = {"part": "conv_chunk", "form": form, **more,
                "rel_err_of_exact": err}
        whole = ms(chunk)
        if whole is not None:
            line["ms"] = round(whole / calls, 4)
            line[f"ms_at_{shape['some_valid']}_valid"] = round(
                ms(shape["some_valid"]) / calls, 4
            )
            if tile:
                by_tiles = [
                    ms(k * tile) / calls for k in range(1, chunk // tile)
                ] + [whole / calls]
                line["ms_by_tiles"] = [round(x, 4) for x in by_tiles]
                line["turn_ms"] = round(sum(
                    s * x for s, x in zip(
                        _share_of_turns(lo, hi, tile, chunk), by_tiles
                    )
                ), 4)
            else:
                line["turn_ms"] = line["ms"]
        print(json.dumps(line), flush=True)
        return bool(jnp.isfinite(got).all()), err

    def no_compute(q_ref, m_ref, l_ref, acc_ref, j, *rest):
        """Nothing attended: a sum of one, so the answer is zeros."""
        l_ref[j] = jnp.ones_like(l_ref[j])

    ok, view_err = report(
        "gathered_view", "gathered_view", rows=chunk, start=start
    )
    shipped = fda.CHUNK_PREFIX_BYTES, conv.CHUNK_TOKEN_TILE, fda._attend_keys
    try:
        for tile in token_tiles:
            for kb in prefix_kb:
                fda.CHUNK_PREFIX_BYTES, conv.CHUNK_TOKEN_TILE = kb << 10, tile
                pack = conv.lane_pack(cfg)
                if not tiny and not fda.flat_chunk_kernel_supported(
                    dt, bs, cfg.kv_width, pack * cfg.head_dim,
                    pack * cfg.n_heads // cfg.n_kv_heads, chunk, tile, mb,
                ):
                    continue
                more = dict(tile=tile, prefix_kb=kb, shipped=(
                    fda.CHUNK_PREFIX_BYTES, tile
                ) == shipped[:2])
                finite, err = report("pool_kernel", "pool_kernel", **more)
                # the definition's arithmetic: no further from the exact
                # than its rounding allows
                ok = ok and finite and err <= max(1.5 * view_err, 1e-5)
                fda._attend_keys = no_compute
                report("pool_kernel_copies_alone", "pool_kernel", **more)
                fda._attend_keys = shipped[2]
    finally:
        fda.CHUNK_PREFIX_BYTES, conv.CHUNK_TOKEN_TILE, fda._attend_keys = (
            shipped
        )
    return ok


def _latent_chunk(cfg, pool, p, layer, table_row, shape, query_rows,
                  repeats, keys):
    """One layer of a latent model's prefill chunk attention
    (``kvpool/latent.chunk_attend``: absorb, the prefix in blocks, the
    chunk's own rows, ``w_kvb``'s value half) at the cell's shape, with
    each of ``shape["n_valid"]`` of the chunk's rows valid: the queries
    in one tile (the whole-chunk form, which scores every row whatever
    is valid) and in tiles of each of ``query_rows``. ONE JSON line:
    ms a call by tile and valid rows, the share of the chunk's rows each
    scored, and the tiled forms' worst distance from the whole-chunk
    form over the valid rows."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.serving.kvpool import latent

    chunk, start, bs = shape["chunk"], shape["start"], shape["block"]
    dt = cfg.compute_dtype
    q_nope = jax.random.normal(
        keys[0], (1, chunk, cfg.n_heads, cfg.qk_nope_dim), dt
    )
    q_rope = jax.random.normal(
        keys[1], (1, chunk, cfg.n_heads, cfg.qk_rope_dim), dt
    )
    row = jax.random.normal(keys[2], (1, chunk, cfg.cache_width), dt)
    line = {
        "part": "latent", "form": "chunk", "chunk": chunk, "start": start,
        "shipped_query_rows": latent.CHUNK_QUERY_ROWS, "tiles": {},
    }
    ok, whole = True, {}
    shipped = latent.CHUNK_QUERY_ROWS
    try:
        for tile in [chunk] + [t for t in query_rows if t != chunk]:
            latent.CHUNK_QUERY_ROWS = tile
            attend = jax.jit(lambda pool, layer, n_valid: latent.chunk_attend(
                cfg, pool, layer, table_row, start, bs, n_valid
            )(p, q_nope, q_rope, row)[0])
            readings = line["tiles"][str(tile)] = {}
            for n in shape["n_valid"]:
                args = (pool, layer, jnp.int32(n))
                got = attend(*args).astype(jnp.float32)
                ok = ok and bool(jnp.isfinite(got).all())
                reading = {"rows_scored_share": round(
                    int(latent.chunk_rows_scored(n, chunk)) / chunk, 4
                )}
                if tile == chunk:
                    whole[n] = got
                else:
                    err = float(
                        jnp.linalg.norm(got[:n] - whole[n][:n])
                        / jnp.linalg.norm(whole[n][:n])
                    )
                    ok = ok and err < 0.02
                    reading["rel_err_of_whole_chunk"] = err
                _put(reading, "ms", _timed(
                    lambda: jax.block_until_ready(attend(*args)), repeats
                ))
                readings[str(n)] = reading
    finally:
        latent.CHUNK_QUERY_ROWS = shipped
    print(json.dumps(line), flush=True)
    return ok


def _latent_chunk_account(path, chunk):
    """Rows scored over rows launched by the prefill chunks of a traced
    benchmark run (its ``traced.json``): every ``serving.step`` span
    that ran a chunk says its ``prefill_tokens`` (the chunk's
    ``n_valid``), the run's ``kv_stats`` the tile in force (none before
    PR 44: the whole chunk), and ``latent.chunk_rows_scored`` is the
    function the program took its trip count from. One JSON line: the
    chunks that ended inside the profiler session, and all on record
    (set-up's context chunks, all of them full, the ramp's and the
    window's turns)."""
    from dlrover_tpu.serving.kvpool import latent

    with open(path) as f:
        facts = json.load(f)
    lo, hi = facts.get("traced_window") or (None, None)
    chunks = [
        (s["ts"] + s["dur_s"], s["attrs"]["prefill_tokens"])
        for s in facts.get("spans") or ()
        if s["name"] == "serving.step" and s.get("dur_s") is not None
        and s["attrs"].get("prefill_tokens")
    ]
    shipped = latent.CHUNK_QUERY_ROWS
    tile = (facts.get("kv_stats") or {}).get("latent_chunk_query_rows", chunk)
    line = {"part": "latent", "form": "chunk_account", "chunk": chunk,
            "query_rows": tile}
    try:
        latent.CHUNK_QUERY_ROWS = tile
        for name, valid in (
            ("traced", [n for end, n in chunks
                        if lo is not None and lo <= end <= hi]),
            ("all", [n for _, n in chunks]),
        ):
            scored = sum(latent.chunk_rows_scored(n, chunk) for n in valid)
            line[name] = {
                "chunks": len(valid), "full_chunks": valid.count(chunk),
                "rows_valid": sum(valid), "rows_scored": scored,
                "rows_launched": chunk * len(valid),
                "scored_over_launched": (
                    round(scored / (chunk * len(valid)), 4) if valid else None
                ),
            }
    finally:
        latent.CHUNK_QUERY_ROWS = shipped
    print(json.dumps(line), flush=True)


def _latent(tile_rows, query_rows, repeats, seed, tiny):
    """One layer of a latent model's decode attention
    (``kvpool/latent.decode_attend``: absorb, the rows, ``w_kvb``'s
    value half) at the ``xing-serve-sessions-16k`` shape, the gathered
    ``[slots, max_len]`` view against the Pallas kernel over the packed
    pool in place at each of ``tile_rows`` device rows a VMEM tile: ms a
    call and the visible rows' GB/s, one JSON line each; then the
    prefill chunk's (:func:`_latent_chunk`, tiles of ``query_rows``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.models import latent_lm
    from dlrover_tpu.ops import latent_decode_attention as lda
    from dlrover_tpu.serving.kvpool import latent
    from dlrover_tpu.serving.kvpool.index_pool import (
        IndexKeyPool,
        tokens_per_row,
    )

    shape = TINY_LATENT if tiny else LATENT
    cfg = latent_lm.tiny_config(**shape["model"])
    slots, mb, bs = shape["slots"], shape["max_blocks"], shape["block"]
    rng = np.random.default_rng(seed)
    keys = jax.random.split(jax.random.key(seed), 8)
    dt = cfg.compute_dtype
    pack = tokens_per_row(cfg.cache_width, bs)
    pool = IndexKeyPool(_normal(keys[0], (
        shape["layers"], shape["num_blocks"], bs // pack,
        pack * cfg.cache_width,
    )).astype(dt), cfg.cache_width)
    tables = jnp.asarray(
        1 + rng.permutation(shape["num_blocks"] - 1)[:slots * mb]
        .reshape(slots, mb).astype(np.int32)
    )
    fills = jnp.asarray(rng.integers(*shape["fills"], slots), jnp.int32)
    r = cfg.kv_lora_rank
    p = {"w_kvb": jax.random.normal(
        keys[1], (r, cfg.n_heads, cfg.qk_nope_dim + cfg.v_head_dim), dt
    ) * r ** -0.5}
    q_nope = jax.random.normal(
        keys[2], (slots, 1, cfg.n_heads, cfg.qk_nope_dim), dt
    )
    q_rope = jax.random.normal(
        keys[3], (slots, 1, cfg.n_heads, cfg.qk_rope_dim), dt
    )
    row = jax.random.normal(keys[4], (slots, 1, cfg.cache_width), dt)
    layer = jnp.int32(shape["layers"] - 1)
    row_bytes = int(fills.sum()) * cfg.cache_width * pool.rows.dtype.itemsize

    def timed(f, *args):
        ms = _timed(lambda: jax.block_until_ready(f(*args)), repeats)
        return {} if ms is None else {
            "ms": ms, "rows_gb_s": round(row_bytes / ms / 1e6, 1)
        }

    def attend(kind):
        return jax.jit(lambda pool, layer, tables, fills: latent.decode_attend(
            cfg, pool, layer, tables, fills, bs, kind=kind
        )(p, q_nope, q_rope, row))

    args = (pool, layer, tables, fills)
    view = attend("gathered_view")
    want = view(*args).astype(jnp.float32)
    print(json.dumps({
        "part": "latent", "form": "gathered_view", "slots": slots,
        "rows_mean": float(fills.mean()), **timed(view, *args),
    }), flush=True)
    ok = True
    shipped = lda.TILE_ROWS
    try:
        for rows in tile_rows or [shipped]:
            lda.TILE_ROWS = rows
            kernel = attend("pool_kernel")
            got = kernel(*args).astype(jnp.float32)
            err = float(
                jnp.linalg.norm(got - want) / jnp.linalg.norm(want)
            )
            ok = ok and bool(jnp.isfinite(got).all()) and err < 0.02
            print(json.dumps({
                "part": "latent", "form": "pool_kernel", "tile_rows": rows,
                "shipped": rows == shipped, "rel_err_of_gathered": err,
                **timed(kernel, *args),
            }), flush=True)
    finally:
        lda.TILE_ROWS = shipped
    return _latent_chunk(
        cfg, pool, p, layer, tables[0], shape,
        query_rows or shape["query_rows"], repeats, keys[5:],
    ) and ok


def run(shape, parts, chunk_kb, repeats, seed, starts, query_rows):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.models import generate as gen_lib
    from dlrover_tpu.models import llama
    from dlrover_tpu.ops import decode_attention as da
    from dlrover_tpu.serving.kvpool import dense

    slots, max_blocks, layers = shape.slots, shape.max_blocks, shape.layers
    heads, kv_heads, head_dim = shape.heads, shape.kv_heads, shape.head_dim
    max_len = max_blocks * BLOCK
    cfg = llama.TpuLMConfig(
        n_layers=layers, n_heads=heads, n_kv_heads=kv_heads,
        head_dim=head_dim, dtype="bfloat16", vocab_size=shape.vocab_size,
        embed_dim=shape.embed_dim, mlp_dim=shape.mlp_dim,
    )
    dev = jax.devices()[0]
    rng = np.random.RandomState(seed)
    num_blocks = slots * max_blocks + 1
    keys = jax.random.split(jax.random.key(seed), 6)
    pool_shape = (layers, num_blocks, BLOCK, kv_heads, head_dim)
    print(json.dumps({
        "part": "shape", **vars(shape), "max_len": max_len,
        # One layer's logical K and V views: what the gather path moves
        # four times over, whatever the fills.
        "view_mb": round(
            2 * slots * max_len * kv_heads * head_dim * 2 / 1e6, 1
        ),
        "engine_would_build": dense.pool_attention_kind(
            cfg, BLOCK, "fp", shape.chunk
        ),
    }), flush=True)

    k_pool, v_pool = _normal(keys[0], pool_shape), _normal(keys[1], pool_shape)
    q = _normal(keys[2], (slots, heads, head_dim))
    k_new = _normal(keys[3], (slots, kv_heads, head_dim))
    v_new = _normal(keys[4], (slots, kv_heads, head_dim))
    tables = jnp.asarray(
        (rng.permutation(slots * max_blocks) + 1)
        .reshape(slots, max_blocks).astype(np.int32)
    )
    active = jnp.ones(slots, bool)
    ok = True

    # ---- parity: one layer, fills on every edge ----------------------------
    if "parity" in parts:
        edge = np.resize(np.minimum(np.array(
            [0, 1, 15, 16, 17, 255, 256, 257, 511, 512, 764, 1000, 2047,
             2048, 2303, 2304], np.int32,
        ), max_len), slots)
        layer = jnp.int32(layers - 1)
        args = (k_pool, v_pool, layer, tables, jnp.asarray(edge))

        def both(q, k_new, v_new):
            return (
                jax.jit(da.pool_decode_attention)(
                    q, k_new, v_new, *args, active
                ),
                jax.jit(_reference)(q, k_new, v_new, *args),
            )

        # As the engine runs them (bf16 in, bf16 out), and with the same
        # values as f32 inputs against the reference at the highest
        # matmul precision: which of the two is nearer the exact softmax.
        got, want = (
            np.asarray(x, np.float32) for x in both(q, k_new, v_new)
        )
        wide = [x.astype(jnp.float32) for x in (q, k_new, v_new)]
        got32, want32 = (np.asarray(x) for x in both(*wide))
        with jax.default_matmul_precision("highest"):
            exact = np.asarray(jax.jit(_reference)(*wide, *args))
        diff = np.abs(got - want)
        parity = {
            "part": "parity", "finite": bool(np.isfinite(got).all()),
            "bf16_max_abs": float(diff.max()),
            "bf16_max_rel": float(
                (diff / np.maximum(np.abs(want), 2.0 ** -6)).max()
            ),
            "bf16_differing_of": [int((diff > 0).sum()), int(diff.size)],
            "f32_kernel_vs_exact": float(np.abs(got32 - exact).max()),
            "f32_gather_vs_exact": float(np.abs(want32 - exact).max()),
        }
        print(json.dumps(parity), flush=True)
        # The reference the kernel is held to is the exact one: XLA runs
        # the gather path's f32 einsums at default precision (one bf16
        # pass on a TPU), so there the two bf16 outputs differ by what
        # the GATHER rounds; off a TPU they differ by a rounding step
        # here and there.
        ok = parity["finite"] and parity["f32_kernel_vs_exact"] <= 1e-5

    # ---- all layers' attention alone ---------------------------------------
    def all_layers(attend):
        def f(q, k_new, v_new, k_pool, v_pool, tables, fills):
            def body(carry, layer):
                out = attend(
                    q, k_new, v_new, k_pool, v_pool, layer, tables, fills
                )
                return carry + out.astype(jnp.float32), None

            total, _ = jax.lax.scan(
                body, jnp.zeros(q.shape, jnp.float32),
                jnp.arange(layers, dtype=jnp.int32),
            )
            return total

        return jax.jit(f)

    def kernel(q, k_new, v_new, k_pool, v_pool, layer, tables, fills):
        return da.pool_decode_attention(
            q, k_new, v_new, k_pool, v_pool, layer, tables, fills, active
        )

    shipped = da._POOL_CHUNK_BYTES
    for kind in ("chat", "full", "short") if "attention" in parts else ():
        fills = _fills(kind, rng, slots, max_len)
        row = {"part": "attention", "fills": kind,
               "kv_rows": int(fills.sum()),
               "kv_mb_read": round(
                   2 * layers * int((-(-fills // BLOCK)).sum()) * BLOCK
                   * kv_heads * head_dim * 2 / 1e6, 1)}
        timed = (
            q, k_new, v_new, k_pool, v_pool, tables, jnp.asarray(fills)
        )
        for kb in chunk_kb:
            da._POOL_CHUNK_BYTES = kb << 10
            fn = all_layers(kernel)
            _put(row, f"kernel_{kb}k_ms", _timed(
                lambda: jax.block_until_ready(fn(*timed)), repeats
            ))
        da._POOL_CHUNK_BYTES = shipped
        fn = all_layers(_reference)
        _put(row, "gather_ms", _timed(
            lambda: jax.block_until_ready(fn(*timed)), repeats
        ))
        print(json.dumps(row), flush=True)
    del q, k_new, v_new

    # ---- the whole decode program ------------------------------------------
    if "decode" in parts:
        params = _weights(cfg, keys[5])
        fills = _fills("chat", rng, slots, max_len)
        host = (
            tables, jnp.asarray(fills), jnp.zeros(slots, jnp.int32),
            active, jnp.zeros(slots, jnp.float32), jax.random.key(0),
            np.int32(0),
        )
        row = {"part": "decode", "kv_rows": int(fills.sum())}
        tokens = {}
        for attn in ("xla_gather", "paged_kernel"):
            step = jax.jit(
                dense._build_paged_decode(
                    cfg, slots, max_blocks, BLOCK, {"decode": 0}, attn=attn
                ),
                donate_argnums=(0, 1),
            )

            def once():
                nonlocal k_pool, v_pool
                k_pool, v_pool, nxt = step(k_pool, v_pool, params, *host)
                return jax.block_until_ready(nxt)

            tokens[attn] = np.asarray(once())
            _put(row, f"{attn}_ms", _timed(once, repeats))
        row["same_tokens"] = [
            int((tokens["xla_gather"] == tokens["paged_kernel"]).sum()),
            slots,
        ]
        print(json.dumps(row), flush=True)
    if "prefill" in parts:
        ok = _prefill(
            cfg, shape, starts, query_rows, repeats, rng, keys, k_pool,
            v_pool,
        ) and ok
    print(json.dumps({
        "ok": ok,
        "device": {"platform": dev.platform, "kind": dev.device_kind},
    }), flush=True)
    return ok


def _chunk_reference(q, k_new, v_new, k_pool, v_pool, layer, table_row,
                     start):
    """What the gather prefill computes a layer: the slot's logical
    view, the chunk written at ``start``, plain causal attention over
    every row of it."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.ops.attention import dot_product_attention

    rows = table_row.shape[0] * BLOCK
    views = [
        jax.lax.dynamic_update_slice(
            pool[layer][table_row].reshape((rows,) + pool.shape[-2:]),
            new.astype(pool.dtype), (start, 0, 0),
        )
        for pool, new in ((k_pool, k_new), (v_pool, v_new))
    ]
    t = q.shape[0]
    return dot_product_attention(
        q[None], views[0][None], views[1][None], causal=True,
        q_positions=(start + jnp.arange(t))[None],
        kv_positions=jnp.arange(rows),
    )[0]


def _prefill(cfg, shape, starts, query_rows, repeats, rng, keys, k_pool,
             v_pool):
    """The prefill mode: one slot's chunk of ``shape.chunk`` tokens at
    each of ``starts``."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.ops import decode_attention as da
    from dlrover_tpu.serving.kvpool import dense

    chunk, layers, max_blocks = shape.chunk, shape.layers, shape.max_blocks
    max_len = max_blocks * BLOCK
    starts = sorted({
        min(s, max_len - chunk) // chunk * chunk for s in starts
    })
    table_row = jnp.asarray(
        (rng.permutation(shape.slots * max_blocks)[:max_blocks] + 1)
        .astype(np.int32)
    )

    q = _normal(keys[2], (chunk, shape.heads, shape.head_dim))
    k_new = _normal(keys[3], (chunk, shape.kv_heads, shape.head_dim))
    v_new = _normal(keys[4], (chunk, shape.kv_heads, shape.head_dim))
    ok = True

    # ---- the kernel's arithmetic against the exact softmax -----------------
    # bf16 values as f32 inputs, so that the outputs are f32 and what is
    # compared is the arithmetic, not the last rounding to bf16.
    wide = [x.astype(jnp.float32) for x in (q, k_new, v_new)]
    for start in starts:
        args = (k_pool, v_pool, jnp.int32(layers - 1), table_row,
                jnp.int32(start))
        with jax.default_matmul_precision("highest"):
            exact = np.asarray(jax.jit(_chunk_reference)(*wide, *args))
        row = {"part": "prefill_parity", "start": start}
        for name, fn in (
            ("kernel_f32_probs", da.pool_chunk_attention),
            ("kernel_bf16_probs", functools.partial(
                da.pool_chunk_attention, exact=False)),
            ("gather", _chunk_reference),
        ):
            got = np.asarray(jax.jit(fn)(*wide, *args))
            row[f"{name}_vs_exact"] = float(np.abs(got - exact).max())
            row["finite"] = row.get("finite", True) and bool(
                np.isfinite(got).all()
            )
        print(json.dumps(row), flush=True)
        ok = ok and row["finite"] and row["kernel_f32_probs_vs_exact"] <= 1e-5

    # ---- all layers' chunk attention alone ---------------------------------
    def all_layers(attend):
        def f(q, k_new, v_new, k_pool, v_pool, table_row, start):
            def body(carry, layer):
                out = attend(
                    q, k_new, v_new, k_pool, v_pool, layer, table_row,
                    start,
                )
                return carry + out.astype(jnp.float32), None

            total, _ = jax.lax.scan(
                body, jnp.zeros(q.shape, jnp.float32),
                jnp.arange(layers, dtype=jnp.int32),
            )
            return total

        return jax.jit(f)

    shipped = da._CHUNK_QUERY_ROWS, da._CHUNK_VMEM_BYTES
    for start in starts:
        row = {"part": "prefill_attention", "start": start,
               "kv_rows": start + chunk}
        timed = (q, k_new, v_new, k_pool, v_pool, table_row,
                 jnp.int32(start))
        for rows in query_rows or shipped[:1]:
            # A larger tile than the shipped one wants more VMEM.
            da._CHUNK_QUERY_ROWS = rows
            da._CHUNK_VMEM_BYTES = shipped[1] * max(1, rows // shipped[0])
            for name, exact in (("f32_probs", True), ("bf16_probs", False)):
                fn = all_layers(functools.partial(
                    da.pool_chunk_attention, exact=exact
                ))
                _put(row, f"kernel_{rows}rows_{name}_ms", _timed(
                    lambda: jax.block_until_ready(fn(*timed)), repeats
                ))
        da._CHUNK_QUERY_ROWS, da._CHUNK_VMEM_BYTES = shipped
        fn = all_layers(_chunk_reference)
        _put(row, "gather_ms", _timed(
            lambda: jax.block_until_ready(fn(*timed)), repeats
        ))
        print(json.dumps(row), flush=True)
    del q, k_new, v_new

    # ---- the whole prefill program -----------------------------------------
    params = _weights(cfg, keys[5])
    tokens = jnp.asarray(
        rng.randint(1, shape.vocab_size, (1, chunk)).astype(np.int32)
    )
    programs = {
        attn: jax.jit(
            dense._build_paged_prefill(
                cfg, max_blocks, BLOCK, chunk, {"prefill": 0}, attn=attn
            ),
            donate_argnums=(0, 1),
        )
        for attn in ("xla_gather", "paged_kernel")
    }
    for start in starts:
        row = {"part": "prefill", "start": start}
        first = {}
        for attn, program in programs.items():
            for head in (True, False):
                def once():
                    nonlocal k_pool, v_pool
                    k_pool, v_pool, tok = program(
                        k_pool, v_pool, params, tokens, table_row,
                        np.int32(start), np.int32(chunk), np.float32(0.0),
                        jax.random.key(0), np.int32(0), np.bool_(head),
                    )
                    return jax.block_until_ready(tok)

                tok = int(once())
                if head:
                    first[attn] = tok
                _put(row, f"{attn}_{'head' if head else 'no_head'}_ms",
                     _timed(once, repeats))
        row["same_first_token"] = first["xla_gather"] == first["paged_kernel"]
        print(json.dumps(row), flush=True)
    return ok


def main():
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--preset", choices=sorted(PRESETS), default="nemo12b")
    ap.add_argument("--slots", type=int)
    ap.add_argument("--max-blocks", type=int,
                    help=f"pages of {BLOCK} rows in a slot's table")
    ap.add_argument("--heads", type=int, help="query heads")
    ap.add_argument("--layers", type=int)
    ap.add_argument("--chunk", type=int, help="tokens of a prefill chunk")
    ap.add_argument("--parts", default="parity,attention,decode,prefill")
    ap.add_argument("--starts", default="0,512,2048",
                    help="prefill: cache rows below the chunk (brought "
                    "down to the last chunk the cache holds)")
    ap.add_argument("--query-rows",
                    help="prefill: query rows (tokens x heads) a grid "
                    "step of the chunk kernel, to time it at (default, "
                    "the one shipped: "
                    "ops.decode_attention._CHUNK_QUERY_ROWS)")
    ap.add_argument("--chunk-kb", default="256,512,1024,2048",
                    help="VMEM chunk sizes to time the kernel at (the "
                    "one shipped: ops.decode_attention._POOL_CHUNK_BYTES; "
                    "conv: ops.flat_decode_attention.CHUNK_BYTES)")
    ap.add_argument("--token-tiles", default="64,128,256",
                    help="conv: tokens a grid step of the chunk kernel, "
                    "to time it at (the one shipped: "
                    "kvpool.conv.CHUNK_TOKEN_TILE)")
    ap.add_argument("--prefix-kb", default="256,512,1024",
                    help="conv: KB of pages a VMEM chunk of the chunk "
                    "kernel (the one shipped: "
                    "ops.flat_decode_attention.CHUNK_PREFIX_BYTES)")
    ap.add_argument("--tile-rows",
                    help="latent: device rows a VMEM tile of the latent "
                    "kernel, to time it at (default, the one shipped: "
                    "ops.latent_decode_attention.TILE_ROWS)")
    ap.add_argument("--chunk-query-rows",
                    help="latent: query rows a tile of the prefill "
                    "chunk's attention, to time it at beside the whole "
                    "chunk as one tile (default 32,64,128; the one "
                    "shipped: kvpool.latent.CHUNK_QUERY_ROWS)")
    ap.add_argument("--chunks-of", metavar="TRACED_JSON",
                    help="latent: print rows scored over rows launched "
                    "by the prefill chunks of that traced benchmark run "
                    "and exit (needs no TPU)")
    ap.add_argument("--repeats", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="a 640-row cache and a narrow model: rehearses "
                    "the script off the chip (interpret mode); no times")
    ns = ap.parse_args()
    shape = dict(PRESETS[ns.preset])
    if ns.tiny:
        shape.update(TINY)
    shape.update({
        k: v for k in ("slots", "max_blocks", "heads", "layers", "chunk")
        if (v := getattr(ns, k)) is not None
    })
    parts = ns.parts.split(",")
    if ns.chunks_of:
        if "latent" not in parts:
            ap.error("--chunks-of is the latent part's")
        _latent_chunk_account(
            ns.chunks_of, (TINY_LATENT if ns.tiny else LATENT)["chunk"]
        )
        return
    import jax

    if not ns.tiny and jax.default_backend() != "tpu":
        ap.error(   # exit 2: 1 is "the kernel is wrong"
            f"no TPU here ({jax.default_backend()}): the kernel would "
            "run in interpret mode and its times would mean nothing; "
            "--tiny rehearses the script without timing"
        )
    if "conv" in parts:
        parts.remove("conv")
        ok = _conv(
            [int(kb) for kb in ns.chunk_kb.split(",")],
            0 if ns.tiny else ns.repeats, ns.seed, ns.tiny,
        ) and _conv_chunk(
            [int(t) for t in ns.token_tiles.split(",")],
            [int(kb) for kb in ns.prefix_kb.split(",")],
            0 if ns.tiny else ns.repeats, ns.seed, ns.tiny,
        )
        if not ok or not parts:
            raise SystemExit(0 if ok else 1)
    if "latent" in parts:
        parts.remove("latent")
        ok = _latent(
            [int(x) for x in ns.tile_rows.split(",")] if ns.tile_rows
            else None,
            [int(x) for x in ns.chunk_query_rows.split(",")]
            if ns.chunk_query_rows else None,
            0 if ns.tiny else ns.repeats, ns.seed, ns.tiny,
        )
        if not ok or not parts:
            raise SystemExit(0 if ok else 1)
    ok = run(
        argparse.Namespace(**shape), parts,
        [int(kb) for kb in ns.chunk_kb.split(",")],
        0 if ns.tiny else ns.repeats, ns.seed,
        [int(x) for x in ns.starts.split(",")],
        [int(x) for x in ns.query_rows.split(",")] if ns.query_rows
        else None,
    )
    raise SystemExit(0 if ok else 1)


if __name__ == "__main__":
    main()
