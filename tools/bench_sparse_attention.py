"""On-chip timing of the sparse-attention serving path, part by part, at
``keye-serve-docqa-32k``'s shape (16 slots x 33,792 rows, a 512-token
chunk, 5 layers of 128 experts): which of score / select / compact /
gather / attend / experts a decode step and a prefill chunk spend their
time in, and the two whole programs. The part ``chunk_kernel`` is the
A/B of the chunk's attention under its selection, one layer: the Pallas
kernel over the pool in place
(``ops.decode_attention.sparse_chunk_attention``) at 1, 2 and 4 valid
query tiles beside ``masked_attention`` over the gathered views (one
128-query block, which is what the ``jax.numpy`` path runs a block), the
two views' gathers it no longer needs, both sides' worst difference from
the float32 softmax over the selected keys, and the kernel again with
its mask, its ``exp``, its ``PV`` product or its strided head reads
taken out (wrong answers, timed only: what is left says what bounds it).
The part ``index_pool`` is the A/B of how the index-key pool is held
(``kvpool/index_pool.py``): one layer's block gather, scores and
selection and the landing of every layer's new keys, the decode step's
way and the chunk's, over the pool as the engine builds it (two 64-wide
keys to a 128-lane row) beside a bare ``[layers, num_blocks, block_size,
index_dim]`` array, ms a call and how many copies of the pool's size
each compiled text holds.

    chiprun -- python3 tools/bench_sparse_attention.py [--parts ...] [--layers N]
    JAX_PLATFORMS=cpu python3 tools/bench_sparse_attention.py --tiny   # rehearsal, times nothing real

Each part is jitted alone, run once to compile and ``--iters`` times
under ``block_until_ready``; a line of JSON a part, all of them again in
``chiprun_out/bench_sparse_attention.json``. Not a benchmark cell: the
cell's own trace (``benchmark/sparse_scopes.py``) is what PERF.md quotes.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def timed(fn, *args, iters=5):
    import jax

    t0 = time.time()
    jax.block_until_ready(fn(*args))
    compile_s = time.time() - t0
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return {"ms": 1e3 * (time.time() - t0) / iters,
            "first_call_s": round(compile_s, 2)}


class _Ablated:
    """``ops.decode_attention`` with a part of the chunk kernels taken
    out while a kernel is traced (wrong answers, for timing alone):
    ``mask`` (the select on the scores), ``exp`` (the identity in its
    place), ``pv`` (no probabilities-by-values product), ``head_rows``
    (a KV head's rows read as a contiguous run instead of a strided
    load and a shift). ``what`` names them, ``+`` between."""

    def __init__(self, module, what):
        import jax
        import jax.numpy as jnp

        self._module, self._jnp, self._lax = module, jnp, jax.lax
        self._what = set(filter(None, what.split("+")))
        self._over = {}
        if "mask" in self._what:
            self._over["where"] = lambda c, a, b: (
                a if isinstance(b, float) else jnp.where(c, a, b)
            )
        if "exp" in self._what:
            self._over["exp"] = lambda x: x
        self._dot = jax.lax.dot_general
        self._head_rows = module._head_rows

    def __getattr__(self, name):
        return self._over.get(name) or getattr(self._jnp, name)

    def __enter__(self):
        jnp = self._jnp
        self._module.jnp = self
        if "pv" in self._what:
            def dot_general(a, b, dims, **kw):
                if dims == (((0,), (0,)), ((), ())):
                    return jnp.zeros((a.shape[1], b.shape[1]), jnp.float32)
                return self._dot(a, b, dims, **kw)

            self._lax.dot_general = dot_general
        if "head_rows" in self._what:
            self._module._head_rows = lambda ref, head, kv_heads, rows: (
                jnp.concatenate(
                    [ref[j, :rows] for j in range(ref.shape[0])], axis=-1
                )
            )

    def __exit__(self, *exc):
        self._module.jnp = self._jnp
        self._lax.dot_general = self._dot
        self._module._head_rows = self._head_rows


def chunk_kernel_part(cfg, eng, report, rng, iters, tiny):
    """The chunk's attention under its selection, one layer, both ways
    (see the module's docstring)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.ops import decode_attention as da
    from dlrover_tpu.ops import sparse_attention as sa
    from dlrover_tpu.serving.kvpool import sparse

    max_len, chunk = eng["max_len"], eng["prefill_chunk"]
    bs = eng["block_size"]
    h, kh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    topk = min(cfg.index_topk, max_len)
    cdt = cfg.compute_dtype
    mb = max_len // bs
    nb = (eng.get("num_blocks") or 16 * mb + 1) if not tiny else 2 * mb + 1
    f = lambda *s: jnp.asarray(rng.normal(size=s), cdt)  # noqa: E731
    k_pool, v_pool = f(1, nb, bs, kh, hd), f(1, nb, bs, kh, hd)
    table = jnp.asarray(1 + rng.permutation(nb - 1)[:mb], jnp.int32)
    start = max_len - 2 * chunk
    q, k_new, v_new = f(chunk, h, hd), f(chunk, kh, hd), f(chunk, kh, hd)
    at = start + jnp.arange(chunk)
    visible = jnp.arange(max_len)[None, :] <= at[:, None]
    selection = jax.jit(lambda s, v: sa.select_mask(s, v, topk))(
        jnp.asarray(rng.normal(size=(chunk, max_len)), jnp.float32), visible
    )
    layer, start = jnp.int32(0), jnp.int32(start)
    sub = min(sparse.CHUNK_QUERY_BLOCK, chunk)
    tile = da._chunk_token_tile(chunk, h) or chunk
    report("chunk_kernel.shape", {
        "start": int(start), "token_tile": tile, "select_block": sub,
        "kind_here": "chunk_kernel" if da.sparse_chunk_kernel_supported(
            cdt, bs, h, kh, hd, chunk, mb) else "masked_attention",
    })

    views = jax.jit(lambda kp, vp, kn, vn: tuple(
        sparse._slot_view(p, layer, table, n[None], start, bs)
        for p, n in ((kp, kn), (vp, vn))
    ))
    k_view, v_view = views(k_pool, v_pool, k_new, v_new)
    masked = jax.jit(sa.masked_attention)
    if not tiny:
        report("chunk_kernel.view_gathers_k_and_v", timed(
            views, k_pool, v_pool, k_new, v_new, iters=iters))
        report("chunk_kernel.masked_attention_one_block", timed(
            masked, q[:sub], k_view, v_view, selection[:sub], iters=iters))

    def kernel():
        fn = jax.jit(lambda kp, vp, sel, n_valid: da.sparse_chunk_attention(
            q, k_new, v_new, kp, vp, layer, table, start, sel, n_valid,
        ))
        return lambda n_valid: fn(k_pool, v_pool, selection, n_valid)

    # Both against the float32 softmax over the selected keys.
    want = jax.jit(sa.masked_attention)(
        q.astype(jnp.float32), k_view.astype(jnp.float32),
        v_view.astype(jnp.float32), selection,
    )
    err = lambda got: float(jnp.max(jnp.abs(  # noqa: E731
        got.astype(jnp.float32) - want)))
    report("chunk_kernel.max_abs_err_vs_float32", {
        "kernel": err(kernel()(jnp.int32(chunk))),
        "masked_attention": err(jnp.concatenate([
            masked(q[lo:lo + sub], k_view, v_view, selection[lo:lo + sub])
            for lo in range(0, chunk, sub)
        ])),
    })
    if tiny:
        return
    for what in ("", "mask", "exp", "mask+exp", "pv", "head_rows",
                 "mask+exp+pv+head_rows"):
        with _Ablated(da, what):
            fn = kernel()
            for tiles in (1, 2, 4):
                if tiles * tile > chunk:
                    continue
                report(
                    f"chunk_kernel.kernel_{tiles}_tiles"
                    + (f".without_{what}" if what else ""),
                    timed(fn, jnp.int32(tiles * tile), iters=iters),
                )


def block_select_part(report, rng, iters, tiny):
    """The block-sparse mixer's decode SELECTION at
    ``sala-serve-docs-64k``'s shape (48 slots x 1,040 blocks, 2 KV heads
    x 16 heads, ``ckeys [6, 10,560, 4, 128]``), one sparse layer: the
    ``jax.numpy`` scores over the table-gathered view beside the kernel
    that reads the pool in place (``ops/block_select.py``) at 8, 16 and
    32 table entries a copy group, over a CONSECUTIVE table (eight
    documents of 1,024 blocks and 16 blocks of a slot's own, as the cell
    holds them) and a SHUFFLED one (no run: a copy a block); the share
    of copy groups that are runs, the two forms' worst difference and
    whether they list the same blocks; and the list's ``lax.top_k``,
    which both forms share."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import common
    from benchmark.runners import serve_linear
    from dlrover_tpu.models import linear_sparse_lm as lsm
    from dlrover_tpu.ops import block_select
    from dlrover_tpu.serving.kvpool import linear

    if tiny:
        cfg = lsm.tiny_config()
        slots, mb, nb, doc = 3, 12, 64, 8
    else:
        cfg_json = common.load_json("configs", "minicpm-sala-9b.json")
        cfg, eng = serve_linear.linear_config(cfg_json), cfg_json["serve_engine"]
        slots, nb = eng["slots"], eng["num_blocks"]
        mb, doc = eng["max_len"] // eng["block_size"], 1024
    c, cdt = cfg, cfg.compute_dtype
    per, bs, layer = c.ckeys_per_block, c.sparse_block, c.sparse_layers[-1]
    f = lambda *s: jnp.asarray(rng.normal(size=s), cdt)  # noqa: E731
    ck = f(c.cache_layers, nb, per, c.head_dim)
    q, fresh = f(slots, c.n_heads, c.head_dim), f(slots, c.n_kv_heads, c.head_dim)
    lengths = jnp.asarray(
        mb * bs - 1 - rng.integers(0, 6 * bs, slots), jnp.int32
    )
    active = jnp.ones((slots,), bool)
    n_docs = (nb - 1 - slots * (mb - doc)) // doc
    tables = {"consecutive": np.stack([np.concatenate([
        1 + doc * (s % n_docs) + np.arange(doc),
        1 + doc * n_docs + (mb - doc) * s + np.arange(mb - doc),
    ]) for s in range(slots)]).astype(np.int32), "shuffled": np.stack([
        1 + rng.permutation(nb - 1)[:mb] for _ in range(slots)
    ]).astype(np.int32)}
    blocks = -(-((np.asarray(lengths) + 1) // c.kernel_stride) // per)
    report("block_select.shape", {
        "slots": slots, "max_blocks": mb, "ckeys": list(ck.shape),
        "kind_here": linear.select_kind(c, ck.dtype, slots, mb),
    })
    listed = jax.jit(lambda s: linear.decode_block_lists(c, s, lengths))

    def scores_fn(select, group_blocks=block_select.GROUP_BLOCKS):
        """``fn(table)``; the pool and the queries go in as arguments (a
        closed-over array is a constant of the program)."""
        fn = jax.jit(lambda t, q, fresh, ck: linear.decode_block_scores(
            c, q, fresh, ck, layer, t, lengths, active, select, group_blocks
        ))
        return lambda t: fn(t, q, fresh, ck)

    for name, table in tables.items():
        t = jnp.asarray(table)
        want = scores_fn("jnp")(t)
        if not tiny:
            report(f"block_select.{name}.jnp", timed(
                scores_fn("jnp"), t, iters=iters))
        for group_blocks in (8, 16, 32):
            groups, runs = block_select.copy_groups(
                table, blocks, group_blocks)
            fn = scores_fn("pool_kernel", group_blocks)
            got = fn(t)
            line = {
                "copy_groups": groups, "run_share": runs / groups,
                "max_abs_diff_vs_jnp": float(jnp.max(jnp.abs(got - want))),
                "same_lists": all(
                    bool(jnp.array_equal(a, b))
                    for a, b in zip(listed(got), listed(want))
                ),
            }
            if not tiny:
                line.update(timed(fn, t, iters=iters))
            report(f"block_select.{name}.kernel_g{group_blocks}", line)
    if not tiny:
        report("block_select.top_k_list", timed(listed, want, iters=iters))


def index_pool_part(cfg, eng, report, rng, iters, tiny):
    """The index-key pool's reads and landings, packed beside bare (see
    the module's docstring). The pool is donated and threaded from call
    to call, as the engine's programs take it."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.serving.kvpool import SENTINEL_BLOCK, sparse
    from dlrover_tpu.serving.kvpool.index_pool import IndexKeyPool

    slots, max_len, chunk = eng["slots"], eng["max_len"], eng["prefill_chunk"]
    bs, layers = eng["block_size"], cfg.n_layers
    hi, di, cdt = cfg.index_heads, cfg.index_dim, cfg.compute_dtype
    mb = max_len // bs
    nb = eng.get("num_blocks") or slots * mb + 1
    f = lambda *s: jnp.asarray(rng.normal(size=s), cdt)  # noqa: E731
    tables = jnp.asarray(
        1 + (rng.permutation(slots * mb) % (nb - 1)).reshape(slots, mb),
        jnp.int32,
    )
    lengths = jnp.asarray(
        rng.integers(max_len - 2 * chunk, max_len - 1, slots), jnp.int32
    )
    layer = jnp.int32(layers - 1)

    def step(ki, q_idx, k_idx, w, news):
        idx, _ = sparse.decode_select(
            cfg, ki, layer, tables, lengths, bs, q_idx, k_idx, w
        )
        blk = jnp.take_along_axis(
            tables, (lengths // bs)[:, None], axis=1
        )[:, 0]
        pool = IndexKeyPool.of(ki).land_tokens(news, blk, lengths % bs)
        return sparse._like(ki, pool), idx

    def chunk_fn(ki, q_idx, k_idx, w, news):
        start = jnp.int32(max_len - 2 * chunk)
        view = sparse._slot_view(ki, layer, tables[0], k_idx, start, bs)
        sub = min(sparse.CHUNK_QUERY_BLOCK, chunk)
        mask = sparse.chunk_select(
            cfg, view, start + jnp.arange(sub), q_idx[:sub], w[:sub]
        )
        pool = IndexKeyPool.of(ki).land_run(
            news, tables[0], start, bs, SENTINEL_BLOCK
        )
        return sparse._like(ki, pool), mask

    calls = {
        "decode": (step, (f(slots, 1, hi, di), f(slots, 1, di),
                          f(slots, 1, hi), f(layers, slots, di))),
        "chunk": (chunk_fn, (f(chunk, hi, di), f(1, chunk, di),
                             f(chunk, hi), f(layers, chunk, di))),
    }
    made = IndexKeyPool.zeros(layers, nb, bs, di, cdt)
    report("index_pool.shape", {
        "logical": list(made.shape), "rows": list(made.rows.shape),
        "tokens_per_row": made.pack,
    })
    for kind in ("packed", "bare"):
        for name, (fn, operands) in calls.items():
            ki = (
                IndexKeyPool.zeros(layers, nb, bs, di, cdt)
                if kind == "packed" else jnp.zeros(made.shape, cdt)
            )
            jitted = jax.jit(fn, donate_argnums=0)
            text = jitted.lower(ki, *operands).compile().as_text()
            copies = sum(
                f"[{layers},{nb}," in line.split(" copy(")[0]
                for line in text.splitlines() if " copy(" in line
            )
            ki, out = jitted(ki, *operands)
            jax.block_until_ready(out)
            t0 = time.time()
            for _ in range(iters):
                ki, out = jitted(ki, *operands)
            jax.block_until_ready((ki, out))
            report(f"index_pool.{name}.{kind}", {
                "ms": 1e3 * (time.time() - t0) / iters,
                "pool_sized_copies": copies,
            })
            del ki


def profile(engine, decode, prefill, calls=3, top=14):
    """ms a call of each program by scope and by (scope, op), from a
    profiler session over ``calls`` calls of each."""
    import collections

    import jax

    from benchmark import common, sparse_scopes, trace_reduce
    from benchmark.runners import serve_sparse

    tables = serve_sparse.program_scopes(engine)
    prof = common.Profile(os.path.join(ROOT, "chiprun_out"))
    prof.start()
    for _ in range(calls):
        jax.block_until_ready(decode())
    for _ in range(calls):
        jax.block_until_ready(prefill())
    dump = sparse_scopes.label(prof.stop(), tables)
    out = {}
    for lines in dump["planes"].values():
        _, program_at = sparse_scopes._programs(lines)
        ops = collections.defaultdict(lambda: collections.Counter())
        for name, start, dur, scope, cat in lines.get(
                trace_reduce.OPS_LINE) or []:
            if cat in trace_reduce.ENVELOPES:
                continue
            mod = program_at(start) or "?"
            key = (sparse_scopes.scope_of(scope)
                   or trace_reduce.scope_of(scope))
            ops[mod][key + ":" + trace_reduce.base_name(name)] += dur
            ops[mod]["= " + key] += dur
        for mod, counter in ops.items():
            out[mod] = [
                [k, round(v / 1e6 / calls, 3)]
                for k, v in counter.most_common(top + 9)
            ]
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--layers", type=int, default=5)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--profile", action="store_true",
                    help="trace 3 calls of each program: ms a call by "
                         "scope and by op")
    ap.add_argument("--parts", nargs="*", default=[
        "ops_decode", "ops_chunk", "chunk_kernel", "index_pool", "experts",
        "programs",
    ])
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import common
    from benchmark.runners import serve_sparse
    from dlrover_tpu.models import sparse_lm
    from dlrover_tpu.ops import sparse_attention as sa

    if jax.default_backend() != "tpu" and not args.tiny:
        print("needs a TPU (or --tiny)", file=sys.stderr)
        return 3
    cfg_json = common.load_json("configs", "keye-vl2-30b-a3b.json")
    eng = dict(cfg_json["serve_engine"])
    if args.tiny:
        from tests.benchmark import tiny_keye

        cfg_json, eng = tiny_keye.CONFIG, dict(tiny_keye.CONFIG["serve_engine"])
    cfg = serve_sparse.sparse_config(cfg_json, n_layers=(
        cfg_json["num_hidden_layers"] if args.tiny else args.layers
    ))
    slots, max_len, chunk = eng["slots"], eng["max_len"], eng["prefill_chunk"]
    bs = eng["block_size"]
    topk = min(cfg.index_topk, max_len)
    cdt = cfg.compute_dtype
    rng = np.random.default_rng(0)
    f = lambda *s: jnp.asarray(rng.normal(size=s), cdt)  # noqa: E731
    out = {"device": jax.devices()[0].device_kind, "shape": {
        "slots": slots, "max_len": max_len, "chunk": chunk, "topk": topk,
        "layers": cfg.n_layers,
    }}

    def report(name, value):
        out[name] = value
        print(json.dumps({name: value}), flush=True)

    it = args.iters
    hi, di, h, kh, hd = (cfg.index_heads, cfg.index_dim, cfg.n_heads,
                         cfg.n_kv_heads, cfg.head_dim)
    if "ops_decode" in args.parts:
        q_idx, w, view = f(slots, 1, hi, di), f(slots, 1, hi), f(slots, max_len, di)
        fills = jnp.asarray(rng.integers(max_len - 900, max_len - 1, slots))
        visible = jnp.arange(max_len)[None, :] <= fills[:, None]
        scores = jax.jit(sa.index_scores)(q_idx, w, view)[:, 0]
        report("decode.index_scores", timed(jax.jit(sa.index_scores), q_idx, w, view, iters=it))
        select = jax.jit(lambda s, v: sa.select_mask(s, v, topk))
        report("decode.select_mask", timed(select, scores, visible, iters=it))
        pick = jax.jit(lambda s, v: sa.select_indices(s, v, topk))
        report("decode.select_indices", timed(pick, scores, visible, iters=it))
        idx, valid = pick(scores, visible)
        pool = f(1, max_len * slots // bs // 4, bs, kh, hd)
        blk = jnp.asarray(rng.integers(0, pool.shape[1], (slots, topk)))
        off = idx % bs
        report("decode.gather_k_and_v", timed(jax.jit(
            lambda p, b, o: (p[0, b, o], p[0, b, o + 0])
        ), pool, blk, off, iters=it))
        q, ks = f(slots, h, hd), f(slots, topk, kh, hd)
        report("decode.gathered_attention", timed(
            jax.jit(sa.gathered_attention), q, ks, ks, valid, iters=it))
    if "ops_chunk" in args.parts:
        q_idx, w, view = f(chunk, hi, di), f(chunk, hi), f(max_len, di)
        start = max_len - 2 * chunk
        visible = jnp.arange(max_len)[None, :] <= (start + jnp.arange(chunk))[:, None]
        scores = jax.jit(sa.index_scores)(q_idx, w, view)
        report("chunk.index_scores", timed(jax.jit(sa.index_scores), q_idx, w, view, iters=it))
        select = jax.jit(lambda s, v: sa.select_mask(s, v, topk))
        report("chunk.select_mask", timed(select, scores, visible, iters=it))
        mask = select(scores, visible)
        q, k = f(chunk, h, hd), f(max_len, kh, hd)
        report("chunk.masked_attention", timed(
            jax.jit(sa.masked_attention), q, k, k, mask, iters=it))
    if "chunk_kernel" in args.parts:
        chunk_kernel_part(cfg, eng, report, rng, it, args.tiny)
    if "index_pool" in args.parts:
        index_pool_part(cfg, eng, report, rng, it, args.tiny)
    if "block_select" in args.parts:
        block_select_part(report, rng, it, args.tiny)
    if "experts" in args.parts or "programs" in args.parts:
        params = jax.jit(
            lambda key: sparse_lm.init_params(cfg, key, dtype=cdt)
        )(jax.random.key(0))
    if "experts" in args.parts:
        p0 = sparse_lm.layer_params(cfg, params, 0)
        mlp = jax.jit(lambda p, x: sparse_lm.expert_mlp(cfg, p, x)[0])
        report("decode.expert_mlp_one_layer", timed(
            mlp, p0, f(slots, 1, cfg.embed_dim), iters=it))
        report("chunk.expert_mlp_one_layer", timed(
            mlp, p0, f(1, chunk, cfg.embed_dim), iters=it))
        del p0
    if "programs" in args.parts:
        from dlrover_tpu.serving.kvpool import PagedServingEngine

        engine = PagedServingEngine(
            cfg, params, slots=slots, max_len=max_len, prefill_chunk=chunk,
            block_size=bs, num_blocks=eng.get("num_blocks"),
        )
        del params
        mb = engine.max_blocks
        tables = np.zeros((slots, mb), np.int32)
        for s in range(slots):
            tables[s] = 1 + (np.arange(mb) + 37 * s) % (engine.num_blocks - 1)
        lengths = np.full(slots, max_len - 600, np.int32)
        i32 = np.int32
        state = {"pools": engine._pools()}

        def decode():
            res = engine._steps.decode(
                *state["pools"], engine._params, jnp.asarray(tables),
                jnp.asarray(lengths), jnp.zeros(slots, jnp.int32),
                jnp.ones(slots, bool), jnp.zeros(slots, jnp.float32),
                engine._rng, i32(0), i32(0), i32(-1),
            )
            state["pools"] = res[:3]
            return res[3]

        def prefill():
            res = engine._steps.prefill(
                *state["pools"], engine._params,
                jnp.zeros((1, chunk), jnp.int32), jnp.asarray(tables[0]),
                i32(max_len - 2 * chunk), i32(chunk), np.float32(0),
                engine._rng, i32(0), np.bool_(True),
            )
            state["pools"] = res[:3]
            return res[3]

        report("program.jit_step", timed(decode, iters=it))
        report("program.jit_prefill", timed(prefill, iters=it))
        if args.profile:
            report("profile", profile(engine, decode, prefill))
        report("memory_peak_gb", (jax.devices()[0].memory_stats() or {}).get(
            "peak_bytes_in_use", 0) / 1e9)
    path = os.path.join(ROOT, "chiprun_out")
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "bench_sparse_attention.json"), "w") as fh:
        json.dump(out, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
