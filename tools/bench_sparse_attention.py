"""On-chip timing of the sparse-attention serving path, part by part, at
``keye-serve-docqa-32k``'s shape (16 slots x 33,792 rows, a 512-token
chunk, 5 layers of 128 experts): which of score / select / compact /
gather / attend / experts a decode step and a prefill chunk spend their
time in, and the two whole programs.

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
        "ops_decode", "ops_chunk", "experts", "programs",
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
