"""Dropless MoE dispatch A/B on the device at hand: ``gmm`` vs ``fused``.

``models/moe.py`` computes the dropless expert MLP two ways: megablox
grouped matmuls around XLA gathers (``dispatch="gmm"``, the default)
and the ``ops/moe_dispatch`` Pallas kernel that gathers, multiplies and
scatters in one pass (``"fused"``). PR 21 made ``fused`` lower for the
v5e and re-defaulted to ``gmm`` on one reading of this probe; ROADMAP
S3 decides from it whether ``fused`` wins or goes.

One layer's ``moe_mlp_dropless`` forward + backward (grads of x and the
three expert weights) at r05's MoE shape: 8 x 2048 tokens,
d = f = 1024, 8 experts, top-2 — with seeded random inputs:

    python tools/bench_moe_dispatch.py          # on the chip: chiprun -- ...

Prints one JSON line: device, compile seconds and the median of
``--repeats`` timed calls per dispatch, ``fused_over_gmm`` (> 1: fused
is slower), and the relative error of ``fused`` against ``gmm`` for the
output and each gradient. Exits 1 if anything is not finite or the two
disagree by more than ``REL_TOL``. A smoke reading, not a benchmark:
one process, one shape, host-clock timing around ``block_until_ready``.

``--serve`` times the SERVED expert layer alone instead
(``moe.routed_experts``, what the ``mlp/experts`` scope of a serve
cell's programs holds) at the shapes of ``SERVE_SHAPES``: the decode
step and the prefill chunk of ``xing-serve-sessions-16k``,
``keye-serve-docqa-32k``, ``lfm2-serve-sessions-8k`` and
``mellum2-serve-mixed-16k``, the cells' widths, all of a cell's expert
layers' experts (five, five, eight, eight) one stack of groups walked by
a traced ``group_offset``, every token's ``top_k`` distinct experts
drawn evenly from the seed (which hits the cells' ~55 of 64 and ~82 of
128 experts at decode); with ``--valid-rows N`` the tokens past the
``N``-th are the ``N``-th repeated, as a partial prefill chunk's pad
rows are (one token routes one way: ``top_k`` heavy groups). For each
shape it walks a table of the two grouped matmuls' weight blocks
``(tk, tn)`` (today's power-of-two tiles, ``moe._weight_block``'s
choice, and ``SERVE_BLOCKS``) and prints one JSON line each: the
blocks, the grid steps a layer, ms a layer (host clock over
``--repeats`` calls of all five layers in flight, so the device's
time), and the GB/s at which the hit experts' weights moved beside the
HBM peak. ``--layouts`` walks the two ROW LAYOUTS instead, under the
rule's blocks: ``packed`` (sorted rows end to end) and ``aligned``
(every expert's rows from a row-tile boundary, ``moe._aligned_rows``'s
other answer), a line each with ``weight_visits_mean`` (the (expert,
tile) visits a grouped matmul makes, ``moe.weight_visits``),
``buffer_rows`` and ``by_rule`` (the layout the program takes at that
shape); ``--profile`` adds ``ops_ms_per_layer``, the device's ms a layer
by op from a profiler session. ``--tiny`` rehearses it on a CPU at toy
widths and times nothing worth reading; without it, off a TPU, it exits
3.

    chiprun -- python tools/bench_moe_dispatch.py --serve
    chiprun -- python tools/bench_moe_dispatch.py --serve --layouts \
        --shapes mellum2_chunk,lfm2_chunk --valid-rows 215

``--share`` times the TRAINED share instead (``moe.moe_mlp_share``, what
the ``mlp/router`` and ``mlp/experts`` scopes of a train cell's step
hold): ONE layer's forward + backward (gradients of x, the router and
the three expert weights) under ``jax.checkpoint`` with the cell's
``remat_keep`` policy (``hybrid.remat_policy``), at the shapes of
``SHARE_SHAPES``: ``kimi`` (``kimilinear-train-8k``: 8,192 tokens x
2,304, top-8 of 256 experts, 8 held) and ``glm``
(``glm47flash-train-8k``: 8,192 x 2,048, top-4 of 64, 8 held), float32
weights cast a layer as the step casts them, a seeded router (even
routing: a share gets ``held / all`` of the pairs, give or take) or
with ``--skew`` a bias that sends every token's pairs to the held
experts (more rows than the usual buffer: the full one). One JSON line a
shape: ms a layer (host clock over ``--repeats`` calls in flight),
``rows_held``, ``rows_max``, ``rows_full_path`` (the rows of a call that
left the fast path; 0 where it ran, null at a parent that has no such
counter), and with ``--profile`` ``ops_ms_per_layer``: the device's ms
by op (each sort, gather, scatter, fusion and kernel under its own
name). ``--tiny`` rehearses it on a CPU. (PR 54's readings of its parent:
this file run against the parent's checkout, which has no
``hybrid.remat_policy``, with ``_block``'s policies supplied under that
name.)

    chiprun -- python tools/bench_moe_dispatch.py --share --profile
    chiprun -- python tools/bench_moe_dispatch.py --share --skew
"""

import argparse
import functools
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# bf16 operands, f32 accumulation, different summation orders: the first
# chip reading had 8e-5 (out) to 4e-3 (dx) between the two.
REL_TOL = 2e-2


def run(batch=8, seq=2048, d=1024, f=1024, experts=8, top_k=2,
        repeats=10, seed=0):
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import moe

    keys = jax.random.split(jax.random.key(seed), 5)

    def normal(key, shape, dtype, scale):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(
            dtype
        )

    args = (
        normal(keys[0], (batch, seq, d), jnp.bfloat16, 1.0),
        normal(keys[1], (d, experts), jnp.float32, d ** -0.5),
        normal(keys[2], (experts, d, f), jnp.bfloat16, d ** -0.5),
        normal(keys[3], (experts, d, f), jnp.bfloat16, d ** -0.5),
        normal(keys[4], (experts, f, d), jnp.bfloat16, f ** -0.5),
    )
    device = jax.devices()[0]
    out = {
        "platform": device.platform, "device": device.device_kind,
        "tokens": batch * seq, "d": d, "f": f, "experts": experts,
        "top_k": top_k, "repeats": repeats,
    }
    results = {}
    for dispatch in ("gmm", "fused"):
        def fwd_bwd(*a, dispatch=dispatch):
            def loss(x, rw, wg, wu, wd):
                y, _ = moe.moe_mlp_dropless(
                    x, rw, wg, wu, wd, top_k=top_k, dispatch=dispatch
                )
                return jnp.sum(y.astype(jnp.float32) ** 2), y

            (_, y), grads = jax.value_and_grad(
                loss, argnums=(0, 2, 3, 4), has_aux=True
            )(*a)
            return (y, *grads)

        t0 = time.time()
        compiled = jax.jit(fwd_bwd).lower(*args).compile()
        out[f"{dispatch}_compile_s"] = round(time.time() - t0, 2)
        jax.block_until_ready(compiled(*args))
        times = []
        for _ in range(repeats):
            t0 = time.time()
            results[dispatch] = jax.block_until_ready(compiled(*args))
            times.append(time.time() - t0)
        out[f"{dispatch}_fwd_bwd_ms_median"] = (
            statistics.median(times) * 1e3
        )
    out["fused_over_gmm"] = (
        out["fused_fwd_bwd_ms_median"] / out["gmm_fwd_bwd_ms_median"]
    )
    ok = True
    for name, a, b in zip(
        ("out", "dx", "dwg", "dwu", "dwd"),
        results["gmm"], results["fused"],
    ):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        rel = float(
            jnp.linalg.norm((a - b).ravel())
            / jnp.maximum(jnp.linalg.norm(a.ravel()), 1e-30)
        )
        out[f"relerr_{name}"] = rel
        ok = ok and bool(jnp.isfinite(b).all()) and rel <= REL_TOL
    out["ok"] = ok
    return out


# The served expert layer's shapes (the cells' configurations under
# benchmark/configs/: hidden_size, moe_intermediate_size, experts,
# experts a token, expert layers held (five unless said); decode rows are
# the cells' slots, chunk rows their prefill chunk).
SERVE_SHAPES = {
    "xing_decode": dict(tokens=32, top_k=4, d=3584, f=1024, experts=64),
    "xing_chunk": dict(tokens=512, top_k=4, d=3584, f=1024, experts=64),
    "keye_decode": dict(tokens=16, top_k=8, d=2048, f=768, experts=128),
    "keye_chunk": dict(tokens=512, top_k=8, d=2048, f=768, experts=128),
    "lfm2_decode": dict(tokens=32, top_k=4, d=2048, f=1536, experts=64,
                        layers=8),
    "lfm2_chunk": dict(tokens=512, top_k=4, d=2048, f=1536, experts=64,
                       layers=8),
    "mellum2_decode": dict(tokens=32, top_k=8, d=2304, f=896, experts=64,
                           layers=8),
    "mellum2_chunk": dict(tokens=512, top_k=8, d=2304, f=896, experts=64,
                          layers=8),
}
SERVE_LAYERS = 5
TINY_SHAPE = dict(tokens=24, top_k=2, d=256, f=128, experts=4)
LAYOUTS = ("packed", "aligned")

# Blocks tried beside today's and the rule's, by (d, f): ((tk, tn) of
# the gate-and-up matmul [d, 2f], (tk, tn) of the down matmul [f, d]).
# The rule budgets for a backward too; the largest here fit a forward
# alone (tests/test_tpu_compile.py has where the compiler's edge is).
SERVE_BLOCKS = {
    (3584, 1024): [
        ((512, 1024), (512, 896)), ((512, 2048), (512, 1792)),
        ((1792, 1024), (1024, 1792)), ((3584, 512), (1024, 1792)),
        ((896, 2048), (512, 3584)),
    ],
    (2048, 768): [
        ((512, 768), (384, 1024)), ((1024, 768), (768, 1024)),
        ((2048, 768), (768, 2048)), ((2048, 1536), (768, 2048)),
    ],
    (256, 128): [((128, 256), (128, 128))],
}


def _grid_steps(visits, d, f, blocks):
    """Grid steps of the two ``gmm`` calls over one layer's groups:
    (row tile, group) visits x the weight blocks a visit streams."""
    (tk1, tn1), (tk2, tn2) = blocks
    per_visit = (
        -(-d // tk1) * -(-2 * f // tn1) + -(-f // tk2) * -(-d // tn2)
    )
    return visits * per_visit


# The trained share's shapes (benchmark/configs/kimi-linear-48b-a3b.json,
# glm-4.7-flash.json: hidden_size, moe_intermediate_size, the router's
# width, experts a token, experts held, routed_scaling_factor, the
# step's remat_keep; tokens: micro_batch x seq_len).
SHARE_SHAPES = {
    "kimi": dict(tokens=8192, d=2304, f=1024, experts=256, top_k=8, held=8,
                 scaling=2.446, remat_keep="dots"),
    "glm": dict(tokens=8192, d=2048, f=1536, experts=64, top_k=4, held=8,
                scaling=1.8, remat_keep="attention"),
}
TINY_SHARE = dict(tokens=256, d=128, f=64, experts=32, top_k=4, held=4,
                  scaling=2.0, remat_keep="dots")


def _ops_ms(fn, args, layers, calls=3, top=12):
    """ms a layer by device op (kernels and XLA's fusions, each under
    its own name: ``fusion.17`` is not ``fusion.16``), from a profiler
    session over ``calls`` calls."""
    import collections

    import jax

    from benchmark import common, trace_reduce

    prof = common.Profile(os.path.join(ROOT, "chiprun_out"))
    prof.start()
    for _ in range(calls):
        jax.block_until_ready(fn(*args))
    ops = collections.Counter()
    for lines in (prof.stop() or {"planes": {}})["planes"].values():
        for name, _, dur, _, cat in lines.get(trace_reduce.OPS_LINE) or []:
            if cat not in trace_reduce.ENVELOPES:
                ops[name] += dur
    return {
        name: round(ns / 1e6 / calls / layers, 4)
        for name, ns in ops.most_common(top)
    }


def run_serve(shapes, repeats=20, seed=0, tiny=False, layouts=False,
              valid_rows=None, profile=False):
    """One JSON-able dict a (shape, blocks) entry, or with ``layouts`` a
    (shape, row layout) entry under the rule's blocks, printed as made."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.models import moe

    device = jax.devices()[0]
    if device.platform != "tpu" and not tiny:
        print("bench_moe_dispatch --serve: no TPU here (--tiny rehearses "
              "on a CPU)", file=sys.stderr)
        return None
    peak = None
    if device.platform == "tpu":
        from benchmark import common, flops

        peak = flops.peaks_for(
            device.device_kind, common.load_json("peaks.json")
        )["hbm_bytes_per_s"]
    block_rule, layout_rule = moe._weight_block, moe._aligned_rows
    by_rule = []    # the layout rule's answers, a trace each

    lines = []
    for name in shapes:
        sh = TINY_SHAPE if tiny else SERVE_SHAPES[name]
        n, top_k, d, f, e = (
            sh["tokens"], sh["top_k"], sh["d"], sh["f"], sh["experts"]
        )
        layers, cdt = sh.get("layers", SERVE_LAYERS), jnp.bfloat16
        keys = jax.random.split(jax.random.key(seed), 5)
        x = jax.random.normal(keys[0], (1, n, d), jnp.float32).astype(cdt)

        @functools.partial(jax.jit, static_argnums=(1, 2))
        def stack(key, k, n_):
            # a layer's weights once and every layer the same: the time
            # a layer takes is not in their values, and eight layers of
            # 64 experts drawn apart would pass through twice their
            # bytes in temporaries
            one = (jax.random.normal(key, (e, k, n_), jnp.float32)
                   * k ** -0.5).astype(cdt)
            return jnp.tile(one, (layers, 1, 1))

        w_gu, w_down = stack(keys[1], d, 2 * f), stack(keys[2], f, d)
        # top_k distinct experts a token, every expert as likely
        experts = jnp.argsort(
            jax.random.uniform(keys[3], (layers, n, e)), axis=-1
        )[..., :top_k].astype(jnp.int32)
        if valid_rows is not None and valid_rows < n:
            experts = experts.at[:, valid_rows:].set(
                experts[:, valid_rows][:, None]
            )
        weights = jax.nn.softmax(
            jax.random.normal(keys[4], (layers, n, top_k)), axis=-1
        )
        sizes = np.stack([
            np.bincount(np.asarray(experts[at]).ravel(), minlength=e)
            for at in range(layers)
        ])
        even = n * top_k // e
        # the aligned layout's row tile, and the packed one's (which a
        # buffer smaller than a tile cuts down)
        tm_whole = min(max(even, moe.ROW_TILE), 512)
        tm = moe._tile(n * top_k, cap=tm_whole)
        size = jnp.dtype(cdt).itemsize
        today = (
            (moe._tile(d), moe._tile(2 * f)), (moe._tile(f), moe._tile(d))
        )
        chosen = (
            block_rule(even, tm, d, 2 * f, size),
            block_rule(even, tm, f, d, size),
        )
        if layouts:
            table = [(chosen, layout) for layout in LAYOUTS]
        else:
            table = [(blocks, None) for blocks in dict.fromkeys(
                [today, chosen, *SERVE_BLOCKS.get((d, f), [])]
            )]
        args = (x, experts, weights, w_gu, w_down)
        packed_out = None
        for blocks, layout in table:
            by_shape = {(d, 2 * f): blocks[0], (f, d): blocks[1]}
            moe._weight_block = lambda even, tm, k, n_, size: by_shape[k, n_]
            # the rule is asked either way, so that a line says what the
            # program takes; a named layout overrides its answer
            def answer(*shape_args, layout=layout):
                by_rule.append(layout_rule(*shape_args))
                return by_rule[-1] if layout is None else layout == "aligned"

            moe._aligned_rows = answer

            # a function a table entry: jit traces each under its blocks
            def all_layers(x, experts, weights, w_gu, w_down):
                def layer(x, at):
                    out, counters = moe.routed_experts(
                        x, experts[at], weights[at], w_gu, w_down, e,
                        group_offset=at * e,
                    )
                    visits = counters.weight_visits
                    if visits is None:
                        visits = moe.weight_visits(
                            jnp.bincount(
                                experts[at].ravel(), length=e
                            ), tm, False,
                        )
                    return out, (counters.experts_hit, visits, out)
                return jax.lax.scan(layer, x, jnp.arange(layers))

            try:
                t0 = time.time()
                fn = jax.jit(all_layers).lower(*args).compile()
                compile_s = time.time() - t0
                out, (hit, visits, outs) = jax.block_until_ready(fn(*args))
                t0 = time.time()
                for _ in range(repeats):
                    res = fn(*args)
                jax.block_until_ready(res)
                ms = (time.time() - t0) * 1e3 / repeats / layers
                ops_ms = _ops_ms(fn, args, layers) if profile else None
            finally:
                moe._weight_block = block_rule
                moe._aligned_rows = layout_rule
            hit_mean = float(jnp.mean(hit))
            aligned = by_rule[-1] if layout is None else layout == "aligned"
            pairs = n * top_k
            tm_line = tm_whole if aligned else tm
            line = {
                "shape": name,
                "platform": device.platform, "device": device.device_kind,
                "pairs": pairs, "groups": layers * e, "tm": tm_line,
                "gate_up_block": list(blocks[0]),
                "down_block": list(blocks[1]),
                "block_mb": [
                    round(tk * tn * size / 1e6, 3) for tk, tn in blocks
                ],
                "today": blocks == today, "chosen": blocks == chosen,
                "layout": "aligned" if aligned else "packed",
                "by_rule": "aligned" if by_rule[-1] else "packed",
                "buffer_rows": (
                    (-(-pairs // tm_whole) + e) * tm_whole if aligned
                    else -(-pairs // tm) * tm if pairs >= tm else pairs
                ),
                "valid_rows": n if valid_rows is None else valid_rows,
                "experts_hit_mean": hit_mean,
                "rows_max": int(sizes.max()),
                "weight_visits_mean": float(jnp.mean(visits)),
                "grid_steps_per_layer": float(np.mean([
                    _grid_steps(int(v), d, f, blocks) for v in visits
                ])),
                "ms_per_layer": ms, "compile_s": round(compile_s, 2),
                "finite": bool(jnp.isfinite(out.astype(jnp.float32)).all()),
            }
            if ops_ms is not None:
                line["ops_ms_per_layer"] = ops_ms
            out = outs[0].astype(jnp.float32)    # the first layer's: of x
            if layout == "packed":
                packed_out = out
            elif layout == "aligned":
                # the same rows through the same kernel, the routing
                # weight applied one rounding later
                line["max_abs_diff_from_packed"] = float(
                    jnp.max(jnp.abs(out - packed_out))
                )
                line["max_abs"] = float(jnp.max(jnp.abs(packed_out)))
            if peak:
                gbs = hit_mean * 3 * d * f * size / (ms * 1e-3) / 1e9
                line["hit_weights_gb_per_s"] = gbs
                line["hbm_peak_pct"] = 100 * gbs * 1e9 / peak
            print(json.dumps(line), flush=True)
            lines.append(line)
        del x, w_gu, w_down, args
    return lines


def run_share(shapes, repeats=20, seed=0, tiny=False, skew=False,
              profile=False):
    """One JSON-able dict a shape, printed as made (None: no TPU)."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import hybrid, moe

    device = jax.devices()[0]
    if device.platform != "tpu" and not tiny:
        print("bench_moe_dispatch --share: no TPU here (--tiny rehearses "
              "on a CPU)", file=sys.stderr)
        return None
    lines = []
    for name in shapes:
        sh = TINY_SHARE if tiny else SHARE_SHAPES[name]
        n, d, f, e, held = (
            sh["tokens"], sh["d"], sh["f"], sh["experts"], sh["held"]
        )
        top_k, first = sh["top_k"], held    # the second share of e / held
        keys = jax.random.split(jax.random.key(seed), 7)

        def normal(key, shape, fan_in, dtype=jnp.float32):
            return (jax.random.normal(key, shape, jnp.float32)
                    / fan_in ** 0.5).astype(dtype)

        bias = 0.01 * jax.random.normal(keys[5], (e,), jnp.float32)
        if skew:
            bias = bias.at[first:first + held].set(100.0)
        args = (
            normal(keys[0], (1, n, d), 1, jnp.bfloat16),
            normal(keys[1], (d, e), d), normal(keys[2], (held, d, f), d),
            normal(keys[3], (held, d, f), d), normal(keys[4], (held, f, d), f),
        )
        cot = normal(keys[6], (1, n, d), 1)

        @functools.partial(
            jax.checkpoint, policy=hybrid.remat_policy(sh["remat_keep"])
        )
        def layer(x, router, w_gate, w_up, w_down):
            return moe.moe_mlp_share(
                x, router, bias, w_gate, w_up, w_down, first=first,
                top_k=top_k, scaling=sh["scaling"],
            )

        def loss(*a):
            out, counters = layer(*a)
            return jnp.sum(out.astype(jnp.float32) * cot), counters

        grad = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)
        t0 = time.time()
        fn = jax.jit(grad).lower(*args).compile()
        compile_s = time.time() - t0
        (_, counters), grads = jax.block_until_ready(fn(*args))
        t0 = time.time()
        for _ in range(repeats):
            res = fn(*args)
        jax.block_until_ready(res)
        full = getattr(counters, "rows_full_path", None)
        line = {
            "shape": name, "skew": skew,
            "platform": device.platform, "device": device.device_kind,
            "tokens": n, "pairs": n * top_k, "held": held,
            "remat_keep": sh["remat_keep"],
            "rows_held": int(counters.rows_held),
            "rows_max": int(counters.rows_max),
            "rows_dropped": int(counters.rows_dropped),
            "rows_full_path": None if full is None else int(full),
            "ms_per_layer": (time.time() - t0) * 1e3 / repeats,
            "compile_s": round(compile_s, 2),
            "finite": all(
                bool(jnp.isfinite(g.astype(jnp.float32)).all()) for g in grads
            ),
        }
        if profile:
            line["ops_ms_per_layer"] = _ops_ms(fn, args, 1, top=48)
        print(json.dumps(line), flush=True)
        lines.append(line)
    return lines


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--repeats", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--serve", action="store_true",
                    help="time the served expert layer over weight blocks")
    ap.add_argument("--share", action="store_true",
                    help="time the trained share's forward + backward")
    ap.add_argument("--skew", action="store_true",
                    help="--share: every token's pairs go to the held experts")
    ap.add_argument("--shapes", default=None,
                    help="which of SERVE_SHAPES (--serve) or SHARE_SHAPES "
                         "(--share), comma-separated; default: all")
    ap.add_argument("--tiny", action="store_true",
                    help="--serve, --share: toy widths, runs on a CPU")
    ap.add_argument("--layouts", action="store_true",
                    help="--serve: both row layouts under the rule's blocks")
    ap.add_argument("--profile", action="store_true",
                    help="--serve, --share: ms a layer by device op on "
                         "each line")
    ap.add_argument("--valid-rows", type=int, default=None,
                    help="--serve: tokens past this one repeat it")
    a = ap.parse_args(argv)
    if a.share or a.serve:
        names = ["tiny"] if a.tiny else (a.shapes or ",".join(
            SHARE_SHAPES if a.share else SERVE_SHAPES
        )).split(",")
        if a.share:
            lines = run_share(
                names, repeats=a.repeats or 20, seed=a.seed, tiny=a.tiny,
                skew=a.skew, profile=a.profile,
            )
        else:
            lines = run_serve(
                names, repeats=a.repeats or 20, seed=a.seed, tiny=a.tiny,
                layouts=a.layouts, valid_rows=a.valid_rows,
                profile=a.profile,
            )
        if lines is None:
            return 3
        return 0 if all(line["finite"] for line in lines) else 1
    out = run(repeats=a.repeats or 10, seed=a.seed)
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
