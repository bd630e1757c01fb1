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
step and the prefill chunk of ``xing-serve-sessions-16k`` and of
``keye-serve-docqa-32k``, the cells' widths, all five expert layers'
experts one stack of groups walked by a traced ``group_offset``, every
token's ``top_k`` distinct experts drawn evenly from the seed (which
hits the cells' ~55 of 64 and ~82 of 128 experts at decode). For each
shape it walks a table of the two grouped matmuls' weight blocks
``(tk, tn)`` (today's power-of-two tiles, ``moe._weight_block``'s
choice, and ``SERVE_BLOCKS``) and prints one JSON line each: the
blocks, the grid steps a layer, ms a layer (host clock over
``--repeats`` calls of all five layers in flight, so the device's
time), and the GB/s at which the hit experts' weights moved beside the
HBM peak. ``--tiny`` rehearses it on a CPU at toy widths and times
nothing worth reading; without it, off a TPU, it exits 3.

    chiprun -- python tools/bench_moe_dispatch.py --serve
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
# experts a token, expert layers held; decode rows are the cells' slots,
# chunk rows their prefill chunk).
SERVE_SHAPES = {
    "xing_decode": dict(tokens=32, top_k=4, d=3584, f=1024, experts=64),
    "xing_chunk": dict(tokens=512, top_k=4, d=3584, f=1024, experts=64),
    "keye_decode": dict(tokens=16, top_k=8, d=2048, f=768, experts=128),
    "keye_chunk": dict(tokens=512, top_k=8, d=2048, f=768, experts=128),
}
SERVE_LAYERS = 5
TINY_SHAPE = dict(tokens=24, top_k=2, d=256, f=128, experts=4)

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


def _grid_steps(group_sizes, tm, d, f, blocks):
    """Grid steps of the two ``gmm`` calls over one layer's groups:
    (row tile, group) visits x the weight blocks a visit streams."""
    ends = group_sizes.cumsum()
    starts = ends - group_sizes
    hit = group_sizes > 0
    visits = int(
        ((ends[hit] - 1) // tm - starts[hit] // tm + 1).sum()
    )
    (tk1, tn1), (tk2, tn2) = blocks
    per_visit = (
        -(-d // tk1) * -(-2 * f // tn1) + -(-f // tk2) * -(-d // tn2)
    )
    return visits * per_visit


def run_serve(shapes, repeats=20, seed=0, tiny=False):
    """One JSON-able dict a (shape, blocks) entry, printed as made."""
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
    rule = moe._weight_block
    lines = []
    for name in shapes:
        sh = TINY_SHAPE if tiny else SERVE_SHAPES[name]
        n, top_k, d, f, e = (
            sh["tokens"], sh["top_k"], sh["d"], sh["f"], sh["experts"]
        )
        layers, cdt = SERVE_LAYERS, jnp.bfloat16
        keys = jax.random.split(jax.random.key(seed), 5)
        x = jax.random.normal(keys[0], (1, n, d), jnp.float32).astype(cdt)
        w_gu = (jax.random.normal(keys[1], (layers * e, d, 2 * f), cdt)
                * d ** -0.5).astype(cdt)
        w_down = (jax.random.normal(keys[2], (layers * e, f, d), cdt)
                  * f ** -0.5).astype(cdt)
        # top_k distinct experts a token, every expert as likely
        experts = jnp.argsort(
            jax.random.uniform(keys[3], (layers, n, e)), axis=-1
        )[..., :top_k].astype(jnp.int32)
        weights = jax.nn.softmax(
            jax.random.normal(keys[4], (layers, n, top_k)), axis=-1
        )
        sizes = np.stack([
            np.bincount(np.asarray(experts[at]).ravel(), minlength=e)
            for at in range(layers)
        ])
        even = n * top_k // e
        tm = moe._tile(n * top_k, cap=min(max(even, moe.ROW_TILE), 512))
        size = jnp.dtype(cdt).itemsize
        today = (
            (moe._tile(d), moe._tile(2 * f)), (moe._tile(f), moe._tile(d))
        )
        chosen = (
            rule(even, tm, d, 2 * f, size), rule(even, tm, f, d, size)
        )
        table = list(dict.fromkeys([today, chosen, *SERVE_BLOCKS[(d, f)]]))
        args = (x, experts, weights, w_gu, w_down)
        for blocks in table:
            by_shape = {(d, 2 * f): blocks[0], (f, d): blocks[1]}
            moe._weight_block = lambda even, tm, k, n_, size: by_shape[k, n_]

            # a function a table entry: jit traces each under its blocks
            def all_layers(x, experts, weights, w_gu, w_down):
                def layer(x, at):
                    out, counters = moe.routed_experts(
                        x, experts[at], weights[at], w_gu, w_down, e,
                        group_offset=at * e,
                    )
                    return out, counters.experts_hit
                return jax.lax.scan(layer, x, jnp.arange(layers))

            try:
                t0 = time.time()
                fn = jax.jit(all_layers).lower(*args).compile()
                compile_s = time.time() - t0
                out, hit = jax.block_until_ready(fn(*args))
                t0 = time.time()
                for _ in range(repeats):
                    res = fn(*args)
                jax.block_until_ready(res)
                ms = (time.time() - t0) * 1e3 / repeats / layers
            finally:
                moe._weight_block = rule
            hit_mean = float(jnp.mean(hit))
            line = {
                "shape": name,
                "platform": device.platform, "device": device.device_kind,
                "pairs": n * top_k, "groups": layers * e, "tm": tm,
                "gate_up_block": list(blocks[0]),
                "down_block": list(blocks[1]),
                "block_mb": [
                    round(tk * tn * size / 1e6, 3) for tk, tn in blocks
                ],
                "today": blocks == today, "chosen": blocks == chosen,
                "experts_hit_mean": hit_mean,
                "grid_steps_per_layer": float(np.mean([
                    _grid_steps(sizes[at], tm, d, f, blocks)
                    for at in range(layers)
                ])),
                "ms_per_layer": ms, "compile_s": round(compile_s, 2),
                "finite": bool(jnp.isfinite(out.astype(jnp.float32)).all()),
            }
            if peak:
                gbs = hit_mean * 3 * d * f * size / (ms * 1e-3) / 1e9
                line["hit_weights_gb_per_s"] = gbs
                line["hbm_peak_pct"] = 100 * gbs * 1e9 / peak
            print(json.dumps(line), flush=True)
            lines.append(line)
        del x, w_gu, w_down, args
    return lines


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--repeats", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--serve", action="store_true",
                    help="time the served expert layer over weight blocks")
    ap.add_argument("--shapes", default=",".join(SERVE_SHAPES),
                    help="--serve: which of SERVE_SHAPES, comma-separated")
    ap.add_argument("--tiny", action="store_true",
                    help="--serve: toy widths, runs on a CPU")
    a = ap.parse_args(argv)
    if a.serve:
        lines = run_serve(
            ["tiny"] if a.tiny else a.shapes.split(","),
            repeats=a.repeats or 20, seed=a.seed, tiny=a.tiny,
        )
        if lines is None:
            return 3
        return 0 if all(line["finite"] for line in lines) else 1
    out = run(repeats=a.repeats or 10, seed=a.seed)
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
