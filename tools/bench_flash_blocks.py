"""The flash-attention kernels' block sizes on the device at hand.

``ops/pallas_attention`` tiles its three kernels (forward, dq, dk/dv) by
block targets that were picked at head size 128; a shape may carry its
own in ``pallas_attention.BLOCK_TARGETS``. This probe times the three
kernels of ONE latent-attention layer at a cell's shape (default
``glm47flash-train-8k``'s: 20 heads, 8,192 tokens, q/k 256, v 256, bf16,
causal) under each candidate target:

    python tools/bench_flash_blocks.py          # on the chip: chiprun -- ...
        [--heads 20 --tokens 8192 --dqk 256 --dv 256]
        [--fwd 512x512,1024x1024,1024x512] [--bwd 256x256,512x512,...]

One JSON line a candidate (``"default"``: what the table gives today,
so the entry this tool's readings put there: PR 43, 512 x 1024 for the
backward at 256 / 256):
``fwd_ms`` (the forward kernel alone), ``bwd_ms`` (dq + dk/dv with the
delta and the transposes around them: forward + backward minus the
forward), or ``"error"`` where the chip's compiler refuses the blocks
(more fast memory than a kernel may use). A smoke reading, not a
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


def pairs(text):
    return [tuple(int(n) for n in p.split("x")) for p in text.split(",")]


def timed(fn, args, reps):
    import jax

    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(1e3 * (time.perf_counter() - t0))
    return statistics.median(times)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--heads", type=int, default=20)
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--dqk", type=int, default=256)
    ap.add_argument("--dv", type=int, default=256)
    ap.add_argument("--fwd", type=pairs,
                    default=pairs("512x512,1024x512,1024x1024,2048x1024"))
    ap.add_argument("--bwd", type=pairs,
                    default=pairs("256x256,512x256,256x512,512x512,"
                                  "1024x512,512x1024,1024x1024"))
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from dlrover_tpu.ops import pallas_attention as pa

    if args.tiny:
        args.heads, args.tokens, args.reps = 2, 256, 1
        args.fwd, args.bwd = pairs("128x128"), pairs("64x128")
    elif jax.default_backend() != "tpu":
        print("bench_flash_blocks: no TPU (times here would mean "
              "nothing); --tiny rehearses", file=sys.stderr)
        return 3
    shape = (args.dqk, args.dv)
    ks = jax.random.split(jax.random.key(0), 4)
    dims = (args.dqk, args.dqk, args.dv, args.dv)
    q, k, v, g = (
        jax.random.normal(
            key, (1, args.tokens, args.heads, d), jnp.bfloat16
        ) for key, d in zip(ks, dims)
    )

    def measure(label, kind, targets):
        kept = dict(pa.BLOCK_TARGETS)
        if targets is not None:
            pa.BLOCK_TARGETS[shape] = dict(
                pa.BLOCK_TARGETS.get(shape, {}), **{kind: targets}
            )
        line = {"candidate": label, "kind": kind, "shape": list(shape)}
        try:
            fwd = jax.jit(lambda q, k, v: pa.flash_attention(q, k, v))
            both = jax.jit(lambda q, k, v, g: jax.vjp(
                lambda q, k, v: pa.flash_attention(q, k, v), q, k, v
            )[1](g))
            line["fwd_ms"] = timed(fwd, (q, k, v), args.reps)
            if kind != "fwd":
                line["bwd_ms"] = (
                    timed(both, (q, k, v, g), args.reps) - line["fwd_ms"]
                )
        except Exception as e:  # noqa: BLE001 -- the compiler's refusal
            line["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        finally:
            pa.BLOCK_TARGETS.clear()
            pa.BLOCK_TARGETS.update(kept)
        print(json.dumps(line), flush=True)

    measure("default", "bwd", None)
    for t in args.fwd:
        measure("x".join(map(str, t)), "fwd", t)
    for t in args.bwd:
        measure("x".join(map(str, t)), "bwd", t)
    return 0


if __name__ == "__main__":
    sys.exit(main())
