"""Dropless MoE dispatch A/B on the device at hand: ``gmm`` vs ``fused``.

``models/moe.py`` computes the dropless expert MLP two ways: megablox
grouped matmuls around XLA gathers (``dispatch="gmm"``, the default)
and the ``ops/moe_dispatch`` Pallas kernel that gathers, multiplies and
scatters in one pass (``"fused"``). PR 21 made ``fused`` lower for the
v5e and re-defaulted to ``gmm`` on one reading of this probe; ROADMAP
S3 decides from it whether ``fused`` wins or goes.

One layer's ``moe_mlp_dropless`` forward + backward (grads of x and the
three expert weights) at the bench's MoE shape — 8 x 2048 tokens,
d = f = 1024, 8 experts, top-2 — with seeded random inputs:

    python tools/bench_moe_dispatch.py          # on the chip: chiprun -- ...

Prints one JSON line: device, compile seconds and the median of
``--repeats`` timed calls per dispatch, ``fused_over_gmm`` (> 1: fused
is slower), and the relative error of ``fused`` against ``gmm`` for the
output and each gradient. Exits 1 if anything is not finite or the two
disagree by more than ``REL_TOL``. A smoke reading, not a benchmark:
one process, one shape, host-clock timing around ``block_until_ready``.
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


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--repeats", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args(argv)
    out = run(repeats=a.repeats, seed=a.seed)
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
