"""The chunked delta-rule scan on the device at hand: kernels vs XLA.

``ops/kda.kda_chunked`` runs one of two forms (``kda_scan_kind``): the
Pallas kernels of ``ops/kda_kernels.py``, a chunk's algebra in VMEM, or
the ``jax.numpy`` form whose chunk tensors cross HBM between XLA's
fusions. This probe holds them against each other on ONE layer's scan
at the ``kimilinear-train-8k`` cell's shape (32 heads, 8,192 tokens,
head size 128, float32):

    python tools/bench_kda_scan.py          # on the chip: chiprun -- ...

One JSON line a form (``--forms pallas,xla``): ``fwd_ms`` (the output
alone), ``fwd_bwd_ms`` (output and the five gradients; the kernels also
``bwd_kernel_ms``, the backward kernel by itself), and ``err``: the
worst of ``o, dq, dk, dv, dg, dbeta`` against the token-by-token float32
recurrence, by norm, on the first ``--check-tokens`` tokens. Inputs are
made as the layer makes them (unit ``q`` and ``k``, gates ``-softplus``
times a per-head rate, ``beta`` a sigmoid). Exits 1 if a form is not
finite or more than ``--tol`` off. A smoke reading, not a benchmark: one
process, host-clock timing around ``block_until_ready``. Times mean
something on a TPU only: anywhere else the tool refuses to run, unless
``--tiny`` rehearses it (interpret mode, two heads, nothing timed).
"""

import argparse
import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

NAMES = ("o", "dq", "dk", "dv", "dg", "dbeta")


def inputs(jax, jnp, key, heads, tokens, dim):
    """(q, k, v, g, beta) ``[1, heads, tokens, ...]`` and a cotangent."""
    ks = jax.random.split(key, 7)
    shape = (1, heads, tokens, dim)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], shape)) * dim ** -0.5
    k = unit(jax.random.normal(ks[1], shape))
    v = jax.random.normal(ks[2], shape)
    # A head's rate, e^-4 .. e^1 a token at the most: slow heads and
    # heads that forget a sub-chunk's worth in one token.
    rate = jnp.exp(
        jax.random.uniform(ks[3], (1, heads, 1, 1), minval=-4, maxval=1)
    )
    g = -rate * jax.nn.softplus(jax.random.normal(ks[4], shape))
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], shape[:3]))
    return (q, k, v, g, beta), jax.random.normal(ks[6], shape)


def timed(jax, fn, args, repeats):
    """Median ms of ``fn(*args)``, compiled and warmed first."""
    jax.block_until_ready(fn(*args))
    if not repeats:
        return None
    times = []
    for _ in range(repeats):
        start = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(1e3 * (time.perf_counter() - start))
    return statistics.median(times)


def main():
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--forms", default="pallas,xla")
    ap.add_argument("--check-tokens", type=int, default=1024)
    ap.add_argument("--tol", type=float, default=5e-4)
    ap.add_argument("--repeats", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="rehearse off the chip: 2 heads, 200 tokens, "
                    "interpret mode, nothing timed")
    ns = ap.parse_args()
    if ns.tiny:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        ns.heads, ns.tokens, ns.check_tokens, ns.repeats = 2, 200, 200, 0

    import jax
    import jax.numpy as jnp

    from dlrover_tpu.ops import kda, kda_kernels

    if not ns.tiny and jax.default_backend() != "tpu":
        sys.exit(
            f"no TPU here ({jax.default_backend()}): the kernels would "
            "run interpreted and the times would mean nothing; "
            "--tiny rehearses the script without timing"
        )
    dim = kda_kernels.LANES
    args, cot = inputs(
        jax, jnp, jax.random.key(ns.seed), ns.heads, ns.tokens, dim
    )
    forms = {
        "pallas": lambda *xs: kda.kda_chunked_kernels(
            *xs, interpret=ns.tiny
        ),
        "xla": kda.kda_chunked_xla,
    }

    def pulled(fn):
        def run(xs, cot):
            out, pull = jax.vjp(fn, *xs)
            return (out,) + pull(cot)
        return jax.jit(run)

    short = tuple(x[:, :, :ns.check_tokens] for x in args)
    short_cot = cot[:, :, :ns.check_tokens]
    want = pulled(kda.kda_recurrent)(short, short_cot)
    failed = False
    for name in ns.forms.split(","):
        fn = forms[name]
        got = pulled(fn)(short, short_cot)
        err = {
            n: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
            for n, a, b in zip(NAMES, got, want)
        }
        line = {
            "form": name, "heads": ns.heads, "tokens": ns.tokens,
            "device": jax.devices()[0].device_kind,
            "err": err, "err_worst": max(err.values()),
            "fwd_ms": timed(jax, jax.jit(fn), args, ns.repeats),
            "fwd_bwd_ms": timed(jax, pulled(fn), (args, cot), ns.repeats),
        }
        if name == "pallas":
            pad = -ns.tokens % kda_kernels.CHUNK
            q, k, v, g, beta, d_o = (
                jnp.pad(
                    x, [(0, 0), (0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 3)
                ) for x in args + (cot,)
            )
            states = kda_kernels.scan_forward(
                q, k, g, v, beta, interpret=ns.tiny
            )[1]
            line["bwd_kernel_ms"] = timed(
                jax, functools.partial(
                    kda_kernels.scan_backward, interpret=ns.tiny
                ), (q, k, g, v, beta, states, d_o), ns.repeats,
            )
        if not line["err_worst"] <= ns.tol:      # a NaN fails too
            failed = True
        print(json.dumps(line), flush=True)
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
