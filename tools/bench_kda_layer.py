"""One KDA layer on the device at hand: the fused kernels around the scan
against the ``jax.numpy`` lines.

``models/hybrid._kda_apply`` does a KDA layer's per-token work on either
side of the delta-rule scan (short convolutions, SiLU, the L2 and RMS
norms, the gates) in one of two forms, ``ops/kda.kda_scan_kind``'s
answer: the Pallas kernels of ``ops/kda_tail.py`` or plain ``jax.numpy``.
This probe times ONE layer's mixer, forward and forward + backward under
the cell's remat policy (``hybrid.remat_policy("dots")``), at
``kimilinear-train-8k``'s shape (1 x 8,192 tokens, width 2,304, 32 heads
x 128, gate rank 128, bfloat16 compute), the scan being the kernels in
every form:

    python tools/bench_kda_layer.py          # on the chip: chiprun -- ...

One JSON line a form (``--forms jnp,kernels``: the ``jax.numpy`` lines
and the kernels; ``--tile`` / ``--rows`` move the kernels' tile and
pass): ``fwd_ms``,
``fwd_bwd_ms``, and ``err``: the worst of the output's and every
gradient's distance from the ``jax.numpy`` form's, by norm (the two
differ by float32 rounding, 1e-6 or so, in what stays float32, and by
bfloat16's last place where a gradient is rounded to it on the way:
``d_h`` reads 2.3e-3 on the chip, two roundings apart). ``--profile`` adds
``kda_ops_ms``: the device's ms a call by (op kind, path under the
``kda`` scope), forward + backward, from a profiler session, the scan's
kernels under ``kda_scan/...``. Exits 1 if a form is not finite or more
than ``--tol`` off. A smoke reading, not a benchmark: one process,
host-clock timing around ``block_until_ready``. Times mean something on
a TPU only: anywhere else the tool refuses to run, unless ``--tiny``
rehearses it (interpret mode, toy width and length, nothing timed).
"""

import argparse
import functools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tools"))

CELL = dict(embed_dim=2304, kda_heads=32, kda_head_dim=128,
            kda_gate_rank=128, kda_conv=4, dtype="bfloat16")
TINY = dict(CELL, embed_dim=64, kda_heads=2, kda_gate_rank=16)


def _distance(jnp, got, want):
    flat = lambda x: jnp.ravel(x.astype(jnp.float32))  # noqa: E731
    got, want = flat(got), flat(want)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def main():
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--forms", default="jnp,kernels")
    ap.add_argument("--tile", type=int, default=None)
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--tol", type=float, default=5e-3)
    ap.add_argument("--repeats", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--profile", action="store_true",
                    help="add the device's ms a call by op under kda")
    ap.add_argument("--tiny", action="store_true",
                    help="rehearse off the chip: width 64, 2 heads, 200 "
                    "tokens, interpret mode, nothing timed")
    ns = ap.parse_args()
    if ns.tiny:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        ns.tokens, ns.repeats = 200, 0
        ns.tile, ns.rows = ns.tile or 128, ns.rows or 32

    import jax
    import jax.numpy as jnp

    import trace_query
    from bench_kda_scan import timed
    from benchmark import common, trace_reduce
    from dlrover_tpu.models import hybrid
    from dlrover_tpu.ops import kda, kda_tail

    if not ns.tiny and jax.default_backend() != "tpu":
        sys.exit(
            f"no TPU here ({jax.default_backend()}): the kernels would "
            "run interpreted and the times would mean nothing; "
            "--tiny rehearses the script without timing"
        )
    kda_tail.TILE = ns.tile or kda_tail.TILE
    kda_tail.ROWS = ns.rows or kda_tail.ROWS
    # The scan is the kernels in every form; what `_kda_apply` reads
    # from `kda_scan_kind` is steered a form.
    kda.kda_chunked = functools.partial(
        kda.kda_chunked_kernels, interpret=ns.tiny
    )
    if ns.tiny:
        for name in ("branch", "decay_gate", "gated_norm"):
            setattr(kda_tail, name, functools.partial(
                getattr(kda_tail, name), interpret=True
            ))
    config = hybrid.HybridLMConfig(**(TINY if ns.tiny else CELL))
    k_p, k_h, k_c = jax.random.split(jax.random.key(ns.seed), 3)
    params = hybrid.MIXERS["kda"].init(config, k_p)
    # Off their starting values, so that every gradient says something.
    params["o_norm"] = 0.1 * jax.random.normal(k_p, params["o_norm"].shape)
    shape = (1, ns.tokens, config.embed_dim)
    h = jax.random.normal(k_h, shape, jnp.float32).astype(config.dtype)
    cot = jax.random.normal(k_c, shape, jnp.float32).astype(config.dtype)

    def programs(form):
        kind = "xla" if form == "jnp" else "pallas"
        kda.kda_scan_kind = lambda dk, dv: kind
        def mixer(p, x):
            # As the model nests it: the forward's ops are then
            # ``jvp(attn)/kda/...`` and ``kda`` a component of its own.
            with jax.named_scope("attn"):
                return hybrid._kda_apply(config, p, x)

        layer = jax.checkpoint(mixer, policy=hybrid.remat_policy("dots"))

        def run(p, x, w):
            out, pull = jax.vjp(layer, p, x)
            return (out,) + pull(w)

        return jax.jit(layer), jax.jit(run)

    want, failed = None, False
    for form in ns.forms.split(","):
        fwd, run = programs(form)
        got = run(params, h, cot)
        names = ["out", "d_h"] + ["d_" + k for k in sorted(got[1])]
        leaves = [got[0], got[2]] + [got[1][k] for k in sorted(got[1])]
        if want is None:
            want = leaves               # the first form: ``jnp`` by default
        err = {
            n: _distance(jnp, a, b) for n, a, b in zip(names, leaves, want)
        }
        line = {
            "form": form, "tokens": ns.tokens, "tile": kda_tail.TILE,
            "rows": kda_tail.ROWS,
            "device": jax.devices()[0].device_kind,
            "err": err, "err_worst": max(err.values()),
            "fwd_ms": timed(jax, fwd, (params, h), ns.repeats),
            "fwd_bwd_ms": timed(jax, run, (params, h, cot), ns.repeats),
        }
        if ns.profile:
            prof = common.Profile(
                os.path.join(ROOT, "chiprun_out", "bench_kda_layer")
            )
            prof.start()
            for _ in range(3):
                jax.block_until_ready(run(params, h, cot))
            scopes = trace_reduce.scopes_from_hlo(
                run.lower(params, h, cot).compile().as_text()
            )
            table = trace_query.device_ops_by_scope(
                prof.stop(scopes) or {}, "jit_run", "kda"
            )
            line["kda_ms"] = round(table["ms_per_launch"], 3)
            line["kda_ops_ms"] = {
                f"{r['kind']} {r['under']}": round(r["ms_per_launch"], 3)
                for r in table["rows"]
            }
        if not line["err_worst"] <= ns.tol:      # a NaN fails too
            failed = True
        print(json.dumps(line), flush=True)
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
