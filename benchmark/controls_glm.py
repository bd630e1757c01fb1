"""Controls of ``glm47flash-train-8k``'s ``correct``: the cell run through
the harness's own path (``run.cell_context`` -> ``runners/train_latent.run``
-> ``run.result_line``) with one thing planted in the REFERENCE, to show
what the cell's limits (first step's main loss, its module loss, its
gradient leaf by leaf) tell apart. The sound program must come out NOT
correct under every one of them.

    chiprun --timeout 3000 -- python3 benchmark/controls_glm.py [--seed N] [NAME ...]

- ``mtp_weight_zero``: the module's loss weighs nothing in the
  reference's gradient (a program that dropped the second loss).
- ``unrotated``: the positional slices of q and of the shared key are
  left as projected.
- ``q_without_norm``: the queries' bottleneck skips its RMSNorm.
- ``router_unnormalised``: the router does not renormalise its top-k
  weights.
- ``head_second_gradient_dropped``: the head's gradient from the
  module's loss is left out (an ``lm_head`` that collects from the main
  loss alone).
- ``matmuls_fp8``: every matmul's operands rounded to float8 (e4m3),
  the nearest precision below the bfloat16 the configuration states.

Each control is a child process (a chip belongs to one process); the
parent imports no JAX. A line a control, then ``{"ok": ...}``: whether
every control came out NOT correct. Exit code 1 if one did not.
"""

import argparse
import contextlib
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

CELL = "glm47flash-train-8k"


def _mtp_weight_zero(ref):
    real = ref.batch_loss_and_grads

    def walk(params, buffers, batch_tokens, spec):
        return real(params, buffers, batch_tokens, dict(spec, mtp_weight=0.0))

    return {"batch_loss_and_grads": walk}


def _unrotated(ref):
    return {"rotate": lambda x, theta: x}


def _q_without_norm(ref):
    def queries(p, x):
        return ref.einsum(
            "sr,rhk->shk", ref.matmul(x, p["w_qa"]), p["w_qb"]
        )

    return {"queries": queries}


def _router_unnormalised(ref):
    import jax
    import jax.numpy as jnp

    def route(p, bias, x, spec):
        scores = jax.nn.sigmoid(ref.matmul(x, p["router"]))
        _, chosen = jax.lax.top_k(scores + bias, spec["top_k"])
        return chosen, spec["routed_scaling"] * jnp.take_along_axis(
            scores, chosen, axis=-1
        )

    return {"route": route}


def _head_second_gradient_dropped(ref):
    return {"head_gradient": lambda from_main, from_module: from_main}


def _matmuls_fp8(ref):
    """Both operands of every matrix product of the reference rounded to
    float8_e4m3fn (values and accumulation still float32)."""
    import jax.numpy as jnp

    def fp8(a):
        return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    return {
        "matmul": lambda a, b: jnp.matmul(fp8(a), fp8(b)),
        "einsum": lambda spec, a, b: jnp.einsum(spec, fp8(a), fp8(b)),
    }


PLANTS = {
    "mtp_weight_zero": _mtp_weight_zero,
    "unrotated": _unrotated,
    "q_without_norm": _q_without_norm,
    "router_unnormalised": _router_unnormalised,
    "head_second_gradient_dropped": _head_second_gradient_dropped,
    "matmuls_fp8": _matmuls_fp8,
}


@contextlib.contextmanager
def planted(name):
    """``reference_glm`` with control ``name`` planted."""
    from benchmark import reference_glm as ref

    patch = PLANTS[name](ref)
    kept = {k: getattr(ref, k) for k in patch}
    for k, v in patch.items():
        setattr(ref, k, v)
    try:
        yield
    finally:
        for k, v in kept.items():
            setattr(ref, k, v)


def run_control(name, ctx):
    """The control's line: the harness's verdict beside what was read."""
    from benchmark import common
    from benchmark import run as bench_run

    runner = bench_run.load_module("runners", ctx["traffic"]["runner"])
    with planted(name):
        facts = runner.run(ctx)
    manifest = common.load_manifest()
    line, problems = bench_run.result_line(manifest, ctx, facts)
    grad = common.by_event(facts["events"], "gradient")[0]
    warm = common.by_event(facts["events"], "warm")[0]
    routed = runner.train_hybrid.routed_leaves(grad["errors"])
    plain = set(grad["errors"]) - routed - {"all"}
    worst = lambda keys: max(  # noqa: E731
        ([k, grad["errors"][k]] for k in keys), key=lambda kv: kv[1]
    )
    return {
        "control": name, "seed": ctx["seed"],
        "correct": line["correct"], "problems": problems,
        "losses": warm["first_losses"],
        "reference_losses": warm["reference_losses"],
        "gradient_worst_plain": worst(plain),
        "gradient_worst_routed": worst(routed),
        "gradient_all": grad["errors"]["all"],
        "train_tokens_per_s": facts["end_to_end"]["train_tokens_per_s"],
    }


def child(name, seed, seconds):
    from benchmark import common
    from benchmark import run as bench_run

    ctx = bench_run.cell_context(
        common.load_manifest(), CELL, seed, seconds, 0
    )
    ctx["out_dir"] = os.path.join(ctx["out_dir"], "controls", name)
    os.makedirs(ctx["out_dir"], exist_ok=True)
    events = os.path.join(ctx["out_dir"], "events.jsonl")
    if os.path.exists(events):
        os.unlink(events)
    line = run_control(name, ctx)
    with open(os.path.join(ctx["out_dir"], "control.json"), "w") as f:
        json.dump(line, f, indent=1)
    print(json.dumps(line), flush=True)
    return 1 if line["correct"] else 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("names", nargs="*", default=list(PLANTS))
    ap.add_argument("--seed", type=int, default=2147483693)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--child", action="store_true")
    args = ap.parse_args(argv)
    if args.child:
        (name,) = args.names
        return child(name, args.seed, args.seconds)
    failed = []
    for i, name in enumerate(args.names):
        rc = subprocess.call([
            sys.executable, os.path.abspath(__file__), "--child", name,
            "--seed", str(args.seed + i), "--seconds", str(args.seconds),
        ])
        if rc:
            failed.append(name)
    print(json.dumps({"ok": not failed, "came_out_correct": failed}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
