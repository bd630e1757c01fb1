"""Controls of ``kimilinear-train-8k``'s ``correct``: the cell run through
the harness's own path (``run.cell_context`` -> ``runners/train_hybrid.run``
-> ``run.result_line``) with one thing planted, to show what the cell's
limits (first-step loss, first-step gradient, the chunked scan against
the recurrence) tell apart.

    chiprun -- python3 benchmark/controls_kimi_linear.py [--seed N] [NAME ...]

- ``kda_scan_bf16``: the REFERENCE carries KDA's recurrence in bfloat16
  (inputs, state and outputs rounded a token a step, forward and
  backward) where the configuration states float32: the nearest
  precision below. The sound program must then come out NOT correct.
- ``router_unnormalised``: the reference's router does not renormalise
  its top-k weights. NOT correct.
- ``skewed_router``: sound, but the set-up's bias sends every token to
  the 8 held experts, 8 times an even share's rows: ``moe_mlp_share``
  takes its full row buffer. Correct, and no row dropped.

Each control is a child process (a chip belongs to one process); the
parent imports no JAX. A line a control, then ``{"ok": ...}``: whether
every control came out as expected. Exit code 1 if one did not.
"""

import argparse
import contextlib
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

CELL = "kimilinear-train-8k"
EXPECT_CORRECT = {
    "kda_scan_bf16": False, "router_unnormalised": False,
    "skewed_router": True,
}


def _kda_scan_bf16(ref):
    import jax
    import jax.numpy as jnp

    real = ref._scan_in_blocks

    def rounded(tree):
        return jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), tree
        )

    def scan(token, state, xs):
        def step(carry, x):
            carry, out = token(carry, x)
            return rounded(carry), rounded(out)

        return real(step, rounded(state), rounded(xs))

    return {"_scan_in_blocks": scan}


def _router_unnormalised(ref):
    import jax
    import jax.numpy as jnp

    def experts(p, bias, x, spec):
        scores = jax.nn.sigmoid(x @ p["router"])
        _, chosen = jax.lax.top_k(scores + bias, spec["top_k"])
        weights = spec["routed_scaling"] * jnp.take_along_axis(
            scores, chosen, axis=-1
        )
        out = ref.swiglu(p["shared"], x)
        for j in range(p["w_gate"].shape[0]):
            expert = {k: p[k][j] for k in ("w_gate", "w_up", "w_down")}
            w = jnp.sum(jnp.where(
                chosen == spec["first_expert"] + j, weights, 0.0
            ), -1)
            out = out + w[:, None] * ref.swiglu(expert, x)
        return out

    return {"experts": experts}


def _skewed_router(ref):
    import jax
    import jax.numpy as jnp

    def bias(params, buffers, batch_tokens, spec):
        """+4 (scores lie in (0, 1)) on the held experts: every token's
        top-k is the held block."""
        held = params["period"][0]["ffn"]["w_gate"].shape[1]

        def skew(b):
            ids = jnp.arange(b.shape[-1])
            at = (ids >= spec["first_expert"]) & (
                ids < spec["first_expert"] + held
            )
            return jnp.broadcast_to(jnp.where(at, 4.0, 0.0), b.shape)

        return jax.tree_util.tree_map(skew, buffers)

    return {"balanced_bias": bias}


PLANTS = {
    "kda_scan_bf16": _kda_scan_bf16,
    "router_unnormalised": _router_unnormalised,
    "skewed_router": _skewed_router,
}


@contextlib.contextmanager
def planted(name):
    """``reference_kimi_linear`` with control ``name`` planted."""
    from benchmark import reference_kimi_linear as ref

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
    worst = max(grad["errors"], key=grad["errors"].get)
    scan = common.by_event(facts["events"], "scan")[0]["errors"]
    scan_worst = max(scan, key=scan.get)
    return {
        "control": name, "seed": ctx["seed"],
        "expected_correct": EXPECT_CORRECT[name],
        "correct": line["correct"], "problems": problems,
        "loss": warm["losses"][0], "reference_loss": warm["reference_loss"],
        "gradient_worst": [worst, grad["errors"][worst]],
        "gradient_all": grad["errors"]["all"],
        "scan_worst": [scan_worst, scan[scan_worst]],
        "moe_rows_held_a_step": facts["counters"]["moe_rows_held"][-1],
        "moe_rows_dropped": sum(facts["counters"]["moe_rows_dropped"]),
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
    return 0 if line["correct"] == line["expected_correct"] else 1


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("names", nargs="*", default=list(PLANTS))
    ap.add_argument("--seed", type=int, default=2147483659)
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
    print(json.dumps({"ok": not failed, "not_as_expected": failed}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
