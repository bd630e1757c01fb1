"""Controls of ``keye-serve-docqa-32k``'s ``correct``: the cell run
through the harness's own path (``run.cell_context`` ->
``runners/serve_sparse.run`` -> ``run.result_line``) with one fault
planted in the PROGRAM, to show which of the cell's limits catches what
(the limits are in ``runners/serve_sparse.py``; the words in brackets
open the problem line that has to appear).

    chiprun --timeout 3000 -- python3 benchmark/controls_keye.py [--seed N] [NAME ...]

- ``dense_attention``: the selection keeps every visible row (full
  causal attention). [``keys_wrong``]: a probed query holds ~33k keys,
  not 2,048, most of them outside the reference's top-(2,048 + margin).
- ``index_scores_bf16``: the index scores are formed and summed in
  bfloat16 where the configuration assumes float32. [``score_err_max``]:
  the program's scores against float32 on the SAME index queries and
  keys. (The selection itself barely moves, about one key in 2,048,
  because queries and keys are bfloat16 already; that is why the
  arithmetic is held and not only its outcome.)
- ``router_unnormalised``: the router's top-8 weights are not
  renormalised (they sum to ~0.2, not 1). [``weight_err_max``], and the
  expert half's output with them.
- ``index_keys_unshared``: a prefix hit shares K and V but not the index
  keys (the hit blocks' index rows read zero in every layer).
  [``share_wide_min``]: the probe chain reads the engine's live pool.
- ``index_keys_one_layer``: the same in ONE layer past the first, drawn
  from the seed (a wrong layer offset into the stacked pool, or a
  layer's index keys mis-landed, looks like this). [``share_wide_min``]:
  every layer is probed, each on its own inputs.
- ``reference_lower_precision``: no fault in the program; the runner
  judges, on (b)'s yardsticks, the REFERENCE computed in the precision
  below the configuration's (bfloat16 index scores and router, float8
  attention and expert operands) in the program's place.
  [``score_err_max``], and the attention and expert outputs with it.

A control's window is 3 s and its sample two requests (the cell's: 30
s, four). Each control is a child process (a chip belongs to one process); the
parent imports no JAX. A line a control, then ``{"ok": ...}``: whether
every control came out NOT correct by the limit named for it. Exit
code 1 if one did not. Not run by the driver.
"""

import argparse
import contextlib
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

CELL = "keye-serve-docqa-32k"
# How the problem line that has to appear opens.
CAUGHT_BY = {
    "dense_attention": "keys_wrong",
    "index_scores_bf16": "score_err_max",
    "router_unnormalised": "weight_err_max",
    "index_keys_unshared": "share_wide_min",
    "index_keys_one_layer": "share_wide_min",
    "reference_lower_precision": "score_err_max",
}


def _dense_attention(seed, runner):
    from dlrover_tpu.ops import sparse_attention as sa

    real = sa.select_indices
    return [
        (sa, "select_mask", lambda scores, visible, topk: visible),
        (sa, "select_indices",
         lambda scores, visible, topk: real(
             scores, visible, scores.shape[-1]
         )),
    ]


def _index_scores_bf16(seed, runner):
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.ops import sparse_attention as sa

    def index_scores(q_idx, w, k_idx):
        hi, di = q_idx.shape[-2:]
        bf = jnp.bfloat16
        dots = jnp.einsum(
            "...qhd,...sd->...qhs", q_idx.astype(bf), k_idx.astype(bf),
            preferred_element_type=bf,
        )
        scores = jnp.einsum(
            "...qhs,...qh->...qs", jax.nn.relu(dots), w.astype(bf),
            preferred_element_type=bf,
        )
        return (scores * bf(di ** -0.5 * hi ** -0.5)).astype(jnp.float32)

    return [(sa, "index_scores", index_scores)]


def _router_unnormalised(seed, runner):
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import moe

    def softmax_route(x, router_w, top_k):
        probs = jax.nn.softmax(jnp.einsum(
            "nd,de->ne", x.astype(jnp.float32),
            router_w.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        ), axis=-1)
        chosen, experts = jax.lax.top_k(probs, top_k)
        return experts.astype(jnp.int32), chosen

    return [(moe, "softmax_route", softmax_route)]


def _index_keys_zeroed(layer_of):
    """A prefix hit whose index keys read zero in ``layer_of(n_layers)``
    (a slice: every layer)."""
    import numpy as np

    from dlrover_tpu.serving.kvpool import engine as paged

    real = paged.PagedServingEngine._admit_slot

    def admit(self, req):
        real(self, req)
        hit = self._slot_blocks[req.slot]
        if hit and self._ki is not None:
            layer = layer_of(self._ki.shape[0])
            self._ki = self._ki.at[layer, np.asarray(hit)].set(0)

    return [(paged.PagedServingEngine, "_admit_slot", admit)]


def _index_keys_unshared(seed, runner):
    return _index_keys_zeroed(lambda n: slice(None))


def _index_keys_one_layer(seed, runner):
    return _index_keys_zeroed(lambda n: 1 + seed % (n - 1))


def _reference_lower_precision(seed, runner):
    return [(runner, "JUDGED", "reference_lower_precision")]


PLANTS = {
    "dense_attention": _dense_attention,
    "index_scores_bf16": _index_scores_bf16,
    "router_unnormalised": _router_unnormalised,
    "index_keys_unshared": _index_keys_unshared,
    "index_keys_one_layer": _index_keys_one_layer,
    "reference_lower_precision": _reference_lower_precision,
}


@contextlib.contextmanager
def planted(name, runner, seed=0):
    """``name``'s fault planted while the block runs (``runner``: the
    loaded ``runners/serve_sparse`` module that will be run)."""
    patch = PLANTS[name](seed, runner)
    kept = [(obj, attr, getattr(obj, attr)) for obj, attr, _ in patch]
    for obj, attr, value in patch:
        setattr(obj, attr, value)
    try:
        yield
    finally:
        for obj, attr, value in kept:
            setattr(obj, attr, value)


REPORTED = (
    "keys_wrong", "keys_min", "score_err_max", "share_wide_min",
    "share_wide_min_by_layer", "share_exact_mean_by_layer", "attn_err_first_max", "attn_err_max",
    "alike_share", "swap_gap_max", "weight_err_max", "y_err_max",
    "tracked_share", "program_deficit_tracked_max",
    "free_deficit_alike_max", "free_deficit_flipped_max", "n_alike_free",
    "low_score_err_max", "low_share_wide_min", "low_attn_err_first_min",
    "low_alike_share", "low_swap_gap_max", "low_y_err_min",
)


def run_control(name, ctx):
    """The control's line: the harness's verdict beside what was read."""
    from benchmark import common
    from benchmark import run as bench_run

    runner = bench_run.load_module("runners", ctx["traffic"]["runner"])
    with planted(name, runner, ctx["seed"]):
        facts = runner.run(ctx)
    line, problems = bench_run.result_line(
        common.load_manifest(), ctx, facts
    )
    ref = facts["reference"]
    return {
        "control": name, "seed": ctx["seed"], "correct": line["correct"],
        "caught_by": CAUGHT_BY[name],
        "caught": any(p.startswith(CAUGHT_BY[name]) for p in problems),
        "problems": problems,
        **{k: ref.get(k) for k in REPORTED},
        "prefix_hit_share": facts["prefix"]["hit_share"],
        "serve_tokens_per_s": facts["end_to_end"]["serve_tokens_per_s"],
    }


def child(name, seed, seconds):
    from benchmark import common
    from benchmark import run as bench_run

    ctx = bench_run.cell_context(
        common.load_manifest(), CELL, seed, seconds, 0
    )
    # two requests through the reference, not four: a control is read
    # off its limit, and the reference is most of a run's minutes
    ctx["traffic"]["reference_sample"] = 2
    ctx["out_dir"] = os.path.join(ctx["out_dir"], "controls", name)
    os.makedirs(ctx["out_dir"], exist_ok=True)
    events = os.path.join(ctx["out_dir"], "events.jsonl")
    if os.path.exists(events):
        os.unlink(events)
    line = run_control(name, ctx)
    with open(os.path.join(ctx["out_dir"], "control.json"), "w") as f:
        json.dump(line, f, indent=1)
    print(json.dumps(line), flush=True)
    return 0 if (not line["correct"] and line["caught"]) else 1


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
