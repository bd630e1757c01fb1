"""Controls of ``xing-serve-sessions-16k``'s ``correct``: the cell run
through the harness's own path (``run.cell_context`` ->
``runners/serve_latent.run`` -> ``run.result_line``) with one fault
planted in the PROGRAM, to show which of the cell's limits catches what
(the limits are in ``runners/serve_latent.py``; the words in brackets
open the problem line that has to appear).

    chiprun --timeout 3000 -- python3 benchmark/controls_xing.py [NAME ...]

- ``rope_unrotated``: the positional slices of queries and keys are not
  rotated. [``rows_err_max``]: the landed rows' ``k_r`` is not the
  reference's.
- ``rope_plain``: plain RoPE frequencies in place of YaRN's blend.
  [``rows_err_max``]: at 16k positions the stretched pairs have turned
  elsewhere.
- ``mscale_left_out``: the softmax scale without ``m^2``.
  [``decode_attn_err_max``], and the chunk's with it.
- ``scores_bf16``: the scores rounded to bfloat16 where they are formed.
  [``decode_scores_err_max``]: over ~16.5k keys attended almost
  uniformly attention's OUTPUT averages the rounding away, so the scores
  are read themselves.
- ``sinkhorn_one_round``: one round of row / column normalisation in
  place of 20. [``stochastic_err_max``], and both mixes.
- ``h_post_unscaled``: ``H_post = sigmoid`` without its factor 2.
  [``mix_err_max``].
- ``streams_averaged``: the maps replaced by constants (``H_pre`` =
  ``H_res`` = 1/n, ``H_post`` = 1): the n streams collapse into one plain
  residual; its ``H_res`` IS doubly stochastic. [``mix_err_max``].
- ``router_unnormalised``: the chosen experts' weights are not divided
  by their sum. [``weight_err_max``], and the MLP's output with them.
- ``router_bias_in_weights``: the selection bias is added to the weights
  as well. [``weight_err_max``].
- ``reference_lower_precision``: no fault in the program; the runner
  judges, on (b)'s and (c)'s yardsticks, the REFERENCE computed in the
  precision below the configuration's (float8 rows, attention and MLP
  operands, bfloat16 residual maps and router) in the program's place.
  [``rows_err_max``], and the attention and MLP outputs with it.

A control's window is 3 s, its sample two requests and its sessions 8
(the cell's: 30 s, four, 32: a control is read off its limit, and set-up
and the reference are most of a run's minutes). Each control is a child
process (a chip belongs to one process); the parent imports no JAX. A
line a control, then ``{"ok": ...}``: whether every control came out NOT
correct by the limit named for it. Exit code 1 if one did not. Not run by
the driver.
"""

import argparse
import contextlib
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

CELL = "xing-serve-sessions-16k"
# How the problem line that has to appear opens.
CAUGHT_BY = {
    "rope_unrotated": "rows_err_max",
    "rope_plain": "rows_err_max",
    "mscale_left_out": "decode_attn_err_max",
    "scores_bf16": "decode_scores_err_max",
    "sinkhorn_one_round": "stochastic_err_max",
    "h_post_unscaled": "mix_err_max",
    "streams_averaged": "mix_err_max",
    "router_unnormalised": "weight_err_max",
    "router_bias_in_weights": "weight_err_max",
    "reference_lower_precision": "rows_err_max",
}


def _rope_unrotated(runner):
    import jax.numpy as jnp

    from dlrover_tpu.models import latent_lm

    return [(latent_lm, "inv_frequencies",
             lambda c: jnp.zeros((c.qk_rope_dim // 2,), jnp.float32))]


def _rope_plain(runner):
    from dlrover_tpu.models import latent_lm
    from dlrover_tpu.ops import rope

    return [(latent_lm, "inv_frequencies",
             lambda c: rope.rope_frequencies(c.qk_rope_dim, c.rope_theta))]


def _mscale_left_out(runner):
    from dlrover_tpu.models import latent_lm

    return [(latent_lm.LatentLMConfig, "softmax_scale", property(
        lambda c: (c.qk_nope_dim + c.qk_rope_dim) ** -0.5
    ))]


def _scores_bf16(runner):
    """The scores rounded to bfloat16 where they are formed
    (``reduce_precision``: a bfloat16 ``preferred_element_type`` cast back
    to float32 is a pair of casts the TPU's compiler skips, and the
    program then came out correct: it WAS)."""
    import jax

    from dlrover_tpu.serving.kvpool import latent

    real = latent._scores
    return [(latent, "_scores", lambda *a: jax.lax.reduce_precision(
        real(*a), exponent_bits=8, mantissa_bits=7
    ))]


def _sinkhorn_one_round(runner):
    real = runner.latent_config
    return [(runner, "latent_config",
             lambda cfg_json, **kw: real(cfg_json, hc_sinkhorn_iters=1, **kw))]


def _maps_through(change):
    from dlrover_tpu.models import latent_lm

    real = latent_lm.mhc_maps
    return [(latent_lm, "mhc_maps", lambda *a: change(real(*a)))]


def _h_post_unscaled(runner):
    return _maps_through(lambda m: m._replace(post=m.post / 2.0))


def _streams_averaged(runner):
    import jax.numpy as jnp

    def constant(m):
        n = m.pre.shape[-1]
        return m._replace(
            pre=jnp.full_like(m.pre, 1.0 / n), post=jnp.ones_like(m.post),
            res=jnp.full_like(m.res, 1.0 / n),
        )

    return _maps_through(constant)


def _routed(weigh):
    """``moe.sigmoid_route`` with the chosen experts' weights made by
    ``weigh(chosen scores, chosen biases, scaling)``."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import moe

    def sigmoid_route(x, router_w, router_bias, top_k, scaling):
        scores = moe.router_scores(x, router_w)
        bias = router_bias.astype(jnp.float32)
        _, experts = jax.lax.top_k(scores + bias, top_k)
        chosen = jnp.take_along_axis(scores, experts, axis=-1)
        return experts.astype(jnp.int32), weigh(
            chosen, bias[experts], scaling
        )

    return [(moe, "sigmoid_route", sigmoid_route)]


def _router_unnormalised(runner):
    return _routed(lambda s, b, scaling: scaling * s)


def _router_bias_in_weights(runner):
    return _routed(
        lambda s, b, scaling:
        scaling * (s + b) / (s + b).sum(-1, keepdims=True)
    )


def _reference_lower_precision(runner):
    return [(runner, "JUDGED", "reference_lower_precision")]


PLANTS = {
    "rope_unrotated": _rope_unrotated,
    "rope_plain": _rope_plain,
    "mscale_left_out": _mscale_left_out,
    "scores_bf16": _scores_bf16,
    "sinkhorn_one_round": _sinkhorn_one_round,
    "h_post_unscaled": _h_post_unscaled,
    "streams_averaged": _streams_averaged,
    "router_unnormalised": _router_unnormalised,
    "router_bias_in_weights": _router_bias_in_weights,
    "reference_lower_precision": _reference_lower_precision,
}


@contextlib.contextmanager
def planted(name, runner):
    """``name``'s fault planted while the block runs (``runner``: the
    loaded ``runners/serve_latent`` module that will be run)."""
    patch = PLANTS[name](runner)
    kept = [(obj, attr, getattr(obj, attr)) for obj, attr, _ in patch]
    for obj, attr, value in patch:
        setattr(obj, attr, value)
    try:
        yield
    finally:
        for obj, attr, value in kept:
            setattr(obj, attr, value)


REPORTED = (
    "logit_deficit_median", "logit_deficit_p90", "logit_within_share",
    "rows_err_max", "decode_attn_err_max", "chunk_attn_err_max",
    "decode_scores_err_max", "low_decode_scores_err_min",
    "stochastic_err_max", "mix_err_max", "h_err_max", "mlp_err_median_max",
    "alike_share_min", "weight_err_max", "low_rows_err_min",
    "low_decode_attn_err_min", "low_chunk_attn_err_min", "low_mix_err_min",
    "low_mlp_err_median_min", "low_alike_share_min",
)


def run_control(name, ctx):
    """The control's line: the harness's verdict beside what was read."""
    from benchmark import common
    from benchmark import run as bench_run

    runner = bench_run.load_module("runners", ctx["traffic"]["runner"])
    with planted(name, runner):
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
    ctx["traffic"]["reference_sample"] = 2
    ctx["traffic"]["sessions"] = dict(ctx["traffic"]["sessions"], count=8)
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
