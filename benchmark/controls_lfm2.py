"""Controls of ``lfm2-serve-sessions-8k``'s ``correct``: the cell run
through the harness's own path (``run.cell_context`` ->
``runners/serve_conv.run`` -> ``run.result_line``) with one fault planted
in the PROGRAM, to show which of the cell's limits catches what (the
limits are in ``runners/serve_conv.py``; the words in brackets open the
problem line that has to appear).

    chiprun --timeout 3000 -- python3 benchmark/controls_lfm2.py [NAME ...]

- ``hit_zero_state``: a prefix hit's restore copies nothing: the slot
  starts its turn from zeros (the engine's counters still say it
  restored). [``rows_after_hit_err_median``]: only the first ``taps - 1``
  rows after the boundary see the state, so only the K and V rows landed
  right after a hit show it.
- ``snapshot_one_row_late``: a chunk writes its snapshot one row past
  the block boundary. [``snapshot_err_median``]: the snapshot at each
  probed prompt's last block boundary is read out of the cache.
- ``gate_c_left_out``: the convolution's output is not gated by ``C``.
  [``conv_err_median_max``].
- ``taps_reversed``: the filter's taps run newest first.
  [``conv_err_median_max``].
- ``last_conv_taps_reversed``: the same in the LAST convolution layer
  alone, every layer below it whole: what a fault deep in the stack reads
  on each number, the free-running logits among them.
  [``conv_err_median_max``]: the worst layer's.
- ``qk_norm_skipped``: queries and keys are not normed a head.
  [``k_rows_err_median``]: the landed K rows are not the reference's.
- ``rope_skipped``: queries and keys are not rotated.
  [``k_rows_err_median``].
- ``router_unnormalised``: the chosen experts' weights are not divided
  by their sum. [``weight_err_median``], and the FFN's output with them.
- ``reference_lower_precision``: no fault in the program; the runner
  judges, on (b)'s and (c)'s yardsticks, the REFERENCE computed in the
  precision below the configuration's (float8 rows, state, mixer and FFN
  operands, a bfloat16 router) in the program's place.
  [``k_rows_err_median``], and the mixers' and FFN's outputs with it.

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

CELL = "lfm2-serve-sessions-8k"
# How the problem line that has to appear opens.
CAUGHT_BY = {
    "hit_zero_state": "rows_after_hit_err_median",
    "snapshot_one_row_late": "snapshot_err_median",
    "gate_c_left_out": "conv_err_median_max",
    "taps_reversed": "conv_err_median_max",
    "last_conv_taps_reversed": "conv_err_median_max",
    "qk_norm_skipped": "k_rows_err_median",
    "rope_skipped": "k_rows_err_median",
    "router_unnormalised": "weight_err_median",
    "reference_lower_precision": "k_rows_err_median",
}


def _hit_zero_state(runner):
    import numpy as np

    from dlrover_tpu.serving.kvpool import engine as paged

    real = paged._state_steps

    def steps(n_state):
        s = real(n_state)
        return s._replace(
            restore=lambda *a: s.restore(*a[:-1], np.int32(0))
        )

    return [(paged, "_state_steps", steps)]


def _snapshot_one_row_late(runner):
    import numpy as np

    from dlrover_tpu.serving.kvpool import PagedServingEngine

    real = PagedServingEngine._chunk_state_args

    def late(self, req, start, n_valid):
        slot, snap_at, snap_id = real(self, req, start, n_valid)
        return slot, np.int32(int(snap_at) + bool(snap_id)), snap_id

    return [(PagedServingEngine, "_chunk_state_args", late)]


def _conv_mix_with(gate_c=True, reverse=False):
    """``conv_lm.conv_mix`` with the ``C`` gate left out or the taps
    reversed."""
    import jax.numpy as jnp

    from dlrover_tpu.models import conv_lm

    def conv_mix(config, pc, u, state, taps=None):
        cdt, d, k = config.compute_dtype, config.embed_dim, config.conv_taps
        s = u.shape[1]
        bcx = jnp.einsum("bsd,de->bse", u, pc["w_in"].astype(cdt))
        gate_b, gate_c_, x = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
        z = gate_b * x
        zz = jnp.concatenate([state.astype(cdt), z], axis=1)
        w = pc["filter"].astype(jnp.float32)
        w = w[::-1] if reverse else w
        conv = sum(
            w[j] * zz[:, j:j + s].astype(jnp.float32) for j in range(k)
        )
        if gate_c:
            conv = gate_c_.astype(jnp.float32) * conv
        y = jnp.einsum(
            "bsd,de->bse", conv.astype(cdt), pc["w_out"].astype(cdt)
        )
        return y, zz

    return [(conv_lm, "conv_mix", conv_mix)]


def _gate_c_left_out(runner):
    return _conv_mix_with(gate_c=False)


def _taps_reversed(runner):
    return _conv_mix_with(reverse=True)


def _last_conv_taps_reversed(runner):
    from dlrover_tpu.models import conv_lm

    real_block, real_mix = conv_lm.block, conv_lm.conv_mix
    (_, _, reversed_mix), = _conv_mix_with(reverse=True)
    inside = []     # the layer whose block is being traced

    def block(config, params, layer, *args, **kwargs):
        inside.append(layer == config.conv_layers[-1])
        try:
            return real_block(config, params, layer, *args, **kwargs)
        finally:
            inside.pop()

    def conv_mix(*args, **kwargs):
        return (reversed_mix if inside[-1] else real_mix)(*args, **kwargs)

    return [(conv_lm, "block", block), (conv_lm, "conv_mix", conv_mix)]


def _qk_norm_skipped(runner):
    import jax.numpy as jnp

    from dlrover_tpu.models import conv_lm
    from dlrover_tpu.ops import rope

    def gqa_inputs(config, pa, u, positions):
        cdt = config.compute_dtype
        q = jnp.einsum("bsd,dhk->bshk", u, pa["wq"].astype(cdt))
        k = jnp.einsum("bsd,dhk->bshk", u, pa["wk"].astype(cdt))
        v = jnp.einsum("bsd,dhk->bshk", u, pa["wv"].astype(cdt))
        return (rope.apply_rope(q, positions, config.rope_theta),
                rope.apply_rope(k, positions, config.rope_theta), v)

    return [(conv_lm, "gqa_inputs", gqa_inputs)]


def _rope_skipped(runner):
    from dlrover_tpu.ops import rope

    return [(rope, "apply_rope", lambda x, *a, **kw: x)]


def _router_unnormalised(runner):
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import moe

    def sigmoid_route(x, router_w, router_bias, top_k, scaling):
        scores = moe.router_scores(x, router_w)
        _, experts = jax.lax.top_k(
            scores + router_bias.astype(jnp.float32), top_k
        )
        chosen = jnp.take_along_axis(scores, experts, axis=-1)
        return experts.astype(jnp.int32), scaling * chosen

    return [(moe, "sigmoid_route", sigmoid_route)]


def _reference_lower_precision(runner):
    return [(runner, "JUDGED", "reference_lower_precision")]


PLANTS = {
    "hit_zero_state": _hit_zero_state,
    "snapshot_one_row_late": _snapshot_one_row_late,
    "gate_c_left_out": _gate_c_left_out,
    "taps_reversed": _taps_reversed,
    "last_conv_taps_reversed": _last_conv_taps_reversed,
    "qk_norm_skipped": _qk_norm_skipped,
    "rope_skipped": _rope_skipped,
    "router_unnormalised": _router_unnormalised,
    "reference_lower_precision": _reference_lower_precision,
}


@contextlib.contextmanager
def planted(name, runner):
    """``name``'s fault planted while the block runs (``runner``: the
    loaded ``runners/serve_conv`` module that will be run)."""
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
    "route_flip_row_share", "logit_within_share_unflipped",
    "logit_within_share_flipped", "low_logit_within_share",
    "low_logit_deficit_median", "state_err_all_layers_median",
    "low_state_err_all_layers_median", "snapshot_err_all_layers_median",
    "low_snapshot_err_all_layers_median",
    "rows_after_hit_err_median_by_layer",
    "low_rows_after_hit_err_median_by_layer",
    "state_err_median", "snapshot_err_median", "k_rows_err_median",
    "v_rows_err_median",
    "rows_after_hit_err_median", "conv_err_median_max",
    "attn_err_median_max", "h_err_median_max", "mlp_err_median_max",
    "alike_share_min", "weight_err_median", "low_state_err_median",
    "low_k_rows_err_median", "low_v_rows_err_median",
    "low_conv_err_median_min",
    "low_attn_err_median_min", "low_mlp_err_median_min",
    "low_alike_share_min",
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
