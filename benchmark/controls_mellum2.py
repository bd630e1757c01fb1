"""Controls of ``mellum2-serve-mixed-16k``'s ``correct``: the cell run
through the harness's own path (``run.cell_context`` ->
``runners/serve_window.run`` -> ``run.result_line``) with one fault
planted in the PROGRAM, to show which of the cell's limits catches what
(the limits are in ``runners/serve_window.py``; the words in brackets
open the problem line that has to appear).

    chiprun --timeout 3000 -- python3 benchmark/controls_mellum2.py [NAME ...]

- ``window_one_row_wide``: a sliding layer's query sees 1,024 rows below
  it instead of 1,023 (both programs, both forms). One row in 1,024 of
  near-uniform weights moves the layer's output by about sqrt(1/1024) =
  3 %. [``window_attn_err_median``]: the first window layer's attention
  at each probed slot's next row, against the reference's band.
- ``window_ignored``: the band's lower bound is a block too low: 63 rows
  below the band are read (what a kernel that copies the band's pages
  and masks nothing below its first row reads, at worst).
  [``window_attn_err_median``].
- ``released_too_early``: the rule of release drops a block a block's
  rows early (at ``fill - 959``): the band's oldest block reads the
  sentinel. [``band_blocks``]: a probed slot lacks a block inside its
  band (and ``rows_bad_share``: the rows read back are the sink's).
- ``yarn_on_window_layers``: the sliding layers rotate by YaRN's
  frequencies and factor too. [``window_rows_err_median``]: the landed K
  rows of the first window layer are not the reference's.
- ``yarn_skipped``: the full layers rotate by the plain frequencies, no
  factor. [``full_rows_err_median``].
- ``attention_factor_dropped``: the full layers rotate by YaRN's
  frequencies but cosine and sine are not multiplied by
  ``attention_factor``. [``full_rows_err_median``]: K is 22 % short.
- ``rotations_swapped``: sliding layers by YaRN, full layers plain.
  [``window_rows_err_median``].
- ``router_unnormalised``: the chosen experts' weights are not divided
  by their sum. [``weight_err_median``].
- ``reference_lower_precision``: no fault in the program; the runner
  judges, on (a)'s, (b)'s and (c)'s yardsticks, the REFERENCE computed
  in the precision below the configuration's (3 bits of mantissa) in the
  program's place. [``window_rows_err_median``], and the others with it.

A control's window is 3 s and its sample two requests, one of them long
(the cell's: 30 s, four: a control is read off its limit, and set-up and
the reference are most of a run's minutes). Each control is a child
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

CELL = "mellum2-serve-mixed-16k"
# How the problem line that has to appear opens.
CAUGHT_BY = {
    "window_one_row_wide": "window_attn_err_median",
    "window_ignored": "window_attn_err_median",
    "released_too_early": "band_blocks",
    "yarn_on_window_layers": "window_rows_err_median",
    "yarn_skipped": "full_rows_err_median",
    "attention_factor_dropped": "full_rows_err_median",
    "rotations_swapped": "window_rows_err_median",
    "router_unnormalised": "weight_err_median",
    "reference_lower_precision": "window_rows_err_median",
}


def _reach_plus(extra):
    """Every window layer of both programs reaches ``extra(block_size)``
    rows further down than the configuration says."""
    from dlrover_tpu.serving.kvpool import window

    real_reach = window.reach_of
    more = [0]

    def reach_of(config, kind):
        reach = real_reach(config, kind)
        return None if reach is None else reach + more[0]

    def with_extra(real_attend):
        def attend(config, layer, pools, tables, at, block_size, *rest):
            more[0] = extra(block_size)
            return real_attend(
                config, layer, pools, tables, at, block_size, *rest
            )

        return attend

    return [
        (window, "reach_of", reach_of),
        (window, "decode_attend", with_extra(window.decode_attend)),
        (window, "chunk_attend", with_extra(window.chunk_attend)),
    ]


def _window_one_row_wide(runner):
    return _reach_plus(lambda block_size: 1)


def _window_ignored(runner):
    return _reach_plus(lambda block_size: block_size - 1)


def _released_too_early(runner):
    from dlrover_tpu.serving.kvpool.groups import ReachGroup

    real = ReachGroup.release_below

    def release_below(self, slot, position):
        return real(self, slot, position + self.block_size)

    return [(ReachGroup, "release_below", release_below)]


def _rotation_of(window_kind, full_kind):
    """``window_lm.rotation`` answering, for a sliding layer, what the
    real one answers for ``window_kind``, and likewise for a full one
    (``"yarn_unscaled"``: YaRN's frequencies, factor 1)."""
    from dlrover_tpu.models import window_lm

    real = window_lm.rotation

    def as_kind(config, kind):
        if kind == "yarn_unscaled":
            return real(config, window_lm.FULL)[0], 1.0
        return real(config, kind)

    def rotation(config, kind):
        return as_kind(
            config, window_kind if kind == window_lm.SLIDING else full_kind
        )

    return [(window_lm, "rotation", rotation)]


def _yarn_on_window_layers(runner):
    from dlrover_tpu.models.window_lm import FULL

    return _rotation_of(FULL, FULL)


def _yarn_skipped(runner):
    from dlrover_tpu.models.window_lm import SLIDING

    return _rotation_of(SLIDING, SLIDING)


def _attention_factor_dropped(runner):
    from dlrover_tpu.models.window_lm import SLIDING

    return _rotation_of(SLIDING, "yarn_unscaled")


def _rotations_swapped(runner):
    from dlrover_tpu.models.window_lm import FULL, SLIDING

    return _rotation_of(FULL, SLIDING)


def _router_unnormalised(runner):
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import moe

    def softmax_route(x, router_w, top_k):
        probs = jax.nn.softmax(jnp.einsum(
            "nd,de->ne", x.astype(jnp.float32), router_w.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        ), axis=-1)
        chosen, experts = jax.lax.top_k(probs, top_k)
        return experts.astype(jnp.int32), chosen

    return [(moe, "softmax_route", softmax_route)]


def _reference_lower_precision(runner):
    return [(runner, "JUDGED", "reference_lower_precision")]


PLANTS = {
    "window_one_row_wide": _window_one_row_wide,
    "window_ignored": _window_ignored,
    "released_too_early": _released_too_early,
    "yarn_on_window_layers": _yarn_on_window_layers,
    "yarn_skipped": _yarn_skipped,
    "attention_factor_dropped": _attention_factor_dropped,
    "rotations_swapped": _rotations_swapped,
    "router_unnormalised": _router_unnormalised,
    "reference_lower_precision": _reference_lower_precision,
}


@contextlib.contextmanager
def planted(name, runner):
    """``name``'s fault planted while the block runs (``runner``: the
    loaded ``runners/serve_window`` module that will be run)."""
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
    "low_logit_deficit_median", "low_logit_within_share",
    "window_rows_err_median", "full_rows_err_median",
    "rows_err_median_by_layer", "low_rows_err_median_by_layer",
    "rows_bad_share", "band_blocks_missing", "band_blocks_stale",
    "window_attn_err_median", "full_attn_err_median",
    "window_attn_err_by_request", "full_attn_err_by_request",
    "mlp_err_median", "alike_share", "weight_err_median",
    "low_window_rows_err_median", "low_full_rows_err_median",
    "low_window_attn_err_median", "low_full_attn_err_median",
    "low_mlp_err_median", "fills",
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
        "serve_tokens_per_s": facts["end_to_end"]["serve_tokens_per_s"],
    }


def child(name, seed, seconds):
    from benchmark import common
    from benchmark import run as bench_run

    ctx = bench_run.cell_context(
        common.load_manifest(), CELL, seed, seconds, 0
    )
    ctx["traffic"].update(reference_sample=2, reference_long=1)
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
