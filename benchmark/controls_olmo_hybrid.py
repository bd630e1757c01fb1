"""Controls of ``olmohybrid-serve-grow-6k``'s ``correct``: each plants one
fault and must come out NOT correct, by a limit of the comparison and not
by all of them.

    chiprun --timeout 3000 -- python3 benchmark/controls_olmo_hybrid.py [NAME ...]

Two kinds. A REFERENCE fault (``REFERENCE``) is planted in the
reference's pass over the probed sessions' whole sequences: the cell is
served ONCE, correct, and its readings are judged again against each
faulty reference. A PROGRAM fault (``PROGRAM``) changes what the engine
does, so the cell is served once for each. The last,
``reference_lower_precision``, puts the reference computed with 3 bits of
mantissa in the program's place. Every one prints a JSON line
``{"control", "correct", "problems"}``; the exit code is 1 if any came
out correct.

What a probe cannot see is said here and held by the CPU tests:
``state_in_bf16`` (the recurrence's state rounded to bfloat16 after every
row) moves a state by ~2^-9 a row that the decay forgets within tens of
rows: it reads inside the limit's room beside the bfloat16 q, k and v the
program itself computes with; ``tests/test_delta_serving.py`` holds the
state arrays' dtypes (float32 beside bfloat16 taps) and the snapshot
against the float32 recurrence at 1e-4.
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import reference_olmo_hybrid  # noqa: E402

WORKLOAD = "olmohybrid-serve-grow-6k"
REFERENCE = reference_olmo_hybrid.FAULTS     # what its ``faults`` knows
PROGRAM = ("conv_taps_zeroed_at_restore", "restore_skipped",
           "snapshot_one_row_early")
LOWER = "reference_lower_precision"
# Planted below what the cell's outputs can see (module docstring): run
# and reported, not counted against the exit code.
BELOW_SIGHT = ("state_in_bf16",)


def plant_restore_skipped(engine):
    """A hit slots its blocks in and starts from ZEROS (both arrays)."""
    restore = engine._restore_state
    engine._restore_state = lambda slot, snapshot: restore(slot, 0)
    return lambda: None


def plant_conv_taps_zeroed_at_restore(engine):
    """A restore moves the matrix state alone: the slot's convolution
    taps start from zeros, as if the pair were one array."""
    import jax.numpy as jnp

    restore = engine._restore_state
    names = engine._pool_names

    def half(slot, snapshot):
        restore(slot, snapshot)
        pools = list(engine._pools())
        at = names.index("taps")
        pools[at] = pools[at].at[:, slot].set(jnp.zeros((), pools[at].dtype))
        engine._set_pools(tuple(pools))

    engine._restore_state = half
    return lambda: None


def plant_snapshot_one_row_early(engine):
    """The snapshot a chunk writes is the state one ROW before the
    boundary its entry names."""
    import numpy as np

    args = engine._chunk_state_args

    def early(req, start, n_valid):
        out = args(req, start, n_valid)
        if len(out) == 3 and int(out[2]) and int(out[1]) >= 1:
            out = (out[0], np.int32(int(out[1]) - 1), out[2])
        return out

    engine._chunk_state_args = early
    return lambda: None


PLANTS = {
    "restore_skipped": plant_restore_skipped,
    "conv_taps_zeroed_at_restore": plant_conv_taps_zeroed_at_restore,
    "snapshot_one_row_early": plant_snapshot_one_row_early,
}


def context(seed=5, seconds=5.0):
    from benchmark import common, run as bench_run

    return bench_run.cell_context(
        common.load_manifest(), WORKLOAD, seed, seconds, 0
    )


def served(runner, ctx, plant=None):
    """One run of the cell with ``plant`` in the program: its problems."""
    import gc

    undo = []
    runner.PLANT = plant and (lambda engine: undo.append(plant(engine)))
    # (the last run's weights go before the next engine)
    runner.LAST.clear()
    gc.collect()
    try:
        facts = runner.run(ctx)
    finally:
        runner.PLANT = None
        for back in undo:
            back()
    return facts["problems"], facts.get("reference", {})


def rejudge(runner, last, name):
    """The kept readings against a reference with ``name`` planted in it
    (or, for ``LOWER``, with the reference in the program's place)."""
    kw = dict(judged=LOWER) if name == LOWER else dict(
        faults=(name,), low_too=False
    )
    return runner.judge(
        last["requests"], last["window_tokens"], last["params"], last["sh"],
        **kw
    )


def main(argv, make_context=context):
    from benchmark import run as bench_run

    runner = bench_run.load_module("runners", "serve_delta")
    names = argv or list(REFERENCE) + list(PROGRAM) + [LOWER]
    unknown = set(names) - set(REFERENCE) - set(PROGRAM) - {LOWER}
    if unknown:
        raise SystemExit(f"no control {sorted(unknown)}")
    failed = []

    def report(name, problems):
        print(json.dumps({
            "control": name, "correct": not problems,
            "problems": problems[:4],
        }), flush=True)
        if not problems and name not in BELOW_SIGHT:
            failed.append(name)

    if set(names) & (set(REFERENCE) | {LOWER}):
        problems, _ = served(runner, make_context())
        print(json.dumps({"control": None, "correct": not problems,
                          "problems": problems[:4]}), flush=True)
        if problems:
            failed.append("the cell itself")
        last = dict(runner.LAST)
        for name in names:
            if name in REFERENCE or name == LOWER:
                report(name, rejudge(runner, last, name)[1])
        runner.LAST.clear()
        del last
    for name in names:
        if name in PROGRAM:
            problems, _ = served(runner, make_context(), PLANTS[name])
            runner.LAST.clear()
            report(name, problems)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
