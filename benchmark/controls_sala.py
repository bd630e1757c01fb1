"""Controls of ``sala-serve-docs-64k``'s ``correct``: each plants one
fault and must come out NOT correct, by a limit of the comparison and not
by all of them.

    chiprun --timeout 3000 -- python3 benchmark/controls_sala.py [NAME ...]

Two kinds. A REFERENCE fault (``REFERENCE``) is planted in the
reference's pass over the probed requests' OWN rows (the document's carry
is the good one: a whole-document pass a fault would be a minute each):
the cell is served ONCE, correct, and its readings are judged again
against each faulty reference. A PROGRAM fault (``PROGRAM``) changes what
the engine does, so the cell is served once for each. The last,
``reference_lower_precision``, puts the reference's own rows computed
with 3 bits of mantissa in the program's place. Every one prints a JSON
line ``{"control", "correct", "problems"}``; the exit code is 1 if any
came out correct.

What an output cannot see is said here and held by the CPU tests
(``tests/test_linear_sparse_serving.py``: the forward pass against the
reference on LOGITS): ``logit_scale_dropped`` scales every logit of a row
alike, so the emitted token and its deficit's sign stand;
``state_in_bf16`` over a request's ~600 own rows reads within the
limit's room on the fast-decaying heads.
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

WORKLOAD = "sala-serve-docs-64k"
REFERENCE = (
    "decay_dropped", "decay_layer_index_held", "rope_on_sparse_layers",
    "rope_skipped_on_lightning", "forced_blocks_dropped", "topk_63",
    "group_sum_skipped", "depth_scale_by_held_layers", "state_in_bf16",
    "logit_scale_dropped",
)
PROGRAM = ("restore_skipped", "snapshot_one_block_early",
           "ckey_in_first_rows_block")
LOWER = "reference_lower_precision"
# Planted below what the cell's outputs can see (module docstring): run
# and reported, not counted against the exit code.
BELOW_SIGHT = ("state_in_bf16", "logit_scale_dropped")


def plant_restore_skipped(engine):
    """A hit slots its blocks in and starts from ZEROS."""
    restore = engine._restore_state
    engine._restore_state = lambda slot, snapshot: restore(slot, 0)
    return lambda: None


def plant_snapshot_one_block_early(engine):
    """The snapshot a chunk writes is the state one block before the
    boundary its entry names."""
    import numpy as np

    args = engine._chunk_state_args

    def early(req, start, n_valid):
        out = args(req, start, n_valid)
        if len(out) == 3 and int(out[2]) and int(out[1]) >= engine.block_size:
            out = (out[0], np.int32(int(out[1]) - engine.block_size), out[2])
        return out

    engine._chunk_state_args = early
    return lambda: None


def plant_ckey_in_first_rows_block(engine):
    """The compressed key that straddles a chunk's start is not the
    chunk's to land (as if it lived with its FIRST row, in the block
    before, which a request that shares that block cannot write): the
    chunk scores and lands zeros in its place."""
    from dlrover_tpu.models import linear_sparse_lm as lsm
    from dlrover_tpu.serving.kvpool import engine as paged

    whole = lsm.compressed_keys

    def without_the_first(k_rows, stride):
        fresh = whole(k_rows, stride)
        return fresh.at[0].set(0) if fresh.shape[0] > 1 else fresh

    lsm.compressed_keys = without_the_first
    paged._linear_steps_for.cache_clear()
    engine._steps = paged._linear_steps(
        engine.config, engine.slots, engine.max_blocks, engine.block_size,
        engine.prefill_chunk,
    )
    engine.warmup()

    def undo():
        lsm.compressed_keys = whole
        paged._linear_steps_for.cache_clear()

    return undo


PLANTS = {
    "restore_skipped": plant_restore_skipped,
    "snapshot_one_block_early": plant_snapshot_one_block_early,
    "ckey_in_first_rows_block": plant_ckey_in_first_rows_block,
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
    # (the last run's weights and carry, 8 GB, go before the next engine)
    runner.LAST.clear()
    gc.collect()
    try:
        facts = runner.run(ctx)
    finally:
        runner.PLANT = None
        for back in undo:
            back()
    return facts["problems"], facts.get("reference", {})


def main(argv, make_context=context):
    from benchmark import run as bench_run

    runner = bench_run.load_module("runners", "serve_linear")
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
            if name in REFERENCE:
                _, found, _ = runner.judge(
                    last["requests"], last["params"], last["sh"],
                    last["doc_len"], carry=last["carry"], faults=(name,),
                )
                report(name, found)
            elif name == LOWER:
                _, found, _ = runner.judge(
                    last["requests"], last["params"], last["sh"],
                    last["doc_len"], carry=last["carry"], judged=LOWER,
                )
                report(name, found)
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
