"""The twelve readers of the engine's ``serving.step`` spans
(``benchmark/step_spans.py`` and its ``layer_metrics``): on hand-built
facts with known answers, on empty facts, on a three-gap toy dump, on a
slice of a traced chip run, and through the serve runner at tiny size."""

import copy
import json
import os

import pytest

from benchmark import common, run as bench_run, step_spans
from tests.benchmark import tiny

DATA = os.path.join(os.path.dirname(__file__), "data")
MS = 1e-3
EPOCH = 1.79e9  # ts = EPOCH + mono in the hand-built facts
NEW = (
    "step_host_serial_ms_p50", "step_prep_ms_p50", "step_launch_ms_p50",
    "step_commit_ms_p50", "step_account_ms_p50", "inter_token_gap_ms_p95",
    "decode_batch_mean", "prefill_step_share_pct", "slot_wait_ms_p50",
    "router_queue_ms_p50", "replica_loop_ms_p50", "idle_attributed_pct",
)


def read(name, facts):
    return bench_run.load_module("layer_metrics", name).read(facts)


def span(name, mono, dur_s, status="ok", span_id=None, parent_id=None,
         **attrs):
    return {
        "name": name, "mono": mono, "ts": EPOCH + mono, "dur_s": dur_s,
        "status": status, "span_id": span_id, "parent_id": parent_id,
        "attrs": attrs,
    }


def step(idx, mono, phases_ms, status="ok", **counts):
    """A serving.step span whose phases (name, milliseconds) tile it."""
    phases, cursor = [], 0.0
    for name, ms in phases_ms:
        phases.append([name, cursor, ms * MS])
        cursor += ms * MS
    counts = dict({"n_decoding": 0, "prefill_tokens": 0}, **counts)
    return span("serving.step", mono, cursor, status=status, idx=idx,
                phases=phases, **counts)


# Three steps that launch (29, 25 and 34 ms; the replica's loop takes
# 1 and 1.5 ms between them), one that launched nothing, one that failed.
STEPS = [
    step(0, 10.0, [
        ("admit", 1), ("prefill_prep", 2), ("prefill_launch", 1),
        ("decode_prep", 1), ("decode_launch", 2), ("decode_fetch", 20),
        ("commit", 1), ("account", 1),
    ], n_decoding=4, prefill_tokens=16),
    step(1, 10.030, [
        ("admit", 1), ("decode_prep", 1), ("decode_launch", 2),
        ("decode_fetch", 18), ("commit", 1), ("account", 2),
    ], n_decoding=6),
    step(2, 10.0565, [
        ("admit", 2), ("prefill_prep", 1), ("prefill_launch", 1),
        ("prefill_fetch", 10), ("commit", 1), ("decode_prep", 1),
        ("decode_launch", 1), ("decode_fetch", 15), ("commit", 1),
        ("account", 1),
    ], n_decoding=8, prefill_tokens=5),
    step(3, 10.0915, [("admit", 1), ("account", 1)]),
    step(4, 10.0945, [("admit", 1), ("account", 5)], status="error"),
]
REQUESTS = [
    span("serving.queue_wait", 5.0, 0.5),
    span("serving.queue_wait", 5.0, 3.0),
    span("serving.queue_wait", 5.0, 4.0),
    span("serving.queue_wait", 5.0, 9.0, status="error"),
    span("fleet.request", 5.0, 6.0, span_id="a"),
    span("fleet.attempt", 5.5, 1.0, parent_id="a", status="error"),
    span("fleet.attempt", 5.002, 0.4, parent_id="a"),
    span("fleet.request", 6.0, 5.0, span_id="b"),
    span("fleet.attempt", 6.004, 4.9, parent_id="b"),
    span("fleet.request", 7.0, 1.0, span_id="never-dispatched"),
]
FACTS = {"spans": STEPS + REQUESTS}
KNOWN = {
    # decode_fetch ends at 27 / 22 ms of steps 0 / 1; the next step's
    # first launch starts 2 / 3 ms into it: 5 and 7.5 ms.
    "step_host_serial_ms_p50": 6.25,
    "step_prep_ms_p50": 4.0,        # 4, 2, 4
    "step_launch_ms_p50": 2.0,      # 3, 2, 2
    "step_commit_ms_p50": 1.0,      # 1, 1, 2
    "step_account_ms_p50": 1.0,     # 1, 2, 1
    # periods 30 ms (6 requests) and 26.5 ms (8): 95 % of 14 is past 8.
    "inter_token_gap_ms_p95": 30.0,
    "decode_batch_mean": 6.0,
    "prefill_step_share_pct": 100.0 * 2 / 3,
    "slot_wait_ms_p50": 3000.0,     # the failed wait is left out
    "router_queue_ms_p50": 3.0,     # 2 ms (first attempt of two), 4 ms
    "replica_loop_ms_p50": 1.25,
}


@pytest.mark.parametrize("name", sorted(KNOWN))
def test_reader_on_hand_built_facts(name):
    assert read(name, FACTS) == pytest.approx(KNOWN[name], rel=1e-9)


@pytest.mark.parametrize("name", NEW)
def test_reader_on_empty_facts(name):
    assert read(name, {}) is None
    assert read(name, {"spans": [], "dump": None, "trace": None}) is None
    # The parent's spans: requests, no steps.
    if name not in ("slot_wait_ms_p50", "router_queue_ms_p50"):
        assert read(name, {"spans": REQUESTS}) is None


def test_readers_keep_to_the_timed_window():
    # The window opens with step 1 and closes before step 2 ends.
    facts = dict(
        FACTS, ctx={"t_start": EPOCH}, end_to_end={"setup_s": 10.0295},
        window={"seconds": 0.05},
    )
    assert [s["attrs"]["idx"] for s in step_spans.steps(facts)] == [1]
    assert read("decode_batch_mean", facts) == 6.0
    assert read("step_account_ms_p50", facts) == pytest.approx(2.0)
    assert read("replica_loop_ms_p50", facts) is None
    assert read("slot_wait_ms_p50", facts) is None


def test_steps_that_are_not_neighbours_are_not_paired():
    facts = {"spans": [STEPS[0], STEPS[2]]}
    assert step_spans.neighbours(facts) == []
    assert read("step_host_serial_ms_p50", facts) is None


# The profiler's clock starts with its session: step 0 begins 5 ms into
# it. Every bench.engine_step annotation opens with its step and closes
# 1 us after it; a later bench.* annotation stretches the traced window
# to 96.5 ms. The device is busy 5-31, 38-56 and 65-94 ms.
def toy_dump():
    host = [
        ["bench.engine_step",
         round((s["mono"] - 10.0) * 1e9 + 5e6), round(s["dur_s"] * 1e9) + 1000]
        for s in STEPS[:3]
    ]
    host.append(["bench.decode", 96_000_000, 500_000])
    ops = [["fusion.1", lo, hi - lo, "", "fusion"]
           for lo, hi in ((5e6, 31e6), (38e6, 56e6), (65e6, 94e6))]
    return {"host": host, "planes": {"/device:TPU:0": {"XLA Ops": ops}}}


def test_idle_is_split_in_proportion_to_the_overlap():
    module = bench_run.load_module("layer_metrics", "idle_attributed_pct")
    facts = dict(FACTS, dump=toy_dump())
    table = module.idle_by_phase(facts)
    assert table["clock"] == {
        "origin_ns": pytest.approx(5e6, abs=300),  # float epoch: 0.24 us
        "spread_ns": pytest.approx(0, abs=300), "pairs": 3,
        "shift_ns": None,  # no "XLA Modules" line to tell by
    }
    assert table["idle_s_shifted"] is None
    want_ms = {
        # gap 31-38: the tail of step 0, the loop, the head of step 1
        # gap 56-65: likewise between steps 1 and 2
        # gap 94-96.5: step 2's tail, then nothing of the program
        "decode_fetch": 1 + 1, "commit": 1 + 1 + 0.5,
        "account": 1 + 2 + 1, "between_steps": 1 + 1.5, "admit": 1 + 2,
        "decode_prep": 1, "decode_launch": 1, "prefill_prep": 1,
        "prefill_launch": 0.5, "unattributed": 1.0,
    }
    assert set(table["idle_s"]) == set(want_ms)
    for name, ms in want_ms.items():
        assert table["idle_s"][name] == pytest.approx(ms * MS, abs=1e-6)
    assert sum(table["idle_s"].values()) == pytest.approx(18.5 * MS)
    assert module.read(facts) == pytest.approx(
        100 * (1 - 1 / 18.5), abs=1e-2
    )


def with_modules(ends_ns):
    dump = toy_dump()
    dump["planes"]["/device:TPU:0"]["XLA Modules"] = [
        ["jit_step(1)", end - 20e6, 20e6] for end in ends_ns
    ]
    return dict(FACTS, dump=dump)


def test_the_second_table_has_the_device_plane_at_its_latest():
    module = bench_run.load_module("layer_metrics", "idle_attributed_pct")
    # The fetches end at 32, 57, 75.5 and 93.5 ms; their programs' last
    # stamps lie 1.0, 0.7, 1.0 and 1.0 ms earlier.
    facts = with_modules((31.0e6, 56.3e6, 74.5e6, 92.5e6))
    table = module.idle_by_phase(facts)
    assert table["clock"]["shift_ns"] == pytest.approx(0.7e6, abs=300)
    # As traced, the first table and the scalar are what they were.
    plain = module.idle_by_phase(dict(FACTS, dump=toy_dump()))
    assert table["idle_s"] == plain["idle_s"]
    assert module.read(facts) == pytest.approx(
        100 * (1 - 1 / 18.5), abs=1e-2
    )
    # Shifted, busy is 5.7-31.7, 38.7-56.7 and 65.7-94.7 ms.
    shifted = table["idle_s_shifted"]
    assert shifted["decode_fetch"] == pytest.approx(0.6 * MS, abs=1e-6)
    assert shifted["decode_launch"] == pytest.approx(1.7 * MS, abs=1e-6)
    assert sum(shifted.values()) == pytest.approx(
        (0.7 + 7 + 9 + 1.8) * MS, abs=1e-6
    )


def test_one_mispaired_fetch_does_not_set_the_shift():
    module = bench_run.load_module("layer_metrics", "idle_attributed_pct")
    # 40 fetches: one pairs with a module that ends 0.1 ms before it,
    # the others wait 1.0-1.2 ms.
    labelled = [[i * 30e6, i * 30e6 + 20e6, "decode_fetch"]
                for i in range(40)]
    waits = [0.1e6] + [1.0e6 + 5e3 * i for i in range(39)]
    planes = {"d": {"XLA Modules": [
        ["jit_step(1)", row[1] - w - 19e6, 19e6]
        for row, w in zip(labelled, waits)
    ]}}
    assert module._device_shift_ns(labelled, planes) == pytest.approx(
        1.0e6 + 5e3, abs=1
    )


def test_no_second_table_when_the_waits_have_no_sharp_floor():
    module = bench_run.load_module("layer_metrics", "idle_attributed_pct")
    # Waits of 1.0, 0.2, 1.0 and 1.0 ms: 0.8 ms from the lowest to the
    # next, so the lowest is not a floor that the others stand on.
    table = module.idle_by_phase(
        with_modules((31.0e6, 56.8e6, 74.5e6, 92.5e6))
    )
    assert table["clock"]["shift_ns"] is None
    assert table["idle_s_shifted"] is None
    assert sum(table["idle_s"].values()) == pytest.approx(18.5 * MS)


def test_idle_is_not_attributed_when_the_clocks_cannot_be_tied():
    module = bench_run.load_module("layer_metrics", "idle_attributed_pct")
    # Spans whose starts wander by milliseconds against the annotations.
    spans = copy.deepcopy(STEPS)
    spans[1]["ts"] += 3 * MS
    spans[2]["ts"] -= 3 * MS
    facts = {"spans": spans, "dump": toy_dump()}
    assert step_spans.profile_clock(facts)["spread_ns"] > 1e6
    assert module.read(facts) is None
    # Annotations that bracket no run of the spans.
    dump = toy_dump()
    for row in dump["host"]:
        row[2] += 7_000_000
    assert step_spans.profile_clock(dict(FACTS, dump=dump)) is None
    assert module.read(dict(FACTS, dump=dump)) is None
    # No device plane.
    assert module.read(dict(FACTS, dump={"host": toy_dump()["host"],
                                         "planes": {}})) is None


def test_the_session_is_found_among_many_steps():
    # 40 steps of distinct lengths; the session covers steps 17-22.
    many = [
        step(i, 10.0 + 0.05 * i, [
            ("admit", 1), ("decode_prep", 1), ("decode_launch", 1),
            ("decode_fetch", 10 + ((i * i * 37) % 41) / 4), ("commit", 1),
            ("account", 1),
        ], n_decoding=2)
        for i in range(40)
    ]
    host = [
        ["bench.engine_step", round((s["mono"] - 10.8) * 1e9) + 2000,
         round(s["dur_s"] * 1e9) + 3000]
        for s in many[17:23]
    ]
    clock = step_spans.profile_clock(
        {"spans": many, "dump": {"host": host, "planes": {}}}
    )
    assert [s["attrs"]["idx"] for s in clock["steps"]] == list(range(17, 23))
    assert clock["origin_ns"] == pytest.approx(50e6 + 2000, abs=300)
    # A slower emission (a JSONL sink, a busier host) lengthens every
    # annotation alike: the session is still found.
    for row in host:
        row[2] += 400_000
    clock = step_spans.profile_clock(
        {"spans": many, "dump": {"host": host, "planes": {}}}
    )
    assert [s["attrs"]["idx"] for s in clock["steps"]] == list(range(17, 23))


@pytest.fixture(scope="module")
def recorded():
    """Six consecutive engine steps of ``nemo12b-serve-chat`` on a TPU
    v5e (PR 24's traced chip run, seed 0): the dump cropped to them as
    ``dump_xplane`` wrote it, and the run's spans from two steps before
    to two steps after."""
    with open(os.path.join(DATA, "serve_6steps_v5e.json")) as f:
        return json.load(f)


def test_recorded_slice_clock_and_idle_table(recorded):
    module = bench_run.load_module("layer_metrics", "idle_attributed_pct")
    clock = step_spans.profile_clock(recorded)
    assert clock["pairs"] == 6
    assert [s["attrs"]["idx"] for s in clock["steps"]] == (
        recorded["expect"]["idx"]
    )
    assert clock["spread_ns"] < 1e6
    table = module.idle_by_phase(recorded)
    assert table["clock"]["shift_ns"] == pytest.approx(
        recorded["expect"]["shift_ns"], rel=1e-6
    )
    for key in ("idle_s", "idle_s_shifted"):
        assert set(table[key]) == set(recorded["expect"][key])
        for name, seconds in recorded["expect"][key].items():
            assert table[key][name] == pytest.approx(seconds, rel=1e-6)
    # The fetch phases give to the launch phases what the shift moves.
    for a, b in (("decode_fetch", "decode_launch"),
                 ("prefill_fetch", "prefill_launch")):
        assert table["idle_s_shifted"][a] < table["idle_s"][a]
        assert table["idle_s_shifted"][b] > table["idle_s"][b]
    assert module.read(recorded) == pytest.approx(
        recorded["expect"]["idle_attributed_pct"], rel=1e-6
    )


def test_recorded_slice_program_clock_readers(recorded):
    for name, value in recorded["expect"]["metrics"].items():
        assert read(name, recorded) == pytest.approx(value, rel=1e-6)


def test_serve_rehearsal_prints_every_program_clock_metric(tmp_path):
    manifest = common.load_manifest()
    ctx = tiny.context("chat-closed", tmp_path, trace=1, seconds=1.0)
    facts = bench_run.load_module("runners", "serve").run(ctx)
    cell = dict(manifest, per_layer=[
        {k: v for k, v in m.items() if k != "workloads"}
        for m in manifest["per_layer"] if m["name"] in NEW
    ])
    assert len(cell["per_layer"]) == 12
    line, problems = bench_run.result_line(cell, ctx, facts)
    assert problems == []
    # A CPU run has no device plane: the trace's metric is left out.
    assert set(line["metrics"]) == set(NEW) - {"idle_attributed_pct"}
    values = {k: v["value"] for k, v in line["metrics"].items()}
    assert all(v >= 0 for v in values.values())
    assert 1 <= values["decode_batch_mean"] <= 4
    assert 0 < values["prefill_step_share_pct"] <= 100
    # The session lies outside the window, and the clocks still tie.
    clock = step_spans.profile_clock(dict(facts, ctx=ctx))
    window_idx = {s["attrs"]["idx"] for s in step_spans.steps(
        dict(facts, ctx=ctx)
    )}
    assert clock["pairs"] >= 2 and clock["spread_ns"] < 1e6
    assert not window_idx & {s["attrs"]["idx"] for s in clock["steps"]}
