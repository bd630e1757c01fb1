"""The four readers of the program's account of its own stalls
(``benchmark/stall_spans.py`` and its ``layer_metrics``): on hand-built
facts with known answers, on facts of a program that does not watch, at
the window's edges, in the manifest, through the serve runner at tiny
size, and on a golden span set that pins the program's classifier: its
thresholds, its pause rule and its order of causes are the metrics'
definition, and a change to them has to fail a test here."""

import pytest

from benchmark import common, run as bench_run, stall_spans
from tests.benchmark import tiny

MS = 1e-3
EPOCH = 1.79e9  # ts = EPOCH + mono in the hand-built facts
NEW = ("host_pause_s", "host_pause_count", "step_stall_share_pct",
       "step_stall_program_share_pct")
SERVE_CELLS = [
    "nemo12b-serve-chat", "keye-serve-docqa-32k", "xing-serve-sessions-16k",
    "lfm2-serve-sessions-8k", "mellum2-serve-mixed-16k",
]
DECODE = [("admit", 1), ("decode_prep", 1), ("decode_launch", 2),
          ("decode_fetch", 14), ("commit", 1), ("account", 1)]


def read(name, facts):
    return bench_run.load_module("layer_metrics", name).read(facts)


def span(name, mono, dur_s, **attrs):
    return {"name": name, "mono": mono, "ts": EPOCH + mono,
            "dur_s": dur_s, "status": "ok", "pid": 7, "attrs": attrs}


def step(idx, mono, phases_ms):
    phases, cursor = [], 0.0
    for name, ms in phases_ms:
        phases.append([name, cursor, ms * MS])
        cursor += ms * MS
    return span("serving.step", mono, cursor, idx=idx, phases=phases,
                n_decoding=8, prefill_tokens=0)


def pause(mono, dur_s, cpu_s):
    return span("host.pause", mono, dur_s, late_s=dur_s - 0.005,
                process_cpu_s=cpu_s)


def fetch(ms):
    return [(n, ms if n == "decode_fetch" else m) for n, m in DECODE]


def steps_with(slow):
    """Fifty decode steps 21 ms apart from mono 10.0 on, ``slow``
    ``{idx: decode_fetch ms}`` of them longer."""
    out, mono = [], 10.0
    for i in range(50):
        out.append(step(i, mono, fetch(slow.get(i, 14))))
        mono += out[-1]["dur_s"] + 1.0 * MS
    return out


WATCH = span("host.watch", 1.0, 0.0, period_s=0.005, min_late_s=0.06)
# Step 10 waits 110 ms longer while the machine stands still, step 20
# waits 130 ms longer while the interpreter is held, step 30 waits 60 ms
# longer with nothing over it (the device's), step 40 waits 110 ms
# longer under a pause whose CPU reading says neither (the v5e's host
# charges a standstill up to 0.08 s of 0.11: PERF.md, PR 53).
STEPS = steps_with({10: 124, 20: 144, 30: 74, 40: 124})
PAUSES = [
    pause(STEPS[10]["mono"] + 0.004, 0.112, 0.001),
    pause(STEPS[20]["mono"] + 0.004, 0.131, 0.128),
    pause(STEPS[40]["mono"] + 0.004, 0.112, 0.07),
]
FACTS = {"spans": [WATCH] + STEPS + PAUSES}
# 49 periods (the last step has no next): 45 of 21 ms and the four.
WINDOW_S = 45 * 0.021 + 0.131 + 0.151 + 0.081 + 0.131
KNOWN = {
    "host_pause_s": 0.224,
    "host_pause_count": 2,
    "step_stall_share_pct": 100 * 0.410 / WINDOW_S,
    "step_stall_program_share_pct": 100 * 0.190 / WINDOW_S,
}


@pytest.mark.parametrize("name", NEW)
def test_reader_on_hand_built_facts(name):
    assert read(name, FACTS) == pytest.approx(KNOWN[name])


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("spans", [
    None, [], STEPS + PAUSES,
], ids=["no-spans", "empty", "steps-and-pauses-but-no-host.watch"])
def test_a_program_that_does_not_watch_reads_none(name, spans):
    facts = {} if spans is None else {"spans": spans}
    assert read(name, facts) is None


@pytest.mark.parametrize("name", NEW)
def test_a_watched_window_without_a_pause_reads_zero(name):
    value = read(name, {"spans": [WATCH] + steps_with({})})
    assert value == 0 and value is not None


def test_a_watcher_and_no_step_reads_no_share():
    facts = {"spans": [WATCH]}
    assert read("host_pause_s", facts) == 0
    assert read("host_pause_count", facts) == 0
    assert read("step_stall_share_pct", facts) is None


def windowed(lo_mono, hi_mono):
    """FACTS with a timed window of those program-clock seconds."""
    return dict(
        FACTS, ctx={"t_start": EPOCH}, end_to_end={"setup_s": lo_mono},
        window={"seconds": hi_mono - lo_mono},
    )


def test_a_pause_counts_where_it_ends():
    machine = PAUSES[0]
    end = machine["mono"] + machine["dur_s"]
    # The window opens inside the pause: it ends inside, and counts whole.
    facts = windowed(machine["mono"] + 0.05, 11.0)
    assert read("host_pause_count", facts) == 1
    assert read("host_pause_s", facts) == pytest.approx(0.112)
    # The window closes inside the pause: it ends outside.
    facts = windowed(9.0, end - 0.01)
    assert read("host_pause_count", facts) == 0
    assert read("host_pause_s", facts) == 0
    # The interpreter's pause is in neither count, window or none.
    facts = windowed(STEPS[15]["mono"], STEPS[25]["mono"])
    assert read("host_pause_count", facts) == 0


def test_the_shares_are_of_the_timed_windows_seconds():
    lo, hi = STEPS[5]["mono"] + 0.001, STEPS[25]["mono"] + 0.001
    facts = windowed(lo, hi)
    # Steps 5..24 end inside: the machine's and the interpreter's.
    assert read("step_stall_share_pct", facts) == pytest.approx(
        100 * 0.240 / (hi - lo)
    )
    assert read("step_stall_program_share_pct", facts) == pytest.approx(
        100 * 0.130 / (hi - lo)
    )
    table = stall_spans.summary(facts)
    assert table["count"] == {"machine": 1, "interpreter": 1}
    assert table["steps"] == 20


def test_the_manifest_appends_the_four_to_the_five_serve_cells():
    manifest = common.load_manifest()
    assert [m["name"] for m in manifest["per_layer"][-4:]] == list(NEW)
    for m in manifest["per_layer"][-4:]:
        assert m["workloads"] == SERVE_CELLS
        assert (m["source"], m["better"], m["moves"]) == (
            "program_span", "lower", "serve_tokens_per_s",
        )
    assert [(m["unit"], m["layer"]) for m in manifest["per_layer"][-4:]] == [
        ("s", "host machine"), ("pauses", "host machine"),
        ("%", "serving engine"), ("%", "serving engine"),
    ]
    trained = [w["name"] for w in manifest["workloads"]
               if w["name"] not in SERVE_CELLS]
    assert len(trained) == 3  # their runners arm no Tracer


def test_serve_rehearsal_prints_the_four(tmp_path):
    manifest = common.load_manifest()
    ctx = tiny.context("chat-closed", tmp_path, trace=1, seconds=1.0)
    facts = bench_run.load_module("runners", "serve").run(ctx)
    cell = dict(manifest, per_layer=[
        {k: v for k, v in m.items() if k != "workloads"}
        for m in manifest["per_layer"] if m["name"] in NEW
    ])
    line, problems = bench_run.result_line(cell, ctx, facts)
    assert problems == []
    assert set(line["metrics"]) == set(NEW)
    values = {k: v["value"] for k, v in line["metrics"].items()}
    assert all(v >= 0 for v in values.values())
    assert (values["step_stall_program_share_pct"]
            <= values["step_stall_share_pct"] <= 100)
    watches = [s for s in facts["spans"] if s["name"] == "host.watch"]
    assert len(watches) == 1
    # Every pause the run met carries both attrs.
    for s in facts["spans"]:
        if s["name"] == "host.pause":
            assert s["attrs"]["late_s"] >= 0.06
            assert "process_cpu_s" in s["attrs"]
    # The run disarmed what it armed: no watcher outlives it.
    import threading

    assert not [t for t in threading.enumerate() if t.name == "host-watch"]


# --- the program's classifier, pinned -----------------------------------
# The four metrics are defined by ``observability/stalls.py``'s rule,
# which lives outside the benchmark's paths. Each case is one slow step
# (idx 10) among fifty of 21 ms, what lies over it, and the cause and
# readings that rule has to give: (host_pause_count, stalled seconds,
# the program's share of them).


def _gc(mono, dur_s):
    return span("host.gc", mono, dur_s, generation=2, collected=0)


def _golden(fetch_ms, over):
    steps = steps_with({10: fetch_ms})
    at = steps[10]["mono"] + 0.004
    return {"spans": [WATCH] + steps + [make(at) for make in over]}


GOLDEN = [
    ("excess-just-under-50ms", 63.5, [], None, (0, 0.0, 0.0)),
    ("excess-just-over-50ms", 64.5, [], "device_wait", (0, 0.0505, 0.0505)),
    ("pause-cpu-49pct-machine", 124,
     [lambda at: pause(at, 0.100, 0.049)], "machine", (1, 0.110, 0.0)),
    ("pause-cpu-51pct-unattributed", 124,
     [lambda at: pause(at, 0.100, 0.051)], "unattributed", (1, 0.110, 0.0)),
    ("pause-cpu-79pct-unattributed", 124,
     [lambda at: pause(at, 0.100, 0.079)], "unattributed", (1, 0.110, 0.0)),
    ("pause-cpu-81pct-interpreter", 124,
     [lambda at: pause(at, 0.100, 0.081)], "interpreter", (0, 0.110, 0.110)),
    ("collection-over-the-pause-is-gc-whatever-the-cpu", 124,
     [lambda at: pause(at, 0.100, 0.001), lambda at: _gc(at + 0.002, 0.097)],
     "gc", (0, 0.110, 0.110)),
    ("collection-under-half-the-pause-is-the-cpus-call", 124,
     [lambda at: pause(at, 0.100, 0.001), lambda at: _gc(at + 0.002, 0.040)],
     "machine", (1, 0.110, 0.0)),
    ("machine-before-interpreter", 124,
     [lambda at: pause(at, 0.070, 0.069),
      lambda at: pause(at + 0.071, 0.070, 0.001)], "machine", (1, 0.110, 0.0)),
    ("interpreter-before-unattributed", 124,
     [lambda at: pause(at, 0.070, 0.040),
      lambda at: pause(at + 0.071, 0.070, 0.069)],
     "interpreter", (1, 0.110, 0.110)),
    ("pause-before-compile", 124,
     [lambda at: pause(at, 0.100, 0.001),
      lambda at: span("compile.backend", at, 0.1)],
     "machine", (1, 0.110, 0.0)),
    ("compile-before-gc", 124,
     [lambda at: span("compile.backend", at, 0.1),
      lambda at: _gc(at, 0.1)], "compile", (0, 0.110, 0.110)),
    ("gc-before-the-phases", 124,
     [lambda at: _gc(at, 0.1)], "gc", (0, 0.110, 0.110)),
]


@pytest.mark.parametrize(
    "fetch_ms, over, cause, known", [c[1:] for c in GOLDEN],
    ids=[c[0] for c in GOLDEN],
)
def test_the_programs_classifier_is_pinned(fetch_ms, over, cause, known):
    facts = _golden(fetch_ms, over)
    table = stall_spans.summary(facts)
    assert table["count"] == ({cause: 1} if cause else {})
    count, stalled_s, programs_s = known
    window_s = 48 * 0.021 + 0.021 + (fetch_ms - 14) * MS
    assert read("host_pause_count", facts) == count
    assert read("step_stall_share_pct", facts) == pytest.approx(
        100 * stalled_s / window_s
    )
    assert read("step_stall_program_share_pct", facts) == pytest.approx(
        100 * programs_s / window_s
    )


def test_a_long_kinds_threshold_is_its_own_median():
    """Chunk steps of 80 ms beside the decode steps: one that takes 70
    ms longer is under its kind's ``max(0.05 s, median)``, one that
    takes 85 ms longer is over it."""
    chunk = [("admit", 1), ("prefill_launch", 2), ("prefill_fetch", 75),
             ("commit", 1)]
    steps, mono = [], 20.0
    for i, extra in enumerate([0, 0, 0, 70, 0, 0, 85, 0, 0, 0]):
        phases = [(n, ms + extra if n == "prefill_fetch" else ms)
                  for n, ms in chunk]
        one = step(100 + i, mono, phases)
        one["attrs"].update(n_decoding=0, prefill_tokens=512)
        steps.append(one)
        mono += one["dur_s"] + 1.0 * MS
    table = stall_spans.summary({"spans": [WATCH] + steps})
    assert [(r["idx"], r["kind"], r["cause"]) for r in table["stalls"]] == [
        (106, "chunk", "device_wait"),
    ]
    assert table["excess_s"]["device_wait"] == pytest.approx(0.085)
