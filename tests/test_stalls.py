"""The stall classifier (``observability/stalls.py``) over hand-built
spans: every cause in the stated order, each kind's own median, the
window, and ``tools/trace_query.py --stalls`` on a small sink."""

import json
import os
import sys

import pytest

from dlrover_tpu.observability import stalls

MS = 1e-3
EPOCH = 1.79e9  # ts = EPOCH + mono
DECODE = [("admit", 1), ("decode_prep", 1), ("decode_launch", 2),
          ("decode_fetch", 14), ("commit", 1), ("account", 1)]
CHUNK = [("admit", 1), ("prefill_prep", 2), ("prefill_launch", 2),
         ("prefill_fetch", 33), ("commit", 1), ("account", 1)]


def span(name, mono, dur_s, **attrs):
    return {"name": name, "mono": mono, "ts": EPOCH + mono,
            "dur_s": dur_s, "status": "ok", "pid": 7, "attrs": attrs}


def step(idx, mono, phases_ms, **counts):
    phases, cursor = [], 0.0
    for name, ms in phases_ms:
        phases.append([name, cursor, ms * MS])
        cursor += ms * MS
    counts = dict({"n_decoding": 0, "prefill_tokens": 0}, **counts)
    return span("serving.step", mono, cursor, idx=idx, phases=phases,
                **counts)


def pause(mono, dur_s, cpu_s):
    return span("host.pause", mono, dur_s, late_s=dur_s - 0.005,
                process_cpu_s=cpu_s)


def with_phase(phases, name, ms):
    return [(n, ms if n == name else m) for n, m in phases]


def run_of(slow_phases=DECODE, gap_after_slow_ms=1.0, n=9, slow=4,
           **slow_counts):
    """``n`` decode steps of 20 ms, 21 ms apart, the ``slow``-th with
    ``slow_phases`` and ``gap_after_slow_ms`` before the next; returns
    (the steps, the slow one)."""
    out, mono = [], 50.0
    for i in range(n):
        phases = slow_phases if i == slow else DECODE
        counts = dict({"n_decoding": 8}, **(slow_counts if i == slow else {}))
        out.append(step(i, mono, phases, **counts))
        mono += out[-1]["dur_s"] + (
            gap_after_slow_ms if i == slow else 1.0
        ) * MS
    return out, out[slow]


SLOW_FETCH = with_phase(DECODE, "decode_fetch", 134)  # 120 ms over


def cause_of(spans):
    (record,) = stalls.stalls(spans)
    return record["cause"]


def test_a_machine_pause_comes_first_of_all():
    steps, slow = run_of(SLOW_FETCH, retraces=1)
    at = slow["mono"] + 0.01
    others = [
        pause(at, 0.115, 0.002), pause(at, 0.115, 0.11),
        span("compile.backend", at, 0.05, fun_name="step"),
        span("host.gc", at, 0.02, generation=2, collected=3),
    ]
    assert cause_of(steps + others) == "machine"


def test_a_pause_that_burned_cpu_is_the_interpreters():
    steps, slow = run_of(SLOW_FETCH, retraces=1)
    at = slow["mono"] + 0.01
    others = [
        pause(at, 0.115, 0.11),
        span("compile.backend", at, 0.05, fun_name="step"),
        # a collection in the same step, before the pause began
        span("host.gc", slow["mono"] + 0.001, 0.004, generation=2,
             collected=3),
    ]
    assert cause_of(steps + others) == "interpreter"


def test_a_pause_the_cpu_clock_cannot_place_is_unattributed():
    steps, slow = run_of(SLOW_FETCH, retraces=1)
    at = slow["mono"] + 0.01
    unplaced = pause(at, 0.115, 0.07)  # neither ~0 nor ~0.115
    compiled = span("compile.backend", at, 0.05, fun_name="step")
    # ... a pause all the same: before a compile in the same step,
    assert cause_of(steps + [unplaced, compiled]) == "unattributed"
    # and after a pause whose cause is known.
    assert cause_of(
        steps + [unplaced, pause(at + 0.116, 0.07, 0.07)]
    ) == "interpreter"


def test_a_collection_over_the_interpreters_pause_is_what_held_it():
    steps, slow = run_of(SLOW_FETCH)
    at = slow["mono"] + 0.01
    held = [pause(at, 0.075, 0.08),
            span("host.gc", at + 0.003, 0.071, generation=2, collected=0)]
    assert cause_of(steps + held) == "gc"
    # ... whatever the CPU clock read meanwhile (the v5e's host charges
    # a collection 0.06-0.26 s and a standstill 0.00-0.08)
    assert cause_of(steps + [pause(at, 0.075, 0.0)] + held[1:]) == "gc"
    # A collection that covers under half of a pause does not claim it.
    brief = span("host.gc", at + 0.003, 0.030, generation=2, collected=0)
    assert cause_of(steps + [pause(at, 0.075, 0.0), brief]) == "machine"
    assert cause_of(steps + [pause(at, 0.075, 0.08), brief]) == "interpreter"


@pytest.mark.parametrize("attrs, cause", [
    ({"process_cpu_s": 0.01}, "machine"),
    ({"process_cpu_s": 0.056}, "machine"),
    # between half and about the whole the clock cannot tell
    ({"process_cpu_s": 0.058}, "unattributed"),
    ({"process_cpu_s": 0.09}, "unattributed"),
    ({"process_cpu_s": 0.093}, "interpreter"),
    ({"process_cpu_s": 0.3}, "interpreter"),
    ({}, "machine"),
])
def test_whose_a_pause_is(attrs, cause):
    p = span("host.pause", 50.0, 0.115, late_s=0.11, **attrs)
    assert stalls.pause_cause(p) == cause
    over = span("host.gc", 50.002, 0.110, generation=2, collected=0)
    assert stalls.pause_cause(p, [over]) == "gc"


@pytest.mark.parametrize("how", ["compile.backend", "compile.trace_lower",
                                 "retraces"])
def test_a_compile_comes_before_a_collection(how):
    counts = {"retraces": 1} if how == "retraces" else {}
    steps, slow = run_of(SLOW_FETCH, **counts)
    others = [span("host.gc", slow["mono"] + 0.01, 0.02, generation=2,
                   collected=3)]
    if how != "retraces":
        others.append(span(how, slow["mono"] + 0.01, 0.1, fun_name="step"))
    assert cause_of(steps + others) == "compile"


def test_a_collection_comes_before_any_phase():
    steps, slow = run_of(SLOW_FETCH)
    others = [span("host.gc", slow["mono"] + 0.003, 0.1, generation=2,
                   collected=3)]
    assert cause_of(steps + others) == "gc"


def test_a_long_fetch_with_nothing_over_it_is_the_devices():
    steps, _ = run_of(SLOW_FETCH)
    assert cause_of(steps) == "device_wait"
    chunk = with_phase(CHUNK, "prefill_fetch", 153)
    steps = [
        step(i, 50.0 + 0.041 * i, CHUNK, prefill_tokens=512)
        for i in range(4)
    ] + [step(4, 50.164, chunk, prefill_tokens=512),
         step(5, 50.325, CHUNK, prefill_tokens=512)]
    assert cause_of(steps) == "device_wait"


def test_a_loop_that_did_not_call_step_is_the_callers():
    steps, _ = run_of(gap_after_slow_ms=150.0)
    assert cause_of(steps) == "caller"


@pytest.mark.parametrize("phase", ["admit", "commit", "account",
                                   "decode_launch"])
def test_any_other_phase_is_the_hosts_by_name(phase):
    steps, _ = run_of(with_phase(DECODE, phase, 90))
    assert cause_of(steps) == "host:" + phase


def test_a_pause_elsewhere_explains_nothing():
    steps, slow = run_of(SLOW_FETCH)
    early = pause(steps[0]["mono"] + 0.001, 0.018, 0.0)
    # ... and one that ends exactly where the slow step starts
    before = pause(slow["mono"] - 0.07, 0.07, 0.0)
    assert cause_of(steps + [early, before]) == "device_wait"


def test_the_record_and_the_summary():
    steps, slow = run_of(SLOW_FETCH)
    steps2, slow2 = run_of(gap_after_slow_ms=81.0)
    for s in steps2:  # a second burst, half a minute later
        s["mono"] += 30.0
        s["ts"] += 30.0
        s["attrs"]["idx"] += 100
    spans = steps + steps2 + [pause(slow["mono"] + 0.004, 0.125, 0.001)]
    table = stalls.summary(spans)
    first, second = table["stalls"]
    assert first == {
        "idx": 4, "ts": slow["ts"], "kind": "decode", "cause": "machine",
        "period_s": pytest.approx(0.141), "excess_s": pytest.approx(0.120),
    }
    assert (second["idx"], second["cause"]) == (104, "caller")
    assert second["excess_s"] == pytest.approx(0.080)
    assert table["count"] == {"machine": 1, "caller": 1}
    assert table["excess_s"] == {
        "machine": pytest.approx(0.120), "caller": pytest.approx(0.080),
    }
    # 16 steps have a next one with the next idx: the two bursts' last
    # do not, and no period runs from one burst into the other.
    assert table["steps"] == 16
    assert table["window_s"] == pytest.approx(14 * 0.021 + 0.141 + 0.101)


def test_a_kind_is_judged_against_its_own_median():
    """A chunk step of twice a decode step's period is not a stall; a
    decode step of that period is."""
    steps, mono = [], 50.0
    for i in range(24):
        if i % 3 == 2:
            steps.append(step(i, mono, CHUNK, prefill_tokens=512))
        elif i == 13:
            steps.append(step(
                i, mono, with_phase(DECODE, "decode_fetch", 74),
                n_decoding=8,
            ))
        else:
            steps.append(step(i, mono, DECODE, n_decoding=8))
        mono += steps[-1]["dur_s"] + 1.0 * MS
    (record,) = stalls.stalls(steps)
    assert (record["idx"], record["kind"]) == (13, "decode")
    # and a step of both kinds is a kind of its own
    both = [
        step(100 + i, 80.0 + 0.1 * i, CHUNK + DECODE, prefill_tokens=64,
             n_decoding=8)
        for i in range(5)
    ]
    assert stalls.stalls(steps + both) == [record]


def test_small_excess_is_no_stall():
    # 20 ms steps: 45 ms over the median is under the 50 ms floor ...
    steps, _ = run_of(with_phase(DECODE, "decode_fetch", 59))
    assert stalls.stalls(steps) == []
    # ... and 0.5 s steps need 0.5 s over theirs.
    slow = with_phase(DECODE, "decode_fetch", 494)
    steps = [step(i, 50.0 + 0.501 * i, slow, n_decoding=8)
             for i in range(6)]
    steps.append(step(6, 50.0 + 0.501 * 6,
                      with_phase(DECODE, "decode_fetch", 894), n_decoding=8))
    steps.append(step(7, steps[-1]["mono"] + 0.901, slow, n_decoding=8))
    assert stalls.stalls(steps) == []


@pytest.mark.parametrize("spans", [
    [], [step(0, 50.0, DECODE, n_decoding=8)],
    [span("host.pause", 50.0, 0.2, late_s=0.195, process_cpu_s=0.0)],
])
def test_nothing_on_an_empty_or_one_step_list(spans):
    assert stalls.stalls(spans) == []
    assert stalls.summary(spans) == {
        "stalls": [], "steps": 0, "window_s": 0.0, "excess_s": {},
        "count": {},
    }


def test_steps_that_launched_nothing_and_gaps_in_idx_are_not_judged():
    steps, _ = run_of()
    idle = step(9, steps[-1]["mono"] + 0.021, [("admit", 1), ("account", 1)])
    late = step(10, idle["mono"] + 5.0, DECODE, n_decoding=8)
    assert stalls.stalls(steps + [idle, late]) == []
    # A ring that lost steps 3..6: step 2's "next" is not its own.
    assert stalls.stalls(steps[:3] + steps[7:]) == []


def test_the_window_holds_the_steps_that_end_inside_it():
    steps, slow = run_of(SLOW_FETCH)
    end = slow["ts"] + slow["dur_s"]
    assert len(stalls.stalls(steps, lo=end - 1.0, hi=end + 1.0)) == 1
    assert stalls.stalls(steps, lo=end + 0.001, hi=end + 1.0) == []
    assert stalls.stalls(steps, lo=end - 1.0, hi=end - 0.001) == []
    table = stalls.summary(steps, lo=end - 1.0, hi=end + 1.0)
    assert table["window_s"] == pytest.approx(2.0)


def test_each_process_is_judged_by_itself():
    steps, slow = run_of(SLOW_FETCH)
    other = pause(slow["mono"] + 0.004, 0.125, 0.0)
    other["pid"] = 8  # another process's clock says nothing of this one
    assert cause_of(steps + [other]) == "device_wait"


def test_train_steps_have_overlaps_or_unknown():
    def train(i, mono, dur_s):
        return span("train.step", mono, dur_s, step=i, dp_size=1)

    steps = [train(i, 10.0 + 0.25 * i, 0.249) for i in range(6)]
    steps.append(train(6, 11.5, 0.9))
    steps.append(train(7, 12.4, 0.249))
    (record,) = stalls.stalls(steps, step_name="train.step")
    assert (record["idx"], record["kind"], record["cause"]) == (
        6, "step", "unknown",
    )
    assert record["excess_s"] == pytest.approx(0.65)
    assert stalls.stalls(steps) == []  # no serving.step among them
    (record,) = stalls.stalls(
        steps + [pause(11.6, 0.6, 0.01)], step_name="train.step"
    )
    assert record["cause"] == "machine"


def test_trace_query_prints_the_stall_table(tmp_path, capsys):
    sys.path.insert(
        0, os.path.join(os.path.dirname(__file__), "..", "tools")
    )
    import trace_query

    steps, slow = run_of(SLOW_FETCH)
    more, _ = run_of(with_phase(DECODE, "commit", 75))
    for s in more:
        s["mono"] += 10.0
        s["ts"] += 10.0
        s["attrs"]["idx"] += 50
    spans = steps + more + [
        span("host.watch", 49.0, 0.0, period_s=0.005, min_late_s=0.06),
        pause(slow["mono"] + 0.004, 0.125, 0.001),
    ]
    sink = tmp_path / "spans.jsonl"
    sink.write_text("".join(json.dumps(s) + "\n" for s in spans))
    assert trace_query.main(["--stalls", "--json", str(sink)]) == 0
    table = json.loads(capsys.readouterr().out)
    assert [r["cause"] for r in table["stalls"]] == ["machine", "host:commit"]
    assert table == json.loads(json.dumps(stalls.summary(spans)))
    assert trace_query.main(["--stalls", str(sink)]) == 0
    out = capsys.readouterr().out
    assert "2 stalled; host watcher on" in out
    lines = [ln.split() for ln in out.splitlines()]
    assert ["machine", "1", "0.120", "22.642"] in lines
    assert any(ln[-1] == "host:commit" and ln[1] == "54" for ln in lines)
    # A sink without the watcher's span says so; one without steps fails.
    sink.write_text("".join(json.dumps(s) + "\n" for s in steps))
    assert trace_query.main(["--stalls", str(sink)]) == 0
    assert "NO host.watch span" in capsys.readouterr().out
    sink.write_text(json.dumps(spans[-1]) + "\n")
    assert trace_query.main(["--stalls", str(sink)]) == 1
    assert trace_query.main(
        ["--stalls", "--step-name", "train.step", str(sink)]
    ) == 1
