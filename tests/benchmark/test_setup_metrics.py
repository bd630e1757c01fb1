"""The five readers of set-up (``benchmark/setup_spans.py`` and its
``layer_metrics``): on a hand-built compile log and hand-built spans
with known answers, on facts with nothing to read, and through the
serve and train runners at tiny size."""

import time

import jax
import pytest

from benchmark import common, run as bench_run, setup_spans
from tests.benchmark import tiny

T0 = 1.79e9           # ctx["t_start"]
SETUP_S = 60.0        # the window opens at T0 + 60
READERS = (
    "setup_compile_s", "setup_cache_load_s", "setup_cache_hit_pct",
    "setup_trace_lower_s", "engine_build_s",
)


def read(name, facts):
    return bench_run.load_module("layer_metrics", name).read(facts)


def backend(at, seconds, fun_name, cache, requested=True, retrieval_s=0.0):
    return {"ts": T0 + at, "mono": at, "event": "backend_compile",
            "seconds": seconds, "fun_name": fun_name, "cache": cache,
            "requested": requested, "retrieval_s": retrieval_s}


def other(at, seconds, event, fun_name):
    return {"ts": T0 + at, "mono": at, "event": event,
            "seconds": seconds, "fun_name": fun_name}


def span(name, at, phases, **attrs):
    laid, cursor = [], 0.0
    for phase, seconds in phases:
        laid.append([phase, cursor, seconds])
        cursor += seconds
    return {"name": name, "ts": T0 + at, "mono": at, "dur_s": cursor,
            "status": "ok", "attrs": dict(attrs, phases=laid)}


# Before the window: the step compiled and stored (20 s), the engine's
# decode program loaded from the cache (5 s in compile-or-load, 4.5 of
# it the retrieval), a tiny program compiled and not kept, one compile
# that never asked the cache. After it: the reference's program.
RECORDS = [
    other(1.0, 2.0, "trace", "step"),
    other(3.0, 1.0, "lower", "jit(step)"),
    backend(4.0, 20.0, "jit(step)", "written"),
    backend(30.0, 5.0, "jit(decode)", "hit", retrieval_s=4.5),
    dict(other(30.2, 4.5, "cache_load", "jit(decode)"), saved_s=12.0),
    backend(40.0, 0.25, "jit(broadcast_in_dim)", "uncached"),
    backend(41.0, 0.5, "jit(convert)", "uncached", requested=False),
    other(95.0, 3.0, "trace", "logits_at"),
    backend(98.0, 30.0, "jit(logits_at)", "written"),
    other(99.0, 1.0, "cache_load", "jit(late)"),
]
SPANS = [
    span("serving.engine_build", 20.0,
         [("prefix_cache", 0.1), ("fuse_params", 2.0),
          ("build_programs", 0.1), ("alloc_pool", 0.3),
          ("host_state", 0.0)],
         params_bytes=100, pool_bytes=40, index_pool_bytes=0),
    span("serving.warmup", 23.0,
         [("prefill", 3.0), ("decode", 5.5), ("decode", 0.1),
          ("reset_pool", 0.1)]),
    span("serving.step", 70.0, [("admit", 0.001)]),
    span("serving.warmup", 120.0, [("prefill", 9.0)]),  # a later engine
]


KV_STATS = {"engine_build_s": 2.5, "warmup_s": 8.7, "used": 3}


def facts(records=RECORDS, spans=SPANS, **more):
    out = {
        "kv_stats": dict(KV_STATS),
        "ctx": {"t_start": T0},
        "end_to_end": {"setup_s": SETUP_S},
        "window": {"seconds": 30.0},
        "compile_log": {"header": {"dir": "/c", "entries": 3, "bytes": 9,
                                   "dropped": 0},
                        "records": list(records)},
        "spans": list(spans), "events": [],
    }
    out.update(more)
    return out


def test_records_after_the_windows_start_are_left_out():
    f = facts()
    # 20 + (5 - 4.5) + 0.25 + 0.5; the reference's 30 s came later.
    assert read("setup_compile_s", f) == pytest.approx(21.25)
    assert read("setup_cache_load_s", f) == pytest.approx(4.5)
    assert read("setup_trace_lower_s", f) == pytest.approx(3.0)
    # Facts that carry no window are read whole.
    whole = facts(ctx=None)
    assert read("setup_compile_s", whole) == pytest.approx(51.25)
    assert read("setup_cache_load_s", whole) == pytest.approx(5.5)
    assert setup_spans.table(whole)["records"]["after_window"] == 0


def test_the_engines_seconds_are_its_own_floats():
    # One source in both serve cells, timed and traced: kv_stats.
    assert read("engine_build_s", facts()) == pytest.approx(11.2)
    assert read("engine_build_s", facts(spans=[])) == pytest.approx(11.2)
    not_warmed = facts(kv_stats={"engine_build_s": 2.5})
    assert read("engine_build_s", not_warmed) == pytest.approx(2.5)
    assert read("engine_build_s", facts(kv_stats={"used": 3})) is None
    # The spans give the table its phases, where there are spans: the
    # first engine's, not the one built after the window.
    assert [e["dur_s"] for e in setup_spans.table(facts())["engine"]] == [
        pytest.approx(2.5), pytest.approx(8.7),
    ]
    assert setup_spans.table(facts(spans=[SPANS[2]]))["engine"] == []


def test_a_hits_retrieval_is_not_counted_twice():
    only_hit = facts(records=RECORDS[3:5])
    assert read("setup_compile_s", only_hit) == pytest.approx(0.5)
    assert read("setup_cache_load_s", only_hit) == pytest.approx(4.5)
    total = (read("setup_compile_s", only_hit)
             + read("setup_cache_load_s", only_hit))
    assert total == pytest.approx(RECORDS[3]["seconds"])


def test_hit_share_counts_the_compiles_that_asked():
    # Three asked (written, hit, uncached); the fourth did not.
    assert read("setup_cache_hit_pct", facts()) == pytest.approx(100 / 3)
    cold = facts(records=[RECORDS[2], RECORDS[5]])
    assert read("setup_cache_hit_pct", cold) == 0.0


def test_no_cache_request_gives_none():
    nobody_asked = facts(records=[RECORDS[0], RECORDS[6]])
    assert read("setup_cache_hit_pct", nobody_asked) is None
    assert read("setup_compile_s", nobody_asked) == pytest.approx(0.5)
    assert read("setup_cache_load_s", nobody_asked) == 0


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_account_gives_nothing(name, monkeypatch):
    """The parent of the PR that added the log and the spans: every
    reader returns None and none raises."""
    from dlrover_tpu.common import compile_cache

    monkeypatch.delattr(compile_cache, "compile_log")
    bare = {"ctx": {"t_start": T0}, "end_to_end": {"setup_s": SETUP_S},
            "spans": [SPANS[2]], "events": []}
    assert read(name, bare) is None
    assert read(name, {"events": []}) is None
    assert bare["events"] == []


def test_the_table_is_left_among_the_events_once():
    f = facts()
    read("setup_compile_s", f)
    read("setup_compile_s", f)
    (table,) = common.by_event(f["events"], setup_spans.TABLE_EVENT)
    assert table["cache"]["entries"] == 3
    assert table["records"] == {
        "before_window": 7, "in_window": 0, "after_window": 3,
    }
    totals = table["totals"]
    assert (totals["requested"], totals["hit"], totals["written"],
            totals["uncached"]) == (3, 1, 1, 2)
    # The scalars are the table's totals.
    assert totals["compile_s"] == read("setup_compile_s", f)
    assert totals["cache_load_s"] == read("setup_cache_load_s", f)
    assert totals["trace_lower_s"] == read("setup_trace_lower_s", f)
    assert table["after_window_backend_s"] == pytest.approx(30.0)
    rows = {r["name"]: r for r in table["programs"]}
    # slowest first; jit(step) == step
    assert table["programs"][0]["name"] == "step"
    assert rows["step"] == {
        "name": "step", "compile_s": 20.0, "cache_load_s": 0,
        "saved_s": 0, "trace_lower_s": 3.0, "requested": 1, "hit": 0,
        "written": 1, "uncached": 0,
    }
    assert rows["decode"]["cache_load_s"] == pytest.approx(4.5)
    assert rows["decode"]["compile_s"] == pytest.approx(0.5)
    assert rows["decode"]["saved_s"] == pytest.approx(12.0)
    assert "logits_at" not in rows
    assert [e["name"] for e in table["engine"]] == [
        "serving.engine_build", "serving.warmup",
    ]
    assert table["engine"][0]["params_bytes"] == 100
    assert table["engine"][1]["phases"][1] == ["decode", 5.5]


def test_many_programs_fold_into_one_row():
    records = [
        backend(float(i), 1.0 + i, f"jit(f{i})", "uncached")
        for i in range(setup_spans.TOP_FUN_NAMES + 3)
    ]
    table = setup_spans.table(facts(records=records))
    rows = table["programs"]
    assert len(rows) == setup_spans.TOP_FUN_NAMES + 1
    assert rows[-1]["name"] == "(other)" and rows[-1]["fun_names"] == 3
    assert rows[-1]["compile_s"] == pytest.approx(1.0 + 2.0 + 3.0)
    assert table["totals"]["compile_s"] == pytest.approx(
        sum(r["seconds"] for r in records)
    )


@pytest.mark.parametrize("trace", [0, 1], ids=["timed", "traced"])
def test_through_the_serve_runner(tmp_path, trace):
    # Whatever this worker compiled before is forgotten and left out
    # of the log the readers see: the run compiles its own programs.
    jax.clear_caches()
    t_before = time.time()
    ctx = tiny.context("chat-closed", tmp_path, trace=trace, seconds=0.5)
    f = dict(bench_run.load_module("runners", "serve").run(ctx), ctx=ctx)
    log = setup_spans.compile_log({})
    f["compile_log"] = dict(log, records=[
        r for r in log["records"] if r["ts"] >= t_before
    ])
    setup_s = f["end_to_end"]["setup_s"]
    compiled = read("setup_compile_s", f)
    loaded = read("setup_cache_load_s", f)
    assert compiled > 0 and loaded == 0  # tests keep the disk cache off
    assert read("setup_trace_lower_s", f) > 0
    assert read("setup_cache_hit_pct", f) is None
    # A timed run's result file carries the engine's own floats; only a
    # traced run has the spans.
    assert f["kv_stats"]["engine_build_s"] > 0
    assert f["kv_stats"]["warmup_s"] > 0
    built = read("engine_build_s", f)
    assert built == pytest.approx(
        f["kv_stats"]["engine_build_s"] + f["kv_stats"]["warmup_s"]
    )
    assert built <= setup_s
    (table,) = common.by_event(f["events"], setup_spans.TABLE_EVENT)
    if trace:  # only a traced run has the spans, and so the phases
        assert sum(e["dur_s"] for e in table["engine"]) == (
            pytest.approx(built)
        )
    # The runner fails a run that compiles in its window; the account
    # agrees, and sees the reference compile after it.
    assert table["records"]["in_window"] == 0
    assert table["records"]["after_window"] > 0
    programs = {r["name"] for r in table["programs"]}
    # The runner's weight program compiles before the engine's
    # constructor gives the cache a directory: the account, open since
    # its import, holds it all the same.
    assert {"prefill", "step", "<lambda>"} <= programs
    assert "logits_at" not in programs


def test_through_the_train_runner(tmp_path):
    jax.clear_caches()
    ctx = tiny.context("pretrain-4k", tmp_path, trace=1)
    f = dict(bench_run.load_module("runners", "train").run(ctx), ctx=ctx)
    assert read("setup_compile_s", f) > 0
    assert read("setup_trace_lower_s", f) > 0
    assert read("engine_build_s", f) is None  # no engine, no floats
    (table,) = common.by_event(f["events"], setup_spans.TABLE_EVENT)
    assert table["records"]["in_window"] == 0
    assert "step" in {r["name"] for r in table["programs"]}
