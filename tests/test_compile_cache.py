"""The process's compile account (common/compile_cache.py): from the
module's import on, JAX's own compile events become a bounded log and,
armed, ``local`` spans on JAX's wall reads; disarmed no span is made,
``enable_compile_cache()`` registers nothing, and one aggregation
(``setup_summary``) reads the log and the spans alike."""

import time

import pytest

import jax
import jax.numpy as jnp
from jax._src import monitoring as jax_monitoring
from jax.experimental.compilation_cache import compilation_cache as jax_cc

from dlrover_tpu.common import compile_cache
from dlrover_tpu.observability import tracing
from dlrover_tpu.observability.tracing import Tracer

pytestmark = pytest.mark.trace


def records_since(t0, event=None):
    return [
        r for r in compile_cache.compile_log()["records"]
        if r["ts"] >= t0 and (event is None or r["event"] == event)
    ]


@pytest.fixture()
def disk_cache(tmp_path, monkeypatch):
    """JAX's persistent cache switched on for one test, in a directory
    of its own, every program kept (thresholds 0): the test process
    otherwise stays off it (tests/conftest.py)."""
    monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, str(tmp_path))
    saved = {
        name: getattr(jax.config, name) for name in (
            "jax_compilation_cache_dir",
            "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
        )
    }
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax_cc.reset_cache()
    try:
        yield tmp_path
    finally:
        for name, value in saved.items():
            jax.config.update(name, value)
        jax_cc.reset_cache()


def fresh_program():
    """A jitted function no other test has compiled, by its name."""
    def compile_cache_probe(x):
        return jnp.tanh(x) @ x + 3.0

    return jax.jit(compile_cache_probe)


def listener_counts(account):
    return [
        listeners.count(ours) for listeners, ours in (
            (jax_monitoring.get_event_listeners(), account._on_event),
            (jax_monitoring.get_event_duration_listeners(),
             account._on_duration),
            (jax_monitoring.get_event_time_span_listeners(),
             account._on_span),
            (jax_monitoring.get_scalar_listeners(), account._on_enter),
        )
    ]


def test_the_import_listens_and_enabling_registers_nothing():
    account = compile_cache._account
    assert listener_counts(account) == [1, 1, 1, 1]
    compile_cache.enable_compile_cache()
    compile_cache.enable_compile_cache()
    assert listener_counts(account) == [1, 1, 1, 1]
    header = compile_cache.compile_log()["header"]
    assert header["capacity"] == compile_cache.LOG_CAPACITY
    assert header["dir"] == compile_cache.compile_cache_dir()
    assert {"entries", "bytes", "dropped", "listening_since"} <= set(
        header
    )


def test_a_compile_before_the_cache_has_a_directory_is_in_the_log(
    tmp_path,
):
    """A runner's weight program, compiled ahead of the engine's
    ``enable_compile_cache()``: recorded, ``uncached``, and the
    directory is looked at once, at the first enable."""
    account = compile_cache._CompileAccount()  # as a fresh import
    tracer = tracing.arm(Tracer(service="test"))
    try:
        assert "dir" not in account.snapshot()["header"]
        fresh_program()(jnp.ones((2, 2))).block_until_ready()
        (tmp_path / "entry").write_bytes(b"x" * 7)
        account.scan_once(str(tmp_path))
        (tmp_path / "later").write_bytes(b"y")
        account.scan_once(str(tmp_path / "elsewhere"))
        fresh_program()(jnp.ones((3, 3))).block_until_ready()
    finally:
        tracing.disarm()
        jax_monitoring.unregister_event_listener(account._on_event)
        jax_monitoring.unregister_event_duration_listener(
            account._on_duration
        )
        jax_monitoring.unregister_event_time_span_listener(
            account._on_span
        )
        jax_monitoring.unregister_scalar_listener(account._on_enter)
    log = account.snapshot()
    early, late = [
        r for r in log["records"] if r["event"] == "backend_compile"
        and r["fun_name"] == "jit(compile_cache_probe)"
    ]
    assert early["cache"] == late["cache"] == "uncached"
    assert log["header"]["dir"] == str(tmp_path)
    assert (log["header"]["entries"], log["header"]["bytes"]) == (1, 7)
    # Both accounts listened, so each span is there twice; only a span
    # made after the scan carries the directory.
    spans = [s for s in tracer.finished()
             if s["name"] == "compile.backend"
             and s["attrs"]["fun_name"] == "jit(compile_cache_probe)"
             and s["attrs"].get("cache_bytes") == 7]
    assert [s["ts"] for s in spans] == [late["ts"]]


def test_the_directory_scan_counts_files_and_bytes(tmp_path):
    assert compile_cache._scan(str(tmp_path / "absent")) == (0, 0)
    (tmp_path / "a").write_bytes(b"x" * 10)
    (tmp_path / "b").write_bytes(b"y" * 32)
    (tmp_path / "sub").mkdir()
    assert compile_cache._scan(str(tmp_path)) == (2, 42)


def test_written_then_hit_and_the_summary_equals_the_logs_sums(disk_cache):
    compile_cache.enable_compile_cache()
    t0 = time.time()
    x = jnp.ones((8, 8))
    fresh_program()(x).block_until_ready()
    # A fresh process's worth of state: nothing compiled in memory,
    # the directory as the first compile left it.
    jax.clear_caches()
    fresh_program()(x).block_until_ready()
    probes = [
        r for r in records_since(t0, "backend_compile")
        if r["fun_name"] == "jit(compile_cache_probe)"
    ]
    assert [r["cache"] for r in probes] == ["written", "hit"]
    assert all(r["requested"] for r in probes)
    assert probes[0]["retrieval_s"] == 0.0 < probes[1]["retrieval_s"]
    # The hit's retrieval lies inside its compile-or-load seconds.
    assert probes[1]["retrieval_s"] <= probes[1]["seconds"]
    loads = records_since(t0, "cache_load")
    assert [r["fun_name"] for r in loads] == ["jit(compile_cache_probe)"]
    assert loads[0]["seconds"] == probes[1]["retrieval_s"]
    assert probes[1]["ts"] <= loads[0]["ts"]
    assert any(disk_cache.iterdir())

    table = compile_cache.setup_summary(records_since(t0))
    backend = records_since(t0, "backend_compile")
    totals = table["totals"]
    assert totals["requested"] == sum(r["requested"] for r in backend)
    assert totals["hit"] == 1
    assert totals["written"] == sum(
        r["cache"] == "written" for r in backend
    )
    assert totals["compile_s"] + totals["cache_load_s"] == pytest.approx(
        sum(r["seconds"] for r in backend)
    )
    assert totals["cache_load_s"] == loads[0]["seconds"]
    assert totals["trace_lower_s"] == pytest.approx(
        sum(r["seconds"] for r in records_since(t0)
            if r["event"] in ("trace", "lower"))
    )
    # jit(f), as compile names it, and f, as its trace does: one row.
    row = next(r for r in table["programs"]
               if r["name"] == "compile_cache_probe")
    assert (row["hit"], row["written"], row["uncached"]) == (1, 1, 0)
    assert row["trace_lower_s"] > 0 and row["saved_s"] == pytest.approx(
        loads[0]["saved_s"]
    )
    assert table["engine"] == [] and table["cache"] == {}


def test_a_function_traced_inside_anothers_trace_is_not_counted():
    compile_cache.enable_compile_cache()

    @jax.jit
    def compile_cache_inner(x):
        return x * 2.0

    @jax.jit
    def compile_cache_outer(x):
        return compile_cache_inner(x) + jnp.where(x > 0, x, 0.0)

    t0 = time.time()
    compile_cache_outer(jnp.ones(4)).block_until_ready()
    traces = [r["fun_name"] for r in records_since(t0, "trace")]
    assert "compile_cache_outer" in traces
    assert "compile_cache_inner" not in traces
    assert compile_cache._account._state()["depth"] == 0
    assert [
        r["cache"] for r in records_since(t0, "backend_compile")
        if r["fun_name"] == "jit(compile_cache_outer)"
    ] == ["uncached"]  # the test process keeps the disk cache off


def test_the_log_is_bounded():
    compile_cache.enable_compile_cache()
    dropped = compile_cache.compile_log()["header"]["dropped"]
    held = len(compile_cache.compile_log()["records"])
    n = compile_cache.LOG_CAPACITY + 5
    now = time.time()
    for i in range(n):
        jax_monitoring.record_event_time_span(
            compile_cache._BACKEND, now, now + 1e-3, fun_name=f"fake{i}"
        )
    log = compile_cache.compile_log()
    assert len(log["records"]) == compile_cache.LOG_CAPACITY
    assert log["records"][-1]["fun_name"] == f"fake{n - 1}"
    assert log["header"]["dropped"] == (
        dropped + held + n - compile_cache.LOG_CAPACITY
    )


def test_armed_the_three_spans_are_local_and_on_the_wall_clock(disk_cache):
    compile_cache.enable_compile_cache()
    x = jnp.ones((8, 8))
    fresh_program()(x).block_until_ready()  # stored, unarmed
    jax.clear_caches()
    tracer = tracing.arm(Tracer(service="test"))
    t0 = time.time()
    try:
        fresh_program()(x).block_until_ready()
    finally:
        tracing.disarm()
    t1 = time.time()
    spans = [
        s for s in tracer.finished() if s["name"].startswith("compile.")
    ]
    assert {s["name"] for s in spans} == {
        "compile.backend", "compile.cache_load", "compile.trace_lower",
    }
    assert all(t0 <= s["ts"] <= s["ts"] + s["dur_s"] <= t1 for s in spans)
    # local: ring (and sink) only, never the export buffer.
    assert not [
        s for s in tracer.drain_exports(10 ** 6)
        if s["name"].startswith("compile.")
    ]
    backend = [s for s in spans if s["name"] == "compile.backend"
               and s["attrs"]["fun_name"] == "jit(compile_cache_probe)"]
    assert [s["attrs"]["cache"] for s in backend] == ["hit"]
    assert backend[0]["attrs"]["cache_entries"] >= 0
    load = next(s for s in spans if s["name"] == "compile.cache_load")
    # The load nests inside the backend span of the same program.
    assert backend[0]["ts"] <= load["ts"]
    assert load["ts"] + load["dur_s"] <= (
        backend[0]["ts"] + backend[0]["dur_s"] + 1e-3
    )
    assert {s["attrs"]["stage"] for s in spans
            if s["name"] == "compile.trace_lower"} == {"trace", "lower"}
    # A span's start is JAX's own wall read: the log's, to the digit.
    logged = {r["ts"] for r in records_since(t0)}
    assert {s["ts"] for s in spans} <= logged
    # So a sink's spans and the log give the one aggregation the same
    # table; the spans carry the directory a sink has no header for.
    from_spans = compile_cache.setup_summary(
        compile_cache.records_from_spans(tracer.finished())
    )
    from_log = compile_cache.setup_summary(records_since(t0))
    assert from_spans["programs"] == from_log["programs"]
    assert from_spans["totals"]["hit"] == 1
    assert set(from_spans["cache"]) == {"entries", "bytes"}


def test_disarmed_no_span_is_made():
    compile_cache.enable_compile_cache()
    tracer = Tracer(service="test")  # never armed
    t0 = time.time()
    fresh_program()(jnp.ones((4, 4))).block_until_ready()
    assert tracing.active_tracer() is None
    assert tracer.finished() == []
    assert records_since(t0, "backend_compile")  # the log still counts
