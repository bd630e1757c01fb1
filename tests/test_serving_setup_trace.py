"""The engines time their own construction and warm-up (docs/DESIGN.md
§29): plain floats on the engine armed or not, and armed one ``local``
span each, ``serving.engine_build`` and ``serving.warmup``, whose phases
tile it, with the ``compile.*`` spans of the programs inside by time."""

import json
import os
import sys

import jax
import pytest

from benchmark import common
from benchmark.runners import serve_sparse
from dlrover_tpu.models import llama, sparse_lm
from dlrover_tpu.observability import tracing
from dlrover_tpu.observability.registry import MetricsRegistry
from dlrover_tpu.observability.tracing import Tracer
from dlrover_tpu.serving.engine import BUILD_PHASES, ServingEngine
from dlrover_tpu.serving.kvpool import PagedServingEngine
from tests.benchmark import tiny, tiny_keye

pytestmark = pytest.mark.trace

KINDS = ("flat", "paged", "paged_sparse", "speculative")
WARMUP_PHASES = {
    "flat": ["prefill", "decode", "decode", "reset_pool"],
    "paged": ["prefill", "decode", "decode", "cow", "imp", "exp",
              "reset_pool"],
    "speculative": ["prefill", "decode", "decode", "verify", "reset_pool"],
}
WARMUP_PHASES["paged_sparse"] = WARMUP_PHASES["paged"]


@pytest.fixture(scope="module")
def parts():
    """(config, params, engine kwargs) at the benchmark's tiny sizes."""
    dense = common.lm_config(tiny.CONFIG)
    sparse = serve_sparse.sparse_config(tiny_keye.CONFIG)
    eng, seng = tiny.CONFIG["serve_engine"], tiny_keye.CONFIG["serve_engine"]
    flat = dict(slots=eng["slots"], max_len=eng["max_len"],
                prefill_chunk=eng["prefill_chunk"])
    return {
        "dense": (dense, llama.init_params(dense, jax.random.key(0))[0]),
        "sparse": (sparse,
                   sparse_lm.init_params(sparse, jax.random.key(1))),
        "flat": flat,
        "paged": dict(flat, block_size=eng["block_size"]),
        "paged_sparse": dict(
            slots=seng["slots"], max_len=seng["max_len"],
            prefill_chunk=seng["prefill_chunk"],
            block_size=seng["block_size"], num_blocks=seng["num_blocks"],
        ),
    }


def build(kind, parts):
    if kind == "paged_sparse":
        cfg, params = parts["sparse"]
        return PagedServingEngine(
            cfg, params, registry=MetricsRegistry(), **parts[kind]
        )
    cfg, params = parts["dense"]
    if kind == "paged":
        return PagedServingEngine(
            cfg, params, registry=MetricsRegistry(), **parts[kind]
        )
    return ServingEngine(
        cfg, params, registry=MetricsRegistry(),
        spec_k=2 if kind == "speculative" else 0, **parts["flat"]
    )


@pytest.fixture()
def tracer():
    t = tracing.arm(Tracer(service="test"))
    yield t
    tracing.disarm()


def named(tracer, name):
    return [s for s in tracer.finished() if s["name"] == name]


@pytest.mark.parametrize("kind", KINDS)
def test_build_and_warmup_phases_tile_their_spans(kind, parts, tracer):
    eng = build(kind, parts)
    assert eng.warmup_s == 0.0 and not named(tracer, "serving.warmup")
    eng.warmup()
    (built,) = named(tracer, "serving.engine_build")
    (warm,) = named(tracer, "serving.warmup")
    for span, seconds in ((built, eng.engine_build_s),
                          (warm, eng.warmup_s)):
        phases = span["attrs"]["phases"]
        assert span["dur_s"] == pytest.approx(seconds, abs=1e-9)
        assert sum(p[2] for p in phases) == pytest.approx(
            span["dur_s"], abs=1e-6
        )
        cursor = 0.0
        for _name, offset, dur in phases:  # contiguous, in order
            assert offset == pytest.approx(cursor, abs=1e-6) and dur >= 0
            cursor += dur
    # Each phase once, in the constructors' order; the paged engine's
    # own open and close the base constructor's.
    build_names = [p[0] for p in built["attrs"]["phases"]]
    flat = ("fuse_params", "build_programs", "alloc_pool", "host_state")
    assert build_names == [
        p for p in BUILD_PHASES if kind.startswith("paged") or p in flat
    ]
    assert [p[0] for p in warm["attrs"]["phases"]] == WARMUP_PHASES[kind]
    # A phase is named as trace_counts names its program.
    programs = set(WARMUP_PHASES[kind]) - {"reset_pool"}
    assert programs <= set(eng.trace_counts)
    # Warm-up follows construction and both are set-up: the engine has
    # served nothing, and the spans stayed in the process.
    assert built["ts"] + built["dur_s"] <= warm["ts"] + 1e-3
    assert not [
        s for s in tracer.drain_exports(10 ** 6)
        if s["name"] in ("serving.engine_build", "serving.warmup")
    ]


@pytest.mark.parametrize("kind", KINDS)
def test_the_build_span_carries_the_sizes(kind, parts, tracer):
    eng = build(kind, parts)
    attrs = named(tracer, "serving.engine_build")[0]["attrs"]
    leaves = jax.tree_util.tree_leaves(eng._params)
    assert attrs["params_bytes"] == sum(x.nbytes for x in leaves) > 0
    assert attrs["pool_bytes"] == eng._k.nbytes + eng._v.nbytes > 0
    if kind == "paged_sparse":
        assert attrs["index_pool_bytes"] == eng._ki.nbytes > 0
        assert attrs["index_pool_bytes"] == (
            eng.kv_stats()["index_pool_bytes"]
        )
    else:
        assert attrs["index_pool_bytes"] == 0


@pytest.mark.parametrize("kind", KINDS)
def test_the_compile_spans_fall_inside_warmup(kind, parts, tracer):
    eng = build(kind, parts)
    # A shape nobody in this process has warmed: the programs compile.
    jax.clear_caches()
    eng.warmup()
    warm = named(tracer, "serving.warmup")[-1]
    lo, hi = warm["ts"], warm["ts"] + warm["dur_s"]
    inside = [
        s for s in named(tracer, "compile.backend")
        if lo - 1e-3 <= s["ts"] and s["ts"] + s["dur_s"] <= hi + 1e-3
    ]
    compiled = {s["attrs"]["fun_name"] for s in inside}
    assert {"jit(prefill)", "jit(step)"} <= compiled or {
        "jit(prefill)", "jit(decode)"} <= compiled, compiled


@pytest.mark.parametrize("kind", KINDS)
def test_disarmed_the_floats_are_kept_and_no_span_is_made(
    kind, parts, monkeypatch
):
    def refuse(*_a, **_kw):
        raise AssertionError("a span was recorded with no Tracer armed")

    monkeypatch.setattr(Tracer, "record_span", refuse)
    assert tracing.active_tracer() is None
    eng = build(kind, parts)
    eng.warmup()
    assert eng.engine_build_s > 0.0 and eng.warmup_s > 0.0
    if kind.startswith("paged"):
        stats = eng.kv_stats()
        assert stats["engine_build_s"] == eng.engine_build_s
        assert stats["warmup_s"] == eng.warmup_s


def test_trace_query_tables_a_replicas_start(parts, tmp_path, capsys):
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools",
    ))
    import trace_query

    sink = str(tmp_path / "spans.jsonl")
    tracing.arm(Tracer(service="replica", sink_path=sink))
    try:
        eng = build("paged", parts)
        jax.clear_caches()  # so that warm-up compiles, as a start does
        eng.warmup()
    finally:
        tracing.disarm()  # closes the sink: local spans are flushed
    assert trace_query.main(["--setup", "--json", sink]) == 0
    table = json.loads(capsys.readouterr().out)
    rows = {r["name"]: r for r in table["programs"]}
    assert {"prefill", "step", "cow", "imp", "exp"} <= set(rows)
    for name in ("prefill", "step"):
        row = rows[name]  # jit(prefill) and its trace are one row
        assert row["compile_s"] > 0 and row["trace_lower_s"] > 0
        assert (row["hit"], row["written"], row["uncached"]) == (0, 0, 1)
    assert table["totals"]["uncached"] == sum(
        r["uncached"] for r in table["programs"]
    )
    assert table["totals"]["compile_s"] <= eng.warmup_s
    assert set(table["cache"]) == {"entries", "bytes"}
    build_row, warm_row = table["engine"]
    assert build_row["name"] == "serving.engine_build"
    assert build_row["dur_s"] == pytest.approx(eng.engine_build_s)
    assert build_row["pool_bytes"] == eng._k.nbytes + eng._v.nbytes
    assert [p[0] for p in warm_row["phases"]] == WARMUP_PHASES["paged"]
    assert trace_query.main(["--setup", sink]) == 0
    out = capsys.readouterr().out
    assert "cache directory at start" in out and "fuse_params" in out
    empty = tmp_path / "other.jsonl"
    empty.write_text(json.dumps({"name": "serving.step", "dur_s": 1.0,
                                 "attrs": {"phases": []}}) + "\n")
    assert trace_query.main(["--setup", str(empty)]) == 1
