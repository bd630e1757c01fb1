"""``lfm2-serve-sessions-8k`` off the chip: the cell finds its files and
states its cut, the program's parameter tree holds what the configuration
says, the runner works end to end at tiny size (timed and traced), a run
whose hits restore nothing and each planted fault of ``controls_lfm2.py``
fail ``correct``, the traffic is what the cell states, each new reader
reads a hand-made dump, and the counts behind the roofline shares are
hand numbers."""

import jax
import numpy as np
import pytest

from benchmark import common, conv_scopes, flops_lfm2, run as bench_run
from tests.benchmark import tiny_lfm2

CELL = "lfm2-serve-sessions-8k"
NEW_READERS = (
    "conv_serve_expert_ms_per_step", "conv_serve_expert_roofline",
    "gqa64_attn_ms_per_step", "gqa64_attn_roofline", "conv_mix_ms_per_step",
    "conv_mix_roofline", "conv_chunk_expert_ms_per_chunk",
    "state_restore_ms_p50", "state_snapshots_per_request_mean",
    "conv_experts_hit_per_layer_mean", "conv_prefix_hit_token_share_pct",
)
# Accepted readers whose lists an accepted test pins by position or to
# one cell: the cell reports their quantities under a name of its own.
TWINS = {
    "conv_idle_attributed_pct": "idle_attributed_pct",
    "conv_decode_batch_mean": "decode_batch_mean",
    "conv_engine_build_s": "engine_build_s",
    "conv_setup_compile_s": "setup_compile_s",
    "conv_decode_unscoped_ms_per_step": "decode_unscoped_ms_per_step",
}
SHARED = (
    "decode_ms_per_token_p50", "prefill_ms_per_ktoken_p50",
    "prefill_program_share_pct", "step_host_serial_ms_p50",
    "prefill_step_share_pct", "slot_wait_ms_p50", "router_queue_ms_p50",
    "replica_loop_ms_p50", "setup_cache_hit_pct",
)


@pytest.fixture(scope="module")
def manifest():
    return common.load_manifest()


@pytest.fixture(scope="module")
def cell(manifest):
    return bench_run.cell_context(
        manifest, CELL, 3, 30, 0, require_tpu=False
    )


@pytest.fixture(scope="module")
def runner():
    return bench_run.load_module("runners", "serve_conv")


def test_the_cell_finds_its_files_and_states_its_cut(manifest, cell, runner):
    cfg_json = cell["config"]
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "lfm2-24b-a2b")
    assert entry["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "layer_types",
    ]
    assert cfg_json["published"]["num_hidden_layers"] == 40
    assert cfg_json["published"]["num_dense_layers"] == 2
    published = cfg_json["published"]["layer_types"]
    assert len(published) == 40 and published.count("full_attention") == 10
    assert cfg_json["layer_types"] == published[1:10]
    assert (cfg_json["num_hidden_layers"], cfg_json["num_dense_layers"]) \
        == (9, 1)
    # every published width, head count, expert count and the vocabulary
    for key, value in {
        "hidden_size": 2048, "intermediate_size": 11776,
        "num_attention_heads": 32, "num_key_value_heads": 8,
        "num_experts": 64, "num_experts_per_tok": 4,
        "moe_intermediate_size": 1536, "routed_scaling_factor": 1,
        "vocab_size": 65536, "conv_L_cache": 3, "conv_bias": False,
        "norm_eps": 1e-05, "norm_topk_prob": True, "use_expert_bias": True,
        "max_position_embeddings": 128000, "model_type": "lfm2_moe",
        "head_dim": 64, "rope_theta": 1000000,
    }.items():
        assert cfg_json[key] == value, key
    assert cfg_json["rope_parameters"] == {
        "rope_theta": 1000000, "rope_type": "default",
    }
    assert "tie_word_embeddings" not in cfg_json and cfg_json["tied_head"]
    for name in ("head_dim", "tied_head", "conv_order", "rope_pairing",
                 "router_denominator", "norm", "weights", "serve_engine"):
        assert name in cfg_json["assumed"], name
    assert "9 of the 40 layers" in cfg_json["deployment"]
    assert cfg_json["serve_engine"] == {
        "slots": 32, "max_len": 9216, "prefill_chunk": 512,
        "block_size": 64, "num_blocks": 5120,
    }
    assert cell["traffic"]["runner"] == "serve_conv"
    cfg = runner.conv_config(cfg_json)
    assert (cfg.n_layers, cfg.n_dense, cfg.n_experts, cfg.moe_top_k) \
        == (9, 1, 64, 4)
    assert (len(cfg.conv_layers), cfg.cache_layers) == (7, 2)
    for name in NEW_READERS:
        m = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert CELL in m["workloads"] and m["moves"] == "serve_tokens_per_s"
    for name in SHARED:
        m = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert CELL in m["workloads"]
    keys = ("unit", "better", "source", "layer", "moves")
    for name, accepted in TWINS.items():
        by_name = {m["name"]: m for m in manifest["per_layer"]}
        assert by_name[name]["workloads"] == [CELL]
        assert [by_name[name][k] for k in keys] \
            == [by_name[accepted][k] for k in keys]
        assert CELL not in by_name[accepted]["workloads"]
    served = next(
        m for m in manifest["end_to_end"] if m["name"] == "serve_tokens_per_s"
    )
    assert CELL in served["workloads"]


def test_parameter_count_from_the_programs_tree(cell, runner):
    """5,177.9 M: the tree ``init_params`` would build, the config's own
    count and the benchmark's count from the published keys agree."""
    from dlrover_tpu.models import conv_lm

    cfg = runner.conv_config(cell["config"])
    tree = jax.eval_shape(
        lambda k: conv_lm.init_params(cfg, k, dtype=cfg.compute_dtype),
        jax.random.key(0),
    )
    leaves = jax.tree_util.tree_leaves(tree)
    n = sum(int(np.prod(x.shape)) for x in leaves)
    assert n == cfg.count_params() == flops_lfm2.parameter_count(
        cell["config"]
    )
    assert n == pytest.approx(5.1779e9, rel=1e-4)
    in_bf16 = sum(
        int(np.prod(x.shape)) for x in leaves if x.dtype == "bfloat16"
    )
    assert 0.999 < in_bf16 / n < 1     # routers, norms, the filters are f32
    # weights + pool + state fill the chip as the configuration file says
    eng = cell["config"]["serve_engine"]
    rows = eng["num_blocks"] * eng["block_size"]
    assert flops_lfm2.cache_bytes_per_token(cell["config"]) == 4096
    assert flops_lfm2.state_bytes_per_slot(cell["config"]) == 57344
    pool = rows * 4096
    assert pool == pytest.approx(1.34e9, rel=1e-2)
    assert 11.6e9 < 2 * n + pool < 11.8e9


def test_traffic_is_what_the_cell_states(cell):
    from benchmark.runners import serve_latent, serve_sparse

    traffic = serve_latent.as_documents(cell["traffic"])
    assert traffic["clients"] == 64 and traffic["temperature"] == 0.0
    eng = cell["config"]["serve_engine"]
    stream = serve_sparse.request_stream(traffic, 65536, 5)
    head = [next(stream) for _ in range(100)]
    contexts = serve_sparse.documents(traffic, 65536, 5)
    assert contexts.shape == (32, 8192)
    for i, (prompt, n_new) in enumerate(head):
        assert prompt[:8192] == contexts[i % 32].tolist()  # fixed rotation
        assert 64 <= len(prompt) - 8192 <= 512 and 16 <= n_new <= 128
    assert max(len(p) + n for p, n in head) <= eng["max_len"]
    # a context is whole chunks and blocks: its snapshot is its last
    # chunk's end, and a hit resumes there
    assert 8192 % eng["prefill_chunk"] == 0 == 8192 % eng["block_size"]
    need = 32 * 8192 // eng["block_size"] + eng["slots"] * (
        -(-(512 + 128) // eng["block_size"])
    ) + eng["max_len"] // eng["block_size"]
    assert eng["num_blocks"] > need


def _check_float32(ref):
    """float32 against float32: every reading at rounding."""
    assert ref["logit_deficit_max"] <= 1e-4
    assert ref["logit_within_share"] == 1.0
    assert ref["state_err_median"] < 1e-5 < ref["low_state_err_median"]
    assert ref["snapshot_err_median"] < 1e-5 and ref["n_snapshots_read"]
    # every layer's landed state, snapshot and rows after a hit, free
    # running: float32 does not drift, the precision below does
    assert ref["state_err_all_layers_median"] < 1e-5 \
        < ref["low_state_err_all_layers_median"]
    assert ref["snapshot_err_all_layers_median"] < 1e-5 \
        < ref["low_snapshot_err_all_layers_median"]
    assert len(ref["rows_after_hit_err_median_by_layer"]) == 2
    assert ref["rows_after_hit_err_median_max"] < 1e-5 \
        < min(ref["low_rows_after_hit_err_median_by_layer"])
    # float32 routes as the reference does, so no row is told apart; the
    # reference in the precision below misses the tolerance somewhere
    assert ref["n_rows_flip_judged"] and ref["route_flip_row_share"] == 0.0
    assert ref["logit_within_share_unflipped"] == 1.0
    assert ref["low_logit_within_share"] < 1.0
    assert ref["k_rows_err_median"] < 1e-5 < ref["low_k_rows_err_median"]
    assert ref["v_rows_err_median"] < 1e-5 < ref["low_v_rows_err_median"]
    assert ref["rows_after_hit_err_median"] < 1e-5
    assert ref["conv_err_median_max"] < 1e-5 < ref["low_conv_err_median_min"]
    assert ref["attn_err_median_max"] < 1e-5 < ref["low_attn_err_median_min"]
    assert ref["h_err_median_max"] < 1e-5
    assert ref["mlp_err_median_max"] < 1e-5 < ref["low_mlp_err_median_min"]
    assert ref["alike_share_min"] == 1.0 and ref["weight_err_max"] < 1e-6
    assert all(ref["hits_restored"])


@pytest.mark.parametrize("trace", [0, 1], ids=["timed", "traced"])
def test_runner_rehearsal(manifest, runner, tmp_path, trace):
    from tests.benchmark.test_harness import _for_cell, _line

    ctx = tiny_lfm2.context(tmp_path, trace=trace)
    facts = runner.run(ctx)
    cell = _for_cell(manifest, {
        "serve_tokens_per_s", "decode_ms_per_token_p50",
        "prefill_step_share_pct", *NEW_READERS, *TWINS,
    })
    line, problems = _line(cell, ctx, facts)
    assert problems == [] and line["correct"]
    assert line["failed"] == 0 and line["attempted"] > 6
    if trace:
        # The CPU has no device plane: the device-time readers find
        # nothing to read and are left out; spans and counts report.
        assert set(line["metrics"]) == {
            "decode_ms_per_token_p50", "prefill_step_share_pct",
            "state_restore_ms_p50", "state_snapshots_per_request_mean",
            "conv_experts_hit_per_layer_mean",
            "conv_prefix_hit_token_share_pct",
            "conv_decode_batch_mean", "conv_engine_build_s",
            "conv_setup_compile_s",
        }
        m = {k: v["value"] for k, v in line["metrics"].items()}
        assert 1 <= m["conv_decode_batch_mean"] <= 4
        assert m["conv_engine_build_s"] > 0
        assert any(e.get("event") == "setup_table" for e in facts["events"])
        assert 1 <= m["conv_experts_hit_per_layer_mean"] <= 8
        assert 60 < m["conv_prefix_hit_token_share_pct"] < 100
        assert 0 < m["state_snapshots_per_request_mean"] <= 1
        assert m["state_restore_ms_p50"] > 0
        steps = [s for s in facts["spans"] if s["name"] == "serving.step"]
        assert any("expert_rows_dropped" in s["attrs"] for s in steps)
    else:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert facts["prefix"]["sessions_cached_blocks"] == 3 * 32 // 4
    assert facts["window"]["host_pauses"] >= 0
    assert facts["window"]["host_pause_s"] < facts["window"]["seconds"]
    assert facts["prefix"]["sessions_snapshots"] == 3
    assert facts["prefix"]["hits"] == facts["prefix"]["snapshot_restores"] > 0
    assert facts["prefix"]["hits"] == facts["prefix"]["admissions"]
    assert facts["prefix"]["context_hit_share"] == 1.0
    assert ctx["traffic"]["prefix_hit_share_min"] == 0.98
    stats = facts["kv_stats"]
    assert stats["moe_rows_dropped"] == 0
    assert (stats["kv_layers"], stats["state_layers"]) == (2, 3)
    assert stats["pool_attention"] == "conv_gathered_view"
    assert stats["state_snapshots_live"] > 3
    ref = facts["reference"]
    assert ref["n_requests"] == 3 and ref["n_emitting"] >= 6
    assert ref["n_layers"] == 5
    assert ref["replayed_tokens"] == ref["window_tokens"]
    _check_float32(ref)


def test_a_run_whose_hits_restore_nothing_fails_correct(runner, tmp_path):
    from benchmark import controls_lfm2

    _clear_programs()
    try:
        with controls_lfm2.planted("hit_zero_state", runner):
            facts = runner.run(tiny_lfm2.context(tmp_path))
    finally:
        _clear_programs()
    assert any(
        p.startswith("rows_after_hit_err_median") for p in facts["problems"]
    ), facts["problems"]


def test_a_context_prefilled_after_setup_fails_correct(
    runner, tmp_path, monkeypatch
):
    """The traffic's ``prefix_hit_share_min`` is held against the
    CONTEXT tokens of the admitted requests (1.0 in a clean run), and one
    context the cache did not supply is named, whatever the share. A
    context that set-up left out is never served from the cache: a
    prompt's one snapshot lies at ITS last whole block, beyond the
    context's end, so no later turn of the session finds a snapshot on
    its own chain."""
    documents = runner.serve_sparse.documents
    calls = []

    def all_but_one_at_setup(traffic, vocab, seed):
        calls.append(1)     # set-up asks first, then the stream
        docs = documents(traffic, vocab, seed)
        return docs[:-1] if len(calls) == 1 else docs

    monkeypatch.setattr(runner.serve_sparse, "documents",
                        all_but_one_at_setup)
    ctx = tiny_lfm2.context(tmp_path)
    ctx["traffic"]["reference_sample"] = 0
    facts = runner.run(ctx)
    prefix = facts["prefix"]
    assert prefix["sessions_snapshots"] == 2
    assert 0.6 < prefix["context_hit_share"] < 0.72     # 2 of 3 sessions
    assert prefix["hits"] < prefix["admissions"]
    assert [p for p in facts["problems"] if "under 98 %" in p]
    assert [p for p in facts["problems"] if "prefilled after set-up" in p]
    # the window's numbers with ONE of 457 requests missing its context
    traffic = ctx["traffic"]
    assert runner.prefix_problems(457 * 32, 457, traffic) == []
    one = runner.prefix_problems(456 * 32, 457, traffic)
    assert len(one) == 1 and "prefilled after set-up" in one[0]
    assert len(runner.prefix_problems(440 * 32, 457, traffic)) == 2


@pytest.mark.parametrize("control, caught_by", [
    ("snapshot_one_row_late", "snapshot_err_median"),
    ("gate_c_left_out", "conv_err_median_max"),
    ("taps_reversed", "conv_err_median_max"),
    ("last_conv_taps_reversed", "conv_err_median_max"),
    ("qk_norm_skipped", "k_rows_err_median"),
    ("rope_skipped", "k_rows_err_median"),
    ("router_unnormalised", "weight_err_median"),
    ("reference_lower_precision", "k_rows_err_median"),
])
def test_a_planted_fault_fails_correct(runner, tmp_path, control, caught_by):
    """``controls_lfm2.py``'s plants at tiny size: each breaks the limit
    named for it."""
    from benchmark import controls_lfm2

    _clear_programs()
    try:
        with controls_lfm2.planted(control, runner):
            facts = runner.run(tiny_lfm2.context(tmp_path))
    finally:
        _clear_programs()
    assert any(p.startswith(caught_by) for p in facts["problems"]), \
        facts["problems"]
    assert controls_lfm2.CAUGHT_BY[control] == caught_by
    assert set(controls_lfm2.PLANTS) == set(controls_lfm2.CAUGHT_BY)


def _clear_programs():
    from dlrover_tpu.serving.kvpool import engine as paged

    paged._paged_steps_for.cache_clear()
    paged._state_steps.cache_clear()


def test_counts_against_hand_numbers(cell):
    cfg = cell["config"]
    assert flops_lfm2.parameter_count(cfg) == 5177950976
    # 32 slots at 8,500 rows: K and V of 512 numbers each, 2 layers
    work = flops_lfm2.gqa_attention_step(cfg, 32 * 8500)
    assert work["bytes"] == 32 * 8500 * 2 * 512 * 2 * 2 == 1114112000
    assert work["bytes"] / 819e9 == pytest.approx(1.36e-3, rel=1e-2)
    # 56 experts hit: gate + up + down, 3 x 2048 x 1536 x 2 B = 18.9 MB
    # each, in the 8 EXPERT layers
    work = flops_lfm2.expert_step(cfg, 56, 32)
    assert work["bytes"] == 56 * 3 * 2048 * 1536 * 2 * 8
    assert work["bytes"] / 819e9 == pytest.approx(10.3e-3, rel=1e-2)
    assert work["flops"] == 2 * 8 * 32 * 4 * 3 * 2048 * 1536
    # 7 convolution layers: 33.6 MB of projections each + 32 slots' state
    work = flops_lfm2.conv_mix_step(cfg, 32)
    assert work["bytes"] == 7 * (
        4 * 2048 * 2048 * 2 + 3 * 2048 * 4 + 2 * 32 * 2 * 2048 * 2
    )
    assert work["bytes"] / 819e9 == pytest.approx(0.29e-3, rel=2e-2)


def test_scope_table_and_the_new_readers_on_a_hand_made_dump():
    """An op's scope comes from the program it ran in; a dump without
    these scopes reads None; every new reader reads what the dump, the
    spans and the counters say, and nothing from a parent's run."""
    from benchmark import sparse_scopes

    dump = {"host": [], "planes": {"/device:TPU:0": {
        "XLA Modules": [["jit_step(1)", 0, 100], ["jit_prefill(2)", 200, 300],
                        ["jit_step(1)", 600, 100]],
        "XLA Ops": [
            ["fusion.1", 10, 20, "", "fusion"],
            ["fusion.2", 40, 30, "", "fusion"],
            ["fusion.3", 80, 10, "", "fusion"],
            ["fusion.1", 210, 50, "", "fusion"],
            ["fusion.2", 300, 80, "", "fusion"],
            ["fusion.1", 610, 20, "", "fusion"],
            ["fusion.2", 640, 30, "", "fusion"],
        ],
    }}}
    assert conv_scopes.reduce(dump) is None
    tables = {
        "jit_step": {"fusion.1": "jit(step)/attn/conv/in/dot_general",
                     "fusion.2": "jit(step)/mlp/experts/gmm",
                     "fusion.3": "jit(step)/attn/gqa/exp"},
        "jit_prefill": {"fusion.1": "jit(prefill)/state/snapshot/scatter",
                        "fusion.2": "jit(prefill)/mlp/experts/gmm"},
    }
    out = conv_scopes.reduce(sparse_scopes.label(dump, tables))
    assert out["jit_step"]["launches"] == 2
    assert out["jit_step"]["scope_s"] == {
        "conv": pytest.approx(40e-9), "experts": pytest.approx(60e-9),
        "gqa": pytest.approx(10e-9),
    }
    assert out["jit_prefill"]["scope_s"] == {
        "snapshot": pytest.approx(50e-9), "experts": pytest.approx(80e-9),
    }
    step = lambda ts, **attrs: {  # noqa: E731
        "name": "serving.step", "ts": ts, "mono": ts, "dur_s": 0.01,
        "status": "ok",
        "attrs": dict(
            {"phases": [["decode_launch", 0.0, 0.001]], "n_decoding": 32},
            **attrs
        ),
    }
    facts = {
        "sparse_scopes": out, "kv_stats": {"state_layers": 7},
        "traced_window": (0.0, 10.0), "window": {"seconds": 10.0},
        "spans": [
            step(1.0, state_restores=2, state_restore_s=0.004,
                 state_snapshots=1, prefix_hit_tokens=8192,
                 prefill_tokens=200, experts_hit=55.0, kv_rows=32 * 8500),
            step(2.0, experts_hit=57.0, kv_rows=32 * 8500),
        ],
    }
    read = lambda name: bench_run.load_module(  # noqa: E731
        "layer_metrics", name
    ).read
    assert read("conv_mix_ms_per_step")(facts) == pytest.approx(20e-6)
    assert read("gqa64_attn_ms_per_step")(facts) == pytest.approx(5e-6)
    assert read("conv_serve_expert_ms_per_step")(facts) == \
        pytest.approx(30e-6)
    assert read("conv_chunk_expert_ms_per_chunk")(facts) == \
        pytest.approx(80e-6)
    assert read("state_restore_ms_p50")(facts) == pytest.approx(2.0)
    assert read("state_snapshots_per_request_mean")(facts) == 0.5
    assert read("conv_experts_hit_per_layer_mean")(facts) == 56.0
    assert read("conv_decode_batch_mean")(facts) == 32
    assert read("conv_decode_unscoped_ms_per_step")(facts) is None
    out["jit_step"]["scope_s"]["unscoped"] = 4e-9
    assert read("conv_decode_unscoped_ms_per_step")(facts) == \
        pytest.approx(2e-6)
    assert read("conv_idle_attributed_pct")(facts) is None  # no clock pairs
    for name in (*NEW_READERS, *TWINS):     # a parent's run: nothing to read
        assert read(name)({"ctx": {}, "spans": [], "trace": None}) is None
    for name in TWINS:      # ... and a program without per-slot state
        assert read(name)(dict(facts, kv_stats={})) is None
