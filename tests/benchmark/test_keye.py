"""``keye-serve-docqa-32k`` off the chip: the cell finds its files, the
program's parameter tree holds what the configuration says, the runner
works end to end at tiny size (timed and traced), each planted fault of
``controls_keye.py`` fails ``correct``, the traffic is what the cell states,
and the byte counts behind the roofline shares are hand numbers."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark import common, flops_keye, run as bench_run, sparse_scopes
from tests.benchmark import tiny_keye

CELL = "keye-serve-docqa-32k"
NEW_READERS = (
    "index_select_ms_per_step", "index_select_roofline",
    "sparse_attn_ms_per_step", "sparse_attn_roofline",
    "serve_expert_ms_per_step", "serve_expert_roofline",
    "selected_keys_share_pct", "experts_hit_per_layer_mean",
    "prefix_hit_token_share_pct",
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
    return bench_run.load_module("runners", "serve_sparse")


def test_the_cell_finds_its_files_and_states_its_cut(manifest, cell, runner):
    cfg_json = cell["config"]
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "keye-vl2-30b-a3b")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert cfg_json["published"] == {"num_hidden_layers": 48}
    assert cfg_json["num_hidden_layers"] == 5
    # every published width, head count, expert count and the vocabulary
    for key, value in {
        "hidden_size": 2048, "head_dim": 128, "num_attention_heads": 32,
        "num_key_value_heads": 4, "num_experts": 128,
        "num_experts_per_tok": 8, "moe_intermediate_size": 768,
        "vocab_size": 151936, "rope_theta": 10000000,
    }.items():
        assert cfg_json[key] == value, key
    assert cfg_json["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048,
    }
    for name in ("qk_norm", "index_input", "index_key_norm", "index_rope",
                 "index_scores"):
        assert name in cfg_json["assumed"], name
    assert "vision tower is not built" in cfg_json["deployment"]
    assert cell["traffic"]["runner"] == "serve_sparse"
    cfg = runner.sparse_config(cfg_json)
    assert (cfg.n_experts, cfg.moe_top_k, cfg.index_topk) == (128, 8, 2048)
    for name in NEW_READERS:
        m = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"


def test_parameter_count_from_the_programs_tree(cell, runner):
    """3.75 B: the tree ``init_params`` would build, the config's own
    count and the benchmark's count from the published keys agree."""
    from dlrover_tpu.models import sparse_lm

    cfg = runner.sparse_config(cell["config"])
    tree = jax.eval_shape(
        lambda k: sparse_lm.init_params(cfg, k, dtype=cfg.compute_dtype),
        jax.random.key(0),
    )
    leaves = jax.tree_util.tree_leaves(tree)
    n = sum(int(np.prod(x.shape)) for x in leaves)
    assert n == cfg.count_params() == flops_keye.parameter_count(
        cell["config"]
    )
    assert n == pytest.approx(3.75e9, rel=5e-3)
    in_bf16 = sum(
        int(np.prod(x.shape)) for x in leaves if x.dtype == "bfloat16"
    )
    assert in_bf16 / n > 0.999        # norm scales and the router are f32
    # weights + pool fill the chip as the configuration file says
    eng = cell["config"]["serve_engine"]
    rows = eng["num_blocks"] * eng["block_size"]
    pool = rows * flops_keye.cache_bytes_per_token(cell["config"])
    assert flops_keye.cache_bytes_per_token(cell["config"]) == 10880
    assert 2 * n + pool > 10e9


def test_traffic_is_what_the_cell_states(cell, runner):
    traffic = cell["traffic"]
    assert traffic["clients"] == 32 and traffic["temperature"] == 0.0
    assert cell["config"]["serve_engine"]["slots"] == 16

    def head(seed, n=150):
        stream = runner.request_stream(traffic, 151936, seed)
        return [next(stream) for _ in range(n)]

    a, b = head(5), head(2 ** 31 + 9)
    docs = runner.documents(traffic, 151936, 5)
    assert docs.shape == (8, 32768)
    for i, (prompt, n_new) in enumerate(a):
        assert prompt[:32768] == docs[i % 8].tolist()     # fixed rotation
        assert 64 <= len(prompt) - 32768 <= 512 and 16 <= n_new <= 128
    assert [(len(p), n) for p, n in a] == [(len(p), n) for p, n in b]
    assert a[0][0] != b[0][0]                              # other tokens
    assert len({(len(p), n) for p, n in a[:64]}) > 32      # a set of 64
    eng = cell["config"]["serve_engine"]
    assert max(len(p) + n for p, n in a) <= eng["max_len"]
    # one chunk a request: the document part is whole chunks and blocks
    assert 32768 % eng["prefill_chunk"] == 0 == 32768 % eng["block_size"]
    need = 8 * 32768 // eng["block_size"] + eng["slots"] * (
        -(-(512 + 128) // eng["block_size"])
    )
    assert eng["num_blocks"] > need


@pytest.mark.parametrize("trace", [0, 1], ids=["timed", "traced"])
def test_runner_rehearsal(manifest, runner, tmp_path, trace):
    from tests.benchmark.test_harness import _for_cell, _line

    ctx = tiny_keye.context(tmp_path, trace=trace)
    facts = runner.run(ctx)
    cell = _for_cell(manifest, {
        "serve_tokens_per_s", "decode_ms_per_token_p50",
        "decode_batch_mean", "prefill_step_share_pct", *NEW_READERS,
    })
    line, problems = _line(cell, ctx, facts)
    assert problems == [] and line["correct"]
    assert line["failed"] == 0 and line["attempted"] > 6
    if trace:
        # The CPU has no device plane: the device-time readers find
        # nothing to read and are left out; spans and counts report.
        assert set(line["metrics"]) == {
            "decode_ms_per_token_p50", "decode_batch_mean",
            "prefill_step_share_pct", "selected_keys_share_pct",
            "experts_hit_per_layer_mean", "prefix_hit_token_share_pct",
        }
        m = {k: v["value"] for k, v in line["metrics"].items()}
        assert 5 < m["selected_keys_share_pct"] < 60     # topk 8 of ~40
        assert 1 <= m["experts_hit_per_layer_mean"] <= 8
        assert 60 < m["prefix_hit_token_share_pct"] < 100
    else:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert facts["prefix"]["documents_cached_blocks"] == 3 * 32 // 4
    assert facts["kv_stats"]["moe_rows_dropped"] == 0
    ref = facts["reference"]                  # float32 against float32
    assert ref["n_rows"] > ref["n_emitting"] >= 6 and ref["n_layers"] == 2
    assert ref["replayed_tokens"] == ref["window_tokens"]
    assert ref["tracked_share"] == 1.0 and ref["pool_err_by_layer"] == [0, 0]
    assert ref["program_deficit_tracked_max"] == 0.0
    assert ref["n_alike_free"] == ref["n_emitting"]
    assert ref["free_deficit_alike_max"] <= 1e-4
    assert ref["keys_wrong"] == 0 and ref["keys_min"] == 8
    assert ref["share_wide_min"] == 1.0
    assert ref["share_exact_mean_by_layer"] == [1.0, 1.0]
    assert ref["score_err_max"] < 1e-5 < ref["low_score_err_max"]
    assert ref["attn_err_max"] < 1e-5 < ref["low_attn_err_first_min"]
    assert ref["alike_share"] == 1.0 and ref["weight_err_max"] < 1e-6
    assert ref["y_err_max"] < 1e-5 < ref["low_y_err_min"]


@pytest.mark.parametrize("control, caught_by", [
    ("dense_attention", "keys_wrong"),
    ("index_scores_bf16", "score_err_max"),
    ("router_unnormalised", "weight_err_max"),
    ("index_keys_unshared", "share_exact"),
    ("index_keys_one_layer", "share_exact"),
    ("reference_lower_precision", "score_err_max"),
])
def test_a_planted_fault_fails_correct(runner, tmp_path, control, caught_by):
    """``controls_keye.py``'s plants at tiny size: each breaks the limit
    named for it. (A margin of 256 places is the whole context here, so
    the two index-key controls are read off the share inside the
    reference's exact top-k, which no limit judges on the chip.)"""
    from benchmark import controls_keye

    _clear_programs()
    try:
        with controls_keye.planted(control, runner, seed=3):
            facts = runner.run(_context(tmp_path, control))
    finally:
        _clear_programs()
    ref = facts["reference"]
    if caught_by == "share_exact":
        assert min(ref["share_exact_mean_by_layer"]) < 0.9
        if control == "index_keys_one_layer":
            assert ref["share_exact_mean_by_layer"][0] == 1.0
    else:
        assert any(p.startswith(caught_by) for p in facts["problems"]), \
            facts["problems"]
        assert controls_keye.CAUGHT_BY[control] == caught_by


def _context(tmp_path, name):
    (tmp_path / name).mkdir()
    return tiny_keye.context(tmp_path / name)


def _clear_programs():
    from dlrover_tpu.serving.kvpool import engine as paged

    paged._paged_steps_for.cache_clear()


def test_byte_counts_against_hand_numbers(cell):
    cfg = cell["config"]
    # 16 slots at 33,000 rows: one 128-byte index key a row, 5 layers
    work = flops_keye.index_select_step(cfg, 16 * 33000)
    assert work["bytes"] == 16 * 33000 * 128 * 5 == 337920000
    assert work["flops"] == 2 * 5 * 16 * 33000 * 16 * 64
    # the selection: 2,048 rows a slot, K + V 2 x 4 x 128 x 2 B = 2 KB
    work = flops_keye.sparse_attention_step(cfg, 16 * 2048)
    assert work["bytes"] == 16 * 2048 * 2048 * 5 == 335544320
    # 80 experts hit: gate + up + down, 3 x 2048 x 768 x 2 B = 9.4 MB each
    work = flops_keye.expert_step(cfg, 80, 16)
    assert work["bytes"] == 80 * 3 * 2048 * 768 * 2 * 5
    assert work["bytes"] / 819e9 == pytest.approx(4.61e-3, rel=1e-2)
    assert work["flops"] == 2 * 5 * 16 * 8 * 3 * 2048 * 768


def test_scope_table_by_program():
    """Two programs share instruction names: an op's scope comes from
    the program it ran in; a dump without these scopes reads None."""
    dump = {"host": [], "planes": {"/device:TPU:0": {
        "XLA Modules": [["jit_step(1)", 0, 100], ["jit_prefill(2)", 200, 300],
                        ["jit_step(1)", 600, 100]],
        "XLA Ops": [
            ["fusion.1", 10, 20, "", "fusion"],
            ["fusion.2", 40, 30, "", "fusion"],
            ["while.1", 0, 100, "", "while"],
            ["fusion.1", 210, 50, "", "fusion"],
            ["fusion.1", 610, 20, "", "fusion"],
            ["fusion.2", 640, 30, "", "fusion"],
        ],
    }}}
    assert sparse_scopes.reduce(dump) is None
    tables = {
        "jit_step": {"fusion.1": "jit(step)/attn/index/dot",
                     "fusion.2": "jit(step)/mlp/experts/gmm"},
        "jit_prefill": {"fusion.1": "jit(prefill)/attn/sparse/exp"},
    }
    out = sparse_scopes.reduce(sparse_scopes.label(dump, tables))
    assert out["jit_step"]["launches"] == 2
    assert out["jit_step"]["scope_s"] == {
        "index": pytest.approx(40e-9), "experts": pytest.approx(60e-9),
    }
    assert out["jit_prefill"]["scope_s"] == {"sparse": pytest.approx(50e-9)}
    facts = {"sparse_scopes": out}
    assert sparse_scopes.per_decode_step_s(facts, ("index", "select")) == \
        pytest.approx(20e-9)
    assert sparse_scopes.per_decode_step_s(facts, ("sparse",)) is None
    for name in NEW_READERS:     # a parent's run: nothing to read
        read = bench_run.load_module("layer_metrics", name).read
        assert read({"ctx": {}, "spans": [], "trace": None}) is None


def test_the_result_files_of_a_cell_land_in_its_directory(cell):
    assert cell["out_dir"].endswith(os.path.join("benchmark", CELL))
    assert json.dumps(cell["traffic"])
