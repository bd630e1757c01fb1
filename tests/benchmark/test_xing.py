"""``xing-serve-sessions-16k`` off the chip: the cell finds its files and
states its cut, the program's parameter tree holds what the configuration
says, the runner works end to end at tiny size (timed and traced), each
planted fault of ``controls_xing.py`` fails ``correct``, the traffic is
what the cell states, and the counts behind the roofline shares are hand
numbers."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark import common, flops_xing, latent_scopes, run as bench_run
from tests.benchmark import tiny_xing

CELL = "xing-serve-sessions-16k"
NEW_READERS = (
    "latent_attn_ms_per_step", "latent_attn_roofline",
    "latent_chunk_attn_ms_per_chunk", "latent_chunk_attn_roofline",
    "mhc_mix_ms_per_step", "serve_moe_expert_roofline",
)
EXTENDED = (
    "engine_build_s", "setup_compile_s", "decode_batch_mean",
    "idle_attributed_pct",
)
# Readers that find their facts in this cell's runs too, but whose lists
# tests/benchmark/test_keye.py and test_decode_unscoped_metric.py pin to
# the sparse cell: a `benchmark` PR's to extend (PERF.md section 7).
PINNED = (
    "prefix_hit_token_share_pct", "experts_hit_per_layer_mean",
    "serve_expert_ms_per_step", "decode_unscoped_ms_per_step",
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
    return bench_run.load_module("runners", "serve_latent")


def test_the_cell_finds_its_files_and_states_its_cut(manifest, cell, runner):
    cfg_json = cell["config"]
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "xing4-29b-a4b")
    assert entry["reduced"] == ["num_hidden_layers", "first_k_dense_replace"]
    assert cfg_json["published"] == {
        "num_hidden_layers": 40, "first_k_dense_replace": 2,
    }
    assert (cfg_json["num_hidden_layers"],
            cfg_json["first_k_dense_replace"]) == (6, 1)
    # every published width, head count, expert count and the vocabulary
    for key, value in {
        "hidden_size": 3584, "intermediate_size": 9216,
        "num_attention_heads": 32, "q_lora_rank": 768, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "n_routed_experts": 64, "n_shared_experts": 1,
        "num_experts_per_tok": 4, "moe_intermediate_size": 1024,
        "routed_scaling_factor": 2, "vocab_size": 131072, "hc_mult": 4,
        "hc_sinkhorn_iters": 20, "num_nextn_predict_layers": 1,
        "rope_theta": 10000, "ep_size": 1,
    }.items():
        assert cfg_json[key] == value, key
    assert cfg_json["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn",
    }
    for name in ("hc_eps", "hc_output", "hc_init", "rope_pairing",
                 "head_dim", "rms_norm_eps", "weights"):
        assert name in cfg_json["assumed"], name
    assert "multi-token-prediction block is not loaded" in \
        cfg_json["deployment"]
    assert cfg_json["serve_engine"] == {
        "slots": 32, "max_len": 17408, "prefill_chunk": 512,
        "block_size": 64, "num_blocks": 9216,
    }
    assert cell["traffic"]["runner"] == "serve_latent"
    cfg = runner.latent_config(cfg_json)
    assert (cfg.n_layers, cfg.first_dense, cfg.n_experts, cfg.moe_top_k) \
        == (6, 1, 64, 4)
    assert cfg.softmax_scale == pytest.approx(1.4159 ** 2 / 192 ** 0.5, 1e-4)
    for name in NEW_READERS:
        m = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"
    for name in EXTENDED:
        m = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert m["workloads"][-1] == CELL
    for name in PINNED + ("serve_expert_roofline",):   # (a dense layer)
        m = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert CELL not in m["workloads"]


def test_the_assumed_words_are_the_references(cell):
    """What the configuration file assumes about the residual and the
    rotation, word for word in the reference's docstring."""
    from benchmark import reference_xing

    doc = " ".join(reference_xing.__doc__.split())
    for name in ("hc_eps", "hc_output", "hc_init", "rope_pairing"):
        words = cell["config"]["assumed"][name]
        assert " ".join(words.split()) in doc.replace("``", ""), name


def test_parameter_count_from_the_programs_tree(cell, runner):
    """4.79 B: the tree ``init_params`` would build, the config's own
    count and the benchmark's count from the published keys agree."""
    from dlrover_tpu.models import latent_lm

    cfg = runner.latent_config(cell["config"])
    tree = jax.eval_shape(
        lambda k: latent_lm.init_params(cfg, k, dtype=cfg.compute_dtype),
        jax.random.key(0),
    )
    leaves = jax.tree_util.tree_leaves(tree)
    n = sum(int(np.prod(x.shape)) for x in leaves)
    assert n == cfg.count_params() == flops_xing.parameter_count(
        cell["config"]
    )
    assert n == pytest.approx(4.793e9, rel=1e-3)
    in_bf16 = sum(
        int(np.prod(x.shape)) for x in leaves if x.dtype == "bfloat16"
    )
    assert 0.998 < in_bf16 / n < 1     # routers, norms, the maps are f32
    # weights + pool fill the chip as the configuration file says
    eng = cell["config"]["serve_engine"]
    rows = eng["num_blocks"] * eng["block_size"]
    assert flops_xing.cache_bytes_per_token(cell["config"]) == 6912
    pool = rows * flops_xing.cache_bytes_per_token(cell["config"])
    assert pool == pytest.approx(4.08e9, rel=1e-2)
    assert 13.5e9 < 2 * n + pool < 14e9


def test_traffic_is_what_the_cell_states(cell, runner):
    from benchmark.runners import serve_sparse

    traffic = runner.as_documents(cell["traffic"])
    assert traffic["clients"] == 64 and traffic["temperature"] == 0.0
    eng = cell["config"]["serve_engine"]
    assert eng["slots"] == 32

    def head(seed, n=150):
        stream = serve_sparse.request_stream(traffic, 131072, seed)
        return [next(stream) for _ in range(n)]

    a, b = head(5), head(2 ** 31 + 9)
    contexts = serve_sparse.documents(traffic, 131072, 5)
    assert contexts.shape == (32, 16384)
    for i, (prompt, n_new) in enumerate(a):
        assert prompt[:16384] == contexts[i % 32].tolist()  # fixed rotation
        assert 64 <= len(prompt) - 16384 <= 512 and 16 <= n_new <= 128
    assert [(len(p), n) for p, n in a] == [(len(p), n) for p, n in b]
    assert a[0][0] != b[0][0]                              # other tokens
    assert len({(len(p), n) for p, n in a[:64]}) > 32      # a set of 64
    assert max(len(p) + n for p, n in a) <= eng["max_len"]
    # one chunk a request: a context is whole chunks and blocks
    assert 16384 % eng["prefill_chunk"] == 0 == 16384 % eng["block_size"]
    need = 32 * 16384 // eng["block_size"] + eng["slots"] * (
        -(-(512 + 128) // eng["block_size"])
    ) + eng["max_len"] // eng["block_size"]
    assert eng["num_blocks"] > need


@pytest.mark.parametrize("trace", [0, 1], ids=["timed", "traced"])
def test_runner_rehearsal(manifest, runner, tmp_path, trace):
    from tests.benchmark.test_harness import _for_cell, _line

    ctx = tiny_xing.context(tmp_path, trace=trace)
    facts = runner.run(ctx)
    cell = _for_cell(manifest, {
        "serve_tokens_per_s", "decode_ms_per_token_p50",
        "decode_batch_mean", "prefill_step_share_pct", *PINNED,
        *NEW_READERS,
    })
    line, problems = _line(cell, ctx, facts)
    assert problems == [] and line["correct"]
    assert line["failed"] == 0 and line["attempted"] > 6
    if trace:
        # The CPU has no device plane: the device-time readers find
        # nothing to read and are left out; spans and counts report.
        assert set(line["metrics"]) == {
            "decode_ms_per_token_p50", "decode_batch_mean",
            "prefill_step_share_pct", "experts_hit_per_layer_mean",
            "prefix_hit_token_share_pct",
        }
        m = {k: v["value"] for k, v in line["metrics"].items()}
        assert 1 <= m["experts_hit_per_layer_mean"] <= 8
        assert 60 < m["prefix_hit_token_share_pct"] < 100
    else:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert facts["prefix"]["sessions_cached_blocks"] == 3 * 32 // 4
    stats = facts["kv_stats"]
    assert stats["moe_rows_dropped"] == 0
    assert stats["latent_row_bytes"] == 24 * 4
    assert stats["latent_chunk_attention"] == "absorbed"
    ref = facts["reference"]                  # float32 against float32
    assert ref["n_requests"] == 3 and ref["n_emitting"] >= 6
    assert ref["n_layers"] == 3
    assert ref["replayed_tokens"] == ref["window_tokens"]
    assert ref["logit_deficit_max"] <= 1e-4
    assert ref["logit_within_share"] == 1.0
    assert ref["rows_err_max"] < 1e-5 < ref["low_rows_err_min"]
    assert ref["decode_attn_err_max"] < 1e-5 < ref["low_decode_attn_err_min"]
    assert ref["chunk_attn_err_max"] < 1e-5 < ref["low_chunk_attn_err_min"]
    assert ref["decode_scores_err_max"] < 1e-5 \
        < ref["low_decode_scores_err_min"]
    assert ref["stochastic_err_max"] < 1e-5
    assert ref["mix_err_max"] < 1e-5 < ref["low_mix_err_min"]
    assert ref["h_err_max"] < 1e-5
    assert ref["mlp_err_median_max"] < 1e-5 < ref["low_mlp_err_median_min"]
    assert ref["alike_share_min"] == 1.0 and ref["weight_err_max"] < 1e-6


@pytest.mark.parametrize("control, caught_by", [
    ("rope_unrotated", "rows_err_max"),
    ("rope_plain", "rows_err_max"),
    ("mscale_left_out", "decode_attn_err_max"),
    ("sinkhorn_one_round", "stochastic_err_max"),
    ("h_post_unscaled", "mix_err_max"),
    ("streams_averaged", "mix_err_max"),
    ("router_unnormalised", "weight_err_max"),
    ("router_bias_in_weights", "weight_err_max"),
    ("reference_lower_precision", "rows_err_max"),
])
def test_a_planted_fault_fails_correct(runner, tmp_path, control, caught_by):
    """``controls_xing.py``'s plants at tiny size: each breaks the limit
    named for it. (``scores_bf16`` is left to the chip: at float32 it
    would be read against float32 rounding, which no limit is set for.)"""
    from benchmark import controls_xing

    _clear_programs()
    try:
        with controls_xing.planted(control, runner):
            facts = runner.run(tiny_xing.context(tmp_path))
    finally:
        _clear_programs()
    assert any(p.startswith(caught_by) for p in facts["problems"]), \
        facts["problems"]
    assert controls_xing.CAUGHT_BY[control] == caught_by
    assert set(controls_xing.PLANTS) == set(controls_xing.CAUGHT_BY)


def _clear_programs():
    from dlrover_tpu.serving.kvpool import engine as paged

    paged._paged_steps_for.cache_clear()


def test_counts_against_hand_numbers(cell):
    cfg = cell["config"]
    assert flops_xing.parameter_count(cfg) == 4792841860
    # 32 slots at 16,500 rows: one 1,152-byte row a token, 6 layers
    work = flops_xing.latent_attention_step(cfg, 32 * 16500)
    assert work["bytes"] == 32 * 16500 * 1152 * 6 == 3649536000
    assert work["bytes"] / 819e9 == pytest.approx(4.46e-3, rel=1e-2)
    assert work["flops"] / work["bytes"] == pytest.approx(60.4, rel=1e-2)
    # a 512-token chunk against 16,896 rows, as the definition counts it
    work = flops_xing.latent_attention_chunk(cfg, 512, 16896)
    assert work["flops"] == 2 * 6 * 512 * 32 * 16896 * 320
    assert work["flops"] / 197e12 == pytest.approx(5.4e-3, rel=2e-2)
    # 55 experts hit: gate + up + down, 3 x 3584 x 1024 x 2 B = 22 MB
    # each, in the 5 EXPERT layers
    work = flops_xing.expert_step(cfg, 55, 32)
    assert work["bytes"] == 55 * 3 * 3584 * 1024 * 2 * 5
    assert work["bytes"] / 819e9 == pytest.approx(7.39e-3, rel=1e-2)
    assert work["flops"] == 2 * 5 * 32 * 4 * 3 * 3584 * 1024


def test_scope_table_by_program():
    """Two programs share instruction names: an op's scope comes from
    the program it ran in; a dump without these scopes reads None, and
    the accepted readers of a serve cell's table read this one."""
    from benchmark import sparse_scopes

    dump = {"host": [], "planes": {"/device:TPU:0": {
        "XLA Modules": [["jit_step(1)", 0, 100], ["jit_prefill(2)", 200, 300],
                        ["jit_step(1)", 600, 100]],
        "XLA Ops": [
            ["fusion.1", 10, 20, "", "fusion"],
            ["fusion.2", 40, 30, "", "fusion"],
            ["fusion.3", 80, 10, "", "fusion"],
            ["while.1", 0, 100, "", "while"],
            ["fusion.1", 210, 50, "", "fusion"],
            ["fusion.1", 610, 20, "", "fusion"],
            ["fusion.2", 640, 30, "", "fusion"],
        ],
    }}}
    assert latent_scopes.reduce(dump) is None
    tables = {
        "jit_step": {"fusion.1": "jit(step)/attn/mla/scores/dot",
                     "fusion.2": "jit(step)/while/body/mlp/experts/gmm",
                     "fusion.3": "jit(step)/resid/mhc/div"},
        "jit_prefill": {"fusion.1": "jit(prefill)/attn/mla/values/exp"},
    }
    out = latent_scopes.reduce(sparse_scopes.label(dump, tables))
    assert out["jit_step"]["launches"] == 2
    assert out["jit_step"]["scope_s"] == {
        "mla": pytest.approx(40e-9), "experts": pytest.approx(60e-9),
        "mhc": pytest.approx(10e-9),
    }
    assert out["jit_prefill"]["scope_s"] == {"mla": pytest.approx(50e-9)}
    facts = {"sparse_scopes": out}
    assert latent_scopes.per_launch_s(facts, "jit_step", "mla") == \
        pytest.approx(20e-9)
    assert latent_scopes.per_launch_s(facts, "jit_prefill", "mhc") is None
    assert sparse_scopes.per_decode_step_s(facts, ("experts",)) == \
        pytest.approx(30e-9)
    read = lambda name: bench_run.load_module(  # noqa: E731
        "layer_metrics", name
    ).read
    assert read("latent_attn_ms_per_step")(facts) == pytest.approx(20e-6)
    assert read("mhc_mix_ms_per_step")(facts) == pytest.approx(5e-6)
    assert read("serve_expert_ms_per_step")(facts) == pytest.approx(30e-6)
    for name in NEW_READERS:     # a parent's run: nothing to read
        assert read(name)({"ctx": {}, "spans": [], "trace": None}) is None


def test_the_result_files_of_a_cell_land_in_its_directory(cell):
    assert cell["out_dir"].endswith(os.path.join("benchmark", CELL))
    assert json.dumps(cell["traffic"])
