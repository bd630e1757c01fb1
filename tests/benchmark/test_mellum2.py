"""``mellum2-serve-mixed-16k`` off the chip: the cell finds its files and
states its cut, the program's parameter tree holds what the configuration
says, the traffic is what the cell states, the runner works end to end at
tiny size (timed and traced), each planted fault of
``controls_mellum2.py`` fails ``correct`` by the limit named for it, each
new reader reads a hand-made dump, and the counts behind the roofline
shares are hand numbers."""

import copy

import jax
import numpy as np
import pytest

from benchmark import common, controls_mellum2, flops_mellum2
from benchmark import run as bench_run
from benchmark import window_scopes
from tests.benchmark import tiny_mellum2

CELL = "mellum2-serve-mixed-16k"
NEW_READERS = (
    "win_attn_ms_per_step", "win_attn_roofline", "full_attn_ms_per_step",
    "full_attn_roofline", "win_chunk_attn_ms_per_chunk",
    "win_chunk_attn_roofline", "full_chunk_attn_ms_per_chunk",
    "full_chunk_attn_roofline", "mix_expert_ms_per_step",
    "mix_expert_roofline", "mix_chunk_expert_ms_per_chunk",
    "mix_experts_hit_per_layer_mean", "win_rows_held_share_pct",
    "win_blocks_released_per_request_mean",
)
# Accepted readers whose lists an accepted test pins by position or to
# one cell: the cell reports their quantities under a name of its own.
TWINS = {
    "mix_decode_batch_mean": "decode_batch_mean",
    "mix_setup_compile_s": "setup_compile_s",
}
SHARED = (
    "decode_ms_per_token_p50", "prefill_ms_per_ktoken_p50",
    "prefill_program_share_pct", "step_host_serial_ms_p50",
    "step_prep_ms_p50", "step_launch_ms_p50", "step_commit_ms_p50",
    "step_account_ms_p50", "inter_token_gap_ms_p95",
    "prefill_step_share_pct", "slot_wait_ms_p50", "router_queue_ms_p50",
    "replica_loop_ms_p50", "setup_cache_load_s", "setup_cache_hit_pct",
    "setup_trace_lower_s",
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
    return bench_run.load_module("runners", "serve_window")


def test_the_cell_finds_its_files_and_states_its_cut(manifest, cell, runner):
    cfg_json = cell["config"]
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "mellum2-12b-a2.5b")
    assert entry["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types",
    ]
    assert entry["source"] == cfg_json["source"]
    published = cfg_json["published"]
    assert published["num_hidden_layers"] == 28
    assert len(published["layer_types"]) == 28
    assert published["layer_types"].count("full_attention") == 7
    assert published["layer_types"][:4] == ["sliding_attention"] * 3 + [
        "full_attention"
    ]
    # two whole periods, the published entries 0-7
    assert cfg_json["layer_types"] == published["layer_types"][:8]
    assert cfg_json["mlp_layer_types"] == published["mlp_layer_types"][:8] \
        == ["sparse"] * 8
    assert cfg_json["num_hidden_layers"] == 8
    # every published width, head count, expert count, the window and the
    # vocabulary
    for key, value in {
        "hidden_size": 2304, "intermediate_size": 7168,
        "num_attention_heads": 32, "num_key_value_heads": 4,
        "head_dim": 128, "num_experts": 64, "num_experts_per_tok": 8,
        "moe_intermediate_size": 896, "sliding_window": 1024,
        "vocab_size": 98304, "max_position_embeddings": 131072,
        "rms_norm_eps": 1e-06, "norm_topk_prob": True,
        "tie_word_embeddings": False, "model_type": "mellum",
        "attention_bias": False, "hidden_act": "silu",
        "max_window_layers": 0, "use_sliding_window": True,
        "rope_theta": 500000, "torch_dtype": "bfloat16",
    }.items():
        assert cfg_json[key] == value, key
    assert cfg_json["rope_parameters"] == {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782,
        },
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
    }
    for name in ("qk_norm", "intermediate_size", "mtp_head", "rope_theta",
                 "torch_dtype", "rope_pairing", "window", "norm", "weights",
                 "serve_engine"):
        assert name in cfg_json["assumed"], name
    assert "layers 0-7 of 28" in cfg_json["deployment"]
    assert cfg_json["serve_engine"] == {
        "slots": 32, "max_len": 16896, "prefill_chunk": 512,
        "block_size": 64, "num_blocks": 9216, "window_blocks": 1024,
    }
    assert cell["traffic"]["runner"] == "serve_window"
    assert cell["chips"] == 1
    cfg = runner.window_config(cfg_json)
    assert (cfg.n_layers, cfg.n_experts, cfg.moe_top_k) == (8, 64, 8)
    assert cfg.cache_groups == (("full", (2, "all")), ("window", (6, 1023)))
    assert cfg.attention_factor == 1.2772588722239782 \
        == pytest.approx(0.1 * np.log(16.0) + 1.0)
    for name in NEW_READERS:
        m = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL]
        assert m["moves"] == "serve_tokens_per_s"
    for name in SHARED:
        m = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert m["workloads"][-1] == CELL
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
    assert served["workloads"][-1] == CELL
    # the lists accepted tests pin by position or to one cell stand
    for name in ("engine_build_s", "idle_attributed_pct"):
        m = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert CELL not in m["workloads"]


def test_parameter_count_from_the_programs_tree(cell, runner):
    """3,794,966,784: the tree ``init_params`` would build, the config's
    own count and the benchmark's count from the published keys agree,
    and weights + both groups' pools fill the chip as the file says."""
    from dlrover_tpu.models import window_lm
    from dlrover_tpu.serving.kvpool.groups import band_blocks

    cfg = runner.window_config(cell["config"])
    tree = jax.eval_shape(
        lambda k: window_lm.init_params(cfg, k, dtype=cfg.compute_dtype),
        jax.random.key(0),
    )
    leaves = jax.tree_util.tree_leaves(tree)
    n = sum(int(np.prod(x.shape)) for x in leaves)
    assert n == cfg.count_params() == flops_mellum2.parameter_count(
        cell["config"]
    ) == 3794966784
    assert "3,794,966,784" in cell["config"]["reduced"]["num_hidden_layers"]
    in_bf16 = sum(
        int(np.prod(x.shape)) for x in leaves if x.dtype == "bfloat16"
    )
    assert 0.999 < in_bf16 / n < 1          # routers and norms are f32
    eng = cell["config"]["serve_engine"]
    assert flops_mellum2.cache_bytes_per_token(
        cell["config"], flops_mellum2.FULL
    ) == 4096
    assert flops_mellum2.cache_bytes_per_token(
        cell["config"], flops_mellum2.SLIDING
    ) == 12288
    full = eng["num_blocks"] * eng["block_size"] * 4096
    window = eng["window_blocks"] * eng["block_size"] * 12288
    assert full == pytest.approx(2.42e9, rel=1e-2)
    assert window == pytest.approx(0.805e9, rel=1e-2)
    assert 10.7e9 < 2 * n + full + window < 10.9e9
    # under ONE table the window layers would hold the full group's rows
    assert eng["num_blocks"] * eng["block_size"] * 12288 > 7.2e9
    # a slot's worst case in each group, and what is left for the cache
    max_blocks = eng["max_len"] // eng["block_size"]
    assert max_blocks == 264
    assert eng["num_blocks"] - 1 - eng["slots"] * max_blocks == 767
    per_slot = band_blocks(1023, 64, eng["max_len"], eng["prefill_chunk"])
    assert per_slot == 26
    assert eng["window_blocks"] - 1 - eng["slots"] * per_slot == 191


def test_traffic_is_what_the_cell_states(cell, runner):
    traffic = cell["traffic"]
    assert traffic["clients"] == 64 and traffic["temperature"] == 0.0
    assert traffic["loop"] == "closed" and traffic["shared_prefix"] is None
    assert (traffic["ramp_s"], traffic["trace_s"]) == (10.0, 3.0)
    assert (traffic["reference_sample"], traffic["reference_long"]) == (4, 2)
    lengths = runner.mixed_length_set(traffic)
    assert len(lengths) == 64
    longs = [p for p, _ in lengths if runner.is_long(traffic, p)]
    shorts = [p for p, _ in lengths if not runner.is_long(traffic, p)]
    assert len(longs) == 16 and len(shorts) == 48
    assert 8192 <= min(longs) and max(longs) <= 16384
    assert 256 <= min(shorts) and max(shorts) <= 2048
    assert all(32 <= n <= 256 for _, n in lengths)
    eng = cell["config"]["serve_engine"]
    assert max(p + n for p, n in lengths) <= eng["max_len"]
    assert 16384 + 256 <= eng["max_len"] == 33 * eng["prefill_chunk"]
    # one schedule for every seed; the tokens are the seed's
    a = runner.request_stream(traffic, 98304, 5)
    b = runner.request_stream(traffic, 98304, 2 ** 31 + 5)
    head_a = [next(a) for _ in range(130)]
    head_b = [next(b) for _ in range(130)]
    assert [(len(p), n) for p, n in head_a] \
        == [(len(p), n) for p, n in head_b]
    assert sorted((len(p), n) for p, n in head_a[:64]) == sorted(lengths)
    assert sorted((len(p), n) for p, n in head_a[64:128]) == sorted(lengths)
    assert head_a[0][0] != head_b[0][0]
    assert head_a[0][0][:64] != head_a[1][0][:64]       # no shared prefix
    # 7.5 chunks a request on average, as the cell's reckoning has it
    chunks = [-(-p // eng["prefill_chunk"]) for p, _ in lengths]
    assert 6.5 < np.mean(chunks) < 9.5


def _check_float32(ref):
    """float32 against float32: every reading at rounding."""
    assert ref["logit_deficit_max"] <= 1e-4
    assert ref["logit_within_share"] == 1.0
    assert ref["window_rows_err_median"] < 1e-5 \
        < ref["low_window_rows_err_median"]
    assert ref["full_rows_err_median"] < 1e-5 < ref["low_full_rows_err_median"]
    assert max(ref["rows_err_p99_by_layer"]) < 1e-5
    assert ref["rows_bad_share"] == 0.0 and ref["n_rows_landed"] > 100
    assert ref["band_blocks_missing"] == ref["band_blocks_stale"] == 0
    assert ref["window_attn_err_median"] < 1e-5 \
        < ref["low_window_attn_err_median"]
    assert ref["full_attn_err_median"] < 1e-5 < ref["low_full_attn_err_median"]
    assert ref["mlp_err_median"] < 1e-5 < ref["low_mlp_err_median"]
    assert ref["alike_share"] == 1.0 and ref["weight_err_max"] < 1e-5
    assert ref["low_logit_within_share"] <= 1.0


@pytest.mark.parametrize("trace", [0, 1], ids=["timed", "traced"])
def test_runner_rehearsal(manifest, runner, tmp_path, trace):
    from tests.benchmark.test_harness import _for_cell, _line

    ctx = tiny_mellum2.context(tmp_path, trace=trace)
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
            "mix_experts_hit_per_layer_mean", "win_rows_held_share_pct",
            "win_blocks_released_per_request_mean",
            "mix_decode_batch_mean", "mix_setup_compile_s",
        }
        m = {k: v["value"] for k, v in line["metrics"].items()}
        assert 1 <= m["mix_decode_batch_mean"] <= 4
        assert any(e.get("event") == "setup_table" for e in facts["events"])
        assert 1 <= m["mix_experts_hit_per_layer_mean"] <= 8
        assert 10 < m["win_rows_held_share_pct"] <= 100
        assert m["win_blocks_released_per_request_mean"] > 0.5
        steps = [s for s in facts["spans"] if s["name"] == "serving.step"]
        assert any("window_blocks_released" in s["attrs"] for s in steps)
        assert any("window_rows" in s["attrs"] for s in steps)
        assert any("expert_rows_dropped" in s["attrs"] for s in steps)
        prefills = [s for s in facts["spans"]
                    if s["name"] == "serving.prefill"]
        assert any("window_blocks_released" in s["attrs"] for s in prefills)
    else:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    window = facts["window"]
    assert window["long_requests"] > 0 < window["window_blocks_released"]
    assert window["prefilled_tokens"] > 0       # prefilled INSIDE the window
    assert window["host_pauses"] >= 0
    stats = facts["kv_stats"]
    assert stats["moe_rows_dropped"] == 0
    assert stats["pool_attention"] == "window_groups"
    assert stats["window_decode_attention"] == "gathered_view"
    assert set(facts["kv_groups"]) == {"full", "window"}
    assert (facts["kv_groups"]["full"]["layers"],
            facts["kv_groups"]["window"]["layers"]) == (1, 2)
    ref = facts["reference"]
    assert ref["n_requests"] == 3 and ref["n_emitting"] >= 6
    assert ref["n_layers"] == 3
    assert max(ref["prompt_lens"]) >= 70          # a long one among them
    assert ref["replayed_tokens"] == ref["window_tokens"]
    _check_float32(ref)


@pytest.fixture(scope="module")
def mini():
    """What a control needs of a run, without its window: an engine over
    a one-period model, three prompts served (what the window would have
    answered), the replay, the probes and the reference."""
    cfg_json = copy.deepcopy(tiny_mellum2.CONFIG)
    runner = bench_run.load_module("runners", "serve_window")
    traffic = tiny_mellum2.context("/tmp")["traffic"]

    def run(control):
        from dlrover_tpu.models import window_lm
        from dlrover_tpu.serving.kvpool import PagedServingEngine
        from dlrover_tpu.serving.kvpool import engine as paged

        paged._grouped_steps_for.cache_clear()
        try:
            with controls_mellum2.planted(control, runner):
                cfg = runner.window_config(cfg_json)
                eng = cfg_json["serve_engine"]
                key = jax.random.key(3)
                params = window_lm.init_params(cfg, key)
                engine = PagedServingEngine(
                    cfg, params, slots=eng["slots"], max_len=eng["max_len"],
                    prefill_chunk=eng["prefill_chunk"],
                    block_size=eng["block_size"],
                    num_blocks=eng["num_blocks"],
                    window_blocks=eng["window_blocks"],
                )
                rng = np.random.default_rng(4)
                prompts = [rng.integers(0, 256, n).tolist()
                           for n in (110, 61, 9)]
                reqs = [engine.submit(p, max_new_tokens=6, temperature=0.0)
                        for p in prompts]
                while engine.pending():
                    engine.step()
                sample = [{"prompt": p, "tokens": list(r.tokens)}
                          for p, r in zip(prompts, reqs)]
                stream = runner.request_stream(traffic, 256, 9)
                requests, _, dropped = runner.replay_and_probe(
                    engine, sample, stream, 12
                )
                check = runner.judge(requests, cfg_json, params)
                return check, runner.problems_of(check, runner.JUDGED), \
                    dropped
        finally:
            paged._grouped_steps_for.cache_clear()

    return run


def test_a_clean_mini_run_is_correct(mini):
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(controls_mellum2.PLANTS, "none", lambda runner: [])
        check, problems, dropped = mini("none")
    assert problems == [] and dropped == 0
    _check_float32(check)
    # the replays hit what the first pass cached, tails and all
    assert check["window_blocks_released_by_probe"][0] < 10


@pytest.mark.parametrize("control, caught_by", [
    ("window_one_row_wide", "window_attn_err_median"),
    ("window_ignored", "window_attn_err_median"),
    ("released_too_early", "band_blocks"),
    ("yarn_on_window_layers", "window_rows_err_median"),
    ("yarn_skipped", "full_rows_err_median"),
    ("attention_factor_dropped", "full_rows_err_median"),
    ("rotations_swapped", "window_rows_err_median"),
    ("router_unnormalised", "weight_err_median"),
    ("reference_lower_precision", "window_rows_err_median"),
])
def test_a_planted_fault_fails_correct(mini, control, caught_by):
    """``controls_mellum2.py``'s plants at tiny size: each breaks the
    limit named for it."""
    _, problems, _ = mini(control)
    assert any(p.startswith(caught_by) for p in problems), problems
    assert controls_mellum2.CAUGHT_BY[control] == caught_by
    assert set(controls_mellum2.PLANTS) == set(controls_mellum2.CAUGHT_BY)


def test_counts_against_hand_numbers(cell):
    cfg = cell["config"]
    assert flops_mellum2.parameter_count(cfg) == 3794966784
    assert flops_mellum2.row_bytes(cfg) == 2048
    # 31 slots at 4,000 rows: K and V of 512 numbers each, 2 full layers
    work = flops_mellum2.attention_step(cfg, flops_mellum2.FULL, 31 * 4000)
    assert work["bytes"] == 31 * 4000 * 2048 * 2 == 507904000
    assert work["bytes"] / 819e9 == pytest.approx(0.62e-3, rel=1e-2)
    # ... and 1,023 visible rows a slot in the 6 window layers
    work = flops_mellum2.attention_step(cfg, flops_mellum2.SLIDING,
                                        31 * 1023)
    assert work["bytes"] == 31 * 1023 * 2048 * 6 == 389689344
    assert work["flops"] == 2 * 6 * 31 * 1023 * 32 * 2 * 128
    # 62 experts hit: gate + up + down, 3 x 2,304 x 896 x 2 B = 12.4 MB
    # each, in the 8 layers
    work = flops_mellum2.expert_step(cfg, 62, 31)
    assert work["bytes"] == 62 * 3 * 2304 * 896 * 2 * 8
    assert work["bytes"] / 819e9 == pytest.approx(7.5e-3, rel=1e-2)
    assert work["flops"] == 2 * 8 * 31 * 8 * 3 * 2304 * 896
    # a whole chunk at row 12,288: a full layer scores every row below
    # each token and the token; a window layer 1,024 keys a token
    pairs, seen = flops_mellum2.chunk_pairs(cfg, flops_mellum2.FULL,
                                            12288, 512)
    assert (pairs, seen) == (512 * 12288 + 512 * 513 // 2, 12288)
    pairs, seen = flops_mellum2.chunk_pairs(cfg, flops_mellum2.SLIDING,
                                            12288, 512)
    assert (pairs, seen) == (512 * 1024, 1023)
    # ... below the window's width a token sees what there is
    pairs, seen = flops_mellum2.chunk_pairs(cfg, flops_mellum2.SLIDING,
                                            0, 512)
    assert (pairs, seen) == (512 * 513 // 2, 0)
    work = flops_mellum2.attention_chunk(
        cfg, flops_mellum2.FULL, [(12288, 512), (0, 512)]
    )
    mean_pairs = (512 * 12288 + 2 * (512 * 513 // 2)) / 2
    assert work["flops"] == 2 * mean_pairs * 4 * 128 * 32
    assert work["bytes"] == 2 * (12288 / 2) * 2048
    assert work["flops"] / 197e12 == pytest.approx(0.545e-3, rel=1e-2)


def test_scope_table_and_the_new_readers_on_a_hand_made_dump(cell):
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
            ["fusion.3", 400, 40, "", "fusion"],
            ["fusion.1", 610, 20, "", "fusion"],
            ["fusion.2", 640, 30, "", "fusion"],
        ],
    }}}
    assert window_scopes.reduce(dump) is None
    tables = {
        "jit_step": {"fusion.1": "jit(step)/attn/window/dot_general",
                     "fusion.2": "jit(step)/mlp/experts/gmm",
                     "fusion.3": "jit(step)/attn/full/exp"},
        "jit_prefill": {"fusion.1": "jit(prefill)/attn/window/custom-call",
                        "fusion.2": "jit(prefill)/mlp/experts/gmm",
                        "fusion.3": "jit(prefill)/attn/full/custom-call"},
    }
    out = window_scopes.reduce(sparse_scopes.label(dump, tables))
    assert out["jit_step"]["launches"] == 2
    assert out["jit_step"]["scope_s"] == {
        "window": pytest.approx(40e-9), "experts": pytest.approx(60e-9),
        "full": pytest.approx(10e-9),
    }
    assert out["jit_prefill"]["scope_s"] == {
        "window": pytest.approx(50e-9), "experts": pytest.approx(80e-9),
        "full": pytest.approx(40e-9),
    }
    step = lambda ts, **attrs: {  # noqa: E731
        "name": "serving.step", "ts": ts, "mono": ts, "dur_s": 0.01,
        "status": "ok",
        "attrs": dict({"phases": [["decode_launch", 0.0, 0.001]]}, **attrs),
    }
    facts = {
        "sparse_scopes": out, "ctx": cell,
        "kv_stats": {"window_decode_attention": "pool_kernel"},
        "device": {"kind": "TPU v5 lite"},
        "traced_window": (0.0, 10.0), "window": {"seconds": 10.0},
        "spans": [
            step(1.0, n_decoding=30, kv_rows=120000, window_rows=30000,
                 experts_hit=61.0, prefill_tokens=512,
                 prefill_kv_rows=12800, window_blocks_released=8,
                 n_finished=1),
            step(2.0, n_decoding=32, kv_rows=130000, window_rows=32000,
                 experts_hit=63.0, window_blocks_released=2, n_finished=1),
        ],
    }
    read = lambda name: bench_run.load_module(  # noqa: E731
        "layer_metrics", name
    ).read
    assert read("win_attn_ms_per_step")(facts) == pytest.approx(20e-6)
    assert read("full_attn_ms_per_step")(facts) == pytest.approx(5e-6)
    assert read("mix_expert_ms_per_step")(facts) == pytest.approx(30e-6)
    assert read("win_chunk_attn_ms_per_chunk")(facts) == pytest.approx(50e-6)
    assert read("full_chunk_attn_ms_per_chunk")(facts) == pytest.approx(40e-6)
    assert read("mix_chunk_expert_ms_per_chunk")(facts) == \
        pytest.approx(80e-6)
    assert window_scopes.traced_chunks(facts) == [(12288, 512)]
    # 31,000 window rows x 6 layers x 2,048 B over 819 GB/s, over 20 ns
    peak = cell["peaks_table"]["TPU v5 lite"]
    want = 31000 * 6 * 2048 / peak["hbm_bytes_per_s"] / 20e-9
    assert read("win_attn_roofline")(facts) == pytest.approx(100 * want)
    want = 125000 * 2 * 2048 / peak["hbm_bytes_per_s"] / 5e-9
    assert read("full_attn_roofline")(facts) == pytest.approx(100 * want)
    want = 62 * 8 * 3 * 2304 * 896 * 2 / peak["hbm_bytes_per_s"] / 30e-9
    assert read("mix_expert_roofline")(facts) == pytest.approx(100 * want)
    want = 6 * 512 * 1024 * 4 * 128 * 32 / peak["bf16_flops_per_s"] / 50e-9
    assert read("win_chunk_attn_roofline")(facts) == pytest.approx(100 * want)
    pairs = 512 * 12288 + 512 * 513 // 2
    want = 2 * pairs * 4 * 128 * 32 / peak["bf16_flops_per_s"] / 40e-9
    assert read("full_chunk_attn_roofline")(facts) == \
        pytest.approx(100 * want)
    assert read("mix_experts_hit_per_layer_mean")(facts) == 62.0
    assert read("win_rows_held_share_pct")(facts) == pytest.approx(
        100 * (0.25 + 32 / 130) / 2
    )
    assert read("win_blocks_released_per_request_mean")(facts) == 5.0
    assert read("mix_decode_batch_mean")(facts) == 31
    for name in (*NEW_READERS, *TWINS):     # a parent's run: nothing to read
        assert read(name)({"ctx": {}, "spans": [], "trace": None}) is None
    for name in TWINS:      # ... and a program whose pool is one group
        assert read(name)(dict(facts, kv_stats={})) is None
