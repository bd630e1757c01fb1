"""``kimilinear-train-8k`` off the chip: the configuration file maps onto
the program's layer pattern and its parameter count is the one the file
states, the runner works end to end at tiny size (timed and traced) and
holds the program to the plain reference by loss AND by gradient, the
planted controls come out through the harness's own comparison as they
should, the scope reducer and every new reader give the right number on
hand-built facts and nothing on empty ones."""

import math

import jax
import numpy as np
import pytest

from benchmark import common, controls_kimi_linear, flops_kimi_linear
from benchmark import hybrid_scopes
from benchmark import reference_kimi_linear
from benchmark import run as bench_run
from benchmark import trace_reduce
from tests.benchmark import tiny, tiny_kimi

CELL = "kimilinear-train-8k"
READERS = [
    "kda_scan_ms_per_step", "kda_scan_roofline", "mla_attn_ms_per_step",
    "mla_attn_roofline", "expert_ffn_ms_per_step", "expert_ffn_roofline",
    "kda_layers_share_pct", "hybrid_train_mfu_pct",
    "expert_rows_per_held_expert_mean", "expert_load_max_over_mean",
]


@pytest.fixture(scope="module")
def cfg_json():
    return common.load_json("configs", "kimi-linear-48b-a3b.json")


@pytest.fixture(scope="module")
def runner():
    return bench_run.load_module("runners", "train_hybrid")


def test_the_manifest_lists_the_cell_and_its_readers():
    manifest = common.load_manifest()
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "pretrain-8k"
    mine = [
        m["name"] for m in manifest["per_layer"]
        if m.get("workloads") == [CELL]
    ]
    assert mine == READERS
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert CELL in e2e["train_tokens_per_s"]["workloads"]
    # One accepted reader finds what it reads in this cell's trace too
    # (``trace.unscoped_share``). ``flash_attn_ms_per_step`` does not:
    # it looks for custom calls named ``attn.N`` and this program's are
    # ``mla.N``, after their innermost scope.
    layer = {m["name"]: m for m in manifest["per_layer"]}
    assert layer["step_unscoped_pct"]["workloads"] == [
        "mistral7b-train", CELL
    ]
    assert layer["flash_attn_ms_per_step"]["workloads"] == ["mistral7b-train"]


def test_the_file_maps_onto_the_layer_pattern(cfg_json, runner):
    cfg = runner.hybrid_config(cfg_json)
    assert cfg.leading == (("kda", "dense"),)
    assert cfg.period == (
        ("kda", "moe"), ("kda", "moe"), ("mla", "moe"), ("kda", "moe"),
    )
    assert cfg.n_periods == 1 and cfg.n_layers == 5
    assert cfg.vocab_size == 20480 and cfg.embed_dim == 2304
    assert (cfg.n_experts, cfg.moe_top_k, cfg.experts_held) == (
        256, 8, (0, 8)
    )
    assert (cfg.qk_nope_dim + cfg.qk_rope_dim, cfg.v_head_dim) == (192, 128)
    # Published keys are kept where the cut does not touch them.
    assert cfg_json["vocab_size"] == 163840 and cfg_json["head_dim"] == 72
    # Two periods would be whole too; a layer count that cuts one is not.
    assert runner.hybrid_config(
        dict(cfg_json, num_hidden_layers=9)
    ).n_periods == 2
    with pytest.raises(ValueError):
        runner.hybrid_config(dict(cfg_json, num_hidden_layers=7))


def test_the_parameter_count_is_the_one_the_file_states(cfg_json, runner):
    """602 M, counted from the program's own tree at published widths
    (shapes only) and by ``flops_kimi_linear`` from the file."""
    from dlrover_tpu.models import hybrid

    cfg = runner.hybrid_config(cfg_json)
    shapes = jax.eval_shape(
        lambda: hybrid.init_params(cfg, jax.random.key(0))[0]
    )
    counted = sum(
        math.prod(x.shape) for x in jax.tree_util.tree_leaves(shapes)
    )
    assert counted == flops_kimi_linear.total_params(cfg_json)
    assert 600e6 < counted < 604e6
    assert flops_kimi_linear.kda_params(cfg_json) == pytest.approx(
        39.5e6, rel=0.005
    )
    assert flops_kimi_linear.mla_params(cfg_json) == pytest.approx(
        29.1e6, rel=0.005
    )
    per_token = flops_kimi_linear.train_flops_per_token(cfg_json, 8192, 1.0)
    assert 2.2e9 < per_token < 2.5e9         # the issue's ~2.4 GFLOP
    kda = 6.0 * 4 * flops_kimi_linear.kda_params(cfg_json)
    assert 0.38 < kda / per_token < 0.48     # "KDA mixers ~45 % of it"


@pytest.mark.parametrize("trace", [0, 1], ids=["timed", "traced"])
def test_train_hybrid_runner_rehearsal(tmp_path, runner, trace):
    ctx = tiny_kimi.context(tmp_path, trace=trace)
    facts = runner.run(ctx)
    manifest = common.load_manifest()
    for group in ("end_to_end", "per_layer"):
        manifest[group] = [
            {k: v for k, v in m.items() if k != "workloads"}
            for m in manifest[group]
            if m["name"] in READERS + ["train_tokens_per_s", "setup_s"]
        ]
    line, problems = bench_run.result_line(manifest, ctx, facts)
    assert problems == [] and line["correct"]
    assert line["failed"] == 0 and line["attempted"] >= 4
    if trace:
        # The CPU has no device plane: the trace readers find nothing;
        # the clock's and the counters' report.
        assert set(line["metrics"]) == {
            "hybrid_train_mfu_pct", "expert_rows_per_held_expert_mean",
            "expert_load_max_over_mean",
        }
        assert facts["traced_steps"] == [3, 5]
    else:
        assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
        assert line["metrics"]["train_tokens_per_s"]["value"] > 0
    warm = common.by_event(facts["events"], "warm")[0]
    assert warm["losses"][0] == pytest.approx(
        warm["reference_loss"], rel=1e-5
    )
    # The first step's gradient, read back from Adam's first moment,
    # against jax.grad of the reference: every leaf (float32 here).
    errors = common.by_event(facts["events"], "gradient")[0]["errors"]
    assert len(errors) > 60 and max(errors.values()) < 1e-4
    # The program's chunked scan against the token-by-token recurrence
    # on the first layer's own inputs: output and five gradients.
    scan = common.by_event(facts["events"], "scan")[0]["errors"]
    assert set(scan) == {"o", "dq", "dk", "dv", "dg", "dbeta"}
    assert max(scan.values()) < 1e-5
    counters = facts["counters"]
    assert len(counters["moe_rows_held"]) == line["attempted"]
    assert not any(counters["moe_rows_dropped"])
    # 80 tokens x 2 of 16 experts, 4 held: 10 rows an expert a layer.
    rows = line["metrics"].get("expert_rows_per_held_expert_mean")
    if rows:
        assert 5 < rows["value"] < 20


@pytest.mark.parametrize("name", sorted(controls_kimi_linear.PLANTS))
def test_a_planted_control_comes_out_as_it_should(tmp_path, name):
    """Through ``run`` and ``result_line``, as on the chip: a reference
    whose KDA recurrence is carried in bfloat16, or whose router does
    not renormalise, makes the sound program NOT correct; a set-up bias
    that sends every row to the held experts (the full row buffer of
    ``moe_mlp_share``) stays correct and drops nothing."""
    line = controls_kimi_linear.run_control(
        name, tiny_kimi.context(tmp_path)
    )
    assert line["correct"] == controls_kimi_linear.EXPECT_CORRECT[name]
    assert bool(line["problems"]) != line["correct"]
    assert line["moe_rows_dropped"] == 0
    if name == "skewed_router":
        # 80 tokens x top-2, all on held experts, 4 expert layers.
        assert line["moe_rows_held_a_step"] == 80 * 2 * 4
    # The plant is gone again.
    assert reference_kimi_linear.experts.__module__ == (
        reference_kimi_linear.__name__
    )


def test_the_limits_fail_what_is_past_them(runner):
    ref = 10.45226
    assert runner.loss_problems(ref * (1 + 0.9 * runner.LOSS_RTOL), ref) == []
    assert runner.loss_problems(ref * (1 + 1.1 * runner.LOSS_RTOL), ref)
    assert runner.loss_problems(float("nan"), ref)
    # (sum of squared differences, sum of squares) a leaf -> errors.
    errors = runner.relative_errors({"a": (4.0, 100.0), "b": (0.0, 300.0)})
    assert errors == {"a": 0.2, "b": 0.0, "all": 0.1}
    plain, routed = "['period'][0]['mixer']['wq']", "['period'][0]['ffn']"
    sound = {
        plain: 0.9 * runner.GRAD_RTOL,
        routed + "['router']": 0.9 * runner.GRAD_RTOL_ROUTED,
        routed + "['w_up']": 1.1 * runner.GRAD_RTOL,
        routed + "['shared']['w_up']": 0.9 * runner.GRAD_RTOL,
        "['leading'][0]['ffn']['w_up']": 0.9 * runner.GRAD_RTOL,
        "all": 0.0,
    }
    assert runner.routed_leaves(sound) == {
        routed + "['router']", routed + "['w_up']"
    }
    assert runner.gradient_problems(sound) == []
    for leaf, limit in ((plain, runner.GRAD_RTOL),
                        (routed + "['shared']['w_up']", runner.GRAD_RTOL),
                        ("['leading'][0]['ffn']['w_up']", runner.GRAD_RTOL),
                        (routed + "['router']", runner.GRAD_RTOL_ROUTED)):
        (problem,) = runner.gradient_problems(dict(sound, **{leaf: 1.1 * limit}))
        assert leaf in problem
    assert runner.gradient_problems({"all": 0.0, plain: float("nan")})
    assert runner.scan_problems({"o": 0.9 * runner.SCAN_RTOL, "dg": 0.0}) == []
    (problem,) = runner.scan_problems({"o": 0.0, "dg": 1.1 * runner.SCAN_RTOL})
    assert "dg" in problem


def test_balanced_bias_evens_out_a_skewed_router():
    """The set-up step that takes the place of a trained router's load
    balancing (benchmark code: the reference's layers): with a common
    mode pushed into every token (an embedding with a large mean), a few
    experts take most rows of the PROGRAM's layers; after it the busiest
    expert's share is near the mean, and the tree is the one the train
    state holds."""
    from dlrover_tpu.models import hybrid

    cfg = hybrid.tiny_config(n_experts=16, experts_held=(0, 16))
    params, _ = hybrid.init_params(cfg, jax.random.key(3))
    params["embed"] = params["embed"] + 2.0
    buffers = hybrid.init_buffers(cfg, jax.random.key(3))
    tokens = jax.random.randint(
        jax.random.key(4), (2, 321), 0, cfg.vocab_size
    )

    def busiest(b):
        _, aux = hybrid.loss_fn(cfg, params, {"tokens": tokens}, b)
        c = aux["counters"]
        assert int(c["moe_rows_held"]) == 4 * 2 * 320 * cfg.moe_top_k
        return int(c["moe_rows_max"]) / (int(c["moe_rows_held"]) / 16)

    balanced = reference_kimi_linear.balanced_bias(
        params, buffers, np.asarray(tokens),
        {"top_k": cfg.moe_top_k, "first_expert": 0,
         "routed_scaling": cfg.routed_scaling},
    )
    assert jax.tree_util.tree_structure(balanced) == (
        jax.tree_util.tree_structure(buffers)
    )
    for new, old in zip(jax.tree_util.tree_leaves(balanced),
                        jax.tree_util.tree_leaves(buffers)):
        assert new.shape == old.shape
    before, after = busiest(buffers), busiest(balanced)
    assert before > 2.0 and after < 1.5, (before, after)


def _op(name, dur_ms, op_name, category="fusion"):
    return [name, 0, int(dur_ms * 1e6), op_name, category]


DUMP = {"planes": {"/device:TPU:0": {trace_reduce.OPS_LINE: [
    _op("fusion.1", 30, "jit(step)/jvp(attn)/kda/mul"),
    _op("fusion.2", 50, "jit(step)/transpose(jvp(attn))/kda/kda_scan/"
        "while/body/dot_general"),
    _op("while.3", 999, "jit(step)/jvp(attn)/kda/kda_scan/while", "while"),
    _op("mla.4", 80, "jit(step)/jvp()/while/body/closed_call/attn/mla/"
        "pallas_call", trace_reduce.KERNEL),
    _op("fusion.5", 2, "jit(step)/jvp()/while/body/attn/mla/concatenate"),
    _op("gmm.6", 4, "jit(step)/mlp/experts/jit(gmm)/pallas_call",
        trace_reduce.KERNEL),
    _op("fusion.7", 6, "jit(step)/transpose(jvp(mlp))/experts/gather"),
    _op("fusion.8", 1, "jit(step)/mlp/router/top_k"),
    _op("fusion.9", 99, "jit(step)/optimizer/add"),
]}}, "host": []}


def test_scope_reducer_books_the_innermost_scope():
    out = hybrid_scopes.reduce(DUMP)
    ms = {k: round(1e3 * v, 6) for k, v in out["scope_s"].items()}
    assert ms == {"kda": 30, "kda_scan": 50, "mla": 82, "experts": 10,
                  "router": 1}
    assert {k: round(1e3 * v, 6) for k, v in out["kernel_s"].items()} == {
        "mla": 80, "experts": 4,
    }
    assert out["device_op_s"] == pytest.approx(0.272)  # the envelope is out
    dense = {"planes": {"/device:TPU:0": {trace_reduce.OPS_LINE: [
        _op("fusion.1", 30, "jit(step)/jvp(attn)/bsd,dhk->bshk/dot_general"),
    ]}}, "host": []}
    assert hybrid_scopes.reduce(dense) is None
    assert hybrid_scopes.reduce({"planes": {}, "host": []}) is None


def _facts(cfg_json):
    """Two traced steps of the real configuration, by hand."""
    return {
        "hybrid_scopes": hybrid_scopes.reduce(DUMP),
        "trace": {"steps": 2},
        "window": {"tokens_per_s": 16384.0, "tokens_per_step": 8192,
                   "micro_batch": 1, "seq_len": 8192, "steps": 3},
        "counters": {"moe_rows_held": [8192, 8000, 8384, 8192, 8192],
                     "moe_rows_max": [400, 400, 400, 512, 512],
                     "moe_rows_dropped": [0] * 5},
        "traced_steps": [1, 3], "window_steps": [3, 5],
        "device": {"kind": "TPU v5 lite"},
        "ctx": {"config": cfg_json, "chips": 1,
                "peaks_table": common.load_json("peaks.json")},
    }


def _expected(cfg_json):
    peak = 197e12
    scan = flops_kimi_linear.kda_scan_step(cfg_json, 1, 8192)
    flash = flops_kimi_linear.mla_flash_step(cfg_json, 1, 8192)
    gmm = flops_kimi_linear.expert_gmm_step(cfg_json, 8192.0)
    least = lambda w: max(w["flops"] / peak, w["bytes"] / 819e9)  # noqa: E731
    per_token = flops_kimi_linear.train_flops_per_token(cfg_json, 8192, 1.0)
    return {
        "kda_scan_ms_per_step": 25.0,
        "kda_scan_roofline": 100 * least(scan) / 0.025,
        "mla_attn_ms_per_step": 40.0,
        "mla_attn_roofline": 100 * least(flash) / 0.040,
        "expert_ffn_ms_per_step": 5.0,
        "expert_ffn_roofline": 100 * least(gmm) / 0.005,
        "kda_layers_share_pct": 100 * 80 / 272,
        "hybrid_train_mfu_pct": 100 * per_token * 16384.0 / peak,
        "expert_rows_per_held_expert_mean": 256.0,
        "expert_load_max_over_mean": 0.5,
    }


@pytest.mark.parametrize("name", READERS)
def test_reader_on_hand_built_facts(cfg_json, name):
    value = bench_run.load_module("layer_metrics", name).read(
        _facts(cfg_json)
    )
    assert value == pytest.approx(_expected(cfg_json)[name], rel=1e-9)
    if name.endswith("_roofline"):
        assert 0 < value < 100


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_on_empty_facts(cfg_json, name):
    """A run with no trace, no counters and no window -- what the parent
    commit, which has none of these scopes, would hand over."""
    ctx = {"config": cfg_json, "chips": 1, "peaks_table": tiny.PEAKS}
    read = bench_run.load_module("layer_metrics", name).read
    assert read({"ctx": ctx, "device": {"kind": "cpu"}}) is None
    assert read({
        "ctx": ctx, "device": {"kind": "cpu"}, "trace": None,
        "hybrid_scopes": None, "window": None, "counters": {},
    }) is None


def test_the_work_functions_count_what_their_docstrings_say(cfg_json):
    scan = flops_kimi_linear.kda_scan_step(cfg_json, 1, 8192)
    # 4 KDA layers x 8,192 tokens x 32 heads x 3 passes of the chunk
    # algebra: 64 (3*128 + 2*128) + 2*64^2/3 + 6*128^2 a head-token.
    per_head = 64 * 640 + 2 * 64 * 64 / 3 + 6 * 128 * 128
    assert scan["flops"] == pytest.approx(4 * 8192 * 32 * 3 * per_head)
    flash = flops_kimi_linear.mla_flash_step(cfg_json, 1, 8192)
    assert flash["flops"] == pytest.approx(
        32 * 8192 * 8192 * (5 * 192 + 4 * 128)
    )
    assert flash["bytes"] == 8192 * 32 * 2 * (8 * 192 + 9 * 128)
    gmm = flops_kimi_linear.expert_gmm_step(cfg_json, 2048)
    assert gmm["flops"] == 6 * 2048 * 3 * 2304 * 1024
    assert flops_kimi_linear.layer_kinds(cfg_json)[3] == ("mla", "moe")
