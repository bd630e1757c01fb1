"""``glm47flash-train-8k`` off the chip: the configuration file holds the
published widths and states its cut, maps onto the program's layer
pattern and counts the parameters it says, the runner works end to end
at tiny size (timed and traced) and holds the program to the plain
reference by both losses AND by gradient, every planted control comes
out NOT correct through the harness's own comparison, a wrong first loss
and a wrong module loss each fail ``correct``, the scope table tells the
module's ops from the stack's, and every new reader gives the right
number on hand-built facts and nothing on empty ones."""

import json
import math

import jax
import pytest

from benchmark import common, controls_glm, flops_glm, mtp_scopes
from benchmark import reference_glm
from benchmark import run as bench_run
from benchmark import trace_reduce
from tests.benchmark import tiny, tiny_glm

CELL = "glm47flash-train-8k"
READERS = [
    "latent_train_mfu_pct", "rope_mla_attn_ms_per_step",
    "rope_mla_attn_roofline", "share_expert_ffn_ms_per_step",
    "share_expert_ffn_roofline", "mtp_step_share_pct",
    "mtp_vocab_ms_per_step", "share_expert_rows_per_held_expert_mean",
]
PUBLISHED = {
    "hidden_size": 2048, "intermediate_size": 10240,
    "moe_intermediate_size": 1536, "num_attention_heads": 20,
    "num_key_value_heads": 20, "q_lora_rank": 768, "kv_lora_rank": 512,
    "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256,
    "num_experts_per_tok": 4, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "first_k_dense_replace": 1,
    "num_nextn_predict_layers": 1, "rope_theta": 1000000,
    "vocab_size": 154880, "max_position_embeddings": 202752,
    "rms_norm_eps": 1e-05, "n_group": 1, "topk_group": 1,
    "partial_rotary_factor": 1, "rope_scaling": None,
    "norm_topk_prob": True, "topk_method": "noaux_tc",
    "model_type": "glm4_moe_lite",
}


@pytest.fixture(scope="module")
def cfg_json():
    return common.load_json("configs", "glm-4.7-flash.json")


@pytest.fixture(scope="module")
def runner():
    return bench_run.load_module("runners", "train_latent")


def test_the_manifest_lists_the_cell_and_its_readers():
    manifest = common.load_manifest()
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "pretrain-mtp-8k"
    assert cell["config"] == "glm-4.7-flash"
    entry = next(
        c for c in manifest["configs"] if c["name"] == "glm-4.7-flash"
    )
    assert entry["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_rows_held"
    ]
    mine = [
        m["name"] for m in manifest["per_layer"]
        if m.get("workloads") == [CELL]
    ]
    assert set(READERS) <= set(mine)
    for m in manifest["per_layer"]:
        if m["name"] in READERS:
            assert m["moves"] == "train_tokens_per_s"
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert CELL in e2e["train_tokens_per_s"]["workloads"]
    traffic = common.load_json("traffic", "pretrain-mtp-8k.json")
    assert traffic["runner"] == "train_latent" and traffic["seq_len"] == 8192
    assert (traffic["warm_steps"], traffic["trace_steps"]) == (3, 4)


def test_the_file_holds_the_published_widths_and_states_its_cut(cfg_json):
    for key, value in PUBLISHED.items():
        assert cfg_json[key] == value, key
    assert cfg_json["published"] == {
        "num_hidden_layers": 47, "n_routed_experts": 64,
        "vocab_rows_held": 154880,
    }
    assert (cfg_json["num_hidden_layers"], cfg_json["n_routed_experts"],
            cfg_json["vocab_rows_held"]) == (5, 8, 19360)
    assert set(cfg_json["reduced"]) == set(cfg_json["published"])
    assert cfg_json["share"] == {
        "chips_per_layer": 8, "expert_rank": 0, "vocab_chips": 8,
    }
    assert "8 chips share each layer" in cfg_json["deployment"]
    for name in ("mtp_weight", "mtp_join_order", "mtp_hidden",
                 "mtp_positions", "rope_pairing", "head_dim", "remat",
                 "router_bias", "learning_rate", "rms_norm_eps", "weights"):
        assert name in cfg_json["assumed"], name
    assert cfg_json["head_dim"] == cfg_json["qk_rope_head_dim"]
    assert cfg_json["train"]["learning_rate"] == 1e-6


def test_the_assumed_words_are_the_references(cfg_json):
    """What the file assumes about the module, word for word in the
    reference's docstring."""
    doc = " ".join(reference_glm.__doc__.split())
    for words in (
        "BEFORE the main model's final norm",
        "the hidden state first",
        "position ``i`` for ``u_i``",
        "channel ``j`` paired with channel ``j + rope / 2``",
    ):
        assert words in doc, words
    assert cfg_json["train"]["mtp_weight"] == 0.3


def test_the_file_maps_onto_the_layer_pattern(cfg_json, runner):
    cfg = runner.latent_config(cfg_json)
    assert cfg.leading == (("mla_rope", "dense"),)
    assert cfg.period == (("mla_rope", "moe"),) and cfg.n_periods == 4
    assert cfg.n_layers == 5 and cfg.mtp_depth == 1
    assert cfg.mtp_kinds == ("mla_rope", "moe") and cfg.positional
    assert cfg.vocab_size == 19360 and cfg.embed_dim == 2048
    assert (cfg.n_experts, cfg.moe_top_k, cfg.experts_held) == (64, 4, (0, 8))
    assert (cfg.qk_nope_dim + cfg.qk_rope_dim, cfg.v_head_dim) == (256, 256)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.n_heads) == (768, 512, 20)
    assert cfg.rope_theta == 1e6 and cfg.routed_scaling == 1.8
    assert cfg.mtp_weight == 0.3 and cfg.remat_keep == "attention"
    with pytest.raises(ValueError):
        runner.latent_config(dict(cfg_json, rope_scaling={"factor": 2}))
    with pytest.raises(ValueError):
        runner.latent_config(dict(cfg_json, n_group=2))


def test_the_parameter_count_is_the_one_the_file_states(cfg_json, runner):
    """706.5 M, counted from the program's own tree at published widths
    (shapes only) and by ``flops_glm`` from the file."""
    from dlrover_tpu.models import hybrid

    cfg = runner.latent_config(cfg_json)
    shapes = jax.eval_shape(
        lambda: hybrid.init_params(cfg, jax.random.key(0))[0]
    )
    count = lambda tree: sum(  # noqa: E731
        math.prod(x.shape) for x in jax.tree_util.tree_leaves(tree)
    )
    assert count(shapes) == flops_glm.total_params(cfg_json)
    assert 706e6 < count(shapes) < 707e6
    assert flops_glm.mla_params(cfg_json) == pytest.approx(21.76e6, rel=1e-3)
    assert count(shapes["mtp"]) == pytest.approx(115.22e6, rel=1e-3)
    assert count(shapes["leading"][0]) == pytest.approx(84.68e6, rel=1e-3)
    assert count(shapes["period"][0]) / 4 == pytest.approx(106.83e6, rel=1e-3)
    per_token = flops_glm.train_flops_per_token(cfg_json, 8192, 2.5)
    assert 3.5e9 < per_token < 3.8e9
    # Six blocks of flash at 256 / 256: about half of what a step needs.
    flash = flops_glm.mla_flash_step(cfg_json, 1, 8192)["flops"]
    assert flash == pytest.approx(6 * 20 * 8192 * 8192 * 9 * 256)
    assert 0.35 < 6 * flops_glm.mla_attention_flops_per_token(
        cfg_json, 8192
    ) / per_token < 0.5


@pytest.mark.parametrize("trace", [0, 1], ids=["timed", "traced"])
def test_train_latent_runner_rehearsal(tmp_path, runner, trace):
    ctx = tiny_glm.context(tmp_path, trace=trace)
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
    json.dumps(line)
    if trace:
        # The CPU has no device plane: the trace readers find nothing;
        # the clock's and the counters' report.
        assert set(line["metrics"]) == {
            "latent_train_mfu_pct", "share_expert_rows_per_held_expert_mean",
        }
        assert facts["traced_steps"] == [3, 5]
    else:
        assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
        assert line["metrics"]["train_tokens_per_s"]["value"] > 0
    warm = common.by_event(facts["events"], "warm")[0]
    assert warm["first_losses"] == pytest.approx(
        warm["reference_losses"], rel=1e-5
    )
    # The whole loss is the two parts under the module's weight.
    assert warm["losses"][0] == pytest.approx(
        warm["first_losses"][0] + 0.3 * warm["first_losses"][1], rel=1e-5
    )
    # The first step's gradient, read back from Adam's first moment,
    # against jax.vjp of the reference: every leaf (float32 here), the
    # module's, the embedding's and the head's among them.
    errors = common.by_event(facts["events"], "gradient")[0]["errors"]
    assert len(errors) == 52 and max(errors.values()) < 1e-4
    assert "['mtp']['w_eh']" in errors and "['lm_head']" in errors
    counters = facts["counters"]
    assert len(counters["moe_rows_held"]) == line["attempted"]
    assert len(counters["ce_mtp"]) == line["attempted"]
    assert not any(counters["moe_rows_dropped"])
    # 80 tokens x 2 of 16 experts, 4 held: 10 rows an expert a block.
    rows = line["metrics"].get("share_expert_rows_per_held_expert_mean")
    if rows:
        assert 5 < rows["value"] < 20
    assert all(0 < m < s for m, s in zip(
        counters["mtp_moe_rows_held"], counters["moe_rows_held"]
    ))


@pytest.mark.parametrize("name", sorted(controls_glm.PLANTS))
def test_a_planted_control_comes_out_not_correct(tmp_path, name):
    """Through ``run`` and ``result_line``, as on the chip."""
    line = controls_glm.run_control(name, tiny_glm.context(tmp_path))
    assert line["correct"] is False and line["problems"]
    said = " ".join(line["problems"])
    if name in ("mtp_weight_zero", "head_second_gradient_dropped"):
        # The losses cannot see it: the gradient does.
        assert "loss" not in said and "gradient" in said
        leaf = "['mtp']" if name == "mtp_weight_zero" else "['lm_head']"
        assert leaf in said
    else:
        assert "loss" in said
    # The plant is gone again.
    for attr in ("rotate", "queries", "route", "head_gradient",
                 "batch_loss_and_grads"):
        assert getattr(reference_glm, attr).__module__ == (
            reference_glm.__name__
        )
    assert reference_glm.matmul is jax.numpy.matmul


def test_a_wrong_first_loss_and_a_wrong_module_loss_each_fail(runner):
    ref = (9.87101, 9.87342)
    near = lambda x, limit, by: x * (1 + by * limit)  # noqa: E731
    assert runner.loss_problems(ref, ref) == []
    assert runner.loss_problems(
        (near(ref[0], runner.LOSS_RTOL, 0.9),
         near(ref[1], runner.MTP_LOSS_RTOL, 0.9)), ref
    ) == []
    (main,) = runner.loss_problems(
        (near(ref[0], runner.LOSS_RTOL, 1.1), ref[1]), ref
    )
    assert "main loss" in main
    (module,) = runner.loss_problems(
        (ref[0], near(ref[1], runner.MTP_LOSS_RTOL, 1.1)), ref
    )
    assert "prediction module's loss" in module
    assert len(runner.loss_problems((float("nan"), float("inf")), ref)) == 2
    plain = "['mtp']['block']['mixer']['w_qb']"
    routed = "['mtp']['block']['ffn']"
    sound = {
        plain: 0.9 * runner.GRAD_RTOL, "['embed']": 0.9 * runner.GRAD_RTOL,
        routed + "['router']": 0.9 * runner.GRAD_RTOL_ROUTED,
        routed + "['w_up']": min(
            1.1 * runner.GRAD_RTOL, 0.9 * runner.GRAD_RTOL_ROUTED
        ),
        routed + "['shared']['w_up']": 0.9 * runner.GRAD_RTOL,
        "all": 0.0,
    }
    assert runner.gradient_problems(sound) == []
    for leaf, limit in ((plain, runner.GRAD_RTOL),
                        ("['embed']", runner.GRAD_RTOL),
                        (routed + "['shared']['w_up']", runner.GRAD_RTOL),
                        (routed + "['router']", runner.GRAD_RTOL_ROUTED)):
        (problem,) = runner.gradient_problems(
            dict(sound, **{leaf: 1.1 * limit})
        )
        assert leaf in problem
    assert runner.gradient_problems({"all": 0.0, plain: float("nan")})


def test_balanced_bias_evens_out_the_modules_router_too():
    """The set-up step that stands in for a trained router's load
    balancing, on the PROGRAM's blocks: with a common mode pushed into
    every token, a few experts take most rows; after it the busiest
    expert's share is near the mean, in the stack and in the module, and
    the tree is the one the train state holds."""
    import numpy as np

    from dlrover_tpu.models import hybrid

    cfg = hybrid.tiny_config(
        leading=(("mla_rope", "dense"),), period=(("mla_rope", "moe"),),
        n_periods=2, q_lora_rank=24, mtp_depth=1, n_experts=16,
        experts_held=(0, 16),
    )
    params, _ = hybrid.init_params(cfg, jax.random.key(3))
    params["embed"] = params["embed"] + 2.0
    buffers = hybrid.init_buffers(cfg, jax.random.key(3))
    tokens = jax.random.randint(
        jax.random.key(4), (2, 322), 0, cfg.vocab_size
    )

    def busiest(b):
        _, aux = jax.jit(
            lambda b: hybrid.loss_fn(cfg, params, {"tokens": tokens}, b)
        )(b)
        c = aux["counters"]
        assert int(c["moe_rows_held"]) == 2 * 2 * 320 * cfg.moe_top_k
        assert int(c["mtp_moe_rows_held"]) == 2 * 320 * cfg.moe_top_k
        return int(c["moe_rows_max"]) / (int(c["moe_rows_held"]) / 16)

    balanced = reference_glm.balanced_bias(
        params, buffers, np.asarray(tokens),
        {"top_k": cfg.moe_top_k, "first_expert": 0,
         "routed_scaling": cfg.routed_scaling,
         "rope_theta": cfg.rope_theta, "mtp_weight": cfg.mtp_weight},
    )
    assert jax.tree_util.tree_structure(balanced) == (
        jax.tree_util.tree_structure(buffers)
    )
    for new, old in zip(jax.tree_util.tree_leaves(balanced),
                        jax.tree_util.tree_leaves(buffers)):
        assert new.shape == old.shape
    assert not np.allclose(
        balanced["mtp"]["block"]["router_bias"],
        buffers["mtp"]["block"]["router_bias"],
    )
    before, after = busiest(buffers), busiest(balanced)
    assert before > 2.0 and after < 1.5, (before, after)


def _op(name, dur_ms, op_name, category="fusion"):
    return [name, 0, int(dur_ms * 1e6), op_name, category]


DUMP = {"planes": {"/device:TPU:0": {trace_reduce.OPS_LINE: [
    _op("mla.1", 300, "jit(step)/jvp()/while/body/closed_call/attn/mla/"
        "pallas_call", trace_reduce.KERNEL),
    _op("fusion.2", 4, "jit(step)/transpose(jvp(attn))/mla/concatenate"),
    _op("mla.3", 100, "jit(step)/jvp(mtp)/attn/mla/pallas_call",
        trace_reduce.KERNEL),
    _op("fusion.4", 2, "jit(step)/transpose(jvp(mtp))/attn/mla/mul"),
    _op("gmm.5", 8, "jit(step)/mlp/experts/jit(gmm)/pallas_call",
        trace_reduce.KERNEL),
    _op("fusion.6", 2, "jit(step)/transpose(jvp(mlp))/experts/gather"),
    _op("gmm.7", 2, "jit(step)/mtp/mlp/experts/jit(gmm)/pallas_call",
        trace_reduce.KERNEL),
    _op("fusion.8", 1, "jit(step)/mlp/router/top_k"),
    _op("fusion.9", 3, "jit(step)/mtp/join/dot_general"),
    _op("fusion.10", 10, "jit(step)/jvp(mtp)/vocab/dot_general"),
    _op("fusion.11", 12, "jit(step)/jvp(vocab)/dot_general"),
    _op("fusion.12", 1, "jit(step)/mtp/convert"),
    _op("while.13", 999, "jit(step)/jvp()/while", "while"),
    _op("fusion.14", 75, "jit(step)/optimizer/add"),
]}}, "host": []}


def test_the_scope_table_tells_the_modules_ops_from_the_stacks():
    out = mtp_scopes.reduce(DUMP)
    ms = {k: round(1e3 * v, 6) for k, v in out["scope_s"].items()}
    assert ms == {
        "mla": 304, "mtp/mla": 102, "experts": 10, "mtp/experts": 2,
        "router": 1, "mtp/join": 3, "mtp/vocab": 10, "vocab": 12, "mtp": 1,
    }
    assert {k: round(1e3 * v, 6) for k, v in out["kernel_s"].items()} == {
        "mla": 300, "mtp/mla": 100, "experts": 8, "mtp/experts": 2,
    }
    assert out["module_s"] == pytest.approx(0.118)
    assert out["device_op_s"] == pytest.approx(0.520)  # the envelope is out
    names = [k for k, _ in out["device_ops"]]
    assert names[:3] == ["mla:mla", "mtp/mla:mla", "optimizer:fusion"]
    assert "mtp/vocab:fusion" in names and "vocab:fusion" in names
    # A program without the module still books its stack; one with none
    # of these scopes books nothing.
    stack = {"planes": {"/device:TPU:0": {trace_reduce.OPS_LINE: [
        DUMP["planes"]["/device:TPU:0"][trace_reduce.OPS_LINE][0],
    ]}}, "host": []}
    assert mtp_scopes.reduce(stack)["module_s"] == 0.0
    assert mtp_scopes.reduce({"planes": {}, "host": []}) is None
    assert mtp_scopes.scope_of("jit(step)/jvp(attn)/kda/mul") is None


def _facts(cfg_json):
    """Two traced steps of the real configuration, by hand."""
    return {
        "mtp_scopes": mtp_scopes.reduce(DUMP),
        "trace": {"steps": 2},
        "window": {"tokens_per_s": 16384.0, "tokens_per_step": 8192,
                   "micro_batch": 1, "seq_len": 8192, "steps": 3},
        "counters": {"moe_rows_held": [16384, 16000, 16768, 16384, 16384],
                     "mtp_moe_rows_held": [4096, 4000, 4192, 4096, 4096],
                     "moe_rows_max": [600] * 5,
                     "moe_rows_dropped": [0] * 5},
        "traced_steps": [1, 3], "window_steps": [3, 5],
        "device": {"kind": "TPU v5 lite"},
        "ctx": {"config": cfg_json, "chips": 1,
                "peaks_table": common.load_json("peaks.json")},
    }


def _expected(cfg_json):
    peak = 197e12
    flash = flops_glm.mla_flash_step(cfg_json, 1, 8192)
    gmm = flops_glm.expert_gmm_step(cfg_json, 20480.0)
    least = lambda w: max(w["flops"] / peak, w["bytes"] / 819e9)  # noqa: E731
    per_token = flops_glm.train_flops_per_token(cfg_json, 8192, 2.5)
    return {
        "latent_train_mfu_pct": 100 * per_token * 16384.0 / peak,
        "rope_mla_attn_ms_per_step": 200.0,
        "rope_mla_attn_roofline": 100 * least(flash) / 0.200,
        "share_expert_ffn_ms_per_step": 6.0,
        "share_expert_ffn_roofline": 100 * least(gmm) / 0.006,
        "mtp_step_share_pct": 100 * 118 / 520,
        "mtp_vocab_ms_per_step": 5.0,
        "share_expert_rows_per_held_expert_mean": 512.0,
    }


@pytest.mark.parametrize("name", READERS)
def test_reader_on_hand_built_facts(cfg_json, name):
    value = bench_run.load_module("layer_metrics", name).read(
        _facts(cfg_json)
    )
    assert value == pytest.approx(_expected(cfg_json)[name], rel=1e-9)
    if name.endswith("_roofline") or "mfu" in name:
        assert 0 < value < 100


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_on_empty_facts(cfg_json, name):
    """A run with no trace, no counters and no window -- and the scope
    table of a program that has no module."""
    ctx = {"config": cfg_json, "chips": 1, "peaks_table": tiny.PEAKS}
    read = bench_run.load_module("layer_metrics", name).read
    assert read({"ctx": ctx, "device": {"kind": "cpu"}}) is None
    assert read({
        "ctx": ctx, "device": {"kind": "cpu"}, "trace": None,
        "mtp_scopes": None, "window": None, "counters": {},
    }) is None


def test_the_work_functions_count_what_their_docstrings_say(cfg_json):
    flash = flops_glm.mla_flash_step(cfg_json, 1, 8192)
    assert flash["flops"] == pytest.approx(
        6 * 20 * 8192 * 8192 * (5 * 256 + 4 * 256)
    )
    assert flash["bytes"] == 6 * 8192 * 20 * 2 * (8 * 256 + 9 * 256)
    gmm = flops_glm.expert_gmm_step(cfg_json, 2048)
    assert gmm["flops"] == 6 * 2048 * 3 * 2048 * 1536
    assert gmm["bytes"] == 6 * (
        5 * 8 * 3 * 2048 * 1536 + 2048 * (2 * 2048 + 3 * 1536)
    )
    assert (flops_glm.n_expert_layers(cfg_json),
            flops_glm.n_blocks(cfg_json)) == (4, 6)
    # The head twice, W_eh once, six attention layers, five shared
    # experts and routers, one dense FFN.
    d = 2048
    assert flops_glm.fixed_matmul_params(cfg_json) == (
        2 * d * 19360 + 6 * flops_glm.mla_params(cfg_json)
        + 3 * d * 10240 + 5 * (d * 64 + 3 * d * 1536) + 2 * d * d
    )
