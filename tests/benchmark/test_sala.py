"""``sala-serve-docs-64k`` off the chip: the cell finds its files and
states its cut, the program's parameter tree holds what the configuration
says, the traffic is what the cell states, the runner works end to end at
tiny size (timed and traced), each planted fault of ``controls_sala.py``
fails ``correct``, each new reader reads a hand-made dump, and the counts
behind the roofline shares are hand numbers. The manifest's lists are
held by what they CONTAIN, never by a position."""

import json

import jax
import numpy as np
import pytest

from benchmark import common, controls_sala, flops_sala, sala_scopes
from benchmark import run as bench_run
from tests.benchmark import tiny_sala

CELL = "sala-serve-docs-64k"
NEW_READERS = (
    "lightning_state_ms_per_step", "lightning_state_roofline",
    "lightning_chunk_ms_per_chunk", "lightning_chunk_roofline",
    "block_select_ms_per_step", "block_select_roofline",
    "block_attn_ms_per_step", "block_attn_roofline",
    "block_chunk_attn_ms_per_chunk", "block_chunk_attn_roofline",
    "dense_mlp_ms_per_step", "dense_mlp_roofline",
    "selected_rows_share_pct",
)
TWINS = {
    "linear_state_restore_ms_p50": "state_restore_ms_p50",
    "linear_snapshots_per_request_mean": "state_snapshots_per_request_mean",
    "linear_prefix_hit_token_share_pct": "prefix_hit_token_share_pct",
    "linear_decode_batch_mean": "decode_batch_mean",
    "linear_idle_attributed_pct": "idle_attributed_pct",
    "linear_engine_build_s": "engine_build_s",
    "linear_setup_compile_s": "setup_compile_s",
    "linear_decode_unscoped_ms_per_step": "decode_unscoped_ms_per_step",
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
    return bench_run.cell_context(manifest, CELL, 3, 30, 0, require_tpu=False)


@pytest.fixture(scope="module")
def runner():
    return bench_run.load_module("runners", "serve_linear")


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        with open(path) as f:
            rows = [json.loads(line) for line in f if line.strip()]
    except OSError:
        pytest.skip("no catalog beside the guides here")
    return next(r for r in rows if r["name"] == "MiniCPM-SALA")


def test_the_cell_finds_its_files_and_states_its_cut(manifest, cell, runner):
    cfg_json = cell["config"]
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "minicpm-sala-9b")
    assert entry["reduced"] == ["num_hidden_layers", "mixer_types"]
    assert entry["source"] == cfg_json["source"]
    assert entry["file"] == "benchmark/configs/minicpm-sala-9b.json"
    published = cfg_json["published"]
    assert published["num_hidden_layers"] == 32
    sparse_at = [i for i, t in enumerate(published["mixer_types"])
                 if t == "minicpm4"]
    assert sparse_at == [0, 9, 16, 17, 22, 29, 30, 31]
    assert cfg_json["mixer_types"] == published["mixer_types"][9:21]
    assert cfg_json["first_published_layer"] == 9
    assert cfg_json["mixer_types"].count("minicpm4") == 3
    assert cfg_json["mixer_types"].count("lightning-attn") == 9
    assert cfg_json["num_hidden_layers"] == 12
    assert set(cfg_json["reduced"]) == {"num_hidden_layers", "mixer_types"}
    assert cfg_json["assumed"]["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64,
        "topk": 64, "init_blocks": 1, "window_size": 2048,
        "dense_len": 8192,
    }
    for name in ("torch_dtype", "decay", "qk_norm", "use_output_norm",
                 "gates", "mup_denominator", "norm", "rope_pairing",
                 "residual", "weights", "serve_engine"):
        assert name in cfg_json["assumed"], name
    assert "layers 9-20 of 32" in cfg_json["deployment"]
    assert cfg_json["serve_engine"] == {
        "slots": 48, "max_len": 66560, "prefill_chunk": 512,
        "block_size": 64, "num_blocks": 10560, "state_snapshots": 64,
    }
    assert cell["traffic"]["runner"] == "serve_linear"
    assert cell["chips"] == 1
    cfg = runner.linear_config(cfg_json)
    assert (cfg.n_layers, cfg.first_layer, cfg.published_layers) == (12, 9, 32)
    assert cfg.depth_scale == pytest.approx(1.4 / 32 ** 0.5)
    assert cfg.logit_scale == 1 / 16 and cfg.scale_emb == 12
    assert cfg.cache_layers == 6 and cfg.list_blocks == 128
    assert cfg.state_rows == (
        ("lightning", (9, (32, 128, 128), "float32")),
    )


def test_every_published_key_of_the_catalog_row_is_in_the_file(cell):
    row = catalog_row()
    cfg_json = cell["config"]
    assert cfg_json["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in ("num_hidden_layers", "mixer_types"):
            assert cfg_json["published"][key] == value
        else:
            assert cfg_json[key] == value, key


def test_the_manifest_holds_the_cell_by_what_its_lists_contain(manifest):
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_READERS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "serve_tokens_per_s"
    for name in SHARED:
        assert CELL in by_name[name]["workloads"]
    keys = ("unit", "better", "source", "layer", "moves")
    for name, accepted in TWINS.items():
        assert by_name[name]["workloads"] == [CELL]
        assert [by_name[name][k] for k in keys] \
            == [by_name[accepted][k] for k in keys]
        assert CELL not in by_name[accepted]["workloads"]
    served = next(
        m for m in manifest["end_to_end"] if m["name"] == "serve_tokens_per_s"
    )
    assert CELL in served["workloads"]
    # the lists accepted tests pin, and the other cells' own
    for name in ("engine_build_s", "setup_compile_s", "decode_batch_mean",
                 "idle_attributed_pct", "host_pause_s", "host_pause_count",
                 "step_stall_share_pct", "step_stall_program_share_pct",
                 "state_restore_ms_p50", "selected_keys_share_pct"):
        assert CELL not in by_name[name]["workloads"]
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "minicpm-sala-9b", "docs-closed-64k", 1
    )
    assert len(cell["why"]) <= 200
    for name in NEW_READERS + tuple(TWINS):
        bench_run.load_module("layer_metrics", name)


def test_parameter_count_from_the_programs_tree(cell, runner):
    """3,930,007,808: the tree ``init_params`` would build, the config's
    own count, the benchmark's count from the published keys and the
    file's number agree to the parameter."""
    from dlrover_tpu.models import linear_sparse_lm

    cfg_json = cell["config"]
    cfg = runner.linear_config(cfg_json)
    tree = jax.eval_shape(
        lambda key: linear_sparse_lm.init_params(cfg, key), jax.random.key(0)
    )
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))
    assert n == cfg.count_params() == flops_sala.parameter_count(cfg_json) \
        == cfg_json["held_parameters"] == 3_930_007_808
    assert flops_sala.state_bytes_per_slot(cfg_json) == 18_874_368
    assert flops_sala.cache_bytes_per_token(cfg_json) == 3 * (1024 + 32)


def test_traffic_is_what_the_cell_states(cell):
    traffic = cell["traffic"]
    assert traffic["loop"] == "closed" and traffic["clients"] == 96
    assert traffic["documents"] == {
        "count": 8, "len": 65536, "rotation": "fixed"
    }
    for name in ("question_len", "output_len"):
        assert traffic[name] == {"dist": "log_uniform", "min": 64, "max": 512}
    assert traffic["length_set_size"] == 64
    assert traffic["length_set_seed"] == 0
    assert traffic["temperature"] == 0.0
    assert (traffic["ramp_s"], traffic["trace_s"]) == (10.0, 3.0)
    assert traffic["reference_sample"] == 2
    assert traffic["prefix_hit_share_min"] == 0.98
    eng = cell["config"]["serve_engine"]
    assert eng["max_len"] == 65536 + 512 + 512
    # the pool holds the documents, every slot's own blocks and a prompt
    assert eng["num_blocks"] >= 1 + 8 * 1024 + 48 * 17 + 1040


@pytest.fixture(scope="module")
def rehearsal(runner, tmp_path_factory):
    """One timed run of the tiny cell, its facts and what its checks
    read (``runner.LAST``), for the controls that judge it again."""
    ctx = tiny_sala.context(tmp_path_factory.mktemp("sala"))
    facts = runner.run(ctx)
    return ctx, facts, dict(runner.LAST)


def test_runner_rehearsal_timed(manifest, rehearsal):
    ctx, facts, last = rehearsal
    assert facts["problems"] == []
    assert facts["end_to_end"]["serve_tokens_per_s"] > 0
    assert facts["prefix"]["context_hit_share"] == 1.0
    assert facts["prefix"]["snapshot_restores"] == facts["prefix"]["hits"] > 0
    assert facts["kv_stats"]["state_snapshots_denied"] == 0
    assert facts["kv_stats"]["state_snapshots_given_up"] > 0
    check = facts["reference"]
    assert check["n_requests"] == 2 and check["lists_equal_share"] == 1.0
    assert check["n_snapshots_read"] == 2
    assert len(last["requests"]) == 2
    line, problems = bench_run.result_line(
        manifest, dict(ctx, workload=CELL), facts
    )
    assert line["correct"] and not problems
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}


def test_runner_rehearsal_traced(manifest, runner, tmp_path):
    ctx = tiny_sala.context(tmp_path, trace=1)
    facts = runner.run(ctx)
    assert facts["problems"] == []
    line, _ = bench_run.result_line(manifest, dict(ctx, workload=CELL), facts)
    # what the host can read without a device trace
    for name in ("selected_rows_share_pct", "linear_decode_batch_mean",
                 "linear_snapshots_per_request_mean",
                 "linear_state_restore_ms_p50",
                 "linear_prefix_hit_token_share_pct",
                 "linear_engine_build_s", "linear_setup_compile_s",
                 "decode_ms_per_token_p50", "prefill_step_share_pct"):
        assert name in line["metrics"], name
    steps = [s for s in facts["spans"] if s["name"] == "serving.step"]
    decoding = [s["attrs"] for s in steps if s["attrs"].get("n_decoding")]
    assert decoding and all(
        {"ckey_rows", "selected_rows", "state_slots", "kv_rows"} <= set(a)
        for a in decoding
    )
    assert all(a["state_slots"] == a["n_decoding"] for a in decoding)
    admitted = [s["attrs"] for s in steps if s["attrs"].get("state_restores")]
    assert admitted and all(
        "prefix_rounded_down_blocks" in a
        and "state_restores_from_snapshot" in a for a in admitted
    )


@pytest.mark.parametrize("fault", [
    f for f in controls_sala.REFERENCE if f not in controls_sala.BELOW_SIGHT
] + [controls_sala.LOWER])
def test_a_fault_planted_in_the_reference_fails_correct(
    runner, rehearsal, fault
):
    _, _, last = rehearsal
    if fault == controls_sala.LOWER:
        kw = dict(judged=fault)
    else:
        kw = dict(faults=(fault,))
    check, problems, _ = runner.judge(
        last["requests"], last["params"], last["sh"], last["doc_len"],
        carry=last["carry"], **kw
    )
    assert problems, (fault, check)


@pytest.mark.parametrize("fault", controls_sala.PROGRAM)
def test_a_fault_planted_in_the_program_fails_correct(
    runner, tmp_path, fault
):
    problems, check = controls_sala.served(
        runner, tiny_sala.context(tmp_path), controls_sala.PLANTS[fault]
    )
    assert problems, (fault, check)


def test_counts_against_hand_numbers(cell):
    cfg = cell["config"]
    state = flops_sala.lightning_state_step(cfg, 48)
    assert state["bytes"] == 48 * 9 * 2 * 2_097_152
    select = flops_sala.block_select_step(cfg, 1000)
    assert select["bytes"] == 1000 * 512
    attn = flops_sala.block_attention_step(cfg, 4096)
    assert attn["bytes"] == 4096 * 3 * 2 * 512
    mlp = flops_sala.dense_mlp_step(cfg, 48)
    assert mlp["bytes"] == 12 * 201_326_592 * 2
    assert flops_sala.rows_listed(cfg, 8191) == 8192
    assert flops_sala.rows_listed(cfg, 65536) == 63 * 64 + 1
    assert flops_sala.rows_listed(cfg, 65599) == 64 * 64
    chunk = flops_sala.lightning_chunk(cfg, 512)
    assert chunk["bytes"] == 9 * 2 * 2_097_152
    assert chunk["flops"] == 2.0 * 9 * 32 * (
        512 * 513 / 2 * 256 + 2 * 512 * 128 * 128
    )
    one = flops_sala.block_chunk(cfg, [(65536, 64)])
    pairs = sum(63 * 64 + 1 + i for i in range(64))
    places = sum((65536 + i + 1) // 16 - 1 for i in range(64))
    assert one["flops"] == 3 * (pairs * 4 * 128 * 32 + places * 2 * 128 * 32)
    assert one["bytes"] == 3 * (65600 * 2 * 512 + 65600 // 16 * 512)


def _dump(scopes_ms):
    """A hand-made dump: one plane, one launch a program, an op a
    scope of ``scopes_ms[program]``."""
    from benchmark import trace_reduce

    ops, modules, at = [], [], 0
    for program, scopes in scopes_ms.items():
        start = at
        for scope, ms in scopes.items():
            ops.append(["fusion", at, ms * 1e6, f"jit({program})/{scope}/dot",
                        "fusion"])
            at += ms * 1e6
        modules.append([f"jit_{program}(1)", start, at - start])
        at += 1e6
    return {"planes": {"/device:TPU:0": {
        trace_reduce.OPS_LINE: ops, trace_reduce.MODULES_LINE: modules,
    }}}


def test_scope_table_and_the_new_readers_on_a_hand_made_dump(cell):
    dump = _dump({
        "step": {"attn/lightning/mul": 2.0, "state/dus": 1.0,
                 "attn/select/top_k": 4.0, "attn/sparse/kernel": 1.0,
                 "mlp/dot": 6.0, "attn/wq": 3.0, "other": 0.5},
        "prefill": {"attn/lightning/dot": 0.5, "state/snapshot/dot": 0.25,
                    "attn/select/x": 4.0, "attn/sparse/x": 20.0},
    })
    table = sala_scopes.reduce(dump)
    step = table["jit_step"]["scope_s"]
    assert step["lightning"] == pytest.approx(2e-3)
    assert step["state"] == pytest.approx(1e-3)
    assert step["select"] == pytest.approx(4e-3)
    assert step["attn"] == pytest.approx(3e-3)
    assert step["unscoped"] == pytest.approx(0.5e-3)
    assert table["jit_prefill"]["scope_s"]["snapshot"] == pytest.approx(
        0.25e-3
    )
    assert sala_scopes.reduce(_dump({"step": {"attn/gqa": 1.0}})) is None
    now = 1000.0
    span = lambda **attrs: {  # noqa: E731
        "name": "serving.step", "ts": now, "dur_s": 0.01, "attrs": attrs,
    }
    facts = {
        "sparse_scopes": table, "traced_window": (now - 1, now + 1),
        "kv_stats": {"lightning_decode": "state_kernel", "state_layers": 9},
        "spans": [
            span(n_decoding=48, state_slots=48, ckey_rows=48 * 3 * 4100,
                 selected_rows=48 * 4050, kv_rows=48 * 65800),
            span(prefill_tokens=200, prefill_kv_rows=65736),
        ],
        "device": {"kind": "TPU v5 lite"},
        "ctx": {"config": cell["config"],
                "peaks_table": common.load_json("peaks.json")},
    }
    read = lambda name: bench_run.load_module(  # noqa: E731
        "layer_metrics", name
    ).read(facts)
    assert read("lightning_state_ms_per_step") == pytest.approx(3.0)
    assert read("block_select_ms_per_step") == pytest.approx(4.0)
    assert read("block_attn_ms_per_step") == pytest.approx(1.0)
    assert read("dense_mlp_ms_per_step") == pytest.approx(6.0)
    assert read("lightning_chunk_ms_per_chunk") == pytest.approx(0.75)
    assert read("block_chunk_attn_ms_per_chunk") == pytest.approx(24.0)
    peaks = common.load_json("peaks.json")
    hbm = next(iter(
        v for k, v in peaks.items() if "v5" in k.lower()
    ))
    for name in ("lightning_state_roofline", "block_select_roofline",
                 "block_attn_roofline", "dense_mlp_roofline",
                 "lightning_chunk_roofline", "block_chunk_attn_roofline"):
        assert 0 < read(name) <= 100, (name, read(name), hbm)
    # a program of another model books nothing here
    other = dict(facts, sparse_scopes=None, kv_stats={"state_layers": 7})
    for name in NEW_READERS + tuple(TWINS):
        assert bench_run.load_module("layer_metrics", name).read(other) \
            is None, name
