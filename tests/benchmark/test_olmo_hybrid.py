"""``olmohybrid-serve-grow-6k`` off the chip: the cell finds its files and
states its cut, the program's parameter tree holds what the published keys
say, the traffic is what the cell states, the runner works end to end at
tiny size (timed and traced), each planted fault of
``controls_olmo_hybrid.py`` that a CPU can rehearse fails ``correct``, each
new reader reads a hand-made dump, and the counts behind the roofline
shares are hand numbers. The manifest's entries are looked up BY NAME:
nothing here asserts a list's last entry, its length or a count of
cells."""

import json

import jax
import numpy as np
import pytest

from benchmark import common, controls_olmo_hybrid, delta_scopes
from benchmark import flops_olmo_hybrid
from benchmark import run as bench_run
from tests.benchmark import tiny_olmo_hybrid

CELL = "olmohybrid-serve-grow-6k"
NEW_READERS = (
    "delta_state_ms_per_step", "delta_state_roofline",
    "delta_chunk_ms_per_chunk", "delta_chunk_roofline",
    "delta_full_attn_ms_per_step", "delta_full_attn_roofline",
    "delta_conv_ms_per_step",
    "delta_snapshots_given_up_per_request_mean",
    "delta_prefill_rows_again_per_request_mean",
)
TWINS = (
    "delta_state_restore_ms_p50", "delta_prefix_hit_token_share_pct",
)


@pytest.fixture(scope="module")
def manifest():
    return common.load_manifest()


@pytest.fixture(scope="module")
def cell(manifest):
    return bench_run.cell_context(manifest, CELL, 3, 30, 0, require_tpu=False)


@pytest.fixture(scope="module")
def runner():
    return bench_run.load_module("runners", "serve_delta")


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        with open(path) as f:
            rows = [json.loads(line) for line in f if line.strip()]
    except OSError:
        pytest.skip("no catalog beside the guides here")
    return next(r for r in rows if r["name"] == "Olmo-Hybrid-7B")


def test_the_cell_finds_its_files_and_states_its_cut(manifest, cell, runner):
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["traffic"] == "sessions-grow-closed-6k"
    config = next(c for c in manifest["configs"] if c["name"] == entry["config"])
    assert config["name"] == "olmo-hybrid-7b"
    assert config["reduced"] == ["num_hidden_layers", "layer_types"]
    assert config["source"] == cell["config"]["source"] == (
        "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json"
    )
    assert cell["traffic"]["runner"] == "serve_delta" and hasattr(runner, "run")
    c = cell["config"]
    assert {"assumed", "published", "deployment", "reduced"} <= set(c)
    assert c["num_hidden_layers"] == 8 and c["published"][
        "num_hidden_layers"
    ] == 32
    # two whole periods: 6 delta + 2 full
    assert c["layer_types"] == c["published"]["layer_types"][:8]
    assert c["layer_types"].count("full_attention") == 2
    assert set(c["reduced"]) == {"num_hidden_layers", "layer_types"}
    eng = c["serve_engine"]
    assert eng["slots"] == 32 and eng["max_len"] == 6656
    assert eng["prefill_chunk"] == 512 and eng["block_size"] == 64
    assert 2304 <= eng["num_blocks"] <= 3072
    assert 128 <= eng["state_snapshots"] <= 192
    # the longest session fits a slot: opening + 7 turns + 8 answers
    t = cell["traffic"]
    assert (t["opening_len"]["max"] + 7 * t["turn_len"]["max"]
            + 8 * t["output_len"]["max"]) == eng["max_len"]


def test_every_published_key_of_the_catalog_row_is_in_the_file(cell):
    row = catalog_row()
    c = cell["config"]
    assert row["source_url"] == c["source"]
    for key, value in row["config"].items():
        if key in ("num_hidden_layers", "layer_types"):
            assert c["published"][key] == value
        else:
            assert c[key] == value, key


def test_the_manifest_holds_the_cell_by_what_its_lists_contain(manifest):
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_READERS + TWINS:
        m = by_name[name]
        assert m["workloads"] == [CELL], name
        assert m["moves"] == "serve_tokens_per_s"
        assert hasattr(bench_run.load_module("layer_metrics", name), "read")
    for name in ("delta_state_roofline", "delta_chunk_roofline",
                 "delta_full_attn_roofline"):
        assert by_name[name]["unit"] == "%"
    tokens = next(
        m for m in manifest["end_to_end"] if m["name"] == "serve_tokens_per_s"
    )
    assert CELL in tokens["workloads"]
    # every reader the cell is listed under exists
    for m in manifest["per_layer"]:
        if CELL in m.get("workloads", ()):
            bench_run.load_module("layer_metrics", m["name"])


def test_parameter_count_from_the_programs_tree(cell, runner):
    from dlrover_tpu.models import delta_lm

    cfg = runner.delta_config(cell["config"])
    shapes = jax.eval_shape(
        lambda key: delta_lm.init_params(cfg, key), jax.random.key(0)
    )
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert n == cfg.count_params() == flops_olmo_hybrid.parameter_count(
        cell["config"]
    ) == cell["config"]["held_parameters"] == 2_435_748_072
    # the arithmetic of the issue: a period of 3 + 1 is ~831.7 M
    period = runner.delta_config(dict(
        cell["config"], num_hidden_layers=4,
        layer_types=cell["config"]["layer_types"][:4],
    )).count_params() - 2 * 100352 * 3840 - 3840
    assert abs(period - 831.7e6) < 1e6
    assert flops_olmo_hybrid.state_bytes_per_slot(cell["config"]) == 6 * (
        2_211_840 + 69_120
    )
    assert flops_olmo_hybrid.cache_bytes_per_token(cell["config"]) == 30_720


def test_traffic_is_what_the_cell_states(cell, runner):
    t = cell["traffic"]
    assert (t["clients"], t["turns"], t["length_set_size"],
            t["length_set_seed"]) == (64, 8, 32, 0)
    assert t["opening_len"] == {"dist": "log_uniform", "min": 512,
                                "max": 1024}
    assert t["turn_len"] == {"dist": "log_uniform", "min": 64, "max": 512}
    assert t["output_len"] == {"dist": "log_uniform", "min": 32, "max": 256}
    assert t["period_completions"] == 512 == t["clients"] * t["turns"]
    assert (t["ramp_s"], t["trace_s"], t["temperature"]) == (10.0, 3.0, 0.0)
    assert t["prefix_hit_share_min"] == 0.7 and t["reference_sample"] == 2
    assert t["reference_min_turn"] == 6
    plans = runner.schedules(t)
    assert len(plans) == 32 and plans == runner.schedules(t)
    assert all(len(adds) == len(answers) == 8 for adds, answers in plans)
    # a client's prompts grow by the answer it was given and a fresh turn
    client = runner.Client(5, plans[5], 1000, 2 ** 31 + 7)
    first, n_new = client.next_request()
    assert len(first) == plans[5][0][0] and n_new == plans[5][1][0]
    answer = list(range(n_new))
    second, _ = client.next_request(answer)
    assert list(second[:len(first)]) == list(first)
    assert list(second[len(first):len(first) + n_new]) == answer
    assert len(second) == len(first) + n_new + plans[5][0][1]
    for _ in range(6):
        client.next_request([1])
    assert client.turn == 8 and client.session == 0
    fresh, _ = client.next_request([1, 2, 3])     # a new session: no carry
    assert client.session == 1 and len(fresh) == plans[5][0][0]
    again = runner.Client(5, plans[5], 1000, 2 ** 31 + 7)
    assert list(again.next_request()[0]) == list(first)


@pytest.fixture(scope="module")
def rehearsal(runner, tmp_path_factory):
    """One timed run of the tiny cell, its facts and what its checks
    read (``runner.LAST``), for the controls that judge it again."""
    ctx = tiny_olmo_hybrid.context(tmp_path_factory.mktemp("olmo"))
    facts = runner.run(ctx)
    return ctx, facts, dict(runner.LAST)


def test_runner_rehearsal_timed(manifest, rehearsal):
    ctx, facts, last = rehearsal
    assert facts["problems"] == []
    assert facts["end_to_end"]["serve_tokens_per_s"] > 0
    assert facts["window"]["periods"] >= 1
    assert facts["prefix"]["snapshot_restores"] == facts["prefix"]["hits"] > 0
    assert facts["prefix"]["hit_share"] >= 0.3
    assert facts["prefix"]["snapshots_given_up"] > 0
    assert facts["prefix"]["evicted_blocks"] > 0
    assert facts["kv_stats"]["state_snapshots_denied"] == 0
    check = facts["reference"]
    assert check["n_requests"] == 2 and check["n_snapshots_read"] == 2
    assert all(t >= 3 for t in check["turns_judged"])
    assert check["logits_err_median"] < 1e-4
    assert check["low_logits_err_median"] > 0.05
    assert len(last["requests"]) == 2
    line, problems = bench_run.result_line(
        manifest, dict(ctx, workload=CELL), facts
    )
    assert line["correct"] and not problems
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}


def test_runner_rehearsal_traced(manifest, runner, tmp_path):
    ctx = tiny_olmo_hybrid.context(tmp_path, trace=1)
    facts = runner.run(ctx)
    assert facts["problems"] == []
    line, _ = bench_run.result_line(manifest, dict(ctx, workload=CELL), facts)
    # what the host can read without a device trace
    for name in TWINS + NEW_READERS[-2:] + (
        "decode_ms_per_token_p50", "prefill_step_share_pct",
    ):
        assert name in line["metrics"], name
    steps = [s for s in facts["spans"] if s["name"] == "serving.step"]
    decoding = [s["attrs"] for s in steps if s["attrs"].get("n_decoding")]
    assert decoding and all(
        {"state_slots", "kv_rows"} <= set(a) for a in decoding
    )
    assert all(a["state_slots"] == a["n_decoding"] for a in decoding)
    admitted = [s["attrs"] for s in steps if s["attrs"].get("state_restores")]
    assert admitted and all(
        "prefill_rows_again" in a and "state_restores_from_snapshot" in a
        for a in admitted
    )
    assert any(a.get("state_snapshots_given_up") for a in
               (s["attrs"] for s in steps))


@pytest.mark.parametrize("fault", [
    f for f in controls_olmo_hybrid.REFERENCE
    if f not in controls_olmo_hybrid.BELOW_SIGHT
] + [controls_olmo_hybrid.LOWER])
def test_a_fault_planted_in_the_reference_fails_correct(
    runner, rehearsal, fault
):
    _, _, last = rehearsal
    check, problems = controls_olmo_hybrid.rejudge(runner, last, fault)
    assert problems, (fault, check)


@pytest.mark.parametrize("fault", controls_olmo_hybrid.PROGRAM)
def test_a_fault_planted_in_the_program_fails_correct(
    runner, tmp_path, fault
):
    problems, check = controls_olmo_hybrid.served(
        runner, tiny_olmo_hybrid.context(tmp_path),
        controls_olmo_hybrid.PLANTS[fault],
    )
    assert problems, (fault, check)


def test_counts_against_hand_numbers(cell):
    c = cell["config"]
    step = flops_olmo_hybrid.delta_state_step(c, 32)
    assert step["bytes"] == 6 * 32 * 2 * 30 * 96 * 192 * 4
    assert step["flops"] == 2.0 * 6 * 32 * 3 * 30 * 96 * 192
    attn = flops_olmo_hybrid.full_attention_step(c, 60_000)
    assert attn["bytes"] == 2 * 60_000 * 2 * 3840 * 2
    one = flops_olmo_hybrid.delta_chunk(c, 64)
    per = (2 * 64 * 64 * 96 + 2 * 64 ** 3 / 3 + 64 * 64 * 96
           + 2 * 64 * 64 * 192 + 6 * 64 * 96 * 192)
    assert one["flops"] == pytest.approx(2.0 * 6 * 30 * per)
    assert flops_olmo_hybrid.delta_chunk(c, 65)["flops"] == pytest.approx(
        2 * one["flops"]
    )
    assert one["bytes"] == 6 * 2 * 30 * 96 * 192 * 4


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
        "step": {"attn/delta/kernel": 2.0, "state/dus": 1.0,
                 "attn/conv/mul": 0.5, "attn/full/kernel": 3.0,
                 "mlp/dot": 6.0, "attn/wqkv": 4.0, "other": 0.25},
        "prefill": {"attn/delta/dot": 5.0, "state/snapshot/dus": 0.25,
                    "state/dus": 0.75, "attn/full/x": 8.0,
                    "attn/conv/x": 0.5},
        "restore": {"state/restore/dus": 0.125},
    })
    table = delta_scopes.reduce(dump)
    step = table["jit_step"]["scope_s"]
    assert step["delta"] == pytest.approx(2e-3)
    assert step["state"] == pytest.approx(1e-3)
    assert step["conv"] == pytest.approx(0.5e-3)
    assert step["full"] == pytest.approx(3e-3)
    assert step["attn"] == pytest.approx(4e-3)
    assert step["unscoped"] == pytest.approx(0.25e-3)
    assert table["jit_prefill"]["scope_s"]["snapshot"] == pytest.approx(
        0.25e-3
    )
    assert table["jit_restore"]["scope_s"]["restore"] == pytest.approx(
        0.125e-3
    )
    assert delta_scopes.reduce(_dump({"step": {"attn/gqa": 1.0}})) is None
    now = 1000.0
    span = lambda **attrs: {  # noqa: E731
        "name": "serving.step", "ts": now, "dur_s": 0.01, "attrs": attrs,
    }
    facts = {
        "sparse_scopes": table, "traced_window": (now - 1, now + 1),
        "kv_stats": {"delta_decode": "state_kernel", "state_layers": 12},
        "spans": [
            span(n_decoding=32, state_slots=32, kv_rows=32 * 1900),
            span(prefill_tokens=400, prefill_kv_rows=3000,
                 state_restores=1, prefill_rows_again=400,
                 state_snapshots=1, state_snapshots_given_up=1),
        ],
        "device": {"kind": "TPU v5 lite"},
        "ctx": {"config": cell["config"],
                "peaks_table": common.load_json("peaks.json")},
    }
    read = lambda name: bench_run.load_module(  # noqa: E731
        "layer_metrics", name
    ).read(facts)
    assert read("delta_state_ms_per_step") == pytest.approx(3.0)
    assert read("delta_full_attn_ms_per_step") == pytest.approx(3.0)
    assert read("delta_conv_ms_per_step") == pytest.approx(0.5)
    assert read("delta_chunk_ms_per_chunk") == pytest.approx(6.0)
    for name in ("delta_state_roofline", "delta_chunk_roofline",
                 "delta_full_attn_roofline"):
        assert 0 < read(name) <= 100, (name, read(name))
    # a program of another model books nothing here
    other = dict(facts, sparse_scopes=None, kv_stats={"state_layers": 7})
    for name in NEW_READERS + TWINS:
        assert bench_run.load_module("layer_metrics", name).read(other) \
            is None, name
