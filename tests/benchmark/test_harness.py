"""The benchmark's harness off the chip: the manifest keeps to its
contract, every runner works end to end at tiny size and returns what
the last line needs, ``run.py`` refuses to print a result without a TPU
or without the program, and a configuration, a traffic mix and a
per-layer metric can each be added as files plus manifest entries."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmark import common, run as bench_run
from tests.benchmark import tiny

REPO = common.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def manifest():
    return common.load_manifest()


def test_manifest_has_exactly_the_contract_keys(manifest):
    assert set(manifest) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert manifest["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= manifest["run_seconds"] <= 51
    assert len(json.dumps(manifest)) < 64 * 1024
    for path in manifest["paths"]:
        assert os.path.isdir(os.path.join(REPO, path))


def test_names_units_and_whys(manifest):
    named = (
        manifest["configs"] + manifest["workloads"]
        + manifest["end_to_end"] + manifest["per_layer"]
    )
    for entry in named:
        assert NAME.match(entry["name"]), entry["name"]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in manifest[group]]
        assert len(set(names)) == len(names), group
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        if "_roofline" in m["name"]:
            assert m["unit"] == "%"
    for entry in manifest["configs"] + manifest["workloads"]:
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]


def test_end_to_end_metrics_and_bounds(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert set(e2e["setup_s"]) == {"name", "unit", "better", "bound",
                                   "source"}  # reported by every cell
    for m in e2e.values():
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in manifest["workloads"]}
    for cell in cells:
        others = [
            m for m in e2e.values()
            if m["name"] != "setup_s" and bench_run.reported_in(m, cell)
        ]
        assert others, f"{cell} reports no end-to-end metric but setup_s"


def test_every_layer_metric_moves_a_metric_its_cells_report(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    cells = [w["name"] for w in manifest["workloads"]]
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert bench_run.reported_in(e2e[m["moves"]], cell), (
                f"{m['name']} moves {m['moves']}, which {cell} does "
                f"not report"
            )
        assert os.path.exists(os.path.join(
            common.HERE, "layer_metrics", m["name"] + ".py"
        ))
    for cell in cells:
        assert any(
            bench_run.reported_in(m, cell) for m in manifest["per_layer"]
        )


def test_every_cell_finds_its_files(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    files = [c["file"] for c in configs.values()]
    assert len(set(files)) == len(files)
    used = set()
    for cell in manifest["workloads"]:
        assert cell["chips"] in (1, 4)
        config = configs[cell["config"]]
        used.add(config["name"])
        assert any(
            config["file"].startswith(p + "/") for p in manifest["paths"]
        )
        with open(os.path.join(REPO, config["file"])) as f:
            cfg_json = json.load(f)
        for key in config["reduced"]:
            assert NAME.match(key) and key in cfg_json["reduced"]
            assert cfg_json["published"][key] != cfg_json[key]
        assert not any(
            k.endswith(("_dim", "_rank", "_size")) for k in config["reduced"]
        )
        assert cfg_json["source"] == config["source"]
        assert {"assumed", "deployment"} <= set(cfg_json)
        common.lm_config(cfg_json)  # maps onto the program's config
        traffic = common.load_json("traffic", cell["traffic"] + ".json")
        assert os.path.exists(os.path.join(
            common.HERE, "runners", traffic["runner"] + ".py"
        ))
    assert used == set(configs), "a configuration no cell uses"
    pairs = [(c["config"], c["traffic"]) for c in manifest["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_peaks_table_names_its_source_and_refuses_strangers():
    from benchmark import flops

    table = common.load_json("peaks.json")
    v5e = flops.peaks_for("TPU v5 lite", table)
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["source"]
    with pytest.raises(KeyError):
        flops.peaks_for("TPU v9", table)


def _line(manifest, ctx, facts):
    line, problems = bench_run.result_line(manifest, ctx, facts)
    assert LINE_KEYS <= set(line) <= LINE_KEYS | {"breakdown"}
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"]
    )
    json.dumps(line)  # plain numbers and strings only
    return line, problems


# Stand-in manifests: the real metric entries, every one reported by the
# rehearsal's cell.
def _for_cell(manifest, names):
    out = dict(manifest)
    for group in ("end_to_end", "per_layer"):
        out[group] = [
            {k: v for k, v in m.items() if k != "workloads"}
            for m in manifest[group]
            if m["name"] in names or m["name"] == "setup_s"
        ]
    return out


@pytest.mark.parametrize("trace", [0, 1], ids=["timed", "traced"])
def test_train_runner_rehearsal(manifest, tmp_path, trace):
    ctx = tiny.context("pretrain-4k", tmp_path, trace=trace)
    facts = bench_run.load_module("runners", "train").run(ctx)
    cell = _for_cell(manifest, {
        "train_tokens_per_s", "train_mfu_pct", "step_unscoped_pct",
        "flash_attn_ms_per_step", "flash_attn_roofline",
    })
    line, problems = _line(cell, ctx, facts)
    assert problems == [] and line["correct"]
    assert line["failed"] == 0 and line["attempted"] >= 4
    if trace:
        # The CPU has no device plane: the trace readers find nothing
        # to read and are left out; the clock-based one reports.
        assert set(line["metrics"]) == {"train_mfu_pct"}
    else:
        assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
        assert line["metrics"]["train_tokens_per_s"]["value"] > 0
    warm = common.by_event(facts["events"], "warm")[0]
    assert warm["losses"][0] == pytest.approx(
        warm["reference_loss"], rel=1e-5
    )


def test_a_wrong_first_loss_fails_the_train_check():
    train = bench_run.load_module("runners", "train")
    assert train.loss_problems(10.8857, 10.8858) == []
    assert train.loss_problems(10.89, 10.8858)      # 4e-4 off
    assert train.loss_problems(float("nan"), 10.8858)


@pytest.mark.parametrize("trace", [0, 1], ids=["timed", "traced"])
def test_serve_runner_rehearsal(manifest, tmp_path, trace):
    ctx = tiny.context("chat-closed", tmp_path, trace=trace, seconds=1.0)
    facts = bench_run.load_module("runners", "serve").run(ctx)
    cell = _for_cell(manifest, {
        "serve_tokens_per_s", "decode_ms_per_token_p50",
        "prefill_ms_per_ktoken_p50", "prefill_program_share_pct",
    })
    line, problems = _line(cell, ctx, facts)
    assert problems == [] and line["correct"]
    assert line["failed"] == 0 and line["attempted"] > 8
    if trace:
        assert set(line["metrics"]) == {
            "decode_ms_per_token_p50", "prefill_ms_per_ktoken_p50",
        }
    else:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    ref = facts["reference"]
    assert ref["max_logit_deficit"] <= 1e-4  # float32 against float32
    assert ref["n_positions"] > 0 and facts["ttft_s"]


def test_every_seed_serves_one_schedule_of_lengths():
    serve = bench_run.load_module("runners", "serve")
    traffic = common.load_json("traffic", "chat-closed.json")

    def head(seed, n=200):
        stream = serve.request_stream(traffic, 1000, seed)
        return [next(stream) for _ in range(n)]

    a, b = head(1), head(2 ** 31 + 5)
    assert [(len(p), n) for p, n in a] == [(len(p), n) for p, n in b]
    assert [p for p, _ in a] != [p for p, _ in b]   # other tokens
    assert head(1) == a                             # same seed, same inputs
    size = traffic["length_set_size"]
    epochs = [sorted((len(p), n) for p, n in a[i:i + size])
              for i in (0, size, 2 * size)]
    assert epochs[0] == epochs[1] == epochs[2]      # the set, each epoch
    lens = [len(p) for p, _ in a]
    assert min(lens) >= 128 and max(lens) <= 2048
    assert all(32 <= n <= 256 for _, n in a)
    engine = common.load_json("configs", "mistral-nemo-12b.json")
    assert max(lens) + 256 <= engine["serve_engine"]["max_len"]


def test_elastic_runner_rehearsal(manifest, tmp_path, monkeypatch):
    """Launcher -> agent -> worker -> three saves -> SIGKILL -> restore
    -> replay, on the CPU."""
    ctx = tiny.context(
        "save-kill-resume", tmp_path, trace=1, seconds=5.0,
    )
    ctx["env"] = {
        "JAX_PLATFORMS": "cpu",
        # A tiny step compiles in under JAX's caching threshold.
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"),
    }
    facts = bench_run.load_module("runners", "elastic_train").run(ctx)
    assert facts["problems"] == []
    assert facts["t_kill"] is not None
    assert set(facts["end_to_end"]) == {
        "setup_s", "ckpt_save_stall_s", "resume_s",
    }
    assert facts["failed"] == 0
    readers = ("agent_respawn_s", "worker_start_s", "recompile_s",
               "ckpt_restore_s", "ckpt_save_gb_per_s", "ckpt_first_save_s")
    for name in readers:
        value = bench_run.load_module("layer_metrics", name).read(
            dict(facts, ctx=ctx)
        )
        assert value is not None and value > 0, name
    assert not [
        f for f in os.listdir("/dev/shm") if facts["job"] in f
    ]


def _run_cli(cwd, *args, **env):
    return subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), *args],
        cwd=cwd, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **env),
    )


def test_run_refuses_without_a_tpu(manifest, tmp_path):
    """The command as the driver runs it, on the CPU: non-zero exit and
    no result line."""
    for cell in manifest["workloads"]:
        p = _run_cli(
            REPO, "--workload", cell["name"], "--seed", "3",
            "--seconds", "1", "--trace", "0",
            JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
        )
        assert p.returncode != 0, cell["name"]
        assert p.stdout.strip() == "", p.stdout
        assert "need 1 TPU chip" in p.stderr


def test_run_refuses_alone_in_a_directory(manifest, tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: nothing to
    measure."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for path in manifest["paths"]:
        shutil.copytree(
            os.path.join(REPO, path), tmp_path / path,
            ignore=shutil.ignore_patterns("__pycache__"),
        )
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         manifest["workloads"][0]["name"], "--seconds", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120,
    )
    assert p.returncode != 0 and p.stdout.strip() == ""


ADDED_READER = '''"""Steps the window completed (a count)."""


def read(facts):
    return facts["window"]["steps"]
'''
ADDED_DRIVER = """
import json, sys
from benchmark import common, run as bench_run
manifest = common.load_manifest()
ctx = bench_run.cell_context(manifest, "tiny.short", 11, 0.3, 1,
                             require_tpu=False)
ctx["peaks_table"] = {"cpu": {"bf16_flops_per_s": 1e12}}
facts = bench_run.load_module("runners", ctx["traffic"]["runner"]).run(ctx)
line, problems = bench_run.result_line(manifest, ctx, facts)
print(json.dumps({"line": line, "problems": problems}))
"""


def test_a_later_pr_adds_files_and_entries_only(manifest, tmp_path):
    """A configuration, a traffic mix and a per-layer metric, each a new
    file plus a manifest entry, in a copy of the benchmark: no file that
    exists is edited, and the new cell runs."""
    copy_root = tmp_path / "checkout"
    shutil.copytree(
        os.path.join(REPO, "benchmark"), copy_root / "benchmark",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    os.symlink(os.path.join(REPO, "dlrover_tpu"), copy_root / "dlrover_tpu")
    before = {
        p: p.read_bytes() for p in (copy_root / "benchmark").rglob("*")
        if p.is_file()
    }
    bench = copy_root / "benchmark"
    (bench / "configs" / "tiny.json").write_text(json.dumps(dict(
        tiny.CONFIG, source="a test", published={}, reduced={},
        assumed={}, deployment="none",
    )))
    (bench / "traffic" / "short-seqs.json").write_text(json.dumps(dict(
        common.load_json("traffic", "pretrain-4k.json"), seq_len=16,
        trace_steps=1,
    )))
    (bench / "layer_metrics" / "steps_done.py").write_text(ADDED_READER)
    added = json.loads(json.dumps(manifest))
    added["configs"].append({
        "name": "tiny", "source": "a test",
        "file": "benchmark/configs/tiny.json", "reduced": [], "why": "t",
    })
    added["workloads"].append({
        "name": "tiny.short", "config": "tiny", "traffic": "short-seqs",
        "chips": 1, "why": "t",
    })
    for m in added["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("tiny.short")
    added["per_layer"].append({
        "name": "steps_done", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "train step",
        "moves": "train_tokens_per_s", "workloads": ["tiny.short"],
    })
    (copy_root / "BENCHMARK.json").write_text(json.dumps(added))
    p = subprocess.run(
        [sys.executable, "-c", ADDED_DRIVER], cwd=copy_root,
        capture_output=True, text=True, timeout=300,
        env=dict(
            os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(copy_root),
            JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
        ),
    )
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["problems"] == [] and out["line"]["correct"]
    assert out["line"]["metrics"]["steps_done"]["value"] >= 1
    for path, content in before.items():
        assert path.read_bytes() == content, f"{path} was edited"
