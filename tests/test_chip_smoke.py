"""chip_smoke.py off the chip: it must refuse to say "ok", its parent
must stay off JAX, and both phases must work end to end at
``tiny_config()`` — so a later PR that breaks the smoke finds out here,
not after a chip call."""

import glob
import json
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke
from dlrover_tpu.common import compile_cache
from dlrover_tpu.models import llama

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_refuses_to_report_ok_without_a_tpu(tmp_path):
    """The command as the driver runs it, on the CPU: non-zero exit, no
    result line, and no JAX in the process that starts the children."""
    code = (
        "import sys, chip_smoke\n"
        "rc = chip_smoke.main([])\n"
        "print('JAX_IN_PARENT', 'jax' in sys.modules)\n"
        "sys.exit(rc)\n"
    )
    env = dict(
        os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
    )
    p = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
    assert "JAX_IN_PARENT False" in p.stdout
    assert "not a TPU" in p.stderr


def test_fails_alone_in_a_directory(tmp_path):
    """Without the program next to it the script has nothing to prove."""
    with open(os.path.join(REPO, "chip_smoke.py")) as src:
        (tmp_path / "chip_smoke.py").write_text(src.read())
    p = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
        capture_output=True, text=True, timeout=60,
    )
    assert p.returncode != 0
    assert '"ok"' not in p.stdout


def test_compile_cache_honours_the_environment(monkeypatch):
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, "/somewhere/else")
    assert compile_cache.enable_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == was  # nothing set


def test_compile_cache_defaults_to_one_path_in_the_checkout(monkeypatch):
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
    try:
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable_compile_cache() == path  # fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    ignored = subprocess.run(
        ["git", "check-ignore", "-q", ".jax_cache/x"], cwd=REPO
    )
    assert ignored.returncode == 0


seen_jobs = set()


@pytest.mark.parametrize("n_devices", [1, 4], ids=["1dev", "dp4"])
def test_train_phase_rehearsal(tmp_path, monkeypatch, n_devices):
    """Launcher -> agent -> worker -> save -> SIGKILL -> restart ->
    restore from shm -> bit-identical replay from a warm compile cache,
    on virtual CPU devices (what ``--chips 4`` runs is the dp4 case)."""
    monkeypatch.setenv(
        compile_cache.CACHE_DIR_ENV, str(tmp_path / "cache")
    )
    out = str(tmp_path / "train")
    worker = os.path.join(REPO, "tests", "workers", "smoke_train_tiny.py")
    facts = chip_smoke.train_phase(
        [worker, str(n_devices)], out, timeout_s=240,
    )
    assert chip_smoke.check_train(facts, n_devices) == []
    # The job name, and with it the /dev/shm segment, is this run's
    # own, and nothing of it is left behind.
    assert facts["job"] not in seen_jobs
    seen_jobs.add(facts["job"])
    assert glob.glob(f"/dev/shm/*{facts['job']}*") == []
    # ... and it is only the chip's own checks that this run fails.
    devices = chip_smoke._device_of([facts])
    assert {d["platform"] for d in devices} == {"cpu"}
    assert chip_smoke.check_on_tpu(devices, [facts])


def test_serve_phase_rehearsal(tmp_path):
    out = str(tmp_path / "serve.json")
    chip_smoke.serve_worker(llama.tiny_config(), (8, 12, 16, 10), 4, out)
    with open(out) as f:
        facts = {"error": "", "rc": 0, "report": json.load(f)}
    assert chip_smoke.check_serve(facts, new_tokens=4) == []
    assert facts["report"]["n_requests"] == 5  # four prompts + a repeat


@pytest.mark.parametrize(
    "runner_up_gap", [0.2, 1.5], ids=["runner_up", "wrong_token"]
)
def test_a_wrong_token_fails_the_serve_check(runner_up_gap):
    """0.2 is where the runner-up of ~N(0, 1) logits over 32000 tokens
    typically sits: the tolerance must not let it through."""
    report = dict(
        n_requests=1, n_completions=1, n_distinct_completed=1,
        all_ok=True, token_counts=[4], repeat_matches=True,
        logits_finite=True, compiles_after_warmup=0,
        retraces_after_warmup=0, prefill_logit_deficit=0.0,
        max_logit_deficit=runner_up_gap,
    )
    bad = chip_smoke.check_serve(
        {"error": "", "rc": 0, "report": report}, new_tokens=4
    )
    assert len(bad) == 1 and "below" in bad[0]
