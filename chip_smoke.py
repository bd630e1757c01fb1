"""Does the system still start on the chip? The quickest proof.

    python chip_smoke.py             one TPU chip: train phase, serve phase
    python chip_smoke.py --chips 4   four chips: the train phase on a dp=4
                                     mesh and its one-chip comparison

Both phases run the model the repo calls its flagship
(``llama.flagship_config()``: vocab 32000, embed 1024, 16 layers,
8 heads x 128, MLP 4096) with random weights from a seed, through the
entry points a user calls:

- **train** — ``python -m dlrover_tpu.run --standalone --nnodes 1`` starts
  master, agent and one worker (this file, ``--worker train``). The worker
  calls ``init_distributed()``, trains at micro 8 x 2048, flash-saves
  through ``Checkpointer``; this script SIGKILLs it after the save has
  drained, the agent restarts it, and the new incarnation restores from
  shm and replays. Checked: finite losses, replayed losses equal the
  pre-kill ones, the compiled step holds the flash kernel, the restart
  hits the persistent compile cache, the restore took the batched branch.
- **serve** — ``PagedServingEngine`` behind ``FleetRouter`` with one
  ``ThreadReplica``: seeded prompts of 128-512 tokens, 32 greedy tokens
  each. Checked: every request completes exactly once, a repeated prompt
  repeats its tokens, every emitted token's logit under the plain
  ``llama.forward`` — the prefill's first token and each decoded one —
  is within ``SERVE_LOGIT_TOL`` of that position's maximum, and nothing
  compiles after warm-up.

This process never imports JAX: a chip belongs to one process, so each
phase is a child that ends before the next begins, and the device in the
last line is what the children saw. Any failed check, or any child that
ran on something other than a TPU, exits non-zero without a result line.
There is no size or platform switch; the worker functions take the model
and sizes as arguments so ``tests/test_chip_smoke.py`` can rehearse them
at ``tiny_config()`` on the CPU.
"""

import argparse
import glob
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

T_START = time.time()  # a worker's start-up is timed from here
HERE = os.path.dirname(os.path.abspath(__file__))
WORK_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

SEED = 0
MICRO, SEQ = 8, 2048
# The worker saves after SAVE_STEP steps, takes REPLAY_STEPS more, then
# idles until this script kills it; the restarted worker restores step
# SAVE_STEP and replays the same REPLAY_STEPS.
SAVE_STEP, REPLAY_STEPS = 3, 2
# Same step index => same seeded batch, same restored f32 state, same
# (cached) executable: the replay should be bit-identical. The tolerance
# only leaves room for a recompile that reassociates a reduction.
REPLAY_LOSS_RTOL = 1e-5
# dp=4 vs one chip: same math, different reduction order over bf16
# activations (per-device batch 2 vs 8).
MESH_LOSS_RTOL = 2e-3
PROMPT_LENS = (128, 256, 384, 512, 200)
NEW_TOKENS = 32
# The engine samples on the device and hands out tokens, never logits,
# so the comparison with the plain forward is made where the two meet:
# the engine's token is the argmax of ITS logits, and if those lie
# within eps of the reference's, the reference's logit of that token
# lies within 2*eps of the reference's maximum. Random weights give
# logits ~N(0, 1) over 32000 tokens, whose top two sit too close for a
# token-exact match against another numeric path (``median_top2_gap``:
# 0.14 on the v5e, 182 of 192 tokens exact), while bf16 matmuls over
# d=1024 put eps of a few hundredths on a logit (largest gap on the
# v5e: 0.030). The tolerance leaves that room and stays under the
# typical top-2 gap: a runner-up fails it at most positions
# (``n_runner_up_would_fail``: 138 of 192), so a path that lost
# precision everywhere cannot pass at all of them.
SERVE_LOGIT_TOL = 0.08

TRAIN_TIMEOUT_S = 480
SERVE_TIMEOUT_S = 360

CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


# ---------------------------------------------------------------------------
# Workers (the only code here that imports JAX)
# ---------------------------------------------------------------------------


def _count_jax_events():
    """Counter of the JAX monitoring events named above, live from now."""
    import collections

    import jax.monitoring

    counts = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda event, **_: counts.update([event])
    )
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **__: counts.update([event])
    )
    return counts


def _device_facts(devices):
    d = devices[0]
    return {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices),
    }


def train_worker(cfg, micro, seq, n_devices, save_step, replay_steps,
                 out_dir, ckpt_dir, require_platform=None):
    """One incarnation of the elastic training worker; runs under the
    agent. Appends JSON events to ``out_dir/events.jsonl``. Exits 3
    right after backend start-up on a platform other than
    ``require_platform``."""
    incarnation = int(os.getenv("DLROVER_TPU_RESTART_COUNT", "0"))

    def emit(event, **kw):
        with open(os.path.join(out_dir, "events.jsonl"), "a") as f:
            f.write(json.dumps(
                {"event": event, "incarnation": incarnation, **kw}
            ) + "\n")

    emit("boot", pid=os.getpid())
    import jax
    import numpy as np

    counts = _count_jax_events()

    from dlrover_tpu.common.compile_cache import compile_cache_dir
    from dlrover_tpu.flash_ckpt import engine as ckpt_engine
    from dlrover_tpu.flash_ckpt.checkpointer import Checkpointer
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.trainer import train_step as ts
    from dlrover_tpu.trainer.runtime import init_distributed

    ctx = init_distributed()
    t0 = time.time()
    devices = jax.devices()
    emit(
        "ready", **_device_facts(devices),
        imports_s=t0 - T_START, backend_init_s=time.time() - t0,
        cache_dir=compile_cache_dir(), process_id=ctx.process_id,
    )
    if require_platform not in (None, devices[0].platform):
        sys.exit(3)

    mesh = build_mesh(MeshConfig(dp=n_devices), devices[:n_devices])
    tc = ts.TrainConfig(warmup_steps=10)
    opt = ts.make_optimizer(tc)
    shardings = ts.state_shardings(ts.state_specs(cfg, opt), mesh)
    # Donated: at flagship width one 16 GB chip cannot hold the old
    # state, the new state and the step's temporaries at once. The async
    # save below is therefore awaited before the next step may run.
    step_fn, _ = ts.make_train_step(cfg, tc, opt, mesh, donate=True)

    ckpt = Checkpointer(ckpt_dir)
    t0 = time.time()
    restored = ckpt.load_checkpoint(sharding_tree=shardings)
    if restored is not None:
        start, state, _ = restored
        # The one barrier: were it to return early, the transfer would
        # show up in the first replayed step's seconds instead.
        jax.block_until_ready(state)
        emit(
            "restored", step=start, seconds=time.time() - t0,
            branch=dict(ckpt_engine.RESTORE_BRANCH_COUNTS),
        )
    else:
        start = 0
        state, _ = ts.init_train_state(cfg, opt, mesh, jax.random.key(SEED))
        emit("fresh_start")
    state_bytes = sum(
        x.nbytes for x in jax.tree_util.tree_leaves(state)
    )
    per_device = {}
    for leaf in jax.tree_util.tree_leaves(state):
        for shard in leaf.addressable_shards:
            per_device[shard.device.id] = (
                per_device.get(shard.device.id, 0) + shard.data.nbytes
            )

    def batch_at(step):
        rng = np.random.default_rng((SEED, step))
        tokens = rng.integers(
            0, cfg.vocab_size, (micro, seq + 1), dtype=np.int32
        )
        return {"tokens": jax.device_put(
            tokens, jax.sharding.NamedSharding(mesh, ts.batch_spec())
        )}

    t0 = time.time()
    with mesh:
        compiled = step_fn.jitted.lower(state, batch_at(0)).compile()
    emit(
        "compiled", seconds=time.time() - t0,
        n_tpu_custom_calls=compiled.as_text().count("tpu_custom_call"),
        cache_hits=counts[CACHE_HIT], cache_misses=counts[CACHE_MISS],
        state_mb=state_bytes / 1e6,
        state_bytes_per_device=per_device,
    )

    for step in range(start + 1, save_step + replay_steps + 1):
        t0 = time.time()
        state, metrics = compiled(state, batch_at(step))
        loss = float(metrics["loss"])
        emit("step", n=step, loss=loss, seconds=time.time() - t0)
        if step == save_step and incarnation == 0:
            t0 = time.time()
            block_s = ckpt.save_checkpoint_async(step, state)
            drained = ckpt.wait_async_save()
            emit(
                "saved", n=step, ok=drained, block_s=block_s,
                seconds=time.time() - t0,
            )
    stats = [d.memory_stats() or {} for d in devices[:n_devices]]
    emit(
        "done",
        peak_bytes_in_use=[s.get("peak_bytes_in_use") for s in stats],
        bytes_in_use=[s.get("bytes_in_use") for s in stats],
    )
    if incarnation == 0:
        while True:  # the kill is the parent's to deliver
            time.sleep(0.1)
    ckpt.close()


def serve_worker(cfg, prompt_lens, new_tokens, out_path,
                 require_platform=None):
    """Serve seeded prompts through FleetRouter -> ThreadReplica ->
    PagedServingEngine and compare with the plain forward. Writes one
    JSON report to ``out_path`` (device facts first, so a refusal of the
    platform — exit 3 — still says what it found)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    counts = _count_jax_events()

    from dlrover_tpu.common.compile_cache import compile_cache_dir
    from dlrover_tpu.models import llama
    from dlrover_tpu.serving.fleet import FleetRouter, ThreadReplica
    from dlrover_tpu.serving.kvpool import PagedServingEngine

    devices = jax.devices()
    report = dict(_device_facts(devices), cache_dir=compile_cache_dir())

    def write():
        with open(out_path, "w") as f:
            json.dump(report, f)

    write()
    if require_platform not in (None, devices[0].platform):
        sys.exit(3)

    params, _ = llama.init_params(cfg, jax.random.key(SEED))
    chunk = min(64, max(prompt_lens))
    max_len = -(-(max(prompt_lens) + new_tokens) // chunk) * chunk
    box = {}

    def factory():
        t0 = time.time()
        engine = PagedServingEngine(
            cfg, params, slots=4, max_len=max_len, prefill_chunk=chunk,
            block_size=min(16, chunk),
        )
        engine.warmup()
        box.update(
            engine=engine, warmup_s=time.time() - t0,
            traces=dict(engine.trace_counts),
            compiles=counts[BACKEND_COMPILE],
        )
        return engine

    rng = np.random.default_rng(SEED)
    prompts = [
        rng.integers(0, cfg.vocab_size, n).tolist() for n in prompt_lens
    ]
    prompts.append(list(prompts[0]))  # the same prompt, submitted twice

    router = FleetRouter([ThreadReplica("0", factory)])
    router.start(timeout_s=SERVE_TIMEOUT_S)
    try:
        t0 = time.time()
        reqs = [router.submit(p, new_tokens) for p in prompts]
        done = router.run_until_idle(timeout_s=SERVE_TIMEOUT_S)
        serve_s = time.time() - t0
    finally:
        router.stop()
    engine = box["engine"]
    results = [r.result for r in reqs]
    report.update(
        warmup_s=box["warmup_s"], serve_s=serve_s,
        n_requests=len(reqs),
        n_completions=len(done),
        n_distinct_completed=len({r.request_id for r in done}),
        all_ok=all(r is not None and r.ok for r in results),
        token_counts=[len(r.tokens) if r else 0 for r in results],
        repeat_matches=results[0].tokens == results[-1].tokens,
        compiles_after_warmup=counts[BACKEND_COMPILE] - box["compiles"],
        retraces_after_warmup=sum(engine.trace_counts.values())
        - sum(box["traces"].values()),
        kv_stats={
            k: v for k, v in engine.kv_stats().items()
            if isinstance(v, (int, float))
        },
    )

    # Reference: ONE plain forward over every served sequence (prompt +
    # emitted tokens, right-padded — causal attention never looks
    # right). Position len(prompt)-1+i predicted emitted token i.
    seqs = [p + r.tokens for p, r in zip(prompts, results)]
    tokens = np.zeros((len(seqs), max_len), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, :len(s)] = s
    logits, _ = jax.jit(
        lambda p, t: llama.forward(cfg, p, t)
    )(params, jnp.asarray(tokens))
    logits = np.asarray(logits)
    deficits, gaps = [], []
    for i, (p, r) in enumerate(zip(prompts, results)):
        rows = logits[i, len(p) - 1:len(p) - 1 + len(r.tokens)]
        deficits.append(
            (rows.max(-1) - rows[np.arange(len(r.tokens)), r.tokens])
            .tolist()
        )
        top2 = np.partition(rows, -2, axis=-1)[:, -2:]
        gaps.extend((top2[:, 1] - top2[:, 0]).tolist())
    stats = devices[0].memory_stats() or {}
    report.update(
        logits_finite=bool(np.isfinite(logits).all()),
        # Element 0 of each request is the token its PREFILL emitted.
        prefill_logit_deficit=max(d[0] for d in deficits),
        max_logit_deficit=max(max(d) for d in deficits),
        n_positions=len(gaps),
        n_argmax_matches=sum(x == 0.0 for d in deficits for x in d),
        median_top2_gap=float(np.median(gaps)),
        n_runner_up_would_fail=sum(g > SERVE_LOGIT_TOL for g in gaps),
        peak_bytes_in_use=stats.get("peak_bytes_in_use"),
    )
    write()


# ---------------------------------------------------------------------------
# Phases (parent side: no JAX)
# ---------------------------------------------------------------------------


def _read_events(out_dir):
    path = os.path.join(out_dir, "events.jsonl")
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _by(events, name, incarnation=None):
    return [
        e for e in events if e["event"] == name
        and (incarnation is None or e["incarnation"] == incarnation)
    ]


def _stop(proc, pids=()):
    """End a child we started, and the workers behind a launcher."""
    if proc.poll() is None:
        proc.terminate()  # the launcher's handler stops agent + master
        try:
            proc.wait(timeout=15)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
    for pid in pids:
        try:
            os.kill(pid, signal.SIGKILL)
        except OSError:
            pass


def _unlink_quietly(path):
    try:
        os.unlink(path)
    except FileNotFoundError:
        pass


def train_phase(worker_argv, out_dir, timeout_s=TRAIN_TIMEOUT_S):
    """Run ``worker_argv + [out_dir, ckpt_dir]`` under the elastic
    launcher, SIGKILL the worker once its save has drained and it
    idles, wait for the agent to restart it and for the job to end.
    Returns the facts."""
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    # Sockets (short: UDS paths cap at 108 chars), the agent's event
    # files and the checkpoint dir (the agent persists the 4 GB shm
    # image there when the worker dies) go under TMPDIR: not /tmp, and
    # not chiprun_out/, which has a size limit.
    scratch = tempfile.mkdtemp(prefix="cs")
    # The job name keys what the package keeps OUTSIDE that directory
    # (the flash-checkpoint segment in /dev/shm, the timer's port file
    # in TMPDIR), so it is this run's own: two checkouts smoking on one
    # machine must not share, or unlink, each other's 4 GB image.
    job = "chip_smoke_" + os.path.basename(scratch)
    segment = f"/dev/shm/dlrover_tpu_ckpt_{job}_n0_0"  # node 0, rank 0
    _unlink_quietly(segment)
    env = dict(
        os.environ, DLROVER_TPU_JOB_NAME=job,
        DLROVER_TPU_SHARED_DIR=scratch,
        DLROVER_TPU_EVENT_DIR=os.path.join(scratch, "events"),
        PYTHONPATH=os.pathsep.join(
            p for p in (HERE, os.environ.get("PYTHONPATH")) if p
        ),
    )
    log_path = os.path.join(out_dir, "launcher.log")
    facts = {"log": log_path, "job": job, "killed": False, "error": ""}
    t_start = time.time()
    with open(log_path, "wb") as log:
        proc = subprocess.Popen(
            [sys.executable, "-m", "dlrover_tpu.run", "--standalone",
             "--nnodes", "1", "--nproc_per_node", "1",
             "--max_restarts", "1", "--monitor_interval", "0.5",
             *worker_argv, out_dir, os.path.join(scratch, "ckpt")],
            env=env, stdout=log, stderr=subprocess.STDOUT, cwd=HERE,
        )
        events = []
        try:
            while proc.poll() is None:
                events = _read_events(out_dir)
                if _by(events, "done", 0) and not facts["killed"]:
                    os.kill(_by(events, "boot", 0)[0]["pid"], signal.SIGKILL)
                    facts["killed"] = True
                if time.time() - t_start > timeout_s:
                    facts["error"] = f"timed out after {timeout_s}s"
                    break
                time.sleep(0.2)
        finally:
            events = _read_events(out_dir)
            _stop(proc, [e["pid"] for e in _by(events, "boot")])
            _unlink_quietly(segment)
            shutil.rmtree(scratch, ignore_errors=True)
    facts.update(
        launcher_rc=proc.returncode, events=events,
        seconds=time.time() - t_start,
    )
    return facts


def check_train(facts, n_devices=1):
    """Failed checks of a train phase that do not depend on the device
    kind (see ``check_on_tpu`` for those)."""
    ev = facts["events"]
    bad = []
    if facts["error"]:
        bad.append(facts["error"])
    if facts["launcher_rc"] != 0:
        bad.append(f"launcher exited {facts['launcher_rc']}")
    if not facts["killed"]:
        bad.append("worker never reached the kill point")
    saved = _by(ev, "saved", 0)
    restored = _by(ev, "restored", 1)
    if not (saved and saved[0]["ok"]):
        bad.append("no drained save before the kill")
    if not restored:
        bad.append("restarted worker did not restore from shm")
    elif saved and restored[0]["step"] != saved[0]["n"]:
        bad.append(
            f"restored step {restored[0]['step']} != saved "
            f"{saved[0]['n']}"
        )
    elif restored[0]["branch"] != {"batched": 1, "per_leaf": 0}:
        # One host: every shard is addressable, so nothing excuses the
        # slow leaf-by-leaf fallback of to_device_state.
        bad.append(f"restore branches ran: {restored[0]['branch']}")
    losses = [e["loss"] for e in _by(ev, "step")]
    if not losses or not all(math.isfinite(x) for x in losses):
        bad.append(f"losses not finite: {losses}")
    before = {e["n"]: e["loss"] for e in _by(ev, "step", 0)}
    replay = {e["n"]: e["loss"] for e in _by(ev, "step", 1)}
    if not replay:
        bad.append("no replayed step")
    for n, loss in replay.items():
        if n not in before or not math.isclose(
            loss, before[n], rel_tol=REPLAY_LOSS_RTOL
        ):
            bad.append(
                f"replayed step {n} loss {loss!r} != pre-kill "
                f"{before.get(n)!r}"
            )
    if not _by(ev, "done", 1):
        bad.append("restarted worker did not finish")
    if any(e["cache_hits"] < 1 for e in _by(ev, "compiled", 1)):
        bad.append("restarted worker missed the persistent compile cache")
    for e in _by(ev, "compiled", 0):
        shares = e["state_bytes_per_device"]
        total = sum(shares.values())
        if len(shares) != n_devices or any(
            abs(v / total - 1 / n_devices) > 0.1 / n_devices
            for v in shares.values()
        ):
            bad.append(
                f"state not spread evenly over {n_devices} devices: "
                f"{shares}"
            )
    return bad


def serve_phase(worker_argv, out_path, timeout_s=SERVE_TIMEOUT_S):
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    for stale in glob.glob(out_path + "*"):
        os.unlink(stale)
    log_path = out_path + ".log"
    facts = {"log": log_path, "error": ""}
    t0 = time.time()
    with open(log_path, "wb") as log:
        proc = subprocess.Popen(
            [sys.executable, *worker_argv], stdout=log,
            stderr=subprocess.STDOUT, cwd=HERE,
        )
        try:
            proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            facts["error"] = f"timed out after {timeout_s}s"
        finally:
            _stop(proc)
    facts.update(rc=proc.returncode, seconds=time.time() - t0, report={})
    if os.path.exists(out_path):
        with open(out_path) as f:
            facts["report"] = json.load(f)
    return facts


def check_serve(facts, new_tokens=NEW_TOKENS):
    r = facts["report"]
    bad = []
    if facts["error"]:
        bad.append(facts["error"])
    if facts["rc"] != 0:
        bad.append(f"serve worker exited {facts['rc']}")
    if "max_logit_deficit" not in r:
        return bad + ["serve worker left no full report"]
    n = r["n_requests"]
    if not (r["all_ok"] and r["n_completions"] == n
            and r["n_distinct_completed"] == n):
        bad.append(
            f"{n} requests, {r['n_completions']} completions "
            f"({r['n_distinct_completed']} distinct), all_ok={r['all_ok']}"
        )
    if r["token_counts"] != [new_tokens] * n:
        bad.append(f"token counts {r['token_counts']}")
    if not r["repeat_matches"]:
        bad.append("the same prompt twice gave different tokens")
    if not r["logits_finite"]:
        bad.append("reference logits not finite")
    for key, what in (
        ("prefill_logit_deficit", "a prefill's first token"),
        ("max_logit_deficit", "an emitted token"),
    ):
        if not r[key] <= SERVE_LOGIT_TOL:
            bad.append(
                f"{what} sits {r[key]:.3f} below the plain forward's "
                f"maximum (tolerance {SERVE_LOGIT_TOL})"
            )
    if r["compiles_after_warmup"] or r["retraces_after_warmup"]:
        bad.append(
            f"{r['compiles_after_warmup']} compiles / "
            f"{r['retraces_after_warmup']} retraces after warm-up"
        )
    return bad


def check_on_tpu(devices, train_facts=()):
    """The checks only a chip can pass: every child ran on a TPU, and
    the compiled train step holds the Pallas flash kernels (fwd, dq,
    dk/dv) — not the XLA op, not interpret mode."""
    bad = [
        f"a child ran on {d.get('platform')!r}, not a TPU"
        for d in devices if d.get("platform") != "tpu"
    ]
    for facts in train_facts:
        for e in _by(facts["events"], "compiled"):
            if e["n_tpu_custom_calls"] < 3:
                bad.append(
                    f"compiled step holds {e['n_tpu_custom_calls']} "
                    f"tpu_custom_call(s): the flash kernel is missing"
                )
    return bad


# ---------------------------------------------------------------------------
# Report
# ---------------------------------------------------------------------------


def _print_train(label, facts):
    ev = facts["events"]
    out = {"phase": label, "seconds": round(facts["seconds"], 1)}
    for e in _by(ev, "ready"):
        out[f"inc{e['incarnation']}_ready"] = {
            k: e[k] for k in (
                "platform", "kind", "count", "imports_s",
                "backend_init_s", "cache_dir",
            )
        }
    for e in _by(ev, "compiled"):
        out[f"inc{e['incarnation']}_compiled"] = {
            k: e[k] for k in (
                "seconds", "n_tpu_custom_calls", "cache_hits",
                "cache_misses", "state_mb", "state_bytes_per_device",
            )
        }
    out["steps"] = [
        (e["incarnation"], e["n"], e["loss"], round(e["seconds"], 3))
        for e in _by(ev, "step")
    ]
    for name in ("saved", "restored", "done"):
        for e in _by(ev, name):
            out[f"inc{e['incarnation']}_{name}"] = {
                k: v for k, v in e.items()
                if k not in ("event", "incarnation")
            }
    out["killed"] = facts["killed"]
    print(json.dumps(out), flush=True)


def _device_of(train_facts, serve_facts=()):
    devices = [
        {k: e[k] for k in ("platform", "kind", "count")}
        for f in train_facts for e in _by(f["events"], "ready")
    ]
    devices += [
        {k: f["report"].get(k) for k in ("platform", "kind", "count")}
        for f in serve_facts
    ]
    return devices


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--worker", nargs="+", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.worker:
        from dlrover_tpu.models import llama

        kind, *rest = args.worker
        if kind == "train":
            n_devices, out_dir, ckpt_dir = rest
            train_worker(
                llama.flagship_config(), MICRO, SEQ, int(n_devices),
                SAVE_STEP, REPLAY_STEPS, out_dir, ckpt_dir,
                require_platform="tpu",
            )
        else:
            serve_worker(
                llama.flagship_config(), PROMPT_LENS, NEW_TOKENS, *rest,
                require_platform="tpu",
            )
        return 0

    if not os.path.isdir(os.path.join(HERE, "dlrover_tpu")):
        print("chip_smoke.py: no dlrover_tpu/ next to me", file=sys.stderr)
        return 2
    me = os.path.abspath(__file__)
    failures, trains, serves = [], [], []

    def run_train(label, n_devices):
        out_dir = os.path.join(WORK_DIR, label)
        facts = train_phase(
            [me, "--worker", "train", str(n_devices)], out_dir,
        )
        trains.append(facts)
        _print_train(label, facts)
        failures.extend(
            f"{label}: {b}" for b in check_train(facts, n_devices)
        )
        return facts

    if args.chips == 4:
        four = run_train("train_dp4", 4)
        one = run_train("train_1chip", 1)
        pairs = [
            (a["n"], a["loss"], b["loss"])
            for a in _by(four["events"], "step", 0)
            for b in _by(one["events"], "step", 0) if a["n"] == b["n"]
        ]
        print(json.dumps({"phase": "dp4_vs_1chip", "losses": pairs}))
        if not pairs or any(
            not math.isclose(x, y, rel_tol=MESH_LOSS_RTOL)
            for _, x, y in pairs
        ):
            failures.append(f"dp=4 and one-chip losses differ: {pairs}")
    else:
        run_train("train", 1)
        out_path = os.path.join(WORK_DIR, "serve.json")
        facts = serve_phase(
            [me, "--worker", "serve", out_path], out_path,
        )
        serves.append(facts)
        print(json.dumps({
            "phase": "serve", "seconds": round(facts["seconds"], 1),
            **facts["report"],
        }), flush=True)
        failures.extend(f"serve: {b}" for b in check_serve(facts))

    devices = _device_of(trains, serves)
    failures.extend(check_on_tpu(devices, trains))
    if not devices or any(d != devices[0] for d in devices):
        failures.append(f"children disagree on the device: {devices}")
    elif devices[0]["count"] != args.chips:
        failures.append(
            f"{devices[0]['count']} devices visible, --chips "
            f"{args.chips} asked"
        )
    assert "jax" not in sys.modules, "the smoke's parent imported JAX"
    if failures:
        for f in failures:
            print("FAILED " + f, file=sys.stderr)
        for facts in trains + serves:
            print(f"--- tail of {facts['log']}", file=sys.stderr)
            with open(facts["log"], errors="replace") as f:
                sys.stderr.write(f.read()[-4000:])
        return 1
    print("note: every time and size above is a smoke reading, "
          "not a benchmark")
    print(json.dumps({"ok": True, "device": devices[0]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
