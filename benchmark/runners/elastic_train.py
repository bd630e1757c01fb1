"""Runner ``elastic_train``: train, flash-save, SIGKILL, resume — one
cycle through ``python -m dlrover_tpu.run --standalone``, a copy of
``chip_smoke.py``'s ``train_phase`` and ``train_worker`` with sizes from
the data files and every event on one wall clock.

The parent (``run``) never imports JAX: the worker the agent starts owns
the chip. The worker (this file run as a script by the launcher) warms
up, takes the steady steps (``--seconds`` caps only those), saves the
whole train state ``saves`` times — each timed from the call of
``save_checkpoint_async`` to the moment the next step may start, which
with donated state is block + drain; the first save also creates and
first-touches the shm segment, the later ones are what a long job pays
—, takes ``replay_steps`` more and idles. The parent kills it then; the
agent restarts it; the new incarnation restores from shm, compiles from
the persistent cache and replays the same steps. One kill to a run: a
second kill of the restarted worker restores from storage, not from shm
(seen in the CPU rehearsal), which is another path than this cell times.
"""

import json
import math
import os
import shutil
import signal
import statistics
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import common  # noqa: E402

# Same step index => same seeded batch, same restored f32 state, same
# (cached) executable: the replay should be bit-identical. The tolerance
# only leaves room for a recompile that reassociates a reduction.
REPLAY_LOSS_RTOL = 1e-5
# One host: every shard is addressable, so nothing excuses the slow
# leaf-by-leaf fallback of to_device_state.
RESTORE_BRANCH = {"batched": 1, "per_leaf": 0}


# ---------------------------------------------------------------------------
# Worker (runs under the agent; the only code here that imports JAX)
# ---------------------------------------------------------------------------


def worker(spec):
    incarnation = int(os.getenv("DLROVER_TPU_RESTART_COUNT", "0"))
    out = spec["out_dir"]
    log = common.EventLog(
        os.path.join(out, "events.jsonl"), incarnation=incarnation
    )
    log.emit("boot", pid=os.getpid())
    import jax

    counts = common.count_jax_events()

    from benchmark.runners import train as train_runner
    from dlrover_tpu.common.compile_cache import compile_cache_dir
    from dlrover_tpu.flash_ckpt import engine as ckpt_engine
    from dlrover_tpu.flash_ckpt.checkpointer import Checkpointer
    from dlrover_tpu.trainer.runtime import init_distributed

    init_distributed()
    devices = jax.devices()
    chips = spec["chips"]
    log.emit(
        "ready", **common.device_facts(devices),
        cache_dir=compile_cache_dir(),
    )
    if spec["require_tpu"]:
        common.require_tpu(devices, chips)
    traffic = spec["traffic"]
    job = train_runner.TrainJob(
        spec["config"], traffic, spec["seed"], chips
    )
    ckpt = Checkpointer(spec["ckpt_dir"])
    t0 = time.time()
    restored = ckpt.load_checkpoint(sharding_tree=job.shardings)
    if restored is not None:
        start, job.state, _ = restored
        # The one barrier: were it to return early, the transfer would
        # show up in the first replayed step's seconds instead.
        jax.block_until_ready(job.state)
        log.emit(
            "restored", step=start, seconds=time.time() - t0,
            branch=dict(ckpt_engine.RESTORE_BRANCH_COUNTS),
        )
    else:
        start = 0
        job.init_state()
        log.emit("fresh_start", seconds=time.time() - t0)
    hits, misses = counts[common.CACHE_HIT], counts[common.CACHE_MISS]
    t0 = time.time()
    job.compile()
    log.emit(
        "compiled", seconds=time.time() - t0,
        cache_hits=counts[common.CACHE_HIT] - hits,
        cache_misses=counts[common.CACHE_MISS] - misses,
        state_bytes=job.state_bytes(), temp_bytes=job.temp_bytes(),
    )

    def step(n, phase):
        t0 = time.time()
        loss = job.step(n)
        log.emit("step", n=n, loss=loss, seconds=time.time() - t0,
                 phase=phase)

    if incarnation > 0:
        for n in range(start + 1, start + traffic["replay_steps"] + 1):
            step(n, "replay")
        log.emit("done")
        ckpt.close()
        return

    ref = job.reference_loss(1)
    log.emit("reference", loss=ref)
    n = 0
    for _ in range(traffic["warm_steps"]):
        n += 1
        step(n, "warm")
    if spec["trace"]:
        losses, reduced, dump = job.traced_steps(
            n + 1, traffic["trace_steps"], out
        )
        for i, loss in enumerate(losses):
            log.emit("step", n=n + 1 + i, loss=loss, phase="traced")
        n += len(losses)
        log.emit("trace", reduced=reduced)
        if dump:
            with open(os.path.join(out, "trace_dump.json"), "w") as f:
                json.dump(dump, f)
    compiles = counts[common.BACKEND_COMPILE]
    t_window = log.emit("window_start")["t"]
    for _ in range(traffic["steady_steps"]):
        n += 1
        step(n, "steady")
        if time.time() - t_window >= spec["seconds"]:
            break
    log.emit(
        "window_end", seconds=time.time() - t_window,
        compiles=counts[common.BACKEND_COMPILE] - compiles,
    )
    for i in range(traffic["saves"]):
        for _ in range(traffic["steps_between_saves"] if i else 0):
            n += 1
            step(n, "between_saves")
        t0 = time.time()
        block_s = ckpt.save_checkpoint_async(n, job.state)
        drained = ckpt.wait_async_save()
        log.emit("saved", n=n, ok=bool(drained), block_s=block_s,
                 seconds=time.time() - t0, nth=i)
    for _ in range(traffic["replay_steps"]):
        n += 1
        step(n, "after_save")
    log.emit(
        "idle", peak_bytes_in_use=common.memory_peak(devices[:chips]),
    )
    while True:  # the kill is the parent's to deliver
        time.sleep(0.1)


# ---------------------------------------------------------------------------
# Parent (no JAX)
# ---------------------------------------------------------------------------


def _stop(proc, pids=()):
    """End the launcher we started, and the workers behind it."""
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


def cycle(ctx):
    """Launcher -> agent -> worker -> SIGKILL once it idles -> restart.
    Returns the facts: events, the kill's wall time, the launcher's
    exit code."""
    out = ctx["out_dir"]
    for name in ("events.jsonl", "launcher.log", "trace_dump.json"):
        _unlink_quietly(os.path.join(out, name))
    # Sockets (UDS paths cap at 108 chars), the agent's event files and
    # the checkpoint dir (the agent persists the shm image there when
    # the worker dies) go under TMPDIR. The job name keys what the
    # package keeps OUTSIDE that directory (the flash-checkpoint segment
    # in /dev/shm), so it is this run's own: two checkouts measuring on
    # one machine must not share, or unlink, each other's image.
    scratch = tempfile.mkdtemp(prefix="bm")
    job = "benchmark_" + os.path.basename(scratch)
    segment = f"/dev/shm/dlrover_tpu_ckpt_{job}_n0_0"  # node 0, rank 0
    _unlink_quietly(segment)
    spec = {
        k: ctx[k] for k in (
            "config", "traffic", "seed", "seconds", "trace", "out_dir",
            "chips", "require_tpu",
        )
    }
    spec["ckpt_dir"] = os.path.join(scratch, "ckpt")
    spec_path = os.path.join(scratch, "spec.json")
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    env = dict(
        os.environ, DLROVER_TPU_JOB_NAME=job,
        DLROVER_TPU_SHARED_DIR=scratch,
        DLROVER_TPU_EVENT_DIR=os.path.join(scratch, "events"),
        PYTHONPATH=os.pathsep.join(
            p for p in (ROOT, os.environ.get("PYTHONPATH")) if p
        ),
    )
    env.update(ctx.get("env", {}))
    log_path = os.path.join(out, "launcher.log")
    facts = {"log": log_path, "job": job, "t_kill": None, "error": ""}
    events_path = os.path.join(out, "events.jsonl")
    timeout_s = ctx["traffic"]["timeout_s"]
    t_start = time.time()
    with open(log_path, "wb") as log:
        proc = subprocess.Popen(
            [sys.executable, "-m", "dlrover_tpu.run", "--standalone",
             "--nnodes", "1", "--nproc_per_node", "1",
             "--max_restarts", "1", "--monitor_interval", "0.5",
             os.path.abspath(__file__), spec_path],
            env=env, stdout=log, stderr=subprocess.STDOUT, cwd=ROOT,
        )
        events = []
        try:
            while proc.poll() is None:
                events = common.EventLog.read(events_path)
                if facts["t_kill"] is None and common.by_event(
                    events, "idle", incarnation=0
                ):
                    pid = common.by_event(
                        events, "boot", incarnation=0
                    )[0]["pid"]
                    facts["t_kill"] = time.time()
                    os.kill(pid, signal.SIGKILL)
                if time.time() - t_start > timeout_s:
                    facts["error"] = f"timed out after {timeout_s}s"
                    break
                time.sleep(0.05)
        finally:
            events = common.EventLog.read(events_path)
            _stop(proc, [e["pid"] for e in common.by_event(events, "boot")])
            _unlink_quietly(segment)
            shutil.rmtree(scratch, ignore_errors=True)
    facts.update(launcher_rc=proc.returncode, events=events)
    return facts


def check(facts, first_loss_problems):
    """Failed checks of one cycle (``chip_smoke.check_train``'s, on this
    runner's events)."""
    ev, by = facts["events"], common.by_event
    bad = list(first_loss_problems)
    if facts["error"]:
        bad.append(facts["error"])
    if facts["launcher_rc"] != 0:
        bad.append(f"launcher exited {facts['launcher_rc']}")
    if facts["t_kill"] is None:
        bad.append("worker never reached the kill point")
    saved = by(ev, "saved", incarnation=0)
    restored = by(ev, "restored", incarnation=1)
    if not saved or not all(e["ok"] for e in saved):
        bad.append("a save did not drain before the kill")
    if not restored:
        bad.append("restarted worker did not restore from shm")
    elif saved and restored[0]["step"] != saved[-1]["n"]:
        bad.append(
            f"restored step {restored[0]['step']} != saved {saved[-1]['n']}"
        )
    elif restored[0]["branch"] != RESTORE_BRANCH:
        bad.append(f"restore branches ran: {restored[0]['branch']}")
    before = {e["n"]: e["loss"] for e in by(ev, "step", incarnation=0)}
    replay = {e["n"]: e["loss"] for e in by(ev, "step", incarnation=1)}
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
    if not by(ev, "done", incarnation=1):
        bad.append("restarted worker did not finish")
    for e in by(ev, "compiled", incarnation=1):
        if e["cache_hits"] < 1 or e["cache_misses"]:
            bad.append(
                f"restarted worker compiled the step with "
                f"{e['cache_hits']} cache hit(s), {e['cache_misses']} "
                f"miss(es)"
            )
    for e in by(ev, "window_end"):
        if e["compiles"]:
            bad.append(f"{e['compiles']} compile(s) inside the window")
    return bad


def run(ctx):
    from benchmark.runners.train import loss_problems

    facts = cycle(ctx)
    ev, by = facts["events"], common.by_event
    ready = by(ev, "ready")
    if ctx["require_tpu"] and (
        not ready or any(e["platform"] != "tpu" for e in ready)
        or ready[0]["count"] < ctx["chips"]
    ):
        print("benchmark: the worker found no TPU: "
              f"{[(e['platform'], e['count']) for e in ready]}",
              file=sys.stderr)
        with open(facts["log"], errors="replace") as f:
            sys.stderr.write(f.read()[-3000:])
        sys.exit(3)
    steps = by(ev, "step")
    first = [e for e in steps if e["n"] == 1 and e["incarnation"] == 0]
    ref = by(ev, "reference")
    problems = check(
        facts,
        loss_problems(first[0]["loss"], ref[0]["loss"])
        if first and ref else ["no first step to hold to the reference"],
    )
    if problems:
        with open(facts["log"], errors="replace") as f:
            sys.stderr.write(f.read()[-3000:])

    e2e = {}
    t_kill = facts["t_kill"]
    window_start = by(ev, "window_start", incarnation=0)
    if window_start:
        e2e["setup_s"] = window_start[0]["t"] - ctx["t_start"]
    saved = by(ev, "saved", incarnation=0)
    compiled0 = by(ev, "compiled", incarnation=0)
    if saved and all(e["ok"] for e in saved):
        # The first save creates and first-touches the segment; where
        # there are more, the stall a long job pays is the later ones'.
        timed = saved[1:] or saved
        e2e["ckpt_save_stall_s"] = statistics.mean(
            e["seconds"] for e in timed
        )
    resumed = by(ev, "step", incarnation=1)
    if t_kill is not None and resumed:
        e2e["resume_s"] = resumed[0]["t"] - t_kill

    failed_steps = sum(not math.isfinite(e["loss"]) for e in steps)
    cycle_ok = [e["ok"] for e in saved] + [
        t_kill is not None, bool(resumed)
    ]
    trace = by(ev, "trace")
    device = (
        {k: ready[0][k] for k in ("platform", "kind", "count")}
        if ready else {}
    )
    idle = by(ev, "idle")
    if idle and compiled0:
        device["memory_peak_bytes"] = max(
            idle[0]["peak_bytes_in_use"],
            compiled0[0]["state_bytes"] // ctx["chips"]
            + compiled0[0]["temp_bytes"],
        )
    return {
        "problems": problems,
        "attempted": len(steps) + len(cycle_ok),
        "failed": failed_steps + cycle_ok.count(False),
        "end_to_end": e2e,
        "t_kill": t_kill,
        "job": facts["job"],
        "device": device,
        "trace": trace[0]["reduced"] if trace else None,
        "events": ev,
    }


if __name__ == "__main__":
    with open(sys.argv[1]) as f:
        worker(json.load(f))
