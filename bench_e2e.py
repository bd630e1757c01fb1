"""Measured end-to-end recovery benchmark: SIGKILL a real supervised
worker mid-training and time every phase of the comeback through the
actual agent path — no modeling.

Topology (same as the agent e2e tests, tests/test_elastic_agent.py):
the parent process runs a LocalJobMaster + the agent-resident
AsyncCheckpointSaver + an ElasticAgent on CPU; the worker subprocess
(this file with --worker) trains a TpuLM on the accelerator, flash-
checkpointing to agent shm. The parent kills the worker between
checkpoints, the agent detects it, restarts it, and the new incarnation
restores from shm and replays the lost steps.

Measured phases (from the timestamped event log the worker writes):
  detect_restart_s   kill -> new worker process boots (agent monitor +
                     rendezvous + spawn)
  runtime_init_s     boot -> JAX backend ready (TPU client init)
  restore_s          backend ready -> state restored from agent shm
  replay_s           restored -> training regained the pre-kill step
  measured_recovery_s  sum: kill -> regained

The worker saves at the Young/Daly-autotuned cadence computed from its
OWN measured save cost (flash_ckpt/autotune.py — the production
autotuner), and the parent kills mid-interval, so the replayed work
equals the expected half-interval a real failure loses. The restarted
incarnation AOT-compiles the train step concurrently with the restore
H2D transfer (shapes are known from specs) and times the restore up to
``jax.block_until_ready`` on the restored state.

The JSON line also reports ``e2e_goodput_pct``: goodput at the
reference's operating point (MTBF 3600s — the basis of DLRover's
69%->95% claim, README.md:61-63) using the MEASURED downtime including
process restart, alongside the formula-only number bench.py prints; the
legacy 60s cadence is reported for comparability. The worker's
``init_distributed()`` points JAX at the persistent compilation cache
(common/compile_cache.py) so the restarted incarnation compiles from
cache — exactly how a production TPU job restarts.

This parent process never imports JAX: the chip belongs to the worker.

Parity: the reference measures recovery the same way operationally
(docs/blogs/flash_checkpoint.md restore-in-seconds claims) but has no
in-repo harness for it; this file is that harness.
"""

import argparse
import json
import os
import signal
import sys
import tempfile
import threading
import time

MTBF_S = 3600.0
SAVE_EVERY_S = 60.0
BASELINE_GOODPUT = 95.0

# The first incarnation trains until the parent kills it (bounded by
# FIRST_RUN_LIMIT_S in case no kill ever comes); the restarted one stops
# STEPS_PAST_KILL steps after regaining the pre-kill step. A fixed step
# count cannot do: how many steps fit in half a save interval depends
# on the chip.
FIRST_RUN_LIMIT_S = 600.0
STEPS_PAST_KILL = 20
FIRST_SAVE_STEP = 10  # past step-time warmup; later saves follow the
                      # autotuned cadence the worker computes and emits


def recovery_config():
    """The ONE recovery-bench model, shared by bench.py's goodput phase
    and this harness's worker so both measure the same workload."""
    from dlrover_tpu.models import llama

    return llama.TpuLMConfig(
        vocab_size=4096,
        embed_dim=256,
        n_layers=4,
        n_heads=8,
        n_kv_heads=4,
        head_dim=32,
        mlp_dim=1024,
        dtype="bfloat16",
    )


# ---------------------------------------------------------------------------
# Worker mode
# ---------------------------------------------------------------------------


def worker_main(events_path: str, ckpt_dir: str):
    def emit(event: str, **kw):
        detail = " ".join(f"{k}={v}" for k, v in kw.items())
        with open(events_path, "a") as f:
            f.write(f"{time.time():.6f} {incarnation} {event} {detail}\n")

    incarnation = int(os.getenv("DLROVER_TPU_RESTART_COUNT", "0"))
    emit("boot")

    import jax

    if os.environ.get("BENCH_E2E_PLATFORM") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from dlrover_tpu.flash_ckpt.checkpointer import Checkpointer
    from dlrover_tpu.models import llama
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.trainer import train_step as ts
    from dlrover_tpu.trainer.runtime import init_distributed

    from dlrover_tpu.flash_ckpt.autotune import optimal_save_interval_s

    emit("imported")  # what a warm standby has behind it already
    ctx = init_distributed()
    incarnation = ctx.restart_count
    platform = jax.devices()[0].platform
    emit("jax_ready", platform=platform)

    if platform == "cpu":
        cfg = llama.tiny_config()
        batch, seq = 8, 64
    else:
        cfg = recovery_config()
        batch, seq = 8, 512

    mesh = build_mesh(MeshConfig(dp=len(jax.devices())), jax.devices())
    tc = ts.TrainConfig(warmup_steps=10)
    opt = ts.make_optimizer(tc)
    # Restore-FIRST: a restarted incarnation goes straight from shm to
    # device state and never compiles (or runs) the init program it
    # would immediately overwrite — only a fresh start pays init.
    specs = ts.state_specs(cfg, opt)
    shardings = ts.state_shardings(specs, mesh)
    step_fn, _ = ts.make_train_step(cfg, tc, opt, mesh, donate=False)

    # AOT-compile the train step CONCURRENTLY with the restore H2D
    # transfer: the shapes are known from the specs, so the restarted
    # incarnation overlaps its (persistent-cache-served) compile with
    # the state transfer instead of paying them back to back — the
    # warmup that dominated replay in earlier rounds.
    abs_state = {
        "params": jax.eval_shape(
            lambda: llama.init_params(cfg, jax.random.key(0))[0]
        ),
        "opt_state": jax.eval_shape(
            opt.init,
            jax.eval_shape(
                lambda: llama.init_params(cfg, jax.random.key(0))[0]
            ),
        ),
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }
    abs_batch = {
        "tokens": jax.ShapeDtypeStruct((batch, seq + 1), jnp.int32)
    }
    aot_box = {}

    def _aot():
        try:
            with mesh:
                aot_box["fn"] = step_fn.jitted.lower(
                    abs_state, abs_batch
                ).compile()
        except Exception as e:  # noqa: BLE001 - fall back to lazy jit
            aot_box["err"] = f"{type(e).__name__}: {e}"

    aot_thread = threading.Thread(target=_aot, daemon=True)
    aot_thread.start()

    ckpt = Checkpointer(ckpt_dir)
    restored = ckpt.load_checkpoint(sharding_tree=shardings)
    if restored is not None:
        rstep, state, _ = restored
        jax.block_until_ready(state)
        state_mb = sum(
            leaf.nbytes for leaf in jax.tree_util.tree_leaves(state)
        ) / 1e6
        emit("restored", step=rstep, mb=round(state_mb, 1))
    else:
        state, _ = ts.init_train_state(cfg, opt, mesh, jax.random.key(0))
        emit("fresh_start")

    tokens = jax.random.randint(
        jax.random.key(1), (batch, seq + 1), 0, cfg.vocab_size
    ).astype(jnp.int32)
    jax.block_until_ready(tokens)
    batch_d = {"tokens": tokens}
    aot_thread.join(timeout=300)
    run_step = aot_box.get("fn", step_fn)
    if "err" in aot_box:
        emit("aot_failed", err=aot_box["err"].replace(" ", "_")[:80])
    emit("data_ready")

    # Saves run the production way: the step loop only pays the device-
    # snapshot block (~ms); the D2H drain proceeds in a background
    # thread (waiting inline would serialize it into every interval AND
    # into replay). "saving" marks the
    # launch (the point defining what a kill loses); "saved" marks the
    # drained, restorable snapshot the parent may kill after. Cadence:
    # the Young/Daly optimum from this run's own measured block+drain —
    # the same autotuner production jobs use (flash_ckpt/autotune.py).
    save_lock = threading.Lock()
    save_st = {"auto": None, "last": None, "busy": False}
    steps_local = 0

    def _drain(step_n, block, launch_t):
        ckpt.wait_async_save()
        drain = time.time() - launch_t
        with save_lock:
            if save_st["auto"] is None:
                save_st["auto"] = optimal_save_interval_s(
                    block, drain_s=drain, mtbf_s=MTBF_S
                )
            save_st["busy"] = False
            cadence = save_st["auto"]
        emit(
            "saved", n=step_n, block=round(block, 4),
            drain=round(drain, 3), cadence=round(cadence, 2),
        )

    if incarnation == 0:
        stop_step = float("inf")
        deadline = time.time() + FIRST_RUN_LIMIT_S
    else:
        stop_step = STEPS_PAST_KILL + max(
            int(kw["n"]) for _, inc, ev, kw in parse_events(events_path)
            if ev == "step" and inc < incarnation
        )
        deadline = float("inf")
    while int(state["step"]) < stop_step and time.time() < deadline:
        t0 = time.time()
        try:
            state, m = run_step(state, batch_d)
        except Exception:  # noqa: BLE001 - AOT input mismatch: fall back
            if run_step is step_fn:
                raise
            run_step = step_fn
            state, m = run_step(state, batch_d)
        float(m["loss"])  # host fetch: the only reliable barrier
        step = int(state["step"])
        steps_local += 1
        emit("step", n=step, dur=round(time.time() - t0, 4))
        with save_lock:
            due = not save_st["busy"] and (
                steps_local >= FIRST_SAVE_STEP
                if save_st["auto"] is None
                else time.time() - save_st["last"] >= save_st["auto"]
            )
            if due:
                save_st["busy"] = True
        if due:
            launch_t = time.time()
            block = ckpt.save_checkpoint_async(step, state)
            with save_lock:
                save_st["last"] = launch_t
            emit("saving", n=step)
            threading.Thread(
                target=_drain, args=(step, block, launch_t), daemon=True
            ).start()
    deadline = time.time() + 60
    while time.time() < deadline:  # let the last drain land
        with save_lock:
            if not save_st["busy"]:
                break
        time.sleep(0.05)
    ckpt.close()
    emit("done")
    sys.exit(0)


# ---------------------------------------------------------------------------
# Parent mode
# ---------------------------------------------------------------------------


def parse_events(path):
    rows = []
    if not os.path.exists(path):
        return rows
    for line in open(path):
        parts = line.split()
        t, inc, event = float(parts[0]), int(parts[1]), parts[2]
        kw = dict(p.split("=", 1) for p in parts[3:])
        rows.append((t, inc, event, kw))
    return rows


def main():
    # No JAX here: the accelerator belongs to the worker; the control
    # plane (master/agent/saver) is host-only.
    from dlrover_tpu.agent.master_client import MasterClient
    from dlrover_tpu.agent.training import (
        ElasticAgent,
        RunResult,
        WorkerSpec,
    )
    from dlrover_tpu.flash_ckpt.saver import AsyncCheckpointSaver
    from dlrover_tpu.master.local_master import LocalJobMaster
    from dlrover_tpu.master.node.job_context import JobContext

    # Unique workdir per run: a previous run killed mid-flight leaves
    # stale UDS sockets / shm ckpts that would poison this one.
    workdir = os.environ.get("BENCH_E2E_DIR") or os.path.join(
        tempfile.gettempdir(), f"dlrover_tpu_bench_e2e_{os.getpid()}"
    )
    os.makedirs(workdir, exist_ok=True)
    events_path = os.path.join(workdir, f"events-{os.getpid()}.log")
    ckpt_dir = os.path.join(workdir, "ckpt")

    os.environ["DLROVER_TPU_JOB_NAME"] = f"bench_e2e_{os.getpid()}"
    os.environ["DLROVER_TPU_SHARED_DIR"] = os.path.join(workdir, "uds")
    os.environ["DLROVER_TPU_NODE_RANK"] = "0"
    JobContext.reset_singleton()
    master = LocalJobMaster(port=0, node_num=1)
    master.prepare()
    client = MasterClient(f"localhost:{master.port}", node_id=0)
    AsyncCheckpointSaver.reset()
    saver = AsyncCheckpointSaver.start_async_saving_ckpt(client=client)

    spec = WorkerSpec(
        entrypoint=os.path.abspath(__file__),
        args=["--worker", events_path, ckpt_dir],
        nproc_per_node=1,
        max_restarts=3,
        node_rank=0,
        monitor_interval=0.2,
        # Restart adopts a pre-spawned interpreter that has already
        # imported jax (agent/standby.py).
        warm_standby=True,
    )
    agent = ElasticAgent(spec, client, ckpt_saver=saver)
    box = {}

    def run():
        box["result"] = agent.run()

    t = threading.Thread(target=run, daemon=True)
    t.start()

    # Kill mid-interval at the worker's own autotuned cadence: a save's
    # LAUNCH defines what a kill loses; its "saved" event means the
    # snapshot drained and is restorable. Kill cadence/2 past the
    # latest restorable launch so the replayed work equals the expected
    # half-interval a production failure loses — then SIGKILL.
    deadline = time.time() + 900
    t_kill = None
    while time.time() < deadline:
        rows = parse_events(events_path)
        launches = {
            int(kw["n"]): t_
            for t_, inc, ev, kw in rows
            if inc == 0 and ev == "saving"
        }
        drained = [
            kw
            for _, inc, ev, kw in rows
            if inc == 0 and ev == "saved"
        ]
        done0 = any(
            inc == 0 and ev == "done" for _, inc, ev, _kw in rows
        )
        assert not done0, (
            "worker gave up waiting for the mid-interval kill "
            f"({FIRST_RUN_LIMIT_S:.0f}s)"
        )
        if drained:
            kw = drained[-1]
            t_launch = launches[int(kw["n"])]
            kill_at = t_launch + float(kw["cadence"]) / 2.0
            if time.time() >= kill_at:
                pid = agent._workers[0].process.pid
                t_kill = time.time()
                os.kill(pid, signal.SIGKILL)
                break
        time.sleep(0.1)
    assert t_kill is not None, "worker never reached the kill point"

    t.join(timeout=900)
    ok = box.get("result") == RunResult.SUCCEEDED
    saver.unlink_all(2)
    AsyncCheckpointSaver.reset()
    master.stop()

    rows = parse_events(events_path)
    pre_kill = max(
        int(kw["n"]) for _, inc, ev, kw in rows if inc == 0 and ev == "step"
    )
    ev1 = [(t_, ev, kw) for t_, inc, ev, kw in rows if inc >= 1]

    def first(evname, pred=lambda kw: True):
        for t_, ev, kw in ev1:
            if ev == evname and pred(kw):
                return t_, kw
        return None, None

    t_boot, _ = first("boot")
    t_imported, _ = first("imported")
    t_ready, _ = first("jax_ready")
    t_restored, restored_kw = first("restored")
    t_caught, _ = first("step", lambda kw: int(kw["n"]) >= pre_kill)
    steps1 = [
        (float(kw["dur"]))
        for _, ev, kw in ev1
        if ev == "step" and int(kw["n"]) > pre_kill
    ]
    save_blocks = [
        float(kw["block"]) for _, inc, ev, kw in rows if ev == "saved"
    ]
    clean_steps = sorted(
        float(kw["dur"])
        for _, inc, ev, kw in rows
        if ev == "step" and inc == 0
    )
    step_s = clean_steps[len(clean_steps) // 2] if clean_steps else 0.0

    result = {
        "metric": "measured_recovery_s",
        "unit": "s",
        "e2e_succeeded": ok,
        # Where the WORKER ran: a CPU run must not read as a chip's.
        "platform": next(
            (kw["platform"] for _, _, ev, kw in rows if ev == "jax_ready"),
            None,
        ),
    }
    if ok and t_caught is not None:
        detect = t_boot - t_kill
        init = t_ready - t_boot
        restore = t_restored - t_ready
        replay = t_caught - t_restored
        recovery = t_caught - t_kill
        lost_steps = pre_kill - int(restored_kw["step"])
        # The first replayed step pays a one-time warmup (jit cache
        # load + device transfer pipelining); steady replay then runs
        # at clean speed. Model the warmup as one-time, not per-step.
        replay_warmup = max(replay - lost_steps * step_s, 0.0)
        # Goodput with MEASURED downtime: per failure, the process
        # restart (detect+init+restore) plus the replay warmup plus
        # replay of half a save interval at clean speed; plus the
        # per-save overhead between failures.
        save_block = sum(save_blocks) / max(len(save_blocks), 1)
        # The save cadence is the Young/Daly optimum from this run's OWN
        # measured blocking cost (flash_ckpt/autotune.py), not the
        # legacy 60s constant; both operating points are reported. The
        # effective recovery a user experiences at the autotuned cadence
        # is the process restart plus expected replay of half the (now
        # short) interval.
        from dlrover_tpu.flash_ckpt.autotune import (
            expected_goodput_pct,
            optimal_save_interval_s,
        )

        auto_every = optimal_save_interval_s(save_block, mtbf_s=MTBF_S)
        restart_cost = detect + init + restore + replay_warmup

        def goodput_at(every_s):
            return expected_goodput_pct(
                every_s, save_block, recovery_s=restart_cost,
                mtbf_s=MTBF_S,
            )

        e2e_goodput = goodput_at(auto_every)
        effective_recovery = (
            detect + init + restore + replay_warmup + auto_every / 2.0
        )
        state_mb = float(restored_kw.get("mb", 0.0))
        s_per_gb = restore / max(state_mb / 1024.0, 1e-9)
        result.update(
            value=round(recovery, 3),
            # Framework cost with the state transfer excluded: what the
            # recovery machinery itself takes (detect + runtime init +
            # replay). The restore is reported with its bytes and
            # achieved bandwidth next to the seconds.
            machinery_recovery_s=round(recovery - restore, 3),
            detect_restart_s=round(detect, 3),
            runtime_init_s=round(init, 3),
            # Part of runtime_init_s: ~0 when a warm standby (which
            # imported everything while it waited) was adopted.
            restart_imports_s=round(t_imported - t_boot, 3),
            restore_s=round(restore, 3),
            restore_state_mb=round(state_mb, 1),
            restore_mb_per_s=round(state_mb / max(restore, 1e-9), 1),
            restore_s_per_gb=round(s_per_gb, 2),
            replay_s=round(replay, 3),
            replayed_steps=lost_steps,
            step_time_s=round(step_s, 4),
            autotuned_save_every_s=round(auto_every, 2),
            effective_recovery_s=round(effective_recovery, 3),
            e2e_goodput_pct=round(e2e_goodput, 2),
            e2e_goodput_at_60s=round(goodput_at(SAVE_EVERY_S), 2),
            e2e_goodput_vs_baseline=round(e2e_goodput / BASELINE_GOODPUT, 4),
        )
    assert "jax" not in sys.modules, "bench_e2e's parent imported JAX"
    print(json.dumps(result), flush=True)
    # Hard exit: master/agent helper threads must not block teardown.
    os._exit(0 if ok else 1)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", nargs=2, metavar=("EVENTS", "CKPT"))
    ns = ap.parse_args()
    if ns.worker:
        worker_main(*ns.worker)
    else:
        main()
