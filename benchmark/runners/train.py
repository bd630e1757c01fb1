"""Runner ``train``: the dense training step, back to back for the window.

A copy of ``chip_smoke.py``'s ``train_worker`` without the launcher: the
program's ``make_train_step`` / ``init_train_state`` on a one-axis mesh,
state donated, sizes from the configuration and traffic files, a timed
window added. ``TrainJob`` is what ``elastic_train`` runs under the
launcher too, so both cells time the same step.
"""

import math
import time

import numpy as np

from benchmark import common, reference

# bf16 activations against the float32 reference, on a loss of ~11 that
# is a mean over 8,192 tokens: the chip runs of three seeds read relative
# differences of 2.3e-6, 8.2e-6 and 9.2e-6 (e.g. 10.885756 against
# 10.885781; my chip runs, PR 23), and every chip run of PR 23 passed.
# A step that computed its matmuls in fp8, dropped a layer or a norm, or
# misplaced RoPE moves the loss by parts in a thousand or more; this
# leaves five times the largest difference read.
LOSS_RTOL = 5e-5


class TrainJob:
    """The program's train step for one configuration on ``n_devices``
    chips (dp), with seeded batches. JAX is imported by the caller's
    process before this is built."""

    def __init__(self, cfg_json, traffic, seed, n_devices=1):
        import jax

        from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
        from dlrover_tpu.trainer import train_step as ts

        self.jax, self.ts = jax, ts
        self.cfg_json, self.seed = cfg_json, seed
        self.cfg = common.lm_config(cfg_json)
        train = cfg_json["train"]
        self.micro = train["micro_batch"] * train["grad_accum"]
        self.seq = traffic["seq_len"]
        self.tokens_per_step = self.micro * self.seq
        self.n_devices = n_devices
        self.mesh = build_mesh(
            MeshConfig(dp=n_devices), jax.devices()[:n_devices]
        )
        self.tc = ts.TrainConfig(
            warmup_steps=train["warmup_steps"],
            grad_accum=train["grad_accum"],
        )
        self.opt = ts.make_optimizer(self.tc)
        self.shardings = ts.state_shardings(
            ts.state_specs(self.cfg, self.opt), self.mesh
        )
        self.step_fn, _ = ts.make_train_step(
            self.cfg, self.tc, self.opt, self.mesh,
            donate=train["donate_state"],
        )
        self.state = None
        self.compiled = None

    def init_state(self):
        """Weights and optimizer state on the device, in one jitted call
        from the seed."""
        self.state, _ = self.ts.init_train_state(
            self.cfg, self.opt, self.mesh, common.rng_key(self.seed)
        )
        self.jax.block_until_ready(self.state)

    def state_bytes(self):
        return sum(
            x.nbytes for x in self.jax.tree_util.tree_leaves(self.state)
        )

    def host_batch(self, step):
        rng = np.random.default_rng((self.seed, step))
        return rng.integers(
            0, self.cfg.vocab_size, (self.micro, self.seq + 1),
            dtype=np.int32,
        )

    def batch_at(self, step):
        sharding = self.jax.sharding.NamedSharding(
            self.mesh, self.ts.batch_spec()
        )
        return {
            "tokens": self.jax.device_put(self.host_batch(step), sharding)
        }

    def compile(self):
        with self.mesh:
            self.compiled = self.step_fn.jitted.lower(
                self.state, self.batch_at(0)
            ).compile()
        return self.compiled

    def temp_bytes(self):
        try:
            return int(self.compiled.memory_analysis().temp_size_in_bytes)
        except Exception:  # noqa: BLE001 — a backend without the analysis
            return 0

    def reference_loss(self, step):
        """The plain float32 loss of batch ``step`` under the CURRENT
        weights (call before the step that consumes them)."""
        return reference.batch_loss(
            self.state["params"], self.host_batch(step),
            self.cfg.rope_theta,
        )

    def step(self, n):
        """Step ``n``: returns its loss, fetched — so the step is over."""
        with common.annotate("bench.batch_build"):
            batch = self.batch_at(n)
        with common.annotate("bench.step_call"):
            self.state, metrics = self.compiled(self.state, batch)
        with common.annotate("bench.loss_fetch"):
            return float(metrics["loss"])

    def traced_steps(self, first, count, workdir):
        """Steps ``first .. first+count-1`` under one profiler session.
        Returns (losses, reduced trace or None, event dump or None)."""
        from benchmark import trace_reduce

        prof = common.Profile(workdir)
        prof.start()
        try:
            losses = [self.step(first + i) for i in range(count)]
        finally:
            dump = prof.stop(
                trace_reduce.scopes_from_hlo(self.compiled.as_text())
            )
        reduced = trace_reduce.reduce(dump) if dump else None
        if reduced is not None:
            reduced["steps"] = count
        return losses, reduced, dump


def loss_problems(loss, ref):
    if not math.isfinite(loss):
        return [f"first step's loss is {loss!r}"]
    if not math.isclose(loss, ref, rel_tol=LOSS_RTOL):
        return [
            f"first step's loss {loss!r} is not within {LOSS_RTOL} of "
            f"the float32 reference's {ref!r}"
        ]
    return []


def run(ctx):
    import jax

    counts = common.count_jax_events()
    from dlrover_tpu.common.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    device = common.device_facts(devices)
    if ctx["require_tpu"]:
        common.require_tpu(devices, ctx["chips"])
    traffic = ctx["traffic"]
    log = common.EventLog(ctx["out_dir"] + "/events.jsonl")
    log.emit("ready", **device, cache_dir=cache_dir)

    job = TrainJob(ctx["config"], traffic, ctx["seed"], ctx["chips"])
    job.init_state()
    t0 = time.time()
    job.compile()
    log.emit(
        "compiled", seconds=time.time() - t0,
        cache_hits=counts[common.CACHE_HIT],
        cache_misses=counts[common.CACHE_MISS],
        state_bytes=job.state_bytes(), temp_bytes=job.temp_bytes(),
    )
    ref = job.reference_loss(1)
    losses = [job.step(n) for n in range(1, traffic["warm_steps"] + 1)]
    problems = loss_problems(losses[0], ref)
    log.emit("warm", losses=losses, reference_loss=ref)
    n = traffic["warm_steps"]

    trace = dump = None
    if ctx["trace"]:
        traced, trace, dump = job.traced_steps(
            n + 1, traffic["trace_steps"], ctx["out_dir"]
        )
        losses += traced
        n += len(traced)

    compiles_before = counts[common.BACKEND_COMPILE]
    first = n + 1
    t_window = time.time()
    setup_s = t_window - ctx["t_start"]
    deadline = t_window + ctx["seconds"]
    while True:
        n += 1
        losses.append(job.step(n))
        t_end = time.time()
        if t_end >= deadline:
            break
    window_s = t_end - t_window
    steps = n - first + 1
    compiles = counts[common.BACKEND_COMPILE] - compiles_before
    if compiles:
        problems.append(f"{compiles} compile(s) inside the window")
    failed = sum(not math.isfinite(x) for x in losses)
    if failed:
        problems.append(f"{failed} step(s) with a non-finite loss")
    tokens_per_s = steps * job.tokens_per_step / window_s
    log.emit(
        "window", steps=steps, seconds=window_s, losses=losses,
        tokens_per_s=tokens_per_s,
    )
    peak = max(
        common.memory_peak(devices[:ctx["chips"]]),
        # memory_stats' peak leaves out the step program's own
        # temporaries (PERF.md, PR 21); they are as real.
        job.state_bytes() // ctx["chips"] + job.temp_bytes(),
    )
    return {
        "problems": problems,
        "attempted": len(losses),
        "failed": failed,
        "end_to_end": {
            "train_tokens_per_s": tokens_per_s, "setup_s": setup_s,
        },
        "device": dict(device, memory_peak_bytes=peak),
        "trace": trace,
        "dump": dump,
        "window": {
            "seconds": window_s, "steps": steps,
            "tokens_per_step": job.tokens_per_step,
            "tokens_per_s": tokens_per_s,
            "micro_batch": job.micro, "seq_len": job.seq,
        },
        "events": common.EventLog.read(log.path),
    }
