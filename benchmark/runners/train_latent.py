"""Runner ``train_latent``: the layer-pattern model with a rotary latent
mixer and a multi-token-prediction module, its training step back to
back for the window -- ``runners/train_hybrid.py``'s protocol (state from
the seed on the device, the routers' biases balanced on batch 0, one
compile, the reference of batch 1 under the initial weights against the
first step, warm steps, traced steps when asked, a window of steps each
ended by the loss fetch, no compile inside it, ``memory_peak_bytes`` with
the program's temporaries) for a ``glm4_moe_lite`` configuration file.
``LatentJob`` is ``train_hybrid.HybridJob`` with another constructor,
another reference, sequences of ``seq_len + 2`` tokens and a step that
keeps both losses and the module's expert rows; ``run`` is
``train_hybrid.run`` with those and the scope table of ``mtp_scopes``.

``correct`` holds the FIRST timed step to the float32 reference
(``benchmark/reference_glm.py``) three ways: its main cross-entropy; the
module's cross-entropy; and its GRADIENT, leaf by leaf, read back from
the step's own optimizer state (Adam's first moment after one step is
``(1 - b1)`` times the clipped gradient) against ``jax.vjp`` of the
reference on the same 8,194 tokens -- the backward of the flash kernels
at 256 / 256 under the rotation, of the expert gathers, of the module
and of the embedding and head that both losses reach, none of which a
loss can see.
"""

import math
import sys
import time

from benchmark import common, mtp_scopes, reference_glm
from benchmark.runners import train, train_hybrid

COUNTERS = train_hybrid.COUNTERS + ("mtp_moe_rows_held",)
LOSSES = ("ce", "ce_mtp")

# Four limits, each THREE TIMES the largest reading on the chip over the
# eight seeds run while they were set (my chip runs, PR 43: calls 1 and
# 2; PERF.md section 6 has every reading, and the seeds run afterwards).
# The program: bf16 projections, experts and attention; the reference:
# float32 throughout. Like ``train_hybrid``'s, these are numbers the
# precision hardly moves until it drops below bfloat16 (control
# ``matmuls_fp8``: the reference with every matmul's operands in float8),
# so each is held to its readings and not put between two precisions.
#
# The first step's MAIN LOSS, ~10.38, a mean over 8,192 tokens: relative
# differences 3.4e-6 .. 5.25e-5 (largest: seed 2900000011).
LOSS_RTOL = 1.6e-4
# The MODULE'S LOSS, ~10.38: 1.7e-6 .. 1.155e-4 (seed 3700000003). It
# reads wider than the main loss because it sits behind one more block
# and a second pass of bf16 rounding on the same hidden states.
MTP_LOSS_RTOL = 3.5e-4
# The first step's GRADIENT by leaf, |g - ref| / |ref|. bf16 noise puts
# the plain leaves (attention, dense and shared FFNs, norms, W_eh,
# embedding, head) 2-7 % off: largest 0.0693 (the module's w_qa; its
# q_norm, w_qb and ffn_norm are the next, 0.055-0.067: the module's
# block sits deepest). The ROUTED leaves (routers, held experts) read
# 0.13-0.23 (largest 0.2292, the module's router): program and
# reference send the few tokens in a hundred that sit at a top-4
# boundary to different experts, and a row that went elsewhere is a
# whole row's gradient (``train_hybrid``: 0.26 at kimi's top-8 of 256).
GRAD_RTOL = 0.21
GRAD_RTOL_ROUTED = 0.69


def latent_config(cfg_json, **overrides):
    """The program's ``HybridLMConfig`` for a ``glm4_moe_lite``
    configuration file: ``first_k_dense_replace`` leading blocks with the
    dense FFN, the rest one expert block repeated, the share from
    ``share`` and the file's own ``vocab_rows_held``."""
    from dlrover_tpu.models import hybrid

    if not hasattr(hybrid, "mtp_loss"):
        sys.exit("benchmark: this program's layer pattern has no rotary "
                 "latent mixer and no prediction module: it cannot run "
                 "this configuration")
    if cfg_json.get("hidden_act", "silu") != "silu":
        raise ValueError("the repo's MLPs are SwiGLU (silu) only")
    if cfg_json.get("tie_word_embeddings") or cfg_json["attention_bias"]:
        raise ValueError("the repo's head is untied and nothing has a bias")
    if not (
        cfg_json["topk_method"] == "noaux_tc" and cfg_json["norm_topk_prob"]
        and cfg_json["n_group"] == cfg_json["topk_group"] == 1
        and cfg_json["rope_scaling"] is None
        and cfg_json["partial_rotary_factor"] == 1
    ):
        raise ValueError("not the router / rotation this model has")
    dense_first = cfg_json["first_k_dense_replace"]
    held = cfg_json["n_routed_experts"]
    knobs = cfg_json["train"]
    kw = dict(
        vocab_size=cfg_json["vocab_rows_held"],
        embed_dim=cfg_json["hidden_size"],
        leading=(("mla_rope", "dense"),) * dense_first,
        period=(("mla_rope", "moe"),),
        n_periods=cfg_json["num_hidden_layers"] - dense_first,
        n_heads=cfg_json["num_attention_heads"],
        q_lora_rank=cfg_json["q_lora_rank"],
        kv_lora_rank=cfg_json["kv_lora_rank"],
        qk_nope_dim=cfg_json["qk_nope_head_dim"],
        qk_rope_dim=cfg_json["qk_rope_head_dim"],
        v_head_dim=cfg_json["v_head_dim"],
        rope_theta=float(cfg_json["rope_theta"]),
        mlp_dim=cfg_json["intermediate_size"],
        moe_mlp_dim=cfg_json["moe_intermediate_size"],
        n_experts=cfg_json["published"]["n_routed_experts"],
        moe_top_k=cfg_json["num_experts_per_tok"],
        experts_held=(cfg_json["share"]["expert_rank"] * held, held),
        n_shared_experts=cfg_json["n_shared_experts"],
        routed_scaling=cfg_json["routed_scaling_factor"],
        mtp_depth=cfg_json["num_nextn_predict_layers"],
        mtp_weight=knobs["mtp_weight"],
        remat_keep=knobs["remat_keep"],
        dtype=cfg_json.get("torch_dtype", "bfloat16"),
    )
    kw.update(overrides)
    return hybrid.HybridLMConfig(**kw)


class LatentJob(train_hybrid.HybridJob):
    """``train_hybrid.HybridJob`` for a ``glm4_moe_lite`` configuration."""

    def __init__(self, cfg_json, traffic, seed, n_devices=1):
        import jax

        from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
        from dlrover_tpu.trainer import train_step as ts

        self.jax, self.ts = jax, ts
        self.cfg_json, self.seed = cfg_json, seed
        self.cfg = latent_config(cfg_json)
        knobs = cfg_json["train"]
        self.micro = knobs["micro_batch"] * knobs["grad_accum"]
        self.seq = traffic["seq_len"]
        self.tokens_per_step = self.micro * self.seq
        self.n_devices = n_devices
        self.mesh = build_mesh(
            MeshConfig(dp=n_devices), jax.devices()[:n_devices]
        )
        self.tc = ts.TrainConfig(
            warmup_steps=knobs["warmup_steps"],
            grad_accum=knobs["grad_accum"],
            learning_rate=knobs["learning_rate"],
        )
        self.opt = ts.make_optimizer(self.tc)
        self.step_fn, _ = ts.make_train_step(
            self.cfg, self.tc, self.opt, self.mesh,
            donate=knobs["donate_state"],
        )
        self.state = None
        self.compiled = None
        self.counters = []   # a step's counters and losses, on the device
        self.grad_norm = None

    def spec(self):
        return dict(
            super().spec(), rope_theta=self.cfg.rope_theta,
            mtp_weight=self.cfg.mtp_weight,
        )

    def host_batch(self, step):
        """``seq_len + 2`` tokens a sequence: the module is held to the
        token after next."""
        import numpy as np

        rng = np.random.default_rng((self.seed, step))
        return rng.integers(
            0, self.cfg.vocab_size, (self.micro, self.seq + 2),
            dtype=np.int32,
        )

    def init_state(self):
        """Weights, optimizer state and buffers from the seed; then the
        routers' score-correction biases, the module's block's too,
        balanced on batch 0 (``reference_glm.balanced_bias``: benchmark
        code, as the seeded weights are)."""
        train.TrainJob.init_state(self)
        balanced = reference_glm.balanced_bias(
            self.state["params"], self.state["buffers"],
            self.host_batch(0), self.spec(),
        )
        self.state["buffers"] = self.jax.tree_util.tree_map(
            lambda new, old: self.jax.device_put(new, old.sharding),
            balanced, self.state["buffers"],
        )
        self.jax.block_until_ready(self.state["buffers"])

    def reference(self, step):
        """((main CE, module CE), gradient on the host) of batch
        ``step`` under the CURRENT weights, by the float32 reference."""
        return reference_glm.batch_loss_and_grads(
            self.state["params"], self.state["buffers"],
            self.host_batch(step), self.spec(),
        )

    def step(self, n):
        """Step ``n``: its loss, fetched -- so the step is over. Its
        counters and the two parts of its loss stay on the device until
        ``fetched_counters``."""
        with common.annotate("bench.batch_build"):
            batch = self.batch_at(n)
        with common.annotate("bench.step_call"):
            self.state, metrics = self.compiled(self.state, batch)
        self.counters.append([metrics[k] for k in COUNTERS + LOSSES])
        self.grad_norm = metrics["grad_norm"]    # on the device
        with common.annotate("bench.loss_fetch"):
            return float(metrics["loss"])

    def fetched_counters(self):
        """{counter or loss part: [its value at every step so far]}."""
        rows = self.jax.device_get(self.counters)
        out = {
            k: [int(row[i]) for row in rows] for i, k in enumerate(COUNTERS)
        }
        for i, k in enumerate(LOSSES, len(COUNTERS)):
            out[k] = [float(row[i]) for row in rows]
        return out


def loss_problems(losses, ref):
    """``losses`` / ``ref``: (main CE, module CE) of the first step and
    of the reference."""
    out = []
    for name, mine, want, limit in zip(
        ("main loss", "prediction module's loss"), losses, ref,
        (LOSS_RTOL, MTP_LOSS_RTOL),
    ):
        if not math.isfinite(mine):
            out.append(f"first step's {name} is {mine!r}")
        elif not math.isclose(mine, want, rel_tol=limit):
            out.append(
                f"first step's {name} {mine!r} is not within {limit} of "
                f"the float32 reference's {want!r}"
            )
    return out


def gradient_problems(errors):
    """``train_hybrid.gradient_problems`` under this cell's limits."""
    routed = train_hybrid.routed_leaves(errors)
    out = []
    for mine, limit in (
        (set(errors) - routed - {"all"}, GRAD_RTOL),
        (routed, GRAD_RTOL_ROUTED),
    ):
        if not mine:
            continue
        worst = max(
            mine, key=lambda k: (not math.isfinite(errors[k]), errors[k])
        )
        if not errors[worst] <= limit:       # a NaN fails too
            out.append(
                f"first step's gradient of {worst} is {errors[worst]!r} "
                f"off the float32 reference's by norm, over {limit}"
            )
    return out


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

    job = LatentJob(ctx["config"], traffic, ctx["seed"], ctx["chips"])
    job.init_state()
    t0 = time.time()
    job.compile()
    log.emit(
        "compiled", seconds=time.time() - t0,
        cache_hits=counts[common.CACHE_HIT],
        cache_misses=counts[common.CACHE_MISS],
        state_bytes=job.state_bytes(), temp_bytes=job.temp_bytes(),
        peak_bytes=job.program_peak_bytes(),
    )
    t0 = time.time()
    ref, ref_grads = job.reference(1)
    log.emit("reference", seconds=time.time() - t0, losses=ref)
    t0 = time.time()
    losses = [job.step(1)]
    errors = job.gradient_errors(ref_grads)
    del ref_grads
    first = job.fetched_counters()
    first = (first["ce"][0], first["ce_mtp"][0])
    problems = loss_problems(first, ref) + gradient_problems(errors)
    log.emit(
        "gradient", seconds=time.time() - t0, errors=errors,
        grad_norm=float(job.grad_norm), losses=first,
    )
    losses += [job.step(n) for n in range(2, traffic["warm_steps"] + 1)]
    log.emit("warm", losses=losses, first_losses=first, reference_losses=ref)
    n = traffic["warm_steps"]

    trace = dump = scopes = None
    traced = range(0)
    if ctx["trace"]:
        traced = range(n, n + traffic["trace_steps"])
        more, trace, dump = job.traced_steps(
            n + 1, traffic["trace_steps"], ctx["out_dir"]
        )
        losses += more
        n += len(more)
        if dump:
            scopes = mtp_scopes.reduce(dump)
        if trace and scopes:
            # The breakdown by this table: it tells ``mtp/attn:mla``
            # from ``attn:mla``, which ``trace_reduce``'s cannot.
            trace["device_ops"] = scopes["device_ops"]

    compiles_before = counts[common.BACKEND_COMPILE]
    first_step = n + 1
    t_window = time.time()
    setup_s = t_window - ctx["t_start"]
    deadline = t_window + ctx["seconds"]
    step_ends = [t_window]
    while True:
        n += 1
        losses.append(job.step(n))
        t_end = time.time()
        step_ends.append(t_end)
        if t_end >= deadline:
            break
    window_s = t_end - t_window
    steps = n - first_step + 1
    compiles = counts[common.BACKEND_COMPILE] - compiles_before
    if compiles:
        problems.append(f"{compiles} compile(s) inside the window")
    failed = sum(not math.isfinite(x) for x in losses)
    if failed:
        problems.append(f"{failed} step(s) with a non-finite loss")
    counters = job.fetched_counters()
    dropped = sum(counters["moe_rows_dropped"])
    if dropped:
        problems.append(f"{dropped} expert row(s) dropped")
    tokens_per_s = steps * job.tokens_per_step / window_s
    log.emit(
        "window", steps=steps, seconds=window_s, losses=losses,
        tokens_per_s=tokens_per_s, counters=counters,
        step_s=[b - a for a, b in zip(step_ends, step_ends[1:])],
        peak_bytes_in_use=common.memory_peak(devices[:ctx["chips"]]),
        memory_stats=devices[0].memory_stats(),
    )
    peak = max(
        common.memory_peak(devices[:ctx["chips"]]),
        # memory_stats' peak leaves out the step program's own
        # temporaries (PERF.md, PR 21); they are as real.
        job.program_peak_bytes(),
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
        "mtp_scopes": scopes,
        "window": {
            "seconds": window_s, "steps": steps,
            "tokens_per_step": job.tokens_per_step,
            "tokens_per_s": tokens_per_s,
            "micro_batch": job.micro, "seq_len": job.seq,
        },
        # Every step so far, and which of them were traced / timed.
        "counters": counters,
        "traced_steps": [traced.start, traced.stop],
        "window_steps": [first_step - 1, n],
        "events": common.EventLog.read(log.path),
    }
