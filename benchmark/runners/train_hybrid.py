"""Runner ``train_hybrid``: the layer-pattern model's training step, back
to back for the window -- ``runners/train.py``'s protocol unchanged (state
from the seed on the device, one compile, the reference loss of batch 1
under the initial weights against the first step's loss, warm steps,
traced steps when asked, a window of steps each ended by the loss fetch,
no compile inside it, ``memory_peak_bytes`` with the program's
temporaries), for a configuration file that ``common.lm_config`` cannot
express. ``HybridJob`` is ``train.TrainJob`` with another constructor,
another reference and a step that keeps the model's counters; ``run`` is
``train.run`` with those and the scope table of ``hybrid_scopes``.

``correct`` holds the first step to the reference three ways: its loss;
its GRADIENT, leaf by leaf, read back from the step's own optimizer
state (Adam's first moment after one step is ``(1 - b1)`` times the
clipped gradient) against ``jax.vjp`` of the reference on the same
8,192 tokens -- the backward of the chunked scan, of the flash kernels
and of the expert gathers, the clip and the first moment, none of which
a loss can see; and the program's chunked SCAN, forward and backward,
against the token-by-token recurrence on the first layer's own inputs,
which is what holds the scan to float32.
"""

import math
import time

from benchmark import common, hybrid_scopes, reference_kimi_linear
from benchmark.runners import train

COUNTERS = ("moe_rows_held", "moe_rows_max", "moe_rows_dropped")

# Three limits, each read on the chip (my chip runs, PR 31; PERF.md
# section 6 has every reading). The program: bf16 projections, experts
# and attention around a float32 delta-rule scan; the reference: float32
# throughout.
#
# The first step's LOSS, ~10.44, a mean over 8,192 tokens: relative
# differences from 0 to 4.9e-5 over 49 runs of 36 seeds (largest:
# 10.430845 against 10.430337). It hardly moves with the precision --
# the reference with EVERY matmul in one bf16 pass is 1.0e-5 off, with
# its KDA recurrence carried in bfloat16 9e-8 -- so the limit is three
# times the largest reading. It catches an un-normalised router (3.5e-4
# to 5.4e-4), and a dropped gate, norm or layer, fp8 matmuls or a
# rotated kr, which move a loss by parts in a thousand or more.
LOSS_RTOL = 1.5e-4
# The first step's GRADIENT by leaf, |g - ref| / |ref|. bf16 noise puts
# every leaf 2-5 % off (largest over 22 runs: 0.0529, a KDA layer's
# w_a1), and the reference's recurrence in bfloat16 moves that by under
# a point: like the loss, a number the precision hardly moves, so three
# times the largest reading. The ROUTED leaves (routers, held experts)
# read 9-26 % (largest 0.2606, a router): program and reference route
# the few tokens in a hundred that sit at a top-8 boundary differently
# (sqrt(2 f) = 0.245 at f = 3 %), and a row that went elsewhere is a
# whole row's gradient -- with every row sent to the held experts
# (control `skewed_router`) the same leaves read 1.6-2.0 %. Three times
# the largest again. An un-normalised router is 0.65-1.2 off on the
# plain leaves and 0.88-0.96 on the routed.
GRAD_RTOL = 0.16
GRAD_RTOL_ROUTED = 0.78
# The program's chunked SCAN against the token-by-token recurrence on
# the same float32 inputs, output and five gradients by the worst: 1.1e-4
# to 3.3e-4 over 19 runs (dg or dk), and 6.7e-3 / 9.6e-3 on two seeds
# (dk; its least, o, 1.7e-3) when the recurrence is carried in bfloat16
# (control `kda_scan_bf16`), the precision below the float32 the
# configuration states. Between the two, at the geometric mean of 3.3e-4
# and 6.7e-3: this is the limit that holds the scan to float32, which
# neither of the two above can see.
SCAN_RTOL = 1.5e-3


def hybrid_config(cfg_json, **overrides):
    """The program's ``HybridLMConfig`` for a ``kimi_linear``
    configuration file: the layer kinds from ``linear_attn_config`` and
    ``first_k_dense_replace``, the share from ``share`` and the file's
    own ``vocab_rows_held``."""
    from dlrover_tpu.models import hybrid

    if cfg_json.get("hidden_act", "silu") != "silu":
        raise ValueError("the repo's MLPs are SwiGLU (silu) only")
    if cfg_json.get("tie_word_embeddings"):
        raise ValueError("the repo's head is untied")
    if cfg_json["moe_router_activation_func"] != "sigmoid" or not (
        cfg_json["moe_renormalize"] and cfg_json["mla_use_nope"]
        and cfg_json["q_lora_rank"] is None
        and cfg_json["num_expert_group"] == cfg_json["topk_group"] == 1
    ):
        raise ValueError("not the router / attention this model has")
    linear = cfg_json["linear_attn_config"]
    dense_first = cfg_json["first_k_dense_replace"]
    n_layers = cfg_json["num_hidden_layers"]

    def kinds(i):  # layers are numbered from 1
        if i in linear["kda_layers"]:
            mixer = "kda"
        elif i in linear["full_attn_layers"]:
            mixer = "mla"
        else:
            raise ValueError(f"layer {i} is of no kind")
        return mixer, "dense" if i <= dense_first else "moe"

    full = linear["full_attn_layers"]
    span = full[1] - full[0]
    rest = [kinds(i) for i in range(dense_first + 1, n_layers + 1)]
    period = tuple(rest[:span])
    if len(rest) % span or rest != list(period) * (len(rest) // span):
        raise ValueError(f"layers {dense_first + 1}..{n_layers} are not "
                         f"whole periods of {span}")
    held = cfg_json["num_experts"]
    kw = dict(
        vocab_size=cfg_json["vocab_rows_held"],
        embed_dim=cfg_json["hidden_size"],
        leading=tuple(kinds(i) for i in range(1, dense_first + 1)),
        period=period, n_periods=len(rest) // span,
        kda_heads=linear["num_heads"], kda_head_dim=linear["head_dim"],
        kda_conv=linear["short_conv_kernel_size"],
        kda_gate_rank=cfg_json["assumed_sizes"]["kda_gate_rank"],
        n_heads=cfg_json["num_attention_heads"],
        kv_lora_rank=cfg_json["kv_lora_rank"],
        qk_nope_dim=cfg_json["qk_nope_head_dim"],
        qk_rope_dim=cfg_json["qk_rope_head_dim"],
        v_head_dim=cfg_json["v_head_dim"],
        mlp_dim=cfg_json["intermediate_size"],
        moe_mlp_dim=cfg_json["moe_intermediate_size"],
        n_experts=cfg_json["published"]["num_experts"],
        moe_top_k=cfg_json["num_experts_per_token"],
        experts_held=(cfg_json["share"]["expert_rank"] * held, held),
        n_shared_experts=cfg_json["num_shared_experts"],
        routed_scaling=cfg_json["routed_scaling_factor"],
        dtype=cfg_json.get("torch_dtype", "bfloat16"),
    )
    kw.update(overrides)
    return hybrid.HybridLMConfig(**kw)


class HybridJob(train.TrainJob):
    """``train.TrainJob`` for a layer-pattern configuration."""

    def __init__(self, cfg_json, traffic, seed, n_devices=1):
        import jax

        from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
        from dlrover_tpu.trainer import train_step as ts

        self.jax, self.ts = jax, ts
        self.cfg_json, self.seed = cfg_json, seed
        self.cfg = hybrid_config(cfg_json)
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
        self.counters = []   # a step's counters, still on the device
        self.grad_norm = None

    def spec(self):
        first, _ = self.cfg.experts_held
        return {"top_k": self.cfg.moe_top_k, "first_expert": first,
                "routed_scaling": self.cfg.routed_scaling}

    def init_state(self):
        """Weights, optimizer state and buffers from the seed; then the
        routers' score-correction biases balanced on batch 0
        (``reference_kimi_linear.balanced_bias``: benchmark code, as the
        seeded weights are), so that the rows this chip's experts get
        are an even share whatever the seed's weights favour."""
        super().init_state()
        balanced = reference_kimi_linear.balanced_bias(
            self.state["params"], self.state["buffers"],
            self.host_batch(0), self.spec(),
        )
        self.state["buffers"] = self.jax.tree_util.tree_map(
            lambda new, old: self.jax.device_put(new, old.sharding),
            balanced, self.state["buffers"],
        )
        self.jax.block_until_ready(self.state["buffers"])

    def reference(self, step):
        """(loss, gradient on the host) of batch ``step`` under the
        CURRENT weights, by the plain float32 reference."""
        return reference_kimi_linear.batch_loss_and_grads(
            self.state["params"], self.state["buffers"],
            self.host_batch(step), self.spec(),
        )

    def gradient_errors(self, ref_grads):
        """{leaf: |g - ref| / |ref|} (2-norms; ``"all"``: over the whole
        trees) of the gradient the FIRST step took against
        ``ref_grads`` (on the host). The step's gradient is read from
        the state it left: Adam's first moment after one step is
        ``(1 - b1) * clipped gradient``, and the clip scaled by
        ``grad_clip / grad_norm`` where that is under 1 -- so call this
        after step 1 and before step 2. Leaf by leaf on the device: the
        reference's leaf goes up, two sums come down."""
        jax = self.jax
        (mu,) = [
            s.mu for s in jax.tree_util.tree_leaves(
                self.state["opt_state"], is_leaf=lambda x: hasattr(x, "mu")
            ) if hasattr(s, "mu")
        ]
        scale = max(1.0, float(self.grad_norm) / self.tc.grad_clip) / (
            1 - self.tc.beta1
        )
        sums = jax.jit(lambda m, r: (
            jax.numpy.sum(jax.numpy.square(m * scale - r)),
            jax.numpy.sum(jax.numpy.square(r)),
        ))
        flat, _ = jax.tree_util.tree_flatten_with_path(mu)
        pairs = {
            jax.tree_util.keystr(path): sums(m, r) for (path, m), r in
            zip(flat, jax.tree_util.tree_leaves(ref_grads))
        }
        return relative_errors(
            {k: (float(d), float(n)) for k, (d, n) in pairs.items()}
        )

    def scan_errors(self, step):
        """{output or gradient: relative error} of the PROGRAM's chunked
        delta-rule scan (``ops/kda.kda_chunked``, as the step calls it:
        the cell's heads, tokens and head size, float32) against the
        reference's token-by-token recurrence, both fed what the first
        layer's recurrence is fed for batch ``step`` under the current
        weights, and pulled back with the reference's output as the
        cotangent: ``o`` and the gradients by ``q, k, v, g, beta``."""
        import jax.numpy as jnp

        from dlrover_tpu.ops import kda as kda_ops

        ref = reference_kimi_linear

        def chunked(*xs):     # [s, h, ...] in and out, as the reference
            major = (jnp.moveaxis(x, 0, 1)[None] for x in xs)
            return jnp.moveaxis(kda_ops.kda_chunked(*major)[0], 0, 1)

        def pulled(fn, xs, cot=None):
            """fn's output, then its pullback of ``cot`` (None: of the
            output itself) by each of ``xs``."""
            out, pull = self.jax.vjp(fn, *xs)
            return (out,) + pull(out if cot is None else cot)

        with self.jax.default_matmul_precision("highest"):
            xs = self.jax.jit(ref.first_layer_kda_inputs)(
                self.state["params"], self.host_batch(step)[0, :-1]
            )
            want = self.jax.jit(
                lambda *xs: pulled(ref.kda_recurrence, xs)
            )(*xs)
        pairs = self.jax.jit(lambda xs, want: [
            (jnp.sum(jnp.square(a - b)), jnp.sum(jnp.square(b)))
            for a, b in zip(pulled(chunked, xs, want[0]), want)
        ])(xs, want)
        names = ("o", "dq", "dk", "dv", "dg", "dbeta")
        errors = relative_errors({
            k: (float(d), float(n)) for k, (d, n) in zip(names, pairs)
        })
        del errors["all"]
        return errors

    def program_peak_bytes(self):
        """The most the step program holds at once, its arguments (the
        donated state) included: the compile's own ``peak_memory``.
        ``state + temp_size`` counts every temporary as if all were
        live together and reads 17.31 GB here, more than the chip has
        (PERF.md section 5); where a backend gives no peak, that sum."""
        try:
            peak = int(self.compiled.memory_analysis().peak_memory_in_bytes)
        except Exception:  # noqa: BLE001 -- a backend without the analysis
            peak = 0
        return peak or self.state_bytes() // self.n_devices + self.temp_bytes()

    def step(self, n):
        """Step ``n``: its loss, fetched -- so the step is over. Its
        counters stay on the device until ``fetched_counters``."""
        with common.annotate("bench.batch_build"):
            batch = self.batch_at(n)
        with common.annotate("bench.step_call"):
            self.state, metrics = self.compiled(self.state, batch)
        self.counters.append([metrics[k] for k in COUNTERS])
        self.grad_norm = metrics["grad_norm"]    # on the device
        with common.annotate("bench.loss_fetch"):
            return float(metrics["loss"])

    def fetched_counters(self):
        """{counter: [its value at every step so far]}."""
        rows = self.jax.device_get(self.counters)
        return {
            k: [int(row[i]) for row in rows] for i, k in enumerate(COUNTERS)
        }


def loss_problems(loss, ref):
    if not math.isfinite(loss):
        return [f"first step's loss is {loss!r}"]
    if not math.isclose(loss, ref, rel_tol=LOSS_RTOL):
        return [
            f"first step's loss {loss!r} is not within {LOSS_RTOL} of "
            f"the float32 reference's {ref!r}"
        ]
    return []


def relative_errors(sums):
    """{name: sqrt(d / n)} of ``{name: (sum of squared differences, sum
    of squares of the reference)}``, with ``"all"``: the same over all
    names together."""
    out = {
        k: math.sqrt(d / n) if n else (0.0 if not d else math.inf)
        for k, (d, n) in sums.items()
    }
    d_all = sum(d for d, _ in sums.values())
    n_all = sum(n for _, n in sums.values())
    out["all"] = math.sqrt(d_all / n_all) if n_all else math.inf
    return out


def routed_leaves(leaves):
    """Those of the gradient leaves ``leaves`` (``keystr``s) that belong
    to the ROUTED part of an expert layer: its router and its held
    experts' own weights, not its shared expert."""
    tail = "['ffn']['router']"
    layers = {k[:-len(tail)] for k in leaves if k.endswith(tail)}
    return {
        k for k in leaves
        if k.split("['ffn']")[0] in layers and "['ffn']" in k
        and "['shared']" not in k
    }


def gradient_problems(errors):
    routed = routed_leaves(errors)
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


def scan_problems(errors):
    worst = max(errors, key=lambda k: (not math.isfinite(errors[k]),
                                       errors[k]))
    if not errors[worst] <= SCAN_RTOL:
        return [
            f"the chunked scan's {worst} is {errors[worst]!r} off the "
            f"token-by-token float32 recurrence by norm, over {SCAN_RTOL}"
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

    job = HybridJob(ctx["config"], traffic, ctx["seed"], ctx["chips"])
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
    log.emit("reference", seconds=time.time() - t0, loss=ref)
    t0 = time.time()
    scan = job.scan_errors(1)
    log.emit("scan", seconds=time.time() - t0, errors=scan)
    t0 = time.time()
    losses = [job.step(1)]
    errors = job.gradient_errors(ref_grads)
    del ref_grads
    problems = (
        loss_problems(losses[0], ref) + scan_problems(scan)
        + gradient_problems(errors)
    )
    log.emit(
        "gradient", seconds=time.time() - t0, errors=errors,
        grad_norm=float(job.grad_norm),
    )
    losses += [job.step(n) for n in range(2, traffic["warm_steps"] + 1)]
    log.emit("warm", losses=losses, reference_loss=ref)
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
            scopes = hybrid_scopes.reduce(dump)

    compiles_before = counts[common.BACKEND_COMPILE]
    first = n + 1
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
    steps = n - first + 1
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
        "hybrid_scopes": scopes,
        "window": {
            "seconds": window_s, "steps": steps,
            "tokens_per_step": job.tokens_per_step,
            "tokens_per_s": tokens_per_s,
            "micro_batch": job.micro, "seq_len": job.seq,
        },
        # Every step so far, and which of them were traced / timed.
        "counters": counters,
        "traced_steps": [traced.start, traced.stop],
        "window_steps": [first - 1, n],
        "events": common.EventLog.read(log.path),
    }
