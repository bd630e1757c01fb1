"""Runner ``serve``: a closed loop of clients through ``FleetRouter`` ->
one ``ThreadReplica`` -> ``PagedServingEngine``, a copy of
``chip_smoke.py``'s ``serve_worker`` with a traffic generator and a
timed window. The generator's length handling follows
``tools/bench_serving.py``'s seeded workload (copied, not imported; the
Poisson schedule it also has is for the open-loop cell that comes
later).

Steadiness: the traffic file fixes the SCHEDULE of (prompt, answer)
lengths — a set drawn once from ``length_set_seed``, served epoch after
epoch, each epoch in an order of that same seed. ``--seed`` changes the
tokens and the weights, never the lengths or their order: with a queue
always standing, admissions follow submissions and submissions follow
completions, so two runs replay one schedule and differ by timing jitter
alone. (An order drawn from ``--seed`` was tried first: which requests a
20 s window happens to complete then moves tokens/s by several per cent
between seeds — PERF.md.) The loop runs ``ramp_s`` seconds before the
window opens (set-up: the engine fills, the first wave of prefills
passes), so the window sees a standing queue from its first moment.
"""

import gc
import math
import time

import numpy as np

from benchmark import common, reference

# How far below the float32 reference's best logit an emitted token's
# logit may sit. The engine hands out tokens, never logits, so the
# comparison is made where the two meet: the engine's token is the
# argmax of ITS logits, and if those lie within eps of the reference's,
# the reference's logit of that token lies within 2 eps of its maximum.
# Set below the reference's median top-2 gap at this width and
# vocabulary, so that a runner-up fails at most positions
# (``n_runner_up_would_fail`` in the result file); values from the first
# chip run are in PERF.md.
SERVE_LOGIT_TOL = 0.08


def init_params(cfg, key):
    """The repo's ``llama.init_params`` with the cast to the serving
    dtype inside the same jitted call (norm scales stay f32, as
    ``prepare_decode_params`` keeps them): at 12 B the f32 tree never
    exists whole."""
    from dlrover_tpu.models import llama

    params, _ = llama.init_params(cfg, key)
    cdt = cfg.compute_dtype
    keep = {"attn_norm", "mlp_norm"}
    return {
        "embed": params["embed"].astype(cdt),
        "layers": {
            k: (v if k in keep else v.astype(cdt))
            for k, v in params["layers"].items()
        },
        "final_norm": params["final_norm"],
        "lm_head": params["lm_head"].astype(cdt),
    }


def _draw(spec, n, rng):
    if spec["dist"] != "log_uniform":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    lo, hi = math.log(spec["min"]), math.log(spec["max"])
    return np.clip(
        np.round(np.exp(rng.uniform(lo, hi, n))).astype(int),
        spec["min"], spec["max"],
    )


def length_set(traffic):
    """The mix's fixed set of (prompt_len, output_len), the same for
    every seed."""
    rng = np.random.default_rng(traffic["length_set_seed"])
    n = traffic["length_set_size"]
    return list(zip(
        _draw(traffic["prompt_len"], n, rng).tolist(),
        _draw(traffic["output_len"], n, rng).tolist(),
    ))


def request_stream(traffic, vocab, seed):
    """Endless (prompt tokens, max_new_tokens): the length set, epoch
    after epoch, in the traffic file's own order, with tokens of the
    seed."""
    lengths = length_set(traffic)
    prefix = traffic.get("shared_prefix")
    heads = None
    if prefix:
        heads = np.random.default_rng((seed, 2 ** 20)).integers(
            0, vocab, (prefix["count"], prefix["len"])
        )
    epoch = 0
    while True:
        rng = np.random.default_rng((seed, epoch))
        order = np.random.default_rng(
            (traffic["length_set_seed"], epoch)
        ).permutation(len(lengths))
        for i in order:
            n_prompt, n_new = lengths[i]
            tokens = rng.integers(0, vocab, n_prompt)
            if prefix and rng.random() < prefix["share"]:
                head = heads[rng.integers(prefix["count"])][:n_prompt]
                tokens[:len(head)] = head
            yield tokens.tolist(), int(n_new)
        epoch += 1


def _annotated(fn, name):
    def wrapped(*a, **kw):
        with common.annotate(name):
            return fn(*a, **kw)
    return wrapped


def reference_check(params, cfg, sample, max_len, out_max):
    """For each (prompt, emitted) of the sample: how far each emitted
    token's float32-reference logit sits below that position's maximum
    (position len(prompt)-1+i predicted emitted token i), and the
    reference's own top-2 gaps."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(reference.logits_at, static_argnums=3)
    deficits, gaps, finite = [], [], True
    for prompt, emitted in sample:
        tokens = np.zeros(max_len, np.int32)
        seq = prompt + emitted
        tokens[:len(seq)] = seq
        positions = np.zeros(out_max, np.int32)
        positions[:len(emitted)] = (
            len(prompt) - 1 + np.arange(len(emitted))
        )
        rows = np.asarray(fn(
            params, jnp.asarray(tokens), jnp.asarray(positions),
            float(cfg.rope_theta),
        ))[:len(emitted)]
        finite = finite and bool(np.isfinite(rows).all())
        deficits.append(
            (rows.max(-1) - rows[np.arange(len(emitted)), emitted])
            .tolist()
        )
        top2 = np.partition(rows, -2, axis=-1)[:, -2:]
        gaps.extend((top2[:, 1] - top2[:, 0]).tolist())
    flat = [x for d in deficits for x in d]
    return {
        "logits_finite": finite,
        "prefill_logit_deficit": max(d[0] for d in deficits),
        "max_logit_deficit": max(flat),
        "n_positions": len(flat),
        "n_argmax_matches": sum(x == 0.0 for x in flat),
        "median_top2_gap": float(np.median(gaps)),
        "n_runner_up_would_fail": sum(g > SERVE_LOGIT_TOL for g in gaps),
    }


def run(ctx):
    import jax

    counts = common.count_jax_events()
    from dlrover_tpu.observability import tracing
    from dlrover_tpu.serving.fleet import FleetRouter, ThreadReplica
    from dlrover_tpu.serving.kvpool import PagedServingEngine

    devices = jax.devices()
    device = common.device_facts(devices)
    if ctx["require_tpu"]:
        common.require_tpu(devices, ctx["chips"])
    traffic, cfg_json = ctx["traffic"], ctx["config"]
    cfg = common.lm_config(cfg_json)
    eng = cfg_json["serve_engine"]
    log = common.EventLog(ctx["out_dir"] + "/events.jsonl")
    make_params = jax.jit(lambda key: init_params(cfg, key))
    key = common.rng_key(ctx["seed"])
    box = {"params": make_params(key)}

    def factory():
        t0 = time.time()
        engine = PagedServingEngine(
            cfg, box.pop("params"), slots=eng["slots"],
            max_len=eng["max_len"], prefill_chunk=eng["prefill_chunk"],
            block_size=eng["block_size"],
        )
        # The engine now holds its own fused copies; the unfused
        # originals behind them are released with the popped tree.
        engine.warmup()
        if ctx["trace"]:
            engine.step = _annotated(engine.step, "bench.engine_step")
            engine._run_prefill_chunk = _annotated(
                engine._run_prefill_chunk, "bench.prefill_chunk"
            )
            engine._run_decode = _annotated(
                engine._run_decode, "bench.decode"
            )
        box.update(
            engine=engine, traces=dict(engine.trace_counts),
            compiles=counts[common.BACKEND_COMPILE],
        )
        log.emit("engine_ready", seconds=time.time() - t0)
        return engine

    tracer = None
    if ctx["trace"]:
        tracer = tracing.arm(
            tracing.Tracer(service="benchmark", ring_capacity=1 << 16)
        )
    stream = request_stream(traffic, cfg.vocab_size, ctx["seed"])
    router = FleetRouter([ThreadReplica("0", factory)])
    router.start(timeout_s=900)
    if "engine" not in box:
        raise RuntimeError("the replica's engine did not come up")
    live, done = {}, []

    def submit():
        prompt, n_new = next(stream)
        req = router.submit(prompt, n_new, traffic["temperature"])
        live[req.request_id] = (req, prompt, n_new)

    def pump(until, phase):
        while time.time() < until:
            finished = router.step()
            now = time.time()
            for req in finished:
                _, prompt, n_new = live.pop(req.request_id)
                done.append({
                    "id": req.request_id, "phase": phase, "t": now,
                    "ok": bool(req.result and req.result.ok),
                    "prompt": prompt, "n_new": n_new,
                    "tokens": list(req.result.tokens) if req.result else [],
                    "truncated": bool(req.result and req.result.truncated),
                    "ttft_s": req.result.ttft_s if req.result else None,
                    "latency_s": (
                        req.result.latency_s if req.result else None
                    ),
                })
                submit()
            if not finished:
                time.sleep(0.002)
        return time.time()

    trace = dump = None
    try:
        for _ in range(traffic["clients"]):
            submit()
        pump(time.time() + traffic["ramp_s"], "ramp")
        if ctx["trace"]:
            prof = common.Profile(ctx["out_dir"])
            prof.start()
            try:
                pump(time.time() + traffic["trace_s"], "traced")
            finally:
                dump = prof.stop()
        compiles_before = counts[common.BACKEND_COMPILE]
        t_window = time.time()
        setup_s = t_window - ctx["t_start"]
        t_end = pump(t_window + ctx["seconds"], "window")
        compiles_in_window = (
            counts[common.BACKEND_COMPILE] - compiles_before
        )
    finally:
        router.stop()
        if tracer is not None:
            tracing.disarm()
    window_s = t_end - t_window
    engine = box.pop("engine")
    retraces = sum(engine.trace_counts.values()) - sum(
        box["traces"].values()
    )
    compiles = counts[common.BACKEND_COMPILE] - box["compiles"]
    kv_stats = {
        k: v for k, v in engine.kv_stats().items()
        if isinstance(v, (int, float))
    }
    peak = common.memory_peak(devices[:ctx["chips"]])
    spans = tracer.finished() if tracer is not None else []
    if dump:
        from benchmark import trace_reduce

        trace = trace_reduce.reduce(dump)
    del engine, router  # the device memory goes to the reference
    gc.collect()  # (the annotated step wrappers close a cycle)

    in_window = [d for d in done if d["phase"] == "window"]
    tokens_out = sum(len(d["tokens"]) for d in in_window)
    problems = []
    ids = [d["id"] for d in done]
    if len(set(ids)) != len(ids):
        problems.append("a request completed more than once")
    bad = [
        d["id"] for d in done
        if not d["ok"] or d["truncated"] or len(d["tokens"]) != d["n_new"]
    ]
    if bad:
        problems.append(
            f"{len(bad)} request(s) failed, were truncated or came back "
            f"short: {bad[:5]}"
        )
    if compiles or retraces:
        problems.append(
            f"{compiles} compile(s) / {retraces} retrace(s) after "
            f"warm-up ({compiles_in_window} inside the window)"
        )
    if not in_window:
        problems.append("no request completed inside the window")

    # Reference: the same weights again from the seed (bit-identical:
    # same jitted program, same key), then the plain forward over a
    # seeded sample of served sequences.
    rng = np.random.default_rng((ctx["seed"], 10 ** 6))
    pool = in_window or done
    picks = rng.permutation(len(pool))[:traffic["reference_sample"]]
    sample = [(pool[i]["prompt"], pool[i]["tokens"]) for i in picks]
    check = {}
    if sample:
        t0 = time.time()
        check = reference_check(
            make_params(key), cfg, sample, eng["max_len"],
            traffic["output_len"]["max"],
        )
        check["seconds"] = time.time() - t0
        if not check["logits_finite"]:
            problems.append("reference logits not finite")
        for name, what in (
            ("prefill_logit_deficit", "a prefill's first token"),
            ("max_logit_deficit", "an emitted token"),
        ):
            if not check[name] <= SERVE_LOGIT_TOL:
                problems.append(
                    f"{what} sits {check[name]:.3f} below the plain "
                    f"forward's maximum (tolerance {SERVE_LOGIT_TOL})"
                )
    log.emit("reference", **check)
    ttfts = sorted(
        d["ttft_s"] for d in in_window if d["ttft_s"] is not None
    )
    return {
        "problems": problems,
        "attempted": len(done),
        "failed": len(bad),
        "end_to_end": {
            "serve_tokens_per_s": tokens_out / window_s,
            "setup_s": setup_s,
        },
        "device": dict(device, memory_peak_bytes=peak),
        "trace": trace,
        "dump": dump,
        "spans": spans,
        "window": {
            "seconds": window_s, "requests": len(in_window),
            "tokens_out": tokens_out,
            "tokens_in": sum(len(d["prompt"]) for d in in_window),
            "in_flight_at_end": len(live),
        },
        "ttft_s": ttfts,
        "reference": check,
        "kv_stats": kv_stats,
        "requests": [
            {k: v for k, v in d.items() if k not in ("prompt", "tokens")}
            for d in done
        ],
        "events": common.EventLog.read(log.path),
    }
