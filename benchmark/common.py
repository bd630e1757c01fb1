"""What every runner shares: where files live, how a configuration file
becomes the program's ``TpuLMConfig``, the JAX event counters, the JSONL
event clock, and the profiler session. Nothing here imports JAX at module
import: ``run.py`` must be able to stay off the chip (one process per
chip) until a runner decides which process owns it."""

import collections
import glob
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

# TpuLMConfig field <- key of the published config.json.
_HF_KEYS = {
    "vocab_size": "vocab_size",
    "embed_dim": "hidden_size",
    "n_layers": "num_hidden_layers",
    "n_heads": "num_attention_heads",
    "n_kv_heads": "num_key_value_heads",
    "head_dim": "head_dim",
    "mlp_dim": "intermediate_size",
    "rope_theta": "rope_theta",
}


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def out_dir(workload):
    """Everything too long for the last line goes here (git-ignored)."""
    path = os.path.join(ROOT, "chiprun_out", "benchmark", workload)
    os.makedirs(path, exist_ok=True)
    return path


def lm_config(cfg_json, **overrides):
    """The program's model config for a configuration file (published
    ``config.json`` keys at its top level)."""
    from dlrover_tpu.models import llama

    if cfg_json.get("hidden_act", "silu") != "silu":
        raise ValueError("the repo's MLP is SwiGLU (silu) only")
    if cfg_json.get("tie_word_embeddings"):
        raise ValueError("the repo's head is untied")
    kw = {field: cfg_json[key] for field, key in _HF_KEYS.items()}
    kw["dtype"] = cfg_json.get("torch_dtype", "bfloat16")
    kw.update(overrides)
    return llama.TpuLMConfig(**kw)


def rng_key(seed, stream=0):
    """A JAX key from any whole-number seed (the driver's exceed 2**31)."""
    import jax

    return jax.random.fold_in(
        jax.random.key(int(seed) % (2 ** 32)), stream
    )


def count_jax_events():
    """Counter of JAX's monitoring events (compiles, cache hits and
    misses), live from this call on."""
    import jax.monitoring

    counts = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda event, **_: counts.update([event])
    )
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **__: counts.update([event])
    )
    return counts


def device_facts(devices):
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def require_tpu(devices, chips):
    """No fallback: a benchmark number comes from the chips the cell
    names or from nowhere."""
    facts = device_facts(devices)
    if facts["platform"] != "tpu" or facts["count"] < chips:
        print(
            f"benchmark: need {chips} TPU chip(s), JAX found "
            f"{facts['count']} x {facts['platform']}", file=sys.stderr,
        )
        sys.exit(3)
    return facts


def memory_peak(devices):
    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use") or 0
        for d in devices
    ]
    return max(peaks)


class EventLog:
    """Append-only JSONL of ``{"event", "t", ...}`` on the host's wall
    clock: what lets a parent and the workers it never shares a
    process with be timed against each other."""

    def __init__(self, path, **fixed):
        self.path = path
        self.fixed = fixed

    def emit(self, event, **kw):
        record = {"event": event, "t": time.time(), **self.fixed, **kw}
        with open(self.path, "a") as f:
            f.write(json.dumps(record) + "\n")
        return record

    @staticmethod
    def read(path):
        if not os.path.exists(path):
            return []
        with open(path) as f:
            return [json.loads(line) for line in f if line.strip()]


def by_event(events, name, **match):
    return [
        e for e in events if e["event"] == name
        and all(e.get(k) == v for k, v in match.items())
    ]


class Profile:
    """One profiler session over a few steps or seconds; ``stop``
    returns the event dump ``trace_reduce.reduce`` reads. Only the
    process that holds the chip can trace it."""

    def __init__(self, workdir):
        self.dir = os.path.join(workdir, "profile")
        shutil.rmtree(self.dir, ignore_errors=True)

    def start(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # our TraceAnnotations, not frames
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self, scopes=None):
        import jax

        from benchmark import trace_reduce

        jax.profiler.stop_trace()
        paths = sorted(glob.glob(
            os.path.join(self.dir, "plugins", "profile", "*", "*.xplane.pb")
        ))
        if not paths:
            return None
        dump = trace_reduce.dump_xplane(paths[-1], scopes)
        shutil.rmtree(self.dir, ignore_errors=True)
        return dump


def annotate(name, **kw):
    """A host span in the profiler's own trace (no-op outside one)."""
    import jax

    return jax.profiler.TraceAnnotation(name, **kw)
