"""Compile each cell's device programs at published widths for a
DESCRIBED TPU v5e (no chip needed) and print ``memory_analysis()``:
what the chip's compiler would refuse, it refuses here, at no chip time.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py [workload ...]

Run by hand before a chip call; not a tier-1 test (a step at these
widths compiles for tens of seconds). Nothing runs, so this says nothing
about results or times, and is never reported as a chip run.
"""

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def _report(label, compiled, t0):
    mem = compiled.memory_analysis()
    gb = lambda n: round(n / 1e9, 3)  # noqa: E731
    print(json.dumps({
        "program": label,
        "compile_s": round(time.time() - t0, 1),
        "argument_gb": gb(mem.argument_size_in_bytes),
        "output_gb": gb(mem.output_size_in_bytes),
        "alias_gb": gb(mem.alias_size_in_bytes),
        "temp_gb": gb(mem.temp_size_in_bytes),
        "n_tpu_custom_calls": compiled.as_text().count("tpu_custom_call"),
    }), flush=True)


def train_programs(cfg_json, traffic, device):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from benchmark import common
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.trainer import train_step as ts

    cfg = common.lm_config(cfg_json)
    train = cfg_json["train"]
    mesh = build_mesh(MeshConfig(dp=1), [device])
    tc = ts.TrainConfig(
        warmup_steps=train["warmup_steps"], grad_accum=train["grad_accum"]
    )
    opt = ts.make_optimizer(tc)
    step_fn, specs = ts.make_train_step(
        cfg, tc, opt, mesh, donate=train["donate_state"]
    )
    shardings = ts.state_shardings(specs, mesh)

    def init(key):
        from dlrover_tpu.models import llama

        params = llama.init_params(cfg, key)[0]
        return {
            "params": params, "opt_state": opt.init(params),
            "step": jnp.zeros((), jnp.int32),
        }

    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype)
    state = jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        jax.eval_shape(init, key), shardings,
    )
    micro = train["micro_batch"] * train["grad_accum"]
    tokens = jax.ShapeDtypeStruct(
        (micro, traffic["seq_len"] + 1), jnp.int32,
        sharding=NamedSharding(mesh, ts.batch_spec()),
    )
    t0 = time.time()
    with mesh:
        _report(
            f"train step micro {micro} x {traffic['seq_len']}",
            step_fn.jitted.lower(state, {"tokens": tokens}).compile(), t0,
        )


def serve_programs(cfg_json, device):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark import common
    from benchmark.runners import serve as serve_runner
    from dlrover_tpu.models import generate as gen_lib
    from dlrover_tpu.serving.kvpool import engine as paged

    cfg = common.lm_config(cfg_json)
    eng = cfg_json["serve_engine"]
    one = SingleDeviceSharding(device)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
            tree,
        )

    key = on_chip(jax.eval_shape(lambda: jax.random.key(0)))
    t0 = time.time()
    init = jax.jit(lambda k: serve_runner.init_params(cfg, k))
    _report("serve weights from seed (bf16)", init.lower(key).compile(), t0)
    raw = jax.eval_shape(lambda k: serve_runner.init_params(cfg, k), key)
    params = on_chip(jax.eval_shape(
        lambda p: gen_lib.prepare_decode_params(cfg, p), raw
    ))
    max_blocks = eng["max_len"] // eng["block_size"]
    num_blocks = eng["slots"] * max_blocks + 1
    steps = paged._paged_steps(
        cfg, eng["slots"], num_blocks, max_blocks, eng["block_size"],
        eng["prefill_chunk"],
    )
    pool = jax.ShapeDtypeStruct(
        (cfg.n_layers, num_blocks, eng["block_size"], cfg.n_kv_heads,
         cfg.head_dim), cfg.compute_dtype, sharding=one,
    )

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    i32, slots = jnp.int32, eng["slots"]
    t0 = time.time()
    _report("paged decode", steps.decode.lower(
        pool, pool, params, arr((slots, max_blocks), i32),
        arr((slots,), i32), arr((slots,), i32), arr((slots,), bool),
        arr((slots,), jnp.float32), key, arr((), i32),
    ).compile(), t0)
    t0 = time.time()
    _report("paged prefill chunk", steps.prefill.lower(
        pool, pool, params, arr((1, eng["prefill_chunk"]), i32),
        arr((max_blocks,), i32), arr((), i32), arr((), i32),
        arr((), jnp.float32), key, arr((), i32),
    ).compile(), t0)


def main(argv):
    import jax
    from jax.experimental import topologies

    from benchmark import common
    from dlrover_tpu.models import llama

    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    )
    # Take the program's TPU branches (flash kernel, compiled Pallas).
    jax.default_backend = lambda: "tpu"
    llama._ATTN_CACHE.clear()
    jax.config.update("jax_enable_compilation_cache", False)
    manifest = common.load_manifest()
    wanted = argv or [w["name"] for w in manifest["workloads"]]
    for cell in manifest["workloads"]:
        if cell["name"] not in wanted:
            continue
        config = next(
            c for c in manifest["configs"] if c["name"] == cell["config"]
        )
        with open(os.path.join(common.ROOT, config["file"])) as f:
            cfg_json = json.load(f)
        traffic = common.load_json("traffic", cell["traffic"] + ".json")
        print(f"== {cell['name']}", flush=True)
        if traffic["runner"] in ("train", "elastic_train"):
            train_programs(cfg_json, traffic, topo.devices[0])
        else:
            serve_programs(cfg_json, topo.devices[0])


if __name__ == "__main__":
    main(sys.argv[1:])
