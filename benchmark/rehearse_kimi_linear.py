"""Compile ``kimilinear-train-8k``'s train step at published widths for a
DESCRIBED TPU v5e (no chip needed) and print ``memory_analysis()``:
``benchmark/rehearse.py`` for the layer-pattern model, which that file
cannot build (it maps every configuration through ``common.lm_config``).
Then the same for the largest programs its ``correct`` runs beside the
train state: the reference's pullback of a layer of each kind.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse_kimi_linear.py [--hlo PATH]

Run by hand before a chip call (about five minutes in all);
not a tier-1 test. Nothing runs, so this says nothing about results or
times, and is never reported as a chip run.
"""

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

WORKLOAD = "kimilinear-train-8k"


def lower_step(cfg_json, traffic, device, **overrides):
    """The cell's jitted step, lowered for ``device`` from shapes."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from benchmark.runners import train_hybrid
    from dlrover_tpu.models import hybrid
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.trainer import train_step as ts

    cfg = train_hybrid.hybrid_config(cfg_json, **overrides)
    knobs = cfg_json["train"]
    mesh = build_mesh(MeshConfig(dp=1), [device])
    tc = ts.TrainConfig(
        warmup_steps=knobs["warmup_steps"], grad_accum=knobs["grad_accum"],
        learning_rate=knobs["learning_rate"],
    )
    opt = ts.make_optimizer(tc)
    step_fn, specs = ts.make_train_step(
        cfg, tc, opt, mesh, donate=knobs["donate_state"]
    )

    def init(key):
        params = hybrid.init_params(cfg, key)[0]
        return {
            "params": params, "opt_state": opt.init(params),
            "step": jnp.zeros((), jnp.int32),
            "buffers": hybrid.init_buffers(cfg, key),
        }

    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype)
    state = jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        jax.eval_shape(init, key), ts.state_shardings(specs, mesh),
    )
    micro = knobs["micro_batch"] * knobs["grad_accum"]
    tokens = jax.ShapeDtypeStruct(
        (micro, traffic["seq_len"] + 1), jnp.int32,
        sharding=NamedSharding(mesh, ts.batch_spec()),
    )
    with mesh:
        return step_fn.jitted.lower(state, {"tokens": tokens})


def lower_reference_layers(cfg_json, traffic, device):
    """The reference's pullback of one layer of each kind (the largest
    programs ``correct`` runs on the chip BESIDE the train state), lowered
    for ``device`` from shapes: ``[(kinds, lowered), ...]``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark import reference_kimi_linear
    from benchmark.runners import train_hybrid
    from dlrover_tpu.models import hybrid

    cfg = train_hybrid.hybrid_config(cfg_json)
    first, _ = cfg.experts_held
    spec = {"top_k": cfg.moe_top_k, "first_expert": first,
            "routed_scaling": cfg.routed_scaling}
    here = SingleDeviceSharding(device)
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype)
    shaped = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=here),
        tree,
    )
    params = shaped(jax.eval_shape(
        lambda k: hybrid.init_params(cfg, k)[0], key
    ))
    buffers = shaped(jax.eval_shape(
        lambda k: hybrid.init_buffers(cfg, k), key
    ))
    x = jax.ShapeDtypeStruct(
        (traffic["seq_len"], cfg.embed_dim), jnp.float32, sharding=here
    )
    one = lambda tree: shaped(jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), tree
    ))
    layers = list(zip(cfg.leading, params["leading"], buffers["leading"]))
    layers += [
        (kinds, one(p), one(b)) for kinds, p, b in
        zip(cfg.period, params["period"], buffers["period"])
    ]
    out, seen = [], set()
    with jax.default_matmul_precision("highest"):
        _, backward = reference_kimi_linear.layer_programs(spec)
        for kinds, p, b in layers:
            if kinds not in seen:
                seen.add(kinds)
                out.append((kinds, backward.lower(p, b, x, x)))
    return out


def main(argv):
    import jax
    from jax.experimental import topologies

    from benchmark import common, rehearse
    from dlrover_tpu.models import llama

    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    )
    # Take the program's TPU branches (flash kernel, compiled Pallas).
    jax.default_backend = lambda: "tpu"
    llama._ATTN_CACHE.clear()
    jax.config.update("jax_enable_compilation_cache", False)
    manifest = common.load_manifest()
    cell = next(w for w in manifest["workloads"] if w["name"] == WORKLOAD)
    config = next(
        c for c in manifest["configs"] if c["name"] == cell["config"]
    )
    cfg_json = common.load_json(
        os.path.relpath(os.path.join(common.ROOT, config["file"]), HERE)
    )
    traffic = common.load_json("traffic", cell["traffic"] + ".json")
    print(f"== {WORKLOAD}", flush=True)
    t0 = time.time()
    compiled = lower_step(cfg_json, traffic, topo.devices[0]).compile()
    rehearse._report(f"train step 1 x {traffic['seq_len']}", compiled, t0)
    if argv[:1] == ["--hlo"]:
        with open(argv[1], "w") as f:
            f.write(compiled.as_text())
    for kinds, lowered in lower_reference_layers(
        cfg_json, traffic, topo.devices[0]
    ):
        t0 = time.time()
        rehearse._report(
            f"reference pullback of a {kinds} layer, beside 7.2 GB of "
            f"state", lowered.compile(), t0,
        )


if __name__ == "__main__":
    main(sys.argv[1:])
