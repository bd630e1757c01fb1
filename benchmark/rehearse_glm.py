"""Compile ``glm47flash-train-8k``'s train step at published widths for a
DESCRIBED TPU v5e (no chip needed) and print ``memory_analysis()`` with
the compile's own peak: ``benchmark/rehearse_kimi_linear.py`` for the
layer-pattern model with the rotary latent mixer and the prediction
module. Then the same for the largest programs its ``correct`` runs
beside the train state: the reference's pullback of a block of each
kind and its head.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse_glm.py
        [--remat dots|attention] [--hlo PATH] [--no-reference]

``--remat``: what a block keeps for its backward, in place of the
configuration file's ``train.remat_keep`` (the reading that chose it is
in PERF.md section 4). Run by hand before a chip call (a few minutes);
not a tier-1 test (``tests/test_tpu_compile.py`` compiles the step at
the cell's shape with fewer layers). Nothing runs, so this says nothing
about results or times, and is never reported as a chip run.
"""

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

WORKLOAD = "glm47flash-train-8k"


def abstract_state(cfg, opt, specs, mesh):
    """The train state's shapes with their shardings on ``mesh``."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import hybrid
    from dlrover_tpu.trainer import train_step as ts

    def init(key):
        params = hybrid.init_params(cfg, key)[0]
        return {
            "params": params, "opt_state": opt.init(params),
            "step": jnp.zeros((), jnp.int32),
            "buffers": hybrid.init_buffers(cfg, key),
        }

    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype)
    return jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        jax.eval_shape(init, key), ts.state_shardings(specs, mesh),
    )


def lower_step(cfg_json, traffic, device, **overrides):
    """The cell's jitted step, lowered for ``device`` from shapes."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from benchmark.runners import train_latent
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.trainer import train_step as ts

    cfg = train_latent.latent_config(cfg_json, **overrides)
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
    micro = knobs["micro_batch"] * knobs["grad_accum"]
    tokens = jax.ShapeDtypeStruct(
        (micro, traffic["seq_len"] + 1 + cfg.mtp_depth), jnp.int32,
        sharding=NamedSharding(mesh, ts.batch_spec()),
    )
    with mesh:
        return step_fn.jitted.lower(
            abstract_state(cfg, opt, specs, mesh), {"tokens": tokens}
        )


def lower_reference_programs(cfg_json, traffic, device):
    """The reference's pullback of the dense block and of an expert
    block, and its head (the largest programs ``correct`` runs on the
    chip BESIDE the train state), lowered for ``device`` from shapes:
    ``[(label, lowered), ...]``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark import reference_glm
    from benchmark.runners import train_latent
    from dlrover_tpu.models import hybrid

    cfg = train_latent.latent_config(cfg_json)
    first, _ = cfg.experts_held
    spec = {"top_k": cfg.moe_top_k, "first_expert": first,
            "routed_scaling": cfg.routed_scaling,
            "rope_theta": cfg.rope_theta, "mtp_weight": cfg.mtp_weight}
    here = SingleDeviceSharding(device)
    shaped = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=here),
        tree,
    )
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype)
    params = shaped(jax.eval_shape(
        lambda k: hybrid.init_params(cfg, k)[0], key
    ))
    buffers = shaped(jax.eval_shape(
        lambda k: hybrid.init_buffers(cfg, k), key
    ))
    s = traffic["seq_len"]
    x = jax.ShapeDtypeStruct((s, cfg.embed_dim), jnp.float32, sharding=here)
    ids = jax.ShapeDtypeStruct((s,), jnp.int32, sharding=here)
    with jax.default_matmul_precision("highest"):
        fn = reference_glm.programs(spec)
        block = params["mtp"]["block"], buffers["mtp"]["block"]
        return [
            ("reference pullback of the dense block", fn["backward"].lower(
                params["leading"][0], buffers["leading"][0], x, x
            )),
            ("reference pullback of an expert block",
             fn["backward"].lower(*block, x, x)),
            ("reference head", fn["head"].lower(
                params["final_norm"], params["lm_head"], x, ids, 1.0
            )),
        ]


def main(argv):
    import jax
    from jax.experimental import topologies

    from benchmark import common, rehearse, run as bench_run
    from dlrover_tpu.models import llama

    def opt(flag):
        return argv[argv.index(flag) + 1] if flag in argv else None

    device = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    ).devices[0]
    # Take the program's TPU branches (flash kernel, compiled Pallas).
    jax.default_backend = lambda: "tpu"
    llama._ATTN_CACHE.clear()
    jax.config.update("jax_enable_compilation_cache", False)
    ctx = bench_run.cell_context(
        common.load_manifest(), WORKLOAD, 0, 30, 0, require_tpu=False
    )
    cfg_json, traffic = ctx["config"], ctx["traffic"]
    over = {"remat_keep": opt("--remat")} if opt("--remat") else {}
    print(f"== {WORKLOAD} {over}", flush=True)
    t0 = time.time()
    compiled = lower_step(cfg_json, traffic, device, **over).compile()
    rehearse._report(f"train step 1 x {traffic['seq_len']} + 2", compiled, t0)
    peak = getattr(compiled.memory_analysis(), "peak_memory_in_bytes", 0)
    print(f"peak_memory_gb {peak / 1e9:.3f}", flush=True)
    if opt("--hlo"):
        with open(opt("--hlo"), "w") as f:
            f.write(compiled.as_text())
    if "--no-reference" in argv:
        return
    for label, lowered in lower_reference_programs(
        cfg_json, traffic, device
    ):
        t0 = time.time()
        rehearse._report(
            f"{label}, beside 8.5 GB of state", lowered.compile(), t0
        )


if __name__ == "__main__":
    main(sys.argv[1:])
