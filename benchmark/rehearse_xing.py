"""Compile ``xing-serve-sessions-16k``'s programs at published widths for
a DESCRIBED TPU v5e (no chip needed) and print ``memory_analysis()``: the
engine's decode step and prefill chunk (``serving/kvpool/latent.py``)
over the cell's pool, built the way the engine's constructor builds them
(``kvpool.engine._paged_steps``), the checks' probe programs
(``runners/serve_latent.build_probes``, which run beside the live
engine), the program that makes the weights, and the reference's two
sublayer programs at the cell's padded length. What lives on the device
while the cell runs is weights + pool (arguments of both programs) plus
the larger program's temporaries: the number that decides 1 + 5 or 1 + 4
layers.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse_xing.py [--layers N]
        [--hlo DIR] [--only NAME,...] [--no-reference]

Run by hand before a chip call (a few minutes); not a tier-1 test
(``tests/test_tpu_compile.py`` compiles the two engine programs at the
cell's shapes with fewer layers). Nothing runs, so this says nothing
about results or times, and is never reported as a chip run.
"""

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

WORKLOAD = "xing-serve-sessions-16k"


def lower_engine_programs(cfg_json, device, probes=True, **overrides):
    """``{"jit_step": lowered, "jit_prefill": lowered, ...}`` for
    ``device``, from shapes alone, at the configuration file's engine
    sizes."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark.runners import serve_latent
    from dlrover_tpu.models import generate as gen_lib, latent_lm
    from dlrover_tpu.serving.kvpool import engine as paged, layout

    cfg = serve_latent.latent_config(cfg_json, **overrides)
    eng = cfg_json["serve_engine"]
    slots, bs, chunk = eng["slots"], eng["block_size"], eng["prefill_chunk"]
    max_blocks = eng["max_len"] // bs
    num_blocks = eng.get("num_blocks") or slots * max_blocks + 1
    here = SingleDeviceSharding(device)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=here)

    on_chip = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: arr(x.shape, x.dtype), tree
    )
    key = on_chip(jax.eval_shape(lambda: jax.random.key(0)))
    init = jax.jit(
        lambda k: latent_lm.init_params(cfg, k, dtype=cfg.compute_dtype)
    )
    params = on_chip(jax.eval_shape(
        lambda k: gen_lib.prepare_decode_params(cfg, init(k)), key
    ))
    steps = paged._paged_steps(cfg, slots, num_blocks, max_blocks, bs, chunk)
    assert steps.pool_attention == "latent_absorbed"
    (a,) = layout.pool_arrays(cfg)
    pool = on_chip(jax.eval_shape(
        lambda: layout.fresh(a, cfg.n_layers, num_blocks, bs)
    ))
    i32, f32 = jnp.int32, jnp.float32
    out = {
        "jit_step": steps.decode.lower(
            pool, params, arr((slots, max_blocks), i32),
            arr((slots,), i32), arr((slots,), i32), arr((slots,), bool),
            arr((slots,), f32), key, arr((), i32), arr((), i32),
            arr((), i32),
        ),
        "jit_prefill": steps.prefill.lower(
            pool, params, arr((1, chunk), i32),
            arr((max_blocks,), i32), arr((), i32), arr((), i32),
            arr((), f32), key, arr((), i32), arr((), bool),
        ),
        "init": init.lower(key),
    }
    if probes:
        probe_chunk, probe_decode0, _ = serve_latent.build_probes(cfg, bs)
        out["probe_chunk"] = probe_chunk.lower(
            pool, params, arr((max_blocks,), i32), arr((), i32),
            arr((1, chunk), i32),
            arr((min(serve_latent.CHUNK_ROWS, chunk),), i32),
        )
        out["probe_decode0"] = probe_decode0.lower(
            pool, params, arr((slots, max_blocks), i32),
            arr((slots,), i32), arr((slots,), i32),
        )
    return out


def lower_reference_sublayers(cfg_json, device):
    """The reference's attention and expert-layer programs over a whole
    padded sequence."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark import reference_xing
    from benchmark.runners import serve_latent
    from dlrover_tpu.models import latent_lm

    cfg = serve_latent.latent_config(cfg_json)
    here = SingleDeviceSharding(device)
    sh = reference_xing.shape_of(cfg_json)
    p, pf = jax.eval_shape(
        lambda k: reference_xing.layer_weights(
            latent_lm.init_params(cfg, k, dtype=cfg.compute_dtype),
            sh["layers"] - 1, sh,
        ),
        jax.random.key(0),
    )
    on_chip = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=here), tree
    )
    t = -(-cfg_json["serve_engine"]["max_len"] // 1024) * 1024
    streams = jax.ShapeDtypeStruct(
        (t, cfg.hc_mult, cfg.embed_dim), jnp.float32, sharding=here
    )
    rows = jax.ShapeDtypeStruct((160,), jnp.int32, sharding=here)
    frozen = reference_xing._frozen(sh)
    return {
        "reference_attention": reference_xing._attention_sublayer.lower(
            on_chip(p), streams, rows, frozen
        ),
        "reference_experts": reference_xing._mlp_sublayer.lower(
            on_chip(p), on_chip(reference_xing._arrays(pf)), streams, frozen,
            (sh["layers"] - 1 - sh["first_dense"]) * sh["experts"],
        ),
    }


def main(argv):
    import jax
    from jax.experimental import topologies

    from benchmark import common, run as bench_run

    def opt(flag, cast=str):
        return cast(argv[argv.index(flag) + 1]) if flag in argv else None

    layers, hlo_dir = opt("--layers", int), opt("--hlo")
    jax.config.update("jax_enable_compilation_cache", False)
    # Code keyed on the backend (the grouped matmul: kernel or
    # interpreter) must take its TPU branch: this process sees a CPU.
    jax.default_backend = lambda: "tpu"
    device = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    ).devices[0]
    ctx = bench_run.cell_context(
        common.load_manifest(), WORKLOAD, 0, 30, 0, require_tpu=False
    )
    over = {"n_layers": layers} if layers else {}
    programs = lower_engine_programs(ctx["config"], device, **over)
    if "--no-reference" not in argv:
        programs.update(lower_reference_sublayers(ctx["config"], device))
    only = opt("--only")
    for name, lowered in programs.items():
        if only and name not in only.split(","):
            continue
        t0 = time.time()
        compiled = lowered.compile()
        m = compiled.memory_analysis()
        print(
            f"{name}: compiled in {time.time() - t0:.0f} s; arguments "
            f"{m.argument_size_in_bytes / 1e9:.2f} GB, outputs "
            f"{m.output_size_in_bytes / 1e9:.2f} GB (aliased "
            f"{m.alias_size_in_bytes / 1e9:.2f}), temporaries "
            f"{m.temp_size_in_bytes / 1e9:.2f} GB, peak "
            f"{getattr(m, 'peak_memory_in_bytes', 0) / 1e9:.2f} GB",
            flush=True,
        )
        if hlo_dir:
            os.makedirs(hlo_dir, exist_ok=True)
            with open(os.path.join(hlo_dir, name + ".hlo.txt"), "w") as f:
                f.write(compiled.as_text())
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
