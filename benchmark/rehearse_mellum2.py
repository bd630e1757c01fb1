"""Compile ``mellum2-serve-mixed-16k``'s programs at published widths for
a DESCRIBED TPU v5e (no chip needed) and print ``memory_analysis()``: the
engine's decode step and prefill chunk (``serving/kvpool/window.py``)
over BOTH groups' pools, built the way the engine's constructor builds
them (``kvpool.engine._grouped_steps``), the checks' probe programs
(``runners/serve_window.build_probes``, which run beside the live engine)
and the program that makes the weights. What lives on the device while
the cell runs is weights + both groups' pools (arguments of both
programs) plus the larger program's temporaries. It also says whether
the pools COMPILE to their logical bytes and alias in and out: a pool
the compiler re-lays (a ``copy(`` of a pool-sized array in the HLO:
``--hlo DIR``, then grep ``bf16[2,9216,`` / ``bf16[6,1024,``) would read
twice its size here.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse_mellum2.py [--hlo DIR]
        [--only NAME,...]

Run by hand before a chip call (a few minutes); not a tier-1 test
(``tests/test_tpu_compile.py`` compiles the two engine programs at the
cell's shapes with one period of layers). Nothing runs, so this says
nothing about results or times, and is never reported as a chip run.
"""

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

WORKLOAD = "mellum2-serve-mixed-16k"


def lower_engine_programs(cfg_json, device, probes=True, **overrides):
    """``{"jit_step": lowered, "jit_prefill": lowered, ...}`` for
    ``device``, from shapes alone, at the configuration file's engine
    sizes; and the logical bytes of the pools' arrays."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from benchmark.runners import serve_window
    from dlrover_tpu.models import generate as gen_lib, window_lm
    from dlrover_tpu.serving.kvpool import engine as paged, layout

    cfg = serve_window.window_config(cfg_json, **overrides)
    eng = cfg_json["serve_engine"]
    slots, bs, chunk = eng["slots"], eng["block_size"], eng["prefill_chunk"]
    max_blocks = eng["max_len"] // bs
    blocks = [eng["num_blocks"], eng["window_blocks"]]
    here = SingleDeviceSharding(device)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=here)

    on_chip = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: arr(x.shape, x.dtype), tree
    )
    key = on_chip(jax.eval_shape(lambda: jax.random.key(0)))
    init = jax.jit(
        lambda k: window_lm.init_params(cfg, k, dtype=cfg.compute_dtype)
    )
    params = on_chip(jax.eval_shape(
        lambda k: gen_lib.prepare_decode_params(cfg, init(k)), key
    ))
    steps = paged._grouped_steps(
        cfg, slots, max_blocks, bs, chunk, tuple(blocks[1:])
    )
    assert steps.window_decode_attention == "pool_kernel", steps
    assert steps.window_chunk_attention == "pool_kernel", steps
    groups = layout.cache_groups(cfg)
    arrays = layout.grouped_pool_arrays(cfg)
    pools = tuple(
        arr((groups[a.group].layers, blocks[a.group], bs) + a.row_shape,
            a.dtype)
        for a in arrays
    )
    logical = {
        a.name: int(np.prod(p.shape)) * jnp.dtype(p.dtype).itemsize
        for a, p in zip(arrays, pools)
    }
    i32, f32 = jnp.int32, jnp.float32
    n_groups = len(groups)
    out = {
        "jit_step": steps.decode.lower(
            *pools, params, arr((n_groups, slots, max_blocks), i32),
            arr((slots,), i32), arr((slots,), i32), arr((slots,), bool),
            arr((slots,), f32), key, arr((), i32), arr((), i32),
            arr((), i32),
        ),
        "jit_prefill": steps.prefill.lower(
            *pools, params, arr((1, chunk), i32),
            arr((n_groups, max_blocks), i32), arr((), i32), arr((), i32),
            arr((), f32), key, arr((), i32), arr((), bool),
        ),
        "init": init.lower(key),
    }
    if probes:
        decode, landed = serve_window.build_probes(cfg, bs, "pool_kernel")
        out["probe_decode"] = decode.lower(
            *pools, params, arr((n_groups, slots, max_blocks), i32),
            arr((slots,), i32), arr((slots,), i32),
        )
        out["probe_landed"] = landed.lower(
            *pools, arr((n_groups, max_blocks), i32)
        )
    return out, logical


def main(argv):
    import jax
    from jax.experimental import topologies

    from benchmark import common, run as bench_run

    def opt(flag, cast=str):
        return cast(argv[argv.index(flag) + 1]) if flag in argv else None

    hlo_dir = opt("--hlo")
    jax.config.update("jax_enable_compilation_cache", False)
    # Code keyed on the backend (the grouped matmul, the attention
    # kernels) must take its TPU branch: this process sees a CPU.
    jax.default_backend = lambda: "tpu"
    device = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    ).devices[0]
    ctx = bench_run.cell_context(
        common.load_manifest(), WORKLOAD, 0, 30, 0, require_tpu=False
    )
    programs, logical = lower_engine_programs(ctx["config"], device)
    print("logical bytes:", {k: f"{v / 1e9:.3f} GB" for k, v in
                             logical.items()}, flush=True)
    only = opt("--only")
    for name, lowered in programs.items():
        if only and name not in only.split(","):
            continue
        t0 = time.time()
        compiled = lowered.compile()
        m = compiled.memory_analysis()
        print(
            f"{name}: compiled in {time.time() - t0:.0f} s; arguments "
            f"{m.argument_size_in_bytes / 1e9:.3f} GB, outputs "
            f"{m.output_size_in_bytes / 1e9:.3f} GB (aliased "
            f"{m.alias_size_in_bytes / 1e9:.3f}), temporaries "
            f"{m.temp_size_in_bytes / 1e9:.3f} GB, peak "
            f"{getattr(m, 'peak_memory_in_bytes', 0) / 1e9:.3f} GB",
            flush=True,
        )
        if hlo_dir:
            os.makedirs(hlo_dir, exist_ok=True)
            with open(os.path.join(hlo_dir, name + ".hlo.txt"), "w") as f:
                f.write(compiled.as_text())
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
