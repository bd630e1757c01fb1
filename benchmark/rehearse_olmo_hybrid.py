"""Compile ``olmohybrid-serve-grow-6k``'s programs at published widths for
a DESCRIBED TPU v5e (no chip needed) and print ``memory_analysis()``: the
engine's decode step and prefill chunk (``serving/kvpool/delta.py``) over
the cell's K/V pool, both state arrays and their snapshots, built the way
the engine's constructor builds them (``kvpool.engine._delta_steps``),
the checks' probe programs (``runners/serve_delta.build_probes``, which
run beside the live engine), the program that makes the weights, and the
reference's block of rows. What lives on the device while the cell runs
is weights + pool + state + snapshots (arguments of both programs) plus
the larger program's temporaries; all six arrays must alias in and out,
and no pool-sized or state-sized array may be re-laid (grep the HLO for a
``copy(`` of ``bf16[2,2560,`` or ``f32[6,32,30,96,192]`` / ``f32[6,161,``).
The arguments' bytes AS HELD exceed the logical ones printed first: a
``[96, 192]`` float32 tile pads its lanes to 256 and a ``[3, 11520]``
bfloat16 one its sublanes to 16.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse_olmo_hybrid.py [--hlo DIR]
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

WORKLOAD = "olmohybrid-serve-grow-6k"


def lower_engine_programs(cfg_json, device, probes=True, reference=True,
                          layers=None):
    """``{"jit_step": lowered, "jit_prefill": lowered, ...}`` for
    ``device``, from shapes alone, at the configuration file's engine
    sizes (``layers``: only the first so many); and the logical bytes of
    the engine's arrays."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from benchmark import reference_olmo_hybrid
    from benchmark.runners import serve_delta
    from dlrover_tpu.models import delta_lm, generate as gen_lib
    from dlrover_tpu.serving.kvpool import engine as paged, layout

    if layers:
        cfg_json = dict(
            cfg_json, num_hidden_layers=layers,
            layer_types=cfg_json["layer_types"][:layers],
        )
    cfg = serve_delta.delta_config(cfg_json)
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
        lambda k: delta_lm.init_params(cfg, k, dtype=cfg.compute_dtype)
    )
    params = on_chip(jax.eval_shape(
        lambda k: gen_lib.prepare_decode_params(cfg, init(k)), key
    ))
    steps = paged._delta_steps(cfg, slots, max_blocks, bs, chunk)
    pool_layers = layout.pool_layers(cfg)
    arrays = layout.pool_arrays(cfg)
    pools = tuple(
        arr((pool_layers, num_blocks, a.block_rows(bs)) + a.row_shape,
            a.dtype)
        for a in arrays
    )
    n_snap = eng["state_snapshots"] + 1            # sentinel
    states = layout.state_arrays(cfg)
    state = tuple(arr((a.layers, slots) + a.shape, a.dtype) for a in states)
    snaps = tuple(arr((a.layers, n_snap) + a.shape, a.dtype) for a in states)
    lead = (*pools, *state, *snaps)
    names = (
        [a.name for a in arrays] + [a.name for a in states]
        + [a.name + "_snapshots" for a in states]
    )
    logical = {
        name: int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
        for name, a in zip(names, lead)
    }
    i32, f32 = jnp.int32, jnp.float32
    out = {
        "jit_step": steps.decode.lower(
            *lead, params, arr((slots, max_blocks), i32),
            arr((slots,), i32), arr((slots,), i32), arr((slots,), bool),
            arr((slots,), f32), key, arr((), i32), arr((), i32),
            arr((), i32),
        ),
        "jit_prefill": steps.prefill.lower(
            *lead, params, arr((1, chunk), i32),
            arr((max_blocks,), i32), arr((), i32), arr((), i32),
            arr((), f32), key, arr((), i32), arr((), bool),
            arr((), i32), arr((), i32), arr((), i32),
        ),
        "init": init.lower(key),
    }
    one = tuple(arr((a.layers,) + a.shape, a.dtype) for a in states)
    if probes:
        probe_chunk, probe_decode, landed = serve_delta.build_probes(
            cfg, bs, dict(steps.linear_kinds)
        )
        out["probe_chunk"] = probe_chunk.lower(
            *pools, *one, params, arr((max_blocks,), i32), arr((), i32),
            arr((1, chunk), i32), arr((), i32),
        )
        out["probe_decode"] = probe_decode.lower(
            *pools, *state, params, arr((slots, max_blocks), i32),
            arr((slots,), i32), arr((slots,), i32),
        )
        out["probe_landed"] = landed.lower(*pools, arr((max_blocks,), i32))
    if reference:
        sh = reference_olmo_hybrid.shape_of(cfg_json)
        carry = on_chip(jax.eval_shape(
            lambda: reference_olmo_hybrid.new_carry(sh, eng["max_len"])
        ))
        raw = on_chip(jax.eval_shape(init, key))
        program = reference_olmo_hybrid._program(
            tuple(sorted(sh.items())), False, ()
        )
        out["reference_block"] = program.lower(
            raw, carry, arr((serve_delta.BLOCK_ROWS,), i32), arr((), i32),
            arr((2,), i32),
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
    # Code keyed on the backend must take its TPU branch: this process
    # sees a CPU.
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
