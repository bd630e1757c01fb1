"""Compile ``keye-serve-docqa-32k``'s programs at published widths for a
DESCRIBED TPU v5e (no chip needed) and print ``memory_analysis()``: the
engine's decode step and prefill chunk (``serving/kvpool/sparse.py``)
over the cell's pool, the two probe programs of the checks
(``runners/serve_sparse.build_probes``, which run beside the live
engine), the program that makes the weights, and the reference's layer
at the cell's padded length with a probed request's rows, so that the
5-layer cut and the pool size are checked before chip time is spent. What lives on
the device while the cell runs is weights + pool (arguments of both
programs) plus the larger program's temporaries.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse_keye.py [--layers N] [--hlo DIR]

Run by hand before a chip call (a few minutes); not a tier-1 test.
Nothing runs, so this says nothing about results or times, and is never
reported as a chip run.
"""

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

WORKLOAD = "keye-serve-docqa-32k"


def lower_engine_programs(cfg_json, device, **overrides):
    """``{"jit_step": lowered, "jit_prefill": lowered, "init": lowered}``
    for ``device``, from shapes alone, at the configuration file's
    engine sizes."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark.runners import serve_sparse
    from dlrover_tpu.models import generate as gen_lib, sparse_lm
    from dlrover_tpu.serving.kvpool import engine as paged

    cfg = serve_sparse.sparse_config(cfg_json, **overrides)
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
        lambda k: sparse_lm.init_params(cfg, k, dtype=cfg.compute_dtype)
    )
    params = on_chip(jax.eval_shape(
        lambda k: gen_lib.prepare_decode_params(cfg, init(k)), key
    ))
    steps = paged._paged_steps(
        cfg, slots, num_blocks, max_blocks, bs, chunk
    )
    assert steps.pool_attention == "sparse_gather"
    cdt = cfg.compute_dtype
    kv = arr((cfg.n_layers, num_blocks, bs, cfg.n_kv_heads, cfg.head_dim), cdt)
    ki = arr((cfg.n_layers, num_blocks, bs, cfg.index_dim), cdt)
    i32, f32 = jnp.int32, jnp.float32
    probe_decode, probe_chunk = serve_sparse.build_probes(cfg, bs)
    take = cfg_json_sample(cfg_json)
    return {
        "probe_decode": probe_decode.lower(
            (kv, kv, ki), params, arr((slots, max_blocks), i32),
            arr((slots,), i32), arr((slots,), i32), arr((slots,), i32),
            arr((slots,), i32), arr((take,), i32),
        ),
        "probe_chunk": probe_chunk.lower(
            (kv, kv, ki), params, arr((max_blocks,), i32), arr((), i32),
            arr((), i32), arr((1, chunk), i32),
            arr((min(serve_sparse.CHUNK_ROWS, chunk),), i32),
            arr((), i32), arr((), i32),
        ),
        "jit_step": steps.decode.lower(
            kv, kv, ki, params, arr((slots, max_blocks), i32),
            arr((slots,), i32), arr((slots,), i32), arr((slots,), bool),
            arr((slots,), f32), key, arr((), i32), arr((), i32),
            arr((), i32),
        ),
        "jit_prefill": steps.prefill.lower(
            kv, kv, ki, params, arr((1, chunk), i32),
            arr((max_blocks,), i32), arr((), i32), arr((), i32),
            arr((), f32), key, arr((), i32), arr((), bool),
        ),
        "init": init.lower(key),
    }


def cfg_json_sample(cfg_json):
    """Requests the checks probe (the traffic file's)."""
    from benchmark import common

    return common.load_json("traffic", "docqa-closed-32k.json")[
        "reference_sample"
    ]


def lower_reference_layer(cfg_json, device):
    """The reference's one-layer program at the cell's padded length,
    with the rows of one probed request held to a program's readings."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark import common, reference_keye
    from benchmark.runners import serve_sparse
    from dlrover_tpu.models import sparse_lm

    cfg = serve_sparse.sparse_config(cfg_json)
    here = SingleDeviceSharding(device)
    layer = jax.eval_shape(
        lambda k: reference_keye.layer_of(
            sparse_lm.init_params(cfg, k, dtype=cfg.compute_dtype), 0
        ),
        jax.random.key(0),
    )
    p = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=here),
        layer,
    )
    x = jax.ShapeDtypeStruct(
        (cfg_json["serve_engine"]["max_len"], cfg.embed_dim), jnp.float32,
        sharding=here,
    )
    sh = tuple(sorted(reference_keye.shape_of(cfg_json).items()))
    traffic = common.load_json("traffic", "docqa-closed-32k.json")
    chunk = cfg_json["serve_engine"]["prefill_chunk"]
    n = min(serve_sparse.CHUNK_ROWS, chunk) + traffic["output_len"]["max"] - 1
    f32, i32 = jnp.float32, jnp.int32
    row = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        (n,) + shape, dtype, sharding=here
    )
    d, k = cfg.embed_dim, cfg.moe_top_k
    rows = {
        "pos": row((), i32), "x_in": row((d,), f32),
        "mask": row((x.shape[0],), bool),
        "attn": row((cfg.n_heads, cfg.head_dim), f32),
        "x_mid": row((d,), f32), "ids": row((k,), i32),
        "weights": row((k,), f32), "y": row((d,), f32),
    }
    return reference_keye._layer_jit.lower(
        p, x, sh, False, rows, serve_sparse.SELECT_MARGIN
    )


def main(argv):
    import jax
    from jax.experimental import topologies

    from benchmark import common, run as bench_run

    layers = int(argv[argv.index("--layers") + 1]) if "--layers" in argv \
        else None
    hlo_dir = argv[argv.index("--hlo") + 1] if "--hlo" in argv else None
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
    programs["reference_layer"] = lower_reference_layer(ctx["config"], device)
    for name, lowered in programs.items():
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
