"""sha256 of the LOWERED programs (StableHLO text) of the cells whose
code a change to the layer pattern, the train step, the flash kernels or
the paged engine can reach, for a described TPU v5e: ``mistral7b-train``'s
step, ``kimilinear-train-8k``'s step, and the two engine programs of each
accepted serve cell (``nemo12b-serve-chat``, built as
``benchmark/rehearse.py`` builds them, ``keye-serve-docqa-32k``, as
``rehearse_keye.py``, ``xing-serve-sessions-16k``, as
``rehearse_xing.py``, ``lfm2-serve-sessions-8k``, as
``rehearse_lfm2.py``, ``mellum2-serve-mixed-16k``, as
``rehearse_mellum2.py``, ``sala-serve-docs-64k``, as
``rehearse_sala.py``, and ``olmohybrid-serve-grow-6k``, as
``rehearse_olmo_hybrid.py``, each where the checkout's manifest has
it). Run it on two checkouts and compare: the same hash is the same
program, so the cell cannot move.

    JAX_PLATFORMS=cpu python3 tools/program_hashes.py [ROOT] [--dump DIR]

``ROOT``: the checkout to read (default: this one). Two things are kept
out of the text, neither a part of the program: the Python tracebacks
JAX embeds in a Pallas kernel's serialized body
(``jax_traceback_in_locations_limit`` 0: file paths and line numbers of
every frame, so any edit to a file on the call stack would move the
hash), and the counter the symbol table appends to a private function's
name (``@_take_844``: one more or one fewer helper traced earlier
renumbers every later one). Nothing compiles and nothing runs (~1 min).
"""

import hashlib
import os
import re
import sys


def main(argv):
    dump = argv[argv.index("--dump") + 1] if "--dump" in argv else None
    roots = [a for a in argv if not a.startswith("--") and a != dump]
    root = os.path.abspath(roots[0] if roots else os.path.join(
        os.path.dirname(os.path.abspath(__file__)), os.pardir
    ))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, root)
    os.chdir(root)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding

    from benchmark import common, rehearse_keye, rehearse_kimi_linear
    from benchmark import rehearse_lfm2, rehearse_xing
    from benchmark import run as bench_run
    from dlrover_tpu.models import llama
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.trainer import train_step as ts

    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_traceback_in_locations_limit", 0)
    jax.default_backend = lambda: "tpu"     # the programs' TPU branches
    llama._ATTN_CACHE.clear()
    device = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    ).devices[0]
    manifest = common.load_manifest()

    def context(cell):
        return bench_run.cell_context(
            manifest, cell, 0, 30, 0, require_tpu=False
        )

    def dense_step(ctx):
        """``benchmark/rehearse.train_programs``, lowered and not
        compiled."""
        cfg = common.lm_config(ctx["config"])
        knobs = ctx["config"]["train"]
        mesh = build_mesh(MeshConfig(dp=1), [device])
        tc = ts.TrainConfig(
            warmup_steps=knobs["warmup_steps"],
            grad_accum=knobs["grad_accum"],
        )
        opt = ts.make_optimizer(tc)
        step_fn, specs = ts.make_train_step(
            cfg, tc, opt, mesh, donate=knobs["donate_state"]
        )

        def init(key):
            params = llama.init_params(cfg, key)[0]
            return {"params": params, "opt_state": opt.init(params),
                    "step": jnp.zeros((), jnp.int32)}

        key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype)
        state = jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            jax.eval_shape(init, key), ts.state_shardings(specs, mesh),
        )
        micro = knobs["micro_batch"] * knobs["grad_accum"]
        tokens = jax.ShapeDtypeStruct(
            (micro, ctx["traffic"]["seq_len"] + 1), jnp.int32,
            sharding=NamedSharding(mesh, ts.batch_spec()),
        )
        with mesh:
            return step_fn.jitted.lower(state, {"tokens": tokens})

    def report(label, lowered):
        text = re.sub(
            r"@([A-Za-z_][A-Za-z_0-9]*?)_[0-9]+\b", r"@\1", lowered.as_text()
        )
        if dump:
            os.makedirs(dump, exist_ok=True)
            with open(os.path.join(dump, label + ".txt"), "w") as f:
                f.write(text)
        print(label, hashlib.sha256(text.encode()).hexdigest(), flush=True)

    report("mistral7b-train.step", dense_step(context("mistral7b-train")))
    ctx = context("kimilinear-train-8k")
    report("kimilinear-train-8k.step", rehearse_kimi_linear.lower_step(
        ctx["config"], ctx["traffic"], device
    ))
    def dense_engine(ctx):
        """``benchmark/rehearse.serve_programs``' two, lowered and not
        compiled."""
        from jax.sharding import SingleDeviceSharding

        from benchmark.runners import serve as serve_runner
        from dlrover_tpu.models import generate as gen_lib
        from dlrover_tpu.serving.kvpool import engine as paged

        cfg, eng = common.lm_config(ctx["config"]), ctx["config"]["serve_engine"]
        one = SingleDeviceSharding(device)
        arr = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
            shape, dtype, sharding=one
        )
        on_chip = lambda tree: jax.tree_util.tree_map(  # noqa: E731
            lambda x: arr(x.shape, x.dtype), tree
        )
        key = on_chip(jax.eval_shape(lambda: jax.random.key(0)))
        params = on_chip(jax.eval_shape(
            lambda k: gen_lib.prepare_decode_params(
                cfg, serve_runner.init_params(cfg, k)
            ), key,
        ))
        slots, bs = eng["slots"], eng["block_size"]
        max_blocks = eng["max_len"] // bs
        num_blocks = slots * max_blocks + 1
        steps = paged._paged_steps(
            cfg, slots, num_blocks, max_blocks, bs, eng["prefill_chunk"]
        )
        pool = arr(
            (cfg.n_layers, num_blocks, bs, cfg.n_kv_heads, cfg.head_dim),
            cfg.compute_dtype,
        )
        i32 = jnp.int32
        return {
            "jit_step": steps.decode.lower(
                pool, pool, params, arr((slots, max_blocks), i32),
                arr((slots,), i32), arr((slots,), i32), arr((slots,), bool),
                arr((slots,), jnp.float32), key, arr((), i32),
            ),
            "jit_prefill": steps.prefill.lower(
                pool, pool, params, arr((1, eng["prefill_chunk"]), i32),
                arr((max_blocks,), i32), arr((), i32), arr((), i32),
                arr((), jnp.float32), key, arr((), i32),
            ),
        }

    serve_cells = {
        "nemo12b-serve-chat": dense_engine,
        "keye-serve-docqa-32k": lambda ctx: rehearse_keye.lower_engine_programs(
            ctx["config"], device
        ),
        "xing-serve-sessions-16k": lambda ctx:
            rehearse_xing.lower_engine_programs(
                ctx["config"], device, probes=False
            ),
        "lfm2-serve-sessions-8k": lambda ctx:
            rehearse_lfm2.lower_engine_programs(
                ctx["config"], device, probes=False
            )[0],
    }
    if any(w["name"] == "mellum2-serve-mixed-16k"
           for w in manifest["workloads"]):
        # (a parent older than the cell has neither its files nor its
        # model: its accepted cells above are what is compared)
        from benchmark import rehearse_mellum2

        serve_cells["mellum2-serve-mixed-16k"] = lambda ctx: \
            rehearse_mellum2.lower_engine_programs(
                ctx["config"], device, probes=False
            )[0]
    if any(w["name"] == "sala-serve-docs-64k"
           for w in manifest["workloads"]):
        from benchmark import rehearse_sala

        serve_cells["sala-serve-docs-64k"] = lambda ctx: \
            rehearse_sala.lower_engine_programs(
                ctx["config"], device, probes=False, reference=False
            )[0]
    if any(w["name"] == "olmohybrid-serve-grow-6k"
           for w in manifest["workloads"]):
        from benchmark import rehearse_olmo_hybrid

        serve_cells["olmohybrid-serve-grow-6k"] = lambda ctx: \
            rehearse_olmo_hybrid.lower_engine_programs(
                ctx["config"], device, probes=False, reference=False
            )[0]
    for cell, lower in serve_cells.items():
        programs = lower(context(cell))
        for name in ("jit_step", "jit_prefill"):
            report(cell + "." + name, programs[name])


if __name__ == "__main__":
    main(sys.argv[1:])
