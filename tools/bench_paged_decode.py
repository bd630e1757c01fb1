"""Paged decode attention A/B on the device at hand: pool kernel vs gather.

``serving/kvpool/engine.py`` builds its plain decode program with one of
two attentions (``decode_attention_kind``): ``paged_kernel`` reads each
layer's K/V from the stacked pool in place, filled pages only
(``ops.decode_attention.pool_decode_attention``); ``xla_gather`` slices
the layer's pool, gathers a ``[slots, max_len]`` view through the tables
and runs ``_append_free_attention`` on it. This probe holds the two
against each other at the shapes of ``--preset``: ``nemo12b``, the
``nemo12b-serve-chat`` cell's (16 slots x 2,304 rows in 16-row pages, 32
heads / 8 KV x 128, 12 layers); ``flagship334m``, ``chip_smoke.py``'s
engine (4 slots x 576 rows, 8 / 8 x 128, 16 layers); ``llama2-7b``, an
MHA model at the cell's cache (32 / 32 x 128). ``--slots`` and
``--max-blocks`` move the cache's size, ``--heads`` the query heads:

    python tools/bench_paged_decode.py          # on the chip: chiprun -- ...

Three parts (``--parts``), one JSON line each: ``parity`` (the kernel
against the gather reference on one layer of a random pool, fills on
every edge), ``attention`` (all layers' attention alone, at the chat
traffic's fills, a full and a short cache, and over four chunk sizes),
``decode`` (the whole decode program both ways, random weights). Exits 1
if the kernel is not finite or, fed f32 queries, more than 1e-5 off the
reference at the highest matmul precision. A smoke reading, not a
benchmark: one process, host-clock timing around ``block_until_ready``.
Times mean something on a TPU only: anywhere else the tool refuses to
run, unless ``--tiny`` rehearses it (interpret mode, nothing timed).
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

BLOCK = 16
PRESETS = {
    "nemo12b": dict(
        slots=16, max_blocks=144, heads=32, kv_heads=8, head_dim=128,
        layers=12, vocab_size=131072, embed_dim=5120, mlp_dim=14336,
    ),
    "flagship334m": dict(
        slots=4, max_blocks=36, heads=8, kv_heads=8, head_dim=128,
        layers=16, vocab_size=32000, embed_dim=1024, mlp_dim=4096,
    ),
    "llama2-7b": dict(
        slots=16, max_blocks=144, heads=32, kv_heads=32, head_dim=128,
        layers=8, vocab_size=32000, embed_dim=4096, mlp_dim=11008,
    ),
}
TINY = dict(max_blocks=40, vocab_size=512, embed_dim=256, mlp_dim=512)


def _timed(fn, repeats):
    """Median ms of ``repeats`` calls of ``fn`` (which blocks), after
    one that is not timed; None where nothing is to be timed."""
    fn()
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return round(1e3 * statistics.median(times), 3) if times else None


def _put(row, key, value):
    if value is not None:
        row[key] = value


def _fills(kind, rng, slots, max_len):
    """Per-slot fills: the chat traffic's (prompt 128-2,048 log-uniform
    plus part of an answer, as far as the cache goes), a full cache, or
    a short one."""
    import numpy as np

    if kind == "full":
        return np.full(slots, max_len - 1, np.int32)
    if kind == "short":
        return np.full(slots, min(128, max_len - 1), np.int32)
    longest = max(128, min(2048, max_len - 64))
    prompts = np.exp(rng.uniform(np.log(128), np.log(longest), slots))
    return np.minimum(
        prompts + rng.uniform(0, 200, slots), max_len - 1
    ).astype(np.int32)


def _reference(q, k_new, v_new, k_pool, v_pool, layer, tables, fills):
    import jax.numpy as jnp

    from dlrover_tpu.models.generate import _append_free_attention

    slots, max_blocks = tables.shape
    shape = (slots, max_blocks * BLOCK) + k_pool.shape[-2:]
    return _append_free_attention(
        q[:, None], k_pool[layer][tables].reshape(shape),
        v_pool[layer][tables].reshape(shape),
        k_new[:, None], v_new[:, None], jnp.asarray(fills),
    )[:, 0]


def run(shape, parts, chunk_kb, repeats, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.models import generate as gen_lib
    from dlrover_tpu.models import llama
    from dlrover_tpu.ops import decode_attention as da
    from dlrover_tpu.serving.kvpool import engine as paged

    slots, max_blocks, layers = shape.slots, shape.max_blocks, shape.layers
    heads, kv_heads, head_dim = shape.heads, shape.kv_heads, shape.head_dim
    max_len = max_blocks * BLOCK
    cfg = llama.TpuLMConfig(
        n_layers=layers, n_heads=heads, n_kv_heads=kv_heads,
        head_dim=head_dim, dtype="bfloat16", vocab_size=shape.vocab_size,
        embed_dim=shape.embed_dim, mlp_dim=shape.mlp_dim,
    )
    dev = jax.devices()[0]
    rng = np.random.RandomState(seed)
    num_blocks = slots * max_blocks + 1
    keys = jax.random.split(jax.random.key(seed), 6)
    pool_shape = (layers, num_blocks, BLOCK, kv_heads, head_dim)
    print(json.dumps({
        "part": "shape", **vars(shape), "max_len": max_len,
        # One layer's logical K and V views: what the gather path moves
        # four times over, whatever the fills.
        "view_mb": round(
            2 * slots * max_len * kv_heads * head_dim * 2 / 1e6, 1
        ),
        "engine_would_build": paged.decode_attention_kind(
            cfg, BLOCK, "fp"
        ),
    }), flush=True)

    def normal(key, shape):
        return jax.jit(
            lambda k: jax.random.normal(k, shape, jnp.bfloat16)
        )(key)

    k_pool, v_pool = normal(keys[0], pool_shape), normal(keys[1], pool_shape)
    q = normal(keys[2], (slots, heads, head_dim))
    k_new = normal(keys[3], (slots, kv_heads, head_dim))
    v_new = normal(keys[4], (slots, kv_heads, head_dim))
    tables = jnp.asarray(
        (rng.permutation(slots * max_blocks) + 1)
        .reshape(slots, max_blocks).astype(np.int32)
    )
    active = jnp.ones(slots, bool)
    ok = True

    # ---- parity: one layer, fills on every edge ----------------------------
    if "parity" in parts:
        edge = np.resize(np.minimum(np.array(
            [0, 1, 15, 16, 17, 255, 256, 257, 511, 512, 764, 1000, 2047,
             2048, 2303, 2304], np.int32,
        ), max_len), slots)
        layer = jnp.int32(layers - 1)
        args = (k_pool, v_pool, layer, tables, jnp.asarray(edge))

        def both(q, k_new, v_new):
            return (
                jax.jit(da.pool_decode_attention)(
                    q, k_new, v_new, *args, active
                ),
                jax.jit(_reference)(q, k_new, v_new, *args),
            )

        # As the engine runs them (bf16 in, bf16 out), and with the same
        # values as f32 inputs against the reference at the highest
        # matmul precision: which of the two is nearer the exact softmax.
        got, want = (
            np.asarray(x, np.float32) for x in both(q, k_new, v_new)
        )
        wide = [x.astype(jnp.float32) for x in (q, k_new, v_new)]
        got32, want32 = (np.asarray(x) for x in both(*wide))
        with jax.default_matmul_precision("highest"):
            exact = np.asarray(jax.jit(_reference)(*wide, *args))
        diff = np.abs(got - want)
        parity = {
            "part": "parity", "finite": bool(np.isfinite(got).all()),
            "bf16_max_abs": float(diff.max()),
            "bf16_max_rel": float(
                (diff / np.maximum(np.abs(want), 2.0 ** -6)).max()
            ),
            "bf16_differing_of": [int((diff > 0).sum()), int(diff.size)],
            "f32_kernel_vs_exact": float(np.abs(got32 - exact).max()),
            "f32_gather_vs_exact": float(np.abs(want32 - exact).max()),
        }
        print(json.dumps(parity), flush=True)
        # The reference the kernel is held to is the exact one: XLA runs
        # the gather path's f32 einsums at default precision (one bf16
        # pass on a TPU), so there the two bf16 outputs differ by what
        # the GATHER rounds; off a TPU they differ by a rounding step
        # here and there.
        ok = parity["finite"] and parity["f32_kernel_vs_exact"] <= 1e-5

    # ---- all layers' attention alone ---------------------------------------
    def all_layers(attend):
        def f(q, k_new, v_new, k_pool, v_pool, tables, fills):
            def body(carry, layer):
                out = attend(
                    q, k_new, v_new, k_pool, v_pool, layer, tables, fills
                )
                return carry + out.astype(jnp.float32), None

            total, _ = jax.lax.scan(
                body, jnp.zeros(q.shape, jnp.float32),
                jnp.arange(layers, dtype=jnp.int32),
            )
            return total

        return jax.jit(f)

    def kernel(q, k_new, v_new, k_pool, v_pool, layer, tables, fills):
        return da.pool_decode_attention(
            q, k_new, v_new, k_pool, v_pool, layer, tables, fills, active
        )

    shipped = da._POOL_CHUNK_BYTES
    for kind in ("chat", "full", "short") if "attention" in parts else ():
        fills = _fills(kind, rng, slots, max_len)
        row = {"part": "attention", "fills": kind,
               "kv_rows": int(fills.sum()),
               "kv_mb_read": round(
                   2 * layers * int((-(-fills // BLOCK)).sum()) * BLOCK
                   * kv_heads * head_dim * 2 / 1e6, 1)}
        timed = (
            q, k_new, v_new, k_pool, v_pool, tables, jnp.asarray(fills)
        )
        for kb in chunk_kb:
            da._POOL_CHUNK_BYTES = kb << 10
            fn = all_layers(kernel)
            _put(row, f"kernel_{kb}k_ms", _timed(
                lambda: jax.block_until_ready(fn(*timed)), repeats
            ))
        da._POOL_CHUNK_BYTES = shipped
        fn = all_layers(_reference)
        _put(row, "gather_ms", _timed(
            lambda: jax.block_until_ready(fn(*timed)), repeats
        ))
        print(json.dumps(row), flush=True)
    del q, k_new, v_new

    # ---- the whole decode program ------------------------------------------
    if "decode" in parts:
        def weights(key):
            params, _ = llama.init_params(cfg, key)
            cast = jax.tree_util.tree_map(
                lambda x: x.astype(jnp.bfloat16), params
            )
            return gen_lib.prepare_decode_params(cfg, cast)

        params = jax.jit(weights)(keys[5])
        fills = _fills("chat", rng, slots, max_len)
        host = (
            tables, jnp.asarray(fills), jnp.zeros(slots, jnp.int32),
            active, jnp.zeros(slots, jnp.float32), jax.random.key(0),
            np.int32(0),
        )
        row = {"part": "decode", "kv_rows": int(fills.sum())}
        tokens = {}
        for attn in ("xla_gather", "paged_kernel"):
            step = jax.jit(
                paged._build_paged_decode(
                    cfg, slots, max_blocks, BLOCK, {"decode": 0}, attn=attn
                ),
                donate_argnums=(0, 1),
            )

            def once():
                nonlocal k_pool, v_pool
                k_pool, v_pool, nxt = step(k_pool, v_pool, params, *host)
                return jax.block_until_ready(nxt)

            tokens[attn] = np.asarray(once())
            _put(row, f"{attn}_ms", _timed(once, repeats))
        row["same_tokens"] = [
            int((tokens["xla_gather"] == tokens["paged_kernel"]).sum()),
            slots,
        ]
        print(json.dumps(row), flush=True)
    print(json.dumps({
        "ok": ok,
        "device": {"platform": dev.platform, "kind": dev.device_kind},
    }), flush=True)
    return ok


def main():
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--preset", choices=sorted(PRESETS), default="nemo12b")
    ap.add_argument("--slots", type=int)
    ap.add_argument("--max-blocks", type=int,
                    help=f"pages of {BLOCK} rows in a slot's table")
    ap.add_argument("--heads", type=int, help="query heads")
    ap.add_argument("--layers", type=int)
    ap.add_argument("--parts", default="parity,attention,decode")
    ap.add_argument("--chunk-kb", default="256,512,1024,2048",
                    help="VMEM chunk sizes to time the kernel at (the "
                    "one shipped: ops.decode_attention._POOL_CHUNK_BYTES)")
    ap.add_argument("--repeats", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="a 640-row cache and a narrow model: rehearses "
                    "the script off the chip (interpret mode); no times")
    ns = ap.parse_args()
    shape = dict(PRESETS[ns.preset])
    if ns.tiny:
        shape.update(TINY)
    shape.update({
        k: v for k in ("slots", "max_blocks", "heads", "layers")
        if (v := getattr(ns, k)) is not None
    })
    import jax

    if not ns.tiny and jax.default_backend() != "tpu":
        ap.error(   # exit 2: 1 is "the kernel is wrong"
            f"no TPU here ({jax.default_backend()}): the kernel would "
            "run in interpret mode and its times would mean nothing; "
            "--tiny rehearses the script without timing"
        )
    ok = run(
        argparse.Namespace(**shape), ns.parts.split(","),
        [int(kb) for kb in ns.chunk_kb.split(",")],
        0 if ns.tiny else ns.repeats, ns.seed,
    )
    raise SystemExit(0 if ok else 1)


if __name__ == "__main__":
    main()
