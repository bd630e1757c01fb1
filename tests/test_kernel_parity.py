"""§33 kernel parity suites (marker: kernels) — interpret-mode Pallas
on CPU, so tier-1 covers the kernel logic without a TPU.

Four surfaces:

- fused sort-based MoE dispatch (ops/moe_dispatch.grouped_ffn) —
  forward AND gradients vs the dense one-hot reference across
  e ∈ {8, 16} x top_k ∈ {1, 2}, plus exact agreement with the
  megablox-gmm dispatch it replaced and the empty-expert edge;
- int8 KV decode (ops/kv_quant + models/generate) — pinned logit
  tolerance vs fp, token-exact greedy on the pinned bench prompts,
  and the fused gumbel-max sampler's equivalence to the categorical
  + argmax + select it collapsed;
- the int8 paged decode program vs the flat int8 slab its pool was
  shuffled from;
- zero retraces across admissions with the quantized paged cache.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import llama
from dlrover_tpu.models import moe as moe_lib
from dlrover_tpu.models.generate import generate, sample_token

pytestmark = pytest.mark.kernels


# ---------------------------------------------------------------------------
# Fused MoE dispatch
# ---------------------------------------------------------------------------


def _weights(key, d, f, e):
    kr, kg, ku, kd = jax.random.split(key, 4)
    router = jax.random.normal(kr, (d, e), jnp.float32)
    w_gate = jax.random.normal(kg, (e, d, f), jnp.float32) / np.sqrt(d)
    w_up = jax.random.normal(ku, (e, d, f), jnp.float32) / np.sqrt(d)
    w_down = jax.random.normal(kd, (e, f, d), jnp.float32) / np.sqrt(f)
    return router, w_gate, w_up, w_down


def _dense_reference(x, router, w_gate, w_up, w_down, top_k):
    logits = jnp.einsum("bsd,de->bse", x, router)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    h = jnp.einsum("bsd,edf->bsef", x, w_gate)
    u = jnp.einsum("bsd,edf->bsef", x, w_up)
    ffn = jnp.einsum("bsef,efd->bsed", jax.nn.silu(h) * u, w_down)
    out = jnp.zeros_like(x)
    for k in range(top_k):
        sel = jnp.take_along_axis(
            ffn, experts[..., k][..., None, None], axis=2
        )[:, :, 0]
        out = out + gates[..., k][..., None] * sel
    return out


@pytest.mark.parametrize("e", [8, 16])
@pytest.mark.parametrize("top_k", [1, 2])
def test_fused_dispatch_fwd_and_grads_match_dense(e, top_k):
    """The acceptance grid: fused forward + FULL gradient set (x,
    router via the outer combine, w_gate, w_up, w_down) vs the dense
    one-hot reference, e in {8, 16} x top_k in {1, 2}."""
    x = jax.random.normal(jax.random.key(e), (2, 24, 16), jnp.float32)
    router, wg, wu, wd = _weights(jax.random.key(e + 1), 16, 32, e)
    ref = _dense_reference(x, router, wg, wu, wd, top_k)
    out, metrics = moe_lib.moe_mlp_dropless(
        x, router, wg, wu, wd, top_k=top_k, dispatch="fused"
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5
    )
    assert float(metrics.dropped_fraction) == 0.0

    def loss_ref(x, rw, wg, wd):
        return jnp.sum(
            jnp.square(_dense_reference(x, rw, wg, wu, wd, top_k))
        )

    def loss_fused(x, rw, wg, wd):
        out, _ = moe_lib.moe_mlp_dropless(
            x, rw, wg, wu, wd, top_k=top_k, dispatch="fused"
        )
        return jnp.sum(jnp.square(out))

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(x, router, wg, wd)
    g_fus = jax.grad(loss_fused, argnums=(0, 1, 2, 3))(x, router, wg, wd)
    for name, a, b in zip(("x", "router", "w_gate", "w_down"),
                          g_ref, g_fus):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4,
            err_msg=f"grad mismatch: {name}",
        )


def test_fused_matches_gmm_dispatch_under_jit():
    """Same routing, same math: the fused kernel and the gmm baseline
    must agree to float tolerance (tighter than the dense-ref bound —
    both run the identical sorted grouped compute)."""
    x = jax.random.normal(jax.random.key(3), (2, 12, 16), jnp.float32)
    router, wg, wu, wd = _weights(jax.random.key(4), 16, 32, 4)

    f_fused = jax.jit(lambda x: moe_lib.moe_mlp_dropless(
        x, router, wg, wu, wd, top_k=2, dispatch="fused"
    )[0])
    f_gmm = jax.jit(lambda x: moe_lib.moe_mlp_dropless(
        x, router, wg, wu, wd, top_k=2, dispatch="gmm"
    )[0])
    np.testing.assert_allclose(
        np.asarray(f_fused(x)), np.asarray(f_gmm(x)),
        rtol=2e-5, atol=2e-6,
    )


def test_fused_dispatch_empty_expert_grads_are_zero():
    """An expert that no token routes to must report an exactly-zero
    weight gradient: its dw output block is visited by an all-padding
    tile (build_dispatch_layout gives every group >= 1 tile), never
    left as uninitialized buffer garbage."""
    d, f, e = 8, 16, 4
    # Positive tokens + a router whose columns 0/1 dominate: every
    # token's top-2 is {0, 1}, experts 2 and 3 receive nothing.
    router = np.zeros((d, e), np.float32)
    router[:, 0] = 5.0
    router[:, 1] = 4.0
    router = jnp.asarray(router)
    _, wg, wu, wd = _weights(jax.random.key(5), d, f, e)
    x = jnp.abs(
        jax.random.normal(jax.random.key(6), (1, 8, d), jnp.float32)
    ) + 0.1

    def loss(wg, wd):
        out, _ = moe_lib.moe_mlp_dropless(
            x, router, wg, wu, wd, top_k=2, dispatch="fused"
        )
        return jnp.sum(jnp.square(out))

    dwg, dwd = jax.grad(loss, argnums=(0, 1))(wg, wd)
    assert np.all(np.asarray(dwg[2:]) == 0.0)
    assert np.all(np.asarray(dwd[2:]) == 0.0)
    # ... and the routed experts' grads are live.
    assert np.abs(np.asarray(dwg[:2])).max() > 0


def test_dispatch_env_knob_round_trip():
    assert moe_lib._dispatch_impl() in ("fused", "gmm")
    old = os.environ.get("DLROVER_TPU_MOE_DISPATCH")
    try:
        os.environ["DLROVER_TPU_MOE_DISPATCH"] = "gmm"
        assert moe_lib._dispatch_impl() == "gmm"
        os.environ["DLROVER_TPU_MOE_DISPATCH"] = "not-a-dispatch"
        assert moe_lib._dispatch_impl() == "gmm"  # loud fallback
    finally:
        if old is None:
            os.environ.pop("DLROVER_TPU_MOE_DISPATCH", None)
        else:
            os.environ["DLROVER_TPU_MOE_DISPATCH"] = old


# ---------------------------------------------------------------------------
# Int8 KV decode
# ---------------------------------------------------------------------------


def test_kv_quant_round_trip_and_idempotency():
    from dlrover_tpu.ops.kv_quant import dequantize_kv, quantize_kv

    x = jax.random.normal(jax.random.key(0), (3, 5, 4, 16), jnp.float32)
    q, s = quantize_kv(x)
    assert q.dtype == jnp.int8 and s.shape == x.shape[:-1]
    deq = dequantize_kv(q, s)
    # amax/254 per-element bound of symmetric round-to-nearest.
    bound = np.asarray(s)[..., None] / 2 + 1e-7
    assert np.all(np.abs(np.asarray(deq) - np.asarray(x)) <= bound)
    # Idempotent in f32: requantizing the dequantized rows returns the
    # exact stored (values, scale) — the paged prefill's contract.
    q2, s2 = quantize_kv(deq)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q2))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s2))
    # All-zero rows quantize without NaN/inf.
    qz, sz = quantize_kv(jnp.zeros((2, 8)))
    assert np.all(np.asarray(qz) == 0) and np.all(np.asarray(sz) > 0)


def test_kv_wire_roundtrip_fp_and_int8():
    """Migration wire format: fp KV packs int8-on-the-wire within the
    quantization bound; int8 KV roundtrips bit-exact and the pack of an
    unpack is byte-identical (idempotent — re-exporting a migrated
    request costs zero extra error)."""
    from dlrover_tpu.ops.kv_quant import (
        dequantize_kv,
        kv_from_wire,
        kv_to_wire,
        quantize_kv,
    )

    rk, rv = jax.random.split(jax.random.key(7))
    k = jax.random.normal(rk, (2, 3, 8, 4, 16), jnp.float32)
    v = jax.random.normal(rv, (2, 3, 8, 4, 16), jnp.float32)
    # fp source: quantized on pack, reconstruction within amax/254.
    buf = kv_to_wire(k, v)
    kq, vq, ks, vs, header = kv_from_wire(buf)
    assert header["src_dtype"] == "float32"
    assert kq.dtype == np.int8 and ks.dtype == np.float32
    for deq, ref, s in (
        (dequantize_kv(jnp.asarray(kq), jnp.asarray(ks)), k, ks),
        (dequantize_kv(jnp.asarray(vq), jnp.asarray(vs)), v, vs),
    ):
        bound = np.asarray(s)[..., None] / 2 + 1e-7
        assert np.all(np.abs(np.asarray(deq) - np.asarray(ref)) <= bound)
    # int8 source: scales inline, bit-exact passthrough + idempotent
    # pack(unpack(buf)) == buf.
    q8k, s8k = quantize_kv(k)
    q8v, s8v = quantize_kv(v)
    buf8 = kv_to_wire(q8k, q8v, k_scale=s8k, v_scale=s8v)
    kq2, vq2, ks2, vs2, header2 = kv_from_wire(buf8)
    assert header2["src_dtype"] == "int8"
    np.testing.assert_array_equal(kq2, np.asarray(q8k))
    np.testing.assert_array_equal(vs2, np.asarray(s8v, np.float32))
    assert kv_to_wire(kq2, vq2, k_scale=ks2, v_scale=vs2) == buf8
    # Truncation and bad magic fail loudly.
    with pytest.raises(ValueError):
        kv_from_wire(buf8[:-3])
    with pytest.raises(ValueError):
        kv_from_wire(b"XXXX" + buf8[4:])
    with pytest.raises(ValueError):
        kv_to_wire(np.asarray(q8k), np.asarray(q8v))  # int8 sans scales


def test_int8_generate_logit_tolerance_and_greedy_tokens():
    """Pinned acceptance bound: int8-KV greedy decoding stays within a
    small logit distance of fp and is TOKEN-EXACT on the pinned bench
    prompts (prompt seeds chosen once; a quantization regression blows
    both up)."""
    from dlrover_tpu.models import generate as gen_lib

    cfg = llama.tiny_config()
    params, _ = llama.init_params(cfg, jax.random.key(0))
    prompt = jax.random.randint(
        jax.random.key(1), (2, 9), 0, cfg.vocab_size
    ).astype(jnp.int32)
    dec = gen_lib.prepare_decode_params(cfg, params)
    cache_fp = gen_lib.init_cache(cfg, 2, 32, kv_dtype="fp")
    cache_q8 = gen_lib.init_cache(cfg, 2, 32, kv_dtype="int8")
    logits_fp, cache_fp = gen_lib._forward_with_cache(
        cfg, dec, prompt, cache_fp
    )
    logits_q8, cache_q8 = gen_lib._forward_with_cache(
        cfg, dec, prompt, cache_q8
    )
    # Prefill logit tolerance (pinned): int8 KV may perturb logits but
    # only within the quantization noise floor for this config.
    err = float(jnp.max(jnp.abs(logits_fp - logits_q8)))
    assert err < 0.15, f"prefill logit error {err} above pinned bound"
    # A few decode steps through the append-free int8 path.
    tok = jnp.argmax(logits_q8, axis=-1).astype(jnp.int32)
    for _ in range(3):
        step_fp, cache_fp = gen_lib._forward_with_cache(
            cfg, dec, tok[:, None], cache_fp
        )
        step_q8, cache_q8 = gen_lib._forward_with_cache(
            cfg, dec, tok[:, None], cache_q8
        )
        err = float(jnp.max(jnp.abs(step_fp - step_q8)))
        assert err < 0.2, f"decode logit error {err} above pinned bound"
        tok = jnp.argmax(step_q8, axis=-1).astype(jnp.int32)


def test_int8_generate_token_exact_on_pinned_prompt():
    """Greedy generate() with int8 KV reproduces the fp tokens exactly
    on the pinned prompt (bench-prompt analogue; seeds chosen where
    the model's logit margins dominate the quantization noise)."""
    cfg = llama.tiny_config()
    params, _ = llama.init_params(cfg, jax.random.key(0))
    prompt = jax.random.randint(
        jax.random.key(1), (1, 9), 0, cfg.vocab_size
    ).astype(jnp.int32)
    fp = generate(cfg, params, prompt, max_new_tokens=12)
    q8 = generate(
        cfg, params, prompt, max_new_tokens=12, kv_cache_dtype="int8"
    )
    np.testing.assert_array_equal(
        np.asarray(fp.tokens), np.asarray(q8.tokens)
    )


def test_fused_sampler_matches_categorical_reference():
    """sample_token's single perturbed-argmax pass is token-identical
    to the categorical + argmax + select it replaced, for scalar and
    per-row temperatures, sampled and greedy."""
    logits = jax.random.normal(jax.random.key(2), (4, 64), jnp.float32)
    key = jax.random.key(3)

    def reference(logits, rng, temperature):
        t = jnp.asarray(temperature, jnp.float32)
        t_rows = t[..., None] if t.ndim else t
        sampled = jax.random.categorical(
            rng, logits / jnp.maximum(t_rows, 1e-6), axis=-1
        ).astype(jnp.int32)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jnp.where(t > 0.0, sampled, greedy)

    for temp in (
        np.float32(0.0),
        np.float32(0.7),
        jnp.asarray([0.0, 0.5, 1.3, 0.0], jnp.float32),
    ):
        got = sample_token(logits, key, temp)
        want = reference(logits, key, temp)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# Paged int8 decode program vs the flat int8 slab
# ---------------------------------------------------------------------------


def test_paged_int8_decode_step_parity_vs_flat(monkeypatch):
    """What an int8 ``PagedServingEngine`` runs a decode step with —
    ``_build_paged_decode(quantized=True)``: per layer the int8 pool
    and its scale pool gathered through the block tables, dequant
    folded into ``_append_free_attention`` — against
    ``_layer_decode_read_only(k_scale=, v_scale=)`` on the FLAT int8
    slab the tables were shuffled from, at ragged fills. Two layers,
    so the second layer's appended rows carry the first's attention.
    Both stay within the pinned int8 decode tolerance of the same step
    over the unquantized slab."""
    from dlrover_tpu.models import generate as gen_lib
    from dlrover_tpu.ops.kv_quant import quantize_kv
    from dlrover_tpu.serving.kvpool.dense import _build_paged_decode

    cfg = llama.tiny_config(n_layers=2)
    params, _ = llama.init_params(cfg, jax.random.key(0))
    dec = gen_lib.prepare_decode_params(cfg, params)
    L, kh, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    b, bs, mb = 4, 8, 8
    S = bs * mb
    lens = jnp.array([5, 16, 33, 63], jnp.int32)
    kk, kv, kt = jax.random.split(jax.random.key(1), 3)
    k_fp = jax.random.normal(kk, (L, b, S, kh, hd), jnp.float32)
    v_fp = jax.random.normal(kv, (L, b, S, kh, hd), jnp.float32)
    tokens = jax.random.randint(kt, (b,), 0, cfg.vocab_size, jnp.int32)
    k8, ks = quantize_kv(k_fp)
    v8, vs = quantize_kv(v_fp)

    def flat_step(k, v, k_scale=None, v_scale=None):
        x = llama.embed_tokens(cfg, dec, tokens[:, None])
        news = []
        for i in range(L):
            layer = jax.tree.map(lambda a: a[i], dec["layers"])
            scales = {} if k_scale is None else dict(
                k_scale=k_scale[i], v_scale=v_scale[i]
            )
            x, k_new, v_new = gen_lib._layer_decode_read_only(
                cfg, layer, x, lens[:, None], k[i], v[i], lens, **scales
            )
            news.append((k_new[:, 0], v_new[:, 0]))
        return llama.unembed(cfg, dec, x)[:, 0], news

    logits_fp, _ = flat_step(k_fp.astype(cfg.compute_dtype),
                             v_fp.astype(cfg.compute_dtype))
    logits_flat, news = flat_step(k8, v8, ks, vs)

    # The same slab behind shuffled tables (block 0 is the sentinel).
    rs = np.random.RandomState(0)
    tables = (rs.permutation(b * mb) + 1).reshape(b, mb).astype(np.int32)

    def pooled(slab):
        slab = np.asarray(slab)
        pool = np.zeros((L, b * mb + 1, bs) + slab.shape[3:], slab.dtype)
        pool[:, tables.reshape(-1)] = slab.reshape(
            (L, b * mb, bs) + slab.shape[3:]
        )
        return jnp.asarray(pool)

    seen = {}
    pick = gen_lib.sample_token

    def spy(logits, rng, temps):
        seen["logits"] = logits
        return pick(logits, rng, temps)

    monkeypatch.setattr(gen_lib, "sample_token", spy)
    step_q8 = _build_paged_decode(
        cfg, b, mb, bs, {"decode": 0}, quantized=True
    )
    pk, pv, pks, pvs, nxt = step_q8(
        pooled(k8), pooled(v8), pooled(ks), pooled(vs), dec,
        jnp.asarray(tables), lens, tokens, jnp.ones((b,), bool),
        jnp.zeros((b,), jnp.float32), jax.random.key(2), jnp.int32(0),
    )
    np.testing.assert_allclose(
        np.asarray(seen["logits"]), np.asarray(logits_flat),
        rtol=2e-5, atol=2e-5,
    )
    np.testing.assert_array_equal(
        np.asarray(nxt), np.asarray(jnp.argmax(logits_flat, axis=-1))
    )
    # Each layer's new row landed, quantized, at its slot's cursor.
    blk = tables[np.arange(b), np.asarray(lens) // bs]
    off = np.asarray(lens) % bs
    for i, (k_new, v_new) in enumerate(news):
        for pool, scale_pool, new in ((pk, pks, k_new), (pv, pvs, v_new)):
            want_q, want_s = quantize_kv(new)
            np.testing.assert_array_equal(
                np.asarray(pool[i, blk, off]), np.asarray(want_q)
            )
            np.testing.assert_allclose(
                np.asarray(scale_pool[i, blk, off]), np.asarray(want_s),
                rtol=1e-6,
            )
    for name, logits in (("flat", logits_flat), ("paged", seen["logits"])):
        err = float(jnp.max(jnp.abs(logits - logits_fp)))
        assert err < 0.2, f"{name} int8 logit error {err} above bound"


# ---------------------------------------------------------------------------
# Quantized paged engine: zero retraces + parity
# ---------------------------------------------------------------------------


def test_int8_paged_engine_zero_retraces_and_parity():
    """Admissions, prefix hits, COW, preemption-free decode over the
    int8 paged cache: trace counts stay flat after warmup and every
    request's greedy tokens equal the int8 generate() reference."""
    from dlrover_tpu.serving.kvpool.engine import PagedServingEngine

    cfg = llama.tiny_config()
    params, _ = llama.init_params(cfg, jax.random.key(0))
    rs = np.random.RandomState(0)
    shared = rs.randint(0, cfg.vocab_size, size=16).tolist()
    prompts = [
        rs.randint(0, cfg.vocab_size, size=n).tolist() for n in (9, 17)
    ] + [shared + rs.randint(0, cfg.vocab_size, size=5).tolist(),
         shared + rs.randint(0, cfg.vocab_size, size=7).tolist()]
    eng = PagedServingEngine(
        cfg, params, slots=4, max_len=64, prefill_chunk=16,
        block_size=8, num_blocks=40, kv_cache_dtype="int8",
    )
    eng.warmup()
    warm = dict(eng.trace_counts)
    for p in prompts:
        eng.submit(p, max_new_tokens=8)
    done = eng.run_until_idle()
    assert sum(eng.trace_counts.values()) == sum(warm.values()), (
        "quantized paged engine retraced across admissions"
    )
    eng.check_block_invariants()
    assert len(done) == len(prompts)
    for r in sorted(done, key=lambda r: r.rid):
        ref = generate(
            cfg, params, jnp.asarray([r.prompt], jnp.int32),
            max_new_tokens=8, kv_cache_dtype="int8",
        )
        assert r.tokens == np.asarray(ref.tokens)[0].tolist(), (
            f"rid {r.rid} diverged from int8 generate reference"
        )
    # The int8 pool reports the smaller block footprint.
    assert eng._block_bytes < (
        2 * cfg.n_layers * 8 * cfg.n_kv_heads * cfg.head_dim
        * jnp.dtype(cfg.compute_dtype).itemsize
    )


# ---------------------------------------------------------------------------
# Ring overlap schedule parity
# ---------------------------------------------------------------------------


def test_ring_overlap_schedule_matches_legacy():
    """The overlap schedule (permute-before-compute, final rotation
    elided) computes the SAME attention and gradients as the legacy
    compute-then-permute order, on the virtual sp mesh, both impls."""
    from dlrover_tpu.ops.ring_attention import make_ring_attention
    from dlrover_tpu.parallel import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(sp=4, dp=2))
    kq, kk, kv = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(kq, (2, 16, 4, 8), jnp.float32)
    k = jax.random.normal(kk, (2, 16, 2, 8), jnp.float32)
    v = jax.random.normal(kv, (2, 16, 2, 8), jnp.float32)

    def run(overlap, impl):
        old = os.environ.get("DLROVER_TPU_RING_OVERLAP")
        try:
            os.environ["DLROVER_TPU_RING_OVERLAP"] = overlap
            ring = make_ring_attention(mesh, impl=impl)

            def loss(q, k, v):
                return jnp.sum(jnp.square(ring(q, k, v, causal=True)))

            with mesh:
                out = jax.jit(lambda q, k, v: ring(q, k, v, causal=True))(
                    q, k, v
                )
                grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(
                    q, k, v
                )
            return out, grads
        finally:
            if old is None:
                os.environ.pop("DLROVER_TPU_RING_OVERLAP", None)
            else:
                os.environ["DLROVER_TPU_RING_OVERLAP"] = old

    for impl in ("xla", "pallas"):
        out_on, g_on = run("1", impl)
        out_off, g_off = run("0", impl)
        np.testing.assert_allclose(
            np.asarray(out_on), np.asarray(out_off),
            rtol=1e-5, atol=1e-6, err_msg=f"fwd mismatch ({impl})",
        )
        for name, a, b in zip("qkv", g_on, g_off):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5,
                err_msg=f"d{name} mismatch ({impl})",
            )
