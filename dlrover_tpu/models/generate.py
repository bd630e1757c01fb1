"""Autoregressive decoding for TpuLM: KV-cache prefill + generate.

TPU-shaped: the whole decode loop is ONE jitted ``lax.scan`` — static
shapes (cache pre-allocated at ``max_len``), no per-token dispatch, and
position-masked attention over the cache so padding never leaks into
the softmax. The cache layout [layers, batch, max_len, kv_heads,
head_dim] keeps the per-step update a ``dynamic_update_slice`` on the
time axis and shards like activations (kv_heads on tp, batch on dp).

The decode layer is BUILT FROM the training layer's own blocks
(llama.attention_qkv / attention_out / mlp_block) plus the shared
``dot_product_attention`` — only the cache append is decode-specific,
so dense-model training and generation cannot drift. Compiled programs
are cached per (config, shapes); temperature is a TRACED scalar, so
per-request temperatures retrace nothing.

The cache's fill cursor is a PER-ROW [b] int32 vector: generate() keeps
every row at the same fill (its append is still one dynamic-update-
slice at the shared cursor), while the continuous-batching serving
engine (serving/engine.py) drives the same layer blocks with genuinely
ragged per-slot fills — the masking (_append_free_attention,
dot_product_attention positions) is per-row either way.

MoE caveat, for the capacity-factor path only (``llama.py``'s expert
layers through ``moe.moe_mlp``): expert capacity is derived from the
LOCAL sequence length of each call (``moe.expert_capacity``), so token
drops differ between a full teacher-forced forward and prefill+decode
(a single-token step clamps capacity to 1 and never drops): exact logit
parity holds for dense configs only. The expert layer the ENGINES serve
is the dropless one (``moe.routed_experts``: every expert held, as
``models/sparse_lm.py`` calls it): one function for the full forward,
the prefill chunk and the decode step, no capacity in any of them, so a
token's expert output is the same in all three
(``tests/test_keye_serving.py``).

    state = ... (restored params)
    out = generate(cfg, params, prompt_tokens, max_new_tokens=64)
"""

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from dlrover_tpu.models import llama
from dlrover_tpu.ops.attention import dot_product_attention


class DecodeCache(NamedTuple):
    k: jnp.ndarray  # [layers, b, max_len, kv_heads, head_dim]
    v: jnp.ndarray
    length: jnp.ndarray  # [b] int32 — tokens filled so far, per row
    # int8 caches only (ops/kv_quant): per-(row, head) f32 scales
    # [layers, b, max_len, kv_heads]; None for fp caches.
    k_scale: Optional[jnp.ndarray] = None
    v_scale: Optional[jnp.ndarray] = None


def init_cache(
    config: llama.TpuLMConfig, batch: int, max_len: int,
    kv_dtype: Optional[str] = None,
) -> DecodeCache:
    """``kv_dtype``: "fp" (cache in compute_dtype; what None means) |
    "int8" (ops/kv_quant per-(row, head) scales — half the decode KV
    bytes)."""
    if config.pp_stages > 1:
        raise NotImplementedError(
            "decode runs on the flat layer stack; merge pipeline stages "
            "for inference"
        )
    kv_dtype = kv_dtype or "fp"
    if kv_dtype not in ("fp", "int8"):
        # Silently building an fp cache would make an intended int8
        # A/B measure the wrong path.
        raise ValueError(
            f"kv_dtype {kv_dtype!r} not in ('fp', 'int8')"
        )
    shape = (
        config.n_layers,
        batch,
        max_len,
        config.n_kv_heads,
        config.head_dim,
    )
    if kv_dtype == "int8":
        return DecodeCache(
            k=jnp.zeros(shape, jnp.int8),
            v=jnp.zeros(shape, jnp.int8),
            length=jnp.zeros((batch,), jnp.int32),
            k_scale=jnp.zeros(shape[:-1], jnp.float32),
            v_scale=jnp.zeros(shape[:-1], jnp.float32),
        )
    dtype = config.compute_dtype
    return DecodeCache(
        k=jnp.zeros(shape, dtype),
        v=jnp.zeros(shape, dtype),
        length=jnp.zeros((batch,), jnp.int32),
    )


def _uniform_cursor(cache_len):
    """Scalar write cursor from a scalar-or-[b] fill. The uniform
    prefill/append paths (generate keeps every row at the same fill)
    write with ONE dynamic-update-slice at the shared cursor; ragged
    callers (the serving engine) never reach these paths — they append
    with a per-row scatter instead."""
    cl = jnp.asarray(cache_len)
    return cl if cl.ndim == 0 else cl[0]


def _fuse_decode_params(config, layers):
    """Concatenate the per-layer projection weights the decode loop
    multiplies back to back: wq|wk|wv -> one [d, h+2kh, hd] matmul and
    w_gate|w_up -> one [d, 2f] matmul (dense configs). Decode is
    op-count-bound (each step is ~160 small dispatches), so halving the
    projection matmuls is a direct ms/token win; the math is identical.
    Leaves are stacked [L, ...]."""
    if config.n_experts > 0:
        return layers
    fused = dict(layers)
    fused["wqkv"] = jnp.concatenate(
        [layers["wq"], layers["wk"], layers["wv"]], axis=2
    )  # [L, d, h + 2*kh, hd]
    fused["w_gu"] = jnp.concatenate(
        [layers["w_gate"], layers["w_up"]], axis=2
    )  # [L, d, 2f]
    for k in ("wq", "wk", "wv", "w_gate", "w_up"):
        del fused[k]
    return fused


def _fused_qkv(config, p, x, positions):
    """attention_qkv over the concatenated projection (decode path)."""
    cdt = config.compute_dtype
    hx = llama.rms_norm(x, p["attn_norm"]).astype(cdt)
    qkv = jnp.einsum("bsd,dhk->bshk", hx, p["wqkv"].astype(cdt))
    h, kh = config.n_heads, config.n_kv_heads
    q, k, v = (
        qkv[:, :, :h],
        qkv[:, :, h:h + kh],
        qkv[:, :, h + kh:],
    )
    q = llama.apply_rope(q, positions, config.rope_theta)
    k = llama.apply_rope(k, positions, config.rope_theta)
    return q, k, v


def _fused_mlp(config, p, x):
    cdt = config.compute_dtype
    residual = x
    hx = llama.rms_norm(x, p["mlp_norm"]).astype(cdt)
    f = config.mlp_dim
    gu = jnp.einsum("bsd,df->bsf", hx, p["w_gu"].astype(cdt))
    a = jax.nn.silu(gu[..., :f]) * gu[..., f:]
    out = jnp.einsum("bsf,fd->bsd", a, p["w_down"].astype(cdt))
    return residual + out.astype(residual.dtype)


def _layer_decode(
    config, p, x, positions, k_cache, v_cache, cache_len,
    k_scale=None, v_scale=None,
):
    """The PREFILL body (``sq > 1``): one decoder block over [b, sq]
    new tokens with cache append. A one-token step never comes here:
    it takes :func:`_layer_decode_read_only`, which rebuilds no cache.
    Returns (x, new_k_cache, new_v_cache) — plus (new_k_scale,
    new_v_scale) when the cache is int8 (``k_scale`` given: the append
    quantizes per ops/kv_quant and the attention materializes the
    dequantized view — prefill is compute-bound). ``cache_len`` may be
    scalar or a UNIFORM [b] vector — the append writes at the shared
    cursor."""
    residual = x
    quantized = k_scale is not None
    if "wqkv" in p:
        q, k, v = _fused_qkv(config, p, x, positions)
    else:
        q, k, v = llama.attention_qkv(config, p, x, positions)
    # Append the new tokens' K/V at the (uniform) cache cursor.
    cursor = _uniform_cursor(cache_len)
    if quantized:
        from dlrover_tpu.ops.kv_quant import quantize_kv

        kq, ks_new = quantize_kv(k)
        vq, vs_new = quantize_kv(v)
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, kq, (0, cursor, 0, 0)
        )
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, vq, (0, cursor, 0, 0)
        )
        k_scale = jax.lax.dynamic_update_slice(
            k_scale, ks_new, (0, cursor, 0)
        )
        v_scale = jax.lax.dynamic_update_slice(
            v_scale, vs_new, (0, cursor, 0)
        )
    else:
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k.astype(k_cache.dtype), (0, cursor, 0, 0)
        )
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v.astype(v_cache.dtype), (0, cursor, 0, 0)
        )
    # Plain attention over the full pre-allocated cache; with
    # contiguous query positions the causal mask already excludes
    # every unfilled slot. Rejected for the one-token step, which the
    # append-free path serves (v5e, b=8, 334M): a Pallas kernel on a
    # sequential (batch, kv_head, block) grid (3.6 vs 1.3 ms/token)
    # and lax.switch-bucketed static prefixes (no gain at b>=8, b=1
    # 0.92 -> 1.39 ms/token).
    if quantized:
        from dlrover_tpu.ops.kv_quant import dequantize_kv

        cdt = config.compute_dtype
        k_attn = dequantize_kv(k_cache, k_scale, cdt)
        v_attn = dequantize_kv(v_cache, v_scale, cdt)
    else:
        k_attn, v_attn = k_cache, v_cache
    attn = dot_product_attention(
        q,
        k_attn,
        v_attn,
        causal=True,
        q_positions=positions,
        kv_positions=jnp.arange(k_cache.shape[1]),
    )
    x = llama.attention_out(config, p, attn, residual)
    if "w_gu" in p:
        x = _fused_mlp(config, p, x)
    else:
        x, _ = llama.mlp_block(config, p, x)
    if quantized:
        return x, k_cache, v_cache, k_scale, v_scale
    return x, k_cache, v_cache


def _append_free_attention(
    q, k_cache, v_cache, k_new, v_new, cache_len,
    k_scale=None, v_scale=None,
):
    """Single-token attention WITHOUT materializing an updated cache.

    The padded-cache decode path spent 21% of device time on two
    100-200MB per-token copies (measured v5e op profile): the layer
    scan rebuilt the full [L, b, max_len, kh, d] cache as stacked scan
    outputs every token, and XLA inserted a layout copy feeding it back
    to the next step. Here the cache is a READ-ONLY input; the new
    token's attention is decomposed into a cache part and a
    new-token part with a merged softmax (exact same math as
    dot_product_attention over the DUS'd cache — the new token is
    always its own last visible key), and the caller appends all
    layers' new K/V with ONE small dynamic-update-slice per token.

    q: [b, 1, h, d]; k_cache/v_cache: [b, S, kh, d] (rows >=
    cache_len unfilled); k_new/v_new: [b, 1, kh, d]; cache_len scalar
    or PER-ROW [b] int32 — ragged fills (the serving engine's slot
    pool) mask each row at its own length. Returns [b, 1, h, d].

    Int8 caches (``k_scale``/``v_scale`` [b, S, kh] — ops/kv_quant):
    dequantization FOLDS into the math — K scales multiply the raw
    logits, V scales the probability rows — so the dequantized cache
    is never materialized and the step's HBM read is the int8 bytes.
    The new token's own K/V stay full-precision here; its quantized
    row is what LATER steps read (write-once scheme).
    """
    from dlrover_tpu.ops.attention import NEG_INF

    b, _, h, d = q.shape
    _, skv, kh, _ = k_cache.shape
    g = h // kh
    scale = d ** -0.5
    q32 = (q[:, 0] * scale).astype(jnp.float32).reshape(b, kh, g, d)
    # Cache part: [b, kh, g, S]; only filled rows are visible — per
    # row, so ragged slot fills mask independently.
    logits = jnp.einsum(
        "bkgd,bskd->bkgs", q32, k_cache.astype(jnp.float32)
    )
    if k_scale is not None:
        logits = logits * k_scale.transpose(0, 2, 1)[:, :, None, :]
    lens = jnp.atleast_1d(jnp.asarray(cache_len, jnp.int32))
    visible = jnp.arange(skv)[None, :] < lens[:, None]  # [1|b, S]
    logits = jnp.where(visible[:, None, None, :], logits, NEG_INF)
    # New-token part: the query always sees itself.
    l_new = jnp.einsum(
        "bkgd,bkd->bkg", q32, k_new[:, 0].astype(jnp.float32)
    )
    m = jnp.maximum(jnp.max(logits, axis=-1), l_new)  # [b, kh, g]
    p = jnp.exp(logits - m[..., None])
    p = jnp.where(visible[:, None, None, :], p, 0.0)
    p_new = jnp.exp(l_new - m)
    denom = jnp.sum(p, axis=-1) + p_new  # >= p_new > 0
    pv = p if v_scale is None else (
        p * v_scale.transpose(0, 2, 1)[:, :, None, :]
    )
    out = (
        jnp.einsum("bkgs,bskd->bkgd", pv, v_cache.astype(jnp.float32))
        + p_new[..., None] * v_new[:, 0].astype(jnp.float32)[:, :, None]
    ) / denom[..., None]
    return out.reshape(b, 1, h, d).astype(q.dtype)


def _layer_decode_read_only(
    config, p, x, positions, k_cache, v_cache, cache_len,
    k_scale=None, v_scale=None,
):
    """One decoder block over [b, 1] tokens; the cache is read-only.
    Returns (x, k_new [b, 1, kh, d], v_new) — the caller batches the
    cache append across all layers (see _append_free_attention).
    ``cache_len`` may be a ragged [b] vector: positions and masking are
    per-row, which is what the serving engine's decode step drives.
    ``k_scale``/``v_scale`` mark an int8 cache (folded dequant)."""
    residual = x
    if "wqkv" in p:
        q, k, v = _fused_qkv(config, p, x, positions)
    else:
        q, k, v = llama.attention_qkv(config, p, x, positions)
    attn = _append_free_attention(
        q, k_cache, v_cache, k, v, cache_len,
        k_scale=k_scale, v_scale=v_scale,
    )
    x = llama.attention_out(config, p, attn, residual)
    if "w_gu" in p:
        x = _fused_mlp(config, p, x)
    else:
        x, _ = llama.mlp_block(config, p, x)
    return x, k, v


def _layer_verify_read_only(
    config, p, x, positions, k_cache, v_cache, cache_len,
    k_scale=None, v_scale=None,
):
    """One decoder block over [b, T] tokens (speculative-decoding
    verification: the fed token plus K drafts); the cache is read-only.
    The T-query sibling of :func:`_layer_decode_read_only`, built on
    ``ops.decode_attention.spec_verify_attention`` — intra-draft
    causality rides inside the merged softmax, so T=1 is exactly the
    single-token step.

    fp caches: returns (x, k_new [b, T, kh, d], v_new). int8 caches
    (``k_scale`` given): the new rows are quantized IN-LAYER (per-row
    round-to-nearest — identical values to a post-scan quantize) so
    later draft queries attend the QUANTIZED earlier-draft keys
    exactly as sequential decode would read them back from the cache;
    returns (x, k_q, k_rows_scale, v_q, v_rows_scale) and the caller
    appends the quantized rows directly."""
    from dlrover_tpu.ops.decode_attention import spec_verify_attention

    residual = x
    if "wqkv" in p:
        q, k, v = _fused_qkv(config, p, x, positions)
    else:
        q, k, v = llama.attention_qkv(config, p, x, positions)
    if k_scale is not None:
        from dlrover_tpu.ops.kv_quant import quantize_kv

        kq, ks_rows = quantize_kv(k)
        vq, vs_rows = quantize_kv(v)
        attn = spec_verify_attention(
            q, k_cache, v_cache, k, v, cache_len,
            k_scale=k_scale, v_scale=v_scale,
            k_new_q=kq, k_new_scale=ks_rows,
            v_new_q=vq, v_new_scale=vs_rows,
        )
    else:
        attn = spec_verify_attention(
            q, k_cache, v_cache, k, v, cache_len
        )
    x = llama.attention_out(config, p, attn, residual)
    if "w_gu" in p:
        x = _fused_mlp(config, p, x)
    else:
        x, _ = llama.mlp_block(config, p, x)
    if k_scale is not None:
        return x, kq, ks_rows, vq, vs_rows
    return x, k, v


# Unroll factor of the decode-time layer scan: ROLLED. With the
# append-free step the rolled scan lets XLA alias the cache append in
# place (measured v5e, 334M, b=8: 1.38 ms/token, zero per-token cache
# copies in the op profile), while unrolling reintroduces
# 100-200MB/token of cache copy traffic (1.47-1.74 ms/token) — the
# unrolled straight-line code defeats the buffer aliasing that the loop
# structure makes provable.
_LAYER_SCAN_UNROLL = 1


def _forward_with_cache(config, params, tokens, cache: DecodeCache):
    """Run [b, sq] tokens through all layers, appending to the cache.
    Returns (logits of the LAST position [b, vocab], new cache).
    Uniform-fill contract: every row of ``cache.length`` holds the same
    value (generate() only ever advances all rows together), so the
    appends are single dynamic-update-slices at the shared cursor."""
    b, sq = tokens.shape
    positions = cache.length[:, None] + jnp.arange(sq, dtype=jnp.int32)[
        None, :
    ]
    x = llama.embed_tokens(config, params, tokens)
    quantized = cache.k_scale is not None
    new_ks = new_vs = None

    if sq == 1:
        # Append-free single-token step (the decode hot loop): the
        # layer scan READS the cache; each layer returns only its new
        # token's K/V, and one small dynamic-update-slice appends all
        # layers at once. The padded-cache path below rebuilt the full
        # cache as stacked scan outputs — 100-200MB of per-token copy
        # traffic, 21% of decode device time (v5e op profile). Int8
        # caches stream half those bytes (dequant folded into the
        # attention math); the append quantizes each layer's new row.
        if quantized:
            def body1(carry, layer_in):
                pl, k_c, v_c, ks, vs = layer_in
                y, k_new, v_new = _layer_decode_read_only(
                    config, pl, carry, positions, k_c, v_c,
                    cache.length, k_scale=ks, v_scale=vs,
                )
                return y, (k_new, v_new)

            x, (k_news, v_news) = jax.lax.scan(
                body1, x,
                (params["layers"], cache.k, cache.v,
                 cache.k_scale, cache.v_scale),
                unroll=_LAYER_SCAN_UNROLL,
            )
        else:
            def body1(carry, layer_in):
                pl, k_c, v_c = layer_in
                y, k_new, v_new = _layer_decode_read_only(
                    config, pl, carry, positions, k_c, v_c,
                    cache.length,
                )
                return y, (k_new, v_new)

            x, (k_news, v_news) = jax.lax.scan(
                body1, x, (params["layers"], cache.k, cache.v),
                unroll=_LAYER_SCAN_UNROLL,
            )
        cursor = _uniform_cursor(cache.length)
        if quantized:
            from dlrover_tpu.ops.kv_quant import quantize_kv

            kq, ks_rows = quantize_kv(k_news)
            vq, vs_rows = quantize_kv(v_news)
            new_k = jax.lax.dynamic_update_slice(
                cache.k, kq, (0, 0, cursor, 0, 0)
            )
            new_v = jax.lax.dynamic_update_slice(
                cache.v, vq, (0, 0, cursor, 0, 0)
            )
            new_ks = jax.lax.dynamic_update_slice(
                cache.k_scale, ks_rows, (0, 0, cursor, 0)
            )
            new_vs = jax.lax.dynamic_update_slice(
                cache.v_scale, vs_rows, (0, 0, cursor, 0)
            )
        else:
            new_k = jax.lax.dynamic_update_slice(
                cache.k, k_news.astype(cache.k.dtype),
                (0, 0, cursor, 0, 0),
            )
            new_v = jax.lax.dynamic_update_slice(
                cache.v, v_news.astype(cache.v.dtype),
                (0, 0, cursor, 0, 0),
            )
    elif quantized:
        def body_q(carry, layer_in):
            pl, k_c, v_c, ks, vs = layer_in
            y, k_c, v_c, ks, vs = _layer_decode(
                config, pl, carry, positions, k_c, v_c, cache.length,
                k_scale=ks, v_scale=vs,
            )
            return y, (k_c, v_c, ks, vs)

        x, (new_k, new_v, new_ks, new_vs) = jax.lax.scan(
            body_q, x,
            (params["layers"], cache.k, cache.v,
             cache.k_scale, cache.v_scale),
            unroll=_LAYER_SCAN_UNROLL,
        )
    else:
        def body(carry, layer_in):
            pl, k_c, v_c = layer_in
            y, k_c, v_c = _layer_decode(
                config, pl, carry, positions, k_c, v_c, cache.length
            )
            return y, (k_c, v_c)

        x, (new_k, new_v) = jax.lax.scan(
            body, x, (params["layers"], cache.k, cache.v),
            unroll=_LAYER_SCAN_UNROLL,
        )
    logits = llama.unembed(config, params, x[:, -1:, :])[:, 0, :]
    new_cache = DecodeCache(
        k=new_k, v=new_v, length=cache.length + sq,
        k_scale=new_ks, v_scale=new_vs,
    )
    return logits, new_cache


def sample_token(logits, rng, temperature):
    """Greedy-or-sampled next token over the last axis of ``logits``
    ([V], [b, V], ...). ``temperature`` is a TRACED scalar or per-row
    vector; <= 0 means argmax. ONE definition shared by generate()'s
    pick and the serving engine's decode/prefill samplers — the
    sampling rule must never drift between batch generation and
    serving.

    Fused gumbel-max form: categorical sampling IS
    ``argmax(logits/t + gumbel)`` — drawing the SAME gumbel noise
    ``jax.random.categorical`` would (same key, same shape) and
    zeroing it where t <= 0 (a positive 1/t rescale never moves an
    argmax) collapses the old categorical + argmax + select — three
    full passes over the [b, V] logits — into ONE perturbed argmax
    pass. Token-identical to the previous implementation for every
    (key, temperature)."""
    t = jnp.asarray(temperature, jnp.float32)
    t_rows = t[..., None] if t.ndim else t
    z = logits / jnp.maximum(t_rows, 1e-6)
    gumbel = jax.random.gumbel(rng, z.shape, z.dtype)
    # t <= 0 rows select the RAW logits (not the 1/t-rescaled copy):
    # rescaling is argmax-preserving in exact arithmetic but could
    # round two near-ties together in low precision.
    z = jnp.where(t_rows > 0.0, z + gumbel, logits)
    return jnp.argmax(z, axis=-1).astype(jnp.int32)


def sample_token_logprobs(logits, rng, temperature, top_k: int = 0):
    """``sample_token`` variant that ALSO returns the chosen token's
    log-probability under the (temperature-scaled) sampling
    distribution — and, with ``top_k > 0``, the top-k alternatives.

    TOKEN-IDENTICAL to :func:`sample_token` for every (key,
    temperature) by construction: the token comes from the same fused
    perturbed-argmax call, and only the extra ``log_softmax`` pass over
    the [*, V] logits is new — which is exactly why this is a separate
    opt-in variant rather than the default hot-path sampler. The
    speculative-decoding verifier needs it for the rejection-sampling
    correction pick (masked residual logits in, chosen token +
    logprob out); ``temperature <= 0`` rows report the argmax token's
    logprob under the unscaled softmax.

    Returns ``(token, logprob)``, or with ``top_k``:
    ``(token, logprob, topk_tokens, topk_logprobs)``."""
    tok = sample_token(logits, rng, temperature)
    t = jnp.asarray(temperature, jnp.float32)
    t_rows = t[..., None] if t.ndim else t
    base = jnp.where(
        t_rows > 0.0, logits / jnp.maximum(t_rows, 1e-6), logits
    )
    logp = jax.nn.log_softmax(base, axis=-1)
    lp = jnp.take_along_axis(logp, tok[..., None], axis=-1)[..., 0]
    if top_k:
        tk_lp, tk_idx = jax.lax.top_k(logp, top_k)
        return tok, lp, tk_idx.astype(jnp.int32), tk_lp
    return tok, lp


def prepare_decode_params(config, params):
    """Decode-ready params: matmul leaves cast to the compute dtype
    (decode is bandwidth-bound on parameter reads — measured 2.2ms/token
    on v5e with f32 masters = one 1.3GB sweep per step; the cast cost
    amortizes over the whole loop and every per-step read halves) plus
    the fused wqkv/w_gu projections (_fuse_decode_params). Norm scales
    (``models/sparse_lm.py``'s q/k and index-key norms among them) and
    the MoE router stay f32 (same precision rule as
    llama.run_layer_stack). Pure jnp: generate()'s jitted run calls it
    traced, the serving engine calls it eagerly once per engine."""
    from dlrover_tpu.models import model_for

    own = getattr(model_for(config), "prepare_decode_params", None)
    if own is not None:     # (a tree nested and stored as a server reads it)
        return own(config, params)
    cdt = config.compute_dtype
    if cdt != jnp.float32:
        keep = {"attn_norm", "mlp_norm", "router", "q_norm", "k_norm",
                "ik_norm_scale", "ik_norm_bias"}
        params = {
            "embed": params["embed"].astype(cdt),
            "layers": {
                k: (v if k in keep else v.astype(cdt))
                for k, v in params["layers"].items()
            },
            "final_norm": params["final_norm"],
            "lm_head": params["lm_head"].astype(cdt),
        }
    return {
        **params,
        "layers": _fuse_decode_params(config, params["layers"]),
    }


class GenerateResult(NamedTuple):
    tokens: jnp.ndarray       # [b, max_new_tokens]
    cache: DecodeCache


@functools.lru_cache(maxsize=32)
def _compiled_generate(
    config: llama.TpuLMConfig,
    batch: int,
    max_new_tokens: int,
    max_len: int,
    kv_dtype: str = "fp",
):
    """One compiled program per (config, shapes, kv_dtype) — repeat
    generate() calls reuse it (jit caches key on the function object,
    which must therefore be cached itself). Temperature is a TRACED
    scalar argument, NOT a cache key: per-request temperatures (a
    serving workload's normal case) previously forced a full retrace
    each time the value changed."""

    pick = sample_token

    def run(params, prompt, rng, temperature):
        params = prepare_decode_params(config, params)
        cache = init_cache(config, batch, max_len, kv_dtype=kv_dtype)
        logits, cache = _forward_with_cache(config, params, prompt, cache)
        rng, first_key = jax.random.split(rng)
        first = pick(logits, first_key, temperature)

        def step(carry, _):
            cache, tok, rng = carry
            rng, sub = jax.random.split(rng)
            logits, cache = _forward_with_cache(
                config, params, tok[:, None], cache
            )
            nxt = pick(logits, sub, temperature)
            return (cache, nxt, rng), tok

        (cache, last, _), toks = jax.lax.scan(
            step, (cache, first, rng), None, length=max_new_tokens - 1
        )
        out = jnp.concatenate(
            [jnp.moveaxis(toks, 0, 1), last[:, None]], axis=1
        )
        return out, cache

    return jax.jit(run)


def generate(
    config: llama.TpuLMConfig,
    params,
    prompt,                    # [b, prompt_len] int32
    max_new_tokens: int,
    max_len: Optional[int] = None,
    temperature: float = 0.0,
    rng: Optional[jax.Array] = None,
    kv_cache_dtype: Optional[str] = None,
) -> GenerateResult:
    """Greedy (temperature=0) or sampled decoding. The prefill and the
    whole decode loop are one jit-compiled program with static shapes.
    ``kv_cache_dtype``: "fp" (default) | "int8" — int8 halves the KV
    bytes every decode step streams (the dtype is a compile-cache key,
    not a retrace)."""
    if max_new_tokens < 1:
        raise ValueError("max_new_tokens must be >= 1")
    b, prompt_len = prompt.shape
    max_len = max_len or (prompt_len + max_new_tokens)
    if max_len < prompt_len + max_new_tokens:
        raise ValueError("max_len too small for prompt + new tokens")
    if temperature > 0.0 and rng is None:
        # A silent fixed default would make every sampled call return
        # identical tokens (best-of-n sampling quietly broken).
        raise ValueError("temperature > 0 requires an explicit rng key")
    rng = rng if rng is not None else jax.random.key(0)
    run = _compiled_generate(
        config, b, max_new_tokens, max_len,
        kv_dtype=kv_cache_dtype or "fp",
    )
    # np.float32, not a Python float: a weakly-typed scalar would give
    # the traced argument a different avals key and retrace once.
    import numpy as np

    tokens, cache = run(params, prompt, rng, np.float32(temperature))
    return GenerateResult(tokens=tokens, cache=cache)
