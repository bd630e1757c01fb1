"""The plain reference of ``olmo-hybrid-7b``: the forward pass of gated
delta-rule layers beside un-grouped full attention, in ``jax.numpy``
float32 under ``jax.default_matmul_precision("highest")``, importing
nothing from ``dlrover_tpu``.

The delta layers run the RECURRENCE, a token a step (``lax.scan``; no
chunk form, no solve): ``S_t = e^{g_t} S_{t-1} + beta_t k_t (v_t - e^{g_t}
S_{t-1}^T k_t)^T``, ``o_t = S_t^T q_t``, after a causal depthwise
convolution of 4 taps and SiLU on q, k and v, an L2 norm a head on q and
k. The full layers are causal softmax attention over every row so far,
30 heads over 30 KV heads, no rotation, an RMS norm over all of q's
(k's) channels. No kernel, cache, page or batching.

So that 6.6k rows fit beside the scores the pass goes over a sequence in
BLOCKS OF ROWS: :func:`advance` takes ``rows`` consecutive rows through
every layer and carries between calls what the mathematics carries (each
delta layer's ``S`` and last three projections, each full layer's keys and
values so far). ``low=True`` rounds every matmul's operands, and the
recurrence's, to 3 bits of mantissa (``lax.reduce_precision``): the
precision below bfloat16's, for the limits' other side.

Departures from the published description (each ``assumed`` in the
configuration file, none stated by a key of the source's config): the
block is Olmo 2/3's reordered norm (``h = x + norm(Mixer(x))``); QK-norm
is over the whole projection; the full layers have NO rotation
(``rope_theta: null``); the norm is ``x * rsqrt(mean x^2 + eps) * (1 +
gain)`` with gains stored as zeros (the same function as a plain gain of
ones); only the first ``num_hidden_layers`` held layers exist. The
weights are the program's tree (``models/delta_lm.py``), read by name and
cast to float32 a layer at a time. ``faults`` (``controls_olmo_hybrid
.py``) plants one named departure more.
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp

DELTA, FULL = "linear_attention", "full_attention"
FAULTS = ("state_in_bf16", "beta_not_doubled", "gate_dropped",
          "l2norm_skipped", "qk_norm_per_head", "rope_on_full_layers")


def shape_of(cfg_json):
    """The sizes the reference needs, from a configuration file's
    published keys (validated)."""
    c = cfg_json
    for key in ("hidden_size", "intermediate_size", "num_attention_heads",
                "num_key_value_heads", "vocab_size", "layer_types",
                "linear_num_key_heads", "linear_num_value_heads",
                "linear_key_head_dim", "linear_value_head_dim",
                "linear_conv_kernel_dim", "linear_allow_neg_eigval"):
        if key not in c:
            raise ValueError(f"configuration lacks {key}")
    types = tuple(c["layer_types"])
    if set(types) - {DELTA, FULL}:
        raise ValueError(f"layer_types {types}")
    if len(types) != c["num_hidden_layers"]:
        raise ValueError("layer_types and num_hidden_layers disagree")
    if c["linear_num_key_heads"] != c["linear_num_value_heads"]:
        raise ValueError("key and value heads of the delta rule differ")
    if c["num_attention_heads"] != c["num_key_value_heads"]:
        raise ValueError("the full attention is un-grouped")
    if (c.get("rope_parameters") or {}).get("rope_theta") is not None:
        raise ValueError("the full layers have no rotation")
    if c["hidden_size"] % c["num_attention_heads"]:
        raise ValueError("hidden_size is not whole heads")
    return dict(
        hidden=c["hidden_size"], mlp=c["intermediate_size"],
        heads=c["num_attention_heads"],
        head_dim=c.get("head_dim")
        or c["hidden_size"] // c["num_attention_heads"],
        vocab=c["vocab_size"], types=types,
        l_heads=c["linear_num_value_heads"], dk=c["linear_key_head_dim"],
        dv=c["linear_value_head_dim"], taps=c["linear_conv_kernel_dim"],
        beta_scale=2.0 if c["linear_allow_neg_eigval"] else 1.0,
        eps=float(c.get("rms_norm_eps", 1e-6)),
    )


def count_params(sh) -> int:
    """Parameters of the held layers, the embedding and the head, from
    the published keys alone."""
    d, f, h = sh["hidden"], sh["mlp"], sh["l_heads"]
    width = h * (2 * sh["dk"] + sh["dv"])
    delta = (
        d * width + 2 * d * h * sh["dv"] + 2 * d * h + 2 * h
        + sh["taps"] * width + sh["dv"]
    )
    qw = sh["heads"] * sh["head_dim"]
    full = 4 * d * qw + 2 * qw
    n_delta = sum(t == DELTA for t in sh["types"])
    return (
        n_delta * delta + (len(sh["types"]) - n_delta) * full
        + len(sh["types"]) * (3 * d * f + 2 * d)
        + 2 * sh["vocab"] * d + d
    )


def fp8(x):
    """3 bits of mantissa (5 of exponent), held in float32."""
    return jax.lax.reduce_precision(
        x.astype(jnp.float32), exponent_bits=5, mantissa_bits=3
    )


def _rel(got, want):
    """Row-wise relative error: ``|got - want| / |want|`` over the last
    axis."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return jnp.linalg.norm(got - want, axis=-1) / jnp.maximum(
        jnp.linalg.norm(want, axis=-1), 1e-30
    )


def _rms(x, gain, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + gain)


def _rotate(x, positions, theta=10000.0):
    """Half-split rotation of ``x [rows, heads, d]`` (a planted fault's:
    the model has none)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions[:, None, None].astype(jnp.float32) * inv
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate(
        [a * jnp.cos(ang) - b * jnp.sin(ang),
         b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1,
    )


def new_carry(sh, max_rows):
    """What a sequence carries from one block of rows to the next, before
    its first row."""
    n_d = sum(t == DELTA for t in sh["types"])
    n_f = len(sh["types"]) - n_d
    width = sh["l_heads"] * (2 * sh["dk"] + sh["dv"])
    kv = (n_f, max_rows, sh["heads"], sh["head_dim"])
    return dict(
        state=jnp.zeros((n_d, sh["l_heads"], sh["dk"], sh["dv"]),
                        jnp.float32),
        taps=jnp.zeros((n_d, sh["taps"] - 1, width), jnp.float32),
        k=jnp.zeros(kv, jnp.float32), v=jnp.zeros(kv, jnp.float32),
    )


def _delta_layer(sh, p, x, state, taps, keep_rows, mm, low, faults):
    """One delta mixer over ``x [rows, d]`` from ``state`` and ``taps``:
    (the mixer's output, the gated mix before ``W_o``, the new state and
    taps, the states and taps after rows ``keep_rows``)."""
    f32 = jnp.float32
    rows, h, dk, dv = x.shape[0], sh["l_heads"], sh["dk"], sh["dv"]
    rnd = fp8 if low else (lambda a: a)
    z = mm(x, p["wqkv"].astype(f32))
    zz = jnp.concatenate([taps, z], axis=0)
    y = jax.nn.silu(sum(
        p["conv"][j].astype(f32) * zz[j:j + rows] for j in range(sh["taps"])
    ))
    kw = h * dk
    q = y[:, :kw].reshape(rows, h, dk)
    k = y[:, kw:2 * kw].reshape(rows, h, dk)
    v = y[:, 2 * kw:].reshape(rows, h, dv)
    if "l2norm_skipped" not in faults:
        unit = lambda a: a * jax.lax.rsqrt(  # noqa: E731
            jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6
        )
        q, k = unit(q), unit(k)
    q = q * dk ** -0.5
    ab = x @ p["w_ab"].astype(f32)          # float32 in the program too
    g = -jnp.exp(p["a_log"].astype(f32)) * jax.nn.softplus(
        ab[:, :h] + p["dt_bias"].astype(f32)
    )
    if "gate_dropped" in faults:
        g = jnp.zeros_like(g)
    beta = jax.nn.sigmoid(ab[:, h:]) * (
        1.0 if "beta_not_doubled" in faults else sh["beta_scale"]
    )
    bf16 = "state_in_bf16" in faults

    def step(s, row):
        q_t, k_t, v_t, g_t, b_t = row
        s = rnd(s) * jnp.exp(g_t)[:, None, None]
        u = b_t[:, None] * (v_t - jnp.sum(s * rnd(k_t)[:, :, None], axis=1))
        s = s + rnd(k_t)[:, :, None] * u[:, None, :]
        if bf16:
            s = s.astype(jnp.bfloat16).astype(f32)
        return s, (jnp.sum(s * rnd(q_t)[:, :, None], axis=1), s)

    # the state after every row is not kept: the rows asked for are
    # picked out of the scan's own carry
    def pick(carry, row):
        s, kept, t = carry
        s, (o, _) = step(s, row)
        kept = jnp.where(
            (keep_rows == t)[:, None, None, None], s[None], kept
        )
        return (s, kept, t + 1), o

    kept0 = jnp.zeros((keep_rows.shape[0],) + state.shape, f32)
    (state, kept, _), o = jax.lax.scan(
        pick, (state, kept0, jnp.int32(0)), (q, k, v, g, beta)
    )
    normed = _rms(o, p["o_norm"].astype(f32), sh["eps"]).reshape(rows, -1)
    gated = normed * jax.nn.silu(mm(x, p["wg"].astype(f32)))
    keep = sh["taps"] - 1
    kept_taps = jnp.stack([
        jnp.where(
            (keep_rows[i] >= 0) & (keep_rows[i] < rows),
            jax.lax.dynamic_slice_in_dim(zz, keep_rows[i] + 1, keep, axis=0),
            0.0,
        )
        for i in range(keep_rows.shape[0])
    ])
    return (mm(gated, p["wo"].astype(f32)), gated, state, zz[rows:],
            kept, kept_taps)


def _full_layer(sh, p, x, k_all, v_all, start, mm, low, faults):
    """One full mixer over ``x [rows, d]`` at rows ``start ...`` over
    the keys and values so far (``k_all`` / ``v_all [max_rows, heads,
    hd]``, this block's rows written into them here)."""
    f32 = jnp.float32
    rows, h, hd = x.shape[0], sh["heads"], sh["head_dim"]
    rnd = fp8 if low else (lambda a: a)

    def normed(w, gain):
        y = mm(x, w.astype(f32))
        if "qk_norm_per_head" in faults:
            y = y.reshape(rows, h, hd)
            return _rms(y, gain.astype(f32).reshape(h, hd), sh["eps"])
        return _rms(y, gain.astype(f32), sh["eps"]).reshape(rows, h, hd)

    q = normed(p["wq"], p["q_norm"])
    k = normed(p["wk"], p["k_norm"])
    v = mm(x, p["wv"].astype(f32)).reshape(rows, h, hd)
    positions = start + jnp.arange(rows)
    if "rope_on_full_layers" in faults:
        q, k = _rotate(q, positions), _rotate(k, positions)
    k_all = jax.lax.dynamic_update_slice_in_dim(k_all, k, start, axis=0)
    v_all = jax.lax.dynamic_update_slice_in_dim(v_all, v, start, axis=0)
    scores = jnp.einsum("shd,thd->hst", rnd(q), rnd(k_all)) * hd ** -0.5
    seen = jnp.arange(k_all.shape[0])[None, :] <= positions[:, None]
    probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hst,thd->shd", rnd(probs), rnd(v_all))
    gated = out.reshape(rows, -1)
    return mm(gated, p["wo"].astype(f32)), gated, k_all, v_all


@functools.lru_cache(maxsize=8)
def _program(sh_items, low: bool, faults):
    sh = dict(sh_items)
    f32 = jnp.float32
    rnd = fp8 if low else (lambda a: a)
    mm = lambda a, b: rnd(a) @ rnd(b)  # noqa: E731

    def run(params, carry, tokens, start, keep_rows):
        with jax.default_matmul_precision("highest"):
            x = jnp.take(params["embed"], tokens, axis=0).astype(f32)
            pl = params["layers"]
            state, taps = carry["state"], carry["taps"]
            k_all, v_all = carry["k"], carry["v"]
            kept_s, kept_t, gated_all = [], [], []
            at_d = at_f = 0
            for layer, kind in enumerate(sh["types"]):
                if kind == DELTA:
                    p = jax.tree_util.tree_map(
                        lambda a: a[at_d], params["delta"]
                    )
                    y, gated, s, t, ks, kt = _delta_layer(
                        sh, p, x, state[at_d], taps[at_d], keep_rows, mm,
                        low, faults,
                    )
                    state, taps = state.at[at_d].set(s), taps.at[at_d].set(t)
                    kept_s.append(ks)
                    kept_t.append(kt)
                    at_d += 1
                else:
                    p = jax.tree_util.tree_map(
                        lambda a: a[at_f], params["full"]
                    )
                    y, gated, k_new, v_new = _full_layer(
                        sh, p, x, k_all[at_f], v_all[at_f], start, mm, low,
                        faults,
                    )
                    k_all = k_all.at[at_f].set(k_new)
                    v_all = v_all.at[at_f].set(v_new)
                    at_f += 1
                gated_all.append(gated)
                h = x + _rms(y, pl["mix_norm"][layer].astype(f32), sh["eps"])
                gu = mm(h, pl["w_gu"][layer].astype(f32))
                f = sh["mlp"]
                y_mlp = mm(
                    jax.nn.silu(gu[:, :f]) * gu[:, f:],
                    pl["w_down"][layer].astype(f32),
                )
                x = h + _rms(
                    y_mlp, pl["ffn_norm"][layer].astype(f32), sh["eps"]
                )
            final = _rms(x, params["final_norm"].astype(f32), sh["eps"])
        carry = dict(state=state, taps=taps, k=k_all, v=v_all)
        return carry, dict(
            logits_of=final, gated=gated_all,
            # [delta layers, kept rows, ...]: the state and the taps
            # AFTER each of ``keep_rows`` (block-relative)
            state_rows=jnp.stack(kept_s) if kept_s else None,
            taps_rows=jnp.stack(kept_t) if kept_t else None,
        )

    return jax.jit(run, donate_argnums=(1,))


def advance(params, carry, tokens, start, sh, low=False, faults=(),
            keep_rows=(-1, -1)):
    """``tokens [rows]`` at rows ``start ...`` through every layer from
    ``carry`` (donated): the new carry and ``out``: ``logits_of [rows,
    d]`` (the final normed residual: :func:`logits_at`), ``gated`` (a
    layer: the mixer's output before ``W_o``), ``state_rows`` /
    ``taps_rows [delta layers, len(keep_rows), ...]`` the state and taps
    after each row of ``keep_rows`` (relative to this block; a row that
    is not in it reads zeros)."""
    program = _program(
        tuple(sorted(sh.items())), bool(low), tuple(sorted(faults))
    )
    return program(
        params, carry, jnp.asarray(tokens, jnp.int32), jnp.int32(start),
        jnp.asarray(keep_rows, jnp.int32),
    )


@functools.partial(jax.jit, static_argnames=("low",))
def logits_at(params, final, rows, low=False):
    """Float32 logits ``[len(rows), vocab]`` of ``final``'s ``rows``."""
    rnd = fp8 if low else (lambda a: a)
    with jax.default_matmul_precision("highest"):
        return rnd(final[rows]) @ rnd(params["head"].astype(jnp.float32))


def forward(params, tokens, sh, block_rows=None, **kw):
    """Float32 logits ``[len(tokens), vocab]`` of one whole sequence, in
    blocks of ``block_rows`` rows (None: one)."""
    n = len(tokens)
    rows = block_rows or n
    pad = -n % rows
    tokens = np.concatenate([np.asarray(tokens, np.int32),
                             np.zeros(pad, np.int32)])
    carry = new_carry(sh, n + pad)
    finals = []
    for start in range(0, n + pad, rows):
        carry, out = advance(
            params, carry, tokens[start:start + rows], start, sh, **kw
        )
        finals.append(out["logits_of"])
    final = jnp.concatenate(finals)[:n]
    return logits_at(params, final, jnp.arange(n), low=kw.get("low", False))
