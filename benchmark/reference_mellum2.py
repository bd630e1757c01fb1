"""The plain reference of ``mellum2-12b-a2.5b``: the published layer written
out in ``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``,
with no kernel, no cache, no page, no batching and nothing imported from
``dlrover_tpu``. One sequence at a time, free running.

Per token, hidden ``x [C]``; ``n(x) = x / sqrt(mean(x^2) + eps) * (1 +
g)``. Every layer: ``x <- x + Attn(n_a(x))``, then ``x <- x + FFN(n_f(x))``;
after the last ``h = n_out(x)``, ``logits = h W_head`` (untied).

Attention, ``u = n_a(x)``: ``q = u W_q`` (32 heads x 128), ``k = u W_k``,
``v = u W_v`` (4 heads x 128), no bias, no per-head norm. Rotation over
the whole head, half-split pairing, by the layer's type:

- ``sliding_attention``: inverse frequencies ``theta^(-2i/128)``, theta
  500,000; key ``j`` visible to query ``i`` iff ``0 <= i - j <
  sliding_window`` (itself and the 1,023 before it);
- ``full_attention``: YaRN: each of those frequencies blended between
  itself and itself / ``factor`` by a ramp over the pair's index between
  the pairs that turn ``beta_fast`` and ``beta_slow`` times within
  ``original_max_position_embeddings``; cosine and sine both multiplied
  by ``attention_factor`` (so ``q . k`` carries its square); causal.

Scores ``q . k / sqrt(128)``, softmax, 8 query heads a KV head; ``y =
concat(P v) W_o``. What a cache holds of a token is ``k`` after rotation
and ``v`` (``kv`` of :func:`forward_at`, every layer's).

FFN, ``h = n_f(x)``: ``p = softmax(h W_r)`` over all 64 experts, the 8
largest, weights ``p`` at the chosen over their sum; ``y = sum w_e
W_down_e(silu(W_gate_e h) * W_up_e h)``, expert width 896, no shared
expert. Every expert computes every token and the router's weights,
zero off the chosen, pick (a 16,640-token sequence x 64 experts x 12.4
MFLOP x 8 layers is 106 TFLOP a sequence; a gather of each token's eight
would move 99 MB of weights a token).

Computed in blocks of queries (attention) and of tokens and experts
(FFN) so that a 17k-token sequence fits beside the bf16 weights, one
layer upcast to float32 at a time; the blocks change no sum's terms.

Beside the forward pass: :func:`hold_row` holds one layer to what a
program computed for ONE row (both sides fed the same input: the
program's residual before the layer, its FFN's normed input, the cache
rows it is given), and ``low=True`` computes the same formulas in the
precision below the configuration's (3 bits of mantissa where the
configuration says bfloat16, through ``reduce_precision``), for the
second reading that every limit of ``runners/serve_window.py`` is set
from.

Parameter tree (``models/window_lm.py``): ``embed [V, C]``, ``final_norm
[C]``, ``lm_head [C, V]``; ``layers``: ``attn_norm, ffn_norm [L, C]``,
``wq [L, C, 32, 128]``, ``wk, wv [L, C, 4, 128]``, ``wo [L, 32, 128,
C]``, ``router [L, C, E]``; ``moe``: ``w_gu [L E, C, 2 f]`` (gate | up),
``w_down [L E, f, C]`` (expert ``e`` of layer ``l`` is group ``l E +
e``).
"""

import functools
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 256
TOKEN_BLOCK = 1024
EXPERT_BLOCK = 8
SLIDING, FULL = "sliding_attention", "full_attention"


def shape_of(cfg_json):
    """The numbers the formulas need, from the published keys."""
    c = cfg_json
    types = tuple(c["layer_types"])
    if len(types) != c["num_hidden_layers"] or set(types) - {SLIDING, FULL}:
        raise ValueError(f"layer_types {types}")
    if set(c["mlp_layer_types"]) != {"sparse"} or not c["norm_topk_prob"]:
        raise ValueError("every layer is sparse and renormalises its top-k")
    full = c["rope_parameters"][FULL]
    sliding = c["rope_parameters"][SLIDING]
    if full["rope_type"] != "yarn" or sliding["rope_type"] != "default":
        raise ValueError("YaRN on the full layers, plain RoPE on the others")
    return {
        "types": types, "hidden": c["hidden_size"],
        "heads": c["num_attention_heads"],
        "kv_heads": c["num_key_value_heads"], "head_dim": c["head_dim"],
        "window": c["sliding_window"], "experts": c["num_experts"],
        "top_k": c["num_experts_per_tok"], "eps": c["rms_norm_eps"],
        "theta": float(sliding["rope_theta"]),
        "yarn_theta": float(full["rope_theta"]),
        "factor": float(full["factor"]),
        "original_max": int(full["original_max_position_embeddings"]),
        "beta_fast": float(full["beta_fast"]),
        "beta_slow": float(full["beta_slow"]),
        "attention_factor": float(full["attention_factor"]),
    }


def _norm(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + scale
    )


def low_precision(a):
    """``a`` rounded to 3 bits of mantissa at bfloat16's range: the
    precision below bfloat16 (``reference_lfm2.fp8``'s reasons: a pair of
    casts is one the compiler may skip, and 4 exponent bits flush these
    weights to zero)."""
    return jax.lax.reduce_precision(
        a.astype(jnp.float32), exponent_bits=8, mantissa_bits=3
    )


def frequencies(sh, kind):
    """(inverse frequencies ``[head_dim / 2]``, what cosine and sine are
    multiplied by) of a layer of type ``kind``."""
    hd = sh["head_dim"]
    pair = jnp.arange(hd // 2, dtype=jnp.float32)
    if kind == SLIDING:
        return 1.0 / sh["theta"] ** (2.0 * pair / hd), 1.0
    plain = 1.0 / sh["yarn_theta"] ** (2.0 * pair / hd)

    def pair_with(turns):       # the pair that turns so often in the span
        return hd * math.log(
            sh["original_max"] / (turns * 2 * math.pi)
        ) / (2 * math.log(sh["yarn_theta"]))

    lo = max(math.floor(pair_with(sh["beta_fast"])), 0)
    hi = min(math.ceil(pair_with(sh["beta_slow"])), hd // 2 - 1)
    ramp = jnp.clip((pair - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return (plain / sh["factor"] * ramp + plain * (1.0 - ramp),
            sh["attention_factor"])


def _rope(x, positions, sh, kind):
    """``x [T, heads, hd]`` rotated at ``positions [T]``."""
    inv_freq, factor = frequencies(sh, kind)
    angles = positions.astype(jnp.float32)[:, None, None] * inv_freq
    cos, sin = jnp.cos(angles) * factor, jnp.sin(angles) * factor
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def gqa_inputs(p, u, positions, sh, kind, low=False):
    """``q [T, 32, 128]``, ``k`` (rotated) and ``v [T, 4, 128]``."""
    rnd = low_precision if low else (lambda a: a)
    u = rnd(u)
    q = jnp.einsum("tc,chk->thk", u, rnd(p["wq"]))
    k = jnp.einsum("tc,chk->thk", u, rnd(p["wk"]))
    v = jnp.einsum("tc,chk->thk", u, rnd(p["wv"]))
    return (_rope(q, positions, sh, kind), _rope(k, positions, sh, kind), v)


def visible(q_pos, k_pos, sh, kind):
    """``[R, T]``: key at ``k_pos`` seen by the query at ``q_pos``."""
    ahead = q_pos[:, None] - k_pos[None, :]
    seen = ahead >= 0
    if kind == SLIDING:
        seen &= ahead < sh["window"]
    return seen


def _attend(q, k, v, mask, sh, low=False):
    """``q [R, heads, hd]`` over ``k``, ``v [T, kv_heads, hd]`` under
    ``mask [R, T]`` -> ``[R, heads, hd]``."""
    if low:
        q, k, v = low_precision(q), low_precision(k), low_precision(v)
    g = sh["heads"] // sh["kv_heads"]
    qg = q.reshape(q.shape[0], sh["kv_heads"], g, -1)
    scores = jnp.einsum("rkgd,tkd->kgrt", qg, k) * sh["head_dim"] ** -0.5
    probs = jax.nn.softmax(
        jnp.where(mask[None, None], scores, -jnp.inf), axis=-1
    )
    if low:
        probs = low_precision(probs)
    return jnp.einsum("kgrt,tkd->rkgd", probs, v).reshape(q.shape)


def sequence_attention(q, k, v, sh, kind, low=False):
    """Every row over the keys it sees, a block of queries at a time."""
    t = q.shape[0]
    qb = min(Q_BLOCK, t)
    assert t % qb == 0, t
    positions = jnp.arange(t)

    def some(start):
        rows = start + jnp.arange(qb)
        return _attend(
            jax.lax.dynamic_slice_in_dim(q, start, qb), k, v,
            visible(rows, positions, sh, kind), sh, low,
        )

    return jax.lax.map(some, jnp.arange(0, t, qb)).reshape(q.shape)


# -- the FFN ------------------------------------------------------------------


def route(w_r, h, sh):
    """The chosen experts ``[T, top_k]`` (best first) and the dense
    weights ``[T, E]``, zero off the chosen."""
    probs = jax.nn.softmax(h @ w_r, axis=-1)
    chosen, ids = jax.lax.top_k(probs, sh["top_k"])
    weights = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    dense = jnp.zeros_like(probs).at[
        jnp.arange(probs.shape[0])[:, None], ids
    ].set(weights)
    return ids, dense


def experts(pm, first, h, dense_weights, low=False):
    """``sum_e w_e SwiGLU_e(h)`` with every expert computing every token,
    a block of tokens and of experts at a time. ``pm``: ``w_gu [groups,
    C, 2 f]``, ``w_down [groups, f, C]`` as the tree stores them (ALL
    layers' experts, in the tree's own dtype); ``first``: the group of
    this layer's expert 0. A block of experts is cut out and upcast when
    its turn comes."""
    t, e = dense_weights.shape
    tb, eb = min(TOKEN_BLOCK, t), min(EXPERT_BLOCK, e)
    assert t % tb == 0 and e % eb == 0, (t, e)
    up = lambda w, at: jax.lax.dynamic_slice_in_dim(  # noqa: E731
        w, first + at, eb, axis=0
    ).astype(jnp.float32)
    if low:
        h = low_precision(h)
        up = lambda w, at, up=up: low_precision(up(w, at))  # noqa: E731
    f = pm["w_down"].shape[-2]

    def some(acc, at):
        w_gu, w_down = up(pm["w_gu"], at), up(pm["w_down"], at)
        pick = jax.lax.dynamic_slice_in_dim(dense_weights, at, eb, axis=1)

        def tokens(start):
            x = jax.lax.dynamic_slice_in_dim(h, start, tb, axis=0)
            gu = jnp.einsum("td,edf->etf", x, w_gu)
            act = jax.nn.silu(gu[..., :f]) * gu[..., f:]
            if low:
                act = low_precision(act)
            y = jnp.einsum("etf,efd->etd", act, w_down)
            return jnp.einsum(
                "etd,te->td", y,
                jax.lax.dynamic_slice_in_dim(pick, start, tb, axis=0),
            )

        out = jax.lax.map(tokens, jnp.arange(0, t, tb))
        return acc + out.reshape(t, -1), None

    return jax.lax.scan(some, jnp.zeros_like(h), jnp.arange(0, e, eb))[0]


# -- the layer and the forward pass -------------------------------------------


def layer_weights(params, layer):
    """Layer ``layer``'s own leaves upcast to float32 (the experts' stack
    stays as the tree holds it)."""
    return {
        k: v[layer].astype(jnp.float32) for k, v in params["layers"].items()
    }


def _frozen(sh):
    return tuple(sorted(sh.items()))


@functools.partial(jax.jit, static_argnames=("sh_items", "kind", "low"))
def _attention_sublayer(p, x, sh_items, kind, low=False):
    sh = dict(sh_items)
    u = _norm(x, p["attn_norm"], sh["eps"])
    q, k, v = gqa_inputs(p, u, jnp.arange(x.shape[0]), sh, kind, low)
    out = sequence_attention(q, k, v, sh, kind, low)
    rnd = low_precision if low else (lambda a: a)
    y = jnp.einsum("thk,hkc->tc", rnd(out), rnd(p["wo"]))
    return x + y, k.reshape(k.shape[0], -1), v.reshape(v.shape[0], -1)


@functools.partial(jax.jit, static_argnames=("sh_items", "low"))
def _ffn_sublayer(p, pm, x, sh_items, first, low=False):
    sh = dict(sh_items)
    h = _norm(x, p["ffn_norm"], sh["eps"])
    ids, dense = route(p["router"], h, sh)
    return x + experts(pm, first, h, dense, low), ids


def forward_at(params, tokens, rows, cfg_json, low=False):
    """The whole sequence ``tokens [T]`` through every layer, free
    running: float32 ``logits [R, V]`` at ``rows``; every layer's cache
    rows of all tokens (``kv``: a list of ``(k [T, 512]`` after rotation,
    ``v [T, 512])``); the experts every layer chose at ``rows``
    (``ids``). ``low``: every matmul's operands (the projections,
    attention, the experts, the head) in the precision below the
    configuration's (:func:`low_precision`), free running too; the
    routers stay as they are."""
    sh = shape_of(cfg_json)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        out = {"kv": [], "ids": []}
        for layer, kind in enumerate(sh["types"]):
            p = layer_weights(params, layer)
            x, k, v = _attention_sublayer(p, x, _frozen(sh), kind, low)
            out["kv"].append((k, v))
            x, ids = _ffn_sublayer(
                p, params["moe"], x, _frozen(sh), layer * sh["experts"], low
            )
            out["ids"].append(ids[rows])
        final = _norm(
            x[rows], params["final_norm"].astype(jnp.float32), sh["eps"]
        )
        head = params["lm_head"].astype(jnp.float32)
        if low:
            final, head = low_precision(final), low_precision(head)
        out["logits"] = final @ head
    return out


def _rel(got, want):
    axes = tuple(range(1, got.ndim))
    return jnp.sqrt(jnp.sum(jnp.square(got - want), axes)) / jnp.sqrt(
        jnp.sum(jnp.square(want), axes) + 1e-30
    )


@functools.partial(jax.jit, static_argnames=("sh_items", "kind"))
def _hold(p, pm, probe, sh_items, kind, first):
    sh = dict(sh_items)
    pos = probe["position"]
    kh, hd = sh["kv_heads"], sh["head_dim"]
    out = {}
    # Attention before W_o for the row at ``pos``, fed the program's
    # residual, over the rows ``k_rows`` / ``v_rows [T, 512]`` it is
    # given (rows at or past ``pos`` unseen) and over its own.
    u = _norm(probe["x_in"][None], p["attn_norm"], sh["eps"])
    seen = visible(pos[None], jnp.arange(probe["k_rows"].shape[0]), sh, kind)
    seen &= (jnp.arange(probe["k_rows"].shape[0]) < pos)[None]
    rows = probe["k_rows"].shape[0]
    mask = jnp.concatenate([seen, jnp.ones((1, 1), bool)], axis=1)

    def attend(low):
        q, k, v = gqa_inputs(p, u, pos[None], sh, kind, low)
        keys = jnp.concatenate([probe["k_rows"].reshape(rows, kh, hd), k])
        values = jnp.concatenate([probe["v_rows"].reshape(rows, kh, hd), v])
        return _attend(q, keys, values, mask, sh, low).reshape(1, -1)

    exact = attend(False)
    out["attn_err"] = _rel(probe["attn"][None], exact)[0]
    out["low_attn_err"] = _rel(attend(True), exact)[0]
    # The FFN on the program's normed input.
    h = probe["h_mlp"][None]
    ids, dense = route(p["router"], h, sh)
    weights = jnp.take_along_axis(dense, ids, axis=-1)
    got_ids, got_w = probe["experts"][None], probe["weights"][None]
    out["alike"] = (jnp.sort(got_ids, -1) == jnp.sort(ids, -1)).all()
    got_dense = jnp.zeros_like(dense).at[0, got_ids[0]].set(got_w[0])
    out["weight_err"] = jnp.max(jnp.abs(got_dense - dense)) / jnp.max(weights)
    # ... the expert sum under the PROGRAM's routing (a flip of two near
    # equal experts is judged above, not twice).
    want_y = experts(pm, first, h, got_dense)
    out["mlp_err"] = _rel(probe["y_mlp"][None], want_y)[0]
    out["low_mlp_err"] = _rel(
        experts(pm, first, h, got_dense, low=True), want_y
    )[0]
    return out


def hold_row(params, layer, probe, cfg_json):
    """Layer ``layer`` held to what a program computed for ONE row, both
    sides fed the same inputs. ``probe``: ``position`` (the row's),
    ``x_in [C]`` (the residual before the layer), ``attn [heads * hd]``
    (attention before ``W_o``), ``k_rows`` / ``v_rows [T, 512]`` (the
    cache rows the reference attends over: rows at or past ``position``
    and rows out of the layer's reach are unseen), ``h_mlp [C]`` (the
    FFN's normed input), ``experts`` / ``weights [top_k]``, ``y_mlp
    [C]``. Returns ``attn_err``, ``alike``, ``weight_err`` (the largest
    difference of a weight over the largest weight), ``mlp_err`` and,
    for the reference in the precision below on the same yardsticks,
    ``low_attn_err`` / ``low_mlp_err``."""
    sh = shape_of(cfg_json)
    with jax.default_matmul_precision("highest"):
        probe = {k: jnp.asarray(v) for k, v in probe.items()}
        for name in ("x_in", "attn", "k_rows", "v_rows", "h_mlp", "weights",
                     "y_mlp"):
            probe[name] = probe[name].astype(jnp.float32)
        return _hold(
            layer_weights(params, layer), params["moe"], probe, _frozen(sh),
            sh["types"][layer], layer * sh["experts"],
        )
