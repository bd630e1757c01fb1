"""The plain reference of ``lfm2-24b-a2b``: the published layer written out
in ``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``,
with no kernel, no cache, no batching and nothing imported from
``dlrover_tpu``. One sequence at a time, free running.

Per token, hidden ``x [C]``; ``n(x) = x / sqrt(mean(x^2) + eps) * (1 +
g)``. Every layer: ``x <- x + Op(n_op(x))``, then ``x <- x + FFN(n_ffn(
x))``; after the last ``h = n_out(x)``, ``logits = h E^T`` with ``E`` the
embedding (tied).

Conv (gated short convolution), ``u = n_op(x)``:

    [B | C | X] = u W_in  [3 C];   z_t = B_t * X_t
    c_t = w_0 * z_{t-2} + w_1 * z_{t-1} + w_2 * z_t     (zeros before the start)
    y_t = (C_t * c_t) W_out

computed as an explicit sum over three shifted copies of ``z``. What a
server keeps of a sequence after row ``t`` is ``(z_{t-1}, z_t)``
(``conv_z`` of :func:`forward_at`).

GQA: ``q = u W_q`` (32 heads x 64), ``k = u W_k``, ``v = u W_v`` (8 heads
x 64); ``q <- n_q(q)``, ``k <- n_k(k)`` over each head's channels, one
scale for all heads; RoPE (theta 1e6, all channels, half-split pairing);
scores ``q . k / 8``, causal softmax, 4 query heads a KV head; ``y =
concat(P v) W_o``. What a cache holds of a token is ``k`` after norm and
rotation and ``v`` (``kv_rows`` of :func:`forward_at`).

FFN. Layers below ``num_dense_layers``: SwiGLU of ``intermediate_size``.
Every other layer: ``s = sigmoid(u W_r)``, the ``num_experts_per_tok``
largest of ``s + b``, weights ``s`` at the chosen over their sum times
``routed_scaling_factor``; ``y = sum w_e SwiGLU_e(u)``; no shared expert.
Every expert computes every token and the router's weights, zero off the
chosen, pick (8,800 tokens x 64 experts x 8 layers is 85 TFLOP a
sequence; a gather of each token's four would move 75 MB of weights a
token).

Computed in blocks of queries (attention) and of tokens and experts
(FFN) so that a 9k-token sequence fits beside the bf16 weights, one
layer upcast to float32 at a time; the blocks change no sum's terms.

Assumed (the configuration file lists each under ``assumed``):
``head_dim`` 64 = 2048 / 32; the tied head; the order ``B, C, X`` and the
tap order; the half-split pairing; no ``+ 1e-6`` in the router's
denominator; the ``(1 + scale)`` gain (zero-initialised scales: the same
function as a plain gain at these weights) with the published eps.

Beside the forward pass: :func:`hold_layer` holds one layer's mixer and
FFN to what a program computed for a few rows (both sides fed the same
inputs), and ``low=True`` computes the same formulas in the precision
below the configuration's (float8 operands where the configuration says
bfloat16), for the second reading that every limit of
``runners/serve_conv.py`` is set from.

Parameter tree (``models/conv_lm.py``): ``embed [V, C]``, ``final_norm
[C]``; ``layers``: ``op_norm, ffn_norm [L, C]``; ``conv``: ``w_in [Lc, C,
3 C]``, ``filter [Lc, 3, C]``, ``w_out [Lc, C, C]``; ``attn``: ``wq [La,
C, 32, 64]``, ``wk, wv [La, C, 8, 64]``, ``q_norm, k_norm [La, 64]``,
``wo [La, 32, 64, C]``; ``dense``: ``w_gu [Ld, C, 2 F]`` (gate | up),
``w_down [Ld, F, C]``; ``moe``: ``router [Lm, C, E]``, ``router_bias
[Lm, E]``, ``w_gu [Lm E, C, 2 f]``, ``w_down [Lm E, f, C]`` (expert ``e``
of expert layer ``l`` at ``l E + e``).
"""

import functools

import jax
import jax.numpy as jnp

Q_BLOCK = 512        # queries an attention block
TOKEN_BLOCK = 1024   # tokens an expert block
EXPERT_BLOCK = 16    # experts an expert block


def shape_of(cfg_json):
    """The numbers the formulas need, from the published keys."""
    if cfg_json.get("model_type", "lfm2_moe") != "lfm2_moe":
        raise ValueError("this reference is the lfm2_moe layer")
    if not cfg_json.get("norm_topk_prob", True):
        raise ValueError("the router's weights are renormalised")
    if cfg_json.get("conv_bias"):
        raise ValueError("the convolution has no bias")
    types = tuple(cfg_json["layer_types"])
    if len(types) != cfg_json["num_hidden_layers"]:
        raise ValueError("layer_types names every layer")
    return {
        "hidden": cfg_json["hidden_size"], "layers": len(types),
        "types": types, "n_dense": cfg_json["num_dense_layers"],
        "heads": cfg_json["num_attention_heads"],
        "kv_heads": cfg_json["num_key_value_heads"],
        "head_dim": cfg_json.get("head_dim")
        or cfg_json["hidden_size"] // cfg_json["num_attention_heads"],
        "taps": cfg_json["conv_L_cache"],
        "experts": cfg_json["num_experts"],
        "top_k": cfg_json["num_experts_per_tok"],
        "scaling": float(cfg_json.get("routed_scaling_factor", 1.0)),
        "theta": float(cfg_json["rope_theta"]),
        "eps": float(cfg_json["norm_eps"]),
    }


def _norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def _rope(x, positions, theta):
    """``x [T, heads, hd]`` rotated by ``positions [T]``, half-split."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def fp8(a):
    """``a`` rounded to float8's 3 bits of mantissa at bfloat16's range
    (a float8 with a scale: weights of 1 / sqrt(2048) lie under e4m3's
    smallest normal number): the precision below bfloat16. By
    ``reduce_precision``: a pair of casts down and up is one the TPU's
    compiler may skip (my chip run, PR 48: the FFN "in float8" read 0.0
    against itself), and with 4 exponent bits the weights flushed to
    zero (0.43 where a rounding reads ~0.04)."""
    return jax.lax.reduce_precision(
        a.astype(jnp.float32), exponent_bits=8, mantissa_bits=3
    )


def bf16(a):
    return jax.lax.reduce_precision(
        a.astype(jnp.float32), exponent_bits=8, mantissa_bits=7
    )


# -- the two mixers -----------------------------------------------------------


def conv(pc, u, low=False, before=None):
    """The gated short convolution over a whole sequence ``u [T, C]``
    (``before [taps - 1, C]``: the gated inputs of the rows before it;
    None: zeros) -> (``y [T, C]``, ``z [T, C]``)."""
    rnd = fp8 if low else (lambda a: a)
    d = u.shape[-1]
    bcx = rnd(u) @ rnd(pc["w_in"])
    gate_b, gate_c, x = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    z = gate_b * x
    taps = pc["filter"].shape[0]
    if before is None:
        before = jnp.zeros((taps - 1, d), z.dtype)
    zz = jnp.concatenate([before, z], axis=0)
    t = u.shape[0]
    c = sum(pc["filter"][j] * zz[j:j + t] for j in range(taps))
    return rnd(gate_c * c) @ rnd(pc["w_out"]), z


def gqa_inputs(pa, u, positions, sh, low=False):
    """``q [T, 32, 64]``, ``k`` (normed, rotated) and ``v [T, 8, 64]``."""
    rnd = fp8 if low else (lambda a: a)
    u = rnd(u)
    q = jnp.einsum("tc,chk->thk", u, rnd(pa["wq"]))
    k = jnp.einsum("tc,chk->thk", u, rnd(pa["wk"]))
    v = jnp.einsum("tc,chk->thk", u, rnd(pa["wv"]))
    q = _rope(_norm(q, pa["q_norm"], sh["eps"]), positions, sh["theta"])
    k = _rope(_norm(k, pa["k_norm"], sh["eps"]), positions, sh["theta"])
    return q, k, v


def _attend(q, k, v, mask, sh, low=False):
    """``q [R, heads, hd]`` over ``k``, ``v [T, kv_heads, hd]`` under
    ``mask [R, T]`` -> ``[R, heads, hd]``."""
    if low:
        q, k, v = fp8(q), fp8(k), fp8(v)
    g = sh["heads"] // sh["kv_heads"]
    qg = q.reshape(q.shape[0], sh["kv_heads"], g, -1)
    scores = jnp.einsum("rkgd,tkd->kgrt", qg, k) * sh["head_dim"] ** -0.5
    probs = jax.nn.softmax(
        jnp.where(mask[None, None], scores, -jnp.inf), axis=-1
    )
    if low:
        probs = fp8(probs)
    return jnp.einsum("kgrt,tkd->rkgd", probs, v).reshape(q.shape)


def causal_attention(q, k, v, sh, low=False):
    """Every row over its causal keys, a block of queries at a time."""
    t = q.shape[0]
    qb = min(Q_BLOCK, t)
    assert t % qb == 0, t

    def some(start):
        rows = start + jnp.arange(qb)
        mask = jnp.arange(t)[None, :] <= rows[:, None]
        return _attend(
            jax.lax.dynamic_slice_in_dim(q, start, qb), k, v, mask, sh, low
        )

    return jax.lax.map(some, jnp.arange(0, t, qb)).reshape(q.shape)


def attention_at(q, k, v, rows, sh, low=False):
    """The queries ``q [R, ...]`` of rows ``rows`` over their causal
    keys."""
    mask = jnp.arange(k.shape[0])[None, :] <= rows[:, None]
    return _attend(q, k, v, mask, sh, low)


# -- the FFN ------------------------------------------------------------------


def _swiglu(x, w_gu, w_down):
    f = w_down.shape[-2]
    gu = x @ w_gu
    return (jax.nn.silu(gu[:, :f]) * gu[:, f:]) @ w_down


def route(pm, h, sh, variant=None):
    """The chosen experts ``[T, top_k]`` (best first) and the dense
    weights ``[T, E]``, zero off the chosen. ``variant``: ``"low"`` the
    router in bfloat16; ``"unnormalised"`` (``controls_lfm2.py``)."""
    w_r, x = pm["router"], h
    if variant == "low":
        w_r, x = bf16(w_r), bf16(x)
    scores = jax.nn.sigmoid(x @ w_r)
    _, ids = jax.lax.top_k(scores + pm["router_bias"], sh["top_k"])
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    weights = sh["scaling"] * chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    dense = jnp.zeros_like(scores).at[
        jnp.arange(scores.shape[0])[:, None], ids
    ].set(weights)
    return ids, dense


def experts(pm, h, dense_weights, low=False):
    """``sum_e w_e SwiGLU_e(h)`` with every expert computing every token,
    a block of tokens and of experts at a time. ``pm``: ``w_gu [groups,
    C, 2 f]``, ``w_down [groups, f, C]`` as the tree stores them (ALL
    expert layers' experts, in the tree's own dtype) and ``first``, the
    group of this layer's expert 0: a block of experts is cut out and
    upcast when its turn comes."""
    first = pm["first"]
    up = lambda w, at: jax.lax.dynamic_slice_in_dim(  # noqa: E731
        w, first + at, eb, axis=0
    ).astype(jnp.float32)
    if low:
        h = fp8(h)
        up = lambda w, at, up=up: fp8(up(w, at))  # noqa: E731
    t, e = dense_weights.shape
    tb, eb = min(TOKEN_BLOCK, t), min(EXPERT_BLOCK, e)
    assert t % tb == 0 and e % eb == 0, (t, e)
    f = pm["w_down"].shape[-2]

    def some(acc, at):
        w_gu, w_down = up(pm["w_gu"], at), up(pm["w_down"], at)
        pick = jax.lax.dynamic_slice_in_dim(dense_weights, at, eb, axis=1)

        def tokens(start):
            x = jax.lax.dynamic_slice_in_dim(h, start, tb, axis=0)
            gu = jnp.einsum("td,edf->etf", x, w_gu)
            act = jax.nn.silu(gu[..., :f]) * gu[..., f:]
            if low:
                act = fp8(act)
            y = jnp.einsum("etf,efd->etd", act, w_down)
            return jnp.einsum(
                "etd,te->td", y,
                jax.lax.dynamic_slice_in_dim(pick, start, tb, axis=0),
            )

        out = jax.lax.map(tokens, jnp.arange(0, t, tb))
        return acc + out.reshape(t, -1), None

    return jax.lax.scan(some, jnp.zeros_like(h), jnp.arange(0, e, eb))[0]


def mlp(pf, h, sh, low=False):
    """One layer's FFN on its normed input ``h [T, C]``: the dense SwiGLU
    (``pf`` with ``w_gu`` alone) or the expert layer -> (``y``, chosen
    experts or None, their weights or None)."""
    if "router" not in pf:
        if low:
            h, pf = fp8(h), {k: fp8(v) for k, v in pf.items()}
        return _swiglu(h, pf["w_gu"], pf["w_down"]), None, None
    ids, dense = route(pf, h, sh)
    weights = jnp.take_along_axis(dense, ids, axis=-1)
    return experts(pf, h, dense, low), ids, weights


# -- the layer and the forward pass -------------------------------------------


def layer_weights(params, layer, sh):
    """Layer ``layer``'s leaves upcast to float32: (``p``: both norms
    and the mixer's, ``pf``: its FFN's, the experts' stack as the tree
    holds it with ``first``, where this layer's begin)."""
    up = lambda a: a.astype(jnp.float32)  # noqa: E731
    kind = sh["types"][layer]
    at = sum(1 for t in sh["types"][:layer] if t == kind)
    p = {k: up(v[layer]) for k, v in params["layers"].items()}
    p.update({
        k: up(v[at])
        for k, v in params["conv" if kind == "conv" else "attn"].items()
    })
    if layer < sh["n_dense"]:
        pf = {k: up(v[layer]) for k, v in params["dense"].items()}
    else:
        at = layer - sh["n_dense"]
        pf = {
            k: v if k in ("w_gu", "w_down") else up(v[at])
            for k, v in params["moe"].items()
        }
        pf["first"] = at * sh["experts"]
    return p, pf


def _frozen(sh):
    return tuple(sorted(sh.items()))


def _arrays(pf):
    return {k: v for k, v in pf.items() if k != "first"}


@functools.partial(jax.jit, static_argnames=("sh_items", "kind", "low"))
def _mixer_sublayer(p, x, rows, sh_items, kind, low=False):
    sh = dict(sh_items)
    u = _norm(x, p["op_norm"], sh["eps"])
    if kind == "conv":
        y, z = conv(p, u, low)
        return x + y, {"z": z}
    positions = jnp.arange(x.shape[0])
    q, k, v = gqa_inputs(p, u, positions, sh, low)
    out = causal_attention(q, k, v, sh, low)
    rnd = fp8 if low else (lambda a: a)
    y = jnp.einsum("thk,hkc->tc", rnd(out), rnd(p["wo"]))
    return x + y, {
        "k": k.reshape(k.shape[0], -1), "v": v.reshape(v.shape[0], -1),
    }


@functools.partial(jax.jit, static_argnames=("sh_items", "first", "low"))
def _ffn_sublayer(p, pf, x, sh_items, first=None, low=False):
    sh = dict(sh_items)
    if first is not None:
        pf = dict(pf, first=first)
    y, ids, _ = mlp(pf, _norm(x, p["ffn_norm"], sh["eps"]), sh, low)
    return x + y, ids


def forward_at(params, tokens, rows, cfg_json, probes=None, state_rows=None,
               after_rows=None, low=False):
    """The whole sequence ``tokens [T]`` through every layer, free
    running: float32 ``logits [R, V]`` at ``rows``; of every convolution
    layer the gated inputs ``z`` at ``state_rows`` (``conv_z [Lc, S,
    C]``: a server's state after row ``t`` is ``z`` at ``t - 1, t``); of
    the FIRST attention layer the cache rows of all tokens (``kv_rows``:
    ``k [T, 512]`` after norm and rotation, ``v [T, 512]``) and of EVERY
    attention layer those at ``after_rows`` (``kv_after``); the experts
    every expert layer chose at ``rows`` (``ids``). ``probes`` (a list, a
    layer, of what a program read at some rows): each layer is also held
    to it (:func:`hold_layer`; ``held``: a list of dicts of arrays).
    ``low``: every matmul's operands (the projections, attention, the
    FFNs, the head) in the precision below the configuration's
    (:func:`fp8`), free running too; the routers stay as they are."""
    sh = shape_of(cfg_json)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        out = {"held": [], "ids": [], "conv_z": [], "kv_after": []}
        for layer, kind in enumerate(sh["types"]):
            p, pf = layer_weights(params, layer, sh)
            if probes is not None:
                out["held"].append(hold_layer(p, pf, probes[layer], sh, kind))
            x, reads = _mixer_sublayer(p, x, rows, _frozen(sh), kind, low)
            if kind == "conv" and state_rows is not None:
                out["conv_z"].append(reads["z"][state_rows])
            elif kind != "conv":
                out.setdefault("kv_rows", (reads["k"], reads["v"]))
                if after_rows is not None:
                    out["kv_after"].append(
                        (reads["k"][after_rows], reads["v"][after_rows])
                    )
            x, ids = _ffn_sublayer(
                p, _arrays(pf), x, _frozen(sh), pf.get("first"), low
            )
            if ids is not None:
                out["ids"].append(ids[rows])
        final = _norm(
            x[rows], params["final_norm"].astype(jnp.float32), sh["eps"]
        )
        head = params["embed"].astype(jnp.float32)
        if low:
            final, head = fp8(final), fp8(head)
        out["logits"] = final @ head.T
    return out


def _rel(got, want):
    axes = tuple(range(1, got.ndim))
    return jnp.sqrt(jnp.sum(jnp.square(got - want), axes)) / jnp.sqrt(
        jnp.sum(jnp.square(want), axes) + 1e-30
    )


@functools.partial(jax.jit, static_argnames=("sh_items", "first", "kind"))
def _hold(p, pf, probe, sh_items, first=None, kind="conv"):
    sh = dict(sh_items)
    if first is not None:
        pf = dict(pf, first=first)
    reads = {}
    u = _norm(probe["x_in"], p["op_norm"], sh["eps"])
    if kind == "conv":
        # The mixer on the PROGRAM's input rows and the program's state
        # before each (``conv_before [R, taps - 1, C]``): a row at a time.
        def one(u_row, before):
            return conv(p, u_row[None], before=before)[0][0]

        def one_low(u_row, before):
            return conv(p, u_row[None], low=True, before=before)[0][0]

        want = jax.vmap(one)(u, probe["conv_before"])
        reads["op_err"] = _rel(probe["y_op"], want)
        reads["low_op_err"] = _rel(
            jax.vmap(one_low)(u, probe["conv_before"]), want
        )
    else:
        # Attention before W_o: the reference's queries of the program's
        # input rows over the rows the program LANDED in the pool.
        q = gqa_inputs(p, u, probe["positions"], sh)[0]
        kh = sh["kv_heads"]
        k = probe["k_landed"].reshape(probe["k_landed"].shape[0], kh, -1)
        v = probe["v_landed"].reshape(probe["v_landed"].shape[0], kh, -1)
        want = attention_at(q, k, v, probe["positions"], sh)
        got = probe["attn"].reshape(want.shape)
        reads["op_err"] = _rel(got, want)
        reads["low_op_err"] = _rel(
            attention_at(q, k, v, probe["positions"], sh, low=True), want
        )
    h = _norm(probe["x_mid"], p["ffn_norm"], sh["eps"])
    reads["h_err"] = _rel(probe["h_mlp"], h)
    y, ids, weights = mlp(pf, probe["h_mlp"], sh)
    reads["y_err"] = _rel(probe["y_mlp"], y)
    reads["low_y_err"] = _rel(mlp(pf, probe["h_mlp"], sh, low=True)[0], y)
    if ids is not None:
        same = jnp.all(
            jnp.sort(ids, -1) == jnp.sort(probe["experts"], -1), axis=-1
        )
        order = jnp.argsort(probe["experts"], -1)
        got_w = jnp.take_along_axis(probe["weights"], order, -1)
        want_w = jnp.take_along_axis(weights, jnp.argsort(ids, -1), -1)
        reads["alike"] = same
        reads["weight_err"] = jnp.max(jnp.abs(got_w - want_w), -1) / jnp.max(
            jnp.abs(want_w), -1
        )
        low_ids, _ = route(pf, probe["h_mlp"], sh, "low")
        reads["low_alike"] = jnp.all(
            jnp.sort(ids, -1) == jnp.sort(low_ids, -1), axis=-1
        )
    return reads


def hold_layer(p, pf, probe, sh, kind):
    """One layer held to a program's readings at a few rows, both sides
    fed the SAME inputs (``probe``: the program's ``x_in``, ``x_mid [R,
    C]``, ``y_op``, ``h_mlp``, ``y_mlp [R, C]``; of a convolution layer
    ``conv_before [R, taps - 1, C]``, the program's state before each
    row; of an attention layer ``attn [R, heads * hd]``, ``positions
    [R]`` and the rows it landed, ``k_landed`` / ``v_landed [T, 512]``;
    of an expert layer ``experts`` / ``weights [R, top_k]``): per row,
    the relative error of the mixer (``op_err``: the convolution's
    output, or attention's before ``W_o``), of the FFN's normed input
    (``h_err``) and output (``y_err``), whether the experts are the
    reference's (``alike``) and how far their weights lie
    (``weight_err``); and the ``low_*`` readings: the reference itself
    in the precision below."""
    probe = {k: jnp.asarray(v) for k, v in probe.items()}
    return _hold(p, _arrays(pf), probe, _frozen(sh), pf.get("first"), kind)
