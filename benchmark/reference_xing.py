"""The plain reference for ``xing4-29b-a4b``: the language model's
forward pass in straightforward ``jax.numpy``, float32 under
``jax.default_matmul_precision("highest")`` — no cache, no absorption
(every key and value is up-projected and attended as written), no
batching, no kernels, no paging. It shares no code with ``dlrover_tpu``:
it reads the program's parameter tree (names and shapes below) and the
configuration file's published keys, and nothing else.

The residual is n = ``hc_mult`` streams, ``X [n, C]`` a token, ``X_0`` =
the embedding repeated n times. EACH sublayer F (attention, then the
MLP; parameters of its own: ``hc_attn`` / ``hc_mlp``) does

    x = RMSNorm(vec(X))                       over all n C entries
    H~pre = a_pre (x phi_pre) + b_pre   [n]     H_pre  = sigmoid(H~pre)
    H~post = a_post (x phi_post) + b_post [n]   H_post = 2 sigmoid(H~post)
    H~res = a_res mat(x phi_res) + b_res [n, n] H_res  = Sinkhorn(exp(clamp(H~res, -30, 30)))
    u = H_pre X  [C];   y = F(RMSNorm(u));   X' = H_res X + H_post^T y

(``phi`` holds the three side by side: n | n | n n columns; ``alpha`` is
``[a_pre, a_post, a_res]``.) After the last layer the n streams are
summed, then the final RMSNorm and the untied head.

Attention, ``h = RMSNorm(u)``, 32 heads:

    c_q = RMSNorm(W_qa h) [768];  [q_nope | q_r]_i = W_qb c_q  [128 + 64]
    [c | k_r] = W_kva h [512 + 64];  c = RMSNorm(c);  q_r, k_r rotated (one k_r for all heads)
    [k_nope | v]_i = W_kvb c  [128 + 128]
    s_i(t, j) = (q_nope,i(t) . k_nope,i(j) + q_r,i(t) . k_r(j)) 192^-1/2 m^2,  causal softmax
    o = W_o concat_i sum_j p_i(t, j) v_i(j)

The rotation is YaRN on the 32 frequency pairs of the 64-wide slice
(``rope_scaling``): each inverse frequency blended between itself and
itself / ``factor`` by the linear ramp between the pairs where
``beta_fast`` and ``beta_slow`` rotations fit into
``original_max_position_embeddings`` positions; ``m = 0.1 mscale_all_dim
ln(factor) + 1``, and the cos / sin factor ``mscale / mscale_all_dim`` is
1. What a cache would hold of a token is ``c`` after its norm and ``k_r``
after its rotation (``cache_rows0`` of :func:`forward_at`).

MLP. Layers below ``first_k_dense_replace``: SwiGLU of width
``intermediate_size``. Every other layer: ``s = sigmoid(W_r h)`` over the
64 routed experts, the 4 largest of ``s + b`` (``n_group`` =
``topk_group`` = 1: no group limit), weights ``s`` at the chosen over
their sum (``norm_topk_prob``) times ``routed_scaling_factor``; ``y = sum
w_e SwiGLU_e(h) + SwiGLU_shared(h)``. Every expert computes every token
and the router's weights, zero off the chosen, pick. The multi-token-
prediction block is not built: the main path's logits do not depend on
it.

Computed in blocks of queries (attention) and of tokens and experts
(MLP) so that a 17k-token sequence fits beside the bf16 weights, one
layer upcast to float32 at a time; the blocks change no sum's terms.

Assumed, because the published config does not say (the configuration
file lists each under ``assumed`` with the same words):
- ``hc_eps`` is added to the Sinkhorn denominators; row before column;
  20 rounds of row-normalise then column-normalise;
- the output is the SUM of the streams;
- alpha drawn as 1.0 and b, phi from the seed, both at scale 0.35 so
  that the map logits have std 0.5 and 20 Sinkhorn rounds converge for
  every token (the paper initialises alpha small so that training starts
  at the identity: at that value the dynamic term would be rounding
  noise and no comparison could see it);
- the repo's half-split pairing of the rotation (a relabelling of
  ``W_qb`` / ``W_kva`` columns at random weights).
Departures, the repo's own: RMSNorm with a ``(1 + scale)`` gain and eps
1e-6 (zero-initialised scales: the same function as a plain gain at
these weights); ``head_dim`` 112 in the configuration file is read by no
layer.

Beside the forward pass: :func:`hold_layer` holds one layer's residual
mixes and its MLP to what a program computed for a few rows (both sides
fed the same inputs), and ``low=True`` computes the same formulas in the
precision below the configuration's (float8 operands where the
configuration says bfloat16, bfloat16 where it says float32), for the
second reading that every limit of ``runners/serve_latent.py`` is set
from. Neither changes the forward pass above.

Parameter tree (``models/latent_lm.py``): ``embed [V, C]``, ``lm_head
[C, V]``, ``final_norm [C]``; ``layers`` with a leading layer axis:
``attn_norm, mlp_norm [L, C]``, ``w_qa [L, C, 768]``, ``q_norm [L,
768]``, ``w_qb [L, 768, 32, 192]``, ``w_kva [L, C, 576]``, ``kv_norm [L,
512]``, ``w_kvb [L, 512, 32, 256]`` (k_nope | v), ``wo [L, 32, 128,
C]``, ``hc_attn`` / ``hc_mlp``: ``norm [L, n C]``, ``phi [L, n C, 2 n +
n n]``, ``bias [L, 2 n + n n]``, ``alpha [L, 3]``; ``dense``: ``w_gu
[Ld, C, 2 F]`` (gate | up), ``w_down [Ld, F, C]``; ``moe``: ``router
[Lm, C, E]``, ``router_bias [Lm, E]``, ``w_gu [Lm E, C, 2 f]``,
``w_down [Lm E, f, C]`` (expert ``e`` of expert layer ``l`` at ``l E +
e``), ``shared_gu [Lm, C, 2 f]``, ``shared_down [Lm, f, C]``.
"""

import functools
import math

import jax
import jax.numpy as jnp

NORM_EPS = 1e-6
Q_BLOCK = 256        # queries an attention block
TOKEN_BLOCK = 1024   # tokens an expert block
EXPERT_BLOCK = 16    # experts an expert block


def shape_of(cfg_json):
    """The numbers the reference needs, from the published keys."""
    scaling = cfg_json["rope_scaling"]
    if scaling["type"] != "yarn" or cfg_json["scoring_func"] != "sigmoid":
        raise ValueError("the reference is YaRN + a sigmoid router")
    if cfg_json["n_group"] != 1 or cfg_json["topk_group"] != 1:
        raise ValueError("the reference has no group-limited routing")
    if not cfg_json["norm_topk_prob"]:
        raise ValueError("the reference renormalises its top-k")
    return {
        "h": cfg_json["num_attention_heads"],
        "r": cfg_json["kv_lora_rank"],
        "nope": cfg_json["qk_nope_head_dim"],
        "rope": cfg_json["qk_rope_head_dim"],
        "v": cfg_json["v_head_dim"],
        "n": cfg_json["hc_mult"],
        "sinkhorn": cfg_json["hc_sinkhorn_iters"],
        "hc_eps": cfg_json["hc_eps"],
        "clamp": (cfg_json["mhc_h_res_clamp_min"],
                  cfg_json["mhc_h_res_clamp_max"]),
        "first_dense": cfg_json["first_k_dense_replace"],
        "layers": cfg_json["num_hidden_layers"],
        "experts": cfg_json["n_routed_experts"],
        "top_k": cfg_json["num_experts_per_tok"],
        "scaling": float(cfg_json["routed_scaling_factor"]),
        "theta": float(cfg_json["rope_theta"]),
        "factor": float(scaling["factor"]),
        "original": scaling["original_max_position_embeddings"],
        "beta_fast": scaling["beta_fast"], "beta_slow": scaling["beta_slow"],
        "mscale": 0.1 * scaling["mscale_all_dim"]
        * math.log(scaling["factor"]) + 1.0,
    }


def _norm(x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + NORM_EPS) * (1.0 + scale)


def yarn_inv_freq(sh):
    """The 32 inverse frequencies of the rotated slice."""
    dim = sh["rope"]

    def pair_with(turns):
        return dim * math.log(
            sh["original"] / (turns * 2 * math.pi)
        ) / (2 * math.log(sh["theta"]))

    low = max(math.floor(pair_with(sh["beta_fast"])), 0)
    high = min(math.ceil(pair_with(sh["beta_slow"])), dim // 2 - 1)
    pair = jnp.arange(dim // 2, dtype=jnp.float32)
    inv = 1.0 / (sh["theta"] ** (2.0 * pair / dim))
    ramp = jnp.clip((pair - low) / max(high - low, 1e-3), 0.0, 1.0)
    return inv / sh["factor"] * ramp + inv * (1.0 - ramp)


def _rope(x, sh, positions):
    """x: [s, heads, rope] at ``positions``, half-split pairs."""
    hd = x.shape[-1]
    ang = positions.astype(jnp.float32)[:, None] * yarn_inv_freq(sh)[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )


def fp8(a):
    """``a`` as float8 (e4m3, one scale a tensor) would hold it: the
    precision below bfloat16, for the readings that set the limits."""
    scale = jnp.max(jnp.abs(a)) / 448.0 + 1e-30
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def bf16(a):
    """``a`` as bfloat16 would hold it: the precision below float32.
    (``reduce_precision`` and not a cast there and back: inside a jitted
    function a TPU's compiler may skip the pair of casts, and did: my
    chip run, PR 38.)"""
    return jax.lax.reduce_precision(
        a.astype(jnp.float32), exponent_bits=8, mantissa_bits=7
    )


# -- the residual -------------------------------------------------------------


def sinkhorn(m, rounds, eps):
    for _ in range(rounds):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def residual_maps(hp, streams, sh, low=False):
    """``streams [T, n, C]`` -> ``H_pre [T, n]``, ``H_post [T, n]``,
    ``H_res [T, n, n]`` of one sublayer (``hp``: its ``norm``, ``phi``,
    ``bias``, ``alpha``). ``low``: the normed streams and ``phi`` held
    in bfloat16."""
    n = sh["n"]
    x = _norm(streams.reshape(streams.shape[0], -1), hp["norm"])
    phi = hp["phi"]
    if low:
        x, phi = bf16(x), bf16(phi)
    raw = x @ phi
    a_pre, a_post, a_res = hp["alpha"][0], hp["alpha"][1], hp["alpha"][2]
    pre = a_pre * raw[:, :n] + hp["bias"][:n]
    post = a_post * raw[:, n:2 * n] + hp["bias"][n:2 * n]
    res = a_res * raw[:, 2 * n:] + hp["bias"][2 * n:]
    res = jnp.clip(res.reshape(-1, n, n), *sh["clamp"])
    return (
        jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post),
        sinkhorn(jnp.exp(res), sh["sinkhorn"], sh["hc_eps"]),
    )


def residual_read(maps, streams):
    """``u = H_pre X`` (products and sums in float32: no matmul unit)."""
    return jnp.sum(maps[0][:, :, None] * streams, axis=1)


def residual_write(maps, streams, y):
    """``X' = H_res X + H_post^T y``."""
    mixed = jnp.sum(
        maps[2][:, :, :, None] * streams[:, None, :, :], axis=2
    )
    return mixed + maps[1][:, :, None] * y[:, None, :]


# -- attention ----------------------------------------------------------------


def cached_scores(q, rows):
    """One token's absorbed queries ``q [heads, 576]`` against cache rows
    ``rows [T, 576]``, before the scale: float32 products and sums of
    the operands AS GIVEN (a program's own bfloat16 queries and rows, so
    that what is read is the arithmetic of its scores and not the
    rounding of their operands)."""
    return jnp.einsum(
        "hw,tw->ht", q.astype(jnp.float32), rows.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )


def attention_inputs(p, h, sh, positions):
    """``q [T, 32, 192]`` (q_nope | rotated q_r), the latent ``c [T,
    512]`` after its norm and ``k_r [T, 64]`` after its rotation."""
    nope, r = sh["nope"], sh["r"]
    c_q = _norm(h @ p["w_qa"], p["q_norm"])
    q = jnp.einsum("tr,rhk->thk", c_q, p["w_qb"])
    q = jnp.concatenate(
        [q[..., :nope], _rope(q[..., nope:], sh, positions)], axis=-1
    )
    kva = h @ p["w_kva"]
    c = _norm(kva[:, :r], p["kv_norm"])
    k_r = _rope(kva[:, None, r:], sh, positions)[:, 0]
    return q, c, k_r


def keys_values(p, c, k_r, sh):
    """Every head's keys ``[T, 32, 192]`` and values ``[T, 32, 128]``."""
    kv = jnp.einsum("tr,rhk->thk", c, p["w_kvb"])
    k = jnp.concatenate([
        kv[..., :sh["nope"]],
        jnp.broadcast_to(k_r[:, None, :], kv.shape[:2] + (k_r.shape[-1],)),
    ], axis=-1)
    return k, kv[..., sh["nope"]:]


def _attend(q, k, v, mask, sh, low=False):
    """q [tq, h, 192], k [T, h, 192], v [T, h, 128], mask [tq, T]."""
    if low:
        q, k, v = fp8(q), fp8(k), fp8(v)
    scale = sh["mscale"] ** 2 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("qhk,thk->hqt", q, k) * scale
    probs = jax.nn.softmax(jnp.where(mask[None], logits, -jnp.inf), axis=-1)
    return jnp.einsum("hqt,thv->qhv", probs, v)


def causal_attention(q, k, v, sh):
    """All queries of a sequence, a block at a time: ``[T, 32, 128]``."""
    t = q.shape[0]
    block = min(Q_BLOCK, t)
    assert t % block == 0, (t, block)

    def one(start):
        rows = start + jnp.arange(block)
        causal = jnp.arange(t)[None, :] <= rows[:, None]
        return _attend(
            jax.lax.dynamic_slice_in_dim(q, start, block, axis=0), k, v,
            causal, sh,
        )

    out = jax.lax.map(one, jnp.arange(0, t, block))
    return out.reshape((t,) + out.shape[2:])


def attention_at(q, k, v, rows, sh, low=False):
    """The queries at ``rows`` alone over their causal keys."""
    causal = jnp.arange(k.shape[0])[None, :] <= rows[:, None]
    return _attend(q[rows], k, v, causal, sh, low)


# -- the MLP ------------------------------------------------------------------


def _swiglu(x, w_gu, w_down):
    f = w_down.shape[-2]
    gu = x @ w_gu
    return (jax.nn.silu(gu[:, :f]) * gu[:, f:]) @ w_down


def route(pm, h, sh, variant=None):
    """Sigmoid scores ``[T, E]``, the chosen experts ``[T, top_k]`` (best
    first) and the dense weights ``[T, E]``, zero off the chosen.
    ``variant`` (``controls_xing.py``): ``"low"`` the router in
    bfloat16."""
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
    return scores, ids, dense


def experts(pm, h, dense_weights, low=False):
    """``sum_e w_e SwiGLU_e(h)`` with every expert computing every token,
    a block of tokens and of experts at a time. ``pm``: ``w_gu [groups,
    C, 2 f]``, ``w_down [groups, f, C]`` as the tree stores them (ALL
    expert layers' experts, in the tree's own dtype) and ``first``, the
    group of this layer's expert 0: a block of experts is cut out and
    upcast when its turn comes, so that no float32 copy of a layer's 64
    experts (2.9 GB) stands beside the sequence's streams."""
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
    """One layer's MLP on its normed input ``h [T, C]``: the dense SwiGLU
    (``pf`` with ``w_gu`` alone) or the expert layer -> (``y``, chosen
    experts or None, their weights or None)."""
    if "router" not in pf:
        if low:
            h, pf = fp8(h), {k: fp8(v) for k, v in pf.items()}
        return _swiglu(h, pf["w_gu"], pf["w_down"]), None, None
    _, ids, dense = route(pf, h, sh)
    shared = (
        _swiglu(fp8(h), fp8(pf["shared_gu"]), fp8(pf["shared_down"]))
        if low else _swiglu(h, pf["shared_gu"], pf["shared_down"])
    )
    weights = jnp.take_along_axis(dense, ids, axis=-1)
    return experts(pf, h, dense, low) + shared, ids, weights


# -- the layer and the forward pass -------------------------------------------


def layer_weights(params, layer, sh):
    """Layer ``layer``'s leaves upcast to float32: (``p`` of
    ``params["layers"]``, ``pf`` its MLP's: the dense one's, or the
    expert layer's router, bias and shared expert with the experts' stack
    as the tree holds it and ``first``, where this layer's begin:
    :func:`experts` upcasts them a block at a time)."""
    up = lambda a: a.astype(jnp.float32)  # noqa: E731
    p = jax.tree_util.tree_map(lambda a: up(a[layer]), params["layers"])
    if layer < sh["first_dense"]:
        pf = {k: up(v[layer]) for k, v in params["dense"].items()}
    else:
        at = layer - sh["first_dense"]
        pf = {
            k: v if k in ("w_gu", "w_down") else up(v[at])
            for k, v in params["moe"].items()
        }
        pf["first"] = at * sh["experts"]
    return p, pf


@functools.partial(jax.jit, static_argnames=("sh_items",))
def _attention_sublayer(p, streams, rows, sh_items):
    sh = dict(sh_items)
    t = streams.shape[0]
    positions = jnp.arange(t)
    maps = residual_maps(p["hc_attn"], streams, sh)
    h = _norm(residual_read(maps, streams), p["attn_norm"])
    q, c, k_r = attention_inputs(p, h, sh, positions)
    k, v = keys_values(p, c, k_r, sh)
    out = causal_attention(q, k, v, sh)
    y = jnp.einsum("thv,hvc->tc", out, p["wo"])
    return residual_write(maps, streams, y), {
        "attn": out[rows].reshape(rows.shape[0], -1),
        "low_attn": attention_at(q, k, v, rows, sh, low=True).reshape(
            rows.shape[0], -1
        ),
        "cache_rows": jnp.concatenate([c, k_r], axis=-1),
    }


@functools.partial(jax.jit, static_argnames=("sh_items", "first"))
def _mlp_sublayer(p, pf, streams, sh_items, first=None):
    sh = dict(sh_items)
    if first is not None:
        pf = dict(pf, first=first)
    maps = residual_maps(p["hc_mlp"], streams, sh)
    h = _norm(residual_read(maps, streams), p["mlp_norm"])
    y, ids, _ = mlp(pf, h, sh)
    return residual_write(maps, streams, y), ids


def _frozen(sh):
    return tuple(sorted(sh.items()))


def _arrays(pf):
    """``pf`` without ``first`` (a Python int: a jitted program takes it
    as a static argument beside the arrays)."""
    return {k: v for k, v in pf.items() if k != "first"}


def forward_at(params, tokens, rows, cfg_json, probes=None):
    """The whole sequence ``tokens [T]`` through every layer, free
    running: float32 ``logits [R, V]`` at ``rows``; of layer 0 the
    attention output before ``W_o`` at ``rows`` (``attn0 [R, 32 * 128]``,
    and ``low_attn0``: the same with float8 operands) and the cache rows
    of ALL tokens (``cache_rows0 [T, 576]``: ``c`` after its norm, ``k_r``
    after its rotation); the experts every expert layer chose at ``rows``
    (``ids [Lm, R, top_k]``). ``probes`` (a list, a layer, of what a
    program read at ``rows``): each layer is also held to it
    (:func:`hold_layer`; ``held``: a list of dicts of ``[R]`` arrays)."""
    sh = shape_of(cfg_json)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        streams = jnp.repeat(x[:, None, :], sh["n"], axis=1)
        out = {"held": [], "ids": []}
        for layer in range(sh["layers"]):
            p, pf = layer_weights(params, layer, sh)
            if probes is not None:
                out["held"].append(hold_layer(p, pf, probes[layer], sh))
            streams, reads = _attention_sublayer(p, streams, rows, _frozen(sh))
            if layer == 0:
                out.update(
                    attn0=reads["attn"], low_attn0=reads["low_attn"],
                    cache_rows0=reads["cache_rows"],
                )
            streams, ids = _mlp_sublayer(
                p, _arrays(pf), streams, _frozen(sh), pf.get("first")
            )
            if ids is not None:
                out["ids"].append(ids[rows])
        final = _norm(
            jnp.sum(streams[rows], axis=1),
            params["final_norm"].astype(jnp.float32),
        )
        out["logits"] = final @ params["lm_head"].astype(jnp.float32)
    return out


def _rel(got, want):
    axes = tuple(range(1, got.ndim))
    return jnp.sqrt(jnp.sum(jnp.square(got - want), axes)) / jnp.sqrt(
        jnp.sum(jnp.square(want), axes) + 1e-30
    )


@functools.partial(jax.jit, static_argnames=("sh_items", "first"))
def _hold(p, pf, probe, sh_items, first=None):
    sh = dict(sh_items)
    if first is not None:
        pf = dict(pf, first=first)
    reads = {}
    # The residual: both mixes on the program's own streams and sublayer
    # outputs, and how far its H_res is from doubly stochastic.
    for name, hp, x, y, x_next in (
        ("attn", p["hc_attn"], probe["x_in"], probe["y_attn"], probe["x_mid"]),
        ("mlp", p["hc_mlp"], probe["x_mid"], probe["y_mlp"], probe["x_out"]),
    ):
        res = probe["res_" + name]
        reads["stochastic_" + name] = jnp.maximum(
            jnp.max(jnp.abs(jnp.sum(res, -1) - 1.0), -1),
            jnp.max(jnp.abs(jnp.sum(res, -2) - 1.0), -1),
        )
        # against the CHANGE the sublayer makes, X' - X
        want = residual_write(residual_maps(hp, x, sh), x, y) - x
        reads["mix_" + name] = _rel(x_next - x, want)
        reads["low_mix_" + name] = _rel(
            residual_write(residual_maps(hp, x, sh, low=True), x, y) - x,
            want,
        )
    # The MLP's normed input: H_pre, the read and the sublayer's norm.
    maps = residual_maps(p["hc_mlp"], probe["x_mid"], sh)
    h = _norm(residual_read(maps, probe["x_mid"]), p["mlp_norm"])
    reads["h_err"] = _rel(probe["h_mlp"], h)
    # The MLP on the PROGRAM's normed input.
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
        _, low_ids, _ = route(pf, probe["h_mlp"], sh, "low")
        reads["low_alike"] = jnp.all(
            jnp.sort(ids, -1) == jnp.sort(low_ids, -1), axis=-1
        )
    return reads


def hold_layer(p, pf, probe, sh):
    """One layer held to a program's readings at a few rows, both sides
    fed the SAME inputs (``probe``: the program's ``x_in``, ``x_mid``,
    ``x_out [R, n, C]``, ``y_attn``, ``y_mlp [R, C]``, ``res_attn``,
    ``res_mlp [R, n, n]``, ``h_mlp [R, C]`` and, of an expert layer,
    ``experts`` / ``weights [R, top_k]``): per row, the relative error of
    each residual mix's CHANGE ``X' - X`` (``mix_attn``, ``mix_mlp``),
    of the MLP's normed input (``h_err``) and of its output (``y_err``),
    how far the program's ``H_res`` is from doubly stochastic
    (``stochastic_*``), whether the experts are the reference's
    (``alike``) and how far their weights lie (``weight_err``); and the
    ``low_*`` readings: the reference itself in the precision below."""
    probe = {k: jnp.asarray(v) for k, v in probe.items()}
    return _hold(p, _arrays(pf), probe, _frozen(sh), pf.get("first"))
