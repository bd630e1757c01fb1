"""The plain reference for ``keye-vl2-30b-a3b``: the language model's
forward pass in straightforward ``jax.numpy``, float32 under
``jax.default_matmul_precision("highest")`` — no cache, no batching, no
kernels, no paging. It shares no code with ``dlrover_tpu``: it reads the
program's parameter tree (names and shapes below) and the configuration
file's published keys, and nothing else.

One layer, ``h = n1(x)`` (RMSNorm), for a sequence of T tokens:

    q = rope(qnorm(Wq h))  [32 x 128]   k = rope(knorm(Wk h)), v = Wv h  [4 x 128]
    qI = rope(WqI h) [16 x 64]   kI = rope(LayerNorm(WkI h)) [64]   w = Ww h [16]
    I(t, s) = sum_j w[t, j] relu(qI[t, j] . kI[s]) * 64^-1/2 * 16^-1/2      (s <= t)
    S_t = the topk (2,048) positions s <= t of largest I(t, s); all of them while t < topk
    o_t = softmax_{s in S_t}(q_t . k_s / sqrt(128)) v_s,  8 query heads a KV head
    x  = x + Wo o
    p = softmax(Wr n2(x)) over 128 experts; the 8 largest, renormalised over the 8
    x  = x + sum_e p_e Wdown_e (silu(Wgate_e n2(x)) * Wup_e n2(x))

Dense ``[t, s]`` index scores and an explicit top-k mask (the k-th
largest score of a row by ``lax.top_k``, then ``score > it`` plus the
ties at it by lower position); every expert computes every token and
the router's weights, zero off the top 8, pick. Computed in blocks of
queries (attention) and of tokens and experts (MLP) so that a
33k-token sequence fits beside the bf16 weights, one layer upcast to
float32 at a time; the blocks change no sum's terms.

Assumed, because the published config does not say (the configuration
file lists each under ``assumed`` with the same words):
- per-head RMSNorm on q and k over ``head_dim``, before the rotation
  (the convention of the 48-layer, 128-expert, top-8 decoder family the
  language model extends);
- the index projections read ``h``, the same normed input as q/k/v;
- the index key passes a LayerNorm (scale 1, bias 0 at these weights);
- the main heads' rotation (``rope_theta``, half-split) is applied to
  ``qI`` and ``kI`` over the whole index head;
- float32 index scores; ties broken by lower position.
Departures, the repo's own: RMSNorm with a ``(1 + scale)`` gain and eps
1e-6 (zero-initialised scales: the same function as a plain gain at
these weights); ``mrope_section`` is not used: the cell is text-only,
the three position streams are equal and the rotation IS one-dimensional
RoPE; the vision tower is not built.

Beside the forward pass: :func:`probe_rows` holds one layer to what a
program computed for a few rows (both sides fed the same inputs), and
``low=True`` on :func:`index_scores`, :func:`route` and :func:`experts`
(and :func:`fp8`) computes the same formulas in the precision below the
configuration's, for the second reading that every limit of
``runners/serve_sparse.py`` is set from. Neither changes the forward
pass above.

Parameter tree (``models/sparse_lm.py``): ``embed [V, d]``, ``lm_head
[d, V]``, ``final_norm [d]``, ``layers`` with a leading layer axis:
``attn_norm, mlp_norm [L, d]``, ``wqkv [L, d, h + 2 kh, hd]`` (q heads,
then k, then v), ``q_norm, k_norm [L, hd]``, ``wo [L, h, hd, d]``,
``w_idx [L, d, hi * di + di + hi]`` (index queries | index key | head
weights), ``ik_norm_scale, ik_norm_bias [L, di]``, ``router [L, d, E]``,
``w_gu [L * E, d, 2 f]`` (gate | up), ``w_down [L * E, f, d]``: the
experts of all layers in one stack, expert ``e`` of layer ``l`` at ``l *
E + e``.
"""

import functools

import jax
import jax.numpy as jnp

NORM_EPS = 1e-6
Q_BLOCK = 256        # queries an attention block
TOKEN_BLOCK = 1024   # tokens an expert block
EXPERT_BLOCK = 16    # experts an expert block


def shape_of(cfg_json):
    """The numbers the reference needs, from the published keys."""
    sa = cfg_json["sa_config"]
    return {
        "h": cfg_json["num_attention_heads"],
        "kh": cfg_json["num_key_value_heads"],
        "hi": sa["indexer_num_heads"], "di": sa["indexer_head_dim"],
        "topk": sa["topk"], "top_k": cfg_json["num_experts_per_tok"],
        "f": cfg_json["moe_intermediate_size"],
        "theta": float(cfg_json["rope_theta"]),
    }


def _norm(x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + NORM_EPS) * (1.0 + scale)


def _layer_norm(x, scale, bias):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + NORM_EPS) * scale + bias


def _rope(x, theta, positions=None):
    """x: [s, heads, hd] at ``positions`` (0..s-1 when None),
    half-split pairs."""
    s, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    if positions is None:
        positions = jnp.arange(s)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )


def attention_inputs(p, x, sh, positions=None):
    """q [T, h, hd], k, v [T, kh, hd], qI [T, hi, di], kI [T, di],
    w [T, hi] of one layer ``p`` (float32) over the rows ``x`` (a whole
    sequence, or rows at ``positions``)."""
    h, kh, hi, di = sh["h"], sh["kh"], sh["hi"], sh["di"]
    rope = lambda a: _rope(a, sh["theta"], positions)  # noqa: E731
    n = _norm(x, p["attn_norm"])
    qkv = jnp.einsum("sd,dhk->shk", n, p["wqkv"])
    q = rope(_norm(qkv[:, :h], p["q_norm"]))
    k = rope(_norm(qkv[:, h:h + kh], p["k_norm"]))
    v = qkv[:, h + kh:]
    idx = n @ p["w_idx"]
    q_idx = rope(idx[:, :hi * di].reshape(-1, hi, di))
    k_idx = _layer_norm(
        idx[:, hi * di:hi * di + di], p["ik_norm_scale"], p["ik_norm_bias"]
    )
    k_idx = rope(k_idx[:, None, :])[:, 0]
    return q, k, v, q_idx, k_idx, idx[:, hi * di + di:]


def index_scores(q_idx, w, k_idx, low=False):
    """I(t, s) for the queries given, [tq, T]; no mask. ``low``: formed
    and summed in bfloat16, the precision below the float32 the
    configuration assumes (for the readings that set the limits)."""
    hi, di = q_idx.shape[1:]
    if low:
        bf = jnp.bfloat16
        q_idx, w, k_idx = q_idx.astype(bf), w.astype(bf), k_idx.astype(bf)
    kw = {"preferred_element_type": q_idx.dtype}
    dots = jax.nn.relu(jnp.einsum("qjd,sd->qjs", q_idx, k_idx, **kw))
    scores = jnp.einsum("qjs,qj->qs", dots, w, **kw)
    return (scores * (di ** -0.5 * hi ** -0.5)).astype(jnp.float32)


def topk_mask(scores, causal, topk):
    """Rows' ``topk`` largest causal scores as a mask, ties at the k-th
    by lower position; every causal position while a row has fewer."""
    masked = jnp.where(causal, scores, -jnp.inf)
    kth = jax.lax.top_k(masked, min(topk, masked.shape[-1]))[0][:, -1:]
    above = masked > kth
    tied = causal & (masked == kth) & jnp.isfinite(kth)
    missing = topk - jnp.sum(above, axis=-1, keepdims=True)
    return (above | (tied & (jnp.cumsum(tied, axis=-1) <= missing))) & causal


def _attend(q, k, v, mask):
    """q [tq, h, hd], k, v [T, kh, hd], mask [tq, T] -> [tq, h, hd]."""
    g = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    logits = jnp.einsum("qhk,thk->hqt", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1])
    )
    probs = jax.nn.softmax(jnp.where(mask[None], logits, -jnp.inf), axis=-1)
    return jnp.einsum("hqt,thk->qhk", probs, v)


def sparse_attention(q, k, v, q_idx, k_idx, w, topk, dense=False):
    """All queries of a sequence, a block at a time. ``dense``: attend
    over every causal position (what the selection is compared with)."""
    t = q.shape[0]
    block = min(Q_BLOCK, t)
    assert t % block == 0, (t, block)
    starts = jnp.arange(0, t, block)

    def one(start):
        rows = start + jnp.arange(block)
        causal = jnp.arange(t)[None, :] <= rows[:, None]
        take = lambda a: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            a, start, block, axis=0
        )
        mask = causal if dense else topk_mask(
            index_scores(take(q_idx), take(w), k_idx), causal, topk
        )
        return _attend(take(q), k, v, mask)

    out = jax.lax.map(one, starts)
    return out.reshape((t,) + out.shape[2:])


def fp8(a):
    """``a`` as float8 (e4m3, one scale a tensor) would hold it: the
    precision below bfloat16, for the readings that set the limits."""
    scale = jnp.max(jnp.abs(a)) / 448.0 + 1e-30
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def route(p, x, sh, low=False):
    """Router over ``x [T, d]`` (already normed): probabilities ``[T,
    E]``, the chosen experts' ids ``[T, top_k]`` (best first) and each
    token's relative gap between its 8th and 9th probability (a small
    one can flip under bf16). ``low``: logits formed in bfloat16."""
    if low:
        bf = jnp.bfloat16
        logits = jnp.dot(
            x.astype(bf), p["router"].astype(bf), preferred_element_type=bf
        ).astype(jnp.float32)
    else:
        logits = x @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    top, ids = jax.lax.top_k(probs, sh["top_k"] + 1)
    gap = (top[:, -2] - top[:, -1]) / top[:, -2]
    return probs, ids[:, :-1], gap


def experts(p, x, sh, low=False):
    """The expert layer's output for ``x [T, d]`` (already normed), and
    the routing as :func:`route` gives it. ``low``: the router in
    bfloat16, weights and activations of the experts in float8."""
    t, d = x.shape
    f, top_k = sh["f"], sh["top_k"]
    n_exp = p["router"].shape[-1]
    probs, ids, gap = route(p, x, sh, low)
    keep = jnp.zeros(probs.shape, bool).at[
        jnp.arange(t)[:, None], ids
    ].set(True)
    weights = jnp.where(keep, probs, 0.0)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    cast = fp8 if low else (lambda a: a)
    tb, eb = min(TOKEN_BLOCK, t), min(EXPERT_BLOCK, n_exp)
    assert t % tb == 0 and n_exp % eb == 0, (t, tb, n_exp, eb)

    def token_block(start):
        xb = cast(jax.lax.dynamic_slice_in_dim(x, start, tb, axis=0))
        wb = jax.lax.dynamic_slice_in_dim(weights, start, tb, axis=0)

        def expert_block(acc, e0):
            w_gu = jax.lax.dynamic_slice_in_dim(p["w_gu"], e0, eb, axis=0)
            w_dn = jax.lax.dynamic_slice_in_dim(p["w_down"], e0, eb, axis=0)
            gu = jnp.einsum("td,edf->etf", xb, cast(w_gu))
            act = cast(jax.nn.silu(gu[..., :f]) * gu[..., f:])
            ys = jnp.einsum("etf,efd->etd", act, cast(w_dn))
            we = jax.lax.dynamic_slice_in_dim(wb, e0, eb, axis=1)
            return acc + jnp.einsum("etd,te->td", ys, we), None

        acc, _ = jax.lax.scan(
            expert_block, jnp.zeros((tb, d), jnp.float32),
            jnp.arange(0, n_exp, eb),
        )
        return acc

    out = jax.lax.map(token_block, jnp.arange(0, t, tb))
    return out.reshape(t, d), {
        "ids": ids, "gap": gap, "weights": weights, "probs": probs,
    }


def _rel(got, want):
    """Row-wise ``|got - want| / |want|`` over all but the first axis."""
    axes = tuple(range(1, got.ndim))
    return jnp.sqrt(jnp.sum(jnp.square(got - want), axes)) / jnp.sqrt(
        jnp.sum(jnp.square(want), axes) + 1e-30
    )


def probe_rows(p, sh, k, v, k_idx, rows, margin):
    """One layer held to what a PROGRAM computed for a few rows of the
    sequence, both sides fed the same inputs: ``rows`` holds, for R
    rows at ``pos [R]``, the program's layer input ``x_in [R, d]``, its
    selection ``mask [R, T]``, its attention output ``attn [R, h,
    hd]``, its input to the expert half ``x_mid [R, d]``, its routing
    ``ids`` / ``weights [R, top_k]`` and the expert half's output ``y
    [R, d]``. The keys, values and index keys are the reference's own
    chain's (``k``, ``v``, ``k_idx`` of all T rows). Returns per row:
    the keys the program holds, their share inside the reference's
    top-(topk + margin) and top-topk, the attention output's error
    against the reference attending over the PROGRAM's keys, whether the
    program's experts are the reference's, how far (relative, in the
    reference's probabilities) the program's least likely expert lies
    below the reference's 8th, the weights' largest difference and the
    expert output's error; and, under ``low_``, what the reference
    itself reads on the same yardsticks when computed in the precision
    below the configuration's (bfloat16 index scores and router, float8
    attention and expert operands)."""
    topk, top_k = sh["topk"], sh["top_k"]
    pos = rows["pos"]
    q, _, _, q_idx, _, w = attention_inputs(p, rows["x_in"], sh, pos)
    causal = jnp.arange(k.shape[0])[None, :] <= pos[:, None]
    scores = index_scores(q_idx, w, k_idx)
    wide = topk_mask(scores, causal, topk + margin)
    exact = topk_mask(scores, causal, topk)
    mask = rows["mask"] & causal

    def shares(m):
        n = jnp.maximum(jnp.sum(m, -1), 1)
        return jnp.sum(m & wide, -1) / n, jnp.sum(m & exact, -1) / n

    attn = _attend(q, k, v, mask)
    h = _norm(rows["x_mid"], p["mlp_norm"])
    y, r = experts(p, h, sh)
    probs = r["probs"]
    sort = lambda a: jnp.sort(a, axis=-1)  # noqa: E731
    alike = jnp.all(sort(rows["ids"]) == sort(r["ids"]), axis=-1)
    eighth = jnp.take_along_axis(probs, r["ids"][:, -1:], axis=-1)[:, 0]

    def swap_gap(ids):
        """How far the least likely of ``ids`` lies below the 8th."""
        least = jnp.min(jnp.take_along_axis(probs, ids, axis=-1), -1)
        return (eighth - least) / eighth

    by_expert = jnp.zeros(probs.shape, jnp.float32).at[
        jnp.arange(pos.shape[0])[:, None], rows["ids"]
    ].set(rows["weights"].astype(jnp.float32))
    share_wide, share_exact = shares(mask)
    # the same yardsticks for the reference in the precision below
    low_mask = topk_mask(
        index_scores(q_idx, w, k_idx, low=True), causal, topk
    )
    low_y, low_r = experts(p, h, sh, low=True)
    return {
        "n_keys": jnp.sum(mask, -1),
        "share_wide": share_wide, "share_exact": share_exact,
        "attn_err": _rel(rows["attn"], attn),
        "alike": alike,
        "swap_gap": swap_gap(rows["ids"]),
        "router_gap": r["gap"],
        "weight_err": jnp.max(jnp.abs(by_expert - r["weights"]), -1),
        "y_err": _rel(rows["y"], y),
        "low_share_wide": shares(low_mask)[0],
        "low_share_exact": shares(low_mask)[1],
        "low_attn_err": _rel(_attend(fp8(q), fp8(k), fp8(v), mask), attn),
        "low_alike": jnp.all(sort(low_r["ids"]) == sort(r["ids"]), -1),
        "low_swap_gap": swap_gap(low_r["ids"]),
        "low_y_err": _rel(low_y, y),
    }


def layer(p, x, sh, dense=False, rows=None, margin=0):
    """One decoder layer over ``x [T, d]``; ``p`` float32, no layer
    axis. Returns the output, the routing (:func:`experts`) and, with
    ``rows``, :func:`probe_rows`' readings."""
    q, k, v, q_idx, k_idx, w = attention_inputs(p, x, sh)
    attn = sparse_attention(q, k, v, q_idx, k_idx, w, sh["topk"], dense)
    x = x + jnp.einsum("qhk,hkd->qd", attn, p["wo"])
    y, routing = experts(p, _norm(x, p["mlp_norm"]), sh)
    read = None if rows is None else probe_rows(
        p, sh, k, v, k_idx, rows, margin
    )
    return x + y, routing, read


@functools.partial(jax.jit, static_argnames=("sh", "dense", "margin"))
def _layer_jit(p, x, sh, dense=False, rows=None, margin=0):
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
        return layer(p, x, dict(sh), dense, rows, margin)


def layer_of(params, i):
    """Layer ``i``'s leaves, its experts cut out of the stack."""
    layers = params["layers"]
    n_exp = layers["router"].shape[-1]
    return {
        k: v[i * n_exp:(i + 1) * n_exp] if k in ("w_gu", "w_down") else v[i]
        for k, v in layers.items()
    }


def forward_at(params, tokens, positions, cfg_json, dense=False,
               rows=None, margin=0):
    """The forward pass of one sequence ``tokens [T]``: float32 logits
    ``[len(positions), V]`` at the given positions, the experts chosen
    there ``[L, len(positions), top_k]`` and their router gaps, the
    embedding there (layer 0's input) and, with ``rows`` (a
    list of :func:`probe_rows`' inputs, one a layer), each layer's
    readings. One layer's program, called a layer at a time (the upcast
    weights of one layer live at once)."""
    sh = tuple(sorted(shape_of(cfg_json).items()))
    x = params["embed"][tokens].astype(jnp.float32)
    embedded = x[positions]
    ids, gaps, reads = [], [], []
    n_layers = params["layers"]["wqkv"].shape[0]
    for i in range(n_layers):
        x, routing, read = _layer_jit(
            layer_of(params, i), x, sh, dense,
            None if rows is None else rows[i], margin,
        )
        ids.append(routing["ids"][positions])
        gaps.append(routing["gap"][positions])
        reads.append(read)
    with jax.default_matmul_precision("highest"):
        h = _norm(x, params["final_norm"].astype(jnp.float32))[positions]
        logits = h @ params["lm_head"].astype(jnp.float32)
    return {
        "logits": logits, "ids": jnp.stack(ids), "gaps": jnp.stack(gaps),
        "embedded": embedded, "rows": reads,
    }


def logits_at(params, tokens, positions, cfg_json, dense=False):
    """Float32 logits ``[len(positions), V]`` of one sequence at the
    given positions and, for each of them, the smallest router gap over
    the layers."""
    out = forward_at(params, tokens, positions, cfg_json, dense)
    return out["logits"], jnp.min(out["gaps"], axis=0)
