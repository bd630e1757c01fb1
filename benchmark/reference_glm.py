"""The plain reference of ``glm-4.7-flash`` TRAINED: the forward pass, the
two losses and their gradient, of the model's public description
(``config.json`` of zai-org/GLM-4.7-Flash, ``glm4_moe_lite``; the
prediction module after DeepSeek-V3, arXiv:2412.19437 section 2.2, eq.
21-25, which this family follows) in straightforward ``jax.numpy``
float32 under ``jax.default_matmul_precision("highest")`` -- no kernels,
no rematerialisation of the program's kind, no sharding. It shares no
code with ``dlrover_tpu``; it reads the program's parameter tree and
buffer tree (names and shapes below) and a ``spec`` of the numbers no
shape tells (``top_k``, ``routed_scaling``, ``first_expert``,
``rope_theta``, ``mtp_weight``).

Per token, hidden ``x [d]``; every block is pre-norm residual:
``x <- x + MLA(norm(x))``, ``x <- x + FFN(norm(x))``.

MLA: ``c_q = norm(x W_qa)``; ``q = c_q W_qb`` -> heads x (nope | rope).
``[c_kv | k_r] = x W_kva``; ``c = norm(c_kv)``; ``[k_nope | v] = c
W_kvb`` -> heads x (nope | v). ``q_rope`` of every head and the ONE
``k_r`` the heads share are rotated by RoPE at the token's position:
all ``rope`` channels, no scaling, channel ``j`` paired with channel
``j + rope / 2``, the pair turned by ``position * theta ** (-2 j /
rope)`` (:func:`rotate`, written per position and per pair). Scores
``(q_nope . k_nope + q_rope . k_r) / sqrt(nope + rope)``, causal softmax,
``out = concat_h(P v) W_o``. No biases. Scores are formed a block of
``QUERY_BLOCK`` queries at a time (``jax.checkpoint``: the values are
the same) so that the gradient of 8,192 tokens fits on a chip beside a
train state.

FFN: the leading layer a SwiGLU; every other layer and the prediction
module's block ``s = sigmoid(x W_r)`` over ALL experts, the ``top_k``
largest of ``s + bias`` (the score-correction bias selects and does not
weight; one group, so plain top-k), weights ``routed_scaling * s_e / sum
of the chosen s``; the result is the shared expert plus the weighted
experts AMONG THOSE HELD HERE (``first_expert`` and the next ``held``):
what the absent experts would add is left out, as in the program.

Multi-token prediction, depth 1. With ``h_i`` the main stack's output at
position ``i`` BEFORE the main model's final norm and ``t`` the tokens:
``u_i = W_eh [norm_h(h_i) | norm_e(Emb(t_{i+1}))]`` (the paper's order:
the hidden state first), ``g = Block(u)`` -- one more block of the
expert kind, causal over the ``u``, position ``i`` for ``u_i`` in its
own rotation -- and ``logits'_i = Head(norm_s(g_i))`` predicts
``t_{i+2}``. ``Emb`` and ``Head`` are the main model's own arrays, so
they collect gradient from both losses. Loss ``CE(logits_i, t_{i+1}) +
mtp_weight * CE(logits'_i, t_{i+2})``, each a token mean plus the
repo's z-loss (``1e-4 * logsumexp(logits)**2``) over the vocabulary
rows held. A sequence is ``seq_len + 2`` tokens.

Departures from the published model, all shared with the program:
RMSNorm with a ``(1 + scale)`` gain and eps 1e-6 (the repo's; the
published file says a plain gain and 1e-5 -- with zero-initialised
scales the two parameterisations are one function); the half-split
RoPE pairing above (the public implementation interleaves and then
permutes: the same function of permuted weights, and the weights here
are random); the score-correction bias is a constant read from the
buffers, set once before the first step by :func:`balanced_bias`.

Parameter tree: ``embed [V, d]``, ``lm_head [d, V]``, ``final_norm
[d]``, ``leading`` a list of layers, ``period`` a list of layers with a
leading repeat axis (layer ``r * len(period) + i`` after the leading
ones is ``period[i][r]``), ``mtp``: ``norm_h, norm_e, norm [d]``, ``w_eh
[2 d, d]``, ``block`` a layer. A layer: ``mixer_norm, ffn_norm [d]``,
``mixer``: ``w_qa [d, rq]``, ``q_norm [rq]``, ``w_qb [rq, h, nope +
rope]``, ``w_kva [d, rank + rope]``, ``kv_norm [rank]``, ``w_kvb [rank,
h, nope + v]``, ``wo [h, v, d]``; ``ffn`` dense: ``w_gate, w_up [d,
f]``, ``w_down [f, d]``; expert: ``router [d, E]``, ``w_gate, w_up
[held, d, f]``, ``w_down [held, f, d]``, ``shared`` (a dense FFN);
buffers ``router_bias [E]`` a layer, ``mtp.block`` likewise.
"""

import jax
import jax.numpy as jnp
import numpy as np

NORM_EPS = 1e-6
Z_WEIGHT = 1e-4
QUERY_BLOCK = 512

# Every matrix product of the reference goes through these two names (a
# control of ``controls_glm.py`` rounds their operands).
matmul = jnp.matmul
einsum = jnp.einsum


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _norm(x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + NORM_EPS) * (1.0 + scale)


def rotate(x, theta):
    """RoPE of ``x [s, ..., rope]``, row ``i`` at position ``i``: channel
    ``j < rope / 2`` and channel ``j + rope / 2`` are one pair, turned by
    the angle ``i * theta ** (-2 j / rope)``."""
    s, half = x.shape[0], x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freq
    angle = angle.reshape((s,) + (1,) * (x.ndim - 2) + (half,))
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([
        a * jnp.cos(angle) - b * jnp.sin(angle),
        b * jnp.cos(angle) + a * jnp.sin(angle),
    ], axis=-1)


def queries(p, x):
    """``c_q = norm(x W_qa)``, ``q = c_q W_qb``: [s, h, nope + rope]."""
    return einsum(
        "sr,rhk->shk", _norm(matmul(x, p["w_qa"]), p["q_norm"]), p["w_qb"]
    )


def mla(p, x, spec):
    """x [s, d] -> [s, d], row ``i`` at position ``i``."""
    s = x.shape[0]
    rank = p["kv_norm"].shape[0]
    d_v = p["wo"].shape[1]
    q = queries(p, x)
    kva = matmul(x, p["w_kva"])
    kvb = einsum("sr,rhk->shk", _norm(kva[:, :rank], p["kv_norm"]),
                 p["w_kvb"])
    nope = kvb.shape[-1] - d_v
    k_r = rotate(kva[:, rank:], spec["rope_theta"])            # [s, rope]
    q = jnp.concatenate(
        [q[..., :nope], rotate(q[..., nope:], spec["rope_theta"])], axis=-1
    )
    k = jnp.concatenate([
        kvb[..., :nope],
        jnp.broadcast_to(k_r[:, None, :], (s, q.shape[1], k_r.shape[-1])),
    ], axis=-1)
    v = kvb[..., nope:]
    scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))
    block = min(QUERY_BLOCK, s)

    @jax.checkpoint
    def attend(args):
        q_rows, first = args
        rows = first + jnp.arange(q_rows.shape[0])
        scores = einsum("qhk,thk->hqt", q_rows, k) * scale
        seen = rows[:, None] >= jnp.arange(s)[None, :]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return einsum("hqt,thk->qhk", probs, v)

    whole = s - s % block
    outs = [jax.lax.map(attend, (
        q[:whole].reshape(-1, block, *q.shape[1:]),
        jnp.arange(0, whole, block),
    )).reshape(whole, q.shape[1], d_v)]
    if s > whole:
        outs.append(attend((q[whole:], whole)))
    return einsum("shk,hkd->sd", jnp.concatenate(outs, axis=0), p["wo"])


def swiglu(p, x):
    return matmul(
        jax.nn.silu(matmul(x, p["w_gate"])) * matmul(x, p["w_up"]),
        p["w_down"],
    )


def route(p, bias, x, spec):
    """(chosen experts [s, k], their weights [s, k])."""
    scores = jax.nn.sigmoid(matmul(x, p["router"]))            # [s, E]
    _, chosen = jax.lax.top_k(scores + bias, spec["top_k"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, spec["routed_scaling"] * picked / jnp.sum(
        picked, axis=-1, keepdims=True
    )


def experts(p, bias, x, spec):
    """The shared expert plus the held experts' part of the routed sum."""
    chosen, weights = route(p, bias, x, spec)
    out = swiglu(p["shared"], x)
    for j in range(p["w_gate"].shape[0]):
        expert = {k: p[k][j] for k in ("w_gate", "w_up", "w_down")}
        w = jnp.sum(
            jnp.where(chosen == spec["first_expert"] + j, weights, 0.0), -1
        )
        out = out + w[:, None] * swiglu(expert, x)
    return out


def layer(p, buffers, x, spec):
    x = x + mla(p["mixer"], _norm(x, p["mixer_norm"]), spec)
    h = _norm(x, p["ffn_norm"])
    if "router" in p["ffn"]:
        return x + experts(p["ffn"], buffers["router_bias"], h, spec)
    return x + swiglu(p["ffn"], h)


def join(p, h, e):
    """The module's input: ``W_eh [norm_h(h) | norm_e(e)]``, [s, d]."""
    return matmul(jnp.concatenate(
        [_norm(h, p["norm_h"]), _norm(e, p["norm_e"])], axis=-1
    ), p["w_eh"])


def n_stack_layers(params):
    repeats = jax.tree_util.tree_leaves(params["period"])[0].shape[0]
    return len(params["leading"]) + repeats * len(params["period"])


def layer_at(params, buffers, i):
    """Stack layer ``i``'s (parameters, buffers), sliced out when asked:
    a copy of every layer at once is a second set of weights."""
    n_lead, span = len(params["leading"]), len(params["period"])
    if i < n_lead:
        return params["leading"][i], buffers["leading"][i]
    r, j = divmod(i - n_lead, span)
    take = lambda t: jax.tree_util.tree_map(lambda a: a[r], t)  # noqa: E731
    return take(params["period"][j]), take(buffers["period"][j])


def _token_losses(h, scale, lm_head, targets):
    """Per-token loss [s] of hidden states [s, d] behind a norm."""
    logits = matmul(_norm(h, scale), lm_head)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    target = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return logz - target + Z_WEIGHT * jnp.square(logz)


def sequence_loss_sums(params, buffers, tokens, spec):
    """(sum of the main per-token loss, sum of the module's, token
    count) of one sequence [s + 2]: the stack reads ``tokens[:s]`` and is
    held to ``tokens[1:s + 1]``, the module to ``tokens[2:]``."""
    params, buffers = _f32(params), _f32(buffers)
    s = tokens.shape[0] - 2
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens[:s]]
        for i in range(n_stack_layers(params)):
            x = layer(*layer_at(params, buffers, i), x, spec)
        main = _token_losses(
            x, params["final_norm"], params["lm_head"], tokens[1:s + 1]
        )
        m = params["mtp"]
        u = join(m, x, params["embed"][tokens[1:s + 1]])
        g = layer(m["block"], buffers["mtp"]["block"], u, spec)
        mtp = _token_losses(g, m["norm"], params["lm_head"], tokens[2:])
    return jnp.sum(main), jnp.sum(mtp), s


def batch_losses(params, buffers, batch_tokens, spec):
    """(main CE, module CE), token means over a batch [b, s + 2], one
    sequence at a time."""
    fn = jax.jit(lambda p, b, t: sequence_loss_sums(p, b, t, spec)[:2])
    main = mtp = 0.0
    count = 0
    for row in batch_tokens:
        a, b = fn(params, buffers, jnp.asarray(row))
        main, mtp, count = main + float(a), mtp + float(b), count + len(row) - 2
    return main / count, mtp / count


def loss(params, buffers, batch_tokens, spec):
    """The scalar the program minimises, differentiable: ``CE +
    mtp_weight * CE'`` of a batch [b, s + 2] (small sizes: the whole
    graph at once)."""
    main = mtp = 0.0
    count = 0
    for row in batch_tokens:
        a, b, n = sequence_loss_sums(params, buffers, row, spec)
        main, mtp, count = main + a, mtp + b, count + n
    return (main + spec["mtp_weight"] * mtp) / count


def programs(spec):
    """The jitted pieces ``batch_loss_and_grads`` walks: a layer's
    forward and pullback, the join's, the two heads'. Trace them under
    the matmul precision wanted."""
    fwd = lambda p, b, x: layer(_f32(p), _f32(b), x, spec)  # noqa: E731

    def head(scale, w, x, targets, weight):
        """(what is differentiated, the plain sum of the losses)."""
        total = jnp.sum(_token_losses(
            x, scale.astype(jnp.float32), w.astype(jnp.float32), targets
        ))
        return weight * total, total

    return {
        "forward": jax.jit(fwd),
        "backward": jax.jit(lambda p, b, x, dy: jax.vjp(
            lambda p, x: fwd(p, b, x), p, x
        )[1](dy)),
        "join": jax.jit(lambda p, h, e: join(_f32(p), h, e)),
        "join_backward": jax.jit(lambda p, h, e, du: jax.vjp(
            lambda p, h, e: join(_f32(p), h, e), p, h, e
        )[1](du)),
        "head": jax.jit(
            jax.value_and_grad(head, argnums=(0, 1, 2), has_aux=True)
        ),
        "spread": jax.jit(lambda embed, rows, dx: jnp.zeros(
            embed.shape, jnp.float32
        ).at[rows].add(dx)),
    }


def head_gradient(from_main, from_module):
    """The head is ONE array behind both losses: its gradient is the
    sum of what each sends it."""
    return from_main + from_module


def batch_loss_and_grads(params, buffers, batch_tokens, spec):
    """((main CE, module CE), the gradient of ``CE + mtp_weight * CE'``
    by the parameters) of a batch [b, s + 2]: ``jax.vjp`` of the
    functions above, a sequence and a LAYER at a time -- forward through
    the stack keeping each layer's input, the main head, the join, the
    module's block, its head, and back the same way, each pullback its
    own program -- so that the most that is live is one layer's
    backward. The gradient comes back as a tree of numpy arrays ON THE
    HOST, shaped like ``params``: on a chip that also holds a train
    state there is no room for it beside a step."""
    host = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: np.asarray(a, np.float32), t
    )
    count = sum(len(row) - 2 for row in batch_tokens)
    n_lead, span = len(params["leading"]), len(params["period"])
    n = n_stack_layers(params)
    m = params["mtp"]
    m_join = {k: m[k] for k in ("norm_h", "norm_e", "w_eh")}
    m_block = (m["block"], buffers["mtp"]["block"])
    main = mtp = 0.0
    sums = None
    with jax.default_matmul_precision("highest"):
        fn = programs(spec)
        for row in batch_tokens:
            s = len(row) - 2
            read, nxt, last = (
                jnp.asarray(row[:s]), jnp.asarray(row[1:s + 1]),
                jnp.asarray(row[2:]),
            )
            xs = [params["embed"][read].astype(jnp.float32)]
            for i in range(n):
                xs.append(fn["forward"](*layer_at(params, buffers, i), xs[-1]))
            h = xs.pop()
            (_, l_main), (d_final, d_head, dh) = fn["head"](
                params["final_norm"], params["lm_head"], h, nxt, 1.0 / count
            )
            e = params["embed"][nxt].astype(jnp.float32)
            u = fn["join"](m_join, h, e)
            g = fn["forward"](*m_block, u)
            (_, l_mtp), (d_norm, d_head2, dg) = fn["head"](
                m["norm"], params["lm_head"], g, last,
                spec["mtp_weight"] / count,
            )
            d_head = head_gradient(host(d_head), host(d_head2))
            del d_head2
            d_block, du = fn["backward"](*m_block, u, dg)
            d_join, dh2, de = fn["join_backward"](m_join, h, e, du)
            d_mtp = dict(host(d_join), block=host(d_block), norm=host(d_norm))
            d_embed = host(fn["spread"](params["embed"], nxt, de))
            dx = dh + dh2
            del h, e, u, g, dg, du, dh, dh2, de, d_block
            main += float(l_main) / count
            mtp += float(l_mtp) / count
            by_layer = []
            for i in reversed(range(n)):
                dp, dx = fn["backward"](
                    *layer_at(params, buffers, i), xs.pop(), dx
                )
                by_layer.insert(0, host(dp))
            d_embed = d_embed + host(fn["spread"](params["embed"], read, dx))
            grads = {
                "embed": d_embed,
                "leading": by_layer[:n_lead],
                "period": [
                    jax.tree_util.tree_map(
                        lambda *a: np.stack(a), *by_layer[n_lead + j::span]
                    )
                    for j in range(span)
                ],
                "final_norm": host(d_final), "lm_head": d_head,
                "mtp": d_mtp,
            }
            sums = grads if sums is None else jax.tree_util.tree_map(
                np.add, sums, grads
            )
    return (main, mtp), sums


def balanced_bias(params, buffers, batch_tokens, spec):
    """``buffers`` with every expert layer's score-correction bias -- the
    module's block's too -- set so that the inputs of ``batch_tokens``
    [b, s + 2] spread evenly over ALL its experts: one forward pass,
    layer by layer, each bias minus the ``1 - top_k / experts`` quantile
    of that expert's scores over the tokens (an expert then clears its
    own bar for one token in ``experts / top_k``), centred. Random
    weights give every token's hidden state a common mode and with it a
    few experts most of the rows; the rule such routers are trained with
    (a step of the bias against each expert's load, arXiv:2408.15664;
    DeepSeek-V3 section 2.1.2, ``noaux_tc``) removes just that. The
    benchmark's set-up calls this once, as part of making the weights
    from the seed; nothing updates the bias afterwards."""

    def balanced(p, b, xs):
        """(the layer's buffers with its bias set, its outputs)."""
        xs = [x + mla(p["mixer"], _norm(x, p["mixer_norm"]), spec)
              for x in xs]
        hs = [_norm(x, p["ffn_norm"]) for x in xs]
        if "router" not in p["ffn"]:
            return b, [x + swiglu(p["ffn"], h) for x, h in zip(xs, hs)]
        scores = jax.nn.sigmoid(
            matmul(jnp.concatenate(hs, axis=0), p["ffn"]["router"])
        )
        share = spec["top_k"] / scores.shape[-1]
        bar = jnp.quantile(scores, 1.0 - share, axis=0)
        b = dict(b, router_bias=jnp.mean(bar) - bar)
        return b, [
            x + experts(p["ffn"], b["router_bias"], h, spec)
            for x, h in zip(xs, hs)
        ]

    def walk(params, buffers, tokens):
        params, buffers = _f32(params), _f32(buffers)
        s = tokens.shape[1] - 2
        xs = [params["embed"][row[:s]] for row in tokens]
        out = []
        for i in range(n_stack_layers(params)):
            b, xs = balanced(*layer_at(params, buffers, i), xs)
            out.append(b)
        m = params["mtp"]
        us = [join(m, x, params["embed"][row[1:s + 1]])
              for x, row in zip(xs, tokens)]
        b, _ = balanced(m["block"], buffers["mtp"]["block"], us)
        return out, b

    with jax.default_matmul_precision("highest"):
        flat, module = jax.jit(walk)(
            params, buffers, jnp.asarray(batch_tokens)
        )
    n_lead, span = len(buffers["leading"]), len(buffers["period"])
    return {
        "leading": flat[:n_lead],
        "period": [
            jax.tree_util.tree_map(
                lambda *a: jnp.stack(a), *flat[n_lead + i::span]
            )
            for i in range(span)
        ],
        "mtp": {"block": module},
    }
