"""The plain reference of ``kimi-linear-48b-a3b``: the forward pass and
next-token loss of the model's public description (arXiv:2510.26692;
``config.json`` of moonshotai/Kimi-Linear-48B-A3B-Instruct) in
straightforward ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")`` -- no kernels, no chunked
algebra, no sharding -- and its gradient by ``jax.grad``. It shares no
code with ``dlrover_tpu``; it reads the program's parameter tree and
buffer tree (names and shapes below) and a ``spec`` of the three numbers
no shape tells (``top_k``, ``routed_scaling``, ``first_expert``).

Only so that the gradient of 8,192 tokens fits on a chip beside a train
state, two places are marked ``jax.checkpoint`` (the values are the
same): a block of ``SCAN_BLOCK`` tokens of KDA's recurrence and a block
of ``QUERY_BLOCK`` queries of the attention; and ``batch_loss_and_grads``
takes ``jax.vjp`` a LAYER at a time, each its own program, handing every
layer's gradient to the host as it comes.

Pre-norm residual blocks ``x += mixer(norm(x))``, ``x += ffn(norm(x))``,
final norm, untied head. A layer is KDA when its mixer has ``a_log`` and
latent attention when it has ``w_kva``; its FFN is the expert layer when
it has ``router`` and the dense SwiGLU otherwise.

KDA, per head, ``d`` the head size, token by token (``lax.scan``):

    q_t = l2norm(silu(conv(Wq x)))_t / sqrt(d)
    k_t = l2norm(silu(conv(Wk x)))_t       v_t = silu(conv(Wv x))_t
    g_t = -exp(a_log) * softplus(W_a2 (W_a1 x_t) + dt_bias)       (per channel)
    beta_t = sigmoid(w_beta . x_t)
    S_t = (I - beta_t k_t k_t^T) diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    y_t = Wo concat_h[ rmsnorm(S_t^T q_t) * sigmoid(W_g2 (W_g1 x_t)) ]

``conv`` is a causal depthwise convolution over time (4 taps, one filter
a channel, zeros to the left, no bias).

Latent attention without rotary: ``q = Wq x`` (heads x (nope + rope)),
``[c; kr] = W_kva x``, ``[kn; v] = W_kvb rmsnorm(c)``, a head's key is
``[kn_h; kr]`` with ``kr`` shared by the heads and NOT rotated, causal
softmax of ``q . k / sqrt(nope + rope)`` over the values (their own head
size), then ``Wo``. Scores are formed a block of queries at a time.

Expert layer: ``s = sigmoid(W_r x)`` over ALL experts, the ``top_k``
largest of ``s + bias``, weights ``routed_scaling * s_e / sum of the
chosen s``; the result is the shared expert plus the weighted experts
AMONG THOSE HELD HERE (``first_expert`` and the next ``held``): what
the absent experts would add is left out, as in the program.

Departures from the published model, all shared with the program:

- RMSNorm with a ``(1 + scale)`` gain and eps 1e-6 (the repo's; the
  published file says a plain gain and 1e-5 -- with zero-initialised
  scales the two parameterisations are one function); the per-head norm
  after KDA likewise. ``l2norm(x) = x / sqrt(sum x^2 + 1e-6)``.
- The rank of the two low-rank gates (``w_a1``, ``w_g1``) is read from
  the tree (the configuration assumes the KDA head size); no projection
  or convolution has a bias.
- The score-correction bias is a constant read from the buffers: the
  benchmark sets it once, before the first step, with ``balanced_bias``
  below (what a router trained with the bias-update balancing rule
  settles to, and NOT the seeded constant ISSUE 31 assumed).
- Loss: mean over tokens of cross-entropy plus the repo's z-loss,
  ``1e-4 * logsumexp(logits)**2``, over the vocabulary rows held.

Parameter tree: ``embed [V, d]``, ``lm_head [d, V]``, ``final_norm [d]``,
``leading`` a list of layers, ``period`` a list of layers with a leading
repeat axis (layer ``r * len(period) + i`` after the leading ones is
``period[i][r]``). A layer: ``mixer_norm, ffn_norm [d]``, ``mixer`` and
``ffn``. KDA: ``wq, wk, wv [d, h, k]``, ``conv_q, conv_k, conv_v
[4, h, k]``, ``w_a1, w_g1 [d, r]``, ``w_a2, w_g2 [r, h, k]``, ``w_beta
[d, h]``, ``a_log [h]``, ``dt_bias [h, k]``, ``o_norm [k]``, ``wo
[h, k, d]``. Latent attention: ``wq [d, h, nope + rope]``, ``w_kva
[d, rank + rope]``, ``kv_norm [rank]``, ``w_kvb [rank, h, nope + v]``,
``wo [h, v, d]``. Dense FFN: ``w_gate, w_up [d, f]``, ``w_down [f, d]``.
Expert FFN: ``router [d, E]``, ``w_gate, w_up [held, d, f]``, ``w_down
[held, f, d]``, ``shared`` (a dense FFN); buffers ``router_bias [E]``.
"""

import jax
import jax.numpy as jnp
import numpy as np

NORM_EPS = 1e-6
L2_EPS = 1e-6
Z_WEIGHT = 1e-4
QUERY_BLOCK = 512
SCAN_BLOCK = 64


def _norm(x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + NORM_EPS) * (1.0 + scale)


def _conv(x, w):
    """x [s, h, k], w [taps, h, k]: y_t = sum_j w[j] x[t - taps + 1 + j]."""
    taps, s = w.shape[0], x.shape[0]
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1,) + x.shape[1:], x.dtype), x], axis=0
    )
    out = jnp.zeros_like(x)
    for j in range(taps):
        out = out + w[j] * padded[j:j + s]
    return out


def _l2norm(x):
    return x / jnp.sqrt(
        jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS
    )


def _scan_in_blocks(token, state, xs):
    """``lax.scan(token, state, xs)[1]``, a token a step, with the steps
    grouped in checkpointed blocks of ``SCAN_BLOCK`` so that the
    backward keeps a state a block and not a state a token."""
    s = xs[0].shape[0]
    whole = s - s % SCAN_BLOCK
    outs = []
    if whole:
        blocks = tuple(
            a[:whole].reshape(-1, SCAN_BLOCK, *a.shape[1:]) for a in xs
        )
        state, o = jax.lax.scan(
            jax.checkpoint(lambda c, b: jax.lax.scan(token, c, b)),
            state, blocks,
        )
        outs.append(o.reshape(whole, *o.shape[2:]))
    if s > whole:
        outs.append(jax.lax.scan(token, state, tuple(a[whole:] for a in xs))[1])
    return jnp.concatenate(outs, axis=0)


def kda_inputs(p, x):
    """x [s, d] -> (q, k, v, g [s, h, k], beta [s, h]) of the recurrence."""
    d_head = p["wq"].shape[-1]

    def branch(w, conv):
        return jax.nn.silu(_conv(jnp.einsum("sd,dhk->shk", x, w), conv))

    q = _l2norm(branch(p["wq"], p["conv_q"])) / jnp.sqrt(jnp.float32(d_head))
    k = _l2norm(branch(p["wk"], p["conv_k"]))
    v = branch(p["wv"], p["conv_v"])
    g = -jnp.exp(p["a_log"])[:, None] * jax.nn.softplus(
        jnp.einsum("sr,rhk->shk", x @ p["w_a1"], p["w_a2"]) + p["dt_bias"]
    )
    beta = jax.nn.sigmoid(x @ p["w_beta"])
    return q, k, v, g, beta


def kda_recurrence(q, k, v, g, beta):
    """The delta rule, a token a step: -> o [s, h, v]."""

    def token(state, t):
        q_t, k_t, v_t, g_t, b_t = t
        state = jnp.exp(g_t)[:, :, None] * state               # diag(alpha) S
        kts = jnp.einsum("hk,hkv->hv", k_t, state)             # k^T (alpha S)
        state = state + b_t[:, None, None] * k_t[:, :, None] * (
            v_t - kts
        )[:, None, :]
        return state, jnp.einsum("hk,hkv->hv", q_t, state)

    zero = jnp.zeros((q.shape[1], q.shape[2], v.shape[-1]), jnp.float32)
    return _scan_in_blocks(token, zero, (q, k, v, g, beta))


def kda(p, x):
    """x [s, d] -> [s, d]."""
    o = kda_recurrence(*kda_inputs(p, x))
    gate = jax.nn.sigmoid(
        jnp.einsum("sr,rhk->shk", x @ p["w_g1"], p["w_g2"])
    )
    return jnp.einsum("shk,hkd->sd", _norm(o, p["o_norm"]) * gate, p["wo"])


def first_layer_kda_inputs(params, tokens):
    """``kda_inputs`` of the model's FIRST layer for one sequence of
    input ids [s]: what its recurrence is fed under these weights."""
    p = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), params["leading"][0]
    )
    if "a_log" not in p["mixer"]:
        raise ValueError("the model's first layer is not a KDA layer")
    x = params["embed"][tokens].astype(jnp.float32)
    return kda_inputs(p["mixer"], _norm(x, p["mixer_norm"]))


def mla(p, x):
    """x [s, d] -> [s, d]; no position enters."""
    s = x.shape[0]
    rank = p["kv_norm"].shape[0]
    d_v = p["wo"].shape[1]
    q = jnp.einsum("sd,dhk->shk", x, p["wq"])
    kva = x @ p["w_kva"]
    kvb = jnp.einsum("sr,rhk->shk", _norm(kva[:, :rank], p["kv_norm"]),
                     p["w_kvb"])
    nope = kvb.shape[-1] - d_v
    shared = jnp.broadcast_to(
        kva[:, None, rank:], (s, q.shape[1], kva.shape[1] - rank)
    )
    k = jnp.concatenate([kvb[..., :nope], shared], axis=-1)
    v = kvb[..., nope:]
    scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))
    block = min(QUERY_BLOCK, s)

    @jax.checkpoint
    def attend(args):
        q_rows, first = args
        rows = first + jnp.arange(q_rows.shape[0])
        scores = jnp.einsum("qhk,thk->hqt", q_rows, k) * scale
        seen = rows[:, None] >= jnp.arange(s)[None, :]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqt,thk->qhk", probs, v)

    # Whole blocks one after another (``lax.map``: a backward then holds
    # one block's scores), and what is left over.
    whole = s - s % block
    outs = [jax.lax.map(attend, (
        q[:whole].reshape(-1, block, *q.shape[1:]),
        jnp.arange(0, whole, block),
    )).reshape(whole, q.shape[1], d_v)]
    if s > whole:
        outs.append(attend((q[whole:], whole)))
    return jnp.einsum("shk,hkd->sd", jnp.concatenate(outs, axis=0), p["wo"])


def swiglu(p, x):
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def experts(p, bias, x, spec):
    """The shared expert plus the held experts' part of the routed sum."""
    scores = jax.nn.sigmoid(x @ p["router"])                   # [s, E]
    _, chosen = jax.lax.top_k(scores + bias, spec["top_k"])    # [s, k]
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = spec["routed_scaling"] * picked / jnp.sum(
        picked, axis=-1, keepdims=True
    )
    out = swiglu(p["shared"], x)
    for j in range(p["w_gate"].shape[0]):
        expert = {k: p[k][j] for k in ("w_gate", "w_up", "w_down")}
        w = jnp.sum(
            jnp.where(chosen == spec["first_expert"] + j, weights, 0.0), -1
        )
        out = out + w[:, None] * swiglu(expert, x)
    return out


def _layer(p, buffers, x, spec):
    mixer = kda if "a_log" in p["mixer"] else mla
    x = x + mixer(p["mixer"], _norm(x, p["mixer_norm"]))
    h = _norm(x, p["ffn_norm"])
    if "router" in p["ffn"]:
        return x + experts(p["ffn"], buffers["router_bias"], h, spec)
    return x + swiglu(p["ffn"], h)


def layers_of(params, buffers):
    """The layers in order, as (parameters, buffers) pairs."""
    out = list(zip(params["leading"], buffers["leading"]))
    take = lambda tree, r: jax.tree_util.tree_map(  # noqa: E731
        lambda a: a[r], tree
    )
    repeats = jax.tree_util.tree_leaves(params["period"])[0].shape[0]
    for r in range(repeats):
        for p, b in zip(params["period"], buffers["period"]):
            out.append((take(p, r), take(b, r)))
    return out


def hidden(params, buffers, tokens, spec):
    """Final-normed hidden states [s, d] of one sequence [s]."""
    f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: a.astype(jnp.float32), t
    )
    x = params["embed"][tokens].astype(jnp.float32)
    for p, b in layers_of(params, buffers):
        x = _layer(f32(p), f32(b), x, spec)
    return _norm(x, params["final_norm"].astype(jnp.float32))


def _token_losses(h, lm_head, targets):
    """Per-token loss [s] of final-normed hidden states [s, d]."""
    logits = h @ lm_head.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    target = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return logz - target + Z_WEIGHT * jnp.square(logz)


def sequence_loss_sums(params, buffers, tokens, spec):
    """(sum of per-token loss, token count) of one sequence [s + 1]:
    inputs ``tokens[:-1]``, targets ``tokens[1:]``."""
    with jax.default_matmul_precision("highest"):
        h = hidden(params, buffers, tokens[:-1], spec)
        per_token = _token_losses(h, params["lm_head"], tokens[1:])
    return jnp.sum(per_token), per_token.shape[0]


def batch_loss(params, buffers, batch_tokens, spec):
    """Token-mean loss of a batch [b, s + 1], one sequence at a time.
    ``spec``: ``{"top_k", "routed_scaling", "first_expert"}``."""
    fn = jax.jit(
        lambda p, b, t: sequence_loss_sums(p, b, t, spec)[0]
    )
    total, count = 0.0, 0
    for row in batch_tokens:
        total += float(fn(params, buffers, jnp.asarray(row)))
        count += len(row) - 1
    return total / count


def layer_programs(spec):
    """(forward, backward) of one layer, jitted: ``forward(p, b, x)`` is
    the layer's output, ``backward(p, b, x, dy)`` its pullback's
    ``(dp, dx)``. Trace them under the matmul precision wanted."""
    f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: a.astype(jnp.float32), t
    )
    forward = jax.jit(lambda p, b, x: _layer(f32(p), f32(b), x, spec))
    backward = jax.jit(lambda p, b, x, dy: jax.vjp(
        lambda p, x: _layer(f32(p), f32(b), x, spec), p, x
    )[1](dy))
    return forward, backward


def batch_loss_and_grads(params, buffers, batch_tokens, spec):
    """(token-mean loss, its gradient by the parameters) of a batch
    [b, s + 1]: ``jax.vjp`` of the functions above, a sequence and a
    LAYER at a time -- forward through the layers keeping each one's
    input, the head's gradient, then back through the layers, each
    layer's pullback its own program -- so that the most that is live is
    one layer's backward. The gradient comes back as a tree of numpy
    arrays ON THE HOST, shaped like ``params``: on a chip that also
    holds a train state there is no room for it beside a step."""
    host = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: np.asarray(a, np.float32), t
    )
    count = sum(len(row) - 1 for row in batch_tokens)
    n_lead, span = len(params["leading"]), len(params["period"])
    repeats = jax.tree_util.tree_leaves(params["period"])[0].shape[0]

    def layer_at(i):
        """Layer ``i``'s (parameters, buffers), sliced out when asked:
        a copy of every layer at once is a second set of weights."""
        if i < n_lead:
            return params["leading"][i], buffers["leading"][i]
        r, j = divmod(i - n_lead, span)
        take = lambda t: jax.tree_util.tree_map(lambda a: a[r], t)  # noqa
        return take(params["period"][j]), take(buffers["period"][j])

    with jax.default_matmul_precision("highest"):
        forward, backward = layer_programs(spec)
        head = jax.jit(jax.value_and_grad(
            lambda scale, w, x, targets: jnp.sum(_token_losses(
                _norm(x, scale.astype(jnp.float32)), w, targets
            )) / count,
            argnums=(0, 1, 2),
        ))
        spread = jax.jit(lambda embed, rows, dx: jnp.zeros(
            embed.shape, jnp.float32
        ).at[rows].add(dx))

        total, sums = 0.0, None
        for row in batch_tokens:
            rows, targets = jnp.asarray(row[:-1]), jnp.asarray(row[1:])
            xs = [params["embed"][rows].astype(jnp.float32)]
            for i in range(n_lead + repeats * span):
                xs.append(forward(*layer_at(i), xs[-1]))
            loss, (d_scale, d_head, dx) = head(
                params["final_norm"], params["lm_head"], xs.pop(), targets
            )
            total += float(loss)
            by_layer = []
            for i in reversed(range(n_lead + repeats * span)):
                dp, dx = backward(*layer_at(i), xs.pop(), dx)
                by_layer.insert(0, host(dp))
            grads = {
                "embed": host(spread(params["embed"], rows, dx)),
                "leading": by_layer[:n_lead],
                "period": [
                    jax.tree_util.tree_map(
                        lambda *a: np.stack(a), *by_layer[n_lead + j::span]
                    )
                    for j in range(span)
                ],
                "final_norm": host(d_scale), "lm_head": host(d_head),
            }
            sums = grads if sums is None else jax.tree_util.tree_map(
                np.add, sums, grads
            )
    return total, sums


def balanced_bias(params, buffers, batch_tokens, spec):
    """``buffers`` with every expert layer's score-correction bias set so
    that the inputs of ``batch_tokens`` [b, s + 1] spread evenly over ALL
    its experts: one forward pass, layer by layer, each bias minus the
    ``1 - top_k / experts`` quantile of that expert's scores over the
    tokens (an expert then clears its own bar for one token in
    ``experts / top_k``), centred. Random weights give every token's
    hidden state a common mode and with it a few experts most of the
    rows; the rule such routers are trained with (a step of the bias
    against each expert's load, arXiv:2408.15664) removes just that. The
    benchmark's set-up calls this once, as part of making the weights
    from the seed; nothing updates the bias afterwards."""

    def walk(params, buffers, tokens):
        f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda a: a.astype(jnp.float32), t
        )
        xs = [params["embed"][row[:-1]].astype(jnp.float32) for row in tokens]
        out = []
        for p, b in layers_of(params, buffers):
            p, b = f32(p), f32(b)
            mix = kda if "a_log" in p["mixer"] else mla
            xs = [x + mix(p["mixer"], _norm(x, p["mixer_norm"])) for x in xs]
            hs = [_norm(x, p["ffn_norm"]) for x in xs]
            if "router" in p["ffn"]:
                scores = jax.nn.sigmoid(
                    jnp.concatenate(hs, axis=0) @ p["ffn"]["router"]
                )
                share = spec["top_k"] / scores.shape[-1]
                bar = jnp.quantile(scores, 1.0 - share, axis=0)
                b = dict(b, router_bias=jnp.mean(bar) - bar)
                ys = [experts(p["ffn"], b["router_bias"], h, spec) for h in hs]
            else:
                ys = [swiglu(p["ffn"], h) for h in hs]
            out.append(b)
            xs = [x + y for x, y in zip(xs, ys)]
        return out

    with jax.default_matmul_precision("highest"):
        flat = jax.jit(walk)(params, buffers, jnp.asarray(batch_tokens))
    n_lead, span = len(buffers["leading"]), len(buffers["period"])
    return {
        "leading": flat[:n_lead],
        "period": [
            jax.tree_util.tree_map(
                lambda *a: jnp.stack(a), *flat[n_lead + i::span]
            )
            for i in range(span)
        ],
    }
