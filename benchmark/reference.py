"""The plain reference: a decoder-only transformer's forward pass and
next-token loss in straightforward ``jax.numpy`` float32 — no kernels, no
cache, no scan, no remat, no sharding. It shares no code with
``dlrover_tpu``; it reads the program's parameter tree (names and
shapes below) and nothing else.

Block, as the repo builds it and Mistral publishes it, with the repo's
departures noted:

    h  = x + Wo . attention(rope(Wq n1(x)), rope(Wk n1(x)), Wv n1(x))
    y  = h + Wdown . (silu(Wgate n2(h)) * (Wup n2(h)))

- RMSNorm with a ``(1 + scale)`` gain and eps 1e-6 (the repo's; the
  published files say a plain gain and 1e-5 — with random weights and
  zero-initialised scales the two parameterisations are one function).
- RoPE in the half-split convention (first half of a head paired with
  the second), as the repo and Hugging Face's Mistral code have it.
- Grouped-query causal attention, scores scaled by 1/sqrt(head_dim),
  softmax in float32; no sliding window (callers keep sequences inside
  the published window, where the two are equal).
- Untied output head. Loss: mean over tokens of cross-entropy plus the
  repo's z-loss, ``1e-4 * logsumexp(logits)**2``.

Parameter tree: ``embed [V, d]``, ``lm_head [d, V]``, ``final_norm [d]``,
``layers`` with a leading layer axis: ``attn_norm, mlp_norm [L, d]``,
``wq [L, d, h, hd]``, ``wk, wv [L, d, kv, hd]``, ``wo [L, h, hd, d]``,
``w_gate, w_up [L, d, f]``, ``w_down [L, f, d]``.

On a TPU a float32 matmul runs in lower precision unless asked
otherwise, so everything here runs under
``jax.default_matmul_precision("highest")``.
"""

import jax
import jax.numpy as jnp

NORM_EPS = 1e-6
Z_WEIGHT = 1e-4


def _norm(x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + NORM_EPS) * (1.0 + scale)


def _rope(x, theta):
    """x: [s, heads, hd], positions 0..s-1."""
    s, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )


def _layer(p, x, theta):
    s = x.shape[0]
    n = _norm(x, p["attn_norm"])
    q = _rope(jnp.einsum("sd,dhk->shk", n, p["wq"]), theta)
    k = _rope(jnp.einsum("sd,dhk->shk", n, p["wk"]), theta)
    v = jnp.einsum("sd,dhk->shk", n, p["wv"])
    h, kv = q.shape[1], k.shape[1]
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    scores = jnp.einsum("qhk,thk->hqt", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1])
    )
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("hqt,thk->qhk", probs, v)
    x = x + jnp.einsum("qhk,hkd->qd", attn, p["wo"])
    n = _norm(x, p["mlp_norm"])
    gate = jax.nn.silu(n @ p["w_gate"]) * (n @ p["w_up"])
    return x + gate @ p["w_down"]


def hidden(params, tokens, theta):
    """Final-normed hidden states [s, d] of one sequence [s]."""
    f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: a.astype(jnp.float32), t
    )
    x = params["embed"][tokens].astype(jnp.float32)
    n_layers = params["layers"]["wq"].shape[0]
    for i in range(n_layers):
        layer = f32({k: v[i] for k, v in params["layers"].items()})
        x = _layer(layer, x, theta)
    return _norm(x, params["final_norm"].astype(jnp.float32))


def logits_at(params, tokens, positions, theta):
    """Float32 logits [len(positions), V] of one sequence at the given
    positions (whole-sequence logits at a 131k vocabulary would not
    fit beside the weights)."""
    with jax.default_matmul_precision("highest"):
        h = hidden(params, tokens, theta)[positions]
        return h @ params["lm_head"].astype(jnp.float32)


def sequence_loss_sums(params, tokens, theta):
    """(sum of per-token loss, token count) of one sequence [s + 1]:
    inputs ``tokens[:-1]``, targets ``tokens[1:]``."""
    with jax.default_matmul_precision("highest"):
        h = hidden(params, tokens[:-1], theta)
        logits = h @ params["lm_head"].astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    target = jnp.take_along_axis(logits, tokens[1:, None], axis=-1)[:, 0]
    per_token = logz - target + Z_WEIGHT * jnp.square(logz)
    return jnp.sum(per_token), per_token.shape[0]


def batch_loss(params, batch_tokens, theta):
    """Token-mean loss of a batch [b, s + 1], one sequence at a time."""
    fn = jax.jit(sequence_loss_sums, static_argnums=2)
    total, count = 0.0, 0
    for row in batch_tokens:
        s, n = fn(params, jnp.asarray(row), float(theta))
        total += float(s)
        count += int(n)
    return total / count
