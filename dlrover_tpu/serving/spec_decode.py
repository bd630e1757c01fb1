"""Self-speculative decoding: drafters + the batched accept/reject law.

Decode buys exactly one token per weight/KV sweep; r05 (v5e, 334M) read
that sweep at 1.33-1.46x the HBM roofline, so the remaining raw-speed
axis is tokens PER step (ROADMAP item 2). Speculative decoding
(Leviathan-style draft-then-verify, self-drafting so no second model
needs sharding) drafts K cheap continuation tokens per slot, then
verifies all K in ONE batched forward through the existing ragged
attention — every accepted draft is a free token amortized onto the
verification sweep.

Two drafters, both derived from the serving model itself:

- **n-gram / prompt lookup** (:func:`propose_ngram`) — pure host-side
  suffix matching over the request's own prompt + generated tokens.
  Zero device cost, and strong exactly where speculation pays most
  (repetitive suffixes: code, extraction, templated text).
- **early exit** — a truncated-layer forward through the FIRST
  ``draft_layers`` decoder blocks of the same weights, reusing the
  live decode cache (drafted partial-layer K/V lands beyond the fill,
  where the visibility invariant keeps it unread until the verify
  pass rewrites those rows with full-model values). Built per engine
  (serving/engine.py, serving/kvpool/engine.py) because the cache
  plumbing differs; the proposal rule is shared greedy argmax.

Verification + rollback (:func:`spec_accept`): the verify step scores
the fed token plus K drafts in one call, then this acceptance law runs
ON DEVICE — greedy rows accept a draft iff it IS the argmax (token-
exact vs the non-speculative baseline by construction), sampled rows
use standard rejection sampling against the deterministic drafter
(accept draft d with prob p(d); on rejection sample the residual — p
with d masked out, the exact distribution-correcting rule), and the
first rejection truncates the chain (cumulative product). Rollback is
FREE: rejected rows sit beyond the advanced fill and the visibility
invariant ("rows visible iff < fill", docs/DESIGN.md SS25/SS31/SS35)
guarantees no cleanup pass exists.
"""

import numpy as np

import jax
import jax.numpy as jnp

from dlrover_tpu.models import generate as gen_lib

SPEC_DRAFTERS = ("ngram", "early_exit")


def propose_ngram(
    history: np.ndarray, k: int, max_ngram: int = 3
) -> np.ndarray:
    """Prompt-lookup draft: match the sequence's own recent suffix.

    Finds the RIGHTMOST earlier occurrence of the longest suffix
    n-gram (``max_ngram`` down to 1) of ``history`` and proposes the
    up-to-``k`` tokens that followed it. Host-only numpy — the
    zero-cost drafter; returns an empty array when nothing matches
    (the engine then degenerates to plain one-token decode for that
    slot, draft_len 0)."""
    history = np.asarray(history, np.int32).reshape(-1)
    n = int(history.shape[0])
    if k <= 0 or n < 2:
        return np.zeros(0, np.int32)
    for g in range(min(max_ngram, n - 1), 0, -1):
        pat = history[n - g:]
        # Candidate starts: 0..n-g-1 (a window ending before the
        # suffix itself, so a continuation token exists). g shifted
        # equality masks beat materializing an [n, g] window matrix —
        # this runs per decoding slot per verify step.
        mask = history[: n - g] == pat[0]
        for j in range(1, g):
            mask &= history[j : j + n - g] == pat[j]
        hits = np.nonzero(mask)[0]
        if hits.size:
            s = int(hits[-1])
            cont = history[s + g : s + g + k]
            if cont.size:
                return cont.astype(np.int32)
    return np.zeros(0, np.int32)


def spec_accept(
    logits,      # [slots, T, V] f32 — verify logits, T = K+1
    drafts,      # [slots, K] int32 — drafted tokens
    draft_len,   # [slots] int32 — valid drafts per slot (0..K)
    temps,       # [slots] f32 — per-slot temperature, <= 0 greedy
    active,      # [slots] bool
    fed_tokens,  # [slots] int32 — the fed token (stable inactive fill)
    rng,
    step_idx,
):
    """The batched accept/reject law; runs inside the verify program.

    Greedy rows (t <= 0): draft i+1 accepted iff it equals
    ``argmax(logits[i])`` — the emitted chain is bit-identical to what
    sequential greedy decode would have produced, because each
    position's logits ARE the sequential step's logits (the verify
    attention reproduces the per-step math exactly).

    Sampled rows: the drafters are deterministic (q = a point mass on
    the drafted token), so Leviathan rejection sampling reduces to:
    accept draft d_i with probability p_i(d_i); on the first rejection
    sample the correction from the residual — p_i with d_i masked out,
    renormalized — and when every draft survives, sample the bonus
    token from the model's own next distribution. Both final picks go
    through :func:`gen_lib.sample_token_logprobs` (one call: greedy
    rows mask nothing that can win, so the same masked pick is exact
    argmax for them too).

    Returns ``(emitted [slots, T] int32, accept_len [slots] int32)``:
    ``emitted[s, :accept_len[s]]`` are the accepted drafts and
    ``emitted[s, accept_len[s]]`` the correction/bonus token — the
    host appends ``accept_len + 1`` tokens and advances the fill by
    the same amount (rejected rows stay beyond the fill: free
    rollback)."""
    from dlrover_tpu.ops.attention import NEG_INF

    slots, T, V = logits.shape
    K = T - 1
    drafts = drafts.astype(jnp.int32)
    m = jnp.argmax(logits, axis=-1).astype(jnp.int32)   # [slots, T]
    greedy_ok = drafts == m[:, :K]
    tcol = jnp.asarray(temps, jnp.float32)[:, None]     # [slots, 1]
    base = jax.random.fold_in(rng, step_idx * 2)
    if K:
        scaled = logits[:, :K] / jnp.maximum(tcol, 1e-6)[..., None]
        logp = jax.nn.log_softmax(scaled, axis=-1)      # [slots, K, V]
        p_draft = jnp.take_along_axis(
            logp, drafts[..., None], axis=-1
        )[..., 0]                                       # [slots, K]
        u = jax.random.uniform(
            jax.random.fold_in(base, 1), (slots, K),
            minval=1e-20, maxval=1.0,
        )
        sampled_ok = jnp.log(u) < p_draft
        ok = jnp.where(tcol > 0.0, sampled_ok, greedy_ok)
        valid = jnp.arange(K)[None, :] < draft_len[:, None]
        ok = ok & valid
        acc = jnp.cumprod(ok.astype(jnp.int32), axis=1)
        a = jnp.sum(acc, axis=1).astype(jnp.int32)      # [slots] 0..K
    else:
        a = jnp.zeros((slots,), jnp.int32)
    # Final pick at position a: a < draft_len -> rejection CORRECTION
    # (residual: the rejected draft is masked out); a == draft_len ->
    # BONUS token from the model's own distribution (no mask). Greedy
    # rows: the mask can only remove a non-argmax token (rejection
    # means draft != argmax), so the masked argmax is the plain argmax.
    logits_a = jnp.take_along_axis(
        logits, a[:, None, None], axis=1
    )[:, 0]                                             # [slots, V]
    if K:
        rejected = a < draft_len
        d_a = jnp.take_along_axis(
            drafts, jnp.minimum(a, K - 1)[:, None], axis=1
        )[:, 0]
        mask = rejected[:, None] & (
            jnp.arange(V)[None, :] == d_a[:, None]
        )
        logits_a = jnp.where(mask, NEG_INF, logits_a)
    t_fin, _ = gen_lib.sample_token_logprobs(
        logits_a, jax.random.fold_in(base, 2), temps
    )
    active = jnp.asarray(active)
    t_fin = jnp.where(active, t_fin, fed_tokens)
    a = jnp.where(active, a, 0)
    pos = jnp.arange(T)[None, :]
    drafts_pad = jnp.concatenate(
        [drafts, jnp.zeros((slots, 1), jnp.int32)], axis=1
    )
    emitted = jnp.where(pos < a[:, None], drafts_pad, t_fin[:, None])
    return emitted, a


def clamp_draft_len(
    k: int, tokens_done: int, max_new_tokens: int,
    fill: int, max_len: int,
) -> int:
    """Per-slot draft budget: never draft past the request's remaining
    token budget (the verify step always emits one final token on top
    of the accepted drafts) or past the cache rows that can become
    visible (``fill + accepted + 1 <= max_len``). The ONE clamp shared
    by both engines and both drafters — the host-side half of the
    scheduler's verification-token accounting."""
    room_tokens = max_new_tokens - tokens_done - 1
    room_rows = max_len - 1 - fill
    return max(0, min(k, room_tokens, room_rows))
