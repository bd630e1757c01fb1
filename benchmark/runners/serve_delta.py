"""Runner ``serve_delta``: multi-turn sessions that GROW inside the timed
window, through ``FleetRouter`` -> one ``ThreadReplica`` ->
``PagedServingEngine`` with the delta-rule / full-attention model
(``models/delta_lm.py``). The stall log is ``runners/serve_sparse``'s, the
collector's log ``runners/serve_latent``'s, the host-pause watch and the
scope table ``runners/serve_conv``'s, the window's edges
``runners/serve_linear``'s (called, not copied); what differs is the
model, that the NEXT prompt is made of what the engine answered, and
what ``correct`` compares.

Traffic: ``clients`` clients in a closed loop. A SESSION is ``turns``
requests: an opening, then turns whose prompt is the last prompt + the
answer the engine gave + a fresh turn; after its last request the client
opens a new session and the old one's blocks and snapshots are left to
the cache's LRU. Lengths come from ``length_set_size`` fixed SCHEDULES
(client ``i`` replays schedule ``i mod size`` for ever), tokens from
(seed, client, session number). Set-up advances client ``i``'s first
session through ``i mod turns`` turns, so that the window sees every
depth from its first completion. The window lies between two completions
that each end a PERIOD of ``period_completions`` (one session of every
client): the first whole number of periods that lasts ``--seconds`` or
more (``serve_linear.window_edges``). Every request after a session's
first is a prefix hit that slots the last prompt's whole blocks into the
table and restores BOTH state arrays from the snapshot at their end; the
answer, the tail and the new turn are prefilled again from there.

``correct`` is decided on what the TIMED path produced and on probes over
the same live engine (limits below, each beside the chip readings that
set it; every one is on a MEDIAN or a SHARE). ``reference_sample`` of the
window's requests at their session's ``reference_min_turn``-th turn or
later are judged: the reference (``reference_olmo_hybrid.py``: the
recurrence a token a step, no cache) runs the session's WHOLE token
sequence, every earlier turn included.
(a) LOGITS of the timed path's tokens: every token the window emitted
    for the request against the reference's logits at its row: the
    median deficit below the reference's best, the share within the
    tolerance.
(b) What the timed chunks LANDED: the snapshot (both arrays) a timed
    chunk wrote at the prompt's last whole-block boundary, after hits,
    restores and chunks-from-state over every earlier turn, against the
    reference's ``S`` and last three projections at that row; the K and V
    rows of the first full layer over the whole conversation.
(c) LOGITS of the program itself: after the window the sampled requests
    are served once more (hit + restore + chunk from state + in-place
    steps, the other slots full of other sessions) and stay in their
    slots; two PROBE programs made of the timed programs' own functions
    (``kvpool/delta.py``'s ``chunk_forward`` and ``decode_forward``) read
    over the live pool and state the logits of the resumed chunk's rows
    and of the step each slot would take next: the median relative error
    against the reference's logits there; the slot's live state (both
    arrays) against the reference's at its row; each mixer's output
    before ``W_o``, a layer.
(d) Every hit restored a snapshot or was counted as rounded down, none
    was denied, the cache supplied ``prefix_hit_share_min`` of the prompt
    tokens, nothing compiled after warm-up, nothing was truncated, blocks
    and snapshot ids are conserved at the end.
Beside (a) to (c) the run reports what the REFERENCE reads on the same
yardstick when computed in the precision below the configuration's
(``low_*``).
"""

import gc
import time

import numpy as np

from benchmark import common, reference_olmo_hybrid as reference
from benchmark.runners import serve as dense_serve
from benchmark.runners import serve_conv, serve_latent, serve_linear
from benchmark.runners import serve_sparse

# The limits: each between the largest reading of the program over the
# seeds read BEFORE the reported runs and what the reference reads on the
# same yardstick in the precision below the configuration's (``low_*``: 3
# bits of mantissa), with room on both sides (my chip runs, PR 57; PERF.md
# section 6 has both readings of each).
# (a) How far below the float32 reference's best logit an emitted token
# may sit: the median over the emitted rows, and the share within the
# tolerance. These catch a gross fault; (b) and (c) part the precisions.
SERVE_LOGIT_TOL = 0.1
LOGIT_DEFICIT_MEDIAN_MAX = 0.03
LOGIT_WITHIN_SHARE_MIN = 0.9
# (b), (c) The landed snapshot and the live state, the median over (layer,
# head) of the relative error of a head's float32 matrix; the taps, a
# (layer, row).
STATE_REL_ERR_MEDIAN_MAX = 0.03
TAPS_REL_ERR_MEDIAN_MAX = 0.025
# The landed K and V rows of the FIRST full layer, the median over rows.
ROWS_REL_ERR_MEDIAN_MAX = 0.03
# (c) The program's own logits against the reference's, the median over
# the probed rows of |got - want| / |want| a row.
LOGITS_REL_ERR_MEDIAN_MAX = 0.05
# A mixer's output before W_o, the median over the probed rows, the worst
# layer of its kind.
DELTA_REL_ERR_MEDIAN_MAX = 0.05
FULL_REL_ERR_MEDIAN_MAX = 0.015
# Rows the reference takes a call.
BLOCK_ROWS = 512


def delta_config(cfg_json, **overrides):
    """The program's config for a configuration file (published keys)."""
    from dlrover_tpu.models import delta_lm

    sh = reference.shape_of(cfg_json)           # validates the keys
    if cfg_json.get("tie_word_embeddings", False):
        raise ValueError("this model's head is its own")
    kw = dict(
        vocab_size=sh["vocab"], embed_dim=sh["hidden"],
        layer_types=sh["types"], n_heads=sh["heads"],
        n_kv_heads=cfg_json["num_key_value_heads"], head_dim=sh["head_dim"],
        linear_heads=sh["l_heads"], linear_key_dim=sh["dk"],
        linear_value_dim=sh["dv"], conv_kernel=sh["taps"],
        allow_neg_eigval=bool(cfg_json["linear_allow_neg_eigval"]),
        mlp_dim=sh["mlp"], norm_eps=sh["eps"],
        dtype=cfg_json.get("torch_dtype")
        or cfg_json.get("assumed", {}).get("torch_dtype", "bfloat16"),
    )
    kw.update(overrides)
    return delta_lm.DeltaLMConfig(**kw)


engine_kwargs = serve_linear.engine_kwargs
snapshot_of = serve_conv._snapshot_of


# -- the traffic --------------------------------------------------------------


def schedules(traffic):
    """The fixed set of session schedules, the same for every seed: each
    ``(prompt additions [turns], answers [turns])``: the opening's length
    then each later turn's, and each request's forced answer length."""
    rng = np.random.default_rng(traffic["length_set_seed"])
    n, turns = traffic["length_set_size"], traffic["turns"]
    draw = dense_serve._draw
    opening = draw(traffic["opening_len"], n, rng)
    later = draw(traffic["turn_len"], n * (turns - 1), rng).reshape(n, -1)
    answers = draw(traffic["output_len"], n * turns, rng).reshape(n, turns)
    return [
        ([int(opening[i])] + later[i].tolist(), answers[i].tolist())
        for i in range(n)
    ]


class Client:
    """One closed-loop client: its schedule, its session's number and
    turn, and the conversation so far."""

    def __init__(self, index, schedule, vocab, seed):
        self.index, self.schedule = index, schedule
        self.vocab, self.seed = vocab, seed
        self.session, self.turn = -1, len(schedule[0])
        self.prompt = np.zeros(0, np.int32)
        self.rng = None

    def next_request(self, answer=()):
        """The next prompt and its forced answer length: the last prompt
        + ``answer`` (what the engine gave) + a fresh turn; a session
        past its last turn gives way to a new one."""
        adds, answers = self.schedule
        if self.turn >= len(adds):
            self.session, self.turn = self.session + 1, 0
            self.rng = np.random.default_rng(
                (self.seed, self.index, self.session)
            )
            self.prompt = np.zeros(0, np.int32)
            answer = ()
        fresh = self.rng.integers(0, self.vocab, adds[self.turn])
        self.prompt = np.concatenate(
            [self.prompt, np.asarray(answer, np.int32), fresh]
        ).astype(np.int32)
        n_new = answers[self.turn]
        self.turn += 1
        return self.prompt, int(n_new)


# -- the program's side: the probes -------------------------------------------


def build_probes(cfg, bs: int, kinds=None):
    """Programs of the check's own over the engine's LIVE pool and state,
    made of the functions the timed programs are made of (``kinds``: what
    the engine's own programs read the full layers' rows with; the delta
    step is always the definition's, which does not write the state in
    place): ``chunk(k, v,
    delta, taps, params, table_row, start, tokens, n_valid)`` walks one
    slot's chunk through every layer from the state ``delta [Ld, heads,
    dk, dv]`` / ``taps [Ld, K - 1, width]`` and hands out the logits of
    every row and, a layer, the mixer's output before ``W_o``;
    ``decode(k, v, delta, taps, params, tables, lengths, tokens)`` the
    step every slot would take next; ``landed(k, v, table_row)`` one
    slot's K and V rows of every full layer."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import delta_lm
    from dlrover_tpu.serving.kvpool import delta as programs

    f32 = jnp.float32
    kinds = dict(kinds or {}, delta_decode="jnp")

    def gated(probe, pick):
        return [
            pick(probe[layer]["gated"]).astype(f32)
            for layer in range(cfg.n_layers)
        ]

    @jax.jit
    def chunk(k, v, delta, taps, params, table_row, start, tokens, n_valid):
        probe = {}
        x, _, _ = programs.chunk_forward(
            cfg, k, v, delta[:, None], taps[:, None], params, tokens,
            table_row, start, 0, bs, n_valid, probe=probe, kinds=kinds,
        )
        return delta_lm.unembed(cfg, params, x)[0], gated(
            probe, lambda a: a[0]
        )

    @jax.jit
    def decode(k, v, delta, taps, params, tables, lengths, tokens):
        probe = {}
        # (the definition's update: the probe must not write the state)
        logits, _, _, _ = programs.decode_forward(
            cfg, k, v, delta, taps, params, tables, lengths, tokens, bs,
            probe=probe, kinds=kinds,
        )
        return logits, gated(probe, lambda a: a[:, 0])

    @jax.jit
    def landed(k, v, table_row):
        rows = lambda pool: pool[:, table_row].reshape(  # noqa: E731
            (pool.shape[0], -1) + pool.shape[3:]
        )[:, :, :cfg.n_kv_heads].astype(f32)
        return rows(k), rows(v)

    return chunk, decode, landed


def probe_program(engine, probes):
    """The probes' readings for each of ``probes`` (requests the engine
    has just served and still holds): a dict a request."""
    import jax
    import jax.numpy as jnp

    cfg, bs, c = engine.config, engine.block_size, engine.prefill_chunk
    chunk, decode, landed = build_probes(cfg, bs, engine.linear_kinds)
    k, v, delta, taps, delta_snaps, taps_snaps = engine._pools()
    params = engine._params
    tables = jnp.asarray(engine._tables)
    next_logits, next_gated = jax.device_get(decode(
        k, v, delta, taps, params, tables, jnp.asarray(engine._lengths),
        jnp.asarray(engine._tokens),
    ))
    out = []
    for r in probes:
        emitted = [int(t) for t in r.tokens]
        fill = int(engine._lengths[r.slot])
        if fill != r.prompt_len + len(emitted) - 1:
            raise RuntimeError(
                f"slot {r.slot} holds {fill} rows for a prompt of "
                f"{r.prompt_len} and {len(emitted)} tokens"
            )
        hit = r.prefix_hit_blocks
        start = hit * bs
        n_valid = min(c, r.prompt_len - start)
        restored = snapshot_of(engine, r.prompt, hit)
        before = [
            s[:, restored] if restored else jnp.zeros_like(s[:, 0])
            for s in (delta_snaps, taps_snaps)
        ]
        tokens = np.zeros((1, c), np.int32)
        tokens[0, :n_valid] = r.prompt[start:start + n_valid]
        chunk_logits, chunk_gated = jax.device_get(chunk(
            k, v, *before, params, tables[r.slot], jnp.int32(start),
            jnp.asarray(tokens), jnp.int32(n_valid),
        ))
        k_rows, v_rows = (
            np.asarray(a) for a in landed(k, v, tables[r.slot])
        )
        boundary = r.prompt_len // bs * bs
        written = snapshot_of(engine, r.prompt, boundary // bs)
        out.append({
            "seq": [int(t) for t in r.prompt] + emitted,
            "prompt_len": r.prompt_len, "emitted": emitted, "slot": r.slot,
            "hit_rows": start, "n_valid": n_valid,
            "restored": int(restored), "fill": fill, "boundary": boundary,
            "chunk_logits": np.asarray(chunk_logits),
            "chunk_gated": [np.asarray(g) for g in chunk_gated],
            "next_logits": np.asarray(next_logits)[r.slot],
            "next_gated": [np.asarray(g)[r.slot] for g in next_gated],
            "state": np.asarray(delta[:, r.slot]),
            "taps": np.asarray(taps[:, r.slot].astype(jnp.float32)),
            "snapshot": (
                np.asarray(delta_snaps[:, written]),
                np.asarray(taps_snaps[:, written].astype(jnp.float32)),
            ) if written else None,
            "k_landed": k_rows[:, :fill], "v_landed": v_rows[:, :fill],
        })
    return out


# -- the reference's side and the comparison ----------------------------------


def reference_side(params, sh, request, window_tokens, faults=(),
                   low_too=True):
    """The reference over one probed request's WHOLE sequence (every
    earlier turn included) and its readings of that request.
    ``window_tokens``: what the TIMED path emitted for the same prompt."""
    import jax.numpy as jnp

    seq, p, fill = request["seq"], request["prompt_len"], request["fill"]
    n = -(-len(seq) // BLOCK_ROWS) * BLOCK_ROWS
    tokens = np.zeros(n, np.int32)
    tokens[:len(seq)] = seq
    keep = (request["boundary"] - 1, fill - 1)

    def run(low, faults=faults, tokens=tokens):
        carry = reference.new_carry(sh, n)
        finals, gated, kept_s, kept_t = [], [], 0.0, 0.0
        for start in range(0, n, BLOCK_ROWS):
            carry, out = reference.advance(
                params, carry, tokens[start:start + BLOCK_ROWS], start, sh,
                low=low, faults=faults,
                keep_rows=tuple(r - start for r in keep),
            )
            finals.append(out["logits_of"])
            gated.append(out["gated"])
            kept_s = kept_s + out["state_rows"]
            kept_t = kept_t + out["taps_rows"]
        return dict(
            final=jnp.concatenate(finals), carry=carry,
            gated=[jnp.concatenate(g) for g in zip(*gated)],
            state_rows=np.asarray(kept_s), taps_rows=np.asarray(kept_t),
        )

    rel = lambda got, want: np.asarray(  # noqa: E731
        reference._rel(jnp.asarray(got), jnp.asarray(want))
    ).reshape(-1)
    flat = lambda a: np.asarray(a).reshape(np.asarray(a).shape[:2] + (-1,))  # noqa: E731
    ref = run(False)
    # (a) the timed path's tokens on the reference's logits. The probe's
    # own answer may leave the window's at a near-tie (the tail below
    # its hit is computed in another chunk): the window's tokens are
    # then judged by a pass over the WINDOW's sequence, not the probe's.
    emitted = np.asarray(window_tokens)
    emit_rows = p - 1 + np.arange(len(emitted))
    same = list(request["emitted"][:len(emitted)]) == list(emitted)
    if same:
        in_window = ref
    else:
        theirs = tokens.copy()
        theirs[p:p + len(emitted)] = emitted
        in_window = run(False, tokens=theirs)
    logits = np.asarray(reference.logits_at(
        params, in_window["final"], jnp.asarray(emit_rows)
    ))
    deficit = logits.max(-1) - logits[np.arange(len(emitted)), emitted]
    top2 = np.partition(logits, -2, axis=-1)[:, -2:]
    # (c) the program's own logits: the resumed chunk's rows and the step
    at, n_valid = request["hit_rows"], request["n_valid"]
    rows = np.concatenate([at + np.arange(n_valid), [fill]])
    got_logits = np.concatenate(
        [request["chunk_logits"][:n_valid], request["next_logits"][None]]
    )

    def own(side, low=False):
        return np.asarray(reference.logits_at(
            params, side["final"], jnp.asarray(rows), low=low
        ))

    want_logits = own(ref)
    side = {
        "deficit": deficit, "top2_gap": top2[:, 1] - top2[:, 0],
        "finite": bool(np.isfinite(logits).all()),
        "logits_err": rel(got_logits, want_logits),
        "tokens_equal": float(np.mean(
            np.asarray(request["emitted"][:len(emitted)]) == emitted
        )),
    }

    def state_readings(state, taps, snapshot):
        """(b) / (c)'s state readings of the program (or of a stand-in
        for it) against ``ref``."""
        r = {
            "state_err": rel(flat(state), flat(ref["state_rows"][:, 1])),
            "taps_err": rel(taps, ref["taps_rows"][:, 1]),
        }
        if snapshot is not None:
            r["snapshot_err"] = rel(
                flat(snapshot[0]), flat(ref["state_rows"][:, 0])
            )
            r["snapshot_taps_err"] = rel(snapshot[1], ref["taps_rows"][:, 0])
        else:
            r["snapshot_err"] = r["snapshot_taps_err"] = np.zeros((0,))
        return r

    side.update(state_readings(
        request["state"], request["taps"], request["snapshot"]
    ))
    want_k = np.asarray(ref["carry"]["k"])[:, :fill]
    want_v = np.asarray(ref["carry"]["v"])[:, :fill]
    side.update(
        k_rows_err=rel(request["k_landed"][0], want_k[0]),
        v_rows_err=rel(request["v_landed"][0], want_v[0]),
        k_rows_err_all=rel(request["k_landed"], want_k),
    )
    delta_err, full_err = [], []
    for layer, kind in enumerate(sh["types"]):
        want = np.asarray(ref["gated"][layer])[rows]
        got = np.concatenate([
            request["chunk_gated"][layer][:n_valid],
            request["next_gated"][layer][None],
        ])
        (delta_err if kind == reference.DELTA else full_err).append(
            rel(got, want)
        )
    side.update(delta_err=delta_err, full_err=full_err)
    if low_too:
        low = run(True, ())
        low_logits = np.asarray(reference.logits_at(
            params, low["final"], jnp.asarray(emit_rows), low=True
        ))
        own_logits = logits if same else np.asarray(reference.logits_at(
            params, ref["final"], jnp.asarray(emit_rows)
        ))
        side["low_deficit"] = own_logits.max(-1) - own_logits[
            np.arange(len(emitted)), low_logits.argmax(-1)
        ]
        side["low_logits_err"] = rel(own(low, low=True), want_logits)
        low_state = state_readings(
            low["state_rows"][:, 1], low["taps_rows"][:, 1],
            (low["state_rows"][:, 0], low["taps_rows"][:, 0]),
        )
        side.update({"low_" + k: v for k, v in low_state.items()})
        side["low_k_rows_err"] = rel(
            np.asarray(low["carry"]["k"])[0, :fill], want_k[0]
        )
        per_layer = lambda kind: [  # noqa: E731
            rel(np.asarray(low["gated"][i])[rows],
                np.asarray(ref["gated"][i])[rows])
            for i, t in enumerate(sh["types"]) if t == kind
        ]
        side["low_delta_err"] = per_layer(reference.DELTA)
        side["low_full_err"] = per_layer(reference.FULL)
    return side


def compare(requests, sides):
    """All readings of (a) to (c) over the probed requests."""
    cat = lambda name: np.concatenate(  # noqa: E731
        [np.asarray(s[name], np.float64).reshape(-1) for s in sides]
    )
    median = lambda a: float(np.median(a)) if len(a) else 0.0  # noqa: E731
    by_layer = lambda name: [  # noqa: E731
        median(np.concatenate([s[name][i] for s in sides]))
        for i in range(len(sides[0][name]))
    ]
    deficit = cat("deficit")
    check = {
        "n_requests": len(requests), "n_emitting": int(deficit.size),
        "rows_judged": [len(r["seq"]) for r in requests],
        # (a)
        "logits_finite": all(s["finite"] for s in sides),
        "logit_deficit_median": float(np.median(deficit)),
        "logit_deficit_p90": float(np.quantile(deficit, 0.9)),
        "logit_deficit_max": float(deficit.max()),
        "logit_within_share": float((deficit <= SERVE_LOGIT_TOL).mean()),
        "n_argmax_matches": int((deficit == 0).sum()),
        "median_top2_gap": float(np.median(cat("top2_gap"))),
        "probe_tokens_equal_share": float(np.mean(
            [s["tokens_equal"] for s in sides]
        )),
        # a request: how many tokens the window emitted, and the places
        # of those beyond the tolerance
        "emitted_by_request": [len(s["deficit"]) for s in sides],
        "over_tolerance_at": [
            np.nonzero(np.asarray(s["deficit"]) > SERVE_LOGIT_TOL)[0]
            .tolist()[:16] for s in sides
        ],
        # (b)
        "snapshot_err_median": median(cat("snapshot_err")),
        "snapshot_taps_err_median": median(cat("snapshot_taps_err")),
        "n_snapshots_read": sum(
            r["snapshot"] is not None for r in requests
        ),
        "k_rows_err_median": median(cat("k_rows_err")),
        "v_rows_err_median": median(cat("v_rows_err")),
        "k_rows_err_all_layers_median": median(cat("k_rows_err_all")),
        "n_rows_landed": int(cat("k_rows_err").size),
        "hits_restored": [
            int(r["restored"] > 0 or not r["hit_rows"]) for r in requests
        ],
        # (c)
        "logits_err_median": median(cat("logits_err")),
        "logits_err_max": float(cat("logits_err").max()),
        "state_err_median": median(cat("state_err")),
        "state_err_max": float(cat("state_err").max()),
        "taps_err_median": median(cat("taps_err")),
        "delta_err_median_by_layer": by_layer("delta_err"),
        "delta_err_median_max": max(by_layer("delta_err")),
        "full_err_median_by_layer": by_layer("full_err"),
        "full_err_median_max": max(by_layer("full_err")),
    }
    if "low_deficit" in sides[0]:
        low = cat("low_deficit")
        check.update(
            low_logit_deficit_median=float(np.median(low)),
            low_logit_within_share=float((low <= SERVE_LOGIT_TOL).mean()),
            low_logits_err_median=median(cat("low_logits_err")),
            low_state_err_median=median(cat("low_state_err")),
            low_snapshot_err_median=median(cat("low_snapshot_err")),
            low_taps_err_median=median(cat("low_taps_err")),
            low_k_rows_err_median=median(cat("low_k_rows_err")),
            low_delta_err_median_min=min(by_layer("low_delta_err")),
            low_full_err_median_min=min(by_layer("low_full_err")),
        )
    return check


def problems_of(check, judged="program"):
    """What ``check`` breaks. ``judged="reference_lower_precision"``
    (``controls_olmo_hybrid.py`` alone): the reference computed in the
    precision below, put in the program's place."""
    c = dict(check)
    if judged == "reference_lower_precision":
        c.update(
            logit_deficit_median=c["low_logit_deficit_median"],
            logit_within_share=c["low_logit_within_share"],
            logits_err_median=c["low_logits_err_median"],
            state_err_median=c["low_state_err_median"],
            snapshot_err_median=c["low_snapshot_err_median"],
            taps_err_median=c["low_taps_err_median"],
            snapshot_taps_err_median=c["low_taps_err_median"],
            k_rows_err_median=c["low_k_rows_err_median"],
            v_rows_err_median=c["low_k_rows_err_median"],
            delta_err_median_max=c["low_delta_err_median_min"],
            full_err_median_max=c["low_full_err_median_min"],
        )
    problems = []

    def limit(name, what, bound, upper=True):
        ok = c[name] <= bound if upper else c[name] >= bound
        if not ok:
            problems.append(f"{name} {c[name]:.4g}: {what} (limit {bound})")

    if not c["logits_finite"]:
        problems.append("reference logits not finite")
    limit("logit_deficit_median", "the window's tokens sit below the plain "
          "forward's best logit", LOGIT_DEFICIT_MEDIAN_MAX)
    limit("logit_within_share", "too few of the window's tokens within "
          f"{SERVE_LOGIT_TOL} of the plain forward's best logit",
          LOGIT_WITHIN_SHARE_MIN, upper=False)
    limit("logits_err_median", "the program's logits at the resumed "
          "chunk's rows and the next step against the plain forward's",
          LOGITS_REL_ERR_MEDIAN_MAX)
    limit("snapshot_err_median", "the delta state in the snapshots the "
          "timed chunks wrote at their prompts' last block boundary "
          "against the recurrence's", STATE_REL_ERR_MEDIAN_MAX)
    limit("snapshot_taps_err_median", "the convolution taps in those "
          "snapshots against the last three projections there",
          TAPS_REL_ERR_MEDIAN_MAX)
    limit("state_err_median", "the slots' delta state after their decode "
          "steps against the recurrence's S_t", STATE_REL_ERR_MEDIAN_MAX)
    limit("taps_err_median", "the slots' convolution taps after their "
          "decode steps", TAPS_REL_ERR_MEDIAN_MAX)
    limit("k_rows_err_median", "the K rows landed in the first full layer "
          "against the reference's", ROWS_REL_ERR_MEDIAN_MAX)
    limit("v_rows_err_median", "the V rows landed in the first full layer "
          "against the reference's", ROWS_REL_ERR_MEDIAN_MAX)
    limit("delta_err_median_max", "a delta mixer's output before W_o "
          "against the reference's", DELTA_REL_ERR_MEDIAN_MAX)
    limit("full_err_median_max", "a full mixer's output before W_o "
          "against the reference's", FULL_REL_ERR_MEDIAN_MAX)
    if not all(c["hits_restored"]):
        problems.append(
            "hits_restored: a probed request's hit has no snapshot in "
            f"the cache ({c['hits_restored']})"
        )
    if c["n_snapshots_read"] < c["n_requests"]:
        problems.append(
            f"only {c['n_snapshots_read']} of {c['n_requests']} probed "
            "prompts' boundary snapshots were still in the cache"
        )
    return problems


JUDGED = "program"     # controls_olmo_hybrid.py sets the other
PLANT = None           # ... or plants a fault in the program: plant(engine)
# What the last run's checks read, kept for ``controls_olmo_hybrid.py`` to
# judge once more against a reference with a fault planted in it.
LAST = {}


def judge(requests, window_tokens, params, sh, faults=(), judged="program",
          low_too=True):
    """The reference's side and the comparison for ``requests`` (the
    probes' readings): ``(check, problems)``. ``faults``: planted in the
    reference (``controls_olmo_hybrid.py``)."""
    t0 = time.time()
    sides = [
        reference_side(params, sh, r, w, faults=faults, low_too=low_too)
        for r, w in zip(requests, window_tokens)
    ]
    check = compare(requests, sides)
    check.update(seconds=time.time() - t0)
    return check, problems_of(check, judged)


# -- the run ------------------------------------------------------------------


def run(ctx):
    import jax

    # First, and before anything is built: a checkout without this model
    # fails here, at once.
    from dlrover_tpu.models import delta_lm

    counts = common.count_jax_events()
    from dlrover_tpu.observability import tracing
    from dlrover_tpu.serving.fleet import FleetRouter, ThreadReplica
    from dlrover_tpu.serving.kvpool import PagedServingEngine

    devices = jax.devices()
    device = common.device_facts(devices)
    if ctx["require_tpu"]:
        common.require_tpu(devices, ctx["chips"])
    cfg_json, traffic = ctx["config"], ctx["traffic"]
    cfg = delta_config(cfg_json)
    sh = reference.shape_of(cfg_json)
    eng = cfg_json["serve_engine"]
    log = common.EventLog(ctx["out_dir"] + "/events.jsonl")
    make_params = jax.jit(
        lambda key: delta_lm.init_params(cfg, key, dtype=cfg.compute_dtype)
    )
    key = common.rng_key(ctx["seed"])
    box = {"params": make_params(key)}

    t0 = time.time()
    engine = PagedServingEngine(cfg, box.pop("params"), **engine_kwargs(eng))
    engine.warmup()
    if PLANT is not None:
        PLANT(engine)
    if ctx["trace"]:
        box["scopes"] = serve_conv.program_scopes(engine)
        engine.step = dense_serve._annotated(
            engine.step, "bench.engine_step"
        )
        engine._run_prefill_chunk = dense_serve._annotated(
            engine._run_prefill_chunk, "bench.prefill_chunk"
        )
        engine._run_decode = dense_serve._annotated(
            engine._run_decode, "bench.decode"
        )
    box.update(
        traces=dict(engine.trace_counts),
        compiles=counts[common.BACKEND_COMPILE],
    )
    log.emit("engine_ready", seconds=time.time() - t0)

    tracer = None
    if ctx["trace"]:
        tracer = tracing.arm(
            tracing.Tracer(service="benchmark", ring_capacity=1 << 16)
        )
    plans = schedules(traffic)
    turns = traffic["turns"]
    clients = [
        Client(i, plans[i % len(plans)], cfg.vocab_size, ctx["seed"])
        for i in range(traffic["clients"])
    ]
    on_gc = serve_latent.log_full_collections(log)
    host_pauses, stop_watch = serve_conv.watch_host_pauses()
    replica = ThreadReplica("0", lambda: engine)
    router = FleetRouter([replica])
    router.start(timeout_s=60)
    live, done, parked = {}, [], []

    def decoded():
        return engine.metrics.tokens.value(kind="decode")

    def submit(client, answer=()):
        prompt, n_new = client.next_request(answer)
        req = router.submit(prompt, n_new, traffic["temperature"])
        live[req.request_id] = (req, client, prompt, n_new)

    def pump(until, phase):
        """``serve_conv.run``'s pump: hand finished requests out and
        refill, until ``until`` (a time, or a callable that says when to
        stop); returns the time it stopped. During ``setup`` a client
        whose first session has reached its depth is parked."""
        stop = until if callable(until) else (lambda: time.time() >= until)
        last, stalled = time.time(), False
        while True:
            if stop():
                return time.time()
            finished = router.step()
            now = time.time()
            if finished or phase in ("setup", "ramp"):
                if stalled:
                    log.emit("stall_end", seconds=now - last,
                             decode_tokens=decoded())
                last, stalled = now, False
            elif not stalled and now - last > 1.0:
                stalled = True
                log.emit("stall", phase=phase, since_s=now - last,
                         decode_tokens=decoded(),
                         stacks=serve_sparse.thread_stacks())
            for req in finished:
                _, client, prompt, n_new = live.pop(req.request_id)
                tokens = list(req.result.tokens) if req.result else []
                done.append({
                    "id": req.request_id, "phase": phase, "t": now,
                    "ok": bool(req.result and req.result.ok),
                    "client": client.index, "session": client.session,
                    "turn": client.turn, "prompt": prompt, "n_new": n_new,
                    "tokens": tokens,
                    "truncated": bool(req.result and req.result.truncated),
                    "ttft_s": req.result.ttft_s if req.result else None,
                    "compiles": counts[common.BACKEND_COMPILE],
                })
                if phase == "setup" and client.turn >= client.index % turns:
                    parked.append((client, tokens))
                else:
                    submit(client, tokens)
            if not finished:
                time.sleep(0.002)

    trace = dump = scopes = traced_window = None
    try:
        # Set-up: client i's first session through ``i mod turns`` turns.
        t0 = time.time()
        for client in clients:
            if client.index % turns:
                submit(client)
            else:
                parked.append((client, ()))
        pump(lambda: not live, "setup")
        resident = engine.kv_stats()
        hit0 = resident["prefix_hit_tokens"]
        prefilled0 = engine.metrics.tokens.value(kind="prefill")
        log.emit("sessions_advanced", seconds=time.time() - t0,
                 requests=len(done), cached_blocks=resident["cached"],
                 snapshots=resident["state_snapshots_live"])
        for client, answer in sorted(parked, key=lambda p: p[0].index):
            submit(client, answer)
        t_ramp = pump(time.time() + traffic["ramp_s"], "ramp")
        if ctx["trace"]:
            prof = common.Profile(ctx["out_dir"])
            t_prof = time.time()
            prof.start()
            try:
                pump(time.time() + traffic["trace_s"], "traced")
            finally:
                dump = prof.stop()
                traced_window = (t_prof, time.time())
            t_ramp = time.time()
        seen = [0, None]
        period = traffic["period_completions"]
        served_of = lambda: [  # noqa: E731
            d for d in done if d["phase"] != "setup"
        ]

        def window_done():
            # (asked again only when something has completed since)
            if len(done) != seen[0]:
                seen[:] = len(done), serve_linear.window_edges(
                    served_of(), t_ramp, ctx["seconds"], period
                )
            return seen[1]

        pump(window_done, "window")
    finally:
        router.stop()
        stop_watch()
        gc.callbacks.remove(on_gc)
        if tracer is not None:
            tracing.disarm()
    served = served_of()
    i0, i1 = serve_linear.window_edges(
        served, t_ramp, ctx["seconds"], period
    )
    in_window = served[i0 + 1:i1 + 1]
    t_window, t_end = served[i0]["t"], served[i1]["t"]
    setup_s = t_window - ctx["t_start"]
    compiles_in_window = served[i1]["compiles"] - served[i0]["compiles"]
    window_s = t_end - t_window
    for at, late in host_pauses:
        log.emit("host_pause", at=at, seconds=late,
                 in_window=bool(t_window <= at <= t_end))
    paused = [late for at, late in host_pauses if t_window <= at <= t_end]
    retraces = sum(engine.trace_counts.values()) - sum(
        box["traces"].values()
    )
    compiles = counts[common.BACKEND_COMPILE] - box["compiles"]
    kv_stats = {
        k: v for k, v in engine.kv_stats().items()
        if isinstance(v, (int, float, str))
    }
    hit_tokens = kv_stats["prefix_hit_tokens"] - hit0
    prefilled = engine.metrics.tokens.value(kind="prefill") - prefilled0
    hit_share = hit_tokens / max(hit_tokens + prefilled, 1)
    snapshot_restores = (
        kv_stats["state_restores_from_snapshot"]
        - resident["state_restores_from_snapshot"]
    )
    served_hits = kv_stats["prefix_hits"] - resident["prefix_hits"]
    given_up = (
        kv_stats["state_snapshots_given_up"]
        - resident["state_snapshots_given_up"]
    )
    peak = common.memory_peak(devices[:ctx["chips"]])
    spans = tracer.finished() if tracer is not None else []
    if dump:
        from benchmark import delta_scopes, sparse_scopes, trace_reduce

        sparse_scopes.label(dump, box.get("scopes") or {})
        trace = trace_reduce.reduce(dump)
        scopes = delta_scopes.reduce(dump)

    # The checks' program side: the replica's thread has stopped; a sample
    # of the window's requests deep in their sessions is served once more
    # over the same pool, state and prefix cache, with other clients'
    # conversations in the other slots, and stays in its slots for the
    # probes to read.
    t_join = time.time()
    while replica.alive() and time.time() - t_join < 120:
        time.sleep(0.05)
    if replica.alive():
        raise RuntimeError("the replica's loop did not stop")
    for req in list(engine.scheduler.active()) + list(engine.scheduler.queue):
        engine.cancel(req)
    engine.run_until_idle()
    conservation = None
    try:
        engine.check_block_invariants()
    except AssertionError as err:
        conservation = str(err)
    rng = np.random.default_rng((ctx["seed"], 10 ** 6))
    bs = eng["block_size"]
    # Deep in their sessions, among the window's LAST completions (one a
    # client: the snapshot a timed chunk wrote at such a prompt's last
    # block boundary is younger than the budget, and still in the cache:
    # asked, not assumed).
    deep = [
        d for d in (in_window or served)[-traffic["clients"]:]
        if d["ok"] and d["tokens"]
        and d["turn"] >= traffic["reference_min_turn"]
        and len(d["prompt"]) + len(d["tokens"]) + 8 <= eng["max_len"]
        and snapshot_of(engine, d["prompt"], len(d["prompt"]) // bs)
    ]
    picks = rng.permutation(len(deep))[:traffic["reference_sample"]]
    sample = [deep[i] for i in picks]
    room = lambda prompt: eng["max_len"] - len(prompt)  # noqa: E731
    probes = [engine.submit(d["prompt"], room(d["prompt"])) for d in sample]
    others = [
        c.prompt for c in clients
        if c.index not in {d["client"] for d in sample}
    ]
    for prompt in others[:engine.slots - len(probes)]:
        engine.submit(prompt, room(prompt))
    while any(
        len(r.tokens) + r.inflight < len(d["tokens"])
        for r, d in zip(probes, sample)
    ):
        engine.step()
        if any(r.failed for r in probes):
            raise RuntimeError("a probe request failed in the engine")
    engine._drain("probe")
    if any(r.slot < 0 for r in probes):
        raise RuntimeError("a probe left its slot before it was read")
    t0 = time.time()
    requests = probe_program(engine, probes) if probes else []
    probe_s = time.time() - t0
    denied = engine.kv_stats()["state_snapshots_denied"]
    del engine, router, probes
    box.clear()
    gc.collect()  # the device memory goes to the reference

    tokens_out = sum(len(d["tokens"]) for d in in_window)
    problems = []
    ids = [d["id"] for d in done]
    if len(set(ids)) != len(ids):
        problems.append("a request completed more than once")
    bad = [
        d["id"] for d in done
        if not d["ok"] or d["truncated"] or len(d["tokens"]) != d["n_new"]
    ]
    if bad:
        problems.append(
            f"{len(bad)} request(s) failed, were truncated or came back "
            f"short: {bad[:5]}"
        )
    if compiles or retraces:
        problems.append(
            f"{compiles} compile(s) / {retraces} retrace(s) after "
            f"warm-up ({compiles_in_window} inside the window)"
        )
    if not in_window:
        problems.append("no request completed inside the window")
    if not sample:
        problems.append(
            "none of the window's last requests was at its session's "
            f"{traffic['reference_min_turn']}th turn or later with its "
            "boundary's snapshot still in the cache"
        )
    if not hit_share >= traffic["prefix_hit_share_min"]:
        problems.append(
            f"{100 * hit_share:.2f} % of the prompt tokens since set-up "
            "came from the prefix cache, under "
            f"{100 * traffic['prefix_hit_share_min']:.0f} %"
        )
    if snapshot_restores != served_hits:
        problems.append(
            f"{served_hits} prefix hit(s) but {snapshot_restores} "
            "restored a state snapshot"
        )
    if denied:
        problems.append(f"{denied} prompt(s) were denied a snapshot id")
    if conservation:
        problems.append("blocks or snapshot ids not conserved at the "
                        f"window's end: {conservation}")

    check = {}
    if requests:
        params = make_params(key)   # bit-identical: same program, same key
        window_tokens = [d["tokens"] for d in sample]
        check, found = judge(
            requests, window_tokens, params, sh, judged=JUDGED
        )
        check.update(probe_seconds=probe_s,
                     turns_judged=[d["turn"] for d in sample])
        problems += found
        LAST.clear()
        LAST.update(requests=requests, window_tokens=window_tokens,
                    params=params, sh=sh)
    log.emit("reference", **check)
    ttfts = sorted(
        d["ttft_s"] for d in in_window if d["ttft_s"] is not None
    )
    return {
        "problems": problems,
        "attempted": len(done),
        "failed": len(bad),
        "end_to_end": {
            "serve_tokens_per_s": tokens_out / window_s,
            "setup_s": setup_s,
        },
        "device": dict(device, memory_peak_bytes=peak),
        "trace": trace,
        # under the key the accepted readers of a serve cell's scope
        # table read; benchmark/delta_scopes.py made it
        "sparse_scopes": scopes,
        "traced_window": traced_window,
        "dump": dump,
        "spans": spans,
        "window": {
            "seconds": window_s, "requests": len(in_window),
            "tokens_out": tokens_out,
            "tokens_in": sum(len(d["prompt"]) for d in in_window),
            "periods": (i1 - i0) // period,
            "in_flight_at_end": len(live),
            "host_pauses": len(paused), "host_pause_s": sum(paused),
            "rows_max": max(
                (len(d["prompt"]) + len(d["tokens"]) for d in in_window),
                default=0,
            ),
        },
        "prefix": {
            "hit_tokens": hit_tokens, "prefilled_tokens": prefilled,
            "hit_share": hit_share, "hits": served_hits,
            "snapshot_restores": snapshot_restores,
            "snapshots_given_up": given_up,
            "evicted_blocks": kv_stats["prefix_evicted_blocks"]
            - resident["prefix_evicted_blocks"],
            "setup_requests": len(done) - len(served),
        },
        "ttft_s": ttfts,
        "reference": check,
        "kv_stats": kv_stats,
        "requests": [
            {k: v for k, v in d.items() if k not in ("prompt", "tokens")}
            for d in done
        ],
        "events": common.EventLog.read(log.path),
    }
