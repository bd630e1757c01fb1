"""Runner ``serve_conv``: short turns against long RESIDENT session
contexts, through ``FleetRouter`` -> one ``ThreadReplica`` ->
``PagedServingEngine`` with the convolution / attention pattern model
(``models/conv_lm.py``). The request stream, the fixed schedule of
lengths and the stall log are ``runners/serve_sparse``'s, the traffic's
renaming and the collector's log ``runners/serve_latent``'s (called, not
copied); what differs is the model, what a prefix hit is, and what
``correct`` compares.

Traffic: ``sessions.count`` contexts of ``sessions.len`` tokens from
``--seed``. During set-up each context is served once, alone, through
the router (one token asked), so that its K/V blocks AND the state
snapshot at its end sit in the prefix cache before any client starts. A
request is then one context (fixed rotation) + a turn + an answer: a
prefix hit that slots 128 blocks into the table and restores one state
snapshot, one chunk carried on from the restored state, and the answer's
decode steps at ~8.5k rows a slot. The window opens at the first
completion after the ramp and closes at the first completion
``--seconds`` later (``serve_latent``'s finding, PR 38: cut at instants
of the clock a window holds one completion more or fewer from run to
run).

``correct`` (limits below, each beside the readings that set it; every
one is on a MEDIAN or a SHARE of rows, never on a single worst row).
After the window a sample of its requests is served once more, greedy,
with stream requests in the other slots (the cell's batch of 32), and
stays in its slots. Two PROBE programs of the check's own, made of the
functions the timed programs are made of (``kvpool/conv.py``'s
``chunk_forward`` and ``decode_forward`` with ``conv_lm.block``'s taps),
then read over the engine's live pool and state what the timed ones keep
to themselves. The reference runs each probed request's whole sequence
(context + turn + answer, ~8.8k tokens) once, free-running.
(a) LOGITS: every emitted token against the reference's logits at its
    row: how far below the reference's best it sits (a routing flip in
    some layer moves a row's logits by more than rounding): the median,
    and the share of rows within the tolerance.
(b) What the timed programs LANDED: the slot's convolution state after
    its decode steps, and the SNAPSHOT a timed chunk wrote at the
    prompt's last whole-block boundary (the replay's own chunk, or the
    window's turn where its entry was still cached and the replay hit
    it), against the reference's ``(z_{t-1}, z_t)``; the first attention layer's K rows (after norm and rotation)
    and V rows in the pool against the reference's, all rows and, apart,
    the rows right after the hit's boundary (the only ones a wrong
    restore reaches).
(c) Each layer fed the program's own inputs
    (``reference_lfm2.hold_layer``): the convolution mixer's output from
    the program's state; attention's output before ``W_o`` in the chunk
    and at the decode step, the reference's queries over the rows the
    program landed; the FFN's normed input and output; the experts
    chosen and their weights.
(d) the prefix cache served the contexts, every hit restored a snapshot,
    no expert row was dropped, nothing compiled after warm-up, nothing
    was truncated.
Beside each reading of (b) and (c) the run reports what the REFERENCE
reads on the same yardstick when computed in the precision below the
configuration's (``low_*``): every such limit lies between the two.
"""

import gc
import time

import numpy as np

from benchmark import common, reference_lfm2
from benchmark.runners import serve as dense_serve
from benchmark.runners import serve_latent, serve_sparse

# The limits, each with the chip readings that set it (my chip runs, PR
# 48: 26 seeds: set A's six, the first rehearsal's ten, set B's seven
# and set C's three; PERF.md section 6): about three times the largest
# the program read over its seeds, below what the REFERENCE reads on the
# same yardstick in the precision below the configuration's (``low_*``:
# float8's 3 bits of mantissa).
# (a) How far below the float32 reference's best logit an emitted token
# may sit: the median over the emitted rows, and the share within the
# tolerance. The head's logits are bfloat16 and of unit scale (a tied
# embedding drawn at 1 / sqrt(d)). Read: median 0.0 in every run, 90th
# percentile 0.21-0.43, largest 1.07-1.59; 73.1-83.3 % of 291-515 rows
# within 0.1; the reference's median top-2 gap 0.144-0.172. The
# reference free running with 3 bits of mantissa, judged as the program
# is (the token IT would emit at each row): median deficit 0.63-0.75,
# 13.4-23.4 % of rows within 0.1 (``low_logit_*``, 17 runs).
# WHY a quarter of the rows miss the tolerance (``route_flip_row_share``
# and the shares apart, set B and C): at 58-71 % of the emitted rows the
# program's functions choose another expert than the free-running
# reference in at least one of the 8 expert layers (1.4-2.1 layers a
# row; no shared expert damps one); the rows WITHOUT a flip are within
# at 88.0-96.9 %, the rows with one at 64.9-76.1 %.
# The share's limit stood at 0.8 before set A, whose six runs read
# 0.749-0.811 (four of them under 0.8, so NOT correct that day): 0.8
# was xing's reading, not this model's; 0.6 lies between this program's
# lowest (0.731) and the lower precision's highest (0.234).
SERVE_LOGIT_TOL = 0.1
LOGIT_DEFICIT_MEDIAN_MAX = 0.03
LOGIT_WITHIN_SHARE_MIN = 0.6
# (b) The landed state and snapshot of the FIRST convolution layer, whose
# inputs are the tokens' own (bfloat16: the product B * X rounded once),
# the median over (request, row).
# Read 0.00363-0.00382 (state) and 0.00362-0.00380 (snapshot); the
# reference's rows with 3 bits of mantissa 0.0258-0.0272.
STATE_REL_ERR_MEDIAN_MAX = 0.012
# Over EVERY layer, free running (a deep layer's inputs went through the
# layers below in bfloat16 on one side and float32 on the other), the
# same things are REPORTED and not limited, because the program's own
# readings leave no room under the reference's in the precision below
# for a limit at three times the largest (PERF.md section 7 row 31):
# ``state_err_all_layers_median`` 0.0170-0.0272 in 35 runs and 0.132 in
# one (low 0.336-0.469); ``snapshot_err_all_layers_median`` 0.018-0.028
# with four probed requests, 0.019-0.152 with a control's two (low
# 0.389-0.496); the rows right after a hit in the SECOND attention layer
# (``rows_after_hit_err_median_by_layer``) 0.015-0.18 (low 0.319-0.419;
# a restore that zeroes the layers above the first reads 0.80).
# The first attention layer's landed K and V rows, the median over rows,
# and over the rows right after a hit's boundary.
# Read 0.00669-0.00672 (K), 0.00643-0.00646 (V), 0.0063-0.0068 (after a
# hit); the reference's rows with 3 bits of mantissa 0.02652-0.02655.
ROWS_REL_ERR_MEDIAN_MAX = 0.02
# (c) A mixer's output on the program's own inputs, the median over the
# probed rows of a layer, the worst layer.
# Read 0.00394-0.00402 (convolution; the reference with 3 bits of
# mantissa 0.0599-0.0611) and 0.0029-0.0079 (attention, 26 seeds;
# 0.0281-0.0363). Attention's limit stood at 0.015 on set A's 0.0057,
# went to 0.018 on the rehearsal's 0.0061 and to 0.024 on set B's
# 0.0079: three times the largest reading each time, so those seeds SET
# it and are not "unused"; the seeds unused are the last call's.
CONV_REL_ERR_MEDIAN_MAX = 0.012
ATTN_REL_ERR_MEDIAN_MAX = 0.024
# The FFN's normed input (one rounding: read 0.00166-0.00167) and its
# output (read 0.00424-0.00428; the reference with 3 bits of mantissa
# 0.0598-0.0607).
H_REL_ERR_MEDIAN_MAX = 0.005
MLP_REL_ERR_MEDIAN_MAX = 0.013
# Rows routed as the reference routes the same input: read 1.0 in every
# layer of fifteen runs and 0.988 (one row of 84) in one; the
# reference's own router in bfloat16 routes 88.9-99.0 % alike, so this
# limit alone does not part the two (the weights' does: the routers'
# weights read the reference's to 9e-4 at worst, median 0.0).
ALIKE_SHARE_MIN = 0.95
ROUTE_WEIGHT_ERR_MEDIAN_MAX = 0.003
# Rows of a sampled request's turn chunk that are probed (evenly spread,
# the first two and the last among them).
CHUNK_ROWS = 24


def conv_config(cfg_json, **overrides):
    """The program's config for a configuration file (published keys)."""
    from dlrover_tpu.models import conv_lm

    sh = reference_lfm2.shape_of(cfg_json)      # validates the keys
    if not cfg_json.get("tied_head"):
        raise ValueError("this model's head is its embedding")
    if not cfg_json.get("use_expert_bias", True):
        raise ValueError("the router selects by score + bias")
    kw = dict(
        vocab_size=cfg_json["vocab_size"], embed_dim=sh["hidden"],
        layer_types=sh["types"], n_dense=sh["n_dense"],
        n_heads=sh["heads"], n_kv_heads=sh["kv_heads"],
        head_dim=sh["head_dim"], conv_taps=sh["taps"],
        mlp_dim=cfg_json["intermediate_size"],
        moe_mlp_dim=cfg_json["moe_intermediate_size"],
        n_experts=sh["experts"], moe_top_k=sh["top_k"],
        routed_scaling=sh["scaling"], rope_theta=sh["theta"],
        norm_eps=sh["eps"], dtype=cfg_json.get("torch_dtype", "bfloat16"),
    )
    kw.update(overrides)
    return conv_lm.ConvLMConfig(**kw)


def program_scopes(engine):
    """``serve_sparse.program_scopes`` for this model's programs: the
    chunk launch carries the slot and the snapshot's place after the
    plain arguments, and the table has to come from the program that
    ran."""
    import jax
    import jax.numpy as jnp

    from benchmark import trace_reduce

    shape = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    lead = jax.tree_util.tree_map(shape, (*engine._pools(), engine._params))
    i32, f32 = jnp.int32, jnp.float32
    arr = jax.ShapeDtypeStruct
    slots, mb = engine.slots, engine.max_blocks
    key = shape(engine._rng)
    texts = {
        "jit_step": engine._steps.decode.lower(
            *lead, arr((slots, mb), i32), arr((slots,), i32),
            arr((slots,), i32), arr((slots,), bool), arr((slots,), f32),
            key, arr((), i32), arr((), i32), arr((), i32),
        ),
        "jit_prefill": engine._steps.prefill.lower(
            *lead, arr((1, engine.prefill_chunk), i32), arr((mb,), i32),
            arr((), i32), arr((), i32), arr((), f32), key, arr((), i32),
            arr((), bool), arr((), i32), arr((), i32), arr((), i32),
        ),
    }
    return {
        name: trace_reduce.scopes_from_hlo(low.compile().as_text())
        for name, low in texts.items()
    }


# -- the program's side: the probes -------------------------------------------

EVERY_LAYER = ("x_in", "y_op", "x_mid", "h_mlp", "y_mlp")


def build_probes(cfg, bs: int):
    """Programs of the check's own over the engine's LIVE pool and state,
    made of the functions the timed programs are made of:
    ``chunk(k, v, before, params, table_row, start, tokens, sel)`` walks
    one slot's turn chunk through every layer from the state ``before
    [Lc, taps - 1, C]`` (the snapshot the hit restored) and hands out
    each layer's taps at the chunk's rows ``sel``; ``decode(k, v, state,
    params, tables, lengths, tokens)`` the step every slot would take
    next, read at every slot (plus the attention layers' new rows);
    ``answer(k, v, before, params, table_row, start, tokens)`` a slot's
    rows from a block boundary on as one chunk, for the experts every
    expert layer chooses at each (``[chunk, top_k]`` a layer);
    ``landed(k, v, table_row)`` one slot's rows of every attention layer
    ``[La, max_len, width]`` each."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.serving.kvpool import conv

    f32 = jnp.float32
    keep = cfg.conv_taps - 1

    def layer_taps(taps, pick, before):
        """Per layer what ``hold_layer`` reads, at ``pick``;
        ``before(i)``: convolution layer ``i``'s state before each
        picked row."""
        out, conv_at = [], 0
        for layer, kind in enumerate(cfg.layer_types):
            t = taps[layer]
            one = {k: pick(t[k]).astype(f32) for k in EVERY_LAYER}
            if kind == "conv":
                one["conv_before"] = before(conv_at).astype(f32)
                conv_at += 1
            else:
                a = pick(t["attn"]).astype(f32)
                one["attn"] = a.reshape(a.shape[0], -1)
            if "experts" in t:
                one["experts"] = t["experts"]
                one["weights"] = t["weights"].astype(f32)
            out.append(one)
        return out

    @jax.jit
    def chunk(k, v, before, params, table_row, start, tokens, sel):
        taps = {}
        _, _, zzs = conv.chunk_forward(
            cfg, k, v, before[:, None], params, tokens, table_row, start, 0,
            bs, taps=taps,
        )
        out = layer_taps(
            taps, lambda a: a[0][sel],
            lambda at: zzs[at][sel[:, None] + jnp.arange(keep)[None, :]],
        )
        for one in out:
            if "experts" in one:
                one["experts"] = one["experts"][sel]
                one["weights"] = one["weights"][sel]
        return out

    @jax.jit
    def decode(k, v, state, params, tables, lengths, tokens):
        taps = {}
        _, (k_new, v_new), _, _ = conv.decode_forward(
            cfg, k, v, state, params, tables, lengths, tokens, bs, taps=taps
        )
        out = layer_taps(taps, lambda a: a[:, 0], lambda at: state[at])
        return out, k_new.astype(f32), v_new.astype(f32)

    @jax.jit
    def answer(k, v, before, params, table_row, start, tokens):
        taps = {}
        conv.chunk_forward(
            cfg, k, v, before[:, None], params, tokens, table_row, start, 0,
            bs, taps=taps,
        )
        return [
            taps[layer]["experts"] for layer in range(len(cfg.layer_types))
            if "experts" in taps[layer]
        ]

    @jax.jit
    def landed(k, v, table_row):
        width = k.shape[-1]
        return (k[:, table_row].reshape(k.shape[0], -1, width).astype(f32),
                v[:, table_row].reshape(v.shape[0], -1, width).astype(f32))

    return chunk, decode, landed, answer


def _snapshot_of(engine, prompt, n_blocks: int) -> int:
    """The snapshot id the cache holds for the end of ``prompt``'s
    ``n_blocks``-th block (0: none)."""
    keys = engine._cache._chain_keys(np.asarray(prompt, np.int32))
    if not 0 < n_blocks <= len(keys):
        return 0
    entry = engine._cache._entries.get(keys[n_blocks - 1][0])
    return entry.snapshot if entry is not None else 0


def probe_program(engine, probes, window_tokens):
    """The probes' readings for each of ``probes`` (requests the engine
    has just served and still holds; ``window_tokens[i]``: what the same
    prompt was answered with inside the window): a dict a request."""
    import jax
    import jax.numpy as jnp

    cfg, bs, c = engine.config, engine.block_size, engine.prefill_chunk
    chunk, decode, landed, answer = build_probes(cfg, bs)
    k, v, state, snaps = engine._pools()
    params = engine._params
    tables = jnp.asarray(engine._tables)
    n_chunk = min(CHUNK_ROWS, c)
    next_taps, k_next, v_next = jax.device_get(decode(
        k, v, state, params, tables, jnp.asarray(engine._lengths),
        jnp.asarray(engine._tokens),
    ))
    state_now = np.asarray(state.astype(jnp.float32))
    attn_layers = [
        i for i, t in enumerate(cfg.layer_types) if t != "conv"
    ]
    out = []
    for r, window in zip(probes, window_tokens):
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
        restored = _snapshot_of(engine, r.prompt, hit)
        before = (
            snaps[:, restored] if restored
            else jnp.zeros_like(snaps[:, 0])
        )
        sel = np.unique(np.concatenate([
            np.arange(min(2, n_valid)),
            np.round(np.linspace(0, n_valid - 1, n_chunk)).astype(np.int64),
        ])).astype(np.int32)[:n_chunk]
        sel[-1] = n_valid - 1
        padded = np.concatenate(
            [sel, np.full(n_chunk - len(sel), n_valid - 1, np.int32)]
        )
        tokens = np.zeros((1, c), np.int32)
        tokens[0, :n_valid] = r.prompt[start:start + n_valid]
        got = jax.device_get(chunk(
            k, v, before, params, tables[r.slot], jnp.int32(start),
            jnp.asarray(tokens), jnp.asarray(padded),
        ))
        k_rows, v_rows = (np.asarray(a) for a in landed(k, v, tables[r.slot]))
        layers = []
        for layer, (one, nxt) in enumerate(zip(got, next_taps)):
            # the chunk's probed rows, then the decode step's one
            both = {
                name: np.concatenate(
                    [np.asarray(one[name])[:len(sel)],
                     np.asarray(nxt[name])[r.slot][None]]
                ) for name in one
            }
            if layer in attn_layers:
                at = attn_layers.index(layer)
                both["positions"] = np.concatenate(
                    [start + sel, [fill]]
                ).astype(np.int32)
                both["k_landed"] = np.concatenate(
                    [k_rows[at, :fill], k_next[at, r.slot][None]]
                )
                both["v_landed"] = np.concatenate(
                    [v_rows[at, :fill], v_next[at, r.slot][None]]
                )
            layers.append(both)
        # The answer's rows once more, as ONE chunk of the check's own
        # from the last block boundary below the prompt's last row and
        # the snapshot a timed chunk wrote there: the experts the
        # program's functions choose at each emitted row.
        answer_from = (r.prompt_len - 1) // bs * bs
        from_state = _snapshot_of(engine, r.prompt, answer_from // bs)
        answer_experts = None
        if from_state and fill - answer_from <= c:
            rows = np.zeros((1, c), np.int32)
            rows[0, :fill - answer_from] = (
                list(r.prompt) + emitted
            )[answer_from:fill]
            answer_experts = [
                np.asarray(a)[:fill - answer_from]
                for a in jax.device_get(answer(
                    k, v, snaps[:, from_state], params, tables[r.slot],
                    jnp.int32(answer_from), jnp.asarray(rows),
                ))
            ]
        # The prompt's last whole-block boundary: a timed chunk wrote a
        # snapshot there (this replay's, or the window's turn if its
        # entry was still cached and the replay hit it).
        boundary = r.prompt_len // bs * bs
        written = _snapshot_of(engine, r.prompt, boundary // bs)
        after = start + np.arange(2)
        after = after[after < fill]
        out.append({
            "answer_from": answer_from, "answer_experts": answer_experts,
            # the rows right after the hit's boundary, EVERY attention
            # layer's: [La, 2, width] each
            "after": after,
            "k_after": k_rows[:, after], "v_after": v_rows[:, after],
            "seq": [int(t) for t in r.prompt] + emitted,
            "prompt_len": r.prompt_len, "emitted": emitted,
            "window": [int(t) for t in window], "slot": r.slot,
            "hit_rows": start, "restored": int(restored), "fill": fill,
            "layers": layers,
            "state": state_now[:, r.slot],
            "boundary": boundary,
            "snapshot": np.asarray(
                snaps[:, written].astype(jnp.float32)
            ) if written else None,
            "k_landed0": k_rows[0, :fill], "v_landed0": v_rows[0, :fill],
        })
    return out


# -- the reference's side and the comparison ----------------------------------


def reference_side(params, cfg_json, request, pad_to):
    """The reference over one probed request's sequence, and its readings
    of that request: per-row arrays for :func:`compare`."""
    import jax.numpy as jnp

    seq, p, fill = request["seq"], request["prompt_len"], request["fill"]
    n_emit = len(request["emitted"])
    emit_rows = (p - 1 + np.arange(n_emit)).astype(np.int32)
    tokens = np.zeros(pad_to, np.int32)
    tokens[:len(seq)] = seq
    b = request["boundary"]
    state_rows = np.asarray([b - 2, b - 1, fill - 2, fill - 1], np.int32)
    after = jnp.asarray(request["after"])
    probes = []
    for one in request["layers"]:
        one = dict(one)
        for name in ("k_landed", "v_landed"):
            if name in one:     # one shape a run: padded, causally unseen
                rows = np.zeros((pad_to, one[name].shape[1]), np.float32)
                rows[:len(one[name])] = one[name]
                one[name] = rows
        probes.append(one)
    ref = reference_lfm2.forward_at(
        params, jnp.asarray(tokens), jnp.asarray(emit_rows), cfg_json,
        probes=probes, state_rows=jnp.asarray(np.maximum(state_rows, 0)),
        after_rows=after,
    )
    logits = np.asarray(ref["logits"])
    emitted = np.asarray(request["emitted"])
    deficit = logits.max(-1) - logits[np.arange(n_emit), emitted]
    # The same sequence through the reference in the precision BELOW the
    # configuration's, free running: the token IT would emit at each row,
    # on the program's yardstick.
    low_ref = reference_lfm2.forward_at(
        params, jnp.asarray(tokens), jnp.asarray(emit_rows), cfg_json,
        state_rows=jnp.asarray(np.maximum(state_rows, 0)),
        after_rows=after, low=True,
    )
    low_emitted = np.asarray(low_ref["logits"]).argmax(-1)
    low_deficit = logits.max(-1) - logits[np.arange(n_emit), low_emitted]
    # In how many of the expert layers the program's functions chose
    # other experts than the free-running reference, a row.
    flips = np.zeros((0,), np.int64)
    if request["answer_experts"] is not None:
        at = emit_rows - request["answer_from"]
        flips = sum(
            (np.sort(got[at], -1) != np.sort(np.asarray(want), -1)).any(-1)
            for got, want in zip(request["answer_experts"], ref["ids"])
        ).astype(np.int64)
    flip_deficit = deficit[:len(flips)]
    top2 = np.partition(logits, -2, axis=-1)[:, -2:]
    window = np.asarray(request["window"][:n_emit])
    differs = np.nonzero(window != emitted[:len(window)])[0]
    split_deficit = 0.0
    if len(differs):
        # one more emitted token of the same prefix: judged with the rest
        at = differs[0]
        split_deficit = float(logits[at].max() - logits[at, window[at]])
        deficit = np.append(deficit, split_deficit)
    rel = lambda got, want: np.asarray(  # noqa: E731
        reference_lfm2._rel(jnp.asarray(got), jnp.asarray(want))
    )
    low = lambda want: np.asarray(  # noqa: E731
        reference_lfm2.fp8(jnp.asarray(want))
    )
    # [Lc, 4, C]: a layer's z at the snapshot's two rows, then the
    # state's. Judged in the FIRST convolution layer, whose inputs are
    # the tokens' own (one rounding of B * X); deeper layers' inputs
    # went through the layers below in bfloat16 on one side and float32
    # on the other, and their drift is reported, not limited.
    z = np.stack([np.asarray(a) for a in ref["conv_z"]])
    low_z = np.stack([np.asarray(a) for a in low_ref["conv_z"]])
    state_err_all = rel(request["state"].reshape(-1, z.shape[-1]),
                        z[:, 2:].reshape(-1, z.shape[-1]))
    state_err = rel(request["state"][0], z[0, 2:])
    low_state_err = rel(low(z[0, 2:]), z[0, 2:])
    snap_err = np.zeros((0,))
    if request["snapshot"] is not None:
        snap_err = rel(request["snapshot"][0], z[0, :2])
    # EVERY layer's landed snapshot, and the K and V rows EVERY attention
    # layer landed right after the hit's boundary, free running (a deep
    # layer's inputs went through the layers below in bfloat16 on one
    # side and float32 on the other): limited between the program's
    # drift and the drift of the reference in the precision below.
    width = z.shape[-1]
    snap_err_all = low_snap_err_all = np.zeros((0,))
    if request["snapshot"] is not None:
        snap_err_all = rel(request["snapshot"].reshape(-1, width),
                           z[:, :2].reshape(-1, width))
        low_snap_err_all = rel(low_z[:, :2].reshape(-1, width),
                               z[:, :2].reshape(-1, width))
    after_err, low_after_err = [], []      # a row an attention layer
    for at, (want, low_want) in enumerate(
        zip(ref["kv_after"], low_ref["kv_after"])
    ):
        got = (request["k_after"][at], request["v_after"][at])
        after_err.append(np.concatenate(
            [rel(g, np.asarray(w)) for g, w in zip(got, want)]
        ))
        low_after_err.append(np.concatenate(
            [rel(np.asarray(l), np.asarray(w))
             for l, w in zip(low_want, want)]
        ))
    want_k, want_v = (np.asarray(a)[:fill] for a in ref["kv_rows"])
    k_err = rel(request["k_landed0"], want_k)
    v_err = rel(request["v_landed0"], want_v)
    after = request["hit_rows"] + np.arange(2)
    after = after[after < fill]
    held = [
        {k: np.asarray(v) for k, v in layer.items()} for layer in ref["held"]
    ]
    return {
        "deficit": deficit, "top2_gap": top2[:, 1] - top2[:, 0],
        "replayed": int(differs[0]) if len(differs) else len(window),
        "split_deficit": split_deficit,
        "finite": bool(np.isfinite(logits).all()),
        "state_err": state_err, "low_state_err": low_state_err,
        "state_err_all_layers": state_err_all,
        "low_state_err_all_layers": rel(
            low_z[:, 2:].reshape(-1, z.shape[-1]),
            z[:, 2:].reshape(-1, z.shape[-1]),
        ),
        "low_deficit": low_deficit,
        "flips": flips, "flip_deficit": flip_deficit,
        "snapshot_err": snap_err,
        "snapshot_err_all_layers": snap_err_all,
        "low_snapshot_err_all_layers": low_snap_err_all,
        "after_err_by_layer": after_err,
        "low_after_err_by_layer": low_after_err,
        "k_rows_err": k_err, "v_rows_err": v_err,
        "rows_after_hit_err": np.concatenate([k_err[after], v_err[after]]),
        "low_k_rows_err": rel(low(want_k), want_k),
        "low_v_rows_err": rel(low(want_v), want_v),
        "held": held,
    }


def compare(requests, sides, layer_types):
    """All readings of (a), (b) and (c) over the probed requests."""
    cat = lambda name: np.concatenate(  # noqa: E731
        [np.asarray(s[name], np.float64).reshape(-1) for s in sides]
    )
    n_layers = len(sides[0]["held"])
    held = lambda name, layer: np.concatenate([  # noqa: E731
        np.asarray(s["held"][layer][name], np.float64) for s in sides
        if name in s["held"][layer]
    ])
    conv_layers = [i for i, t in enumerate(layer_types) if t == "conv"]
    attn_layers = [i for i, t in enumerate(layer_types) if t != "conv"]
    expert_layers = [
        i for i in range(n_layers) if "alike" in sides[0]["held"][i]
    ]
    by_layer = lambda name, fn, layers=range(n_layers): [  # noqa: E731
        float(fn(held(name, i))) for i in layers
    ]
    median = lambda a: float(np.median(a)) if len(a) else 0.0  # noqa: E731
    deficit = cat("deficit")
    flips, flip_deficit = cat("flips"), cat("flip_deficit")
    within = lambda a: (  # noqa: E731
        float((a <= SERVE_LOGIT_TOL).mean()) if len(a) else None
    )
    after_by_layer = lambda name: [  # noqa: E731
        float(np.median(np.concatenate([s[name][at] for s in sides])))
        for at in range(len(attn_layers))
    ]
    alike_w = np.concatenate([
        held("weight_err", i)[held("alike", i) > 0] for i in expert_layers
    ]) if expert_layers else np.zeros((0,))
    return {
        "n_requests": len(requests), "n_emitting": int(deficit.size),
        "n_layers": n_layers,
        # (a)
        "logits_finite": all(s["finite"] for s in sides),
        "logit_deficit_median": float(np.median(deficit)),
        "logit_deficit_p90": float(np.quantile(deficit, 0.9)),
        "logit_deficit_max": float(deficit.max()),
        "logit_within_share": float((deficit <= SERVE_LOGIT_TOL).mean()),
        "n_argmax_matches": int((deficit == 0).sum()),
        "median_top2_gap": float(np.median(cat("top2_gap"))),
        "replayed_tokens": [s["replayed"] for s in sides],
        "window_tokens": [len(r["window"]) for r in requests],
        "split_deficit_max": max(s["split_deficit"] for s in sides),
        # why rows miss the tolerance: rows where the program's functions
        # chose other experts than the free-running reference in some
        # expert layer, and the share within the tolerance apart
        "n_rows_flip_judged": int(flips.size),
        "route_flip_row_share": (
            float((flips > 0).mean()) if flips.size else None
        ),
        "route_flips_per_row_mean": (
            float(flips.mean()) if flips.size else None
        ),
        "logit_within_share_unflipped": within(flip_deficit[flips == 0]),
        "logit_within_share_flipped": within(flip_deficit[flips > 0]),
        "logit_deficit_p90_unflipped": (
            float(np.quantile(flip_deficit[flips == 0], 0.9))
            if (flips == 0).any() else None
        ),
        # (b)
        "state_err_median": median(cat("state_err")),
        "state_err_all_layers_median": median(cat("state_err_all_layers")),
        "state_err_all_layers_max": float(cat("state_err_all_layers").max()),
        "snapshot_err_median": median(cat("snapshot_err")),
        "n_snapshots_read": int(cat("snapshot_err").size),
        "snapshot_err_all_layers_median": median(
            cat("snapshot_err_all_layers")
        ),
        "rows_after_hit_err_median_max": max(
            after_by_layer("after_err_by_layer")
        ),
        "rows_after_hit_err_median_by_layer": after_by_layer(
            "after_err_by_layer"
        ),
        "k_rows_err_median": median(cat("k_rows_err")),
        "k_rows_err_p99": float(np.quantile(cat("k_rows_err"), 0.99)),
        "v_rows_err_median": median(cat("v_rows_err")),
        "v_rows_err_p99": float(np.quantile(cat("v_rows_err"), 0.99)),
        "rows_after_hit_err_median": median(cat("rows_after_hit_err")),
        "n_rows_landed": int(cat("k_rows_err").size),
        "hits_restored": [int(r["restored"] > 0) for r in requests],
        # (c)
        "conv_err_median_max": max(by_layer("op_err", np.median, conv_layers)),
        "conv_err_median_by_layer": by_layer("op_err", np.median, conv_layers),
        "attn_err_median_max": max(by_layer("op_err", np.median, attn_layers)),
        "attn_err_median_by_layer": by_layer("op_err", np.median, attn_layers),
        "attn_err_decode_row": [
            float(s["held"][i]["op_err"][-1])
            for s in sides for i in attn_layers
        ],
        "h_err_median_max": max(by_layer("h_err", np.median)),
        "mlp_err_median_max": max(by_layer("y_err", np.median)),
        "mlp_err_median_by_layer": by_layer("y_err", np.median),
        "mlp_err_max_by_layer": by_layer("y_err", np.max),
        "alike_share_min": min(
            by_layer("alike", np.mean, expert_layers), default=1.0
        ),
        "alike_share_by_layer": by_layer("alike", np.mean, expert_layers),
        "weight_err_median": median(alike_w),
        "weight_err_max": float(alike_w.max()) if len(alike_w) else 0.0,
        # the reference in the precision below, on the same yardsticks
        "low_logit_deficit_median": float(np.median(cat("low_deficit"))),
        "low_logit_deficit_p90": float(np.quantile(cat("low_deficit"), 0.9)),
        "low_logit_within_share": within(cat("low_deficit")),
        "low_state_err_all_layers_median": median(
            cat("low_state_err_all_layers")
        ),
        "low_snapshot_err_all_layers_median": median(
            cat("low_snapshot_err_all_layers")
        ),
        "low_rows_after_hit_err_median_by_layer": after_by_layer(
            "low_after_err_by_layer"
        ),
        "low_state_err_median": median(cat("low_state_err")),
        "low_k_rows_err_median": median(cat("low_k_rows_err")),
        "low_v_rows_err_median": median(cat("low_v_rows_err")),
        "low_conv_err_median_min": min(
            by_layer("low_op_err", np.median, conv_layers)
        ),
        "low_attn_err_median_min": min(
            by_layer("low_op_err", np.median, attn_layers)
        ),
        "low_mlp_err_median_min": min(by_layer("low_y_err", np.median)),
        "low_mlp_err_median_by_layer": by_layer("low_y_err", np.median),
        "low_alike_share_min": min(
            by_layer("low_alike", np.mean, expert_layers), default=1.0
        ),
    }


def problems_of(check, judged="program"):
    """What ``check`` breaks. ``judged="reference_lower_precision"``
    (``controls_lfm2.py`` alone): the reference computed in the precision
    below, put in the program's place on (b)'s and (c)'s yardsticks."""
    c = dict(check)
    if judged == "reference_lower_precision":
        c.update(
            logit_deficit_median=c["low_logit_deficit_median"],
            logit_within_share=c["low_logit_within_share"],
            state_err_median=c["low_state_err_median"],
            k_rows_err_median=c["low_k_rows_err_median"],
            v_rows_err_median=c["low_v_rows_err_median"],
            conv_err_median_max=c["low_conv_err_median_min"],
            attn_err_median_max=c["low_attn_err_median_min"],
            mlp_err_median_max=c["low_mlp_err_median_min"],
            alike_share_min=c["low_alike_share_min"],
        )
    problems = []

    def limit(name, what, bound, upper=True):
        ok = c[name] <= bound if upper else c[name] >= bound
        if not ok:
            problems.append(f"{name} {c[name]:.4g}: {what} (limit {bound})")

    if not c["logits_finite"]:
        problems.append("reference logits not finite")
    limit("logit_deficit_median", "the emitted tokens sit below the plain "
          "forward's best logit", LOGIT_DEFICIT_MEDIAN_MAX)
    limit("logit_within_share", "too few emitted tokens within "
          f"{SERVE_LOGIT_TOL} of the plain forward's best logit",
          LOGIT_WITHIN_SHARE_MIN, upper=False)
    limit("state_err_median", "the slots' convolution state after their "
          "decode steps against the reference's gated inputs (first "
          "convolution layer)", STATE_REL_ERR_MEDIAN_MAX)
    limit("snapshot_err_median", "the snapshots the timed chunks wrote at "
          "their prompts' last block boundary against the reference's "
          "(first convolution layer)", STATE_REL_ERR_MEDIAN_MAX)
    limit("k_rows_err_median", "the K rows (normed, rotated) the timed "
          "programs landed in the first attention layer against the "
          "reference's", ROWS_REL_ERR_MEDIAN_MAX)
    limit("v_rows_err_median", "the V rows the timed programs landed in "
          "the first attention layer against the reference's",
          ROWS_REL_ERR_MEDIAN_MAX)
    limit("rows_after_hit_err_median", "the K and V rows right after a "
          "hit's boundary (what a wrong restore reaches)",
          ROWS_REL_ERR_MEDIAN_MAX)
    limit("conv_err_median_max", "a convolution mixer's output against "
          "the reference's on the same input and state",
          CONV_REL_ERR_MEDIAN_MAX)
    limit("attn_err_median_max", "attention before W_o against the "
          "reference's queries over the landed rows",
          ATTN_REL_ERR_MEDIAN_MAX)
    limit("h_err_median_max", "the FFN's normed input against the "
          "reference's on the same residual", H_REL_ERR_MEDIAN_MAX)
    limit("mlp_err_median_max", "the FFN's output against the reference's "
          "on the same input", MLP_REL_ERR_MEDIAN_MAX)
    limit("alike_share_min", "too few rows routed as the reference routes "
          "the same input", ALIKE_SHARE_MIN, upper=False)
    limit("weight_err_median", "router weights against the reference's",
          ROUTE_WEIGHT_ERR_MEDIAN_MAX)
    if not all(c["hits_restored"]):
        problems.append(
            "hits_restored: a probed request's hit has no snapshot in "
            f"the cache ({c['hits_restored']})"
        )
    return problems


def prefix_problems(hit_tokens, admissions, traffic):
    """(d)'s first part: what ``admissions`` requests admitted since the
    sessions were resident, whose hits supplied ``hit_tokens`` tokens,
    break. The traffic's share is of their CONTEXT tokens; and since the
    traffic says a context is never prefilled once set-up is over, one
    that was is named whatever the share."""
    context_tokens = admissions * traffic["sessions"]["len"]
    share = hit_tokens / max(context_tokens, 1)
    problems = []
    if not share >= traffic["prefix_hit_share_min"]:
        problems.append(
            f"{100 * share:.2f} % of the admitted requests' context "
            f"tokens came from the prefix cache, under "
            f"{100 * traffic['prefix_hit_share_min']:.0f} %"
        )
    if hit_tokens < context_tokens:
        problems.append(
            f"{admissions} request(s) admitted but the cache supplied "
            f"{hit_tokens} tokens, under their contexts' {context_tokens}: "
            "a context was prefilled after set-up"
        )
    return problems


JUDGED = "program"   # controls_lfm2.py's last control sets the other

# A wake-up this late is the MACHINE's pause, not the program's: the
# collector's longest stop of every thread here is 0.04 s (a generation-1
# collection), the machine's shortest pause 0.088 s (my chip runs, PR 48).
HOST_PAUSE_MIN_S = 0.06


def watch_host_pauses(period_s=0.005):
    """A thread that sleeps ``period_s`` at a time and notes every
    wake-up that came ``HOST_PAUSE_MIN_S`` late or more: ``(when it fell
    asleep, seconds late)``. The one-chip machine stops ALL its processes
    for 0.09-0.12 s at a time, none to four times in 30 s (a child
    process that imports nothing saw each pause of this one at the same
    instant, as long: my chip runs, PR 48), and once for 2.9 s; a step
    here is 18 ms, so each costs the window's rate 0.37 % and a run's
    reader should be able to tell such a window from a slow program.
    Returns ``(pauses, stop)``: the growing list, and what ends the
    thread."""
    import threading

    pauses, done = [], threading.Event()

    def loop():
        last = time.time()
        while not done.wait(period_s):
            now = time.time()
            if now - last - period_s >= HOST_PAUSE_MIN_S:
                pauses.append((last, now - last - period_s))
            last = now

    threading.Thread(target=loop, name="host-pause-watch", daemon=True).start()
    return pauses, done.set


# -- the run ------------------------------------------------------------------


def run(ctx):
    import jax

    # First, and before anything is built: a checkout without this model
    # fails here, at once.
    from dlrover_tpu.models import conv_lm

    counts = common.count_jax_events()
    from dlrover_tpu.observability import tracing
    from dlrover_tpu.serving.fleet import FleetRouter, ThreadReplica
    from dlrover_tpu.serving.kvpool import PagedServingEngine

    devices = jax.devices()
    device = common.device_facts(devices)
    if ctx["require_tpu"]:
        common.require_tpu(devices, ctx["chips"])
    cfg_json = ctx["config"]
    traffic = serve_latent.as_documents(ctx["traffic"])
    cfg = conv_config(cfg_json)
    eng = cfg_json["serve_engine"]
    log = common.EventLog(ctx["out_dir"] + "/events.jsonl")
    make_params = jax.jit(
        lambda key: conv_lm.init_params(cfg, key, dtype=cfg.compute_dtype)
    )
    key = common.rng_key(ctx["seed"])
    box = {"params": make_params(key)}

    # The engine is built here and handed to the replica's thread: a
    # failure to build it is this process's error at once.
    t0 = time.time()
    engine = PagedServingEngine(
        cfg, box.pop("params"), slots=eng["slots"],
        max_len=eng["max_len"], prefill_chunk=eng["prefill_chunk"],
        block_size=eng["block_size"], num_blocks=eng.get("num_blocks"),
    )
    engine.warmup()
    if ctx["trace"]:
        box["scopes"] = program_scopes(engine)
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
    stream = serve_sparse.request_stream(traffic, cfg.vocab_size, ctx["seed"])
    on_gc = serve_latent.log_full_collections(log)
    host_pauses, stop_watch = watch_host_pauses()
    replica = ThreadReplica("0", lambda: engine)
    router = FleetRouter([replica])
    router.start(timeout_s=60)
    live, done = {}, []

    def decoded():
        return engine.metrics.tokens.value(kind="decode")

    def submit(prompt=None, n_new=None):
        if prompt is None:
            prompt, n_new = next(stream)
        req = router.submit(prompt, n_new, traffic["temperature"])
        live[req.request_id] = (req, prompt, n_new)

    def pump(until, phase, to_completion=False):
        """Hand finished requests out and refill, until ``until`` (a
        time, or a callable that says when to stop); ``to_completion``:
        and then on to the next completion, whose time is returned (an
        edge of the window). A second without a completion while clients
        wait is logged with every thread's stack."""
        stop = until if callable(until) else (lambda: time.time() >= until)
        last, stalled = time.time(), False
        while True:
            past = stop()
            if past and not to_completion:
                return time.time()
            finished = router.step()
            now = time.time()
            if finished or phase in ("sessions", "ramp"):
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
                _, prompt, n_new = live.pop(req.request_id)
                done.append({
                    "id": req.request_id, "phase": phase, "t": now,
                    "ok": bool(req.result and req.result.ok),
                    "prompt": prompt, "n_new": n_new,
                    "tokens": list(req.result.tokens) if req.result else [],
                    "truncated": bool(req.result and req.result.truncated),
                    "ttft_s": req.result.ttft_s if req.result else None,
                })
                if phase != "sessions":
                    submit()
            if past and finished:
                return now
            if not finished:
                time.sleep(0.002)

    trace = dump = scopes = traced_window = None
    try:
        # Set-up: every session's context once, alone, so that its
        # blocks AND the snapshot at its end are in the prefix cache
        # before any client starts.
        t0 = time.time()
        for context in serve_sparse.documents(
            traffic, cfg.vocab_size, ctx["seed"]
        ):
            submit(context.tolist(), 1)
            pump(lambda: not live, "sessions")
        resident = engine.kv_stats()
        hit0 = resident["prefix_hit_tokens"]
        prefilled0 = engine.metrics.tokens.value(kind="prefill")
        log.emit("sessions_resident", seconds=time.time() - t0,
                 cached_blocks=resident["cached"],
                 snapshots=resident["state_snapshots_live"])
        for _ in range(traffic["clients"]):
            submit()
        t_window = pump(
            time.time() + traffic["ramp_s"], "ramp",
            to_completion=not ctx["trace"],
        )
        if ctx["trace"]:
            prof = common.Profile(ctx["out_dir"])
            t_prof = time.time()
            prof.start()
            try:
                pump(time.time() + traffic["trace_s"], "traced")
            finally:
                dump = prof.stop()
                traced_window = (t_prof, time.time())
            t_window = pump(time.time(), "traced", to_completion=True)
        compiles_before = counts[common.BACKEND_COMPILE]
        setup_s = t_window - ctx["t_start"]
        t_end = pump(t_window + ctx["seconds"], "window", to_completion=True)
        compiles_in_window = (
            counts[common.BACKEND_COMPILE] - compiles_before
        )
    finally:
        router.stop()
        stop_watch()
        gc.callbacks.remove(on_gc)
        if tracer is not None:
            tracing.disarm()
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
    served_hits = kv_stats["prefix_hits"] - resident["prefix_hits"]
    # What the traffic's ``prefix_hit_share_min`` is held against: of the
    # CONTEXT tokens of the requests admitted since the sessions were
    # resident, the share the cache supplied. A turn's own tokens are new
    # by construction (``hit_share`` above counts them, so it cannot pass
    # sessions.len / (sessions.len + a turn)); a context is resident, so
    # this reads 1.0, and one request that prefilled its context shows.
    admissions = served_hits + (
        kv_stats["prefix_misses"] - resident["prefix_misses"]
    )
    context_hit_share = hit_tokens / max(
        admissions * traffic["sessions"]["len"], 1
    )
    snapshot_restores = (
        kv_stats["state_restores_from_snapshot"]
        - resident["state_restores_from_snapshot"]
    )
    peak = common.memory_peak(devices[:ctx["chips"]])
    spans = tracer.finished() if tracer is not None else []
    if dump:
        from benchmark import conv_scopes, sparse_scopes, trace_reduce

        sparse_scopes.label(dump, box.get("scopes") or {})
        trace = trace_reduce.reduce(dump)
        scopes = conv_scopes.reduce(dump)

    # The checks' program side. The replica's thread has stopped; what
    # is still in the engine is cancelled. A sample of the window's
    # requests is served once more from here, over the same pool, state
    # and prefix cache, with stream requests in the other slots (the
    # cell's batch), and stays in its slots for the probes to read.
    t_join = time.time()
    while replica.alive() and time.time() - t_join < 120:
        time.sleep(0.05)
    if replica.alive():
        raise RuntimeError("the replica's loop did not stop")
    for req in list(engine.scheduler.active()) + list(engine.scheduler.queue):
        engine.cancel(req)
    engine.run_until_idle()
    in_window = [d for d in done if d["phase"] == "window"]
    served = [d for d in done if d["phase"] != "sessions"]
    rng = np.random.default_rng((ctx["seed"], 10 ** 6))
    pool = [d for d in (in_window or served) if d["ok"] and d["tokens"]]
    picks = rng.permutation(len(pool))[:traffic["reference_sample"]]
    sample = [pool[i] for i in picks]
    out_max = traffic["output_len"]["max"]
    longest = traffic["sessions"]["len"] + traffic["turn_len"]["max"]
    n_new = out_max + min(4 * engine.slots, eng["max_len"] - longest - out_max)
    probes = [engine.submit(d["prompt"], n_new) for d in sample]
    for _ in range(engine.slots - len(probes)):
        engine.submit(next(stream)[0], n_new)
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
    requests = probe_program(
        engine, probes, [d["tokens"] for d in sample]
    ) if probes else []
    probe_s = time.time() - t0
    probe_dropped = engine.kv_stats()["moe_rows_dropped"]
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
    problems += prefix_problems(hit_tokens, admissions, traffic)
    if snapshot_restores != served_hits:
        problems.append(
            f"{served_hits} prefix hit(s) but {snapshot_restores} "
            "restored a state snapshot"
        )
    if probe_dropped:
        problems.append(f"{probe_dropped} expert row(s) dropped")

    check = {}
    if requests:
        params = make_params(key)   # bit-identical: same program, same key
        t0 = time.time()
        pad_to = -(-max(len(r["seq"]) for r in requests) // 1024) * 1024
        sides = [
            reference_side(params, cfg_json, r, pad_to) for r in requests
        ]
        check = compare(requests, sides, cfg.layer_types)
        check.update(probe_seconds=probe_s, seconds=time.time() - t0)
        problems += problems_of(check, JUDGED)
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
        # table read; benchmark/conv_scopes.py made it
        "sparse_scopes": scopes,
        "traced_window": traced_window,
        "dump": dump,
        "spans": spans,
        "window": {
            "seconds": window_s, "requests": len(in_window),
            "tokens_out": tokens_out,
            "tokens_in": sum(len(d["prompt"]) for d in in_window),
            "in_flight_at_end": len(live),
            # the machine's pauses inside the window (not the program's;
            # not taken out of the rate): how many, and their seconds
            "host_pauses": len(paused), "host_pause_s": sum(paused),
        },
        "prefix": {
            "hit_tokens": hit_tokens, "prefilled_tokens": prefilled,
            "hit_share": hit_share, "hits": served_hits,
            "admissions": admissions,
            "context_hit_share": context_hit_share,
            "snapshot_restores": snapshot_restores,
            "sessions_cached_blocks": resident["cached"],
            "sessions_snapshots": resident["state_snapshots_live"],
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
