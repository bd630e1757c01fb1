"""Runner ``serve_linear``: questions about long RESIDENT documents, through
``FleetRouter`` -> one ``ThreadReplica`` -> ``PagedServingEngine`` with the
lightning / block-sparse model (``models/linear_sparse_lm.py``). The
request stream, the fixed schedule of lengths and the stall log are
``runners/serve_sparse``'s, the collector's log ``runners/serve_latent``'s,
the host-pause watch ``runners/serve_conv``'s (called, not copied); what
differs is the model, what a prefix hit is, and what ``correct`` compares.

Traffic: ``documents.count`` documents of ``documents.len`` tokens from
``--seed``. During set-up each is served once, alone, through the router
(one token asked), so that its K/V blocks, its compressed keys AND the
state snapshot at its end sit in the prefix cache before any client
starts. A request is then one document (fixed rotation) + a question + an
answer: a prefix hit that slots 1,024 blocks into the table and restores
one 18.9 MB float32 state, one chunk carried on from it, and the answer's
decode steps at ~66k rows a slot, each sparse layer reading 64 listed
pages a KV head. The window lies between two completions, each the end
of an epoch of the fixed length set: it opens at the first such after
the ramp and closes at the first a whole number of epochs and
``--seconds`` or more later (:func:`window_edges`).

``correct`` (limits below, each beside the chip readings that set it;
every one is on a MEDIAN or a SHARE). After the window
``reference_sample`` of its requests ON ONE DOCUMENT are served once more,
greedy, with stream requests in the other slots (the cell's batch), and
stay in their slots. Two PROBE programs of the check's own, made of the
functions the timed programs are made of (``kvpool/linear.py``'s
``chunk_forward`` and ``decode_forward`` with the model's taps), read
over the engine's live pool and state what the timed ones keep to
themselves. The reference (``reference_sala.py``: the recurrence, a sort)
runs the document ONCE in blocks of rows and each probed request's own
rows from a copy of what the document left, free-running.
(a) LOGITS: every emitted token against the reference's logits at its
    row: the median deficit below the reference's best, and the share of
    rows within the tolerance.
(b) What the timed programs LANDED: the slot's lightning state after its
    decode steps and the SNAPSHOT a timed chunk wrote at the prompt's last
    whole-block boundary against the reference's ``S_t``; the K, V and
    compressed-key rows of the request's own blocks, all and those right
    after the hit's boundary (the key that straddles it is the first).
(c) THE SELECTED BLOCKS THEMSELVES, at the decode step and at the chunk's
    rows: the share of (row, KV head) lists equal to the reference's as
    sets, and for the unequal ones how far, on the reference's own block
    scores, the blocks that differ sit from the selection's edge (an
    output cannot see a block of near-zero weight: PR 38's lesson).
(d) Each mixer's output before ``W_o``, a layer, at the chunk's rows and
    the decode step.
(e) Every hit restored a snapshot, none was denied, the cache supplied
    the documents, nothing compiled after warm-up, nothing was truncated,
    blocks and snapshot ids are conserved at the end.
Beside (a), (b) and (d) the run reports what the REFERENCE reads on the
same yardstick when its request's own rows are computed in the precision
below the configuration's (``low_*``).
"""

import gc
import time

import numpy as np

from benchmark import common, reference_sala
from benchmark.runners import serve as dense_serve
from benchmark.runners import serve_conv, serve_latent, serve_sparse

# The limits: about three times the largest reading of the program over
# the seeds read BEFORE the reported runs (my chip runs, PR 55: seeds
# 2147483659, 2147483661, 2147483663; PERF.md section 6), under what the
# reference reads on the same yardstick when a request's own rows are
# computed in the precision below the configuration's (``low_*``: 3 bits
# of mantissa).
# (a) How far below the float32 reference's best logit an emitted token
# may sit: the median over the emitted rows, and the share within the
# tolerance. The head's logits are bfloat16 and of unit scale (the head
# drawn at 16 / sqrt(d) under the family's 1/16). Read: median 0.0, 90th
# percentile 0.0, largest 0.009, 100 % of 385-879 rows within 0.1,
# 98.9-99.0 % the reference's own argmax; the reference's median top-2
# gap 0.160. These two limits catch a gross fault and do NOT part the
# precisions: the reference's own rows with 3 bits of mantissa, from the
# document's float32 carry, emit tokens within 0.1 at 98.5-99.5 %
# (``low_logit_within_share``): a request's ~600 own rows are 1 % of what
# its logits rest on. (b) to (d) part them.
SERVE_LOGIT_TOL = 0.1
LOGIT_DEFICIT_MEDIAN_MAX = 0.03
LOGIT_WITHIN_SHARE_MIN = 0.9
# (b) The landed state and snapshot, the median over (layer, head) of the
# relative error of a head's 128 x 128 float32 matrix, free running (the
# program's q, k, v are bfloat16, the reference's float32).
# Read 0.00420-0.00453 (state) and 0.00464-0.00467 (snapshot); the
# reference's own rows with 3 bits of mantissa 0.0780-0.0796.
STATE_REL_ERR_MEDIAN_MAX = 0.014
# The landed K, V and compressed-key rows of the FIRST sparse layer (whose
# inputs are the tokens' own), the median over rows, and over the rows
# right after a hit's boundary (the key that straddles it is the first).
# Read 0.00234-0.00235 (K, V), 0.00282-0.00291 (compressed keys),
# 0.00239-0.00246 (after a hit); every layer's 0.0032-0.0035, reported;
# the reference's K rows with 3 bits of mantissa 0.0376-0.0377.
ROWS_REL_ERR_MEDIAN_MAX = 0.009
# (c) Lists equal to the reference's as sets (chunk rows and the decode
# step, every sparse layer and KV head), free running, and for the lists
# that are not the distance of the blocks that differ from the
# selection's edge on the reference's OWN scores, as a share of the
# edge's score. At seeded weights a group's score is nearly flat over
# ~1,000 blocks (16 heads' softmax over 4,100 places), so a list's 31
# free places are cut at an edge where neighbours differ by 1e-4 of the
# score: read 80.3-81.2 % of 2,982-3,0xx lists equal (75-92 % of the
# decode step's 12), the unequal ones' blocks 1.5-1.6e-4 from the edge at
# the median and 9.5e-4 at worst. A selection that drops a forced block,
# takes 63, or goes by one head's scores reads 0 % equal.
LISTS_EQUAL_SHARE_MIN = 0.4
LIST_EDGE_GAP_MEDIAN_MAX = 0.0005
# (d) A mixer's output before W_o, free running, the median over the
# probed rows (a chunk's valid rows and the decode step's), the worst
# layer of its kind.
# Read 0.00707-0.00715 (lightning, growing 0.0052 -> 0.0071 with depth;
# the reference with 3 bits of mantissa 0.0806-0.0837 at its best layer)
# and 0.00569-0.00599 (sparse; 0.220-0.224).
LIGHTNING_REL_ERR_MEDIAN_MAX = 0.022
SPARSE_REL_ERR_MEDIAN_MAX = 0.018
# Rows of a probed request's chunk that are read.
TAIL_ROWS = 1024
DOC_ROWS = 2048
QUERY_ROWS = 64


def linear_config(cfg_json, **overrides):
    """The program's config for a configuration file (published keys)."""
    from dlrover_tpu.models import linear_sparse_lm

    sh = reference_sala.shape_of(cfg_json)      # validates the keys
    if cfg_json.get("tie_word_embeddings", False):
        raise ValueError("this model's head is its own")
    kw = dict(
        vocab_size=sh["vocab"], embed_dim=sh["hidden"],
        mixer_types=sh["types"], first_layer=sh["first_layer"],
        published_layers=sh["published_layers"], n_heads=sh["heads"],
        n_kv_heads=sh["kv_heads"], head_dim=sh["head_dim"],
        lightning_heads=sh["l_heads"], lightning_head_dim=sh["l_dim"],
        mlp_dim=sh["mlp"], rope_theta=sh["theta"], norm_eps=sh["eps"],
        scale_emb=sh["scale_emb"], scale_depth=sh["scale_depth"],
        dim_model_base=sh["base"], kernel_size=sh["kernel_size"],
        kernel_stride=sh["kernel_stride"], sparse_block=sh["block_size"],
        topk=sh["topk"], init_blocks=sh["init_blocks"],
        window_size=sh["window_size"], dense_len=sh["dense_len"],
        dtype=cfg_json.get("torch_dtype")
        or cfg_json.get("assumed", {}).get("torch_dtype", "bfloat16"),
    )
    kw.update(overrides)
    return linear_sparse_lm.LinearSparseLMConfig(**kw)


def engine_kwargs(eng):
    """``serve_engine`` of a configuration file as the engine takes it."""
    return dict(
        slots=eng["slots"], max_len=eng["max_len"],
        prefill_chunk=eng["prefill_chunk"], block_size=eng["block_size"],
        num_blocks=eng.get("num_blocks"),
        state_snapshots=eng.get("state_snapshots"),
    )


# -- the program's side: the probes -------------------------------------------


def build_probes(cfg, bs: int):
    """Programs of the check's own over the engine's LIVE pool and state,
    made of the functions the timed programs are made of: ``chunk(kp, vp,
    ck, before, params, table_row, start, tokens)`` walks one slot's
    chunk through every layer from the state ``before [Ll, heads, d, d]``
    and hands out, a layer, the mixer's output before ``W_o`` and, of a
    sparse layer, the block scores and mask of every row; ``decode(kp,
    vp, ck, state, params, tables, lengths, tokens)`` the step every slot
    would take next (the lists of every slot); ``landed(kp, vp, ck,
    table_row)`` one slot's rows of every pool layer."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.serving.kvpool import linear

    f32 = jnp.float32

    def layer_taps(taps, pick):
        out = []
        for layer in range(cfg.n_layers):
            t = taps[layer]
            one = {"gated": pick(t["gated"]).astype(f32)}
            for name in ("scores", "mask", "blocks", "count"):
                if name in t:
                    one[name] = t[name]
            out.append(one)
        return out

    @jax.jit
    def chunk(kp, vp, ck, before, params, table_row, start, tokens):
        taps = {}
        linear.chunk_forward(
            cfg, kp, vp, ck, before[:, None], params, tokens, table_row,
            start, 0, bs, taps=taps,
        )
        return layer_taps(taps, lambda a: a[0])

    @jax.jit
    def decode(kp, vp, ck, state, params, tables, lengths, tokens):
        taps = {}
        # (the definition's update: the probe must not write the state)
        linear.decode_forward(
            cfg, kp, vp, ck, state, params, tables, lengths, tokens, bs,
            taps=taps, state_kind="jnp",
        )
        return layer_taps(taps, lambda a: a[:, 0])

    @jax.jit
    def landed(kp, vp, ck, table_row):
        rows = lambda pool: pool[:, table_row].reshape(  # noqa: E731
            pool.shape[0], -1, pool.shape[-1]
        ).astype(f32)
        return rows(kp), rows(vp), rows(ck)

    return chunk, decode, landed


def probe_program(engine, probes, window_tokens):
    """The probes' readings for each of ``probes`` (requests the engine
    has just served and still holds): a dict a request."""
    import jax
    import jax.numpy as jnp

    cfg, bs, c = engine.config, engine.block_size, engine.prefill_chunk
    chunk, decode, landed = build_probes(cfg, bs)
    kp, vp, ck, state, snaps = engine._pools()
    params = engine._params
    tables = jnp.asarray(engine._tables)
    next_taps = jax.device_get(decode(
        kp, vp, ck, state, params, tables, jnp.asarray(engine._lengths),
        jnp.asarray(engine._tokens),
    ))
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
        restored = serve_conv._snapshot_of(engine, r.prompt, hit)
        before = (
            snaps[:, restored] if restored else jnp.zeros_like(snaps[:, 0])
        )
        tokens = np.zeros((1, c), np.int32)
        tokens[0, :n_valid] = r.prompt[start:start + n_valid]
        got = jax.device_get(chunk(
            kp, vp, ck, before, params, tables[r.slot], jnp.int32(start),
            jnp.asarray(tokens),
        ))
        k_rows, v_rows, c_rows = (
            np.asarray(a) for a in landed(kp, vp, ck, tables[r.slot])
        )
        boundary = r.prompt_len // bs * bs
        written = serve_conv._snapshot_of(engine, r.prompt, boundary // bs)
        stride = cfg.kernel_stride
        out.append({
            "seq": [int(t) for t in r.prompt] + emitted,
            "prompt_len": r.prompt_len, "emitted": emitted,
            "window": [int(t) for t in window], "slot": r.slot,
            "hit_rows": start, "n_valid": n_valid,
            "restored": int(restored), "fill": fill, "boundary": boundary,
            "chunk": [
                {k: np.asarray(v) for k, v in one.items()} for one in got
            ],
            "next": [
                {k: np.asarray(v)[r.slot] for k, v in one.items()}
                for one in next_taps
            ],
            "state": np.asarray(state[:, r.slot]),
            "snapshot": np.asarray(snaps[:, written]) if written else None,
            # the request's own rows: [pool layers, fill - start, d]
            "k_landed": k_rows[:, start:fill],
            "v_landed": v_rows[:, start:fill],
            # places start / stride ... fill / stride - 1
            "c_landed": c_rows[:, start // stride:fill // stride],
        })
    return out


# -- the reference's side and the comparison ----------------------------------


def document_carry(params, sh, document, faults=()):
    """What the reference carries out of a whole document."""
    n = len(document)
    rows = DOC_ROWS if n % DOC_ROWS == 0 else n
    carry = reference_sala.new_carry(sh, n + TAIL_ROWS)
    for start in range(0, n, rows):
        carry, _ = reference_sala.advance(
            params, carry, document[start:start + rows], start, sh,
            faults=faults, query_rows=QUERY_ROWS,
        )
    return carry


def _list_readings(mask_got, mask_want, scores_want):
    """``mask_* [kv_heads, rows, blocks]``: per (kv head, row) whether
    the two selections are the same set, and for those that are not the
    distance of the blocks that differ from the selection's edge on the
    reference's scores, as a share of the edge's score."""
    mask_got, mask_want = np.asarray(mask_got), np.asarray(mask_want)
    n = min(mask_got.shape[-1], mask_want.shape[-1])
    mask_got, mask_want = mask_got[..., :n], mask_want[..., :n]
    scores = np.asarray(scores_want, np.float64)[..., :n]
    equal = (mask_got == mask_want).all(-1)
    gaps = []
    for kh, row in zip(*np.nonzero(~equal)):
        s, want = scores[kh, row], mask_want[kh, row]
        finite = np.isfinite(s)
        if not (want & finite).any():
            gaps.append(1.0)
            continue
        edge = s[want & finite].min()
        differ = (mask_got[kh, row] != want) & finite
        if (mask_got[kh, row] != want)[~finite].any() or not differ.any():
            gaps.append(1.0)     # a forced or an unseen block differs
            continue
        gaps.append(float(
            np.abs(s[differ] - edge).max() / max(abs(edge), 1e-30)
        ))
    return equal.reshape(-1), np.asarray(gaps)


def lists_as_mask(blocks, count, n_blocks):
    """``blocks [kv_heads, width]``, ``count [kv_heads]`` -> ``[kv_heads,
    1, n_blocks]`` bool."""
    blocks, count = np.asarray(blocks), np.asarray(count)
    mask = np.zeros((blocks.shape[0], 1, n_blocks), bool)
    for kh in range(blocks.shape[0]):
        mask[kh, 0, blocks[kh, :count[kh]]] = True
    return mask


def reference_side(params, sh, carry, request, doc, faults=(),
                   low_too=True):
    """The reference over one probed request's own rows from the
    ``doc``-row document's ``carry`` (copied), and its readings of that
    request (whose hit may lie deeper than the document: its window's
    own turn, where that entry still held its snapshot)."""
    import jax
    import jax.numpy as jnp

    seq, p, fill = request["seq"], request["prompt_len"], request["fill"]
    at = request["hit_rows"] - doc      # the hit's boundary, in own rows
    if at < 0 or len(seq) - doc > TAIL_ROWS:
        raise RuntimeError(
            f"a probed request of {len(seq)} rows, hit {request['hit_rows']}"
            f", over a document of {doc}"
        )
    n_emit = len(request["emitted"])
    tail = np.zeros(TAIL_ROWS, np.int32)
    tail[:len(seq) - doc] = seq[doc:]
    emit_rows = p - 1 - doc + np.arange(n_emit)
    keep = (request["boundary"] - 1 - doc, fill - 1 - doc)

    def run(low, faults=faults):
        copy = jax.tree_util.tree_map(jnp.copy, carry)
        _, out = reference_sala.advance(
            params, copy, tail, doc, sh, low=low, faults=faults,
            query_rows=QUERY_ROWS, keep_rows=keep,
        )
        logits = np.asarray(reference_sala.logits_at(
            params, out["logits_of"], jnp.asarray(emit_rows)
        ))
        return out, logits

    rel = lambda got, want: np.asarray(  # noqa: E731
        reference_sala._rel(jnp.asarray(got), jnp.asarray(want))
    )
    ref, logits = run(False)
    emitted = np.asarray(request["emitted"])
    deficit = logits.max(-1) - logits[np.arange(n_emit), emitted]
    top2 = np.partition(logits, -2, axis=-1)[:, -2:]
    types = sh["types"]
    kh, stride = sh["kv_heads"], sh["kernel_stride"]
    n_own = fill - doc
    side = {
        "deficit": deficit, "top2_gap": top2[:, 1] - top2[:, 0],
        "finite": bool(np.isfinite(logits).all()),
    }

    def readings(got_state, got_snapshot):
        """(b)'s state readings of the program (or of a stand-in for it:
        the low reference's own state) against ``ref``."""
        flat = lambda a: np.asarray(a).reshape(  # noqa: E731
            np.asarray(a).shape[:2] + (-1,)
        )
        r = {}
        want_state = np.stack([np.asarray(s[1]) for s in ref["state_rows"]])
        want_snap = np.stack([np.asarray(s[0]) for s in ref["state_rows"]])
        r["state_err"] = rel(flat(got_state), flat(want_state)).reshape(-1)
        r["snapshot_err"] = (
            rel(flat(got_snapshot), flat(want_snap)).reshape(-1)
            if got_snapshot is not None else np.zeros((0,))
        )
        return r

    side.update(readings(request["state"], request["snapshot"]))
    # landed rows, a pool layer = (sparse layer, KV head); the FIRST
    # sparse layer's are limited, every layer's reported
    k_err, v_err, c_err = [], [], []
    n_places = (n_own - at) // stride
    for layer_at in range(len(ref["k"])):
        for j in range(kh):
            pool_layer = layer_at * kh + j
            want_k = np.asarray(ref["k"][layer_at])[at:n_own, j]
            want_v = np.asarray(ref["v"][layer_at])[at:n_own, j]
            # the reference holds c_{p - 1} at index p, as the pool's
            # places do; the first landed one straddles the hit's boundary
            first = (doc + at) // stride
            want_c = np.asarray(ref["ckeys"][layer_at])[
                first:first + n_places, j
            ]
            k_err.append(rel(request["k_landed"][pool_layer], want_k))
            v_err.append(rel(request["v_landed"][pool_layer], want_v))
            c_err.append(rel(request["c_landed"][pool_layer], want_c))
    side.update(
        k_rows_err=np.concatenate(k_err[:kh]),
        v_rows_err=np.concatenate(v_err[:kh]),
        c_rows_err=np.concatenate(c_err[:kh]),
        k_rows_err_all=np.concatenate(k_err),
        c_rows_err_all=np.concatenate(c_err),
        after_hit_err=np.concatenate(
            [e[:2] for e in k_err[:kh] + v_err[:kh]]
            + [e[:1] for e in c_err[:kh]]
        ),
    )
    # (c) the lists, and (d) the mixers
    n_valid = request["n_valid"]
    equal, gaps, lightning_err, sparse_err = [], [], [], []
    at_s = 0
    for layer, kind in enumerate(types):
        want_gated = np.asarray(ref["gated"][layer])
        got = np.concatenate([
            request["chunk"][layer]["gated"][:n_valid],
            request["next"][layer]["gated"][None],
        ])
        want = np.concatenate(
            [want_gated[at:at + n_valid], want_gated[n_own][None]]
        )
        err = rel(got, want)
        if kind == reference_sala.LIGHTNING:
            lightning_err.append(err)
            continue
        sparse_err.append(err)
        want_mask = np.asarray(ref["mask"][at_s])
        want_scores = np.asarray(ref["scores"][at_s])
        e1, g1 = _list_readings(
            request["chunk"][layer]["mask"][:, :n_valid],
            want_mask[:, at:at + n_valid], want_scores[:, at:at + n_valid],
        )
        nxt = request["next"][layer]
        e2, g2 = _list_readings(
            lists_as_mask(nxt["blocks"], nxt["count"], want_mask.shape[-1]),
            want_mask[:, n_own:n_own + 1], want_scores[:, n_own:n_own + 1],
        )
        equal += [e1, e2]
        gaps += [g1, g2]
        at_s += 1
    side.update(
        lists_equal=np.concatenate(equal), list_gaps=np.concatenate(gaps),
        decode_lists_equal=np.concatenate(equal[1::2]),
        lightning_err=lightning_err, sparse_err=sparse_err,
    )
    if low_too:
        low, low_logits = run(True, ())
        low_emitted = low_logits.argmax(-1)
        side["low_deficit"] = (
            logits.max(-1) - logits[np.arange(n_emit), low_emitted]
        )
        low_state = np.stack([np.asarray(s[1]) for s in low["state_rows"]])
        side["low_state_err"] = readings(low_state, None)["state_err"]
        side["low_k_rows_err"] = rel(
            np.asarray(low["k"][0])[:n_own], np.asarray(ref["k"][0])[:n_own]
        ).reshape(-1)
        own = lambda i: rel(  # noqa: E731
            np.asarray(low["gated"][i])[:n_own],
            np.asarray(ref["gated"][i])[:n_own],
        )
        side["low_lightning_err"] = [
            own(i) for i, t in enumerate(types)
            if t == reference_sala.LIGHTNING
        ]
        side["low_sparse_err"] = [
            own(i) for i, t in enumerate(types)
            if t != reference_sala.LIGHTNING
        ]
    return side


def compare(requests, sides):
    """All readings of (a) to (d) over the probed requests."""
    cat = lambda name: np.concatenate(  # noqa: E731
        [np.asarray(s[name], np.float64).reshape(-1) for s in sides]
    )
    median = lambda a: float(np.median(a)) if len(a) else 0.0  # noqa: E731
    by_layer = lambda name: [  # noqa: E731
        median(np.concatenate([s[name][i] for s in sides]))
        for i in range(len(sides[0][name]))
    ]
    deficit = cat("deficit")
    gaps = cat("list_gaps")
    check = {
        "n_requests": len(requests), "n_emitting": int(deficit.size),
        # (a)
        "logits_finite": all(s["finite"] for s in sides),
        "logit_deficit_median": float(np.median(deficit)),
        "logit_deficit_p90": float(np.quantile(deficit, 0.9)),
        "logit_deficit_max": float(deficit.max()),
        "logit_within_share": float((deficit <= SERVE_LOGIT_TOL).mean()),
        "n_argmax_matches": int((deficit == 0).sum()),
        "median_top2_gap": float(np.median(cat("top2_gap"))),
        # (b)
        "state_err_median": median(cat("state_err")),
        "state_err_max": float(cat("state_err").max()),
        "snapshot_err_median": median(cat("snapshot_err")),
        "n_snapshots_read": sum(
            r["snapshot"] is not None for r in requests
        ),
        "k_rows_err_median": median(cat("k_rows_err")),
        "v_rows_err_median": median(cat("v_rows_err")),
        "c_rows_err_median": median(cat("c_rows_err")),
        "k_rows_err_all_layers_median": median(cat("k_rows_err_all")),
        "c_rows_err_all_layers_median": median(cat("c_rows_err_all")),
        "rows_after_hit_err_median": median(cat("after_hit_err")),
        "n_rows_landed": int(cat("k_rows_err").size),
        "hits_restored": [int(r["restored"] > 0) for r in requests],
        # (c)
        "lists_equal_share": float(cat("lists_equal").mean()),
        "decode_lists_equal_share": float(cat("decode_lists_equal").mean()),
        "n_lists": int(cat("lists_equal").size),
        "list_edge_gap_median": median(gaps),
        "list_edge_gap_max": float(gaps.max()) if len(gaps) else 0.0,
        # (d)
        "lightning_err_median_by_layer": by_layer("lightning_err"),
        "lightning_err_median_max": max(by_layer("lightning_err")),
        "sparse_err_median_by_layer": by_layer("sparse_err"),
        "sparse_err_median_max": max(by_layer("sparse_err")),
    }
    if "low_deficit" in sides[0]:
        low = cat("low_deficit")
        check.update(
            low_logit_deficit_median=float(np.median(low)),
            low_logit_within_share=float((low <= SERVE_LOGIT_TOL).mean()),
            low_state_err_median=median(cat("low_state_err")),
            low_k_rows_err_median=median(cat("low_k_rows_err")),
            low_lightning_err_median_min=min(by_layer("low_lightning_err")),
            low_sparse_err_median_min=min(by_layer("low_sparse_err")),
        )
    return check


def problems_of(check, judged="program"):
    """What ``check`` breaks. ``judged="reference_lower_precision"``
    (``controls_sala.py`` alone): the reference's own rows computed in
    the precision below, put in the program's place on (a), (b) and (d)."""
    c = dict(check)
    if judged == "reference_lower_precision":
        c.update(
            logit_deficit_median=c["low_logit_deficit_median"],
            logit_within_share=c["low_logit_within_share"],
            state_err_median=c["low_state_err_median"],
            k_rows_err_median=c["low_k_rows_err_median"],
            lightning_err_median_max=c["low_lightning_err_median_min"],
            sparse_err_median_max=c["low_sparse_err_median_min"],
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
    limit("state_err_median", "the slots' lightning state after their "
          "decode steps against the recurrence's S_t",
          STATE_REL_ERR_MEDIAN_MAX)
    limit("snapshot_err_median", "the snapshots the timed chunks wrote at "
          "their prompts' last block boundary against the recurrence's",
          STATE_REL_ERR_MEDIAN_MAX)
    limit("k_rows_err_median", "the K rows landed in the first sparse "
          "layer against the reference's", ROWS_REL_ERR_MEDIAN_MAX)
    limit("v_rows_err_median", "the V rows landed in the first sparse "
          "layer against the reference's", ROWS_REL_ERR_MEDIAN_MAX)
    limit("c_rows_err_median", "the compressed keys landed in the first "
          "sparse layer against the reference's", ROWS_REL_ERR_MEDIAN_MAX)
    limit("rows_after_hit_err_median", "the K, V and compressed-key rows "
          "right after a hit's boundary (the key that straddles it)",
          ROWS_REL_ERR_MEDIAN_MAX)
    limit("lists_equal_share", "too few (row, KV head) block lists equal "
          "to the reference's as sets", LISTS_EQUAL_SHARE_MIN, upper=False)
    limit("list_edge_gap_median", "the blocks that differ from the "
          "reference's selection sit far from its edge on the reference's "
          "own scores", LIST_EDGE_GAP_MEDIAN_MAX)
    limit("lightning_err_median_max", "a lightning mixer's output before "
          "W_o against the reference's", LIGHTNING_REL_ERR_MEDIAN_MAX)
    limit("sparse_err_median_max", "a sparse mixer's output before W_o "
          "against the reference's", SPARSE_REL_ERR_MEDIAN_MAX)
    if not all(c["hits_restored"]):
        problems.append(
            "hits_restored: a probed request's hit has no snapshot in "
            f"the cache ({c['hits_restored']})"
        )
    return problems


def prefix_problems(hit_tokens, admissions, traffic):
    """``serve_conv.prefix_problems`` under this traffic's names."""
    return serve_conv.prefix_problems(
        hit_tokens, admissions, dict(traffic, sessions=traffic["documents"])
    )


def window_edges(done, after, seconds, period):
    """The timed window's two edges among the requests served (``done``
    but the documents), as indices in completion order: ``(i0, i1)``, or
    None while the run has not got that far. BOTH edges are completions
    that end an EPOCH of the fixed length set (the ``period``-th, ``2
    period``-th ... completion): ``i0`` the first such at or after
    ``after``, ``i1`` the first a whole number of epochs on and
    ``seconds`` or more later. The window is then every request from
    ``i0 + 1`` to ``i1``: a whole number of epochs, so the same lengths in
    every run, where a window cut by the clock alone held one long answer
    more or fewer from run to run (an answer is up to 512 tokens of a
    window's 44k: the five timed runs so cut spread 1.56 %, my chip runs,
    PR 55)."""
    served = [d for d in done if d["phase"] != "documents"]
    i0 = next(
        (i for i in range(period - 1, len(served), period)
         if served[i]["t"] >= after), None,
    )
    if i0 is None:
        return None
    i1 = next(
        (i for i in range(i0 + period, len(served), period)
         if served[i]["t"] >= served[i0]["t"] + seconds), None,
    )
    return None if i1 is None else (i0, i1)


JUDGED = "program"     # controls_sala.py's last control sets the other
PLANT = None           # ... or plants this in the program: plant(engine)
# What the last run's checks read, kept for ``controls_sala.py`` to judge
# once more against a reference with a fault planted in it.
LAST = {}


def judge(requests, params, sh, doc_len, carry=None, faults=(),
          judged="program"):
    """The reference's side and the comparison for ``requests`` (the
    probes' readings): ``(check, problems, carry)``. ``carry``: what the
    reference carries out of the requests' document (None: computed
    here). ``faults``: planted in the reference's pass over the requests'
    OWN rows (``controls_sala.py``)."""
    import jax

    t0 = time.time()
    if carry is None:
        document = np.asarray(requests[0]["seq"][:doc_len], np.int32)
        carry = document_carry(params, sh, document)
        jax.block_until_ready(carry)
    doc_s = time.time() - t0
    sides = [
        reference_side(params, sh, carry, r, doc_len, faults=faults)
        for r in requests
    ]
    check = compare(requests, sides)
    check.update(document_seconds=doc_s, seconds=time.time() - t0)
    return check, problems_of(check, judged), carry


# -- the run ------------------------------------------------------------------


def run(ctx):
    import jax

    # First, and before anything is built: a checkout without this model
    # fails here, at once.
    from dlrover_tpu.models import linear_sparse_lm

    counts = common.count_jax_events()
    from dlrover_tpu.observability import tracing
    from dlrover_tpu.serving.fleet import FleetRouter, ThreadReplica
    from dlrover_tpu.serving.kvpool import PagedServingEngine

    devices = jax.devices()
    device = common.device_facts(devices)
    if ctx["require_tpu"]:
        common.require_tpu(devices, ctx["chips"])
    cfg_json, traffic = ctx["config"], ctx["traffic"]
    cfg = linear_config(cfg_json)
    sh = reference_sala.shape_of(cfg_json)
    eng = cfg_json["serve_engine"]
    log = common.EventLog(ctx["out_dir"] + "/events.jsonl")
    make_params = jax.jit(
        lambda key: linear_sparse_lm.init_params(
            cfg, key, dtype=cfg.compute_dtype
        )
    )
    key = common.rng_key(ctx["seed"])
    box = {"params": make_params(key)}

    t0 = time.time()
    engine = PagedServingEngine(cfg, box.pop("params"), **engine_kwargs(eng))
    engine.warmup()
    if PLANT is not None:
        PLANT(engine)
    if ctx["trace"]:
        # (the leading arrays are the engine's own, however many)
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
    stream = serve_sparse.request_stream(traffic, cfg.vocab_size, ctx["seed"])
    docs = serve_sparse.documents(traffic, cfg.vocab_size, ctx["seed"])
    on_gc = serve_latent.log_full_collections(log)
    host_pauses, stop_watch = serve_conv.watch_host_pauses()
    replica = ThreadReplica("0", lambda: engine)
    router = FleetRouter([replica])
    router.start(timeout_s=60)
    live, done = {}, []

    def decoded():
        return engine.metrics.tokens.value(kind="decode")

    def submit(prompt=None, n_new=None):
        if prompt is None:
            prompt, n_new = next(stream)
        # One array, not a list of 66k numbers: this process keeps every
        # prompt it sent (``live``, ``done``), and a generation-2
        # collection walks every element of every list it can reach:
        # 0.13-0.26 s of standstill at ~400 prompts (my chip runs, PR 55).
        prompt = np.asarray(prompt, np.int32)
        req = router.submit(prompt, n_new, traffic["temperature"])
        live[req.request_id] = (req, prompt, n_new)

    def pump(until, phase):
        """``serve_conv.run``'s pump: hand finished requests out and
        refill, until ``until`` (a time, or a callable that says when to
        stop); returns the time it stopped."""
        stop = until if callable(until) else (lambda: time.time() >= until)
        last, stalled = time.time(), False
        while True:
            if stop():
                return time.time()
            finished = router.step()
            now = time.time()
            if finished or phase in ("documents", "ramp"):
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
                    "compiles": counts[common.BACKEND_COMPILE],
                })
                if phase != "documents":
                    submit()
            if not finished:
                time.sleep(0.002)

    trace = dump = scopes = traced_window = None
    try:
        # Set-up: every document once, alone, so that its blocks, its
        # compressed keys AND the snapshot at its end are in the prefix
        # cache before any client starts.
        t0 = time.time()
        for document in docs:
            submit(document, 1)
            pump(lambda: not live, "documents")
        resident = engine.kv_stats()
        hit0 = resident["prefix_hit_tokens"]
        prefilled0 = engine.metrics.tokens.value(kind="prefill")
        log.emit("documents_resident", seconds=time.time() - t0,
                 cached_blocks=resident["cached"],
                 snapshots=resident["state_snapshots_live"])
        for _ in range(traffic["clients"]):
            submit()
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

        def window_done():
            # (asked again only when something has completed since)
            if len(done) != seen[0]:
                seen[:] = len(done), window_edges(
                    done, t_ramp, ctx["seconds"], traffic["length_set_size"]
                )
            return seen[1]

        pump(window_done, "window")
    finally:
        router.stop()
        stop_watch()
        gc.callbacks.remove(on_gc)
        if tracer is not None:
            tracing.disarm()
    served = [d for d in done if d["phase"] != "documents"]
    i0, i1 = window_edges(
        done, t_ramp, ctx["seconds"], traffic["length_set_size"]
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
    served_hits = kv_stats["prefix_hits"] - resident["prefix_hits"]
    admissions = served_hits + (
        kv_stats["prefix_misses"] - resident["prefix_misses"]
    )
    context_hit_share = hit_tokens / max(
        admissions * traffic["documents"]["len"], 1
    )
    snapshot_restores = (
        kv_stats["state_restores_from_snapshot"]
        - resident["state_restores_from_snapshot"]
    )
    peak = common.memory_peak(devices[:ctx["chips"]])
    spans = tracer.finished() if tracer is not None else []
    if dump:
        from benchmark import sala_scopes, sparse_scopes, trace_reduce

        sparse_scopes.label(dump, box.get("scopes") or {})
        trace = trace_reduce.reduce(dump)
        scopes = sala_scopes.reduce(dump)

    # The checks' program side (``serve_conv.run``'s): the replica's
    # thread has stopped; a sample of the window's requests ON ONE
    # DOCUMENT is served once more over the same pool, state and prefix
    # cache, with stream requests in the other slots, and stays in its
    # slots for the probes to read.
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
    out_max = traffic["output_len"]["max"]
    doc_len = traffic["documents"]["len"]
    # (a probe decodes on past its window's answer, beside the other's:
    # only requests whose slot has the room for it are sampled)
    pool = [
        d for d in (in_window or served) if d["ok"] and d["tokens"]
        and len(d["prompt"]) + out_max + 32 <= eng["max_len"]
    ]
    sample = []
    if pool:
        first = pool[rng.permutation(len(pool))[0]]
        same = [
            d for d in pool
            if np.array_equal(d["prompt"][:64], first["prompt"][:64])
        ]
        picks = rng.permutation(len(same))[:traffic["reference_sample"]]
        sample = [same[i] for i in picks]
    room = lambda prompt: eng["max_len"] - len(prompt)  # noqa: E731
    probes = [engine.submit(d["prompt"], room(d["prompt"])) for d in sample]
    for _ in range(engine.slots - len(probes)):
        prompt = np.asarray(next(stream)[0], np.int32)
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
    requests = probe_program(
        engine, probes, [d["tokens"] for d in sample]
    ) if probes else []
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
    problems += prefix_problems(hit_tokens, admissions, traffic)
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
        check, found, carry = judge(
            requests, params, sh, doc_len, judged=JUDGED
        )
        check.update(probe_seconds=probe_s)
        problems += found
        LAST.clear()
        LAST.update(requests=requests, params=params, sh=sh,
                    doc_len=doc_len, carry=carry)
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
        # table read; benchmark/sala_scopes.py made it
        "sparse_scopes": scopes,
        "traced_window": traced_window,
        "dump": dump,
        "spans": spans,
        "window": {
            "seconds": window_s, "requests": len(in_window),
            "tokens_out": tokens_out,
            "tokens_in": sum(len(d["prompt"]) for d in in_window),
            "in_flight_at_end": len(live),
            "host_pauses": len(paused), "host_pause_s": sum(paused),
        },
        "prefix": {
            "hit_tokens": hit_tokens, "prefilled_tokens": prefilled,
            "hit_share": hit_share, "hits": served_hits,
            "admissions": admissions,
            "context_hit_share": context_hit_share,
            "snapshot_restores": snapshot_restores,
            "documents_cached_blocks": resident["cached"],
            "documents_snapshots": resident["state_snapshots_live"],
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
