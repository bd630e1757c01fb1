"""Runner ``serve_sparse``: document question-answering through
``FleetRouter`` -> one ``ThreadReplica`` -> ``PagedServingEngine`` with
the sparse-attention expert model (``models/sparse_lm.py``). The closed
loop, the timed window and the fixed schedule of lengths are
``runners/serve.py``'s (its ``length_set`` is called, not copied); what
differs is the traffic and what ``correct`` compares.

Traffic: ``documents.count`` documents of ``documents.len`` tokens from
``--seed``. During set-up each document is served once, alone, through
the router (one token asked), so that its blocks — K, V and index keys —
sit in the prefix cache before any client starts. A request is then one
document (fixed rotation: request i asks document i mod count) + a
question + an answer, both lengths from the fixed set, in its fixed
order. Every request's document part is a prefix hit: it costs one
chunk against ~33k cached rows and its answer's decode steps.

``correct`` (limits below, each with the readings that set it). After
the window a sample of its requests is served once more, greedy, with
stream requests in the other slots (the cell's batch of 16), and stays
in its slots. A PROBE CHAIN then runs over the engine's live pool: two
programs of the check's own, made of the functions the timed programs
are made of, that walk the layers over a request's last question chunk
and over each decode step of its answer and hand out what the timed
ones keep to themselves (``build_probes``). The reference then runs
each probed request's whole sequence (document + question + answer,
~33k tokens) once, free-running, and beside its own chain holds EVERY
layer to the probe chain's readings at the probed rows, both sides fed
the same layer input (``reference_keye.probe_rows``): a routing flip or
a rounding in one layer does not reach the next comparison.
(a) TOKENS and LOGITS. The timed programs against the probe chain: the
    K, V and index-key rows they landed in the pool are the chain's, in
    every layer, for most rows (an expert flipped between the two
    compilations shows from the next layer on; such rows are counted
    and left out), and on those rows the timed token is the chain's
    best logit to within bfloat16's spacing. The window against the
    replay: the same tokens, or off a tie where they part. The emitted
    tokens against the reference's free-running logits where program
    and reference chose the same experts in every layer: rounding, not
    routing, under the reference's top-2 gap (how many rows flips touch
    is reported).
(b) LAYERS, every one: the index scores against float32 on the same
    index queries and keys; the number of keys selected and their share
    inside the reference's top-(topk + margin), the program's keys being
    the pool's as the prefix cache shared them; the attention output
    against the reference attending over the PROGRAM's keys; the experts
    chosen for the same input (the same, or off a tie), their weights,
    and the expert half's output.
(c) the prefix cache served the documents, no expert row was dropped,
    nothing compiled after warm-up, nothing was truncated.
Beside each reading of (b) the run reports what the REFERENCE reads on
the same yardstick when computed in the precision below the
configuration's (``low_*``): every limit of (b) lies between the two.
"""

import gc
import time

import numpy as np

from benchmark import common, reference_keye
from benchmark.runners import serve as dense_serve

# The limits, each with the chip readings that set it (my chip runs, PR
# 33; PERF.md section 6): the largest the program read over its seeds,
# and what the REFERENCE reads on the same yardstick when computed in
# the precision below the configuration's (``low_*`` in every run's
# ``layers``: bfloat16 index scores and router, float8 attention and
# expert operands).
# (a) A probe row TRACKS the timed programs when the K, V and index-key
# rows they landed for it equal the probe chain's, in every layer
# (relative L2): an expert flipped between the two compilations would
# show as a few per cent from the next layer on. Read: 0.0 in every
# layer of every row (the scan's body compiles to the same code).
POOL_ROW_TOL = 0.01
TRACK_SHARE_MIN = 0.5
# How far below the probe chain's best logit the timed programs' token
# may sit on a tracked row. Read: at most 0.0156 (the head's logits are
# bfloat16: one place at 2-4 is 0.0156).
PROGRAM_LOGIT_TOL = 0.05
# How far below the float32 reference's FREE-RUNNING best logit an
# emitted token may sit where program and reference chose the same
# experts in every layer (about two rows in three; a flip in some layer
# touches the rest, which every layer's own comparison below still
# judges): rounding, not routing. Read over seventeen seeds: at most 0.050
# on 63-281 such rows a run (rows a flip touched: up to 0.77); the
# reference's median top-2 gap is 0.136-0.200.
SERVE_LOGIT_TOL = 0.1
ALIKE_ROWS_MIN = 8
# (b) by layer, both sides fed the program's inputs. Index scores
# against float32 on the same index queries and keys (largest
# difference over the visible rows / the scores' rms). Read: 0.0
# (bfloat16 products are exact in float32 and both sum alike); the
# reference's formula in bfloat16 0.0180-0.0215.
SCORE_ERR_MAX = 1e-4
# The reference's set is widened by this many places: the program's
# index keys are the pool's, bfloat16 and its own chain's (a flipped
# expert at an earlier row moves that row's keys of every later layer).
# Read: 1.0 in layer 0 down to 0.984-0.987 in layer 4; dense attention,
# index keys zeroed in every layer or in one: 0.069, 0.046-0.057, 0.056
# (in that layer alone; the others as a sound run).
SELECT_MARGIN = 256
SELECT_SHARE_MIN = 0.9
# Attention output against the reference attending over the PROGRAM's
# keys (which boundary keys were chosen is (b)'s share, not this). In
# layer 0 both chains' keys and values are one rounding apart and the
# error is the attention's own arithmetic: read 0.0047-0.0050, the
# reference with float8 operands 0.0189-0.0198. Deeper, the chains'
# rows differ wherever an earlier row's expert flipped (read up to
# 0.041): the limit there is for wrong rows, not for rounding.
ATTN_REL_ERR_FIRST_MAX = 0.01
ATTN_REL_ERR_MAX = 0.15
# Routing on the same input: the weights where the experts are the
# same (read 0.0012-0.0017; unrenormalised 0.33); where they are
# not (1.1-2.1 % of rows: the program's router input is rounded to
# bfloat16), the program's odd expert must lie this close, relative and
# in the reference's probabilities, to the reference's 8th (read
# 0.0031-0.0061; a bfloat16 router reads 0.0088-0.0146 and 2.9-4.2 % of
# rows, which these two do not tell apart with room: they are for an
# expert that is plainly the wrong one).
ROUTE_WEIGHT_ERR_MAX = 0.01
ROUTE_SWAP_GAP_MAX = 0.02
ALIKE_SHARE_MIN = 0.9
# The expert half's output where the experts are the same. Read
# 0.0105-0.0117 in every layer; the reference with float8 weights and
# activations 0.0444-0.0476.
EXPERT_REL_ERR_MAX = 0.022
# Rows a sampled request is probed at: of its question chunk (evenly
# spread, the last one among them) and every decode step of its answer.
CHUNK_ROWS = 24


def sparse_config(cfg_json, **overrides):
    """The program's config for a configuration file (published keys)."""
    from dlrover_tpu.models import sparse_lm

    if cfg_json.get("hidden_act", "silu") != "silu":
        raise ValueError("the repo's expert MLP is SwiGLU (silu) only")
    if cfg_json.get("tie_word_embeddings"):
        raise ValueError("the repo's head is untied")
    if not cfg_json.get("norm_topk_prob", True):
        raise ValueError("the served router renormalises its top-k")
    sa = cfg_json["sa_config"]
    if sa.get("indexer_num_kv_heads", 1) != 1:
        raise ValueError("the pool keeps ONE index key a token")
    kw = dict(
        vocab_size=cfg_json["vocab_size"],
        embed_dim=cfg_json["hidden_size"],
        n_layers=cfg_json["num_hidden_layers"],
        n_heads=cfg_json["num_attention_heads"],
        n_kv_heads=cfg_json["num_key_value_heads"],
        head_dim=cfg_json["head_dim"],
        mlp_dim=cfg_json["moe_intermediate_size"],
        n_experts=cfg_json["num_experts"],
        moe_top_k=cfg_json["num_experts_per_tok"],
        index_heads=sa["indexer_num_heads"],
        index_dim=sa["indexer_head_dim"],
        index_topk=sa["topk"],
        rope_theta=float(cfg_json["rope_theta"]),
        dtype=cfg_json.get("torch_dtype", "bfloat16"),
    )
    kw.update(overrides)
    return sparse_lm.SparseLMConfig(**kw)


def documents(traffic, vocab, seed):
    spec = traffic["documents"]
    return np.random.default_rng((seed, 2 ** 20)).integers(
        0, vocab, (spec["count"], spec["len"])
    )


def request_stream(traffic, vocab, seed):
    """Endless (prompt tokens, max_new_tokens): request i is document
    ``i mod count`` + a question; the (question, answer) lengths are the
    fixed set, epoch after epoch, in the traffic file's own order."""
    lengths = dense_serve.length_set(
        dict(traffic, prompt_len=traffic["question_len"])
    )
    docs = documents(traffic, vocab, seed)
    i, epoch = 0, 0
    while True:
        rng = np.random.default_rng((seed, epoch))
        order = np.random.default_rng(
            (traffic["length_set_seed"], epoch)
        ).permutation(len(lengths))
        for j in order:
            n_question, n_new = lengths[j]
            question = rng.integers(0, vocab, n_question)
            doc = docs[i % len(docs)]
            yield np.concatenate([doc, question]).tolist(), int(n_new)
            i += 1
        epoch += 1


def thread_stacks(depth=8):
    """Thread name -> the innermost ``depth`` frames, as text."""
    import sys
    import threading
    import traceback

    names = {t.ident: t.name for t in threading.enumerate()}
    return {
        names.get(ident, str(ident)): [
            f"{f.filename.rsplit('/', 2)[-1]}:{f.lineno} {f.name}"
            for f in traceback.extract_stack(frame)[-depth:]
        ]
        for ident, frame in sys._current_frames().items()
    }


def program_scopes(engine):
    """Program name -> instruction -> named-scope path, from the decode
    and prefill programs compiled once more at the engine's shapes (a
    hit in the persistent cache where it is on)."""
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
            arr((), bool),
        ),
    }
    return {
        name: trace_reduce.scopes_from_hlo(low.compile().as_text())
        for name, low in texts.items()
    }


# -- the program's side: the probe chain --------------------------------------


def build_probes(cfg, bs: int):
    """Two programs of the check's own over the engine's LIVE pool (of
    ``bs``-row blocks), made
    of the functions the timed programs are made of
    (``sparse_lm.attention_inputs``, ``kvpool/sparse.py``'s select and
    attend, ``llama.attention_out``, ``sparse_lm.expert_mlp``, the
    layer scan), that hand out what the timed ones keep to themselves:
    every layer's input, selection, attention output, routing and
    output at the probed rows, the index scores' distance from float32
    on the same inputs, how far the rows the TIMED programs landed in
    the pool lie from the chain's, and where the timed token sits in
    the chain's logits. ``decode(pools, params, tables, lengths, tokens,
    nxt, alt, take)``: one decode step of every slot, read at the slots
    ``take``; ``chunk(pools, params, table_row, start, n_valid, tokens,
    sel, nxt, alt)``: one slot's last prefill chunk, read at its rows
    ``sel``."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import llama, moe, sparse_lm
    from dlrover_tpu.ops import sparse_attention as sa
    from dlrover_tpu.ops.norms import rms_norm
    from dlrover_tpu.serving.kvpool import sparse

    f32 = jnp.float32

    def rel(got, want):
        axes = tuple(range(1, got.ndim))
        got, want = got.astype(f32), want.astype(f32)
        return jnp.sqrt(jnp.sum(jnp.square(got - want), axes)) / jnp.sqrt(
            jnp.sum(jnp.square(want), axes) + 1e-30
        )

    def pool_err(pools, layer, blk, off, news):
        """Rows the timed programs landed against the chain's, the
        worst of K, V and the index keys."""
        return jnp.max(jnp.stack([
            rel(sparse._at_layer(pool, layer, blk, off),
                new.astype(pool.dtype))
            for pool, new in zip(pools, news)
        ]), axis=0)

    def score_err(scores, q_idx, w, view, visible):
        """The program's index scores against the reference's formula
        in float32 at the highest matmul precision on the SAME index
        queries, weights and keys (largest difference over the visible
        rows / the scores' rms), and what that formula reads in
        bfloat16."""
        up = lambda a: a.astype(f32)  # noqa: E731

        def formula(low):
            fn = lambda q, w_, k_: reference_keye.index_scores(  # noqa: E731
                q, w_, k_, low
            )
            if view.ndim == 3:      # a view a query
                return jax.vmap(fn)(
                    up(q_idx)[:, None], up(w)[:, None], up(view)
                )[:, 0]
            return fn(up(q_idx), up(w), up(view))

        with jax.default_matmul_precision("highest"):
            want = formula(False)
        n = jnp.sum(visible, -1)
        rms = jnp.sqrt(jnp.sum(jnp.where(visible, want, 0.0) ** 2, -1) / n)
        off = lambda got: jnp.max(  # noqa: E731
            jnp.where(visible, jnp.abs(got - want), 0.0), -1
        ) / rms
        return off(scores), off(formula(True))

    def expert_half(p, layers, layer, x_mid):
        hx = rms_norm(x_mid, p["mlp_norm"]).astype(cfg.compute_dtype)
        ids, weights = moe.softmax_route(
            hx.reshape(-1, hx.shape[-1]), p["router"], cfg.moe_top_k
        )
        x_out, _ = sparse_lm.expert_mlp(cfg, p, x_mid, layers, layer)
        return ids, weights, x_out

    def logit_deficits(params, h, nxt, alt):
        logits = llama.unembed(cfg, params, h)[:, 0]
        top = jnp.max(logits, axis=-1)
        at = lambda t: jnp.take_along_axis(  # noqa: E731
            logits, t[:, None], axis=-1
        )[:, 0]
        return top - at(nxt), top - at(alt)

    @jax.jit
    def decode(pools, params, tables, lengths, tokens, nxt, alt, take):
        k, v, ki = pools
        slots, max_blocks = tables.shape
        max_len = max_blocks * bs
        at = jnp.minimum(lengths, max_len - 1)
        blk = jnp.take_along_axis(tables, (at // bs)[:, None], axis=1)[:, 0]
        off = at % bs
        positions = lengths[:, None]
        visible = jnp.arange(max_len)[None, :] <= at[take][:, None]
        x = llama.embed_tokens(cfg, params, tokens[:, None])

        def body(x, layer_in):
            p, layer = layer_in
            q, k_new, v_new, q_idx, k_idx, w = sparse_lm.attention_inputs(
                cfg, p, x, positions
            )
            idx, valid = sparse.decode_select(
                cfg, ki, layer, tables, lengths, bs, q_idx, k_idx, w
            )
            attn = sparse.decode_attend(
                cfg, k, v, ki, layer, tables, lengths, bs
            )(q, k_new, v_new, q_idx, k_idx, w)
            x_mid = llama.attention_out(cfg, p, attn, x)
            ids, weights, x_out = expert_half(
                p, params["layers"], layer, x_mid
            )
            view = sparse._at_layer(ki, layer, tables[take]).reshape(
                take.shape[0], max_len, -1
            )
            view = view.at[jnp.arange(take.shape[0]), at[take]].set(
                k_idx[take, 0].astype(view.dtype)
            )
            scores = sa.index_scores(q_idx[take], w[take], view)[:, 0]
            return x_out, {
                "x_in": x[take, 0].astype(f32), "idx": idx[take],
                "valid": valid[take], "attn": attn[take, 0].astype(f32),
                "x_mid": x_mid[take, 0].astype(f32), "ids": ids[take],
                "weights": weights[take].astype(f32),
                "x_out": x_out[take, 0].astype(f32),
                "pool_err": pool_err(
                    pools, layer, blk, off,
                    (k_new[:, 0], v_new[:, 0], k_idx[:, 0]),
                )[take],
                "score_err": jnp.stack(score_err(
                    scores, q_idx[take, 0], w[take, 0], view, visible
                )),
            }

        x, out = sparse_lm.scan_layers(cfg, params, body, x)
        out["deficit"], out["alt_deficit"] = logit_deficits(
            params, x[take], nxt[take], alt[take]
        )
        return out

    @jax.jit
    def chunk(pools, params, table_row, start, n_valid, tokens, sel, nxt,
              alt):
        k, v, ki = pools
        max_len = table_row.shape[0] * bs
        c = tokens.shape[1]
        positions = (start + jnp.arange(c, dtype=jnp.int32))[None, :]
        at = start + sel
        blk, off = table_row[at // bs], at % bs
        visible = jnp.arange(max_len)[None, :] <= at[:, None]
        x = llama.embed_tokens(cfg, params, tokens)

        def body(x, layer_in):
            p, layer = layer_in
            q, k_new, v_new, q_idx, k_idx, w = sparse_lm.attention_inputs(
                cfg, p, x, positions
            )
            view = sparse._slot_view(ki, layer, table_row, k_idx, start, bs)
            mask = sparse.chunk_select(
                cfg, view, at, q_idx[0][sel], w[0][sel]
            )
            attn = sparse.chunk_attend(
                cfg, k, v, ki, layer, table_row, start, bs, n_valid
            )(q, k_new, v_new, q_idx, k_idx, w)
            x_mid = llama.attention_out(cfg, p, attn, x)
            ids, weights, x_out = expert_half(
                p, params["layers"], layer, x_mid
            )
            scores = sa.index_scores(q_idx[0][sel], w[0][sel], view)
            return x_out, {
                "x_in": x[0][sel].astype(f32), "mask": mask & visible,
                "attn": attn[0][sel].astype(f32),
                "x_mid": x_mid[0][sel].astype(f32), "ids": ids[sel],
                "weights": weights[sel].astype(f32),
                "x_out": x_out[0][sel].astype(f32),
                "pool_err": pool_err(
                    pools, layer, blk, off,
                    (k_new[0][sel], v_new[0][sel], k_idx[0][sel]),
                ),
                "score_err": jnp.stack(score_err(
                    scores, q_idx[0][sel], w[0][sel], view, visible
                )),
            }

        x, out = sparse_lm.scan_layers(cfg, params, body, x)
        h = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
        out["deficit"], out["alt_deficit"] = logit_deficits(
            params, h, nxt[None], alt[None]
        )
        return out

    return decode, chunk


PER_LAYER = ("x_in", "mask", "attn", "x_mid", "ids", "weights", "x_out",
             "pool_err", "score_err", "low_score_err")
PER_ROW = ("pos", "valid", "nxt", "alt", "deficit", "alt_deficit")


def probe_program(engine, probes, window_tokens, n_decode):
    """The probe chain's readings for each of ``probes`` (requests the
    engine has just served and still holds; ``window_tokens[i]``: what
    the same prompt was answered with inside the window): a dict a
    request of numpy arrays ``[R, ...]`` (``PER_ROW``) and ``[R, L,
    ...]`` (``PER_LAYER``), ``R = chunk rows + n_decode``, rows past the
    request's own not ``valid``. A row emitted ``nxt`` (what the TIMED
    programs sampled there; -1: a chunk row but the last emits
    nothing) where the window's answer has ``alt``."""
    import jax
    import jax.numpy as jnp

    decode, chunk = build_probes(engine.config, engine.block_size)
    pools, params = engine._pools(), engine._params
    tables = jnp.asarray(engine._tables)
    c, slots, max_len = engine.prefill_chunk, engine.slots, engine.max_len
    n_layers = engine.config.n_layers
    take = jnp.asarray([r.slot for r in probes], jnp.int32)
    n_chunk = min(CHUNK_ROWS, c)

    def split(got, i):
        """Row ``i`` of a probe call's output, by layer."""
        row = {k: np.asarray(v)[:, i] for k, v in got.items()
               if k not in ("deficit", "alt_deficit", "score_err")}
        row["score_err"] = np.asarray(got["score_err"])[:, 0, i]
        row["low_score_err"] = np.asarray(got["score_err"])[:, 1, i]
        return row

    out = []
    for r, window in zip(probes, window_tokens):
        emitted = list(r.tokens)[:len(window)]
        alt = list(window)
        start = (r.prompt_len - 1) // c * c
        n_valid = r.prompt_len - start
        sel = np.unique(
            np.round(np.linspace(0, n_valid - 1, n_chunk)).astype(np.int32)
        )
        tokens = np.zeros((1, c), np.int32)
        tokens[0, :n_valid] = r.prompt[start:]
        padded = np.concatenate(
            [sel, np.full(n_chunk - len(sel), n_valid - 1, np.int32)]
        )
        got = jax.device_get(chunk(
            pools, params, tables[r.slot], jnp.int32(start),
            jnp.int32(n_valid), jnp.asarray(tokens), jnp.asarray(padded),
            jnp.int32(emitted[0]), jnp.int32(alt[0]),
        ))
        rows = []
        for i, at in enumerate(sel):
            last = at == n_valid - 1
            rows.append(dict(
                split(got, i), pos=start + at, valid=True,
                nxt=emitted[0] if last else -1, alt=alt[0] if last else -1,
                deficit=float(got["deficit"][0]) if last else np.nan,
                alt_deficit=float(got["alt_deficit"][0]) if last else np.nan,
            ))
        out.append({"rows": rows, "emitted": emitted, "alt": alt,
                    "seq": r.prompt.tolist() + emitted, "slot": r.slot,
                    "prompt_len": r.prompt_len, "window": alt})
    # Decode step j of a request: its token j - 1 at row p + j - 1, every
    # slot at once (the cell's shape), read at the probed slots.
    held = [r for r in engine.scheduler.active() if len(r.tokens) >= 2]
    for j in range(1, max(len(o["emitted"]) for o in out)):
        lengths, tokens, nxt, alt = (
            np.zeros(slots, np.int32) for _ in range(4)
        )
        for r in held:
            jj = min(j, len(r.tokens) - 1)
            lengths[r.slot] = r.prompt_len + jj - 1
            tokens[r.slot], nxt[r.slot] = r.tokens[jj - 1], r.tokens[jj]
        for o in out:
            if j < len(o["emitted"]):
                alt[o["slot"]] = o["alt"][j]
        got = jax.device_get(decode(
            pools, params, tables, jnp.asarray(lengths),
            jnp.asarray(tokens), jnp.asarray(nxt), jnp.asarray(alt), take,
        ))
        for i, o in enumerate(out):
            if j >= len(o["emitted"]):
                continue
            row = split(got, i)
            idx, ok = row.pop("idx"), row.pop("valid")
            mask = np.zeros((n_layers, max_len), bool)
            for layer in range(n_layers):
                mask[layer, idx[layer][ok[layer]]] = True
            o["rows"].append(dict(
                row, mask=mask, pos=o["prompt_len"] + j - 1, valid=True,
                nxt=o["emitted"][j], alt=o["alt"][j],
                deficit=float(got["deficit"][i]),
                alt_deficit=float(got["alt_deficit"][i]),
            ))
    total = n_chunk + n_decode
    requests = []
    for o in out:
        rows = o.pop("rows")
        blank = {
            k: np.zeros_like(np.asarray(v)) for k, v in rows[0].items()
        }
        rows += [dict(blank, valid=False, nxt=-1)] * (total - len(rows))
        requests.append(dict(o, **{
            k: np.stack([np.asarray(row[k]) for row in rows])
            for k in PER_LAYER + PER_ROW
        }))
    return requests


# -- the reference's side and the comparison ----------------------------------


def reference_side(params, cfg_json, request, max_len):
    """The reference over one probed request's sequence: its free-running
    logits and routing at the probed rows, and every layer held to the
    program's readings there (``reference_keye.probe_rows``)."""
    import jax.numpy as jnp

    tokens = np.zeros(max_len, np.int32)
    tokens[:len(request["seq"])] = request["seq"]
    n_layers = request["x_in"].shape[1]
    per_layer = [
        {
            "pos": jnp.asarray(request["pos"], jnp.int32),
            "x_in": jnp.asarray(request["x_in"][:, layer]),
            "mask": jnp.asarray(request["mask"][:, layer]),
            "attn": jnp.asarray(request["attn"][:, layer]),
            "x_mid": jnp.asarray(request["x_mid"][:, layer]),
            "ids": jnp.asarray(request["ids"][:, layer]),
            "weights": jnp.asarray(request["weights"][:, layer]),
            "y": jnp.asarray(
                request["x_out"][:, layer] - request["x_mid"][:, layer]
            ),
        }
        for layer in range(n_layers)
    ]
    out = reference_keye.forward_at(
        params, jnp.asarray(tokens), per_layer[0]["pos"], cfg_json,
        rows=per_layer, margin=SELECT_MARGIN,
    )
    embedded = np.asarray(out["embedded"])
    reads = {
        name: np.stack([np.asarray(r[name]) for r in out["rows"]], axis=1)
        for name in out["rows"][0]
    }                                                   # [R, L]
    reads["embed_err"] = np.abs(embedded - request["x_in"][:, 0]).max(-1)
    logits = np.asarray(out["logits"])
    return {
        "reads": reads, "logits": logits,
        "free_ids": np.moveaxis(np.asarray(out["ids"]), 0, 1),  # [R, L, k]
        "free_gap": np.asarray(out["gaps"]).min(0),
        "finite": bool(np.isfinite(logits).all()),
    }


def compare(requests, sides, topk):
    """All readings of (a) and (b) over the probed requests, and the
    problems they make."""
    cat = lambda name: np.concatenate([  # noqa: E731
        np.asarray(s["reads"][name]) for s in sides
    ])
    req = lambda name: np.concatenate([  # noqa: E731
        np.asarray(r[name]) for r in requests
    ])
    valid = req("valid").astype(bool)
    emits = valid & (req("nxt") >= 0)
    pos = req("pos")
    n_layers = req("pool_err").shape[1]
    # (a) the timed programs against the probe chain
    tracked_to = np.where(
        (req("pool_err") <= POOL_ROW_TOL).all(1), n_layers,
        (req("pool_err") > POOL_ROW_TOL).argmax(1),
    )
    tracked = tracked_to == n_layers
    deficit = np.where(emits, req("deficit"), np.nan)
    # ... the window's answer where it leaves the replay
    replay, first_split = [], []
    for r in requests:
        on = r["valid"].astype(bool) & (r["nxt"] >= 0)
        differs = on & (r["nxt"] != r["alt"])
        at = int(differs.argmax()) if differs.any() else len(on)
        replay.append(int(on[:at].sum()))
        first_split.append(np.arange(len(on)) == at)
    first_split = np.concatenate(first_split)
    # (a) the emitted tokens under the free-running reference
    logits = np.concatenate([s["logits"] for s in sides])
    nxt = np.where(emits, req("nxt"), 0)
    free_deficit = logits.max(-1) - logits[np.arange(len(nxt)), nxt]
    top2 = np.partition(logits, -2, axis=-1)[:, -2:]
    free_ids = np.concatenate([s["free_ids"] for s in sides])
    alike_free = (
        np.sort(req("ids"), -1) == np.sort(free_ids, -1)
    ).all((1, 2))
    free_gap = np.concatenate([s["free_gap"] for s in sides])
    # (b) by layer
    layer_valid = valid[:, None] & np.ones(n_layers, bool)[None]
    alike = cat("alike").astype(bool)
    want_keys = np.minimum(topk, pos + 1)

    def worst(a, where, fn=np.max, default=0.0):
        a = np.asarray(a, np.float64)[where]
        return float(fn(a)) if a.size else default

    def by_layer(a, where, fn=np.max):
        return [worst(a[:, i], where[:, i], fn) for i in range(n_layers)]

    check = {
        "n_rows": int(valid.sum()), "n_emitting": int(emits.sum()),
        "n_layers": n_layers,
        # (a)
        "pool_err_layer0_max": worst(req("pool_err")[:, 0], valid),
        "pool_err_by_layer": by_layer(req("pool_err"), layer_valid),
        "tracked_share": worst(tracked, valid, np.mean),
        "tracked_to_hist": np.bincount(
            tracked_to[valid], minlength=n_layers + 1
        ).tolist(),
        "program_deficit_tracked_max": worst(deficit, emits & tracked),
        "program_deficit_untracked_max": worst(deficit, emits & ~tracked),
        "replayed_tokens": replay,
        "window_tokens": [len(r["window"]) for r in requests],
        "split_deficit_tracked_max": worst(
            req("alt_deficit"), first_split & tracked
        ),
        "n_alike_free": int((emits & alike_free).sum()),
        "free_deficit_alike_max": worst(free_deficit, emits & alike_free),
        "free_deficit_flipped_max": worst(free_deficit, emits & ~alike_free),
        "free_deficits_alike_top": sorted(
            np.round(free_deficit[emits & alike_free], 4).tolist(),
            reverse=True,
        )[:12],
        "n_argmax_matches": int((free_deficit[emits] == 0).sum()),
        "median_top2_gap": worst(top2[:, 1] - top2[:, 0], emits, np.median),
        "n_runner_up_would_fail": int(
            ((top2[:, 1] - top2[:, 0]) > SERVE_LOGIT_TOL)[emits].sum()
        ),
        "free_gap_under_2pct": int((free_gap < 0.02)[emits].sum()),
        "logits_finite": all(s["finite"] for s in sides),
        # (b)
        "embed_err_max": worst(cat("embed_err"), valid),
        "score_err_max": worst(req("score_err"), layer_valid),
        "score_err_by_layer": by_layer(req("score_err"), layer_valid),
        "keys_wrong": int(
            (cat("n_keys") != want_keys[:, None])[layer_valid].sum()
        ),
        "keys_min": worst(cat("n_keys"), layer_valid, np.min),
        "share_wide_min": worst(cat("share_wide"), layer_valid, np.min, 1.0),
        "share_wide_min_by_layer": by_layer(
            cat("share_wide"), layer_valid, np.min
        ),
        "share_exact_mean_by_layer": by_layer(
            cat("share_exact"), layer_valid, np.mean
        ),
        "attn_err_first_max": worst(cat("attn_err")[:, 0], valid),
        "attn_err_max": worst(cat("attn_err"), layer_valid),
        "attn_err_by_layer": by_layer(cat("attn_err"), layer_valid),
        "alike_share": worst(alike, layer_valid, np.mean, 1.0),
        "swap_gap_max": worst(cat("swap_gap"), layer_valid & ~alike),
        "weight_err_max": worst(cat("weight_err"), layer_valid & alike),
        "y_err_max": worst(cat("y_err"), layer_valid & alike),
        "y_err_by_layer": by_layer(cat("y_err"), layer_valid & alike),
        # the reference in the precision below, on the same yardsticks
        "low_score_err_max": worst(req("low_score_err"), layer_valid),
        "low_share_wide_min": worst(
            cat("low_share_wide"), layer_valid, np.min, 1.0
        ),
        "low_share_exact_mean": worst(
            cat("low_share_exact"), layer_valid, np.mean
        ),
        "low_attn_err_first_min": worst(
            cat("low_attn_err")[:, 0], valid, np.min
        ),
        "low_attn_err_max": worst(cat("low_attn_err"), layer_valid),
        "low_alike_share": worst(cat("low_alike"), layer_valid, np.mean),
        "low_swap_gap_max": worst(
            cat("low_swap_gap"), layer_valid & ~cat("low_alike").astype(bool)
        ),
        "low_y_err_min": worst(cat("low_y_err"), layer_valid, np.min),
    }
    return check


def problems_of(check, judged="program"):
    """What ``check`` breaks. ``judged="reference_lower_precision"``
    (``controls_keye.py`` alone): the reference computed in the
    precision below, put in the program's place on (b)'s yardsticks."""
    c = dict(check)
    if judged == "reference_lower_precision":
        c.update(
            score_err_max=c["low_score_err_max"],
            share_wide_min=c["low_share_wide_min"],
            attn_err_first_max=c["low_attn_err_first_min"],
            attn_err_max=c["low_attn_err_max"],
            alike_share=c["low_alike_share"],
            swap_gap_max=c["low_swap_gap_max"],
            y_err_max=c["low_y_err_min"],
        )
    problems = []

    def limit(name, what, bound, upper=True):
        ok = c[name] <= bound if upper else c[name] >= bound
        if not ok:
            problems.append(
                f"{name} {c[name]:.4g}: {what} (limit {bound})"
            )

    if not c["logits_finite"]:
        problems.append("reference logits not finite")
    limit("pool_err_layer0_max", "the rows the timed programs landed in "
          "layer 0 are not the probe chain's", POOL_ROW_TOL)
    limit("tracked_share", "too few probed rows whose landed rows are the "
          "probe chain's in every layer", TRACK_SHARE_MIN, upper=False)
    limit("program_deficit_tracked_max", "a timed token sits below the "
          "probe chain's best logit", PROGRAM_LOGIT_TOL)
    limit("split_deficit_tracked_max", "the window answered otherwise "
          "than the replay, off a tie", PROGRAM_LOGIT_TOL)
    limit("free_deficit_alike_max", "an emitted token sits below the "
          "plain forward's maximum where both chose the same experts",
          SERVE_LOGIT_TOL)
    if c["n_alike_free"] < ALIKE_ROWS_MIN:
        problems.append(
            f"n_alike_free {c['n_alike_free']}: too few emitting rows where "
            f"program and plain forward chose the same experts in every "
            f"layer (limit {ALIKE_ROWS_MIN})"
        )
    limit("embed_err_max", "layer 0's input is not the embedding", 0.0)
    limit("score_err_max", "index scores off float32 on the same inputs",
          SCORE_ERR_MAX)
    if c["keys_wrong"]:
        problems.append(
            f"keys_wrong {c['keys_wrong']}: a probed query selected another "
            f"number of keys than min(topk, visible) (least {c['keys_min']})"
        )
    limit("share_wide_min", "the program's keys are not the reference's "
          f"top-(topk + {SELECT_MARGIN})", SELECT_SHARE_MIN, upper=False)
    limit("attn_err_first_max", "layer 0's attention output against the "
          "reference over the program's keys", ATTN_REL_ERR_FIRST_MAX)
    limit("attn_err_max", "attention output against the reference over "
          "the program's keys", ATTN_REL_ERR_MAX)
    limit("alike_share", "too few rows routed as the reference routes "
          "the same input", ALIKE_SHARE_MIN, upper=False)
    limit("swap_gap_max", "an expert chosen that the reference has "
          "clearly below its 8th", ROUTE_SWAP_GAP_MAX)
    limit("weight_err_max", "router weights against the reference's",
          ROUTE_WEIGHT_ERR_MAX)
    limit("y_err_max", "the expert half's output against the reference's",
          EXPERT_REL_ERR_MAX)
    return problems


JUDGED = "program"   # controls_keye.py's last control sets the other


# -- the run ------------------------------------------------------------------


def run(ctx):
    import jax

    counts = common.count_jax_events()
    from dlrover_tpu.models import sparse_lm
    from dlrover_tpu.observability import tracing
    from dlrover_tpu.serving.fleet import FleetRouter, ThreadReplica
    from dlrover_tpu.serving.kvpool import PagedServingEngine

    devices = jax.devices()
    device = common.device_facts(devices)
    if ctx["require_tpu"]:
        common.require_tpu(devices, ctx["chips"])
    traffic, cfg_json = ctx["traffic"], ctx["config"]
    cfg = sparse_config(cfg_json)
    eng = cfg_json["serve_engine"]
    log = common.EventLog(ctx["out_dir"] + "/events.jsonl")
    make_params = jax.jit(
        lambda key: sparse_lm.init_params(cfg, key, dtype=cfg.compute_dtype)
    )
    key = common.rng_key(ctx["seed"])
    box = {"params": make_params(key)}

    # The engine is built here and handed to the replica's thread: a
    # failure to build it is this process's error at once (a factory
    # that raises on the loop thread leaves ``start`` waiting).
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
    stream = request_stream(traffic, cfg.vocab_size, ctx["seed"])
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

    def pump(until, phase):
        """Hand finished requests out and refill, until ``until`` (a
        time, or a callable that says when to stop). A second without a
        completion while clients wait is logged with every thread's
        stack and the engine's token count (PERF.md section 7 row 14:
        whole seconds lost in one run of many, never yet caught)."""
        stop = until if callable(until) else (lambda: time.time() >= until)
        last, stalled = time.time(), False
        while not stop():
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
                         decode_tokens=decoded(), stacks=thread_stacks())
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
                if phase != "documents":
                    submit()
            if not finished:
                time.sleep(0.002)
        return time.time()

    trace = dump = scopes = traced_window = None
    try:
        # Set-up: every document once, alone, so that its blocks are in
        # the prefix cache before any client starts.
        t0 = time.time()
        for doc in documents(traffic, cfg.vocab_size, ctx["seed"]):
            submit(doc.tolist(), 1)
            pump(lambda: not live, "documents")
        resident = engine.kv_stats()
        hit0 = resident["prefix_hit_tokens"]
        prefilled0 = engine.metrics.tokens.value(kind="prefill")
        log.emit("documents_resident", seconds=time.time() - t0,
                 cached_blocks=resident["cached"])
        for _ in range(traffic["clients"]):
            submit()
        pump(time.time() + traffic["ramp_s"], "ramp")
        if ctx["trace"]:
            prof = common.Profile(ctx["out_dir"])
            t_prof = time.time()
            prof.start()
            try:
                pump(time.time() + traffic["trace_s"], "traced")
            finally:
                dump = prof.stop()
                traced_window = (t_prof, time.time())
        compiles_before = counts[common.BACKEND_COMPILE]
        t_window = time.time()
        setup_s = t_window - ctx["t_start"]
        t_end = pump(t_window + ctx["seconds"], "window")
        compiles_in_window = (
            counts[common.BACKEND_COMPILE] - compiles_before
        )
    finally:
        router.stop()
        if tracer is not None:
            tracing.disarm()
    window_s = t_end - t_window
    retraces = sum(engine.trace_counts.values()) - sum(
        box["traces"].values()
    )
    compiles = counts[common.BACKEND_COMPILE] - box["compiles"]
    kv_stats = {
        k: v for k, v in engine.kv_stats().items()
        if isinstance(v, (int, float))
    }
    hit_tokens = kv_stats["prefix_hit_tokens"] - hit0
    prefilled = engine.metrics.tokens.value(kind="prefill") - prefilled0
    hit_share = hit_tokens / max(hit_tokens + prefilled, 1)
    peak = common.memory_peak(devices[:ctx["chips"]])
    spans = tracer.finished() if tracer is not None else []
    if dump:
        from benchmark import sparse_scopes, trace_reduce

        sparse_scopes.label(dump, box.get("scopes") or {})
        trace = trace_reduce.reduce(dump)
        scopes = sparse_scopes.reduce(dump)

    # The checks' program side. The replica's thread has stopped; what
    # is still in the engine is cancelled. A sample of the window's
    # requests is served once more from here, over the same pool and
    # prefix cache, with stream requests in the other slots (the cell's
    # batch), and stays in its slots for the probe chain to read.
    t_join = time.time()
    while replica.alive() and time.time() - t_join < 120:
        time.sleep(0.05)
    if replica.alive():
        raise RuntimeError("the replica's loop did not stop")
    for req in list(engine.scheduler.active()) + list(engine.scheduler.queue):
        engine.cancel(req)
    engine.run_until_idle()
    in_window = [d for d in done if d["phase"] == "window"]
    served = [d for d in done if d["phase"] != "documents"]
    rng = np.random.default_rng((ctx["seed"], 10 ** 6))
    pool = [d for d in (in_window or served) if d["ok"] and d["tokens"]]
    picks = rng.permutation(len(pool))[:traffic["reference_sample"]]
    sample = [pool[i] for i in picks]
    out_max = traffic["output_len"]["max"]
    longest = traffic["documents"]["len"] + traffic["question_len"]["max"]
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
        engine, probes, [d["tokens"] for d in sample], out_max - 1
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
    if not hit_share >= traffic["prefix_hit_share_min"]:
        problems.append(
            f"{100 * hit_share:.2f} % of the prompt tokens came from the "
            f"prefix cache, under "
            f"{100 * traffic['prefix_hit_share_min']:.0f} %"
        )
    if probe_dropped:
        problems.append(f"{probe_dropped} expert row(s) dropped")

    check = {}
    if requests:
        params = make_params(key)   # bit-identical: same program, same key
        t0 = time.time()
        sides = [
            reference_side(params, cfg_json, r, eng["max_len"])
            for r in requests
        ]
        check = compare(requests, sides, cfg.index_topk)
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
        "sparse_scopes": scopes,
        "traced_window": traced_window,
        "dump": dump,
        "spans": spans,
        "window": {
            "seconds": window_s, "requests": len(in_window),
            "tokens_out": tokens_out,
            "tokens_in": sum(len(d["prompt"]) for d in in_window),
            "in_flight_at_end": len(live),
        },
        "prefix": {
            "hit_tokens": hit_tokens, "prefilled_tokens": prefilled,
            "hit_share": hit_share,
            "documents_cached_blocks": resident["cached"],
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
