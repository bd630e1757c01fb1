"""Runner ``serve_latent``: short turns against long RESIDENT session
contexts, through ``FleetRouter`` -> one ``ThreadReplica`` ->
``PagedServingEngine`` with the latent-attention model
(``models/latent_lm.py``). The request stream, the fixed schedule of
lengths, the stall log and the scope tables are ``runners/serve_sparse``'s
(called, not copied: a session's context is its ``document``, a turn its
``question``); what differs is the model and what ``correct`` compares.

Traffic: ``sessions.count`` contexts of ``sessions.len`` tokens from
``--seed``. During set-up each context is served once, alone, through
the router (one token asked), so that its blocks of latent rows sit in
the prefix cache before any client starts. A request is then one context
(fixed rotation: request i continues session i mod count) + a turn + an
answer, both lengths from the fixed set, in its fixed order. Every
request's context is a prefix hit: it costs one chunk against ~16k
cached latent rows and its answer's decode steps at ~16.5k rows a slot.
The window opens at the first completion after the ramp and closes at
the first completion ``--seconds`` later (at most one inter-completion
gap, ~0.12 s, over): its tokens are those of the requests completed
after the one and up to the other, over the time between the two. Cut at
instants of the clock instead, ~245 completions of ~47 tokens were one
more or one fewer from run to run, 0.4-0.5 % of ``serve_tokens_per_s``
each, and a set of six spread by 0.51 % where half the 1 % bound is the
most a new cell may (my chip run, PR 38; PERF.md section 6).

``correct`` (limits below, each beside the readings that set it). After
the window a sample of its requests is served once more, greedy, with
stream requests in the other slots (the cell's batch of 32), and stays
in its slots. Two PROBE programs of the check's own, made of the
functions the timed programs are made of (``latent_lm.block`` with its
taps, ``kvpool/latent.py``'s two ``attend``), then read over the
engine's live pool what the timed ones keep to themselves: every layer
of a request's turn chunk at a few rows, and layer 0 of the decode step
that would come next, all 32 slots at once. The reference runs each
probed request's whole sequence (context + turn + answer, ~17k tokens)
once, free-running.
(a) LOGITS: every emitted token against the reference's logits at its
    row: how far below the reference's best it sits. A routing flip in
    some layer (the program's router input is bfloat16) moves a row's
    logits by more than rounding (and the batch a request shares a step
    with decides which way a near-tie in a router falls: in two runs of
    six the window's answer left the replay's at a token 1.1-1.3 below
    the reference's best), so the limits are on the median and on the
    share of rows within the tolerance, the window's token where it
    leaves the replay among them.
(b) ATTENTION (random weights attend broadly, and logits barely see
    attention's precision): layer 0's inputs are the tokens' own, so the
    reference's are the program's: the decode step's absorbed attention
    at the cell's shape and the chunk's, before ``W_o``, against the
    reference's unabsorbed attention, and the rows the timed programs
    LANDED in the pool (``c`` after its norm, rotated ``k_r``) against
    the reference's. Over ~16.5k keys attended almost uniformly the
    OUTPUT averages the scores' rounding away (scores rounded to
    bfloat16 read 0.0082 where float32 ones read 0.0067-0.0086: my chip
    runs, PR 38), so the decode step's SCORES are read themselves, against
    float32 products of the same queries and rows
    (``reference_xing.cached_scores``).
(c) RESIDUAL and MLP, every layer, both sides fed the program's inputs
    (``reference_xing.hold_layer``): the program's ``H_res`` doubly
    stochastic, both mixes' change ``X' - X``, the MLP's normed input,
    the MLP's output (its median over the rows: a flipped expert is one
    row's), the experts chosen and their weights.
(d) the prefix cache served the contexts, no expert row was dropped,
    nothing compiled after warm-up, nothing was truncated.
Beside each reading of (b) and (c) the run reports what the REFERENCE
reads on the same yardstick when computed in the precision below the
configuration's (``low_*``): every such limit lies between the two.
"""

import gc
import time

import numpy as np

from benchmark import common, reference_xing
from benchmark.runners import serve as dense_serve
from benchmark.runners import serve_sparse

# The limits, each with the chip readings that set it (my chip runs, PR
# 38; PERF.md section 6): the largest the program read over its seeds,
# and what the REFERENCE reads on the same yardstick in the precision
# below the configuration's (``low_*`` in every run's ``reference``).
# (a) How far below the float32 reference's best logit an emitted token
# may sit: the median over the emitted rows, and the share of rows within
# the tolerance. The head's logits are bfloat16 (one place at 2-4 is
# 0.0156) and a routing flip in some layer moves a row's by more (read
# over 25 runs: median 0.0, 90th percentile up to 0.017, largest 2.69;
# 91.5-96.6 % of 178-490 rows within 0.1; the reference's median top-2
# gap 0.131-0.183).
SERVE_LOGIT_TOL = 0.1
LOGIT_DEFICIT_MEDIAN_MAX = 0.03
LOGIT_WITHIN_SHARE_MIN = 0.8
# (b) layer 0, whose inputs are the tokens' own. The rows the timed
# programs landed (bfloat16: one rounding of c and of the rotated k_r)
# against the reference's, the worst of ~66k rows. Read 0.0045-0.0049;
# the reference's rows in float8 0.0220-0.0228.
ROWS_REL_ERR_MAX = 0.012
# Attention's output before W_o, the absorbed decode step (32 queries at
# ~16.5k rows) and the chunk, against the reference's unabsorbed
# attention, the worst row. Read 0.0066-0.0094 (decode) and 0.0087-0.0134
# (chunk); the reference with float8 operands 0.0302-0.0532 and
# 0.0283-0.0443.
ATTN_REL_ERR_MAX = 0.02
# The decode step's scores (before the scale) against float32 products
# and sums of the same bfloat16 queries and rows, a slot's 32 heads x
# ~16.5k visible keys. Read 6.6e-8 (float32 accumulation on the MXU,
# another order of summation) in every run; the same scores held in
# bfloat16 0.00166, which is also what the program read with its scores
# rounded there (controls_xing.py).
SCORES_REL_ERR_MAX = 1e-5
# (c) H_res: rows and columns sum to 1. Read 1.4e-6 (hc_eps is 1e-6).
STOCHASTIC_ERR_MAX = 1e-3
# Both mixes' change X' - X on the program's own streams and sublayer
# output (float32 up to the read; the maps' projection at the highest
# precision, the mixes as float32 products). Read 1.5e-6 to 2.9e-6; the
# reference's maps in bfloat16 read 5.0e-4 to 7.3e-4. Two faults were found by it
# and repaired: a TPU's default float32 matmul in the mixes (2.8e-2),
# and a sublayer output tapped in bfloat16 where the compiler hands the
# mix the unrounded float32 (1.7e-3: the tap is now what the mix reads).
MIX_REL_ERR_MAX = 1e-4
# The MLP's normed input (bfloat16: one rounding). Read 0.0017-0.0018.
H_REL_ERR_MAX = 0.006
# The MLP's output on the same normed input: the median over the probed
# rows of a layer (a row whose experts differ from the reference's is a
# routing flip, counted apart), and the share of rows routed alike. Read
# 0.0031 (dense) and 0.0037 (expert layers), every row alike, the
# routers' weights the reference's to the bit; the reference with float8
# weights and activations 0.0563-0.0567 (its bfloat16 router routes
# 94.8-99.0 % of rows alike).
MLP_REL_ERR_MEDIAN_MAX = 0.0125
ALIKE_SHARE_MIN = 0.9
ROUTE_WEIGHT_ERR_MAX = 0.01
# Rows of a sampled request's turn chunk that are probed (evenly
# spread, the last one among them).
CHUNK_ROWS = 24


def latent_config(cfg_json, **overrides):
    """The program's config for a configuration file (published keys)."""
    from dlrover_tpu.models import latent_lm

    if cfg_json.get("hidden_act", "silu") != "silu":
        raise ValueError("the repo's MLP is SwiGLU (silu) only")
    if cfg_json.get("tie_word_embeddings"):
        raise ValueError("the repo's head is untied")
    reference_xing.shape_of(cfg_json)   # YaRN, sigmoid, no groups
    if cfg_json.get("moe_layer_freq", 1) != 1:
        raise ValueError("every layer past the dense ones is an expert layer")
    scaling = cfg_json["rope_scaling"]
    if scaling.get("mscale", 1) != scaling.get("mscale_all_dim", 1):
        raise ValueError("the rotation's cos / sin factor is 1 here")
    kw = dict(
        vocab_size=cfg_json["vocab_size"],
        embed_dim=cfg_json["hidden_size"],
        n_layers=cfg_json["num_hidden_layers"],
        first_dense=cfg_json["first_k_dense_replace"],
        n_heads=cfg_json["num_attention_heads"],
        q_lora_rank=cfg_json["q_lora_rank"],
        kv_lora_rank=cfg_json["kv_lora_rank"],
        qk_nope_dim=cfg_json["qk_nope_head_dim"],
        qk_rope_dim=cfg_json["qk_rope_head_dim"],
        v_head_dim=cfg_json["v_head_dim"],
        mlp_dim=cfg_json["intermediate_size"],
        moe_mlp_dim=cfg_json["moe_intermediate_size"],
        n_experts=cfg_json["n_routed_experts"],
        moe_top_k=cfg_json["num_experts_per_tok"],
        n_shared_experts=cfg_json["n_shared_experts"],
        routed_scaling=float(cfg_json["routed_scaling_factor"]),
        hc_mult=cfg_json["hc_mult"],
        hc_sinkhorn_iters=cfg_json["hc_sinkhorn_iters"],
        hc_eps=cfg_json["hc_eps"],
        hc_clamp=float(cfg_json["mhc_h_res_clamp_max"]),
        rope_theta=float(cfg_json["rope_theta"]),
        rope_factor=float(scaling["factor"]),
        rope_original_max=scaling["original_max_position_embeddings"],
        rope_beta_fast=float(scaling["beta_fast"]),
        rope_beta_slow=float(scaling["beta_slow"]),
        rope_mscale_all_dim=float(scaling["mscale_all_dim"]),
        dtype=cfg_json.get("torch_dtype", "bfloat16"),
    )
    kw.update(overrides)
    return latent_lm.LatentLMConfig(**kw)


def as_documents(traffic):
    """The traffic under the names ``serve_sparse``'s stream reads."""
    return dict(
        traffic, documents=traffic["sessions"],
        question_len=traffic["turn_len"],
    )


# -- the program's side: the probes -------------------------------------------

EVERY_LAYER = ("x_in", "res_attn", "attn", "y_attn", "x_mid", "res_mlp",
               "h_mlp", "y_mlp", "x_out")
EXPERT_LAYER = ("experts", "weights")


def build_probes(cfg, bs: int):
    """Programs of the check's own over the engine's LIVE pool (of
    ``bs``-row blocks), made of the functions the timed programs are made
    of: ``chunk(pool, params, table_row, start, tokens, sel)`` walks one
    slot's turn chunk through every layer (``latent_lm.block`` behind
    ``kvpool/latent.chunk_attend``) and hands out the block's taps at the
    chunk's rows ``sel`` (a dict of ``[L, R, ...]``; ``experts`` /
    ``weights [Lm, R, k]``); ``decode0(pool, params, tables, lengths,
    tokens)`` is layer 0 of the decode step every slot would take next
    (``decode_attend``): its attention output before ``W_o`` ``[slots,
    heads * v]``, and a slot the relative error of its scores over the
    visible keys against ``reference_xing.cached_scores`` of the same
    queries and rows, beside what those scores read when held in
    bfloat16 (``[slots]`` each); ``rows0(pool, table_row)`` one slot's
    landed rows of layer 0 ``[max_len, cache_width]``."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import latent_lm
    from dlrover_tpu.serving.kvpool import latent

    f32 = jnp.float32

    @jax.jit
    def chunk(pool, params, table_row, start, tokens, sel):
        positions = (
            start + jnp.arange(tokens.shape[1], dtype=jnp.int32)
        )[None, :]

        def body(streams, p, layer):
            taps = {}
            streams, _, _ = latent_lm.block(
                cfg, params, p, layer, streams, positions,
                latent.chunk_attend(cfg, pool, layer, table_row, start, bs),
                taps=taps,
            )
            every = {k: taps[k][0][sel].astype(f32) for k in EVERY_LAYER}
            every["attn"] = every["attn"].reshape(sel.shape[0], -1)
            expert = None
            if "experts" in taps:
                expert = {
                    "experts": taps["experts"][sel],
                    "weights": taps["weights"][sel].astype(f32),
                }
            return streams, (every, expert)

        _, (every, expert) = latent_lm.layer_loop(
            cfg, params, body, latent_lm.embed_streams(cfg, params, tokens)
        )
        return dict(every, **(expert or {}))

    @jax.jit
    def decode0(pool, params, tables, lengths, tokens):
        taps, seen = {}, {}
        latent_lm.block(
            cfg, params, latent_lm.layer_params(params, 0), 0,
            latent_lm.embed_streams(cfg, params, tokens[:, None]),
            lengths[:, None],
            latent.decode_attend(
                cfg, pool, 0, tables, lengths, bs, taps=seen
            ),
            taps=taps,
        )

        def scores_err(slot):
            """One slot's scores over its visible keys (a slot at a
            time: its rows in float32 are 40 MB)."""
            q, got, table_row, length = slot
            rows = pool.blocks_at(0, table_row).reshape(-1, cfg.cache_width)
            want = reference_xing.cached_scores(q, rows)
            visible = jnp.arange(rows.shape[0])[None, :] < length
            norm = lambda a: jnp.sqrt(  # noqa: E731
                jnp.sum(jnp.where(visible, jnp.square(a), 0.0)) + 1e-30
            )
            return (
                norm(got - want) / norm(want),
                norm(reference_xing.bf16(want) - want) / norm(want),
            )

        err, low = jax.lax.map(
            scores_err, (seen["queries"], seen["scores"], tables, lengths)
        )
        attn = taps["attn"][:, 0].reshape(tokens.shape[0], -1).astype(f32)
        return attn, err, low

    @jax.jit
    def rows0(pool, table_row):
        return pool.blocks_at(0, table_row).reshape(
            -1, cfg.cache_width
        ).astype(f32)

    return chunk, decode0, rows0


def probe_program(engine, probes, window_tokens):
    """The probes' readings for each of ``probes`` (requests the engine
    has just served and still holds; ``window_tokens[i]``: what the same
    prompt was answered with inside the window): a dict a request."""
    import jax
    import jax.numpy as jnp

    chunk, decode0, rows0 = build_probes(engine.config, engine.block_size)
    (pool,), params = engine._pools(), engine._params
    tables = jnp.asarray(engine._tables)
    c = engine.prefill_chunk
    n_chunk = min(CHUNK_ROWS, c)
    next_attn, scores_err, low_scores_err = (np.asarray(a) for a in decode0(
        pool, params, tables, jnp.asarray(engine._lengths),
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
        start = (r.prompt_len - 1) // c * c
        n_valid = r.prompt_len - start
        sel = np.unique(
            np.round(np.linspace(0, n_valid - 1, n_chunk)).astype(np.int32)
        )
        padded = np.concatenate(
            [sel, np.full(n_chunk - len(sel), n_valid - 1, np.int32)]
        )
        tokens = np.zeros((1, c), np.int32)
        tokens[0, :n_valid] = r.prompt[start:]
        got = jax.device_get(chunk(
            pool, params, tables[r.slot], jnp.int32(start),
            jnp.asarray(tokens), jnp.asarray(padded),
        ))
        out.append({
            "seq": [int(t) for t in r.prompt] + emitted,
            "prompt_len": r.prompt_len, "emitted": emitted,
            "window": [int(t) for t in window], "slot": r.slot,
            "chunk_rows": start + sel,
            "chunk": {k: np.asarray(v)[:, :len(sel)] for k, v in got.items()},
            "next_attn": next_attn[r.slot],
            "next_scores_err": float(scores_err[r.slot]),
            "low_next_scores_err": float(low_scores_err[r.slot]),
            "landed": np.asarray(rows0(pool, tables[r.slot]))[:fill],
        })
    return out


# -- the reference's side and the comparison ----------------------------------


def reference_side(params, cfg_json, request, pad_to):
    """The reference over one probed request's sequence, and its readings
    of that request: per-row arrays for :func:`compare`."""
    import jax.numpy as jnp

    seq, p = request["seq"], request["prompt_len"]
    n_emit = len(request["emitted"])
    # rows: the chunk's probed ones, every emitting row, the row the next
    # decode step would take (the last token's)
    emit_rows = p - 1 + np.arange(n_emit)
    rows = np.concatenate(
        [request["chunk_rows"], emit_rows, [len(seq) - 1]]
    ).astype(np.int32)
    n_chunk = len(request["chunk_rows"])
    tokens = np.zeros(pad_to, np.int32)
    tokens[:len(seq)] = seq
    n_layers = request["chunk"]["x_in"].shape[0]
    first_expert = n_layers - request["chunk"]["experts"].shape[0] \
        if "experts" in request["chunk"] else n_layers
    probes = []
    for layer in range(n_layers):
        probe = {k: request["chunk"][k][layer] for k in EVERY_LAYER
                 if k != "attn"}
        if layer >= first_expert:
            for k in EXPERT_LAYER:
                probe[k] = request["chunk"][k][layer - first_expert]
        probes.append(probe)
    ref = reference_xing.forward_at(
        params, jnp.asarray(tokens), jnp.asarray(rows), cfg_json,
        probes=probes,
    )
    logits = np.asarray(ref["logits"])[n_chunk:n_chunk + n_emit]
    emitted = np.asarray(request["emitted"])
    deficit = logits.max(-1) - logits[np.arange(n_emit), emitted]
    top2 = np.partition(logits, -2, axis=-1)[:, -2:]
    # where the window's answer leaves the replay's
    window = np.asarray(request["window"][:n_emit])
    differs = np.nonzero(window != emitted[:len(window)])[0]
    split_deficit = 0.0
    if len(differs):
        # one more emitted token of the same prefix: judged with the rest
        at = differs[0]
        split_deficit = float(logits[at].max() - logits[at, window[at]])
        deficit = np.append(deficit, split_deficit)
    rel = lambda got, want: np.asarray(  # noqa: E731
        reference_xing._rel(jnp.asarray(got), jnp.asarray(want))
    )
    attn0 = np.asarray(ref["attn0"])
    low_attn0 = np.asarray(ref["low_attn0"])
    want_rows = np.asarray(ref["cache_rows0"])[:len(request["landed"])]
    held = [
        {k: np.asarray(v) for k, v in layer.items()} for layer in ref["held"]
    ]
    return {
        "deficit": deficit, "top2_gap": top2[:, 1] - top2[:, 0],
        "replayed": int(differs[0]) if len(differs) else len(window),
        "split_deficit": split_deficit,
        "finite": bool(np.isfinite(np.asarray(ref["logits"])).all()),
        "chunk_attn_err": rel(request["chunk"]["attn"][0], attn0[:n_chunk]),
        "low_chunk_attn_err": rel(low_attn0[:n_chunk], attn0[:n_chunk]),
        "decode_attn_err": rel(request["next_attn"][None], attn0[-1:]),
        "low_decode_attn_err": rel(low_attn0[-1:], attn0[-1:]),
        "decode_scores_err": request["next_scores_err"],
        "low_decode_scores_err": request["low_next_scores_err"],
        "rows_err": rel(request["landed"], want_rows),
        "low_rows_err": rel(
            np.asarray(reference_xing.fp8(jnp.asarray(want_rows))), want_rows
        ),
        "held": held,
    }


def compare(requests, sides):
    """All readings of (a), (b) and (c) over the probed requests."""
    cat = lambda name: np.concatenate(  # noqa: E731
        [np.asarray(s[name], np.float64).reshape(-1) for s in sides]
    )
    n_layers = len(sides[0]["held"])
    held = lambda name, layer: np.concatenate([  # noqa: E731
        np.asarray(s["held"][layer][name], np.float64) for s in sides
        if name in s["held"][layer]
    ])
    expert_layers = [
        i for i in range(n_layers) if "alike" in sides[0]["held"][i]
    ]
    by_layer = lambda name, fn, layers=range(n_layers): [  # noqa: E731
        float(fn(held(name, i))) for i in layers
    ]
    deficit = cat("deficit")
    check = {
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
        # (b)
        "rows_err_max": float(cat("rows_err").max()),
        "rows_err_mean": float(cat("rows_err").mean()),
        "n_rows_landed": int(cat("rows_err").size),
        "decode_attn_err_max": float(cat("decode_attn_err").max()),
        "chunk_attn_err_max": float(cat("chunk_attn_err").max()),
        "decode_scores_err_max": float(cat("decode_scores_err").max()),
        # (c)
        "stochastic_err_max": max(
            by_layer("stochastic_attn", np.max)
            + by_layer("stochastic_mlp", np.max)
        ),
        "mix_err_max": max(
            by_layer("mix_attn", np.max) + by_layer("mix_mlp", np.max)
        ),
        "mix_err_by_layer": by_layer("mix_mlp", np.max),
        "h_err_max": max(by_layer("h_err", np.max)),
        "mlp_err_median_max": max(by_layer("y_err", np.median)),
        "mlp_err_median_by_layer": by_layer("y_err", np.median),
        "mlp_err_max_by_layer": by_layer("y_err", np.max),
        "alike_share_min": min(
            by_layer("alike", np.mean, expert_layers), default=1.0
        ),
        "alike_share_by_layer": by_layer("alike", np.mean, expert_layers),
        "weight_err_max": max((
            float(held("weight_err", i)[held("alike", i) > 0].max())
            for i in expert_layers if (held("alike", i) > 0).any()
        ), default=0.0),
        # the reference in the precision below, on the same yardsticks
        "low_rows_err_min": float(cat("low_rows_err").min()),
        "low_decode_attn_err_min": float(cat("low_decode_attn_err").min()),
        "low_chunk_attn_err_min": float(cat("low_chunk_attn_err").min()),
        "low_decode_scores_err_min": float(
            cat("low_decode_scores_err").min()
        ),
        "low_mix_err_min": min(
            by_layer("low_mix_attn", np.max) + by_layer("low_mix_mlp", np.max)
        ),
        "low_mlp_err_median_min": min(by_layer("low_y_err", np.median)),
        "low_alike_share_min": min(
            by_layer("low_alike", np.mean, expert_layers), default=1.0
        ),
    }
    return check


def problems_of(check, judged="program"):
    """What ``check`` breaks. ``judged="reference_lower_precision"``
    (``controls_xing.py`` alone): the reference computed in the precision
    below, put in the program's place on (b)'s and (c)'s yardsticks."""
    c = dict(check)
    if judged == "reference_lower_precision":
        c.update(
            rows_err_max=c["low_rows_err_min"],
            decode_attn_err_max=c["low_decode_attn_err_min"],
            chunk_attn_err_max=c["low_chunk_attn_err_min"],
            decode_scores_err_max=c["low_decode_scores_err_min"],
            mix_err_max=c["low_mix_err_min"],
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
    limit("rows_err_max", "the latent rows the timed programs landed in "
          "layer 0 against the reference's", ROWS_REL_ERR_MAX)
    limit("decode_attn_err_max", "layer 0's absorbed decode attention "
          "against the reference's unabsorbed", ATTN_REL_ERR_MAX)
    limit("chunk_attn_err_max", "layer 0's chunk attention against the "
          "reference's unabsorbed", ATTN_REL_ERR_MAX)
    limit("decode_scores_err_max", "layer 0's decode scores against "
          "float32 products of the same queries and rows",
          SCORES_REL_ERR_MAX)
    limit("stochastic_err_max", "H_res rows or columns do not sum to 1",
          STOCHASTIC_ERR_MAX)
    limit("mix_err_max", "a residual mix's change against the "
          "reference's on the same streams", MIX_REL_ERR_MAX)
    limit("h_err_max", "the MLP's normed input against the reference's "
          "on the same streams", H_REL_ERR_MAX)
    limit("mlp_err_median_max", "the MLP's output against the reference's "
          "on the same input", MLP_REL_ERR_MEDIAN_MAX)
    limit("alike_share_min", "too few rows routed as the reference routes "
          "the same input", ALIKE_SHARE_MIN, upper=False)
    limit("weight_err_max", "router weights against the reference's",
          ROUTE_WEIGHT_ERR_MAX)
    return problems


JUDGED = "program"   # controls_xing.py's last control sets the other


def log_full_collections(log):
    """Every full (generation 2) collection of Python's collector into
    ``log`` with its seconds: it stops every thread of the process, the
    replica's loop among them, so a pause inside the window can be told
    from one (none falls between ramp and window's end in a run that
    does not pause, and one made by hand once the sessions are resident
    takes 0.08 s: my chip runs, PR 38). Returns the callback, for the
    caller to take out of ``gc.callbacks`` again."""
    started = []

    def on_gc(phase, info):
        if info["generation"] < 2:
            return
        if phase == "start":
            started.append(time.time())
        elif started:
            log.emit("full_collection", seconds=time.time() - started.pop(),
                     collected=info["collected"])

    gc.callbacks.append(on_gc)
    return on_gc


# -- the run ------------------------------------------------------------------


def run(ctx):
    import jax

    counts = common.count_jax_events()
    from dlrover_tpu.models import latent_lm
    from dlrover_tpu.observability import tracing
    from dlrover_tpu.serving.fleet import FleetRouter, ThreadReplica
    from dlrover_tpu.serving.kvpool import PagedServingEngine

    devices = jax.devices()
    device = common.device_facts(devices)
    if ctx["require_tpu"]:
        common.require_tpu(devices, ctx["chips"])
    cfg_json = ctx["config"]
    traffic = as_documents(ctx["traffic"])
    cfg = latent_config(cfg_json)
    eng = cfg_json["serve_engine"]
    log = common.EventLog(ctx["out_dir"] + "/events.jsonl")
    make_params = jax.jit(
        lambda key: latent_lm.init_params(cfg, key, dtype=cfg.compute_dtype)
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
        box["scopes"] = serve_sparse.program_scopes(engine)
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
    on_gc = log_full_collections(log)
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
        wait is logged with every thread's stack
        (``serve_sparse.thread_stacks``)."""
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
        # blocks are in the prefix cache before any client starts.
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
                 cached_blocks=resident["cached"])
        for _ in range(traffic["clients"]):
            submit()
        # Both edges of the window are completions: a window cut at
        # arbitrary instants holds ~245 completions give or take one,
        # 0.4 % of the tokens each, and six runs spread by that (docstring).
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
        gc.callbacks.remove(on_gc)
        if tracer is not None:
            tracing.disarm()
    window_s = t_end - t_window
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
    peak = common.memory_peak(devices[:ctx["chips"]])
    spans = tracer.finished() if tracer is not None else []
    if dump:
        from benchmark import latent_scopes, sparse_scopes, trace_reduce

        sparse_scopes.label(dump, box.get("scopes") or {})
        trace = trace_reduce.reduce(dump)
        scopes = latent_scopes.reduce(dump)

    # The checks' program side. The replica's thread has stopped; what
    # is still in the engine is cancelled. A sample of the window's
    # requests is served once more from here, over the same pool and
    # prefix cache, with stream requests in the other slots (the cell's
    # batch), and stays in its slots for the probes to read.
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
        pad_to = -(-max(len(r["seq"]) for r in requests) // 1024) * 1024
        sides = [
            reference_side(params, cfg_json, r, pad_to) for r in requests
        ]
        check = compare(requests, sides)
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
        # table read (serve_expert_ms_per_step, decode_unscoped_ms_per_
        # step); benchmark/latent_scopes.py made it
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
            "sessions_cached_blocks": resident["cached"],
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
