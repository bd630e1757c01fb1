"""Runner ``serve_window``: short and long prompts in ONE queue, every
prompt token prefilled inside the timed window, through ``FleetRouter``
-> one ``ThreadReplica`` -> ``PagedServingEngine`` with the window / full
attention pattern model (``models/window_lm.py``), whose pool is in two
layer groups. The stall log, the collector's log and the host-pause
watch are the accepted runners' (called, not copied); what differs is the
model, the traffic's two length ranges, and what ``correct`` compares.

Traffic: a fixed set of ``length_set_size`` (prompt, answer) lengths from
``length_set_seed``, ``long_share`` of them drawn from
``long_prompt_len`` and the rest from ``prompt_len``, replayed epoch
after epoch in the traffic file's own order by every seed; tokens random
from ``--seed``, no shared prefix: the prefix cache runs, inserts and
evicts, and never hits. Closed loop: a finished request's client sends
the next at once. The window opens at the first completion after the
ramp and closes at the first completion ``--seconds`` later
(``serve_latent``'s finding, PR 38).

``correct`` (limits below, each beside the readings that set it; every
one is on a MEDIAN or a SHARE of rows, never on a single worst row).
After the window a sample of its requests (``reference_long`` long ones
among ``reference_sample``) is served once more, greedy, with stream
requests in the other slots, and stays in its slots (where its entry is
still in the prefix cache with its tail the replay HITS it and carries
on from the rows the window's own chunks landed; where the tail was
dropped it prefills again). A PROBE program of
the check's own, made of the functions the timed programs are made of
(``kvpool/window.decode_forward`` with ``window_lm.block``'s taps), reads
over the engine's live pools what the timed ones keep to themselves, and
the rows they landed are read out of both groups. The reference runs each
probed request's whole sequence (prompt + answer, up to 16.8k tokens)
once, free running.
(a) LOGITS: every emitted token against the reference's logits at its
    row: how far below the reference's best it sits: the median, and the
    share of rows within the tolerance.
(b) What the timed programs LANDED, chunked prefill then decode through
    both groups: every layer's K rows (rotated by its type) and V rows
    against the reference's, free running: the full group's every row,
    the window group's band below the slot's fill (what the rule of
    release must have kept); the first layer of each group is limited,
    the deeper ones reported, and no more than a thousandth of all rows
    may be off by a quarter (a released block read back, a sentinel).
(c) The first window layer and the first full layer fed the program's
    own inputs at the slot's NEXT row (past 8,192 for a long prompt,
    where YaRN's stretched pairs and the band both bite): attention
    before ``W_o``, the reference's query over the rows the program
    landed; and of every layer the router's choice and weights and the
    expert sum on the program's own normed input.
(d) no expert row dropped, nothing compiled after warm-up, nothing
    truncated, both groups' allocators conserve at the window's end, the
    window group released blocks, and no slot held a block wholly below
    its band nor lacked one inside it.
Beside each reading of (b) and (c) the run reports what the REFERENCE
reads on the same yardstick when computed in the precision below the
configuration's (``low_*``): every such limit lies between the two.
"""

import gc
import time

import numpy as np

from benchmark import common, reference_mellum2
from benchmark.runners import serve as dense_serve
from benchmark.runners import serve_conv, serve_latent, serve_sparse

# The limits, each with the chip readings that set it (my chip runs, PR
# 51, the four runs of calls A and B, seeds 4021337, 2500000011, 77 and
# 3123456789, read BEFORE the runs that are reported; PERF.md section
# 6): about three times the largest the program read over those seeds,
# below what the REFERENCE reads on the same yardstick in the precision
# below the configuration's (``low_*``: 3 bits of mantissa).
# (a) How far below the float32 reference's best logit an emitted token
# may sit: the median over the emitted rows, and the share within the
# tolerance. The head's logits are bfloat16 and of unit scale. Read:
# median 0.0 in every run (so is the lower precision's: this limit
# parts nothing, it is the share that does), 98.8-99.3 % of 251-975 rows
# within 0.1, largest 0.26; the reference free running with 3 bits of
# mantissa, judged as the program is (the token IT would emit at each
# row): 86.3-89.6 % within. The share's limit lies between the two.
SERVE_LOGIT_TOL = 0.1
LOGIT_DEFICIT_MEDIAN_MAX = 0.03
LOGIT_WITHIN_SHARE_MIN = 0.94
# (b) The landed K and V rows of the FIRST layer of each group, the
# median over rows: the window group's first layer is layer 0, whose
# inputs are the tokens' own (one rounding); the full group's first is
# layer 3, free running through three layers. Read 0.002450-0.002451
# (window; lower precision 0.0375-0.0376) and 0.00346-0.00351 (full;
# 0.0486-0.0488). Deeper layers are REPORTED (``rows_err_median_by_
# layer``: 0.0027 ... 0.0066 at layer 7; lower precision 0.041 ... 0.097).
WINDOW_ROWS_ERR_MEDIAN_MAX = 0.008
FULL_ROWS_ERR_MEDIAN_MAX = 0.012
# ... and the share of ALL landed rows (every layer, both groups,
# 98,000-166,000 a run) that are off by more than ROW_BAD: a block
# released too early reads the sentinel (an error of ~1.4) in 64 of its
# slot's 1,023 band rows; the deepest layer's 99th percentile reads
# 0.139-0.144, and no row of any run passed 0.5.
ROW_BAD = 0.5
ROWS_BAD_SHARE_MAX = 0.001
# (c) Attention before W_o on the program's own inputs at the next row,
# the median over the probed requests: first window layer (read
# 0.00344-0.00412, a request 0.0025-0.0056; lower precision
# 0.0624-0.0649), first full layer (0.00301-0.00369, a request
# 0.0019-0.0063; 0.0587-0.0622). A window one row wide moves the first
# by about sqrt(1/1024) = 0.03.
WINDOW_ATTN_ERR_MEDIAN_MAX = 0.012
FULL_ATTN_ERR_MEDIAN_MAX = 0.012
# The expert sum on the program's own normed input and routing, the
# median over (request, layer): read 0.00481-0.00486 (lower precision
# 0.0652-0.0662).
MLP_ERR_MEDIAN_MAX = 0.015
# Rows routed as the reference routes the same input, over (request,
# layer): 32 rows a run, read 0.9375 / 0.96875 / 1.0 / 1.0. On the chip
# the router's weights sit 0.00145-0.00156 off the reference's (median,
# the largest difference over the largest weight, alike rows and layers
# alike; 1e-7 on a CPU: PERF.md section 7), so an 8th and a 9th expert
# that close change places in a row in thirty: the share is held only to
# a floor far below, and the weights' limit is what parts a wrong router
# from a right one (an unnormalised one reads ~0.5).
ALIKE_SHARE_MIN = 0.7
ROUTE_WEIGHT_ERR_MEDIAN_MAX = 0.01


def window_config(cfg_json, **overrides):
    """The program's config for a configuration file (published keys)."""
    from dlrover_tpu.models import window_lm

    sh = reference_mellum2.shape_of(cfg_json)      # validates the keys
    if cfg_json.get("tie_word_embeddings"):
        raise ValueError("this model's head is untied")
    kw = dict(
        vocab_size=cfg_json["vocab_size"], embed_dim=sh["hidden"],
        layer_types=sh["types"], sliding_window=sh["window"],
        n_heads=sh["heads"], n_kv_heads=sh["kv_heads"],
        head_dim=sh["head_dim"],
        moe_mlp_dim=cfg_json["moe_intermediate_size"],
        n_experts=sh["experts"], moe_top_k=sh["top_k"],
        rope_theta=sh["theta"], rope_factor=sh["factor"],
        rope_original_max=sh["original_max"], beta_fast=sh["beta_fast"],
        beta_slow=sh["beta_slow"], attention_factor=sh["attention_factor"],
        norm_eps=sh["eps"], dtype=cfg_json.get("torch_dtype", "bfloat16"),
    )
    kw.update(overrides)
    return window_lm.WindowLMConfig(**kw)


def mixed_length_set(traffic):
    """The mix's fixed set of (prompt_len, output_len), the same for
    every seed: ``long_share`` of ``length_set_size`` prompts from
    ``long_prompt_len`` (first in the set), the rest from ``prompt_len``
    (``runners/serve``'s draws)."""
    rng = np.random.default_rng(traffic["length_set_seed"])
    n = traffic["length_set_size"]
    n_long = round(traffic["long_share"] * n)
    prompts = np.concatenate([
        dense_serve._draw(traffic["long_prompt_len"], n_long, rng),
        dense_serve._draw(traffic["prompt_len"], n - n_long, rng),
    ])
    return list(zip(
        prompts.tolist(),
        dense_serve._draw(traffic["output_len"], n, rng).tolist(),
    ))


def request_stream(traffic, vocab, seed):
    """Endless (prompt tokens, max_new_tokens): the length set, epoch
    after epoch, in the traffic file's own order (``runners/serve``'s
    permutation a epoch), with tokens of the seed."""
    if traffic.get("shared_prefix"):
        raise ValueError("this mix shares no prefix")
    lengths = mixed_length_set(traffic)
    epoch = 0
    while True:
        rng = np.random.default_rng((seed, epoch))
        order = np.random.default_rng(
            (traffic["length_set_seed"], epoch)
        ).permutation(len(lengths))
        for i in order:
            n_prompt, n_new = lengths[i]
            yield rng.integers(0, vocab, n_prompt).tolist(), int(n_new)
        epoch += 1


def is_long(traffic, prompt_len):
    return prompt_len >= traffic["long_prompt_len"]["min"]


def program_scopes(engine):
    """``serve_sparse.program_scopes`` for this model's programs: the
    tables ride stacked, a group each."""
    import jax
    import jax.numpy as jnp

    from benchmark import trace_reduce

    shape = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    lead = jax.tree_util.tree_map(shape, (*engine._pools(), engine._params))
    i32, f32 = jnp.int32, jnp.float32
    arr = jax.ShapeDtypeStruct
    slots, mb = engine.slots, engine.max_blocks
    groups = engine._program_tables.shape[0]
    key = shape(engine._rng)
    texts = {
        "jit_step": engine._steps.decode.lower(
            *lead, arr((groups, slots, mb), i32), arr((slots,), i32),
            arr((slots,), i32), arr((slots,), bool), arr((slots,), f32),
            key, arr((), i32), arr((), i32), arr((), i32),
        ),
        "jit_prefill": engine._steps.prefill.lower(
            *lead, arr((1, engine.prefill_chunk), i32),
            arr((groups, mb), i32), arr((), i32), arr((), i32),
            arr((), f32), key, arr((), i32), arr((), bool),
        ),
    }
    return {
        name: trace_reduce.scopes_from_hlo(low.compile().as_text())
        for name, low in texts.items()
    }


# -- the program's side: the probes -------------------------------------------

TAPS = ("x_in", "attn", "h_mlp", "y_mlp", "experts", "weights")


def build_probes(cfg, bs: int, kind: str):
    """Programs of the check's own over the engine's LIVE pools, made of
    the functions the timed programs are made of: ``decode(*pools,
    params, tables, lengths, tokens)`` the step every slot would take
    next, read at every slot: a layer's taps (``TAPS``) and the new rows
    by group; ``landed(*pools, table_rows)`` one slot's rows of every
    layer of every group, ``[Lg, max_len, kv_heads * hd]`` a group."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.serving.kvpool import window

    f32 = jnp.float32
    n_pools = 2 * len(cfg.cache_groups)

    @jax.jit
    def decode(*args):
        pools, (params, tables, lengths, tokens) = (
            args[:n_pools], args[n_pools:]
        )
        taps = {}
        _, new_rows, _ = window.decode_forward(
            cfg, pools, params, tables, lengths, tokens, bs, taps=taps,
            kind=kind,
        )
        out = []
        for layer in range(cfg.n_layers):
            t = taps[layer]
            one = {
                "x_in": t["x_in"][:, 0], "h_mlp": t["h_mlp"][:, 0],
                "y_mlp": t["y_mlp"][:, 0],
                "attn": t["attn"][:, 0].reshape(t["attn"].shape[0], -1),
                "weights": t["weights"],
            }
            one = {k: v.astype(f32) for k, v in one.items()}
            one["experts"] = t["experts"]
            out.append(one)
        flat = lambda a: a.astype(f32).reshape(  # noqa: E731
            a.shape[:2] + (-1,)
        )
        return out, [(flat(k), flat(v)) for k, v in new_rows]

    @jax.jit
    def landed(*args):
        pools, table_rows = args[:n_pools], args[n_pools]
        out = []
        for g in range(len(cfg.cache_groups)):
            k, v = pools[2 * g], pools[2 * g + 1]
            rows = lambda p: p[:, table_rows[g]].reshape(  # noqa: E731
                p.shape[0], -1, p.shape[-2] * p.shape[-1]
            ).astype(f32)
            out.append((rows(k), rows(v)))
        return out

    return decode, landed


def band_of(engine, slot):
    """``(first row, fill)`` of what the slot's next query sees in each
    reach group, and whether the slot holds every block of it and none
    wholly below it."""
    fill = int(engine._lengths[slot])
    out = []
    for g in engine._reach_groups:
        lo = max(fill - g.reach, 0)
        held = g.slot_blocks[slot]
        want = range(lo // g.block_size, -(-fill // g.block_size))
        out.append({
            "lo": lo, "missing": sum(1 for b in want if b not in held),
            "stale": sum(1 for b in held if b < want.start - 1),
        })
    return fill, out


def probe_program(engine, probes, window_tokens):
    """The probes' readings for each of ``probes`` (requests the engine
    has just served and still holds; ``window_tokens[i]``: what the same
    prompt was answered with inside the window): a dict a request."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.serving.kvpool import window

    cfg, bs = engine.config, engine.block_size
    decode, landed = build_probes(cfg, bs, engine.window_decode_attention)
    pools, params = engine._pools(), engine._params
    tables = jnp.asarray(engine._program_tables)
    next_taps, next_rows = jax.device_get(decode(
        *pools, params, tables, jnp.asarray(engine._lengths),
        jnp.asarray(engine._tokens),
    ))
    group_of = [
        window.group_index(cfg, kind) for kind in cfg.layer_types
    ]
    out = []
    for r, tokens_in_window in zip(probes, window_tokens):
        emitted = [int(t) for t in r.tokens]
        fill, bands = band_of(engine, r.slot)
        if fill != r.prompt_len + len(emitted) - 1:
            raise RuntimeError(
                f"slot {r.slot} holds {fill} rows for a prompt of "
                f"{r.prompt_len} and {len(emitted)} tokens"
            )
        rows = jax.device_get(landed(*pools, tables[:, r.slot]))
        layers = []
        for layer in range(cfg.n_layers):
            g, at = group_of[layer], cfg.index_in_kind(layer)
            lo = bands[g - 1]["lo"] if g else 0
            one = {k: np.asarray(v)[r.slot] for k, v in
                   next_taps[layer].items()}
            one.update(
                group=g, lo=lo,
                k_landed=np.asarray(rows[g][0][at, lo:fill]),
                v_landed=np.asarray(rows[g][1][at, lo:fill]),
                k_next=np.asarray(next_rows[g][0][at, r.slot]),
                v_next=np.asarray(next_rows[g][1][at, r.slot]),
            )
            layers.append(one)
        out.append({
            "seq": [int(t) for t in r.prompt] + emitted,
            "prompt_len": r.prompt_len, "emitted": emitted,
            "window": [int(t) for t in tokens_in_window], "slot": r.slot,
            "fill": fill, "bands": bands, "layers": layers,
            "released": r.window_blocks_released,
        })
    return out


# -- the reference's side and the comparison ----------------------------------


def reference_side(params, cfg_json, request, pad_to, held_layers):
    """The reference over one probed request's sequence, and its readings
    of that request: per-row arrays for :func:`compare`."""
    import jax.numpy as jnp

    seq, p, fill = request["seq"], request["prompt_len"], request["fill"]
    n_emit = len(request["emitted"])
    emit_rows = (p - 1 + np.arange(n_emit)).astype(np.int32)
    tokens = np.zeros(pad_to, np.int32)
    tokens[:len(seq)] = seq
    ref = reference_mellum2.forward_at(
        params, jnp.asarray(tokens), jnp.asarray(emit_rows), cfg_json
    )
    logits = np.asarray(ref["logits"])
    emitted = np.asarray(request["emitted"])
    deficit = logits.max(-1) - logits[np.arange(n_emit), emitted]
    # The same sequence through the reference in the precision BELOW the
    # configuration's, free running: the token IT would emit at each row,
    # on the program's yardstick, and the rows IT would land.
    low_ref = reference_mellum2.forward_at(
        params, jnp.asarray(tokens), jnp.asarray(emit_rows), cfg_json,
        low=True,
    )
    low_emitted = np.asarray(low_ref["logits"]).argmax(-1)
    low_deficit = logits.max(-1) - logits[np.arange(n_emit), low_emitted]
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
        reference_mellum2._rel(jnp.asarray(got), jnp.asarray(want))
    )
    rows_err, low_rows_err, held = [], [], {}
    for layer, one in enumerate(request["layers"]):
        lo = one["lo"]
        want = [np.asarray(a[lo:fill]) for a in ref["kv"][layer]]
        low_want = [np.asarray(a[lo:fill]) for a in low_ref["kv"][layer]]
        rows_err.append(np.concatenate([
            rel(one["k_landed"], want[0]), rel(one["v_landed"], want[1]),
        ]))
        low_rows_err.append(np.concatenate([
            rel(low_want[0], want[0]), rel(low_want[1], want[1]),
        ]))
        probe = {k: one[k] for k in TAPS}
        probe["position"] = np.int32(fill)
        # the rows the program landed, at their own positions (what lies
        # below the band was released: unseen by a window layer)
        k_rows = np.zeros((pad_to, one["k_landed"].shape[1]), np.float32)
        v_rows = np.zeros_like(k_rows)
        if layer in held_layers:
            k_rows[lo:fill], v_rows[lo:fill] = (
                one["k_landed"], one["v_landed"]
            )
        probe.update(k_rows=k_rows, v_rows=v_rows)
        got = reference_mellum2.hold_row(params, layer, probe, cfg_json)
        held[layer] = {k: float(v) for k, v in got.items()}
    return {
        "deficit": deficit, "top2_gap": top2[:, 1] - top2[:, 0],
        "replayed": int(differs[0]) if len(differs) else len(window),
        "split_deficit": split_deficit,
        "finite": bool(np.isfinite(logits).all()),
        "low_deficit": low_deficit,
        "rows_err": rows_err, "low_rows_err": low_rows_err, "held": held,
    }


def compare(requests, sides, layer_types):
    """All readings of (a), (b) and (c) over the probed requests."""
    cat = lambda name: np.concatenate(  # noqa: E731
        [np.asarray(s[name], np.float64).reshape(-1) for s in sides]
    )
    n_layers = len(layer_types)
    first_window = layer_types.index("sliding_attention")
    first_full = layer_types.index("full_attention")
    rows_of = lambda name, layer: np.concatenate(  # noqa: E731
        [s[name][layer] for s in sides]
    )
    median = lambda a: float(np.median(a)) if len(a) else 0.0  # noqa: E731
    held = lambda name, layers=range(n_layers): np.asarray(  # noqa: E731
        [s["held"][i][name] for s in sides for i in layers], np.float64
    )
    within = lambda a: (  # noqa: E731
        float((a <= SERVE_LOGIT_TOL).mean()) if len(a) else None
    )
    deficit = cat("deficit")
    all_rows = np.concatenate(
        [rows_of("rows_err", i) for i in range(n_layers)]
    )
    return {
        "n_requests": len(requests), "n_emitting": int(deficit.size),
        "n_layers": n_layers,
        "prompt_lens": [r["prompt_len"] for r in requests],
        "fills": [r["fill"] for r in requests],
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
        "window_rows_err_median": median(rows_of("rows_err", first_window)),
        "full_rows_err_median": median(rows_of("rows_err", first_full)),
        "rows_err_median_by_layer": [
            median(rows_of("rows_err", i)) for i in range(n_layers)
        ],
        "rows_err_p99_by_layer": [
            float(np.quantile(rows_of("rows_err", i), 0.99))
            for i in range(n_layers)
        ],
        "rows_bad_share": float((all_rows > ROW_BAD).mean()),
        "n_rows_landed": int(all_rows.size),
        "band_blocks_missing": sum(
            b["missing"] for r in requests for b in r["bands"]
        ),
        "band_blocks_stale": sum(
            b["stale"] for r in requests for b in r["bands"]
        ),
        "window_blocks_released_by_probe": [
            r["released"] for r in requests
        ],
        # (c)
        "window_attn_err_median": median(held("attn_err", [first_window])),
        "full_attn_err_median": median(held("attn_err", [first_full])),
        "window_attn_err_by_request": held(
            "attn_err", [first_window]
        ).tolist(),
        "full_attn_err_by_request": held("attn_err", [first_full]).tolist(),
        "mlp_err_median": median(held("mlp_err")),
        "mlp_err_max": float(held("mlp_err").max()),
        "alike_share": float(held("alike").mean()),
        "alike_by_layer": [
            float(held("alike", [i]).mean()) for i in range(n_layers)
        ],
        "weight_err_median": median(held("weight_err")),
        "weight_err_max": float(held("weight_err").max()),
        "weight_err_median_by_layer": [
            median(held("weight_err", [i])) for i in range(n_layers)
        ],
        # the reference in the precision below, on the same yardsticks
        "low_logit_deficit_median": float(np.median(cat("low_deficit"))),
        "low_logit_within_share": within(cat("low_deficit")),
        "low_window_rows_err_median": median(
            rows_of("low_rows_err", first_window)
        ),
        "low_full_rows_err_median": median(
            rows_of("low_rows_err", first_full)
        ),
        "low_rows_err_median_by_layer": [
            median(rows_of("low_rows_err", i)) for i in range(n_layers)
        ],
        "low_window_attn_err_median": median(
            held("low_attn_err", [first_window])
        ),
        "low_full_attn_err_median": median(
            held("low_attn_err", [first_full])
        ),
        "low_mlp_err_median": median(held("low_mlp_err")),
    }


def problems_of(check, judged="program"):
    """What ``check`` breaks. ``judged="reference_lower_precision"``
    (``controls_mellum2.py`` alone): the reference computed in the
    precision below, put in the program's place on (a)'s, (b)'s and
    (c)'s yardsticks."""
    c = dict(check)
    if judged == "reference_lower_precision":
        c.update(
            logit_deficit_median=c["low_logit_deficit_median"],
            logit_within_share=c["low_logit_within_share"],
            window_rows_err_median=c["low_window_rows_err_median"],
            full_rows_err_median=c["low_full_rows_err_median"],
            window_attn_err_median=c["low_window_attn_err_median"],
            full_attn_err_median=c["low_full_attn_err_median"],
            mlp_err_median=c["low_mlp_err_median"],
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
    limit("window_rows_err_median", "the K (rotated) and V rows the timed "
          "programs landed in the first window layer against the "
          "reference's", WINDOW_ROWS_ERR_MEDIAN_MAX)
    limit("full_rows_err_median", "the K (rotated, YaRN) and V rows the "
          "timed programs landed in the first full layer against the "
          "reference's", FULL_ROWS_ERR_MEDIAN_MAX)
    limit("rows_bad_share", f"landed rows off by more than {ROW_BAD} (a "
          "block released inside its band reads the sentinel)",
          ROWS_BAD_SHARE_MAX)
    limit("window_attn_err_median", "the first window layer's attention "
          "before W_o at the next row against the reference's query over "
          "the band of landed rows", WINDOW_ATTN_ERR_MEDIAN_MAX)
    limit("full_attn_err_median", "the first full layer's attention "
          "before W_o at the next row against the reference's query over "
          "the landed rows", FULL_ATTN_ERR_MEDIAN_MAX)
    limit("mlp_err_median", "the expert sum against the reference's on "
          "the same input and routing", MLP_ERR_MEDIAN_MAX)
    limit("alike_share", "too few rows routed as the reference routes "
          "the same input", ALIKE_SHARE_MIN, upper=False)
    limit("weight_err_median", "router weights against the reference's",
          ROUTE_WEIGHT_ERR_MEDIAN_MAX)
    if c["band_blocks_missing"] or c["band_blocks_stale"]:
        problems.append(
            f"band_blocks: a probed slot lacks {c['band_blocks_missing']} "
            f"block(s) inside its band and holds {c['band_blocks_stale']} "
            "wholly below it"
        )
    return problems


JUDGED = "program"   # controls_mellum2.py's last control sets the other


def replay_and_probe(engine, sample, stream, n_new):
    """Serve ``sample`` (finished requests: ``prompt``, ``tokens``) once
    more from here, over the engine's pools and prefix cache as the
    window left them, with ``stream`` requests in the other slots (the
    cell's batch), ``n_new`` tokens each so that none leaves its slot,
    until each has emitted what it emitted before; then read the probes.
    Returns (:func:`probe_program`'s requests, the probes' seconds, the
    expert rows dropped)."""
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
    return (requests, time.time() - t0,
            engine.kv_stats()["moe_rows_dropped"])


def judge(requests, cfg_json, params):
    """The reference over every probed request, and all readings."""
    t0 = time.time()
    types = list(cfg_json["layer_types"])
    pad_to = -(-max(len(r["seq"]) for r in requests) // 1024) * 1024
    held_layers = (
        types.index("sliding_attention"), types.index("full_attention")
    )
    sides = [
        reference_side(params, cfg_json, r, pad_to, held_layers)
        for r in requests
    ]
    check = compare(requests, sides, types)
    check["seconds"] = time.time() - t0
    return check


# -- the run ------------------------------------------------------------------


def run(ctx):
    import jax

    # First, and before anything is built: a checkout without this model
    # fails here, at once.
    from dlrover_tpu.models import window_lm

    counts = common.count_jax_events()
    from dlrover_tpu.observability import tracing
    from dlrover_tpu.serving.fleet import FleetRouter, ThreadReplica
    from dlrover_tpu.serving.kvpool import PagedServingEngine

    devices = jax.devices()
    device = common.device_facts(devices)
    if ctx["require_tpu"]:
        common.require_tpu(devices, ctx["chips"])
    cfg_json, traffic = ctx["config"], ctx["traffic"]
    cfg = window_config(cfg_json)
    eng = cfg_json["serve_engine"]
    log = common.EventLog(ctx["out_dir"] + "/events.jsonl")
    make_params = jax.jit(
        lambda key: window_lm.init_params(cfg, key, dtype=cfg.compute_dtype)
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
        window_blocks=eng.get("window_blocks"),
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
    on_gc = serve_latent.log_full_collections(log)
    host_pauses, stop_watch = serve_conv.watch_host_pauses()
    replica = ThreadReplica("0", lambda: engine)
    router = FleetRouter([replica])
    router.start(timeout_s=60)
    live, done = {}, []

    def decoded():
        return engine.metrics.tokens.value(kind="decode")

    def submit():
        prompt, n_new = next(stream)
        req = router.submit(prompt, n_new, traffic["temperature"])
        live[req.request_id] = (req, prompt, n_new)

    def pump(until, phase, to_completion=False):
        """Hand finished requests out and refill, until ``until`` (a
        time); ``to_completion``: and then on to the next completion,
        whose time is returned (an edge of the window). A second without
        a completion while clients wait is logged with every thread's
        stack (a long prompt's 32 chunks alone are ~0.5 s)."""
        last, stalled = time.time(), False
        while True:
            past = time.time() >= until
            if past and not to_completion:
                return time.time()
            finished = router.step()
            now = time.time()
            if finished or phase == "ramp":
                if stalled:
                    log.emit("stall_end", seconds=now - last,
                             decode_tokens=decoded())
                last, stalled = now, False
            elif not stalled and now - last > 2.0:
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
                submit()
            if past and finished:
                return now
            if not finished:
                time.sleep(0.002)

    trace = dump = scopes = traced_window = None
    try:
        prefilled0 = engine.metrics.tokens.value(kind="prefill")
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
        released0 = engine.kv_stats()["window_blocks_released_total"]
        prefilled1 = engine.metrics.tokens.value(kind="prefill")
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
    peak = common.memory_peak(devices[:ctx["chips"]])
    spans = tracer.finished() if tracer is not None else []
    if dump:
        from benchmark import sparse_scopes, trace_reduce, window_scopes

        sparse_scopes.label(dump, box.get("scopes") or {})
        trace = trace_reduce.reduce(dump)
        scopes = window_scopes.reduce(dump)

    # The checks' program side. The replica's thread has stopped; the
    # groups' books are read as the window left them, then what is still
    # in the engine is cancelled. A sample of the window's requests is
    # served once more from here, over the same pools and prefix cache,
    # with stream requests in the other slots (the cell's batch), and
    # stays in its slots for the probes to read.
    t_join = time.time()
    while replica.alive() and time.time() - t_join < 120:
        time.sleep(0.05)
    if replica.alive():
        raise RuntimeError("the replica's loop did not stop")
    problems = []
    try:
        engine.check_block_invariants()
    except AssertionError as e:
        problems.append(f"block invariants at the window's end: {e}")
    kv_stats = engine.kv_stats()
    groups = kv_stats.pop("groups")
    kv_stats = {
        k: v for k, v in kv_stats.items()
        if isinstance(v, (int, float, str))
    }
    released = kv_stats["window_blocks_released_total"] - released0
    prefilled = engine.metrics.tokens.value(kind="prefill") - prefilled1
    for req in list(engine.scheduler.active()) + list(engine.scheduler.queue):
        engine.cancel(req)
    engine.run_until_idle()
    in_window = [d for d in done if d["phase"] == "window"]
    rng = np.random.default_rng((ctx["seed"], 10 ** 6))
    pool = [d for d in (in_window or done) if d["ok"] and d["tokens"]]
    order = rng.permutation(len(pool))
    longs = [i for i in order if is_long(traffic, len(pool[i]["prompt"]))]
    shorts = [i for i in order if i not in set(longs)]
    n_long = min(traffic["reference_long"], len(longs))
    picks = longs[:n_long] + shorts[:traffic["reference_sample"] - n_long]
    sample = [pool[i] for i in picks]
    out_max = traffic["output_len"]["max"]
    longest = traffic["long_prompt_len"]["max"]
    n_new = out_max + min(4 * engine.slots, eng["max_len"] - longest - out_max)
    requests, probe_s, probe_dropped = replay_and_probe(
        engine, sample, stream, n_new
    )
    del engine, router
    box.clear()
    gc.collect()  # the device memory goes to the reference

    tokens_out = sum(len(d["tokens"]) for d in in_window)
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
    if not released:
        problems.append(
            "window_blocks_released: no block of the window group was "
            "released inside the window"
        )
    if probe_dropped:
        problems.append(f"{probe_dropped} expert row(s) dropped")

    check = {}
    if requests:
        params = make_params(key)   # bit-identical: same program, same key
        check = judge(requests, cfg_json, params)
        check["probe_seconds"] = probe_s
        problems += problems_of(check, JUDGED)
    log.emit("reference", **check)
    ttfts = sorted(
        d["ttft_s"] for d in in_window if d["ttft_s"] is not None
    )
    n_long_done = sum(
        1 for d in in_window if is_long(traffic, len(d["prompt"]))
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
        # table read; benchmark/window_scopes.py made it
        "sparse_scopes": scopes,
        "traced_window": traced_window,
        "dump": dump,
        "spans": spans,
        "window": {
            "seconds": window_s, "requests": len(in_window),
            "long_requests": n_long_done,
            "tokens_out": tokens_out,
            "tokens_in": sum(len(d["prompt"]) for d in in_window),
            "prefilled_tokens": prefilled,
            "window_blocks_released": released,
            "in_flight_at_end": len(live),
            # the machine's pauses inside the window (not the program's;
            # not taken out of the rate): how many, and their seconds
            "host_pauses": len(paused), "host_pause_s": sum(paused),
        },
        "ttft_s": ttfts,
        "reference": check,
        "kv_stats": kv_stats,
        "kv_groups": groups,
        "requests": [
            {k: v for k, v in d.items() if k not in ("prompt", "tokens")}
            for d in done
        ],
        "events": common.EventLog.read(log.path),
    }
