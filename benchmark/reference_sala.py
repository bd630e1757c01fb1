"""The plain reference of ``minicpm-sala-9b``: the forward pass of lightning
linear-attention layers beside NoPE block-sparse attention layers, in
``jax.numpy`` float32 under ``jax.default_matmul_precision("highest")``,
importing nothing from ``dlrover_tpu``.

The lightning layers run the RECURRENCE, a token a step (``lax.scan``; no
chunk algebra): ``S_t = lambda S_{t-1} + k_t v_t^T``, ``o_t = S_t^T q_t``.
The sparse layers compute every compressed key from the keys as written
(``c_j = mean(k_{16 j} ... k_{16 j + 31})``), every head's softmax over the
visible ones, the group's sum, the max over the compressed keys that
overlap a block, the forced blocks, the top 64 by a SORT, and dense
attention under that mask. No kernel, cache, page or batching.

So that 66k rows fit a chip the pass goes over a sequence in BLOCKS OF
ROWS: :func:`advance` takes ``rows`` consecutive rows through every layer
and carries between calls what the mathematics carries (each lightning
layer's ``S``, each sparse layer's keys and values so far). A document is
``len / rows`` calls; a request that continues it takes one more from a
copy of the document's carry. ``low=True`` rounds every matmul's operands
to 3 bits of mantissa (``lax.reduce_precision``): the precision below
bfloat16's, for the limits' other side.

The weights are the program's tree (``models/linear_sparse_lm.py``), read
by name and cast to float32 a layer at a time.
"""

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp

LIGHTNING, SPARSE = "lightning-attn", "minicpm4"

# The family's sparse sizes where the configuration's ``assumed`` states
# none (MiniCPM4's published ``sparse_config``).
SPARSE_DEFAULTS = dict(
    kernel_size=32, kernel_stride=16, block_size=64, topk=64,
    init_blocks=1, window_size=2048, dense_len=8192,
)


def shape_of(cfg_json):
    """The sizes the reference needs, from a configuration file's
    published keys (validated) and its ``assumed.sparse_config``."""
    c = cfg_json
    for key in ("hidden_size", "intermediate_size", "num_attention_heads",
                "num_key_value_heads", "head_dim", "vocab_size",
                "mixer_types", "scale_emb", "scale_depth",
                "dim_model_base", "lightning_nh", "lightning_head_dim"):
        if key not in c:
            raise ValueError(f"configuration lacks {key}")
    types = tuple(c["mixer_types"])
    if set(types) - {LIGHTNING, SPARSE}:
        raise ValueError(f"mixer_types {types}")
    if len(types) != c["num_hidden_layers"]:
        raise ValueError("mixer_types and num_hidden_layers disagree")
    if c.get("attn_use_rope", False) or not c.get("lightning_use_rope", True):
        raise ValueError("rotation is on the lightning layers alone")
    published = c.get("published", {})
    sparse = dict(SPARSE_DEFAULTS)
    stated = c.get("assumed", {}).get("sparse_config", {})
    sparse.update({k: stated[k] for k in SPARSE_DEFAULTS if k in stated})
    return dict(
        hidden=c["hidden_size"], mlp=c["intermediate_size"],
        heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], vocab=c["vocab_size"], types=types,
        first_layer=int(c.get("first_published_layer", 0)),
        published_layers=int(
            published.get("num_hidden_layers", c["num_hidden_layers"])
        ),
        scale_emb=float(c["scale_emb"]), scale_depth=float(c["scale_depth"]),
        base=int(c["dim_model_base"]), l_heads=c["lightning_nh"],
        l_dim=c["lightning_head_dim"],
        theta=float(c.get("rope_theta", 10000.0)),
        eps=float(c.get("rms_norm_eps", 1e-6)), **sparse,
    )


def slopes_of(sh, layer):
    """``-log lambda_h`` of held layer ``layer``."""
    n = sh["l_heads"]
    base = 2.0 ** (-8.0 * (np.arange(n) + 1) / n)
    depth = 1.0 - (sh["first_layer"] + layer) / max(
        sh["published_layers"] - 1, 1
    ) + 1e-5
    return jnp.asarray(base * depth, jnp.float32)


def fp8(x):
    """3 bits of mantissa (5 of exponent), held in float32."""
    return jax.lax.reduce_precision(
        x.astype(jnp.float32), exponent_bits=5, mantissa_bits=3
    )


def _rel(got, want):
    """Row-wise relative error: ``|got - want| / |want|`` over the last
    axis."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return jnp.linalg.norm(got - want, axis=-1) / jnp.maximum(
        jnp.linalg.norm(want, axis=-1), 1e-30
    )


def _rms(x, gain, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + gain)


def _rotate(x, positions, theta):
    """Half-split rotation of ``x [rows, heads, d]`` by ``positions``."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions[:, None, None].astype(jnp.float32) * inv
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate(
        [a * jnp.cos(ang) - b * jnp.sin(ang),
         b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1,
    )


def new_carry(sh, max_rows):
    """What a sequence carries from one block of rows to the next, before
    its first row."""
    n_l = sum(t == LIGHTNING for t in sh["types"])
    n_s = len(sh["types"]) - n_l
    kv = (n_s, max_rows, sh["kv_heads"], sh["head_dim"])
    return dict(
        state=jnp.zeros((n_l, sh["l_heads"], sh["l_dim"], sh["l_dim"]),
                        jnp.float32),
        k=jnp.zeros(kv, jnp.float32), v=jnp.zeros(kv, jnp.float32),
    )


def all_ckeys(sh, keys):
    """Every compressed key of ``keys [rows, kv_heads, d]`` (``rows``
    whole strides), ``c_j`` at index ``j + 1`` (index 0 holds nothing:
    ``rows / stride`` entries, whole blocks' worth), and each one's last
    row (index 0: never reached)."""
    stride = sh["kernel_stride"]
    halves = jnp.mean(keys.reshape((-1, stride) + keys.shape[1:]), axis=1)
    c = (halves[:-1] + halves[1:]) * 0.5
    c = jnp.concatenate([jnp.zeros_like(c[:1]), c])
    j = jnp.arange(c.shape[0]) - 1
    last = jnp.where(j >= 0, stride * j + sh["kernel_size"] - 1, 2 ** 30)
    return c, last


def select_blocks(sh, q, ckeys, last, positions, n_blocks, group_sum=True):
    """Block scores and the selection of queries ``q [r, heads, d]`` at
    ``positions``: (``B [kv_heads, r, blocks]`` with the forced blocks at
    +inf and unseen ones at -inf, ``mask [kv_heads, r, blocks]``)."""
    kh, hd, bs = sh["kv_heads"], sh["head_dim"], sh["block_size"]
    g = sh["heads"] // kh
    r = q.shape[0]
    per = bs // sh["kernel_stride"]
    qg = q.reshape(r, kh, g, hd)
    scores = jnp.einsum("rkgd,jkd->kgrj", qg, ckeys) / math.sqrt(hd)
    seen = last[None, :] <= positions[:, None]             # [r, j]
    scores = jnp.where(seen[None, None], scores, -jnp.inf)
    top = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.where(
        seen[None, None],
        jnp.exp(scores - jnp.where(jnp.isfinite(top), top, 0.0)), 0.0,
    )
    total = jnp.sum(e, axis=-1, keepdims=True)
    probs = e / jnp.where(total > 0, total, 1.0)
    pooled = jnp.sum(probs, axis=1) if group_sum else probs[:, 0]
    # block b <- c_j for j in 4b - 1 ... 4b + 3: its own ``per`` entries
    # (index j + 1 = 4b ... 4b + 3) and the next block's first
    own = pooled.reshape(pooled.shape[:2] + (n_blocks, per))
    nxt = jnp.pad(own[..., 1:, 0], ((0, 0), (0, 0), (0, 1)))
    block = jnp.maximum(jnp.max(own, axis=-1), nxt)
    b = jnp.arange(n_blocks)[None, :]
    own = (positions // bs)[:, None]
    visible = b <= own
    forced = visible & (
        (b < sh["init_blocks"]) | (own - b < sh["window_size"] // bs)
    )
    block = jnp.where(forced[None], jnp.inf, block)
    block = jnp.where(visible[None], block, -jnp.inf)
    order = jnp.argsort(-block, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    mask = (rank < sh["topk"]) & visible[None]
    dense = (positions + 1 <= sh["dense_len"])[None, :, None]
    return block, jnp.where(dense, visible[None], mask)


def _advance(params, carry, tokens, start, keep_rows, sh, low, faults,
             query_rows):
    """See :func:`advance`."""
    f32 = jnp.float32
    op = fp8 if low else (lambda a: a)
    rows = tokens.shape[0]
    positions = start + jnp.arange(rows)
    depth = sh["scale_depth"] / math.sqrt(
        len(sh["types"]) if "depth_scale_by_held_layers" in faults
        else sh["published_layers"]
    )
    eps, hd, bs = sh["eps"], sh["head_dim"], sh["block_size"]
    mm = lambda a, w: jnp.einsum(  # noqa: E731
        "rd,d...->r...", op(a), op(w.astype(f32))
    )
    x = sh["scale_emb"] * jnp.take(params["embed"], tokens, axis=0).astype(f32)
    out = {"gated": [], "state_rows": [], "k": [], "v": [], "ckeys": [],
           "scores": [], "mask": []}
    state, k_all, v_all = carry["state"], carry["k"], carry["v"]
    max_rows = k_all.shape[1]
    n_blocks = max_rows // bs
    at_l = at_s = 0
    pl = params["layers"]
    for layer, kind in enumerate(sh["types"]):
        u = _rms(x, pl["mix_norm"][layer], eps)
        if kind == LIGHTNING:
            pm = {n: a[at_l] for n, a in params["lightning"].items()}
            q = _rms(mm(u, pm["wq"]), pm["q_norm"], eps)
            k = _rms(mm(u, pm["wk"]), pm["k_norm"], eps)
            v = mm(u, pm["wv"])
            if "rope_skipped_on_lightning" not in faults:
                q = _rotate(q, positions, sh["theta"])
                k = _rotate(k, positions, sh["theta"])
            q = q / math.sqrt(sh["l_dim"])
            slopes = slopes_of(
                dict(sh, first_layer=0) if "decay_layer_index_held" in faults
                else sh, layer,
            )
            lam = jnp.exp(-slopes)[:, None, None]
            if "decay_dropped" in faults:
                lam = jnp.ones_like(lam)

            def step(carry, row):
                s, saved = carry
                q_t, k_t, v_t, i = row
                s = lam * s + op(k_t)[:, :, None] * op(v_t)[:, None, :]
                if "state_in_bf16" in faults:
                    s = s.astype(jnp.bfloat16).astype(f32)
                saved = jnp.where(
                    (i == keep_rows)[:, None, None, None], s[None], saved
                )
                return (s, saved), jnp.einsum("hkv,hk->hv", op(s), op(q_t))

            (s_end, saved), o = jax.lax.scan(
                step,
                (state[at_l],
                 jnp.zeros((keep_rows.shape[0],) + state.shape[1:], f32)),
                (q, k, v, jnp.arange(rows)),
            )
            out["state_rows"].append(saved)
            state = state.at[at_l].set(s_end)
            flat = _rms(o.reshape(rows, -1), pm["o_norm"], eps)
            gated = flat * jax.nn.sigmoid(mm(u, pm["wg"]))
            y = jnp.einsum(
                "rhk,hkd->rd", op(gated.reshape(rows, sh["l_heads"], -1)),
                op(pm["wo"].astype(f32)),
            )
            at_l += 1
        else:
            ps = {n: a[at_s] for n, a in params["sparse"].items()}
            q, k, v = mm(u, ps["wq"]), mm(u, ps["wk"]), mm(u, ps["wv"])
            if "rope_on_sparse_layers" in faults:
                q = _rotate(q, positions, sh["theta"])
                k = _rotate(k, positions, sh["theta"])
            k_all = jax.lax.dynamic_update_slice(
                k_all, k[None], (at_s, start, 0, 0)
            )
            v_all = jax.lax.dynamic_update_slice(
                v_all, v[None], (at_s, start, 0, 0)
            )
            keys, values = k_all[at_s], v_all[at_s]
            ckeys, last = all_ckeys(sh, keys)
            g = sh["heads"] // sh["kv_heads"]

            def attend(args):
                q_b, pos_b = args                    # [qr, heads, d], [qr]
                score, mask = select_blocks(
                    sh, op(q_b), op(ckeys), last, pos_b, n_blocks,
                    group_sum="group_sum_skipped" not in faults,
                )
                if "forced_blocks_dropped" in faults:
                    score = jnp.where(jnp.isposinf(score), 0.0, score)
                    order = jnp.argsort(-score, axis=-1, stable=True)
                    rank = jnp.argsort(order, axis=-1, stable=True)
                    mask = jnp.where(
                        (pos_b + 1 <= sh["dense_len"])[None, :, None], mask,
                        (rank < sh["topk"]) & jnp.isfinite(score),
                    )
                if "topk_63" in faults:
                    order = jnp.argsort(-score, axis=-1, stable=True)
                    rank = jnp.argsort(order, axis=-1, stable=True)
                    mask = jnp.where(
                        (pos_b + 1 <= sh["dense_len"])[None, :, None], mask,
                        mask & (rank < sh["topk"] - 1),
                    )
                seen = jnp.repeat(mask, bs, axis=-1)
                seen = seen & (
                    jnp.arange(max_rows)[None, :] <= pos_b[:, None]
                )[None]
                logits = jnp.einsum(
                    "rkgd,tkd->kgrt",
                    op(q_b).reshape(-1, sh["kv_heads"], g, hd), op(keys),
                ) / math.sqrt(hd)
                probs = jax.nn.softmax(
                    jnp.where(seen[:, None], logits, -jnp.inf), axis=-1
                )
                o = jnp.einsum("kgrt,tkd->rkgd", op(probs), op(values))
                return o.reshape(q_b.shape), score, mask

            qr = min(query_rows, rows)
            o, score, mask = jax.lax.map(
                attend,
                (q.reshape(rows // qr, qr, sh["heads"], hd),
                 positions.reshape(rows // qr, qr)),
            )
            o = o.reshape(rows, -1)
            if rows <= 1024:
                out["scores"].append(
                    jnp.moveaxis(score, 0, 1).reshape(sh["kv_heads"], rows, -1)
                )
                out["mask"].append(
                    jnp.moveaxis(mask, 0, 1).reshape(sh["kv_heads"], rows, -1)
                )
                out["k"].append(k)
                out["v"].append(v)
                out["ckeys"].append(ckeys)
            gated = o * jax.nn.sigmoid(mm(u, ps["wg"]))
            y = jnp.einsum(
                "rhk,hkd->rd", op(gated.reshape(rows, sh["heads"], hd)),
                op(ps["wo"].astype(f32)),
            )
            at_s += 1
        if rows <= 1024:
            out["gated"].append(gated)
        x = x + depth * y
        h = _rms(x, pl["ffn_norm"][layer], eps)
        gu = mm(h, pl["w_gu"][layer])
        f = sh["mlp"]
        x = x + depth * mm(jax.nn.silu(gu[:, :f]) * gu[:, f:],
                           pl["w_down"][layer])
    h = _rms(x, params["final_norm"], eps)
    if "logit_scale_dropped" not in faults:
        h = h * (sh["base"] / sh["hidden"])
    out["logits_of"] = h
    return dict(state=state, k=k_all, v=v_all), out


@functools.lru_cache(maxsize=None)
def _program(sh_items, low, faults, query_rows):
    sh = dict(sh_items)

    def run(params, carry, tokens, start, keep_rows):
        with jax.default_matmul_precision("highest"):
            return _advance(params, carry, tokens, start, keep_rows, sh, low,
                            faults, query_rows)

    return jax.jit(run, donate_argnums=(1,))


def advance(params, carry, tokens, start, sh, low=False, faults=(),
            query_rows=256, keep_rows=(-1, -1)):
    """``tokens [rows]`` (rows ``start ...`` of the sequence ``carry``
    holds so far; ``start`` and ``rows`` whole blocks' worth... of
    strides) through every layer: ``(carry', out)``. ``carry`` is
    consumed. With at most 1,024 rows ``out`` holds, a layer, what the
    checks read: ``gated`` (each mixer's output before ``W_o``),
    ``state_rows`` (a lightning layer's ``S`` after rows ``keep_rows`` of
    the call, ``[len(keep_rows), heads, d, d]``: in every call), ``k``,
    ``v``, ``ckeys``, ``scores`` and ``mask`` (a sparse layer's, by row);
    and ``logits_of``, the head's input ``[rows, hidden]``
    (:func:`logits_at`). ``faults``: names of ``controls_sala.py``."""
    key = tuple(sorted(
        (k, v) for k, v in sh.items()
    ))
    return _program(key, bool(low), tuple(faults), int(query_rows))(
        params, carry, jnp.asarray(tokens, jnp.int32),
        jnp.asarray(start, jnp.int32), jnp.asarray(keep_rows, jnp.int32),
    )


def logits_at(params, head_in, rows):
    """The head over ``rows`` of ``head_in`` (:func:`advance`'s
    ``logits_of``): float32 ``[len(rows), vocab]``."""
    with jax.default_matmul_precision("highest"):
        return jnp.einsum(
            "rd,dv->rv", head_in[rows], params["head"].astype(jnp.float32)
        )


def forward(params, tokens, cfg_json, low=False, faults=(), rows=None):
    """A whole sequence ``tokens [n]`` from nothing, in blocks of
    ``rows`` (None: one block): float32 logits ``[n, vocab]``. For small
    sizes (the CPU tests)."""
    sh = shape_of(cfg_json)
    n = len(tokens)
    unit = sh["block_size"]
    rows = rows or -(-n // unit) * unit
    total = -(-n // rows) * rows
    padded = np.zeros(total, np.int32)
    padded[:n] = np.asarray(tokens)
    carry = new_carry(sh, total + unit)
    outs = []
    for start in range(0, total, rows):
        carry, out = advance(
            params, carry, padded[start:start + rows], start, sh, low=low,
            faults=faults, query_rows=rows,
        )
        outs.append(out["logits_of"])
    return logits_at(params, jnp.concatenate(outs), jnp.arange(n))
