"""Mixture-of-Experts MLP: GShard einsum dispatch and a dropless
grouped-matmul path.

Two implementations behind one surface:

- **gshard** (:func:`moe_mlp`): one-hot einsum dispatch with per-expert
  capacity; over-capacity tokens are dropped (residual carries them).
  Expert-parallel by construction — the dispatch/combine einsums
  contract token axes (sharded over dp/ep) against expert axes (ep), so
  GSPMD lowers the resharding to all-to-all over ICI. Static shapes,
  works under any mesh.
- **dropless** (:func:`moe_mlp_dropless`): megablox-style — sort token
  copies by expert and run grouped (ragged) matmuls
  (``jax.experimental.pallas.ops.tpu.megablox.gmm``), so NO token is
  ever dropped and no capacity/one-hot FLOPs are wasted. Group sizes
  are data-dependent, which GSPMD cannot shard over ``ep`` — this path
  is for meshes with ep == 1 (each device holds all experts; dp/tp as
  usual). ``models/llama.mlp_block`` picks it automatically on
  single-device meshes only (auto-selection under multi-device meshes
  stays with the GSPMD-proven gshard path).
- **dropless under ep** (:func:`moe_mlp_dropless_ep`): the dropless
  property survives expert scaling via ``shard_map`` — each ep shard
  routes its local tokens, ships them to their experts' shards with
  ``jax.lax.ragged_all_to_all`` (sized by the actual routing, no
  capacity bound), runs the per-shard grouped matmuls, and ships
  results back through the reverse ragged exchange.

The reference has no MoE/EP support (SURVEY.md section 2.9: "absent") —
this is parity-plus for the TPU build.
"""

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.parallel.sharding import with_logical_constraint


class MoEMetrics(NamedTuple):
    aux_loss: jnp.ndarray     # load-balance loss (scalar)
    router_z_loss: jnp.ndarray
    dropped_fraction: jnp.ndarray


def expert_capacity(
    seq: int, n_experts: int, top_k: int, capacity_factor: float
) -> int:
    cap = int(seq * top_k * capacity_factor / n_experts)
    return max(cap, 1)


def moe_mlp(
    x,
    router_w,     # [embed, experts]
    w_gate,       # [experts, embed, mlp]
    w_up,         # [experts, embed, mlp]
    w_down,       # [experts, mlp, embed]
    top_k: int = 2,
    capacity_factor: float = 1.25,
):
    """x: [batch, seq, embed] -> (out, MoEMetrics).

    Groups = batch rows (tokens within one sequence compete for expert
    capacity). Over-capacity tokens are dropped (residual carries them).
    """
    b, s, d = x.shape
    e = router_w.shape[-1]
    cap = expert_capacity(s, e, top_k, capacity_factor)

    router_logits = jnp.einsum(
        "gsd,de->gse", x.astype(jnp.float32), router_w.astype(jnp.float32)
    )
    probs = jax.nn.softmax(router_logits, axis=-1)

    # --- iterative top-k one-hot assignment with capacity ---------------
    combine = jnp.zeros((b, s, e, cap), dtype=jnp.float32)
    remaining = probs
    # position counters per expert, advanced between the k rounds
    used = jnp.zeros((b, e), dtype=jnp.int32)
    dropped = 0.0
    for _ in range(top_k):
        idx = jnp.argmax(remaining, axis=-1)                   # [g, s]
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)     # [g, s, e]
        gate = jnp.sum(remaining * onehot, axis=-1)            # [g, s]
        remaining = remaining * (1.0 - onehot)
        # capacity slot for each token in its chosen expert
        pos_in_expert = (
            jnp.cumsum(onehot, axis=1) - onehot
        ) + used[:, None, :]                                   # [g, s, e]
        pos = jnp.einsum("gse,gse->gs", pos_in_expert, onehot).astype(
            jnp.int32
        )
        fits = pos < cap
        dropped = dropped + jnp.mean(1.0 - fits)
        gate = gate * fits
        pos_onehot = jax.nn.one_hot(
            jnp.where(fits, pos, cap), cap, dtype=jnp.float32
        )  # out-of-range -> all-zero row
        combine = combine + (
            gate[..., None, None] * onehot[..., None] * pos_onehot[:, :, None, :]
        )
        used = used + jnp.sum(onehot * fits[..., None], axis=1).astype(jnp.int32)

    # renormalize the kept gates so they sum to 1 per token (when any kept)
    denom = jnp.sum(combine, axis=(-2, -1), keepdims=True)
    combine = combine / jnp.maximum(denom, 1e-9)
    dispatch = (combine > 0.0).astype(x.dtype)
    combine = combine.astype(x.dtype)

    # --- dispatch -> expert compute -> combine --------------------------
    # [e, g, cap, d]: token shards (dp/ep) contract into expert shards (ep)
    expert_in = jnp.einsum("gsec,gsd->egcd", dispatch, x)
    expert_in = with_logical_constraint(
        expert_in, ("expert", "batch", "capacity", "embed")
    )
    h = jnp.einsum("egcd,edf->egcf", expert_in, w_gate.astype(x.dtype))
    u = jnp.einsum("egcd,edf->egcf", expert_in, w_up.astype(x.dtype))
    h = jax.nn.silu(h) * u
    h = with_logical_constraint(h, ("expert", "batch", "capacity", "mlp"))
    expert_out = jnp.einsum("egcf,efd->egcd", h, w_down.astype(x.dtype))
    # Without this constraint GSPMD infers an (e, d)-sharded layout from
    # w_down and then can't reshard the backward cotangent (which
    # arrives batch-sharded from dout) efficiently — involuntary full
    # rematerialization on the ep mesh.
    expert_out = with_logical_constraint(
        expert_out, ("expert", "batch", "capacity", "embed")
    )
    out = jnp.einsum("egcd,gsec->gsd", expert_out, combine)
    out = with_logical_constraint(out, ("batch", "seq", "embed"))

    # --- router losses (shared with the dropless path) -------------------
    aux, z = _router_losses(router_logits, probs)
    metrics = MoEMetrics(
        aux_loss=aux,
        router_z_loss=z,
        dropped_fraction=dropped / top_k,
    )
    return out, metrics


# ---------------------------------------------------------------------------
# Dropless path: sort-by-expert + grouped matmul (megablox gmm)
# ---------------------------------------------------------------------------


def _router_losses(router_logits, probs):
    e = probs.shape[-1]
    top1 = jax.nn.one_hot(jnp.argmax(probs, -1), e, dtype=jnp.float32)
    frac_tokens = jnp.mean(top1, axis=tuple(range(top1.ndim - 1)))
    mean_probs = jnp.mean(probs, axis=tuple(range(probs.ndim - 1)))
    aux = e * jnp.sum(frac_tokens * mean_probs)
    z = jnp.mean(
        jax.scipy.special.logsumexp(router_logits, axis=-1) ** 2
    )
    return aux, z


def _tile(dim: int, cap: int = 512) -> int:
    """Largest power-of-two divisor of ``dim``, capped — gmm requires
    every dimension to be tile-divisible."""
    t = 1
    while t * 2 <= min(dim, cap) and dim % (t * 2) == 0:
        t *= 2
    return t


# A row tile is never under 128 rows, and 128 rows on a weight block are
# 128 FLOP a weight byte where a v5e does ~240 in the time it moves one
# (197 TFLOP/s over 819 GB/s): a group with fewer rows than that waits
# for its weights, whatever the tile.
ROW_TILE = 128

# What a Pallas TPU kernel may hold in VMEM unless it asks for more
# (megablox asks for nothing), less half a MiB for the compiler's own.
_VMEM_BUDGET = 16 * 2**20 - 2**19


def _weight_block(even: int, tm: int, k: int, n: int, itemsize: int):
    """``(tk, tn)`` of the ``[k, n]`` weight block a grouped matmul
    streams a grid step, from shapes alone.

    ``even`` is the rows an evenly loaded expert gets. From ``ROW_TILE``
    up a visit of a group keeps the MXU busy for as long as its weights
    take to arrive, and the blocks are :func:`_tile`'s (powers of two up
    to 512: the trained shapes, letter for letter). Below it every visit
    waits for its weights. ``gmm``'s grid is ``(n / tn, visits, k /
    tk)``: a step of a 0.25-0.5 MB block costs its DMA and ~0.23 us
    more, a quarter to a third of it, and from ~2 MB a block up little
    but the DMA is left (PERF.md section 6, PR 45). There the block is
    the LARGEST whose dimensions are multiples of 128 dividing ``k`` and
    ``n`` (not powers of two: 1,792 and 896 divide 3,584) that fits
    VMEM, whole rows of the matrix preferred among equals (one
    contiguous run a block). What has to fit: two weight blocks, two row
    blocks, two output blocks and one float32 accumulator, the forward's
    ``[tm, tn]`` or the ``[tk, tn]`` of the weights' gradient (``gmm``'s
    pullback runs ``tgmm`` under the forward's tiling: a block that only
    the forward could hold would be a backward that does not compile).
    A dimension with no such divisor keeps ``_tile``'s."""
    if even >= ROW_TILE:
        return _tile(k), _tile(n)

    def cuts(dim):
        return [
            t for t in range(128, dim + 1, 128) if dim % t == 0
        ] or [_tile(dim)]

    def vmem(tk, tn):
        return (
            2 * tk * tn * itemsize + 2 * tm * (tk + tn) * itemsize
            + 4 * max(tm, tk) * tn
        )

    # never empty: a dimension's smallest cut is 128 or less
    fits = [
        (tk, tn) for tk in cuts(k) for tn in cuts(n)
        if vmem(tk, tn) <= _VMEM_BUDGET
    ]
    return max(fits, key=lambda b: (b[0] * b[1], b[1]))


# FLOP a v5e's MXU does in the time its HBM moves a byte (197 TFLOP/s over
# 819 GB/s): what turns the lay-out matmul of :func:`_aligned_rows` into
# the bytes it is weighed against.
_FLOP_PER_BYTE = 240


def _aligned_rows(even: int, tm: int, rows: int, held: int, n: int, d: int,
                  f: int, itemsize: int) -> bool:
    """Whether a call's sorted rows are laid out EXPERT-ALIGNED (every
    held expert's rows from a row-tile boundary,
    :func:`_share_rows_aligned`) or packed end to end
    (:func:`_share_rows`), from shapes alone.

    ``gmm`` visits a (group, row tile) pair once, and below ``ROW_TILE``
    rows a group a visit costs what streaming the group's weights costs
    (:func:`_weight_block`). Packed, ``rows`` sorted rows have ``tiles_m
    - 1`` tile edges and nearly each falls inside a group, whose weights
    then go through twice: ``(tiles_m - 1) * 3 d f`` weight elements
    read for nothing. Aligned, no group straddles, and what that costs:
    the buffer grows by ``held * tm`` rows (a group's last tile is part
    empty), which the matmuls' row blocks and the activation pass over,
    ``held * tm * (d + 3 f)`` elements; and the ``n`` tokens reach their
    rows by a one-hot matmul (:func:`_lay_rows`), ``2 * buffer rows * n
    * d`` FLOP. Aligned when the bytes saved outweigh the bytes added and
    the bytes the HBM would move while the MXU lays the rows out. On the
    chip (PERF.md section 6, PR 52) the quotient orders the served
    cells' shapes as the layer's time does: a chunk of 512 tokens x
    top-8 over 64 experts 1.9 (19 % faster aligned), x top-4 over 64
    1.4 and 1.2 (12 and 9 %), x top-8 over 128 experts 0.9 (1 %
    slower: twice the rows for the same edges), a decode step 0.14 or
    0 (one or two tiles: 5 % slower); the trained shapes have ``even``
    258 / 513 and are not asked."""
    if even >= ROW_TILE or rows < 2 * tm:
        return False
    saved = (rows // tm - 1) * 3 * d * f * itemsize
    added = held * tm * (d + 3 * f) * itemsize
    lay = 2 * (rows + held * tm) * n * d / _FLOP_PER_BYTE
    return saved >= added + lay


def weight_visits(group_sizes, tm: int, aligned: bool):
    """(group, row tile) pairs a grouped matmul visits, each one pass of
    the group's weights: ``gmm``'s own count (``make_group_metadata``'s
    ``num_tiles``). Packed end to end a group is visited once a tile its
    rows touch; ``aligned`` (each group from a tile boundary) once a
    tile it fills, ``ceil(rows / tm)``. ``group_sizes`` are the rows a
    group got, unrounded, either way."""
    if aligned:
        return jnp.sum(-(-group_sizes // tm)).astype(jnp.int32)
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    return jnp.sum(jnp.where(
        group_sizes > 0, (ends - 1) // tm - starts // tm + 1, 0
    )).astype(jnp.int32)


# The dispatch/combine gathers are permutation-shaped, and XLA's
# transpose of a gather is a SCATTER(-add) — slow on TPU and the bulk
# of the dropless path's overhead in the backward. Both inverses are
# already in hand (argsort byproducts), so custom VJPs express every
# backward as another gather: zero scatters in fwd+bwd.


@jax.custom_vjp
def _permute_rows(x, perm, inv_perm):
    """x[perm] where ``inv_perm`` is perm's inverse permutation."""
    return jnp.take(x, perm, axis=0)


def _permute_fwd(x, perm, inv_perm):
    return jnp.take(x, perm, axis=0), (perm, inv_perm)


def _permute_bwd(res, g):
    perm, inv_perm = res
    return (
        jnp.take(g, inv_perm, axis=0),
        np.zeros(perm.shape, jax.dtypes.float0),
        np.zeros(inv_perm.shape, jax.dtypes.float0),
    )


_permute_rows.defvjp(_permute_fwd, _permute_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gather_dispatch(xf, order, inv_order, top_k):
    """xs[i] = xf[order[i] // top_k] (each token duplicated top_k
    times, sorted by expert). Backward: unsort to token-major and
    reduce the k copies densely — no scatter."""
    return jnp.take(xf, order // top_k, axis=0)


def _dispatch_fwd(xf, order, inv_order, top_k):
    return (
        jnp.take(xf, order // top_k, axis=0),
        (order, inv_order, xf.shape[0]),
    )


def _dispatch_bwd(top_k, res, g):
    order, inv_order, n = res
    d = g.shape[-1]
    gt = jnp.take(g, inv_order, axis=0).reshape(n, top_k, d)
    return (
        jnp.sum(gt.astype(jnp.float32), axis=1).astype(g.dtype),
        np.zeros(order.shape, jax.dtypes.float0),
        np.zeros(inv_order.shape, jax.dtypes.float0),
    )


_gather_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def _dispatch_impl() -> str:
    """"gmm" (megablox grouped matmuls around XLA gathers, the default)
    | "fused" (ops/moe_dispatch grouped kernel) for the dropless expert
    compute. DLROVER_TPU_MOE_DISPATCH picks; typos warn once and fall
    back to "gmm". "fused" was the default from PR 14 to PR 21 without
    ever having compiled for a chip; repaired, its first on-chip
    reading is 1.4x SLOWER than gmm (ops/moe_dispatch.py STATUS), so
    gmm is the default until a benchmark says otherwise."""
    from dlrover_tpu.common.env_utils import resolve_env_choice

    return resolve_env_choice(
        "DLROVER_TPU_MOE_DISPATCH", ("fused", "gmm"), "gmm"
    )


def _dropless_core(
    xf, router_w, w_gate, w_up, w_down, top_k, interpret, dispatch=None
):
    """Sorted grouped-matmul expert compute over flat tokens [n, d] ->
    out [n, d] f32. Local to one device (all experts resident).
    ``dispatch``: "gmm" is the megablox path with XLA gathers; "fused"
    routes through the ops/moe_dispatch Pallas kernel (gather→GEMM→
    scatter in one pass, custom VJP on the same permutation)."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    n, d = xf.shape
    e = router_w.shape[-1]
    m = n * top_k
    dispatch = dispatch or _dispatch_impl()

    router_logits = jnp.einsum(
        "nd,de->ne", xf.astype(jnp.float32),
        router_w.astype(jnp.float32),
    )
    probs = jax.nn.softmax(router_logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, top_k)        # [n, k]
    gates = gates / jnp.maximum(
        jnp.sum(gates, axis=-1, keepdims=True), 1e-9
    )

    flat_expert = experts.reshape(m)

    if dispatch == "fused":
        from dlrover_tpu.ops import moe_dispatch as md

        cdt = xf.dtype
        tm = md.default_tile_m(m)
        row_ids, dest_ids, tile_expert = md.build_dispatch_layout(
            flat_expert, e, tm, top_k
        )
        w_gu = jnp.concatenate(
            [w_gate.astype(cdt), w_up.astype(cdt)], axis=-1
        )
        out_tok = md.grouped_ffn(
            xf, w_gu, w_down.astype(cdt), row_ids, dest_ids,
            tile_expert, m, top_k, tm, interpret,
        )
        return jnp.sum(
            out_tok.reshape(n, top_k, d).astype(jnp.float32)
            * gates[:, :, None],
            axis=1,
        )

    order = jnp.argsort(flat_expert, stable=True)       # [m]
    inv_order = jnp.argsort(order)
    xs = _gather_dispatch(xf, order, inv_order, top_k)  # [m, d] sorted
    group_sizes = jnp.bincount(flat_expert, length=e).astype(jnp.int32)

    # gmm needs tile-divisible dims; pad the row dim with zero rows
    # assigned to the LAST group (sorted order keeps them contiguous at
    # the end) and slice them off before the combine.
    f = w_gate.shape[-1]
    m_pad = ((m + 127) // 128 * 128) if m >= 128 else m
    if m_pad != m:
        xs = jnp.pad(xs, ((0, m_pad - m), (0, 0)))
        group_sizes = group_sizes.at[e - 1].add(m_pad - m)
    cdt = xf.dtype
    # gate and up share lhs rows and group structure: ONE fused gmm over
    # the concatenated [e, d, 2f] weights reads the sorted tokens once
    # (half the lhs HBM traffic and kernel launches of separate calls).
    w_gu = jnp.concatenate(
        [w_gate.astype(cdt), w_up.astype(cdt)], axis=-1
    )
    hu = gmm(
        xs, w_gu, group_sizes, interpret=interpret,
        tiling=(_tile(m_pad), _tile(d), _tile(2 * f)),
    )
    a = (jax.nn.silu(hu[:, :f]) * hu[:, f:]).astype(cdt)
    out_sorted = gmm(
        a, w_down.astype(cdt), group_sizes, interpret=interpret,
        tiling=(_tile(m_pad), _tile(f), _tile(d)),
    )[:m]                                               # [m, d] f32

    # Combine WITHOUT a [n, d] scatter-add (slow on TPU): invert the
    # sort permutation (int sort + [m, d] gather), then the k copies of
    # each token sit contiguously — a dense reshape-sum finishes it.
    out_tok_major = _permute_rows(out_sorted, inv_order, order)
    return jnp.sum(
        out_tok_major.reshape(n, top_k, d)
        * gates.astype(out_sorted.dtype)[:, :, None],
        axis=1,
    )


def _global_router_metrics(x, router_w):
    logits = jnp.einsum(
        "bsd,de->bse", x.astype(jnp.float32),
        router_w.astype(jnp.float32),
    )
    aux, z = _router_losses(logits, jax.nn.softmax(logits, axis=-1))
    return MoEMetrics(
        aux_loss=aux,
        router_z_loss=z,
        dropped_fraction=jnp.zeros((), jnp.float32),
    )


def moe_mlp_dropless(
    x,
    router_w,     # [embed, experts]
    w_gate,       # [experts, embed, mlp]
    w_up,         # [experts, embed, mlp]
    w_down,       # [experts, mlp, embed]
    top_k: int = 2,
    interpret=None,
    dispatch=None,
):
    """x: [batch, seq, embed] -> (out, MoEMetrics). Zero dropped tokens.

    Token copies are stably sorted by their routed expert; the expert
    matmuls then run as grouped matmuls over the sorted rows (megablox
    gmm: contiguous per-expert row groups hit the MXU with no one-hot
    dispatch algebra and no capacity padding). Single-device math —
    multi-device meshes go through :func:`moe_mlp_dropless_sharded`
    (ep == 1) or :func:`moe_mlp_dropless_ep` (ep > 1)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, s, d = x.shape
    out = _dropless_core(
        x.reshape(b * s, d), router_w, w_gate, w_up, w_down,
        top_k, interpret, dispatch=dispatch,
    )
    out = with_logical_constraint(
        out.astype(x.dtype).reshape(b, s, d), ("batch", "seq", "embed")
    )
    return out, _global_router_metrics(x, router_w)


def moe_mlp_dropless_sharded(
    x,
    router_w,
    w_gate,
    w_up,
    w_down,
    mesh,
    top_k: int = 2,
    interpret=None,
    dispatch=None,
):
    """Dropless MoE on a multi-device mesh WITHOUT expert parallelism:
    every device holds all experts, so each shard routes and computes
    its local tokens independently — a ``shard_map`` island over the
    batch axes with replicated weights. (The global-argsort single-
    device path has data-dependent group sizes GSPMD cannot lower
    soundly; this per-shard form sidesteps that entirely.)"""
    from jax.sharding import PartitionSpec as P

    from dlrover_tpu.parallel.sharding import logical_to_spec

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    d = x.shape[-1]

    def body(xl, rw, wg, wu, wd):
        bl, sl, _ = xl.shape
        out = _dropless_core(
            xl.reshape(bl * sl, d), rw, wg, wu, wd, top_k, interpret,
            dispatch=dispatch,
        )
        return out.astype(xl.dtype).reshape(bl, sl, d)

    xspec = logical_to_spec(("batch", None, None))
    out = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(xspec, P(), P(), P(), P()),
        out_specs=xspec,
        check_vma=False,
    )(x, router_w, w_gate, w_up, w_down)
    out = with_logical_constraint(out, ("batch", "seq", "embed"))
    return out, _global_router_metrics(x, router_w)


# ---------------------------------------------------------------------------
# Dropless under expert parallelism: shard_map + ragged all-to-all
# ---------------------------------------------------------------------------


def _exchange(rows, sizes_mat, me, n_shards, axis_name, reverse=False):
    """One ragged all-to-all hop of ``rows`` ([cap, d], per-shard).

    ``sizes_mat[src, dst]`` — rows src ships to dst — is known on every
    shard, so each shard derives all four offset/size vectors locally:
    chunks live densely in SOURCE-major order on the sender and land in
    SOURCE-major order on the receiver. ``reverse=True`` runs the
    mirrored exchange (processed rows travel home).

    On TPU this is ``jax.lax.ragged_all_to_all`` (wire bytes sized by
    the actual routing). XLA:CPU does not implement that opcode, so the
    virtual-mesh test/dryrun path takes a semantically identical dense
    ``all_to_all`` of capacity-padded chunks instead."""
    if reverse:
        sizes_mat = sizes_mat.T
    send = sizes_mat[me]                                   # [n_shards]
    recv = sizes_mat[:, me]
    input_offsets = jnp.cumsum(send) - send
    # Where MY chunk starts on each receiver: after every earlier
    # source's chunk for that receiver.
    col_excl = jnp.cumsum(sizes_mat, axis=0) - sizes_mat   # [src, dst]
    output_offsets = col_excl[me]
    if jax.default_backend() == "tpu":
        return jax.lax.ragged_all_to_all(
            rows,
            jnp.zeros_like(rows),
            input_offsets.astype(jnp.int32),
            send.astype(jnp.int32),
            output_offsets.astype(jnp.int32),
            recv.astype(jnp.int32),
            axis_name=axis_name,
        )
    cap, d = rows.shape
    lane = jnp.arange(cap)
    # Pack: slot j carries my chunk for peer j (zero-padded).
    src_idx = jnp.clip(input_offsets[:, None] + lane[None, :], 0, cap - 1)
    valid = lane[None, :] < send[:, None]
    packed = jnp.where(
        valid[..., None], jnp.take(rows, src_idx, axis=0), 0
    )                                                      # [ep, cap, d]
    arrived = jax.lax.all_to_all(packed, axis_name, 0, 0)  # slot i: from i
    # Unpack into the contiguous source-major receive layout.
    pos = col_excl[:, me][:, None] + lane[None, :]         # [src, cap]
    pos = jnp.where(lane[None, :] < recv[:, None], pos, cap)
    return (
        jnp.zeros_like(rows)
        .at[pos.reshape(-1)]
        .set(arrived.reshape(-1, d), mode="drop")
    )


def moe_mlp_dropless_ep(
    x,
    router_w,
    w_gate,       # [experts, embed, mlp] — expert dim sharded over ep
    w_up,
    w_down,
    mesh,
    top_k: int = 2,
    axis_name: str = "ep",
    interpret=None,
    dispatch=None,
):
    """Dropless MoE that SURVIVES expert parallelism (the ep==1-only
    restriction of :func:`moe_mlp_dropless` lifted).

    Per ep shard, under ``shard_map``: route local tokens, sort the
    token copies by expert, ship each shard's copies to the shards
    owning their experts via ``jax.lax.ragged_all_to_all`` (buffers
    sized by the ACTUAL routing — no capacity bound, nothing dropped),
    run the fused grouped matmuls over the received rows, and ship the
    results back through the mirrored exchange. The all-to-all size
    matrix is replicated via an all_gather of per-shard counts, so all
    offset bookkeeping is local arithmetic.

    Worst-case receive buffer is ``top_k * n_global`` rows (all tokens
    routed to one shard) — the price of true droplessness; the gshard
    path bounds memory with capacity instead (and drops).
    """
    from jax.sharding import PartitionSpec as P

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, s, d = x.shape
    e = router_w.shape[-1]
    f = w_gate.shape[-1]
    ep = dict(mesh.shape).get(axis_name, 1)
    if e % ep:
        raise ValueError(f"{e} experts not divisible by ep={ep}")
    e_loc = e // ep
    cdt = x.dtype

    # Router losses from the (GSPMD-sharded) global logits — the tiny
    # [n, e] matmul is recomputed inside the shards for routing.
    logits_global = jnp.einsum(
        "bsd,de->bse", x.astype(jnp.float32),
        router_w.astype(jnp.float32),
    )
    aux, z = _router_losses(
        logits_global, jax.nn.softmax(logits_global, axis=-1)
    )

    from dlrover_tpu.parallel.sharding import logical_to_spec

    xspec = logical_to_spec(("batch", None, None))
    # Worst case for one ep shard: every copy in its ep row lands on it
    # (batch is sharded over e.g. dcn x dp x ep; the exchange stays
    # within one row of the non-ep batch shards, so other rows' tokens
    # can never arrive).
    batch_axes = xspec[0]
    axes = (
        (batch_axes,) if isinstance(batch_axes, str)
        else tuple(batch_axes or ())
    )
    other = 1
    for a in axes:
        if a != axis_name:
            other *= dict(mesh.shape).get(a, 1)
    cap_rows = (b // max(other, 1)) * s * top_k
    cap_rows = (cap_rows + 127) // 128 * 128

    def body(xl, rw, wg, wu, wd):
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        me = jax.lax.axis_index(axis_name)
        bl, sl, _ = xl.shape
        n_loc = bl * sl
        m_loc = n_loc * top_k
        xf = xl.reshape(n_loc, d)

        logits = jnp.einsum(
            "nd,de->ne", xf.astype(jnp.float32), rw.astype(jnp.float32)
        )
        probs = jax.nn.softmax(logits, axis=-1)
        gates, experts = jax.lax.top_k(probs, top_k)       # [n_loc, k]
        gates = gates / jnp.maximum(
            jnp.sum(gates, axis=-1, keepdims=True), 1e-9
        )

        flat_expert = experts.reshape(m_loc)
        order = jnp.argsort(flat_expert, stable=True)
        inv_order = jnp.argsort(order)
        xs = _gather_dispatch(xf, order, inv_order, top_k)  # [m_loc, d]
        counts = jnp.bincount(flat_expert, length=e)       # [e]

        # Replicate the full src x dst size matrix and per-(src, local
        # expert) counts: every shard then derives offsets locally.
        counts_all = jax.lax.all_gather(counts, axis_name)  # [ep, e]
        sizes_mat = counts_all.reshape(ep, ep, e_loc).sum(-1)

        xs_pad = jnp.zeros((cap_rows, d), cdt).at[:m_loc].set(
            xs.astype(cdt)
        )
        recv = _exchange(xs_pad, sizes_mat, me, ep, axis_name)

        # Received rows are (src, expert)-major; regroup expert-major
        # for gmm. Row expert ids reconstruct from the counts matrix
        # (data-dependent lengths -> repeat with a static total).
        my_counts = jax.lax.dynamic_slice_in_dim(
            counts_all, me * e_loc, e_loc, axis=1
        )                                                   # [src, e_loc]
        seg_experts = jnp.tile(jnp.arange(e_loc), ep)       # [src*e_loc]
        row_expert = jnp.repeat(
            seg_experts, my_counts.reshape(-1),
            total_repeat_length=cap_rows,
        )
        n_recv = my_counts.sum()
        # Padding rows past n_recv got arbitrary repeat values; force
        # them to the sentinel group (>= e_loc) so the fused layout
        # drops them / the gmm sort sends them to the end.
        row_expert = jnp.where(
            jnp.arange(cap_rows) < n_recv, row_expert, e_loc
        )
        w_gu = jnp.concatenate([wg.astype(cdt), wu.astype(cdt)], -1)

        if (dispatch or _dispatch_impl()) == "fused":
            # The SAME grouped kernel as the local core, driven by the
            # exchange layout: row_ids gather the (src, expert)-major
            # received rows per expert segment and dest_ids scatter
            # results straight back to that layout — the xs2/ys2
            # [cap_rows, d] permute round-trips disappear.
            from dlrover_tpu.ops import moe_dispatch as md

            tm = md.default_tile_m(cap_rows)
            row_ids, dest_ids, tile_expert = md.build_dispatch_layout(
                row_expert, e_loc, tm, 1
            )
            ys = md.grouped_ffn(
                recv, w_gu, wd.astype(cdt), row_ids, dest_ids,
                tile_expert, cap_rows, 1, tm, interpret,
            ).astype(cdt)
        else:
            order2 = jnp.argsort(row_expert, stable=True)
            inv2 = jnp.argsort(order2)
            xs2 = _permute_rows(recv, order2, inv2)
            group_sizes = jnp.bincount(
                row_expert, length=e_loc + 1
            ).astype(jnp.int32)
            # gmm groups must cover all rows: fold the pad tail (zero
            # rows, zero outputs regardless of expert) into the last
            # real group.
            group_sizes = (
                group_sizes[:e_loc]
                .at[e_loc - 1].add(group_sizes[e_loc])
            )
            hu = gmm(
                xs2, w_gu, group_sizes, interpret=interpret,
                tiling=(_tile(cap_rows), _tile(d), _tile(2 * f)),
            )
            a = (jax.nn.silu(hu[:, :f]) * hu[:, f:]).astype(cdt)
            ys2 = gmm(
                a, wd.astype(cdt), group_sizes, interpret=interpret,
                tiling=(_tile(cap_rows), _tile(f), _tile(d)),
            ).astype(cdt)
            # Unsort to (src, expert)-major and ship results home.
            ys = _permute_rows(ys2, inv2, order2)
        back = _exchange(ys, sizes_mat, me, ep, axis_name, reverse=True)

        # Home layout equals the original sorted xs rows; unsort and
        # combine the k copies per token with a dense reshape-sum.
        out_tok = _permute_rows(back[:m_loc], inv_order, order)
        out = jnp.sum(
            out_tok.reshape(n_loc, top_k, d).astype(jnp.float32)
            * gates[:, :, None],
            axis=1,
        )
        return out.astype(x.dtype).reshape(bl, sl, d)

    wspec = P(axis_name)
    out = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(xspec, P(), wspec, wspec, wspec),
        out_specs=xspec,
        check_vma=False,
    )(x, router_w, w_gate, w_up, w_down)
    out = with_logical_constraint(out, ("batch", "seq", "embed"))

    metrics = MoEMetrics(
        aux_loss=aux,
        router_z_loss=z,
        dropped_fraction=jnp.zeros((), jnp.float32),
    )
    return out, metrics


# ---------------------------------------------------------------------------
# One chip's share of an expert layer (sigmoid router, no capacity)
# ---------------------------------------------------------------------------


class ShareCounters(NamedTuple):
    rows_held: jnp.ndarray     # (token, k) pairs the held experts computed
    rows_max: jnp.ndarray      # the busiest held expert's rows
    rows_dropped: jnp.ndarray  # 0: the row buffer holds any routing
    # experts that got a row at all (``routed_experts`` alone counts it)
    experts_hit: Optional[jnp.ndarray] = None
    # (expert, row tile) visits of each grouped matmul, where the rows
    # are expert-aligned (:func:`weight_visits`); None where they are not
    weight_visits: Optional[jnp.ndarray] = None
    # ``rows_held`` of a call that went through the buffer that holds any
    # routing, 0 of one that took a share's fast path
    # (:func:`_share_rows_held`); ``moe_mlp_share`` alone counts it
    rows_full_path: Optional[jnp.ndarray] = None


def router_scores(x, router_w):
    """``sigmoid(x W)`` in float32, x ``[n, d]`` -> ``[n, experts]``."""
    return jax.nn.sigmoid(jnp.einsum(
        "nd,de->ne", x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    ))


def sigmoid_route(x, router_w, router_bias, top_k: int, scaling: float):
    """Sigmoid router with a score-correction bias: ``s = sigmoid(x W)``,
    the ``top_k`` experts by ``s + bias`` (the bias selects and does not
    weight), weights ``scaling * s_e / sum of the chosen s``. All in
    float32. x ``[n, d]`` -> (experts ``[n, k]`` int32, weights ``[n, k]``)."""
    scores = router_scores(x, router_w)
    _, experts = jax.lax.top_k(
        scores + jax.lax.stop_gradient(router_bias.astype(jnp.float32)),
        top_k,
    )
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    weights = scaling * chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return experts.astype(jnp.int32), weights


def softmax_route(x, router_w, top_k: int):
    """Softmax router: ``p = softmax(x W)`` over all experts in float32,
    the ``top_k`` largest, weights renormalised over the chosen ones.
    x ``[n, d]`` -> (experts ``[n, k]`` int32, weights ``[n, k]``)."""
    probs = jax.nn.softmax(jnp.einsum(
        "nd,de->ne", x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    ), axis=-1)
    chosen, experts = jax.lax.top_k(probs, top_k)
    weights = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return experts.astype(jnp.int32), weights


def _take_or_zero(x, idx):
    """``x[idx]``; an index of ``len(x)`` reads a row of zeros."""
    return jnp.take(x, idx, axis=0, mode="fill", fill_value=0)


@jax.custom_vjp
def _gather_with_inverse(x, idx, back):
    """``_take_or_zero(x, idx)`` whose transpose is a gather too:
    ``back [len(x), m]`` names the up-to-``m`` output rows that read each
    row of ``x`` (``len(idx)``: none), so the backward sums ``m`` gathers
    where XLA's own transpose would scatter-add."""
    return _take_or_zero(x, idx)


def _gwi_fwd(x, idx, back):
    return _take_or_zero(x, idx), (idx, back)


def _gwi_bwd(res, g):
    idx, back = res
    dx = sum(
        _take_or_zero(g, back[:, j]).astype(jnp.float32)
        for j in range(back.shape[1])
    ).astype(g.dtype)
    return (
        dx,
        np.zeros(idx.shape, jax.dtypes.float0),
        np.zeros(back.shape, jax.dtypes.float0),
    )


_gather_with_inverse.defvjp(_gwi_fwd, _gwi_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _lay_rows(x, back, rows: int):
    """``rows`` rows of which row ``back[i, j]`` is ``x[i]`` (``rows``:
    none) and every other is zeros, by a ONE-HOT MATMUL ``[rows, len(x)]
    @ x``: one 1 a filled row under a float32 accumulator, so the row
    itself, to the bit. No index is gathered: the chip pays ~12 ns an
    INDEX for a gather, whether it fetches a row of 4.6 KB or one
    ``int32`` (PERF.md section 6, PR 52), a buffer of 12,288 rows is
    0.15 ms a gather, and the MXU waits for weights in this layer
    anyway. The 1s come from comparing ``back`` with the row numbers
    (a row a token's ``j``-th copy at most: the copies' rows differ).
    ``x`` must be finite (0 x inf). The transpose is
    :func:`_gather_with_inverse`'s: the cotangent's unread rows may hold
    anything, which a matmul would spread."""
    lay = jnp.any(
        back.T[None, :, :] == jnp.arange(rows)[:, None, None], axis=1
    ).astype(x.dtype)                                  # [rows, len(x)]
    # (a float32 ``x`` in one bfloat16 pass would lose its low bits)
    exact = jax.lax.Precision.HIGHEST if x.dtype.itemsize > 2 else None
    return jnp.dot(lay, x, preferred_element_type=x.dtype, precision=exact)


def _lay_fwd(x, back, rows):
    return _lay_rows(x, back, rows), back


def _lay_bwd(rows, back, g):
    dx, _, no_back = _gwi_bwd((back, back), g)
    return dx, no_back


_lay_rows.defvjp(_lay_fwd, _lay_bwd)


def moe_mlp_share(
    x,
    router_w,      # [embed, all experts]
    router_bias,   # [all experts], a buffer
    w_gate,        # [held, embed, mlp]
    w_up,          # [held, embed, mlp]
    w_down,        # [held, mlp, embed]
    first: int,
    top_k: int,
    scaling: float = 1.0,
    interpret=None,
):
    """The part of a routed expert layer that the experts
    ``first .. first + held - 1`` give: x ``[batch, seq, embed]`` ->
    (out, :class:`ShareCounters`).

    Every token is routed over ALL experts (the router's width and
    ``top_k`` are the model's; the weights are normalised over all
    ``top_k`` chosen experts, held or not). The (token, k) pairs whose
    expert lives here are sorted to the front by expert and run through
    one grouped matmul a projection (megablox ``gmm``, which visits the
    tiles of the rows its groups name and zeroes the rest); what the
    absent experts would have added is left out. No capacity and no
    drop: a token picks distinct experts, so at most ``n * min(top_k,
    held)`` pairs can land here, and a row buffer of that size holds any
    routing. An evenly loaded share fills ``held / all`` of ``n *
    top_k`` rows, so the step first asks whether the pairs fit a buffer
    of four times that (``lax.cond`` on the count) and takes the full
    one only when they do not: the same function either way, and never
    a dropped row (on the chip, 8,192 tokens x top-8 of 256, 8 held, a
    five-layer step: 12,465 tokens/s through the usual buffer, 9,352
    with every pair sent here and the full one taken, loss and
    gradients on the reference both ways; PERF.md, PR 31). This is
    what expert parallelism asks of a shard
    before and after its exchange; no exchange happens here.
    """
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    with jax.named_scope("router"):
        experts, weights = sigmoid_route(
            xf, router_w, router_bias, top_k, scaling
        )
    out, group_sizes, n_held, visits, full = _routed_share(
        xf, (b, s, d), experts, weights, router_w.shape[-1], w_down,
        w_gate=w_gate, w_up=w_up, first=first, interpret=interpret,
    )
    return out, ShareCounters(
        rows_held=n_held,
        rows_max=jnp.max(group_sizes),
        rows_dropped=jnp.zeros((), jnp.int32),
        weight_visits=visits,
        rows_full_path=full,
    )


def routed_experts(x, experts, weights, w_gu, w_down, n_experts: int,
                   group_offset=None, interpret=None):
    """A whole dropless expert layer for a caller that has routed: x
    ``[batch, seq, embed]``, ``experts`` / ``weights [n, k]`` (every
    expert is held) -> (out, :class:`ShareCounters`). ``w_gu [groups,
    embed, 2 * mlp]`` holds gate and up side by side (a decode step
    reads its weights once; joining them a call would read and write
    them all again) and ``w_down [groups, mlp, embed]``. ``groups`` may
    be more than ``n_experts``: expert ``e`` is group ``group_offset +
    e`` of the stack (a layer's offset into all layers' experts, traced
    in a layer loop), and the other groups get no row. The same
    function for a full forward, a prefill chunk and a decode step: no
    capacity in any of them, so a token's experts give it the same
    output whatever else the call carries."""
    b, s, d = x.shape
    out, group_sizes, n_held, visits, _ = _routed_share(
        x.reshape(b * s, d), (b, s, d), experts, weights, n_experts,
        w_down, w_gu=w_gu, held=n_experts, group_offset=group_offset,
        interpret=interpret,
    )
    return out, ShareCounters(
        rows_held=n_held,
        rows_max=jnp.max(group_sizes),
        rows_dropped=jnp.zeros((), jnp.int32),
        experts_hit=jnp.sum(group_sizes > 0, dtype=jnp.int32),
        weight_visits=visits,
    )


def _routed_share(xf, shape, experts, weights, e_all: int, w_down, *,
                  w_gate=None, w_up=None, w_gu=None, first=0, held=None,
                  group_offset=None, interpret=None):
    """The expert compute both entries share: ``xf [n, embed]`` and its
    routing -> (out ``shape``, rows a weight group got, their sum, the
    matmuls' weight visits where the rows are expert-aligned or None,
    the rows that went through the buffer that holds any routing).
    Experts ``first .. first + held - 1`` are computed (``held``: every
    group of ``w_down`` when None); expert ``first`` is weight group
    ``group_offset`` (0 when None)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, top_k = experts.shape
    groups = w_down.shape[0]
    if held is None:
        held = groups
    with jax.named_scope("experts"):
        local = experts.reshape(n * top_k) - first
        is_held = (local >= 0) & (local < held)
        if group_offset is not None:
            local = local + group_offset
        local = jnp.where(is_held, local, groups)      # absent: sorted last
        # A row tile no larger than the rows an evenly loaded expert
        # gets: a group pays for whole tiles.
        even = n * top_k // e_all
        tm = min(max(even, ROW_TILE), 512)

        def padded(rows):
            return (rows + tm - 1) // tm * tm if rows >= tm else rows

        most = padded(n * min(top_k, held))
        usual = padded(4 * even * held)
        # A SHARE (few of the experts held) fills a fraction of ``most``:
        # it asks whether its pairs fit four even shares' rows, and then
        # works by row and by token, not by pair (:func:`_share_rows_held`).
        share = 0 < usual < most   # (0: fewer pairs than experts)
        # one layout a call, by the buffer that holds any routing (a
        # share's rows are packed in both of its buffers)
        aligned = not share and _aligned_rows(
            even, tm, most, held, n, xf.shape[1], w_down.shape[-2],
            jnp.dtype(xf.dtype).itemsize,
        )

        operands = (xf, weights, w_gate, w_up, w_down, w_gu)

        def through_most(xf, weights, w_gate, w_up, w_down, w_gu,
                         group_sizes=None, n_held=None):
            order = jnp.argsort(local, stable=True)
            inv_order = jnp.argsort(order)
            if group_sizes is None:
                group_sizes = jnp.bincount(
                    local, length=groups + 1
                )[:groups].astype(jnp.int32)
                n_held = jnp.sum(group_sizes)
            if aligned:
                out = _share_rows_aligned(
                    xf, weights, w_gate, w_up, w_down, order, inv_order,
                    is_held, local, group_sizes,
                    (-(-most // tm) + held) * tm, tm, interpret, w_gu, even,
                )
            else:
                out = _share_rows(
                    xf, weights, w_gate, w_up, w_down, order, inv_order,
                    is_held, group_sizes, n_held, most, tm, interpret, w_gu,
                    even,
                )
            return out, group_sizes, n_held

        if share:
            group_of = local.reshape(n, top_k)
            # (token, group): 1 where the token holds the group's expert.
            # A token's experts differ, so a column sum is a group's rows.
            member = jnp.sum(
                group_of[:, :, None] == jnp.arange(groups), axis=1,
                dtype=jnp.int32,
            )
            group_sizes = jnp.sum(member, axis=0)
            n_held = jnp.sum(group_sizes)
            tail = min(TAIL_TOKENS, n)
            fast = (n_held <= usual) & (jnp.sum(
                jnp.sum(member, axis=1) > LEVELS
            ) <= tail)
            branches = (
                lambda *operands: _share_rows_held(
                    *operands, group_of, member, group_sizes, n_held, usual,
                    tail, tm, interpret, even,
                ),
                lambda *operands: through_most(
                    *operands, group_sizes, n_held
                )[0],
            )
            if most >= RERUN_FROM * usual:
                # Each branch keeps nothing for its backward but its
                # operands and runs again there: what a branch keeps, a
                # ``cond`` hands out of BOTH its branches, the other's as
                # zeros, so the fast path would write the full path's
                # residuals (2 GB a layer at 8,192 tokens x top-8 of 256:
                # 2.5 ms of a 12.9 ms layer) every time it ran.
                branches = tuple(jax.checkpoint(b) for b in branches)
            out = jax.lax.cond(fast, *branches, *operands)
            full = jnp.where(fast, 0, n_held)
        else:
            out, group_sizes, n_held = through_most(*operands)
            full = n_held
        out = with_logical_constraint(
            out.reshape(shape), ("batch", "seq", "embed")
        )
        visits = weight_visits(group_sizes, tm, True) if aligned else None
    return out, group_sizes, n_held, visits, full


def _grouped_swiglu(xs, w_gate, w_up, w_gu, w_down, group_sizes, tm, even,
                    interpret):
    """``xs [rows, d]`` by group -> each group's SwiGLU of its rows,
    ``[rows, d]``: two grouped matmuls (megablox ``gmm``, which visits the
    (group, row tile) pairs ``group_sizes`` name and writes no other
    tile) in row tiles of ``tm`` under :func:`_weight_block`'s blocks."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    d, f = xs.shape[1], w_down.shape[-2]
    cdt = xs.dtype
    if w_gu is None:
        w_gu = jnp.concatenate(
            [w_gate.astype(cdt), w_up.astype(cdt)], axis=-1
        )
    else:
        w_gu = w_gu.astype(cdt)
    size = jnp.dtype(cdt).itemsize
    hu = gmm(
        xs, w_gu, group_sizes, preferred_element_type=cdt,
        interpret=interpret,
        tiling=(tm, *_weight_block(even, tm, d, 2 * f, size)),
    )
    act = (jax.nn.silu(hu[:, :f]) * hu[:, f:]).astype(cdt)
    return gmm(
        act, w_down.astype(cdt), group_sizes, preferred_element_type=cdt,
        interpret=interpret,
        tiling=(tm, *_weight_block(even, tm, f, d, size)),
    )


def _share_rows(xf, weights, w_gate, w_up, w_down, order, inv_order,
                is_held, group_sizes, n_held, rows, tm, interpret,
                w_gu, even):
    """``moe_mlp_share``'s expert compute through a buffer of ``rows``
    rows (>= ``n_held``): gather the held pairs' tokens by expert, two
    grouped matmuls, weight each row, and sum a token's rows back.
    ``even``: the rows an evenly loaded expert gets, which says how the
    matmuls' weight blocks are cut (:func:`_weight_block`)."""
    n, d = xf.shape
    top_k = weights.shape[1]
    cdt = xf.dtype
    front = order[:rows] if rows <= order.shape[0] else jnp.pad(
        order, (0, rows - order.shape[0])
    )
    # Pair j sits at sorted row inv_order[j]; an absent expert's pair is
    # past the buffer and reads, or is read as, a row of zeros.
    at = jnp.where(is_held, inv_order, rows)
    xs = _gather_with_inverse(
        xf, front // top_k, at.reshape(n, top_k)
    )                                                  # [rows, d], by expert
    ys = _grouped_swiglu(
        xs, w_gate, w_up, w_gu, w_down, group_sizes, _tile(rows, cap=tm),
        even, interpret,
    )                                  # (rows past the groups: unwritten)
    by_row = _gather_with_inverse(
        weights.reshape(n * top_k, 1), front, at[:, None]
    )
    ys = (ys.astype(jnp.float32) * by_row).astype(cdt)
    read_by = jnp.where(jnp.arange(rows) < n_held, front, n * top_k)
    per_pair = _gather_with_inverse(ys, at, read_by[:, None])
    return jnp.sum(
        per_pair.reshape(n, top_k, d).astype(jnp.float32), axis=1
    ).astype(cdt)                                      # back in token order


# A share's fast path reads a token's first LEVELS held rows by one
# gather of ``n`` indices a level, and the further rows of the few tokens
# that hold more (by the binomial of an even router one token in 700 of
# 8,192 x top-8 of 256 with 8 held, one in 140 of top-4 of 64) through a
# buffer of TAIL_TOKENS tokens; more of them than that and the call takes
# the full path.
LEVELS = 2
TAIL_TOKENS = 256
# A share whose full buffer is at least this many usual ones runs its
# ``cond``'s branches again in the backward (``_routed_share``). The zeros
# a fast call writes for the full branch's residuals grow with the full
# buffer; what running again costs is a second compiled copy of each
# branch's forward, ~30 MB of executable a branch to load at every start
# (+4.5 s of a 56 s set-up where the ratio is 2 and the zeros 1.8 ms a
# layer; at 8 the zeros are 2.5 ms of a 12.9 ms layer: PERF.md, PR 54).
RERUN_FROM = 4


def _held_reads(at, tail_token, rows: int):
    """How a token reads its rows of a ``rows``-row buffer, from ``at [n,
    top_k]`` (the row of each of its pairs; ``rows``: an absent
    expert's): a list of (``index [n]``, ``select [n, top_k]``) a
    level, ``index`` the row (``rows``: none, read as zeros) and
    ``select`` the one ``k`` it belongs to; and, where a token can hold
    more than ``LEVELS`` rows, the same for ALL the later pairs of the
    tail's tokens ``tail_token [tail]`` (``n``: a free slot) at once:
    (``index [tail, top_k]``, ``select [tail, top_k]``), else None.
    Elementwise over ``top_k`` but for the tail's ``tail`` indices."""
    def nth(at):
        hit = at < rows
        return hit, jnp.cumsum(hit, axis=1, dtype=jnp.int32) - hit

    hit, earlier = nth(at)
    levels = []
    for level in range(min(LEVELS, at.shape[1])):
        select = hit & (earlier == level)
        levels.append((jnp.min(jnp.where(select, at, rows), axis=1), select))
    if tail_token is None:
        return levels, None
    at = jnp.take(at, tail_token, axis=0, mode="fill", fill_value=rows)
    hit, earlier = nth(at)
    later = hit & (earlier >= LEVELS)
    return levels, (jnp.where(later, at, rows), later)


def _tail_rows(buf, index):
    """``buf[index]`` in float32, ``[tail, top_k, d]``, by one gather."""
    return _take_or_zero(buf, index.reshape(-1)).astype(
        jnp.float32
    ).reshape(*index.shape, buf.shape[1])


def _read_held(buf, weights, at, tail_token):
    """``out[t] = sum over k of weights[t, k] * buf[at[t, k]]`` in
    float32, ``[n, d]``: level by level, the tail's sum laid back over
    the tokens by :func:`_lay_rows` (rounded to ``buf``'s dtype first:
    one rounding of a partial sum of a token in hundreds). ``weights``
    None: ones."""
    n = at.shape[0]
    levels, tail = _held_reads(at, tail_token, buf.shape[0])
    out = 0.0
    for index, select in levels:
        got = _take_or_zero(buf, index).astype(jnp.float32)
        if weights is not None:
            got = got * jnp.sum(
                jnp.where(select, weights, 0.0), axis=1, keepdims=True
            )
        out = out + got
    if tail is not None:
        index, select = tail
        got = _tail_rows(buf, index)
        if weights is not None:
            got = got * jnp.where(select, jnp.take(
                weights, tail_token, axis=0, mode="fill", fill_value=0
            ), 0.0)[:, :, None]
        out = out + _lay_rows(
            jnp.sum(got, axis=1).astype(buf.dtype), tail_token[:, None], n
        )
    return out


def _no_grad(*maps):
    """The cotangents of ``int32`` maps (None: an operand left out)."""
    return tuple(
        None if m is None else np.zeros(m.shape, jax.dtypes.float0)
        for m in maps
    )


@jax.custom_vjp
def _dispatch_held(xf, token_of, at, tail_token):
    """``_take_or_zero(xf, token_of)``: a buffer's rows, each the token
    it computes (``n``: none). The transpose is the combine's read with
    no weights: a token sums the cotangents of its rows."""
    return _take_or_zero(xf, token_of)


def _dispatch_held_fwd(xf, token_of, at, tail_token):
    return _take_or_zero(xf, token_of), (token_of, at, tail_token)


def _dispatch_held_bwd(res, g):
    token_of, at, tail_token = res
    dx = _read_held(g, None, at, tail_token).astype(g.dtype)
    return (dx, *_no_grad(token_of, at, tail_token))


_dispatch_held.defvjp(_dispatch_held_fwd, _dispatch_held_bwd)


@jax.custom_vjp
def _combine_held(ys, weights, token_of, pair_of, at, tail_token):
    """A token's weighted rows summed in float32 (:func:`_read_held`),
    ``[n, d]`` in ``ys``'s dtype. The transposes: a row's cotangent is
    its token's times its weight, one gather of ``rows`` indices out of
    the ``[n, d]`` cotangent (``token_of``, ``pair_of [rows]``: the
    token and the pair a row computes); a weight's is its row's dot with
    the token's cotangent, level by level."""
    return _read_held(ys, weights, at, tail_token).astype(ys.dtype)


def _combine_held_fwd(ys, weights, token_of, pair_of, at, tail_token):
    out = _read_held(ys, weights, at, tail_token).astype(ys.dtype)
    return out, (ys, weights, token_of, pair_of, at, tail_token)


def _combine_held_bwd(res, g):
    ys, weights, token_of, pair_of, at, tail_token = res
    n, top_k = at.shape
    by_row = _take_or_zero(weights.reshape(n * top_k, 1), pair_of)
    d_ys = (
        _take_or_zero(g, token_of).astype(jnp.float32) * by_row
    ).astype(ys.dtype)
    levels, tail = _held_reads(at, tail_token, ys.shape[0])
    g = g.astype(jnp.float32)
    d_weights = sum(
        jnp.where(select, jnp.sum(
            _take_or_zero(ys, index).astype(jnp.float32) * g, axis=1,
            keepdims=True,
        ), 0.0)
        for index, select in levels
    )
    if tail is not None:
        index, select = tail
        dots = jnp.sum(
            _tail_rows(ys, index) * _take_or_zero(g, tail_token)[:, None, :],
            axis=2,
        )
        d_weights = d_weights + _lay_rows(
            jnp.where(select, dots, 0.0), tail_token[:, None], n
        )
    return (d_ys, d_weights.astype(weights.dtype),
            *_no_grad(token_of, pair_of, at, tail_token))


_combine_held.defvjp(_combine_held_fwd, _combine_held_bwd)


def _share_rows_held(xf, weights, w_gate, w_up, w_down, w_gu, group_of,
                     member, group_sizes, n_held, rows, tail, tm, interpret,
                     even):
    """:func:`_share_rows` for a SHARE, whose ``rows`` (>= ``n_held``)
    are a fraction of the ``n * top_k`` pairs: no map, gather or
    transpose is sized by the pairs, and nothing scatters. ``group_of
    [n, top_k]``: a pair's weight group (``groups``: absent), ``member
    [n, groups]``: 1 where the token holds the group's expert.

    * pair -> row by arithmetic: a group's first row plus the earlier
      tokens that hold the group (a running sum down ``n`` a group), so
      a group's rows are in token order as a stable sort leaves them;
    * row -> pair by ONE sort, of ``group * pairs + pair`` (the payload
      is in the key), cut to ``rows``;
    * a token reads its rows level by level (:func:`_read_held`), the
      routing weight multiplying in float32 at the token, as in
      :func:`_share_rows_aligned`; ``tail`` tokens may hold more than
      ``LEVELS`` rows (the caller has counted them);
    * every transpose is the other direction's read.
    """
    n, top_k = group_of.shape
    groups, pairs = group_sizes.shape[0], n * top_k
    starts = jnp.cumsum(group_sizes) - group_sizes
    place = starts + jnp.cumsum(member, axis=0) - member      # [n, groups]
    at = jnp.sum(jnp.where(
        group_of[:, :, None] == jnp.arange(groups), place[:, None, :], 0
    ), axis=2)
    at = jnp.where(group_of < groups, at, rows)
    pair_of = jnp.sort(
        group_of.reshape(pairs) * pairs + jnp.arange(pairs)
    )[:rows] % pairs
    pair_of = jnp.where(jnp.arange(rows) < n_held, pair_of, pairs)
    token_of = pair_of // top_k
    tail_token = None
    if min(top_k, groups) > LEVELS:
        more = jnp.sum(member, axis=1) > LEVELS                # [n]
        slot = jnp.cumsum(more, dtype=jnp.int32) - more
        tail_token = jnp.sum(jnp.where(
            more & (slot == jnp.arange(tail)[:, None]), jnp.arange(n) - n, 0
        ), axis=1) + n                                         # (n: free)
    xs = _dispatch_held(xf, token_of, at, tail_token)      # [rows, d]
    ys = _grouped_swiglu(
        xs, w_gate, w_up, w_gu, w_down, group_sizes, _tile(rows, cap=tm),
        even, interpret,
    )                                  # (rows past the groups: unwritten)
    return _combine_held(ys, weights, token_of, pair_of, at, tail_token)


def _share_rows_aligned(xf, weights, w_gate, w_up, w_down, order, inv_order,
                        is_held, local, group_sizes, rows, tm, interpret,
                        w_gu, even):
    """:func:`_share_rows` with the sorted rows laid out EXPERT-ALIGNED:
    a group's rows start on a row-tile boundary (the tiles before it are
    the whole tiles of the groups before it, an empty group none), and
    ``gmm`` is handed the group sizes rounded up to whole tiles, so that
    a tile belongs to one group and a group's weights go through once a
    tile it FILLS, never once more for a tile it shares. ``rows``: the
    packed buffer's and ``tm`` more a held expert (a group's last tile is
    part empty: zeros in, zeros out, never read back; the tiles no group
    holds are never visited). The same kernel under the same blocks as
    the packed layout, and a row's result does not depend on where in
    the buffer it sits. What differs besides the layout: the rows are
    laid out by :func:`_lay_rows`, and the routing weight multiplies at
    the combine, in float32, before a token's rows are summed (the
    packed path rounds the weighted row to the compute dtype first), so
    that no pass over the buffer is left but the matmuls' own and the
    activation's. The maps between pairs and buffer rows are index
    arithmetic over ``int32`` vectors; every transpose is a gather
    (:func:`_gather_with_inverse`), as there."""
    n, d = xf.shape
    top_k = weights.shape[1]
    groups = group_sizes.shape[0]
    tiles = -(-group_sizes // tm)                      # a group fills
    tile_ends = jnp.cumsum(tiles)
    begins = (tile_ends - tiles) * tm                  # a group's first row
    # ... less its first sorted row: what a pair's sorted row moves by
    shift = begins - (jnp.cumsum(group_sizes) - group_sizes)
    # Pair j sits at its sorted row, moved with its group (a comparison
    # with every group and a sum, not a gather of ``shift``: see
    # :func:`_lay_rows`); an absent expert's pair is past the buffer, as
    # in the packed layout.
    moved = jnp.sum(jnp.where(
        local[:, None] == jnp.arange(groups)[None, :], shift[None, :], 0
    ), axis=1)
    at = jnp.where(is_held, inv_order + moved, rows)
    xs = _lay_rows(
        xf, at.reshape(n, top_k), rows
    )                                  # [rows, d], by expert, from tiles
    ys = _grouped_swiglu(
        xs, w_gate, w_up, w_gu, w_down, tiles * tm, tm, even, interpret
    )
    # Buffer row -> the pair it holds, for the combine's transpose alone
    # (a served program's compiler drops it unused): a tile's group (the groups
    # that end at or before the tile, counted; past them all the last,
    # whose rows the tile is past too), a row's place in the group, the
    # pair sorted there.
    tile_group = jnp.minimum(jnp.sum(
        tile_ends[None, :] <= jnp.arange(rows // tm)[:, None], axis=1
    ), groups - 1)
    row = jnp.arange(rows).reshape(rows // tm, tm)
    pair = jnp.where(
        row - jnp.take(begins, tile_group)[:, None]
        < jnp.take(group_sizes, tile_group)[:, None],
        jnp.take(
            order, row - jnp.take(shift, tile_group)[:, None], mode="clip"
        ),
        n * top_k,
    ).reshape(rows, 1)
    per_pair = _gather_with_inverse(ys, at, pair)
    return jnp.sum(
        per_pair.reshape(n, top_k, d).astype(jnp.float32)
        * weights[:, :, None].astype(jnp.float32), axis=1,
    ).astype(xf.dtype)                                 # back in token order
