"""Fused sort-based MoE dispatch: tile-blocked gather→GEMM→scatter
over the expert-sorted row order, Pallas.

The dropless MoE path (models/moe.py ``_dropless_core``) previously
moved every token copy through HBM **five** times around the grouped
matmuls: an XLA gather materializes the expert-sorted ``[m, d]`` copy
(write + read), megablox ``gmm`` reads it, the ``[m, 2f]`` silu
intermediate round-trips between the two gmm calls, and a second
``[m, d]`` gather unsorts the result for the combine. At the bench
shape (m = 32k rows, d = 1024) that is the difference between 24.9%
and >40% active-MFU — the MXU starves behind permutation traffic.

Here the permutation never touches HBM as data. The sorted row order
rides as a scalar-prefetch vector and the kernels walk per-expert row
segments in a group-aligned padded layout (every ``tile_m``-row tile
belongs to exactly ONE expert, megablox-style but with the gather
folded in):

- **forward** (one kernel): per tile, DMA the tile's source rows
  straight from the token-major input into VMEM (``row_ids`` names
  them; all ``tile_m`` copies are issued before the first wait, so the
  DMA engine pipelines the row reads), run gate|up GEMM → silu·mul →
  down GEMM on the MXU while the next tile's rows stream in, and DMA
  the result rows to their copy-major positions (``dest_ids``). One
  HBM read of x-rows, one HBM write of y-rows — nothing else.
- **backward** (custom VJP): the SAME permutation vectors drive three
  kernels — dx (gather x and dy rows, recompute h/a flash-style,
  chain through both GEMMs transposed, scatter dx rows), and two
  per-expert weight-gradient kernels that accumulate ``dw = lhsᵀ @
  rhs`` into expert-indexed output blocks (consecutive tiles of one
  expert revisit the same block, so the accumulator lives in VMEM).
  XLA's transpose-of-gather — a scatter-add, the dominant cost of the
  old backward — never appears.

The layout is static-shaped: ``m`` copies pad to
``(cdiv(m, tile_m) + n_groups) * tile_m`` slots (each expert wastes at
most one tile), padding slots carry ``row_id = -1`` and are masked to
zero rows / skipped scatters. Group sizes are data-dependent VALUES,
never shapes — the whole thing jits once.

STATUS (PR 21): compiles for a v5e (tests/test_tpu_compile.py) and
runs on one with gmm-level agreement, but is NOT the default dispatch:
the one on-chip reading so far has it at 20.3 ms fwd+bwd against gmm's
14.1 ms at the bench shape (a smoke reading; ROADMAP S3 owns the real
A/B). The >40% MFU figure above is the design's target, never measured.
Rows move as f32 through a [rows, 1, d] view (see ``_dma_rows``), which
doubles the gather/scatter bytes of a bf16 model.

VMEM budget note: the kernels hold one expert's weights (w_gu
``[d, 2f]``, w_down ``[f, d]``) plus ``tile_m``-row tiles in VMEM; at
the bench shape (d = f = 1024, bf16, tile_m = 128) the worst kernel
(dwgu: ``[d, 2f]`` f32 accumulator) sits at ~9 MB of the 16 MB core
budget. Larger mlp_dim wants an f-tiled grid axis — out of scope here.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# One expert's double-buffered weights, the f32 row tiles and (in the dw
# kernels) an f32 [d, 2f] accumulator block outgrow Mosaic's default
# 16 MiB scoped-VMEM budget at d = f = 1024; v5e has 128 MiB.
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024)


def default_tile_m(m: int) -> int:
    """Row-tile size: the MXU-shaped 128 once there is enough work,
    the bf16 16-sublane minimum for test-sized inputs (covers the f32
    minimum of 8 too)."""
    return 128 if m >= 1024 else 16


def build_dispatch_layout(
    flat_expert, n_groups: int, tile_m: int, copies_per_src: int
):
    """Group-aligned padded layout for ``m`` copies sorted by expert.

    ``flat_expert`` [m] int32 holds each copy's routed group in
    ``[0, n_groups)``; entries >= n_groups are SENTINEL copies (the ep
    path's exchange padding) that get no slot. Returns

    - ``row_ids``  [m_pad]: source row (``sorted_copy // copies_per_src``)
      per padded slot, -1 on padding;
    - ``dest_ids`` [m_pad]: copy-major output row per slot, -1 on
      padding (= the sorted copy's original index, so the scatter IS
      the unsort);
    - ``tile_expert`` [T]: the one group every row of tile ``t``
      belongs to.

    with ``T = cdiv(m, tile_m) + n_groups + 1`` static (each group
    wastes < 1 tile; +1 absorbs sentinel copies) and
    ``m_pad = T * tile_m``. All data-dependent quantities are VALUES
    under jit — shapes depend only on ``m``/``tile_m``/``n_groups``.
    """
    m = flat_expert.shape[0]
    T = -(-m // tile_m) + n_groups + 1
    m_pad = T * tile_m
    fe = jnp.asarray(flat_expert, jnp.int32)
    # Sentinel copies sort into an internal trailing group and are
    # dropped from the slot scatter below.
    fe_int = jnp.minimum(fe, n_groups)
    order = jnp.argsort(fe_int, stable=True)              # [m]
    sorted_grp = fe_int[order]
    counts = jnp.bincount(fe_int, length=n_groups + 1)
    padded = -(-counts // tile_m) * tile_m
    # Every REAL group gets >= 1 tile even when empty: a dw output
    # block that no grid step visits would keep its backing buffer's
    # garbage — an empty expert's all-padding tile initializes it to
    # the zero gradient instead.
    padded = padded.at[:n_groups].max(tile_m)
    pad_off = jnp.cumsum(padded) - padded                 # [g+1]
    grp_start = jnp.cumsum(counts) - counts
    j = jnp.arange(m)
    slot = pad_off[sorted_grp] + (j - grp_start[sorted_grp])
    valid = sorted_grp < n_groups
    slot = jnp.where(valid, slot, m_pad)  # out of range -> dropped
    row_ids = jnp.full((m_pad,), -1, jnp.int32)
    dest_ids = jnp.full((m_pad,), -1, jnp.int32)
    src = (order // copies_per_src).astype(jnp.int32)
    row_ids = row_ids.at[slot].set(src, mode="drop")
    dest_ids = dest_ids.at[slot].set(
        order.astype(jnp.int32), mode="drop"
    )
    ends = jnp.cumsum(padded)
    tile_expert = jnp.searchsorted(
        ends, jnp.arange(T, dtype=jnp.int32) * tile_m, side="right"
    ).astype(jnp.int32)
    tile_expert = jnp.clip(tile_expert, 0, n_groups - 1)
    return row_ids, dest_ids, tile_expert


def _dma_rows(x):
    """``x`` [rows, d] as the f32 [rows, 1, d] array the row DMAs move.

    Mosaic only slices a memref along its two minor dims in whole
    tiles, and a [rows, d] array's tile spans 8 rows (16 packed, for
    bf16): one row is not addressable. With a unit second-minor dim the
    row index is an UNTILED leading dim instead — for 32-bit types a
    free reshape with (1, 128) tiles (bf16 would pad every row to two).
    So rows travel as f32; the MXU still sees the compute dtype."""
    return x.astype(jnp.float32).reshape(x.shape[0], 1, x.shape[1])


def _gather_rows(src_hbm, ids_ref, base, dst_ref, sems, tile_m):
    """DMA ``tile_m`` rows ``src_hbm[ids[base + r]]`` into ``dst_ref``;
    all copies start before the first wait so the DMA engine pipelines
    the row reads. Padding ids (< 0) fetch row 0 and are masked by the
    caller."""

    def start(r, _):
        rid = jnp.maximum(ids_ref[base + r], 0)
        pltpu.make_async_copy(
            src_hbm.at[rid], dst_ref.at[r], sems.at[r]
        ).start()
        return 0

    def wait(r, _):
        rid = jnp.maximum(ids_ref[base + r], 0)
        pltpu.make_async_copy(
            src_hbm.at[rid], dst_ref.at[r], sems.at[r]
        ).wait()
        return 0

    jax.lax.fori_loop(0, tile_m, start, 0)
    jax.lax.fori_loop(0, tile_m, wait, 0)


def _scatter_rows(src_ref, ids_ref, base, dst_hbm, sems, tile_m):
    """DMA rows of ``src_ref`` out to ``dst_hbm[ids[base + r]]``,
    skipping padding ids (< 0)."""

    def start(r, _):
        d = ids_ref[base + r]

        @pl.when(d >= 0)
        def _():
            pltpu.make_async_copy(
                src_ref.at[r], dst_hbm.at[d], sems.at[r]
            ).start()
        return 0

    def wait(r, _):
        d = ids_ref[base + r]

        @pl.when(d >= 0)
        def _():
            pltpu.make_async_copy(
                src_ref.at[r], dst_hbm.at[d], sems.at[r]
            ).wait()
        return 0

    jax.lax.fori_loop(0, tile_m, start, 0)
    jax.lax.fori_loop(0, tile_m, wait, 0)


def _valid_mask(ids_ref, base, tile_m):
    """[tile_m, 1] mask of the tile's non-padding rows. The ids live in
    SMEM (scalar prefetch), which Mosaic reads one scalar at a time, so
    the valid rows are COUNTED with scalar loads and the mask is an
    iota compare: ``build_dispatch_layout`` packs each group's copies
    at the front of its tiles, so a tile's valid rows are a prefix."""
    n_valid = jax.lax.fori_loop(
        0, tile_m,
        lambda r, c: c + (ids_ref[base + r] >= 0).astype(jnp.int32),
        jnp.int32(0),
    )
    rows = jax.lax.broadcasted_iota(jnp.int32, (tile_m, 1), 0)
    return rows < n_valid


def _silu_bwd(hg, hu, da):
    """d(silu(hg) * hu) pulled back through the elementwise gate."""
    sg = jax.nn.sigmoid(hg)
    silu = hg * sg
    dhu = da * silu
    dhg = da * hu * (sg * (1.0 + hg * (1.0 - sg)))
    return dhg, dhu


def _fwd_kernel(
    row_ids, dest_ids, te, x_hbm, wgu_ref, wdn_ref, y_hbm,
    xt, yt, gsem, ssem, *, tile_m,
):
    i = pl.program_id(0)
    base = i * tile_m
    _gather_rows(x_hbm, row_ids, base, xt, gsem, tile_m)
    mask = _valid_mask(row_ids, base, tile_m)
    xm = jnp.where(mask, xt[:, 0, :], 0).astype(wgu_ref.dtype)
    h = jax.lax.dot_general(
        xm, wgu_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    f = h.shape[-1] // 2
    a = (jax.nn.silu(h[:, :f]) * h[:, f:]).astype(wdn_ref.dtype)
    yt[:, 0, :] = jax.lax.dot_general(
        a, wdn_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    _scatter_rows(yt, dest_ids, base, y_hbm, ssem, tile_m)


def _dx_kernel(
    row_ids, dest_ids, te, x_hbm, dy_hbm, wgu_ref, wdn_ref,
    dx_hbm, dh_ref, a_ref,
    xt, dyt, dxt, gsem, dsem, ssem, *, tile_m,
):
    i = pl.program_id(0)
    base = i * tile_m
    _gather_rows(x_hbm, row_ids, base, xt, gsem, tile_m)
    _gather_rows(dy_hbm, dest_ids, base, dyt, dsem, tile_m)
    mask = _valid_mask(row_ids, base, tile_m)
    xm = jnp.where(mask, xt[:, 0, :], 0).astype(wgu_ref.dtype)
    dy = jnp.where(mask, dyt[:, 0, :], 0)
    h = jax.lax.dot_general(
        xm, wgu_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    f = h.shape[-1] // 2
    hg, hu = h[:, :f], h[:, f:]
    a = jax.nn.silu(hg) * hu
    a_ref[:] = a.astype(a_ref.dtype)
    # da = dy @ w_downᵀ  (contract the d axis of both)
    da = jax.lax.dot_general(
        dy.astype(wdn_ref.dtype), wdn_ref[0],
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dhg, dhu = _silu_bwd(hg, hu, da)
    dh = jnp.concatenate([dhg, dhu], axis=-1)
    dh_ref[:] = dh.astype(dh_ref.dtype)
    # dx = dh @ w_guᵀ  (contract the 2f axis)
    dxt[:, 0, :] = jax.lax.dot_general(
        dh.astype(wgu_ref.dtype), wgu_ref[0],
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    _scatter_rows(dxt, dest_ids, base, dx_hbm, ssem, tile_m)


def _dw_accum_kernel(
    gather_ids, te, lhs_hbm, rhs_ref, dw_ref, lt, gsem, *, tile_m,
):
    """dw[te[i]] += gathered(lhs)ᵀ @ rhs_tile, accumulated across the
    consecutive tiles of each expert (same output block stays resident
    in VMEM; ``init`` detects the group edge from the prefetch vector
    itself)."""
    i = pl.program_id(0)
    base = i * tile_m
    _gather_rows(lhs_hbm, gather_ids, base, lt, gsem, tile_m)
    mask = _valid_mask(gather_ids, base, tile_m)
    lhs = jnp.where(mask, lt[:, 0, :], 0).astype(rhs_ref.dtype)
    contrib = jax.lax.dot_general(
        lhs, rhs_ref[:], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )[None]
    init = jnp.logical_or(
        i == 0, te[i] != te[jnp.maximum(i - 1, 0)]
    )
    dw_ref[:] = jnp.where(
        init, contrib, dw_ref[:] + contrib
    ).astype(dw_ref.dtype)


def _dw_sorted_lhs_kernel(
    gather_ids, te, lhs_ref, rhs_hbm, dw_ref, rt, gsem, *, tile_m,
):
    """dw[te[i]] += lhs_tileᵀ @ gathered(rhs) — the mirrored variant
    (sorted lhs read as a regular block, rhs gathered per row)."""
    i = pl.program_id(0)
    base = i * tile_m
    _gather_rows(rhs_hbm, gather_ids, base, rt, gsem, tile_m)
    mask = _valid_mask(gather_ids, base, tile_m)
    rhs = jnp.where(mask, rt[:, 0, :], 0).astype(lhs_ref.dtype)
    contrib = jax.lax.dot_general(
        lhs_ref[:], rhs, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )[None]
    init = jnp.logical_or(
        i == 0, te[i] != te[jnp.maximum(i - 1, 0)]
    )
    dw_ref[:] = jnp.where(
        init, contrib, dw_ref[:] + contrib
    ).astype(dw_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def grouped_ffn(
    x, w_gu, w_down, row_ids, dest_ids, tile_expert,
    n_out: int, copies_per_src: int, tile_m: int, interpret: bool,
):
    """Fused dispatch-FFN over expert-sorted row segments.

    ``y[dest_ids[j]] = ffn(x[row_ids[j]], w_*[tile_expert[j // tile_m]])``
    for every non-padding slot ``j`` — gather, gate|up GEMM, silu·mul,
    down GEMM, and the unsorting scatter in ONE kernel. ``x`` is
    ``[n_src, d]`` token-major; the result is ``[n_out, d]``
    copy-major (callers combine the ``top_k`` copies densely).

    The custom VJP reuses ``row_ids``/``dest_ids`` verbatim: dx is a
    mirrored gather-GEMM-scatter (h/a recomputed flash-style, never
    stored), dw a pair of per-expert segment accumulations — no XLA
    scatter-of-gathers anywhere in fwd+bwd. Requires the invariant
    ``row_ids[j] == dest_ids[j] // copies_per_src`` (true for both the
    local sort layout and the ep exchange layout), which lets the VJP
    reduce the per-copy dx densely."""
    y, _ = _grouped_ffn_fwd(
        x, w_gu, w_down, row_ids, dest_ids, tile_expert,
        n_out, copies_per_src, tile_m, interpret,
    )
    return y


def _grouped_ffn_fwd(
    x, w_gu, w_down, row_ids, dest_ids, tile_expert,
    n_out, copies_per_src, tile_m, interpret,
):
    T = tile_expert.shape[0]
    d = x.shape[-1]
    two_f = w_gu.shape[-1]
    row_tile = pltpu.VMEM((tile_m, 1, d), jnp.float32)
    y = pl.pallas_call(
        functools.partial(_fwd_kernel, tile_m=tile_m),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(T,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(
                    (1, d, two_f), lambda i, ri, di, te: (te[i], 0, 0)
                ),
                pl.BlockSpec(
                    (1, two_f // 2, d),
                    lambda i, ri, di, te: (te[i], 0, 0),
                ),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                row_tile,
                row_tile,
                pltpu.SemaphoreType.DMA((tile_m,)),
                pltpu.SemaphoreType.DMA((tile_m,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n_out, 1, d), jnp.float32),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(row_ids, dest_ids, tile_expert, _dma_rows(x), w_gu, w_down)
    y = y.reshape(n_out, d).astype(x.dtype)
    return y, (x, w_gu, w_down, row_ids, dest_ids, tile_expert)


def _grouped_ffn_bwd(n_out, copies_per_src, tile_m, interpret, res, g):
    import numpy as np

    x, w_gu, w_down, row_ids, dest_ids, tile_expert = res
    T = tile_expert.shape[0]
    m_pad = T * tile_m
    n_src, d = x.shape
    two_f = w_gu.shape[-1]
    f = two_f // 2
    x3, g3 = _dma_rows(x), _dma_rows(g)
    row_tile = pltpu.VMEM((tile_m, 1, d), jnp.float32)
    dx_c, dh_sorted, a_sorted = pl.pallas_call(
        functools.partial(_dx_kernel, tile_m=tile_m),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(T,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(
                    (1, d, two_f), lambda i, ri, di, te: (te[i], 0, 0)
                ),
                pl.BlockSpec(
                    (1, f, d), lambda i, ri, di, te: (te[i], 0, 0)
                ),
            ],
            out_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(
                    (tile_m, two_f), lambda i, ri, di, te: (i, 0)
                ),
                pl.BlockSpec(
                    (tile_m, f), lambda i, ri, di, te: (i, 0)
                ),
            ],
            scratch_shapes=[
                row_tile,
                row_tile,
                row_tile,
                pltpu.SemaphoreType.DMA((tile_m,)),
                pltpu.SemaphoreType.DMA((tile_m,)),
                pltpu.SemaphoreType.DMA((tile_m,)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((n_out, 1, d), jnp.float32),
            jax.ShapeDtypeStruct((m_pad, two_f), x.dtype),
            jax.ShapeDtypeStruct((m_pad, f), x.dtype),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(row_ids, dest_ids, tile_expert, x3, g3, w_gu, w_down)
    e = w_gu.shape[0]
    dwgu = pl.pallas_call(
        functools.partial(_dw_accum_kernel, tile_m=tile_m),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(T,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(
                    (tile_m, two_f), lambda i, ri, te: (i, 0)
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, d, two_f), lambda i, ri, te: (te[i], 0, 0)
            ),
            scratch_shapes=[
                row_tile,
                pltpu.SemaphoreType.DMA((tile_m,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((e, d, two_f), jnp.float32),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(row_ids, tile_expert, x3, dh_sorted)
    dwdn = pl.pallas_call(
        functools.partial(_dw_sorted_lhs_kernel, tile_m=tile_m),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(T,),
            in_specs=[
                pl.BlockSpec(
                    (tile_m, f), lambda i, di, te: (i, 0)
                ),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(
                (1, f, d), lambda i, di, te: (te[i], 0, 0)
            ),
            scratch_shapes=[
                row_tile,
                pltpu.SemaphoreType.DMA((tile_m,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((e, f, d), jnp.float32),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(dest_ids, tile_expert, a_sorted, g3)
    # Per-copy dx reduces densely over the k copies of each source row
    # (the row_ids == dest_ids // copies invariant): no scatter.
    dx = jnp.sum(
        dx_c.reshape(n_src, copies_per_src, d), axis=1
    ).astype(x.dtype)
    return (
        dx,
        dwgu.astype(w_gu.dtype),
        dwdn.astype(w_down.dtype),
        np.zeros(row_ids.shape, jax.dtypes.float0),
        np.zeros(dest_ids.shape, jax.dtypes.float0),
        np.zeros(tile_expert.shape, jax.dtypes.float0),
    )


grouped_ffn.defvjp(_grouped_ffn_fwd, _grouped_ffn_bwd)
