"""Pallas TPU kernels for a KDA layer's per-token work AROUND the scan.

``models/hybrid.py``'s ``_kda_apply`` spells that work in ``jax.numpy``
(and those lines stay: they are the definition these kernels are held
to, and what runs off a TPU). Between a projection's output and the
scan's inputs, and between the scan's output and the output projection,
every step is per token and per head over ``[b, heads, s, 128]``:

- :func:`branch`: ``silu(causal K-tap convolution)`` of a projection's
  output, then for q and k the L2 norm over the head's 128 channels and
  a constant scale;
- :func:`decay_gate`: ``rate * softplus(z + dt_bias)``, the scan's log
  decay (``rate = -exp(a_log)``, a number a head);
- :func:`gated_norm`: ``rms_norm(o, o_norm) * sigmoid(z)`` of the scan's
  output, rounded to the compute dtype for the output projection.

As XLA stages them each is several float32 passes over 128 MB tensors,
and every row sum (the norms' sum of squares; ``sum(dy * y)`` in their
pullbacks) a fusion of its own that reads a tensor to write a column:
thirteen such fusions a layer at ~0.7 ms each on the chip, where their
bytes take 0.16. Here each function is ONE pass forward and ONE
backward: a grid step reads a ``[TILE, 128]`` tile of each input once,
walks it ``ROWS`` rows at a time, nothing between a pass's loads and
its stores leaving VMEM, and writes each output once (71-91 % of the
HBM peak at their bytes, kernel by kernel). All of it float32 (the taps,
the convolution's sum, SiLU, both norms, softplus, the gates, the row
sums), as the ``jax.numpy`` lines are; the inputs' and outputs' dtypes
are theirs (a projection's output comes as the compute dtype and its
cotangent goes back as that, the scan's tensors are float32).

The backward kernels keep no float32 residual: they read the same
inputs as the forward (a projection's output, which the layer's remat
policy keeps anyway, being a dot's) and form the forward's
intermediates again. Gradients of what is shared over the rows (the
taps, ``dt_bias``, the rate, ``o_norm``) are summed in an output block
that stays put along the grid's tile axis, ``[8, 128]`` partial sums in
registers inside a step, and over batch (and heads, for ``o_norm``) by
the caller.

The convolution looks back ``K - 1`` rows: a pass over a tile's first
rows reads them from a second view of the same array, the 16 rows
before the tile (one bfloat16 ``(16, 128)`` memory tile; zeros before
the first). Its transpose looks FORWARD ``K - 1`` rows of ``d_c``,
which exists only once the later rows' pullback has run: the backward
walks tiles, and rows inside a tile, from the last to the first, and
carries the 8 rows of ``d_c`` after the ones at hand (in registers
inside a step, in a VMEM scratch between steps). A sequence is padded
with zero rows to whole tiles by the caller, which changes nothing: a
zero row after the end convolves to zero, and its cotangent is zero.

A row sum is the XLU's lane reduction. The other candidate, a
contraction with a ``[128, 128]`` block of ones on the MXU at precision
HIGHEST (the sum comes back already broadcast over the lanes), was
timed beside it at the cell's shape and lost: one layer forward +
backward 35.9 ms against 34.3, and 34.6 as three bfloat16 passes
(PERF.md section 6, PR 58; inside a pass that is otherwise bound by its
bytes the XLU idles, while a float32 matmul's operand splitting puts
more on the vector slots than the reduction takes). ``TILE`` and
``ROWS`` from the same probe (``tools/bench_kda_layer.py``): 1,024 to
8,192 rows a step and 128 to 512 a pass read within 1 % of each other,
64 rows a pass 4 % slower.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
TILE = 4096         # rows a grid step
ROWS = 256          # rows a pass inside a step
HALO = 16           # rows read before a tile: one bfloat16 memory tile
L2_EPS = 1e-6       # ``models/hybrid._l2norm``'s
RMS_EPS = 1e-6      # ``ops/norms.rms_norm``'s default
_F32 = jnp.float32


def _row_sum(x):
    """``x [rows, 128]`` summed over its lanes, ``[rows, 1]``."""
    return jnp.sum(x, axis=-1, keepdims=True)


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


def _folded(x):
    """``x [rows, 128]`` summed down to ``[8, 128]``: adds of whole
    registers, the last 8 -> 1 left to the end of the step."""
    return jnp.sum(x.reshape(-1, 8, LANES), axis=0)


def _at(i, rows):
    """Pass ``i``'s rows of the step's tile."""
    return pl.ds(pl.multiple_of(i * rows, rows), rows)


def _rows_of(ref, i, rows):
    return ref[_at(i, rows), :]


def _add_folded(ref, accs):
    """``ref [n, 128] += `` each of ``accs``' ``[8, 128]`` summed."""
    ref[...] += jnp.concatenate(
        [jnp.sum(a, axis=0, keepdims=True) for a in accs], 0
    )


# -- the branch: silu(conv) and the L2 norm ----------------------------------


def _window(x_ref, before_tile, i, rows):
    """Rows ``[i * rows - 8, (i + 1) * rows)`` of the step's tile as
    float32 ``[8 + rows, 128]``; for the first pass the 8 before come
    from ``before_tile`` (the ``HALO`` rows before the tile, float32)."""
    start = pl.multiple_of(jnp.maximum(i * rows - HALO, 0), HALO)
    before = x_ref[pl.ds(start, HALO), :].astype(_F32)
    before = jnp.where(i == 0, before_tile, before)
    return jnp.concatenate(
        [before[HALO - 8:], _rows_of(x_ref, i, rows).astype(_F32)], 0
    )


def _taps_rows(window, width):
    """What tap ``j`` multiplies, for the window's last rows: row ``t``
    of entry ``j`` is ``x[t - (width - 1) + j]``."""
    return [
        pltpu.roll(window, width - 1 - j, 0)[8:] for j in range(width - 1)
    ] + [window[8:]]


def _branch_rows(x_rows, taps, scale):
    """The forward of one pass: ``(c, sigmoid(c), a, r)`` with ``a`` the
    SiLU of the convolution ``c`` and ``r`` what the norm multiplies it
    by (None without one)."""
    c = sum(taps[j:j + 1] * x for j, x in enumerate(x_rows))
    sig = _sigmoid(c)
    a = c * sig
    if scale is None:
        return c, sig, a, None
    return c, sig, a, jax.lax.rsqrt(_row_sum(a * a) + L2_EPS)


def _before_tile(prev_ref, tile):
    return jnp.where(tile > 0, prev_ref[...].astype(_F32), 0.0)


def _branch_fwd_kernel(x_ref, prev_ref, taps_ref, y_ref, *, scale, rows):
    before_tile = _before_tile(prev_ref, pl.program_id(1))
    taps = taps_ref[...]

    def one_pass(i, _):
        x_rows = _taps_rows(
            _window(x_ref, before_tile, i, rows), taps.shape[0]
        )
        _, _, a, r = _branch_rows(x_rows, taps, scale)
        y = a if scale is None else a * (r * scale)
        y_ref[_at(i, rows), :] = y
        return 0

    jax.lax.fori_loop(0, x_ref.shape[0] // rows, one_pass, 0)


def _branch_bwd_kernel(x_ref, prev_ref, taps_ref, dy_ref, dx_ref, dtaps_ref,
                       after_ref, *, scale, rows, n_tiles):
    step = pl.program_id(1)

    @pl.when(step == 0)
    def _():
        after_ref[...] = jnp.zeros_like(after_ref)
        dtaps_ref[...] = jnp.zeros_like(dtaps_ref)

    before_tile = _before_tile(prev_ref, n_tiles - 1 - step)
    taps = taps_ref[...]
    width = taps.shape[0]
    passes = x_ref.shape[0] // rows

    def one_pass(p, carried):
        after, accs = carried
        i = passes - 1 - p
        x_rows = _taps_rows(_window(x_ref, before_tile, i, rows), width)
        c, sig, a, r = _branch_rows(x_rows, taps, scale)
        d_a = _rows_of(dy_ref, i, rows)
        if scale is not None:
            d_a = d_a * scale
            d_a = r * (d_a - a * (r * r) * _row_sum(d_a * a))
        d_c = d_a * (sig * (1.0 + c * (1.0 - sig)))
        # dx_u = sum_j taps[j] d_c[u + (width - 1 - j)].
        ahead = jnp.concatenate([d_c, after], 0)
        d_x = taps[width - 1:] * d_c + sum(
            taps[j:j + 1]
            * pltpu.roll(ahead, rows + 8 - (width - 1 - j), 0)[:rows]
            for j in range(width - 1)
        )
        dx_ref[_at(i, rows), :] = d_x.astype(dx_ref.dtype)
        accs = tuple(
            acc + _folded(d_c * x) for acc, x in zip(accs, x_rows)
        )
        return d_c[:8], accs

    zeros = jnp.zeros((8, LANES), _F32)
    after, accs = jax.lax.fori_loop(
        0, passes, one_pass, (after_ref[...], (zeros,) * width)
    )
    after_ref[...] = after
    _add_folded(dtaps_ref, accs)


# -- the decay gate -----------------------------------------------------------


def _gate_fwd_kernel(z_ref, rate_ref, bias_ref, g_ref, *, rows):
    rate, bias = rate_ref[...], bias_ref[...]

    def one_pass(i, _):
        z = _rows_of(z_ref, i, rows).astype(_F32) + bias
        g_ref[_at(i, rows), :] = rate * _softplus(z)
        return 0

    jax.lax.fori_loop(0, z_ref.shape[0] // rows, one_pass, 0)


def _gate_bwd_kernel(z_ref, rate_ref, bias_ref, dg_ref, dz_ref, drate_ref,
                     dbias_ref, *, rows):
    @pl.when(pl.program_id(1) == 0)
    def _():
        drate_ref[...] = jnp.zeros_like(drate_ref)
        dbias_ref[...] = jnp.zeros_like(dbias_ref)

    rate, bias = rate_ref[...], bias_ref[...]

    def one_pass(i, accs):
        z = _rows_of(z_ref, i, rows).astype(_F32) + bias
        d_g = _rows_of(dg_ref, i, rows)
        d_z = (d_g * rate) * _sigmoid(z)
        dz_ref[_at(i, rows), :] = d_z.astype(dz_ref.dtype)
        return (
            accs[0] + _folded(d_g * _softplus(z)), accs[1] + _folded(d_z)
        )

    zeros = jnp.zeros((8, LANES), _F32)
    accs = jax.lax.fori_loop(
        0, z_ref.shape[0] // rows, one_pass, (zeros, zeros)
    )
    _add_folded(drate_ref, accs[:1])
    _add_folded(dbias_ref, accs[1:])


# -- the gated norm on the way out --------------------------------------------


def _out_rows(o_ref, z_ref, i, rows):
    """``(o's RMS-normed rows before the weight, sigmoid(z), what the
    norm multiplied o by)`` of one pass."""
    o = _rows_of(o_ref, i, rows)
    r = jax.lax.rsqrt(_row_sum(o * o) * (1.0 / LANES) + RMS_EPS)
    return o * r, _sigmoid(_rows_of(z_ref, i, rows).astype(_F32)), r


def _out_fwd_kernel(o_ref, z_ref, scale_ref, y_ref, *, rows):
    weight = 1.0 + scale_ref[...]

    def one_pass(i, _):
        normed, gate, _ = _out_rows(o_ref, z_ref, i, rows)
        y_ref[_at(i, rows), :] = (
            (normed * weight) * gate
        ).astype(y_ref.dtype)
        return 0

    jax.lax.fori_loop(0, o_ref.shape[0] // rows, one_pass, 0)


def _out_bwd_kernel(o_ref, z_ref, scale_ref, dy_ref, do_ref, dz_ref,
                    dscale_ref, *, rows):
    @pl.when(pl.program_id(1) == 0)
    def _():
        dscale_ref[...] = jnp.zeros_like(dscale_ref)

    weight = 1.0 + scale_ref[...]

    def one_pass(i, acc):
        normed, gate, r = _out_rows(o_ref, z_ref, i, rows)
        d_y = _rows_of(dy_ref, i, rows).astype(_F32)
        dz_ref[_at(i, rows), :] = (
            d_y * (normed * weight) * (gate * (1.0 - gate))
        ).astype(dz_ref.dtype)
        d_scaled = d_y * gate
        d_normed = d_scaled * weight
        do_ref[_at(i, rows), :] = r * (
            d_normed - normed * (_row_sum(d_normed * normed) * (1.0 / LANES))
        )
        return acc + _folded(d_scaled * normed)

    acc = jax.lax.fori_loop(
        0, o_ref.shape[0] // rows, one_pass, jnp.zeros((8, LANES), _F32)
    )
    _add_folded(dscale_ref, (acc,))


# -- the calls ----------------------------------------------------------------

_PARAMS = dict(
    compiler_params=pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=64 * 1024 * 1024,
    ),
)


def _form():
    """``(rows a tile, rows a pass)`` as the module's constants stand:
    read by the public functions a call and handed down as a static
    argument, so that a jitted call is traced for the tiling it runs
    (the probe and the tests move the constants)."""
    return TILE, ROWS


def _tiling(s, form):
    """``(rows a tile, tiles)`` for ``s`` rows."""
    tile = min(form[0], -(-s // form[1]) * form[1])
    return tile, -(-s // tile)


def _call(kernel, name, form, shape, ins, outs, interpret, backwards=False,
          scratch=(), **static):
    """One ``pallas_call`` over ``[b, h, s, 128]`` (``shape``; ``s`` a
    whole number of tiles), grid ``(b x h, tiles)``, the tiles walked
    from the last when ``backwards``. ``ins`` / ``outs``: ``(kind,
    array or dtype)`` with kind ``"rows"`` (``[b, h, s, 128]``, a tile
    a step), ``"before"`` (the same array, the ``HALO`` rows before the
    tile), ``"head"`` (``[h, n, 128]``, the head's block) or ``"all"``
    (``[n, 128]``, whole) for an input, ``"rows"`` or an int ``n`` (a
    ``[b, h, n, 128]`` sum a step adds to) for an output."""
    b, h, s, _ = shape
    tile, n_tiles = _tiling(s, form)
    if backwards:
        static["n_tiles"] = n_tiles
    at = (lambda t: n_tiles - 1 - t) if backwards else (lambda t: t)
    specs = {
        "rows": lambda a: pl.BlockSpec(
            (None, None, tile, LANES),
            lambda i, t: (i // h, i % h, at(t), 0),
        ),
        "before": lambda a: pl.BlockSpec(
            (None, None, HALO, LANES),
            lambda i, t: (
                i // h, i % h, jnp.maximum(at(t) * (tile // HALO) - 1, 0), 0
            ),
        ),
        "head": lambda a: pl.BlockSpec(
            (None,) + a.shape[1:], lambda i, t: (i % h, 0, 0)
        ),
        "all": lambda a: pl.BlockSpec(a.shape, lambda i, t: (0, 0)),
    }
    out_specs, out_shape = [], []
    for kind, dtype in outs:
        if kind == "rows":
            out_specs.append(specs["rows"](None))
            out_shape.append(jax.ShapeDtypeStruct(shape, dtype))
        else:
            out_specs.append(pl.BlockSpec(
                (None, None, kind, LANES), lambda i, t: (i // h, i % h, 0, 0)
            ))
            out_shape.append(
                jax.ShapeDtypeStruct((b, h, kind, LANES), dtype)
            )
    return pl.pallas_call(
        functools.partial(kernel, rows=form[1], **static),
        grid=(b * h, n_tiles),
        in_specs=[specs[kind](a) for kind, a in ins],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=list(scratch),
        name=name,
        interpret=interpret,
        **({} if interpret else _PARAMS),
    )(*(a for _, a in ins))


def _whole_tiles(fn, form, xs, *rest):
    """``fn(*xs, *rest)`` with ``xs [b, h, s, 128]`` zero-padded to whole
    tiles of rows and the result cut back to ``s``."""
    s = xs[0].shape[2]
    tile, n_tiles = _tiling(s, form)
    pad = n_tiles * tile - s
    if pad:
        xs = [jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0))) for x in xs]
    return fn(*xs, *rest)[:, :, :s]


def _jit(n_static):
    """``jax.jit`` with the first ``n_static`` arguments static: a call
    is then traced, and its kernel lowered, once a signature and not
    once a call site (60 in the cell's step, of 10 signatures)."""
    return functools.partial(jax.jit, static_argnums=tuple(range(n_static)))


def _by_head(taps):
    return jnp.moveaxis(taps.astype(_F32), 0, 1)


def branch(x, taps, scale=None, interpret=False):
    """``silu(causal convolution of x by taps)``, and with ``scale`` its
    L2 norm over the lanes times ``scale``: ``x [b, h, s, 128]`` (a
    projection's output, any float dtype), ``taps [K, h, 128]`` float32
    -> float32. What ``_kda_apply`` writes as ``_l2norm(silu(
    _short_conv(x, taps))) * scale``."""
    assert taps.shape[0] - 1 <= 8, taps.shape
    form = _form()
    return _whole_tiles(
        functools.partial(_branch, form, scale, interpret), form, [x], taps
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
@_jit(3)
def _branch(form, scale, interpret, x, taps):
    return _call(
        _branch_fwd_kernel, "kda_branch_fwd", form, x.shape,
        [("rows", x), ("before", x), ("head", _by_head(taps))],
        [("rows", _F32)], interpret, scale=scale,
    )[0]


def _branch_fwd(form, scale, interpret, x, taps):
    return _branch(form, scale, interpret, x, taps), (x, taps)


@_jit(3)
def _branch_bwd(form, scale, interpret, res, d_y):
    x, taps = res
    d_x, d_taps = _call(
        _branch_bwd_kernel, "kda_branch_bwd", form, x.shape,
        [("rows", x), ("before", x), ("head", _by_head(taps)),
         ("rows", d_y)],
        [("rows", x.dtype), (taps.shape[0], _F32)], interpret,
        backwards=True, scratch=[pltpu.VMEM((8, LANES), _F32)],
        scale=scale,
    )
    d_taps = jnp.moveaxis(jnp.sum(d_taps, axis=0), 0, 1)
    return d_x, d_taps.astype(taps.dtype)


_branch.defvjp(_branch_fwd, _branch_bwd)


def decay_gate(z, a_log, dt_bias, interpret=False):
    """The scan's log decay ``-exp(a_log) * softplus(z + dt_bias)``:
    ``z [b, h, s, 128]`` (the gate's projection), ``a_log [h]``,
    ``dt_bias [h, 128]`` -> float32."""
    h = a_log.shape[0]
    rate = jnp.broadcast_to(-jnp.exp(a_log)[:, None, None], (h, 1, LANES))
    form = _form()
    return _whole_tiles(
        functools.partial(_gate, form, interpret), form, [z], rate,
        dt_bias[:, None, :],
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
@_jit(2)
def _gate(form, interpret, z, rate, bias):
    return _call(
        _gate_fwd_kernel, "kda_gate_fwd", form, z.shape,
        [("rows", z), ("head", rate), ("head", bias)],
        [("rows", _F32)], interpret,
    )[0]


def _gate_fwd(form, interpret, z, rate, bias):
    return _gate(form, interpret, z, rate, bias), (z, rate, bias)


@_jit(2)
def _gate_bwd(form, interpret, res, d_g):
    z, rate, bias = res
    d_z, d_rate, d_bias = _call(
        _gate_bwd_kernel, "kda_gate_bwd", form, z.shape,
        [("rows", z), ("head", rate), ("head", bias), ("rows", d_g)],
        [("rows", z.dtype), (1, _F32), (1, _F32)], interpret,
    )
    return d_z, jnp.sum(d_rate, axis=0), jnp.sum(d_bias, axis=0)


_gate.defvjp(_gate_fwd, _gate_bwd)


def gated_norm(o, z, scale, dtype, interpret=False):
    """``(rms_norm(o, scale) * sigmoid(z)).astype(dtype)``: ``o [b, h,
    s, 128]`` float32 (the scan's output), ``z`` the same shape (the
    output gate's projection), ``scale [128]`` (``ops/norms.rms_norm``'s
    (1 + scale), eps 1e-6)."""
    form = _form()
    return _whole_tiles(
        functools.partial(_out, form, jnp.dtype(dtype), interpret), form,
        [o, z], scale.astype(_F32)[None, :],
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
@_jit(3)
def _out(form, dtype, interpret, o, z, scale):
    return _call(
        _out_fwd_kernel, "kda_out_fwd", form, o.shape,
        [("rows", o), ("rows", z), ("all", scale)],
        [("rows", dtype)], interpret,
    )[0]


def _out_fwd(form, dtype, interpret, o, z, scale):
    return _out(form, dtype, interpret, o, z, scale), (o, z, scale)


@_jit(3)
def _out_bwd(form, dtype, interpret, res, d_y):
    o, z, scale = res
    d_o, d_z, d_scale = _call(
        _out_bwd_kernel, "kda_out_bwd", form, o.shape,
        [("rows", o), ("rows", z), ("all", scale), ("rows", d_y)],
        [("rows", o.dtype), ("rows", z.dtype), (1, _F32)], interpret,
    )
    return d_o, d_z, jnp.sum(d_scale, axis=(0, 1))


_out.defvjp(_out_fwd, _out_bwd)
