"""Pallas TPU kernels for the chunked delta-rule scan of ``ops/kda.py``.

The equations are that module's; what changes is where a chunk's
tensors live. Grid ``(batch x heads / 4, chunks)``: the first axis is
parallel, the second walks the sequence in order (a TPU grid runs
sequentially), so the ``dk x dv`` state -- in the backward its
cotangent -- is carried in a VMEM scratch from one chunk to the next
(transposed, ``[dv, dk]``: a channel's decay then runs along the lanes
and the decay to the chunk's end is a row broadcast). A grid step reads
its chunk's ``[64, 128]`` tiles of ``q, k, g, v`` and its 64 ``beta``
once (the backward also ``dO`` and the state that entered the chunk),
and everything the ``jax.numpy`` form writes to HBM between two fusions
-- the cumulated gate, the row and column factors, the blocks of ``A``
and ``P``, the inverse ``T``, ``U`` -- is formed, used and dropped in
VMEM. What crosses HBM: the five inputs, ``O`` and one entering state a
chunk in the forward; those, ``dO`` and the five gradients in the
backward.

A step works on ``HEADS_A_STEP`` heads, vmapped inside the kernel body.
A chunk is a chain of dependent matmuls (blocks -> inverse -> ``U`` ->
``O``), each waiting on the MXU's latency; the heads' chains are
independent, and vmap issues each op for all of them before the next,
so the scheduler fills one's wait with another's work (on the chip a
layer's forward took 7.5 ms a head a step, 5.2 at four, 4.9 at eight).

Nothing can overflow here either: a block of ``A`` / ``P`` below the
diagonal is a matmul of rows scaled by ``exp(G_t - R)`` and columns by
``exp(R - G_i)``, ``R`` the cumulated decay at the row sub-chunk's
start and the columns only those before it (both exponents <= 0); the
sub-chunks' own 16 x 16 blocks are summed channel by channel with the
exponent of every pair ``i > t`` masked to ``-inf``. Every product is
float32 at precision HIGHEST (Mosaic's ``contract_precision<fp32>``).

The unit lower-triangular system is solved as in ``ops/kda.py``, by
block forward substitution: the four 16 x 16 diagonal blocks a column
a step (all four at once, as one block-diagonal ``[64, 64]``), then
the blocks below them from those inverses.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64
SUB = 16
N_SUB = CHUNK // SUB
LANES = 128
HEADS_A_STEP = 4
_HIGHEST = jax.lax.Precision.HIGHEST
_F32 = jnp.float32

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(
        a, b, dims, precision=_HIGHEST, preferred_element_type=_F32
    )


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _shifted_sum(x, shifts, keep):
    """``x [heads, 64, n]`` plus itself moved down its rows by each of
    ``shifts`` in turn (rows that ``keep(row, shift)`` refuses get
    nothing): a running sum in log steps."""
    row = _iota(x.shape, 1)
    for shift in shifts:
        x = x + jnp.where(
            keep(row, shift), pltpu.roll(x, shift % CHUNK, 1), 0.0
        )
    return x


def _cumulated_gate(g):
    """The gate ``[heads, 64, n]`` cumulated from the start of each
    token's sub-chunk, and the decay cumulated before that sub-chunk
    (the same for its 16 rows); their sum is the decay cumulated from
    the chunk's start. Kept apart because every exponent below is a
    difference of cumulated decays, and most are differences inside a
    sub-chunk: taken there, of numbers a quarter the size, they round
    finer (against a float64 recurrence the kernels read 1.5-8e-6 by
    norm where the ``jax.numpy`` form reads 2.5e-6 to 1.2e-5)."""
    heads, _, n = g.shape
    inside = _shifted_sum(
        g, (1, 2, 4, 8), lambda row, shift: row % SUB >= shift
    )
    total = inside.reshape(heads, N_SUB, SUB, n)[:, :, SUB - 1:, :]
    total = jnp.broadcast_to(total, (heads, N_SUB, SUB, n)).reshape(g.shape)
    row = _iota(g.shape, 1)
    before = sum(
        jnp.where(row >= shift, pltpu.roll(total, shift, 1), 0.0)
        for shift in range(SUB, CHUNK, SUB)
    )
    return inside, before


def _summed_to_the_end(x):
    """``x [heads, 64, n]`` summed from each row to the last: the
    pullback of a running sum from the first."""
    return _shifted_sum(
        x, (-1, -2, -4, -8, -16, -32),
        lambda row, shift: row < CHUNK + shift,
    )


def _sub_row(x, i):
    """Row ``i`` of every sub-chunk of ``x [64, n]``, repeated over its
    sub-chunk's rows."""
    n = x.shape[-1]
    x = x.reshape(N_SUB, SUB, n)[:, i:i + 1, :]
    return jnp.broadcast_to(x, (N_SUB, SUB, n)).reshape(CHUNK, n)


def _as_column(row):
    """``[1, n]`` -> ``[n, 1]`` without a transpose."""
    n = row.shape[-1]
    eye = _iota((n, n), 0) == _iota((n, n), 1)
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _as_row(col):
    n = col.shape[0]
    eye = _iota((n, n), 0) == _iota((n, n), 1)
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _chunk_algebra(q, k, g_in, g_before, v, beta_row, state_t, need_a):
    """A chunk's forward algebra from its inputs (the gate as
    :func:`_cumulated_gate` gives it) and entering state
    (held transposed, ``[dv, dk]``, so that a channel's decay runs along
    the lanes): everything the output, the next state and the pullback
    are made of."""
    rows = _iota((CHUNK, CHUNK), 0)
    cols = _iota((CHUNK, CHUNK), 1)
    local = _iota((CHUNK, 1), 0) % SUB              # row inside its sub-chunk
    own = cols - (rows // SUB) * SUB                # column inside the row's
    beta_col = _as_column(beta_row)

    g_cum = g_in + g_before
    grow = jnp.exp(g_cum)
    g_end = g_cum[CHUNK - 1:CHUNK]
    shrink = jnp.exp(g_end - g_cum)

    # Below the diagonal: row sub-chunk s against the columns before it.
    zeros = jnp.zeros((SUB, CHUNK), _F32)
    below, a_off, p_off = [], [zeros], [zeros]
    for s in range(1, N_SUB):
        lo = s * SUB
        rf = jnp.exp(g_in[lo:lo + SUB])
        cf = jnp.exp(jnp.minimum(
            (g_before[lo:lo + 1] - g_before) - g_in, 0.0
        ))
        before = _iota((2 * SUB, CHUNK), 1) < lo
        row_s = jnp.concatenate(
            [k[lo:lo + SUB] * rf, q[lo:lo + SUB] * rf], 0
        )
        col_s = k * cf
        off = jnp.where(before, _dot(row_s, col_s, _NT), 0.0)
        below.append((lo, before, rf, cf, row_s, col_s))
        a_off.append(off[:SUB])
        p_off.append(off[SUB:])
    a_off = jnp.concatenate(a_off, 0)
    p_off = jnp.concatenate(p_off, 0)

    # On it: column i of all four sub-chunks' own blocks a step, summed
    # channel by channel, and with it step i of their forward
    # substitution, (I + N) T = I: row i of T is final, and rows below
    # it lose N[t, i] times it.
    inv = (rows == cols).astype(_F32)
    p_diag = jnp.zeros((CHUNK, CHUNK), _F32)
    a_diag = jnp.zeros((CHUNK, CHUNK), _F32)
    for i in range(SUB):
        decay = jnp.exp(
            jnp.where(local >= i, g_in - _sub_row(g_in, i), -jnp.inf)
        )
        col_i = _sub_row(k, i) * decay
        kk = jnp.where(
            local > i, jnp.sum(k * col_i, axis=1, keepdims=True), 0.0
        )
        qk = jnp.sum(q * col_i, axis=1, keepdims=True)
        p_diag = jnp.where(own == i, qk, p_diag)
        if need_a:
            a_diag = jnp.where(own == i, kk, a_diag)
        inv = inv - (beta_col * kk) * _sub_row(inv, i)

    # The blocks below them, by block forward substitution. With T1 the
    # four inverses and N the rest of diag(beta) A, M = T1 N is strictly
    # block-lower, M^4 = 0, and (I + M)^-1 T1 = (I + M^2)(I - M) T1: the
    # pairwise merges' products, in a chain one matmul shorter.
    n_off = beta_col * a_off
    m = _dot(inv, n_off)
    first = inv - _dot(m, inv)
    inv = first + _dot(_dot(m, m), first)

    t_beta = inv * beta_row
    k_in = k * grow
    q_in = q * grow
    from_state = _dot(jnp.concatenate([k_in, q_in], 0), state_t, _NT)
    x = v - from_state[:CHUNK]
    u = _dot(t_beta, x)
    return dict(
        grow=grow, shrink=shrink, g_end=g_end,
        below=below, local=local, own=own,
        beta_col=beta_col, a=a_off + a_diag, p=p_off + p_diag,
        inv=inv, t_beta=t_beta, k_in=k_in, q_in=q_in, k_out=k * shrink,
        x=x, u=u, o_state=from_state[CHUNK:],
    )


def _fwd_chunk(q, k, g_in, g_before, v, beta_row, state_t):
    c = _chunk_algebra(q, k, g_in, g_before, v, beta_row, state_t, False)
    # S' = diag(e^G_C) S + K_out^T U, transposed.
    new_t = jnp.exp(c["g_end"]) * state_t + _dot(c["u"], c["k_out"], _TN)
    return c["o_state"] + _dot(c["p"], c["u"]), new_t


def _fwd_kernel(q_ref, k_ref, g_ref, v_ref, beta_ref, o_ref, states_ref,
                state_ref):
    @pl.when(pl.program_id(1) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    state_t = state_ref[...]
    states_ref[...] = state_t
    # Over the step's heads by vmap: every op is issued for all of them
    # before the next one, so their independent chains interleave.
    o_ref[...], state_ref[...] = jax.vmap(_fwd_chunk)(
        q_ref[...], k_ref[...], *_cumulated_gate(g_ref[...]), v_ref[...],
        beta_ref[...], state_t,
    )


def _bwd_kernel(q_ref, k_ref, g_ref, v_ref, beta_ref, states_ref, do_ref,
                dq_ref, dk_ref, dg_ref, dv_ref, dbeta_ref, dstate_ref):
    @pl.when(pl.program_id(1) == 0)
    def _():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)

    (dq_ref[...], dk_ref[...], d_g_cum, dv_ref[...], dbeta_ref[...],
     dstate_ref[...]) = jax.vmap(_bwd_chunk)(
        q_ref[...], k_ref[...], *_cumulated_gate(g_ref[...]), v_ref[...],
        beta_ref[...], states_ref[...], do_ref[...], dstate_ref[...],
    )
    # G is g cumulated: its pullback sums from a token to the chunk's end.
    dg_ref[...] = _summed_to_the_end(d_g_cum)


def _bwd_chunk(q, k, g_in, g_before, v, beta_row, state_t, d_o, d_next_t):
    """A chunk's pullback: ``d_next_t`` is the cotangent of the state it
    leaves (transposed, ``[dv, dk]``); returns the cotangents of ``(q,
    k, the cumulated gate, v, beta, the state it entered with)``."""
    c = _chunk_algebra(q, k, g_in, g_before, v, beta_row, state_t, True)
    rows = _iota((CHUNK, CHUNK), 0)
    cols = _iota((CHUNK, CHUNK), 1)
    local, own, beta_col = c["local"], c["own"], c["beta_col"]
    inv, u, x = c["inv"], c["u"], c["x"]

    # O = Q_in S + P U;  S' = diag(e^G_C) S + K_out^T U;  U = T_beta X.
    d_u = _dot(c["p"], d_o, _TN) + _dot(c["k_out"], d_next_t, _NT)
    d_p = jnp.where(rows >= cols, _dot(d_o, u, _NT), 0.0)
    d_k_out = _dot(u, d_next_t)
    d_t_beta = _dot(d_u, x, _NT)
    d_x = _dot(c["t_beta"], d_u, _TN)
    into_state = jnp.concatenate([d_o, -d_x], 0)
    d_in = _dot(into_state, state_t)                # d(Q_in) over d(K_in)
    d_q_in, d_k_in = d_in[:CHUNK], d_in[CHUNK:]
    decay = jnp.exp(c["g_end"])
    d_state_t = decay * d_next_t + _dot(
        into_state, jnp.concatenate([c["q_in"], c["k_in"]], 0), _TN
    )
    # d(G_C), a row over the channels: e^G_C * sum_v dS' * S, and K_out's.
    d_g_end = decay * jnp.sum(
        d_next_t * state_t, axis=0, keepdims=True
    ) + jnp.sum(d_k_out * c["k_out"], axis=0, keepdims=True)

    # T_beta = T diag(beta);  T = (I + N)^-1;  N = diag(beta) A.
    d_beta = jnp.sum(d_t_beta * inv, axis=0, keepdims=True)
    d_n = jnp.where(
        rows > cols,
        -_dot(_dot(inv, d_t_beta * beta_row, _TN), inv, _NT), 0.0,
    )
    d_beta = d_beta + _as_row(jnp.sum(d_n * c["a"], axis=1, keepdims=True))
    d_a = beta_col * d_n

    # The blocks below the diagonal: rows [K_s; Q_s] e^(G - R) against
    # columns K e^(R - G); R's own gradient cancels between the two.
    d_k = d_k_in * c["grow"] + d_k_out * c["shrink"]
    d_q = d_q_in * c["grow"]
    d_g = d_k_in * c["k_in"] + d_q_in * c["q_in"] - d_k_out * c["k_out"]
    dk_rows = [jnp.zeros((SUB, LANES), _F32)]
    dq_rows = [jnp.zeros((SUB, LANES), _F32)]
    dg_rows = [jnp.zeros((SUB, LANES), _F32)]
    for lo, before, rf, cf, row_s, col_s in c["below"]:
        d_off = jnp.where(before, jnp.concatenate(
            [d_a[lo:lo + SUB], d_p[lo:lo + SUB]], 0
        ), 0.0)
        d_row = _dot(d_off, col_s)                      # [2c, dk]
        d_col = _dot(d_off, row_s, _TN)                 # [C, dk]
        dk_rows.append(d_row[:SUB] * rf)
        dq_rows.append(d_row[SUB:] * rf)
        weighed = d_row * row_s
        dg_rows.append(weighed[:SUB] + weighed[SUB:])
        d_k = d_k + d_col * cf
        d_g = d_g - d_col * col_s
    d_k = d_k + jnp.concatenate(dk_rows, 0)
    d_q = d_q + jnp.concatenate(dq_rows, 0)
    d_g = d_g + jnp.concatenate(dg_rows, 0)

    # The sub-chunks' own blocks, a column a step: with E = e^(G_t - G_i),
    # dx_t = sum_i dD_ti k_i E, dk_i = sum_t dD_ti x_t E and the gates'
    # gradient x_t dx_t - k_i dk_i.
    dk_t = jnp.zeros((CHUNK, LANES), _F32)
    dq_t = jnp.zeros((CHUNK, LANES), _F32)
    dk_i = jnp.zeros((CHUNK, LANES), _F32)
    for i in range(SUB):
        e = jnp.exp(
            jnp.where(local >= i, g_in - _sub_row(g_in, i), -jnp.inf)
        )
        col_i = _sub_row(k, i) * e
        d_kk = jnp.sum(jnp.where(own == i, d_a, 0.0), 1, keepdims=True)
        d_qk = jnp.sum(jnp.where(own == i, d_p, 0.0), 1, keepdims=True)
        dk_t = dk_t + d_kk * col_i
        dq_t = dq_t + d_qk * col_i
        into_col = (d_kk * k + d_qk * q) * e            # [C, dk], rows t
        summed = jnp.sum(
            into_col.reshape(N_SUB, SUB, LANES), axis=1, keepdims=True
        )
        summed = jnp.broadcast_to(
            summed, (N_SUB, SUB, LANES)
        ).reshape(CHUNK, LANES)
        dk_i = jnp.where(local == i, summed, dk_i)
    d_k = d_k + dk_t + dk_i
    d_q = d_q + dq_t
    d_g = d_g + k * dk_t + q * dq_t - k * dk_i
    last = _iota((CHUNK, 1), 0) == CHUNK - 1
    d_g = d_g + jnp.where(last, d_g_end, 0.0)
    return d_q, d_k, d_g, d_x, d_beta, d_state_t


_PARAMS = dict(
    compiler_params=pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=64 * 1024 * 1024,
    ),
)


def _heads_a_step(heads):
    """Heads a grid step works on (the module docstring says why)."""
    return next(n for n in (HEADS_A_STEP, 2, 1) if heads % n == 0)


def _specs(heads, chunk_of):
    """The block of a ``[b, h, s, 128]`` array and of a ``[b, h, n, rows,
    cols]`` one that grid step ``(i, c)`` works on: heads group ``i`` of
    the ``b x h / group`` groups, chunk ``chunk_of(c)``."""
    group = _heads_a_step(heads)
    groups = heads // group

    def tokens():
        return pl.BlockSpec(
            (None, group, CHUNK, LANES),
            lambda i, c: (i // groups, i % groups, chunk_of(c), 0),
        )

    def a_chunk(rows, cols):
        return pl.BlockSpec(
            (None, group, None, rows, cols),
            lambda i, c: (i // groups, i % groups, chunk_of(c), 0, 0),
        )

    return group, tokens, a_chunk


@functools.partial(jax.jit, static_argnames=("interpret",))
def scan_forward(q, k, g, v, beta, interpret=False):
    """``q, k, g, v [b, h, s, 128]`` and ``beta [b, h, s]`` (``s`` a
    multiple of the chunk, float32) -> the output ``[b, h, s, 128]`` and
    the state entering each chunk, transposed (``[dv, dk]``):
    ``[b, h, n, 128, 128]``."""
    b, h, s, _ = k.shape
    n = s // CHUNK
    group, tokens, a_chunk = _specs(h, lambda c: c)
    return pl.pallas_call(
        _fwd_kernel,
        grid=(b * h // group, n),
        in_specs=[tokens()] * 4 + [a_chunk(1, CHUNK)],
        out_specs=[tokens(), a_chunk(LANES, LANES)],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, LANES), _F32),
            jax.ShapeDtypeStruct((b, h, n, LANES, LANES), _F32),
        ],
        scratch_shapes=[pltpu.VMEM((group, LANES, LANES), _F32)],
        name="kda_scan_fwd",
        interpret=interpret,
        **({} if interpret else _PARAMS),
    )(q, k, g, v, beta.reshape(b, h, n, 1, CHUNK))


@functools.partial(jax.jit, static_argnames=("interpret",))
def scan_backward(q, k, g, v, beta, states, d_o, interpret=False):
    """The pullback of :func:`scan_forward`'s output: ``(dq, dk, dg, dv,
    dbeta)``, the chunks walked from the last to the first."""
    b, h, s, _ = k.shape
    n = s // CHUNK
    group, tokens, a_chunk = _specs(h, lambda c: n - 1 - c)
    like_tokens = jax.ShapeDtypeStruct((b, h, s, LANES), _F32)
    *grads, d_beta = pl.pallas_call(
        _bwd_kernel,
        grid=(b * h // group, n),
        in_specs=[tokens()] * 4 + [
            a_chunk(1, CHUNK), a_chunk(LANES, LANES), tokens(),
        ],
        out_specs=[tokens()] * 4 + [a_chunk(1, CHUNK)],
        out_shape=[like_tokens] * 4 + [
            jax.ShapeDtypeStruct((b, h, n, 1, CHUNK), _F32)
        ],
        scratch_shapes=[pltpu.VMEM((group, LANES, LANES), _F32)],
        name="kda_scan_bwd",
        interpret=interpret,
        **({} if interpret else _PARAMS),
    )(q, k, g, v, beta.reshape(b, h, n, 1, CHUNK), states, d_o)
    return (*grads, d_beta.reshape(b, h, s))
