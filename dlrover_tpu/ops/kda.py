"""Gated delta-rule linear attention (KDA), chunked.

Per head, with ``q_t, k_t`` in R^dk, ``v_t`` in R^dv, a per-channel log
decay ``g_t <= 0`` (``alpha_t = exp(g_t)``) and a scalar ``beta_t``:

    S_t = (I - beta_t k_t k_t^T) diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                      S_0 = 0,  S in R^{dk x dv}

:func:`kda_recurrent` is that recurrence, a token a step. The training
path is :func:`kda_chunked`, the WY / UT-transform form: with
``u_t = beta_t (v_t - S_{t-1}^T (alpha_t * k_t))`` the update is
``S_t = diag(alpha_t) S_{t-1} + k_t u_t^T``, and inside a chunk of ``C``
tokens that enters with state ``S`` (``G_t`` the decay cumulated from the
chunk's start)

    (I + diag(beta) A) U = diag(beta) (V - (K * exp(G)) S)
    A_ti = sum_c k_tc k_ic exp(G_tc - G_ic)      (i < t)
    O    = (Q * exp(G)) S + P U
    P_ti = sum_c q_tc k_ic exp(G_tc - G_ic)      (i <= t)
    S'   = diag(exp(G_C)) S + (K * exp(G_C - G))^T U

so everything but ``S`` is computed for all chunks at once; substituting
``U`` gives ``S' = M S + B`` with ``M = diag(exp(G_C)) - (K * exp(G_C -
G))^T W`` and only that ``dk x dk`` product walks the sequence
(``lax.scan`` over chunks; reverse mode keeps one state a chunk).

A decay is per channel, so ``exp(G_t - G_i)`` does not factor into one
matmul without ``exp(-G_i)``, which overflows float32 once a chunk
forgets more than e^88. As the public implementation does, a chunk is
cut into sub-chunks of ``c`` tokens: a block of ``A`` / ``P`` below the
diagonal is a matmul of rows scaled by ``exp(G_t - R)`` and columns by
``exp(R - G_i)``, ``R`` the cumulated decay at the ROW sub-chunk's
start, both factors <= 1; a diagonal block is summed channel by channel
with its exponents masked to <= 0. Nothing here can overflow whatever
the gates say.

Everything runs in float32 (``PRECISION`` for every matmul: on a TPU a
float32 matmul is one bf16 pass unless asked otherwise); the unit
triangular system is solved by (block) forward substitution with its
inverse's closed form as the backward.

Two ways of staging the one set of equations, chosen from what
:func:`kda_chunked` can observe (:func:`kda_scan_kind`: platform, head
size, mesh -- no switch): on a TPU at the published head size (keys and
values one 128-lane tile) the Pallas kernels of ``ops/kda_kernels.py``,
forward and backward, which keep a chunk's tensors in VMEM and carry
the state (its cotangent) across the grid's chunk axis, so that the
``S' = M S + B`` rewriting and the ``lax.scan`` are not needed there;
anywhere else the ``jax.numpy`` form of this file
(:func:`kda_chunked_xla`), which is also the definition the kernels are
tested against. ``head_groups`` bounds the ``jax.numpy`` form's live
chunk tensors only: the kernels have none in HBM and walk the heads in
their grid.
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from dlrover_tpu.ops import kda_kernels
from dlrover_tpu.parallel.sharding import current_mesh

PRECISION = jax.lax.Precision.HIGHEST
# One pair of sizes for both forms (the kernels are written for them).
CHUNK = kda_kernels.CHUNK       # tokens a chunk (the state's stride): 64
SUB_CHUNK = kda_kernels.SUB     # a sub-chunk (the exact diagonal blocks): 16
GROUP_TOKENS = 65536   # head-tokens walked at once (``head_groups``)


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=PRECISION)


# -- the unit lower-triangular inverse --------------------------------------

LEAF = 16  # rows solved by substitution before blocks are merged


def _substitute(n):
    """``(I + n)^-1`` for strictly lower ``n [B, c, c]`` by forward
    substitution, ``T[t] = e_t - sum_{i<t} n[t, i] T[i]``, unrolled, the
    batch in the lanes (``[c, c, B]``) so that every step is a dense
    elementwise pass over small arrays and nothing is updated in place."""
    c = n.shape[-1]
    x = jnp.moveaxis(n, 0, -1)                           # [c, c, B]
    eye = jnp.eye(c, dtype=n.dtype)[:, :, None]
    rows = []
    for t in range(c):
        row = jnp.broadcast_to(eye[t], x.shape[1:])      # [c, B]
        for i in range(t):
            row = row - x[t, i] * rows[i]
        rows.append(row)
    return jnp.moveaxis(jnp.stack(rows), -1, 0)


def _merge(inv_a, b, inv_d):
    """Inverse of ``[[A, 0], [B, D]]`` from the halves' inverses:
    ``[[A^-1, 0], [-D^-1 B A^-1, D^-1]]``; batch in the lanes again, a
    product being ``half`` broadcast multiply-adds."""
    def matmul(x, y):                            # [m, k, B] x [k, n, B]
        return sum(
            x[:, k, None, :] * y[None, k, :, :] for k in range(x.shape[1])
        )

    a, bb, d = (jnp.moveaxis(m, 0, -1) for m in (inv_a, b, inv_d))
    lower = -matmul(matmul(d, bb), a)
    top = jnp.concatenate([a, jnp.zeros_like(a)], axis=1)
    bottom = jnp.concatenate([lower, d], axis=1)
    return jnp.moveaxis(jnp.concatenate([top, bottom], axis=0), -1, 0)


def _unit_lower_inverse(n):
    """``[B, C, C]`` -> its ``(I + n)^-1``: ``LEAF``-row diagonal blocks
    by substitution, then merged pairwise (block forward substitution:
    as stable as the row-by-row form, and no 64-step loop over HBM)."""
    size = n.shape[-1]
    if size <= LEAF or size % 2:
        return _substitute(n)
    half = size // 2
    batch = n.shape[0]
    halves = _unit_lower_inverse(jnp.concatenate(
        [n[:, :half, :half], n[:, half:, half:]], axis=0
    ))
    return _merge(halves[:batch], n[:, half:, :half], halves[batch:])


@jax.custom_vjp
def unit_lower_inverse(n):
    """``(I + n)^-1`` for strictly lower-triangular ``n [..., C, C]``."""
    return _unit_lower_inverse(
        n.reshape(-1, *n.shape[-2:])
    ).reshape(n.shape)


def _inverse_fwd(n):
    inv = unit_lower_inverse(n)
    return inv, inv


def _inverse_bwd(inv, g):
    # d(I+n)^-1 = -T dn T  =>  dn = -T^T g T^T, on n's own triangle.
    t_t = jnp.swapaxes(inv, -1, -2)
    dn = -_mm("...ij,...jk->...ik", _mm("...ij,...jk->...ik", t_t, g), t_t)
    size = inv.shape[-1]
    strict = jnp.tril(jnp.ones((size, size), bool), -1)
    return (jnp.where(strict, dn, 0.0),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


# -- the decayed products A and P ---------------------------------------------


def _block_decay(gs):
    """``exp(G_t - G_i)`` for ``t >= i`` inside a sub-chunk, else 0:
    ``[..., c(t), c(i), dk]``; the masked exponents are <= 0."""
    sub = gs.shape[-2]
    diff = gs[..., :, None, :] - gs[..., None, :, :]
    seen = jnp.arange(sub)[:, None] >= jnp.arange(sub)[None, :]
    return jnp.exp(jnp.where(seen[..., None], diff, -jnp.inf))


@jax.custom_vjp
def _diagonal_blocks(qs, ks, gs):
    """The sub-chunks' own blocks of ``A`` (strictly lower) over those
    of ``P`` (lower), ``[..., 2c, c]``, summed channel by channel:
    ``D_ti = sum_c x_tc k_ic exp(G_tc - G_ic)``, x = k then q."""
    decay = _block_decay(gs)
    cols = ks[..., None, :, :] * decay
    kk = jnp.sum(ks[..., :, None, :] * cols, -1)
    qk = jnp.sum(qs[..., :, None, :] * cols, -1)
    sub = gs.shape[-2]
    strict = jnp.arange(sub)[:, None] > jnp.arange(sub)[None, :]
    return jnp.concatenate([jnp.where(strict, kk, 0.0), qk], axis=-2)


def _diagonal_fwd(qs, ks, gs):
    return _diagonal_blocks(qs, ks, gs), (qs, ks, gs)


def _diagonal_bwd(res, d_out):
    # With E = exp(G_t - G_i): dx_t = sum_i dD_ti k_i E, dk_i = sum_t
    # dD_ti x_t E, and since dE/dG_t = E = -dE/dG_i the gates' gradient
    # is x_t * dx_t - k_i * dk_i: three sums, not autodiff's six.
    qs, ks, gs = res
    sub = gs.shape[-2]
    strict = jnp.arange(sub)[:, None] > jnp.arange(sub)[None, :]
    d_kk = jnp.where(strict, d_out[..., :sub, :], 0.0)[..., None]
    d_qk = d_out[..., sub:, :][..., None]
    decay = _block_decay(gs)
    cols = ks[..., None, :, :] * decay
    d_k_rows = jnp.sum(d_kk * cols, axis=-2)            # [.., c(t), dk]
    d_q = jnp.sum(d_qk * cols, axis=-2)
    d_cols = jnp.sum(
        (d_kk * ks[..., :, None, :] + d_qk * qs[..., :, None, :]) * decay,
        axis=-3,
    )                                                   # [.., c(i), dk]
    d_gs = ks * d_k_rows + qs * d_q - ks * d_cols
    return d_q, d_k_rows + d_cols, d_gs


_diagonal_blocks.defvjp(_diagonal_fwd, _diagonal_bwd)


@functools.partial(jax.checkpoint, static_argnums=(4,))
def decayed_products(q, k, g_cum, beta, sub):
    """``(N, P)`` of one layer's chunks: ``N = diag(beta) A`` strictly
    lower and ``P`` lower with its diagonal, ``[..., C, C]``, from
    ``q, k, g_cum [..., C, dk]`` (``g_cum`` cumulated inside the chunk)
    and ``beta [..., C]``. Rematerialised: its backward re-forms the
    per-channel exponentials rather than keeping them."""
    *lead, size, dk = k.shape
    n_sub = size // sub
    shape = (*lead, n_sub, sub, dk)
    qs, ks, gs = q.reshape(shape), k.reshape(shape), g_cum.reshape(shape)
    # R[s]: the cumulated decay just before sub-chunk s (0 for the first).
    ref = jnp.concatenate(
        [jnp.zeros_like(gs[..., :1, -1, :]), gs[..., :-1, -1, :]], axis=-2
    )                                                   # [..., n_sub, dk]
    rows = jnp.concatenate([ks, qs], axis=-2) * jnp.exp(
        jnp.concatenate([gs, gs], axis=-2) - ref[..., None, :]
    )                                                   # [..., s, 2c, dk]
    # The chunk's columns as row sub-chunk s sees them; a column of
    # sub-chunk >= s is masked below, its exponent held at 0 so that
    # nothing overflows.
    lift = ref[..., :, None, :] - g_cum[..., None, :, :]
    cols = k[..., None, :, :] * jnp.exp(jnp.minimum(lift, 0.0))
    off = _mm("...sad,...sid->...sai", rows, cols)      # [.., s, 2c, C]
    diag = _diagonal_blocks(qs, ks, gs)                 # [.., s, 2c, c]
    # One pass lays both out: column sub-chunk r of row sub-chunk s is
    # the matmul's below the diagonal (r < s), the exact block on it.
    col_sub = jnp.arange(size)[None, None, :] // sub
    row_sub = jnp.arange(n_sub)[:, None, None]
    full = jnp.where(col_sub < row_sub, off, 0.0) + jnp.where(
        col_sub == row_sub, jnp.tile(diag, n_sub), 0.0
    )                                                   # [.., s, 2c, C]
    a = full[..., :sub, :].reshape(*lead, size, size)
    p = full[..., sub:, :].reshape(*lead, size, size)
    return beta[..., :, None] * a, p


# -- the two forms ------------------------------------------------------------
#
# Heads-major: ``q, k, g [b, h, s, dk]``, ``v [b, h, s, dv]``, ``beta
# [b, h, s]`` -> ``[b, h, s, dv]`` float32 (a projection writes that
# layout as cheaply as any, and a chunk is then a free reshape).


def kda_recurrent(q, k, v, g, beta, state=None):
    """The recurrence, a token a step (``state``: the file's last note)."""
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    zeros = jnp.zeros(k.shape[:2] + (k.shape[-1], v.shape[-1]), f32)

    def step(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = state * jnp.exp(g_t)[..., None]
        u = b_t[..., None] * (v_t - _mm("bhk,bhkv->bhv", k_t, state))
        state = state + k_t[..., None] * u[..., None, :]
        return state, _mm("bhk,bhkv->bhv", q_t, state)

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (q, k, v, g, beta))
    first = zeros if state is None else state.astype(f32)
    last, out = jax.lax.scan(step, first, xs)
    out = jnp.moveaxis(out, 0, 2)
    return out if state is None else (out, last)


def head_groups(heads: int, seq: int) -> int:
    """In how many groups :func:`kda_chunked` walks ``heads`` heads of
    ``seq`` tokens: the fewest that keep a group at ``GROUP_TOKENS``
    head-tokens or under (and divide the heads). 32 heads x 8,192 tokens
    go in 4 groups: on the chip a five-layer step ran 12,465 tokens/s so
    and 11,928 in one group, at a compiled peak of 15.40 against 14.96
    GB (PERF.md, PR 31) -- a group's chunk tensors are what the backward
    keeps live, and smaller ones schedule better."""
    for groups in range(1, heads + 1):
        if heads % groups == 0 and heads // groups * seq <= GROUP_TOKENS:
            return groups
    return heads


def kda_scan_kind(dk: int, dv: int) -> str:
    """Which form :func:`kda_chunked` runs, read from the platform and
    the head's shape alone: ``"pallas"`` (``ops/kda_kernels.py``) on a
    TPU when keys and values are one whole 128-lane tile, outside a
    multi-device mesh (GSPMD cannot partition a Mosaic kernel);
    ``"xla"``, the ``jax.numpy`` form below, anywhere else."""
    mesh = current_mesh()
    if (
        jax.default_backend() == "tpu"
        and dk == dv == kda_kernels.LANES
        and (mesh is None or mesh.size == 1)
    ):
        return "pallas"
    return "xla"


def kda_chunked(q, k, v, g, beta):
    """The same function of the same arguments as
    :func:`kda_recurrent`, in chunks; any sequence length (the tail is
    padded with tokens that leave the state alone). Which of the two
    forms below runs is :func:`kda_scan_kind`'s answer."""
    if kda_scan_kind(k.shape[-1], v.shape[-1]) == "pallas":
        return kda_chunked_kernels(q, k, v, g, beta)
    return kda_chunked_xla(q, k, v, g, beta)


def kda_chunked_xla(q, k, v, g, beta, chunk=CHUNK, sub=SUB_CHUNK):
    """:func:`kda_chunked` in plain ``jax.numpy``: the definition the
    kernels are held to, and what runs off a TPU or at a head size that
    is not a lane tile. The heads are walked a group at a time
    (:func:`head_groups` of them), each group rematerialised in the
    backward, so that one group's chunk tensors are live at once and
    not the layer's (heads are independent; the result is the same)."""
    b, h, s = beta.shape
    n_groups = head_groups(h, s)

    def split(x):
        x = x.reshape(b, n_groups, h // n_groups, *x.shape[2:])
        return jnp.moveaxis(x, 1, 0)

    one = jax.checkpoint(lambda xs: _kda_chunked(*xs, chunk, sub))
    out = jax.lax.map(one, tuple(split(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1).reshape(b, h, s, out.shape[-1])


def kda_chunked_kernels(q, k, v, g, beta, interpret=False):
    """:func:`kda_chunked` through the Pallas kernels of
    ``ops/kda_kernels.py`` (head size 128, chunk 64, sub-chunk 16): no
    chunk tensor in HBM, the heads walked by the grid. ``interpret``
    runs them off a TPU, for the tests."""
    s = beta.shape[2]
    pad = -s % kda_kernels.CHUNK

    def whole_chunks(x):
        """float32, the tokens zero-padded to whole chunks."""
        widths = [(0, 0), (0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 3)
        return jnp.pad(x.astype(jnp.float32), widths)

    xs = (whole_chunks(x) for x in (q, k, g, v, beta))
    return _scan_kernels(interpret, *xs)[:, :, :s]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _scan_kernels(interpret, q, k, g, v, beta):
    return kda_kernels.scan_forward(q, k, g, v, beta, interpret=interpret)[0]


def _scan_kernels_fwd(interpret, q, k, g, v, beta):
    out, states = kda_kernels.scan_forward(
        q, k, g, v, beta, interpret=interpret
    )
    states = checkpoint_name(states, "kda_states")
    return out, (q, k, g, v, beta, states)


def _scan_kernels_bwd(interpret, res, d_out):
    return kda_kernels.scan_backward(*res, d_out, interpret=interpret)


_scan_kernels.defvjp(_scan_kernels_fwd, _scan_kernels_bwd)


def _kda_chunked(q, k, v, g, beta, chunk, sub):
    f32 = jnp.float32
    b, h, s, dk = k.shape
    dv = v.shape[-1]
    pad = -s % chunk
    n = (s + pad) // chunk

    def chunks(x):
        """[b, h, s, ...] -> [b, h, n, C, ...], zero-padded."""
        x = x.astype(f32)
        if pad:
            tail = [(0, 0)] * (x.ndim - 3)
            x = jnp.pad(x, [(0, 0), (0, 0), (0, pad)] + tail)
        return x.reshape(b, h, n, chunk, *x.shape[3:])

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    # The decay cumulated inside a chunk, as a triangular matmul (a
    # windowed reduction is many times slower on the chip).
    ones = jnp.tril(jnp.ones((chunk, chunk), f32))
    g_cum = _mm("ij,bhnjd->bhnid", ones, g)
    n_mat, p_mat = decayed_products(q, k, g_cum, beta, sub)
    t_beta = unit_lower_inverse(n_mat) * beta[..., None, :]
    grow = jnp.exp(g_cum)
    g_end = g_cum[..., -1:, :]
    k_out = k * jnp.exp(g_end - g_cum)                   # K * exp(G_C - G)
    w = _mm("bhnij,bhnjk->bhnik", t_beta, k * grow)      # U = u0 - W S
    u0 = _mm("bhnij,bhnjv->bhniv", t_beta, v)
    # S' = diag(exp(G_C)) S + K_out^T (u0 - W S) = M S + B: the only
    # thing that walks the sequence is one dk x dk product a chunk.
    m_mat = jnp.eye(dk, dtype=f32) * jnp.exp(g_end) - _mm(
        "bhnik,bhnil->bhnkl", k_out, w
    )
    b_mat = _mm("bhnik,bhniv->bhnkv", k_out, u0)

    def step(state, x):
        m_c, b_c = x
        return _mm("bhkl,bhlv->bhkv", m_c, state) + b_c, state

    xs = (jnp.moveaxis(m_mat, 2, 0), jnp.moveaxis(b_mat, 2, 0))
    _, states = jax.lax.scan(step, jnp.zeros((b, h, dk, dv), f32), xs)
    states = jnp.moveaxis(states, 0, 2)                  # entering a chunk
    # O = (Q * exp(G)) S + P (u0 - W S)
    q_eff = q * grow - _mm("bhnij,bhnjk->bhnik", p_mat, w)
    out = _mm("bhnik,bhnkv->bhniv", q_eff, states) + _mm(
        "bhnij,bhnjv->bhniv", p_mat, u0
    )
    return out.reshape(b, h, n * chunk, dv)[:, :, :s]


# :func:`kda_recurrent` is the ONE definition both delta rules are held
# to: the trained one (this file's chunked forms) and the SERVED one,
# whose gate is one number a head (``ops/gated_delta.py``: ``g``
# broadcast over the channels). ``state [b, h, dk, dv]`` is what the run
# enters with: None (zeros) hands back the outputs alone, as ever; given,
# ``(outputs, the state after the last row)``. Written down here, below
# every kernel's call site: a compiled kernel carries its callers' line
# numbers, and no line above moves for this note.
