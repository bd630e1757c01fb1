"""The gated delta rule with ONE gate a head, SERVED: a chunk form that
enters with a state and hands states back, and a one-token update of the
slots' state in place.

Per head, ``q_t, k_t`` in R^dk (the caller has L2-normed them and scaled
``q``), ``v_t`` in R^dv, a log decay ``g_t <= 0`` (one number a head) and
``beta_t``:

    S_t = e^{g_t} S_{t-1} + beta_t k_t (v_t - e^{g_t} S_{t-1}^T k_t)^T
    o_t = S_t^T q_t                                 S in R^{dk x dv}

which is ``ops/kda.kda_recurrent`` with the gate equal in every channel
(the one definition both are tested against). ``ops/kda.py`` TRAINS that
rule from zeros and returns outputs alone; what a server needs is here:

:func:`delta_chunk` runs ``s`` rows of one sequence that ENTER with ``S``
and hands back the outputs, the state after the first ``n_valid`` rows
and the state after the first ``snap_at`` rows (the prefix cache's
snapshot), all from ONE solve a sub-chunk of :data:`CHUNK` rows. Inside a
sub-chunk (``G`` the gate cumulated from its start, ``A_ti = e^{G_t -
G_i} k_t . k_i`` for ``i < t``, ``P_ti`` the same with ``q_t`` for ``i <=
t``):

    (I + diag(beta) A) U = diag(beta) (V - (K * e^G) S)
    O   = (Q * e^G) S + P U
    S_n = e^{G_n} S + (K_{<n} * e^{G_n - G})^T U_{<n}      any n in 0..C

``U`` is causal (row ``t`` of it depends on rows ``<= t`` alone), so the
state after ANY number of rows is the last line over the rows before it:
no second solve, and the rows past ``n`` (a chunk's padding) cannot reach
it. The gate is one number a head, so ``e^{G_t - G_i}`` is one ``[C, C]``
matrix whose exponents are all ``<= 0`` (masked above the diagonal):
``ops/kda.py``'s sub-chunks, which exist because a per-CHANNEL decay does
not factor, are not needed. Everything but the walk over sub-chunks is
computed for all of them at once; the walk is two small products a
sub-chunk. All of it is float32 at :data:`PRECISION`.

:func:`delta_step` is one row a SLOT over the state array whole
(``[layers, slots, heads, dk, dv]``): where :func:`step_kind` answers
``state_kernel`` a grid step takes one slot's ``[heads, dk, dv]`` of one
layer through VMEM ONCE, decays it, reads ``S^T k``, adds the rank-1
update, reads ``S^T q`` and writes it back where it was (aliased; the
other layers' blocks are never touched; a slot that is not active keeps
its state). :func:`delta_step_reference` is the same in ``jax.numpy``:
the definition, and what runs wherever the kernel does not lower.
:func:`step_kind` decides from the platform, the dtype and the sizes; no
option, environment variable or argument selects it.
"""

import functools

import jax
import jax.numpy as jnp

from dlrover_tpu.ops.kda import unit_lower_inverse

PRECISION = jax.lax.Precision.HIGHEST
CHUNK = 64          # rows a solve: the prefix cache's block, a sub-chunk
_VMEM_BYTES = 40 << 20
_LANES = 128


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=PRECISION)


# -- the chunk form -----------------------------------------------------------


def delta_chunk(q, k, v, g, beta, state, n_valid=None, snap_at=0,
                chunk: int = CHUNK):
    """``q``, ``k [s, heads, dk]``, ``v [s, heads, dv]``, ``g``, ``beta
    [s, heads]``, ``state [heads, dk, dv]`` (float32: ``S`` as of the row
    before the run's first) -> (``o [s, heads, dv]`` float32, the state
    after the first ``n_valid`` rows, the state after the first
    ``snap_at`` rows). ``n_valid`` (None: ``s``) and ``snap_at`` may be
    traced, 0 ... ``s`` (0: ``state`` itself). ``s`` is whole sub-chunks
    of ``chunk`` rows. The outputs of rows at or past ``n_valid`` mean
    nothing (padding), and nothing a row before them reads depends on
    them."""
    f32 = jnp.float32
    s, heads, dk = k.shape
    if s % chunk:
        raise ValueError(f"{s} rows are not whole sub-chunks of {chunk}")
    n = s // chunk

    def chunks(x):
        """[s, heads, ...] -> [heads, n, C, ...] float32."""
        x = jnp.moveaxis(x.astype(f32), 1, 0)
        return x.reshape((heads, n, chunk) + x.shape[2:])

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    lower = jnp.tril(jnp.ones((chunk, chunk), f32))
    g_cum = _mm("ij,hnj->hni", lower, g)                  # G, inclusive
    decay = jnp.exp(jnp.where(
        lower > 0, g_cum[..., :, None] - g_cum[..., None, :], -jnp.inf
    ))                                                    # [h, n, C, C]
    a_mat = _mm("hntd,hnid->hnti", k, k) * decay
    n_mat = beta[..., :, None] * jnp.where(
        jnp.tril(jnp.ones((chunk, chunk), bool), -1), a_mat, 0.0
    )
    p_mat = _mm("hntd,hnid->hnti", q, k) * decay
    t_beta = unit_lower_inverse(n_mat) * beta[..., None, :]
    grow = jnp.exp(g_cum)[..., None]
    w = _mm("hnti,hnid->hntd", t_beta, k * grow)          # U = u0 - W S
    u0 = _mm("hnti,hniv->hntv", t_beta, v)
    g_end = g_cum[..., -1]                                # [h, n]
    k_out = k * jnp.exp(g_end[..., None] - g_cum)[..., None]

    def step(entering, x):
        w_c, u0_c, k_out_c, g_end_c = x
        u_c = u0_c - _mm("htd,hdv->htv", w_c, entering)
        after = jnp.exp(g_end_c)[:, None, None] * entering + _mm(
            "htd,htv->hdv", k_out_c, u_c
        )
        return after, (entering, u_c)

    per = lambda x: jnp.moveaxis(x, 1, 0)  # noqa: E731
    _, (entered, u) = jax.lax.scan(
        step, state.astype(f32), (per(w), per(u0), per(k_out), per(g_end))
    )
    entered, u = per(entered), per(u)       # [h, n, dk, dv], [h, n, C, dv]
    o = _mm("hntd,hndv->hntv", q * grow, entered) + _mm(
        "hnti,hniv->hntv", p_mat, u
    )

    def state_after(rows):
        """``S`` after the first ``rows`` rows of the run: one sub-chunk's
        entering state, its ``K``, ``G`` and ``U`` below the row."""
        rows = jnp.asarray(rows, jnp.int32)
        c = jnp.clip((rows - 1) // chunk, 0, n - 1)
        local = rows - c * chunk                          # 0 ... C
        pick = lambda x: jax.lax.dynamic_index_in_dim(  # noqa: E731
            x, c, axis=1, keepdims=False
        )
        g_c = pick(g_cum)                                 # [h, C]
        g_at = jnp.where(
            local > 0,
            jax.lax.dynamic_index_in_dim(
                g_c, jnp.maximum(local - 1, 0), axis=1, keepdims=False
            ),
            0.0,
        )                                                 # [h]
        weight = jnp.exp(jnp.where(
            jnp.arange(chunk)[None, :] < local, g_at[:, None] - g_c, -jnp.inf
        ))                                                # [h, C]
        return jnp.exp(g_at)[:, None, None] * pick(entered) + _mm(
            "htd,htv->hdv", pick(k) * weight[..., None], pick(u)
        )

    o = jnp.moveaxis(o.reshape(heads, s, -1), 0, 1)
    return o, state_after(s if n_valid is None else n_valid), \
        state_after(snap_at)


# -- the one-token update -----------------------------------------------------


def delta_step_reference(q, k, v, g, beta, state):
    """One row a sequence: ``q``, ``k [b, heads, dk]``, ``v [b, heads,
    dv]``, ``g``, ``beta [b, heads]``, ``state [b, heads, dk, dv]``
    float32 -> (``o [b, heads, dv]`` float32, the new state): the decay,
    one read ``S^T k``, one rank-1 update, one read ``S^T q``."""
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    decayed = state * jnp.exp(g)[..., None, None]
    u = beta[..., None] * (v - jnp.sum(decayed * k[..., None], axis=-2))
    new = decayed + k[..., None] * u[..., None, :]
    return jnp.sum(new * q[..., None], axis=-2), new


def step_kernel_supported(state_dtype, heads: int, dk: int, dv: int) -> bool:
    """Shapes :func:`delta_step`'s kernel lowers for on a TPU: a float32
    state whose head is whole sublane tiles (``dk`` a multiple of 8; the
    lanes pad to whole 128s in VMEM) and whose one slot of one layer, in
    and out and double-buffered, fits the VMEM the kernel asks for."""
    lanes = -(-dv // _LANES) * _LANES
    return (
        jnp.dtype(state_dtype) == jnp.float32
        and dk % 8 == 0
        and 4 * heads * dk * lanes * 4 <= _VMEM_BYTES - (8 << 20)
    )


def step_kind(state_dtype, heads: int, dk: int, dv: int) -> str:
    """What the decode step's delta layers run: ``"state_kernel"``
    (:func:`delta_step`: a slot's state of a layer through VMEM once,
    updated and read there, written back in place) where that kernel
    lowers (a TPU, :func:`step_kernel_supported`) and ``"jnp"``
    (:func:`delta_step_reference`), the definition, everywhere else.
    Read from the platform, the dtype and the sizes alone."""
    if jax.default_backend() == "tpu" and step_kernel_supported(
        state_dtype, heads, dk, dv
    ):
        return "state_kernel"
    return "jnp"


def _step_kernel(active_ref, decay_ref, beta_ref, kt_ref, qt_ref, v_ref,
                 s_ref, o_ref, out_ref, *, heads: int):
    from jax.experimental import pallas as pl

    slot = pl.program_id(0)
    keep = active_ref[slot] > 0
    for h in range(heads):
        old = s_ref[h]                                        # [dk, dv]
        kt = kt_ref[:, h:h + 1]                               # [dk, 1]
        s = old * decay_ref[slot * heads + h]
        u = beta_ref[slot * heads + h] * (
            v_ref[h:h + 1, :] - jnp.sum(s * kt, axis=0, keepdims=True)
        )
        new = s + kt * u
        o_ref[h:h + 1, :] = jnp.sum(
            new * qt_ref[:, h:h + 1], axis=0, keepdims=True
        )
        out_ref[h] = jnp.where(keep, new, old)


def delta_step(q, k, v, g, beta, state, layer: int, active, interpret=None):
    """``q``, ``k [slots, heads, dk]``, ``v [slots, heads, dv]``, ``g``,
    ``beta [slots, heads]``; ``state [layers, slots, heads, dk, dv]``
    float32; ``layer`` a Python int; ``active [slots]`` bool -> (``o
    [slots, heads, dv]`` float32, the state with layer ``layer`` of the
    active slots updated, aliased to ``state``). ``k`` and ``q`` go in
    transposed (``[dk, heads]``: a head's vector down the sublanes, so
    that it broadcasts along the lanes of ``S``), ``v`` as it is, the
    decay and ``beta`` as scalars."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    slots, heads, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    transposed = lambda a: jnp.swapaxes(a.astype(f32), 1, 2)  # noqa: E731
    vec = pl.BlockSpec((None, dk, heads), lambda s, *_: (s, 0, 0))
    row = pl.BlockSpec((None, heads, dv), lambda s, *_: (s, 0, 0))
    block = pl.BlockSpec(
        (None, None, heads, dk, dv), lambda s, *_: (layer, s, 0, 0, 0)
    )
    o, new = pl.pallas_call(
        functools.partial(_step_kernel, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(slots,),
            in_specs=[vec, vec, row, block],
            out_specs=[row, block],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((slots, heads, dv), f32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        # scalars (3) + kt, qt, v, then the state: argument 6 -> output 1
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_BYTES,
        ),
        interpret=interpret,
        name="delta_state_step",
    )(
        active.astype(jnp.int32),
        jnp.exp(g.astype(f32)).reshape(-1), beta.astype(f32).reshape(-1),
        transposed(k), transposed(q), v.astype(f32), state,
    )
    return o, new
