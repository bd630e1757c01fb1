"""The lightning mixer's DECODE update over the slots' state in place:
one rank-1 update and one read a (slot, head), the state read once and
written once (``models/linear_sparse_lm.lightning_step`` is the
definition, in ``jax.numpy``; XLA reads a layer's state twice for it and,
landing it after the layer loop, copies the whole array there and back).

    S' = lambda_h * S + k v^T        S [d, d] float32, a (slot, head)
    o  = S'^T q

The state array goes in WHOLE (``[layers, slots, heads, d, d]``) and
comes out aliased to itself: a grid step takes one slot's ``[heads, d,
d]`` of one layer through VMEM and writes it back where it was; the other
layers' blocks are never touched. ``k`` and ``q`` come transposed (``[d,
heads]``: a head's vector down the sublanes, so that it broadcasts along
the lanes of ``S``), ``v`` as it is (``[heads, d]``: along the lanes). A
slot that is not active keeps its state.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_VMEM_BYTES = 40 << 20


def state_kernel_supported(state_dtype, heads: int, head_dim: int) -> bool:
    """Shapes the kernel lowers for on a TPU: a float32 state of whole
    (8, 128) tiles a head whose one slot, in and out and double-buffered,
    fits the VMEM the kernel asks for."""
    return (
        jnp.dtype(state_dtype) == jnp.float32
        and head_dim % 128 == 0
        and 4 * heads * head_dim * head_dim * 4 <= _VMEM_BYTES - (8 << 20)
    )


def _kernel(active_ref, lam_ref, kt_ref, qt_ref, v_ref, s_ref, o_ref,
            out_ref, *, heads: int):
    slot = pl.program_id(0)
    keep = active_ref[slot] > 0
    for h in range(heads):
        s = s_ref[h]
        new = lam_ref[h] * s + kt_ref[:, h:h + 1] * v_ref[h:h + 1, :]
        o_ref[h:h + 1, :] = jnp.sum(
            new * qt_ref[:, h:h + 1], axis=0, keepdims=True
        )
        out_ref[h] = jnp.where(keep, new, s)


def state_step(q, k, v, state, layer: int, slopes, active, interpret=None):
    """``q``, ``k``, ``v [slots, heads, d]``; ``state [layers, slots,
    heads, d, d]`` float32; ``layer`` a Python int; ``slopes [heads]``;
    ``active [slots]`` bool -> (``o [slots, heads, d]`` float32, the
    state with layer ``layer`` of the active slots updated, aliased to
    ``state``)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    slots, heads, d = q.shape
    f32 = jnp.float32
    lam = jnp.exp(-jnp.asarray(slopes, f32))
    transposed = lambda a: jnp.swapaxes(a.astype(f32), 1, 2)  # noqa: E731
    vec = pl.BlockSpec((None, d, heads), lambda s, *_: (s, 0, 0))
    row = pl.BlockSpec((None, heads, d), lambda s, *_: (s, 0, 0))
    block = pl.BlockSpec(
        (None, None, heads, d, d), lambda s, *_: (layer, s, 0, 0, 0)
    )
    o, new = pl.pallas_call(
        functools.partial(_kernel, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(slots,),
            in_specs=[vec, vec, row, block],
            out_specs=[row, block],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((slots, heads, d), f32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        # scalars (2) + kt, qt, v, then the state: argument 5 -> output 1
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_BYTES,
        ),
        interpret=interpret,
        name="lightning_state_step",
    )(
        active.astype(jnp.int32), lam, transposed(k), transposed(q),
        v.astype(f32), state,
    )
    return o, new
