"""Decode attention over a LIST of pages a (slot, KV head): the block-sparse
mixer's step (``models/linear_sparse_lm.py``), where the selected blocks
ARE pages of the pool.

The pool holds one KV head a pool layer (``[layers, num_blocks,
block_size, head_dim]``), so a selected block of one head is one
contiguous page. The kernel is ``ops.decode_attention._pool_kernel``
itself (``_page_stream``'s copies, the online softmax opened by the new
token's own term): what it takes for "a slot's table" is here the list of
a (slot, KV head), already mapped to pages and offset to the head's pool
layer, and for "the slot's fill" the rows of that list the query may see
(every listed page is whole but the last, the query's own block). All
pool layers are one page axis (a free reshape), so one call serves every
(slot, KV head) of a model layer.

:func:`list_attention` is the same in ``jax.numpy`` (the definition, and
what runs off a TPU): the listed pages gathered.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.decode_attention import (
    _POOL_CHUNK_BYTES,
    _pool_kernel,
)


def list_kernel_supported(pool_dtype, block_size: int, head_dim: int) -> bool:
    """Shapes the list kernel lowers for on a TPU: a bf16 pool whose page
    ``[block_size, head_dim]`` is whole (16, 128) tiles (one contiguous
    DMA) and fits a VMEM chunk."""
    return (
        jnp.dtype(pool_dtype) == jnp.bfloat16
        and head_dim % 128 == 0
        and block_size % 16 == 0
        and block_size * head_dim * 2 <= _POOL_CHUNK_BYTES
    )


def list_attention(q, k_new, v_new, k_pool, v_pool, pages, length):
    """The definition: ``q [b, g, d]`` (one query token a list, ``g``
    heads), ``k_new`` / ``v_new [b, d]`` its own row, ``k_pool`` /
    ``v_pool [pages, block_size, d]`` (every pool layer's pages on one
    axis), ``pages [b, width]`` the list, ``length [b]`` the rows of the
    gathered list the query sees (a prefix) -> ``[b, g, d]``."""
    b, _, d = q.shape
    f32 = jnp.float32
    k_rows = k_pool[pages].reshape(b, -1, d)
    v_rows = v_pool[pages].reshape(b, -1, d)
    scores = jnp.einsum("bgd,btd->bgt", q, k_rows, preferred_element_type=f32)
    seen = jnp.arange(k_rows.shape[1])[None, :] < length[:, None]
    scores = jnp.where(seen[:, None, :], scores, -jnp.inf)
    mine = jnp.einsum("bgd,bd->bg", q, k_new, preferred_element_type=f32)
    probs = jax.nn.softmax(
        jnp.concatenate([scores, mine[..., None]], axis=-1) * d ** -0.5,
        axis=-1,
    )
    out = jnp.einsum(
        "bgt,btd->bgd", probs[..., :-1].astype(v_rows.dtype), v_rows,
        preferred_element_type=f32,
    ) + probs[..., -1:] * v_new[:, None, :].astype(f32)
    return out.astype(q.dtype)


def list_decode_attention(q, k_new, v_new, k_pool, v_pool, pages, length,
                          active, interpret=None):
    """:func:`list_attention` with the pool read IN PLACE: only the
    listed pages that hold a visible row are copied, a page a DMA, scores
    and softmax in VMEM (``ops.decode_attention._pool_kernel`` with one
    KV head a page). A list that is not ``active`` reads nothing and
    answers its own ``v_new``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, g, d = q.shape
    n_pages, block_size, _ = k_pool.shape
    width = pages.shape[1]
    chunk_pages = max(1, min(_POOL_CHUNK_BYTES // (block_size * d * 2), width))
    q32 = (q * d ** -0.5).astype(jnp.float32)
    s_new = jnp.einsum("bgd,bd->bg", q32, k_new.astype(jnp.float32))[..., None]
    v_rows = jnp.broadcast_to(
        v_new.astype(jnp.float32)[:, None, :], (b, g, d)
    )
    hp = -(-g // 16) * 16
    pad = ((0, 0), (0, hp - g), (0, 0))
    q32, s_new, v_rows = (jnp.pad(x, pad) for x in (q32, s_new, v_rows))
    fill = jnp.where(active, jnp.asarray(length, jnp.int32), 0)
    fill = jnp.minimum(fill, width * block_size)
    scalars = (
        jnp.zeros((1,), jnp.int32),
        (fill + block_size - 1) // block_size,
        fill,
        jnp.asarray(pages, jnp.int32).reshape(-1),
    )
    pooled = (1, n_pages, block_size, d)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(
            _pool_kernel, chunk_pages=chunk_pages, page_rows=block_size,
            kv_heads=1, group=hp, max_blocks=width,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(),
            in_specs=[
                vmem, vmem, vmem,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=vmem,
            scratch_shapes=[
                pltpu.VMEM((2, chunk_pages * block_size, d), k_pool.dtype),
                pltpu.VMEM((2, chunk_pages * block_size, d), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hp, d), jnp.float32),
        interpret=interpret,
        name="paged_block_list_decode_attention",
    )(
        *scalars, q32, s_new, v_rows,
        k_pool.reshape(pooled), v_pool.reshape(pooled),
    )
    return out[:, :g].astype(q.dtype)
