"""What a block holds: the model's statement, read by the pool.

A paged pool is a tuple of device arrays ``[layers, num_blocks,
block_size, *row_shape]`` addressed by ONE block table. Which arrays,
and what a token's row of each is, the model's config says
(``config.cache_rows``: name -> per-token shape; a config without it is
a dense GQA model: ``k`` and ``v`` of ``[kv_heads, head_dim]``):

- dense GQA (``models/llama.py``): ``k``, ``v``;
- the same with ``kv_cache_dtype="int8"`` (the engine's option, not the
  model's): ``k``, ``v`` in int8 and ``k_scale``, ``v_scale``, one
  float32 a (row, head);
- a learned selection of the cache (``models/sparse_lm.py``): ``k``,
  ``v``, ``index_keys [index_dim]`` (on the device ``pack`` tokens to a
  row: ``kvpool/index_pool.py``);
- latent attention (``models/latent_lm.py``): ``latent [kv_lora_rank +
  qk_rope_dim]`` alone, with no head axis and no V (on the device two
  576-wide rows to a 1,152-lane row, the same class).

- a pattern of convolution and attention layers
  (``models/conv_lm.py``): ``k_rows``, ``v_rows [kv_heads * head_dim]``,
  a token's K and V held flat (no head narrower than a lane row pads
  one), over the ATTENTION layers alone (``config.cache_layers``: the
  pool's layer axis; every other config's is ``n_layers``).

Everything that MOVES a block (copy-on-write, import, export, prefix
sharing, preemption, release) moves every array of this tuple and never
asks what they are; everything that SIZES a block sums over it. Only the
programs that read and write rows (``kvpool/engine.py``'s dense ones,
``kvpool/sparse.py``, ``kvpool/latent.py``, ``kvpool/conv.py``) know the
arrays by name.

A SECOND kind of array holds what a sequence keeps whatever its length
(``config.state_rows``: name -> (layers, a slot's shape); no other
config states any): per-SLOT state ``[layers, slots, *shape]``, which
no block table addresses, and beside each its SNAPSHOTS ``[layers,
snapshots, *shape]``: the state as of a block boundary, owned by the
prefix cache's entry for that boundary (``kvpool/prefix_cache.py``). A
run of cached blocks can be continued only from a boundary that has one.
The engine treats them as it treats the arrays above: every program
takes and returns them after the pool's, admission restores or zeroes a
slot's, a chunk writes a snapshot, migration carries a slot's raw, and
``kv_stats()`` sizes them; only ``kvpool/conv.py`` knows what they mean.
"""

from typing import NamedTuple, Tuple

import numpy as np

import jax.numpy as jnp

from dlrover_tpu.serving.kvpool.index_pool import IndexKeyPool

# Arrays a migration carries through ``ops.kv_quant.kv_to_wire`` (int8
# on the wire whatever the pool's dtype); every other array travels raw,
# bit for bit in the pool's dtype.
KV_WIRE = ("k", "v", "k_scale", "v_scale")


# Arrays of one flat row a token whose width is not whole 128-lane rows:
# held ``pack`` tokens to a device row (``kvpool/index_pool.py``: 2 x 64
# index keys, 2 x 576 latent rows), logical shape unchanged.
PACKED = ("index_keys", "latent")


class PoolArray(NamedTuple):
    name: str
    row_shape: Tuple[int, ...]      # a token's row, a layer
    dtype: object                   # on the device
    # What a migrated block's rows arrive as (float32 for a dense
    # model's K and V: the wire's int8 rows dequantized on the host).
    import_dtype: object

    @property
    def raw(self) -> bool:
        return self.name not in KV_WIRE

    def block_bytes(self, n_layers: int, block_size: int) -> int:
        """Bytes of one block of this array, all layers."""
        return int(
            n_layers * block_size * np.prod(self.row_shape, dtype=np.int64)
            * jnp.dtype(self.dtype).itemsize
        )

    def describe(self) -> str:
        shape = "x".join(str(n) for n in self.row_shape) or "1"
        return f"{self.name} [{shape}] {jnp.dtype(self.dtype).name}"


def cache_rows(config):
    rows = getattr(config, "cache_rows", None)
    if rows is None:
        head = (config.n_kv_heads, config.head_dim)
        rows = (("k", head), ("v", head))
    return tuple(rows)


def pool_arrays(config, kv_cache_dtype: str = "fp") -> Tuple[PoolArray, ...]:
    """The pool's arrays for ``config`` under the engine's
    ``kv_cache_dtype``, in the order every compiled program takes and
    returns them."""
    cdt = config.compute_dtype
    rows = cache_rows(config)
    if kv_cache_dtype != "int8":
        return tuple(
            PoolArray(name, tuple(shape), cdt,
                      cdt if name not in KV_WIRE else jnp.float32)
            for name, shape in rows
        )
    if [name for name, _ in rows] != ["k", "v"]:
        raise ValueError(
            "an int8 pool holds K and V alone; this model's blocks hold "
            + ", ".join(name for name, _ in rows)
        )
    values = tuple(
        PoolArray(name, tuple(shape), jnp.int8, jnp.int8)
        for name, shape in rows
    )
    scales = tuple(
        PoolArray(name + "_scale", tuple(shape[:-1]), jnp.float32,
                  jnp.float32)
        for name, shape in rows
    )
    return values + scales


def fresh(array: PoolArray, n_layers: int, num_blocks: int,
          block_size: int):
    """A zeroed device array of ``array`` for a pool of this size."""
    if array.name in PACKED:
        return IndexKeyPool.zeros(
            n_layers, num_blocks, block_size, array.row_shape[0],
            array.dtype,
        )
    return jnp.zeros(
        (n_layers, num_blocks, block_size) + array.row_shape, array.dtype
    )


def pool_layers(config) -> int:
    """The pool's layer axis: the layers that keep per-token rows."""
    return getattr(config, "cache_layers", None) or config.n_layers


class StateArray(NamedTuple):
    name: str
    layers: int
    shape: Tuple[int, ...]          # a slot's (a snapshot's), a layer
    dtype: object

    def entry_bytes(self) -> int:
        """Bytes of one slot's state (one snapshot), all layers."""
        return int(
            self.layers * np.prod(self.shape, dtype=np.int64)
            * jnp.dtype(self.dtype).itemsize
        )

    def fresh(self, entries: int):
        return jnp.zeros((self.layers, entries) + self.shape, self.dtype)

    def describe(self) -> str:
        shape = "x".join(str(n) for n in self.shape)
        return (f"{self.name} [{shape}] {jnp.dtype(self.dtype).name} x "
                f"{self.layers} layers")


def default_snapshots(num_blocks: int, slots: int) -> int:
    """Snapshot ids an engine keeps unless told otherwise: one a cached
    block at most (block 0 is the sentinel), and one a slot for those
    lent to prompts that are still prefilling."""
    return num_blocks - 1 + slots


def state_arrays(config) -> Tuple[StateArray, ...]:
    """The per-slot arrays ``config`` states (none: every model whose
    whole state is rows in pages)."""
    return tuple(
        StateArray(name, int(layers), tuple(shape), config.compute_dtype)
        for name, (layers, shape) in getattr(config, "state_rows", ())
    )
