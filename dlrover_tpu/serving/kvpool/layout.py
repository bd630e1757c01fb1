"""What a block holds: the model's statement, read by the pool.

A paged pool is a tuple of device arrays ``[layers, num_blocks,
block_size, *row_shape]`` addressed by a block table, in one GROUP or in
several (below). Which arrays,
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

- lightning and block-sparse layers (``models/linear_sparse_lm.py``):
  ``k_pages``, ``v_pages [head_dim]``, ONE KV head a pool layer
  (``cache_layers`` = sparse layers x KV heads: a selected block of a
  head is one whole page), and ``ckeys [head_dim]``, the compressed keys,
  an array at a STRIDE (``config.cache_strides``: name -> tokens a row
  stands for; ``PoolArray.stride``): ``[layers, num_blocks, block_size /
  stride, *row_shape]``, ``block_size / stride`` rows a block. Place
  ``p`` of a sequence's compressed keys (the mean of rows ``[stride (p -
  1), stride (p + 1))``) is held in the block of its LAST row, block ``p
  // r`` at offset ``p % r``: every array of a block is a function of
  the tokens up to that block's end alone, so a shared block means the
  same to every sequence that shares it.

- delta-rule and un-grouped full-attention layers
  (``models/delta_lm.py``): ``k``, ``v [kv_heads_held, head_dim]`` over
  the FULL layers alone (``cache_layers``), the 30 KV heads held as 32
  (zero heads: what such a row pads to on the device anyway, declared so
  the dense model's kernels apply as they are).

Everything that MOVES a block (copy-on-write, import, export, prefix
sharing, preemption, release) moves every array of this tuple and never
asks what they are; everything that SIZES a block sums over it (an array
at a stride counts ``block_size / stride`` rows: ``PoolArray
.block_rows``). Only the
programs that read and write rows (``kvpool/dense.py``,
``kvpool/sparse.py``, ``kvpool/latent.py``, ``kvpool/conv.py``,
``kvpool/linear.py``, ``kvpool/delta.py``) know the arrays by name.

GROUPS (``config.cache_groups``: name -> (layers, ``"all"`` or a reach
in rows); a config without it is ONE group that keeps all, and nothing
of its pool or its programs changes). Layers that keep a sequence's rows
for different spans keep them apart: each group has the arrays above
over its OWN layer axis, its own number of blocks, its own block ids,
its own ``BlockAllocator`` and its own ``[slots, max_blocks]`` table,
indexed by the logical block as one table is. The first group keeps
``"all"`` (it is what sizes a slot and what the prefix cache's entries
name); a group with a reach ``r`` keeps a row only while some query yet
to come can see it: the engine RELEASES a slot's block of that group
once every row of it is below ``next row to be written - r``, and the
table's entry goes to the sentinel block (``models/window_lm.py``: the
sliding-window layers, ``r = sliding_window - 1``; its arrays are named
``k_window``, ``v_window``: the group's name after the array's). What
moves or sizes a block walks the groups; the programs take the groups'
arrays in order and the tables stacked (``kvpool/window.py``).

A SECOND kind of array holds what a sequence keeps whatever its length
(``config.state_rows``: name -> (layers, a slot's shape[, dtype]); ONE
array for ``models/conv_lm.py`` and ``models/linear_sparse_lm.py``, TWO
of two dtypes for ``models/delta_lm.py``: the float32 matrix state and
the compute-dtype convolution taps, which are one state and move
together: every walk below is over :func:`state_arrays`, whatever their
number): per-SLOT state ``[layers, slots, *shape]``, which
no block table addresses, and beside each its SNAPSHOTS ``[layers,
snapshots, *shape]``: the state as of a block boundary, owned by the
prefix cache's entry for that boundary (``kvpool/prefix_cache.py``). A
run of cached blocks can be continued only from a boundary that has one.
The engine treats them as it treats the arrays above: every program
takes and returns them after the pool's, admission restores or zeroes a
slot's, a chunk writes a snapshot, migration carries a slot's raw, and
``kv_stats()`` sizes them; only ``kvpool/conv.py``,
``kvpool/linear.py`` and ``kvpool/delta.py`` know what they mean. A state array is made in the
dtype its config states (a third item beside layers and shape:
``models/linear_sparse_lm.py``'s running sum is float32) and in
``compute_dtype`` where it states none. HOW MANY snapshots an engine
keeps is a budget in BYTES wherever one snapshot outweighs one block
(:func:`budgeted_snapshots`: 18.9 MB against 0.2 MB there), and one a
cached block where it does not (:func:`default_snapshots`: 57 KB against
0.26 MB for ``models/conv_lm.py``); when no id is free the prefix cache
gives up its least recently used entry's SNAPSHOT before any block.
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
    group: int = 0                  # which of ``cache_groups`` holds it
    stride: int = 1                 # tokens a row stands for

    @property
    def raw(self) -> bool:
        return self.name not in KV_WIRE

    def block_rows(self, block_size: int) -> int:
        """Rows a block of ``block_size`` tokens holds of this array."""
        if block_size % self.stride:
            raise ValueError(
                f"block_size {block_size} is not whole strides of "
                f"{self.stride} tokens ({self.name})"
            )
        return block_size // self.stride

    def block_bytes(self, n_layers: int, block_size: int) -> int:
        """Bytes of one block of this array, all layers."""
        return int(
            n_layers * self.block_rows(block_size)
            * np.prod(self.row_shape, dtype=np.int64)
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
    strides = getattr(config, "cache_strides", None) or {}
    if kv_cache_dtype != "int8":
        return tuple(
            PoolArray(name, tuple(shape), cdt,
                      cdt if name not in KV_WIRE else jnp.float32,
                      stride=int(strides.get(name, 1)))
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
        (n_layers, num_blocks, array.block_rows(block_size))
        + array.row_shape, array.dtype,
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


# The share of the per-token pool's bytes an engine spends on snapshots
# when one outweighs a block and nobody states a count.
SNAPSHOT_POOL_SHARE = 0.5


def budgeted_snapshots(entry_bytes: int, pool_bytes: int, slots: int) -> int:
    """Snapshot ids an engine keeps when ONE snapshot outweighs a block:
    as many as :data:`SNAPSHOT_POOL_SHARE` of the pool's bytes pay for,
    and never fewer than one a slot (a prompt's own, lent while it
    prefills) and one more."""
    return max(
        slots + 1, int(SNAPSHOT_POOL_SHARE * pool_bytes) // max(entry_bytes, 1)
    )


def state_arrays(config) -> Tuple[StateArray, ...]:
    """The per-slot arrays ``config`` states (none: every model whose
    whole state is rows in pages), each in the dtype stated beside its
    layers and shape, ``compute_dtype`` where none is."""
    return tuple(
        StateArray(name, int(spec[0]), tuple(spec[1]),
                   jnp.dtype(spec[2]) if len(spec) > 2
                   else config.compute_dtype)
        for name, spec in getattr(config, "state_rows", ())
    )


class CacheGroup(NamedTuple):
    name: str
    layers: int
    reach: object        # None: keeps all; else rows below the next one

    @property
    def keeps_all(self) -> bool:
        return self.reach is None


def cache_groups(config) -> Tuple[CacheGroup, ...]:
    """The pool's groups for ``config`` (module docstring): one that
    keeps all over :func:`pool_layers` layers unless the config states
    more; the first always keeps all."""
    stated = getattr(config, "cache_groups", None)
    if stated is None:
        return (CacheGroup("all", pool_layers(config), None),)
    groups = tuple(
        CacheGroup(name, int(layers), None if retain == "all" else int(retain))
        for name, (layers, retain) in stated
    )
    if not groups or not groups[0].keeps_all or any(
        g.keeps_all for g in groups[1:]
    ):
        raise ValueError(
            f"cache_groups {stated}: the first group keeps all rows and "
            "every other one states its reach"
        )
    return groups


def grouped_pool_arrays(config, kv_cache_dtype: str = "fp"):
    """:func:`pool_arrays` for every group of ``config``, in the order
    every compiled program takes and returns them: the first group's
    under their own names, every other group's with ``_<group>`` after
    them and their ``group`` set."""
    first = pool_arrays(config, kv_cache_dtype)
    out = list(first)
    for g, group in enumerate(cache_groups(config)[1:], start=1):
        out.extend(
            a._replace(name=f"{a.name}_{group.name}", group=g) for a in first
        )
    return tuple(out)
