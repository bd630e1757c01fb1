"""The pool's third array, a sparse model's index keys, as the device
holds it (docs/DESIGN.md §39).

A token's index key is ``index_dim`` wide (64 at published widths): half
of a TPU's 128-lane row. Held as ``[layers, num_blocks, block_size,
index_dim]`` the array is given a device layout with the BLOCK axis
minor-most, which neither the block gather, nor the scores, nor the
landing scatter of ``kvpool/sparse.py`` can read: every decode step and
every prefill chunk re-tiled the whole array three times (PERF.md §6,
PR 36). So the array holds ``pack = 128 // index_dim`` tokens a row,

    rows [layers, num_blocks, block_size // pack, pack * index_dim]

token ``t`` of a block in row ``t // pack`` at lanes ``(t % pack) *
index_dim ...``: a gathered block, flattened, is its tokens in order, and
the block axis is where it is in K and V, so whatever works on whole
blocks (copy-on-write, import, export, the trie's sharing) does not know.
``pack`` follows from the shape (:func:`tokens_per_row`), 1 where the
shape does not pack: the array as it was, through the same code.

:class:`IndexKeyPool` is that array and its ``index_dim`` as one pytree:
a program takes it as ONE argument (donated, aliased) and reads it by
token coordinates; the host sees the logical ``shape``.
"""

import jax
import jax.numpy as jnp

LANES = 128


def tokens_per_row(index_dim: int, block_size: int) -> int:
    """Tokens a row of the pool holds, so that the row is whole 128-lane
    rows: ``128 // index_dim`` where that fills one and divides a block,
    two of a row 1.5, 2.5 ... lane rows wide, else 1."""
    if index_dim <= 0:
        return 1
    if index_dim > LANES:
        # A row wider than the lanes and not a multiple of them (a latent
        # model's 576 = 4.5 x 128: kvpool/latent.py): the fewest tokens
        # that fill whole lane rows, two at most.
        pack = 2 if index_dim % LANES and (2 * index_dim) % LANES == 0 else 1
    elif LANES % index_dim:
        return 1
    else:
        pack = LANES // index_dim
    return pack if block_size % pack == 0 else 1


def gather_at_layer(pool, layer, *index):
    """``pool[layer, *index]`` (``index``: arrays of one shape) as ONE
    gather over the stacked pool. With the layer a traced scalar the
    indexing slices the layer's whole pool out first (a 0.3 GB copy a
    layer and a step for K and for V: 18 of the decode step's 44 ms on
    the chip; PERF.md §6, PR 33); as an array of the others' shape it is
    one more coordinate of the gather."""
    return pool[(jnp.full_like(index[0], layer),) + index]


class _BlocksAt:
    """``pool.at[layer, block].set(values)``: whole blocks, the values
    in the logical shape ``[..., block_size, index_dim]`` (or a scalar)."""

    def __init__(self, pool, index=None):
        self._pool, self._index = pool, index

    def __getitem__(self, index):
        return _BlocksAt(self._pool, _block_index(index))

    def set(self, values):
        pool = self._pool
        values = jnp.asarray(values, pool.dtype)
        if values.ndim >= 2:
            values = values.reshape(
                values.shape[:-2] + pool.rows.shape[2:]
            )
        return IndexKeyPool(
            pool.rows.at[self._index].set(values), pool.index_dim
        )


def _block_index(index):
    index = index if isinstance(index, tuple) else (index,)
    if len(index) > 2 or any(i is Ellipsis or i is None for i in index):
        raise TypeError(
            "an index-key pool is indexed by [layer, block]; a token's "
            "key is read through kvpool.sparse._at_layer"
        )
    return index


@jax.tree_util.register_pytree_node_class
class IndexKeyPool:
    """``rows [layers, num_blocks, block_size // pack, pack *
    index_dim]`` and the static ``index_dim``. ``shape``, ``dtype``,
    ``pool[layer, block]`` and ``pool.at[layer, block].set(...)`` are the
    logical array's ``[layers, num_blocks, block_size, index_dim]``."""

    def __init__(self, rows, index_dim: int):
        self.rows, self.index_dim = rows, index_dim

    @classmethod
    def zeros(cls, layers: int, num_blocks: int, block_size: int,
              index_dim: int, dtype):
        pack = tokens_per_row(index_dim, block_size)
        return cls(
            jnp.zeros(
                (layers, num_blocks, block_size // pack, pack * index_dim),
                dtype,
            ),
            index_dim,
        )

    @classmethod
    def of(cls, pool):
        """``pool`` itself, or a bare ``[layers, num_blocks, block_size,
        index_dim]`` array as the pool of one token a row."""
        return pool if isinstance(pool, cls) else cls(pool, pool.shape[-1])

    def tree_flatten(self):
        return (self.rows,), self.index_dim

    @classmethod
    def tree_unflatten(cls, index_dim, children):
        return cls(children[0], index_dim)

    @property
    def pack(self) -> int:
        return self.rows.shape[-1] // self.index_dim

    @property
    def shape(self):
        layers, blocks, rows, _ = self.rows.shape
        return (layers, blocks, rows * self.pack, self.index_dim)

    ndim = 4

    @property
    def dtype(self):
        return self.rows.dtype

    @property
    def nbytes(self) -> int:
        return self.rows.nbytes

    def __getitem__(self, index):
        got = self.rows[_block_index(index)]
        return got.reshape(got.shape[:-2] + self.shape[2:])

    @property
    def at(self):
        return _BlocksAt(self)

    # -- token coordinates (the programs of kvpool/sparse.py) -----------

    def blocks_at(self, layer, blocks):
        """The rows of ``blocks`` in ``layer`` AS STORED ``[*blocks,
        block_size // pack, pack * index_dim]``; flattened, the tokens
        in order."""
        return gather_at_layer(self.rows, layer, blocks)

    def tokens_at(self, layer, blocks, offsets):
        """The keys ``[*blocks, index_dim]`` of the tokens at ``offsets``
        of ``blocks`` in ``layer``."""
        pack = self.pack
        rows = gather_at_layer(self.rows, layer, blocks, offsets // pack)
        return _lanes_of(rows, offsets % pack, pack)

    def land_tokens(self, keys, blocks, offsets):
        """``keys [layers, n, index_dim]`` at ``(blocks [n], offsets
        [n])`` of every layer, a token a row at most: each row is read,
        the token's lanes replaced, and written back at its ``(layer,
        block, row)``. The layer is a coordinate of the scatter: a
        window across the layer axis makes the compiler re-lay the whole
        pool, there and back."""
        pack = self.pack
        layers, n = keys.shape[:2]
        at = (
            jnp.arange(layers)[:, None],
            jnp.broadcast_to(blocks, (layers, n)),
            jnp.broadcast_to(offsets // pack, (layers, n)),
        )
        keys = _with_token(
            self.rows[at], keys.astype(self.dtype), offsets % pack, pack
        )
        return IndexKeyPool(self.rows.at[at].set(keys), self.index_dim)

    def land_run(self, keys, table_row, start, block_size: int,
                 past: int):
        """``keys [layers, n, index_dim]`` at the logical tokens ``start
        ... start + n`` of the slot whose blocks are ``table_row``: whole
        rows where ``start`` and ``n`` are multiples of ``pack``, and
        the rows at either end, which the run shares with tokens that
        stay, read and merged. Rows past the table go to block
        ``past`` (the sentinel)."""
        pack, dim = self.pack, self.index_dim
        layers, n = keys.shape[:2]
        n_rows = (n + pack - 2) // pack + 1   # what a run can touch
        first, shift = start // pack, start % pack
        keys = jnp.pad(
            keys.astype(self.dtype), ((0, 0), (pack, 2 * pack), (0, 0))
        )
        keys = jax.lax.dynamic_slice_in_dim(
            keys, pack - shift, n_rows * pack, axis=1
        ).reshape(layers, n_rows, pack, dim)
        row = first + jnp.arange(n_rows)
        per_block = block_size // pack
        block = row // per_block
        inside = block < table_row.shape[0]
        block = jnp.where(
            inside, table_row[jnp.where(inside, block, 0)], past
        )
        at = (
            jnp.arange(layers)[:, None],
            jnp.broadcast_to(block, (layers, n_rows)),
            jnp.broadcast_to(row % per_block, (layers, n_rows)),
        )
        if pack > 1:
            token = jnp.arange(n_rows * pack).reshape(n_rows, pack) - shift
            keys = jnp.where(
                ((token >= 0) & (token < n))[..., None], keys,
                _split(self.rows[at], pack),
            )
        keys = keys.reshape(layers, n_rows, pack * dim)
        return IndexKeyPool(self.rows.at[at].set(keys), self.index_dim)

    def lay_in(self, view, keys, at):
        """A gathered view AS STORED ``[n, rows, pack * index_dim]`` with
        ``keys [n, index_dim]`` laid in at the logical tokens ``at [n]``."""
        pack = self.pack
        row = (jnp.arange(view.shape[0]), at // pack)
        keys = _with_token(
            view[row], keys.astype(view.dtype), at % pack, pack
        )
        return view.at[row].set(keys)


def _split(rows, pack: int):
    """Rows as stored ``[..., pack * dim]`` -> ``[..., pack, dim]``."""
    return rows.reshape(rows.shape[:-1] + (pack, -1))


def _lanes_of(rows, which, pack: int):
    """The ``dim`` lanes of token ``which [...]`` of each of ``rows [...,
    pack * dim]``."""
    if pack == 1:
        return rows
    return jnp.take_along_axis(
        _split(rows, pack), which[..., None, None], axis=-2
    )[..., 0, :]


def _with_token(rows, keys, which, pack: int):
    """``rows [..., pack * dim]`` with ``keys [..., dim]`` in the lanes
    of token ``which [...]`` of each; at one token a row, the keys (and
    the read of ``rows`` is dead code)."""
    if pack == 1:
        return keys
    mine = jnp.arange(pack) == which[..., None]
    return jnp.where(
        mine[..., None], keys[..., None, :], _split(rows, pack)
    ).reshape(rows.shape)
