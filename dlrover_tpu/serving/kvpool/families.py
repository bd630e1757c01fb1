"""The seam between the paged engine and the model families.

``kvpool/engine.py`` is the host: slots, blocks, tables, the prefix
cache, the step loop. What its two programs COMPUTE is a family module's
(``kvpool/dense.py``, ``sparse.py``, ``latent.py``, ``conv.py``,
``window.py``, ``linear.py``, ``delta.py``), and the engine finds that
module through :func:`programs_for` alone. A family module states:

- ``POOL_ATTENTION``: the name of its programs' definition, what
  ``kv_stats()["pool_attention"]`` and the construction log line say (the
  dense family's ``kinds`` answers it by shape under that same name);
- ``kinds(config, pool_dtype, block_size, chunk, slots, max_blocks) ->
  Dict[str, str]``: what each of its parts runs (a Pallas kernel over the
  pool in place, the gathered definition, ...), decided from the
  platform, the pool's dtype and the shapes, with no option for any of
  them and nothing that falls back afterwards, under the names
  ``kv_stats()`` prints. The answers are a part of the programs' cache
  key;
- ``build_decode(config, slots, max_blocks, block_size, counts, kinds)``
  and ``build_prefill(config, max_blocks, block_size, chunk, counts,
  kinds)``: the two functions the engine jits, each taking and handing
  back the pool's arrays in ``kvpool/layout.py``'s order (``counts``:
  the trace counters, bumped when a program is traced);

and, only where it has something to say:

- ``check_shapes(config, block_size, chunk)``: what its programs are not
  built for, refused by name when an engine is constructed;
- ``decode_counts(config, fills)``: what a decode launch over slots at
  rows ``fills`` carries, for its ``serving.step`` span;
- ``chunk_rows_scored(n_valid, chunk, kinds)``: the token rows a chunk
  of ``n_valid`` valid rows scores of the ``chunk`` it launches;
- ``pool_stats(engine)``: the ``kv_stats()`` keys that family alone
  reports;
- ``build_verify`` / ``build_draft`` and a ``quantized`` keyword on the
  two builders: the speculative and the int8 programs (the dense
  family's; the engine refuses both for every layout but K and V in one
  group).

This module is a LEAF: it imports nothing of the package (a family
module by name, when asked), so every file of it may import this one.
It also holds the two things every family shares: the platform probe and
the sentinel block.
"""

import importlib

# Pool row 0 absorbs the masked-garbage appends of non-active slots;
# never allocated, never read.
SENTINEL_BLOCK = 0

# ``config.kind`` (``models.model_for``: the model's module) -> the
# module of this package that holds its paged programs.
FAMILIES = {
    "llama": "dense",
    "sparse_lm": "sparse",
    "latent_lm": "latent",
    "conv_lm": "conv",
    "window_lm": "window",
    "linear_sparse_lm": "linear",
    "delta_lm": "delta",
}


def programs_for(config):
    """The family module that serves ``config`` (module docstring)."""
    return importlib.import_module(
        "dlrover_tpu.serving.kvpool." + FAMILIES[config.kind]
    )


def _on_tpu() -> bool:
    """The platform probe of every family's ``kinds`` (tests patch it
    HERE to take the kernel paths in interpret mode)."""
    import jax

    return jax.default_backend() == "tpu"
