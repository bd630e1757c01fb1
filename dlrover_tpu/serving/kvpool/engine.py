"""Paged serving engine: block-table KV over a shared block pool.

The flat engine (serving/engine.py) reserves one ``[max_len]`` KV row
per slot — a 20-token request pins as much HBM as a 1024-token one, and
a shared system prompt re-prefills from scratch in every slot. Here the
cache is ``[layers, num_blocks, block_size, kv_heads, head_dim]`` and a
slot's logical cache is the pool rows its BLOCK TABLE names:

- **Block tables as traced args.** The ``[slots, max_blocks]`` int32
  tables ride into the compiled steps exactly like the fill vector:
  every admission/allocation/COW changes table VALUES, never shapes, so
  the no-retrace-across-admissions property survives paging. The
  append is a per-slot scatter at ``(table[cursor // bs], cursor %
  bs)``; non-active slots are redirected to the reserved SENTINEL block
  0 so their masked-garbage writes can never land in a block another
  slot shares (the flat engine's own-row trick does not survive
  sharing).
- **This file is the HOST**: slots, blocks, tables, the prefix cache,
  the step loop. What the two programs compute is a FAMILY module's
  (``kvpool/families.py``: ``programs_for(config)`` names it, and it
  states ``kinds`` / ``build_decode`` / ``build_prefill``); one cached
  builder (:func:`_steps_for`) jits them for every family, and this file
  names no family above the adapter block at its end. What each part of
  an engine's programs runs (a kernel over the pool in place, the
  gathered definition) follows from the platform and the pool, not from
  a knob: :attr:`PagedServingEngine.kinds`, the construction log line and
  ``kv_stats()`` say it.
- **Visibility invariant, unchanged.** A logical row is read iff
  ``row < fill``; stale or foreign content beyond a slot's fill —
  including the longer tail of a SHARED prefix block — is masked out
  per slot, per row (docs/DESIGN.md §31).
- **Cross-request prefix cache.** Admission hashes the prompt's full
  blocks against the :class:`PrefixCache`; a hit slots the warm chain
  straight into the block table and prefill SKIPS the covered chunks
  (TTFT drops by the skipped chunk iterations). Shared blocks are
  refcounted and immutable: the one legal rewrite (a chunk-aligned
  re-prefill over a shared block, identical values) privatizes first
  via copy-on-write — a small compiled block-copy program, counted in
  ``trace_counts`` like its siblings.
- **Oversubscription + relief.** ``num_blocks`` may be far below
  ``slots * max_blocks`` (short requests hold few blocks — that is the
  capacity win). When the pool runs dry the engine first evicts
  prefix-cache LRU chains, then PREEMPTS the youngest active request
  (front-requeued with progress reset, no requeue-budget charge); an
  engine is constructed with room for at least one full-length slot,
  so relief always terminates.
"""

import functools
import time
from typing import Dict, List, NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from dlrover_tpu.common.log import logger
from dlrover_tpu.serving.engine import ServingEngine, _PhaseMarks, _h2d
from dlrover_tpu.serving.kvpool.allocator import (
    BlockAllocator,
    BlockPoolExhausted,
)
from dlrover_tpu.serving.kvpool import layout as pool_layout
from dlrover_tpu.serving.kvpool.families import SENTINEL_BLOCK, programs_for
from dlrover_tpu.serving.kvpool.groups import ReachGroup, band_blocks
from dlrover_tpu.serving.kvpool.prefix_cache import PrefixCache
from dlrover_tpu.serving.scheduler import DECODE, PREFILL, Request


class _PagedSteps(NamedTuple):
    """An engine's compiled programs, whatever its family, and what they
    were built with."""

    prefill: object
    decode: object
    cow: object
    imp: object          # migration import: host block rows -> pool[dst]
    exp: object          # migration export: pool[src] -> one block's rows
    trace_counts: Dict[str, int]
    pool_attention: str  # the name of the programs' definition
    kinds: tuple         # the family's ``kinds()``, its items sorted

    # Read by name in benchmark/ (rehearse_mellum2.py, rehearse_olmo_hybrid
    # .py): retired with the adapter block at this file's end.
    window_decode_attention = property(
        lambda self: dict(self.kinds).get("window_decode_attention", "")
    )
    window_chunk_attention = property(
        lambda self: dict(self.kinds).get("window_chunk_attention", "")
    )
    linear_kinds = property(lambda self: self.kinds)


class _PagedSpecSteps(NamedTuple):
    """Speculative verify/draft programs over the block pool —
    compiled separately from _PagedSteps for the same reason as the
    flat engine's _SpecSteps: spec on/off engines share the base
    programs."""

    verify: object
    draft: object        # None for the host-side n-gram drafter
    trace_counts: Dict[str, int]


def _build_cow_copy(counts, n_pools: int, n_state: int = 0):
    """Device block copy src -> dst in EVERY pool array, all layers (K
    and V; the scale pools of an int8 cache; the index keys of a sparse
    model; a latent model's one array): the copy-on-write primitive.
    What a block holds is the
    model's; that a block operation moves all of it is the pool's
    (docs/DESIGN.md §37). src/dst are traced scalars — privatizing any
    block never retraces. The ``n_state`` per-slot arrays (and their
    snapshots) that follow the pools ride through untouched: no block
    holds any of them."""

    def cow(*args):
        counts["cow"] += 1  # traces only
        pools, state = args[:n_pools], args[n_pools:n_pools + n_state]
        src, dst = args[n_pools + n_state:]
        return tuple(p.at[:, dst].set(p[:, src]) for p in pools) + state

    return cow


def _build_import_scatter(counts, n_pools: int, n_state: int = 0):
    """Migration import (kvpool/migrate, §36): land one migrated
    block's rows — host data, one ``[L, block_size, ...]`` array a pool
    array, in the pools' order — at pool row ``dst``. ``dst`` is a
    traced scalar like the COW src/dst, so importing any number of
    requests into any blocks never retraces."""

    def imp(*args):
        counts["imp"] += 1  # traces only
        pools, state = args[:n_pools], args[n_pools:n_pools + n_state]
        rows = args[n_pools + n_state:2 * n_pools + n_state]
        dst = args[2 * n_pools + n_state]
        return tuple(
            p.at[:, dst].set(r.astype(p.dtype))
            for p, r in zip(pools, rows)
        ) + state

    return imp


def _build_export_gather(counts, n_pools: int, n_state: int = 0):
    """Migration export (kvpool/migrate, §36): read one block's rows
    out of every pool array at row ``src`` — the gather mirror of the
    import scatter. ``src`` is a traced scalar, so exporting a request
    of ANY block count is n calls of one compiled program; the jnp
    fancy-index alternative (``k[:, ids]``) recompiles per block-count
    and stalled the serve loop ~400ms per new shape on CPU. No pool
    donation: the request stays live on the source until released."""

    def exp(*args):
        counts["exp"] += 1  # traces only
        return tuple(
            p[:, args[n_pools + n_state]] for p in args[:n_pools]
        )

    return exp


@functools.lru_cache(maxsize=16)
def _paged_spec_steps(
    config, slots: int, num_blocks: int, max_blocks: int, block_size: int,
    spec_k: int, draft_layers: int, kv_dtype: str = "fp",
) -> _PagedSpecSteps:
    family = programs_for(config)
    counts = {"verify": 0, "draft": 0}
    quantized = kv_dtype == "int8"
    pool_args = (0, 1, 2, 3) if quantized else (0, 1)
    verify = jax.jit(
        family.build_verify(config, slots, max_blocks, block_size, spec_k,
                            counts, quantized=quantized),
        donate_argnums=pool_args,
    )
    draft = None
    if draft_layers > 0:
        draft = jax.jit(
            family.build_draft(config, slots, max_blocks, block_size,
                               spec_k, draft_layers, counts,
                               quantized=quantized),
            donate_argnums=pool_args,
        )
    return _PagedSpecSteps(verify=verify, draft=draft,
                           trace_counts=counts)


def _steps(config, slots: int, num_blocks: int, max_blocks: int,
           block_size: int, chunk: int, kv_dtype: str = "fp",
           group_blocks: tuple = ()) -> _PagedSteps:
    """The programs of ``config``'s family (``kvpool/families.py``),
    compiled once per shape key and shared across engines (the flat
    engine's lru_cache discipline). What each of their parts runs (the
    family's ``kinds``) is asked here and is part of the key.
    ``group_blocks``: the reach groups' block counts (a pool in groups)."""
    kinds = programs_for(config).kinds(
        config, pool_layout.pool_arrays(config, kv_dtype)[0].dtype,
        block_size, chunk, slots, max_blocks,
    )
    return _steps_for(
        config, slots, num_blocks, max_blocks, block_size, chunk, kv_dtype,
        group_blocks, tuple(sorted(kinds.items())),
    )


@functools.lru_cache(maxsize=64)
def _steps_for(config, slots: int, num_blocks: int, max_blocks: int,
               block_size: int, chunk: int, kv_dtype: str,
               group_blocks: tuple, kinds: tuple) -> _PagedSteps:
    family, named = programs_for(config), dict(kinds)
    counts = {"prefill": 0, "decode": 0, "cow": 0, "imp": 0, "exp": 0}
    # The pools every program leads with and hands back, donated: the
    # model's statement of what a block holds (kvpool/layout.py) ...
    layout = pool_layout.grouped_pool_arrays(config, kv_dtype)
    n_pools = len(layout)
    # ... and the per-slot arrays with their snapshots, after them.
    n_state = 2 * len(pool_layout.state_arrays(config))
    pool_args = tuple(range(n_pools + n_state))
    # (an int8 pool: the family's programs' int8 twins, the scale pools
    # donated with the rest)
    q8 = {"quantized": True} if kv_dtype == "int8" else {}
    decode = jax.jit(family.build_decode(
        config, slots, max_blocks, block_size, counts, named, **q8
    ), donate_argnums=pool_args)
    prefill = jax.jit(family.build_prefill(
        config, max_blocks, block_size, chunk, counts, named, **q8
    ), donate_argnums=pool_args)
    imp = exp = None
    if len(pool_layout.cache_groups(config)) > 1:
        # A pool in groups copies the first group's arrays alone (the one
        # group a block of which more than one owner can hold while it is
        # written; the others ride through as the state arrays do), and
        # migrates none: refused by name (kvpool/migrate.py).
        n_first = sum(1 for a in layout if not a.group)
        cow = _build_cow_copy(counts, n_first, n_pools - n_first)
    else:
        cow = _build_cow_copy(counts, n_pools, n_state)
        imp = jax.jit(
            _build_import_scatter(counts, n_pools, n_state),
            donate_argnums=pool_args,
        )
        # No donation: export reads the pools and the source keeps
        # serving from them until the importer acks.
        exp = jax.jit(_build_export_gather(counts, n_pools, n_state))
    return _PagedSteps(
        prefill, decode, jax.jit(cow, donate_argnums=pool_args), imp, exp,
        counts, named.get("pool_attention", family.POOL_ATTENTION),
        kinds,
    )


class _StateSteps(NamedTuple):
    restore: object
    get: object
    put: object
    trace_counts: Dict[str, int]


@functools.lru_cache(maxsize=4)
def _state_steps(n_state: int) -> _StateSteps:
    """The programs that move a SLOT's state, for ``n_state`` per-slot
    arrays (``kvpool/layout.py``), whatever they hold: ``restore(*state,
    *snapshots, slot, snapshot)`` sets the slot's state to a snapshot's
    (snapshot 0, the sentinel: to zeros) and hands back both tuples;
    ``get(*state, slot)`` / ``put(*state, *rows, slot)`` read and write
    one slot's (migration). Slot and snapshot are traced scalars: no
    admission retraces."""
    counts = {"state_restore": 0, "state_get": 0, "state_put": 0}

    def restore(*args):
        counts["state_restore"] += 1  # traces only
        state, snaps = args[:n_state], args[n_state:2 * n_state]
        slot, snapshot = args[2 * n_state:]
        with jax.named_scope("state"), jax.named_scope("restore"):
            return tuple(
                s.at[:, slot].set(
                    jnp.where(snapshot > 0, p[:, snapshot], 0).astype(s.dtype)
                )
                for s, p in zip(state, snaps)
            ) + tuple(snaps)

    def get(*args):
        counts["state_get"] += 1  # traces only
        return tuple(s[:, args[n_state]] for s in args[:n_state])

    def put(*args):
        counts["state_put"] += 1  # traces only
        state, rows = args[:n_state], args[n_state:2 * n_state]
        return tuple(
            s.at[:, args[2 * n_state]].set(r.astype(s.dtype))
            for s, r in zip(state, rows)
        )

    return _StateSteps(
        jax.jit(restore, donate_argnums=tuple(range(2 * n_state))),
        jax.jit(get),
        jax.jit(put, donate_argnums=tuple(range(n_state))),
        counts,
    )


class PagedServingEngine(ServingEngine):
    """ServingEngine over a paged block pool (see module docstring).

    Same host-side step loop, scheduler, metrics, spans, and recovery
    semantics as the flat engine — only the pool hooks and the two step
    programs differ. ``num_blocks`` defaults to exactly the flat
    engine's HBM budget (``slots * max_len / block_size`` + sentinel);
    pass fewer blocks and MORE slots for the oversubscribed capacity
    win the bench measures. ``kv_cache_dtype="int8"`` stores the pool
    as int8 with per-(row, head) f32 scale pools (ops/kv_quant, §33):
    ~1.94x the blocks fit the same HBM, dequantization folds into the
    attention math, and COW/prefix/preemption machinery is unchanged
    (shared blocks share their scales)."""

    def __init__(
        self,
        config,
        params,
        slots: int,
        max_len: int,
        prefill_chunk: int = 64,
        block_size: int = 16,
        num_blocks: Optional[int] = None,
        prefix_cache: bool = True,
        prefix_cache_blocks: Optional[int] = None,
        token_budget: Optional[int] = None,
        drain_mode: bool = False,
        rng=None,
        registry=None,
        max_requeues: int = 3,
        slo_classes=None,
        kv_cache_dtype: str = "fp",
        spec_k: int = 0,
        spec_drafter: str = "ngram",
        spec_draft_layers: int = 2,
        window_blocks: Optional[int] = None,
        state_snapshots: Optional[int] = None,
    ):
        if kv_cache_dtype not in ("fp", "int8"):
            raise ValueError(
                f"kv_cache_dtype {kv_cache_dtype!r} not in "
                f"('fp', 'int8')"
            )
        if max_len % block_size:
            raise ValueError(
                f"max_len {max_len} must be a multiple of block_size "
                f"{block_size}"
            )
        if prefill_chunk % block_size and block_size % prefill_chunk:
            # Chunk/block alignment keeps the prefill scatter-back a
            # STATIC number of whole blocks; misaligned chunks would
            # straddle a shared/fresh block boundary mid-block.
            raise ValueError(
                f"prefill_chunk {prefill_chunk} and block_size "
                f"{block_size} must divide one another"
            )
        # serving.engine_build opens here and ends with this __init__
        # (the base constructor marks its own phases into it).
        build = _PhaseMarks(time.monotonic())
        self.kv_cache_dtype = kv_cache_dtype
        # The module that holds this model's programs (kvpool/families.py).
        self._family = programs_for(config)
        # What a block holds (kvpool/layout.py), and the device arrays
        # by name; ``_pools()`` is their tuple in the layout's order.
        # The pool's groups (one, unless the config states more): the
        # first keeps every row, the others a reach below the next row.
        self._groups = pool_layout.cache_groups(config)
        if len(self._groups) > 1:
            refused = [name for name, on in (
                ("an int8 pool (kv_cache_dtype='int8')",
                 kv_cache_dtype != "fp"),
                ("speculative decoding (spec_k)", spec_k),
                (f"a prefill_chunk of {prefill_chunk} that is not whole "
                 f"blocks of {block_size}", prefill_chunk % block_size),
            ) if on]
            if refused:
                raise ValueError(
                    "a pool in layer groups ("
                    + ", ".join(g.name for g in self._groups)
                    + ") is not served with " + " nor with ".join(refused)
                    + ": the int8 and the verify / draft programs know one "
                    "group under one table, and a prefix hit resumes at a "
                    "block boundary"
                )
        self._layout = pool_layout.grouped_pool_arrays(config, kv_cache_dtype)
        # ... and what a SLOT holds whatever its length (none: every
        # model whose whole state is rows in pages).
        self._state_layout = pool_layout.state_arrays(config)
        # Every program's leading arguments by name, in order: the
        # per-token arrays, the per-slot ones, their snapshots.
        state = [a.name for a in self._state_layout]
        self._pool_names = [a.name for a in self._layout] + state + [
            name + "_snapshots" for name in state
        ]
        self._arrays: Dict[str, object] = {}
        if self._state_layout and (kv_cache_dtype != "fp" or spec_k):
            raise ValueError(
                "a model with per-slot state ("
                + ", ".join(a.describe() for a in self._state_layout)
                + ") is served from a floating-point pool without "
                "speculative decoding: the int8 and the verify / draft "
                "programs know rows in pages alone"
            )
        if self._state_layout and prefill_chunk % block_size:
            raise ValueError(
                f"prefill_chunk {prefill_chunk} must be whole blocks of "
                f"{block_size} for a model with per-slot state: a prefix "
                "hit resumes at the block boundary of its snapshot"
            )
        if spec_k and [a.name for a in self._layout][:2] != ["k", "v"]:
            raise ValueError(
                "the speculative programs read K and V; this model's "
                "blocks hold " + self._block_holds()
            )
        self.block_size = block_size
        self.max_blocks = max_len // block_size
        if num_blocks is None:
            num_blocks = slots * self.max_blocks + 1
        if num_blocks - 1 < self.max_blocks:
            # Room for at least one full-length slot, or pool-pressure
            # relief (evict cache, preempt peers) could never free
            # enough for a lone max-length request.
            raise ValueError(
                f"num_blocks {num_blocks} cannot hold one full slot "
                f"({self.max_blocks} blocks + sentinel)"
            )
        self.num_blocks = num_blocks
        self._allocator = BlockAllocator(num_blocks, reserved=1)
        # What the family's programs are not built for, refused by name.
        getattr(self._family, "check_shapes", lambda *a: None)(
            config, block_size, prefill_chunk
        )
        self.state_snapshots = (
            self._snapshot_budget(state_snapshots, slots)
            if self._state_layout and prefix_cache else 0
        )
        # Every group's table, stacked as the grouped programs take
        # them; ``_tables`` is the first group's, a view.
        self._group_tables = np.zeros(
            (len(self._groups), slots, self.max_blocks), np.int32
        )
        self._tables = self._group_tables[0]
        self._reach_groups = self._build_reach_groups(
            slots, prefill_chunk, window_blocks
        )
        self._cache: Optional[PrefixCache] = (
            self._new_prefix_cache(prefix_cache_blocks)
            if prefix_cache else None
        )
        self._slot_blocks: List[List[int]] = [[] for _ in range(slots)]
        # USABLE-hit accounting (what kv_stats/bench/heartbeats report):
        # a raw cache hit whose blocks are all discarded by chunk
        # alignment saved nothing and must count as a miss — the
        # cache's own raw counters would overstate the win.
        self._prefix_hits = 0
        self._prefix_misses = 0
        self._prefix_hit_blocks = 0
        self._prefix_hit_tokens = 0   # prompt rows no chunk had to run
        # Per-slot state: slots restored from a snapshot / zeroed at
        # admission, snapshots written, hit blocks given up because no
        # snapshot lay that deep, and the id each prefilling slot holds.
        self._state_restores = 0
        self._state_restores_from_snapshot = 0
        self._state_snapshots_taken = 0
        self._state_snapshots_denied = 0
        self._prefix_rounded_down_blocks = 0
        self._chunk_rows_launched = self._chunk_rows_scored = 0
        self._slot_snapshot = [0] * slots
        # Reach groups: blocks released in the iteration under way, and
        # each slot's lowest query position in its last launch.
        self._released_this_step = 0
        self._launch_position = np.zeros(slots, np.int64)
        build.mark("prefix_cache")
        # The base __init__ builds every pool array via _alloc_pool().
        super().__init__(
            config, params, slots, max_len,
            prefill_chunk=prefill_chunk, token_budget=token_budget,
            drain_mode=drain_mode, rng=rng, registry=registry,
            max_requeues=max_requeues, slo_classes=slo_classes,
            spec_k=spec_k, spec_drafter=spec_drafter,
            spec_draft_layers=spec_draft_layers, build_marks=build,
        )
        build.mark("alloc_side_pools")
        # Block watermark: only admit a request the pool can hold
        # (prompt + first decode block) counting evictable cache as
        # free — otherwise bursty arrivals thrash preemptions, each
        # one burning its victim's whole prefill investment.
        self.scheduler.admission_gate = self._can_admit
        # The base __init__ bound the FLAT step programs (never traced
        # — jit is lazy); swap in the paged programs, keyed on the
        # paged shapes, and re-settle the retrace snapshot.
        self._steps = _steps(
            config, slots, self.num_blocks, self.max_blocks, block_size,
            prefill_chunk, kv_cache_dtype,
            tuple(g.num_blocks for g in self._reach_groups),
        )
        logger.info(
            "paged engine: %d slots x %d rows, %d blocks of %d "
            "(%s KV%s), a block holds %s; decode and prefill attention "
            "%s%s%s%s",
            slots, max_len, self.num_blocks, block_size, kv_cache_dtype,
            f" + index keys [{self._index_dim}], "
            f"{self.index_tokens_per_row} to a row, top-"
            f"{config.index_topk}" if self._index_dim else "",
            self._block_holds(), self.pool_attention,
            "".join(
                f", {name} {kind}" for name, kind in self.kinds.items()
                if name != "pool_attention"
            ),
            "; a slot holds " + ", ".join(
                a.describe() for a in self._state_layout
            ) + f", {self.state_snapshots} snapshots"
            if self._state_layout else "",
            "; layer groups " + ", ".join(
                [f"{self._groups[0].name} ({self._groups[0].layers} layers, "
                 "keeps all)"]
                + [f"{g.name} ({g.layers} layers, {g.num_blocks} blocks, "
                   f"keeps {g.reach} rows below the next, then releases)"
                   for g in self._reach_groups]
            ) if self._reach_groups else "",
        )
        if self.spec_k:
            # Same swap for the spec programs (the flat ones the base
            # __init__ bound were never traced — jit is lazy).
            self._spec = _paged_spec_steps(
                config, slots, self.num_blocks, self.max_blocks,
                block_size, self.spec_k, self.spec_draft_layers,
                kv_dtype=kv_cache_dtype,
            )
        self._trace_snapshot = self._all_trace_counts()
        # Bytes per block, for the HBM-in-use gauge, by array: int8
        # pools pay 1 byte/element + one f32 scale per (row, head) —
        # the 1.94x-per-token capacity lever the equal-HBM bench
        # exploits.
        self._array_block_bytes = {
            a.name: a.block_bytes(self._groups[a.group].layers, block_size)
            for a in self._layout
        }
        # (the first group's: what ``bytes_in_use`` goes by)
        self._block_bytes = sum(
            self._array_block_bytes[a.name] for a in self._layout
            if not a.group
        )
        self.metrics.kv_blocks_total.set(
            self._allocator.managed, group=self._groups[0].name
        )
        for g in self._reach_groups:
            self.metrics.kv_blocks_total.set(
                g.allocator.managed, group=g.name
            )
        build.mark("paged_programs")
        self._end_build(build)

    def _build_bytes(self) -> Dict[str, int]:
        index = self._ki.nbytes if self._ki is not None else 0
        n_pools = len(self._layout)
        out = {
            "params_bytes": sum(
                x.nbytes for x in jax.tree_util.tree_leaves(self._params)
            ),
            "pool_bytes": sum(
                p.nbytes for p in self._pools()[:n_pools]
            ) - index,
            "index_pool_bytes": index,
        }
        if self._state_layout:
            out["state_bytes"] = sum(
                p.nbytes for p in self._pools()[n_pools:]
            )
        return out

    # ---- pool construction / programs --------------------------------------

    def _pool_array(name):  # noqa: N805 (a property factory)
        """The device array ``name`` of the pool; None for a model whose
        blocks hold none."""
        return property(
            lambda self: self._arrays.get(name),
            lambda self, value: self._arrays.__setitem__(name, value),
        )

    _k, _v = _pool_array("k"), _pool_array("v")
    _kscale, _vscale = _pool_array("k_scale"), _pool_array("v_scale")
    _ki = _pool_array("index_keys")
    _latent = _pool_array("latent")
    del _pool_array

    def _block_holds(self) -> str:
        return ", ".join(a.describe() for a in self._layout)

    @property
    def _quantized(self) -> bool:
        return self.kv_cache_dtype == "int8"

    @property
    def _index_dim(self) -> int:
        """Width of the pool's index-key rows: the model's, 0 for a
        model whose attention reads its whole cache."""
        return (
            self.config.index_dim
            if getattr(self.config, "index_topk", 0) else 0
        )

    @property
    def index_tokens_per_row(self) -> int:
        """Tokens a row of the index-key pool holds on the device
        (``index_pool.tokens_per_row``: from ``index_dim`` and
        ``block_size`` alone); 0 for a model that keeps none."""
        return self._ki.pack if self._ki is not None else 0

    @property
    def pool_attention(self) -> str:
        """The name of the programs' definition (the family's
        ``POOL_ATTENTION``; a dense model's ``"paged_kernel"`` or
        ``"xla_gather"``, by shape)."""
        return self._steps.pool_attention

    @property
    def kinds(self) -> Dict[str, str]:
        """What each part of this engine's programs runs, by the names
        ``kv_stats()`` prints (the family's ``kinds``: decided from the
        platform, the pool's dtype and the shapes; part of the programs'
        cache key)."""
        return dict(self._steps.kinds)

    # Read by name in benchmark/runners (serve_delta.py, serve_window.py):
    # retired with the adapter block at this file's end.
    linear_kinds = kinds
    window_decode_attention = property(
        lambda self: self.kinds.get("window_decode_attention", "")
    )

    def _fresh_arrays(self) -> Dict[str, object]:
        """Every array of the pool, zeroed: ONE rebuild site for all of
        them (init, warmup, step-error recovery), so that no two can be
        mismatched."""
        blocks = [self.num_blocks] + [
            g.num_blocks for g in self._reach_groups
        ]
        arrays = {
            a.name: pool_layout.fresh(
                a, self._groups[a.group].layers, blocks[a.group],
                self.block_size,
            )
            for a in self._layout
        }
        # Per-slot state, then its snapshots (row 0: the sentinel).
        for a in self._state_layout:
            arrays[a.name] = a.fresh(self.slots)
        for a in self._state_layout:
            arrays[a.name + "_snapshots"] = a.fresh(self.state_snapshots + 1)
        return arrays

    def _alloc_pool(self) -> None:
        self._arrays = jax.block_until_ready(self._fresh_arrays())

    def _pools(self):
        """The donated-pool argument tuple every compiled program
        leads with, in the layout's order: (k, v) for fp, (k, v,
        k_scale, v_scale) for int8, (k, v, index keys) for a sparse
        model, (latent,) for a latent one; after them a model's
        per-slot arrays and their snapshots (``kvpool/layout.py``).
        Call sites splat this and hand the returned tuple back to
        :meth:`_set_pools` — ONE argument list per program, whatever a
        block or a slot holds."""
        return tuple(self._arrays[name] for name in self._pool_names)

    def _set_pools(self, pools) -> None:
        self._arrays = dict(zip(self._pool_names, pools))

    def warmup(self) -> None:
        """Compile all three paged programs on throwaway state, then
        rebuild the pool — first real request pays no compile."""
        marks = _PhaseMarks(time.monotonic())
        chunk = np.zeros((1, self.prefill_chunk), np.int32)
        pools = self._pools()
        *pools, first = self._steps.prefill(
            *pools, self._params, jnp.asarray(chunk),
            jnp.zeros(self._program_tables.shape[:-2] + (self.max_blocks,),
                      jnp.int32),
            np.int32(0), np.int32(1), np.float32(0.0),
            self._rng, np.int32(0), np.bool_(True),
            *((np.int32(0),) * 3 if self._state_layout else ()),
        )
        jax.block_until_ready(first)
        marks.mark("prefill")
        # Both ways a launch is fed: the host's tokens and a chunk's
        # first token, then the vector that launch returned.
        fed = jnp.asarray(np.zeros(self.slots, np.int32))
        n_pools = len(pools)
        for first, first_slot in ((first, 0), (self._no_first, -1)):
            out = self._steps.decode(
                *pools, self._params,
                jnp.asarray(np.zeros_like(self._program_tables)),
                jnp.asarray(np.zeros(self.slots, np.int32)), fed,
                jnp.asarray(np.zeros(self.slots, bool)),
                jnp.asarray(np.zeros(self.slots, np.float32)),
                self._rng, np.int32(0), first, np.int32(first_slot),
            )
            pools, fed = out[:n_pools], out[n_pools]
            jax.block_until_ready(fed)
            marks.mark("decode")
        pools = jax.block_until_ready(
            self._steps.cow(*pools, np.int32(0), np.int32(0))
        )
        marks.mark("cow")
        # Import hands one block's rows an array, in the logical shape
        # and as kvpool/migrate hands them: a dense model's K and V
        # dequantized to f32 on the host, everything else in the pool's
        # own dtype.
        rows = [
            jnp.zeros(
                (pool_layout.pool_layers(self.config),
                 a.block_rows(self.block_size)) + a.row_shape,
                a.import_dtype,
            )
            for a in self._layout
        ]
        if self._steps.imp is not None:     # (a grouped pool migrates none)
            pools = jax.block_until_ready(
                self._steps.imp(*pools, *rows, np.int32(0))
            )
            marks.mark("imp")
            # Export gather (non-donating): warm so the first migration
            # out of this engine never stalls the serve loop on a compile.
            jax.block_until_ready(self._steps.exp(*pools, np.int32(0)))
            marks.mark("exp")
        if self._state_layout:
            self._set_pools(pools)
            self._restore_state(0, 0)
            self._put_slot_state(0, self._get_slot_state(0))
            pools = jax.block_until_ready(self._pools())
            marks.mark("state")
        if self._spec is not None:
            tbl = jnp.asarray(
                np.zeros((self.slots, self.max_blocks), np.int32)
            )
            z_i = jnp.asarray(np.zeros(self.slots, np.int32))
            z_b = jnp.asarray(np.zeros(self.slots, bool))
            z_f = jnp.asarray(np.zeros(self.slots, np.float32))
            drafts = jnp.asarray(
                np.zeros((self.slots, self.spec_k), np.int32)
            )
            if self._spec.draft is not None:
                *pools, drafts = self._spec.draft(
                    *pools, self._params, tbl, z_i, z_i, z_b
                )
                jax.block_until_ready(drafts)
                marks.mark("draft")
            *pools, _em, acc = self._spec.verify(
                *pools, self._params, tbl, z_i, z_i, drafts, z_i,
                z_b, z_f, self._rng, np.int32(0),
            )
            jax.block_until_ready(acc)
            marks.mark("verify")
        # The warmed arrays go BEFORE the fresh ones are made: both at
        # once were the process's peak (16.4 GB of the chip's 16.9 with
        # 4.3 GB of pools and state: my chip runs, PR 55).
        del pools
        self._arrays = {}
        self._alloc_pool()
        self._trace_snapshot = self._all_trace_counts()
        self._end_warmup(marks)

    # ---- block bookkeeping -------------------------------------------------

    def _can_admit(self, req: Request) -> bool:
        """Admission watermark: free + cache-evictable blocks must
        cover the request's whole prompt plus one decode block (a
        prefix hit only LOWERS the real need — conservative). A
        request that already LOST its slot to pool pressure re-admits
        pessimistically, against its full prompt+decode worst case:
        optimistic re-admission is exactly the preempt-readmit-preempt
        thrash cycle, each lap burning a whole prefill."""
        rows = req.prompt_len + (
            req.max_new_tokens if req.preemptions else 1
        )
        rows = min(rows, self.max_len)
        need = -(-rows // self.block_size)
        stats = self._allocator.stats(self._live_block_ids())
        if stats["free"] + stats["cached"] < need:
            return False
        # Each reach group by its own need: a long prompt wants its whole
        # length of the first group and a band and a chunk of these.
        for g in self._reach_groups:
            stats = g.stats()
            if stats["free"] + stats["cached"] < g.blocks_for(
                rows, self.prefill_chunk
            ):
                return False
        return True

    def _live_block_ids(self) -> set:
        live = set()
        for blocks in self._slot_blocks:
            live.update(blocks)
        return live

    def _alloc_blocks(self, n: int, requester: Request) -> List[int]:
        """All-or-nothing allocation with the relief ladder: prefix
        cache LRU eviction first, then preemption of the YOUNGEST
        active request (never ``requester``). Raises only when relief
        is structurally impossible (requester alone overflows the
        pool), which the step-error recovery path bounds."""
        while True:
            try:
                return self._allocator.alloc(n)
            except BlockPoolExhausted:
                missing = n - self._allocator.free_count()
                if self._cache is not None and self._cache.evict_lru(
                    missing, must_free=True
                ):
                    continue
                victim = self._pick_preemption_victim(requester)
                if victim is None:
                    raise
                # A preemption resets its victim: commit what the
                # device still holds for it first.
                self._drain("preempt")
                self._preempt(victim)

    def _pick_preemption_victim(
        self, requester: Request
    ) -> Optional[Request]:
        cands = [
            r for r in self.scheduler.active()
            if r is not requester and r.state in (PREFILL, DECODE)
            and self._slot_blocks[r.slot]
        ]
        if not cands:
            return None
        return max(cands, key=lambda r: r.rid)  # youngest first out

    def _preempt(self, victim: Request) -> None:
        slot = victim.slot
        # Reset-to-zero progress is wasted compute (§34 accounting).
        if victim.prefill_pos:
            self.metrics.tokens_wasted.inc(
                victim.prefill_pos, kind="prefill"
            )
        if victim.tokens:
            self.metrics.tokens_wasted.inc(
                len(victim.tokens), kind="decode"
            )
        self.scheduler.preempt(victim)
        self._release_slot(victim, slot)
        self._lengths[slot] = 0
        self._tokens[slot] = 0
        self._temps[slot] = 0.0
        self.metrics.kv_preemptions.inc()
        self.metrics.requests.inc(outcome="preempted")
        logger.info(
            "kvpool pressure: preempted rid %d (slot %d) to free "
            "blocks", victim.rid, slot,
        )

    def _ensure_blocks(self, req: Request, upto_rows: int) -> None:
        """Grow ``req``'s block table to cover ``upto_rows`` logical
        rows (clamped to max_len)."""
        upto_rows = min(upto_rows, self.max_len)
        need = -(-upto_rows // self.block_size)
        blocks = self._slot_blocks[req.slot]
        missing = need - len(blocks)
        if missing <= 0:
            return
        fresh = self._alloc_blocks(missing, req)
        start = len(blocks)
        blocks.extend(fresh)
        self._tables[req.slot, start:start + len(fresh)] = fresh

    def _privatize(self, req: Request, logical_idx: int) -> None:
        """COW: the slot is about to WRITE logical block
        ``logical_idx``; if that block is shared, copy it to a fresh
        private block first (shared blocks are immutable)."""
        blocks = self._slot_blocks[req.slot]
        old = blocks[logical_idx]
        if self._allocator.refcount(old) <= 1:
            return
        new = self._alloc_blocks(1, req)[0]
        self._set_pools(self._steps.cow(
            *self._pools(), np.int32(old), np.int32(new)
        ))
        self._allocator.decref(old)
        self._allocator.cow_copies_total += 1
        blocks[logical_idx] = new
        self._tables[req.slot, logical_idx] = new
        self.metrics.kv_cow_copies.inc()

    # ---- pool hooks (the base step loop calls these) -----------------------

    def _admit_slot(self, req: Request) -> None:
        super()._admit_slot(req)
        slot = req.slot
        self._tables[slot, :] = SENTINEL_BLOCK
        self._slot_blocks[slot] = []
        if self._state_layout:
            self._admit_state_slot(req)
            return
        if self._reach_groups:
            self._admit_grouped_slot(req)
            return
        if self._cache is None:
            return
        hit = self._cache.lookup(req.prompt)
        # Never skip the FINAL prompt token: its forward produces the
        # first sampled token, so a full-prompt hit still re-runs the
        # last chunk (identical values; COW privatizes any shared
        # touched block). Chunk-align the resume point, and drop hit
        # blocks that lie ENTIRELY inside the re-prefilled span — they
        # would only be COW-copied and rewritten.
        start = 0
        if hit:
            start = min(len(hit) * self.block_size, req.prompt_len - 1)
            start -= start % self.prefill_chunk
            keep = -(-start // self.block_size)  # partial head stays
            for block in hit[keep:]:
                self._allocator.decref(block)
            hit = hit[:keep]
        self._adopt_hit(req, hit, start)

    def _adopt_hit(self, req: Request, hit: List[int], start: int) -> None:
        """``hit`` (cached blocks, already incref'd) becomes the head of
        the slot's table and the prompt resumes at row ``start``."""
        if not hit:
            self._prefix_misses += 1
            return
        slot = req.slot
        self._prefix_hits += 1
        self._prefix_hit_blocks += len(hit)
        req.prefix_hit_blocks = len(hit)
        self._slot_blocks[slot] = list(hit)
        self._tables[slot, :len(hit)] = hit
        req.prefill_pos = start
        self._lengths[slot] = start
        self._prefix_hit_tokens += start
        if self._step_trace is not None:
            counts = self._step_trace.counts
            counts["prefix_hit_tokens"] = (
                counts.get("prefix_hit_tokens", 0) + start
            )

    def _release_slot(self, req: Request, slot: int) -> None:
        for block in self._slot_blocks[slot]:
            self._allocator.decref(block)
        self._slot_blocks[slot] = []
        self._tables[slot, :] = SENTINEL_BLOCK
        for g in self._reach_groups:
            g.release_slot(slot)
        if self._slot_snapshot[slot]:
            # Lent to a prompt that left before it was inserted.
            self._cache.give_snapshot(self._slot_snapshot[slot])
            self._slot_snapshot[slot] = 0

    def _reset_pool(self) -> None:
        # A failed step may have invalidated the donated pools: the
        # device blocks AND everything that points at them (allocator,
        # prefix cache, tables, int8 scale pools) restart from scratch.
        self._arrays = self._fresh_arrays()
        self._allocator = BlockAllocator(self.num_blocks, reserved=1)
        for g in self._reach_groups:
            g.reset()
        if self._cache is not None:
            self._cache = self._new_prefix_cache(self._cache.capacity_blocks)
        self._tables[:, :] = SENTINEL_BLOCK
        self._slot_blocks = [[] for _ in range(self.slots)]
        self._slot_snapshot = [0] * self.slots

    def _sync_pool_metrics(self) -> None:
        stats = self._allocator.stats(self._live_block_ids())
        self.metrics.kv_blocks.set(stats["free"], state="free")
        self.metrics.kv_blocks.set(stats["used"], state="used")
        self.metrics.kv_blocks.set(stats["cached"], state="cached")
        self.metrics.kv_bytes_in_use.set(
            (stats["used"] + stats["cached"]) * self._block_bytes
        )
        if self._state_layout:
            entry = sum(a.entry_bytes() for a in self._state_layout)
            live = self._cache.snapshots_live if self._cache else 0
            self.metrics.state_bytes.set((self.slots + live) * entry)
        if self._released_this_step:
            self.metrics.kv_window_blocks_released.inc(
                self._released_this_step
            )
            self._released_this_step = 0

    # ---- step internals ----------------------------------------------------

    def _run_prefill_chunk(self, req: Request, finished: List[Request]):
        c = self.prefill_chunk
        start = req.prefill_pos
        n_valid = min(c, req.prompt_len - start)
        self._ensure_blocks(req, start + n_valid)
        # Privatize every block this chunk touches (a prefix-hit resume
        # can chunk-align BELOW the shared span: the re-prefill writes
        # identical values, but never into a shared block).
        first_blk = start // self.block_size
        last_blk = min(
            (start + c - 1) // self.block_size,
            len(self._slot_blocks[req.slot]) - 1,
        )
        for idx in range(first_blk, last_blk + 1):
            self._privatize(req, idx)
        self._prepare_reach_groups(req, start, start + n_valid)
        chunk = np.zeros((1, c), np.int32)
        chunk[0, :n_valid] = req.prompt[start:start + n_valid]
        scored = getattr(self._family, "chunk_rows_scored", None)
        if scored is not None:
            self._chunk_rows_launched += c
            self._chunk_rows_scored += scored(n_valid, c, self.kinds)
        self._mark_prefill_prep(n_valid, start + n_valid)
        *pools, first = self._steps.prefill(
            *self._pools(), self._params, jnp.asarray(chunk),
            _h2d(self._program_tables[..., req.slot, :]),
            np.int32(start), np.int32(n_valid),
            np.float32(req.temperature), self._rng,
            np.int32(self._step_idx),
            np.bool_(start + n_valid == req.prompt_len),
            *self._chunk_state_args(req, start, n_valid),
        )
        self._set_pools(pools)
        self._mark("prefill_launch")
        req.prefill_pos += n_valid
        self._lengths[req.slot] = req.prefill_pos
        self.metrics.tokens.inc(n_valid, kind="prefill")
        if req.prefill_pos < req.prompt_len:
            return
        if self._cache is not None:
            # Register the FULL prompt blocks for future hits (partial
            # tails stay private: the owner's decode appends into them).
            n_full = req.prompt_len // self.block_size
            snapshot, self._slot_snapshot[req.slot] = (
                self._slot_snapshot[req.slot], 0
            )
            self._cache.insert(
                req.prompt, self._slot_blocks[req.slot][:n_full],
                snapshot=(n_full, snapshot) if snapshot else None,
                tails=(n_full, [
                    g.tail(req.slot, n_full * self.block_size)
                    for g in self._reach_groups
                ]) if self._reach_groups and n_full else None,
            )
        self._launched_first(req, first)

    def _run_decode(self, decoding: List[Request],
                    finished: List[Request]):
        if self.spec_k:
            # The speculative path drafts from the committed tokens.
            self._drain("spec_k")
            self._run_decode_spec(decoding, finished)
            return
        # Block-budget pass FIRST: growing a cursor past a block edge
        # may preempt the youngest peer, which must then sit this
        # iteration out.
        for r in list(decoding):
            if r.state != DECODE:
                continue  # preempted by an earlier peer's allocation
            cursor = min(self._lengths[r.slot], self.max_len - 1)
            self._ensure_blocks(r, cursor + 1)
            self._privatize(r, cursor // self.block_size)
            if self._reach_groups and r.state == DECODE:
                self._prepare_reach_groups(r, cursor, cursor + 1)
        decoding = [r for r in decoding if r.state == DECODE]
        if not decoding:
            return
        active = np.zeros(self.slots, bool)
        for r in decoding:
            active[r.slot] = True
        self._mark_decode_prep(decoding)
        pools = self._pools()
        out = self._steps.decode(
            *pools, self._params, _h2d(self._program_tables),
            _h2d(self._lengths), self._fed_tokens(),
            jnp.asarray(active), _h2d(self._temps),
            self._rng, np.int32(self._step_idx), *self._fed_first(),
        )
        self._set_pools(out[:len(pools)])
        self._mark("decode_launch")
        # A sparse model's step hands back its expert counts after the
        # tokens; they are fetched with them, a step later.
        self._launched_decode(decoding, *out[len(pools):])

    # ---- speculative decode hooks (§35) ------------------------------------

    def _spec_prepare_rows(self, decoding: List[Request]):
        """Every decoding slot needs rows fill..fill+spec_k writable
        BEFORE the device calls: allocate the covering blocks (relief
        ladder may preempt the youngest peer, which then sits this
        iteration out) and privatize every touched block — drafted-
        then-rejected rows must never land in a block another slot or
        the prefix cache shares."""
        T = self.spec_k + 1
        for r in list(decoding):
            if r.state != DECODE:
                continue  # preempted by an earlier peer's allocation
            fill = int(self._lengths[r.slot])
            upto = min(fill + T, self.max_len)
            self._ensure_blocks(r, upto)
            first_blk = min(fill, self.max_len - 1) // self.block_size
            last_blk = min(
                (upto - 1) // self.block_size,
                len(self._slot_blocks[r.slot]) - 1,
            )
            for idx in range(first_blk, last_blk + 1):
                self._privatize(r, idx)
        return [r for r in decoding if r.state == DECODE]

    def _spec_draft_device(self, active):
        *pools, drafts = self._spec.draft(
            *self._pools(), self._params, jnp.asarray(self._tables),
            jnp.asarray(self._lengths), jnp.asarray(self._tokens),
            jnp.asarray(active),
        )
        self._set_pools(pools)
        return drafts

    def _spec_verify_device(self, active, drafts, draft_len):
        *pools, emitted, acc = self._spec.verify(
            *self._pools(), self._params, jnp.asarray(self._tables),
            jnp.asarray(self._lengths), jnp.asarray(self._tokens),
            jnp.asarray(drafts), jnp.asarray(draft_len),
            jnp.asarray(active), jnp.asarray(self._temps),
            self._rng, np.int32(self._step_idx),
        )
        self._set_pools(pools)
        return emitted, acc

    # ---- observability -----------------------------------------------------

    def kv_stats(self) -> Dict[str, object]:
        """Allocator + prefix-cache accounting (heartbeats, SignalBus,
        bench, the chaos block-reclaim invariant)."""
        stats = dict(self._allocator.stats(self._live_block_ids()))
        stats["bytes_in_use"] = (
            (stats["used"] + stats["cached"]) * self._block_bytes
        )
        stats["cow_copies"] = self._allocator.cow_copies_total
        stats["pool_attention"] = self.pool_attention
        # What each part of the programs runs, and what this family alone
        # reports (kvpool/families.py).
        stats.update(self.kinds)
        stats.update(
            getattr(self._family, "pool_stats", lambda engine: {})(self)
        )
        stats["kv_layers"] = pool_layout.pool_layers(self.config)
        stats["state_layers"] = sum(a.layers for a in self._state_layout)
        if self._state_layout:
            entry = sum(a.entry_bytes() for a in self._state_layout)
            live = self._cache.snapshots_live if self._cache else 0
            stats["state_bytes"] = self.slots * entry
            stats["state_snapshots_live"] = live
            stats["state_snapshot_bytes"] = live * entry
            stats["state_snapshot_capacity"] = self.state_snapshots
            stats["state_restores"] = self._state_restores
            stats["state_restores_from_snapshot"] = (
                self._state_restores_from_snapshot
            )
            stats["state_snapshots"] = self._state_snapshots_taken
            stats["state_snapshots_denied"] = self._state_snapshots_denied
            stats["state_snapshots_given_up"] = (
                self._cache.snapshots_given_up_total if self._cache else 0
            )
            stats["prefix_rounded_down_blocks"] = (
                self._prefix_rounded_down_blocks
            )
        if self._reach_groups:
            stats["groups"] = {
                name: dict(
                    layers=layers, blocks_free=g_stats["free"],
                    pool_bytes=blocks * per_block,
                    bytes_in_use=(
                        g_stats["used"] + g_stats["cached"]
                    ) * per_block,
                )
                for name, layers, blocks, per_block, g_stats in (
                    self._group_accounts(stats)
                )
            }
            stats["window_rows"] = self._window_rows(
                r.slot for r in self.scheduler.active()
            )
            stats["window_blocks_released_total"] = sum(
                g.released_total for g in self._reach_groups
            )
            stats["prefix_rounded_down_blocks"] = (
                self._prefix_rounded_down_blocks
            )
        # What a restart pays before the first request: construction
        # and warm-up as the engine timed them (0.0: not warmed up).
        stats["engine_build_s"] = self.engine_build_s
        stats["warmup_s"] = self.warmup_s
        if self._index_dim:
            # The third per-token array: its share of the bytes above,
            # and the whole array's size on the device.
            per_block = self._array_block_bytes["index_keys"]
            stats["index_bytes_in_use"] = (
                (stats["used"] + stats["cached"]) * per_block
            )
            stats["index_pool_bytes"] = self.num_blocks * per_block
            stats["index_tokens_per_row"] = self.index_tokens_per_row
        if self._latent is not None:
            # The one array of a latent model: its bytes in use and the
            # whole array's size.
            per_block = self._array_block_bytes["latent"]
            stats["latent_bytes_in_use"] = (
                (stats["used"] + stats["cached"]) * per_block
            )
            stats["latent_pool_bytes"] = self.num_blocks * per_block
        if (self._state_layout or self._reach_groups or self._index_dim
                or self._latent is not None):
            # (every layout but K and V alone in one group: those models'
            # decode steps hand back their expert counts)
            stats["moe_rows_dropped"] = self._moe_rows_dropped
        if self._cache is not None:
            for key, value in self._cache.stats().items():
                stats[f"prefix_{key}"] = value
            if self._reach_groups:
                stats["prefix_tails_live"] = self._cache.tails_live
                stats["prefix_tails_dropped"] = (
                    self._cache.tails_dropped_total
                )
            # Report USABLE hits (blocks that actually skipped
            # prefill), not the cache's raw lookup counters: a hit
            # fully discarded by chunk alignment saved nothing.
            lookups = self._prefix_hits + self._prefix_misses
            stats["prefix_hits"] = self._prefix_hits
            stats["prefix_misses"] = self._prefix_misses
            stats["prefix_hit_blocks"] = self._prefix_hit_blocks
            stats["prefix_hit_tokens"] = self._prefix_hit_tokens
            stats["prefix_hit_rate"] = round(
                self._prefix_hits / lookups if lookups else 0.0, 4
            )
        return stats

    def check_block_invariants(self) -> None:
        """Raise unless conservation + refcount sanity hold (tests)."""
        self._allocator.check()
        stats = self._allocator.stats(self._live_block_ids())
        total = stats["free"] + stats["used"] + stats["cached"]
        if total != self._allocator.managed:
            raise AssertionError(
                f"free+used+cached {total} != managed "
                f"{self._allocator.managed}: {stats}"
            )
        # Every pool array is addressed by the same block ids: they
        # agree on how many blocks there are and how long a block is.
        blocks = [self.num_blocks] + [
            g.num_blocks for g in self._reach_groups
        ]
        for g in self._reach_groups:
            g.check(self._launch_position)
        for a, pool in zip(self._layout, self._pools()):
            want = (self._groups[a.group].layers, blocks[a.group],
                    a.block_rows(self.block_size))
            if pool.shape[:3] != want:
                raise AssertionError(
                    f"pool array {a.name} of {pool.shape[:3]} in a pool "
                    f"of {want}: one table cannot address it"
                )
        if self._cache is not None and self._state_layout:
            # Every snapshot id is free, attached to an entry, or lent
            # to a slot that is still prefilling.
            lent = sum(1 for s in self._slot_snapshot if s)
            held = (self._cache.snapshots_live + self._cache.snapshots_free
                    + lent)
            if held != self.state_snapshots:
                raise AssertionError(
                    f"{held} snapshot ids accounted for of "
                    f"{self.state_snapshots}"
                )

    # ---- layer groups (kvpool/layout.py, kvpool/groups.py) -----------------

    @property
    def _program_tables(self) -> np.ndarray:
        """The host mirror of what the programs take as tables: the one
        ``[slots, max_blocks]`` table, or every group's stacked."""
        return self._group_tables if self._reach_groups else self._tables

    def _build_reach_groups(self, slots: int, chunk: int,
                            window_blocks: Optional[int]):
        """One :class:`ReachGroup` a group of the pool after the first.
        ``window_blocks`` sizes each (None: every slot's band and chunk,
        and as much again for cached prompts' tails)."""
        out = []
        for i, spec in enumerate(self._groups[1:], start=1):
            per_slot = band_blocks(
                spec.reach, self.block_size,
                self.max_blocks * self.block_size, chunk,
            )
            blocks = window_blocks or 2 * slots * per_slot + 1
            if blocks - 1 < per_slot:
                raise ValueError(
                    f"window_blocks {blocks} cannot hold one slot's band "
                    f"and chunk in group {spec.name} ({per_slot} blocks + "
                    "sentinel)"
                )
            out.append(ReachGroup(
                spec.name, spec.layers, spec.reach, blocks,
                self.block_size, self._group_tables[i],
            ))
        return out

    def _new_prefix_cache(self, capacity_blocks) -> PrefixCache:
        return PrefixCache(
            self._allocator, self.block_size,
            capacity_blocks=capacity_blocks,
            snapshots=self.state_snapshots,
            tail_allocators=[g.allocator for g in self._reach_groups],
        )

    def _group_accounts(self, first_stats):
        """(name, layers, blocks, a block's bytes, allocator stats) a
        group, the first from ``first_stats``."""
        per_block = [0] * len(self._groups)
        for a in self._layout:
            per_block[a.group] += self._array_block_bytes[a.name]
        yield (self._groups[0].name, self._groups[0].layers,
               self.num_blocks, per_block[0], first_stats)
        for i, g in enumerate(self._reach_groups, start=1):
            yield g.name, g.layers, g.num_blocks, per_block[i], g.stats()

    def _window_rows(self, slots) -> int:
        """Rows of the reach groups' bands below the given slots' fills:
        what their next decode launch reads of those groups, a layer."""
        return sum(
            min(int(self._lengths[slot]), g.reach)
            for slot in slots for g in self._reach_groups
        )

    def _admit_grouped_slot(self, req: Request) -> None:
        """Admission for a pool in groups: the slot starts from the
        deepest cached boundary whose entry still owns the reach groups'
        rows just below it (the first group's blocks up to it and those
        tails slot into the tables; a deeper match without them is given
        up), else from row 0. A hit is BLOCK-aligned."""
        slot, bs = req.slot, self.block_size
        self._launch_position[slot] = 0
        for g in self._reach_groups:
            g.release_slot(slot)        # (a reused slot holds none)
        if self._cache is None:
            return
        # Never skip the FINAL prompt token (see _admit_slot).
        hit, tails, rounded = self._cache.lookup_with_tails(
            req.prompt, max_blocks=(req.prompt_len - 1) // bs
        )
        self._prefix_rounded_down_blocks += rounded
        req.prefix_rounded_down_blocks = rounded
        self._adopt_hit(req, hit, len(hit) * bs)
        for g, ids in zip(self._reach_groups, tails):
            for back, block_id in enumerate(reversed(ids), start=1):
                g.adopt(slot, len(hit) - back, block_id)

    def _alloc_reach_blocks(self, group, n: int,
                            requester: Request) -> List[int]:
        """:meth:`_alloc_blocks` in a reach group: cached prompts' tails
        go first, oldest first, then the YOUNGEST active request (never
        ``requester``) is preempted."""
        while True:
            try:
                return group.allocator.alloc(n)
            except BlockPoolExhausted:
                missing = n - group.allocator.free_count()
                if self._cache is not None and self._cache.drop_tails_lru(
                    missing
                ):
                    continue
                victim = self._pick_preemption_victim(requester)
                if victim is None:
                    raise
                self._drain("preempt")
                self._preempt(victim)

    def _prepare_reach_groups(self, req: Request, position: int,
                              upto_rows: int) -> None:
        """Before a launch that reads ``req``'s rows from ``position``
        (its lowest query) and writes ``[position, upto_rows)``: the rule
        of release (``kvpool/groups.py``), then blocks for the rows it
        writes, in every reach group."""
        slot = req.slot
        self._launch_position[slot] = position
        for g in self._reach_groups:
            released = g.release_below(slot, position)
            if released:
                self._released_this_step += released
                req.window_blocks_released += released
                if self._step_trace is not None:
                    counts = self._step_trace.counts
                    counts["window_blocks_released"] = released + counts.get(
                        "window_blocks_released", 0
                    )
            want = g.missing(slot, position, min(upto_rows, self.max_len))
            if want:
                for logical, block_id in zip(
                    want, self._alloc_reach_blocks(g, len(want), req)
                ):
                    g.adopt(slot, logical, block_id)

    def _mark_decode_prep(self, decoding: List[Request],
                          at: Optional[float] = None) -> None:
        super()._mark_decode_prep(decoding, at)
        if self._reach_groups and self._step_trace is not None:
            self._step_trace.counts["window_rows"] = self._window_rows(
                r.slot for r in decoding
            )
        counts = getattr(self._family, "decode_counts", None)
        if counts is not None and self._step_trace is not None:
            self._step_trace.counts.update(counts(
                self.config, [int(self._lengths[r.slot]) for r in decoding]
            ))

    # ---- per-slot state (kvpool/layout.py) ---------------------------------

    def _all_trace_counts(self) -> Dict[str, int]:
        counts = super()._all_trace_counts()
        if self._state_layout:
            counts.update(_state_steps(len(self._state_layout)).trace_counts)
        return counts

    def _admit_state_slot(self, req: Request) -> None:
        """Admission for a model with per-slot state: the slot starts
        from the snapshot of the deepest cached boundary that has one
        (the blocks up to it slot into the table; a deeper match without
        a snapshot is given up), else from zeros. A hit is BLOCK-aligned:
        its chunks start where the snapshot was taken."""
        hit, snapshot, bs = [], 0, self.block_size
        if self._cache is not None:
            # Never skip the FINAL prompt token (see _admit_slot).
            hit, snapshot, rounded = self._cache.lookup_with_state(
                req.prompt, max_blocks=(req.prompt_len - 1) // bs
            )
            self._prefix_rounded_down_blocks += rounded
            req.prefix_rounded_down_blocks = rounded
            self._adopt_hit(req, hit, len(hit) * bs)
        t0 = time.monotonic()
        self._restore_state(req.slot, snapshot)
        self._state_restores += 1
        if self._step_trace is not None:
            counts = self._step_trace.counts
            for name, more in (
                ("state_restores", 1),
                ("state_restores_from_snapshot", int(bool(snapshot))),
                ("state_restore_s", time.monotonic() - t0),
                # rows of the prompt that the hit did not cover
                ("prefill_rows_again", req.prompt_len - len(hit) * bs),
                ("prefix_rounded_down_blocks",
                 req.prefix_rounded_down_blocks or 0),
            ):
                counts[name] = counts.get(name, 0) + more

    def _restore_state(self, slot: int, snapshot: int) -> None:
        """The slot's state := snapshot ``snapshot`` (0: zeros), on the
        device: one compiled copy, no host round trip."""
        self._state_restores_from_snapshot += bool(snapshot)
        blocks, state, snaps, steps = self._state_pools()
        self._set_pools(blocks + steps.restore(
            *state, *snaps, np.int32(slot), np.int32(snapshot)
        ))

    def _state_pools(self):
        """``_pools()`` in its three parts (per-token arrays, per-slot
        state, snapshots) and the programs that move a slot's state."""
        pools, n = self._pools(), len(self._state_layout)
        at = len(self._layout)
        return (pools[:at], pools[at:at + n], pools[at + n:],
                _state_steps(n))

    def _get_slot_state(self, slot: int):
        """A slot's state, one host array a state array (migration)."""
        _, state, _, steps = self._state_pools()
        return jax.device_get(steps.get(*state, np.int32(slot)))

    def _put_slot_state(self, slot: int, rows) -> None:
        blocks, state, snaps, steps = self._state_pools()
        self._set_pools(blocks + steps.put(
            *state, *(jnp.asarray(r) for r in rows), np.int32(slot)
        ) + snaps)

    def _snapshot_budget(self, stated: Optional[int], slots: int) -> int:
        """Snapshot ids this engine keeps (``kvpool/layout.py``): the
        count its caller states; else one a cached block while a
        snapshot is no larger than a block; else what a share of the
        per-token pool's bytes pays for."""
        if stated is not None:
            if stated < 1:
                raise ValueError(
                    f"state_snapshots {stated}: a prefix cache over a "
                    "model with per-slot state needs one at least"
                )
            return int(stated)
        entry = sum(a.entry_bytes() for a in self._state_layout)
        block = sum(
            a.block_bytes(self._groups[a.group].layers, self.block_size)
            for a in self._layout if not a.group
        )
        if entry <= block:
            return pool_layout.default_snapshots(self.num_blocks, slots)
        return pool_layout.budgeted_snapshots(
            entry, self.num_blocks * block, slots
        )

    def _chunk_state_args(self, req: Request, start: int, n_valid: int):
        """What a chunk launch of a model with per-slot state carries
        after the plain arguments: the slot, and where in the chunk to
        take a snapshot and under which id. A prompt gets ONE: the state
        at its last whole-block boundary, in the chunk that holds it."""
        if not self._state_layout:
            return ()
        boundary = req.prompt_len // self.block_size * self.block_size
        snap_at = snap_id = 0
        # Exactly one chunk of a prompt has the boundary past its first
        # row and at or before its last valid one's end (a boundary that
        # is a chunk's first row was the END of the chunk before it, or
        # the hit's own boundary, which has its snapshot).
        if self._cache is not None and start < boundary <= start + n_valid:
            given_up = self._cache.snapshots_given_up_total
            snap_id = self._cache.take_snapshot()
            given_up = self._cache.snapshots_given_up_total - given_up
            if given_up and self._step_trace is not None:
                counts = self._step_trace.counts
                counts["state_snapshots_given_up"] = given_up + counts.get(
                    "state_snapshots_given_up", 0
                )
            if snap_id:
                snap_at = boundary - start
                self._slot_snapshot[req.slot] = snap_id
                self._state_snapshots_taken += 1
                if self._step_trace is not None:
                    self._step_trace.counts["state_snapshots"] = 1
            else:
                # Every id is lent: this prompt runs without one.
                self._state_snapshots_denied += 1
                self.metrics.state_snapshots_denied.inc()
        return np.int32(req.slot), np.int32(snap_at), np.int32(snap_id)



# ---- adapters: the names benchmark/ and tests/benchmark/ pin ----------------
#
# Each is a line or two over :func:`_steps`; a ``benchmark`` PR that moves
# those files onto ``_steps`` / ``engine.kinds`` retires the block (ROADMAP
# D12), with the three properties of :class:`_PagedSteps` and the engine's
# ``linear_kinds`` / ``window_decode_attention``. Who reads what:
# ``_paged_steps`` rehearse.py, rehearse_keye.py, rehearse_xing.py,
# rehearse_lfm2.py, tools/program_hashes.py; ``_grouped_steps``
# rehearse_mellum2.py; ``_linear_steps`` rehearse_sala.py, controls_sala.py;
# ``_delta_steps`` rehearse_olmo_hybrid.py; the ``_*_steps_for`` (their
# ``.cache_clear()``) controls_sala.py and tests/benchmark/test_keye.py,
# test_xing.py, test_lfm2.py, test_mellum2.py. (``_state_steps`` and
# ``PagedServingEngine._chunk_state_args`` above are pinned too:
# controls_lfm2.py, controls_sala.py, controls_olmo_hybrid.py.)


def _paged_steps(config, slots: int, num_blocks: int, max_blocks: int,
                 block_size: int, chunk: int, kv_dtype: str = "fp"):
    return _steps(config, slots, num_blocks, max_blocks, block_size, chunk,
                  kv_dtype)


def _grouped_steps(config, slots: int, max_blocks: int, block_size: int,
                   chunk: int, group_blocks):
    return _steps(config, slots, 0, max_blocks, block_size, chunk,
                  group_blocks=tuple(group_blocks))


def _linear_steps(config, slots: int, max_blocks: int, block_size: int,
                  chunk: int):
    return _steps(config, slots, 0, max_blocks, block_size, chunk)


_delta_steps = _linear_steps
_paged_steps_for = _grouped_steps_for = _linear_steps_for = _steps_for
