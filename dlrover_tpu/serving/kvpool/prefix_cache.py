"""Cross-request prefix cache: token prefixes → warm KV block chains.

Shared system prompts are the serving fleet's biggest redundant work:
every request carrying the same leading tokens re-prefills identical
K/V on every replica. This cache keys FULL blocks of prompt tokens by a
cumulative chain hash (block k's key folds block k-1's key, so equal
keys mean equal token paths from position 0, not just an equal k-th
block) and keeps the finished blocks warm in the paged pool under a
cache-owned reference.

Structure is a trie over blocks: one entry per (parent chain, block
tokens), each holding one cache reference on its block. Lookup walks
root→leaf while keys match, increfs every hit block, and hands the
chain to the engine — the hit blocks slot straight into the request's
block table and prefill SKIPS the covered chunks. Insert registers a
finished prompt's full blocks (partial tails are never cached: a
partial block is still written by its owner's decode appends, and
shared blocks must stay immutable — COW handles the one legal rewrite,
a chunk-aligned re-prefill over a shared block).

Eviction is leaf-first LRU: only entries with no children are
evictable (evicting a mid-chain entry would orphan its suffix —
unreachable entries silently pinning blocks forever), and eviction
drops the cache's reference, freeing the block once no slot still
points at it. ``evict_lru`` is also the allocator's relief valve: the
engine calls it before preempting a request when the pool runs dry, and
then takes only leaves no live slot still holds (``must_free``).

**Snapshots** (``snapshots > 0``: a model with per-slot state,
``kvpool/layout.py``). A run of cached blocks can be continued only with
the state AS OF its last row, which no block holds, so an entry may own
one snapshot id (a row of the engine's snapshot arrays; 0 is the
sentinel nobody owns): the state at its block's END boundary. The ids
live here with the entries: ``take_snapshot`` lends one to a prefilling
request, ``insert`` attaches it to the entry of the boundary it was
taken at (or takes it back: the entry had one, or was never made),
evicting an entry frees its id with its block. ``lookup_with_state``
returns the chain only as far as the DEEPEST entry holding a snapshot,
with that id and how many matched blocks lay beyond it. The ids are a
BUDGET the engine sizes (one a block where a snapshot is small, so many
bytes where one outweighs a block: ``kvpool/layout.py``): when none is
free ``take_snapshot`` takes the snapshot least recently USED (written,
or restored from by a hit: an order of the snapshots' own, not the
entries', whose every ancestor a hit touches), and its entry stays.

**Tails** (``tail_allocators``: a pool in groups, ``kvpool/layout.py``).
The entries name blocks of the FIRST group, which keeps every row. A
group with a reach keeps only the rows a query can still see, so a run
of cached blocks can be continued from a boundary only if that group
still holds the rows just below it: an entry may own, a reach group, a
TAIL, the group's blocks that hold rows ``[boundary - reach, boundary)``
(oldest first), each under a cache-owned reference in that group's own
allocator. ``insert`` attaches the tails a finished prompt still holds
to the entry of its last whole block; ``lookup_with_tails`` returns the
chain only as far as the deepest entry that owns them, with them
(incref'd for the caller) and how many matched blocks lay beyond it;
evicting an entry frees its tails with its block, and
``drop_tails_lru`` frees tails alone, oldest first, when a reach group
runs dry (the entry stays, and can no longer be continued from).
"""

from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

from dlrover_tpu.serving.kvpool.allocator import BlockAllocator

# The chain root: block 0's parent. Any non-key value works; None keeps
# the trie honest (no token path hashes to it).
_ROOT = None


@dataclass
class _Entry:
    key: Tuple
    parent_key: Optional[Tuple]
    block_id: int
    # The block's literal tokens: verified on every hit, so a chain-hash
    # collision degrades to a miss instead of serving another prompt's
    # KV (correctness must not hang on 64-bit hash uniqueness).
    tokens: Tuple[int, ...] = ()
    children: Set[Tuple] = field(default_factory=set)
    # The state at this block's end boundary (0: none); see the module
    # docstring.
    snapshot: int = 0
    # A reach group's blocks that hold the rows just below this block's
    # end boundary, one tuple a group (none: ()); see the module
    # docstring.
    tails: Tuple[Tuple[int, ...], ...] = ()


class PrefixCache:
    """See module docstring. Not thread-safe — engine-loop owned."""

    def __init__(
        self,
        allocator: BlockAllocator,
        block_size: int,
        capacity_blocks: Optional[int] = None,
        snapshots: int = 0,
        tail_allocators: Sequence[BlockAllocator] = (),
    ):
        if block_size < 1:
            raise ValueError("block_size must be >= 1")
        self._alloc = allocator
        self.block_size = block_size
        # None = bounded only by the pool itself (eviction then happens
        # purely through the allocator-pressure relief valve).
        self.capacity_blocks = capacity_blocks
        self._entries: "OrderedDict[Tuple, _Entry]" = OrderedDict()
        self.hits_total = 0
        self.misses_total = 0
        self.hit_blocks_total = 0
        self.evicted_blocks_total = 0
        # Snapshot ids 1..snapshots (0 is the sentinel), lowest first.
        self.snapshots = snapshots
        self._free_snapshots = list(range(snapshots, 0, -1))
        self.snapshots_live = 0       # entries that hold one
        self.snapshots_given_up_total = 0   # taken from an entry that stayed
        # Snapshot id -> the entry that holds it, least recently USED
        # first: a snapshot is used when it is written and when a hit
        # restores from it. Not the entries' own order: a hit touches its
        # whole chain, so a growing session's superseded boundaries would
        # stay as young as its newest one, and the snapshots given up
        # would be those of the sessions that have waited longest, all
        # of them, the one about to be needed last of all (my chip run,
        # PR 57: 22 % of a grown session's turns found none).
        self._snapshot_lru: "OrderedDict[int, _Entry]" = OrderedDict()
        # The reach groups' allocators, in the groups' order.
        self._tail_allocs = tuple(tail_allocators)
        self.tails_live = 0           # entries that own tails
        self.tails_dropped_total = 0

    # ---- keys --------------------------------------------------------------

    def _chain_keys(
        self, prompt: Sequence[int]
    ) -> List[Tuple[Tuple, Tuple[int, ...]]]:
        """Per-full-block ``(cumulative key, block tokens)`` pairs."""
        bs = self.block_size
        keys: List[Tuple[Tuple, Tuple[int, ...]]] = []
        parent: Optional[Tuple] = _ROOT
        # One conversion for the whole prompt: a 32k-token document is
        # 512 blocks, and an ``int()`` a token was most of an admission.
        tokens = prompt.tolist() if hasattr(prompt, "tolist") else [
            int(t) for t in prompt
        ]
        for k in range(len(tokens) // bs):
            block = tuple(tokens[k * bs:(k + 1) * bs])
            key = (hash((parent, block)), k)
            keys.append((key, block))
            parent = key
        return keys

    # ---- lookup / insert ---------------------------------------------------

    def _matched(self, prompt: Sequence[int]) -> List[_Entry]:
        """The longest cached chain of full prompt blocks, touched."""
        held: List[_Entry] = []
        for key, tokens in self._chain_keys(prompt):
            entry = self._entries.get(key)
            if entry is None or entry.tokens != tokens:
                break
            self._entries.move_to_end(key)
            held.append(entry)
        return held

    def _lend(self, entries: Sequence[_Entry]) -> List[int]:
        """The entries' blocks, INCREF'd for the caller and counted."""
        blocks = [entry.block_id for entry in entries]
        for block_id in blocks:
            self._alloc.incref(block_id)
        if blocks:
            self.hits_total += 1
            self.hit_blocks_total += len(blocks)
        else:
            self.misses_total += 1
        return blocks

    def lookup(self, prompt: Sequence[int]) -> List[int]:
        """Longest cached chain of full prompt blocks. Every returned
        block is INCREF'd for the caller — the hit is a loan the slot
        must decref like any other block it owns."""
        return self._lend(self._matched(prompt))

    def lookup_with_state(
        self, prompt: Sequence[int], max_blocks: Optional[int] = None
    ) -> Tuple[List[int], int, int]:
        """:meth:`lookup` for a model with per-slot state (module
        docstring): ``(blocks, snapshot id, rounded down)``, the chain
        up to the deepest entry among its first ``max_blocks`` that
        holds a snapshot, that snapshot (0 with no blocks), and how many
        matched blocks lay beyond it and were given up."""
        usable, rounded = self._continuable(
            prompt, max_blocks, lambda entry: entry.snapshot
        )
        snapshot = usable[-1].snapshot if usable else 0
        if snapshot:
            self._snapshot_lru.move_to_end(snapshot)
        return self._lend(usable), snapshot, rounded

    def _continuable(self, prompt, max_blocks, owns):
        """The matched chain down to the deepest entry among its first
        ``max_blocks`` that ``owns`` what a sequence needs to be
        continued from its boundary, and how many matched blocks lay
        beyond it."""
        held = self._matched(prompt)
        usable = held[:max_blocks]
        while usable and not owns(usable[-1]):
            usable.pop()
        return usable, len(held) - len(usable)

    def lookup_with_tails(
        self, prompt: Sequence[int], max_blocks: Optional[int] = None
    ) -> Tuple[List[int], Tuple[Tuple[int, ...], ...], int]:
        """:meth:`lookup` for a pool in groups (module docstring):
        ``(blocks, tails, rounded down)``, the chain up to the deepest
        entry among its first ``max_blocks`` that owns its tails, those
        tails (one tuple of block ids a reach group, every id INCREF'd
        for the caller in its group's allocator; ``()`` with no blocks),
        and how many matched blocks lay beyond it and were given up."""
        usable, rounded = self._continuable(
            prompt, max_blocks, lambda entry: entry.tails
        )
        tails = usable[-1].tails if usable else ()
        for alloc, ids in zip(self._tail_allocs, tails):
            for block_id in ids:
                alloc.incref(block_id)
        return self._lend(usable), tails, rounded

    def _free_entry_tails(self, entry: _Entry) -> int:
        """Drop the entry's tails; returns the blocks that freed."""
        freed = 0
        if entry.tails:
            self.tails_live -= 1
            for alloc, ids in zip(self._tail_allocs, entry.tails):
                freed += sum(alloc.decref(block_id) for block_id in ids)
            entry.tails = ()
        return freed

    def drop_tails_lru(self, n_blocks: int) -> int:
        """A reach group's relief valve: drop entries' tails, oldest
        entry first, until ``n_blocks`` blocks have freed (or no entry
        owns any). The entries stay: their first-group blocks are still
        a prefix, only no longer one to continue from. Returns the
        blocks freed."""
        freed = 0
        for entry in list(self._entries.values()):
            if freed >= n_blocks:
                break
            if entry.tails:
                freed += self._free_entry_tails(entry)
                self.tails_dropped_total += 1
        return freed

    def take_snapshot(self) -> int:
        """Lend a snapshot id to a request that is about to write the
        state at a boundary. With none free, the entry whose snapshot
        was least recently used (written, or restored from by a hit)
        gives up its SNAPSHOT (the entry and its
        block stay: the chain is still a prefix, continued from then on
        from a shallower boundary that has one, and the blocks beyond it
        are what ``lookup_with_state`` reports as rounded down): a
        snapshot is given up before any block. 0: every id is lent to a
        prompt that is still prefilling; the caller writes the sentinel
        and runs without. It comes back through :meth:`insert` or
        :meth:`give_snapshot`."""
        if not self._free_snapshots and self._snapshot_lru:
            entry = next(iter(self._snapshot_lru.values()))
            self._free_entry_snapshot(entry)
            entry.snapshot = 0
            self.snapshots_given_up_total += 1
        return self._free_snapshots.pop() if self._free_snapshots else 0

    def give_snapshot(self, snapshot: int) -> None:
        if snapshot:
            self._free_snapshots.append(snapshot)

    def _free_entry_snapshot(self, entry: _Entry) -> None:
        if entry.snapshot:
            self.snapshots_live -= 1
            self._snapshot_lru.pop(entry.snapshot, None)
            self.give_snapshot(entry.snapshot)

    def insert(self, prompt: Sequence[int], blocks: Sequence[int],
               snapshot=None, tails=None) -> int:
        """Register a prefilled prompt's full blocks (``blocks[k]``
        holds rows ``[k*bs, (k+1)*bs)``). Newly cached blocks gain one
        cache-owned reference; chains already present are touched, not
        re-owned (a concurrent twin's identical blocks stay owned by
        its slot alone). ``snapshot``: ``(n, id)``, the state as of the
        end of the prompt's ``n``-th block under a lent id; the entry of
        that boundary adopts it unless it holds one already.
        ``tails``: ``(n, tails)``, the reach groups' blocks that hold
        the rows below the end of the prompt's ``n``-th block (one
        sequence of ids a group, the caller's own references): the entry
        of that boundary takes a reference of its own on each unless it
        owns tails already. Returns the number of blocks newly cached."""
        keys = self._chain_keys(prompt)
        n_full = min(len(keys), len(blocks))
        added = 0
        at, lent = snapshot or (0, 0)
        tails_at, new_tails = tails or (0, ())
        parent: Optional[Tuple] = _ROOT
        for k in range(n_full):
            key, tokens = keys[k]
            entry = self._entries.get(key)
            if entry is None:
                self._alloc.incref(blocks[k])
                entry = _Entry(
                    key=key, parent_key=parent, block_id=blocks[k],
                    tokens=tokens,
                )
                self._entries[key] = entry
                if parent is not _ROOT and parent in self._entries:
                    self._entries[parent].children.add(key)
                added += 1
            elif entry.tokens != tokens:
                # Chain-hash collision with a different token path:
                # cannot extend THIS chain past it (the child links
                # would corrupt the trie) — stop registering here.
                break
            else:
                self._entries.move_to_end(key)
            if lent and k == at - 1 and not entry.snapshot:
                entry.snapshot, lent = lent, 0
                self.snapshots_live += 1
                self._snapshot_lru[entry.snapshot] = entry
            if (new_tails and k == tails_at - 1 and not entry.tails
                    and all(new_tails)):
                entry.tails = tuple(tuple(ids) for ids in new_tails)
                self.tails_live += 1
                for alloc, ids in zip(self._tail_allocs, entry.tails):
                    for block_id in ids:
                        alloc.incref(block_id)
            parent = key
        self.give_snapshot(lent)
        if self.capacity_blocks is not None:
            over = len(self._entries) - self.capacity_blocks
            if over > 0:
                self.evict_lru(over)
        return added

    # ---- eviction ----------------------------------------------------------

    def evict_lru(self, n_blocks: int, must_free: bool = False) -> int:
        """Release up to ``n_blocks`` cache references, oldest LEAF
        first (never a mid-chain entry — an orphaned suffix would pin
        blocks unreachably). Returns how many entries were evicted.

        ``must_free`` (the allocator's relief valve): only leaves whose
        block the cache ALONE still references are taken, so every
        eviction frees its block. A leaf a live slot also holds frees
        nothing when dropped, and dropping it uncovers its parent: a dry
        pool would otherwise eat a long shared chain from its tail while
        the slots reading it keep every block allocated, and the next
        request for that chain prefills it again."""
        evicted = 0
        while evicted < n_blocks:
            victim = None
            for key, entry in self._entries.items():
                if entry.children:
                    continue
                if must_free and self._alloc.refcount(entry.block_id) > 1:
                    continue
                victim = entry
                break
            if victim is None:
                break
            del self._entries[victim.key]
            if (
                victim.parent_key is not _ROOT
                and victim.parent_key in self._entries
            ):
                self._entries[victim.parent_key].children.discard(
                    victim.key
                )
            self._alloc.decref(victim.block_id)
            self._free_entry_snapshot(victim)
            self._free_entry_tails(victim)
            evicted += 1
            self.evicted_blocks_total += 1
        return evicted

    def clear(self) -> None:
        """Drop every cache reference (pool rebuild after a step error:
        the device blocks are gone, the warm set with them)."""
        for entry in self._entries.values():
            self._alloc.decref(entry.block_id)
            self._free_entry_snapshot(entry)
            self._free_entry_tails(entry)
        self._entries.clear()

    # ---- accounting --------------------------------------------------------

    @property
    def cached_entries(self) -> int:
        return len(self._entries)

    def cached_block_ids(self) -> Set[int]:
        return {e.block_id for e in self._entries.values()}

    @property
    def snapshots_free(self) -> int:
        return len(self._free_snapshots)

    def hit_rate(self) -> float:
        total = self.hits_total + self.misses_total
        return self.hits_total / total if total else 0.0

    def stats(self) -> Dict[str, float]:
        return {
            "entries": len(self._entries),
            "hits": self.hits_total,
            "misses": self.misses_total,
            "hit_blocks": self.hit_blocks_total,
            "evicted_blocks": self.evicted_blocks_total,
            "hit_rate": round(self.hit_rate(), 4),
        }
