"""Host bookkeeping of a pool group that keeps a row only while a query
can still see it (``kvpool/layout.py``: a group with a REACH).

Pure host code, like the allocator it owns. A :class:`ReachGroup` has
its own block ids, its own :class:`BlockAllocator` and its own ``[slots,
max_blocks]`` table, indexed by the logical block as the first group's
is: entry ``b`` of a slot names the block that holds the slot's rows
``[b * block_size, (b + 1) * block_size)`` while the slot holds it and
the sentinel block 0 otherwise (never allocated; released). The engine
drives it at the same points as the first group (admission, the chunk's
and the decode step's block-budget pass, release, reset) and at one
more:

**The rule of release.** Before a launch whose lowest query position is
``p`` (a decode step: the slot's fill; a chunk: its ``start``), the slot
drops its reference to every block whose rows all lie below ``p -
reach``, and the table's entry goes to the sentinel: a row read after
its release would read the sink, never a neighbour's data (no kernel
reads one: ``ops/window_attention.py`` starts at the band's first page).
A block the prefix cache also references stays allocated until the cache
gives it up.
"""

from typing import Dict, List, Set

import numpy as np

from dlrover_tpu.serving.kvpool.allocator import BlockAllocator
from dlrover_tpu.serving.kvpool.families import SENTINEL_BLOCK


def band_blocks(reach: int, block_size: int, rows: int, chunk: int) -> int:
    """Blocks a sequence of ``rows`` rows holds at most in a group of
    reach ``reach`` while it is prefilled in chunks of ``chunk`` rows and
    then decoded: its band, a chunk above it, and the two blocks the
    band's ends share with their neighbours."""
    held = min(rows, reach + max(chunk, 1))
    return -(-held // block_size) + (2 if held < rows else 0)


class ReachGroup:
    """See the module docstring. ``tables`` is the engine's ``[slots,
    max_blocks]`` int32 mirror for this group (a view of its stacked
    tables), written in place."""

    def __init__(self, name: str, layers: int, reach: int, num_blocks: int,
                 block_size: int, tables: np.ndarray):
        self.name, self.layers, self.reach = name, layers, reach
        self.num_blocks, self.block_size = num_blocks, block_size
        self.tables = tables
        self.allocator = BlockAllocator(num_blocks, reserved=1)
        # A slot's held blocks by logical index.
        self.slot_blocks: List[Dict[int, int]] = [
            {} for _ in range(tables.shape[0])
        ]
        self.released_total = 0

    def blocks_for(self, rows: int, chunk: int) -> int:
        return band_blocks(self.reach, self.block_size, rows, chunk)

    def reset(self) -> None:
        self.allocator = BlockAllocator(self.num_blocks, reserved=1)
        self.tables[:, :] = SENTINEL_BLOCK
        self.slot_blocks = [{} for _ in self.slot_blocks]

    def live_ids(self) -> Set[int]:
        live: Set[int] = set()
        for held in self.slot_blocks:
            live.update(held.values())
        return live

    def stats(self) -> Dict[str, int]:
        return self.allocator.stats(self.live_ids())

    def missing(self, slot: int, lo_row: int, hi_row: int) -> List[int]:
        """Logical blocks of rows ``[lo_row, hi_row)`` the slot does not
        hold."""
        held = self.slot_blocks[slot]
        first, last = lo_row // self.block_size, -(-hi_row // self.block_size)
        return [b for b in range(first, last) if b not in held]

    def adopt(self, slot: int, logical: int, block_id: int) -> None:
        """``block_id`` (a reference the caller already owns) becomes the
        slot's logical block ``logical``."""
        self.slot_blocks[slot][logical] = block_id
        self.tables[slot, logical] = block_id

    def release_below(self, slot: int, position: int) -> int:
        """The rule of release for a launch whose lowest query position
        is ``position``; returns the blocks released."""
        below = (position - self.reach) // self.block_size   # whole blocks
        held = self.slot_blocks[slot]
        gone = [b for b in held if b < below]
        for b in gone:
            self.allocator.decref(held.pop(b))
            self.tables[slot, b] = SENTINEL_BLOCK
        self.released_total += len(gone)
        return len(gone)

    def release_slot(self, slot: int) -> None:
        for block_id in self.slot_blocks[slot].values():
            self.allocator.decref(block_id)
        self.slot_blocks[slot] = {}
        self.tables[slot, :] = SENTINEL_BLOCK

    def tail(self, slot: int, boundary: int) -> List[int]:
        """The slot's blocks that hold rows ``[boundary - reach,
        boundary)``, oldest first, or ``[]`` where it no longer holds
        them all (what a prefix-cache entry of that boundary needs to be
        continued from: ``kvpool/prefix_cache.py``)."""
        lo = max(boundary - self.reach, 0) // self.block_size
        hi = -(-boundary // self.block_size)
        held = self.slot_blocks[slot]
        if any(b not in held for b in range(lo, hi)):
            return []
        return [held[b] for b in range(lo, hi)]

    def check(self, positions) -> None:
        """Conservation, and no slot holds a block wholly below the band
        of its last launch (``positions``: each slot's lowest query
        position in it, as the engine noted it)."""
        self.allocator.check()
        stats = self.stats()
        total = stats["free"] + stats["used"] + stats["cached"]
        if total != self.allocator.managed:
            raise AssertionError(
                f"group {self.name}: free+used+cached {total} != managed "
                f"{self.allocator.managed}: {stats}"
            )
        for slot, held in enumerate(self.slot_blocks):
            below = (int(positions[slot]) - self.reach) // self.block_size
            stale = [b for b in held if b < below]
            if stale:
                raise AssertionError(
                    f"group {self.name}: slot {slot} launched at row "
                    f"{int(positions[slot])} still holds blocks {stale}, "
                    f"wholly below its band of {self.reach} rows"
                )
            for b, block_id in held.items():
                if self.tables[slot, b] != block_id:
                    raise AssertionError(
                        f"group {self.name}: slot {slot} table entry {b} "
                        f"is {self.tables[slot, b]}, holds {block_id}"
                    )
