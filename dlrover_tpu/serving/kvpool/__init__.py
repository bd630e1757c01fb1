"""Paged KV memory plane (§31): block-table cache, cross-request
prefix reuse, SLO-class admission riding the serving scheduler.

- :class:`BlockAllocator` — jax-free free list + refcounts + COW over
  the ``[layers, num_blocks, block_size, kv_heads, head_dim]`` pool;
- :class:`PrefixCache` — token-prefix trie → warm refcounted block
  chains, leaf-first LRU eviction;
- :class:`PagedServingEngine` — the flat engine's step loop over block
  tables threaded as traced args (zero retraces across admissions),
  prefix-hit prefill skipping, pool-pressure relief (cache eviction →
  youngest-request preemption);
- ``migrate`` (§36) — a request's blocks + scheduler state as bytes:
  export from one engine, decode-entry import into another (the
  disaggregated-serving / live-drain primitive).
"""

from dlrover_tpu.serving.kvpool.allocator import (
    BlockAllocator,
    BlockPoolExhausted,
)
from dlrover_tpu.serving.kvpool.engine import PagedServingEngine
from dlrover_tpu.serving.kvpool.families import SENTINEL_BLOCK
from dlrover_tpu.serving.kvpool.migrate import (
    MigrationError,
    MigrationRefused,
    can_import,
    export_request,
    import_request,
    peek_header,
    release_exported,
)
from dlrover_tpu.serving.kvpool.prefix_cache import PrefixCache

__all__ = [
    "BlockAllocator",
    "BlockPoolExhausted",
    "PrefixCache",
    "PagedServingEngine",
    "SENTINEL_BLOCK",
    "MigrationError",
    "MigrationRefused",
    "can_import",
    "export_request",
    "import_request",
    "peek_header",
    "release_exported",
]
