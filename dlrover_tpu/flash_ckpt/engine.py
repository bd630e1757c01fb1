"""Trainer-side flash checkpoint engine.

Parity: reference trainer/torch/flash_checkpoint/engine.py
(CheckpointEngine.save_state_dict_to_memory:365,
get_state_dict_from_memory:406) adapted to JAX pytrees: the blocking cost
of a save is one ``jax.device_get`` of the state into shared memory; the
agent persists asynchronously. Restore is memory-first, storage-fallback,
with resharding handled through shard metadata +
``jax.make_array_from_callback`` under the *current* mesh.
"""

import os
import queue
import time
from typing import Any, Dict, Optional

import numpy as np

from dlrover_tpu.common.constants import NodeEnv
from dlrover_tpu.common.env_utils import get_env_int
from dlrover_tpu.common.log import logger
from dlrover_tpu.fault import FaultInjected, fault_point
from dlrover_tpu.flash_ckpt import storage as ckpt_storage
from dlrover_tpu.flash_ckpt.shared_obj import (
    SharedLockClient,
    SharedQueueClient,
)
from dlrover_tpu.flash_ckpt.shm_handler import (
    SharedMemoryHandler,
    bounds_to_slices,
)
from dlrover_tpu.trainer.runtime import get_context

CKPT_EVENT_QUEUE = "ckpt_event"
CKPT_LOCK_PREFIX = "ckpt_shm"


def shm_segment_name(local_rank: int) -> str:
    """Per-worker shm segment. The node rank is part of the name so
    same-host multi-node setups (tests, packed dev boxes) never collide;
    agent and workers of one node share the same NODE_RANK env."""
    job = os.getenv(NodeEnv.JOB_NAME, "job")
    node_rank = os.getenv(NodeEnv.NODE_RANK, "0")
    return f"dlrover_tpu_ckpt_{job}_n{node_rank}_{local_rank}"


class SaveEvent:
    SAVE_MEM = "save_mem"
    SAVE_DISK = "save_disk"

    def __init__(
        self,
        kind: str,
        step: int,
        checkpoint_dir: str = "",
        local_world_size: int = 1,
    ):
        self.kind = kind
        self.step = step
        self.checkpoint_dir = checkpoint_dir
        self.local_world_size = local_world_size


class CheckpointEngine:
    """One instance per worker process."""

    def __init__(
        self,
        checkpoint_dir: str,
        standalone: bool = False,
    ):
        """``standalone=True`` runs without an agent (no UDS servers): saves
        go to shm and persistence happens synchronously in-process — used
        for notebooks/tests and as a degraded mode."""
        self.checkpoint_dir = checkpoint_dir
        self._ctx = get_context()
        self._local_rank = self._ctx.local_rank
        self._shm = SharedMemoryHandler(shm_segment_name(self._local_rank))
        self._standalone = standalone
        if standalone:
            self._lock = None
            self._event_queue = None
        else:
            self._lock = SharedLockClient(
                f"{CKPT_LOCK_PREFIX}_{self._local_rank}"
            )
            self._event_queue = SharedQueueClient(CKPT_EVENT_QUEUE)
        self._last_save_time = 0.0
        self._last_disk_step = -1  # newest step a disk save was requested for
        # Async snapshot pipeline: the training thread only LAUNCHES the
        # device->host DMA; a writer thread materializes the arrays (the
        # np conversion completes the in-flight transfer) and writes shm.
        import threading

        self._snap_cond = threading.Condition()
        # Serializes ALL shm writes in this process (training thread's
        # direct saves vs the async writer thread); the UDS SharedLock
        # only guards against the agent, not intra-process races, and is
        # absent entirely in standalone mode.
        self._save_mutex = threading.Lock()
        self._pending_snapshot = None  # (step, state, user_meta)
        self._writing_step = -1
        self._last_written_step = -1
        self._write_error: Optional[BaseException] = None
        self._writer_thread = None
        self._writer_stop = False
        from dlrover_tpu.flash_ckpt.autotune import SaveCostTracker

        self.cost_tracker = SaveCostTracker()
        from dlrover_tpu.observability.registry import default_registry

        registry = default_registry()
        self._saves_counter = registry.counter(
            "flash_ckpt_memory_saves_total",
            "flash checkpoint shm saves completed",
        )
        self._save_block_hist = registry.histogram(
            "flash_ckpt_save_block_seconds",
            "training-thread seconds blocked per shm save",
        )
        self._restore_hist = registry.histogram(
            "flash_ckpt_restore_seconds",
            "storage restore wall seconds (read + assembly)",
        )
        self._restore_bw_hist = registry.histogram(
            "flash_ckpt_restore_mb_per_s",
            "storage restore bandwidth (local bytes / wall seconds)",
            buckets=(1, 4, 16, 64, 256, 1024, 4096, 16384),
        )
        self._restore_bytes = registry.counter(
            "flash_ckpt_restore_bytes_total",
            "bytes materialized by storage restores",
        )
        self._restore_rejected = registry.counter(
            "flash_ckpt_restore_steps_rejected_total",
            "checkpoint steps rejected at restore (torn/corrupt shards)",
        )
        # How many earlier step dirs a restore may fall back through
        # when the newest is corrupt; retention keeps ~max_to_keep dirs.
        self._restore_fallback_steps = get_env_int(
            "DLROVER_TPU_CKPT_RESTORE_FALLBACK_STEPS", 3
        )

    # ---- save --------------------------------------------------------------

    def save_to_memory(
        self,
        step: int,
        state: Any,
        user_meta: Optional[Dict[str, Any]] = None,
    ) -> float:
        """Blocking-path save: device -> shm. Returns block seconds.

        For a TRAINING-THREAD caller the whole elapsed is the blocking
        cost the Young/Daly autotuner needs, so it is recorded as such
        here; the async writer thread must use :meth:`_save_to_memory`
        instead — its shm write overlaps training and recording it as a
        blocking cost would inflate the recommended cadence ~100x."""
        elapsed = self._save_to_memory(step, state, user_meta)
        if elapsed > 0.0:
            self.cost_tracker.record_block(elapsed)
            self._save_block_hist.observe(elapsed)
        return elapsed

    def _save_to_memory(
        self,
        step: int,
        state: Any,
        user_meta: Optional[Dict[str, Any]] = None,
    ) -> float:
        start = time.time()
        with self._save_mutex:
            if step < self._last_written_step:
                # shm must only move forward: an older (async) snapshot
                # racing a newer direct save is refused, not written.
                logger.warning(
                    "refusing to write step %d over newer shm step %d",
                    step,
                    self._last_written_step,
                )
                return 0.0
            return self._save_to_memory_locked(
                step, state, user_meta, start
            )

    def _save_to_memory_locked(self, step, state, user_meta, start):
        import jax

        from dlrover_tpu.training_event import TrainerEvents

        with TrainerEvents.ckpt_save_memory(step) as span:
            jax.block_until_ready(state)
            meta = dict(user_meta or {})
            meta["process_id"] = self._ctx.process_id
            meta["num_processes"] = self._ctx.num_processes
            meta["local_rank"] = self._local_rank
            # Identity stamp: a segment left behind by a DIFFERENT job
            # that happened to share the shm name must never be restored.
            # realpath: '/a/ckpt/' vs '/a/ckpt' vs symlink spellings of
            # the same dir must not false-reject our own image.
            meta["ckpt_dir"] = os.path.realpath(self.checkpoint_dir)
            if self._lock is not None:
                self._lock.acquire()
            try:
                self._shm.save_state_dict(step, state, meta)
            finally:
                if self._lock is not None:
                    self._lock.release()
            if self._event_queue is not None and self._local_rank == 0:
                self._event_queue.put(
                    SaveEvent(
                        SaveEvent.SAVE_MEM,
                        step,
                        self.checkpoint_dir,
                        self._ctx.local_world_size,
                    )
                )
            elapsed = time.time() - start
            span.content["block_s"] = elapsed
        self._last_save_time = time.time()
        self._last_written_step = max(self._last_written_step, step)
        self.cost_tracker.record_drain(elapsed)
        self._saves_counter.inc()
        logger.info(
            "flash ckpt step %d -> shm in %.3fs", step, elapsed
        )
        return elapsed

    def save_to_memory_async(
        self,
        step: int,
        state: Any,
        user_meta: Optional[Dict[str, Any]] = None,
    ) -> float:
        """Non-blocking save: launch device->host DMA and return.

        The TPU flash-checkpoint hot path: ``copy_to_host_async`` starts
        the transfer, compute on the next step overlaps with the DMA, and
        a writer thread lands the bytes in shm when they arrive. The
        caller must NOT donate the passed state to later steps (keep
        ``donate=False`` on the jitted step, or pass a copy).

        Returns the blocking seconds (async-copy launch cost, ~ms even
        for multi-GB states).
        """
        import jax

        start = time.time()
        for leaf in jax.tree_util.tree_leaves(state):
            if hasattr(leaf, "copy_to_host_async"):
                leaf.copy_to_host_async()
        with self._snap_cond:
            if self._pending_snapshot is not None:
                logger.info(
                    "dropping unwritten snapshot of step %d for step %d",
                    self._pending_snapshot[0],
                    step,
                )
            self._pending_snapshot = (step, state, user_meta)
            self._ensure_writer()
            self._snap_cond.notify_all()
        elapsed = time.time() - start
        self.cost_tracker.record_block(elapsed)
        logger.info(
            "flash ckpt step %d async-launched in %.4fs", step, elapsed
        )
        return elapsed

    def recommended_interval_s(self, mtbf_s: float = 3600.0):
        """Young/Daly save cadence from THIS engine's measured costs
        (flash_ckpt/autotune.py); None until a save was measured."""
        return self.cost_tracker.recommended_interval_s(mtbf_s)

    def wait_async_save(self, timeout: float = 600.0) -> bool:
        """Block until every launched snapshot has landed in shm.

        False on timeout OR if the last write failed (the caller must
        not assume the launched step is restorable)."""
        deadline = time.time() + timeout
        with self._snap_cond:
            while (
                self._pending_snapshot is not None
                or self._writing_step >= 0
            ):
                remaining = deadline - time.time()
                if remaining <= 0:
                    return False
                self._snap_cond.wait(min(remaining, 1.0))
            return self._write_error is None

    def _ensure_writer(self):
        import threading

        if self._writer_thread is None or not self._writer_thread.is_alive():
            self._writer_stop = False
            self._writer_thread = threading.Thread(
                target=self._writer_loop, name="ckpt-writer", daemon=True
            )
            self._writer_thread.start()

    def _writer_loop(self):
        while True:
            with self._snap_cond:
                while self._pending_snapshot is None:
                    if self._writer_stop:
                        return
                    self._snap_cond.wait(1.0)
                step, state, user_meta = self._pending_snapshot
                self._pending_snapshot = None
                if step <= self._last_written_step:
                    # A direct save_to_memory of a NEWER step landed while
                    # this snapshot waited: writing it would regress shm.
                    logger.info(
                        "skipping stale async snapshot of step %d "
                        "(step %d already in shm)",
                        step,
                        self._last_written_step,
                    )
                    self._snap_cond.notify_all()
                    continue
                self._writing_step = step
            try:
                # _save_to_memory, NOT save_to_memory: this thread's shm
                # write overlaps training — it is drain, not block.
                self._save_to_memory(step, state, user_meta)
                with self._snap_cond:
                    self._write_error = None
            except Exception as e:
                logger.exception("async snapshot write failed")
                with self._snap_cond:
                    self._write_error = e
            finally:
                with self._snap_cond:
                    self._writing_step = -1
                    self._snap_cond.notify_all()

    def save_to_storage(
        self,
        step: int,
        state: Any,
        user_meta: Optional[Dict[str, Any]] = None,
    ) -> float:
        """Save to shm, then request async persistence to storage."""
        elapsed = self.save_to_memory(step, state, user_meta)
        prev_disk_step = self._last_disk_step
        self._last_disk_step = step
        if self._standalone:
            # Mirror the agent path: one persister per node. Every local
            # worker writing the node's files concurrently would race on
            # the shared tmp names and multiply checkpoint I/O by the
            # local world size.
            if self._local_rank == 0 and not self._persist_in_process(step):
                logger.error(
                    "standalone persist of step %d failed; the disk "
                    "checkpoint for this step was NOT committed",
                    step,
                )
                # This process KNOWS the step never committed: leaving it
                # recorded would make wait_saving_complete block its full
                # timeout on a tracker that will never advance.
                self._last_disk_step = prev_disk_step
        elif self._local_rank == 0:
            self._event_queue.put(
                SaveEvent(
                    SaveEvent.SAVE_DISK,
                    step,
                    self.checkpoint_dir,
                    self._ctx.local_world_size,
                )
            )
        return elapsed

    def _persist_in_process(self, step: int) -> bool:
        from dlrover_tpu.flash_ckpt.saver import persist_shm_to_storage

        node_rank = int(os.getenv(NodeEnv.NODE_RANK, "0"))
        # Standalone has no shm locks and no agent: sibling local workers
        # write their segments on their own schedule, so wait (bounded)
        # until every local segment holds >= the requested step before
        # reading — otherwise the persist sees a missing/older sibling
        # image and the step's disk checkpoint is silently dropped.
        if not self._wait_local_segments(step, timeout=30.0):
            logger.error(
                "not all %d local shm segments reached step %d within "
                "30s; aborting standalone persist",
                self._ctx.local_world_size,
                step,
            )
            return False
        # Expect every node of the world: only the leader (lowest rank)
        # commits, and only after all nodes' shard markers exist — each
        # node committing alone would advance the tracker to steps whose
        # peer shards aren't on disk yet (unrestorable "latest" step).
        # The agent injects the ACTUAL membership; arithmetic over
        # process counts would be wrong for uneven or non-contiguous
        # worlds.
        expected = list(self._ctx.node_ranks) or [node_rank]
        # Standalone runs the commit on the TRAINING thread: a dead peer
        # must cost seconds, not the agent path's 10 minutes. Tunable
        # because this wait is uninterruptible — a live-rescale worker
        # whose peer was just killed is blind to the superseding plan
        # until the commit wait returns, so rescale harnesses cap it.
        commit_timeout = get_env_int(
            "DLROVER_TPU_CKPT_COMMIT_TIMEOUT_S", 30
        )
        return persist_shm_to_storage(
            self.checkpoint_dir,
            step,
            node_rank,
            local_world_size=self._ctx.local_world_size,
            expected_nodes=expected,
            commit_timeout=float(commit_timeout),
        )

    def _wait_local_segments(self, step: int, timeout: float) -> bool:
        """True once every local worker's shm segment holds >= ``step``.

        One SharedMemoryHandler per sibling is attached ONCE and polled,
        not opened/closed every 50ms (each open is a shm_open+mmap
        syscall pair). A lagging sibling's handler is re-attached about
        once a second — the rare case where the sibling unlinked and
        recreated a larger segment would otherwise pin us to the stale
        mapping forever.
        """
        deadline = time.time() + timeout
        handlers = {
            lr: SharedMemoryHandler(shm_segment_name(lr))
            for lr in range(self._ctx.local_world_size)
            if lr != self._local_rank  # our own save already landed
        }
        try:
            polls = 0
            while True:
                ready = True
                for lr, handler in handlers.items():
                    if handler.get_step() < step:
                        ready = False
                        if polls and polls % 20 == 0:
                            handler.close()  # re-attach next poll
                        break
                if ready:
                    return True
                if time.time() >= deadline:
                    return False
                polls += 1
                time.sleep(0.05)
        finally:
            for handler in handlers.values():
                handler.close()

    # ---- load --------------------------------------------------------------

    def load(self, step: Optional[int] = None, sharding_tree=None):
        """Return (step, state, user_meta) or None.

        Memory-first: the shm image survives worker restarts on the same
        host (its leaves come back as numpy). Falls back to the committed
        storage checkpoint; with ``sharding_tree`` the storage path is a
        sharding-aware partial restore — only this process's addressable
        byte ranges are read and leaves come back as placed jax Arrays.
        """
        from dlrover_tpu.training_event import TrainerEvents

        result = self._load_from_memory(step)
        if result is not None:
            logger.info("restored step %d from host memory", result[0])
            TrainerEvents.ckpt_restore(result[0], "memory")
            return result
        result = self._load_from_storage(step, sharding_tree)
        if result is not None:
            logger.info("restored step %d from storage", result[0])
            TrainerEvents.ckpt_restore(result[0], "storage")
        return result

    def _load_from_memory(self, step: Optional[int] = None):
        try:
            fault_point("ckpt.restore.memory", step=step)
        except FaultInjected:
            # Chaos: the host (and its shm) was replaced — there is no
            # memory image to restore; storage must carry the recovery.
            logger.warning("chaos: shm image treated as lost")
            return None
        mem_step = self._shm.get_step()
        if mem_step < 0 or (step is not None and mem_step != step):
            return None
        loaded = self._shm.load_state_dict()
        if loaded is None:
            return None
        mem_step, state, meta = loaded
        if self._is_foreign_image(meta):
            # Leftover segment from another job sharing the shm name
            # (default JOB_NAME, reused dev box): not our checkpoint.
            logger.warning(
                "ignoring shm image of foreign checkpoint %s",
                meta.get("ckpt_dir"),
            )
            return None
        if meta.get("num_processes") != self._ctx.num_processes:
            # World changed: per-process shm images do not cover the same
            # index set; storage has the complete picture.
            return None
        state = assemble_sharded_leaves(state)
        if state is None:
            return None
        return mem_step, state, meta

    def _load_from_storage(
        self, step: Optional[int] = None, sharding_tree=None
    ):
        """Restore the requested (or tracker) step; when that step's
        shard files are torn/corrupt/incomplete AND no explicit step was
        demanded, fall back to the newest earlier step dir that still
        restores — a torn write must cost one checkpoint interval, not
        the job (docs/DESIGN.md §26 invariant 2). Explicit ``step``
        requests never silently substitute a different step."""
        target = step
        if target is None:
            target = ckpt_storage.read_tracker(self.checkpoint_dir)
        if target < 0:
            return None
        candidates = [target]
        if step is None:
            candidates += [
                s
                for s in sorted(
                    ckpt_storage.list_step_dirs(self.checkpoint_dir),
                    reverse=True,
                )
                if s < target
            ][: self._restore_fallback_steps]
        for i, cand in enumerate(candidates):
            metas = ckpt_storage.load_step_meta(self.checkpoint_dir, cand)
            if not metas:
                continue
            start = time.time()
            result = load_global_state(
                self.checkpoint_dir, cand, metas, sharding_tree
            )
            if result is None:
                self._restore_rejected.inc()
                logger.error(
                    "checkpoint step %d is not restorable (torn/corrupt/"
                    "incomplete shards); trying an earlier step", cand
                )
                continue
            if i > 0:
                logger.warning(
                    "restored FALLBACK step %d (newest step %d was "
                    "unrestorable)", cand, target
                )
            elapsed = max(time.time() - start, 1e-9)
            nbytes = _state_local_nbytes(result[1])
            self._restore_hist.observe(elapsed)
            self._restore_bytes.inc(nbytes)
            self._restore_bw_hist.observe(nbytes / 1e6 / elapsed)
            return result
        return None

    def _is_foreign_image(self, meta: dict) -> bool:
        stamped = meta.get("ckpt_dir")
        return stamped is not None and stamped != os.path.realpath(
            self.checkpoint_dir
        )

    def latest_step(self) -> int:
        """Newest restorable step (max of shm image and storage tracker).
        A foreign job's shm image is not restorable by us and must not
        be advertised."""
        mem_step = -1
        meta = self._shm.load_meta()
        if meta is not None and not self._is_foreign_image(
            meta.get("user_meta", {})
        ):
            mem_step = meta.get("step", -1)
        return max(
            mem_step,
            ckpt_storage.read_tracker(self.checkpoint_dir),
        )

    def close(self):
        drained = self.wait_async_save(timeout=60.0)
        with self._snap_cond:
            self._writer_stop = True
            self._snap_cond.notify_all()
        if not drained:
            logger.error(
                "async snapshot did not drain cleanly before close; the "
                "newest launched step may not be restorable from memory"
            )
        # Let the writer finish/exit before closing shm under it.
        if self._writer_thread is not None:
            self._writer_thread.join(timeout=10.0)
            if self._writer_thread.is_alive():
                # Never close the segment under an in-progress write: a
                # leaked handle beats a torn snapshot. The daemon thread
                # dies with the process.
                logger.error(
                    "ckpt writer still running at close; leaving shm open"
                )
                return
        self._shm.close()


# --------------------------------------------------------------------------
# Reassembly helpers
# --------------------------------------------------------------------------


def assemble_sharded_leaves(state):
    """Convert {"__shards__": ...} leaf records into full numpy arrays.

    Returns None if any leaf's shards don't cover its global shape (the
    caller must then use storage, which has every process's shards).
    """
    import jax

    incomplete = []

    def fix(leaf):
        if not (isinstance(leaf, dict) and "__shards__" in leaf):
            return leaf
        assembled = _assemble_from_shards(
            leaf["__global_shape__"], leaf["__dtype__"], leaf["__shards__"]
        )
        if assembled is None:
            incomplete.append(leaf["__global_shape__"])
        return assembled

    is_record = lambda x: isinstance(x, dict) and "__shards__" in x  # noqa: E731
    out = jax.tree_util.tree_map(fix, state, is_leaf=is_record)
    if incomplete:
        return None
    return out


def _assemble_from_shards(global_shape, dtype_name, shards):
    from dlrover_tpu.flash_ckpt.shm_handler import _np_dtype

    dtype = _np_dtype(dtype_name)
    out = np.zeros(global_shape, dtype=dtype)
    covered = np.zeros(global_shape, dtype=bool) if global_shape else None
    for bounds, arr in shards:
        slices = bounds_to_slices(bounds)
        out[slices] = arr
        if covered is not None:
            covered[slices] = True
    if covered is not None and not covered.all():
        return None
    return out


def _state_local_nbytes(state) -> int:
    """Bytes this process materialized for ``state``: DISTINCT
    addressable shard bytes for jax Arrays (the partial-restore
    footprint; replicas of the same index dedupe — the restore read
    them from disk once), full nbytes for host arrays."""
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(state):
        if isinstance(leaf, jax.Array):
            try:
                seen = set()
                for s in leaf.addressable_shards:
                    key = tuple(
                        (sl.start, sl.stop) for sl in s.index
                    )
                    if key in seen:
                        continue
                    seen.add(key)
                    total += s.data.nbytes
                continue
            except Exception:
                pass
        if hasattr(leaf, "nbytes"):
            total += leaf.nbytes
    return total


def _norm_bounds(bounds, global_shape):
    """Close open slice ends: ((0,None),) over (8,) -> ((0,8),)."""
    return tuple(
        (lo if lo is not None else 0, hi if hi is not None else dim)
        for (lo, hi), dim in zip(bounds, global_shape)
    )


def _norm_index(index, global_shape):
    """Normalize a tuple of slices (a jax shard index) to closed bounds."""
    return tuple(
        (s.start if s.start is not None else 0,
         s.stop if s.stop is not None else dim)
        for s, dim in zip(index, global_shape)
    )


def _intersect_bounds(a, b):
    """Intersection of two closed bounds tuples, or None if empty."""
    out = []
    for (a0, a1), (b0, b1) in zip(a, b):
        lo, hi = max(a0, b0), min(a1, b1)
        if lo >= hi:
            return None
        out.append((lo, hi))
    return tuple(out)


def _bounds_volume(b) -> int:
    vol = 1
    for lo, hi in b:
        vol *= hi - lo
    return vol


def _tiles_exactly(region, inters) -> bool:
    """True if ``inters`` (intersections already clipped to ``region``)
    are pairwise disjoint and their volumes sum to the region's — an
    O(h^2) proof of full coverage that replaces an O(region-bytes)
    boolean mask for the common disjoint-shard layout."""
    if sum(_bounds_volume(b) for b in inters) != _bounds_volume(region):
        return False
    for i in range(len(inters)):
        for j in range(i + 1, len(inters)):
            if _intersect_bounds(inters[i], inters[j]) is not None:
                return False
    return True


def _needed_region_bounds(sharding, global_shape, addressable=None):
    """The distinct index bounds THIS process must materialize for a
    leaf under ``sharding`` — the partial-restore index set. Replicas
    collapse; non-addressable devices' shards are never read."""
    if addressable is None:
        addressable = sharding.addressable_devices
    imap = sharding.devices_indices_map(tuple(global_shape))
    needed = {}
    for dev, idx in imap.items():
        if dev not in addressable:
            continue
        needed[_norm_index(idx, global_shape)] = True
    return list(needed)


class _LazyReaders:
    """Opens a process's shard file on FIRST use, not up front: after a
    re-mesh on a large world, a partial restore may need bytes from a
    handful of the N proc files — eagerly opening all N (open + header
    parse + stat each, per restoring process, against shared storage)
    would put O(world size) metadata I/O on the hot path."""

    def __init__(self, checkpoint_dir: str, step: int, pids):
        import threading

        self._dir = checkpoint_dir
        self._step = step
        self._pids = set(pids)
        self._lock = threading.Lock()
        self._open: Dict[int, Any] = {}
        self._missing = set()

    def get(self, pid: int):
        if pid not in self._pids or pid in self._missing:
            return None
        with self._lock:
            reader = self._open.get(pid)
            if reader is None and pid not in self._missing:
                reader = ckpt_storage.open_proc_shards(
                    self._dir, self._step, pid
                )
                if reader is None:
                    self._missing.add(pid)
                else:
                    self._open[pid] = reader
            return reader

    def close_all(self):
        with self._lock:
            for reader in self._open.values():
                reader.close()
            self._open.clear()


def _index_shard_locations(metas: Dict[int, dict]):
    """Build (leaf_info, locations) from per-process metas.

    leaf_info[i] = (global_shape, dtype_name);
    locations[i] = [(pid, key, closed shard bounds), ...].
    """
    first = metas[min(metas)]
    num_leaves = len(first["leaves"])
    leaf_info = [None] * num_leaves
    locations = [[] for _ in range(num_leaves)]
    for pid, meta in sorted(metas.items()):
        for leaf_meta in meta["leaves"]:
            i = leaf_meta.leaf_id
            gshape = tuple(leaf_meta.global_shape)
            leaf_info[i] = (gshape, leaf_meta.dtype)
            for j, shard in enumerate(leaf_meta.shards):
                locations[i].append(
                    (pid, f"leaf{i}_shard{j}",
                     _norm_bounds(shard.index, gshape))
                )
    return leaf_info, locations


def _assemble_leaf_regions(info, shard_locs, readers, region_bounds_list):
    """Read exactly the byte ranges covering ``region_bounds_list`` for
    one leaf. Allocates O(region bytes) host memory — never the global
    shape (the partial-restore guarantee). Returns {bounds: array}, or
    None if any region is not fully covered by the stored shards.
    """
    from dlrover_tpu.flash_ckpt.shm_handler import _np_dtype

    gshape, dtype_name = info
    dtype = _np_dtype(dtype_name)
    regions = {}
    for rb in region_bounds_list:
        shape = tuple(hi - lo for lo, hi in rb)
        if 0 in shape:
            # Zero-size leaf (empty optimizer slot): there are no bytes
            # to read and no coverage to prove — _intersect_bounds
            # treats empty extents as "no hit", which must not make the
            # whole checkpoint unrestorable.
            regions[rb] = np.empty(shape, dtype=dtype)
            continue
        # Which stored shards intersect this region? Identical
        # intersections dedupe (a leaf replicated across P processes
        # appears in every proc file — reading it P times would multiply
        # disk I/O by P and the overlap would force the coverage mask).
        hits = []
        seen_inter = set()
        for pid, key, sb in shard_locs:
            # Intersect on the METADATA bounds before touching the
            # reader: with lazy opening, a proc file none of whose
            # shards intersect our regions is never even opened.
            if shape:
                inter = _intersect_bounds(rb, sb)
                if inter is None or inter in seen_inter:
                    continue
            reader = readers.get(pid)
            if reader is None or key not in reader:
                continue
            if not shape:
                hits.append((reader, key, (), ()))
                continue
            seen_inter.add(inter)
            hits.append((reader, key, inter, sb))
        if not hits:
            return None
        out = np.empty(shape, dtype=dtype)
        if not shape:
            reader, key, _, _ = hits[0]
            reader.read_slice_into(key, (), out, verify=True)
            regions[rb] = out
            continue
        # Coverage proof without the O(region) bool mask when possible:
        # disjoint intersections whose volumes sum to the region volume
        # tile it exactly (the normal sharded-save layout). The mask is
        # only materialized for overlapping shards (replicas straddling
        # a region boundary).
        exact = _tiles_exactly(rb, [h[2] for h in hits])
        covered = None if exact else np.zeros(shape, dtype=bool)
        for reader, key, inter, sb in hits:
            src = tuple(
                slice(lo - s0, hi - s0)
                for (lo, hi), (s0, _) in zip(inter, sb)
            )
            dst = tuple(
                slice(lo - r0, hi - r0)
                for (lo, hi), (r0, _) in zip(inter, rb)
            )
            # Full-shard reads checksum the copied bytes (the format's
            # bitflip guarantee); sub-range reads can't without reading
            # the whole shard, which would defeat partial restore.
            reader.read_slice_into(
                key, src, out[dst], verify=(inter == sb)
            )
            if covered is not None:
                covered[dst] = True
        if covered is not None and not covered.all():
            return None
        regions[rb] = out
    return regions


def load_global_state(
    checkpoint_dir: str,
    step: int,
    metas: Dict[int, dict],
    sharding_tree=None,
):
    """Assemble the state for ``step`` from the per-process shard files.

    Without ``sharding_tree``: full global numpy leaves (every byte is
    read), leaf reads fanned out over a thread pool.

    With ``sharding_tree`` (matching pytree of ``jax.sharding.Sharding``):
    sharding-aware partial restore — each leaf's addressable index set is
    computed from its sharding, ONLY the intersecting byte ranges are
    read from the mmap'd shard files, and leaves come back as jax Arrays
    built with ``jax.make_array_from_callback``. Host RAM is O(local
    bytes), and completed leaves stream into device transfer while later
    leaves are still on disk (pipelined restore).
    """
    import jax

    from dlrover_tpu.common.serialize import loads_pytree
    from dlrover_tpu.flash_ckpt.raw_format import ShardCorruptionError

    first = metas[min(metas)]
    treedef = loads_pytree(first["treedef"])
    user_meta = first.get("user_meta", {})
    leaf_info, locations = _index_shard_locations(metas)
    num_leaves = len(leaf_info)

    shardings = None
    if sharding_tree is not None:
        try:
            shardings = treedef.flatten_up_to(sharding_tree)
        except ValueError as e:
            logger.warning(
                "sharding_tree does not match the checkpoint's structure "
                "(%s); falling back to full-state restore", e
            )

    readers = _LazyReaders(checkpoint_dir, step, metas)
    try:

        def region_bounds_for(i):
            gshape = leaf_info[i][0]
            sharding = shardings[i] if shardings is not None else None
            if sharding is None:
                return [tuple((0, d) for d in gshape)]  # full leaf
            return _needed_region_bounds(sharding, gshape)

        leaves = [None] * num_leaves
        from concurrent.futures import ThreadPoolExecutor, as_completed

        # Pipelined restore: the pool only READS (host region buffers);
        # jax-array construction runs here on the caller's thread as
        # each leaf's bytes land, so H2D transfer of early leaves
        # overlaps disk reads of later ones.
        with ThreadPoolExecutor(
            max_workers=ckpt_storage.io_threads(max(num_leaves, 1)),
            thread_name_prefix="ckpt-restore",
        ) as pool:
            futures = {
                pool.submit(
                    _assemble_leaf_regions,
                    leaf_info[i],
                    locations[i],
                    readers,
                    region_bounds_for(i),
                ): i
                for i in range(num_leaves)
                if leaf_info[i] is not None
            }
            for fut in as_completed(futures):
                i = futures[fut]
                regions = fut.result()
                if regions is None:
                    continue
                gshape = leaf_info[i][0]
                sharding = (
                    shardings[i] if shardings is not None else None
                )
                if sharding is None:
                    leaves[i] = regions[tuple((0, d) for d in gshape)]
                    continue

                def cb(idx, _regions=regions, _gshape=gshape):
                    return _regions[_norm_index(idx, _gshape)]

                leaves[i] = jax.make_array_from_callback(
                    gshape, sharding, cb
                )
    except ShardCorruptionError as e:
        logger.error(
            "refusing corrupt checkpoint step %d: %s", step, e
        )
        return None
    finally:
        readers.close_all()
    if any(l is None for l in leaves):
        return None
    state = jax.tree_util.tree_unflatten(treedef, leaves)
    return step, state, user_meta


def load_state_regions(
    checkpoint_dir: str,
    step: int,
    regions_by_leaf: Optional[Dict[int, list]] = None,
):
    """Explicit-region partial restore (the live-rescale path for hosts
    that address their shards by byte range rather than a jax sharding).

    ``regions_by_leaf``: leaf_id -> list of closed bounds tuples
    (``((lo, hi), ...)`` per dim); leaves absent from the map are read
    in full. Reads ONLY the intersecting byte ranges from the step's
    mmap'd shard files through the same lazy-reader machinery the
    sharding-tree restore uses — after an N→M re-mesh each survivor
    pays O(its new bytes), not O(global state).

    Returns ``(step, leaves, user_meta)`` with
    ``leaves[leaf_id] = {bounds: np.ndarray}``, or None when the step is
    missing/torn/not fully covering a requested region.
    """
    from dlrover_tpu.flash_ckpt.raw_format import ShardCorruptionError

    metas = ckpt_storage.load_step_meta(checkpoint_dir, step)
    if not metas:
        return None
    first = metas[min(metas)]
    user_meta = first.get("user_meta", {})
    leaf_info, locations = _index_shard_locations(metas)
    regions_by_leaf = regions_by_leaf or {}
    readers = _LazyReaders(checkpoint_dir, step, metas)
    leaves: Dict[int, dict] = {}
    try:
        for i, info in enumerate(leaf_info):
            if info is None:
                return None
            gshape = info[0]
            bounds_list = regions_by_leaf.get(i)
            if bounds_list is None:
                bounds_list = [tuple((0, d) for d in gshape)]
            bounds_list = [
                tuple(tuple(b) for b in bounds) for bounds in bounds_list
            ]
            regions = _assemble_leaf_regions(
                info, locations[i], readers, bounds_list
            )
            if regions is None:
                logger.error(
                    "step %d leaf %d: requested regions not covered by "
                    "stored shards", step, i
                )
                return None
            leaves[i] = regions
    except ShardCorruptionError as e:
        logger.error("refusing corrupt checkpoint step %d: %s", step, e)
        return None
    finally:
        readers.close_all()
    return step, leaves, user_meta


# Which host->device branch to_device_state's sharded restores took in
# this process: the fallback is ~10x slower and only logs a warning, so
# a caller that must not take it silently (chip_smoke.py) reads this.
RESTORE_BRANCH_COUNTS = {"batched": 0, "per_leaf": 0}


def to_device_state(np_state, sharding_tree=None):
    """Put a numpy pytree onto devices under the current mesh.

    sharding_tree: matching pytree of ``jax.sharding.Sharding`` (or None
    for single-device default placement). Each process materializes only
    its addressable shards — the resharding restore path ("universal
    checkpoint" analogue).

    A single batched ``device_put`` lets the runtime pipeline all leaf
    transfers (~10x faster restore than per-leaf puts on slow links);
    the per-leaf ``make_array_from_callback`` path is the fallback for
    runtimes that reject global host arrays under non-addressable
    shardings.

    Leaves that are ALREADY placed jax Arrays under their requested
    sharding (the partial-restore path returns these) pass through
    untouched — re-putting them would be a no-op at best.
    """
    import jax

    leaves = jax.tree_util.tree_leaves(np_state)
    if leaves and all(isinstance(l, jax.Array) for l in leaves):
        if sharding_tree is None:
            return np_state
        placed = jax.tree_util.tree_leaves(sharding_tree)
        if len(placed) == len(leaves) and all(
            getattr(l, "sharding", None) == s
            for l, s in zip(leaves, placed)
        ):
            return np_state

    if sharding_tree is None:
        return jax.tree_util.tree_map(jax.numpy.asarray, np_state)

    try:
        placed = jax.device_put(np_state, sharding_tree)
        RESTORE_BRANCH_COUNTS["batched"] += 1
        return placed
    except (ValueError, NotImplementedError, jax.errors.JaxRuntimeError) as e:
        # The known "runtime rejects global host arrays under
        # non-addressable shardings" shapes only — anything else (host
        # OOM, dtype corruption) must surface, not be absorbed by the
        # slower per-leaf fallback.
        logger.warning(
            "batched device_put restore unavailable (%s: %s); using "
            "per-leaf transfers",
            type(e).__name__,
            e,
        )
    RESTORE_BRANCH_COUNTS["per_leaf"] += 1

    def put(arr, sharding):
        arr = np.asarray(arr)
        return jax.make_array_from_callback(
            arr.shape, sharding, lambda idx: arr[idx]
        )

    return jax.tree_util.tree_map(put, np_state, sharding_tree)
