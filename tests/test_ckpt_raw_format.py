"""Raw mmap checkpoint format: roundtrip, npz compat, corruption
rejection, sharding-aware partial restore, parallel-persist race, and
retention edge cases."""

import os
import pickle
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu.flash_ckpt import engine as ckpt_engine
from dlrover_tpu.flash_ckpt import storage as ckpt_storage
from dlrover_tpu.flash_ckpt.checkpointer import Checkpointer, StorageType
from dlrover_tpu.flash_ckpt.raw_format import (
    RawShardReader,
    ShardCorruptionError,
    write_raw_shards,
)
from dlrover_tpu.flash_ckpt.shm_handler import LeafMeta, ShardMeta
from dlrover_tpu.trainer import runtime


@pytest.fixture(autouse=True)
def fresh_runtime(monkeypatch, tmp_path):
    runtime._context = None
    monkeypatch.setenv(
        "DLROVER_TPU_JOB_NAME", f"raw{os.getpid()}_{time.time_ns() % 100000}"
    )
    monkeypatch.setenv("DLROVER_TPU_SHARED_DIR", str(tmp_path / "uds"))
    yield
    runtime._context = None


# ---------------------------------------------------------------------------
# Format-level roundtrip
# ---------------------------------------------------------------------------


def test_raw_file_roundtrip(tmp_path):
    path = str(tmp_path / "p.raw")
    arrays = {
        "leaf0_shard0": np.arange(32, dtype=np.float32).reshape(8, 4),
        "leaf1_shard0": np.asarray(7, np.int32),  # 0-d scalar leaf
    }
    bounds = {"leaf0_shard0": ((0, 8), (0, 4)), "leaf1_shard0": ()}
    write_raw_shards(path, step=3, process_id=1, arrays=arrays,
                     shard_bounds=bounds)
    with RawShardReader(path) as r:
        assert r.step == 3 and r.process_id == 1
        assert set(r.keys()) == set(arrays)
        assert r.bounds("leaf0_shard0") == ((0, 8), (0, 4))
        np.testing.assert_array_equal(
            r.get("leaf0_shard0"), arrays["leaf0_shard0"]
        )
        assert r.get("leaf1_shard0") == 7
        # sub-range read touches only the requested rows
        sl = r.read_slice("leaf0_shard0", (slice(2, 4), slice(0, 4)))
        np.testing.assert_array_equal(sl, arrays["leaf0_shard0"][2:4])
        # zero-copy view is mmap-backed
        v = r.view("leaf0_shard0")
        assert v.base is not None
        assert r.verify_all()
    assert r._mm is None  # context exit closed the mapping


def test_raw_handles_bf16_and_empty_shards(tmp_path):
    """bfloat16 (ml_dtypes — memoryview.cast chokes on it) and
    zero-size arrays must survive the raw write/read path; both are
    routine in real states (bf16 params, empty optimizer slots)."""
    import ml_dtypes

    path = str(tmp_path / "p.raw")
    bf16 = np.arange(16, dtype=np.float32).astype(ml_dtypes.bfloat16)
    arrays = {
        "leaf0_shard0": bf16.reshape(4, 4),
        "leaf1_shard0": np.zeros((0, 4), np.float32),
    }
    write_raw_shards(path, 1, 0, arrays)
    with RawShardReader(path) as r:
        got = r.get("leaf0_shard0")
        assert got.dtype == ml_dtypes.bfloat16
        np.testing.assert_array_equal(
            got.astype(np.float32), bf16.reshape(4, 4).astype(np.float32)
        )
        assert r.get("leaf1_shard0").shape == (0, 4)
        assert r.verify_all()


def test_zero_size_leaf_restores(tmp_path):
    """An empty leaf must not make the whole checkpoint unrestorable
    (the coverage logic treats empty extents as 'no hit')."""
    ckpt_dir = str(tmp_path / "ckpt")
    ckpt = Checkpointer(ckpt_dir, standalone=True)
    try:
        state = {"w": jnp.ones((8, 4)), "empty": jnp.zeros((0,))}
        ckpt.save_checkpoint(3, state, StorageType.DISK)
        ckpt._engine._shm.unlink()
        ckpt._engine._shm.close()
        result = ckpt.load_checkpoint(to_device=False)
        assert result is not None
        step, restored, _ = result
        assert step == 3
        assert np.asarray(restored["empty"]).shape == (0,)
        np.testing.assert_array_equal(
            np.asarray(restored["w"]), np.ones((8, 4))
        )
    finally:
        ckpt._engine._shm.unlink()
        ckpt.close()


def test_bf16_state_disk_roundtrip(tmp_path):
    """End-to-end disk persist/restore of a bfloat16 state through the
    engine (the production dtype for params)."""
    ckpt_dir = str(tmp_path / "ckpt")
    ckpt = Checkpointer(ckpt_dir, standalone=True)
    try:
        state = {"w": jnp.arange(32.0, dtype=jnp.bfloat16).reshape(8, 4)}
        ckpt.save_checkpoint(2, state, StorageType.DISK)
        assert ckpt_storage.read_tracker(ckpt_dir) == 2
        ckpt._engine._shm.unlink()
        ckpt._engine._shm.close()
        step, restored, _ = ckpt.load_checkpoint(to_device=False)
        assert step == 2
        np.testing.assert_array_equal(
            np.asarray(restored["w"]).astype(np.float32),
            np.arange(32.0, dtype=np.float32).reshape(8, 4),
        )
    finally:
        ckpt._engine._shm.unlink()
        ckpt.close()


def test_raw_rejects_truncation_and_bitflips(tmp_path):
    path = str(tmp_path / "p.raw")
    arrays = {"leaf0_shard0": np.ones((256, 256), np.float32)}
    write_raw_shards(path, 1, 0, arrays)
    size = os.path.getsize(path)

    # Torn write: file ends mid-data.
    trunc = str(tmp_path / "trunc.raw")
    with open(path, "rb") as src, open(trunc, "wb") as dst:
        dst.write(src.read(size - 4096))
    with pytest.raises(ShardCorruptionError):
        RawShardReader(trunc)

    # Silent bitflip in the data region: caught by the crc on read.
    flipped = str(tmp_path / "flip.raw")
    with open(path, "rb") as src:
        blob = bytearray(src.read())
    blob[-17] ^= 0xFF
    with open(flipped, "wb") as dst:
        dst.write(bytes(blob))
    with RawShardReader(flipped) as r:
        with pytest.raises(ShardCorruptionError):
            r.get("leaf0_shard0")
        assert not r.verify_all()

    # Garbage header.
    bad = str(tmp_path / "bad.raw")
    with open(bad, "wb") as f:
        f.write(b"NOTAFMT1" + b"\x00" * 64)
    with pytest.raises(ShardCorruptionError):
        RawShardReader(bad)


def test_engine_load_refuses_corrupt_step(tmp_path):
    """A torn shard file makes the restore return None (caller falls
    back), never a half-poisoned state."""
    ckpt_dir = str(tmp_path / "ckpt")
    ckpt = Checkpointer(ckpt_dir, standalone=True)
    try:
        ckpt.save_checkpoint(5, {"w": jnp.ones((64, 64))}, StorageType.DISK)
        sdir = ckpt_storage.step_dir(ckpt_dir, 5)
        raw = [n for n in os.listdir(sdir) if n.endswith(".raw")]
        assert raw, "disk save must write raw shard files"
        path = os.path.join(sdir, raw[0])
        blob = open(path, "rb").read()
        with open(path, "wb") as f:
            f.write(blob[: len(blob) // 2])
        ckpt._engine._shm.unlink()
        ckpt._engine._shm.close()
        assert ckpt.load_checkpoint() is None
    finally:
        ckpt._engine._shm.unlink()
        ckpt.close()


# ---------------------------------------------------------------------------
# Read compat: legacy .npz step dirs
# ---------------------------------------------------------------------------


def _two_proc_payloads(rows=8, cols=4, dtype=np.float32, value_scale=1.0):
    """A (8,4) leaf row-split across two 'processes', as the agent's
    persist path would build it."""
    full = (
        np.arange(rows * cols, dtype=dtype).reshape(rows, cols) * value_scale
    )
    state = {"w": full}
    _, treedef = jax.tree_util.tree_flatten(state)
    tb = pickle.dumps(treedef)
    half = rows // 2
    payloads = {}
    for pid, (lo, hi) in enumerate(((0, half), (half, rows))):
        payloads[pid] = {
            "arrays": {"leaf0_shard0": full[lo:hi]},
            "meta": {
                "treedef": tb,
                "leaves": [
                    LeafMeta(
                        leaf_id=0,
                        global_shape=(rows, cols),
                        dtype=np.dtype(dtype).name,
                        shards=[
                            ShardMeta(((lo, hi), (0, cols)), (hi - lo, cols))
                        ],
                    )
                ],
                "user_meta": {"process_id": pid},
            },
        }
    return payloads, full


def test_old_npz_step_dir_still_restores(tmp_path):
    ckpt_dir = str(tmp_path / "legacy")
    payloads, full = _two_proc_payloads()
    ckpt_storage.persist_node_shards(
        ckpt_dir, 7, node_rank=0, proc_payloads=payloads,
        fmt=ckpt_storage.NPZ_FORMAT,
    )
    sdir = ckpt_storage.step_dir(ckpt_dir, 7)
    assert any(n.endswith(".npz") for n in os.listdir(sdir))
    assert not any(n.endswith(".raw") for n in os.listdir(sdir))
    metas = ckpt_storage.load_step_meta(ckpt_dir, 7)
    loaded = ckpt_engine.load_global_state(ckpt_dir, 7, metas)
    assert loaded is not None
    step, state, _ = loaded
    assert step == 7
    np.testing.assert_array_equal(state["w"], full)
    # and through the full engine path (tracker -> storage restore)
    ckpt_storage.write_tracker(ckpt_dir, 7)
    ckpt = Checkpointer(ckpt_dir, standalone=True)
    try:
        step2, restored, _ = ckpt.load_checkpoint(to_device=False)
        assert step2 == 7
        np.testing.assert_array_equal(np.asarray(restored["w"]), full)
    finally:
        ckpt._engine._shm.unlink()
        ckpt.close()


def test_load_proc_arrays_context_managed(tmp_path):
    ckpt_dir = str(tmp_path / "cm")
    payloads, full = _two_proc_payloads()
    ckpt_storage.persist_node_shards(ckpt_dir, 1, 0, payloads)
    with ckpt_storage.load_proc_arrays(ckpt_dir, 1, 0) as reader:
        assert reader is not None
        assert "leaf0_shard0" in reader
        np.testing.assert_array_equal(
            reader.get("leaf0_shard0"), full[:4]
        )
        reader.view("leaf0_shard0")  # force the mapping open
        assert reader._mm is not None
    assert reader._mm is None  # closed deterministically on exit
    with ckpt_storage.load_proc_arrays(ckpt_dir, 1, 99) as missing:
        assert missing is None


# ---------------------------------------------------------------------------
# Sharding-aware partial restore
# ---------------------------------------------------------------------------


def test_partial_restore_reads_only_addressable(tmp_path):
    """With an addressable fraction < 1 the restore materializes ONLY
    the addressable regions — never a global-shape host array."""
    ckpt_dir = str(tmp_path / "partial")
    payloads, full = _two_proc_payloads()
    ckpt_storage.persist_node_shards(ckpt_dir, 2, 0, payloads)
    metas = ckpt_storage.load_step_meta(ckpt_dir, 2)
    leaf_info, locations = ckpt_engine._index_shard_locations(metas)

    devices = np.array(jax.devices())
    mesh = Mesh(devices.reshape(8), ("x",))
    sharding = NamedSharding(mesh, P("x", None))
    # Pretend only 2 of the 8 devices are addressable (a 2-process mesh
    # where this host owns devices 2 and 3): 1/4 of the leaf.
    addressable = set(devices[2:4].tolist())
    needed = ckpt_engine._needed_region_bounds(
        sharding, (8, 4), addressable=addressable
    )
    assert sorted(needed) == [((2, 3), (0, 4)), ((3, 4), (0, 4))]

    readers = {
        pid: ckpt_storage.open_proc_shards(ckpt_dir, 2, pid)
        for pid in metas
    }
    try:
        regions = ckpt_engine._assemble_leaf_regions(
            leaf_info[0], locations[0], readers, needed
        )
    finally:
        for r in readers.values():
            r.close()
    assert regions is not None
    # Shape inspection: every materialized buffer is a sub-global slice.
    assert {r.shape for r in regions.values()} == {(1, 4)}
    total_elems = sum(r.size for r in regions.values())
    assert total_elems == 8  # 2 rows of 4 — 1/4 of the 32-element leaf
    for bounds, arr in regions.items():
        (r0, r1), _ = bounds
        np.testing.assert_array_equal(arr, full[r0:r1])


def test_engine_restore_catches_data_bitflip(tmp_path):
    """A flipped byte inside the data region (file structurally intact)
    must fail the full-shard crc on the ENGINE path — restore returns
    None rather than poisoned weights."""
    ckpt_dir = str(tmp_path / "flip")
    ckpt = Checkpointer(ckpt_dir, standalone=True)
    try:
        ckpt.save_checkpoint(4, {"w": jnp.ones((64, 64))}, StorageType.DISK)
        sdir = ckpt_storage.step_dir(ckpt_dir, 4)
        path = [
            os.path.join(sdir, n)
            for n in os.listdir(sdir)
            if n.endswith(".raw")
        ][0]
        blob = bytearray(open(path, "rb").read())
        blob[-100] ^= 0xFF  # data region; header untouched
        with open(path, "wb") as f:
            f.write(bytes(blob))
        ckpt._engine._shm.unlink()
        ckpt._engine._shm.close()
        assert ckpt.load_checkpoint() is None
    finally:
        ckpt._engine._shm.unlink()
        ckpt.close()


def test_replicated_leaf_read_once(tmp_path):
    """A leaf replicated into every proc file is read from disk ONCE on
    restore (identical intersections dedupe), and the disjoint-tiling
    proof still applies (no coverage mask needed)."""
    full = np.arange(32, dtype=np.float32).reshape(8, 4)
    _, treedef = jax.tree_util.tree_flatten({"w": full})
    tb = pickle.dumps(treedef)
    payloads = {}
    for pid in (0, 1):  # BOTH procs hold the full leaf (replicated)
        payloads[pid] = {
            "arrays": {"leaf0_shard0": full},
            "meta": {
                "treedef": tb,
                "leaves": [
                    LeafMeta(
                        leaf_id=0, global_shape=(8, 4), dtype="float32",
                        shards=[ShardMeta(((0, 8), (0, 4)), (8, 4))],
                        replicated=True,
                    )
                ],
                "user_meta": {"process_id": pid},
            },
        }
    ckpt_dir = str(tmp_path / "rep")
    ckpt_storage.persist_node_shards(ckpt_dir, 1, 0, payloads)
    metas = ckpt_storage.load_step_meta(ckpt_dir, 1)
    leaf_info, locations = ckpt_engine._index_shard_locations(metas)
    assert len(locations[0]) == 2  # both procs advertise the leaf
    readers = {
        pid: ckpt_storage.open_proc_shards(ckpt_dir, 1, pid)
        for pid in metas
    }
    try:
        regions = ckpt_engine._assemble_leaf_regions(
            leaf_info[0], locations[0], readers, [((0, 8), (0, 4))]
        )
        assert regions is not None
        np.testing.assert_array_equal(regions[((0, 8), (0, 4))], full)
        total_read = sum(r.bytes_read for r in readers.values())
        assert total_read == full.nbytes, (
            f"replicated leaf read {total_read} bytes, expected "
            f"{full.nbytes} (each byte exactly once)"
        )
    finally:
        for r in readers.values():
            r.close()


def test_header_corruption_rejected_at_open(tmp_path):
    """A bitflip inside the JSON index (still-parseable header) must be
    rejected at open — a shifted offset would misdirect the unverified
    partial-range reads."""
    path = str(tmp_path / "p.raw")
    write_raw_shards(path, 1, 0, {"leaf0_shard0": np.ones((64,), np.float32)})
    blob = bytearray(open(path, "rb").read())
    # Flip one byte inside the JSON payload region (after the 20B prefix).
    blob[40] ^= 0x01
    with open(path, "wb") as f:
        f.write(bytes(blob))
    with pytest.raises(ShardCorruptionError, match="header checksum"):
        RawShardReader(path)


def test_partial_restore_opens_only_needed_proc_files(tmp_path):
    """Lazy reader opening: a partial restore whose regions intersect
    only proc 0's shards never opens (or stats) proc 1's file."""
    ckpt_dir = str(tmp_path / "lazy")
    payloads, full = _two_proc_payloads()
    ckpt_storage.persist_node_shards(ckpt_dir, 2, 0, payloads)
    metas = ckpt_storage.load_step_meta(ckpt_dir, 2)
    leaf_info, locations = ckpt_engine._index_shard_locations(metas)
    readers = ckpt_engine._LazyReaders(ckpt_dir, 2, metas)
    try:
        # Rows 0-2 live entirely in proc 0's shard (rows 0-4).
        regions = ckpt_engine._assemble_leaf_regions(
            leaf_info[0], locations[0], readers, [((0, 2), (0, 4))]
        )
        assert regions is not None
        np.testing.assert_array_equal(regions[((0, 2), (0, 4))], full[:2])
        assert set(readers._open) == {0}, (
            f"opened {set(readers._open)}; proc 1 holds no needed bytes"
        )
    finally:
        readers.close_all()


def test_partial_restore_incomplete_coverage_fails(tmp_path):
    ckpt_dir = str(tmp_path / "gap")
    payloads, _ = _two_proc_payloads()
    del payloads[1]  # second half of the leaf never persisted
    ckpt_storage.persist_node_shards(ckpt_dir, 2, 0, payloads)
    metas = ckpt_storage.load_step_meta(ckpt_dir, 2)
    leaf_info, locations = ckpt_engine._index_shard_locations(metas)
    readers = {0: ckpt_storage.open_proc_shards(ckpt_dir, 2, 0)}
    try:
        regions = ckpt_engine._assemble_leaf_regions(
            leaf_info[0], locations[0], readers,
            [((0, 8), (0, 4))],  # wants the full leaf
        )
    finally:
        readers[0].close()
    assert regions is None


def test_sharding_tree_restore_from_storage(tmp_path):
    """End-to-end: save sharded, wipe shm, restore with a sharding_tree
    — leaves come back as placed jax Arrays via the partial path."""
    ckpt_dir = str(tmp_path / "ckpt")
    devices = np.array(jax.devices())
    mesh = Mesh(devices.reshape(8), ("x",))
    s1 = NamedSharding(mesh, P("x", None))
    w = jax.device_put(jnp.arange(64.0).reshape(8, 8), s1)
    ckpt = Checkpointer(ckpt_dir, standalone=True)
    ckpt.save_checkpoint(9, {"w": w, "step": jnp.int32(9)}, StorageType.DISK)
    ckpt._engine._shm.unlink()
    ckpt._engine._shm.close()
    runtime._context = None
    ckpt2 = Checkpointer(ckpt_dir, standalone=True)
    try:
        # restore under a DIFFERENT layout (reshard on restore)
        mesh2 = Mesh(devices.reshape(2, 4), ("a", "b"))
        s2 = NamedSharding(mesh2, P(None, "b"))
        step, restored, _ = ckpt2.load_checkpoint(
            sharding_tree={"w": s2, "step": NamedSharding(mesh2, P())}
        )
        assert step == 9
        assert isinstance(restored["w"], jax.Array)
        assert restored["w"].sharding == s2
        np.testing.assert_array_equal(
            np.asarray(restored["w"]), np.arange(64.0).reshape(8, 8)
        )
        assert int(restored["step"]) == 9
    finally:
        ckpt2._engine._shm.unlink()
        ckpt2.close()
        ckpt.close()


# ---------------------------------------------------------------------------
# Parallel persist vs concurrent saves
# ---------------------------------------------------------------------------


def test_parallel_persist_race_keeps_step_dirs_single_step(tmp_path):
    """Concurrent shm saves during a persist can delay or abort a
    commit, but every step dir that lands holds shards of exactly one
    step (headers uniform, values uniform)."""
    from dlrover_tpu.flash_ckpt.engine import shm_segment_name
    from dlrover_tpu.flash_ckpt.saver import persist_shm_to_storage
    from dlrover_tpu.flash_ckpt.shm_handler import SharedMemoryHandler

    ckpt_dir = str(tmp_path / "race")
    handlers = [
        SharedMemoryHandler(shm_segment_name(lr)) for lr in (0, 1)
    ]
    locks = [threading.Lock(), threading.Lock()]

    def write_step(lr, step):
        with locks[lr]:
            handlers[lr].save_state_dict(
                step,
                {"w": np.full((64, 64), float(step), np.float32)},
                {"process_id": lr},
            )

    try:
        for lr in (0, 1):
            write_step(lr, 5)

        stop = threading.Event()
        persist_results = []

        def persist_loop():
            for step in (5, 6, 7):
                ok = persist_shm_to_storage(
                    ckpt_dir, step, node_rank=0, local_world_size=2,
                    expected_nodes=[0], commit_timeout=5.0, locks=locks,
                )
                persist_results.append(ok)
            stop.set()

        t = threading.Thread(target=persist_loop)
        t.start()
        # Race: keep advancing the segments while persists run.
        step = 6
        while not stop.is_set() and step <= 7:
            for lr in (0, 1):
                write_step(lr, step)
            step += 1
            time.sleep(0.01)
        t.join(timeout=30)
        assert not t.is_alive()

        committed_dirs = ckpt_storage.list_step_dirs(ckpt_dir)
        assert committed_dirs, "at least one persist must land"
        for s in committed_dirs:
            sdir = ckpt_storage.step_dir(ckpt_dir, s)
            for name in os.listdir(sdir):
                if not name.endswith(".raw"):
                    continue
                with RawShardReader(os.path.join(sdir, name)) as r:
                    assert r.step == s, (name, r.step, s)
                    arr = r.get("leaf0_shard0")
                    assert np.all(arr == float(s)), (
                        f"step dir {s} holds data of step {arr.flat[0]}"
                    )
    finally:
        for h in handlers:
            h.unlink()


# ---------------------------------------------------------------------------
# Retention + misc satellites
# ---------------------------------------------------------------------------


def test_shm_v1_layout_still_readable():
    """Images written by pre-step-field builds (magic DLRTPUC1, meta at
    byte 16) must still load, and get_step's fast path must not
    misparse them."""
    from multiprocessing import shared_memory

    from dlrover_tpu.flash_ckpt.shm_handler import (
        MAGIC_V1,
        SharedMemoryHandler,
    )

    arr = np.arange(8, dtype=np.float32)
    _, treedef = jax.tree_util.tree_flatten({"w": 0})
    meta = {
        "step": 12,
        "user_meta": {},
        "treedef": pickle.dumps(treedef),
        "leaves": [
            LeafMeta(
                0, (8,), "float32",
                [ShardMeta(((0, 8),), (8,), offset=0, nbytes=32)],
                replicated=True,
            )
        ],
        "data_start": 4096,
    }
    payload = pickle.dumps(meta)
    name = f"v1compat_{time.time_ns()}"
    shm = shared_memory.SharedMemory(name=name, create=True, size=4096 + 64)
    try:
        buf = shm.buf
        buf[8:16] = len(payload).to_bytes(8, "big")
        buf[16 : 16 + len(payload)] = payload  # v1: meta directly at 16
        view = np.ndarray((8,), np.float32, buffer=buf, offset=4096)
        view[:] = arr
        del view
        buf[:8] = MAGIC_V1
        h = SharedMemoryHandler(name)
        assert h.get_step() == 12
        step, state, _ = h.load_state_dict()
        assert step == 12
        np.testing.assert_array_equal(state["w"], arr)
        h.close()
    finally:
        shm.close()
        try:
            shm.unlink()
        except FileNotFoundError:
            pass


def test_keep_latest_zero_removes_uncommitted(tmp_path):
    root = str(tmp_path / "hist")
    for s in (10, 20, 30):
        os.makedirs(ckpt_storage.step_dir(root, s))
    ckpt_storage.write_tracker(root, 30)
    ckpt_storage.KeepLatestDeletionStrategy(max_to_keep=0).clean_up(root)
    kept = ckpt_storage.list_step_dirs(root)
    assert kept == [30]  # only the committed step survives


def test_elastic_trainer_restore_adopts_step(tmp_path):
    from dlrover_tpu.observability.flight_recorder import FlightRecorder
    from dlrover_tpu.trainer.elastic.trainer import (
        ElasticBatchConfig,
        ElasticTrainer,
    )

    ckpt_dir = str(tmp_path / "ckpt")
    ckpt = Checkpointer(ckpt_dir, standalone=True)
    try:
        ckpt.save_checkpoint(
            42, {"w": jnp.ones((8, 8))}, StorageType.DISK
        )
        recorder = FlightRecorder(capacity=16)
        trainer = ElasticTrainer(
            ElasticBatchConfig(global_batch_size=32,
                               micro_batch_per_device=4),
            dp_size=8,
            flight_recorder=recorder,
        )
        result = trainer.restore_checkpoint(ckpt)
        assert result is not None
        state, _ = result
        assert trainer.global_step == 42
        np.testing.assert_array_equal(
            np.asarray(state["w"]), np.ones((8, 8))
        )
        records = recorder.snapshot()["steps"]
        restores = [r for r in records if r.get("event") == "ckpt_restore"]
        assert restores and restores[0]["step"] == 42
        assert restores[0]["mb_per_s"] > 0
        # nothing restorable -> None, step untouched
        empty = ElasticTrainer(
            ElasticBatchConfig(global_batch_size=32,
                               micro_batch_per_device=4),
            dp_size=8,
        )
        ckpt2 = Checkpointer(str(tmp_path / "empty"), standalone=True)
        try:
            assert empty.restore_checkpoint(ckpt2) is None
            assert empty.global_step == 0
        finally:
            ckpt2._engine._shm.unlink()
            ckpt2.close()
    finally:
        ckpt._engine._shm.unlink()
        ckpt.close()
