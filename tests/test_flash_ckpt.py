"""Flash checkpoint tests: shm image, engine save/load, resharding restore,
commit protocol. (Reference test model: trainer/tests/torch fsdp_ckpt_test,
tests/test_ckpt_saver.py.)"""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu.flash_ckpt import storage as ckpt_storage
from dlrover_tpu.flash_ckpt.checkpointer import Checkpointer, StorageType
from dlrover_tpu.flash_ckpt.engine import to_device_state
from dlrover_tpu.flash_ckpt.saver import persist_shm_to_storage
from dlrover_tpu.flash_ckpt.shm_handler import SharedMemoryHandler
from dlrover_tpu.trainer import runtime


@pytest.fixture(autouse=True)
def fresh_runtime(monkeypatch, tmp_path):
    """Isolate shm/uds names and reset the runtime context per test."""
    runtime._context = None
    monkeypatch.setenv("DLROVER_TPU_JOB_NAME", f"t{os.getpid()}_{time.time_ns() % 100000}")
    monkeypatch.setenv("DLROVER_TPU_SHARED_DIR", str(tmp_path / "uds"))
    yield
    runtime._context = None


def _cleanup(ckpt: Checkpointer):
    ckpt._engine._shm.unlink()
    ckpt.close()


def test_shm_handler_roundtrip():
    h = SharedMemoryHandler(f"test_shm_{time.time_ns()}")
    state = {"w": np.arange(12, dtype=np.float32).reshape(3, 4), "step": np.int64(7)}
    h.save_state_dict(5, state, {"tag": "x"})
    step, loaded, meta = h.load_state_dict()
    assert step == 5
    assert meta["tag"] == "x"
    np.testing.assert_array_equal(loaded["w"], state["w"])
    assert loaded["step"] == 7
    # overwrite with a bigger state grows the segment
    big = {"w": np.ones((100, 100), dtype=np.float32)}
    h.save_state_dict(6, big)
    step, loaded, _ = h.load_state_dict()
    assert step == 6 and loaded["w"].shape == (100, 100)
    h.unlink()


def test_memory_save_load_roundtrip(tmp_path):
    ckpt = Checkpointer(str(tmp_path / "ckpt"), standalone=True)
    state = {
        "params": {"w": jnp.ones((8, 4)), "b": jnp.zeros((4,))},
        "opt": {"mu": jnp.full((8, 4), 0.5)},
    }
    block = ckpt.save_checkpoint(3, state)
    assert block < 5.0
    result = ckpt.load_checkpoint()
    assert result is not None
    step, restored, meta = result
    assert step == 3
    np.testing.assert_array_equal(
        np.asarray(restored["params"]["w"]), np.ones((8, 4))
    )
    _cleanup(ckpt)


def test_optax_state_roundtrip(tmp_path):
    """Custom pytree node types (optax NamedTuple optimizer states) must
    survive the restricted-unpickle restore path — a policy that only
    admits plain containers would make every real checkpoint
    save-but-never-restore."""
    import optax

    params = {"w": jnp.ones((8, 4)), "b": jnp.zeros((4,))}
    tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(1e-3))
    state = {"params": params, "opt_state": tx.init(params), "step": 11}
    ckpt = Checkpointer(str(tmp_path / "ckpt"), standalone=True)
    ckpt.save_checkpoint(11, state, storage_type=StorageType.DISK)
    # memory restore
    step, restored, _ = ckpt.load_checkpoint()
    assert step == 11
    chex_leaves = jax.tree_util.tree_leaves(restored["opt_state"])
    assert len(chex_leaves) == len(
        jax.tree_util.tree_leaves(state["opt_state"])
    )
    # storage restore (forces the on-disk meta/treedef path)
    ckpt._engine._shm.unlink()
    ckpt._engine._shm.close()
    step2, restored2, _ = ckpt.load_checkpoint()
    assert step2 == 11
    assert type(restored2["opt_state"]) is type(state["opt_state"])
    ckpt.close()


def test_restricted_unpickler_blocks_gadgets():
    import pickle

    from dlrover_tpu.common.serialize import loads, loads_pytree

    class Evil:
        def __reduce__(self):
            return (eval, ("1+1",))

    payload = pickle.dumps(Evil())
    for loader in (loads, loads_pytree):
        with pytest.raises(pickle.UnpicklingError):
            loader(payload)

    class EvilFnUnderAllowedPrefix:
        def __reduce__(self):
            import optax

            return (optax.adamw, (1e-3,))

    with pytest.raises(pickle.UnpicklingError):
        loads_pytree(pickle.dumps(EvilFnUnderAllowedPrefix()))


def test_disk_save_and_commit(tmp_path):
    ckpt_dir = str(tmp_path / "ckpt")
    ckpt = Checkpointer(ckpt_dir, standalone=True)
    state = {"w": jnp.arange(16.0).reshape(4, 4)}
    ckpt.save_checkpoint(10, state, StorageType.DISK)
    assert ckpt_storage.read_tracker(ckpt_dir) == 10
    # memory wiped (new process simulation): storage restore works
    ckpt._engine._shm.unlink()
    runtime._context = None
    ckpt2 = Checkpointer(ckpt_dir, standalone=True)
    step, restored, _ = ckpt2.load_checkpoint()
    assert step == 10
    np.testing.assert_array_equal(
        np.asarray(restored["w"]), np.arange(16.0).reshape(4, 4)
    )
    _cleanup(ckpt2)
    _cleanup(ckpt)


def test_sharded_state_memory_roundtrip(tmp_path):
    """FSDP-style sharded leaves survive the shm roundtrip on one process."""
    devices = jax.devices()
    mesh = Mesh(np.array(devices).reshape(8), ("fsdp",))
    sharding = NamedSharding(mesh, P("fsdp"))
    w = jax.device_put(jnp.arange(64.0).reshape(8, 8), sharding)
    state = {"w": w}
    ckpt = Checkpointer(str(tmp_path / "ckpt"), standalone=True)
    ckpt.save_checkpoint(1, state)
    step, restored, _ = ckpt.load_checkpoint(
        sharding_tree={"w": sharding}
    )
    assert step == 1
    np.testing.assert_array_equal(
        np.asarray(restored["w"]), np.arange(64.0).reshape(8, 8)
    )
    assert restored["w"].sharding == sharding
    _cleanup(ckpt)


def test_resharding_restore_from_storage(tmp_path):
    """Save under one sharding, restore under a different mesh layout —
    the reference needs DeepSpeed UCP conversion for this (training.py:1548);
    here shard metadata makes it direct."""
    ckpt_dir = str(tmp_path / "ckpt")
    devices = np.array(jax.devices())
    mesh1 = Mesh(devices.reshape(8), ("x",))
    s1 = NamedSharding(mesh1, P("x", None))
    w = jax.device_put(jnp.arange(64.0).reshape(8, 8), s1)
    ckpt = Checkpointer(ckpt_dir, standalone=True)
    ckpt.save_checkpoint(2, {"w": w}, StorageType.DISK)
    ckpt._engine._shm.unlink()
    runtime._context = None
    # new "world": 2x4 mesh, shard on second axis instead
    mesh2 = Mesh(devices.reshape(2, 4), ("a", "b"))
    s2 = NamedSharding(mesh2, P(None, "b"))
    ckpt2 = Checkpointer(ckpt_dir, standalone=True)
    step, restored, _ = ckpt2.load_checkpoint(sharding_tree={"w": s2})
    assert step == 2
    assert restored["w"].sharding == s2
    np.testing.assert_array_equal(
        np.asarray(restored["w"]), np.arange(64.0).reshape(8, 8)
    )
    _cleanup(ckpt2)
    _cleanup(ckpt)


def test_save_blocking_time_small_vs_state_size(tmp_path):
    """The blocking cost is a host memcpy, far below any disk write."""
    ckpt = Checkpointer(str(tmp_path / "ckpt"), standalone=True)
    state = {"w": jnp.ones((512, 512))}  # 1MB
    t0 = ckpt.save_checkpoint(1, state)
    t1 = ckpt.save_checkpoint(2, state)  # steady-state: no realloc
    assert t1 < 1.0
    _cleanup(ckpt)


def test_commit_protocol_multi_node(tmp_path, monkeypatch):
    """Leader only commits once all expected node markers exist."""
    ckpt_dir = str(tmp_path / "ckpt")
    ckpt = Checkpointer(ckpt_dir, standalone=True)
    ckpt.save_checkpoint(4, {"w": jnp.ones((4,))})
    # persist as node 0 of a 2-node world: commit must time out (node 1
    # never writes its marker)
    ok = persist_shm_to_storage(
        ckpt_dir, 4, node_rank=0, local_world_size=1,
        expected_nodes=[0, 1], commit_timeout=1.0,
    )
    assert not ok
    assert ckpt_storage.read_tracker(ckpt_dir) == -1
    # node 1's marker appears -> leader commit succeeds
    sdir = ckpt_storage.step_dir(ckpt_dir, 4)
    done = os.path.join(sdir, "._" + "dlrover_ckpt_done")
    ckpt_storage.persist_node_shards(ckpt_dir, 4, 1, {})
    ok = persist_shm_to_storage(
        ckpt_dir, 4, node_rank=0, local_world_size=1,
        expected_nodes=[0, 1], commit_timeout=5.0,
    )
    assert ok
    assert ckpt_storage.read_tracker(ckpt_dir) == 4
    _cleanup(ckpt)


def test_async_save_lands_and_overlaps(tmp_path):
    from dlrover_tpu.flash_ckpt.engine import CheckpointEngine

    engine = CheckpointEngine(str(tmp_path / "ackpt"), standalone=True)
    try:
        state = {"w": jnp.arange(1024, dtype=jnp.float32), "step": jnp.int32(3)}
        block = engine.save_to_memory_async(3, state)
        # The launch must be far cheaper than a synchronous device_get
        # of the same state (it only starts the DMA).
        assert block < 1.0
        assert engine.wait_async_save(timeout=30)
        loaded = engine.load()
        assert loaded is not None
        step, np_state, _ = loaded
        assert step == 3
        np.testing.assert_array_equal(
            np.asarray(np_state["w"]), np.arange(1024, dtype=np.float32)
        )
    finally:
        engine._shm.unlink()
        engine.close()


def test_async_save_coalesces_to_newest(tmp_path):
    from dlrover_tpu.flash_ckpt.engine import CheckpointEngine

    engine = CheckpointEngine(str(tmp_path / "ackpt2"), standalone=True)
    try:
        for step in (1, 2, 3):
            engine.save_to_memory_async(
                step, {"w": jnp.full((8,), float(step))}
            )
        assert engine.wait_async_save(timeout=30)
        step, np_state, _ = engine.load()
        # Intermediate snapshots may be dropped; the NEWEST must land.
        assert step == 3
        np.testing.assert_array_equal(
            np.asarray(np_state["w"]), np.full((8,), 3.0)
        )
    finally:
        engine._shm.unlink()
        engine.close()


def test_keep_step_interval_deletion(tmp_path):
    import os as _os

    from dlrover_tpu.flash_ckpt.storage import (
        KeepStepIntervalDeletionStrategy,
        step_dir,
        write_tracker,
    )

    root = str(tmp_path / "hist")
    for s in (10, 20, 25, 30, 35, 40, 45):
        _os.makedirs(step_dir(root, s))
    write_tracker(root, 45)
    KeepStepIntervalDeletionStrategy(keep_interval=20, max_to_keep=2).clean_up(
        root
    )
    kept = sorted(
        int(d.split("-")[-1])
        for d in _os.listdir(root)
        if d.startswith("checkpoint-")
    )
    # Multiples of 20 survive (20, 40), plus the 2 newest (40, 45).
    assert kept == [20, 40, 45]


def test_foreign_job_shm_image_rejected(tmp_path):
    from dlrover_tpu.flash_ckpt.engine import CheckpointEngine

    e1 = CheckpointEngine(str(tmp_path / "job_a"), standalone=True)
    try:
        e1.save_to_memory(9, {"w": jnp.ones((4,))})
        # Same shm namespace, different checkpoint dir: must not restore.
        e2 = CheckpointEngine(str(tmp_path / "job_b"), standalone=True)
        assert e2.load() is None
        # The rightful owner still restores.
        step, _, _ = e1.load()
        assert step == 9
    finally:
        e1._shm.unlink()
        e1.close()


def test_keep_interval_selected_by_env(monkeypatch):
    from dlrover_tpu.flash_ckpt.saver import default_deletion_strategy
    from dlrover_tpu.flash_ckpt.storage import (
        KeepLatestDeletionStrategy,
        KeepStepIntervalDeletionStrategy,
    )

    assert isinstance(
        default_deletion_strategy(), KeepLatestDeletionStrategy
    )
    monkeypatch.setenv("DLROVER_TPU_CKPT_KEEP_INTERVAL", "500")
    strategy = default_deletion_strategy()
    assert isinstance(strategy, KeepStepIntervalDeletionStrategy)
    assert strategy.keep_interval == 500


def test_autotune_interval_math():
    from dlrover_tpu.flash_ckpt.autotune import (
        expected_goodput_pct,
        optimal_save_interval_s,
    )

    # ~3ms block cost at 1h MTBF -> ~4.6s cadence.
    tau = optimal_save_interval_s(0.003, drain_s=0.5, mtbf_s=3600.0)
    assert 4.0 < tau < 6.0, tau
    # Costlier blocking saves push the cadence out (monotonic).
    assert optimal_save_interval_s(0.3, 0.5, 3600.0) > tau
    # The drain floor binds when transfers are slow.
    assert optimal_save_interval_s(0.003, drain_s=10.0) == 20.0
    # Bounds hold.
    assert optimal_save_interval_s(1e-9, 0.0) >= 2.0
    assert optimal_save_interval_s(1e9, 0.0) <= 600.0
    # The autotuned cadence beats the old 60s constant on goodput.
    g_auto = expected_goodput_pct(tau, 0.003, recovery_s=7.0)
    g_60 = expected_goodput_pct(60.0, 0.003, recovery_s=7.0)
    assert g_auto > g_60 > 95.0


def test_engine_recommends_interval_from_measured_saves(tmp_path):
    from dlrover_tpu.flash_ckpt.engine import CheckpointEngine

    engine = CheckpointEngine(str(tmp_path), standalone=True)
    try:
        assert engine.recommended_interval_s() is None
        state = {"w": jnp.arange(16.0)}
        engine.save_to_memory_async(1, state)
        assert engine.wait_async_save()
        rec = engine.recommended_interval_s()
        assert rec is not None and 2.0 <= rec <= 600.0
    finally:
        engine.close()


def test_async_writer_does_not_pollute_block_cost(tmp_path):
    """The writer thread's shm write is DRAIN (overlaps training); only
    the ~ms async launch may count as blocking cost, or Young/Daly
    recommends a ~100x sparser cadence than the engine earns."""
    from dlrover_tpu.flash_ckpt.engine import CheckpointEngine

    engine = CheckpointEngine(str(tmp_path), standalone=True)
    try:
        state = {"w": jnp.arange(1 << 16, dtype=jnp.float32)}
        for step in (1, 2, 3):
            engine.save_to_memory_async(step, state)
            assert engine.wait_async_save()
        block = engine.cost_tracker.block_s
        drain = engine.cost_tracker.drain_s
        assert block is not None and drain is not None
        # launch cost must be well under the full shm write
        assert block <= drain, (block, drain)
        assert block < 0.05, f"async launch recorded as {block}s"
    finally:
        engine.close()


def test_sharded_restore_counts_the_batched_branch():
    """A sharded restore on one host takes the batched device_put and
    says so: the per-leaf fallback only logs a warning, and
    chip_smoke.py fails the run if the count shows it ran."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from dlrover_tpu.flash_ckpt import engine as engine_lib

    mesh = Mesh(np.asarray(jax.devices()[:4]), ("dp",))
    tree = {"w": np.arange(32.0).reshape(8, 4), "step": np.int32(7)}
    shardings = {
        "w": NamedSharding(mesh, P("dp")), "step": NamedSharding(mesh, P()),
    }
    before = dict(engine_lib.RESTORE_BRANCH_COUNTS)
    state = to_device_state(tree, shardings)
    assert engine_lib.RESTORE_BRANCH_COUNTS == {
        "batched": before["batched"] + 1, "per_leaf": before["per_leaf"],
    }
    assert state["w"].sharding == shardings["w"]
    np.testing.assert_array_equal(np.asarray(state["w"]), tree["w"])
