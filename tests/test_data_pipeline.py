"""Pipelined elastic data path: batched task RPCs, shard-lease prefetch,
ring-buffer batch assembly, and exactly-once accounting under failure.

Covers the ISSUE-3 acceptance criteria: chaos (a worker dies holding
prefetched leases, every record index is accounted exactly once after
recovery) and a shard-checkpoint round trip taken mid-prefetch that
resumes without replaying reported-done shards.
"""

import threading
import time

import numpy as np
import pytest

from dlrover_tpu.common import comm
from dlrover_tpu.common.constants import NodeStatus, NodeType, TaskType
from dlrover_tpu.common.node import NodeGroupResource, NodeResource
from dlrover_tpu.master.shard.task_manager import (
    BatchDatasetManager,
    TaskManager,
)
from dlrover_tpu.master.shard.dataset_splitter import TableDatasetSplitter
from dlrover_tpu.trainer.elastic.dataloader import (
    ElasticDataLoader,
    PrefetchingDataLoader,
    device_put_prefetch,
)
from dlrover_tpu.trainer.elastic.sampler import ElasticDistributedSampler
from dlrover_tpu.trainer.elastic.sharding_client import (
    IndexShardingClient,
    ShardingClient,
)


def wait_until(predicate, timeout=5.0, interval=0.02):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return False


class DirectMasterClient:
    """MasterClient data-sharding surface served by an in-process
    TaskManager — no transport, exact RPC counting."""

    def __init__(self, task_manager: TaskManager, node_id: int = 0):
        self._tm = task_manager
        self._node_id = node_id
        self.rpcs = 0

    def report_dataset_shard_params(self, params: comm.DatasetShardParams):
        self.rpcs += 1
        self._tm.new_dataset(params)

    def get_task(self, dataset_name):
        self.rpcs += 1
        return self._tm.get_task(self._node_id, dataset_name)

    def get_tasks(self, dataset_name, count=1):
        self.rpcs += 1
        tasks = self._tm.get_tasks(self._node_id, dataset_name, count)
        wait = bool(tasks) and tasks[0].task_type == TaskType.WAIT
        return (
            [] if wait else [t for t in tasks if t.task_id >= 0]
        ), wait

    def report_task_done(self, dataset_name, task_id, success=True):
        self.rpcs += 1
        self._tm.report_task_done(
            dataset_name, task_id, self._node_id, success
        )

    def report_tasks_done_batch(self, dataset_name, done_ids, failed_ids=None):
        self.rpcs += 1
        self._tm.report_tasks_done(
            dataset_name, self._node_id, done_ids, failed_ids
        )
        return comm.BaseResponse(True)

    def get_shard_checkpoint(self, dataset_name):
        self.rpcs += 1
        return self._tm.get_shard_checkpoint(dataset_name)

    def restore_shard_checkpoint(self, dataset_name, checkpoint):
        self.rpcs += 1
        self._tm.restore_shard_checkpoint(dataset_name, checkpoint)


# ---- master-side batched dispatch ------------------------------------------


def test_get_tasks_batched_dispatch_and_sentinels():
    mgr = BatchDatasetManager(
        "training", TableDatasetSplitter("ds", 100, 10)
    )
    tasks = mgr.get_tasks(node_id=0, count=4)
    assert [t.task_id for t in tasks] == [0, 1, 2, 3]
    rest = mgr.get_tasks(node_id=0, count=100)
    assert len(rest) == 6  # only what exists
    # Everything leased: a further batched fetch gets ONE WAIT sentinel.
    waiting = mgr.get_tasks(node_id=1, count=8)
    assert len(waiting) == 1 and waiting[0].task_type == TaskType.WAIT
    for t in tasks + rest:
        assert mgr.report_task_done(t.task_id, 0)
    done = mgr.get_tasks(node_id=1, count=8)
    assert len(done) == 1 and done[0].task_id < 0
    assert done[0].task_type != TaskType.WAIT
    assert mgr.completed()


def test_todo_is_deque_and_recovery_requeues_at_head():
    from collections import deque

    mgr = BatchDatasetManager(
        "training", TableDatasetSplitter("ds", 40, 10)
    )
    assert isinstance(mgr.todo, deque)
    first = mgr.get_task(node_id=7)
    second = mgr.get_task(node_id=8)
    # Node 7 dies: its shard goes back to the HEAD of the queue, ahead
    # of never-dispatched shards.
    mgr.recover_node_tasks(7)
    redispatched = mgr.get_task(node_id=8)
    assert redispatched.shard.start == first.shard.start
    assert second.task_id != redispatched.task_id


def test_task_manager_batched_report():
    tm = TaskManager()
    tm.new_dataset(
        comm.DatasetShardParams(
            dataset_name="batch-ds", dataset_size=30, shard_size=10
        )
    )
    tasks = tm.get_tasks(0, "batch-ds", 3)
    assert len(tasks) == 3
    tm.report_tasks_done(
        "batch-ds", 0, [tasks[0].task_id, tasks[1].task_id],
        [tasks[2].task_id],
    )
    mgr = tm.get_dataset("batch-ds")
    # Two completed; the failed one is back in todo.
    assert len(mgr.todo) == 1 and not mgr.doing
    assert mgr.todo[0].shard.start == tasks[2].start


# ---- client: prefetch + coalesced reports ----------------------------------


def test_prefetching_client_consumes_all_exactly_once():
    tm = TaskManager()
    client = DirectMasterClient(tm)
    isc = IndexShardingClient(
        client, "pf-ds", dataset_size=100, shard_size=7
    )
    seen = list(isc)
    assert sorted(seen) == list(range(100))
    assert tm.finished()
    # Strictly fewer control RPCs than the 2-per-shard sync path.
    assert client.rpcs < 2 * 15


def test_empty_shard_skipped_and_reported():
    """An empty shard must neither end iteration nor rot in ``doing``."""

    class ScriptedClient:
        def __init__(self):
            self.done = []

        def report_dataset_shard_params(self, params):
            pass

        def get_tasks(self, name, count=1):
            out = []
            while self._tasks and len(out) < count:
                out.append(self._tasks.pop(0))
            return out, False

        def report_task_done(self, name, task_id, success=True):
            self.done.append(task_id)

        def report_tasks_done_batch(self, name, done_ids, failed_ids=None):
            self.done.extend(done_ids)
            return comm.BaseResponse(True)

    for prefetch_depth in (0, 4):  # sync and pipelined paths
        client = ScriptedClient()
        client._tasks = [
            comm.ShardTask(task_id=0, task_type="training", start=0, end=3),
            comm.ShardTask(task_id=1, task_type="training", start=5, end=5),
            comm.ShardTask(task_id=2, task_type="training", start=3, end=6),
        ]
        isc = IndexShardingClient(
            client, "empty-ds", dataset_size=6, shard_size=3,
            prefetch_depth=prefetch_depth, report_batch=1,
        )
        assert sorted(isc) == [0, 1, 2, 3, 4, 5]
        assert wait_until(lambda: sorted(client.done) == [0, 1, 2])


def test_reports_coalesced_and_flushed_on_count():
    tm = TaskManager()
    client = DirectMasterClient(tm)
    sc = ShardingClient(
        client, "co-ds", dataset_size=40, shard_size=10,
        report_batch=4, report_interval_s=3600.0,
        wait_flush_age_s=3600.0,  # only the count flush may fire
    )
    tasks = [sc.fetch_task() for _ in range(4)]
    assert all(t is not None for t in tasks)
    for t in tasks[:3]:
        sc.report_task_done(t)
    mgr = tm.get_dataset("co-ds")
    assert len(mgr.doing) == 4  # below count threshold: nothing sent
    sc.report_task_done(tasks[3])  # 4th report trips the batch flush
    assert wait_until(lambda: len(mgr.doing) == 0)
    assert tm.finished()
    sc.stop()


def test_shard_checkpoint_mid_prefetch_no_replay_no_loss():
    """Shard checkpoint taken while the prefetcher is live: pending done
    reports are force-flushed first, so the checkpoint holds exactly the
    unconsumed shards — restore replays nothing and loses nothing."""
    tm = TaskManager()
    client = DirectMasterClient(tm)
    isc = IndexShardingClient(
        client, "ck-ds", dataset_size=60, shard_size=10,
        report_batch=64, report_interval_s=3600.0,  # only forced flushes
    )
    consumed = [isc.fetch_record_index() for _ in range(20)]
    assert sorted(consumed) == list(range(20))
    mgr = tm.get_dataset("ck-ds")
    # Nothing flushed yet: the two finished shards still sit in doing.
    assert len(mgr.doing) >= 2
    ckpt = isc.get_shard_checkpoint()  # forces the flush
    assert mgr._completed_count == 2
    import json

    undone = json.loads(ckpt)["undone_shards"]
    starts = sorted(s[0] for s in undone)
    assert starts == [20, 30, 40, 50]  # done shards NOT in the ckpt
    isc.kill()  # crash: prefetched leases die with the worker

    # Restart: fresh master, fresh worker, restore the checkpoint.
    tm2 = TaskManager()
    client2 = DirectMasterClient(tm2, node_id=1)
    isc2 = IndexShardingClient(
        client2, "ck-ds", dataset_size=60, shard_size=10
    )
    isc2.restore_shard_checkpoint(ckpt)
    resumed = sorted(isc2)
    assert resumed == list(range(20, 60))  # no replay, no loss
    assert tm2.finished()


# ---- chaos: worker death with prefetched leases ----------------------------


def test_chaos_kill_worker_holding_prefetched_leases():
    """Sim-cluster chaos: a worker dies holding prefetched shard leases.
    TaskRescheduleCallback re-queues them; the union of the dead
    worker's REPORTED shards and the survivor's consumption covers every
    record index exactly once."""
    from dlrover_tpu.master.node.dist_job_manager import (
        DistributedJobManager,
    )
    from dlrover_tpu.master.node.event_callback import (
        TaskRescheduleCallback,
    )
    from dlrover_tpu.master.node.job_context import JobContext
    from dlrover_tpu.testing.sim_cluster import (
        SimCluster,
        SimNodeWatcher,
        SimScaler,
    )

    JobContext.reset_singleton()
    tm = TaskManager()
    cluster = SimCluster()
    mgr = DistributedJobManager(
        job_name="chaos-job",
        node_groups={
            NodeType.WORKER: NodeGroupResource(
                count=2, node_resource=NodeResource(tpu_chips=4)
            )
        },
        scaler=SimScaler("chaos-job", cluster),
        watcher=SimNodeWatcher("chaos-job", cluster),
    )
    mgr.add_node_event_callback(TaskRescheduleCallback(tm))
    try:
        mgr.start()
        assert wait_until(
            lambda: sum(
                n.status == NodeStatus.RUNNING
                for n in mgr.worker_manager.nodes.values()
            )
            == 2
        )
        nodes = sorted(mgr.worker_manager.nodes)
        victim_id, survivor_id = nodes[0], nodes[1]

        total = 120
        # Victim: prefetches aggressively, reports every done shard
        # immediately (report_batch=1) so "reported" is unambiguous,
        # consumes two full shards, then dies.
        vc = DirectMasterClient(tm, node_id=victim_id)
        victim = IndexShardingClient(
            vc, "chaos-ds", dataset_size=total, shard_size=10,
            prefetch_depth=16, fetch_batch=8, report_batch=1,
        )
        committed = [victim.fetch_record_index() for _ in range(20)]
        dmgr = tm.get_dataset("chaos-ds")
        assert wait_until(lambda: dmgr._completed_count == 2)
        # The prefetcher leased shards beyond the two consumed: the
        # chaos point of the test.
        assert len(dmgr.doing) > 0
        victim.kill()
        cluster.fail_node(victim_id)
        # Node-death recovery re-queues every lease the victim held.
        assert wait_until(lambda: len(dmgr.doing) == 0)

        sc = DirectMasterClient(tm, node_id=survivor_id)
        survivor = IndexShardingClient(
            sc, "chaos-ds", dataset_size=total, shard_size=10
        )
        rest = list(survivor)
        everything = sorted(committed + rest)
        assert everything == list(range(total))  # exactly once
        assert tm.finished()
    finally:
        mgr.stop()
        JobContext.reset_singleton()


# ---- prefetching dataloader -------------------------------------------------


def _record_table(n=64, width=3):
    data = np.arange(n * width, dtype=np.int32).reshape(n, width)
    return data, lambda i: {"x": data[i]}


def test_prefetching_loader_matches_sync_loader():
    data, fetch = _record_table()
    sync = ElasticDataLoader(
        fetch,
        ElasticDistributedSampler(64, 0, 2, shuffle=False),
        per_host_batch_size=4,
    )
    pipe = PrefetchingDataLoader(
        fetch,
        ElasticDistributedSampler(64, 0, 2, shuffle=False),
        per_host_batch_size=4,
        depth=2,
    )
    expect = [b["x"].copy() for b in sync]
    # Ring buffers are reused: anything kept across iterations must be
    # copied (the documented ownership rule).
    got = [b["x"].copy() for b in pipe]
    assert len(got) == len(expect) == 8
    for e, g in zip(expect, got):
        np.testing.assert_array_equal(e, g)


def test_prefetching_loader_reuses_ring_buffers():
    _, fetch = _record_table(64)
    loader = PrefetchingDataLoader(
        fetch, iter(range(64)), per_host_batch_size=4, depth=2
    )
    ids = [id(b["x"]) for b in loader]
    assert len(ids) == 16
    assert len(set(ids)) <= loader.depth + 1  # ring, not fresh allocs


def test_prefetching_loader_advances_cursor_on_yield():
    _, fetch = _record_table(64)
    sampler = ElasticDistributedSampler(64, 0, 1, shuffle=False)
    loader = PrefetchingDataLoader(
        fetch, sampler, per_host_batch_size=8, sampler=sampler, depth=2
    )
    it = iter(loader)
    next(it)
    # Exactly one batch was HANDED OVER; assembled-but-queued batches in
    # the ring must not advance the resume cursor.
    assert sampler.state_dict()["completed"] == 8
    consumed = 1
    for _ in it:
        consumed += 1
    assert consumed == 8
    assert sampler.state_dict()["completed"] == 64


def test_prefetching_loader_drops_trailing_partial_batch():
    _, fetch = _record_table(10)
    loader = PrefetchingDataLoader(
        fetch, iter(range(10)), per_host_batch_size=4
    )
    assert len(list(loader)) == 2


def test_prefetching_loader_with_sharding_client():
    tm = TaskManager()
    client = DirectMasterClient(tm)
    isc = IndexShardingClient(
        client, "dl-ds", dataset_size=48, shard_size=6
    )
    data, fetch = _record_table(48)
    loader = PrefetchingDataLoader(fetch, isc, per_host_batch_size=8)
    rows = np.concatenate([b["x"].copy() for b in loader])
    np.testing.assert_array_equal(
        np.sort(rows[:, 0]), data[:, 0]
    )
    assert tm.finished()


def test_device_put_prefetch_double_buffering():
    import jax

    _, fetch = _record_table(32)
    loader = PrefetchingDataLoader(
        fetch, iter(range(32)), per_host_batch_size=4, depth=2
    )
    batches = list(device_put_prefetch(loader))
    assert len(batches) == 8
    flat = np.concatenate([np.asarray(b["x"])[:, 0] for b in batches])
    # Device copies must hold the right rows even though the host ring
    # buffers were recycled underneath them.
    np.testing.assert_array_equal(np.sort(flat), np.arange(32) * 3)
    assert all(
        isinstance(b["x"], jax.Array) for b in batches
    )


def test_prefetching_loader_propagates_fetch_errors():
    def bad_fetch(i):
        if i == 5:
            raise ValueError("poisoned record")
        return {"x": np.zeros(2, np.float32)}

    loader = PrefetchingDataLoader(
        bad_fetch, iter(range(8)), per_host_batch_size=2
    )
    with pytest.raises(ValueError, match="poisoned record"):
        list(loader)


def test_stop_unblocks_training_thread_in_fetch_task():
    """stop()/kill() from another thread must wake a consumer blocked on
    the empty prefetch queue instead of hanging it forever."""
    tm = TaskManager()
    client = DirectMasterClient(tm, node_id=0)
    # Another worker leases everything: our queue stays empty (WAIT).
    hog = ShardingClient(
        DirectMasterClient(tm, node_id=9), "hang-ds",
        dataset_size=20, shard_size=10, prefetch_depth=0,
    )
    assert hog.fetch_task() is not None and hog.fetch_task() is not None
    sc = ShardingClient(client, "hang-ds", dataset_size=20, shard_size=10)
    result = {}

    def blocked_fetch():
        result["task"] = sc.fetch_task()

    t = threading.Thread(target=blocked_fetch, daemon=True)
    t.start()
    time.sleep(0.3)
    assert t.is_alive()
    sc.kill()
    t.join(timeout=5)
    assert not t.is_alive()
    assert result["task"] is None


def test_loader_stop_unblocks_consumer():
    def stuck_source():
        yield from range(4)
        while True:  # index source wedged (e.g. master unreachable)
            time.sleep(0.05)

    loader = PrefetchingDataLoader(
        lambda i: {"x": np.zeros(2, np.float32)},
        stuck_source(),
        per_host_batch_size=4,
        depth=2,
    )
    got = []

    def consume():
        for b in loader:
            got.append(b["x"].copy())

    t = threading.Thread(target=consume, daemon=True)
    t.start()
    time.sleep(0.3)
    assert t.is_alive()  # one batch delivered, then blocked on the next
    loader.stop()
    t.join(timeout=5)
    assert not t.is_alive()
    assert len(got) == 1


# ---- transport keep-alive ---------------------------------------------------


def test_http_stub_reuses_connection():
    from dlrover_tpu.common.comm import Message
    from dlrover_tpu.rpc.transport import (
        HttpMasterServer,
        HttpMasterStub,
        MasterService,
    )

    class Echo(MasterService):
        def get(self, message):
            return message

        def report(self, message):
            return message

    import http.client as http_client

    server = HttpMasterServer(0, Echo())
    server.start()
    try:
        stub = HttpMasterStub(f"localhost:{server.port}")
        stub.get(Message(node_id=1))
        conn1 = stub._local.conn
        sock1 = conn1.sock
        stub.get(Message(node_id=2))
        # Keep-alive: same connection AND same TCP socket (HTTP/1.1 —
        # under 1.0 the server would close after every response).
        assert stub._local.conn is conn1
        assert conn1.sock is sock1
        # An idled-out keep-alive socket (server closed it without a
        # response) is retried once on a fresh connection.
        class StaleConn:
            def request(self, *a, **k):
                raise http_client.RemoteDisconnected("idle timeout")

            def close(self):
                pass

        stub._local.conn = StaleConn()
        resp = stub.get(Message(node_id=3))
        assert resp.node_id == 3
        assert not isinstance(stub._local.conn, StaleConn)
        stub.close()
    finally:
        server.stop()
