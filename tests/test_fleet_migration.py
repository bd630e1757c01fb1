"""Disaggregated prefill/decode serving (§36): two-phase router
dispatch, migration fallbacks (exactly-once under every failure),
live drain, the affinity-LRU purge, and thread-fleet token-exactness
through a real migration.

Policy-level tests run against FAKE replicas under an injected clock
(the test_fleet posture); the two integration tests at the bottom
drive real ThreadReplicas over paged engines.
"""

import time

import numpy as np
import pytest

from dlrover_tpu.observability.registry import MetricsRegistry
from dlrover_tpu.serving.fleet import (
    FleetRouter,
    HealthPolicy,
    ReplicaDeadError,
    RouterConfig,
)

pytestmark = pytest.mark.fleet


class FakeClock:
    def __init__(self, t: float = 100.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


class FakeReplica:
    """Mailbox double with the §36 control surface: ``send`` records
    ops and (optionally) auto-answers export/import, so a live drain
    can complete inside drain_replica's internal pump."""

    mode = "fake"

    def __init__(self, replica_id, clock, role="mixed",
                 auto_migrate=False, auto_import_ok=True):
        self.replica_id = str(replica_id)
        self.role = role
        self._clock = clock
        self.inbox = []
        self.outbox = []
        self.ops = []
        self.generation = 0
        self.is_alive = True
        self.beating = True
        self.auto_migrate = auto_migrate
        self.auto_import_ok = auto_import_ok

    def start(self):
        self.is_alive = True

    def wait_ready(self, timeout=0.0):
        return True

    def alive(self):
        return self.is_alive

    def kill(self):
        self.is_alive = False
        self.beating = False

    def stop(self):
        self.is_alive = False

    def restart(self):
        self.generation += 1
        self.inbox = []
        self.is_alive = True
        self.beating = True

    def submit(self, item):
        if not self.is_alive:
            raise ReplicaDeadError(f"fake {self.replica_id} dead")
        self.inbox.append(item)

    def send(self, payload):
        if not self.is_alive:
            raise ReplicaDeadError(f"fake {self.replica_id} dead")
        self.ops.append(payload)
        if not self.auto_migrate:
            return
        op = payload.get("op")
        if op == "export":
            self.outbox.append({
                "kind": "exported",
                "request_id": payload["request_id"],
                "attempt": payload["attempt"],
                "payload": "QUJD",
                "generation": self.generation,
            })
        elif op == "import":
            event = {
                "kind": "imported",
                "request_id": payload["request_id"],
                "attempt": payload["attempt"],
                "ok": self.auto_import_ok,
                "generation": self.generation,
            }
            if not self.auto_import_ok:
                event["reason"] = "MigrationRefused"
            self.outbox.append(event)

    def poll(self):
        out, self.outbox = self.outbox, []
        return out

    def last_heartbeat(self):
        return self._clock() if self.beating else 0.0

    # -- test helpers --------------------------------------------------------

    def take(self):
        assert self.inbox, f"replica {self.replica_id} has no work"
        return self.inbox.pop(0)

    def export(self, item, payload="QUJD"):
        self.outbox.append({
            "kind": "exported", "request_id": item.request_id,
            "attempt": item.attempt, "payload": payload,
            "generation": self.generation,
        })

    def export_failed(self, item):
        self.outbox.append({
            "kind": "exported", "request_id": item.request_id,
            "attempt": item.attempt, "error": "MigrationError",
            "generation": self.generation,
        })

    def imported(self, item, ok=True, reason="MigrationRefused"):
        event = {
            "kind": "imported", "request_id": item.request_id,
            "attempt": item.attempt, "ok": ok,
            "generation": self.generation,
        }
        if not ok:
            event["reason"] = reason
        self.outbox.append(event)

    def complete(self, item, tokens=(1, 2), ttft_s=0.001):
        self.outbox.append({
            "kind": "done", "request_id": item.request_id,
            "attempt": item.attempt, "ok": True,
            "tokens": list(tokens), "truncated": False,
            "failure_reason": "", "ttft_s": ttft_s,
            "generation": self.generation,
        })

    def op_kinds(self):
        return [o.get("op") for o in self.ops]


def _router(roles=("prefill", "decode"), clock=None, **cfg_kw):
    clock = clock or FakeClock()
    cfg_kw.setdefault("retry_backoff_s", 0.1)
    cfg_kw.setdefault("retry_jitter_frac", 0.0)
    cfg_kw.setdefault("auto_restart", False)
    cfg_kw.setdefault(
        "health",
        HealthPolicy(heartbeat_timeout_s=5.0, probe_cooldown_s=1.0,
                     probe_successes=1),
    )
    reps = [
        FakeReplica(i, clock, role=role) for i, role in enumerate(roles)
    ]
    router = FleetRouter(
        reps, RouterConfig(**cfg_kw), clock=clock,
        registry=MetricsRegistry(),
    )
    router.start()
    return router, reps, clock


def test_two_phase_dispatch_migrates_and_releases():
    """submit -> prefill replica (flagged) -> exported -> import op to
    the decode replica -> ack moves the ledger, releases the source,
    counts the migration + pause -> completion arrives from decode."""
    router, (pre, dec), clock = _router()
    req = router.submit(list(range(20)), 8)
    router.step()
    item = pre.take()
    assert item.migrate_after_prefill
    assert not dec.inbox                  # decode role takes no prompts
    pre.export(item)
    router.step()
    imp = dec.ops[-1]
    assert imp["op"] == "import" and imp["payload"] == "QUJD"
    assert imp["request_id"] == req.request_id
    dec.imported(item, ok=True)
    clock.advance(0.01)
    router.step()
    assert any(o["op"] == "release" for o in pre.ops)
    assert router.metrics.migrations.value() == 1
    assert router.metrics.migration_pause.count() == 1
    dec.complete(item, tokens=(7,) * 8)
    router.step()
    assert req.result.ok
    assert req.result.replica_id == dec.replica_id
    assert req.result.retries == 0


def test_import_refused_source_completes():
    """A refused import is a fallback, not a failure: no release, no
    breaker strike, the source's completion wins."""
    router, (pre, dec), clock = _router()
    req = router.submit(list(range(20)), 8)
    router.step()
    item = pre.take()
    pre.export(item)
    router.step()
    dec.imported(item, ok=False)
    router.step()
    assert not any(o["op"] == "release" for o in pre.ops)
    assert router.metrics.migrations.value() == 0
    assert router.metrics.migration_failures.value(
        reason="MigrationRefused"
    ) == 1
    assert router.health_state(dec.replica_id) == "healthy"
    pre.complete(item, tokens=(5,) * 8)
    router.step()
    assert req.result.ok and req.result.replica_id == pre.replica_id


def test_no_destination_source_completes():
    router, (pre, dec), clock = _router()
    req = router.submit(list(range(20)), 8)
    router.step()
    item = pre.take()
    dec.kill()
    pre.export(item)
    router.step()
    assert router.metrics.migration_failures.value(
        reason="no_destination"
    ) == 1
    assert not dec.ops
    pre.complete(item)
    router.step()
    assert req.result.ok and req.result.replica_id == pre.replica_id


def test_export_failure_counted_source_completes():
    """A source that cannot serialize (flat engine) reports an error
    event: counted, and the request just completes co-located."""
    router, (pre, dec), clock = _router()
    req = router.submit(list(range(20)), 8)
    router.step()
    item = pre.take()
    pre.export_failed(item)
    router.step()
    assert not dec.ops
    assert router.metrics.migration_failures.value(
        reason="export_failed"
    ) == 1
    pre.complete(item)
    router.step()
    assert req.result.ok


def test_migration_ack_timeout_pruned_source_completes():
    """Destination SIGKILLed between export and ack: the migration is
    forgotten after migration_timeout_s; the source — never released —
    completes the request. Exactly one result."""
    router, (pre, dec), clock = _router(migration_timeout_s=5.0)
    req = router.submit(list(range(20)), 8)
    router.step()
    item = pre.take()
    pre.export(item)
    router.step()
    assert dec.ops and dec.ops[-1]["op"] == "import"
    dec.kill()                            # ack never comes
    clock.advance(6.0)
    router.step()
    assert router.metrics.migration_failures.value(reason="timeout") == 1
    pre.complete(item, tokens=(3,) * 8)
    router.step()
    assert req.result.ok and req.result.replica_id == pre.replica_id
    assert router.metrics.migrations.value() == 0


def test_destination_death_after_ack_reprefills_once():
    """After the ack the decode replica owns the attempt; its death is
    the ordinary crash-re-route — ONE from-scratch re-prefill, one
    result."""
    router, (pre, dec), clock = _router()
    req = router.submit(list(range(20)), 8)
    router.step()
    item = pre.take()
    pre.export(item)
    router.step()
    dec.imported(item, ok=True)
    router.step()                         # ledger moved to dec
    assert router.metrics.migrations.value() == 1
    dec.kill()
    router.step()                         # reclaim + immediate requeue
    item2 = pre.take()
    assert item2.attempt == 1
    assert not item2.migrate_after_prefill  # no decode peer alive
    pre.complete(item2, tokens=(4,) * 8)
    router.step()
    assert req.result.ok and req.result.retries == 1
    assert router.metrics.reroutes.value() == 1


def test_decode_role_excluded_until_no_other_choice():
    """Fresh prompts never land on a dedicated decode replica while a
    prefill-capable one lives — but availability beats role purity
    when every prefill-capable replica is down."""
    router, (pre, dec), clock = _router()
    router.submit(list(range(4)), 4)
    router.step()
    assert pre.inbox and not dec.inbox
    pre.complete(pre.take())
    router.step()
    pre.kill()
    router.step()
    req2 = router.submit(list(range(30, 40)), 4)
    router.step()
    item = dec.take()                     # last resort: decode serves
    assert not item.migrate_after_prefill
    dec.complete(item)
    router.step()
    assert req2.result.ok


def test_affinity_purged_on_drain_and_crash_reclaim():
    """Regression (§36 satellite): the prefix-affinity LRU must drop
    entries pointing at a drained or crash-reclaimed replica eagerly,
    not leave them to lapse lazily on lookup."""
    router, (a, b, c), clock = _router(roles=("mixed", "mixed", "mixed"))
    prompt = list(range(20))
    req = router.submit(prompt, 4)
    router.step()
    src = next(r for r in (a, b, c) if r.inbox)
    assert router._affinity[req.prefix_key] == src.replica_id
    src.complete(src.take())
    router.step()
    # Drain: the entry must vanish with the replica.
    router.drain_replica(src.replica_id, migrate=False)
    assert src.replica_id not in router._affinity.values()
    # Crash reclaim: in-flight ledger + dead replica -> purge too.
    others = [r for r in (a, b, c) if r is not src]
    req2 = router.submit(list(range(50, 70)), 4)
    router.step()
    victim = next(r for r in others if r.inbox)
    assert router._affinity[req2.prefix_key] == victim.replica_id
    victim.kill()
    router.step()
    assert victim.replica_id not in router._affinity.values()


def test_live_drain_migrates_inflight_decodes():
    """drain_replica moves in-flight work off the victim through the
    migration path (auto-answering fakes): no retry is charged, the
    ledger entry lands on the survivor, and the drained replica's
    affinity entries are gone."""
    clock = FakeClock()
    reps = [
        FakeReplica(0, clock, role="mixed", auto_migrate=True),
        FakeReplica(1, clock, role="mixed", auto_migrate=True),
    ]
    router = FleetRouter(
        reps,
        RouterConfig(
            retry_jitter_frac=0.0, auto_restart=False,
            health=HealthPolicy(heartbeat_timeout_s=5.0),
        ),
        clock=clock, registry=MetricsRegistry(),
    )
    router.start()
    req = router.submit(list(range(20)), 8)
    router.step()
    src = next(r for r in reps if r.inbox)
    dst = next(r for r in reps if r is not src)
    item = src.take()
    assert router.drain_replica(src.replica_id)
    assert any(o["op"] == "import" for o in dst.ops)
    assert any(o["op"] == "release" for o in src.ops)
    assert router.metrics.migrations.value() == 1
    assert src.replica_id not in router.replica_ids()
    dst.complete(item, tokens=(2,) * 8)
    router.step()
    assert req.result.ok and req.result.retries == 0
    assert req.result.replica_id == dst.replica_id


# ---------------------------------------------------------------------------
# Thread-fleet integration: real paged engines, real migrations
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    import jax

    from dlrover_tpu.models import llama

    cfg = llama.tiny_config()
    params, _ = llama.init_params(cfg, jax.random.key(0))
    return cfg, params


def _paged_factory(tiny, slots=4, max_len=48, **kw):
    from dlrover_tpu.serving.kvpool import PagedServingEngine

    cfg, params = tiny

    def factory():
        # Enough slots that a burst of concurrent migrations is never
        # refused for want of a destination slot.
        eng = PagedServingEngine(
            cfg, params, slots=slots, max_len=max_len, prefill_chunk=4,
            block_size=8, **kw,
        )
        eng.warmup()
        return eng

    return factory


def _reference_tokens(tiny, prompts, max_new, max_len=48):
    from dlrover_tpu.serving.kvpool import PagedServingEngine

    cfg, params = tiny
    eng = PagedServingEngine(
        cfg, params, slots=2, max_len=max_len, prefill_chunk=4,
        block_size=8,
    )
    eng.warmup()
    out = []
    for p in prompts:
        req = eng.submit(np.asarray(p, np.int32), max_new)
        eng.run_until_idle()
        out.append(list(req.tokens))
    return out


def test_thread_fleet_two_phase_token_exact(tiny):
    """A real prefill->decode fleet: every request migrates after
    prefill, finishes on the decode replica, and its greedy tokens
    match an unmigrated single-engine run exactly."""
    from dlrover_tpu.serving.fleet import ThreadReplica

    cfg, _ = tiny
    prompts = [
        np.random.RandomState(s).randint(
            0, cfg.vocab_size, 9
        ).tolist()
        for s in (1, 2, 3)
    ]
    expected = _reference_tokens(tiny, prompts, 24)
    router = FleetRouter(
        [
            ThreadReplica("p0", _paged_factory(tiny), role="prefill"),
            ThreadReplica("d0", _paged_factory(tiny), role="decode"),
        ],
        RouterConfig(),
        registry=MetricsRegistry(),
    )
    router.start()
    try:
        reqs = [router.submit(p, 24) for p in prompts]
        router.run_until_idle(timeout_s=120.0)
        for req, want in zip(reqs, expected):
            assert req.result.ok, req.result
            assert req.result.tokens == want
            assert req.result.retries == 0
        assert router.metrics.migrations.value() == len(prompts)
    finally:
        router.stop()


def test_thread_fleet_live_drain_token_exact(tiny):
    """Draining a mixed replica mid-decode migrates its in-flight
    request out: the result keeps the already-sampled tokens (greedy
    sequence identical to an undrained run) and charges no retry."""
    from dlrover_tpu.serving.fleet import ThreadReplica

    cfg, _ = tiny
    prompts = [
        np.random.RandomState(s).randint(
            0, cfg.vocab_size, 9
        ).tolist()
        for s in (7, 8)
    ]
    # Long decodes (hundreds of ms on the CPU): the drain below lands
    # mid-decode however fast the machine is. At 24 tokens a request
    # could finish inside the 50 ms settle sleep, leaving nothing to
    # migrate.
    new_tokens, max_len = 400, 416
    expected = _reference_tokens(tiny, prompts, new_tokens, max_len)
    router = FleetRouter(
        [
            ThreadReplica(
                "m0", _paged_factory(tiny, max_len=max_len), role="mixed"
            ),
            ThreadReplica(
                "m1", _paged_factory(tiny, max_len=max_len), role="mixed"
            ),
        ],
        RouterConfig(),
        registry=MetricsRegistry(),
    )
    router.start()
    try:
        reqs = [router.submit(p, new_tokens) for p in prompts]
        # Let both replicas admit and start decoding.
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            router.step()
            if all(len(led) for led in router._ledger.values()):
                break
            time.sleep(0.005)
        time.sleep(0.05)
        router.step()
        router.drain_replica("m0")
        router.run_until_idle(timeout_s=120.0)
        for req, want in zip(reqs, expected):
            assert req.result.ok, req.result
            assert req.result.tokens == want
            assert req.result.retries == 0, (
                "live drain must migrate, not requeue-from-zero"
            )
        assert router.metrics.migrations.value() >= 1
        assert router.replica_ids() == ["m1"]
    finally:
        router.stop()


# ---------------------------------------------------------------------------
# Chaos episode 6: kill_during_migration
# ---------------------------------------------------------------------------


def test_kill_during_migration_plan_deterministic():
    """Episode 6 is registered, its plan is seed-reproducible, and the
    schedule SIGKILLs the DESTINATION decode replica inside the
    export→import-ack window (the ``fleet.replica.import`` point)."""
    from dlrover_tpu.testing.fleet_soak import build_migration_schedules
    from dlrover_tpu.testing.soak import EPISODE_KINDS, build_episode_plan

    assert EPISODE_KINDS[6] == "kill_during_migration"
    plan = build_episode_plan(0, 6)
    assert plan.kind == "kill_during_migration"
    sched = build_migration_schedules(0, 6)
    again = build_migration_schedules(0, 6)
    assert set(sched) == {"1"}  # the decode tier of the 2-replica split
    rule = sched["1"].rules[0]
    assert rule.point == "fleet.replica.import"
    assert rule.action == "crash"
    assert rule.nth == again["1"].rules[0].nth  # seeded, not random


@pytest.mark.soak
@pytest.mark.slow
def test_kill_during_migration_episode(tmp_path):
    """Chaos soak episode 6 end-to-end: the destination replica is
    SIGKILLed holding an unacked KV import. The orphaned migration is
    accounted as a failure (never a silent loss), the request finishes
    on its never-released source exactly once, block conservation
    holds through the kill, and a migration succeeds post-restart —
    the decode tier's breaker is probed by migration traffic."""
    from dlrover_tpu.testing.fleet_soak import (
        FleetSoakConfig,
        run_migration_episode,
    )
    from dlrover_tpu.testing.soak import build_episode_plan

    plan = build_episode_plan(0, 6)
    assert plan.kind == "kill_during_migration"
    report = run_migration_episode(
        0, episode=6,
        cfg=FleetSoakConfig(watchdog_s=150.0),
        work_dir=str(tmp_path),
        runner_schedule=plan.runner_schedule,
    )
    assert report["completed"] + report["failed"] == report["requests"]
    assert report["restarts"] >= 1
    assert report["migrations"] >= 1
    assert report["migration_failures"] >= 1
    assert any(
        f["point"] == "fleet.replica.import" and f["action"] == "crash"
        for f in report["faults"]
    )
    for stats in report["kv_blocks"].values():
        assert stats["used"] + stats["free"] + stats["cached"] == (
            stats["total"]
        )
