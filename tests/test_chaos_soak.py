"""Seeded chaos-soak smoke: one full episode (worker SIGKILL mid-step +
dropped get_task reply) through the real master/worker/checkpoint stack
on CPU, with all invariants asserted — the recovery paths run in CI's
slow lane, not just on demand (docs/DESIGN.md §26).

The full three-episode matrix (torn shard writes, serving step errors,
...) runs via ``python tools/chaos_soak.py --seed 0 --episodes 3``.
"""

import pytest

from dlrover_tpu.testing.soak import SoakConfig, build_episode_plan, run_soak


@pytest.mark.chaos
def test_episode_plans_are_deterministic_and_cover_core_faults():
    """Same (seed, episode) -> identical plan; the first three episodes
    of any seed cover the four required fault classes."""
    plans = [build_episode_plan(0, k) for k in range(3)]
    again = [build_episode_plan(0, k) for k in range(3)]
    for a, b in zip(plans, again):
        assert a.kind == b.kind
        assert [r.to_dict() for s in a.worker_schedules for r in s.rules] \
            == [r.to_dict() for s in b.worker_schedules for r in s.rules]
        assert [r.to_dict() for r in a.runner_schedule.rules] \
            == [r.to_dict() for r in b.runner_schedule.rules]
    points = {
        r.point
        for p in plans
        for s in p.worker_schedules + [p.runner_schedule]
        for r in s.rules
    }
    assert "agent.worker.crash" in points          # worker SIGKILL
    assert "rpc.get.drop_reply" in points          # dropped get_task reply
    assert "ckpt.persist.torn_write" in points     # torn shard write
    assert "serving.step.error" in points          # serving step exception


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.soak
def test_soak_episode_crash_and_dropped_reply(tmp_path):
    """Episode 0 at seed 0: the worker is SIGKILLed mid-step and a
    get_task reply is dropped; after restart + checkpoint/shard-ckpt
    restore the exactly-once, integrity and watchdog invariants hold."""
    cfg = SoakConfig(
        dataset_size=256,
        shard_size=16,
        serve=False,  # serving invariant has its own fast test + CLI
        watchdog_s=150.0,
    )
    summary = run_soak(
        seed=0, episode=0, cfg=cfg, work_dir=str(tmp_path)
    )
    assert summary["invariants"] == "pass"
    report = summary["reports"][0]
    assert report["kind"] == "crash_drop"
    assert report["deaths"] == 1
    assert report["generations"] == 2
    fired = {f["rule_id"] for f in report["faults"]}
    assert fired == {"worker-sigkill", "drop-get-task-reply"}
    assert summary["mttr_mean_s"] > 0
