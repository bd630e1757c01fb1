"""Seeded chaos-soak CLI: drive the whole stack through reproducible
fault episodes and assert the five system invariants.

    python tools/chaos_soak.py --seed 0 --episodes 8
    python tools/chaos_soak.py --seed 0 --episode 1      # repro one
    python tools/chaos_soak.py --seed 0 --episode 3      # rescale kill
    python tools/chaos_soak.py --seed 0 --episode 4      # fleet reroute
    python tools/chaos_soak.py --seed 0 --episode 5      # autoscaler A/B
    python tools/chaos_soak.py --seed 0 --episode 6      # migration kill
    python tools/chaos_soak.py --seed 0 --episode 7      # master kill

Each episode runs an in-process master, worker subprocesses and a
serving engine under a deterministic seeded fault schedule (worker
SIGKILL mid-step, dropped RPC replies, torn checkpoint shard writes,
serving step errors, SIGKILL mid-live-rescale ...). Episode 3 is the
multi-worker ``kill_during_rescale`` episode
(``dlrover_tpu/testing/rescale_soak.py``): a worker is killed between
the rescale-plan ack and the first post-rescale step, and the restored
state must still be bit-identical to the single-host reference.
Episode 4 is the serving-fleet ``replica_kill_reroute`` episode
(``dlrover_tpu/testing/fleet_soak.py``): a router over N subprocess
serving replicas has one replica SIGKILLed mid-decode; every accepted
request must complete or be explicitly failed exactly once and the
victim's breaker must walk BROKEN → HALF_OPEN → HEALTHY. Episode 5 is
the closed-loop autoscaler episode
(``dlrover_tpu/testing/autoscale_soak.py``): one seeded fault+traffic
schedule (persistent per-rank delay at the step fault point, worker
deaths, a serving spike) run static, dry-run and autoscaled — the
autoscaled run must evict the straggler within bounded decision
windows and strictly beat the static goodput fraction. Episode 7 is
the control-plane crash episode
(``dlrover_tpu/testing/master_kill_soak.py``): the MASTER subprocess
is SIGKILLed between a journaled shard dispatch and its reply,
restarted from its durable journal (DESIGN.md §37), and the
never-restarted worker must ride the outage out and finish with
exactly-once accounting. The
implementation and the invariant definitions live in
``dlrover_tpu/testing/soak.py`` (docs/DESIGN.md §26-§30); exit code 0
means every episode held every invariant. Prints one JSON summary line
with goodput fraction and per-fault MTTR.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dlrover_tpu.testing.soak import (  # noqa: E402
    SoakConfig,
    SoakInvariantError,
    run_soak,
)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description="seeded chaos soak")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--episodes", type=int, default=8,
        help="episode count; 8 covers the full fault matrix incl. "
        "kill_during_rescale, replica_kill_reroute, the "
        "straggler_evict autoscaler A/B, the §36 "
        "kill_during_migration destination SIGKILL and the §37 "
        "master_kill control-plane crash",
    )
    parser.add_argument(
        "--episode", type=int, default=None,
        help="run only this episode index (repro mode)",
    )
    parser.add_argument("--dataset-size", type=int, default=512)
    parser.add_argument("--shard-size", type=int, default=16)
    parser.add_argument("--watchdog-s", type=float, default=180.0)
    parser.add_argument("--no-serving", action="store_true")
    parser.add_argument(
        "--artifact-dir", default=None,
        help="where failure evidence lands (default: under the work dir)",
    )
    parser.add_argument(
        "--keep-artifacts", action="store_true",
        help="keep episode dirs even on success",
    )
    args = parser.parse_args(argv)
    cfg = SoakConfig(
        dataset_size=args.dataset_size,
        shard_size=args.shard_size,
        watchdog_s=args.watchdog_s,
        serve=not args.no_serving,
        keep_artifacts_on_success=args.keep_artifacts,
    )
    try:
        summary = run_soak(
            seed=args.seed,
            episodes=args.episodes,
            episode=args.episode,
            cfg=cfg,
            artifact_dir=args.artifact_dir,
        )
    except SoakInvariantError:
        # run_episode already printed the failure, artifact dir and the
        # one-line repro command.
        return 1
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
