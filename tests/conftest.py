"""Test environment: force JAX onto a virtual 8-device CPU mesh so all
sharding paths (dp/fsdp/tp/pp/sp/ep) are exercised without TPU hardware.

The environment variables cover the child processes tests start;
jax.config is updated as well in case JAX was imported before this file.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# init_distributed() and the serving engines point JAX at the persistent
# compile cache; the test process itself stays off it (a warm cache must
# not decide what a test compiles). Children get theirs from the env.
jax.config.update("jax_enable_compilation_cache", False)
assert len(jax.devices()) == 8, (
    f"expected 8 virtual CPU devices, got {jax.devices()}"
)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running; excluded from the tier-1 run"
    )
    config.addinivalue_line(
        "markers",
        "chaos: exercises injected-fault recovery paths (fault plane, "
        "probe rigging)",
    )
    config.addinivalue_line(
        "markers",
        "soak: seeded chaos-soak episodes through the whole stack; "
        "pair with slow for the CI slow lane",
    )
    config.addinivalue_line(
        "markers",
        "rescale: live elastic N→M rescale protocol (plan broadcast, "
        "barrier, resharded restore) — docs/DESIGN.md §27",
    )
    config.addinivalue_line(
        "markers",
        "fleet: self-healing serving fleet (health-gated router, "
        "retries/hedges, crash re-routing) — docs/DESIGN.md §28",
    )
    config.addinivalue_line(
        "markers",
        "trace: cross-process distributed tracing + straggler/hang "
        "diagnosis plane — docs/DESIGN.md §29",
    )
    config.addinivalue_line(
        "markers",
        "autoscale: closed-loop autoscaler (signal bus, rule policy, "
        "actuators, static-vs-autoscaled soak A/B) — docs/DESIGN.md §30",
    )
    config.addinivalue_line(
        "markers",
        "kvpool: paged KV memory plane (block-table cache, prefix "
        "reuse, COW, SLO-class admission) — docs/DESIGN.md §31",
    )
    config.addinivalue_line(
        "markers",
        "control_plane: master saturation plane (per-verb RPC "
        "telemetry, overload shed law, sim load harness) — "
        "docs/DESIGN.md §32; fast lane runs the 64-worker smoke, the "
        "1k-worker ramp is slow-lane",
    )
    config.addinivalue_line(
        "markers",
        "kernels: Pallas kernel parity suites (fused MoE dispatch, "
        "int8-KV decode, paged decode) — docs/DESIGN.md §33; run in "
        "interpret mode so the CPU tier-1 lane covers kernel logic "
        "without a TPU",
    )
    config.addinivalue_line(
        "markers",
        "whatif: decision-outcome observability plane (signal "
        "recording, outcome attribution, what-if policy replay) — "
        "docs/DESIGN.md §34; fast lane runs synthetic-recording "
        "smokes, the record→replay→perturb soak leg is slow-lane",
    )
    config.addinivalue_line(
        "markers",
        "spec: self-speculative decoding (draft/verify/fill-rewind "
        "over both serving engines, accept-law parity, int8 "
        "bit-stability) — docs/DESIGN.md §35",
    )
    config.addinivalue_line(
        "markers",
        "master_recovery: control-plane crash recovery (durable master "
        "journal WAL, epoch-fenced worker ride-through, exactly-once "
        "rehydration) — docs/DESIGN.md §37; the master_kill soak "
        "episode itself is slow-lane",
    )
