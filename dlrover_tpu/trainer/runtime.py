"""Worker-process runtime: consume the agent's env and bring up JAX.

The agent injects the ``jax.distributed.initialize`` triple (see
dlrover_tpu.common.constants.WorkerEnv); a training script calls
``init_distributed()`` first thing. Single-process worlds skip
``jax.distributed`` entirely so local runs work on any backend.

Parity note: replaces the reference's reliance on torchrun env
(WORLD_SIZE/RANK/MASTER_ADDR, training.py:_initialize_workers) with JAX's
coordination model.
"""

import os
import time
from dataclasses import dataclass
from typing import Optional

from dlrover_tpu.common.constants import NodeEnv, WorkerEnv
from dlrover_tpu.common.log import logger


@dataclass
class DistributedContext:
    coordinator_address: str
    num_processes: int
    process_id: int
    local_rank: int
    local_world_size: int
    restart_count: int
    rdzv_round: int
    node_ranks: tuple = ()
    num_slices: int = 1
    initialized_jax_distributed: bool = False

    @property
    def is_leader(self) -> bool:
        return self.process_id == 0



_context: Optional[DistributedContext] = None


def read_worker_env() -> DistributedContext:
    return DistributedContext(
        coordinator_address=os.getenv(WorkerEnv.COORDINATOR_ADDRESS, ""),
        num_processes=int(os.getenv(WorkerEnv.NUM_PROCESSES, "1")),
        process_id=int(os.getenv(WorkerEnv.PROCESS_ID, "0")),
        local_rank=int(os.getenv(WorkerEnv.LOCAL_RANK, "0")),
        local_world_size=int(os.getenv(WorkerEnv.LOCAL_WORLD_SIZE, "1")),
        restart_count=int(os.getenv(WorkerEnv.RESTART_COUNT, "0")),
        rdzv_round=int(os.getenv(WorkerEnv.RDZV_ROUND, "0")),
        node_ranks=tuple(
            int(r)
            for r in os.getenv(WorkerEnv.NODE_RANKS, "").split(",")
            if r.strip()
        ),
        num_slices=int(os.getenv(WorkerEnv.NUM_SLICES, "1")),
    )


def init_distributed(timeout_secs: int = 300) -> DistributedContext:
    """Initialize JAX multi-process coordination from agent-injected env.

    Idempotent per process. Must be called before any other JAX API touches
    the backend.
    """
    global _context
    if _context is not None:
        return _context
    ctx = read_worker_env()
    # Imported here: the module listens to JAX's compile events from
    # its import on, so it imports JAX, and this module's importers
    # (the checkpoint engine's saver side) must not.
    from dlrover_tpu.common.compile_cache import enable_compile_cache

    enable_compile_cache()
    if ctx.num_processes > 1 and ctx.coordinator_address:
        import jax

        logger.info(
            "jax.distributed.initialize(%s, num=%d, id=%d)",
            ctx.coordinator_address,
            ctx.num_processes,
            ctx.process_id,
        )
        jax.distributed.initialize(
            coordinator_address=ctx.coordinator_address,
            num_processes=ctx.num_processes,
            process_id=ctx.process_id,
            initialization_timeout=timeout_secs,
        )
        ctx.initialized_jax_distributed = True
    # Always: SIGUSR2 (agent hang post-mortem) must never be fatal, and
    # faulthandler costs nothing until the signal arrives.
    try:
        from dlrover_tpu.tpu_timer.py_tracing import (
            install_stack_dump_handler,
        )

        install_stack_dump_handler()
    except Exception:
        logger.warning(
            "stack dump handler unavailable; SIGUSR2 will be fatal to "
            "workers",
            exc_info=True,
        )
    _maybe_start_tpu_timer(ctx)
    _setup_flight_recorder(ctx)
    _setup_tracing(ctx)
    _setup_hang_watchdog(ctx)
    _context = ctx
    return ctx


def _setup_hang_watchdog(ctx: DistributedContext):
    """Arm the rolling-deadline hang watchdog (on by default: a wedged
    worker that stops beating past ``max(DLROVER_TPU_HANG_DEADLINE_S,
    factor x EWMA(step gap))`` dumps all-thread stacks to the
    agent-collectable path). ``DLROVER_TPU_HANG_DEADLINE_S=0`` disables;
    the default 300s floor keeps slow-compile first steps quiet."""
    try:
        from dlrover_tpu.observability import hang_watchdog

        raw = os.getenv("DLROVER_TPU_HANG_DEADLINE_S", "300")
        try:
            floor_s = float(raw)
        except ValueError:
            floor_s = 300.0
        if floor_s <= 0:
            return
        node_rank = int(os.getenv(NodeEnv.NODE_RANK, "0"))
        hang_watchdog.install_watchdog(
            node_rank=node_rank,
            local_rank=ctx.local_rank,
            min_deadline_s=floor_s,
            meta={"process_id": ctx.process_id},
        )
    except Exception:
        logger.warning("hang watchdog unavailable", exc_info=True)


def _setup_tracing(ctx: DistributedContext):
    """Arm distributed tracing when the env rigging asks for it
    (``DLROVER_TPU_TRACE_FILE``, same contract the fleet replica worker
    honors). Per-process sink: ``<path>`` gets ``.rank<pid>`` inserted
    before the extension on multi-process worlds so workers never
    interleave writes into one file. Disarmed (env unset) costs nothing
    — every span site stays one global check."""
    try:
        from dlrover_tpu.observability import tracing

        path = os.getenv(tracing.TRACE_FILE_ENV, "")
        if not path:
            return
        if ctx.num_processes > 1:
            base, ext = os.path.splitext(path)
            path = f"{base}.rank{ctx.process_id}{ext or '.jsonl'}"
        tracing.arm(tracing.Tracer(
            service=f"worker{ctx.process_id}", sink_path=path
        ))
        logger.info("tracing armed -> %s", path)
    except Exception:
        logger.warning("tracing unavailable", exc_info=True)


def _setup_flight_recorder(ctx: DistributedContext):
    """Arm the per-step flight recorder: a host-side ring buffer (never
    touches the jitted path) dumped as JSON on crash/SIGTERM at a path
    the agent can reconstruct from (node_rank, local_rank), so the last
    N steps of a dead worker survive for diagnosis."""
    try:
        from dlrover_tpu.observability import flight_recorder

        node_rank = int(os.getenv(NodeEnv.NODE_RANK, "0"))
        flight_recorder.install_recorder(
            node_rank=node_rank,
            local_rank=ctx.local_rank,
            meta={
                "process_id": ctx.process_id,
                "num_processes": ctx.num_processes,
                "restart_count": ctx.restart_count,
                "rdzv_round": ctx.rdzv_round,
            },
        )
    except Exception:
        logger.warning("flight recorder unavailable", exc_info=True)


def _maybe_start_tpu_timer(ctx: DistributedContext):
    """Start the native profiler daemon when enabled (reference xpu_timer
    daemon at :18889; here BASE_PORT + local_rank per worker process).
    The actually-bound port is published to a port file the launcher-side
    collector re-reads, so an OS-assigned fallback port still gets
    scraped."""
    from dlrover_tpu.common.env_utils import get_env_bool

    if not get_env_bool("DLROVER_TPU_TIMER"):
        return
    try:
        from dlrover_tpu.tpu_timer import get_timer
        from dlrover_tpu.tpu_timer.bridge import publish_port
        from dlrover_tpu.tpu_timer.py_tracing import trace_gc

        timer = get_timer()
        port = timer.start_server(18889 + ctx.local_rank)
        if not port:  # port taken (e.g. stale process): let the OS pick
            port = timer.start_server(0)
        if port:
            publish_port(ctx.local_rank, port)
        trace_gc()
        # Kernel-level acquisition (PJRT trace listener) — the TPU
        # analogue of the reference's LD_PRELOAD hook layer; gated by
        # DLROVER_TPU_TIMER_XLA.
        from dlrover_tpu.tpu_timer.xla_capture import maybe_start_listener

        maybe_start_listener(ctx.local_rank)
    except Exception:
        logger.warning("tpu_timer daemon failed to start", exc_info=True)


def get_context() -> DistributedContext:
    if _context is None:
        return init_distributed()
    return _context


def shutdown_distributed():
    global _context
    if _context is not None and _context.initialized_jax_distributed:
        import jax

        try:
            jax.distributed.shutdown()
        except Exception:
            logger.warning("jax.distributed.shutdown failed", exc_info=True)
    _context = None
