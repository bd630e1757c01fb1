"""Serving metrics: the engine's view into the observability hub.

One call wires the continuous-batching engine into the SAME process
registry the master scrapes (observability/registry.py) — queue depth,
slot occupancy, TTFT, per-token latency, token/request counters — so a
serving job's health rides the existing /metrics exposition and the
flight-recorder ring with zero new plumbing.

Registration is idempotent (the registry returns existing families), so
multiple engines in one process share counters; gauges describe the
LAST engine to update them, which is the single-engine common case.
"""

from typing import Optional

from dlrover_tpu.observability.registry import default_registry

# Sub-second buckets: decode iterations are milliseconds, not the
# registry's default 5ms..300s I/O scale.
_LATENCY_BUCKETS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
    0.25, 0.5, 1.0, 2.5, 5.0,
)
_TTFT_BUCKETS = (
    0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0,
)


class ServingMetrics:
    """Handle bundle over the registry families the engine updates."""

    def __init__(self, registry=None):
        reg = registry or default_registry()
        self.queue_depth = reg.gauge(
            "serving_queue_depth", "requests waiting for a slot"
        )
        self.active_slots = reg.gauge(
            "serving_active_slots", "slots holding a live request"
        )
        self.slots_total = reg.gauge(
            "serving_slots_total", "slot-pool size of the engine"
        )
        self.requests = reg.counter(
            "serving_requests_total",
            "requests by lifecycle outcome",
            labelnames=("outcome",),
        )
        self.tokens = reg.counter(
            "serving_tokens_total",
            "tokens processed, prefill (prompt) vs decode (generated)",
            labelnames=("kind",),
        )
        self.two_chunk_steps = reg.counter(
            "serving_two_chunk_steps_total",
            "engine iterations that launched two prefill chunks (the "
            "oldest prompt's next two, while another prompt waited in "
            "a slot behind it)",
        )
        self.tokens_wasted = reg.counter(
            "serving_tokens_wasted_total",
            "computed tokens thrown away by progress resets (step-error "
            "requeues, pool preemptions) — the serving side of the §34 "
            "useful-token fraction in /api/goodput",
            labelnames=("kind",),
        )
        self.retraces = reg.counter(
            "serving_retraces_total",
            "step-program traces (must stay flat after warmup)",
        )
        self.step_errors = reg.counter(
            "serving_step_errors_total",
            "engine iterations that raised and re-queued their in-flight "
            "requests",
        )
        self.pipeline_drains = reg.counter(
            "serving_pipeline_drains_total",
            "times the engine fetched its step in flight before the "
            "next launch because it needed the committed state, by "
            "reason (spec_k, preempt, cancel, migrate)",
            labelnames=("reason",),
        )
        self.shed = reg.counter(
            "serving_requests_shed_total",
            "queued requests dropped before admission, by reason "
            '(reason="deadline": past their TTL, never prefillled) '
            "and SLO class",
            labelnames=("reason", "slo_class"),
        )
        self.class_queue_depth = reg.gauge(
            "serving_class_queue_depth",
            "requests waiting for a slot, per SLO class",
            labelnames=("slo_class",),
        )
        self.failures = reg.counter(
            "serving_requests_failed_total",
            "terminally failed requests by machine-readable reason "
            "(requeue_budget, deadline, ...)",
            labelnames=("reason",),
        )
        self.ttft = reg.histogram(
            "serving_ttft_seconds",
            "submit-to-first-token latency",
            buckets=_TTFT_BUCKETS,
        )
        self.token_latency = reg.histogram(
            "serving_token_latency_seconds",
            "per-decoded-token latency (iteration wall time)",
            buckets=_LATENCY_BUCKETS,
        )
        # ---- paged KV pool (serving/kvpool, §31) ------------------------
        self.kv_blocks = reg.gauge(
            "serving_kv_blocks",
            "paged KV pool blocks by state (free | used: referenced by "
            "a live slot's block table | cached: held warm by the "
            "prefix cache only); states sum to the managed pool size",
            labelnames=("state",),
        )
        self.kv_blocks_total = reg.gauge(
            "serving_kv_blocks_total",
            "managed (allocatable) blocks in the paged KV pool, by layer "
            "group (one group, 'all', unless the model states more)",
            labelnames=("group",),
        )
        self.kv_window_blocks_released = reg.counter(
            "serving_kv_window_blocks_released_total",
            "blocks of a group that keeps only the rows a query can "
            "still see, released by live slots as they slid out of reach",
        )
        self.state_bytes = reg.gauge(
            "serving_state_bytes",
            "bytes of per-slot state and of its live snapshots (a model "
            "whose layers keep a state whatever the length)",
        )
        self.state_snapshots_denied = reg.counter(
            "serving_state_snapshots_denied_total",
            "prompts that ran without a snapshot of their own: every "
            "snapshot id was lent to a prompt still prefilling",
        )
        self.kv_bytes_in_use = reg.gauge(
            "serving_kv_bytes_in_use",
            "bytes of KV pool HBM referenced by live slots or the "
            "prefix cache (allocated blocks x block bytes, K+V)",
        )
        self.kv_cow_copies = reg.counter(
            "serving_kv_cow_copies_total",
            "copy-on-write block privatizations (a shared block was "
            "about to be rewritten)",
        )
        self.kv_preemptions = reg.counter(
            "serving_kv_preemptions_total",
            "requests preempted (re-queued, progress reset) to free "
            "blocks for an older request under pool pressure",
        )
        # ---- speculative decoding (serving/spec_decode, §35) ------------
        self.spec_tokens = reg.counter(
            "serving_spec_tokens_total",
            "speculative-decoding tokens by fate (drafted: proposed by "
            "the drafter and verified; accepted: survived verification "
            "and committed; rejected: rolled back by the fill rewind)",
            labelnames=("kind",),
        )
        self.spec_tokens_per_step = reg.gauge(
            "serving_spec_accepted_tokens_per_step",
            "running mean of tokens committed per verify step across "
            "decoding slots (accepted drafts + the correction/bonus "
            "token; 1.0 = no speculation win, K+1 = every draft lands)",
        )

    def annotate(self, event: str, **fields):
        """Drop a marker in the flight-recorder ring IF one is armed —
        admissions/evictions then land in the merged job timeline next
        to training steps. Never creates a recorder."""
        from dlrover_tpu.observability.flight_recorder import (
            active_recorder,
        )

        rec = active_recorder()
        if rec is not None:
            rec.annotate(event, **fields)


_metrics: Optional[ServingMetrics] = None


def serving_metrics(registry=None) -> ServingMetrics:
    """Process-wide handle (or a private one for a passed registry)."""
    global _metrics
    if registry is not None:
        return ServingMetrics(registry)
    if _metrics is None:
        _metrics = ServingMetrics()
    return _metrics


def reset_serving_metrics():
    """Tests only: forget the cached handle (the registry itself is
    reset separately via reset_default_registry)."""
    global _metrics
    _metrics = None
