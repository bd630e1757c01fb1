"""Continuous-batching decode engine: slot-pooled KV cache, ragged
per-slot fills, iteration-level scheduling.

The only inference entry point before this was ``models/generate.py`` —
a fixed-batch ``lax.scan`` whose fill cursor is shared by every row, so
a batch can only hold same-phase sequences and admitting new work means
draining the batch and re-prefilling everything. This engine is the
serving-shaped alternative (Orca's iteration-level scheduling over this
repo's single-slab cache — the TPU-native analogue of vLLM's pooled
blocks, one slot = one sequence's [max_len] slab):

- **Slot pool.** One [layers, slots, max_len, kv_heads, head_dim] K and
  V slab, allocated once, DONATED through every step call so XLA
  updates it in place — admissions/evictions/completions never change a
  traced shape; occupancy is a [slots] mask and per-slot fill lengths
  are a [slots] int32 vector.
- **Ragged decode.** One compiled step decodes every active slot at its
  OWN fill length: per-row positions drive RoPE, per-row masking drives
  the append-free attention (models/generate._append_free_attention),
  and the append is a per-row scatter at each slot's cursor. Inactive
  slots compute masked garbage that lands only in never-visible rows
  (the visibility invariant, docs/DESIGN.md §25).
- **Chunked prefill.** Prompts enter ``prefill_chunk`` tokens at a time
  through a second compiled program (one slot per call), so a long
  prompt interleaves with decode iterations instead of stalling them.
- **Zero retraces.** Both programs are compiled once per
  (config, slots, max_len, chunk) and every dynamic quantity — slot
  index, cursor, lengths, occupancy, temperatures, sampling step — is a
  traced argument. ``trace_counts`` exposes the compile counter the
  no-retrace tests and the serving bench assert on.
- **One step in flight.** ``step()`` enqueues its chunk and its decode
  launch BEFORE it fetches the tokens the previous iteration's launches
  sampled: the token vector goes from launch to launch on the device,
  the host schedules on counts (there is no stop token, so who decodes
  next is known before any token's value), and a finished request is
  returned one ``step()`` after its last launch (docs/DESIGN.md §29).

Typical use::

    eng = ServingEngine(cfg, params, slots=8, max_len=1024)
    eng.submit(prompt_ids, max_new_tokens=64, temperature=0.8)
    while eng.pending():
        for req in eng.step():
            consume(req.rid, req.tokens)
"""

import functools
import time
from typing import Dict, List, NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from dlrover_tpu.common.compile_cache import enable_compile_cache
from dlrover_tpu.common.log import logger
from dlrover_tpu.fault import fault_point
from dlrover_tpu.models import generate as gen_lib
from dlrover_tpu.observability import tracing
from dlrover_tpu.models import llama
from dlrover_tpu.serving import scheduler as sched_lib
from dlrover_tpu.serving import spec_decode as spec_lib
from dlrover_tpu.serving.metrics import serving_metrics
from dlrover_tpu.serving.scheduler import DECODE, PREFILL, Request, Scheduler


class _CompiledSteps(NamedTuple):
    prefill: object
    decode: object
    trace_counts: Dict[str, int]


# Lookback horizon for the host n-gram drafter: the rightmost suffix
# match decides the proposal, so only recent history can win — and the
# per-step host cost must stay flat as sequences grow.
_NGRAM_WINDOW = 128


# Phase vocabulary of a ``serving.step`` span (docs/DESIGN.md §29). A
# phase is named by the mark that CLOSES it, so the host time between
# two marks always belongs to the later one and the phases tile the span
# whatever path a step takes. A steady iteration passes them as admit,
# prefill_prep, prefill_launch (both once a chunk: twice in a two-chunk
# step), decode_prep, decode_launch, decode_fetch,
# commit, account: it launches its own programs first and then fetches
# and commits the tokens of the PREVIOUS iteration's (``prefill_fetch``
# when that one launched a prompt's last chunk and no decode). A drain
# puts a fetch and a commit before the launch that needed them.
STEP_PHASES = (
    "admit", "prefill_prep", "prefill_launch", "prefill_fetch",
    "decode_prep", "decode_launch", "decode_fetch",
    "spec_draft", "spec_verify", "commit", "account",
)


# Phases of the two set-up spans, named like a step's by the mark that
# closes them, each name once a span. ``serving.engine_build``: the
# paged engine's allocator and trie (``prefix_cache``), the eager fused
# copy of the weights (``fuse_params``), the jit wrappers (``build_programs``;
# nothing compiles there, jit is lazy), the value pools
# (``alloc_pool``), the host mirrors of the slots (``host_state``), and
# after the base constructor the paged engine's scale and index-key
# pools (``alloc_side_pools``) and its own jit wrappers
# (``paged_programs``). ``serving.warmup``: one phase a program run,
# named as ``trace_counts`` names it (``decode`` twice: fed by the
# host, then by a launch), each closed by a ``block_until_ready`` so
# that a program's compile, load and first run are its own, then
# ``reset_pool``. The ``compile.*`` spans of common/compile_cache.py
# fall inside these by time.
BUILD_PHASES = ("prefix_cache", "fuse_params", "build_programs",
                "alloc_pool", "host_state", "alloc_side_pools",
                "paged_programs")


class _PhaseMarks:
    """Clock reads at phase boundaries: ``phases`` is ``[[name,
    offset_s, dur_s], ...]`` and tiles ``t0``..``last``."""

    __slots__ = ("t0", "last", "phases")

    def __init__(self, t0: float):
        self.t0 = self.last = t0
        self.phases: List[list] = []

    def mark(self, phase: str, at: Optional[float] = None) -> None:
        """Close ``phase`` now (or at an already-taken clock read)."""
        if at is None:
            at = time.monotonic()
        self.phases.append([phase, self.last - self.t0, at - self.last])
        self.last = at

    def emit(self, name: str, **attrs) -> float:
        """The marks as one ``local`` span when a Tracer is armed;
        returns the seconds they cover either way."""
        tracer = tracing.active_tracer()
        if tracer is not None:
            tracer.record_span(
                name, self.t0, self.last, local=True,
                attrs=dict(attrs, phases=self.phases),
            )
        return self.last - self.t0


class _StepTrace(_PhaseMarks):
    """Marks and counts of ONE armed ``step()``: plain floats and ints
    while the iteration runs, one retrospective span at its end."""

    __slots__ = ("counts",)

    def __init__(self, t0: float):
        super().__init__(t0)
        self.counts = {"n_admitted": 0, "n_decoding": 0,
                       "prefill_chunks": 0, "prefill_rows": 0,
                       "prefill_tokens": 0, "overlapped": 0}


class _Flight:
    """What ONE iteration launched and the host has not fetched: the
    decode launch's token vector with a row per request in it, and the
    first token of a prompt whose last chunk it ran. A row is ``(req,
    slot, end)``; ``end`` is None while the request decodes on, else
    how this token ends it (``"finished"`` / ``"truncated"``): the host
    knows that by count when it launches, and gave the slot away."""

    __slots__ = ("nxt", "rows", "first", "first_row", "aux")

    def __init__(self):
        self.nxt = None           # [slots] int32 on the device
        self.aux = None           # a sparse model's step: expert counts
        self.rows: List[tuple] = []
        self.first = None         # int32 scalar on the device
        self.first_row: Optional[tuple] = None

    def __bool__(self) -> bool:
        return self.nxt is not None or self.first is not None


class _SpecSteps(NamedTuple):
    """Speculative-decoding programs, compiled SEPARATELY from the
    base prefill/decode pair: a spec-on and a spec-off engine with the
    same (config, slots, max_len, chunk) share one _CompiledSteps entry
    — the bench's spec A/B genuinely runs on the same compiled base
    programs, and spec_k changes can't invalidate them."""

    verify: object
    draft: object        # None for the host-side n-gram drafter
    trace_counts: Dict[str, int]


def _build_decode_step(config, slots: int, max_len: int, counts):
    """[slots] tokens -> one decoded token per slot, ragged lengths.

    The cache is read by the layer scan (append-free attention) and the
    new K/V of ALL layers land with one per-row scatter at each slot's
    own cursor — the ragged generalization of generate()'s single
    dynamic-update-slice."""

    def step(k, v, params, lengths, tokens, active, temps, rng, step_idx,
             first=0, first_slot=-1):
        counts["decode"] += 1  # traces only; execution never reaches here
        tokens = _place_first(tokens, first, first_slot)
        positions = lengths[:, None]                     # [slots, 1]
        x = llama.embed_tokens(config, params, tokens[:, None])

        def body(carry, layer_in):
            pl, k_c, v_c = layer_in
            y, k_new, v_new = gen_lib._layer_decode_read_only(
                config, pl, carry, positions, k_c, v_c, lengths
            )
            return y, (k_new, v_new)

        x, (k_news, v_news) = jax.lax.scan(
            body, x, (params["layers"], k, v)
        )
        # Per-row append at each slot's cursor. Inactive slots write
        # garbage into rows that are not visible (>= fill) and are
        # always rewritten before any cursor passes them; the clamp
        # keeps a full stale slot's scatter in bounds.
        row = jnp.arange(slots)
        write = jnp.minimum(lengths, max_len - 1)
        k = k.at[:, row, write].set(k_news[:, :, 0].astype(k.dtype))
        v = v.at[:, row, write].set(v_news[:, :, 0].astype(v.dtype))
        logits = llama.unembed(config, params, x)[:, 0]   # [slots, V]
        sub = jax.random.fold_in(rng, step_idx * 2)
        nxt = gen_lib.sample_token(logits, sub, temps)
        # Inactive slots keep their fed token: the vector that comes out
        # is the one the next launch takes in (the host fetches it a
        # step later, and ignores them).
        nxt = jnp.where(active, nxt, tokens)
        return k, v, nxt

    return step


def _h2d(mirror: np.ndarray):
    """A host mirror as a launch argument. The launch gets a copy of
    its own: the mirror is written again (``_lengths`` at once) while
    the launch is still queued, and a backend may read a host buffer
    after the call returns (the CPU client aliases an aligned one)."""
    return jnp.asarray(mirror.copy())


def _place_first(tokens, first, first_slot):
    """The decode programs' first lines: the token vector fed back from
    the previous launch, with the first token of the prompt whose last
    chunk ran in this iteration put at its slot (``first_slot`` -1:
    none). Both stay on the device between the launches (§29)."""
    at = jnp.arange(tokens.shape[0], dtype=jnp.int32) == first_slot
    return jnp.where(at, jnp.asarray(first, tokens.dtype), tokens)


def _build_prefill_chunk(config, slots: int, max_len: int, chunk: int,
                         counts):
    """One prompt chunk ([1, chunk] tokens) into ONE slot's cache rows
    [start, start+chunk), plus the first sampled token (meaningful only
    on the final chunk — taken at the last REAL prompt position
    ``n_valid - 1``; pad rows beyond it hold garbage K/V that stays
    invisible)."""

    L = config.n_layers
    kh, hd = config.n_kv_heads, config.head_dim

    def prefill(k, v, params, tokens, slot, start, n_valid, temp, rng,
                step_idx):
        counts["prefill"] += 1  # traces only
        k_slot = jax.lax.dynamic_slice(
            k, (0, slot, 0, 0, 0), (L, 1, max_len, kh, hd)
        )
        v_slot = jax.lax.dynamic_slice(
            v, (0, slot, 0, 0, 0), (L, 1, max_len, kh, hd)
        )
        positions = (
            start + jnp.arange(chunk, dtype=jnp.int32)
        )[None, :]
        x = llama.embed_tokens(config, params, tokens)

        def body(carry, layer_in):
            pl, k_c, v_c = layer_in
            y, k_c, v_c = gen_lib._layer_decode(
                config, pl, carry, positions, k_c, v_c, start
            )
            return y, (k_c, v_c)

        x, (k_slot, v_slot) = jax.lax.scan(
            body, x, (params["layers"], k_slot, v_slot)
        )
        k = jax.lax.dynamic_update_slice(
            k, k_slot.astype(k.dtype), (0, slot, 0, 0, 0)
        )
        v = jax.lax.dynamic_update_slice(
            v, v_slot.astype(v.dtype), (0, slot, 0, 0, 0)
        )
        h = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
        logits = llama.unembed(config, params, h)[0, 0]    # [V]
        sub = jax.random.fold_in(rng, step_idx * 2 + 1)
        first = gen_lib.sample_token(logits, sub, temp)
        return k, v, first

    return prefill


def _build_verify_step(config, slots: int, max_len: int, K: int, counts):
    """[slots] fed tokens + [slots, K] drafts -> accepted tokens, one
    batched pass. T = K+1 queries run the SAME ragged append-free
    attention as the decode step (generalized to multiple queries with
    an intra-draft causal mask — models/generate._layer_verify_read_
    only), all T rows' K/V land with one per-row scatter at rows
    fill..fill+K, and the accept/reject law (spec_decode.spec_accept)
    picks how many drafts survive. Rows past an accepted prefix stay
    beyond the advanced fill — rollback is the fill rewind, no cleanup
    pass exists. Writes past max_len drop (``mode="drop"``): near the
    boundary the host clamps draft_len so no DROPPED row can ever
    become visible."""
    T = K + 1

    def verify(k, v, params, lengths, tokens, drafts, draft_len,
               active, temps, rng, step_idx):
        counts["verify"] += 1  # traces only
        toks = jnp.concatenate([tokens[:, None], drafts], axis=1)
        positions = (
            lengths[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
        )
        x = llama.embed_tokens(config, params, toks)

        def body(carry, layer_in):
            pl, k_c, v_c = layer_in
            y, k_new, v_new = gen_lib._layer_verify_read_only(
                config, pl, carry, positions, k_c, v_c, lengths
            )
            return y, (k_new, v_new)

        x, (k_news, v_news) = jax.lax.scan(
            body, x, (params["layers"], k, v)
        )
        row = jnp.arange(slots)[:, None]
        writes = positions                                # [slots, T]
        k = k.at[:, row, writes].set(
            k_news.astype(k.dtype), mode="drop"
        )
        v = v.at[:, row, writes].set(
            v_news.astype(v.dtype), mode="drop"
        )
        logits = llama.unembed(config, params, x)         # [slots, T, V]
        emitted, acc = spec_lib.spec_accept(
            logits, drafts, draft_len, temps, active, tokens,
            rng, step_idx,
        )
        return k, v, emitted, acc

    return verify


def _build_draft_step(config, slots: int, max_len: int, K: int,
                      draft_layers: int, counts):
    """Early-exit drafter: K sequential single-token forwards through
    the FIRST ``draft_layers`` decoder blocks of the same weights
    (greedy argmax through the shared final-norm/unembed head). Each
    drafted token's partial-layer K/V lands at its row beyond the fill
    so the NEXT draft can attend it — invisible to everyone else by
    the visibility invariant, and the verify pass rewrites those rows
    with full-model K/V for every layer before any of them can become
    visible. Out-of-range writes drop."""
    d = draft_layers

    def draft(k, v, params, lengths, tokens, active):
        counts["draft"] += 1  # traces only
        layers_d = jax.tree_util.tree_map(
            lambda a: a[:d], params["layers"]
        )
        row = jnp.arange(slots)
        cur = tokens
        drafts = []
        for i in range(K):
            lens_i = lengths + i
            positions = lens_i[:, None]
            x = llama.embed_tokens(config, params, cur[:, None])

            def body(carry, layer_in):
                pl, k_c, v_c = layer_in
                y, k_new, v_new = gen_lib._layer_decode_read_only(
                    config, pl, carry, positions, k_c, v_c, lens_i
                )
                return y, (k_new, v_new)

            x, (k_news, v_news) = jax.lax.scan(
                body, x, (layers_d, k[:d], v[:d])
            )
            k = k.at[:d, row, lens_i].set(
                k_news[:, :, 0].astype(k.dtype), mode="drop"
            )
            v = v.at[:d, row, lens_i].set(
                v_news[:, :, 0].astype(v.dtype), mode="drop"
            )
            logits = llama.unembed(config, params, x)[:, 0]
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            cur = jnp.where(active, nxt, cur)
            drafts.append(cur)
        return k, v, jnp.stack(drafts, axis=1)           # [slots, K]

    return draft


@functools.lru_cache(maxsize=16)
def _compiled_spec_steps(
    config: llama.TpuLMConfig, slots: int, max_len: int,
    spec_k: int, draft_layers: int,
) -> _SpecSteps:
    """Verify (+ optional early-exit draft) programs, one per shape
    key, KV slabs donated — the spec siblings of _compiled_steps.
    spec_k is a SHAPE key (the verify batch is [slots, K+1]); the
    per-slot accept length rides as a traced vector, so variable
    acceptance never retraces."""
    counts = {"verify": 0, "draft": 0}
    verify = jax.jit(
        _build_verify_step(config, slots, max_len, spec_k, counts),
        donate_argnums=(0, 1),
    )
    draft = None
    if draft_layers > 0:
        draft = jax.jit(
            _build_draft_step(config, slots, max_len, spec_k,
                              draft_layers, counts),
            donate_argnums=(0, 1),
        )
    return _SpecSteps(verify=verify, draft=draft, trace_counts=counts)


@functools.lru_cache(maxsize=16)
def _compiled_steps(
    config: llama.TpuLMConfig, slots: int, max_len: int, chunk: int
) -> _CompiledSteps:
    """Both step programs, compiled once per shape key and SHARED by
    every engine with the same key (the bench's continuous and static
    engines reuse one compile). The KV slabs are donated so the pool is
    updated in place; everything else is a plain traced argument."""
    counts = {"prefill": 0, "decode": 0}
    decode = jax.jit(
        _build_decode_step(config, slots, max_len, counts),
        donate_argnums=(0, 1),
    )
    prefill = jax.jit(
        _build_prefill_chunk(config, slots, max_len, chunk, counts),
        donate_argnums=(0, 1),
    )
    return _CompiledSteps(prefill=prefill, decode=decode,
                          trace_counts=counts)


class ServingEngine:
    """Single-host continuous-batching engine over a slot-pooled cache.

    Host bookkeeping (the Scheduler) is jax-free; each ``step()`` runs
    at most two prefill chunks (``Scheduler.pick_prefills``) and one
    ragged decode iteration. The engine is not thread-safe — drive it
    from one serving loop."""

    def __init__(
        self,
        config: llama.TpuLMConfig,
        params,
        slots: int,
        max_len: int,
        prefill_chunk: int = 64,
        token_budget: Optional[int] = None,
        drain_mode: bool = False,
        rng: Optional[jax.Array] = None,
        registry=None,
        max_requeues: int = 3,
        slo_classes=None,
        spec_k: int = 0,
        spec_drafter: str = "ngram",
        spec_draft_layers: int = 2,
        build_marks: Optional[_PhaseMarks] = None,
    ):
        if config.pp_stages > 1:
            raise NotImplementedError(
                "serving runs on the flat layer stack; merge pipeline "
                "stages for inference"
            )
        enable_compile_cache()  # before the step programs compile
        # Construction times itself (a dozen clock reads, armed or
        # not). A subclass that began the marks hands them in as
        # ``build_marks``, and ends them itself.
        build = build_marks or _PhaseMarks(time.monotonic())
        if max_len % 8:
            raise ValueError("max_len must be a multiple of 8")
        if max_len % prefill_chunk:
            # The final chunk of a near-full prompt would otherwise
            # start at a non-chunk-aligned cursor close enough to the
            # end that its fixed-size dynamic_update_slice CLAMPS —
            # silently rewriting already-visible rows below the cursor
            # with K/V computed for later positions. Chunk starts are
            # always multiples of prefill_chunk (partial chunks only
            # ever END a prompt), so divisibility makes the clamp
            # unreachable.
            raise ValueError(
                f"max_len {max_len} must be a multiple of "
                f"prefill_chunk {prefill_chunk}"
            )
        if spec_k < 0:
            raise ValueError("spec_k must be >= 0")
        if spec_k:
            if spec_drafter not in spec_lib.SPEC_DRAFTERS:
                raise ValueError(
                    f"spec_drafter must be one of "
                    f"{spec_lib.SPEC_DRAFTERS}, got {spec_drafter!r}"
                )
            if spec_drafter == "early_exit" and not (
                0 < spec_draft_layers <= config.n_layers
            ):
                raise ValueError(
                    f"spec_draft_layers must be in 1..{config.n_layers}"
                )
        self.config = config
        self.slots = slots
        self.max_len = max_len
        self.prefill_chunk = prefill_chunk
        self.spec_k = spec_k
        self.spec_drafter = spec_drafter
        # draft_layers keys the compile cache; 0 = no device drafter
        # (the n-gram drafter is pure host code).
        self.spec_draft_layers = (
            spec_draft_layers
            if spec_k and spec_drafter == "early_exit" else 0
        )
        # How many step-error restarts a request gets before it is
        # EXPLICITLY failed — a persistent device error must not
        # livelock the serve loop re-queueing the same work forever.
        self.max_requeues = max_requeues
        self.scheduler = Scheduler(
            slots, max_len, prefill_chunk, token_budget, drain_mode,
            slo_classes=slo_classes,
            decode_tokens_per_slot=1 + spec_k,
        )
        self.metrics = serving_metrics(registry)
        self.metrics.slots_total.set(slots)
        self._params = jax.block_until_ready(
            gen_lib.prepare_decode_params(config, params)
        )
        build.mark("fuse_params")
        self._steps = _compiled_steps(config, slots, max_len,
                                      prefill_chunk)
        self._spec = (
            _compiled_spec_steps(config, slots, max_len, spec_k,
                                 self.spec_draft_layers)
            if spec_k else None
        )
        # Running accepted-tokens-per-step mean (slot-steps in the
        # denominator: one decoding slot through one verify call).
        self._spec_emitted = 0
        self._spec_slot_steps = 0
        # Per-iteration emitted-token counts, one entry per decoding
        # slot — step() turns them into per-TOKEN latency observations
        # (a verify step that commits 4 tokens is 4 cheap tokens, not
        # one slow one).
        self._iter_advance: List[int] = []
        # The armed step's phase marks; None whenever no Tracer is armed.
        self._step_trace: Optional[_StepTrace] = None
        self._trace_snapshot = self._all_trace_counts()
        self._rng = rng if rng is not None else jax.random.key(0)
        self._step_idx = 0
        build.mark("build_programs")
        self._alloc_pool()
        build.mark("alloc_pool")
        # Host mirrors of the device-side per-slot state; passed into
        # every step call (tiny H2D) so host and device can never
        # drift. ``_lengths`` advances when a launch is enqueued;
        # ``_tokens`` holds the COMMITTED tokens, and feeds a launch
        # only when no decode launch is in flight (otherwise that
        # launch's vector does, on the device).
        self._lengths = np.zeros(slots, np.int32)
        self._tokens = np.zeros(slots, np.int32)
        self._temps = np.zeros(slots, np.float32)
        # One step in flight (docs/DESIGN.md §29): what the previous
        # iteration launched and nobody fetched, what the running
        # iteration has launched so far (None between steps), requests
        # that gave their slot away at their last launch and wait for
        # its tokens, and requests finished since step() last returned.
        self._flight: Optional[_Flight] = None
        self._cur: Optional[_Flight] = None
        self._leaving: Dict[int, Request] = {}
        self._done: List[Request] = []
        self._no_first = jnp.zeros((), jnp.int32)
        # A model whose attention reads a learned selection of its cache
        # (models/sparse_lm.py): how many rows a query keeps, else 0.
        self._index_topk = getattr(config, "index_topk", 0)
        self._moe_rows_dropped = 0
        self.engine_build_s = self.warmup_s = 0.0
        build.mark("host_state")
        if build_marks is None:
            self._end_build(build)

    def _end_build(self, build: _PhaseMarks) -> None:
        """Close construction: ``engine_build_s``, and armed one
        ``serving.engine_build`` span whose ``phases`` tile it."""
        self.engine_build_s = build.emit(
            "serving.engine_build", **self._build_bytes()
        )

    def _build_bytes(self) -> Dict[str, int]:
        return {
            "params_bytes": sum(
                x.nbytes for x in jax.tree_util.tree_leaves(self._params)
            ),
            "pool_bytes": self._k.nbytes + self._v.nbytes,
            "index_pool_bytes": 0,
        }

    def _alloc_pool(self) -> None:
        """Build the cache's device arrays (the paged engine builds
        what its model's blocks hold: kvpool/layout.py)."""
        self._k, self._v = jax.block_until_ready(self._fresh_pool())

    def _fresh_pool(self):
        shape = (
            self.config.n_layers, self.slots, self.max_len,
            self.config.n_kv_heads, self.config.head_dim,
        )
        dtype = _slab_dtype(self.config)
        return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)

    # ---- public API --------------------------------------------------------

    def _all_trace_counts(self) -> Dict[str, int]:
        """Base + spec compile counters merged (key sets are disjoint:
        prefill/decode/... vs verify/draft)."""
        counts = dict(self._steps.trace_counts)
        if self._spec is not None:
            counts.update(self._spec.trace_counts)
        return counts

    @property
    def trace_counts(self) -> Dict[str, int]:
        """Compile counter per step program (shared across engines with
        the same shape key) — flat after warmup or something retraced."""
        return self._all_trace_counts()

    def submit(self, prompt, max_new_tokens: int,
               temperature: float = 0.0,
               deadline_s: Optional[float] = None,
               trace: Optional[dict] = None,
               slo_class: Optional[str] = None) -> Request:
        req = self.scheduler.submit(
            prompt, max_new_tokens, temperature, deadline_s=deadline_s,
            slo_class=slo_class,
        )
        # Upstream trace carrier (fleet attempt span): stored as a
        # plain dict; the phase spans are emitted retrospectively at
        # completion, so the step loop never touches the tracer.
        req.trace = trace
        self.metrics.queue_depth.set(len(self.scheduler.queue))
        return req

    def cancel(self, req: Request) -> None:
        """Evict a live request; its slot is recycled immediately. One
        with a token in flight is committed first: if that token was
        its last it has finished, and the next step() returns it."""
        if req.inflight:
            self._drain("cancel")
        if req.state == sched_lib.DONE:
            return
        if req.state == sched_lib.QUEUED:
            try:
                self.scheduler.queue.remove(req)
            except ValueError:
                pass
        slot = req.slot
        self.scheduler.evict(req)
        if slot >= 0:
            self._release_slot(req, slot)
        self.metrics.requests.inc(outcome="cancelled")

    def pending(self) -> int:
        """Requests step() has yet to return: queued, in a slot, out of
        their slot with their last tokens still on the device, or
        finished by a drain between two steps."""
        return (
            len(self.scheduler.queue) + len(self.scheduler.active())
            + len(self._leaving) + len(self._done)
        )

    def warmup(self) -> None:
        """Compile both step programs on throwaway state, then reset the
        pool — so the first real request pays no compile and the
        trace counters are settled for no-retrace assertions."""
        marks = _PhaseMarks(time.monotonic())
        chunk = np.zeros((1, self.prefill_chunk), np.int32)
        k, v, first = self._steps.prefill(
            self._k, self._v, self._params, jnp.asarray(chunk),
            np.int32(0), np.int32(0), np.int32(1), np.float32(0.0),
            self._rng, np.int32(0),
        )
        jax.block_until_ready(first)
        marks.mark("prefill")
        # Both ways a launch is fed: the host's tokens and a chunk's
        # first token, then the vector that launch returned.
        fed = jnp.asarray(np.zeros(self.slots, np.int32))
        for first, first_slot in ((first, 0), (self._no_first, -1)):
            k, v, fed = self._steps.decode(
                k, v, self._params,
                jnp.asarray(np.zeros(self.slots, np.int32)), fed,
                jnp.asarray(np.zeros(self.slots, bool)),
                jnp.asarray(np.zeros(self.slots, np.float32)),
                self._rng, np.int32(0), first, np.int32(first_slot),
            )
            jax.block_until_ready(fed)
            marks.mark("decode")
        nxt = fed
        if self._spec is not None:
            z_i = jnp.asarray(np.zeros(self.slots, np.int32))
            z_b = jnp.asarray(np.zeros(self.slots, bool))
            z_f = jnp.asarray(np.zeros(self.slots, np.float32))
            drafts = jnp.asarray(
                np.zeros((self.slots, self.spec_k), np.int32)
            )
            if self._spec.draft is not None:
                k, v, drafts = self._spec.draft(
                    k, v, self._params, z_i, z_i, z_b
                )
                jax.block_until_ready(drafts)
                marks.mark("draft")
            k, v, _, acc = self._spec.verify(
                k, v, self._params, z_i, z_i, drafts, z_i, z_b, z_f,
                self._rng, np.int32(0),
            )
            nxt = acc
            jax.block_until_ready(nxt)
            marks.mark("verify")
        del k, v
        self._k, self._v = jax.block_until_ready(self._fresh_pool())
        self._trace_snapshot = self._all_trace_counts()
        self._end_warmup(marks)

    def _end_warmup(self, marks: _PhaseMarks) -> None:
        marks.mark("reset_pool")
        self.warmup_s = marks.emit("serving.warmup")

    def step(self) -> List[Request]:
        """One scheduler iteration: admissions, the prefill chunks
        the scheduler picks (none, one, or two of the oldest prompt
        while another waits behind it: ``Scheduler.pick_prefills``) and
        one ragged decode step LAUNCHED, then the tokens of what the
        previous iteration launched fetched and handed out, so the
        device holds its next programs while the host commits, accounts
        and prepares (one step in flight, docs/DESIGN.md §29).
        Returns the requests whose last token arrived in THIS iteration
        (tokens fully populated).

        With a Tracer armed the iteration also times itself: one
        ``local`` span ``serving.step`` whose ``phases`` (STEP_PHASES)
        tile it, with the counts taken where the work happens.
        Disarmed, the ``is not None`` checks are the whole cost."""
        t0 = time.monotonic()
        tracer = tracing.active_tracer()
        st = self._step_trace = (
            _StepTrace(t0) if tracer is not None else None
        )
        sch = self.scheduler
        finished = self._done  # a drain between steps may have begun it
        self._cur = _Flight()
        self._iter_advance = []
        for req in sch.shed_expired(t0):
            # Past-deadline queued work is an explicit terminal outcome,
            # surfaced through step()'s return like any completion.
            self._report_shed(req, finished)
        admitted = sch.admit(t0)
        for req in admitted:
            self._admit_slot(req)
            if req.requeues == 0:
                # Re-admission after a step-error requeue is not a new
                # request: counting it again would skew done/admitted
                # completion-rate dashboards.
                self.metrics.requests.inc(outcome="admitted")
        for req in sch.drain_admission_shed():
            # Deadline lapsed while waiting for a free slot: shed at
            # the admission decision, same terminal surface.
            self._report_shed(req, finished)
        self._mark("admit", "n_admitted", len(admitted))
        status = "ok"
        try:
            fault_point("serving.step.error", step_idx=self._step_idx)
            # Two chunk launches back to back are what one a step
            # already is ACROSS steps (§29): host state (``prefill_pos``,
            # ``_lengths``, the slot's blocks) advances at the launch,
            # the pools chain the programs by data dependence, and a
            # launch takes a copy of its table row. No launch follows a
            # prompt's last chunk, so ``_cur`` holds one ``first``.
            chunks = sch.pick_prefills()
            for pf in chunks:
                self._run_prefill_chunk(pf, finished)
            if len(chunks) > 1:
                self.metrics.two_chunk_steps.inc()
            decoding = sch.decoding()
            if decoding:
                self._run_decode(decoding, finished)
            if self._flight is not None:
                self._fetch_commit(self._flight, finished)
            self._flight = self._cur or None
        except Exception as e:  # noqa: BLE001 — device/XLA errors vary
            # A launch's error may surface here or at its fetch, an
            # iteration later: either way nothing on the device is kept.
            self._recover_from_step_error(e, finished)
            self._iter_advance = []
            status = "error"
        self._cur = None
        self._done = []
        idx = self._step_idx
        self._step_idx += 1
        self.metrics.queue_depth.set(len(sch.queue))
        for name, depth in sch.queue_depth_by_class().items():
            self.metrics.class_queue_depth.set(depth, slo_class=name)
        self.metrics.active_slots.set(len(sch.active()))
        self._sync_pool_metrics()
        retraces = self._sync_retrace_metric()
        if self._iter_advance:
            # One observation PER EMITTED TOKEN at the per-token cost,
            # not one per iteration at the full wall time — a verify
            # step committing 4 tokens per slot must read as 4 fast
            # tokens, or spec decode would look SLOWER per token the
            # better it performs.
            dt = time.monotonic() - t0
            per_tok = dt / sum(self._iter_advance)
            for adv in self._iter_advance:
                for _ in range(adv):
                    self.metrics.token_latency.observe(per_tok)
        if st is not None:
            self._step_trace = None
            st.mark("account")
            attrs = dict(st.counts, idx=idx, phases=st.phases,
                         n_finished=len(finished))
            if retraces:
                attrs["retraces"] = retraces  # THIS step recompiled
            tracer.record_span(
                "serving.step", st.t0, st.last, attrs=attrs,
                status=status, local=True,
            )
        return finished

    def _mark(self, phase: str, count: Optional[str] = None,
              value: int = 0, at: Optional[float] = None) -> None:
        """Armed steps only: close step phase ``phase`` here (``at``: a
        clock read the site already took) and note a count with it."""
        st = self._step_trace
        if st is not None:
            st.mark(phase, at)
            if count is not None:
                st.counts[count] = value

    def _mark_prefill_prep(self, n_valid: int, kv_rows: int) -> None:
        """Close ``prefill_prep`` with what the chunk launch carries:
        its ``prefill_tokens`` and ``prefill_kv_rows``, the cache rows
        its attention has a use for (the slot's fill below the chunk
        plus the chunk; over ``max_len`` the share of the logical view,
        as ``kv_rows`` is for the decode launch). Both are those of ONE
        launch, the step's last: their readers pair them with a
        per-launch device time. What the step's ``prefill_chunks``
        launches prefilled together is ``prefill_rows``."""
        st = self._step_trace
        if st is not None:
            st.mark("prefill_prep")
            st.counts["prefill_chunks"] += 1
            st.counts["prefill_rows"] += n_valid
            st.counts["prefill_tokens"] = n_valid
            st.counts["prefill_kv_rows"] = kv_rows

    def _mark_decode_prep(self, decoding: List[Request],
                          at: Optional[float] = None) -> None:
        """Close ``decode_prep`` with what the launch will carry: the
        slots in it and ``kv_rows``, the cache rows visible to them
        (over ``n_decoding * max_len`` it is the share of the logical
        view that attention has any use for)."""
        st = self._step_trace
        if st is not None:
            st.mark("decode_prep", at)
            st.counts["n_decoding"] = len(decoding)
            fills = [int(self._lengths[r.slot]) for r in decoding]
            st.counts["kv_rows"] = sum(fills)
            if self._index_topk:
                # The K/V rows the launch reads: a slot's selection.
                st.counts["selected_rows"] = sum(
                    min(f, self._index_topk) for f in fills
                )

    def run_until_idle(self, max_iters: int = 100000) -> List[Request]:
        """Drive step() until nothing is pending; returns all finished."""
        done: List[Request] = []
        for _ in range(max_iters):
            if not self.pending():
                return done
            done.extend(self.step())
        raise RuntimeError(
            f"engine did not drain within {max_iters} iterations"
        )

    # ---- pool hooks (overridden by the paged engine, serving/kvpool) -------

    def _admit_slot(self, req: Request) -> None:
        """Bind engine-side per-slot state for a freshly admitted
        request. A recycled slot starts from fill 0: stale KV above the
        cursor is invisible and rewritten before visibility."""
        self._lengths[req.slot] = 0
        self._tokens[req.slot] = 0
        self._temps[req.slot] = req.temperature

    def _release_slot(self, req: Request, slot: int) -> None:
        """A request left its slot (finish/cancel). The flat pool has
        nothing to reclaim — stale rows are invisible; the paged engine
        returns the slot's blocks to the allocator here."""

    def _reset_pool(self) -> None:
        """Rebuild ALL device-side cache state after a failed step call
        (donated buffers may be invalidated)."""
        self._k, self._v = self._fresh_pool()

    def _sync_pool_metrics(self) -> None:
        """Per-iteration pool gauges; the flat pool has none beyond the
        slot gauges step() already sets."""

    def _report_shed(self, req: Request, finished: List[Request]) -> None:
        finished.append(req)
        self.metrics.shed.inc(reason="deadline", slo_class=req.slo_class)
        self.metrics.requests.inc(outcome="shed")
        self.metrics.failures.inc(reason="deadline")
        self._emit_request_spans(req, status="error")

    # ---- internals ---------------------------------------------------------

    def _recover_from_step_error(self, err: BaseException,
                                 finished: List[Request]):
        """A compiled step raised (device fault, XLA error, injected
        chaos). The donated KV slabs may have been invalidated by the
        failed call, so NOTHING cached on device survives: rebuild the
        pool and return every in-flight request (in a slot, or out of
        it with its last tokens not yet fetched) to the front of the
        queue to restart from scratch. A request that keeps landing in
        a raising step is EXPLICITLY failed after ``max_requeues``
        restarts — admitted work is never silently lost, and a
        persistent error cannot livelock the serve loop. Failed
        requests surface through ``finished`` with ``failed=True``."""
        # Progress about to be reset IS the wasted work: prompt rows
        # already prefilled and tokens already decoded replay from
        # scratch (§34 useful-token accounting).
        leaving = list(self._leaving.values())
        active = self.scheduler.active() + leaving
        wasted_prefill = sum(r.prefill_pos for r in active)
        wasted_decode = sum(len(r.tokens) for r in active)
        requeued = self.scheduler.requeue_active(released=leaving)
        # The step in flight goes with the pool it ran on.
        self._flight = None
        self._cur = _Flight() if self._cur is not None else None
        self._leaving.clear()
        self._reset_pool()
        self._lengths[:] = 0
        self._tokens[:] = 0
        self._temps[:] = 0.0
        self.metrics.step_errors.inc()
        if wasted_prefill:
            self.metrics.tokens_wasted.inc(wasted_prefill, kind="prefill")
        if wasted_decode:
            self.metrics.tokens_wasted.inc(wasted_decode, kind="decode")
        failed = 0
        for req in requeued:
            if req.requeues > self.max_requeues:
                try:
                    self.scheduler.queue.remove(req)
                except ValueError:
                    pass
                req.failed = True
                req.failure_reason = "requeue_budget"
                self.scheduler.finish(req)
                finished.append(req)
                self._emit_request_spans(req, status="error")
                failed += 1
                self.metrics.requests.inc(outcome="failed")
                self.metrics.failures.inc(reason="requeue_budget")
            else:
                self.metrics.requests.inc(outcome="requeued")
        self.metrics.annotate(
            "serving_step_error",
            error=f"{type(err).__name__}: {err}"[:200],
            requeued=len(requeued) - failed, failed=failed,
        )
        logger.warning(
            "serving step raised (%s: %s); pool rebuilt, %d in-flight "
            "request(s) re-queued, %d explicitly failed",
            type(err).__name__, err, len(requeued) - failed, failed,
        )

    def _run_prefill_chunk(self, req: Request, finished: List[Request]):
        c = self.prefill_chunk
        start = req.prefill_pos
        n_valid = min(c, req.prompt_len - start)
        chunk = np.zeros((1, c), np.int32)
        chunk[0, :n_valid] = req.prompt[start:start + n_valid]
        self._mark_prefill_prep(n_valid, start + n_valid)
        self._k, self._v, first = self._steps.prefill(
            self._k, self._v, self._params, jnp.asarray(chunk),
            np.int32(req.slot), np.int32(start), np.int32(n_valid),
            np.float32(req.temperature), self._rng,
            np.int32(self._step_idx),
        )
        self._mark("prefill_launch")
        req.prefill_pos += n_valid
        self._lengths[req.slot] = req.prefill_pos
        self.metrics.tokens.inc(n_valid, kind="prefill")
        if req.prefill_pos < req.prompt_len:
            return  # more chunks to come; `first` is discarded unfetched
        self._launched_first(req, first)

    def _launched_first(self, req: Request, first) -> None:
        """A prompt's LAST chunk is enqueued: its first token is in
        flight, and by count the request decodes from this iteration's
        launch on (the token goes there on the device), or, asked for
        one token only, is done but for the fetch."""
        req.inflight += 1
        req.state = DECODE
        end = "finished" if req.max_new_tokens <= 1 else None
        self._cur.first = first
        self._cur.first_row = (req, req.slot, end)
        if end:
            self._vacate(req)

    def _vacate(self, req: Request) -> None:
        """The request's last launch is enqueued: slot (and blocks) go
        to the next request now, its completion waits for the fetch."""
        slot = req.slot
        self.scheduler.release(req)
        self._release_slot(req, slot)
        self._leaving[req.rid] = req

    def _run_decode(self, decoding: List[Request],
                    finished: List[Request]):
        if self.spec_k:
            # The speculative path drafts from the committed tokens.
            self._drain("spec_k")
            self._run_decode_spec(decoding, finished)
            return
        active = np.zeros(self.slots, bool)
        for r in decoding:
            active[r.slot] = True
        self._mark_decode_prep(decoding)
        self._k, self._v, nxt = self._steps.decode(
            self._k, self._v, self._params,
            _h2d(self._lengths), self._fed_tokens(),
            jnp.asarray(active), _h2d(self._temps),
            self._rng, np.int32(self._step_idx), *self._fed_first(),
        )
        self._mark("decode_launch")
        self._launched_decode(decoding, nxt)

    def _fed_tokens(self):
        """The decode launch's token vector: the one the launch in
        flight returns, still on the device, else the host's."""
        prev = self._flight
        if prev is None or prev.nxt is None:
            return _h2d(self._tokens)
        if self._step_trace is not None:
            self._step_trace.counts["overlapped"] = 1
        return prev.nxt

    def _fed_first(self):
        """``(first, first_slot)`` of the decode launch: this
        iteration's last-chunk token and the slot that decodes it."""
        cur = self._cur
        if cur.first is None or cur.first_row[2]:
            return self._no_first, np.int32(-1)
        return cur.first, np.int32(cur.first_row[1])

    def _launched_decode(self, decoding: List[Request], nxt,
                         aux=None) -> None:
        """The decode launch is enqueued: advance every slot in it by
        the row its fed token lands in, and settle BY COUNT who decodes
        on. A request whose token in flight is its last (of
        ``max_new_tokens``, or with no row left to feed it back) leaves
        its slot here. ``aux``: what the program returned after its
        tokens (kvpool/sparse.py), fetched with them."""
        cur = self._cur
        cur.nxt = nxt
        cur.aux = aux
        for r in decoding:
            slot = r.slot
            self._lengths[slot] += 1   # the fed token's KV lands
            r.inflight += 1
            end = None
            if len(r.tokens) + r.inflight >= r.max_new_tokens:
                end = "finished"
            elif self._lengths[slot] + 1 > self.max_len:
                end = "truncated"      # no room to feed this token
            cur.rows.append((r, slot, end))
            if end:
                self._vacate(r)

    def _fetch_commit(self, flight: _Flight,
                      finished: List[Request]) -> None:
        """Fetch one iteration's tokens (blocks on the device) and hand
        them out: the first token of the prompt it finished, one token
        to every request its decode launch carried. First-token time is
        stamped here, when the host holds the token."""
        nxt, first, *aux = jax.device_get(
            (flight.nxt, flight.first) if flight.aux is None
            else (flight.nxt, flight.first, flight.aux)
        )
        if aux:
            (aux,) = aux
            # [experts hit (mean over layers), expert rows dropped] of
            # the launch whose tokens arrive here.
            self._moe_rows_dropped += int(aux[1])
            if self._step_trace is not None:
                self._step_trace.counts["experts_hit"] = float(aux[0])
                self._step_trace.counts["expert_rows_dropped"] = int(aux[1])
        if flight.first_row is not None:
            req, slot, end = flight.first_row
            req.first_token_ts = time.monotonic()
            self._mark(
                "prefill_fetch" if nxt is None else "decode_fetch",
                at=req.first_token_ts,
            )
            if req.requeues == 0:
                # A re-run after a step-error requeue would re-observe
                # an inflated first-token latency for the same request.
                self.metrics.ttft.observe(req.ttft_s)
            self._commit_token(req, slot, int(first), end, finished)
        else:
            self._mark("decode_fetch")
        for r, slot, end in flight.rows:
            self._commit_token(r, slot, int(nxt[slot]), end, finished)
            self._iter_advance.append(1)
        self._mark("commit")

    def _commit_token(self, req: Request, slot: int, tok: int,
                      end: Optional[str], finished: List[Request]):
        req.tokens.append(tok)
        req.inflight -= 1
        self.metrics.tokens.inc(kind="decode")
        if end:
            req.truncated = end == "truncated"
            self._finish(req, finished)
        else:
            self._tokens[slot] = tok

    def _drain(self, reason: str) -> None:
        """Fetch and commit every launch still in flight, so that the
        host's state is the committed state: called where the code can
        see it needs that (the speculative path, a preemption, a
        cancellation, a migration). Inside step() a failed fetch is the
        step's error; between steps it is recovered from here, and what
        finishes waits in ``_done`` for the next step()."""
        flights = [f for f in (self._flight, self._cur) if f]
        if not flights:
            return
        self.metrics.pipeline_drains.inc(reason=reason)
        try:
            for f in flights:
                self._fetch_commit(f, self._done)
        except Exception as e:  # noqa: BLE001 — device/XLA errors vary
            if self._cur is not None:
                raise
            self._recover_from_step_error(e, self._done)
            return
        self._flight = None
        if self._cur is not None:
            self._cur = _Flight()

    # ---- speculative decode (§35) ------------------------------------------

    def _run_decode_spec(self, decoding: List[Request],
                         finished: List[Request]):
        """One draft → verify → commit iteration for every decoding
        slot. The verify program replaces the decode program entirely
        while spec is on (draft_len 0 degenerates to plain one-token
        decode), so variable per-slot acceptance is just a ragged fill
        advance — the SAME continuous-batching law as everything else,
        zero retraces. Rollback of rejected drafts is the fill NOT
        advancing past them."""
        decoding = self._spec_prepare_rows(decoding)
        if not decoding:
            return
        active = np.zeros(self.slots, bool)
        for r in decoding:
            active[r.slot] = True
        t_d = time.monotonic()
        self._mark_decode_prep(decoding, at=t_d)
        drafts, draft_len = self._spec_draft(decoding, active)
        t_v = time.monotonic()
        self._mark("spec_draft", at=t_v)
        emitted, acc = self._spec_verify_device(active, drafts,
                                                draft_len)
        emitted = np.asarray(jax.device_get(emitted))
        acc = np.asarray(jax.device_get(acc))
        t_e = time.monotonic()
        self._mark("spec_verify", at=t_e)
        n_dec = len(decoding)
        d_dt = (t_v - t_d) / n_dec
        v_dt = (t_e - t_v) / n_dec
        for r in decoding:
            r.draft_s += d_dt
            r.verify_s += v_dt
        for r in decoding:
            s = r.slot
            n_acc = int(acc[s])
            dl = int(draft_len[s])
            toks = [int(t) for t in emitted[s, : n_acc + 1]]
            # All T rows' KV landed; only the accepted prefix plus the
            # final token become visible — the rest sits beyond the
            # fill (free rollback).
            self._lengths[s] += n_acc + 1
            r.tokens.extend(toks)
            self._tokens[s] = toks[-1]
            r.spec_drafted += dl
            r.spec_accepted += n_acc
            self.metrics.tokens.inc(n_acc + 1, kind="decode")
            if dl:
                self.metrics.spec_tokens.inc(dl, kind="drafted")
                if n_acc:
                    self.metrics.spec_tokens.inc(n_acc, kind="accepted")
                if dl - n_acc:
                    self.metrics.spec_tokens.inc(dl - n_acc,
                                                 kind="rejected")
            self._spec_emitted += n_acc + 1
            self._spec_slot_steps += 1
            self._iter_advance.append(n_acc + 1)
            if len(r.tokens) >= r.max_new_tokens:
                self._finish(r, finished)
            elif self._lengths[s] + 1 > self.max_len:
                # No room to feed the final token back.
                r.truncated = True
                self._finish(r, finished)
        self.metrics.spec_tokens_per_step.set(
            self._spec_emitted / self._spec_slot_steps
        )
        self._mark("commit")

    def _spec_prepare_rows(self, decoding: List[Request]):
        """Make rows fill..fill+spec_k writable for every decoding
        slot. The flat slab always has them (each slot owns [max_len]
        rows); the paged engine allocates/privatizes blocks here and
        may preempt."""
        return decoding

    def _spec_draft(self, decoding: List[Request], active):
        """Propose up to spec_k tokens per slot. Returns
        ``(drafts [slots, K], draft_len np[slots])`` — drafts may live
        on device (early exit) or host (n-gram)."""
        K = self.spec_k
        draft_len = np.zeros(self.slots, np.int32)
        caps = {
            r.slot: spec_lib.clamp_draft_len(
                K, len(r.tokens), r.max_new_tokens,
                int(self._lengths[r.slot]), self.max_len,
            )
            for r in decoding
        }
        if self.spec_drafter == "early_exit":
            drafts = self._spec_draft_device(active)
            # The device drafter always proposes K tokens; the clamp
            # rides in draft_len (acceptance never crosses it).
            jax.block_until_ready(drafts)  # honest draft/verify split
            for s, cap in caps.items():
                draft_len[s] = cap
            return drafts, draft_len
        drafts_np = np.zeros((self.slots, K), np.int32)
        window = _NGRAM_WINDOW
        for r in decoding:
            s = r.slot
            cap = caps[s]
            if cap <= 0:
                continue
            # Bounded lookback: the rightmost match is what wins, and
            # the motifs worth speculating on recur within a short
            # horizon — an unbounded concat would make the host draft
            # cost grow with sequence length every step.
            toks = r.tokens
            if len(toks) >= window:
                hist = np.asarray(toks[-window:], np.int32)
            else:
                hist = np.concatenate([
                    np.asarray(
                        r.prompt[-(window - len(toks)):], np.int32
                    ),
                    np.asarray(toks, np.int32),
                ])
            prop = spec_lib.propose_ngram(hist, cap)
            n = min(len(prop), cap)
            drafts_np[s, :n] = prop[:n]
            draft_len[s] = n
        return drafts_np, draft_len

    def _spec_draft_device(self, active):
        self._k, self._v, drafts = self._spec.draft(
            self._k, self._v, self._params,
            jnp.asarray(self._lengths), jnp.asarray(self._tokens),
            jnp.asarray(active),
        )
        return drafts

    def _spec_verify_device(self, active, drafts, draft_len):
        self._k, self._v, emitted, acc = self._spec.verify(
            self._k, self._v, self._params,
            jnp.asarray(self._lengths), jnp.asarray(self._tokens),
            jnp.asarray(drafts), jnp.asarray(draft_len),
            jnp.asarray(active), jnp.asarray(self._temps),
            self._rng, np.int32(self._step_idx),
        )
        return emitted, acc

    def _finish(self, req: Request, finished: List[Request]):
        # A request that left its slot at its last launch
        # (:meth:`_vacate`) has none to release.
        if self._leaving.pop(req.rid, None) is None and req.slot >= 0:
            self._release_slot(req, req.slot)
        self.scheduler.finish(req)
        finished.append(req)
        self.metrics.requests.inc(
            outcome="truncated" if req.truncated else "finished"
        )
        self._emit_request_spans(req)

    def _emit_request_spans(self, req: Request, status: str = "ok"):
        """Retrospective phase tree for one terminal request: queue-wait
        / prefill / decode cut at the timestamps the scheduler already
        records, contiguous by construction so their durations sum to
        the request's e2e latency (the §29 trace invariant). Disarmed:
        one global check — zero per-iteration cost in the step loop."""
        tracer = tracing.active_tracer()
        if tracer is None:
            return
        finish = (
            req.finish_ts if req.finish_ts is not None
            else time.monotonic()
        )
        root = tracer.record_span(
            "serving.request", req.submit_ts, finish,
            kind="server", parent=req.trace,
            attrs={
                "rid": req.rid,
                "prompt_len": req.prompt_len,
                "new_tokens": len(req.tokens),
                "truncated": req.truncated,
                "requeues": req.requeues,
                "failure_reason": req.failure_reason,
                "slo_class": req.slo_class,
                "prefix_hit_blocks": req.prefix_hit_blocks,
            },
            status=status,
        )
        if req.admit_ts is None:
            # Never reached a slot (shed / failed while queued): the
            # whole life was queue wait.
            tracer.record_span(
                "serving.queue_wait", req.submit_ts, finish,
                parent=root, status=status,
            )
            return
        tracer.record_span(
            "serving.queue_wait", req.submit_ts, req.admit_ts,
            parent=root,
        )
        if req.first_token_ts is None:
            tracer.record_span(
                "serving.prefill", req.admit_ts, finish,
                parent=root, status=status,
            )
            return
        tracer.record_span(
            "serving.prefill", req.admit_ts, req.first_token_ts,
            parent=root, attrs=dict(
                {"prompt_len": req.prompt_len},
                **({"prefix_rounded_down_blocks":
                    req.prefix_rounded_down_blocks}
                   if req.prefix_rounded_down_blocks else {}),
                **({"window_blocks_released": req.window_blocks_released}
                   if req.window_blocks_released else {}),
            ),
        )
        decode_start = req.first_token_ts
        if req.migrate_end_ts is not None:
            # Migrated request (kvpool/migrate, §36): the migrate
            # window sits between the source-side prefill and the
            # local decode. Decode the SOURCE ran before a live-drain
            # export gets its own contiguous segment so the children
            # still tile the request end to end (the §29 invariant).
            m_end = min(req.migrate_end_ts, finish)
            m_start = req.migrate_start_ts
            if m_start is None or m_start < req.first_token_ts:
                m_start = req.first_token_ts
            m_start = min(m_start, m_end)
            if m_start - req.first_token_ts > 1e-6:
                tracer.record_span(
                    "serving.decode", req.first_token_ts, m_start,
                    parent=root, attrs={"segment": "pre_migrate"},
                )
            tracer.record_span(
                "serving.migrate", m_start, m_end, parent=root,
                attrs={"pause_s": round(m_end - m_start, 6)},
            )
            decode_start = m_end
        decode_span = tracer.record_span(
            "serving.decode", decode_start, finish,
            parent=root, attrs={"new_tokens": len(req.tokens)},
        )
        if req.verify_s > 0.0:
            # Spec decode splits the decode phase into draft / verify
            # sub-spans (per-slot shares of the iteration wall time,
            # laid contiguously — durations are the signal, not the
            # absolute placement).
            td = min(decode_start + req.draft_s, finish)
            tv = min(td + req.verify_s, finish)
            tracer.record_span(
                "serving.decode.draft", decode_start, td,
                parent=decode_span,
                attrs={"spec_drafted": req.spec_drafted},
            )
            tracer.record_span(
                "serving.decode.verify", td, tv,
                parent=decode_span,
                attrs={"spec_accepted": req.spec_accepted},
            )

    def _sync_retrace_metric(self) -> int:
        """Count programs traced since the last call; returns that
        delta so an armed step can say it is the one that recompiled."""
        now = self._all_trace_counts()
        delta = sum(now.values()) - sum(self._trace_snapshot.values())
        if delta > 0:
            self.metrics.retraces.inc(delta)
            self._trace_snapshot = dict(now)
        return delta



def _slab_dtype(config):
    """The slot engine's K/V slabs' dtype; a model that keeps anything
    else than K and V a token is refused by name (down here: the file is
    line-neutral above)."""
    if getattr(config, "state_rows", None):
        raise ValueError(
            "a model with per-slot state ("
            + ", ".join(name for name, _ in config.state_rows)
            + ") is served by PagedServingEngine alone: the slot engine "
            "(models/generate.py's slabs) holds K and V a token and "
            "nothing a slot"
        )
    return config.compute_dtype
