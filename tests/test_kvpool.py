"""Paged KV memory plane (§31): allocator alloc/free/refcount/COW
properties, paged ragged decode token-exact vs the flat pool, prefix
cache hits actually skipping prefill, recycled blocks leaking no KV,
zero retraces across admissions with varying block tables, SLO-class
weighted-fair admission + admission-time deadline sheds, and the paged
Pallas decode kernel's parity through a shuffled block table."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dlrover_tpu.models import llama
from dlrover_tpu.serving import ServingEngine, Scheduler, SloClass
from dlrover_tpu.serving.kvpool import (
    BlockAllocator,
    BlockPoolExhausted,
    PagedServingEngine,
    PrefixCache,
)
from tests.greedy_reference import naive_greedy

pytestmark = pytest.mark.kvpool


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.tiny_config()
    params, _ = llama.init_params(cfg, jax.random.key(0))
    return cfg, params


def make_prompts(cfg, lens, seed=0):
    rs = np.random.RandomState(seed)
    return [
        rs.randint(0, cfg.vocab_size, size=n).astype(np.int32)
        for n in lens
    ]


# ---- allocator properties ---------------------------------------------------


def test_allocator_alloc_free_conservation():
    a = BlockAllocator(9, reserved=1)
    assert a.managed == 8
    got = a.alloc(3)
    assert len(got) == 3 and 0 not in got
    assert a.free_count() == 5
    for b in got:
        assert a.refcount(b) == 1
        assert a.decref(b)            # freed
    assert a.free_count() == 8
    a.check()
    with pytest.raises(ValueError):
        a.decref(got[0])              # double free raises


def test_allocator_all_or_nothing_exhaustion():
    a = BlockAllocator(5, reserved=1)
    a.alloc(3)
    with pytest.raises(BlockPoolExhausted):
        a.alloc(2)                    # only 1 free: nothing granted
    assert a.free_count() == 1
    a.check()


def test_allocator_refcount_and_cow():
    a = BlockAllocator(6, reserved=1)
    (b,) = a.alloc(1)
    # Sole owner: ensure_private is the identity, no copy.
    same, copied = a.ensure_private(b)
    assert same == b and not copied
    a.incref(b)                       # a second owner appears
    new, copied = a.ensure_private(b)
    assert copied and new != b
    assert a.refcount(b) == 1         # the other owner keeps the old
    assert a.refcount(new) == 1
    assert a.cow_copies_total == 1
    a.check()


def test_allocator_stats_split_used_vs_cached():
    a = BlockAllocator(8, reserved=1)
    blocks = a.alloc(4)
    stats = a.stats(live_blocks=blocks[:3])
    assert stats == {
        "total": 7, "free": 3, "used": 3, "cached": 1,
        "min_ref": 1, "negative_refs": 0,
    }


# ---- prefix cache properties ------------------------------------------------


def test_prefix_cache_insert_lookup_refcounts():
    a = BlockAllocator(17, reserved=1)
    cache = PrefixCache(a, block_size=4)
    prompt = np.arange(10, dtype=np.int32)     # 2 full blocks + tail
    blocks = a.alloc(3)
    assert cache.insert(prompt, blocks[:2]) == 2   # tail never cached
    assert a.refcount(blocks[0]) == 2              # slot + cache
    hit = cache.lookup(prompt)
    assert hit == blocks[:2]
    assert a.refcount(blocks[0]) == 3              # + the new borrower
    # A diverging prompt shares only the common full blocks.
    other = prompt.copy()
    other[6] += 1                                  # diverge in block 1
    assert cache.lookup(other) == blocks[:1]
    # Unrelated prompt: clean miss.
    assert cache.lookup(np.arange(100, 108, dtype=np.int32)) == []
    assert cache.hits_total == 2 and cache.misses_total == 1


def test_prefix_cache_leaf_first_eviction_frees_blocks():
    a = BlockAllocator(17, reserved=1)
    cache = PrefixCache(a, block_size=4)
    prompt = np.arange(12, dtype=np.int32)         # 3 full blocks
    blocks = a.alloc(3)
    cache.insert(prompt, blocks)
    for b in blocks:
        a.decref(b)                                # slot released
    assert a.stats()["cached"] == 3
    # One eviction takes the LEAF (block 2), never an interior entry.
    assert cache.evict_lru(1) == 1
    assert a.refcount(blocks[2]) == 0              # freed
    assert a.refcount(blocks[0]) == 1              # chain head intact
    assert cache.lookup(prompt) == blocks[:2]      # prefix still hits
    for b in blocks[:2]:
        a.decref(b)
    cache.clear()
    a.check()
    assert a.free_count() == a.managed


def test_relief_eviction_skips_leaves_a_slot_still_holds():
    """The allocator's relief valve asks for blocks that FREE: a leaf a
    live slot also references frees nothing, and dropping it would
    uncover its parent, so a dry pool ate a long shared chain from the
    tail while its readers kept every block allocated."""
    a = BlockAllocator(17, reserved=1)
    cache = PrefixCache(a, block_size=4)
    shared = np.arange(12, dtype=np.int32)         # a 3-block document
    doc = a.alloc(3)
    cache.insert(shared, doc)                      # a live slot holds it
    done = np.arange(100, 108, dtype=np.int32)     # a finished request's
    old = a.alloc(2)
    cache.insert(done, old)
    for b in old:
        a.decref(b)                                # ... slot released
    free = a.free_count()
    # Oldest-first without the flag takes the document's tail although
    # a slot reads it; nothing is freed.
    assert cache.evict_lru(1) == 1
    assert a.free_count() == free and a.refcount(doc[2]) == 1
    assert cache.lookup(shared) == doc[:2]         # the chain got shorter
    for b in doc[:2]:
        a.decref(b)
    # The relief valve passes over the held chain and frees the finished
    # request's blocks, leaf first; then it has nothing left to take.
    assert cache.evict_lru(5, must_free=True) == 2
    assert a.free_count() == free + 2
    assert [a.refcount(b) for b in doc[:2]] == [2, 2]
    assert cache.evict_lru(1, must_free=True) == 0
    assert cache.cached_entries == 2


# ---- paged engine: exactness, reuse, retraces -------------------------------


def test_paged_ragged_decode_matches_flat_and_teacher_forced(tiny):
    """The ISSUE acceptance bar: same staggered ragged workload through
    the flat engine and the paged engine (greedy) — token-exact against
    each other AND the teacher-forced reference."""
    cfg, params = tiny
    prompts = make_prompts(cfg, (5, 3, 9), seed=1)
    plans = list(zip(prompts, (6, 5, 4)))

    def run(engine):
        reqs = [engine.submit(prompts[0], 6)]
        for _ in range(4):
            engine.step()
        reqs.append(engine.submit(prompts[1], 5))
        reqs.append(engine.submit(prompts[2], 4))
        engine.run_until_idle()
        return [r.tokens for r in reqs]

    flat = ServingEngine(cfg, params, slots=2, max_len=32,
                         prefill_chunk=4)
    flat.warmup()
    paged = PagedServingEngine(cfg, params, slots=2, max_len=32,
                               prefill_chunk=4, block_size=8)
    paged.warmup()
    flat_tokens = run(flat)
    paged_tokens = run(paged)
    assert paged_tokens == flat_tokens
    for tokens, (prompt, max_new) in zip(paged_tokens, plans):
        assert tokens == naive_greedy(cfg, params, prompt, max_new)
    paged.check_block_invariants()


def test_prefix_cache_hit_skips_prefill_and_stays_exact(tiny):
    """A repeated prompt must HIT (prefill chunks skipped — measured by
    the engine's prefill-token counter), decode the exact same greedy
    tokens, and leave the allocator conserved."""
    cfg, params = tiny
    eng = PagedServingEngine(cfg, params, slots=2, max_len=32,
                             prefill_chunk=4, block_size=8)
    eng.warmup()
    (prompt,) = make_prompts(cfg, (17,), seed=3)   # 2 full blocks + 1
    ref = naive_greedy(cfg, params, prompt, 5)
    r1 = eng.submit(prompt, 5)
    eng.run_until_idle()
    assert r1.tokens == ref and r1.prefix_hit_blocks == 0
    first_prefill = eng.metrics.tokens.value(kind="prefill")
    r2 = eng.submit(prompt, 5)
    eng.run_until_idle()
    assert r2.tokens == ref
    assert r2.prefix_hit_blocks == 2
    resumed_prefill = (
        eng.metrics.tokens.value(kind="prefill") - first_prefill
    )
    # 17-token prompt, 16 covered, resume at 16 (chunk-aligned): only
    # the final 1-valid-token chunk re-runs.
    assert resumed_prefill < first_prefill
    assert resumed_prefill == 1
    eng.check_block_invariants()


def test_cow_privatizes_shared_block_on_rewrite(tiny):
    """A fully-cached block-aligned prompt re-runs its last chunk (the
    first token must be re-sampled) INTO a shared block: the write must
    COW, both requests stay exact, refcounts stay sane."""
    cfg, params = tiny
    eng = PagedServingEngine(cfg, params, slots=2, max_len=32,
                             prefill_chunk=4, block_size=8)
    eng.warmup()
    (prompt,) = make_prompts(cfg, (8,), seed=5)    # exactly one block
    ref = naive_greedy(cfg, params, prompt, 5)
    r1 = eng.submit(prompt, 5)
    eng.run_until_idle()
    r2 = eng.submit(prompt, 5)
    eng.run_until_idle()
    assert r1.tokens == ref and r2.tokens == ref
    assert eng.kv_stats()["cow_copies"] >= 1
    eng.check_block_invariants()


def test_recycled_block_does_not_leak_kv(tiny):
    """Blocks freed by a long request and re-allocated to a short one
    must not leak the previous occupant's KV (cache disabled so reuse
    is guaranteed)."""
    cfg, params = tiny
    eng = PagedServingEngine(cfg, params, slots=1, max_len=32,
                             prefill_chunk=8, block_size=8,
                             prefix_cache=False)
    eng.warmup()
    long_p, short_p = make_prompts(cfg, (12, 3), seed=2)
    r_long = eng.submit(long_p, 12)
    eng.run_until_idle()
    assert r_long.state == "done" and len(r_long.tokens) == 12
    assert eng.kv_stats()["free"] == eng.num_blocks - 1  # all recycled
    r_short = eng.submit(short_p, 6)
    eng.run_until_idle()
    assert r_short.tokens == naive_greedy(cfg, params, short_p, 6)
    eng.check_block_invariants()


def test_no_retrace_across_admissions_with_varying_tables(tiny):
    """After warmup, admissions with new prompt lengths, temperatures,
    prefix hits, COW copies, and block churn must trace NOTHING — every
    dynamic quantity (tables included) is a traced argument."""
    cfg, params = tiny
    eng = PagedServingEngine(cfg, params, slots=2, max_len=32,
                             prefill_chunk=4, block_size=8)
    eng.warmup()
    base = dict(eng.trace_counts)
    rs = np.random.RandomState(3)
    for plen, mnew, temp in (
        (2, 3, 0.0), (8, 2, 0.9), (11, 5, 0.3), (8, 9, 1.7),
    ):
        prompt = rs.randint(0, cfg.vocab_size, plen).astype(np.int32)
        eng.submit(prompt, mnew, temperature=temp)
        # And a guaranteed repeat (hit + COW path) mid-stream.
    (prompt,) = make_prompts(cfg, (8,), seed=9)
    eng.submit(prompt, 3)
    eng.submit(prompt, 3)
    eng.run_until_idle()
    assert eng.trace_counts == base, (
        f"retraced: {eng.trace_counts} vs {base}"
    )
    eng.check_block_invariants()


def test_oversubscribed_pool_preempts_youngest_and_conserves(tiny):
    """More logical slot capacity than physical blocks: the pool runs
    dry mid-decode, the youngest request is preempted (front-requeued,
    NOT failed) and everything still completes with exact tokens."""
    cfg, params = tiny
    eng = PagedServingEngine(cfg, params, slots=4, max_len=32,
                             prefill_chunk=8, block_size=8,
                             num_blocks=10, prefix_cache=False)
    eng.warmup()
    prompts = make_prompts(cfg, (12, 12, 12, 12), seed=7)
    reqs = [eng.submit(p, 16) for p in prompts]
    eng.run_until_idle()
    assert all(r.state == "done" and not r.failed for r in reqs)
    assert eng.metrics.kv_preemptions.value() >= 1
    for req, prompt in zip(reqs, prompts):
        assert req.tokens == naive_greedy(cfg, params, prompt, 16)
    eng.check_block_invariants()
    assert eng.kv_stats()["free"] == eng.num_blocks - 1


# ---- SLO-class scheduling ---------------------------------------------------


def test_slo_weighted_fair_admission_ratio():
    """3:1 weights with both classes saturated: admissions interleave
    ~3 interactive per 1 batch, FCFS within each class."""
    classes = (SloClass("interactive", weight=3.0),
               SloClass("batch", weight=1.0))
    sch = Scheduler(slots=4, max_len=64, prefill_chunk=8,
                    slo_classes=classes)
    for i in range(8):
        sch.submit(np.zeros(4, np.int32) + i, 4,
                   slo_class="interactive")
        sch.submit(np.zeros(4, np.int32) + i, 4, slo_class="batch")
    first = sch.admit(now=1.0)
    assert [r.slo_class for r in first] == [
        "interactive", "interactive", "interactive", "batch",
    ]
    # Interactive admissions kept FCFS order.
    inter = [r for r in first if r.slo_class == "interactive"]
    assert [r.rid for r in inter] == sorted(r.rid for r in inter)
    # Drain and refill: the ratio persists across rounds.
    for r in first:
        sch.finish(r)
    second = sch.admit(now=2.0)
    assert [r.slo_class for r in second].count("interactive") == 3


def test_slo_single_class_is_fcfs():
    sch = Scheduler(slots=2, max_len=64, prefill_chunk=8)
    reqs = [sch.submit(np.zeros(4, np.int32), 4) for _ in range(3)]
    admitted = sch.admit(now=1.0)
    assert [r.rid for r in admitted] == [reqs[0].rid, reqs[1].rid]
    assert all(r.slo_class == "default" for r in admitted)


def test_slo_unknown_class_rejected():
    sch = Scheduler(slots=1, max_len=64, prefill_chunk=8)
    with pytest.raises(ValueError, match="unknown SLO class"):
        sch.submit(np.zeros(4, np.int32), 4, slo_class="platinum")


def test_slo_class_default_deadline_applies():
    classes = (SloClass("interactive", default_deadline_s=0.5),)
    sch = Scheduler(slots=1, max_len=64, prefill_chunk=8,
                    slo_classes=classes)
    req = sch.submit(np.zeros(4, np.int32), 4, now=10.0)
    assert req.deadline == pytest.approx(10.5)


def test_slo_admission_time_deadline_shed():
    """A queued request whose TTL lapses while WAITING for a slot is
    shed at the admission decision (satellite: not only at pump time),
    and the next-in-class request takes the slot instead."""
    sch = Scheduler(slots=1, max_len=64, prefill_chunk=8)
    doomed = sch.submit(np.zeros(4, np.int32), 4, now=10.0,
                        deadline_s=1.0)
    live = sch.submit(np.zeros(4, np.int32), 4, now=10.0)
    admitted = sch.admit(now=99.0)      # doomed expired while queued
    assert [r.rid for r in admitted] == [live.rid]
    shed = sch.drain_admission_shed()
    assert [r.rid for r in shed] == [doomed.rid]
    assert doomed.failed and doomed.failure_reason == "deadline"


def test_admission_gate_veto_preserves_drr_credit():
    """A block-watermark veto must not charge the selected class's
    deficit-round-robin credit: repeated vetoes under pool pressure
    would otherwise invert the configured class weights."""
    classes = (SloClass("interactive", weight=3.0),
               SloClass("batch", weight=1.0))
    sch = Scheduler(slots=4, max_len=64, prefill_chunk=8,
                    slo_classes=classes)
    for _ in range(4):
        sch.submit(np.zeros(4, np.int32), 4, slo_class="interactive")
        sch.submit(np.zeros(4, np.int32), 4, slo_class="batch")
    vetoes = {"n": 0}

    def gate(req):
        vetoes["n"] += 1
        return False

    sch.admission_gate = gate
    for _ in range(5):
        assert sch.admit(now=1.0) == []
    assert vetoes["n"] == 5
    sch.admission_gate = None
    admitted = sch.admit(now=2.0)
    # The weighted-fair ratio survives the vetoed rounds untilted.
    assert [r.slo_class for r in admitted] == [
        "interactive", "interactive", "interactive", "batch",
    ]


def test_chunk_aligned_discarded_hit_reports_as_miss(tiny):
    """A raw cache hit whose blocks are ALL discarded by chunk
    alignment saved nothing: kv_stats must report it as a miss (the
    review finding — raw cache counters overstate the win)."""
    cfg, params = tiny
    # chunk 16 > block 8: a 1-block hit on a 9-token prompt aligns
    # start to 0 — the whole hit is discarded.
    eng = PagedServingEngine(cfg, params, slots=2, max_len=32,
                             prefill_chunk=16, block_size=8)
    eng.warmup()
    (prompt,) = make_prompts(cfg, (9,), seed=13)
    eng.submit(prompt, 3)
    eng.run_until_idle()
    r2 = eng.submit(prompt, 3)
    eng.run_until_idle()
    assert r2.prefix_hit_blocks == 0
    stats = eng.kv_stats()
    assert stats["prefix_hits"] == 0
    assert stats["prefix_hit_rate"] == 0.0
    eng.check_block_invariants()


def test_engine_shed_metrics_carry_slo_class(tiny):
    from dlrover_tpu.observability.registry import MetricsRegistry

    cfg, params = tiny
    reg = MetricsRegistry()
    eng = ServingEngine(
        cfg, params, slots=1, max_len=32, prefill_chunk=8,
        registry=reg,
        slo_classes=(SloClass("interactive"), SloClass("batch")),
    )
    eng.warmup()
    import time as time_lib

    doomed = eng.submit([1, 2, 3], 3, deadline_s=1e-6,
                        slo_class="batch")
    live = eng.submit([4, 5, 6], 3, slo_class="interactive")
    time_lib.sleep(0.01)
    eng.run_until_idle()
    assert doomed.failed and doomed.failure_reason == "deadline"
    assert live.tokens and not live.failed
    assert reg.get("serving_requests_shed_total").value(
        reason="deadline", slo_class="batch"
    ) == 1
    # Per-class queue-depth gauge exists and settled to zero.
    assert reg.get("serving_class_queue_depth").value(
        slo_class="interactive"
    ) == 0


# ---- paged Pallas kernels --------------------------------------------------


def test_pool_decode_attention_matches_flat_slab_through_shuffled_table():
    """A second, independent reference for the kernel the decode
    program runs: the FLAT slab is made first and scattered into the
    pool through a shuffled table, and the kernel over the pool
    (interpret mode on CPU) must equal ``_append_free_attention`` on
    the slab itself — no gather through the table on the reference's
    side. GQA 8 / 4, fills 1 / 23 / 40 / 64 of 64."""
    from dlrover_tpu.models.generate import _append_free_attention
    from dlrover_tpu.ops.decode_attention import pool_decode_attention

    b, S, h, kh, d = 4, 64, 8, 4, 32
    bs = 16
    mb = S // bs
    lens = jnp.array([1, 23, 40, 64], jnp.int32)
    ks = jax.random.split(jax.random.key(0), 5)
    q = jax.random.normal(ks[0], (b, h, d), jnp.float32)
    k_cache = jax.random.normal(ks[1], (b, S, kh, d), jnp.float32)
    v_cache = jax.random.normal(ks[2], (b, S, kh, d), jnp.float32)
    k_new = jax.random.normal(ks[3], (b, kh, d), jnp.float32)
    v_new = jax.random.normal(ks[4], (b, kh, d), jnp.float32)

    rs = np.random.RandomState(0)
    tables = (rs.permutation(b * mb) + 1).reshape(b, mb).astype(np.int32)
    # Layer 1 of a two-layer pool holds the slab; layer 0 holds NaNs.
    pool_shape = (2, b * mb + 1, bs, kh, d)
    k_pool = np.full(pool_shape, np.nan, np.float32)
    v_pool = np.full(pool_shape, np.nan, np.float32)
    k_pool[1], v_pool[1] = 0.0, 0.0
    for i in range(b):
        for j in range(mb):
            rows = slice(j * bs, (j + 1) * bs)
            k_pool[1, tables[i, j]] = np.asarray(k_cache[i, rows])
            v_pool[1, tables[i, j]] = np.asarray(v_cache[i, rows])

    got = pool_decode_attention(
        q, k_new, v_new, jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.int32(1), jnp.asarray(tables), lens, jnp.ones((b,), bool),
    )
    ref = _append_free_attention(
        q[:, None], k_cache, v_cache, k_new[:, None], v_new[:, None], lens
    )[:, 0]
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


# Each case: per-slot fills, which slots decode, and what its table
# rows are made of, for a 5-slot pool of 16-row pages with 32 query
# heads on 8 KV heads and a 320-row cache, read in 128-row chunks.
_POOL_CASES = {
    # Nothing cached, one row, exactly a page, mid-page, the whole cache.
    "fills_on_every_edge": dict(fills=(0, 1, 16, 23, 320)),
    # Chunk edges: one row short of a chunk, exactly one, one more;
    # exactly two, and one more.
    "fills_around_a_chunk_edge": dict(fills=(127, 128, 129, 256, 257)),
    # Slot 1 is free and slot 3 mid-prefill: their table rows name
    # pages of NaNs, which an inactive slot must never read.
    "inactive_slots_with_stale_tables": dict(
        fills=(40, 33, 7, 200, 90), active=(1, 0, 1, 0, 1),
        poisoned=(1, 3),
    ),
    # Slots 0 and 1 share their first three pages (a prefix-cache hit)
    # and read them to different depths; slot 1 goes on in its own.
    "two_slots_share_prefix_blocks": dict(
        fills=(37, 70, 5, 48, 120), shared=((0, 1), 3),
    ),
}


@pytest.mark.parametrize("pool_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(_POOL_CASES))
def test_pool_decode_attention_matches_gather(
    case, pool_dtype, monkeypatch
):
    """The in-place pool kernel (interpret mode on CPU) against what
    the paged decode step computed before it: the layer's pool gathered
    through a shuffled table into ``[slots, max_len]`` views, then
    ``_append_free_attention``. Layer 2 of a three-layer stacked pool;
    f32 queries, so the only difference left is the order of
    summation."""
    from dlrover_tpu.models.generate import _append_free_attention
    from dlrover_tpu.ops import decode_attention as da
    from dlrover_tpu.ops.decode_attention import pool_decode_attention

    spec = _POOL_CASES[case]
    b, h, kh, d, bs, mb, n_layers, layer = 5, 32, 8, 128, 16, 20, 3, 2
    monkeypatch.setattr(da, "_POOL_CHUNK_BYTES", 128 * kh * d * 2)
    fills = np.asarray(spec["fills"], np.int32)
    active = np.asarray(spec.get("active", (1,) * b), bool)
    rs = np.random.RandomState(0)
    tables = (rs.permutation(b * mb) + 1).reshape(b, mb).astype(np.int32)
    if "shared" in spec:
        (first, second), n = spec["shared"]
        tables[second, :n] = tables[first, :n]
    ks = jax.random.split(jax.random.key(1), 5)
    pool_shape = (n_layers, b * mb + 1, bs, kh, d)
    k_pool = jax.random.normal(ks[0], pool_shape).astype(pool_dtype)
    v_pool = jax.random.normal(ks[1], pool_shape).astype(pool_dtype)
    for slot in spec.get("poisoned", ()):
        k_pool = k_pool.at[:, tables[slot]].set(jnp.nan)
        v_pool = v_pool.at[:, tables[slot]].set(jnp.nan)
    q = jax.random.normal(ks[2], (b, h, d), jnp.float32)
    k_new = jax.random.normal(ks[3], (b, kh, d), jnp.float32)
    v_new = jax.random.normal(ks[4], (b, kh, d), jnp.float32)

    got = np.asarray(pool_decode_attention(
        q, k_new, v_new, k_pool, v_pool, jnp.int32(layer),
        jnp.asarray(tables), jnp.asarray(fills), jnp.asarray(active),
    ))
    views = [
        pool[layer][tables].reshape(b, mb * bs, kh, d)
        for pool in (k_pool, v_pool)
    ]
    want = np.asarray(_append_free_attention(
        q[:, None], *views, k_new[:, None], v_new[:, None],
        jnp.asarray(fills),
    ))[:, 0]
    assert np.isfinite(got).all()
    np.testing.assert_allclose(
        got[active], want[active], rtol=2e-5, atol=2e-6
    )
    # A slot that reads nothing answers with its own new token.
    idle = ~active | (fills == 0)
    np.testing.assert_allclose(
        got[idle], np.repeat(np.asarray(v_new), h // kh, axis=1)[idle],
        rtol=1e-6,
    )
    # Another layer of the same pool is another answer.
    other = np.asarray(pool_decode_attention(
        q, k_new, v_new, k_pool, v_pool, jnp.int32(0),
        jnp.asarray(tables), jnp.asarray(fills), jnp.asarray(active),
    ))
    busy = active & (fills > 0)
    assert np.abs(other[busy] - got[busy]).max() > 1e-2


# Each case: one slot's prefill chunk of ``chunk`` tokens over a pool of
# ``bs``-row pages behind a shuffled 12-page table, ``start`` cache rows
# below it; 32 query heads on 8 KV heads unless said. A VMEM chunk
# holds 4 pages of the bf16 pool, a grid step 8 tokens.
_CHUNK_CASES = {
    # A prompt's first chunk: nothing below it, no page read.
    "first_chunk": dict(chunk=16, bs=16, start=0),
    # Mid-prompt, whole pages below the chunk, one VMEM chunk of them.
    "mid_prompt": dict(chunk=16, bs=16, start=48),
    # Two whole VMEM chunks of prefix: no row of them is masked.
    "prefix_of_whole_vmem_chunks": dict(chunk=16, bs=16, start=128),
    # The prefix spans three VMEM chunks, the last one a page short.
    "prefix_of_three_vmem_chunks": dict(chunk=16, bs=8, start=80),
    # A chunk is a share of a block: the prefix ends mid-page, the
    # rows of that page past ``start`` (the chunk's own, not written
    # yet) are masked.
    "chunk_smaller_than_a_block": dict(chunk=8, bs=16, start=40),
    "mha": dict(chunk=8, bs=8, start=24, heads=16, kv_heads=16),
    "one_query_head_a_kv_head_pair": dict(
        chunk=8, bs=8, start=16, heads=16, kv_heads=8,
    ),
}


@pytest.mark.parametrize("pool_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(_CHUNK_CASES))
def test_pool_chunk_attention_matches_gather(case, pool_dtype, monkeypatch):
    """The in-place chunk kernel (interpret mode on CPU) against what
    the paged prefill computed before it: the slot's pages gathered
    into a ``[max_len]`` view, the chunk written at ``start``, plain
    ``dot_product_attention`` over every row of it. Layer 1 of a
    two-layer pool; pages the table does not reach below ``start`` hold
    NaNs, which the kernel must never copy."""
    from dlrover_tpu.ops import decode_attention as da
    from dlrover_tpu.ops.attention import dot_product_attention

    spec = dict(_CHUNK_CASES[case])
    t, bs, start = spec["chunk"], spec["bs"], spec["start"]
    h, kh = spec.get("heads", 32), spec.get("kv_heads", 8)
    d, mb, n_layers, layer = 128, 12, 2, 1
    monkeypatch.setattr(da, "_POOL_CHUNK_BYTES", 4 * bs * kh * d * 2)
    monkeypatch.setattr(da, "_CHUNK_QUERY_ROWS", 8 * h)
    rs = np.random.RandomState(0)
    table = (rs.permutation(2 * mb)[:mb] + 1).astype(np.int32)
    ks = jax.random.split(jax.random.key(2), 5)
    pool_shape = (n_layers, 2 * mb + 1, bs, kh, d)
    k_pool = jax.random.normal(ks[0], pool_shape).astype(pool_dtype)
    v_pool = jax.random.normal(ks[1], pool_shape).astype(pool_dtype)
    q = jax.random.normal(ks[2], (t, h, d)).astype(pool_dtype)
    k_new = jax.random.normal(ks[3], (t, kh, d)).astype(pool_dtype)
    v_new = jax.random.normal(ks[4], (t, kh, d)).astype(pool_dtype)

    def view(pool, new):
        rows = pool[layer][table].reshape(mb * bs, kh, d)
        return jax.lax.dynamic_update_slice(rows, new, (start, 0, 0))[None]

    want = np.asarray(dot_product_attention(
        q[None], view(k_pool, k_new), view(v_pool, v_new), causal=True,
        q_positions=(start + jnp.arange(t))[None],
        kv_positions=jnp.arange(mb * bs),
    )[0], np.float32)
    unread = table[-(-start // bs):]
    k_pool = k_pool.at[:, unread].set(jnp.nan)
    v_pool = v_pool.at[:, unread].set(jnp.nan)
    args = (k_pool, v_pool, jnp.int32(layer), jnp.asarray(table),
            jnp.int32(start))
    got = np.asarray(
        da.pool_chunk_attention(q, k_new, v_new, *args), np.float32
    )
    assert np.isfinite(got).all()
    # f32: the order of summation; bf16: the output's last rounding.
    tol = dict(rtol=2e-5, atol=2e-6) if pool_dtype == "float32" else dict(
        rtol=2 ** -7, atol=2 ** -9
    )
    np.testing.assert_allclose(got, want, **tol)
    # Probabilities rounded to the pool's dtype once (what XLA's default
    # matmul precision makes of the reference on a TPU): the same
    # answer to that rounding.
    rounded = np.asarray(da.pool_chunk_attention(
        q, k_new, v_new, *args, exact=False
    ), np.float32)
    np.testing.assert_allclose(rounded, want, rtol=2 ** -6, atol=2 ** -7)
    # Another layer of the same pool is another answer (below start).
    if start:
        other = np.asarray(da.pool_chunk_attention(
            q, k_new, v_new, *args[:2], jnp.int32(0), *args[3:]
        ), np.float32)
        assert not np.isfinite(other).all() or (
            np.abs(other - got).max() > 1e-2
        )


# (prefill_chunk, block_size): a chunk of one block, of two, and half
# of one (the divisibility contract's two sides).
_CHUNK_BLOCK = {"chunk_is_a_block": (8, 8), "chunk_of_two_blocks": (16, 8),
                "chunk_is_half_a_block": (8, 16)}


def _admit_f32_pools(monkeypatch):
    """Take the kernel path on the CPU: the platform probe says TPU
    (the kernels then run in interpret mode) and the page predicate
    admits the f32 pools of the tiny models here, where XLA's f32 is
    f32 and the two paths differ by the order of summation alone."""
    from dlrover_tpu.ops import decode_attention as da
    from dlrover_tpu.serving.kvpool import families

    monkeypatch.setattr(families, "_on_tpu", lambda: True)
    monkeypatch.setattr(da, "pool_kernel_supported", lambda *a: True)


@pytest.mark.parametrize("case", sorted(_CHUNK_BLOCK))
def test_paged_engine_tokens_are_the_same_through_the_pool_kernels(
    case, monkeypatch,
):
    """One run of mixed prompts over a pool too small for them (slots
    admitted and finished mid-run, the youngest preempted once): the
    decode and prefill programs built with the pool kernels — the
    platform probe patched, the kernels in interpret mode — emit the
    greedy tokens of the gather programs, and retrace nothing across
    admissions."""
    from dlrover_tpu.observability.registry import MetricsRegistry

    chunk, bs = _CHUNK_BLOCK[case]
    cfg = llama.tiny_config(
        n_layers=2, n_heads=16, n_kv_heads=8, head_dim=128,
    )
    params, _ = llama.init_params(cfg, jax.random.key(0))
    prompts = make_prompts(cfg, (12, 5, 19, 12, 9, 3), seed=7)
    new = (16, 9, 12, 16, 5, 11)

    def run():
        eng = PagedServingEngine(
            cfg, params, slots=4, max_len=32, prefill_chunk=chunk,
            block_size=bs, num_blocks=80 // bs, prefix_cache=False,
            registry=MetricsRegistry(),
        )
        eng.warmup()
        base = dict(eng.trace_counts)
        reqs = [eng.submit(p, n) for p, n in zip(prompts, new)]
        eng.run_until_idle()
        assert all(r.state == "done" and not r.failed for r in reqs)
        assert eng.metrics.kv_preemptions.value() >= 1
        assert eng.trace_counts == base
        eng.check_block_invariants()
        assert eng.kv_stats()["pool_attention"] == eng.pool_attention
        return eng.pool_attention, [r.tokens for r in reqs]

    kind, want = run()
    assert kind == "xla_gather"
    _admit_f32_pools(monkeypatch)
    kind, got = run()
    assert kind == "paged_kernel"
    assert got == want
    assert [len(t) for t in got] == list(new)


@pytest.mark.parametrize("case", sorted(_CHUNK_BLOCK))
def test_in_place_prefill_never_rewrites_a_shared_block(case, monkeypatch):
    """A prefix-cache hit resumes a prompt mid-way (``start > 0`` on
    its first chunk): the in-place prefill reads the shared pages and
    writes its own, so every block the cache holds keeps its bytes, the
    tokens are the gather engine's, and the books balance."""
    from dlrover_tpu.observability.registry import MetricsRegistry

    chunk, bs = _CHUNK_BLOCK[case]
    cfg = llama.tiny_config(
        n_layers=2, n_heads=16, n_kv_heads=8, head_dim=128,
    )
    params, _ = llama.init_params(cfg, jax.random.key(0))
    shared = make_prompts(cfg, (32,), seed=3)[0]
    tails = make_prompts(cfg, (5, 9, 3), seed=4)
    prompts = [np.concatenate([shared, t]) for t in tails]

    def run():
        eng = PagedServingEngine(
            cfg, params, slots=2, max_len=64, prefill_chunk=chunk,
            block_size=bs, registry=MetricsRegistry(),
        )
        eng.warmup()
        first = eng.submit(prompts[0], 4)
        eng.run_until_idle()
        held = sorted(
            b for b in range(1, eng.num_blocks)
            if eng._allocator.refcount(b) > 0
        )
        assert held  # the cache keeps the finished prompt's full blocks
        before = [np.asarray(p[:, held]) for p in eng._pools()]
        rest = [eng.submit(p, 4) for p in prompts[1:]]
        eng.run_until_idle()
        assert eng.kv_stats()["prefix_hits"] == 2
        assert all(r.prefix_hit_blocks > 0 for r in rest)
        for was, pool in zip(before, eng._pools()):
            np.testing.assert_array_equal(was, np.asarray(pool[:, held]))
        eng.check_block_invariants()
        return eng.pool_attention, [r.tokens for r in [first] + rest]

    kind, want = run()
    assert kind == "xla_gather"
    _admit_f32_pools(monkeypatch)
    kind, got = run()
    assert kind == "paged_kernel"
    assert got == want


@pytest.mark.parametrize(
    "kv_dtype,in_place",
    [("fp", False), ("int8", False), ("fp", True)],
    ids=["fp_gather", "int8_gather", "fp_in_place"],
)
def test_prefill_runs_its_head_on_a_last_chunk_only(
    kv_dtype, in_place, tiny, monkeypatch
):
    """The chunk program under its ``last`` flag: the pools it returns
    are bit for bit the same with the head on, off, and called with
    ten arguments as before the flag (the head then always on); the
    first token of a last chunk is that call's, any other chunk's is
    the placeholder 0 — and one compiled program serves both."""
    cfg, params = tiny
    if in_place:
        cfg = llama.tiny_config(
            n_layers=2, n_heads=16, n_kv_heads=8, head_dim=128,
        )
        params, _ = llama.init_params(cfg, jax.random.key(0))
        _admit_f32_pools(monkeypatch)
    eng = PagedServingEngine(
        cfg, params, slots=2, max_len=32, prefill_chunk=8,
        block_size=8, kv_cache_dtype=kv_dtype,
    )
    assert eng.pool_attention == (
        "paged_kernel" if in_place else "xla_gather"
    )
    eng.warmup()
    traced = dict(eng.trace_counts)
    prompt = make_prompts(cfg, (8,), seed=5)[0]
    table = jnp.asarray(np.arange(1, 5, dtype=np.int32))
    args = (
        eng._params, jnp.asarray(prompt[None]), table, np.int32(0),
        np.int32(8), np.float32(0.0), eng._rng, np.int32(3),
    )

    def chunk(*flag):
        # The programs donate their pools: a fresh set a call.
        pools = tuple(eng._fresh_arrays().values())
        *pools, first = eng._steps.prefill(*pools, *args, *flag)
        return [np.asarray(p) for p in pools], int(first)

    pools_old, first_old = chunk()
    pools_on, first_on = chunk(np.bool_(True))
    pools_off, first_off = chunk(np.bool_(False))
    for old, on, off in zip(pools_old, pools_on, pools_off):
        np.testing.assert_array_equal(old, on)
        np.testing.assert_array_equal(old, off)
    assert np.abs(pools_old[0]).max() > 0
    assert first_on == first_old and first_off == 0
    # The traced flag is one program (the ten-argument call, whose
    # flag is a Python constant, is the other).
    assert eng.trace_counts["prefill"] == traced["prefill"] + 1


# What pool_attention_kind sees -> what it builds. Defaults: a TPU, a
# bf16 model of 32 heads on 8 KV heads x 128, 16-row pages, chunks of
# 256 tokens (the ``nemo12b-serve-chat`` engine), a bf16 pool.
_KIND_CASES = {
    "the_cell": (dict(), "paged_kernel"),
    "mha": (dict(n_kv_heads=32), "paged_kernel"),
    "not_on_a_tpu": (dict(on_tpu=False), "xla_gather"),
    "int8_pool": (dict(kv_dtype="int8"), "xla_gather"),
    "f32_model": (dict(dtype="float32"), "xla_gather"),
    "head_dim_64": (dict(head_dim=64), "xla_gather"),
    "four_kv_heads": (dict(n_kv_heads=4), "xla_gather"),
    # 1,024 rows x 8 x 128 x 2 B = 2 MB: one page outgrows a VMEM chunk.
    "page_larger_than_a_chunk": (dict(block_size=1024), "xla_gather"),
    "page_of_one_chunk": (dict(block_size=512), "paged_kernel"),
    # chip_smoke's engine: 8 / 8 heads, chunks of 64.
    "short_chunks": (dict(n_heads=8, chunk=64), "paged_kernel"),
    # No tile of whole sublanes of tokens divides the chunk.
    "chunk_of_12_tokens": (dict(chunk=12, block_size=4), "xla_gather"),
    # 1,024 tokens x 32 KV heads of own K/V outgrow the kernel's VMEM.
    "long_chunks_of_mha": (
        dict(n_kv_heads=32, chunk=1024), "xla_gather"
    ),
}


@pytest.mark.parametrize("case", sorted(_KIND_CASES))
def test_pool_attention_kind_goes_by_what_it_can_see(case, monkeypatch):
    from dlrover_tpu.serving.kvpool import dense, families

    seen, want = _KIND_CASES[case]
    seen = dict(seen)
    on_tpu = seen.pop("on_tpu", True)
    monkeypatch.setattr(families, "_on_tpu", lambda: on_tpu)
    block_size = seen.pop("block_size", 16)
    kv_dtype = seen.pop("kv_dtype", "fp")
    chunk = seen.pop("chunk", 256)
    cfg = llama.tiny_config(**{
        **dict(n_heads=32, n_kv_heads=8, head_dim=128, dtype="bfloat16"),
        **seen,
    })
    assert dense.pool_attention_kind(
        cfg, block_size, kv_dtype, chunk
    ) == want


@pytest.mark.parametrize("tiny", [False, True])
def test_bench_paged_decode_times_nothing_off_a_tpu(tiny):
    """The on-chip A/B tool's times mean something on a TPU only: off
    one it refuses to run, and its ``--tiny`` rehearsal (interpret
    mode) goes through every part and prints no time."""
    import subprocess
    import sys

    tool = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools", "bench_paged_decode.py",
    )
    shape = ["--preset", "flagship334m", "--slots", "2", "--layers", "1",
             "--max-blocks", "8", "--chunk-kb", "1024"]
    out = subprocess.run(
        [sys.executable, tool, *shape, *(["--tiny"] if tiny else [])],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, text=True,
        capture_output=True, timeout=300,
    )
    if not tiny:
        assert out.returncode == 2 and "no TPU here" in out.stderr
        assert out.stdout == ""
        return
    assert out.returncode == 0, out.stderr[-2000:]
    rows = [json.loads(x) for x in out.stdout.splitlines() if x[:1] == "{"]
    assert [r.get("part") for r in rows] == [
        "shape", "parity", "attention", "attention", "attention",
        "decode", "prefill_parity", "prefill_parity",
        "prefill_attention", "prefill_attention", "prefill", "prefill",
        None,
    ]
    assert rows[-1]["ok"] and rows[5]["same_tokens"] == [2, 2]
    assert [r["start"] for r in rows[6:8]] == [0, 64]
    assert all(r["same_first_token"] for r in rows[-3:-1])
    assert all(
        r["finite"] and r["kernel_f32_probs_vs_exact"] < 1e-5
        for r in rows[6:8]
    )
    assert not [k for r in rows for k in r if k.endswith(("_ms", "_s"))]


# ---- autoscaler signal source -----------------------------------------------


def test_kvpool_signal_source(tiny):
    from dlrover_tpu.autoscaler import SignalBus, kvpool_source

    cfg, params = tiny
    eng = PagedServingEngine(
        cfg, params, slots=2, max_len=32, prefill_chunk=8,
        block_size=8,
        slo_classes=(SloClass("interactive"), SloClass("batch")),
    )
    eng.warmup()
    (p,) = make_prompts(cfg, (9,), seed=11)
    eng.submit(p, 3, slo_class="interactive")
    eng.run_until_idle()
    bus = SignalBus().add_source("kv", kvpool_source(eng))
    snap = bus.sample()
    assert snap.get("kv.blocks_total") == eng.num_blocks - 1
    assert snap.get("kv.blocks_free_frac") is not None
    assert snap.get("kv.queue_depth.interactive") == 0
    assert "kv.error" not in snap.values
