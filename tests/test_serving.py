"""Continuous-batching serving engine tests: ragged batched decode must
match per-sequence teacher-forced forwards EXACTLY (dense config), a
recycled slot must not leak the previous occupant's KV, admissions must
never retrace after warmup, and the per-row-length Pallas decode kernel
must match masked reference attention."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dlrover_tpu.models import llama
from dlrover_tpu.serving import DECODE, PREFILL, ServingEngine, Scheduler
from tests.greedy_reference import naive_greedy


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


# ---- ragged decode parity ---------------------------------------------------


def test_ragged_decode_matches_teacher_forced(tiny):
    """Three requests with different prompt/output lengths over TWO
    slots (forces slot reuse), admissions staggered mid-decode so the
    batch is genuinely ragged + multi-chunk prefill (chunk 4 < prompt
    lens). Greedy tokens must match each sequence's solo teacher-forced
    loop exactly."""
    cfg, params = tiny
    eng = ServingEngine(cfg, params, slots=2, max_len=32,
                        prefill_chunk=4)
    eng.warmup()
    prompts = make_prompts(cfg, (5, 3, 9), seed=1)
    plans = list(zip(prompts, (6, 5, 4)))

    reqs = [eng.submit(prompts[0], 6)]
    # Let request 0 get ahead so lengths diverge before 1 and 2 join.
    for _ in range(4):
        eng.step()
    reqs.append(eng.submit(prompts[1], 5))
    reqs.append(eng.submit(prompts[2], 4))
    eng.run_until_idle()

    for req, (prompt, max_new) in zip(reqs, plans):
        assert req.state == "done"
        assert not req.truncated
        assert req.tokens == naive_greedy(cfg, params, prompt, max_new), (
            f"rid {req.rid}"
        )


def test_recycled_slot_does_not_leak_kv(tiny):
    """A LONG request fills a slot high; a SHORT one recycles it. If
    stale rows above the new fill were visible, the short request's
    logits would differ from its solo run."""
    cfg, params = tiny
    eng = ServingEngine(cfg, params, slots=1, max_len=32,
                        prefill_chunk=8)
    eng.warmup()
    long_p, short_p = make_prompts(cfg, (12, 3), seed=2)
    r_long = eng.submit(long_p, 12)
    eng.run_until_idle()
    assert r_long.state == "done" and len(r_long.tokens) == 12
    r_short = eng.submit(short_p, 6)
    eng.run_until_idle()
    assert r_short.tokens == naive_greedy(cfg, params, short_p, 6)


def test_no_retrace_across_admissions(tiny):
    """After warmup, admissions/evictions with NEW prompt lengths,
    output lengths, and temperatures must not trace either step
    program again (shapes are fixed; everything dynamic is traced)."""
    cfg, params = tiny
    eng = ServingEngine(cfg, params, slots=2, max_len=32,
                        prefill_chunk=4)
    eng.warmup()
    base = dict(eng.trace_counts)
    rs = np.random.RandomState(3)
    for i, (plen, mnew, temp) in enumerate(
        [(2, 3, 0.0), (7, 2, 0.9), (11, 5, 0.3), (4, 9, 1.7)]
    ):
        prompt = rs.randint(0, cfg.vocab_size, plen).astype(np.int32)
        eng.submit(prompt, mnew, temperature=temp)
    eng.run_until_idle()
    assert eng.trace_counts == base, (
        f"retraced: {eng.trace_counts} vs {base}"
    )


def test_engine_rejects_non_chunk_divisible_max_len(tiny):
    """max_len % prefill_chunk != 0 must be rejected at construction:
    a near-full prompt's final fixed-size chunk would otherwise clamp
    its dynamic_update_slice and rewrite already-visible KV rows
    (confirmed to corrupt outputs at max_len=40, chunk=16)."""
    cfg, params = tiny
    with pytest.raises(ValueError, match="multiple of"):
        ServingEngine(cfg, params, slots=1, max_len=40,
                      prefill_chunk=16)


def test_truncation_at_cache_capacity(tiny):
    """A request whose prompt + max_new overflows max_len is truncated
    at capacity, flagged, and its slot recycled."""
    cfg, params = tiny
    eng = ServingEngine(cfg, params, slots=1, max_len=16,
                        prefill_chunk=8)
    eng.warmup()
    (prompt,) = make_prompts(cfg, (10,), seed=4)
    req = eng.submit(prompt, 50)
    eng.run_until_idle()
    assert req.truncated
    # fill never exceeds max_len: prompt(10) + fed-back tokens.
    assert len(req.tokens) == eng.max_len - len(prompt) + 1
    # Slot is reusable afterwards.
    (p2,) = make_prompts(cfg, (3,), seed=5)
    r2 = eng.submit(p2, 4)
    eng.run_until_idle()
    assert r2.tokens == naive_greedy(cfg, params, p2, 4)


def test_sampled_requests_deterministic_per_engine_key(tiny):
    """Same engine rng key + same submission order => same sampled
    tokens; a different key changes them (temperature actually routes
    through categorical)."""
    cfg, params = tiny

    def run(key):
        eng = ServingEngine(cfg, params, slots=2, max_len=32,
                            prefill_chunk=4, rng=jax.random.key(key))
        eng.warmup()
        (p1, p2) = make_prompts(cfg, (4, 6), seed=6)
        r1 = eng.submit(p1, 6, temperature=1.0)
        r2 = eng.submit(p2, 6, temperature=1.0)
        eng.run_until_idle()
        return r1.tokens, r2.tokens

    a = run(7)
    assert a == run(7)
    assert a != run(8)


# ---- scheduler unit behavior ------------------------------------------------


def test_scheduler_budget_gates_prefill():
    sch = Scheduler(slots=4, max_len=64, prefill_chunk=8,
                    token_budget=10)
    for plen in (8, 8, 8):
        sch.submit(np.zeros(plen, np.int32), 4)
    sch.admit()
    reqs = sch.active()
    # Two slots decoding -> 2 + 8 <= 10 allows the chunk...
    reqs[0].state = DECODE
    reqs[1].state = DECODE
    assert sch.pick_prefills() == [reqs[2]]
    # ...three decoding -> 3 + 8 > 10 defers it.
    reqs[2].state = DECODE
    sch.submit(np.zeros(4, np.int32), 4)
    sch.admit()
    assert sch.pick_prefills() == []


def test_scheduler_drain_mode_admits_only_empty():
    sch = Scheduler(slots=2, max_len=64, prefill_chunk=8,
                    drain_mode=True)
    for _ in range(3):
        sch.submit(np.zeros(4, np.int32), 4)
    first = sch.admit()
    assert len(first) == 2 and not sch.admit()  # pool busy -> no admits
    sch.finish(first[0])
    assert not sch.admit()                      # still one live slot
    sch.finish(first[1])
    assert len(sch.admit()) == 1                # empty pool -> refill


# ---- metrics wiring ---------------------------------------------------------


def test_serving_metrics_land_in_registry(tiny):
    from dlrover_tpu.observability.registry import MetricsRegistry

    cfg, params = tiny
    reg = MetricsRegistry()
    eng = ServingEngine(cfg, params, slots=2, max_len=32,
                        prefill_chunk=4, registry=reg)
    eng.warmup()
    (p,) = make_prompts(cfg, (5,), seed=9)
    eng.submit(p, 3)
    eng.run_until_idle()
    assert reg.get("serving_requests_total").value(outcome="finished") == 1
    assert reg.get("serving_tokens_total").value(kind="decode") == 3
    assert reg.get("serving_tokens_total").value(kind="prefill") == 5
    assert reg.get("serving_ttft_seconds").count() == 1
    assert reg.get("serving_retraces_total").value() == 0
    assert reg.get("serving_slots_total").value() == 2
