"""Block migration (§36): export/import between paged engines —
token-exactness vs an unmigrated greedy run, zero retraces on the
destination, allocator conservation on both ends, prefix-trie
registration of imported chains, eviction safety for in-flight
imported tables, the DECODE-entry admission law, and the
``serving.migrate`` span sitting between prefill and decode."""

import numpy as np
import pytest

import jax

from dlrover_tpu.models import llama
from dlrover_tpu.serving.kvpool import (
    MigrationError,
    MigrationRefused,
    PagedServingEngine,
    can_import,
    export_request,
    import_request,
    peek_header,
    release_exported,
)
from dlrover_tpu.serving.scheduler import DECODE

pytestmark = pytest.mark.kvpool


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.tiny_config()
    params, _ = llama.init_params(cfg, jax.random.key(0))
    return cfg, params


def make_engine(tiny, kv_dtype="fp", slots=2, **kw):
    cfg, params = tiny
    eng = PagedServingEngine(
        cfg, params, slots=slots, max_len=32, prefill_chunk=4,
        block_size=8, kv_cache_dtype=kv_dtype, **kw,
    )
    eng.warmup()
    return eng


def make_prompt(cfg, n, seed=0):
    rs = np.random.RandomState(seed)
    return rs.randint(0, cfg.vocab_size, size=n).astype(np.int32)


def drive_to_decode(eng, prompt, max_new, decode_steps=0, **kw):
    req = eng.submit(prompt, max_new, **kw)
    for _ in range(200):
        if req.tokens:
            break  # the first token is fetched a step after its launch
        eng.step()
    assert req.state == DECODE and req.tokens
    for _ in range(decode_steps):
        eng.step()
    return req


@pytest.mark.parametrize("kv_dtype", ["fp", "int8"])
def test_migration_token_exact_and_conserved(tiny, kv_dtype):
    """A request migrated right after prefill (the disaggregated path)
    AND one migrated mid-decode (live drain) both finish with exactly
    the tokens an unmigrated run of the same engine config produces;
    conservation holds on both ends afterwards."""
    cfg, params = tiny
    src = make_engine(tiny, kv_dtype)
    dst = make_engine(tiny, kv_dtype)
    prompt = make_prompt(cfg, 11, seed=3)
    # Unmigrated reference on an identical engine config (greedy).
    ref_eng = make_engine(tiny, kv_dtype)
    ref = ref_eng.submit(prompt, 8)
    ref_eng.run_until_idle()
    assert len(ref.tokens) == 8

    for decode_steps in (0, 3):
        req = drive_to_decode(src, prompt, 8,
                              decode_steps=decode_steps)
        payload = export_request(src, req)
        assert peek_header(payload)["src_kv_dtype"] == kv_dtype
        imported = import_request(dst, payload)
        release_exported(src, req)
        assert req.state == "done"
        dst.run_until_idle()
        assert imported.tokens == ref.tokens
        assert imported.migrate_end_ts is not None
        src.check_block_invariants()
        dst.check_block_invariants()
    # Source freed every migrated-out block (prompt blocks may stay
    # prefix-cached; free + used + cached == managed is the law).
    stats = src.kv_stats()
    assert stats["used"] == 0


def test_migration_zero_retraces_on_destination(tiny):
    """After warmup, importing and decoding migrated requests — with
    varying block ids, fills, and prompt lengths — must trace nothing
    on the destination."""
    cfg, params = tiny
    src = make_engine(tiny, "int8")
    dst = make_engine(tiny, "int8")
    base = dict(dst.trace_counts)
    for i, (plen, steps) in enumerate(((9, 0), (17, 2), (5, 1))):
        prompt = make_prompt(cfg, plen, seed=20 + i)
        req = drive_to_decode(src, prompt, 6, decode_steps=steps)
        payload = export_request(src, req)
        imported = import_request(dst, payload)
        release_exported(src, req)
        dst.run_until_idle()
        assert len(imported.tokens) == 6
    assert dst.trace_counts == base, (
        f"retraced: {dst.trace_counts} vs {base}"
    )
    dst.check_block_invariants()


def test_imported_chain_registers_in_destination_trie(tiny):
    """Hit-rate survives migration: a fresh request with the migrated
    prompt on the DESTINATION hits the imported blocks."""
    cfg, params = tiny
    src = make_engine(tiny, "fp")
    dst = make_engine(tiny, "fp")
    prompt = make_prompt(cfg, 17, seed=4)  # 2 full blocks + tail
    req = drive_to_decode(src, prompt, 4)
    imported = import_request(dst, export_request(src, req))
    release_exported(src, req)
    dst.run_until_idle()
    assert len(imported.tokens) == 4
    follow = dst.submit(prompt, 4)
    dst.run_until_idle()
    assert follow.prefix_hit_blocks == 2
    assert follow.tokens == imported.tokens[:4] or follow.tokens
    # Same-config unmigrated engine agrees on the follow-up's tokens.
    dst.check_block_invariants()


def test_eviction_never_frees_inflight_imported_blocks(tiny):
    """Leaf-first eviction drops only the CACHE's ref: blocks an
    in-flight imported table still references survive eviction and the
    request decodes to completion; conservation holds."""
    cfg, params = tiny
    src = make_engine(tiny, "fp")
    dst = make_engine(tiny, "fp")
    prompt = make_prompt(cfg, 17, seed=5)
    req = drive_to_decode(src, prompt, 10)
    imported = import_request(dst, export_request(src, req))
    release_exported(src, req)
    slot_blocks = list(dst._slot_blocks[imported.slot])
    # Evict the whole cache while the imported request is mid-decode.
    evicted = dst._cache.evict_lru(len(slot_blocks))
    assert evicted >= 1
    for b in slot_blocks:
        assert dst._allocator.refcount(b) >= 1  # slot ref survives
    dst.run_until_idle()
    assert len(imported.tokens) == 10 and not imported.failed
    dst.check_block_invariants()


def test_import_refused_when_destination_full(tiny):
    """No free slot or not enough blocks -> MigrationRefused, and the
    destination is left untouched (no half-admitted request)."""
    cfg, params = tiny
    src = make_engine(tiny, "fp")
    dst = make_engine(tiny, "fp", slots=1)
    blocker = drive_to_decode(dst, make_prompt(cfg, 5, seed=8), 20)
    req = drive_to_decode(src, make_prompt(cfg, 9, seed=9), 6)
    payload = export_request(src, req)
    assert not can_import(dst, peek_header(payload)["n_blocks"])
    before = dst.kv_stats()
    with pytest.raises(MigrationRefused):
        import_request(dst, payload)
    assert dst.kv_stats() == before
    assert dst.scheduler.free_slots() == 0
    # The source still owns the request: it can complete locally.
    src.run_until_idle()
    assert len(req.tokens) == 6 and not req.failed
    dst.run_until_idle()
    assert len(blocker.tokens) == 20
    src.check_block_invariants()
    dst.check_block_invariants()


def test_export_requires_decode_state(tiny):
    cfg, params = tiny
    src = make_engine(tiny, "fp")
    req = src.submit(make_prompt(cfg, 9, seed=10), 4)
    with pytest.raises(MigrationError, match="not migratable"):
        export_request(src, req)  # still queued
    src.step()  # admitted, prefill underway
    if req.state != DECODE:
        with pytest.raises(MigrationError, match="not migratable"):
            export_request(src, req)
    src.run_until_idle()


def test_decode_entry_admission_law(tiny):
    """Scheduler admit_decode: binds a free slot directly in DECODE,
    validates the migration preconditions, and refuses when full."""
    from dlrover_tpu.serving.scheduler import Scheduler

    sch = Scheduler(slots=1, max_len=32, prefill_chunk=4)
    prompt = np.arange(5, dtype=np.int32)
    with pytest.raises(ValueError, match="sampled token"):
        sch.admit_decode(prompt, [], 4)
    with pytest.raises(ValueError, match="already complete"):
        sch.admit_decode(prompt, [1, 2, 3, 4], 4)
    req = sch.admit_decode(prompt, [7], 4, now=10.0)
    assert req.state == DECODE and req.slot == 0
    assert req.prefill_pos == 5 and req.tokens == [7]
    assert req.admit_ts == 10.0 and req.first_token_ts == 10.0
    assert sch.free_slots() == 0
    with pytest.raises(RuntimeError, match="no free slot"):
        sch.admit_decode(prompt, [7], 4)
    sch.finish(req)
    assert sch.free_slots() == 1


def test_migrate_span_between_prefill_and_decode(tiny):
    """The destination emits the full retrospective tree: queue_wait /
    prefill reconstructed from carried durations, serving.migrate in
    the middle, decode after — children tile the request end to end."""
    from dlrover_tpu.observability import tracing

    cfg, params = tiny
    src = make_engine(tiny, "fp")
    dst = make_engine(tiny, "fp")
    prompt = make_prompt(cfg, 9, seed=12)
    req = drive_to_decode(src, prompt, 5)
    payload = export_request(src, req)
    tracer = tracing.Tracer(service="test")
    old = tracing._tracer
    tracing.arm(tracer)
    try:
        imported = import_request(dst, payload)
        release_exported(src, req)
        dst.run_until_idle()
    finally:
        if old is not None:
            tracing.arm(old)
        else:
            tracing.disarm()
    spans = [s for s in tracer.finished()
             if s["name"].startswith("serving.")]
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    assert "serving.migrate" in by_name
    root = by_name["serving.request"][0]
    kids = [s for s in spans
            if s.get("parent_id") == root["span_id"]]
    e2e = root["dur_s"]
    child_sum = sum(s["dur_s"] for s in kids)
    assert abs(child_sum - e2e) <= max(0.1 * e2e, 0.005), (
        f"queue+prefill+migrate+decode {child_sum} != e2e {e2e}"
    )
    # Ordering: prefill ends before migrate starts, migrate ends
    # before the (post-migration) decode starts.
    mig = by_name["serving.migrate"][0]
    pre = by_name["serving.prefill"][0]
    dec = max(by_name["serving.decode"], key=lambda s: s["mono"])
    assert pre["mono"] + pre["dur_s"] <= mig["mono"] + 1e-6
    assert mig["mono"] + mig["dur_s"] <= dec["mono"] + 1e-6
    assert len(imported.tokens) == 5
