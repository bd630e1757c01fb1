"""The convolution / attention pattern model (``models/conv_lm.py``)
through the paged engine (``serving/kvpool/conv.py``), on a CPU at tiny
size with seeded random weights: the model's forward against the plain
reference (``benchmark/reference_lfm2``) on LOGITS; chunked prefill +
decode through the pool and the per-slot state against the reference's
full forward; prefix hits that restore a state snapshot (off a chunk
boundary, a growing session, a hit rounded down to the deepest
snapshot); preemption, a reused slot, eviction, migration; what the
engine refuses."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_lfm2
from dlrover_tpu.models import conv_lm
from dlrover_tpu.serving.kvpool import (
    PagedServingEngine,
    export_request,
    import_request,
    layout,
    release_exported,
)
from tests.benchmark import tiny_lfm2

BS, CHUNK = 4, 8


def seeded_params(cfg, seed):
    """One program a model, not one a leaf's shape."""
    return jax.jit(lambda key: conv_lm.init_params(cfg, key))(
        jax.random.key(seed)
    )


@pytest.fixture(scope="module")
def tiny():
    cfg = conv_lm.tiny_config()
    return cfg, seeded_params(cfg, 0)


def cfg_json_of(cfg):
    """The published keys that describe ``cfg`` (for the reference)."""
    return dict(
        tiny_lfm2.CONFIG, hidden_size=cfg.embed_dim,
        vocab_size=cfg.vocab_size, num_hidden_layers=cfg.n_layers,
        layer_types=list(cfg.layer_types), num_dense_layers=cfg.n_dense,
        num_attention_heads=cfg.n_heads, num_key_value_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, conv_L_cache=cfg.conv_taps,
        num_experts=cfg.n_experts, num_experts_per_tok=cfg.moe_top_k,
        rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps,
    )


def prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).tolist() for n in lengths]


def engine(cfg, params, **kw):
    kw = dict(dict(slots=3, max_len=64, prefill_chunk=CHUNK, block_size=BS,
                   num_blocks=60), **kw)
    return PagedServingEngine(cfg, params, **kw)


def serve(eng, items):
    reqs = [eng.submit(p, n) for p, n in items]
    while eng.pending():
        eng.step()
    eng.check_block_invariants()
    return [list(r.tokens) for r in reqs]


@pytest.fixture(scope="module")
def want(tiny):
    """``want(prompt, out)``: the REFERENCE's greedy tokens at the
    positions that emitted ``out`` (its free-running full forward over
    prompt + out, padded to 64 tokens, which a causal model's earlier
    positions cannot see)."""
    cfg, params = tiny
    cfg_json = cfg_json_of(cfg)

    def greedy(prompt, out):
        tokens = np.zeros(64, np.int32)
        tokens[:len(prompt) + len(out)] = prompt + out
        rows = np.arange(len(prompt) - 1, len(prompt) + len(out) - 1)
        logits = reference_lfm2.forward_at(
            params, jnp.asarray(tokens), jnp.asarray(rows, jnp.int32),
            cfg_json,
        )["logits"]
        return np.asarray(logits).argmax(-1).tolist()

    return greedy


@pytest.fixture(scope="module")
def warm(tiny):
    """One engine for the tests that only add requests to it."""
    return engine(*tiny)


def test_the_config_states_both_kinds_of_cache(tiny, warm):
    cfg, _ = tiny
    assert cfg.cache_layers == 2 and len(cfg.conv_layers) == 3
    assert cfg.cache_rows == (("k_rows", (16,)), ("v_rows", (16,)))
    assert [a.name for a in layout.pool_arrays(cfg)] == ["k_rows", "v_rows"]
    assert all(a.raw for a in layout.pool_arrays(cfg))
    (state,) = layout.state_arrays(cfg)
    assert (state.name, state.layers, state.shape) == (
        "conv_state", 3, (2, 32)
    )
    assert layout.state_arrays(
        __import__("dlrover_tpu.models.llama", fromlist=["x"]).TpuLMConfig()
    ) == ()
    k, v, conv, snaps = warm._pools()
    assert k.shape == v.shape == (2, 60, BS, 16)
    assert conv.shape == (3, 3, 2, 32)
    assert snaps.shape == (3, warm.state_snapshots + 1, 2, 32)
    stats = warm.kv_stats()
    assert stats["pool_attention"] == "conv_gathered_view"
    assert stats["kv_layers"] == 2 and stats["state_layers"] == 3
    assert stats["state_bytes"] == conv.nbytes
    assert warm._block_bytes * warm.num_blocks == k.nbytes + v.nbytes


def test_forward_against_the_reference_on_logits(tiny):
    cfg, params = tiny
    (seq,) = prompts(cfg, [64], seed=3)
    logits, dropped = jax.jit(
        lambda t: conv_lm.forward(cfg, params, t)
    )(jnp.asarray([seq]))
    ref = reference_lfm2.forward_at(
        params, jnp.asarray(seq), jnp.arange(64), cfg_json_of(cfg),
        state_rows=jnp.arange(64),
    )
    assert int(dropped) == 0
    np.testing.assert_allclose(
        np.asarray(logits)[0], np.asarray(ref["logits"]), rtol=2e-4, atol=2e-4
    )
    assert len(ref["conv_z"]) == 3 and ref["kv_rows"][0].shape == (64, 16)


def test_chunked_prefill_then_decode_against_the_reference(tiny, warm, want):
    cfg, _ = tiny
    # no multiple of the chunk (8) or the block (4)
    items = [(p, 7) for p in prompts(cfg, [19, 5, 27], seed=1)]
    for (prompt, _), out in zip(items, serve(warm, items)):
        assert out == want(prompt, out)
    assert warm.kv_stats()["moe_rows_dropped"] == 0


def test_a_prefix_hit_off_a_chunk_boundary_restores_a_snapshot(tiny, want):
    cfg, params = tiny
    eng = engine(cfg, params)
    first, other = prompts(cfg, [14, 9], seed=2)   # 3 whole blocks + 2
    serve(eng, [(first, 3)])
    before = eng.kv_stats()
    assert before["state_snapshots_live"] == 1
    assert before["state_snapshots"] == 1
    second = first[:12] + other                    # boundary 12: not 8 | 16
    (out,) = serve(eng, [(second, 6)])
    after = eng.kv_stats()
    assert after["prefix_hit_tokens"] - before["prefix_hit_tokens"] == 12
    assert after["prefix_hits"] - before["prefix_hits"] == 1
    assert out == want(second, out)
    (cold,) = serve(engine(cfg, params, prefix_cache=False), [(second, 6)])
    assert out == cold


def test_a_growing_session_hits_down_to_its_last_snapshot(tiny, want):
    cfg, params = tiny
    eng = engine(cfg, params)
    (turn1,) = prompts(cfg, [10], seed=4)
    (answer,) = serve(eng, [(turn1, 9)])
    # turn 2 = turn 1's prompt + its answer + new tokens: blocks 0-1 are
    # cached with a snapshot at row 8; rows 8-18 were written by the
    # tail of the prompt and by decode, and no boundary there has one.
    turn2 = turn1 + answer + prompts(cfg, [5], seed=5)[0]
    before = eng.kv_stats()["prefix_hit_tokens"]
    (out,) = serve(eng, [(turn2, 5)])
    assert eng.kv_stats()["prefix_hit_tokens"] - before == 8
    assert out == want(turn2, out)
    # ... and turn 2's own boundary (24 rows) now holds one
    assert eng.kv_stats()["state_snapshots_live"] == 2
    turn3 = turn2 + out + prompts(cfg, [3], seed=6)[0]
    before = eng.kv_stats()["prefix_hit_tokens"]
    (out3,) = serve(eng, [(turn3, 4)])
    assert eng.kv_stats()["prefix_hit_tokens"] - before == 24
    assert out3 == want(turn3, out3)


def test_a_hit_deeper_than_the_deepest_snapshot_rounds_down(tiny, want):
    cfg, params = tiny
    eng = engine(cfg, params)
    short, long_tail = prompts(cfg, [9], seed=7)[0], prompts(cfg, [13], 8)[0]
    long = short[:8] + long_tail                   # 21 tokens, 5 blocks
    serve(eng, [(short, 2), (long, 2)])
    # entries: blocks 0-1 (snapshot at 8, from ``short``), 2-4 of ``long``
    # (snapshot at 20). A prompt matching 4 blocks of ``long`` has no
    # snapshot at 16 or 12: it resumes at 8.
    probe = long[:17] + prompts(cfg, [4], seed=9)[0]
    before = eng.kv_stats()
    (out,) = serve(eng, [(probe, 5)])
    after = eng.kv_stats()
    assert after["prefix_hit_tokens"] - before["prefix_hit_tokens"] == 8
    assert (after["prefix_rounded_down_blocks"]
            - before["prefix_rounded_down_blocks"]) == 2
    assert out == want(probe, out)


def test_preempt_and_resume_equals_an_unpreempted_run(tiny, want):
    cfg, params = tiny
    eng = engine(cfg, params, slots=2, num_blocks=24, max_len=32)
    a, b = prompts(cfg, [13, 11], seed=10)
    ra, rb = eng.submit(a, 12), eng.submit(b, 12)
    for _ in range(6):
        eng.step()
    eng._drain("test")
    eng._preempt(rb)
    while eng.pending():
        eng.step()
    eng.check_block_invariants()
    assert eng.kv_stats()["state_restores"] >= 3
    assert list(ra.tokens) == want(a, list(ra.tokens))
    assert list(rb.tokens) == want(b, list(rb.tokens))


def test_a_released_slots_next_cold_tenant_starts_from_zeros(tiny, want):
    cfg, params = tiny
    eng = engine(cfg, params, slots=1, prefix_cache=False)
    first, second = prompts(cfg, [17, 6], seed=11)
    serve(eng, [(first, 5)])
    assert float(jnp.abs(eng._arrays["conv_state"]).max()) > 0
    (out,) = serve(eng, [(second, 6)])
    assert out == want(second, out)
    (fresh,) = serve(
        engine(cfg, params, slots=1, prefix_cache=False), [(second, 6)]
    )
    assert out == fresh


def test_eviction_frees_the_snapshot_with_its_block(tiny):
    cfg, params = tiny
    eng = engine(cfg, params)
    serve(eng, [(p, 2) for p in prompts(cfg, [9, 13, 6], seed=12)])
    cache = eng._cache

    def holding():
        return sum(1 for e in cache._entries.values() if e.snapshot)

    assert eng.kv_stats()["state_snapshots_live"] == holding() == 3
    free = cache.snapshots_free
    assert cache.evict_lru(1) == 1
    eng.check_block_invariants()
    assert eng.kv_stats()["state_snapshots_live"] == holding() == 2
    assert cache.snapshots_free == free + 1
    cache.evict_lru(100)
    eng.check_block_invariants()
    assert eng.kv_stats()["state_snapshots_live"] == holding() == 0
    assert cache.snapshots_free == eng.state_snapshots


def test_export_then_import_carries_the_state(tiny, want):
    cfg, params = tiny
    src, dst = engine(cfg, params), engine(cfg, params)
    (prompt,) = prompts(cfg, [11], seed=13)
    req = src.submit(prompt, 10)
    while len(req.tokens) < 4:
        src.step()
    payload = export_request(src, req)
    release_exported(src, req)
    src.check_block_invariants()
    moved = import_request(dst, payload)
    while dst.pending():
        dst.step()
    dst.check_block_invariants()
    assert list(moved.tokens) == want(prompt, list(moved.tokens))
    assert len(moved.tokens) == 10


def test_int8_and_speculative_engines_are_refused_by_name(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="int8 pool holds K and V alone"):
        engine(cfg, params, kv_cache_dtype="int8")
    with pytest.raises(ValueError, match="per-slot state"):
        engine(cfg, params, spec_k=2)
    with pytest.raises(ValueError, match="whole blocks"):
        engine(cfg, params, prefill_chunk=2, block_size=4)


def test_no_program_retraces_across_admissions(tiny, warm):
    cfg, _ = tiny
    before = dict(warm.trace_counts)
    serve(warm, [(p, 3) for p in prompts(cfg, [9, 12, 21, 5], seed=14)])
    assert dict(warm.trace_counts) == before


def test_the_chunk_through_the_pool_kernel_emits_the_same_tokens(monkeypatch):
    """The prefill program built with the chunk kernel — the platform
    probe patched, the predicate admitting a float32 pool of 4-row
    pages, tiles of 4 tokens in chunks of 8, two pages a VMEM chunk, the
    kernel interpreted — emits the greedy tokens of the program built
    over the gathered prefix: cold prompts whose last chunks are mostly
    padding, and a prefix hit that resumes off a chunk boundary.
    ``kv_stats()`` names the kind and counts the rows the chunks
    launched and those their attention scored."""
    from dlrover_tpu.ops import flat_decode_attention as fda
    from dlrover_tpu.serving.kvpool import conv, families
    from dlrover_tpu.serving.kvpool import engine as paged

    # two KV heads of 64 are ONE lane row, under two query heads each
    cfg = conv_lm.tiny_config(n_heads=4, n_kv_heads=2, head_dim=64)
    params = seeded_params(cfg, 0)
    first, other, third = prompts(cfg, [14, 9, 27], seed=2)

    def a_day():
        eng = engine(cfg, params, slots=2)
        tokens = serve(eng, [(first, 3), (third, 5)])
        tokens += serve(eng, [(first[:12] + other, 6)])
        return tokens, eng.kv_stats()

    want, stats = a_day()
    assert stats["conv_chunk_attention"] == "gathered_view"
    # 14 -> 2 chunks, 27 -> 4, the hit's 9 rows after 12 -> 2
    assert stats["conv_chunk_rows_launched"] == 8 * CHUNK
    assert stats["conv_chunk_rows_scored"] == 8 * CHUNK
    monkeypatch.setattr(families, "_on_tpu", lambda: True)
    monkeypatch.setattr(fda, "flat_chunk_kernel_supported", lambda *a: True)
    monkeypatch.setattr(fda, "CHUNK_PREFIX_BYTES", 2 * BS * cfg.kv_width * 4)
    monkeypatch.setattr(conv, "CHUNK_TOKEN_TILE", 4)
    # tile and VMEM chunk are no part of a program's key: programs of
    # the test's own
    monkeypatch.setattr(paged, "_steps_for", functools.lru_cache(
        maxsize=16
    )(paged._steps_for.__wrapped__))
    calls = []
    kernel = fda.pool_flat_chunk_attention
    monkeypatch.setattr(
        fda, "pool_flat_chunk_attention",
        lambda *a, **kw: calls.append((a[5], kw["tile"])) or kernel(*a, **kw),
    )
    got, stats = a_day()
    assert calls == [(0, 4), (1, 4)]   # traced once, both attention layers
    assert stats["conv_chunk_attention"] == "pool_kernel"
    assert stats["conv_decode_attention"] == "gathered_view"   # float32
    assert stats["pool_attention"] == "conv_gathered_view"
    assert got == want
    assert stats["conv_chunk_rows_launched"] == 8 * CHUNK
    # valid rows 8, 6 | 8, 8, 8, 3 | 8, 1 in tiles of 4
    assert stats["conv_chunk_rows_scored"] == 16 + 28 + 12
