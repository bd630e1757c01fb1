"""A model whose attention layers follow a pattern of sliding-window and
full layers (``models/window_lm.py``), served by the paged engine over a
pool in two LAYER GROUPS (``kvpool/layout.py``, ``kvpool/groups.py``,
``kvpool/window.py``): the model against the plain reference, chunked
prefill then decode through both groups against the full forward pass at
prompts below, at and several times the window, the rule of release,
conservation in both groups through release, preemption, a reused slot
and eviction, what a prefix hit means, and what is refused by name."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import reference_mellum2
from dlrover_tpu.models import window_lm
from dlrover_tpu.serving.kvpool import layout as pool_layout
from dlrover_tpu.serving.kvpool import migrate, window
from dlrover_tpu.serving.kvpool.engine import PagedServingEngine
from dlrover_tpu.serving.kvpool.groups import ReachGroup, band_blocks
from tests.benchmark import tiny_mellum2

BS, CHUNK, WINDOW = 8, 16, 24


@pytest.fixture(scope="module")
def model():
    cfg = window_lm.tiny_config()
    assert cfg.sliding_window == WINDOW
    return cfg, window_lm.init_params(cfg, jax.random.PRNGKey(0))


def _engine(model, **kw):
    cfg, params = model
    args = dict(slots=3, max_len=160, prefill_chunk=CHUNK, block_size=BS,
                num_blocks=64, window_blocks=28)
    args.update(kw)
    return PagedServingEngine(cfg, params, **args)


@pytest.fixture(scope="module")
def forward(model):
    cfg, params = model
    return jax.jit(lambda t: window_lm.forward(cfg, params, t)[0])


def _serve(engine, prompts, new):
    reqs = [engine.submit(p, max_new_tokens=n, temperature=0.0)
            for p, n in zip(prompts, new)]
    done = {}
    while engine.pending():
        for r in engine.step():
            done[r.rid] = r
        engine.check_block_invariants()
    return [done[r.rid] for r in reqs]


def _prompts(lengths, seed=1, vocab=96):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in lengths]


def _greedy(forward, prompt, tokens):
    seq = list(prompt) + list(tokens)
    padded = np.zeros(160, np.int32)        # one shape: causal, so the
    padded[:len(seq)] = seq                 # padding changes no row
    logits = np.asarray(forward(jnp.asarray(padded)[None])[0])
    return np.argmax(logits[len(prompt) - 1:len(seq) - 1], -1).tolist()


def test_forward_is_the_reference(model, forward):
    """``window_lm.forward`` against the plain float32 reference, which
    writes both rotations and the band out again."""
    cfg, params = model
    cfg_json = dict(
        tiny_mellum2.CONFIG, hidden_size=cfg.embed_dim,
        vocab_size=cfg.vocab_size,
    )
    cfg_json["rope_parameters"] = {
        "full_attention": dict(
            cfg_json["rope_parameters"]["full_attention"],
            attention_factor=cfg.attention_factor,
        ),
        "sliding_attention": cfg_json["rope_parameters"]["sliding_attention"],
    }
    assert tuple(cfg_json["layer_types"]) == cfg.layer_types
    tokens = _prompts([96])[0]
    want = reference_mellum2.forward_at(
        params, jnp.asarray(tokens), jnp.arange(96), cfg_json
    )["logits"]
    got = forward(jnp.asarray(tokens)[None])[0]
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert cfg.attention_factor == pytest.approx(0.1 * np.log(4.0) + 1)
    assert window_lm.tiny_config(rope_factor=1.0).attention_factor == 1.0


def test_the_config_states_its_groups(model):
    cfg, _ = model
    groups = pool_layout.cache_groups(cfg)
    assert [(g.name, g.layers, g.reach) for g in groups] == [
        ("full", 1, None), ("window", 2, WINDOW - 1),
    ]
    names = [a.name for a in pool_layout.grouped_pool_arrays(cfg)]
    assert names == ["k", "v", "k_window", "v_window"]
    # a config that states none is one group that keeps all
    from dlrover_tpu.models import llama

    (one,) = pool_layout.cache_groups(llama.tiny_config())
    assert one.keeps_all and one.layers == llama.tiny_config().n_layers
    assert cfg.count_params() == sum(
        int(np.prod(x.shape))
        for x in jax.tree_util.tree_leaves(model[1])
    )


@pytest.fixture(scope="module")
def served(model):
    """One engine, five prompts through three slots (so slots are
    reused): shorter than, equal to and several times the window."""
    engine = _engine(model)
    lengths = [5, WINDOW, 100, 37, 70]
    prompts = _prompts(lengths)
    before = dict(engine.trace_counts)
    out = _serve(engine, prompts, [6, 8, 12, 20, 5])
    return engine, prompts, out, before


def test_prefill_then_decode_through_both_groups_is_the_forward_pass(
    served, forward
):
    engine, prompts, out, _ = served
    for prompt, r in zip(prompts, out):
        assert list(r.tokens) == _greedy(forward, prompt, r.tokens)
    stats = engine.kv_stats()
    assert stats["pool_attention"] == "window_groups"
    assert stats["window_decode_attention"] == "gathered_view"
    assert stats["window_chunk_attention"] == "gathered_view"
    assert stats["moe_rows_dropped"] == 0
    assert set(stats["groups"]) == {"full", "window"}
    assert stats["groups"]["window"]["layers"] == 2
    # everything came back: only the prefix cache holds blocks
    assert stats["used"] == 0 and stats["cached"] > 0
    assert stats["groups"]["window"]["bytes_in_use"] > 0   # cached tails
    assert stats["prefix_tails_live"] >= 1
    # blocks a 100-row prompt and its 12 tokens slid out of: its last
    # launch's query sits at row 110 and sees rows from 87 on -> blocks
    # 0-9; the 70-row one's at 73 -> 6; the 37-row one's at 55 -> 4
    assert stats["window_blocks_released_total"] == 10 + 6 + 4
    assert [r.window_blocks_released for r in out] == [0, 0, 10, 4, 6]


def test_no_admission_retraces(served):
    engine, _, _, before = served
    # (the first call of each program traced it; nothing after)
    assert all(v <= 1 for v in engine.trace_counts.values())
    assert engine.trace_counts["decode"] == engine.trace_counts["prefill"] == 1
    assert before["decode"] == 0


def test_blocks_are_released_by_the_rule_and_never_read_again(model, forward):
    """Step by step: a slot holds exactly the blocks of its band (and the
    rows being written); NaN planted in every released block changes no
    token."""
    engine = _engine(model)
    (prompt,) = _prompts([90], seed=3)
    req = engine.submit(prompt, max_new_tokens=10, temperature=0.0)
    (group,) = engine._reach_groups
    seen_release = False
    freed = set()
    while engine.pending():
        before = dict(group.slot_blocks[req.slot]) if req.slot >= 0 else {}
        engine.step()
        engine.check_block_invariants()
        if req.slot < 0:
            continue
        held = group.slot_blocks[req.slot]
        position = int(engine._launch_position[req.slot])
        below = (position - group.reach) // BS
        assert all(b >= below for b in held)
        gone = {b: blk for b, blk in before.items() if b not in held}
        if gone:
            seen_release = True
            assert all(b < below for b in gone)
            assert all(engine._group_tables[1, req.slot, b] == 0
                       for b in gone)
            freed.update(gone.values())
            # poison what was released (the prefix cache holds none of a
            # prompt that is still prefilling or decoding)
            poison = jnp.asarray(sorted(gone.values()))
            for name in ("k_window", "v_window"):
                engine._arrays[name] = engine._arrays[name].at[
                    :, poison
                ].set(jnp.nan)
    assert seen_release and freed
    done = req
    assert list(done.tokens) == _greedy(forward, prompt, done.tokens)


def test_conservation_through_preemption_and_resume(model, forward):
    """A full group too small for three long prompts' answers: the
    youngest is preempted (BOTH groups' blocks of it come back) and
    resumes; both groups conserve throughout and every answer is still
    the forward pass's. (The window group itself never preempts a peer:
    a slot's holding there is bounded, and admission's watermark counts
    it whole.)"""
    engine = _engine(model, num_blocks=40, prefix_cache=False)
    prompts = _prompts([80, 90, 100], seed=5)
    out = _serve(engine, prompts, [30, 30, 30])
    assert engine.metrics.kv_preemptions.value() >= 1
    for prompt, r in zip(prompts, out):
        assert list(r.tokens) == _greedy(forward, prompt, r.tokens)
    stats = engine.kv_stats()
    assert stats["used"] == stats["cached"] == 0
    window_group = stats["groups"]["window"]
    assert window_group["blocks_free"] == 27
    assert window_group["bytes_in_use"] == 0
    # A window group with room for ONE slot's band and chunk (prompts
    # admitted in one pass each see it free): the relief ladder of that
    # group preempts the youngest too, and the answers stand.
    per_slot = band_blocks(WINDOW - 1, BS, 160, CHUNK)
    assert per_slot == 7
    small = _engine(model, window_blocks=per_slot + 2, prefix_cache=False)
    out = _serve(small, prompts, [6, 6, 6])
    assert small.metrics.kv_preemptions.value() >= 1
    for prompt, r in zip(prompts, out):
        assert list(r.tokens) == _greedy(forward, prompt, r.tokens)


def test_a_prefix_hit_resumes_only_where_the_tail_is_held(model, forward):
    engine = _engine(model)
    (prompt,) = _prompts([61], seed=7)          # 7 whole blocks + 5 rows
    (first,) = _serve(engine, [prompt], [4])
    assert engine.kv_stats()["prefix_tails_live"] == 1
    # the same prompt again: continued from the boundary at 56, the full
    # group's 7 blocks and the window group's 3 (rows 33 ... 55)
    (again,) = _serve(engine, [prompt], [4])
    assert again.prefix_hit_blocks == 7
    assert again.prefix_rounded_down_blocks == 0
    assert list(again.tokens) == list(first.tokens) \
        == _greedy(forward, prompt, first.tokens)
    # a longer prompt over the same 56 rows: hit at the same boundary
    longer = np.concatenate([prompt[:56], _prompts([30], seed=8)[0]])
    (third,) = _serve(engine, [longer], [4])
    assert third.prefix_hit_blocks == 7
    assert list(third.tokens) == _greedy(forward, longer, third.tokens)
    # a SHORTER match (4 blocks) has no entry that owns a tail there:
    # every matched block is given up, and the prompt is prefilled whole
    shorter = np.concatenate([prompt[:32], _prompts([10], seed=9)[0]])
    (fourth,) = _serve(engine, [shorter], [4])
    assert fourth.prefix_hit_blocks == 0
    assert fourth.prefix_rounded_down_blocks == 4
    assert list(fourth.tokens) == _greedy(forward, shorter, fourth.tokens)
    stats = engine.kv_stats()
    assert stats["prefix_rounded_down_blocks"] == 4
    assert stats["prefix_hits"] == 2
    # the tails dropped (the window group's relief valve): the entries
    # stay, and can no longer be continued from
    dropped = engine._cache.drop_tails_lru(10 ** 6)
    assert dropped > 0 and engine._cache.tails_live == 0
    engine.check_block_invariants()
    (fifth,) = _serve(engine, [prompt], [4])
    assert fifth.prefix_hit_blocks == 0
    assert fifth.prefix_rounded_down_blocks == 7
    assert list(fifth.tokens) == list(first.tokens)
    # eviction frees both groups' blocks of an entry together
    engine._cache.evict_lru(10 ** 6)
    engine.check_block_invariants()
    stats = engine.kv_stats()
    assert stats["cached"] == 0
    assert stats["groups"]["window"]["bytes_in_use"] == 0


def test_what_is_not_carried_is_refused_by_name(model):
    cfg, params = model
    with pytest.raises(ValueError, match="int8 pool"):
        _engine(model, kv_cache_dtype="int8")
    with pytest.raises(ValueError, match="speculative decoding"):
        _engine(model, spec_k=2)
    with pytest.raises(ValueError, match="not whole blocks"):
        _engine(model, prefill_chunk=4, block_size=8)
    with pytest.raises(ValueError, match="window_blocks"):
        _engine(model, window_blocks=3)
    engine = _engine(model)
    (prompt,) = _prompts([20])
    req = engine.submit(prompt, max_new_tokens=30, temperature=0.0)
    while len(req.tokens) < 3:
        engine.step()
    with pytest.raises(migrate.MigrationError, match="layer groups"):
        migrate.export_request(engine, req)
    with pytest.raises(migrate.MigrationError, match="layer groups"):
        migrate.import_request(engine, b"")
    with pytest.raises(ValueError, match="full_attention"):
        window_lm.tiny_config(layer_types=(window_lm.SLIDING,) * 2)


def test_the_reach_group_alone():
    """``kvpool/groups.py`` without an engine: the rule, the tail, the
    books."""
    tables = np.zeros((2, 12), np.int32)
    g = ReachGroup("window", 4, 23, 16, 8, tables)
    assert g.blocks_for(160, 16) == 5 + 2 and g.blocks_for(20, 16) == 3
    assert g.missing(0, 0, 20) == [0, 1, 2]
    for logical, block in zip([0, 1, 2], g.allocator.alloc(3)):
        g.adopt(0, logical, block)
    assert g.tail(0, 24) == tables[0, :3].tolist()   # rows 1 ... 23
    assert g.tail(0, 16) == tables[0, :2].tolist()
    assert g.release_below(0, 23 + 7) == 0       # row 7 is still seen
    assert g.release_below(0, 23 + 8) == 1       # block 0 slid out
    assert tables[0, 0] == 0 and g.released_total == 1
    assert g.tail(0, 24) == []                   # row 1 is gone
    g.check([31, 0])
    with pytest.raises(AssertionError, match="wholly below"):
        g.check([23 + 16, 0])
    g.release_slot(0)
    assert g.stats()["free"] == 15 and not tables.any()


def test_the_programs_through_their_kernels(model):
    """Both programs with ``kind="pool_kernel"`` (the kernels
    interpreted) land the rows and emit the tokens of the gathered
    form."""
    cfg = window_lm.tiny_config(
        layer_types=(window_lm.SLIDING, window_lm.FULL)
    )
    params = window_lm.init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(11)
    slots, mb = 2, 8
    pools = tuple(
        jnp.asarray(rng.normal(size=(g.layers, 20, BS, 2, 8)), jnp.float32)
        for g in pool_layout.cache_groups(cfg) for _ in "kv"
    )
    tables = np.zeros((2, slots, mb), np.int32)
    tables[0] = rng.permutation(np.arange(1, 20))[:slots * mb].reshape(
        slots, mb
    )
    tables[1] = rng.permutation(np.arange(1, 20))[:slots * mb].reshape(
        slots, mb
    )
    lengths = jnp.asarray([40, 9], jnp.int32)
    tokens = jnp.asarray([3, 5], jnp.int32)
    for low, slot in ((40 - 23) // BS, 0), (0, 1):     # released entries
        tables[1, slot, :low] = 0
    both = [
        window.decode_forward(
            cfg, pools, params, jnp.asarray(tables), lengths, tokens, BS,
            kind=kind, active=jnp.asarray([True, True]),
        ) for kind in ("gathered_view", "pool_kernel")
    ]
    np.testing.assert_allclose(both[0][0], both[1][0], atol=2e-4)
    chunk = jnp.asarray(rng.integers(0, 96, (1, CHUNK)), jnp.int32)
    both = [
        window.chunk_forward(
            cfg, pools, params, chunk, jnp.asarray(tables[:, 0]),
            jnp.int32(40), BS, kind=kind,
        ) for kind in ("gathered_view", "pool_kernel")
    ]
    np.testing.assert_allclose(both[0][0], both[1][0], atol=2e-4)
    for (k0, v0), (k1, v1) in zip(both[0][1], both[1][1]):
        np.testing.assert_allclose(k0, k1, atol=2e-4)
        np.testing.assert_allclose(v0, v1, atol=2e-4)
