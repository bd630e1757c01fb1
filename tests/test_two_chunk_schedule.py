"""How many prefill chunks an iteration launches
(``Scheduler.pick_prefills``, docs/DESIGN.md §25): the rule case by case
on the scheduler alone, then every served family under the one-chunk
budget (``prefill_chunk + slots``) and under the default: the same
requests must come out token for token the same, with the pool's
invariants holding after every step."""

import numpy as np
import pytest

import jax

from benchmark.runners import serve_delta
from dlrover_tpu.models import (
    conv_lm, delta_lm, linear_sparse_lm, llama, window_lm,
)
from dlrover_tpu.observability.registry import MetricsRegistry
from dlrover_tpu.serving import DECODE, Scheduler, ServingEngine
from dlrover_tpu.serving.kvpool import PagedServingEngine
from tests.benchmark import tiny_olmo_hybrid

SLOTS, CHUNK = 4, 8

# (prompt lengths, one a slot, in rid order; how many of the LAST slots
# already decode; token_budget or None for the default; launches wanted,
# as indices into the requests)
RULE = {
    "one_prefill_slot_one_chunk": ((30, 9, 9, 9), 3, None, [0]),
    "another_waits_behind_a_long_prompt_two_of_the_oldest":
        ((30, 30, 9, 9), 2, None, [0, 0]),
    "first_chunk_ends_its_prompt_one": ((8, 30, 9, 9), 2, None, [0]),
    "budget_of_chunk_plus_slots_one":
        ((30, 30, 9, 9), 2, CHUNK + SLOTS, [0]),
    "budget_too_small_none": ((30, 30, 9, 9), 2, CHUNK + 1, []),
    "nothing_decoding_budget_ignored":
        ((30, 30, 30, 30), 0, 1, [0, 0]),
}


@pytest.mark.parametrize("case", list(RULE))
def test_the_rule_of_the_second_chunk(case):
    lengths, n_decoding, budget, want = RULE[case]
    sch = Scheduler(slots=SLOTS, max_len=64, prefill_chunk=CHUNK,
                    token_budget=budget)
    assert sch.token_budget == (budget or 2 * CHUNK + SLOTS)
    reqs = [sch.submit(np.zeros(n, np.int32), 4) for n in lengths]
    sch.admit()
    for r in reqs[SLOTS - n_decoding:]:
        r.state = DECODE
    picked = sch.pick_prefills()
    assert [reqs.index(r) for r in picked] == want
    # A function of the slots' states: asking again changes nothing.
    assert sch.pick_prefills() == picked


def _seeded(init, cfg):
    return jax.jit(lambda key: init(cfg, key))(jax.random.key(0))


def _flat():
    cfg = llama.tiny_config()
    return ServingEngine, cfg, llama.init_params(cfg, jax.random.key(0))[0], \
        dict(slots=4, max_len=64, prefill_chunk=8)


def _dense():
    cls, cfg, params, kw = _flat()
    return PagedServingEngine, cfg, params, dict(kw, block_size=8)


def _window():
    cfg = window_lm.tiny_config()
    return PagedServingEngine, cfg, \
        window_lm.init_params(cfg, jax.random.PRNGKey(0)), \
        dict(slots=4, max_len=160, prefill_chunk=16, block_size=8,
             num_blocks=64, window_blocks=40)


def _conv():
    cfg = conv_lm.tiny_config()
    return PagedServingEngine, cfg, _seeded(conv_lm.init_params, cfg), \
        dict(slots=4, max_len=64, prefill_chunk=8, block_size=4,
             num_blocks=60)


def _linear():
    cfg = linear_sparse_lm.tiny_config()
    return PagedServingEngine, cfg, \
        _seeded(linear_sparse_lm.init_params, cfg), \
        dict(slots=4, max_len=128, prefill_chunk=16, block_size=8,
             num_blocks=80)


def _delta():
    cfg = serve_delta.delta_config(tiny_olmo_hybrid.CONFIG)
    return PagedServingEngine, cfg, _seeded(delta_lm.init_params, cfg), \
        dict(slots=4, max_len=192, prefill_chunk=16, block_size=8,
             num_blocks=100)


FAMILIES = {"flat": _flat, "dense": _dense, "window": _window,
            "conv": _conv, "linear": _linear, "delta": _delta}
# Prompt lengths in chunks and new tokens: five requests over the three
# slots a decoding pilot leaves, so a slot is reused; the first three
# stand in PREFILL together and the oldest is long; the fourth repeats
# the first one's prompt (a prefix hit, and for a family with per-slot
# state its one snapshot).
PLAN = ((3.2, 6), (2.4, 5), (1.3, 7), (3.2, 4), (0.6, 6))
PILOT = (4, 50)     # a prompt's rows and its new tokens: it outlasts PLAN


def _serve(cls, cfg, params, kw, budget):
    """PLAN through a fresh engine under ``budget``: ``(tokens,
    two-chunk steps, stats that must not depend on the schedule)``."""
    eng = cls(cfg, params, token_budget=budget,
              registry=MetricsRegistry(), **kw)
    chunk = kw["prefill_chunk"]
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, int(f * chunk) + 1)
               for f, _ in PLAN]
    prompts[3] = prompts[0].copy()
    # With nothing decoding the budget is ignored, so the one-chunk
    # schedule needs a request that decodes throughout.
    reqs = [eng.submit(rng.integers(1, cfg.vocab_size, PILOT[0]), PILOT[1])]
    while not reqs[0].tokens:
        eng.step()
    reqs += [eng.submit(p, n) for p, (_, n) in zip(prompts, PLAN)]
    paged = isinstance(eng, PagedServingEngine)
    while eng.pending():
        eng.step()
        if paged:
            eng.check_block_invariants()
            for g in eng._reach_groups:
                # release-then-allocate before EACH launch: a slot's
                # band, a chunk above it and the two shared ends.
                assert max(map(len, g.slot_blocks)) <= g.blocks_for(
                    kw["max_len"], chunk
                )
    assert all(r.state == "done" and not r.failed for r in reqs)
    stats = eng.kv_stats() if paged else {}
    kept = {k: stats[k] for k in (
        "prefix_hits", "prefix_hit_tokens", "state_snapshots",
        "state_restores_from_snapshot", "state_snapshots_denied",
    ) if k in stats}
    return ([list(r.tokens) for r in reqs],
            eng.metrics.two_chunk_steps.value(), kept)


@pytest.mark.parametrize("family", list(FAMILIES))
def test_one_and_two_chunk_schedules_serve_the_same_tokens(family):
    cls, cfg, params, kw = FAMILIES[family]()
    one = _serve(cls, cfg, params, kw, kw["prefill_chunk"] + kw["slots"])
    two = _serve(cls, cfg, params, kw, None)
    assert one[1] == 0 and two[1] >= 2
    assert two[0] == one[0]
    assert [len(t) for t in two[0]] == [PILOT[1]] + [n for _, n in PLAN]
    assert two[2] == one[2]
