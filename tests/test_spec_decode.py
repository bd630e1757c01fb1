"""Self-speculative decoding tests (docs/DESIGN.md §35): greedy spec
decode must be TOKEN-EXACT vs the non-speculative engines (flat and
paged, fp and int8) with zero retraces across admissions and variable
accept lengths; the accept law must be greedy-exact and distribution-
correct under sampling; arbitrary accept-length vectors must leave the
paged allocator/prefix-cache/COW invariants intact; and the scheduler
token budget must count verification tokens."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dlrover_tpu.models import generate as gen_lib
from dlrover_tpu.models import llama
from dlrover_tpu.observability.registry import MetricsRegistry
from dlrover_tpu.serving import spec_decode as spec_lib
from dlrover_tpu.serving.engine import ServingEngine
from dlrover_tpu.serving.kvpool.engine import PagedServingEngine
from dlrover_tpu.serving.scheduler import DECODE, Scheduler
from tests.greedy_reference import naive_greedy

pytestmark = pytest.mark.spec


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.tiny_config()
    params, _ = llama.init_params(cfg, jax.random.key(0))
    return cfg, params


def copy_last_token_params(params):
    """A model that PROVABLY repeats its last token: with every block's
    output projection zeroed the residual stream is the token's own
    embedding, and with the embedding table as the unembedding logit j
    is <norm(e_tok), e_j> — largest at j = tok. Its continuation of any
    prompt is a motif the n-gram drafter must both draft and see
    accepted. A random init promises neither: whether its greedy tokens
    ever revisit the prompt is up to the RNG of the installed JAX (on
    0.9.0 they never do, and the drafter rightly proposes nothing)."""
    layers = dict(
        params["layers"],
        wo=jnp.zeros_like(params["layers"]["wo"]),
        w_down=jnp.zeros_like(params["layers"]["w_down"]),
    )
    return dict(params, layers=layers, lm_head=params["embed"].T)


def spec_prompts(cfg, seed=0):
    """One REPETITIVE prompt (the n-gram drafter's home turf when the
    model continues the motif) and one random prompt (forces draft_len 0
    / early rejections), so one episode sweeps accept lengths."""
    rs = np.random.RandomState(seed)
    rep = np.tile(rs.randint(0, cfg.vocab_size, 4).astype(np.int32), 5)
    rnd = rs.randint(0, cfg.vocab_size, 7).astype(np.int32)
    return [rep, rnd]


# ---- tentpole: token-exact greedy parity, zero retraces ---------------------


@pytest.mark.parametrize("drafter,layers", [("ngram", 0),
                                            ("early_exit", 2)])
def test_flat_spec_greedy_parity(tiny, drafter, layers):
    """Spec-on flat engine, staggered admissions: every request's
    greedy tokens must equal its solo teacher-forced run, and neither
    the base nor the spec programs may retrace after warmup."""
    cfg, params = tiny
    if drafter == "ngram":
        params = copy_last_token_params(params)
    eng = ServingEngine(cfg, params, slots=2, max_len=64,
                        prefill_chunk=4, spec_k=3,
                        spec_drafter=drafter, spec_draft_layers=layers)
    eng.warmup()
    base = dict(eng.trace_counts)
    p_rep, p_rnd = spec_prompts(cfg, seed=1)
    r0 = eng.submit(p_rep, 10)
    for _ in range(4):  # let r0 get ahead so fills diverge
        eng.step()
    r1 = eng.submit(p_rnd, 7)
    eng.run_until_idle()
    assert r0.tokens == naive_greedy(cfg, params, p_rep, 10)
    assert r1.tokens == naive_greedy(cfg, params, p_rnd, 7)
    assert eng.trace_counts == base, (
        f"retraced: {eng.trace_counts} vs {base}"
    )
    # The episode must actually exercise the draft path (a draft_len-0
    # degenerate run would vacuously "pass" parity); the n-gram
    # drafter must also ACCEPT, which the copying model guarantees —
    # early-exit acceptance depends on the (random-init) model agreeing
    # with its own truncation, which tiny_config does not.
    assert r0.spec_drafted > 0
    if drafter == "ngram":
        assert r0.tokens == [int(p_rep[-1])] * 10
        assert r0.spec_accepted > 0


@pytest.mark.parametrize("kv_dtype", ["fp", "int8"])
def test_paged_spec_greedy_parity(tiny, kv_dtype):
    """Paged engine: spec on vs spec off must emit identical greedy
    tokens (int8 included — drafted-then-rejected appends must leave
    quantized blocks bit-stable), zero retraces, and the allocator
    invariants must hold afterwards."""
    cfg, params = tiny

    def run(spec_k):
        eng = PagedServingEngine(
            cfg, params, slots=2, max_len=64, prefill_chunk=4,
            block_size=4, kv_cache_dtype=kv_dtype, spec_k=spec_k,
        )
        eng.warmup()
        base = dict(eng.trace_counts)
        p_rep, p_rnd = spec_prompts(cfg, seed=2)
        r0 = eng.submit(p_rep, 10)
        for _ in range(4):
            eng.step()
        r1 = eng.submit(p_rnd, 7)
        eng.run_until_idle()
        assert eng.trace_counts == base
        eng.check_block_invariants()
        return [r0.tokens, r1.tokens]

    assert run(spec_k=3) == run(spec_k=0)


# ---- accept law -------------------------------------------------------------


def test_spec_accept_greedy_law():
    """Hand-built logits: drafts matching the per-position argmax chain
    are accepted up to the first mismatch, the correction token is the
    argmax at the rejection position, and invalid (beyond draft_len)
    columns never count."""
    slots, K, V = 3, 3, 11
    T = K + 1
    logits = np.full((slots, T, V), -5.0, np.float32)
    best = np.array([[1, 2, 3, 4],   # slot 0: argmax chain 1,2,3,4
                     [5, 6, 7, 8],   # slot 1
                     [9, 1, 2, 3]],  # slot 2
                    np.int32)
    for s in range(slots):
        for t in range(T):
            logits[s, t, best[s, t]] = 5.0
    drafts = np.array([
        [1, 2, 3],    # all match -> accept 3, bonus = best[0, 3] = 4
        [5, 0, 8],    # mismatch at i=1 -> accept 1, correction best[1,1]
        [9, 1, 2],    # matches but draft_len=0 -> accept 0
    ], np.int32)
    draft_len = np.array([3, 3, 0], np.int32)
    emitted, acc = jax.jit(spec_lib.spec_accept)(
        jnp.asarray(logits), jnp.asarray(drafts),
        jnp.asarray(draft_len), jnp.zeros(slots, jnp.float32),
        jnp.ones(slots, bool), jnp.zeros(slots, jnp.int32),
        jax.random.key(7), jnp.int32(0),
    )
    emitted, acc = np.asarray(emitted), np.asarray(acc)
    assert acc.tolist() == [3, 1, 0]
    assert emitted[0, :4].tolist() == [1, 2, 3, 4]
    assert emitted[1, :2].tolist() == [5, 6]
    assert emitted[2, 0] == 9


def test_spec_accept_rejection_sampling_is_distribution_correct():
    """temperature > 0: with a deterministic drafter the accept law is
    Leviathan rejection sampling — each draft accepted w.p. p(draft),
    the correction drawn from the residual (draft masked out). Checked
    empirically over many independent slots: the accept rate matches
    p(draft) and a rejected slot never re-emits the rejected token."""
    slots, V = 4096, 8
    K = 1
    rs = np.random.RandomState(11)
    logits = rs.randn(slots, K + 1, V).astype(np.float32)
    drafts = np.full((slots, K), 3, np.int32)
    temps = np.full(slots, 1.0, np.float32)
    emitted, acc = jax.jit(spec_lib.spec_accept)(
        jnp.asarray(logits), jnp.asarray(drafts),
        jnp.asarray(np.ones(slots, np.int32)), jnp.asarray(temps),
        jnp.ones(slots, bool), jnp.zeros(slots, jnp.int32),
        jax.random.key(3), jnp.int32(5),
    )
    emitted, acc = np.asarray(emitted), np.asarray(acc)
    p_draft = np.exp(logits[:, 0]) / np.exp(logits[:, 0]).sum(
        -1, keepdims=True
    )
    expected = float(p_draft[:, 3].mean())
    observed = float((acc == 1).mean())
    # 4096 Bernoulli trials: 4 sigma ~ 4*sqrt(0.25/4096) ~ 0.031.
    assert abs(observed - expected) < 0.035, (observed, expected)
    rejected = acc == 0
    assert rejected.any() and (~rejected).any()
    # The residual pick must NEVER return the rejected draft token.
    assert (emitted[rejected, 0] != 3).all()


def test_spec_verify_attention_T1_matches_append_free():
    """T=1 (no drafts) must reduce the verify attention to the exact
    single-token append-free step the decode program uses."""
    from dlrover_tpu.ops.decode_attention import spec_verify_attention

    b, S, h, kh, d = 3, 16, 4, 2, 8
    rs = np.random.RandomState(5)
    q = rs.randn(b, 1, h, d).astype(np.float32)
    k_c = rs.randn(b, S, kh, d).astype(np.float32)
    v_c = rs.randn(b, S, kh, d).astype(np.float32)
    k_n = rs.randn(b, 1, kh, d).astype(np.float32)
    v_n = rs.randn(b, 1, kh, d).astype(np.float32)
    lens = np.array([0, 5, 15], np.int32)
    got = spec_verify_attention(
        jnp.asarray(q), jnp.asarray(k_c), jnp.asarray(v_c),
        jnp.asarray(k_n), jnp.asarray(v_n), jnp.asarray(lens),
    )
    want = gen_lib._append_free_attention(
        jnp.asarray(q), jnp.asarray(k_c), jnp.asarray(v_c),
        jnp.asarray(k_n), jnp.asarray(v_n), jnp.asarray(lens),
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


# ---- satellite: sample_token_logprobs ---------------------------------------


def test_sample_token_logprobs_matches_sample_token():
    """The logprob variant must pick the IDENTICAL token as
    sample_token for every (key, temperature), report the token's
    log-probability under the temperature-scaled softmax, and the
    top-k extension must contain the argmax."""
    rs = np.random.RandomState(4)
    logits = jnp.asarray(rs.randn(6, 32).astype(np.float32))
    temps = jnp.asarray([0.0, 0.0, 0.7, 1.0, 1.5, 0.3], jnp.float32)
    for seed in range(3):
        key = jax.random.key(seed)
        want = gen_lib.sample_token(logits, key, temps)
        tok, lp = gen_lib.sample_token_logprobs(logits, key, temps)
        assert np.array_equal(np.asarray(tok), np.asarray(want))
        base = np.asarray(logits)
        t = np.asarray(temps)[:, None]
        scaled = np.where(t > 0, base / np.maximum(t, 1e-6), base)
        ref = scaled - np.log(
            np.exp(scaled - scaled.max(-1, keepdims=True)).sum(
                -1, keepdims=True
            )
        ) - scaled.max(-1, keepdims=True)
        want_lp = ref[np.arange(6), np.asarray(tok)]
        np.testing.assert_allclose(np.asarray(lp), want_lp, rtol=1e-5,
                                   atol=1e-5)
    tok, lp, tk_idx, tk_lp = gen_lib.sample_token_logprobs(
        logits, jax.random.key(0), temps, top_k=5
    )
    assert tk_idx.shape == (6, 5) and tk_lp.shape == (6, 5)
    argmax = np.asarray(jnp.argmax(logits, axis=-1))
    assert all(
        argmax[i] in np.asarray(tk_idx)[i] for i in range(6)
    )
    # top-k logprobs are sorted descending.
    assert (np.diff(np.asarray(tk_lp), axis=1) <= 1e-6).all()


# ---- satellite: per-token latency accounting --------------------------------


def test_token_latency_observed_once_per_token(tiny):
    """A verify step committing N tokens must add N observations (at
    dt/N each), not one at the full iteration time — the histogram's
    count equals the decode-token counter minus the first tokens that
    prefill emits outside the decode loop."""
    cfg, params = tiny
    reg = MetricsRegistry()
    eng = ServingEngine(cfg, params, slots=2, max_len=64,
                        prefill_chunk=4, spec_k=3, registry=reg)
    eng.warmup()
    p_rep, p_rnd = spec_prompts(cfg, seed=3)
    eng.submit(p_rep, 10)
    eng.submit(p_rnd, 6)
    eng.run_until_idle()
    decode_tokens = reg.get("serving_tokens_total").value(kind="decode")
    assert decode_tokens == 16
    assert reg.get("serving_token_latency_seconds").count() == (
        decode_tokens - 2  # two first tokens came from prefill
    )
    # Spec accounting families moved with the same episode.
    drafted = reg.get("serving_spec_tokens_total").value(kind="drafted")
    accepted = reg.get("serving_spec_tokens_total").value(
        kind="accepted"
    )
    rejected = reg.get("serving_spec_tokens_total").value(
        kind="rejected"
    )
    assert drafted == accepted + rejected
    assert accepted > 0
    assert reg.get("serving_spec_accepted_tokens_per_step").value() >= 1.0


# ---- satellite: scheduler budget counts verification tokens -----------------


def test_scheduler_budget_counts_verification_tokens():
    """With decode_tokens_per_slot = 1 + spec_k, a decoding slot
    reserves its verification tokens, so the same token_budget that
    admits a prefill chunk alongside 1-token decode refuses it when
    every decode step may burn K+1."""

    def gated(per_slot):
        sch = Scheduler(slots=2, max_len=32, prefill_chunk=8,
                        token_budget=10,
                        decode_tokens_per_slot=per_slot)
        dec = sch.submit(np.arange(4, dtype=np.int32), 4)
        pre = sch.submit(np.arange(4, dtype=np.int32), 4)
        sch.admit(0.0)
        dec.state = DECODE
        return sch.pick_prefills() == []

    assert not gated(1)   # 1*1 + 8 = 9 <= 10: prefill proceeds
    assert gated(4)       # 1*4 + 8 = 12 > 10: decode reserves first
    eng_budget = Scheduler(slots=2, max_len=32, prefill_chunk=8,
                           decode_tokens_per_slot=4)
    # The default never blocks what the rule would launch: two chunks.
    assert eng_budget.token_budget == 2 * 8 + 2 * 4


def test_engine_wires_spec_budget(tiny):
    cfg, params = tiny
    eng = ServingEngine(cfg, params, slots=2, max_len=32,
                        prefill_chunk=4, spec_k=3)
    assert eng.scheduler.decode_tokens_per_slot == 4
    with pytest.raises(ValueError, match="spec_drafter"):
        ServingEngine(cfg, params, slots=2, max_len=32,
                      prefill_chunk=4, spec_k=2, spec_drafter="nope")


# ---- satellite: random accept lengths vs block invariants -------------------


class _OracleDraftEngine(PagedServingEngine):
    """Paged engine whose drafter proposes the TRUE greedy continuation
    with a randomly corrupted suffix — sweeping the whole accept-length
    range 0..K per slot per step while keeping greedy output exactly
    checkable against the solo run."""

    def __init__(self, *a, oracle=None, oracle_seed=0, **kw):
        super().__init__(*a, **kw)
        self._oracle = oracle  # rid -> full greedy continuation
        self._oracle_rs = np.random.RandomState(oracle_seed)

    def _spec_draft(self, decoding, active):
        K = self.spec_k
        draft_len = np.zeros(self.slots, np.int32)
        drafts = np.zeros((self.slots, K), np.int32)
        for r in decoding:
            cap = spec_lib.clamp_draft_len(
                K, len(r.tokens), r.max_new_tokens,
                int(self._lengths[r.slot]), self.max_len,
            )
            n = self._oracle_rs.randint(0, cap + 1)
            if n == 0:
                continue
            cont = self._oracle[r.rid][
                len(r.tokens):len(r.tokens) + n
            ]
            row = np.zeros(n, np.int32)
            row[:len(cont)] = cont
            if self._oracle_rs.rand() < 0.5:
                # Corrupt a random tail -> acceptance truncates there.
                j = self._oracle_rs.randint(0, n)
                row[j] = (row[j] + 1) % self.config.vocab_size
            drafts[r.slot, :n] = row
            draft_len[r.slot] = n
        return drafts, draft_len


def test_random_accept_lengths_keep_block_invariants(tiny):
    """Satellite 3 property test: random accept-length vectors through
    the paged engine (prefix cache + COW live, shared prompt heads)
    must keep greedy parity, block conservation, and refcount sanity
    after EVERY episode."""
    cfg, params = tiny
    rs = np.random.RandomState(21)
    shared_head = rs.randint(0, cfg.vocab_size, 8).astype(np.int32)
    prompts = [
        np.concatenate([
            shared_head,
            rs.randint(0, cfg.vocab_size, 1 + rs.randint(4)),
        ]).astype(np.int32)
        for _ in range(4)
    ]
    expect = {
        i: naive_greedy(cfg, params, p, 12)
        for i, p in enumerate(prompts)
    }
    eng = _OracleDraftEngine(
        cfg, params, slots=2, max_len=64, prefill_chunk=4,
        block_size=4, spec_k=3, oracle_seed=13,
    )
    eng._oracle = {}
    eng.warmup()
    for episode in range(2):
        reqs = []
        for i, p in enumerate(prompts):
            r = eng.submit(p, 12)
            eng._oracle[r.rid] = expect[i]
            reqs.append(r)
            eng.step()  # interleave admissions with decode
        eng.run_until_idle()
        for i, r in enumerate(reqs):
            assert r.tokens == expect[i], f"episode {episode} req {i}"
        eng.check_block_invariants()
        stats = eng.kv_stats()
        # All slots drained: no used blocks may linger.
        assert stats["used"] == 0
        assert stats["free"] + stats["cached"] == eng._allocator.managed
