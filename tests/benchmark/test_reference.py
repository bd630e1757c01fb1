"""``benchmark/reference.py`` — the plain float32 forward the chip runs
are held to — against the program at tiny size on the CPU: the training
forward and loss, and prefill + decode through ``PagedServingEngine``.

Everything here is float32 on one backend, so the two sides differ only
by the order of their sums: 1e-4 absolute on logits of order 1 (a wrong
RoPE convention, norm gain, GQA grouping or causal mask moves logits by
tenths) and 1e-5 relative on a loss of ~5.6."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference
from dlrover_tpu.models import llama
from dlrover_tpu.serving.kvpool import PagedServingEngine

LOGIT_ATOL = 1e-4


@pytest.fixture(scope="module")
def model():
    cfg = llama.tiny_config()
    params, _ = llama.init_params(cfg, jax.random.key(3))
    # Norm scales start at zero, where (1+scale) and a plain gain are
    # one function: move them so the parameterisation is tested.
    params["layers"]["attn_norm"] = 0.1 * jax.random.normal(
        jax.random.key(4), params["layers"]["attn_norm"].shape
    )
    params["final_norm"] = 0.1 * jax.random.normal(
        jax.random.key(5), params["final_norm"].shape
    )
    return cfg, params


def test_logits_agree_with_the_programs_forward(model):
    cfg, params = model
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 48))
    want, _ = llama.forward(cfg, params, jnp.asarray(tokens))
    for row, expected in zip(tokens, np.asarray(want)):
        got = reference.logits_at(
            params, jnp.asarray(row), jnp.arange(48), cfg.rope_theta
        )
        np.testing.assert_allclose(got, expected, atol=LOGIT_ATOL)


def test_loss_agrees_with_the_programs_loss(model):
    cfg, params = model
    batch = np.random.default_rng(1).integers(
        0, cfg.vocab_size, (3, 33), dtype=np.int32
    )
    want, _ = llama.loss_fn(cfg, params, {"tokens": jnp.asarray(batch)})
    got = reference.batch_loss(params, batch, cfg.rope_theta)
    assert got == pytest.approx(float(want), rel=1e-5)


def test_a_wrong_rope_base_is_seen(model):
    cfg, params = model
    tokens = jnp.asarray(
        np.random.default_rng(2).integers(0, cfg.vocab_size, 48)
    )
    want, _ = llama.forward(cfg, params, tokens[None])
    got = reference.logits_at(
        params, tokens, jnp.arange(48), cfg.rope_theta * 100
    )
    assert np.abs(np.asarray(got) - np.asarray(want)[0]).max() > 10 * LOGIT_ATOL


def test_prefill_and_decode_through_the_paged_engine_agree(model):
    """Greedy tokens served through chunked prefill and the paged cache
    are the reference's argmax at every position: the emitted token's
    reference logit sits within LOGIT_ATOL of that position's maximum."""
    cfg, params = model
    engine = PagedServingEngine(
        cfg, params, slots=2, max_len=64, prefill_chunk=8, block_size=8
    )
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in (5, 19, 30)]
    reqs = [engine.submit(p, 12) for p in prompts]
    engine.run_until_idle()
    for prompt, req in zip(prompts, reqs):
        emitted = list(req.tokens)
        assert len(emitted) == 12
        seq = jnp.asarray(prompt + emitted)
        positions = len(prompt) - 1 + jnp.arange(len(emitted))
        rows = np.asarray(reference.logits_at(
            params, seq, positions, cfg.rope_theta
        ))
        deficit = rows.max(-1) - rows[np.arange(len(emitted)), emitted]
        assert deficit.max() <= LOGIT_ATOL
