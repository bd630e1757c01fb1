"""The dense model's greedy reference for the serving tests: ONE compiled
forward a (config, padded length), not an un-jitted ``llama.forward`` at
every length of the growing sequence (each of which compiled its own
primitives: the tests that call it took 193 s of tier-1, ROADMAP D16)."""

import functools

import numpy as np

import jax
import jax.numpy as jnp

from dlrover_tpu.models import llama

_PAD = 64       # sequences are padded to whole multiples: few programs


@functools.lru_cache(maxsize=None)
def _forward(cfg):
    return jax.jit(lambda params, seq: llama.forward(cfg, params, seq)[0])


def naive_greedy(cfg, params, prompt, max_new: int):
    """Re-forward the growing sequence and take the argmax at its last
    row, ``max_new`` times. The sequence sits in a zero-padded row of
    fixed length: attention is causal, so the rows past the last real
    token touch no logit that is read."""
    n = len(prompt)
    seq = np.zeros((1, -(-(n + max_new) // _PAD) * _PAD), np.int32)
    seq[0, :n] = np.asarray(prompt)
    out, forward = [], _forward(cfg)
    for at in range(n, n + max_new):
        logits = forward(params, jnp.asarray(seq))
        out.append(int(jnp.argmax(logits[0, at - 1])))
        seq[0, at] = out[-1]
    return out
