"""chip_smoke.py's train worker at tiny_config(), for the CPU rehearsal
in tests/test_chip_smoke.py (run under ``python -m dlrover_tpu.run``)."""

import sys

import jax

import chip_smoke
from dlrover_tpu.models import llama

if __name__ == "__main__":
    # A tiny step compiles in under JAX's one-second caching threshold.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    chip_smoke.train_worker(
        llama.tiny_config(), micro=4, seq=32, n_devices=int(sys.argv[1]),
        save_step=2, replay_steps=2, out_dir=sys.argv[2],
        ckpt_dir=sys.argv[3],
    )
