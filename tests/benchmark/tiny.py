"""Tiny stand-ins for the data files, for CPU rehearsals: a published-
style config at ``tiny_config()``'s sizes and the three traffic mixes
shrunk to seconds."""

import copy

from benchmark import common, run as bench_run

CONFIG = {
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_hidden_layers": 2,
    "num_key_value_heads": 2, "head_dim": 16, "rope_theta": 10000.0,
    "tie_word_embeddings": False, "torch_dtype": "float32",
    "vocab_size": 256,
    "train": {"micro_batch": 2, "grad_accum": 1, "donate_state": True,
              "warmup_steps": 10},
    "serve_engine": {"slots": 4, "max_len": 96, "prefill_chunk": 16,
                     "block_size": 8},
}
PEAKS = {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
SHRINK = {
    "pretrain-4k": {"seq_len": 32, "trace_steps": 2},
    "save-kill-resume": {"seq_len": 32, "trace_steps": 2, "timeout_s": 200},
    "chat-closed": {
        "clients": 8, "ramp_s": 0.5, "trace_s": 0.3, "reference_sample": 4,
        "prompt_len": {"dist": "log_uniform", "min": 8, "max": 64},
        "output_len": {"dist": "log_uniform", "min": 4, "max": 32},
    },
}


def context(traffic, out_dir, trace=0, seconds=0.5, seed=2 ** 31 + 7,
            **more_traffic):
    """What ``run.cell_context`` builds for a cell, at tiny size and
    without the demand for a TPU."""
    return {
        "workload": "tiny", "chips": 1, "config": copy.deepcopy(CONFIG),
        "traffic": dict(
            common.load_json("traffic", traffic + ".json"),
            **SHRINK[traffic], **more_traffic,
        ),
        "seed": seed, "seconds": seconds, "trace": bool(trace),
        "out_dir": str(out_dir), "t_start": bench_run.T_START,
        "require_tpu": False, "peaks_table": PEAKS,
    }
