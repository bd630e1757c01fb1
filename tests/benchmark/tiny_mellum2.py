"""A ``mellum``-style configuration file at CPU-test size (the keys
``runners/serve_window.window_config`` and ``reference_mellum2.shape_of``
read, widths shrunk, a window of 24 rows, float32) and the context
``run.cell_context`` would build for it, with the mixed traffic shrunk
to seconds: "long" prompts several windows long, short ones below and
around one."""

import copy

from benchmark import common, run as bench_run
from tests.benchmark import tiny

TYPES = ["sliding_attention", "sliding_attention", "full_attention"]
CONFIG = {
    "model_type": "mellum", "torch_dtype": "float32",
    "tie_word_embeddings": False, "hidden_size": 32,
    "intermediate_size": 48, "vocab_size": 256, "num_hidden_layers": 3,
    "layer_types": TYPES, "mlp_layer_types": ["sparse"] * 3,
    "sliding_window": 24, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "rms_norm_eps": 1e-06,
    "rope_theta": 10000,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 10000, "factor": 4,
            "original_max_position_embeddings": 32, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.138629436111989,
        },
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000},
    },
    "moe_intermediate_size": 16, "num_experts": 8, "num_experts_per_tok": 2,
    "norm_topk_prob": True,
    "serve_engine": {"slots": 4, "max_len": 160, "prefill_chunk": 16,
                     "block_size": 8, "num_blocks": 96, "window_blocks": 40},
}
SHRINK = {
    "clients": 6, "ramp_s": 0.5, "trace_s": 0.3, "reference_sample": 3,
    "reference_long": 1, "length_set_size": 8,
    "prompt_len": {"dist": "log_uniform", "min": 5, "max": 40},
    "long_prompt_len": {"dist": "log_uniform", "min": 70, "max": 120},
    "output_len": {"dist": "log_uniform", "min": 2, "max": 8},
}


def context(out_dir, trace=0, seconds=2.0, seed=2 ** 31 + 7):
    return {
        "workload": "tiny-mellum2", "chips": 1,
        "config": copy.deepcopy(CONFIG),
        "traffic": dict(
            common.load_json("traffic", "mixed-closed-16k.json"), **SHRINK
        ),
        "seed": seed, "seconds": seconds, "trace": bool(trace),
        "out_dir": str(out_dir), "t_start": bench_run.T_START,
        "require_tpu": False, "peaks_table": tiny.PEAKS,
    }
