"""An ``olmo_hybrid``-style configuration file at CPU-test size (the keys
``runners/serve_delta.delta_config`` and ``reference_olmo_hybrid.shape_of``
read, widths shrunk, float32) and the context ``run.cell_context`` would
build for it, with the growing-session traffic shrunk to seconds."""

import copy

from benchmark import common, run as bench_run
from tests.benchmark import tiny

CONFIG = {
    "model_type": "olmo_hybrid", "torch_dtype": "float32",
    "hidden_size": 32, "intermediate_size": 48, "vocab_size": 96,
    "num_hidden_layers": 6,
    "layer_types": ["linear_attention", "linear_attention", "full_attention",
                    "linear_attention", "linear_attention",
                    "full_attention"],
    "published": {"num_hidden_layers": 12},
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "linear_num_key_heads": 3, "linear_num_value_heads": 3,
    "linear_key_head_dim": 8, "linear_value_head_dim": 12,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None},
    "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
    "attention_bias": False, "hidden_act": "silu",
    "serve_engine": {"slots": 4, "max_len": 192, "prefill_chunk": 16,
                     "block_size": 8, "num_blocks": 140,
                     "state_snapshots": 40},
}
SHRINK = {
    "clients": 6, "ramp_s": 0.3, "trace_s": 0.3, "reference_sample": 2,
    "period_completions": 24,
    "length_set_size": 3, "turns": 4, "reference_min_turn": 3,
    "opening_len": {"dist": "log_uniform", "min": 17, "max": 30},
    "turn_len": {"dist": "log_uniform", "min": 9, "max": 20},
    "output_len": {"dist": "log_uniform", "min": 2, "max": 6},
    "prefix_hit_share_min": 0.3,
}


def context(out_dir, trace=0, seconds=0.6, seed=2 ** 31 + 7):
    return {
        "workload": "tiny-olmo-hybrid", "chips": 1,
        "config": copy.deepcopy(CONFIG),
        "traffic": dict(
            common.load_json("traffic", "sessions-grow-closed-6k.json"),
            **SHRINK
        ),
        "seed": seed, "seconds": seconds, "trace": bool(trace),
        "out_dir": str(out_dir), "t_start": bench_run.T_START,
        "require_tpu": False, "peaks_table": tiny.PEAKS,
    }
