"""An ``lfm2_moe``-style configuration file at CPU-test size (the keys
``runners/serve_conv.conv_config`` and ``reference_lfm2.shape_of`` read,
widths shrunk, float32) and the context ``run.cell_context`` would build
for it, with the session traffic shrunk to seconds."""

import copy

from benchmark import common, run as bench_run
from tests.benchmark import tiny

CONFIG = {
    "model_type": "lfm2_moe", "torch_dtype": "float32", "tied_head": True,
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 32,
    "intermediate_size": 48, "vocab_size": 256, "num_hidden_layers": 5,
    "layer_types": ["conv", "full_attention", "conv", "conv",
                    "full_attention"],
    "num_dense_layers": 1, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "norm_eps": 1e-05,
    "rope_theta": 10000,
    "rope_parameters": {"rope_theta": 10000, "rope_type": "default"},
    "moe_intermediate_size": 16, "num_experts": 8, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "routed_scaling_factor": 1,
    "use_expert_bias": True,
    "serve_engine": {"slots": 4, "max_len": 96, "prefill_chunk": 16,
                     "block_size": 4, "num_blocks": 140},
}
SHRINK = {
    "clients": 6, "ramp_s": 0.5, "trace_s": 0.3, "reference_sample": 3,
    "sessions": {"count": 3, "len": 32, "rotation": "fixed"},
    # every turn's last whole block ends INSIDE its one chunk (of 16):
    # a snapshot taken a row late there is a wrong one
    "turn_len": {"dist": "log_uniform", "min": 5, "max": 15},
    "output_len": {"dist": "log_uniform", "min": 2, "max": 8},
}


def context(out_dir, trace=0, seconds=2.0, seed=2 ** 31 + 7):
    return {
        "workload": "tiny-lfm2", "chips": 1,
        "config": copy.deepcopy(CONFIG),
        "traffic": dict(
            common.load_json("traffic", "sessions-closed-8k.json"), **SHRINK
        ),
        "seed": seed, "seconds": seconds, "trace": bool(trace),
        "out_dir": str(out_dir), "t_start": bench_run.T_START,
        "require_tpu": False, "peaks_table": tiny.PEAKS,
    }
