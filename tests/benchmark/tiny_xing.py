"""An ``xing4``-style configuration file at CPU-test size (the keys
``runners/serve_latent.latent_config`` and ``reference_xing.shape_of``
read, widths shrunk, float32, ``original_max_position_embeddings`` 16 so
that the sessions' positions lie past what YaRN stretches) and the
context ``run.cell_context`` would build for it, with the session
traffic shrunk to seconds."""

import copy

from benchmark import common, run as bench_run
from tests.benchmark import tiny

CONFIG = {
    "model_type": "xing4_0", "hidden_act": "silu",
    "tie_word_embeddings": False, "torch_dtype": "float32",
    "attention_bias": False, "hidden_size": 32, "intermediate_size": 48,
    "vocab_size": 256, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 8,
    "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 8, "v_head_dim": 8, "rms_norm_eps": 1e-06,
    "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 0.05, "factor": 8,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16, "type": "yarn"},
    "moe_intermediate_size": 16, "moe_layer_freq": 1, "n_group": 1,
    "topk_group": 1, "n_routed_experts": 8, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_experts_per_tok": 2,
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "num_nextn_predict_layers": 1,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-06,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "serve_engine": {"slots": 4, "max_len": 96, "prefill_chunk": 8,
                     "block_size": 4, "num_blocks": 140},
}
SHRINK = {
    "clients": 6, "ramp_s": 0.5, "trace_s": 0.3, "reference_sample": 3,
    "sessions": {"count": 3, "len": 32, "rotation": "fixed"},
    "turn_len": {"dist": "log_uniform", "min": 3, "max": 16},
    "output_len": {"dist": "log_uniform", "min": 2, "max": 8},
    "prefix_hit_share_min": 0.6,
}


def context(out_dir, trace=0, seconds=2.0, seed=2 ** 31 + 7):
    return {
        "workload": "tiny-xing", "chips": 1,
        "config": copy.deepcopy(CONFIG),
        "traffic": dict(
            common.load_json("traffic", "sessions-closed-16k.json"), **SHRINK
        ),
        "seed": seed, "seconds": seconds, "trace": bool(trace),
        "out_dir": str(out_dir), "t_start": bench_run.T_START,
        "require_tpu": False, "peaks_table": tiny.PEAKS,
    }
