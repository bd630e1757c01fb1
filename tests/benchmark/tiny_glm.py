"""A ``glm4_moe_lite`` configuration file at CPU-test size (the keys
``runners/train_latent.latent_config`` reads, widths shrunk; one dense
block, two expert blocks and the prediction module, 4 of 16 experts
held, 64 of 256 vocabulary rows) and the context ``run.cell_context``
would build for it."""

import copy

from benchmark import common, run as bench_run
from tests.benchmark import tiny

CONFIG = {
    "model_type": "glm4_moe_lite", "hidden_act": "silu",
    "attention_bias": False, "tie_word_embeddings": False,
    "torch_dtype": "float32",
    "hidden_size": 32, "intermediate_size": 64, "vocab_size": 256,
    "vocab_rows_held": 64, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_nextn_predict_layers": 1,
    "num_attention_heads": 2, "num_key_value_heads": 2,
    "q_lora_rank": 12, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 4, "v_head_dim": 8,
    "rope_theta": 1000000, "rope_scaling": None, "partial_rotary_factor": 1,
    "n_routed_experts": 4, "num_experts_per_tok": 2,
    "moe_intermediate_size": 16, "n_shared_experts": 1,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "routed_scaling_factor": 1.8, "n_group": 1, "topk_group": 1,
    "published": {"n_routed_experts": 16, "vocab_rows_held": 256,
                  "num_hidden_layers": 8},
    "share": {"chips_per_layer": 4, "expert_rank": 1, "vocab_chips": 4},
    "train": {"micro_batch": 1, "grad_accum": 1, "donate_state": True,
              "warmup_steps": 10, "learning_rate": 1e-6,
              "mtp_weight": 0.3, "remat_keep": "attention"},
}
SHRINK = {"seq_len": 80, "trace_steps": 2}


def context(out_dir, trace=0, seconds=0.5, seed=2 ** 31 + 7):
    return {
        "workload": "tiny-glm", "chips": 1,
        "config": copy.deepcopy(CONFIG),
        "traffic": dict(
            common.load_json("traffic", "pretrain-mtp-8k.json"), **SHRINK
        ),
        "seed": seed, "seconds": seconds, "trace": bool(trace),
        "out_dir": str(out_dir), "t_start": bench_run.T_START,
        "require_tpu": False, "peaks_table": tiny.PEAKS,
    }
