"""A ``kimi_linear`` configuration file at CPU-test size (the keys
``runners/train_hybrid.hybrid_config`` reads, widths shrunk; one dense
layer and one period, 4 of 16 experts held, 64 of 256 vocabulary rows)
and the context ``run.cell_context`` would build for it."""

import copy

from benchmark import common, run as bench_run
from tests.benchmark import tiny

CONFIG = {
    "model_type": "kimi_linear", "hidden_act": "silu",
    "tie_word_embeddings": False, "torch_dtype": "float32",
    "hidden_size": 32, "intermediate_size": 64, "vocab_size": 256,
    "vocab_rows_held": 64, "num_hidden_layers": 5,
    "first_k_dense_replace": 1,
    "linear_attn_config": {
        "kda_layers": [1, 2, 3, 5, 6, 7], "full_attn_layers": [4, 8],
        "num_heads": 2, "head_dim": 8, "short_conv_kernel_size": 4,
    },
    "num_attention_heads": 2, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 4, "v_head_dim": 8, "q_lora_rank": None,
    "mla_use_nope": True,
    "num_experts": 4, "num_experts_per_token": 2,
    "moe_intermediate_size": 16, "num_shared_experts": 1,
    "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
    "routed_scaling_factor": 2.446, "num_expert_group": 1, "topk_group": 1,
    "published": {"num_experts": 16, "vocab_rows_held": 256,
                  "num_hidden_layers": 8},
    "assumed_sizes": {"kda_gate_rank": 8},
    "share": {"chips_per_layer": 4, "expert_rank": 1, "vocab_chips": 4},
    "train": {"micro_batch": 1, "grad_accum": 1, "donate_state": True,
              "warmup_steps": 10, "learning_rate": 1e-6},
}
SHRINK = {"seq_len": 80, "trace_steps": 2}   # 80: not a multiple of 64


def context(out_dir, trace=0, seconds=0.5, seed=2 ** 31 + 7):
    return {
        "workload": "tiny-kimi", "chips": 1,
        "config": copy.deepcopy(CONFIG),
        "traffic": dict(
            common.load_json("traffic", "pretrain-8k.json"), **SHRINK
        ),
        "seed": seed, "seconds": seconds, "trace": bool(trace),
        "out_dir": str(out_dir), "t_start": bench_run.T_START,
        "require_tpu": False, "peaks_table": tiny.PEAKS,
    }
