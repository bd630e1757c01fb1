"""A ``keye-vl2``-style configuration file at CPU-test size (the keys
``runners/serve_sparse.sparse_config`` and ``reference_keye.shape_of``
read, widths shrunk, float32) and the context ``run.cell_context`` would
build for it, with the document traffic shrunk to seconds."""

import copy

from benchmark import common, run as bench_run
from tests.benchmark import tiny

CONFIG = {
    "model_type": "KeyeVL2", "hidden_act": "silu",
    "tie_word_embeddings": False, "torch_dtype": "float32",
    "hidden_size": 32, "intermediate_size": 64, "vocab_size": 256,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "rope_theta": 10000.0,
    "num_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 16, "norm_topk_prob": True,
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                  "indexer_num_kv_heads": 1, "topk": 8},
    "serve_engine": {"slots": 4, "max_len": 96, "prefill_chunk": 8,
                     "block_size": 4, "num_blocks": 120},
}
SHRINK = {
    "clients": 6, "ramp_s": 0.5, "trace_s": 0.3, "reference_sample": 3,
    "documents": {"count": 3, "len": 32, "rotation": "fixed"},
    "question_len": {"dist": "log_uniform", "min": 3, "max": 16},
    "output_len": {"dist": "log_uniform", "min": 2, "max": 8},
    "prefix_hit_share_min": 0.6,
}


def context(out_dir, trace=0, seconds=2.0, seed=2 ** 31 + 7):
    return {
        "workload": "tiny-keye", "chips": 1,
        "config": copy.deepcopy(CONFIG),
        "traffic": dict(
            common.load_json("traffic", "docqa-closed-32k.json"), **SHRINK
        ),
        "seed": seed, "seconds": seconds, "trace": bool(trace),
        "out_dir": str(out_dir), "t_start": bench_run.T_START,
        "require_tpu": False, "peaks_table": tiny.PEAKS,
    }
