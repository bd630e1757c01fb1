"""A ``minicpm_sala``-style configuration file at CPU-test size (the keys
``runners/serve_linear.linear_config`` and ``reference_sala.shape_of``
read, widths shrunk, float32) and the context ``run.cell_context`` would
build for it, with the document traffic shrunk to seconds."""

import copy

from benchmark import common, run as bench_run
from tests.benchmark import tiny

CONFIG = {
    "model_type": "minicpm_sala", "torch_dtype": "float32",
    "hidden_size": 32, "intermediate_size": 48, "vocab_size": 256,
    "num_hidden_layers": 5, "first_published_layer": 2,
    "mixer_types": ["minicpm4", "lightning-attn", "lightning-attn",
                    "minicpm4", "lightning-attn"],
    "published": {"num_hidden_layers": 8},
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "lightning_nh": 4, "lightning_nkv": 4, "lightning_head_dim": 8,
    "lightning_use_rope": True, "attn_use_rope": False, "qk_norm": True,
    "rms_norm_eps": 1e-06, "rope_theta": 10000, "scale_emb": 12,
    "scale_depth": 1.4, "dim_model_base": 8, "tie_word_embeddings": False,
    "use_output_gate": True, "use_output_norm": True,
    "attn_use_output_gate": True,
    "assumed": {"sparse_config": {
        "kernel_size": 4, "kernel_stride": 2, "block_size": 8, "topk": 5,
        "init_blocks": 1, "window_size": 16, "dense_len": 16,
    }},
    "serve_engine": {"slots": 4, "max_len": 128, "prefill_chunk": 16,
                     "block_size": 8, "num_blocks": 120,
                     "state_snapshots": 12},
}
SHRINK = {
    "clients": 6, "ramp_s": 0.5, "trace_s": 0.3, "reference_sample": 2,
    "documents": {"count": 3, "len": 64, "rotation": "fixed"},
    # a turn's last whole block ends INSIDE its chunks (of 16)
    "question_len": {"dist": "log_uniform", "min": 9, "max": 23},
    "output_len": {"dist": "log_uniform", "min": 2, "max": 8},
}


def context(out_dir, trace=0, seconds=2.0, seed=2 ** 31 + 7):
    return {
        "workload": "tiny-sala", "chips": 1,
        "config": copy.deepcopy(CONFIG),
        "traffic": dict(
            common.load_json("traffic", "docs-closed-64k.json"), **SHRINK
        ),
        "seed": seed, "seconds": seconds, "trace": bool(trace),
        "out_dir": str(out_dir), "t_start": bench_run.T_START,
        "require_tpu": False, "peaks_table": tiny.PEAKS,
    }
