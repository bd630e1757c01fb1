"""Operations and bytes of ``xing4-29b-a4b``'s two serving programs,
from the published keys and the steps' own counts. Kept with the
benchmark so that no later PR can move the basis of a roofline share.

- the decode step's latent attention reads one cache row (``kv_lora_rank
  + qk_rope_head_dim`` numbers) a visible row a layer (``kv_rows`` of the
  step span: the sum of the decoding slots' fills): memory-bound (~60
  FLOP a byte absorbed), and counted from the rows whatever implements
  it;
- the prefill chunk's latent attention is charged the DEFINITION's
  operations, ``2 T heads rows (qk_nope + qk_rope + v)`` a layer
  (``prefill_kv_rows``: the slot's fill below the chunk plus the chunk):
  the least any form can do, so an absorbed chunk is charged for its
  extra work and the share cannot pass 100;
- the experts read the gate, up and down weights of every expert a
  step's tokens hit (``experts_hit``, the mean over the EXPERT layers),
  once, in every expert layer (``num_hidden_layers -
  first_k_dense_replace``: the leading dense layers have none).
"""


def parameter_count(cfg):
    """Parameters of the configuration as cut, from the published keys
    (what ``models/latent_lm.py``'s tree must hold)."""
    d, h, n = cfg["hidden_size"], cfg["num_attention_heads"], cfg["hc_mult"]
    rq, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    attn = (
        d * rq + rq + rq * h * (nope + rope) + d * (r + rope) + r
        + r * h * (nope + v) + h * v * d
    )
    maps = 2 * n + n * n
    mhc = n * d * maps + n * d + maps + 3        # phi, its norm, bias, alpha
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    e, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    expert_layer = (
        d * e + e + e * 3 * d * f + cfg["n_shared_experts"] * 3 * d * f
    )
    return (
        layers * (attn + 2 * mhc + 2 * d)
        + dense * 3 * d * cfg["intermediate_size"]
        + (layers - dense) * expert_layer
        + 2 * cfg["vocab_size"] * d + d
    )


def cache_bytes_per_token(cfg, itemsize=2):
    """The latent and the rotated positional key, all layers."""
    width = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return cfg["num_hidden_layers"] * width * itemsize


def latent_attention_step(cfg, kv_rows, itemsize=2):
    """The decode step: every visible row read once a layer; absorbed,
    a row meets every head's query (``r + rope`` multiply-adds) and its
    probability (``r``)."""
    r, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    layers, h = cfg["num_hidden_layers"], cfg["num_attention_heads"]
    return {
        "flops": 2.0 * layers * kv_rows * h * (2 * r + rope),
        "bytes": float(layers * kv_rows * (r + rope) * itemsize),
    }


def latent_attention_chunk(cfg, chunk_tokens, kv_rows, itemsize=2):
    """The prefill chunk, as the definition counts it: QK^T over ``nope +
    rope`` and PV over ``v`` for every head; the rows read once."""
    layers, h = cfg["num_hidden_layers"], cfg["num_attention_heads"]
    per = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
           + cfg["v_head_dim"])
    return {
        "flops": 2.0 * layers * chunk_tokens * h * kv_rows * per,
        "bytes": float(
            layers * kv_rows
            * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize
        ),
    }


def expert_step(cfg, experts_hit, n_tokens, itemsize=2):
    """The grouped matmuls: the three projections of every expert hit
    (an expert layer's mean), read once an EXPERT layer; ``n_tokens x
    top_k`` rows of FLOPs."""
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = n_tokens * cfg["num_experts_per_tok"]
    return {
        "flops": 2.0 * layers * rows * 3 * d * f,
        "bytes": float(layers * experts_hit * 3 * d * f * itemsize),
    }
