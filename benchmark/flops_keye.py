"""Operations and bytes of ``keye-vl2-30b-a3b``'s decode step, from the
published keys and the step's own counts. Kept with the benchmark so
that no later PR can move the basis of a roofline share. All three are
memory-bound in decode (one query a slot): the FLOPs are given for the
record, the bytes decide.

- the indexer reads one index key a visible row a layer (``kv_rows`` of
  the step span: the sum of the decoding slots' fills) and scores it
  against 16 index queries;
- the main heads read the K and V rows of the selection (``selected_rows``:
  the sum of ``min(fill, topk)``), and nothing else of the cache;
- the experts read the gate, up and down weights of every expert a
  step's tokens hit (``experts_hit``, the mean over layers), once.
"""


def parameter_count(cfg):
    """Parameters of the configuration as cut, from the published keys
    (what ``models/sparse_lm.py``'s tree must hold)."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    sa = cfg["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    attn = d * (h + 2 * kh) * hd + h * hd * d + 2 * hd
    index = d * (hi * di + di + hi) + 2 * di
    experts = e * 3 * d * f + d * e
    layer = attn + index + experts + 2 * d
    return cfg["num_hidden_layers"] * layer + 2 * cfg["vocab_size"] * d + d


def cache_bytes_per_token(cfg, itemsize=2):
    """K + V + index key, all layers."""
    kv = 2 * cfg["num_key_value_heads"] * cfg["head_dim"]
    return cfg["num_hidden_layers"] * itemsize * (
        kv + cfg["sa_config"]["indexer_head_dim"]
    )


def index_select_step(cfg, kv_rows, itemsize=2):
    """Score and select: every visible row's index key is read once a
    layer; 2 FLOPs a multiply-add against 16 index queries."""
    sa = cfg["sa_config"]
    layers = cfg["num_hidden_layers"]
    return {
        "flops": 2.0 * layers * kv_rows * sa["indexer_num_heads"]
        * sa["indexer_head_dim"],
        "bytes": float(layers * kv_rows * sa["indexer_head_dim"] * itemsize),
    }


def sparse_attention_step(cfg, selected_rows, itemsize=2):
    """Gather and attend: the selected rows' K and V, read once a layer;
    QK^T and PV for every query head."""
    layers, hd = cfg["num_hidden_layers"], cfg["head_dim"]
    return {
        "flops": 2.0 * 2 * layers * selected_rows
        * cfg["num_attention_heads"] * hd,
        "bytes": float(
            layers * selected_rows * 2 * cfg["num_key_value_heads"] * hd
            * itemsize
        ),
    }


def expert_step(cfg, experts_hit, n_tokens, itemsize=2):
    """The grouped matmuls: the three projections of every expert hit
    (a layer's mean), read once a layer; ``n_tokens x top_k`` rows of
    FLOPs."""
    layers, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    f = cfg["moe_intermediate_size"]
    rows = n_tokens * cfg["num_experts_per_tok"]
    return {
        "flops": 2.0 * layers * rows * 3 * d * f,
        "bytes": float(layers * experts_hit * 3 * d * f * itemsize),
    }
