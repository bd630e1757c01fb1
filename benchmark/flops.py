"""Operations and bytes the algorithm needs, from shapes alone. Kept with
the benchmark so that no later PR can move the basis of a utilization.

Counted per trained token: every matmul parameter three times over
(forward, and the two backward products) at 2 FLOPs a multiply-add, the
output head included, plus causal attention's two sequence-length
matmuls. Not counted: the embedding lookup (a gather, not a matmul — the
repo's ``6 x count_params`` counts its table), norms and activations,
and anything rematerialisation runs twice.
"""


def matmul_params(cfg):
    """Parameters that sit in a matmul, for a published config.json."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    attn = d * (h + 2 * kv) * hd + h * hd * d
    mlp = 3 * d * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (attn + mlp) + d * cfg["vocab_size"]


def total_params(cfg):
    d = cfg["hidden_size"]
    norms = (2 * cfg["num_hidden_layers"] + 1) * d
    return matmul_params(cfg) + cfg["vocab_size"] * d + norms


def attention_flops_per_token(cfg, seq_len, passes=3):
    """Causal attention's QK^T and PV per token: 2 matmuls x 2 FLOPs x
    seq x heads x head_dim, halved by the causal mask, times ``passes``
    (1 forward, 3 forward + backward)."""
    per_layer = (
        2 * 2 * seq_len * cfg["num_attention_heads"] * cfg["head_dim"] / 2
    )
    return passes * cfg["num_hidden_layers"] * per_layer


def train_flops_per_token(cfg, seq_len):
    return 6.0 * matmul_params(cfg) + attention_flops_per_token(
        cfg, seq_len, passes=3
    )


def flash_attention_step(cfg, batch, seq_len, itemsize=2):
    """FLOPs and HBM bytes of one training step's flash-attention
    kernels (all layers): forward (S = QK^T, O = PV: 2 matmuls), dq
    (S again, dP = dO V^T, dQ = dS K: 3) and dk/dv (S again, dV = P^T dO,
    dP, dK = dS^T Q: 4), each s x s x head_dim per head and halved by
    the causal mask. Bytes: each kernel reads q, k, v (forward) plus o,
    dO (backward) and writes its outputs once, in ``itemsize`` bytes;
    the log-sum-exp rows are noise beside them."""
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, layers = cfg["head_dim"], cfg["num_hidden_layers"]
    one = 2.0 * batch * h * seq_len * seq_len * hd / 2  # causal matmul
    q_bytes = batch * seq_len * h * hd * itemsize
    kv_bytes = batch * seq_len * kv * hd * itemsize
    fwd_bytes = 2 * q_bytes + 2 * kv_bytes              # q, o; k, v
    dq_bytes = 4 * q_bytes + 2 * kv_bytes               # q, o, dO, dq
    dkv_bytes = 3 * q_bytes + 4 * kv_bytes              # q, o, dO; k, v, dk, dv
    return {
        "flops": layers * (2 + 3 + 4) * one,
        "bytes": layers * (fwd_bytes + dq_bytes + dkv_bytes),
    }


def roofline_s(work, peaks):
    """The least time the chip could take, and which roof bounds it."""
    t_flops = work["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), (
        "compute" if t_flops >= t_bytes else "memory"
    )


def peaks_for(kind, table):
    """A device that is not in the table is an error, not a default."""
    if kind not in table:
        raise KeyError(
            f"no peaks for device kind {kind!r} in benchmark/peaks.json"
        )
    return table[kind]
