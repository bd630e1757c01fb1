"""Operations and bytes the ``kimi_linear`` step needs, from shapes and
from the step's own row counts. Kept with the benchmark, as ``flops.py``
is, so that no later PR can move the basis of a utilization.

Per trained token: every matmul parameter a token really touches three
times over (forward and the two backward products) at 2 FLOPs a
multiply-add -- the mixers, the dense MLP, the shared experts, the
router, the head over the rows held, and the HELD experts at the rows
the step counted for them (an absent expert's rows cost this chip
nothing) -- plus latent attention's two causal sequence-length matmuls
(at the q/k head size and at the value head size) and KDA's chunk
algebra. Not counted: the embedding lookup, norms, activations, gates'
elementwise work, and anything rematerialisation runs twice.

KDA's chunk algebra, per head and chunk of C tokens (dk, dv the head
sizes; a triangular product counts its half): the two decayed products
``A``, ``P`` (C^2 dk each), the unit triangular solve (2 C^3 / 3),
``T K`` and ``T V`` (C^2 dk, C^2 dv), ``P U`` (C^2 dv), and the three
products with the dk x dv state (2 C dk dv each). The backward is
counted as twice the forward. ``KDA_CHUNK`` is the algorithm's chunk,
fixed here whatever chunk the program picks.
"""

KDA_CHUNK = 64


def layer_kinds(cfg):
    """[(mixer, ffn)] of the layers this configuration file keeps."""
    linear = cfg["linear_attn_config"]
    return [
        ("kda" if i in linear["kda_layers"] else "mla",
         "dense" if i <= cfg["first_k_dense_replace"] else "moe")
        for i in range(1, cfg["num_hidden_layers"] + 1)
    ]


def kda_params(cfg):
    linear = cfg["linear_attn_config"]
    d, width = cfg["hidden_size"], linear["num_heads"] * linear["head_dim"]
    r = cfg["assumed_sizes"]["kda_gate_rank"]
    return (
        4 * d * width                     # q, k, v, o
        + 2 * (d * r + r * width)         # the two low-rank gates
        + d * linear["num_heads"]         # beta
        + 3 * linear["short_conv_kernel_size"] * width
    )


def mla_params(cfg):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, dv = cfg["kv_lora_rank"], cfg["v_head_dim"]
    return (
        d * h * (nope + rope) + d * (rank + rope)
        + rank * h * (nope + dv) + h * dv * d
    )


def expert_params(cfg):
    """One routed expert (the shared expert is this times
    ``num_shared_experts``)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def fixed_matmul_params(cfg):
    """Matmul parameters every token touches: all but the routed
    experts."""
    d = cfg["hidden_size"]
    total = d * cfg["vocab_rows_held"]
    for mixer, ffn in layer_kinds(cfg):
        total += kda_params(cfg) if mixer == "kda" else mla_params(cfg)
        if ffn == "dense":
            total += 3 * d * cfg["intermediate_size"]
        else:
            total += d * cfg["published"]["num_experts"]
            total += cfg["num_shared_experts"] * expert_params(cfg)
    return total


def total_params(cfg):
    """Every parameter held here (embedding, norms, held experts)."""
    d = cfg["hidden_size"]
    linear = cfg["linear_attn_config"]
    total = fixed_matmul_params(cfg) + d * cfg["vocab_rows_held"] + d
    for mixer, ffn in layer_kinds(cfg):
        total += 2 * d
        if mixer == "kda":
            total += (linear["num_heads"] * (1 + linear["head_dim"])
                      + linear["head_dim"])
        else:
            total += cfg["kv_lora_rank"]
        if ffn == "moe":
            total += cfg["num_experts"] * expert_params(cfg)
    return total


def n_layers_of(cfg, mixer):
    return sum(m == mixer for m, _ in layer_kinds(cfg))


def kda_chunk_flops_per_token(cfg, passes=3):
    """One KDA layer's chunk algebra per token (see the module
    docstring), forward (``passes`` 1) or forward + backward (3)."""
    linear = cfg["linear_attn_config"]
    dk = dv = linear["head_dim"]
    c = KDA_CHUNK
    per_head = c * (3 * dk + 2 * dv) + 2 * c * c / 3 + 6 * dk * dv
    return passes * linear["num_heads"] * per_head


def mla_attention_flops_per_token(cfg, seq_len, passes=3):
    """One latent-attention layer's QK^T (at nope + rope) and PV (at the
    value head size) per token, halved by the causal mask."""
    dqk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    per_pass = 2 * seq_len * cfg["num_attention_heads"] * (
        dqk + cfg["v_head_dim"]
    ) / 2
    return passes * per_pass


def train_flops_per_token(cfg, seq_len, expert_rows_per_token):
    """``expert_rows_per_token``: (token, k) pairs the held experts
    computed in a step, summed over the expert layers, over the step's
    tokens (the step's ``moe_rows_held`` counter)."""
    return (
        6.0 * fixed_matmul_params(cfg)
        + 6.0 * expert_rows_per_token * expert_params(cfg)
        + n_layers_of(cfg, "mla") * mla_attention_flops_per_token(
            cfg, seq_len
        )
        + n_layers_of(cfg, "kda") * kda_chunk_flops_per_token(cfg)
    )


def kda_scan_step(cfg, batch, seq_len, itemsize=4):
    """FLOPs and HBM bytes of one training step's delta-rule scans (all
    KDA layers), float32: the forward reads q, k, g (dk a head), v, beta
    and writes o; the backward reads them and dO and writes the five
    gradients; one dk x dv state a chunk is written forward and read
    back."""
    linear = cfg["linear_attn_config"]
    h, dk = linear["num_heads"], linear["head_dim"]
    dv, tokens = dk, batch * seq_len
    fwd = 3 * dk + 2 * dv + 1
    bwd = (3 * dk + dv + 1) * 2 + dv
    states = 2 * dk * dv / KDA_CHUNK
    layers = n_layers_of(cfg, "kda")
    return {
        "flops": layers * tokens * kda_chunk_flops_per_token(cfg),
        "bytes": layers * tokens * h * (fwd + bwd + states) * itemsize,
    }


def mla_flash_step(cfg, batch, seq_len, itemsize=2):
    """FLOPs and HBM bytes of one training step's latent-attention flash
    kernels (all MLA layers), as ``flops.flash_attention_step`` counts
    the dense model's but with q/k of ``dqk`` and values of ``dv``:
    forward S (dqk) and PV (dv); dq S, dP (dv), dQ (dqk); dk/dv S, dV
    (dv), dP (dv), dK (dqk); each a causal half of b h s^2."""
    h = cfg["num_attention_heads"]
    dqk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    half = 2.0 * batch * h * seq_len * seq_len / 2
    rows = batch * seq_len * h * itemsize
    layers = n_layers_of(cfg, "mla")
    return {
        "flops": layers * half * (5 * dqk + 4 * dv),
        # fwd q, k, v, o; dq q, k, v, o, dO, dq; dk/dv q, o, dO, k, v, dk, dv
        "bytes": layers * rows * (8 * dqk + 9 * dv),
    }


def expert_gmm_step(cfg, rows_held, itemsize=2):
    """FLOPs and HBM bytes of one training step's grouped expert matmuls
    at the rows the step counted (``rows_held``, summed over the expert
    layers): gate + up and down, forward and both backward products; the
    held experts' weights are read twice and their gradients written
    once a layer, the rows' activations move once a product."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    layers = sum(ffn == "moe" for _, ffn in layer_kinds(cfg))
    weights = layers * cfg["num_experts"] * expert_params(cfg)
    return {
        "flops": 6.0 * rows_held * expert_params(cfg),
        "bytes": 3 * itemsize * (weights + rows_held * (2 * d + 3 * f)),
    }
