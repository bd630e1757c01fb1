"""Operations and bytes the ``glm-4.7-flash`` training step needs, from
shapes and from the step's own row counts. Kept with the benchmark, as
``flops.py`` and ``flops_kimi_linear.py`` are, so that no later PR can
move the basis of a utilization. They count what the mathematics needs,
whatever implements it.

Per trained token: every matmul parameter a token really touches three
times over (forward and the two backward products) at 2 FLOPs a
multiply-add -- latent attention's five projections in every block, the
dense SwiGLU, the shared experts, the routers, the prediction module's
``W_eh``, the head over the rows held TWICE (the main loss and the
module's both go through it), and the HELD experts at the rows the step
counted for them, the stack's and the module's (an absent expert's rows
cost this chip nothing) -- plus latent attention's two causal
sequence-length matmuls a block (at the q/k head size and at the value
head size), the module's block among them. Not counted: the embedding
lookups, norms, rotations, activations, and anything rematerialisation
runs twice.
"""


def n_expert_layers(cfg):
    """Expert layers of the stack (the module's block is one more)."""
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def n_expert_blocks(cfg):
    """Blocks with an expert FFN: the stack's and the module's."""
    return n_expert_layers(cfg) + cfg["num_nextn_predict_layers"]


def n_blocks(cfg):
    """Blocks with a latent attention layer: the stack's and the
    module's."""
    return cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]


def mla_params(cfg):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, rq, dv = cfg["kv_lora_rank"], cfg["q_lora_rank"], cfg["v_head_dim"]
    return (
        d * rq + rq * h * (nope + rope) + d * (rank + rope)
        + rank * h * (nope + dv) + h * dv * d
    )


def expert_params(cfg):
    """One routed expert (the shared expert is this times
    ``n_shared_experts``)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def fixed_matmul_params(cfg):
    """Matmul parameters every token touches, each counted as often as a
    token goes through it: all but the routed experts, the head twice."""
    d = cfg["hidden_size"]
    return (
        (1 + cfg["num_nextn_predict_layers"]) * d * cfg["vocab_rows_held"]
        + n_blocks(cfg) * mla_params(cfg)
        + cfg["first_k_dense_replace"] * 3 * d * cfg["intermediate_size"]
        + n_expert_blocks(cfg) * (
            d * cfg["published"]["n_routed_experts"]
            + cfg["n_shared_experts"] * expert_params(cfg)
        )
        + cfg["num_nextn_predict_layers"] * 2 * d * d
    )


def total_params(cfg):
    """Every parameter held here (embedding, head, norms, held experts)."""
    d = cfg["hidden_size"]
    mtp = cfg["num_nextn_predict_layers"]
    head_passes = (1 + mtp) * d * cfg["vocab_rows_held"]
    norms = n_blocks(cfg) * (
        2 * d + cfg["q_lora_rank"] + cfg["kv_lora_rank"]
    ) + d + mtp * 3 * d
    return (
        fixed_matmul_params(cfg) - head_passes
        + 2 * d * cfg["vocab_rows_held"]           # embedding and head
        + n_expert_blocks(cfg) * cfg["n_routed_experts"] * expert_params(cfg)
        + norms
    )


def mla_attention_flops_per_token(cfg, seq_len, passes=3):
    """One latent-attention block's QK^T (at nope + rope) and PV (at the
    value head size) per token, halved by the causal mask."""
    dqk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    per_pass = 2 * seq_len * cfg["num_attention_heads"] * (
        dqk + cfg["v_head_dim"]
    ) / 2
    return passes * per_pass


def train_flops_per_token(cfg, seq_len, expert_rows_per_token):
    """``expert_rows_per_token``: (token, k) pairs the held experts
    computed in a step, summed over the stack's expert layers AND the
    module's block (``moe_rows_held + mtp_moe_rows_held``), over the
    step's tokens."""
    return (
        6.0 * fixed_matmul_params(cfg)
        + 6.0 * expert_rows_per_token * expert_params(cfg)
        + n_blocks(cfg) * mla_attention_flops_per_token(cfg, seq_len)
    )


def mla_flash_step(cfg, batch, seq_len, itemsize=2):
    """FLOPs and HBM bytes of one training step's latent-attention flash
    kernels (every block, the module's too), as
    ``flops_kimi_linear.mla_flash_step`` counts them: forward S (dqk)
    and PV (dv); dq S, dP (dv), dQ (dqk); dk/dv S, dV (dv), dP (dv), dK
    (dqk); each a causal half of b h s^2."""
    h = cfg["num_attention_heads"]
    dqk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    half = 2.0 * batch * h * seq_len * seq_len / 2
    rows = batch * seq_len * h * itemsize
    return {
        "flops": n_blocks(cfg) * half * (5 * dqk + 4 * dv),
        # fwd q, k, v, o; dq q, k, v, o, dO, dq; dk/dv q, o, dO, k, v, dk, dv
        "bytes": n_blocks(cfg) * rows * (8 * dqk + 9 * dv),
    }


def expert_gmm_step(cfg, rows_held, itemsize=2):
    """FLOPs and HBM bytes of one training step's grouped expert matmuls
    at the rows the step counted (``rows_held``, summed over the stack's
    expert layers and the module's block): gate + up and down, forward
    and both backward products; the held experts' weights are read twice
    and their gradients written once a block, the rows' activations move
    once a product."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = n_expert_blocks(cfg) * cfg["n_routed_experts"] * (
        expert_params(cfg)
    )
    return {
        "flops": 6.0 * rows_held * expert_params(cfg),
        "bytes": 3 * itemsize * (weights + rows_held * (2 * d + 3 * f)),
    }
