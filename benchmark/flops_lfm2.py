"""Operations and bytes of ``lfm2-24b-a2b``'s two serving programs, from
the published keys and the steps' own counts. Kept with the benchmark so
that no later PR can move the basis of a roofline share. Each counts what
the mathematics needs, whatever implements it (a gathered view and a pool
kernel are charged the same rows).

- the decode step's grouped-query attention reads a visible row's K and
  V (``num_key_value_heads * head_dim`` numbers each) once an ATTENTION
  layer (``kv_rows`` of the step span: the sum of the decoding slots'
  fills): memory-bound;
- the experts read the gate, up and down weights of every expert a
  step's tokens hit (``experts_hit``, the mean over the expert layers),
  once, in every EXPERT layer (``num_hidden_layers - num_dense_layers``);
- the convolution mixer reads its two projections (``W_in`` ``[C, 3 C]``
  and ``W_out`` ``[C, C]``), its filter and the decoding slots' state
  (read and written: ``2 x (taps - 1) x C`` numbers a slot) once a
  CONVOLUTION layer: memory-bound on the weights.
"""


def _layers(cfg):
    types = cfg["layer_types"]
    conv = sum(1 for t in types if t == "conv")
    return conv, len(types) - conv


def head_dim(cfg):
    return cfg.get("head_dim") or (
        cfg["hidden_size"] // cfg["num_attention_heads"]
    )


def parameter_count(cfg):
    """Parameters of the configuration as cut, from the published keys
    (what ``models/conv_lm.py``'s tree must hold; the head is tied)."""
    d, h, kh, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], head_dim(cfg))
    n_conv, n_attn = _layers(cfg)
    conv = 3 * d * d + d * d + cfg["conv_L_cache"] * d
    attn = 2 * d * h * hd + 2 * d * kh * hd + 2 * hd
    layers, dense = cfg["num_hidden_layers"], cfg["num_dense_layers"]
    e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    expert_layer = d * e + e + e * 3 * d * f
    return (
        n_conv * conv + n_attn * attn + layers * 2 * d
        + dense * 3 * d * cfg["intermediate_size"]
        + (layers - dense) * expert_layer
        + cfg["vocab_size"] * d + d
    )


def cache_bytes_per_token(cfg, itemsize=2):
    """K and V of the attention layers alone."""
    _, n_attn = _layers(cfg)
    return 2 * n_attn * cfg["num_key_value_heads"] * head_dim(cfg) * itemsize


def state_bytes_per_slot(cfg, itemsize=2):
    """The convolution layers' state of one sequence (one snapshot)."""
    n_conv, _ = _layers(cfg)
    return n_conv * (cfg["conv_L_cache"] - 1) * cfg["hidden_size"] * itemsize


def gqa_attention_step(cfg, kv_rows, itemsize=2):
    """The decode step: every visible row's K and V read once an
    attention layer; a row meets every query head (``head_dim``
    multiply-adds for the score, ``head_dim`` for its value)."""
    _, n_attn = _layers(cfg)
    h, kh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 head_dim(cfg))
    return {
        "flops": 2.0 * n_attn * kv_rows * h * 2 * hd,
        "bytes": float(n_attn * kv_rows * 2 * kh * hd * itemsize),
    }


def expert_step(cfg, experts_hit, n_tokens, itemsize=2):
    """The grouped matmuls: the three projections of every expert hit
    (an expert layer's mean), read once an EXPERT layer; ``n_tokens x
    top_k`` rows of FLOPs."""
    layers = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = n_tokens * cfg["num_experts_per_tok"]
    return {
        "flops": 2.0 * layers * rows * 3 * d * f,
        "bytes": float(layers * experts_hit * 3 * d * f * itemsize),
    }


def conv_mix_step(cfg, n_tokens, itemsize=2):
    """The decode step's convolution mixers: both projections' weights
    and the filter read once a convolution layer, the decoding slots'
    state read and written."""
    n_conv, _ = _layers(cfg)
    d, taps = cfg["hidden_size"], cfg["conv_L_cache"]
    weights = (4 * d * d) * itemsize + taps * d * 4
    state = 2 * n_tokens * (taps - 1) * d * itemsize
    return {
        "flops": 2.0 * n_conv * n_tokens * (4 * d * d + (taps + 2) * d),
        "bytes": float(n_conv * (weights + state)),
    }
