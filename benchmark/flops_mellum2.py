"""Operations and bytes of ``mellum2-12b-a2.5b``'s two serving programs,
from the published keys and the steps' own counts. Kept with the
benchmark so that no later PR can move the basis of a roofline share.
Each counts what the mathematics needs, whatever implements it (a
gathered view and a pool kernel are charged the same rows): only the
rows a query can SEE, only the experts a step's tokens hit.

- a decode step's attention reads a visible row's K and V
  (``num_key_value_heads * head_dim`` numbers each) once a layer of its
  type: ``kv_rows`` (the decoding slots' fills) in the FULL layers,
  ``window_rows`` (``min(fill, sliding_window - 1)`` a slot) in the
  SLIDING layers: memory-bound;
- a prefill chunk's attention scores, for token ``t`` of ``n_valid`` at
  position ``start + t``: ``start + t + 1`` keys in a full layer,
  ``min(start + t, sliding_window - 1) + 1`` in a sliding one; it reads
  the pool rows below ``start`` that some token sees once a layer; the
  larger of the two times counts (the chunk is compute-bound past a few
  hundred rows);
- the experts read the gate, up and down weights of every expert a
  step's tokens hit (``experts_hit``, the mean over the layers), once a
  layer.
"""

SLIDING, FULL = "sliding_attention", "full_attention"


def layers_of(cfg, kind):
    return sum(1 for t in cfg["layer_types"] if t == kind)


def parameter_count(cfg):
    """Parameters of the configuration as cut, from the published keys
    (what ``models/window_lm.py``'s tree must hold; the head is untied)."""
    d, h, kh, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    layer = 2 * d * h * hd + 2 * d * kh * hd + d * e + e * 3 * d * f + 2 * d
    return cfg["num_hidden_layers"] * layer + 2 * cfg["vocab_size"] * d + d


def row_bytes(cfg, itemsize=2):
    """K and V of one token in one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def cache_bytes_per_token(cfg, kind, itemsize=2):
    """K and V of one token over the layers of type ``kind``."""
    return layers_of(cfg, kind) * row_bytes(cfg, itemsize)


def attention_step(cfg, kind, rows, itemsize=2):
    """The decode step, the layers of type ``kind``: every visible row's
    K and V read once a layer; a row meets every query head
    (``head_dim`` multiply-adds for the score, ``head_dim`` for its
    value). ``rows``: ``kv_rows`` for the full layers, ``window_rows``
    for the sliding ones."""
    n = layers_of(cfg, kind)
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    return {
        "flops": 2.0 * n * rows * h * 2 * hd,
        "bytes": float(n * rows * row_bytes(cfg, itemsize)),
    }


def chunk_pairs(cfg, kind, start, n_valid):
    """Visible (query, key) pairs of a chunk's ``n_valid`` tokens at
    positions ``start ...`` in ONE layer of type ``kind``, and the pool
    rows below ``start`` that some token of it sees."""
    if kind == FULL:
        return n_valid * start + n_valid * (n_valid + 1) // 2, start
    reach = cfg["sliding_window"] - 1
    pairs = sum(min(start + t, reach) + 1 for t in range(n_valid))
    return pairs, min(start, reach)


def attention_chunk(cfg, kind, chunks, itemsize=2):
    """A mean prefill chunk over ``chunks`` (``(start, n_valid)`` each),
    the layers of type ``kind``: 4 x head_dim FLOP a (pair, query head),
    and the K and V of the pool rows its band needs."""
    n = layers_of(cfg, kind)
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    flops = rows = 0.0
    for start, n_valid in chunks:
        pairs, seen = chunk_pairs(cfg, kind, start, n_valid)
        flops += pairs * 4.0 * hd * h
        rows += seen
    count = max(len(chunks), 1)
    return {
        "flops": n * flops / count,
        "bytes": n * rows / count * row_bytes(cfg, itemsize),
    }


def expert_step(cfg, experts_hit, n_tokens, itemsize=2):
    """The grouped matmuls: the three projections of every expert hit (a
    layer's mean), read once a layer; ``n_tokens x top_k`` rows of
    FLOPs."""
    layers = cfg["num_hidden_layers"]
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = n_tokens * cfg["num_experts_per_tok"]
    return {
        "flops": 2.0 * layers * rows * 3 * d * f,
        "bytes": float(layers * experts_hit * 3 * d * f * itemsize),
    }
