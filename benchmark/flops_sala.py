"""Operations and bytes of ``minicpm-sala-9b``'s two serving programs, from
the published keys, the family's sparse sizes and the steps' own counts.
Kept with the benchmark so that no later PR can move the basis of a
roofline share. Each counts only what the mathematics needs, whatever
implements it (a gathered list and a pool kernel are charged the same
rows; a state is charged one read and one write):

- a lightning layer's decode step reads and writes each decoding slot's
  state once: ``state_slots x 2 x heads x d x d x 4 B`` a layer;
- a lightning layer's chunk does the intra-chunk products over its
  ``n_valid`` rows (``Q K^T`` and ``(.) V`` under the causal half), the
  state's term in and the state's update out, and reads and writes one
  state;
- block selection reads the visible compressed keys once a sparse layer
  (``ckey_rows`` of the step span: (sparse layer, place) pairs, every KV
  head's key of that place = ``kv_heads x head_dim x 2 B``);
- block attention reads the K and V rows of each list once
  (``selected_rows`` of the step span: the rows ONE list a slot holds,
  times the sparse layers and the KV heads' lists);
- the dense FFN's decode step reads the gate, up and down weights of
  every layer once.
"""

from benchmark import reference_sala


def _counts(cfg):
    sh = reference_sala.shape_of(cfg)
    n_l = sum(1 for t in sh["types"] if t == reference_sala.LIGHTNING)
    return sh, n_l, len(sh["types"]) - n_l


def parameter_count(cfg):
    """Parameters of the configuration as cut, from the published keys
    (what ``models/linear_sparse_lm.py``'s tree must hold)."""
    sh, n_l, n_s = _counts(cfg)
    d, f = sh["hidden"], sh["mlp"]
    lw = sh["l_heads"] * sh["l_dim"]
    qw, kw = sh["heads"] * sh["head_dim"], sh["kv_heads"] * sh["head_dim"]
    lightning = 5 * d * lw + 2 * sh["l_dim"] + lw
    sparse = 3 * d * qw + 2 * d * kw
    return (
        n_l * lightning + n_s * sparse + (n_l + n_s) * (3 * d * f + 2 * d)
        + 2 * sh["vocab"] * d + d
    )


def state_bytes_per_slot(cfg):
    """The lightning layers' float32 state of one sequence (one
    snapshot)."""
    sh, n_l, _ = _counts(cfg)
    return n_l * sh["l_heads"] * sh["l_dim"] * sh["l_dim"] * 4


def cache_bytes_per_token(cfg, itemsize=2):
    """K and V of the sparse layers, and the compressed keys' share."""
    sh, _, n_s = _counts(cfg)
    row = sh["kv_heads"] * sh["head_dim"] * itemsize
    return n_s * (2 * row + row / sh["kernel_stride"])


def lightning_state_step(cfg, state_slots):
    """The decode step: each slot's state read AND written once a
    lightning layer; a rank-1 update and a matrix-vector product a
    head."""
    sh, n_l, _ = _counts(cfg)
    per = sh["l_heads"] * sh["l_dim"] * sh["l_dim"]
    return {
        "flops": 2.0 * n_l * state_slots * 2 * per,
        "bytes": float(n_l * state_slots * 2 * per * 4),
    }


def lightning_chunk(cfg, n_valid):
    """A prefill chunk's lightning layers over ``n_valid`` rows: the
    intra-chunk products under the causal half (``n (n + 1) / 2`` pairs,
    ``2 d`` multiply-adds each for the score and its value), the state's
    term in and the update out (``2 n d d`` multiply-adds a head), one
    state read and one written."""
    sh, n_l, _ = _counts(cfg)
    h, d = sh["l_heads"], sh["l_dim"]
    pairs = n_valid * (n_valid + 1) / 2.0
    return {
        "flops": 2.0 * n_l * h * (pairs * 2 * d + 2 * n_valid * d * d),
        "bytes": float(n_l * 2 * h * d * d * 4),
    }


def block_select_step(cfg, ckey_rows, itemsize=2):
    """The decode step's scoring: every visible compressed key read once
    (``ckey_rows`` pairs of (sparse layer, place)); each meets every
    query head of its group."""
    sh, _, _ = _counts(cfg)
    row = sh["kv_heads"] * sh["head_dim"]
    return {
        "flops": 2.0 * ckey_rows * sh["heads"] * sh["head_dim"],
        "bytes": float(ckey_rows * row * itemsize),
    }


def block_attention_step(cfg, selected_rows, itemsize=2):
    """The decode step's attention over the lists: ``selected_rows`` rows
    a list, a list a KV head, K and V of ONE head a row, in every sparse
    layer; a row meets its group's query heads."""
    sh, _, n_s = _counts(cfg)
    lists = n_s * sh["kv_heads"]
    group = sh["heads"] // sh["kv_heads"]
    return {
        "flops": 2.0 * lists * selected_rows * group * 2 * sh["head_dim"],
        "bytes": float(lists * selected_rows * 2 * sh["head_dim"] * itemsize),
    }


def rows_listed(cfg, position):
    """Rows one list of a query at ``position`` holds that it sees."""
    sh = reference_sala.shape_of(cfg)
    if position + 1 <= sh["dense_len"]:
        return position + 1
    bs = sh["block_size"]
    blocks = min(sh["topk"], position // bs + 1)
    return (blocks - 1) * bs + position % bs + 1


def block_chunk(cfg, chunks, itemsize=2):
    """The sparse layers of the mean traced chunk (``chunks``: ``(start,
    n_valid)`` each): the selected (query, key) pairs, ``4 x head_dim``
    FLOP a query head each, plus the scoring of the visible compressed
    keys; against the K and V bytes of the pages some query selected
    (at most every page below the chunk's end) and the compressed keys."""
    sh, _, n_s = _counts(cfg)
    hd, heads = sh["head_dim"], sh["heads"]
    row = sh["kv_heads"] * hd * itemsize
    flops = bytes_ = 0.0
    for start, n_valid in chunks:
        positions = range(start, start + n_valid)
        pairs = sum(rows_listed(cfg, t) for t in positions)
        places = sum(
            max((t + 1) // sh["kernel_stride"] - 1, 0) for t in positions
        )
        flops += n_s * (pairs * 4 * hd * heads + places * 2 * hd * heads)
        # every query's list is its own: together they may touch every
        # row below the chunk's end, never more
        rows = min(start + n_valid, sum(
            rows_listed(cfg, t) for t in positions
        ))
        bytes_ += n_s * (
            rows * 2 * row + (start + n_valid) // sh["kernel_stride"] * row
        )
    n = max(len(chunks), 1)
    return {"flops": flops / n, "bytes": bytes_ / n}


def dense_mlp_step(cfg, n_tokens, itemsize=2):
    """The decode step's FFNs: gate, up and down of every layer read
    once."""
    sh, n_l, n_s = _counts(cfg)
    weights = 3 * sh["hidden"] * sh["mlp"]
    return {
        "flops": 2.0 * (n_l + n_s) * n_tokens * weights,
        "bytes": float((n_l + n_s) * weights * itemsize),
    }
