"""Operations and bytes of ``olmo-hybrid-7b``'s two serving programs, from
the published keys and the steps' own counts. Kept with the benchmark so
that no later PR can move the basis of a roofline share. Each counts only
what the mathematics needs, whatever implements it (a state is charged
one read and one write of its ``heads x dk x dv x 4`` bytes, not the
lanes the device pads it to; K and V are charged the model's 30 heads,
not the 32 a pool row holds):

- a delta layer's decode step reads and writes each decoding slot's
  state once: ``state_slots x 2 x heads x dk x dv x 4 B`` a layer;
- a delta layer's chunk does the chunk algebra by ``flops_kimi_linear``'s
  convention for the same solve (per head and sub-chunk of C = 64 rows:
  ``A`` and ``P`` C^2 dk each, the unit triangular solve 2 C^3 / 3, ``T
  K`` C^2 dk, ``T V`` and ``P U`` C^2 dv each, three products with the
  dk x dv state 2 C dk dv each) over the sub-chunks that hold one of its
  ``n_valid`` rows, and reads and writes one state;
- a full layer's decode step reads the K and V rows of the active slots
  once (``kv_rows`` of the step span: the rows every decoding slot
  holds).
"""

from benchmark import reference_olmo_hybrid as reference
from benchmark.flops_kimi_linear import KDA_CHUNK


def _counts(cfg):
    sh = reference.shape_of(cfg)
    n_d = sum(1 for t in sh["types"] if t == reference.DELTA)
    return sh, n_d, len(sh["types"]) - n_d


def parameter_count(cfg):
    """Parameters of the configuration as cut, from the published keys
    (what ``models/delta_lm.py``'s tree must hold)."""
    return reference.count_params(reference.shape_of(cfg))


def state_bytes_per_slot(cfg, itemsize=2):
    """The delta layers' state of one sequence (one snapshot): the
    float32 matrix state and the convolutions' taps, as the mathematics
    counts them."""
    sh, n_d, _ = _counts(cfg)
    width = sh["l_heads"] * (2 * sh["dk"] + sh["dv"])
    return n_d * (
        sh["l_heads"] * sh["dk"] * sh["dv"] * 4
        + (sh["taps"] - 1) * width * itemsize
    )


def cache_bytes_per_token(cfg, itemsize=2):
    """K and V of the full layers, every head its own."""
    sh, _, n_f = _counts(cfg)
    return n_f * 2 * sh["heads"] * sh["head_dim"] * itemsize


def delta_state_step(cfg, state_slots):
    """The decode step: each slot's state read AND written once a delta
    layer; a decay, two matrix-vector products and a rank-1 update a
    head."""
    sh, n_d, _ = _counts(cfg)
    per = sh["l_heads"] * sh["dk"] * sh["dv"]
    return {
        "flops": 2.0 * n_d * state_slots * 3 * per,
        "bytes": float(n_d * state_slots * 2 * per * 4),
    }


def delta_chunk(cfg, n_valid):
    """A prefill chunk's delta layers over ``n_valid`` rows."""
    sh, n_d, _ = _counts(cfg)
    h, dk, dv, c = sh["l_heads"], sh["dk"], sh["dv"], KDA_CHUNK
    subs = -(-n_valid // c)
    per = (
        2 * c * c * dk + 2.0 * c ** 3 / 3 + c * c * dk + 2 * c * c * dv
        + 3 * 2 * c * dk * dv
    )
    return {
        "flops": 2.0 * n_d * h * subs * per,
        "bytes": float(n_d * 2 * h * dk * dv * 4),
    }


def full_attention_step(cfg, kv_rows, itemsize=2):
    """The decode step's attention: ``kv_rows`` rows (all decoding slots
    together) of K and V read once a full layer; a row meets one query a
    head."""
    sh, _, n_f = _counts(cfg)
    row = sh["heads"] * sh["head_dim"]
    return {
        "flops": 2.0 * n_f * kv_rows * 2 * row,
        "bytes": float(n_f * kv_rows * 2 * row * itemsize),
    }
