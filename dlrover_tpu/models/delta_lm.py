"""A decoder whose layers follow a LIST of two mixers: the gated delta rule
(Gated DeltaNet: a linear-attention layer whose whole memory of a
sequence is one float32 ``dk x dv`` matrix a head and the last few inputs
of three short convolutions) and un-grouped full attention without
rotation, each over a dense SwiGLU, with the norm AFTER each sublayer
(Olmo 2/3's reordered norm) and an untied head (Olmo-Hybrid).

Every layer: ``h = x + norm(Mixer(x))``, then ``y = h + norm(W_down(silu(
W_gate h) * W_up h))``; ``x_0 = embed(token)``; logits ``= head(norm(
x_L))``. The residual is held in float32, matmul operands in the compute
dtype.

- **Gated delta rule** (``layer_types[l] == "linear_attention"``). ``W_q
  x, W_k x`` (``linear_heads`` heads of ``linear_key_dim``) and ``W_v x``
  (heads of ``linear_value_dim``) go, side by side, through a causal
  depthwise convolution of ``conv_kernel`` taps (no bias; rows before the
  sequence's start are zeros), then SiLU. Per head ``q^ = q / ||q|| /
  sqrt(dk)``, ``k^ = k / ||k||``; ``beta_t = 2 sigmoid(w_b x_t)`` (the 2:
  ``allow_neg_eigval``); ``g_t = -exp(A_log) softplus(w_a x_t + dt_bias)
  <= 0``, one number a head;

      S_t = e^{g_t} S_{t-1} + beta_t k^_t (v_t - e^{g_t} S_{t-1}^T k^_t)^T
      o_t = S_t^T q^_t                        S in R^{dk x dv}, float32

  then ``o`` RMS-normed a head (one learned gain of ``dv``), gated by
  ``silu(W_g x)``, and ``W_o``. What a sequence leaves behind in such a
  layer is TWO things whatever its length: ``S`` (``[heads, dk, dv]``
  float32) and the convolutions' last ``conv_kernel - 1`` inputs
  (``[conv_kernel - 1, conv_width]``, compute dtype):
  :attr:`DeltaLMConfig.state_rows` states both, the per-SLOT arrays of
  ``serving/kvpool/layout.py``, each with its own dtype. The recurrence's
  chunk form and its one-token update are ``ops/gated_delta.py``.
- **Full attention** (``"full_attention"``). ``q = norm_q(W_q x)``, ``k =
  norm_k(W_k x)`` over ALL ``n_heads * head_dim`` channels at once (Olmo
  2/3's QK-norm), ``v = W_v x``; ``n_heads`` heads over as many KV heads
  (no grouping), no rotation, causal ``softmax(q k^T / sqrt(head_dim))
  v``, ``W_o``. Only these layers keep per-token rows
  (:attr:`DeltaLMConfig.cache_rows`): ``k`` and ``v`` of ``[kv_heads_held,
  head_dim]``, the KV heads padded with zero heads to whole sublane tiles
  of 8 (30 -> 32: a ``[30, 128]`` bfloat16 row pads to 32 on the device
  whatever is declared, and declared so the engine's dense paged kernels,
  which collapse ``[block, heads]`` into rows, apply as they are).

The layers are walked in Python (the list is static). The model is
SERVED: ``PagedServingEngine`` takes this config and builds its programs
from the functions here (``serving/kvpool/delta.py``). :func:`forward` is
the same layers over whole sequences from a zero state, with no cache:
the definition the engine's logits are held to. Nothing here trains it.
"""

import dataclasses
import math
from typing import ClassVar, Tuple

import jax
import jax.numpy as jnp

from dlrover_tpu.ops import gated_delta
from dlrover_tpu.ops.norms import rms_norm

DELTA, FULL = "linear_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class DeltaLMConfig:
    kind: ClassVar[str] = "delta_lm"           # models.model_for
    vocab_size: int = 100352
    embed_dim: int = 3840
    layer_types: Tuple[str, ...] = (DELTA, DELTA, DELTA, FULL)
    n_heads: int = 30                # the full mixer's query AND KV heads
    n_kv_heads: int = 30
    head_dim: int = 128
    linear_heads: int = 30
    linear_key_dim: int = 96
    linear_value_dim: int = 192
    conv_kernel: int = 4
    allow_neg_eigval: bool = True
    mlp_dim: int = 11008
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    pp_stages: int = 1               # the engines ask; never staged

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        bad = set(self.layer_types) - {DELTA, FULL}
        if bad or not self.layer_types:
            raise ValueError(
                f"layer_types {self.layer_types}: each is {DELTA!r} or "
                f"{FULL!r}"
            )
        if self.n_heads != self.n_kv_heads:
            raise ValueError(
                f"{self.n_heads} query heads over {self.n_kv_heads} KV "
                "heads: this model's full attention is un-grouped"
            )
        if self.conv_kernel < 2:
            raise ValueError("conv_kernel must be 2 or more")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def delta_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types) if t == DELTA)

    @property
    def full_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types) if t == FULL)

    def index_in_kind(self, layer: int) -> int:
        """Layer ``layer``'s index among the layers of its own kind."""
        kinds = self.layer_types
        return sum(1 for t in kinds[:layer] if t == kinds[layer])

    @property
    def key_width(self) -> int:
        return self.linear_heads * self.linear_key_dim

    @property
    def value_width(self) -> int:
        return self.linear_heads * self.linear_value_dim

    @property
    def conv_width(self) -> int:
        """Channels the three convolutions run over, q | k | v."""
        return 2 * self.key_width + self.value_width

    @property
    def beta_scale(self) -> float:
        return 2.0 if self.allow_neg_eigval else 1.0

    @property
    def kv_heads_held(self) -> int:
        """KV heads a pool row holds: the model's, padded with zero heads
        to whole sublane tiles of 8."""
        return -(-self.n_kv_heads // 8) * 8

    # The pool's statement (``serving/kvpool/layout.py``): K and V rows
    # over the full layers alone, and two per-SLOT arrays of two dtypes
    # over the delta layers.
    @property
    def cache_layers(self) -> int:
        return len(self.full_layers)

    @property
    def cache_rows(self):
        row = (self.kv_heads_held, self.head_dim)
        return (("k", row), ("v", row))

    @property
    def state_rows(self):
        """name -> (layers, a slot's shape[, dtype])."""
        n = len(self.delta_layers)
        return (
            ("delta", (n, (self.linear_heads, self.linear_key_dim,
                           self.linear_value_dim), "float32")),
            ("taps", (n, (self.conv_kernel - 1, self.conv_width))),
        )

    def count_params(self) -> int:
        d, f = self.embed_dim, self.mlp_dim
        h = self.linear_heads
        qw = self.n_heads * self.head_dim
        delta = (
            d * self.conv_width + 2 * d * self.value_width      # qkv, g, o
            + 2 * d * h + 2 * h                     # w_a, w_b, A_log, dt_bias
            + self.conv_kernel * self.conv_width + self.linear_value_dim
        )
        full = 4 * d * qw + 2 * qw
        return (
            len(self.delta_layers) * delta + len(self.full_layers) * full
            + self.n_layers * (3 * d * f + 2 * d)
            + 2 * self.vocab_size * d + d
        )


def tiny_config(**overrides) -> DeltaLMConfig:
    """Small enough for a CPU test: two periods of 2 delta + 1 full, 3
    un-grouped heads (held as 8), value heads wider than key heads."""
    kw = dict(
        vocab_size=96, embed_dim=32,
        layer_types=(DELTA, DELTA, FULL, DELTA, DELTA, FULL),
        n_heads=3, n_kv_heads=3, head_dim=8, linear_heads=3,
        linear_key_dim=8, linear_value_dim=12, conv_kernel=4, mlp_dim=48,
        dtype="float32",
    )
    kw.update(overrides)
    return DeltaLMConfig(**kw)


# Leaves a server keeps in float32 whatever its compute dtype: the norms'
# gains, the convolutions' taps, and what makes the gate and beta (one
# number a head: their rounding would move every channel of a head).
FLOAT32_LEAVES = frozenset({
    "mix_norm", "ffn_norm", "q_norm", "k_norm", "o_norm", "final_norm",
    "conv", "a_log", "dt_bias", "w_ab",
})


def init_params(config: DeltaLMConfig, rng: jax.Array, dtype=None):
    """Seeded weights, normal(0, 1 / sqrt(fan_in)); norm gains zero (the
    ``1 + scale`` form); the convolutions' taps normal(0, 1 / sqrt(taps));
    ``A_log`` and ``dt_bias`` as the public implementation draws them (``A
    ~ U(0, 16)``, ``dt`` log-uniform in [0.001, 0.1], ``dt_bias = dt +
    log(-expm1(-dt))``), so that the state forgets at a trained model's
    rates. ``dtype``: what the matmul leaves are made in (float32 when
    None; a server passes its compute dtype, so that the float32 tree
    never exists)."""
    c = config
    d, f, L = c.embed_dim, c.mlp_dim, c.n_layers
    Ld, Lf = len(c.delta_layers), len(c.full_layers)
    h, qw = c.linear_heads, c.n_heads * c.head_dim
    dtype = jnp.dtype(dtype or jnp.float32)
    keys = iter(jax.random.split(rng, 16))

    def dense(shape, fan_in, to=dtype):
        w = jax.random.normal(next(keys), shape, jnp.float32)
        return (w / math.sqrt(fan_in)).astype(to)

    a = jax.random.uniform(next(keys), (Ld, h), jnp.float32, 1e-4, 16.0)
    dt = jnp.exp(jax.random.uniform(
        next(keys), (Ld, h), jnp.float32, math.log(1e-3), math.log(1e-1)
    ))
    return {
        "embed": dense((c.vocab_size, d), 1.0),
        "head": dense((d, c.vocab_size), d),
        "layers": {
            "mix_norm": jnp.zeros((L, d), jnp.float32),
            "ffn_norm": jnp.zeros((L, d), jnp.float32),
            "w_gu": dense((L, d, 2 * f), d),
            "w_down": dense((L, f, d), f),
        },
        "delta": {
            "wqkv": dense((Ld, d, c.conv_width), d),
            "wg": dense((Ld, d, c.value_width), d),
            "wo": dense((Ld, c.value_width, d), c.value_width),
            "w_ab": dense((Ld, d, 2 * h), d, jnp.float32),
            "a_log": jnp.log(a),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "conv": dense((Ld, c.conv_kernel, c.conv_width), c.conv_kernel,
                          jnp.float32),
            "o_norm": jnp.zeros((Ld, c.linear_value_dim), jnp.float32),
        },
        "full": {
            "wq": dense((Lf, d, qw), d),
            "wk": dense((Lf, d, qw), d),
            "wv": dense((Lf, d, qw), d),
            "wo": dense((Lf, qw, d), qw),
            "q_norm": jnp.zeros((Lf, qw), jnp.float32),
            "k_norm": jnp.zeros((Lf, qw), jnp.float32),
        },
        "final_norm": jnp.zeros((d,), jnp.float32),
    }


def prepare_decode_params(config: DeltaLMConfig, params):
    """The tree as a server reads it: matmul leaves in the compute dtype,
    :data:`FLOAT32_LEAVES` as they are."""
    cdt = config.compute_dtype

    def cast(path, leaf):
        name = getattr(path[-1], "key", None)
        return leaf if name in FLOAT32_LEAVES else leaf.astype(cdt)

    return jax.tree_util.tree_map_with_path(cast, params)


def _norm(config, x, scale):
    return rms_norm(x, scale, eps=config.norm_eps)


# -- the delta mixer ----------------------------------------------------------


def zero_taps(config: DeltaLMConfig, batch: int):
    """A sequence's convolution inputs before its first token."""
    return jnp.zeros(
        (batch, config.conv_kernel - 1, config.conv_width),
        config.compute_dtype,
    )


def conv_inputs(config: DeltaLMConfig, pd, z, taps):
    """The three short convolutions over the projections ``z [b, s,
    conv_width]`` (``W_q x | W_k x | W_v x``) carried on from ``taps [b,
    K - 1, conv_width]`` (the projections of the rows before the call's
    first): (``q``, ``k [b, s, heads, dk]``, ``v [b, s, heads, dv]``
    float32 after SiLU, ``zz [b, K - 1 + s, conv_width]``). The taps
    after the call's first ``n`` rows are ``zz[:, n:n + K - 1]`` (``n =
    0``: what it came from)."""
    c, f32 = config, jnp.float32
    s, taps_n = z.shape[1], c.conv_kernel
    zz = jnp.concatenate([taps.astype(z.dtype), z], axis=1)
    y = sum(
        pd["conv"][j].astype(f32) * zz[:, j:j + s].astype(f32)
        for j in range(taps_n)
    )
    y = jax.nn.silu(y)
    kw = c.key_width
    heads = lambda a, dim: a.reshape(  # noqa: E731
        a.shape[:2] + (c.linear_heads, dim)
    )
    return (heads(y[..., :kw], c.linear_key_dim),
            heads(y[..., kw:2 * kw], c.linear_key_dim),
            heads(y[..., 2 * kw:], c.linear_value_dim), zz)


def unit_rows(x, eps: float = 1e-6):
    """``x / ||x||`` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def delta_inputs(config: DeltaLMConfig, pd, u, taps):
    """What the recurrence takes of ``u [b, s, d]``: ``q^``, ``k^ [b, s,
    heads, dk]``, ``v [b, s, heads, dv]``, ``g``, ``beta [b, s, heads]``
    (all float32), the output gate before its SiLU ``[b, s, heads * dv]``
    and ``zz`` (:func:`conv_inputs`)."""
    c, f32 = config, jnp.float32
    z = jnp.einsum("bsd,dw->bsw", u, pd["wqkv"].astype(u.dtype))
    with jax.named_scope("conv"):
        q, k, v, zz = conv_inputs(c, pd, z, taps)
    q = unit_rows(q) * c.linear_key_dim ** -0.5
    k = unit_rows(k)
    ab = jnp.einsum("bsd,dh->bsh", u.astype(f32), pd["w_ab"].astype(f32),
                    precision=jax.lax.Precision.HIGHEST)
    h = c.linear_heads
    g = -jnp.exp(pd["a_log"].astype(f32)) * jax.nn.softplus(
        ab[..., :h] + pd["dt_bias"].astype(f32)
    )
    beta = c.beta_scale * jax.nn.sigmoid(ab[..., h:])
    gate = jnp.einsum("bsd,de->bse", u, pd["wg"].astype(u.dtype))
    return q, k, v, g, beta, gate, zz


def delta_out(config: DeltaLMConfig, pd, o, gate):
    """``o [b, s, heads, dv]`` float32 -> (the gated mix ``[b, s, heads *
    dv]``, the mixer's output ``[b, s, embed_dim]``): the norm a head, the
    gate, ``W_o``."""
    cdt = config.compute_dtype
    b, s = o.shape[:2]
    normed = _norm(config, o, pd["o_norm"]).reshape(b, s, -1)
    gated = (
        normed.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
    ).astype(cdt)
    return gated, jnp.einsum("bse,ed->bsd", gated, pd["wo"].astype(cdt))


# -- the full mixer -----------------------------------------------------------


def full_inputs(config: DeltaLMConfig, pf, u):
    """``q``, ``k``, ``v [b, s, heads, head_dim]``: ``q`` and ``k``
    RMS-normed over all heads' channels at once, no rotation."""
    c, cdt = config, config.compute_dtype
    proj = lambda w: jnp.einsum(  # noqa: E731
        "bsd,de->bse", u, w.astype(cdt)
    )
    heads = lambda a: a.astype(cdt).reshape(  # noqa: E731
        a.shape[:2] + (c.n_heads, c.head_dim)
    )
    return (heads(_norm(c, proj(pf["wq"]), pf["q_norm"])),
            heads(_norm(c, proj(pf["wk"]), pf["k_norm"])),
            heads(proj(pf["wv"])))


def definition_attention(config: DeltaLMConfig, q, k, v):
    """Causal attention over one whole sequence as written: ``q``, ``k``,
    ``v [s, heads, hd]`` -> ``[s, heads, hd]`` (float32 scores)."""
    s = q.shape[0]
    scores = jnp.einsum(
        "shd,thd->hst", q, k, preferred_element_type=jnp.float32
    ) * config.head_dim ** -0.5
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum(
        "hst,thd->shd", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)


# -- the block and the layer loop ---------------------------------------------


def feed(config: DeltaLMConfig, params, layer: int, h):
    """Layer ``layer``'s SwiGLU on ``h [b, s, d]``."""
    pl = params["layers"]
    with jax.named_scope("mlp"):
        f = config.mlp_dim
        gu = jnp.einsum("bsd,df->bsf", h, pl["w_gu"][layer].astype(h.dtype))
        act = (jax.nn.silu(gu[..., :f]) * gu[..., f:]).astype(h.dtype)
        return jnp.einsum(
            "bsf,fd->bsd", act, pl["w_down"][layer].astype(h.dtype)
        )


def block(config: DeltaLMConfig, params, layer: int, x, mixer, taps=None,
          probe=None):
    """Decoder block ``layer`` over the residual ``x [b, s, d]``
    (float32). ``mixer``: for a delta layer ``mix(q, k, v, g, beta) -> o
    [b, s, heads, dv]`` float32 (the caller holds the state) and ``taps
    [b, K - 1, conv_width]`` the convolutions' inputs it carries on from;
    for a full layer ``attend(q, k, v) -> [b, s, heads, hd]``. Returns
    (``x``, what the layer leaves: ``zz`` of :func:`conv_inputs` for a
    delta layer, the new rows' ``(k, v)`` for a full one). ``probe``: a
    dict the block fills with what it otherwise keeps to itself (a
    check's probe reads them): ``mix`` (the mixer's output before its
    norm and gate), ``gated`` (before ``W_o``), ``x_out``."""
    c, cdt = config, config.compute_dtype
    pl = params["layers"]
    u = x.astype(cdt)
    at = c.index_in_kind(layer)
    if c.layer_types[layer] == DELTA:
        pm = jax.tree_util.tree_map(lambda a: a[at], params["delta"])
        with jax.named_scope("attn"):
            q, k, v, g, beta, gate, left = delta_inputs(c, pm, u, taps)
            with jax.named_scope("delta"):
                mix = mixer(q, k, v, g, beta)
            gated, y = delta_out(c, pm, mix, gate)
    else:
        pm = jax.tree_util.tree_map(lambda a: a[at], params["full"])
        with jax.named_scope("attn"):
            q, k, v = full_inputs(c, pm, u)
            with jax.named_scope("full"):
                mix = mixer(q, k, v)
            b, s = mix.shape[:2]
            gated = mix.reshape(b, s, -1).astype(cdt)
            y = jnp.einsum("bse,ed->bsd", gated, pm["wo"].astype(cdt))
            left = (k, v)
    h = x + _norm(c, y.astype(jnp.float32), pl["mix_norm"][layer])
    y_mlp = feed(c, params, layer, h.astype(cdt))
    x = h + _norm(c, y_mlp.astype(jnp.float32), pl["ffn_norm"][layer])
    if probe is not None:
        probe.update(mix=mix, gated=gated, x_out=x)
    return x, left


def embed(config: DeltaLMConfig, params, tokens):
    """The residual's start: the tokens' embeddings, float32."""
    return jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)


def unembed(config: DeltaLMConfig, params, x):
    """The final norm and the untied head: float32 logits."""
    with jax.named_scope("vocab"):
        h = _norm(config, x, params["final_norm"]).astype(
            config.compute_dtype
        )
        return jnp.einsum(
            "bsd,dv->bsv", h, params["head"].astype(config.compute_dtype)
        ).astype(jnp.float32)


def forward(config: DeltaLMConfig, params, tokens):
    """``tokens [b, s]`` -> float32 logits ``[b, s, vocab]``: the layers
    as the engine runs them, but over whole sequences from a zero state
    and with no cache (the recurrence a token a step,
    ``gated_delta.delta_step_reference`` under ``lax.scan``;
    :func:`definition_attention`)."""
    c = config
    b = tokens.shape[0]
    zero = jnp.zeros(
        (b, c.linear_heads, c.linear_key_dim, c.linear_value_dim),
        jnp.float32,
    )

    def recurrence(q, k, v, g, beta):
        def step(state, row):
            o, state = gated_delta.delta_step_reference(*row, state)
            return state, o

        rows = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
        return jnp.moveaxis(jax.lax.scan(step, zero, rows)[1], 0, 1)

    attend = jax.vmap(lambda *a: definition_attention(c, *a))
    x = embed(c, params, tokens)
    for layer, kind in enumerate(c.layer_types):
        if kind == DELTA:
            x, _ = block(c, params, layer, x, recurrence,
                         taps=zero_taps(c, b))
        else:
            x, _ = block(c, params, layer, x, attend)
    return unembed(c, params, x)
