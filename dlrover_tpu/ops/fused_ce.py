"""Fused blockwise cross-entropy: the LM loss without the [N, V] logits.

The baseline loss (models/llama.py ``cross_entropy``) materializes full
f32 logits — at the flagship bench shape that is a 2 GB HBM round-trip
per pass (forward write, logsumexp read, softmax write/read in the
backward, plus the 2 GB value_and_grad residual). This op computes the
identical token-mean ``nll + z_weight * logz^2`` loss by streaming the
vocab or the rows in blocks, so only a [block_rows, V] or an
[N, block_v] tile ever exists. Two paths, chosen by the mesh
(``resolve_impl``), both plain XLA:

- **Chunked path** (default): ``lax.scan`` over ROW chunks with exact
  per-chunk softmax, computing loss AND unit-cotangent gradients in the
  forward (the loss is a scalar, so grads scale linearly by the incoming
  cotangent — the backward is two multiplies). Total matmul FLOPs equal
  the dense path's three (logits, dx, dw): no flash-style recompute.
  Peak memory is one [block_rows, V] f32 logits tile plus the [d, V]
  f32 dw accumulator — residuals are (dx_unit, dw_unit), both small.
- **XLA path** (sharded meshes): the same math as a ``lax.scan`` over
  vocab blocks with an online logsumexp, keeping the [N, d]
  activations un-rechunked so GSPMD sharding over batch/seq axes
  passes through untouched. Its custom VJP keeps residuals to (x, w,
  targets, weights, logz) — logz is [N], everything else is an input —
  and recomputes the logits tile in the backward.

A Pallas version of the vocab scan (forward kernel, dx and dw kernels
recomputing the logits tile flash-style) lost on the chip: 30.61 ms
against the dense loss's 20.56 at the flagship head shape (v5e, r05) —
5 logits-sized matmuls where the chunked path pays 3. Deleted in PR 29.

Parity note: the reference has no loss kernels at all (torch frameworks
own the compute path, SURVEY.md §2.9); this is the TPU-native analogue
of the fused-CE kernels its workloads would get from apex/liger.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30

# Dense/fused crossover in N*V elements (f32-logits bytes / 4). What was
# measured (r05, v5e, 2026-08-01, 334M): the flagship head shape
# n=16384, v=32000 (N*V = 5.24e8, just below this line) ran chunked at
# 1.042x DENSE (the [d, V] f32 dw-carry HBM round-trip per row chunk is
# pure overhead while the logits still fit), so dense keeps its edge
# below the line; above it the ~2 GiB+ logits are what stop
# long-context steps from fitting (the attn_save remat budget), and the
# fused path's time cost is a wash. llama.resolve_ce_path delegates here.
AUTO_FUSED_MIN_NV = 2 * 1024**3 // 4


def auto_prefers_dense(n_tokens: int, vocab: int) -> bool:
    """True when CE "auto" should run the DENSE logits path for a batch
    of ``n_tokens`` rows over ``vocab`` classes (below the measured
    crossover, see AUTO_FUSED_MIN_NV)."""
    return n_tokens * vocab < AUTO_FUSED_MIN_NV


def _ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# ---------------------------------------------------------------------------
# XLA (lax.scan over vocab blocks) implementation — the sharded-mesh path
# ---------------------------------------------------------------------------


def _xla_forward(x, w, tgt, z_weight, block_v):
    n, d = x.shape
    v = w.shape[1]
    vp = _ceil_to(v, block_v)
    nb = vp // block_v
    wp = jnp.pad(w, ((0, 0), (0, vp - v))).astype(x.dtype)

    def body(carry, j):
        m, l, tl = carry
        wj = jax.lax.dynamic_slice_in_dim(wp, j * block_v, block_v, axis=1)
        logits = jax.lax.dot_general(
            x, wj, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [n, block_v]
        cols = j * block_v + jax.lax.iota(jnp.int32, block_v)
        logits = jnp.where(cols[None, :] < v, logits, NEG_INF)
        m_blk = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        l = l * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits - m_new[:, None]), axis=-1
        )
        tl = tl + jnp.sum(
            jnp.where(cols[None, :] == tgt[:, None], logits, 0.0), axis=-1
        )
        return (m_new, l, tl), None

    init = (
        jnp.full((n,), NEG_INF, jnp.float32),
        jnp.zeros((n,), jnp.float32),
        jnp.zeros((n,), jnp.float32),
    )
    (m, l, tl), _ = jax.lax.scan(body, init, jnp.arange(nb))
    logz = m + jnp.log(jnp.maximum(l, 1e-30))
    per_tok = logz - tl + z_weight * jnp.square(logz)
    return per_tok, logz


def _xla_backward(x, w, tgt, logz, coef_a, coef_b, block_v):
    """coef_a/b: [n] f32 — a*softmax - b*onehot is d(loss)/d(logits)."""
    n, d = x.shape
    v = w.shape[1]
    vp = _ceil_to(v, block_v)
    nb = vp // block_v
    wp = jnp.pad(w, ((0, 0), (0, vp - v))).astype(x.dtype)

    def body(dx, j):
        wj = jax.lax.dynamic_slice_in_dim(wp, j * block_v, block_v, axis=1)
        logits = jax.lax.dot_general(
            x, wj, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        cols = j * block_v + jax.lax.iota(jnp.int32, block_v)
        logits = jnp.where(cols[None, :] < v, logits, NEG_INF)
        p = jnp.exp(logits - logz[:, None])
        g = coef_a[:, None] * p - jnp.where(
            cols[None, :] == tgt[:, None], coef_b[:, None], 0.0
        )
        g = g.astype(x.dtype)
        dx = dx + jax.lax.dot_general(
            g, wj, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dwj = jax.lax.dot_general(
            x, g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [d, block_v]
        return dx, dwj

    dx, dws = jax.lax.scan(body, jnp.zeros((n, d), jnp.float32),
                           jnp.arange(nb))
    dw = dws.transpose(1, 0, 2).reshape(d, vp)[:, :v]
    return dx, dw


# ---------------------------------------------------------------------------
# Chunked implementation — gradients computed in the forward
# ---------------------------------------------------------------------------


def _pick_chunk(n: int, v: int, block_rows: Optional[int]) -> int:
    """Rows per chunk: the largest power of two whose f32 logits tile
    stays under ~1.1 GB (measured on v5e at n=16k/v=32k: 8192 rows runs
    at 1.014x dense vs 1.07x for 4096 — the [d, V] dw-carry HBM
    round-trip amortizes with fewer chunks — while one ~1 GB transient
    tile still leaves HBM for a long-context step)."""
    if block_rows is not None:
        return max(8, min(block_rows, n))
    budget = 1152 * 1024**2
    c = 8
    while c * 2 <= n and (c * 2) * v * 4 <= budget:
        c *= 2
    # Padding to a chunk multiple costs real matmul FLOPs on zero-weight
    # rows (n=8200 with chunk 8192 would nearly double the CE) — halve
    # the chunk while the pad waste exceeds ~12.5% of n.
    while c > 8 and ((n + c - 1) // c * c - n) * 8 > n:
        c //= 2
    return c


def _chunk_grad_tile(x, w, tgt, wgt, z_weight):
    """One row chunk, exact softmax: (loss_contrib, dx_unit, dw_unit)."""
    logits = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [c, v] f32
    m = jnp.max(logits, axis=-1, keepdims=True)
    p_un = jnp.exp(logits - m)
    logz = (m + jnp.log(jnp.sum(p_un, axis=-1, keepdims=True)))[:, 0]
    tl = jnp.take_along_axis(logits, tgt[:, None], axis=-1)[:, 0]
    per_tok = logz - tl + z_weight * jnp.square(logz)
    loss = jnp.sum(per_tok * wgt)
    # d(loss)/d(logits) at unit cotangent: a*softmax - wgt*onehot.
    a = wgt * (1.0 + 2.0 * z_weight * logz)
    cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    g = a[:, None] * jnp.exp(logits - logz[:, None]) - jnp.where(
        cols == tgt[:, None], wgt[:, None], 0.0
    )
    g = g.astype(x.dtype)
    dx = jax.lax.dot_general(
        g, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)
    dw = jax.lax.dot_general(
        x, g, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [d, v] f32
    return loss, dx, dw


def _chunked_loss_only(x, w, tgt, wgt, z_weight, chunk):
    n, d = x.shape
    nb = n // chunk
    wc = w.astype(x.dtype)

    def body(loss, inp):
        xs, ts, ws = inp
        logits = jax.lax.dot_general(
            xs, wc, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        tl = jnp.take_along_axis(logits, ts[:, None], axis=-1)[:, 0]
        per_tok = logz - tl + z_weight * jnp.square(logz)
        return loss + jnp.sum(per_tok * ws), None

    loss, _ = jax.lax.scan(
        body,
        jnp.zeros((), jnp.float32),
        (
            x.reshape(nb, chunk, d),
            tgt.reshape(nb, chunk),
            wgt.reshape(nb, chunk),
        ),
    )
    return loss


def _chunked_fwd_pass(x, w, tgt, wgt, z_weight, chunk):
    """Full fwd+grad sweep: (loss, dx_unit [n,d], dw_unit [d,v] f32)."""
    n, d = x.shape
    v = w.shape[1]
    nb = n // chunk
    wc = w.astype(x.dtype)

    def body(carry, inp):
        dw_acc, loss_acc = carry
        xs, ts, ws = inp
        loss, dx, dw = _chunk_grad_tile(xs, wc, ts, ws, z_weight)
        return (dw_acc + dw, loss_acc + loss), dx

    (dw, loss), dxs = jax.lax.scan(
        body,
        (jnp.zeros((d, v), jnp.float32), jnp.zeros((), jnp.float32)),
        (
            x.reshape(nb, chunk, d),
            tgt.reshape(nb, chunk),
            wgt.reshape(nb, chunk),
        ),
    )
    return loss, dxs.reshape(n, d), dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _chunked_ce_core(x, w, tgt, wgt, z_weight, chunk):
    return _chunked_loss_only(x, w, tgt, wgt, z_weight, chunk)


def _chunked_fwd(x, w, tgt, wgt, z_weight, chunk):
    loss, dx_unit, dw_unit = _chunked_fwd_pass(
        x, w, tgt, wgt, z_weight, chunk
    )
    return loss, (dx_unit, dw_unit.astype(w.dtype))


def _chunked_bwd(z_weight, chunk, res, gbar):
    dx_unit, dw_unit = res
    n = dx_unit.shape[0]
    return (
        (gbar * dx_unit.astype(jnp.float32)).astype(dx_unit.dtype),
        (gbar * dw_unit.astype(jnp.float32)).astype(dw_unit.dtype),
        np.zeros((n,), jax.dtypes.float0),
        jnp.zeros((n,), jnp.float32),
    )


_chunked_ce_core.defvjp(_chunked_fwd, _chunked_bwd)


# ---------------------------------------------------------------------------
# Custom-VJP core and public op
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _fused_ce_core(x, w, tgt, wgt, z_weight, block_v):
    per_tok, _ = _xla_forward(x, w, tgt, z_weight, block_v)
    return jnp.sum(per_tok * wgt)


def _core_fwd(x, w, tgt, wgt, z_weight, block_v):
    per_tok, logz = _xla_forward(x, w, tgt, z_weight, block_v)
    return jnp.sum(per_tok * wgt), (x, w, tgt, wgt, logz)


def _core_bwd(z_weight, block_v, res, gbar):
    x, w, tgt, wgt, logz = res
    scaled = gbar * wgt                                   # [n] f32
    coef_a = scaled * (1.0 + 2.0 * z_weight * logz)
    coef_b = scaled
    dx, dw = _xla_backward(x, w, tgt, logz, coef_a, coef_b, block_v)
    return (
        dx.astype(x.dtype),
        dw.astype(w.dtype),
        np.zeros(tgt.shape, jax.dtypes.float0),
        jnp.zeros_like(wgt),
    )


_fused_ce_core.defvjp(_core_fwd, _core_bwd)


def _multi_device_mesh_active() -> bool:
    """True when tracing under a ``with mesh:`` context spanning >1
    device — the case where the chunked path's row re-chunking could
    fight GSPMD's batch/seq sharding and the plain vocab-scan XLA path
    (which leaves [N, d] intact) is the safe choice."""
    try:
        from dlrover_tpu.parallel.sharding import current_mesh

        mesh = current_mesh()
        return mesh is not None and mesh.size > 1
    except Exception:
        return False


def resolve_impl(impl: Optional[str] = None) -> str:
    """The fused-CE sub-impl auto-selection: "chunked" single-device,
    the GSPMD-safe vocab-scan "xla" path under a multi-device mesh.
    Mesh-dependent — call under the active ``with mesh:``."""
    if impl is not None:
        return impl
    return "xla" if _multi_device_mesh_active() else "chunked"


def fused_cross_entropy(
    x,
    w,
    targets,
    mask=None,
    z_weight: float = 1e-4,
    block_v: int = 1024,
    block_rows: Optional[int] = None,
    impl: Optional[str] = None,
):
    """Token-mean CE + z-loss from hidden states, no [N, V] logits.

    Identical semantics to ``llama.cross_entropy(x @ w, targets, mask)``
    (f32 logits, token-mean weighting, ``z_weight * logz^2``). x: [..., d]
    hidden states (post final-norm, compute dtype); w: [d, V] unembedding;
    targets int [...]; mask optional [...] — tokens with mask 0 contribute
    nothing.

    impl: "chunked" | "xla" | None. Auto picks "chunked" (dense-speed,
    O(block_rows*V) memory) except under a multi-device mesh, where the
    vocab-scan "xla" path keeps GSPMD shardings intact (``resolve_impl``
    is the selection, shared with the driver dryrun's per-mesh CE
    logging). Anything else raises ``ValueError``.
    """
    impl = resolve_impl(impl)
    if impl not in ("chunked", "xla"):
        raise ValueError(f"impl {impl!r} not in ('chunked', 'xla')")
    d = x.shape[-1]
    n = int(np.prod(x.shape[:-1]))
    x2 = x.reshape(n, d)
    tgt = targets.reshape(n)
    if mask is None:
        wgt = jnp.full((n,), 1.0 / n, jnp.float32)
    else:
        m = mask.reshape(n).astype(jnp.float32)
        wgt = m / jnp.maximum(jnp.sum(m), 1.0)
    wgt = jax.lax.stop_gradient(wgt)
    if impl == "chunked":
        chunk = _pick_chunk(max(n, 8), w.shape[1], block_rows)
        n_pad = _ceil_to(max(n, 8), chunk)
    else:
        # Pad the token dim so any (b, s) works; padded rows carry zero
        # weight and target 0 — they affect neither loss nor grads.
        n_pad = _ceil_to(max(n, 8), 8)
    if n_pad != n:
        x2 = jnp.pad(x2, ((0, n_pad - n), (0, 0)))
        tgt = jnp.pad(tgt, (0, n_pad - n))
        wgt = jnp.pad(wgt, (0, n_pad - n))
    if impl == "chunked":
        return _chunked_ce_core(x2, w, tgt, wgt, z_weight, chunk)
    return _fused_ce_core(x2, w, tgt, wgt, z_weight, block_v)
