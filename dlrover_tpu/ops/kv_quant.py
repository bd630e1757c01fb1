"""Int8 KV-cache quantization: per-(row, head) scales, symmetric.

Decode is HBM-bandwidth-bound and the KV cache is the stream that
grows with context: r05 (v5e, 2026-08-01, 334M) read the decode step at 1.33–1.46× the
HBM roofline with bf16 KV. Storing the cache as int8 halves the bytes
every decode step must move — the direct lever on that gap — and
doubles how many paged-KV blocks fit in the same HBM (the serving
capacity axis of docs/DESIGN.md §31).

Scheme: one f32 scale per KV **head per cache row** (``amax / 127``
over the head_dim vector — the finest granularity that adds no
per-element metadata). A head's K row is written once and never
updated, so the scale is computed at append time and immutable after;
d=128 int8 values + one f32 scale = 132 bytes/head/row vs 256 for
bf16 (1.94×). Dequantization happens at the READ site — folded into
the attention math (scales applied to logits / probabilities, never
materializing a dequantized cache) in the XLA append-free step
(models/generate._append_free_attention), which every int8 decode
program runs.

The quantizer is round-to-nearest (deterministic — the cache must be
bit-stable across replays); clipping is impossible by construction
(values are scaled by their own amax).
"""

import json
import struct

import jax.numpy as jnp
import numpy as np

# Scales of all-zero rows would be 0 -> 0/0 at dequant; clamp to a
# denormal-free floor instead (the quantized values are 0 either way).
_SCALE_FLOOR = 1e-20


def quantize_kv(x):
    """x [..., d] float -> (q int8 [..., d], scale f32 [...]).

    ``q * scale[..., None]`` reconstructs x to within amax/254 per
    element (symmetric round-to-nearest over the head_dim vector)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax / 127.0, _SCALE_FLOOR)
    q = jnp.round(xf / scale[..., None])
    return q.astype(jnp.int8), scale


def dequantize_kv(q, scale, dtype=jnp.float32):
    """Materializing inverse (tests / prefill views); the hot decode
    paths fold ``scale`` into logits/probabilities instead."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def bytes_per_head_row(
    head_dim: int, kv_dtype: str, fp_itemsize: int = 2
) -> int:
    """HBM bytes one KV head's cache row costs under this scheme —
    int8 values plus the one f32 scale, or ``head_dim * fp_itemsize``
    for fp caches. The ONE definition shared by the paged engine's
    block gauge, the equal-HBM serving bench sizing, and the decode
    roofline, so the three byte accounts can never drift."""
    if kv_dtype == "int8":
        return head_dim + 4
    return head_dim * fp_itemsize


# ---------------------------------------------------------------------------
# Pure-bytes wire format (block migration between fleet replicas)
# ---------------------------------------------------------------------------
#
# Layout: MAGIC (4B) | header_len (u32 LE) | json header | kq | vq | ks | vs
# with kq/vq int8 C-order and ks/vs f32 LE C-order. The header records
# the int8 payload shape, the scale shape, and the SOURCE cache dtype so
# the importer knows whether dequantization reconstructs the original
# cache exactly (int8 source: bit-exact passthrough) or to within the
# amax/254 quantization bound (fp source: wire cost roughly halves).

_WIRE_MAGIC = b"KVW1"


def kv_to_wire(k, v, k_scale=None, v_scale=None):
    """Pack a (k, v) KV span into a self-describing byte string.

    Floating inputs are int8-quantized here (``quantize_kv``), scales
    inline; int8 inputs must arrive WITH their scales and pass through
    bit-exact (the idempotent-roundtrip contract). Shapes are arbitrary
    ``[..., d]`` as long as k and v match."""
    k = np.asarray(k)
    v = np.asarray(v)
    if k.shape != v.shape:
        raise ValueError(f"k/v shape mismatch: {k.shape} vs {v.shape}")
    if k.dtype == np.int8:
        if k_scale is None or v_scale is None:
            raise ValueError("int8 KV requires k_scale and v_scale")
        kq, ks = k, np.asarray(k_scale, np.float32)
        vq, vs = v, np.asarray(v_scale, np.float32)
        src_dtype = "int8"
    else:
        if k_scale is not None or v_scale is not None:
            raise ValueError("scales only accompany int8 KV")
        kq, ks = quantize_kv(jnp.asarray(k))
        vq, vs = quantize_kv(jnp.asarray(v))
        kq, ks = np.asarray(kq), np.asarray(ks, np.float32)
        vq, vs = np.asarray(vq), np.asarray(vs, np.float32)
        src_dtype = str(k.dtype)
    if ks.shape != kq.shape[:-1] or vs.shape != vq.shape[:-1]:
        raise ValueError(
            f"scale shape {ks.shape} does not match KV rows {kq.shape[:-1]}"
        )
    header = json.dumps(
        {
            "v": 1,
            "shape": list(kq.shape),
            "scale_shape": list(ks.shape),
            "src_dtype": src_dtype,
        }
    ).encode()
    return b"".join(
        [
            _WIRE_MAGIC,
            struct.pack("<I", len(header)),
            header,
            np.ascontiguousarray(kq).tobytes(),
            np.ascontiguousarray(vq).tobytes(),
            np.ascontiguousarray(ks).tobytes(),
            np.ascontiguousarray(vs).tobytes(),
        ]
    )


def kv_from_wire(buf):
    """Inverse of :func:`kv_to_wire`.

    Returns ``(kq, vq, ks, vs, header)`` — always int8 values + f32
    scales; the importer dequantizes (``dequantize_kv``) only when its
    destination cache is fp. ``kv_to_wire(*kv_from_wire(b)[:4])`` is
    byte-identical to ``b`` (idempotent roundtrip)."""
    if buf[:4] != _WIRE_MAGIC:
        raise ValueError("bad KV wire magic")
    (hlen,) = struct.unpack_from("<I", buf, 4)
    off = 8
    header = json.loads(buf[off : off + hlen].decode())
    off += hlen
    shape = tuple(header["shape"])
    scale_shape = tuple(header["scale_shape"])
    n_q = int(np.prod(shape, dtype=np.int64)) if shape else 1
    n_s = int(np.prod(scale_shape, dtype=np.int64)) if scale_shape else 1
    want = off + 2 * n_q + 2 * 4 * n_s
    if len(buf) != want:
        raise ValueError(f"KV wire truncated: {len(buf)} != {want}")
    kq = np.frombuffer(buf, np.int8, n_q, off).reshape(shape)
    off += n_q
    vq = np.frombuffer(buf, np.int8, n_q, off).reshape(shape)
    off += n_q
    ks = np.frombuffer(buf, "<f4", n_s, off).reshape(scale_shape)
    off += 4 * n_s
    vs = np.frombuffer(buf, "<f4", n_s, off).reshape(scale_shape)
    return kq, vq, ks, vs, header
