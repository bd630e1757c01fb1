"""Block migration: a request's paged-KV state as bytes (§36).

The disaggregated serving plane treats paged KV blocks as a fleet-wide
currency: a prefill replica fills a request's blocks, serializes them,
and a decode replica admits the request MID-STREAM — no re-prefill.
The same primitive backs live drain (autoscaler shrink / weight swap
without killing in-flight decodes).

- :func:`export_request` serializes a DECODE-state request on a source
  :class:`PagedServingEngine`: block contents (always int8 on the wire
  via ``ops.kv_quant.kv_to_wire`` — bit-exact passthrough for int8
  caches, quantize-on-export for fp caches so the wire cost roughly
  halves), fill cursor, sampled tokens, and scheduler state. The
  source keeps the request live until the importer acks — the caller
  decides whether the fallback is source-side completion (live drain)
  or a from-scratch re-prefill (two-phase dispatch).
- :func:`release_exported` drops the request from the source after the
  ack: slot recycled, blocks decref'd — conservation holds (prompt
  blocks the prefix cache holds a ref on stay cached).
- :func:`import_request` admits the payload into a destination engine
  through the scheduler's DECODE-entry path: allocate blocks (fresh,
  refcount 1 — COW state is rebuilt by construction, never shipped),
  install the table and fill, scatter the rows through a compiled
  per-block program whose destination id is a traced scalar (zero
  retraces, the COW-copy discipline), register the full prompt blocks
  into the destination prefix trie (hit-rate survives migration), and
  reconstruct the request's phase timeline on the local monotonic
  clock — the ``serving.migrate`` span lands between the (source-side)
  prefill and the local decode.

Payload layout: ``MAGIC | u32 header_len | json header | [index keys]
| kv wire`` with the kv wire from :func:`ops.kv_quant.kv_to_wire` (its
own self-describing header carries dtype + shapes). A sparse model's
blocks also hold index keys (kvpool/sparse.py): they travel in the
pool's dtype, bit for bit, in the LOGICAL shape ``[layers, n,
block_size, index_dim]`` whatever a row of the sender's pool holds
(kvpool/index_pool.py; the header's ``index`` gives dtype, shape and
byte count), because which rows a query selects hangs on their exact
values, and a block that arrived without them would be read as zeros.
Every other array a model's blocks hold beside K and V travels the same
way (``kvpool/layout.py``: the header's ``raw`` lists name, dtype, shape
and byte count, the bytes follow the index keys'); a latent model's
blocks hold nothing else (``latent``), and its payload ends there, with
no kv wire. A model with per-SLOT state (``kvpool/layout.py``:
``models/conv_lm.py``'s convolution state) ships the slot's state too,
raw and bit for bit, after the blocks' arrays (the header's ``state``
lists name, dtype, shape and byte count): a request that arrived
without it would decode on from another sequence's state, so an engine
that holds such state refuses a payload that lacks it, and the other
way round (:class:`MigrationError`). Snapshots stay behind: they belong
to the source's prefix cache. Wall-clock export
stamps bound the migration pause across processes on one host.
"""

import json
import struct
import time
from typing import Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp

from dlrover_tpu.common.log import logger
from dlrover_tpu.ops.kv_quant import kv_from_wire, kv_to_wire
from dlrover_tpu.serving.scheduler import DECODE, Request

MIGRATE_MAGIC = b"KVM1"


class MigrationError(RuntimeError):
    """Structural failure: payload malformed or engines incompatible."""


class MigrationRefused(MigrationError):
    """Destination cannot hold the request right now (no free slot /
    not enough blocks) — the router treats this as a routing miss, not
    a breaker strike."""


def _refuse_grouped(engine, what: str) -> None:
    """A pool in layer groups (``kvpool/layout.py``) migrates nothing
    yet: the wire carries one table's blocks, and a reach group's held
    rows and first block would have to ride with them."""
    if getattr(engine, "_reach_groups", None):
        raise MigrationError(
            f"migration {what} refused: this engine's pool is in layer "
            "groups (" + ", ".join(g.name for g in engine._groups)
            + "), and the wire format carries the blocks of one table"
        )


def export_request(engine, req: Request,
                   now: Optional[float] = None) -> bytes:
    """Serialize ``req``'s blocks + scheduler state on the source
    engine. The request stays LIVE on the source — pair with
    :func:`release_exported` once the importer acks."""
    _refuse_grouped(engine, "export")
    if req.inflight:
        # Fill and tokens are exported as the host committed them.
        engine._drain("migrate")
    if req.state != DECODE or not req.tokens:
        raise MigrationError(
            f"rid {req.rid} not migratable: state={req.state!r}, "
            f"{len(req.tokens)} tokens (prefill must have completed)"
        )
    if req.slot < 0:
        raise MigrationError(f"rid {req.rid} holds no slot")
    if now is None:
        now = time.monotonic()
    slot = req.slot
    blocks = list(engine._slot_blocks[slot])
    fill = int(engine._lengths[slot])
    if not blocks or fill > len(blocks) * engine.block_size:
        raise MigrationError(
            f"rid {req.rid}: fill {fill} exceeds {len(blocks)} blocks"
        )
    # Per-block compiled gather (``exp`` in _PagedSteps), NOT a jnp
    # fancy-index: ``k[:, ids]`` specializes XLA on len(ids), so every
    # distinct block count a migration touched compiled a fresh gather
    # (~400ms each on CPU) INSIDE the source's serve loop — the decode
    # batch stalled exactly when a request was leaving to unblock it.
    # n calls of one warmed program trade that for n dispatches.
    rows = [
        jax.device_get(engine._steps.exp(*engine._pools(), np.int32(b)))
        for b in blocks
    ]
    by_name = {
        a.name: np.stack([np.asarray(r[i]) for r in rows], axis=1)
        for i, a in enumerate(engine._layout)
    }
    wire = b""
    if "k" in by_name:
        wire = kv_to_wire(
            by_name["k"], by_name["v"],
            k_scale=by_name.get("k_scale"), v_scale=by_name.get("v_scale"),
        )
    # What the blocks hold beside K and V travels raw, in the pool's
    # dtype and logical shape (the index keys under their own key).
    raw_bytes, index_meta, raw_meta = b"", None, []
    for a in engine._layout:
        if not a.raw:
            continue
        data = np.ascontiguousarray(by_name[a.name]).view(np.uint8).tobytes()
        meta = {
            "dtype": str(by_name[a.name].dtype),
            "shape": list(by_name[a.name].shape), "nbytes": len(data),
        }
        raw_bytes += data
        if a.name == "index_keys":
            index_meta = meta
        else:
            raw_meta.append(dict(meta, name=a.name))
    # The slot's own state, one array a state array of the layout.
    state_meta = []
    if engine._state_layout:
        for a, rows in zip(engine._state_layout,
                           engine._get_slot_state(slot)):
            data = np.ascontiguousarray(rows).view(np.uint8).tobytes()
            state_meta.append({
                "name": a.name, "dtype": str(rows.dtype),
                "shape": list(rows.shape), "nbytes": len(data),
            })
            raw_bytes += data
    admit_ts = req.admit_ts if req.admit_ts is not None else (
        req.submit_ts
    )
    first_ts = req.first_token_ts if req.first_token_ts is not None \
        else now
    header = {
        "v": 1,
        "src_rid": req.rid,
        "prompt": [int(t) for t in req.prompt],
        "tokens": [int(t) for t in req.tokens],
        "max_new_tokens": req.max_new_tokens,
        "temperature": req.temperature,
        "slo_class": req.slo_class,
        "fill": fill,
        "n_blocks": len(blocks),
        "block_size": engine.block_size,
        "src_kv_dtype": engine.kv_cache_dtype,
        "index": index_meta,
        "raw": raw_meta,
        "state": state_meta,
        # Source-side phase durations, for timeline reconstruction on
        # the destination clock (monotonic stamps don't cross
        # processes; durations do).
        "queue_s": max(admit_ts - req.submit_ts, 0.0),
        "prefill_s": max(first_ts - admit_ts, 0.0),
        "decode_s": max(now - first_ts, 0.0),
        "deadline_remaining_s": (
            req.deadline - now if req.deadline is not None else None
        ),
        "prefix_hit_blocks": req.prefix_hit_blocks,
        # Wall clock (same-host processes): bounds the migration pause.
        "exported_wall": time.time(),
    }
    hdr = json.dumps(header).encode()
    return b"".join(
        [MIGRATE_MAGIC, struct.pack("<I", len(hdr)), hdr, raw_bytes, wire]
    )


def peek_header(payload: bytes) -> Dict[str, object]:
    """The scheduler-state header alone — routers size destinations
    (``n_blocks``) without touching the KV bytes."""
    if payload[:4] != MIGRATE_MAGIC:
        raise MigrationError("bad migration payload magic")
    (hlen,) = struct.unpack_from("<I", payload, 4)
    return json.loads(payload[8:8 + hlen].decode())


def release_exported(engine, req: Request,
                     now: Optional[float] = None) -> None:
    """Source-side release after the importer acked: recycle the slot,
    decref the blocks (prefix-cached prompt blocks keep the cache's
    ref — conservation holds), and record a ``migrated`` outcome. The
    request's spans are emitted by the DESTINATION: the source emits
    nothing, or the request would double-report."""
    if req.inflight:
        engine._drain("migrate")  # it may finish here: the source won
    if req.state == DECODE and req.slot >= 0:
        slot = req.slot
        engine.scheduler.evict(req, now)
        engine._release_slot(req, slot)
        engine._lengths[slot] = 0
        engine._tokens[slot] = 0
        engine._temps[slot] = 0.0
    engine.metrics.requests.inc(outcome="migrated")
    engine.metrics.annotate("serving_migrate_out", rid=req.rid)


def can_import(engine, n_blocks: int) -> bool:
    """Cheap admission probe: a free slot plus ``n_blocks`` coverable
    by free + evictable-cache blocks (the import never preempts a
    peer — a migration must not burn another request's prefill)."""
    if engine.scheduler.free_slots() < 1:
        return False
    stats = engine._allocator.stats(engine._live_block_ids())
    return stats["free"] + stats["cached"] >= n_blocks


def import_request(engine, payload: bytes,
                   trace: Optional[dict] = None) -> Request:
    """Admit a migrated request into ``engine`` mid-stream (see module
    docstring). Raises :class:`MigrationRefused` when the engine
    cannot hold it, :class:`MigrationError` on incompatibility."""
    _refuse_grouped(engine, "import")
    t_in = time.monotonic()
    header = peek_header(payload)
    (hlen,) = struct.unpack_from("<I", payload, 4)
    index_meta = header.get("index")
    if bool(index_meta) != bool(engine._index_dim):
        raise MigrationError(
            "index keys on one side only: wire "
            f"{'has' if index_meta else 'lacks'} them, engine "
            f"{'keeps' if engine._index_dim else 'does not keep'} them"
        )
    metas = ([dict(index_meta, name="index_keys")] if index_meta else []) \
        + list(header.get("raw") or ())
    wants = [a for a in engine._layout if a.raw]
    if [m["name"] for m in metas] != [a.name for a in wants]:
        raise MigrationError(
            f"wire blocks hold {[m['name'] for m in metas]} beside K/V, "
            f"the engine's {[a.name for a in wants]}"
        )
    raw_rows, at = {}, 8 + hlen
    for m in metas:
        raw_rows[m["name"]] = np.frombuffer(
            payload[at:at + m["nbytes"]], np.uint8
        ).view(jnp.dtype(m["dtype"])).reshape(m["shape"])
        at += m["nbytes"]
    state_metas = list(header.get("state") or ())
    if [m["name"] for m in state_metas] != [
        a.name for a in engine._state_layout
    ]:
        raise MigrationError(
            f"wire carries the slot state {[m['name'] for m in state_metas]}"
            f", the engine's slots hold "
            f"{[a.name for a in engine._state_layout]}"
        )
    state_rows = []
    for m, a in zip(state_metas, engine._state_layout):
        rows = np.frombuffer(
            payload[at:at + m["nbytes"]], np.uint8
        ).view(jnp.dtype(m["dtype"])).reshape(m["shape"])
        if rows.shape != (a.layers,) + a.shape:
            raise MigrationError(
                f"{a.name} {rows.shape} vs a slot's "
                f"{(a.layers,) + a.shape}"
            )
        state_rows.append(rows)
        at += m["nbytes"]
    pool_layers = engine._pools()[0].shape[0]
    has_kv = any(a.name == "k" for a in engine._layout)
    if has_kv:
        kq, vq, ks, vs, _ = kv_from_wire(payload[at:])
        L, n, bs, kh, hd = kq.shape
        # What the pool holds of K: its own layer axis and row (a model
        # that keeps K/V in some layers, or pads its heads, states both).
        held = (pool_layers,) + next(
            a.row_shape for a in engine._layout if a.name == "k"
        )
        if (L, kh, hd) != held:
            raise MigrationError(
                f"model shape mismatch: wire {(L, kh, hd)} vs engine "
                f"{held}"
            )
    elif len(payload) != at or not wants:
        raise MigrationError("a kv wire for an engine that keeps no K/V")
    else:
        L, n, bs = raw_rows[wants[0].name].shape[:3]
    for a in wants:
        # (an array at a stride holds fewer rows a block)
        want = (pool_layers, n, a.block_rows(bs)) + a.row_shape
        if raw_rows[a.name].shape != want:
            raise MigrationError(
                f"{a.name} {raw_rows[a.name].shape} vs blocks "
                f"{(pool_layers, n, a.block_rows(bs))} x {a.row_shape}"
            )
    if bs != engine.block_size or bs != header["block_size"]:
        raise MigrationError(
            f"block_size mismatch: wire {bs} vs engine "
            f"{engine.block_size}"
        )
    if n != header["n_blocks"] or n > engine.max_blocks:
        raise MigrationError(
            f"{n} wire blocks vs header {header['n_blocks']} / "
            f"table capacity {engine.max_blocks}"
        )
    fill = int(header["fill"])
    if fill > n * bs:
        raise MigrationError(f"fill {fill} exceeds {n} wire blocks")
    # The import writes a slot's token on the host: the host's vector
    # has to be the one the next launch is fed.
    engine._drain("migrate")
    if not can_import(engine, n):
        raise MigrationRefused(
            f"destination full: {n} blocks + a slot needed"
        )
    slo = header["slo_class"]
    if slo not in engine.scheduler.slo_classes:
        # A stock single-class destination must not reject tagged
        # traffic mid-migration; untagged is the local default.
        slo = None
    req = engine.scheduler.admit_decode(
        np.asarray(header["prompt"], np.int32),
        [int(t) for t in header["tokens"]],
        int(header["max_new_tokens"]),
        temperature=float(header["temperature"]),
        slo_class=slo,
        now=t_in,
    )
    slot = req.slot
    try:
        blocks = engine._alloc_blocks(n, req)
    except Exception:
        engine.scheduler.evict(req)
        raise
    engine._tables[slot, :] = 0  # SENTINEL_BLOCK
    engine._slot_blocks[slot] = list(blocks)
    engine._tables[slot, :n] = blocks
    engine._lengths[slot] = fill
    engine._tokens[slot] = req.tokens[-1]
    engine._temps[slot] = req.temperature
    arriving = dict(raw_rows)
    if has_kv and engine._quantized:
        arriving.update(k=kq, v=vq, k_scale=ks, v_scale=vs)
    elif has_kv:
        # fp destination: dequantize the int8 wire rows on the host
        # (q * scale is exact in f32 — the idempotent-roundtrip rule).
        arriving.update(
            k=kq.astype(np.float32) * ks[..., None],
            v=vq.astype(np.float32) * vs[..., None],
        )
    for i, dst in enumerate(blocks):
        engine._set_pools(engine._steps.imp(
            *engine._pools(),
            *(jnp.asarray(arriving[a.name][:, i]) for a in engine._layout),
            np.int32(dst),
        ))
    if state_rows:
        engine._put_slot_state(slot, state_rows)
    if engine._cache is not None:
        # Imported chains join the destination trie: the NEXT request
        # sharing this prompt hits warm blocks — hit-rate survives
        # migration. Partial tails stay private (decode appends there).
        n_full = req.prompt_len // bs
        if n_full:
            engine._cache.insert(req.prompt, blocks[:n_full])
    # Timeline on the LOCAL monotonic clock: the migrate window ends
    # now; its start is bounded by the wall-clock export stamp; the
    # source phases hang off it by their carried durations.
    t_done = time.monotonic()
    pause = max(time.time() - header["exported_wall"], t_done - t_in)
    req.migrate_end_ts = t_done
    req.migrate_start_ts = t_done - pause
    req.first_token_ts = req.migrate_start_ts - header["decode_s"]
    req.admit_ts = req.first_token_ts - header["prefill_s"]
    req.submit_ts = req.admit_ts - header["queue_s"]
    remaining = header.get("deadline_remaining_s")
    req.deadline = (
        t_done + remaining if remaining is not None else None
    )
    req.prefix_hit_blocks = int(header.get("prefix_hit_blocks", 0))
    req.trace = trace
    engine.metrics.requests.inc(outcome="imported")
    engine.metrics.annotate(
        "serving_import", rid=req.rid, src_rid=header["src_rid"],
        blocks=n, fill=fill, pause_s=round(pause, 6),
    )
    logger.debug(
        "imported rid %d (src rid %d): %d blocks, fill %d, pause %.1f"
        "ms", req.rid, header["src_rid"], n, fill, pause * 1e3,
    )
    return req
