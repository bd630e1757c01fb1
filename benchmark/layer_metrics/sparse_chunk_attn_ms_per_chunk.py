"""Kernels: device time under ``attn/sparse`` in the PREFILL program
(the chunk's attention under its selection: the Pallas kernel over the
pool in place since PR 34, ``masked_attention`` over the gathered views
before and off the chip) per traced chunk launch, all layers. The scope's
seconds and the launches are what ``sparse_scopes.reduce`` gathers for
``jit_prefill``; a program without the scope (the parent of the cell, a
dense model) gives nothing to read."""

from benchmark import sparse_scopes


def read(facts):
    chunk = (facts.get("sparse_scopes") or {}).get(sparse_scopes.PREFILL)
    if not chunk or not chunk.get("launches"):
        return None
    s = (chunk.get("scope_s") or {}).get("sparse")
    return 1e3 * s / chunk["launches"] if s else None
