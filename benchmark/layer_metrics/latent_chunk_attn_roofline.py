"""Kernels: the least time the chip could take for a chunk's attention
AS DEFINED (``flops_xing.latent_attention_chunk`` at the traced chunks'
mean ``prefill_tokens`` and ``prefill_kv_rows``: ``2 T heads rows 320``
FLOPs a layer; compute-bound) over the time under ``attn/mla`` in the
prefill program. An absorbed chunk does 3.4 times the definition's
operations and is charged for them, so the share cannot pass 100."""

from benchmark import flops_xing, latent_scopes, sparse_scopes


def read(facts):
    s = latent_scopes.per_launch_s(facts, latent_scopes.PREFILL, "mla")
    tokens = latent_scopes.traced_prefill_mean(facts, "prefill_tokens")
    rows = latent_scopes.traced_prefill_mean(facts, "prefill_kv_rows")
    if s is None or not tokens or not rows:
        return None
    work = flops_xing.latent_attention_chunk(
        facts["ctx"]["config"], tokens, rows
    )
    return sparse_scopes.roofline_pct(facts, work, s)
