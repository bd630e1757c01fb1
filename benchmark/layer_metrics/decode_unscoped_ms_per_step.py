"""Serving engine: device-op time of the decode program (``jit_step``)
under NONE of its scopes (``attn`` with ``index`` / ``select`` /
``sparse``, ``mlp`` with ``router`` / ``experts``, ``vocab``) per traced
decode launch: what the step does besides the model's arithmetic, such
as re-tiling a pool array whose device layout its gathers and its
landing scatter cannot read (three copies of the 0.21 GB index-key pool
a step before PR 36). ``sparse_scopes.reduce`` books it as
``scope_s["unscoped"]``; a program with no op outside its scopes, or a
run without the scope table, gives nothing to read. Named apart from the
train cells' ``step_unscoped_pct``, a share of the train step."""

from benchmark import sparse_scopes


def read(facts):
    s = sparse_scopes.per_decode_step_s(facts, ("unscoped",))
    return None if s is None else 1e3 * s
