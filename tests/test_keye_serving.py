"""The sparse-attention expert model (models/sparse_lm.py) through the
paged engine against the plain reference (benchmark/reference_keye.py),
on LOGITS, at a size where the selection is active (``index_topk`` 8,
contexts of 9-60 rows): chunked prefill + paged decode, a prefix hit
against the same request served cold, copy-on-write, preemption and
export/import with the index keys in tow, and the expert layer in a
full forward, a prefill chunk and a decode step. The cases run over the
shapes of ``SHAPES``: how many index keys the pool holds to a 128-lane
row follows from ``index_dim`` and ``block_size``
(``kvpool/index_pool.py``), and nothing a request is answered with may
depend on it."""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_keye as ref
from dlrover_tpu.models import sparse_lm
from dlrover_tpu.serving.kvpool import PagedServingEngine, migrate, sparse
from dlrover_tpu.serving.kvpool import index_pool

CFG = sparse_lm.tiny_config()
CFG_JSON = dict(
    num_attention_heads=CFG.n_heads, num_key_value_heads=CFG.n_kv_heads,
    num_experts_per_tok=CFG.moe_top_k, moe_intermediate_size=CFG.mlp_dim,
    rope_theta=CFG.rope_theta,
    sa_config=dict(indexer_num_heads=CFG.index_heads,
                   indexer_head_dim=CFG.index_dim, topk=CFG.index_topk),
)
MAX_LEN, CHUNK, BS = 96, 8, 4
# name -> (index_dim, block_size): what the pool's third array packs to
SHAPES = {
    "one_a_row": (CFG.index_dim, BS),     # 16 keys do not divide 4 tokens
    "sixteen_a_row": (8, 16),
    "two_a_row": (64, 4),
}


class Shape(NamedTuple):
    cfg: sparse_lm.SparseLMConfig
    cfg_json: dict
    params: dict
    bs: int
    pack: int


@functools.lru_cache(maxsize=None)
def _model(index_dim):
    cfg = sparse_lm.tiny_config(index_dim=index_dim)
    cfg_json = dict(CFG_JSON, sa_config=dict(
        CFG_JSON["sa_config"], indexer_head_dim=index_dim
    ))
    return cfg, cfg_json, sparse_lm.init_params(cfg, jax.random.key(7))


def _shape(index_dim, bs):
    return Shape(*_model(index_dim), bs,
                 index_pool.tokens_per_row(index_dim, bs))


@pytest.fixture(params=list(SHAPES))
def shape(request):
    got = _shape(*SHAPES[request.param])
    assert got.pack == {"one": 1, "two": 2, "sixteen": 16}[
        request.param.split("_")[0]
    ]
    return got


@pytest.fixture(scope="module")
def params():
    return _model(CFG.index_dim)[2]


def _shaped(shape, **kw):
    eng = _engine(shape.params, cfg=shape.cfg,
                  **{"block_size": shape.bs, **kw})
    assert eng.kv_stats()["index_tokens_per_row"] == \
        index_pool.tokens_per_row(shape.cfg.index_dim, eng.block_size)
    return eng


def _one_a_row(monkeypatch):
    """Engines built from here on hold one index key a row whatever
    their shape: a replica of before the pool packed."""
    monkeypatch.setattr(index_pool, "tokens_per_row", lambda *a: 1)


def _engine(params, cfg=CFG, **kw):
    kw = {"slots": 3, "max_len": MAX_LEN, "prefill_chunk": CHUNK,
          "block_size": BS, **kw}
    return PagedServingEngine(cfg, params, **kw)


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n).tolist()


def _drive(eng):
    done = []
    while eng.pending():
        done.extend(eng.step())
        eng.check_block_invariants()
    return done


def _ref_logits(params, seq, positions, cfg_json=CFG_JSON, **kw):
    """The reference's logits at ``positions`` of ``seq``. The sequence
    is padded to whole multiples of 32 rows and the positions to 8 (the
    reference is causal: what follows a row touches none of its logits),
    so that the reference compiles a few programs, not one a length."""
    positions = list(positions)
    pad, more = -len(seq) % 32, -len(positions) % 8
    logits, _ = ref.logits_at(
        params, jnp.asarray(seq + [0] * pad),
        jnp.asarray(positions + positions[-1:] * more), cfg_json, **kw,
    )
    return np.asarray(logits)[:len(positions)]


@functools.lru_cache(maxsize=None)
def _forwards(cfg, bs, chunk_attention=None):
    """``sparse.decode_forward``'s logits and ``chunk_forward``'s rows as
    two programs a (model, block size, what the chunk attends with):
    called bare, each call traced and compiled its layer scan anew."""
    return (
        jax.jit(lambda pools, *args: sparse.decode_forward(
            cfg, *pools, *args, bs
        )[0]),
        jax.jit(lambda pools, *args: sparse.chunk_forward(
            cfg, *pools, *args, bs, None, chunk_attention
        )[0]),
    )


def _next_logits(eng, req):
    """The decode program's logits for ``req``'s next token, from the
    engine's own pool, table and fill (what ``step`` samples from)."""
    eng._drain("test")
    tokens = np.zeros(eng.slots, np.int32)
    tokens[req.slot] = req.tokens[-1]
    logits = _forwards(eng.config, eng.block_size)[0](
        eng._pools(), eng._params, jnp.asarray(eng._tables),
        jnp.asarray(eng._lengths), jnp.asarray(tokens),
    )
    return np.asarray(logits[req.slot])


def _step_until(eng, req, n_tokens):
    while len(req.tokens) + req.inflight < n_tokens:
        eng.step()
    eng._drain("test")


def _take_the_chunk_kernel(monkeypatch):
    """Build the prefill program as on a TPU: the platform probe says
    so (the kernel then runs in interpret mode) and the predicate admits
    the tiny model's float32 pool and narrow heads."""
    from dlrover_tpu.ops import decode_attention as da
    from dlrover_tpu.serving.kvpool import families

    monkeypatch.setattr(families, "_on_tpu", lambda: True)
    monkeypatch.setattr(da, "sparse_chunk_kernel_supported", lambda *a: True)


@pytest.mark.parametrize("shape_name, chunk_attention", [
    ("one_a_row", "masked_attention"), ("one_a_row", "chunk_kernel"),
    ("sixteen_a_row", "masked_attention"), ("two_a_row", "masked_attention"),
])
@pytest.mark.parametrize("n_prompt", [9, 21, 37, 60])
def test_chunked_prefill_and_paged_decode_give_the_references_logits(
        n_prompt, shape_name, chunk_attention, monkeypatch):
    """Prompt in chunks through the pool, then decode steps: the logits
    the next step samples from are the plain forward's at that position,
    after 1 token (the prefill's) and after 5; the tokens emitted are
    its argmax; and the last chunk's own logits are its rows. Once with
    the chunk attended by ``masked_attention`` over the gathered views
    (what a CPU builds), once by the Pallas kernel over the pool in
    place (what a TPU builds; here interpreted). The decode steps pass
    odd and even fills, and the chunks' last is short of a row."""
    if chunk_attention == "chunk_kernel":
        _take_the_chunk_kernel(monkeypatch)
    shape = _shape(*SHAPES[shape_name])
    params, bs = shape.params, shape.bs
    ref_logits = functools.partial(_ref_logits, cfg_json=shape.cfg_json)
    eng = _shaped(shape)
    assert eng.pool_attention == "sparse_gather"
    assert eng.kv_stats()["sparse_chunk_attention"] == chunk_attention
    prompt = _prompt(n_prompt, n_prompt)
    req = eng.submit(prompt, 8)
    for n_out in (1, 5):
        _step_until(eng, req, n_out)
        seq = prompt + req.tokens
        want = ref_logits(params, seq, [len(seq) - 1])[0]
        np.testing.assert_allclose(
            _next_logits(eng, req), want, rtol=2e-4, atol=2e-4
        )
    # the prompt's last chunk, recomputed over the pool's rows below it
    start = (n_prompt - 1) // CHUNK * CHUNK
    chunk = np.zeros((1, CHUNK), np.int32)
    chunk[0, :n_prompt - start] = prompt[start:]
    x = _forwards(eng.config, bs, chunk_attention)[1](
        eng._pools(), eng._params, jnp.asarray(chunk),
        jnp.asarray(eng._tables[req.slot]), jnp.int32(start),
    )
    from dlrover_tpu.models import llama

    got = np.asarray(
        llama.unembed(shape.cfg, eng._params, x)
    )[0, :n_prompt - start]
    want = ref_logits(params, prompt, list(range(start, n_prompt)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    _drive(eng)
    seq = prompt + req.tokens
    rows = ref_logits(params, seq, n_prompt - 1 + np.arange(8))
    assert rows.argmax(-1).tolist() == req.tokens
    assert eng.kv_stats()["moe_rows_dropped"] == 0


def test_selection_matters_at_this_size_and_vanishes_below_topk(params):
    """Past ``topk`` rows the sparse logits differ from dense attention's
    (so the tests above would catch a selection that is not applied);
    with ``topk`` above the context the engine IS full causal
    attention."""
    prompt = _prompt(1, 40)
    sparse_rows = _ref_logits(params, prompt, [39])
    dense_rows = _ref_logits(params, prompt, [39], dense=True)
    assert np.abs(sparse_rows - dense_rows).max() > 1e-2
    wide = sparse_lm.tiny_config(index_topk=MAX_LEN)
    eng = _engine(params, cfg=wide)
    req = eng.submit(prompt, 4)
    _step_until(eng, req, 2)
    seq = prompt + req.tokens
    want = _ref_logits(params, seq, [len(seq) - 1], dense=True)[0]
    np.testing.assert_allclose(
        _next_logits(eng, req), want, rtol=2e-4, atol=2e-4
    )


def test_a_prefix_hit_is_the_same_request_served_cold(shape):
    """The second request's document part comes from the trie, K, V AND
    index keys: its logits and tokens are those of a cold engine."""
    BS = shape.bs
    doc, q0, q1 = _prompt(2, 32), _prompt(3, 7), _prompt(4, 11)
    cold = _shaped(shape)
    r_cold = cold.submit(doc + q1, 6)
    _step_until(cold, r_cold, 3)
    warm = _shaped(shape)
    warm.submit(doc + q0, 2)
    _drive(warm)
    r_warm = warm.submit(doc + q1, 6)
    _step_until(warm, r_warm, 3)
    assert r_warm.prefix_hit_blocks == len(doc) // BS
    assert warm.kv_stats()["prefix_hit_tokens"] == len(doc)
    assert r_warm.tokens == r_cold.tokens
    np.testing.assert_array_equal(
        _next_logits(warm, r_warm), _next_logits(cold, r_cold)
    )
    # the shared blocks are the first request's, index keys included
    shared = warm._slot_blocks[r_warm.slot][:len(doc) // BS]
    own = cold._slot_blocks[r_cold.slot][:len(doc) // BS]
    np.testing.assert_array_equal(
        np.asarray(warm._ki[:, shared]), np.asarray(cold._ki[:, own])
    )
    assert np.abs(np.asarray(warm._ki[:, shared])).max() > 0


@pytest.mark.parametrize("index_dim, pack", [(8, 16), (64, 2), (24, 1)])
def test_copy_on_write_copies_the_index_keys(index_dim, pack):
    """A full-prompt hit re-runs the last chunk; with blocks longer than
    a chunk that chunk lies inside a SHARED block, which is privatized
    first, and the copy carries the index keys: at 16 keys a row the
    re-run chunk is the second half of a row whose first half the copy
    brought."""
    prompt = _prompt(5, 32)           # 4 whole chunks, 2 whole blocks
    shape = _shape(index_dim, 16)
    assert shape.pack == pack
    eng = _shaped(shape)
    first = eng.submit(prompt, 3)
    _drive(eng)
    again = eng.submit(prompt, 3)
    _step_until(eng, again, 1)
    stats = eng.kv_stats()
    assert stats["cow_copies"] >= 1 and again.prefix_hit_blocks
    cached = eng._cache.lookup(prompt)      # the trie's own chain
    mine = eng._slot_blocks[again.slot][:len(cached)]
    copied = [i for i, (a, b) in enumerate(zip(cached, mine)) if a != b]
    assert copied
    for i in copied:
        for pool in eng._pools():
            np.testing.assert_array_equal(
                np.asarray(pool[:, cached[i]]), np.asarray(pool[:, mine[i]])
            )
    for block in cached:
        eng._allocator.decref(block)
    _drive(eng)
    assert again.tokens == first.tokens


def test_preemption_keeps_the_pool_consistent(shape):
    """A pool too small for three long requests preempts the youngest;
    everyone still gets the tokens an unpressed engine gives."""
    prompts = [_prompt(10 + i, 30) for i in range(3)]
    roomy = _shaped(shape, prefix_cache=False)
    want = [roomy.submit(p, 10) for p in prompts]
    _drive(roomy)
    tight = _shaped(
        shape, prefix_cache=False, num_blocks=MAX_LEN // shape.bs + 2
    )
    got = [tight.submit(p, 10) for p in prompts]
    _drive(tight)
    assert tight.metrics.kv_preemptions.value() >= 1
    assert [r.tokens for r in got] == [r.tokens for r in want]
    assert tight.kv_stats()["used"] == 0


def test_a_dry_pool_does_not_evict_a_document_its_slots_still_read(params):
    """Two resident documents; two long answers over the first are in
    flight when a short question touches the second, so the FIRST is the
    least recently used; then the answers' growth runs the pool dry. The
    relief valve must free the second document's blocks: the first one's
    leaf is held by two slots and frees nothing. (Before, the dry pool
    dropped it all the same, uncovered its parent, and ate the document
    from its tail without freeing a block: the next question over it
    prefilled it again, a 5.7 s stall on the chip.)"""
    doc_a, doc_b = _prompt(40, 32), _prompt(41, 32)
    eng = _engine(params, max_len=64, num_blocks=1 + 2 * (32 // BS) + 6)
    preempted = eng.metrics.kv_preemptions.value()   # (a shared registry)
    for doc in (doc_a, doc_b):
        eng.submit(doc, 1)
        _drive(eng)
    long = [eng.submit(doc_a + _prompt(60 + i, 3), 12) for i in range(2)]
    _step_until(eng, long[1], 1)
    short = eng.submit(doc_b + _prompt(70, 3), 2)
    _drive(eng)
    assert [len(r.tokens) for r in long + [short]] == [12, 12, 2]
    assert eng._cache.evicted_blocks_total > 0          # the pool ran dry
    assert eng.metrics.kv_preemptions.value() == preempted
    hits0 = eng.kv_stats()["prefix_hit_tokens"]
    eng.submit(doc_a + _prompt(80, 3), 1)
    _drive(eng)
    assert eng.kv_stats()["prefix_hit_tokens"] - hits0 == 32
    eng.check_block_invariants()


@pytest.mark.parametrize("shape_name, packs", [
    ("one_a_row", "as_built"),
    ("sixteen_a_row", "source_only"), ("sixteen_a_row", "destination_only"),
    ("two_a_row", "source_only"), ("two_a_row", "destination_only"),
])
def test_export_and_import_carry_the_index_keys(shape_name, packs,
                                                monkeypatch):
    """A request leaves one engine mid-decode and goes on in another:
    the payload holds its index keys bit for bit (K and V go as int8),
    in the LOGICAL shape whatever the source's rows hold, so a replica
    that packs and one that does not exchange blocks; and a dense
    destination refuses it."""
    shape = _shape(*SHAPES[shape_name])
    BS = shape.bs
    prompt = _prompt(6, 26)
    with monkeypatch.context() as m:
        if packs == "destination_only":
            _one_a_row(m)
        src = _shaped(shape)
    req = src.submit(prompt, 8)
    _step_until(src, req, 3)
    payload = migrate.export_request(src, req)
    header = migrate.peek_header(payload)
    fill = header["fill"]
    assert header["index"]["shape"] == [
        CFG.n_layers, header["n_blocks"], BS, shape.cfg.index_dim
    ]
    with monkeypatch.context() as m:
        if packs == "source_only":
            _one_a_row(m)
        dst = _shaped(shape)
    if packs != "as_built":
        assert {src.index_tokens_per_row, dst.index_tokens_per_row} == \
            {1, shape.pack}
    moved = migrate.import_request(dst, payload)
    rows_src = np.asarray(src._ki[:, src._slot_blocks[req.slot]])
    rows_dst = np.asarray(dst._ki[:, dst._slot_blocks[moved.slot]])
    flat = lambda a: a.reshape(a.shape[0], -1, a.shape[-1])[:, :fill]  # noqa: E731
    np.testing.assert_array_equal(flat(rows_src), flat(rows_dst))
    migrate.release_exported(src, req)
    _drive(dst)
    dst.check_block_invariants()
    assert len(moved.tokens) == 8
    from dlrover_tpu.models import llama

    dense_cfg = llama.tiny_config(
        n_layers=CFG.n_layers, n_kv_heads=CFG.n_kv_heads,
        head_dim=CFG.head_dim, dtype="float32",
    )
    dense = PagedServingEngine(
        dense_cfg, llama.init_params(dense_cfg, jax.random.key(0))[0],
        slots=2, max_len=MAX_LEN, prefill_chunk=CHUNK, block_size=BS,
    )
    with pytest.raises(migrate.MigrationError, match="index keys"):
        migrate.import_request(dense, payload)


@pytest.mark.parametrize("shape_name", ["sixteen_a_row", "two_a_row"])
def test_what_a_row_holds_changes_no_token(shape_name, monkeypatch):
    """The same model, pool and requests (a document asked twice, a
    cold prompt, fills odd and even) on the engine as its shape builds
    it and on one holding one key a row: the same tokens, and the same
    index keys in every block a request holds."""
    shape = _shape(*SHAPES[shape_name])
    doc = _prompt(20, 32)
    prompts = [doc + _prompt(21, 5), _prompt(22, 19), doc + _prompt(23, 10)]

    def serve():
        eng = _shaped(shape)
        first = eng.submit(prompts[0], 4)
        _drive(eng)
        rest = [eng.submit(p, 9) for p in prompts[1:]]
        _step_until(eng, rest[-1], 6)
        keys = [
            np.asarray(eng._ki[:, eng._slot_blocks[r.slot]]).reshape(
                CFG.n_layers, -1, shape.cfg.index_dim
            )[:, :eng._lengths[r.slot]]
            for r in rest
        ]
        _drive(eng)
        return eng, [r.tokens for r in [first] + rest], keys

    packed, tokens, keys = serve()
    _one_a_row(monkeypatch)
    plain, want_tokens, want_keys = serve()
    assert (packed.index_tokens_per_row, plain.index_tokens_per_row) == \
        (shape.pack, 1)
    assert packed._ki.rows.shape[-1] == 128
    assert packed._ki.shape == plain._ki.shape == plain._ki.rows.shape
    assert tokens == want_tokens
    for got, want in zip(keys, want_keys):
        np.testing.assert_array_equal(got, want)


def test_the_expert_layer_is_one_function_of_the_token(params):
    """The same token's expert output from a full forward, a prefill
    chunk and a decode step's batch: no capacity anywhere, nothing
    dropped, so what else a call carries changes nothing."""
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(size=(1, 24, CFG.embed_dim)).astype(np.float32))
    p = sparse_lm.layer_params(CFG, params, 0)
    full, c_full = sparse_lm.expert_mlp(CFG, p, x)
    chunk, c_chunk = sparse_lm.expert_mlp(CFG, p, x[:, 8:16])
    step, c_step = sparse_lm.expert_mlp(
        CFG, p, jnp.swapaxes(x[:, [3, 9, 20]], 0, 1)
    )
    np.testing.assert_allclose(chunk[0], full[0, 8:16], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        step[:, 0], full[0, [3, 9, 20]], rtol=1e-5, atol=1e-6
    )
    for c in (c_full, c_chunk, c_step):
        assert int(c.rows_dropped) == 0
    assert int(c_full.rows_held) == 24 * CFG.moe_top_k
    assert 1 <= int(c_step.experts_hit) <= 3 * CFG.moe_top_k
    # and it is the reference's layer
    with jax.default_matmul_precision("highest"):
        h = ref._norm(x[0], p["mlp_norm"])
        want, _ = ref.experts(p, h, ref.shape_of(CFG_JSON))
    np.testing.assert_allclose(
        full[0] - x[0], want, rtol=1e-4, atol=1e-5
    )


def test_dense_programs_are_what_they_were(params):
    """A dense config's pool has two arrays and its decode and prefill
    programs do not know of a third: their lowered text is the text the
    builders give when called as before this model existed."""
    from dlrover_tpu.models import llama
    from dlrover_tpu.serving.kvpool import dense, engine as paged

    cfg = llama.tiny_config(dtype="float32")
    counts = {"prefill": 0, "decode": 0}
    steps = paged._paged_steps(cfg, 2, 9, 4, 4, 8)
    assert steps.pool_attention == "xla_gather"
    eng = PagedServingEngine(
        cfg, llama.init_params(cfg, jax.random.key(0))[0], slots=2,
        max_len=16, prefill_chunk=8, block_size=4,
    )
    assert len(eng._pools()) == 2 and eng._ki is None
    assert "index_pool_bytes" not in eng.kv_stats()
    i32 = jnp.int32
    pools = eng._pools()
    dec_args = (
        *pools, eng._params, jnp.zeros((2, 4), i32), jnp.zeros(2, i32),
        jnp.zeros(2, i32), jnp.zeros(2, bool), jnp.zeros(2, jnp.float32),
        jax.random.key(0), i32(0), i32(0), i32(-1),
    )
    pre_args = (
        *pools, eng._params, jnp.zeros((1, 8), i32), jnp.zeros(4, i32),
        i32(0), i32(1), jnp.float32(0), jax.random.key(0), i32(0),
        jnp.bool_(True),
    )
    direct_decode = jax.jit(
        dense._build_paged_decode(cfg, 2, 4, 4, counts), donate_argnums=(0, 1)
    )
    direct_prefill = jax.jit(
        dense._build_paged_prefill(cfg, 4, 4, 8, counts), donate_argnums=(0, 1)
    )
    assert steps.decode.lower(*dec_args).as_text() == \
        direct_decode.lower(*dec_args).as_text()
    assert steps.prefill.lower(*pre_args).as_text() == \
        direct_prefill.lower(*pre_args).as_text()
    assert len(steps.decode.lower(*dec_args).out_info) == 3


def test_sparse_programs_are_what_the_sparse_builders_give(params):
    """The sibling of the dense case above, for this model: the decode
    and prefill programs the one builder hands a sparse engine lower to
    the text ``kvpool/sparse.py``'s builders give when called directly,
    whatever other families it builds (this family's ``kinds`` alone are
    a part of its key)."""
    from dlrover_tpu.serving.kvpool import engine as paged

    slots, max_blocks = 3, MAX_LEN // BS
    eng = _engine(params)
    steps = eng._steps
    assert steps is paged._paged_steps(
        CFG, slots, eng.num_blocks, max_blocks, BS, CHUNK
    )
    assert steps.pool_attention == "sparse_gather"
    assert steps.kinds == (("sparse_chunk_attention", "masked_attention"),)
    counts = {"prefill": 0, "decode": 0}
    i32 = jnp.int32
    pools = eng._pools()
    assert len(pools) == 3
    dec_args = (
        *pools, eng._params, jnp.zeros((slots, max_blocks), i32),
        jnp.zeros(slots, i32), jnp.zeros(slots, i32),
        jnp.zeros(slots, bool), jnp.zeros(slots, jnp.float32),
        jax.random.key(0), i32(0), i32(0), i32(-1),
    )
    pre_args = (
        *pools, eng._params, jnp.zeros((1, CHUNK), i32),
        jnp.zeros(max_blocks, i32), i32(0), i32(1), jnp.float32(0),
        jax.random.key(0), i32(0), jnp.bool_(True),
    )
    direct_decode = jax.jit(
        sparse.build_decode(CFG, slots, max_blocks, BS, counts),
        donate_argnums=(0, 1, 2),
    )
    direct_prefill = jax.jit(
        sparse.build_prefill(
            CFG, max_blocks, BS, CHUNK, counts,
            {"sparse_chunk_attention": "masked_attention"},
        ),
        donate_argnums=(0, 1, 2),
    )
    assert steps.decode.lower(*dec_args).as_text() == \
        direct_decode.lower(*dec_args).as_text()
    assert steps.prefill.lower(*pre_args).as_text() == \
        direct_prefill.lower(*pre_args).as_text()


@pytest.mark.parametrize("model", ["dense", "sparse"])
def test_no_other_models_engine_speaks_of_a_latent_pool(model, params):
    """``kv_stats()`` of a dense and of a sparse engine carry no
    ``latent_*`` key, and their programs' key no latent kind: what a
    latent model's decode step reads its rows with is nothing to them."""
    from dlrover_tpu.models import llama

    if model == "dense":
        cfg = llama.tiny_config(dtype="float32")
        eng = PagedServingEngine(
            cfg, llama.init_params(cfg, jax.random.key(0))[0], slots=2,
            max_len=16, prefill_chunk=8, block_size=4,
        )
    else:
        eng = _engine(params)
    assert not [k for k in eng.kv_stats() if k.startswith("latent")]
    assert "latent_decode_attention" not in eng.kinds
    assert eng._latent is None
