"""Compile the default TPU paths for a DESCRIBED v5e, without the chip.

libtpu's compiler is installed in the CPU sandbox and compiles for a
topology that is described, not attached: what it refuses here the chip
refuses too (a Mosaic kernel that does not lower, a kernel GSPMD cannot
partition, a program that does not fit 16 GB). Nothing runs — a compile
that passes is not a chip run (``chip_smoke.py`` is that).

Rules this file follows (only ONE process may load libtpu, and xdist
workers all import every test file): the topology is described inside a
module-scoped, non-autouse fixture that skips when it cannot be — never
at import, in a ``skipif``/``parametrize`` or in conftest; everything
compiles in the test's own process; and all such tests live in this one
file. Code keyed on ``jax.default_backend()`` is steered by monkeypatch.
"""

import contextlib
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from dlrover_tpu.models import llama
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.trainer import train_step as ts


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "no libtpu"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _tpu_branches():
    """Take the TPU branches (flash kernel, non-interpret Pallas) and
    keep the persistent cache out: a described-device executable can be
    written to it but never read back."""
    from jax.experimental.compilation_cache import compilation_cache

    patch = pytest.MonkeyPatch()
    patch.setattr(jax, "default_backend", lambda: "tpu")
    patch.setattr(llama, "_ATTN_CACHE", {})
    patch.delenv("DLROVER_TPU_MOE_DISPATCH", raising=False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        patch.undo()
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(autouse=True)
def _as_if_on_tpu():
    with _tpu_branches():
        yield


def _n_kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def _qkv(sharding, b=8, s=2048, h=8, d=128):
    x = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=sharding)
    return x, x, x


def test_flash_attention_forward_compiles(one_chip):
    from dlrover_tpu.ops.pallas_attention import flash_attention

    c = jax.jit(flash_attention).lower(*_qkv(one_chip)).compile()
    assert _n_kernels(c) == 1


def test_flash_attention_backward_compiles(one_chip):
    from dlrover_tpu.ops.pallas_attention import flash_attention

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v).astype(jnp.float32))

    c = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *_qkv(one_chip)
    ).compile()
    assert _n_kernels(c) == 3  # fwd + dq + dk/dv


def test_flash_attention_with_its_own_value_head_compiles(one_chip):
    """Latent attention's shapes (q/k 192 = 128 + 64, v 128, 32 heads,
    8,192 tokens): 192 is no multiple of the lane width, so the kernels
    take the transposed layout with a full-width minor block."""
    from dlrover_tpu.ops.pallas_attention import flash_attention

    def sds(d):
        return jax.ShapeDtypeStruct(
            (1, 8192, 32, d), jnp.bfloat16, sharding=one_chip
        )

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v).astype(jnp.float32))

    c = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        sds(192), sds(192), sds(128)
    ).compile()
    assert _n_kernels(c) == 3


def test_flash_attention_at_256_256_compiles(one_chip):
    """``glm47flash-train-8k``'s shapes (q/k 256 = 192 + 64, v 256, 20
    heads, 8,192 tokens): both head sizes are multiples of the lane
    width, so the forward folds the heads into the minor axis; the
    backward runs at the shape's own 512 x 1024 blocks
    (``BLOCK_TARGETS``), which reach its kernels and no other shape's."""
    from dlrover_tpu.ops import pallas_attention as pa

    def sds(heads, d):
        return jax.ShapeDtypeStruct(
            (1, 8192, heads, d), jnp.bfloat16, sharding=one_chip
        )

    def loss(q, k, v):
        return jnp.sum(pa.flash_attention(q, k, v).astype(jnp.float32))

    grad = lambda: jax.jit(jax.grad(loss, argnums=(0, 1, 2)))  # noqa: E731
    c = grad().lower(sds(20, 256), sds(20, 256), sds(20, 256)).compile()
    assert _n_kernels(c) == 3
    assert pa._block_targets("fwd", 256, 256, 1024) == (1024, 1024)
    assert pa._block_targets("bwd", 256, 256, 512) == (512, 1024)
    assert pa._block_targets("fwd", 128, 128, 1024) == (1024, 1024)
    assert pa._block_targets("bwd", 192, 128, 512) == (512, 512)
    assert pa._block_targets("bwd", 128, 128, 1024) == (1024, 1024)


def test_latent_train_step_compiles_at_the_cells_shape(topo):
    """``glm47flash-train-8k``'s step at published widths and 8,192 + 2
    tokens (1 dense + 1 expert block of its 1 + 4: the scan's body is
    one expert block either way, and the module whole) lowers for the
    described v5e through ``make_train_step``: every block's three flash
    kernels are there ONCE (what a block keeps for its backward is the
    forward kernel's two outputs, so the re-forward runs none), the
    module's sit under ``mtp`` and the stack's do not, and every scope
    the cell's readers book device time to is in the program."""
    from benchmark import common, mtp_scopes, rehearse_glm

    cfg_json = common.load_json("configs", "glm-4.7-flash.json")
    traffic = common.load_json("traffic", "pretrain-mtp-8k.json")
    c = rehearse_glm.lower_step(
        cfg_json, traffic, topo.devices[0], n_periods=1
    ).compile()
    text = c.as_text()
    names = [
        line.split('op_name="')[1].split('"')[0]
        for line in text.splitlines()
        if "tpu_custom_call" in line and 'op_name="' in line
    ]
    booked = [mtp_scopes.scope_of(n) for n in names]
    assert booked.count("mla") == 6 and booked.count("mtp/mla") == 3
    assert booked.count("mtp/experts") > 0 and booked.count("experts") > 0
    every = {
        mtp_scopes.scope_of(m) for m in set(
            line.split('op_name="')[1].split('"')[0]
            for line in text.splitlines() if 'op_name="' in line
        )
    }
    assert {
        "mla", "dense", "router", "experts", "shared", "vocab",
        "mtp/join", "mtp/mla", "mtp/router", "mtp/experts", "mtp/shared",
        "mtp/vocab",
    } <= every
    assert c.memory_analysis().peak_memory_in_bytes < 16e9


def test_kda_scan_kernels_compile_at_the_cells_shape(one_chip):
    """``kimilinear-train-8k``'s scan (32 heads x 8,192 tokens x 128,
    float32): on a TPU ``kda_chunked`` is the two Pallas kernels, and
    both carry the caller's scope into their ``op_name``, the backward
    too, so ``benchmark/hybrid_scopes`` books them under ``kda_scan``."""
    from dlrover_tpu.ops import kda

    assert kda.kda_scan_kind(128, 128) == "pallas"
    x = jax.ShapeDtypeStruct((1, 32, 8192, 128), jnp.float32,
                             sharding=one_chip)
    beta = jax.ShapeDtypeStruct((1, 32, 8192), jnp.float32,
                                sharding=one_chip)

    def pulled(*xs):
        with jax.named_scope("kda_scan"):
            out, pull = jax.vjp(kda.kda_chunked, *xs)
        return out, pull(out)

    c = jax.jit(pulled).lower(x, x, x, x, beta).compile()
    from benchmark import hybrid_scopes

    names = [
        line.split('op_name="')[1].split('"')[0]
        for line in c.as_text().splitlines() if "tpu_custom_call" in line
    ]
    assert [hybrid_scopes.scope_of(n) for n in names] == ["kda_scan"] * 2
    assert sorted(n.split("/")[-2] for n in names) == [
        "kda_scan_bwd", "kda_scan_fwd"
    ]


def _kernel_names(compiled):
    """{scope: [kernel name, ...]} of a program's Pallas calls, by the
    scope ``benchmark/hybrid_scopes`` books each to."""
    from benchmark import hybrid_scopes

    booked = {}
    for line in compiled.as_text().splitlines():
        if "tpu_custom_call" in line and 'op_name="' in line:
            name = line.split('op_name="')[1].split('"')[0]
            booked.setdefault(hybrid_scopes.scope_of(name), []).append(
                name.split("/")[-2]
            )
    return booked


def test_kda_layer_kernels_compile_at_the_cells_shape(one_chip):
    """A KDA layer of ``kimilinear-train-8k`` (1 x 8,192 tokens, 32
    heads x 128) forward and pullback under the cell's remat policy: on
    a TPU the per-token work around the scan is ``ops/kda_tail.py``'s
    kernels, which lower for the described v5e and carry ``kda`` and NOT
    ``kda_scan`` in their ``op_name``, forward and backward, so
    ``benchmark/hybrid_scopes`` books them to the layer and the scan's
    readers keep reading the scan alone."""
    import functools

    from benchmark import common
    from benchmark.runners import train_hybrid
    from dlrover_tpu.models import hybrid

    cfg_json = common.load_json("configs", "kimi-linear-48b-a3b.json")
    traffic = common.load_json("traffic", "pretrain-8k.json")
    cfg = train_hybrid.hybrid_config(cfg_json)
    layer = jax.checkpoint(
        functools.partial(hybrid._kda_apply, cfg),
        policy=hybrid.remat_policy(cfg.remat_keep),
    )

    def pulled(p, h):
        with jax.named_scope("attn"):
            out, pull = jax.vjp(layer, p, h)
        return out, pull(out)

    params = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(
            lambda k: hybrid.MIXERS["kda"].init(cfg, k), jax.random.key(0)
        ),
    )
    h = jax.ShapeDtypeStruct(
        (1, traffic["seq_len"], cfg.embed_dim), cfg.compute_dtype,
        sharding=one_chip,
    )
    booked = _kernel_names(jax.jit(pulled).lower(params, h).compile())
    # q, k, v forward, again in the re-forward, and their pullbacks; the
    # scan's forward is not run again (its output and states are kept).
    assert sorted(booked.pop("kda")) == sorted(
        ["kda_branch_fwd"] * 6 + ["kda_branch_bwd"] * 3
        + ["kda_gate_fwd"] * 2 + ["kda_gate_bwd"]
        + ["kda_out_fwd"] * 2 + ["kda_out_bwd"]
    )
    assert sorted(booked.pop("kda_scan")) == ["kda_scan_bwd", "kda_scan_fwd"]
    assert not booked


@pytest.mark.slow
def test_kimilinear_step_fits_the_chip_with_the_layer_kernels(topo):
    """The cell's whole step, built with those kernels, still peaks
    under the chip's 16 GB (13.94 GB at PR 58, 14.41 at its parent). Out
    of the tier-1 run: the compile alone is ~80 s of a run that has ~150
    to spare, ``benchmark/rehearse_kimi_linear.py`` makes it by hand and
    the cell's ``memory_peak_bytes`` reads the same number on the chip."""
    from benchmark import common, rehearse_kimi_linear

    step = rehearse_kimi_linear.lower_step(
        common.load_json("configs", "kimi-linear-48b-a3b.json"),
        common.load_json("traffic", "pretrain-8k.json"), topo.devices[0],
    ).compile()
    booked = _kernel_names(step)
    assert len(booked["kda"]) == 4 * 15 and len(booked["kda_scan"]) == 4 * 2
    assert step.memory_analysis().peak_memory_in_bytes < 16e9


def test_expert_share_compiles_fwd_bwd(one_chip):
    """``moe_mlp_share`` at the hybrid cell's shape (8 of 256 experts
    held, top-8, 8,192 tokens of width 2,304): both row buffers' grouped
    matmuls, under the ``lax.cond`` that picks one."""
    from dlrover_tpu.models import moe

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(x, router, w_gate, w_up, w_down):
        out, _ = moe.moe_mlp_share(
            x, router, jnp.zeros((256,)), w_gate, w_up, w_down,
            first=0, top_k=8, scaling=2.446,
        )
        return jnp.sum(out.astype(jnp.float32))

    c = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        sds((1, 8192, 2304), jnp.bfloat16), sds((2304, 256)),
        sds((8, 2304, 1024)), sds((8, 2304, 1024)), sds((8, 1024, 2304)),
    ).compile()
    # gmm x2 forward, gmm x2 + tgmm x2 backward, in each of two branches
    assert _n_kernels(c) == 12


# The served expert layer of the two expert serve cells: (tokens, top_k,
# embed, mlp, experts a layer); five layers' experts are one stack.
_SERVED_EXPERT_SHAPES = {
    "xing_decode": (32, 4, 3584, 1024, 64),
    "xing_chunk": (512, 4, 3584, 1024, 64),
    "keye_decode": (16, 8, 2048, 768, 128),
    "keye_chunk": (512, 8, 2048, 768, 128),
}


@pytest.mark.parametrize("shape", sorted(_SERVED_EXPERT_SHAPES))
def test_served_expert_layer_compiles_under_its_weight_blocks(
        one_chip, shape):
    """``moe.routed_experts`` at the serve cells' decode and chunk
    shapes, where a group holds fewer rows than a row tile and
    ``moe._weight_block`` cuts the weights into blocks of megabytes: a
    block over the scoped VMEM limit fails here as it would on the
    chip. The pullback too (``gmm``'s VJP runs ``tgmm`` under the
    forward's tiling, with a float32 accumulator the block's size)."""
    from dlrover_tpu.models import moe

    n, top_k, d, f, e = _SERVED_EXPERT_SHAPES[shape]
    groups = 5 * e
    assert moe._weight_block(n * top_k // e, 128, d, 2 * f, 2) != (
        moe._tile(d), moe._tile(2 * f)
    )

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def layer(x, experts, weights, w_gu, w_down, at):
        out, _ = moe.routed_experts(
            x, experts, weights, w_gu, w_down, e, group_offset=at * e
        )
        return jnp.sum(out.astype(jnp.float32))

    args = (
        sds((1, n, d)), sds((n, top_k), jnp.int32),
        sds((n, top_k), jnp.float32), sds((groups, d, 2 * f)),
        sds((groups, f, d)), sds((), jnp.int32),
    )
    assert _n_kernels(jax.jit(layer).lower(*args).compile()) == 2
    back = jax.jit(jax.grad(layer, argnums=(0, 3, 4))).lower(*args).compile()
    # the first gmm, and both pullbacks (a gmm and a tgmm each)
    assert _n_kernels(back) == 5


def test_served_expert_chunk_compiles_over_expert_aligned_rows(one_chip):
    """``mellum2-serve-mixed-16k``'s prefill chunk (512 tokens x top-8
    over 64 experts of 2,304 x 896, the third of eight layers in the
    stack): ``moe._aligned_rows`` lays its rows out expert-aligned, 4,096
    + 64 x 128 of them, and both grouped matmuls compile over that
    buffer under ``moe._weight_block``'s blocks inside the scoped VMEM
    limit. XLA's own ops make two arrays of the buffer's size (the
    one-hot matmul that lays the rows out; the SiLU product between the
    kernels) and none copies one: no row gather and no fill going in, no
    weighting pass coming out."""
    from dlrover_tpu.models import moe

    n, top_k, d, f, e, layers = 512, 8, 2304, 896, 64, 8
    rows = n * top_k + e * moe.ROW_TILE
    assert moe._aligned_rows(
        n * top_k // e, moe.ROW_TILE, n * top_k, e, n, d, f, 2
    )

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def layer(x, experts, weights, w_gu, w_down, at):
        out, counters = moe.routed_experts(
            x, experts, weights, w_gu, w_down, e, group_offset=at * e,
            interpret=False,
        )
        return out, counters.weight_visits

    text = jax.jit(layer).lower(
        sds((1, n, d)), sds((n, top_k), jnp.int32),
        sds((n, top_k), jnp.float32), sds((layers * e, d, 2 * f)),
        sds((layers * e, f, d)), sds((), jnp.int32),
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    made = [
        line.split(" = ")[1] for line in text[text.index("\nENTRY "):]
        .splitlines()
        if " = " in line and line.split(" = ")[1].startswith(f"bf16[{rows},")
    ]
    # what the main program makes at the buffer's size: the rows laid
    # out, the two matmuls' results, the activation between them
    assert sorted(m.split("{")[0] for m in made) == sorted([
        f"bf16[{rows},{d}]", f"bf16[{rows},{2 * f}]", f"bf16[{rows},{f}]",
        f"bf16[{rows},{d}]",
    ]), made
    assert not [
        m for m in made
        if " copy(" in m or " select(" in m or " gather(" in m
    ]


@pytest.mark.parametrize("dispatch", [None, "fused", "gmm"])
def test_moe_dispatch_compiles_fwd_bwd(one_chip, dispatch):
    """``moe_mlp_dropless`` at the bench's MoE shape (e=8, top-2,
    d=f=1024, 8x2048 tokens) with the default dispatch and with each
    named one. The fused kernels passed every interpret-mode test from
    PR 14 on while the chip's compiler refused them (a vector load from
    SMEM, one-row DMA slices of a tiled memref, the scoped-VMEM
    budget): only a compile shows that."""
    from dlrover_tpu.models import moe

    b, s, d, f, e = 8, 2048, 1024, 1024, 8

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    w_in = sds((e, d, f), jnp.bfloat16)
    args = (
        sds((b, s, d), jnp.bfloat16), sds((d, e), jnp.float32),
        w_in, w_in, sds((e, f, d), jnp.bfloat16),
    )

    def loss(*a):
        out, _ = moe.moe_mlp_dropless(*a, top_k=2, dispatch=dispatch)
        return jnp.sum(out.astype(jnp.float32))

    c = jax.jit(jax.grad(loss, argnums=(0, 2, 3, 4))).lower(*args).compile()
    assert _n_kernels(c) >= 4


def _flagship_step(mesh_config, devices, micro=8, seq=2048):
    """The flagship train step lowered for ``devices`` from shapes only
    (a described device holds no array)."""
    cfg = llama.flagship_config()
    mesh = build_mesh(mesh_config, devices)
    tc = ts.TrainConfig(warmup_steps=10)
    opt = ts.make_optimizer(tc)
    step_fn, specs = ts.make_train_step(cfg, tc, opt, mesh, donate=True)
    shardings = ts.state_shardings(specs, mesh)

    def init():
        params = llama.init_params(cfg, jax.random.key(0))[0]
        return {
            "params": params,
            "opt_state": opt.init(params),
            "step": jnp.zeros((), jnp.int32),
        }

    state = jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        jax.eval_shape(init), shardings,
    )
    tokens = jax.ShapeDtypeStruct(
        (micro, seq + 1), jnp.int32,
        sharding=NamedSharding(mesh, ts.batch_spec()),
    )
    with mesh:
        return step_fn.jitted.lower(state, {"tokens": tokens}).compile()


def test_flagship_step_compiles_for_one_chip(topo):
    c = _flagship_step(MeshConfig(), topo.devices[:1])
    assert _n_kernels(c) == 3
    mem = c.memory_analysis()
    # f32 weights + two Adam moments of 334M params.
    assert 3.5e9 < mem.argument_size_in_bytes < 4.5e9


@pytest.mark.parametrize(
    "mesh_config",
    [MeshConfig(dp=4), MeshConfig(dp=2, tp=2)],
    ids=["dp4", "dp2xtp2"],
)
def test_flagship_step_compiles_for_four_chips(topo, mesh_config):
    """Elastic data-parallel training on real chips: the flash kernel
    must sit in a shard_map island (GSPMD cannot partition Mosaic), and
    the state must be SHARDED — about a quarter per device."""
    c = _flagship_step(mesh_config, topo.devices)
    assert _n_kernels(c) >= 3
    mem = c.memory_analysis()
    assert 0.8e9 < mem.argument_size_in_bytes < 1.3e9


# Widths, engine shape and what the decode and prefill programs must be
# built with (prefill chunks of 256 tokens unless said).
_PAGED_CASES = {
    # ``nemo12b-serve-chat``: GQA 32 / 8, a 2,304-row cache.
    "nemo12b_cell": dict(
        vocab_size=131072, embed_dim=5120, mlp_dim=14336, n_heads=32,
        n_kv_heads=8, head_dim=128, want="paged_kernel",
    ),
    # MHA (Llama-2-7B): a page is 4x the bytes, so a VMEM chunk holds
    # a quarter of the rows; sized in rows it overflowed the VMEM.
    "llama2_7b_mha": dict(
        vocab_size=32000, embed_dim=4096, mlp_dim=11008, n_heads=32,
        n_kv_heads=32, head_dim=128, want="paged_kernel",
    ),
    # Narrow on purpose: the temporaries are held under 32 MB below,
    # and a wide layer's sliced-out ``wqkv`` alone would pass that.
    "kv16_head256": dict(
        vocab_size=32000, embed_dim=512, mlp_dim=2048, n_heads=32,
        n_kv_heads=16, head_dim=256, want="paged_kernel",
    ),
    # ``chip_smoke.py``'s engine: 8 query heads (padded to one bf16
    # tile of 16), 4 slots, a 576-row cache.
    "flagship_short_cache": dict(
        vocab_size=32000, embed_dim=1024, mlp_dim=4096, n_heads=8,
        n_kv_heads=8, head_dim=128, slots=4, max_blocks=36, chunk=64,
        want="paged_kernel",
    ),
    # One page is larger than a VMEM chunk: the kernel cannot hold it.
    "page_larger_than_a_chunk": dict(
        vocab_size=32000, embed_dim=1024, mlp_dim=4096, n_heads=8,
        n_kv_heads=8, head_dim=128, max_blocks=4, block_size=1024,
        want="xla_gather",
    ),
}


def _decode_args(cfg, one_chip):
    """What a decode program starts from, as shapes on the described
    chip: ``arr`` (any shape there), a PRNG key, the decode-ready
    params of ``cfg``."""
    from dlrover_tpu.models import generate as gen_lib

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: arr(x.shape, x.dtype), tree
        )

    key = on_chip(jax.eval_shape(lambda: jax.random.key(0)))
    params = on_chip(jax.eval_shape(
        lambda k: gen_lib.prepare_decode_params(
            cfg, llama.init_params(cfg, k)[0]
        ), key,
    ))
    return arr, key, params


def _paged_programs(case, one_chip):
    """The case's config and paged step programs, and the arguments
    both programs start with, as shapes on the described chip."""
    from dlrover_tpu.serving.kvpool import dense, engine as paged

    spec = dict(_PAGED_CASES[case])
    want = spec.pop("want")
    slots, max_blocks = spec.pop("slots", 16), spec.pop("max_blocks", 144)
    bs, chunk = spec.pop("block_size", 16), spec.pop("chunk", 256)
    cfg = llama.TpuLMConfig(n_layers=2, dtype="bfloat16", **spec)
    num_blocks = slots * max_blocks + 1
    assert dense.pool_attention_kind(cfg, bs, "fp", chunk) == want
    assert dense.pool_attention_kind(cfg, bs, "int8", chunk) == "xla_gather"
    steps = paged._paged_steps(cfg, slots, num_blocks, max_blocks, bs, chunk)
    assert steps.pool_attention == want
    arr, key, params = _decode_args(cfg, one_chip)
    pool = arr(
        (cfg.n_layers, num_blocks, bs, cfg.n_kv_heads, cfg.head_dim),
        jnp.bfloat16,
    )
    shape = dict(slots=slots, max_blocks=max_blocks, bs=bs, chunk=chunk,
                 num_blocks=num_blocks)
    return cfg, steps, want, shape, arr, (pool, pool, params), key


@pytest.mark.parametrize("case", sorted(_PAGED_CASES))
def test_paged_decode_reads_the_pool_in_place(one_chip, case):
    """The paged decode program (2 layers) compiles for the described
    v5e with the attention ``pool_attention_kind`` chose for it —
    nothing falls back after that choice, so what it admits has to
    lower. Built with the pool kernel it has one call in the layer
    body and NO copy of the cache — neither the layer's pool sliced
    out of the stacked arrays nor the gathered ``[slots, max_len]``
    view, which were 153 MB of temporaries and 43 % of the decode
    program's time at ``nemo12b-serve-chat`` (PERF.md §5, PR 25)."""
    cfg, steps, want, shape, arr, lead, key = _paged_programs(
        case, one_chip
    )
    slots, max_blocks = shape["slots"], shape["max_blocks"]
    bs, num_blocks = shape["bs"], shape["num_blocks"]
    i32 = jnp.int32
    # As the engine launches it: the token vector is the previous
    # launch's output and the first token the chunk program's, both
    # device arguments; nothing of the merge is a program of its own.
    c = steps.decode.lower(
        *lead, arr((slots, max_blocks), i32),
        arr((slots,), i32), arr((slots,), i32), arr((slots,), bool),
        arr((slots,), jnp.float32), key, arr((), i32),
        arr((), i32), arr((), i32),
    ).compile()
    text = c.as_text()
    assert "jit_step," in text.splitlines()[0]   # what traces name it
    assert "{0}: (0, {}, may-alias), {1}: (1, {}, may-alias)" in text
    n_params = len(jax.tree_util.tree_leaves(lead)) + 9
    assert f"parameter({n_params - 1})" in text
    assert f"parameter({n_params})" not in text
    if want == "xla_gather":
        assert _n_kernels(c) == 0
        return
    assert _n_kernels(c) == 1  # in the scan's body, once for all layers
    assert "paged_pool_decode_attention" in text
    kv = f"{cfg.n_kv_heads},{cfg.head_dim}]"
    for rows in (num_blocks, num_blocks - 1):   # a layer's pool, a view
        assert f"[{rows},{bs},{kv}" not in text
    assert c.memory_analysis().temp_size_in_bytes < 32e6


@pytest.mark.parametrize("case", sorted(_PAGED_CASES))
def test_paged_prefill_does_only_its_chunks_work(one_chip, case):
    """The paged prefill program (2 layers) compiles for the described
    v5e with the same choice. Built with the chunk kernel it has one
    call in the layer body, NO array with a ``max_len`` axis — the
    gather program carries one slot's ``[layers, 1, max_len]`` view
    through the layer scan, in and out, and scores all of it whatever
    ``start`` is (PERF.md §5, PR 28) — and the pools alias their
    outputs. The flag that turns the head off is a traced argument:
    one program for a prompt's every chunk."""
    import re

    cfg, steps, want, shape, arr, lead, key = _paged_programs(
        case, one_chip
    )
    max_blocks, bs, chunk = shape["max_blocks"], shape["bs"], shape["chunk"]
    i32 = jnp.int32
    c = steps.prefill.lower(
        *lead, arr((1, chunk), i32), arr((max_blocks,), i32),
        arr((), i32), arr((), i32), arr((), jnp.float32), key,
        arr((), i32), arr((), bool),
    ).compile()
    text = c.as_text()
    assert "jit_prefill," in text.splitlines()[0]   # what traces name it
    assert "{0}: (0, {}, may-alias), {1}: (1, {}, may-alias)" in text
    max_len_axes = re.findall(rf"[\[,]{max_blocks * bs}[,\]]", text)
    if want == "xla_gather":
        assert _n_kernels(c) == 0 and max_len_axes
        return
    assert _n_kernels(c) == 1  # in the scan's body, once for all layers
    assert "paged_pool_chunk_attention" in text
    assert not max_len_axes
    # 0.40 GB of temporaries in the gather program at the cell's shape
    # (12 layers); what is left is the layer's sliced-out weights.
    assert c.memory_analysis().temp_size_in_bytes < 0.2e9


def _one_token_step(kind, cfg, slots, max_len):
    """A slab cache's one-token step as the two callers build it: the
    flat ``ServingEngine``'s decode program, and the step ``generate()``
    scans over. Returns the function (both caches donated) and what
    follows the caches and the params among its arguments, as (shape,
    dtype) pairs; ``"key"`` stands for a PRNG key."""
    from dlrover_tpu.models import generate as gen_lib
    from dlrover_tpu.serving import engine as flat

    i32 = jnp.int32
    if kind == "flat_engine":
        step = flat._build_decode_step(cfg, slots, max_len, {"decode": 0})
        rest = [
            ((slots,), i32), ((slots,), i32), ((slots,), bool),
            ((slots,), jnp.float32), "key", ((), i32),
            ((), i32), ((), i32),   # the chunk's first token, its slot
        ]
        return jax.jit(step, donate_argnums=(0, 1)), rest

    def step(k, v, params, lengths, tokens):
        logits, cache = gen_lib._forward_with_cache(
            cfg, params, tokens[:, None], gen_lib.DecodeCache(k, v, lengths)
        )
        return cache.k, cache.v, logits

    return (
        jax.jit(step, donate_argnums=(0, 1)),
        [((slots,), i32), ((slots,), i32)],
    )


@pytest.mark.parametrize("kind", ["flat_engine", "generate"])
def test_slab_decode_step_is_plain_xla_and_one_rolled_loop(one_chip, kind):
    """A one-token step over a slab cache at the flagship width (334 M,
    8 rows x 384 cache rows: the shape its readings were taken at)
    compiles for the described v5e with NO Pallas kernel and ONE loop
    over the layers, the cache written in place. Nothing else can be
    chosen any more: the sequential-grid Pallas kernel took 3.6
    ms/token against 1.3, an unrolled scan 1.47-1.74 against 1.38 with
    100-200 MB/token of cache copies (v5e, BENCH_r05)."""
    cfg = llama.flagship_config(dtype="bfloat16")
    slots, max_len = 8, 384
    arr, key, params = _decode_args(cfg, one_chip)
    cache = arr(
        (cfg.n_layers, slots, max_len, cfg.n_kv_heads, cfg.head_dim),
        jnp.bfloat16,
    )
    step, rest = _one_token_step(kind, cfg, slots, max_len)
    c = step.lower(
        cache, cache, params,
        *(key if a == "key" else arr(*a) for a in rest),
    ).compile()
    text = c.as_text()
    assert _n_kernels(c) == 0
    assert text.count(" while(") == 1
    # Both caches alias their outputs, and no second copy of one is
    # among the temporaries (a side is 100.7 MB; unrolled, the scanned
    # step holds 91.7 MB of them and no loop).
    cache_bytes = 2 * cfg.n_layers * slots * max_len * (
        cfg.n_kv_heads * cfg.head_dim
    )
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * cache_bytes
    assert mem.temp_size_in_bytes < cache_bytes


@pytest.fixture(scope="module")
def keye_cell_programs(topo):
    """``keye-serve-docqa-32k``'s two engine programs (2 of its 5 layers)
    as ``benchmark/rehearse_keye.py`` lowers them, over a BARE index-key
    array, COMPILED for the described v5e once for the two tests that
    read them (the same compile, 30-40 s of tier-1: ROADMAP D16)."""
    from benchmark import common, rehearse_keye

    with _tpu_branches():   # (a module's fixture is made before a test's)
        programs = rehearse_keye.lower_engine_programs(
            common.load_json("configs", "keye-vl2-30b-a3b.json"),
            topo.devices[0], n_layers=2,
        )
        return {
            name: programs[name].compile()
            for name in ("jit_step", "jit_prefill")
        }


def test_sparse_expert_serving_programs_compile_at_the_cells_shape(
    keye_cell_programs,
):
    """``keye-serve-docqa-32k``'s decode step and prefill chunk (2 of
    its 5 layers: the scan's body is one layer either way) at published
    widths, 16 slots x 33,792 rows over the cell's 5,200-block pool,
    lower for the described v5e under their trace names; the three pool
    arrays alias their outputs, the expert matmuls are the grouped
    kernel (two calls a layer body, no dense [tokens, experts, ...]
    product), and weights + pool + temporaries stay inside the chip."""
    from benchmark import sparse_scopes, trace_reduce

    aliased = (
        "{0}: (0, {}, may-alias), {1}: (1, {}, may-alias), "
        "{2}: (2, {}, may-alias)"
    )
    for name, c in keye_cell_programs.items():
        text = c.as_text()
        assert name + "," in text.splitlines()[0]
        assert aliased in text
        assert 2 <= _n_kernels(c) <= 4           # gmm: gate|up, down
        # the scopes the cell's readers book device time to (in the
        # chunk program select and sparse sit inside the query blocks'
        # loop and its cond: ``attn/while/body/.../sparse``)
        booked = {
            sparse_scopes.scope_of(op_name)
            for op_name in trace_reduce.scopes_from_hlo(text).values()
        }
        assert booked >= {"index", "select", "sparse", "router", "experts"}
        m = c.memory_analysis()
        # 2 layers of weights + the 2-layer pool + temporaries: the 5
        # layers add 3 x (1.25 + 0.65) GB of arguments, no temporaries.
        assert m.temp_size_in_bytes < 2.0e9
        assert m.argument_size_in_bytes + m.temp_size_in_bytes < 8e9
        # The chunk attends under its selection in the Pallas kernel
        # over the pool in place (PR 34): no gathered [max_len] view of
        # K or V, no float32 logits a KV head and query block. The
        # decode step gathers its selected rows as before.
        view, logits = "bf16[33792,4,128]", "f32[128,8,33792]"
        if name == "jit_prefill":
            assert "paged_pool_sparse_chunk_attention" in text
            assert view not in text and logits not in text
        else:
            assert "paged_pool_sparse_chunk_attention" not in text


def test_latent_serving_programs_compile_at_the_cells_shape(topo):
    """``xing-serve-sessions-16k``'s decode step and prefill chunk (1
    dense + 1 expert layer of its 1 + 5: the scan's body is one expert
    layer either way) at published widths, 32 slots x 17,408 rows over
    the cell's 9,216-block pool, lower for the described v5e under their
    trace names from what the engine's constructor builds
    (``kvpool.engine._paged_steps``); the ONE pool array aliases its
    output and is never copied (two 576-wide rows to a 1,152-lane device
    row: the bare array is re-tiled whole, 4 GB twice a step), the expert
    matmuls are the grouped kernel, every scope the cell's readers book
    device time to is there, and no ``[heads, chunk, max_len]`` logits
    exist in the chunk program. The decode step reads its rows by the
    Pallas kernel over the pool in place (PR 40), booked to ``attn/mla``
    where ``latent_attn_ms_per_step`` reads it: no gathered ``[slots,
    max_len]`` view and no float32 scores of it are left in the step.
    The chunk walks its queries in tiles of ``latent.CHUNK_QUERY_ROWS``
    under a trip count taken from ``n_valid`` (PR 44): its largest
    scores are a TILE's against a block of prefix rows, no whole
    chunk's, and it is still ``jax.numpy``, no latent kernel. The
    check's probe of the decode step lowers with the kernel's scores as
    its second output."""
    from benchmark import common, latent_scopes, trace_reduce
    from benchmark import rehearse_xing
    from dlrover_tpu.serving.kvpool import latent

    cfg_json = common.load_json("configs", "xing4-29b-a4b.json")
    programs = rehearse_xing.lower_engine_programs(
        cfg_json, topo.devices[0], probes=True, n_layers=2
    )
    pool = "bf16[2,9216,32,1152]"
    kernel = "paged_latent_decode_attention"
    for name in ("jit_step", "jit_prefill"):
        c = programs[name].compile()
        text = c.as_text()
        assert name + "," in text.splitlines()[0]
        assert "{0}: (0, {}, may-alias)" in text
        scopes = trace_reduce.scopes_from_hlo(text)
        if name == "jit_step":
            # gmm: gate|up, down; the latent kernel in the dense layer
            # and in the scan's body
            assert 4 <= _n_kernels(c) <= 6
            calls = [v for k, v in scopes.items() if k.startswith(kernel)]
            assert len(calls) == 2 and all(
                latent_scopes.scope_of(op_name) == "mla" for op_name in calls
            ), calls
            assert "bf16[32,8704,1152]" not in text    # the gathered view
            assert "f32[32,32,17408]" not in text      # its scores
        else:
            assert 2 <= _n_kernels(c) <= 4           # gmm: gate|up, down
            assert kernel not in text
        made = [
            line for line in text.splitlines()
            if f"= {pool}" in line and " parameter(" not in line
            and "get-tuple-element" not in line and "bitcast" not in line
        ]
        # the landing scatter (alone or fused), in place: never a copy
        assert made and not any(
            " copy(" in line or " transpose(" in line for line in made
        ), made
        booked = {latent_scopes.scope_of(v) for v in scopes.values()}
        assert booked >= {"mla", "mhc", "router", "experts", "shared",
                          "dense"}
        m = c.memory_analysis()
        # 2 layers of weights (5.4 GB) + the 2-layer pool (1.4 GB) +
        # temporaries: the other 4 expert layers add 4 x (1.49 + 0.68)
        # GB of arguments and no temporaries. The step's were 0.70 GB
        # of view and scores before the kernel; the chunk's 0.24 GB
        # (134 MB of a whole chunk's scores against a block among
        # them) before its queries went in tiles, 0.118 GB since.
        assert m.temp_size_in_bytes < (
            0.1e9 if name == "jit_step" else 0.13e9
        )
        assert m.argument_size_in_bytes + m.temp_size_in_bytes < 8.5e9
        if name == "jit_prefill":
            tile = latent.CHUNK_QUERY_ROWS
            assert tile < 512 and not 512 % tile
            assert "f32[32,512,17408]" not in text
            assert "f32[32,512,2048]" not in text    # a whole chunk's
            # a tile's queries against a block of prefix rows
            assert f"f32[32,{tile},2048]" in text
    text = programs["probe_decode0"].compile().as_text()
    assert kernel in text
    # the kernel's own raw scores: 64 query rows against a slot's 8,704
    # device rows, to whole tiles
    from dlrover_tpu.ops import latent_decode_attention as lda

    rows = -(-8704 // lda.TILE_ROWS) * lda.TILE_ROWS
    assert f"f32[32,64,{rows}]" in text


def test_conv_serving_programs_compile_at_the_cells_shape(topo):
    """``lfm2-serve-sessions-8k``'s decode step and prefill chunk (a
    dense convolution layer, then attention, convolution and attention
    layers over experts, of its 9) at published widths, 32 slots x 9,216 rows
    over the cell's 5,120-block pool, lower for the described v5e under
    their trace names from what the engine's constructor builds
    (``kvpool.engine._paged_steps``): all four arrays (K, V, the slots'
    state, its snapshots) alias their outputs; the K/V pool, held flat,
    compiles to its LOGICAL bytes and is never copied or re-laid (a
    landing scatter with the layer as a window re-lays it whole, twice a
    step); no ``[..., 8, 64]`` view of the gathered rows exists (the
    device pads a 64-wide head to 128 lanes: attention reads lane rows,
    ``conv.lane_pack``); the expert matmuls are the grouped kernel; and
    every scope the cell's readers book device time to is there. The
    decode step reads its rows by the Pallas kernel over the flat pools
    in place (PR 49), once an attention layer, booked to ``attn/gqa``
    where ``gqa64_attn_ms_per_step`` reads it: no gathered ``[slots,
    max_len]`` view is left in the step, and its temporaries, 0.62 GB of
    views and scores before, are under 0.1 GB. The prefill chunk reads
    its prefix by the chunk kernel over the same pools in place (PR 50),
    once an attention layer under ``attn/gqa`` of ``jit_prefill``, where
    ``gqa64_chunk_attn_ms_per_chunk`` reads it: no gathered block of the
    prefix and no float32 scores of 512 rows against one are left in the
    layer body, and its temporaries, 0.64 GB before, are under 0.2 GB.
    ``pool_attention`` goes on naming the definition."""
    from benchmark import common, conv_scopes, trace_reduce
    from benchmark import rehearse_lfm2
    from dlrover_tpu.serving.kvpool import conv

    cfg_json = common.load_json("configs", "lfm2-24b-a2b.json")
    programs, logical = rehearse_lfm2.lower_engine_programs(
        cfg_json, topo.devices[0], probes=False,
        layer_types=("conv", "full_attention", "conv", "full_attention"),
        n_dense=1,
    )
    pool = "bf16[2,5120,64,512]"
    kernels = {"jit_step": "paged_flat_decode_attention",
               "jit_prefill": "paged_flat_chunk_attention"}
    assert logical["k_rows"] == 2 * 5120 * 64 * 512 * 2
    for name in ("jit_step", "jit_prefill"):
        c = programs[name].compile()
        text = c.as_text()
        assert name + "," in text.splitlines()[0]
        for i in range(4):
            assert f"{{{i}}}: ({i}, {{}}, may-alias)" in text
        scopes = trace_reduce.scopes_from_hlo(text)
        calls = [
            v for k, v in scopes.items() if k.startswith(kernels[name])
        ]
        # gmm: gate|up, down x 3, and the two attention layers'
        assert 8 <= _n_kernels(c) <= 14
        assert len(calls) == 2 and all(
            conv_scopes.scope_of(op_name) == "gqa" for op_name in calls
        ), calls
        assert not [k for k in kernels.values() if k != kernels[name]
                    and k in text]
        if name == "jit_step":
            assert "bf16[32,9216,512]" not in text     # the gathered view
            assert "f32[32,4,8,9216]" not in text      # its scores
        else:
            assert "bf16[32,64,512]" not in text       # a gathered block
            assert "bf16[2048,512]" not in text
            assert "f32[32,512,2048]" not in text      # its scores
            assert "f32[32,512,512]" not in text       # the chunk's own
        made = [
            line for line in text.splitlines()
            if f"= {pool}" in line and " parameter(" not in line
            and "get-tuple-element" not in line and "bitcast" not in line
        ]
        # the landing scatters (alone or fused), in place: never a copy
        assert made and not any(
            " copy(" in line or " transpose(" in line for line in made
        ), made
        assert "bf16[32,9216,8,64]" not in text    # heads split out
        booked = {conv_scopes.scope_of(v) for v in scopes.values()}
        assert booked >= {"conv", "gqa", "router", "experts", "dense"}
        if name == "jit_prefill":
            assert "snapshot" in booked
            assert "f32[32,512,9216]" not in text   # no whole-view scores
        m = c.memory_analysis()
        # the pool's two arrays at their logical bytes among the
        # arguments (a padded minor dimension would double them)
        assert m.alias_size_in_bytes < 2 * logical["k_rows"] + 0.31e9
        assert m.temp_size_in_bytes < (
            0.1e9 if name == "jit_step" else 0.2e9
        )
    from benchmark.runners import serve_conv

    cfg = serve_conv.conv_config(cfg_json)
    assert conv.lane_pack(cfg) == 2
    assert conv.POOL_ATTENTION == "conv_gathered_view"
    assert conv.kinds(cfg, cfg.compute_dtype, 64, 512, 32, 144) == {
        "conv_decode_attention": "pool_kernel",
        "conv_chunk_attention": "pool_kernel",
    }


# What ``conv.decode_attention_kind`` sees -> what it must answer;
# unnamed: a bf16 pool of 64-token pages of 512-wide flat rows (8 KV
# heads of 64, two to a lane row) under 32 query heads, 32 slots x 144
# pages (the cell's shape), on a TPU.
_CONV_KIND_CASES = {
    "the_cells_shape": ({}, "pool_kernel"),
    # 128-wide heads, one to a lane row, 16-row pages: 64 pages a chunk
    "a_head_to_a_lane_row": (
        dict(head_dim=128, n_kv_heads=4, n_heads=16, block_size=16,
             max_blocks=576),
        "pool_kernel",
    ),
    "off_the_chip": (dict(on_tpu=False), "gathered_view"),
    "a_float32_pool": (dict(dtype="float32"), "gathered_view"),
    # 3 KV heads of 64: 192 lanes a row, and one head to a 64-lane row
    "a_width_that_is_not_whole_lane_rows": (
        dict(n_kv_heads=3, n_heads=12), "gathered_view"
    ),
    # 8 rows of bf16 are half a (16, 128) tile
    "a_block_too_small_to_tile": (dict(block_size=8), "gathered_view"),
    # a 2 MB page for a 1 MB chunk
    "a_page_past_the_chunk": (dict(block_size=2048, max_blocks=8),
                              "gathered_view"),
    # 128 slots x 4,096 pages: 2 MB of tables for 1 MB of scalar memory
    "tables_past_the_scalar_memory": (
        dict(slots=128, max_blocks=4096), "gathered_view"
    ),
}


def _conv_kind_case(seen, monkeypatch):
    """What a case of the two tables above and below sees: the platform
    probe patched, and ``(config, block_size, max_blocks)``."""
    from dlrover_tpu.models import conv_lm
    from dlrover_tpu.serving.kvpool import families

    on_tpu = seen.get("on_tpu", True)
    monkeypatch.setattr(families, "_on_tpu", lambda: on_tpu)
    cfg = conv_lm.tiny_config(
        n_heads=seen.get("n_heads", 32), n_kv_heads=seen.get("n_kv_heads", 8),
        head_dim=seen.get("head_dim", 64), dtype=seen.get("dtype", "bfloat16"),
    )
    return cfg, seen.get("block_size", 64), seen.get("max_blocks", 144)


@pytest.mark.parametrize("case", sorted(_CONV_KIND_CASES))
def test_conv_decode_attention_kind_admits_only_what_compiles(
    case, one_chip, monkeypatch,
):
    """What reads the convolution / attention model's decode rows is
    chosen by what the code can see, and nothing falls back after the
    choice: where the answer is ``pool_kernel`` the kernel compiles for
    the described v5e at that shape (the pools read in place: no copy of
    either), and another platform, a float32 pool or a shape outside the
    kernel's tiling, VMEM chunk or scalar memory answers
    ``gathered_view``."""
    from dlrover_tpu.ops import flat_decode_attention as fda
    from dlrover_tpu.serving.kvpool import conv

    seen, want = _CONV_KIND_CASES[case]
    cfg, bs, mb = _conv_kind_case(seen, monkeypatch)
    slots = seen.get("slots", 32)
    assert conv.decode_attention_kind(
        cfg, cfg.compute_dtype, bs, mb, slots
    ) == want
    if want != "pool_kernel":
        return
    n_layers, nb = 2, 2 * mb + 1
    arr = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip
    )
    bf, i32 = jnp.bfloat16, jnp.int32
    pack = conv.lane_pack(cfg)
    placed = (slots, cfg.n_kv_heads // pack,
              pack * cfg.n_heads // cfg.n_kv_heads, 128)
    c = jax.jit(
        lambda q, k_own, v_own, k, v, *a: (fda.pool_flat_decode_attention(
            q, k_own, v_own, k, v, *a, scale=0.125
        ), k, v),
        donate_argnums=(3, 4),
    ).lower(
        arr(placed, bf), arr(placed[:2] + (128,), bf),
        arr(placed[:2] + (128,), bf),
        arr((n_layers, nb, bs, cfg.kv_width), bf),
        arr((n_layers, nb, bs, cfg.kv_width), bf),
        arr((), i32), arr((slots, mb), i32), arr((slots,), i32),
        arr((slots,), bool),
    ).compile()
    text = c.as_text()
    assert _n_kernels(c) == 1
    assert "paged_flat_decode_attention" in text
    # the pools go in and out untouched
    assert f"bf16[{n_layers},{nb}," not in "".join(
        line for line in text.splitlines() if " copy(" in line
    )
    assert c.memory_analysis().temp_size_in_bytes < 16e6


# What ``conv.chunk_attention_kind`` sees -> what it must answer;
# unnamed: the same pool and heads, one slot's table of 144 pages under a
# chunk of 512 tokens, on a TPU.
_CONV_CHUNK_KIND_CASES = {
    "the_cells_shape": ({}, "pool_kernel"),
    # 128-wide heads, one to a lane row, 16-row pages: 4 placed queries
    "a_head_to_a_lane_row": (
        dict(head_dim=128, n_kv_heads=4, n_heads=16, block_size=16,
             max_blocks=576),
        "pool_kernel",
    ),
    # 192 tokens are no whole tiles of 128: one tile of 192
    "a_chunk_that_is_one_odd_tile": (dict(chunk=192), "pool_kernel"),
    "off_the_chip": (dict(on_tpu=False), "gathered_view"),
    "a_float32_pool": (dict(dtype="float32"), "gathered_view"),
    "a_width_that_is_not_whole_lane_rows": (
        dict(n_kv_heads=3, n_heads=12), "gathered_view"
    ),
    "a_block_too_small_to_tile": (dict(block_size=8), "gathered_view"),
    # a 2 MB page for a 512 KB chunk
    "a_page_past_the_chunk": (dict(block_size=2048, max_blocks=8),
                              "gathered_view"),
    # 8 tokens are half a (16, 128) tile of the chunk's own rows
    "a_chunk_too_small_to_tile": (dict(chunk=8), "gathered_view"),
    # 2,112 tokens as ONE tile: 16,896 query rows of scores
    "an_odd_tile_past_the_vmem": (dict(chunk=2112), "gathered_view"),
    # 262,144 pages: 1 MB of table for the scalar memory
    "a_table_past_the_scalar_memory": (
        dict(max_blocks=262144), "gathered_view"
    ),
}


@pytest.mark.parametrize("case", sorted(_CONV_CHUNK_KIND_CASES))
def test_conv_chunk_attention_kind_admits_only_what_compiles(
    case, one_chip, monkeypatch,
):
    """What reads the convolution / attention model's prefix under a
    prefill chunk is chosen by what the code can see, and nothing falls
    back after the choice: where the answer is ``pool_kernel`` the chunk
    kernel compiles for the described v5e at that shape (the pools read
    in place: no copy of either), and another platform, a float32 pool
    or a shape outside the kernel's tiling, VMEM or scalar memory
    answers ``gathered_view``."""
    from dlrover_tpu.ops import flat_decode_attention as fda
    from dlrover_tpu.serving.kvpool import conv

    seen, want = _CONV_CHUNK_KIND_CASES[case]
    cfg, bs, mb = _conv_kind_case(seen, monkeypatch)
    chunk = seen.get("chunk", 512)
    assert conv.chunk_attention_kind(
        cfg, cfg.compute_dtype, bs, mb, chunk
    ) == want
    if want != "pool_kernel":
        return
    n_layers, nb = 2, 2 * mb + 1
    arr = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip
    )
    bf, i32 = jnp.bfloat16, jnp.int32
    pack = conv.lane_pack(cfg)
    placed = (chunk, cfg.n_kv_heads // pack,
              pack * cfg.n_heads // cfg.n_kv_heads, 128)
    c = jax.jit(
        lambda q, k_own, v_own, k, v, *a: (fda.pool_flat_chunk_attention(
            q, k_own, v_own, k, v, *a, scale=0.125,
            tile=conv.chunk_token_tile(chunk),
        ), k, v),
        donate_argnums=(3, 4),
    ).lower(
        arr(placed, bf), arr((chunk, cfg.kv_width), bf),
        arr((chunk, cfg.kv_width), bf),
        arr((n_layers, nb, bs, cfg.kv_width), bf),
        arr((n_layers, nb, bs, cfg.kv_width), bf),
        arr((), i32), arr((mb,), i32), arr((), i32), arr((), i32),
    ).compile()
    text = c.as_text()
    assert _n_kernels(c) == 1
    assert "paged_flat_chunk_attention" in text
    # the pools go in and out untouched
    assert f"bf16[{n_layers},{nb}," not in "".join(
        line for line in text.splitlines() if " copy(" in line
    )
    assert c.memory_analysis().temp_size_in_bytes < 16e6


# What ``latent.decode_attention_kind`` sees -> what it must answer;
# unnamed: a bf16 pool of 64-token pages, 576-wide rows (two to a
# 1,152-lane device row) under 32 heads, 32 slots x 272 pages (the
# cell's shape), on a TPU.
_LATENT_KIND_CASES = {
    "the_cells_shape": ({}, "pool_kernel"),
    # one token a 640-lane row, 128-row pages: four pages a tile
    "a_width_of_whole_lane_rows": (
        dict(kv_lora_rank=512, rope=128, block_size=128, max_blocks=64),
        "pool_kernel",
    ),
    "off_the_chip": (dict(on_tpu=False), "gathered_view"),
    "a_float32_pool": (dict(dtype="float32"), "gathered_view"),
    # 520 lanes a token: neither one nor two are whole 128-lane blocks
    "a_width_that_does_not_pack": (
        dict(kv_lora_rank=512, rope=8), "gathered_view"
    ),
    # 16 tokens are 8 device rows: half a (16, 128) tile a page
    "a_block_too_small_to_tile": (dict(block_size=16), "gathered_view"),
    # 128 slots x 4,096 pages: 2 MB of tables for 1 MB of scalar memory
    "tables_past_the_scalar_memory": (
        dict(slots=128, max_blocks=4096), "gathered_view"
    ),
}


@pytest.mark.parametrize("case", sorted(_LATENT_KIND_CASES))
def test_latent_decode_attention_kind_admits_only_what_compiles(
    case, one_chip, monkeypatch,
):
    """What reads the latent decode step's rows is chosen by what the
    code can see, and nothing falls back after the choice: where the
    answer is ``pool_kernel`` the kernel compiles for the described v5e
    at that shape (the pool read in place: no copy of it), and another
    platform, a float32 pool or a shape outside the kernel's tiling or
    scalar memory answers ``gathered_view``."""
    from dlrover_tpu.models import latent_lm
    from dlrover_tpu.ops import latent_decode_attention as lda
    from dlrover_tpu.serving.kvpool import families, latent, layout

    seen, want = _LATENT_KIND_CASES[case]
    on_tpu = seen.get("on_tpu", True)
    monkeypatch.setattr(families, "_on_tpu", lambda: on_tpu)
    bs, mb = seen.get("block_size", 64), seen.get("max_blocks", 272)
    slots = seen.get("slots", 32)
    cfg = latent_lm.tiny_config(
        n_heads=32, kv_lora_rank=seen.get("kv_lora_rank", 512),
        qk_rope_dim=seen.get("rope", 64), dtype=seen.get("dtype", "bfloat16"),
    )
    assert latent.decode_attention_kind(
        cfg, cfg.compute_dtype, bs, mb, slots
    ) == want
    if want != "pool_kernel":
        return
    n_layers, nb = 2, 2 * mb + 1
    (a,) = layout.pool_arrays(cfg)
    rows = jax.eval_shape(lambda: layout.fresh(a, n_layers, nb, bs)).rows
    arr = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip
    )
    bf, i32 = jnp.bfloat16, jnp.int32
    c = jax.jit(
        lambda q, own, pool, *a: (lda.pool_latent_decode_attention(
            q, own, pool, *a, rank=cfg.kv_lora_rank, scale=0.1
        ), pool),
        donate_argnums=(2,),
    ).lower(
        arr((slots, 32, cfg.cache_width), bf), arr((slots, cfg.cache_width), bf),
        arr(rows.shape, bf), arr((), i32), arr((slots, mb), i32),
        arr((slots,), i32),
    ).compile()
    text = c.as_text()
    assert _n_kernels(c) == 1
    assert "paged_latent_decode_attention" in text
    # the pool goes in and out untouched
    assert f"bf16[{n_layers},{nb}," not in "".join(
        line for line in text.splitlines() if " copy(" in line
    )
    assert c.memory_analysis().temp_size_in_bytes < 16e6


@pytest.mark.parametrize("pool", ["the_engines_pool", "a_bare_array"])
def test_sparse_serving_programs_do_not_copy_the_index_key_pool(
    topo, pool, request,
):
    """``keye-serve-docqa-32k``'s decode step and prefill chunk over the
    index-key pool AS THE ENGINE BUILDS IT (``IndexKeyPool.zeros``: two
    64-wide keys to a 128-lane row, ``[layers, 5200, 32, 128]``) hold no
    ``copy`` of any pool array: the block gather, the scores and the
    landing scatter take the rows as stored. Over a BARE ``[layers,
    5200, 64, 64]`` array (one key a row: what the engine held before,
    and what ``benchmark/rehearse_keye.py`` lowers) the same two
    programs re-tile the index pool, whose device layout has the block
    axis minor-most: three 0.21 GB copies a call at 5 layers (PERF.md
    §6, PR 36). K and V are in place either way."""
    from benchmark import common
    from benchmark.runners import serve_sparse
    from dlrover_tpu.models import generate as gen_lib, sparse_lm
    from dlrover_tpu.serving.kvpool import engine as paged
    from dlrover_tpu.serving.kvpool.index_pool import IndexKeyPool

    cfg_json = common.load_json("configs", "keye-vl2-30b-a3b.json")
    cfg = serve_sparse.sparse_config(cfg_json, n_layers=2)
    eng = cfg_json["serve_engine"]
    slots, bs, chunk = eng["slots"], eng["block_size"], eng["prefill_chunk"]
    mb, nb = eng["max_len"] // bs, eng["num_blocks"]
    here = SingleDeviceSharding(topo.devices[0])
    on_chip = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=here), tree
    )
    arr = lambda shape, dt: on_chip(  # noqa: E731
        jax.ShapeDtypeStruct(shape, dt)
    )
    key = on_chip(jax.eval_shape(lambda: jax.random.key(0)))
    params = on_chip(jax.eval_shape(
        lambda k: gen_lib.prepare_decode_params(
            cfg, sparse_lm.init_params(cfg, k, dtype=cfg.compute_dtype)
        ), key,
    ))
    cdt, L = cfg.compute_dtype, cfg.n_layers
    kv = arr((L, nb, bs, cfg.n_kv_heads, cfg.head_dim), cdt)
    ki = on_chip(jax.eval_shape(
        lambda: IndexKeyPool.zeros(L, nb, bs, cfg.index_dim, cdt)
    ))
    assert ki.pack == 2 and ki.rows.shape == (L, nb, bs // 2, 128)
    steps = paged._paged_steps(cfg, slots, nb, mb, bs, chunk)
    i32, f32 = jnp.int32, jnp.float32
    programs = request.getfixturevalue(
        "keye_cell_programs"    # (the bare array: rehearse_keye.py's)
    ) if pool == "a_bare_array" else {
        "jit_step": steps.decode.lower(
            kv, kv, ki, params, arr((slots, mb), i32), arr((slots,), i32),
            arr((slots,), i32), arr((slots,), bool), arr((slots,), f32),
            key, arr((), i32), arr((), i32), arr((), i32),
        ).compile(),
        "jit_prefill": steps.prefill.lower(
            kv, kv, ki, params, arr((1, chunk), i32), arr((mb,), i32),
            arr((), i32), arr((), i32), arr((), f32), key, arr((), i32),
            arr((), bool),
        ).compile(),
    }
    for name, c in programs.items():
        text = c.as_text()
        assert name + "," in text.splitlines()[0]
        copies = [
            line.split(" copy(")[0].split("=")[-1].strip()
            for line in text.splitlines() if " copy(" in line
        ]
        of_a_pool = [c_ for c_ in copies if c_.startswith(f"bf16[{L},{nb},")]
        if pool == "the_engines_pool":
            assert of_a_pool == [], (name, of_a_pool)
            assert c.memory_analysis().temp_size_in_bytes < 1.2e9
        else:
            assert len(of_a_pool) >= 2, (name, copies)
            assert all(f"bf16[{L},{nb},{bs},{cfg.index_dim}]" in c_
                       for c_ in of_a_pool), of_a_pool


# What ``sparse_chunk_attention_kind`` sees -> what it must answer;
# unnamed: bf16, 64-row pages of 4 KV heads x 128 under 32 query heads,
# a 512-token chunk, 528 pages a slot (the cell's shape), on a TPU.
_SPARSE_KIND_CASES = {
    "the_cells_shape": ({}, "chunk_kernel"),
    "eight_kv_heads_16_row_pages": (
        dict(kv_heads=8, block_size=16, max_blocks=144, chunk=256),
        "chunk_kernel",
    ),
    "off_the_chip": (dict(on_tpu=False), "masked_attention"),
    "a_float32_pool": (dict(dtype="float32"), "masked_attention"),
    # a token tile of 64 tokens is no whole lane block of 128
    "a_chunk_of_half_a_lane_block": (dict(chunk=64), "masked_attention"),
    # 12 KV heads are no whole tile of the pool's layout
    "twelve_kv_heads": (dict(kv_heads=12, heads=48), "masked_attention"),
    # a quarter of a million rows a slot: the selection's block alone
    # is 2 x 33 MB of VMEM
    "a_slot_of_262k_rows": (dict(max_blocks=4096), "masked_attention"),
}


@pytest.mark.parametrize("case", sorted(_SPARSE_KIND_CASES))
def test_sparse_chunk_attention_kind_admits_only_what_compiles(
    case, one_chip, monkeypatch,
):
    """The sparse chunk's attention is chosen by what the code can see,
    and nothing falls back after the choice: where the answer is
    ``chunk_kernel`` the kernel compiles for the described v5e at that
    shape (the pool read in place: no copy of it), and a float32 pool,
    another platform or a shape outside the kernel's tiling or VMEM
    answers ``masked_attention``."""
    from dlrover_tpu.models import sparse_lm
    from dlrover_tpu.ops import decode_attention as da
    from dlrover_tpu.serving.kvpool import families, sparse

    seen, want = _SPARSE_KIND_CASES[case]
    seen = dict(seen)
    on_tpu = seen.pop("on_tpu", True)
    monkeypatch.setattr(families, "_on_tpu", lambda: on_tpu)
    h, kh = seen.get("heads", 32), seen.get("kv_heads", 4)
    bs, mb = seen.get("block_size", 64), seen.get("max_blocks", 528)
    t, dtype = seen.get("chunk", 512), seen.get("dtype", "bfloat16")
    cfg = sparse_lm.tiny_config(
        n_heads=h, n_kv_heads=kh, head_dim=128, dtype=dtype
    )
    assert sparse.chunk_attention_kind(
        cfg, cfg.compute_dtype, bs, t, mb
    ) == want
    if want != "chunk_kernel":
        return
    d, n_layers, nb = 128, 2, mb + 1
    arr = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip
    )
    bf, i32 = jnp.bfloat16, jnp.int32
    pool = arr((n_layers, nb, bs, kh, d), bf)
    c = jax.jit(
        lambda *a: (da.sparse_chunk_attention(*a), a[3], a[4]),
        donate_argnums=(3, 4),
    ).lower(
        arr((t, h, d), bf), arr((t, kh, d), bf), arr((t, kh, d), bf),
        pool, pool, arr((), i32), arr((mb,), i32), arr((), i32),
        arr((t, mb * bs), bool), arr((), i32),
    ).compile()
    text = c.as_text()
    assert _n_kernels(c) == 1
    assert "paged_pool_sparse_chunk_attention" in text
    # the pools go in and out untouched: the collapse of [block_size,
    # kv_heads] into rows is a bitcast of their layout, not a copy
    assert f"bf16[{n_layers},{nb}," not in "".join(
        line for line in text.splitlines() if " copy(" in line
    )
    assert c.memory_analysis().temp_size_in_bytes < 64e6


def test_window_serving_programs_compile_at_the_cells_shape(topo):
    """``mellum2-serve-mixed-16k``'s decode step and prefill chunk (one
    period of its 8 layers: three sliding-window layers and a full one,
    over experts) at published widths, 32 slots x 16,896 rows over BOTH
    groups' pools (9,216 blocks of 1 full layer, 1,024 of 3 window
    layers), lower for the described v5e under their trace names from
    what the engine's constructor builds (``kvpool.engine.
    _grouped_steps``): all four pool arrays alias their outputs and none
    is copied or re-laid (whole-block landing windows re-lay a pool of 4
    KV heads, there and back: the chunk lands a row a token); the full
    layer reads its rows by the accepted pool kernels and every window
    layer by ``ops/window_attention.py``'s, each booked to its own scope
    (``attn/full``, ``attn/window``), so no ``[slots, max_len]`` view of
    a window layer (nor of a full one) exists; the expert matmuls are the
    grouped kernel; and every scope the cell's readers book device time
    to is there."""
    from benchmark import common, rehearse_mellum2, trace_reduce
    from benchmark import window_scopes
    from dlrover_tpu.serving.kvpool import window

    cfg_json = common.load_json("configs", "mellum2-12b-a2.5b.json")
    types = cfg_json["layer_types"][:4]
    programs, logical = rehearse_mellum2.lower_engine_programs(
        cfg_json, topo.devices[0], probes=False, layer_types=types,
    )
    assert logical == {
        "k": 1 * 9216 * 64 * 512 * 2, "v": 1 * 9216 * 64 * 512 * 2,
        "k_window": 3 * 1024 * 64 * 512 * 2,
        "v_window": 3 * 1024 * 64 * 512 * 2,
    }
    kernels = {
        "jit_step": {"full": "paged_pool_decode_attention",
                     "window": "paged_window_decode_attention"},
        "jit_prefill": {"full": "paged_pool_chunk_attention",
                        "window": "paged_window_chunk_attention"},
    }
    for name in ("jit_step", "jit_prefill"):
        c = programs[name].compile()
        text = c.as_text()
        assert name + "," in text.splitlines()[0]
        for i in range(4):
            assert f"{{{i}}}: ({i}, {{}}, may-alias)" in text
        scopes = trace_reduce.scopes_from_hlo(text)
        for scope, kernel in kernels[name].items():
            calls = [v for k, v in scopes.items() if k.startswith(kernel)]
            assert len(calls) == (1 if scope == "full" else 3), (kernel, calls)
            assert all(
                window_scopes.scope_of(op_name) == scope for op_name in calls
            ), calls
        # the pools go in and out untouched
        copies = "".join(
            line for line in text.splitlines() if " copy(" in line
        )
        assert "bf16[1,9216," not in copies and "bf16[3,1024," not in copies
        # no gathered view of a slot's rows, of either group
        assert "bf16[32,16896,4,128]" not in text
        assert "bf16[1,16896,4,128]" not in text
        assert "f32[32,16896,4,128]" not in text
        booked = {window_scopes.scope_of(v) for v in scopes.values()}
        assert booked >= {"window", "full", "router", "experts", "vocab"}
        m = c.memory_analysis()
        assert m.alias_size_in_bytes == sum(logical.values())
        assert m.temp_size_in_bytes < 0.3e9
    from benchmark.runners import serve_window

    cfg = serve_window.window_config(cfg_json)
    assert set(window.kinds(
        cfg, cfg.compute_dtype, 64, 512, 32, 264
    ).values()) == {"pool_kernel"}
    assert set(window.kinds(
        cfg, jnp.float32, 64, 512, 32, 264
    ).values()) == {"gathered_view"}


def test_linear_sparse_serving_programs_compile_at_the_cells_shape(topo):
    """``sala-serve-docs-64k``'s decode step and prefill chunk (one
    period of its 12 layers: a block-sparse layer and three lightning
    layers) at published widths, 48 slots x 66,560 rows over the cell's
    10,560-block pool, lower for the described v5e under their trace
    names from what the engine's constructor builds (``kvpool.engine.
    _linear_steps``): all five arrays (K and V pages, the compressed keys
    at their stride, the slots' float32 state, its snapshots) alias their
    outputs and none is copied or re-laid; the decode step reads its
    listed pages by the list kernel (``ops/block_sparse_attention.py``),
    once a sparse layer, booked to ``attn/sparse``; and every scope the
    cell's readers book device time to is there."""
    from benchmark import common, rehearse_sala, sala_scopes, trace_reduce
    from dlrover_tpu.serving.kvpool import linear

    cfg_json = common.load_json("configs", "minicpm-sala-9b.json")
    types = cfg_json["mixer_types"][:4]
    assert types == ["minicpm4"] + ["lightning-attn"] * 3
    programs, logical = rehearse_sala.lower_engine_programs(
        cfg_json, topo.devices[0], probes=False, reference=False,
        mixer_types=types,
    )
    assert logical == {
        "k_pages": 2 * 10560 * 64 * 128 * 2,
        "v_pages": 2 * 10560 * 64 * 128 * 2,
        "ckeys": 2 * 10560 * 4 * 128 * 2,
        "lightning": 3 * 48 * 32 * 128 * 128 * 4,
        "lightning_snapshots": 3 * 65 * 32 * 128 * 128 * 4,
    }
    for name in ("jit_step", "jit_prefill"):
        c = programs[name].compile()
        text = c.as_text()
        assert name + "," in text.splitlines()[0]
        for i in range(5):
            assert f"{{{i}}}: ({i}, {{}}, may-alias)" in text
        scopes = trace_reduce.scopes_from_hlo(text)
        calls = [
            v for k, v in scopes.items()
            if k.startswith("paged_block_list_decode_attention")
        ]
        assert len(calls) == (1 if name == "jit_step" else 0), calls
        assert all(sala_scopes.scope_of(v) == "sparse" for v in calls)
        # ... and scores its compressed keys in place (PR 56), once a
        # sparse layer, booked to ``attn/select``; the chunk does not.
        calls = [
            v for k, v in scopes.items()
            if k.startswith("paged_block_select_scores")
        ]
        assert len(calls) == (1 if name == "jit_step" else 0), calls
        assert all(sala_scopes.scope_of(v) == "select" for v in calls)
        copies = "".join(
            line for line in text.splitlines() if " copy(" in line
        )
        assert "bf16[2,10560," not in copies
        assert "f32[3,48,32,128,128]" not in copies
        assert "f32[3,65,32,128,128]" not in copies
        booked = {sala_scopes.scope_of(v) for v in scopes.values()}
        assert booked >= {"lightning", "select", "sparse", "state"}
        if name == "jit_prefill":
            assert "snapshot" in booked
        m = c.memory_analysis()
        assert m.alias_size_in_bytes == sum(logical.values())
        assert m.temp_size_in_bytes < 2.2e9
    from benchmark.runners import serve_linear

    cfg = serve_linear.linear_config(cfg_json)
    assert linear.decode_attention_kind(cfg, cfg.compute_dtype, 64) == \
        "pool_kernel"
    assert linear.decode_attention_kind(cfg, jnp.float32, 64) == \
        "gathered_pages"
    assert linear.decode_attention_kind(cfg, cfg.compute_dtype, 8) == \
        "gathered_pages"
    assert linear.select_kind(cfg, cfg.compute_dtype, 48, 1040) == \
        "pool_kernel"
    assert linear.select_kind(cfg, jnp.float32, 48, 1040) == "jnp"
    # every slot's table rides in scalar memory: 768 KB of it at most
    assert linear.select_kind(cfg, cfg.compute_dtype, 192, 1040) == "jnp"
    assert linear.kinds(cfg, cfg.compute_dtype, 64)["block_select"] == "jnp"


def test_delta_serving_programs_compile_at_the_cells_shape(topo):
    """``olmohybrid-serve-grow-6k``'s decode step and prefill chunk (one
    period of its 8 layers: three delta-rule layers and a full layer) at
    published widths, 32 slots x 6,656 rows over the cell's pool, lower
    for the described v5e under their trace names from what the engine's
    constructor builds (``kvpool.engine._delta_steps``): all six arrays
    (K and V pages of 32 held heads, the slots' float32 delta state and
    bfloat16 taps, the snapshots of each) alias their outputs and none is
    copied or re-laid; the decode step holds ONE state step a delta
    layer (``ops/gated_delta.py``), booked to ``attn/delta``, and reads
    its full layer's rows by the dense model's pool kernel, booked to
    ``attn/full``; the chunk reads them by the dense chunk kernel; and
    every scope the cell's readers book device time to is there."""
    from benchmark import common, delta_scopes, rehearse_olmo_hybrid
    from benchmark import trace_reduce
    from benchmark.runners import serve_delta
    from dlrover_tpu.serving.kvpool import delta

    cfg_json = common.load_json("configs", "olmo-hybrid-7b.json")
    eng = cfg_json["serve_engine"]
    blocks, snaps = eng["num_blocks"], eng["state_snapshots"] + 1
    programs, logical = rehearse_olmo_hybrid.lower_engine_programs(
        cfg_json, topo.devices[0], probes=False, reference=False, layers=4,
    )
    assert logical == {
        "k": blocks * 64 * 32 * 128 * 2, "v": blocks * 64 * 32 * 128 * 2,
        "delta": 3 * 32 * 30 * 96 * 192 * 4,
        "taps": 3 * 32 * 3 * 11520 * 2,
        "delta_snapshots": 3 * snaps * 30 * 96 * 192 * 4,
        "taps_snapshots": 3 * snaps * 3 * 11520 * 2,
    }
    for name in ("jit_step", "jit_prefill"):
        c = programs[name].compile()
        text = c.as_text()
        assert name + "," in text.splitlines()[0]
        for i in range(6):
            assert f"{{{i}}}: ({i}, {{}}, may-alias)" in text
        scopes = trace_reduce.scopes_from_hlo(text)
        steps = [
            v for k, v in scopes.items() if k.startswith("delta_state_step")
        ]
        assert len(steps) == (3 if name == "jit_step" else 0), steps
        assert all(delta_scopes.scope_of(v) == "delta" for v in steps)
        kernel = ("paged_pool_decode_attention" if name == "jit_step"
                  else "paged_pool_chunk_attention")
        calls = [v for k, v in scopes.items() if k.startswith(kernel)]
        assert len(calls) == 1, (kernel, sorted(scopes)[:40])
        assert all(delta_scopes.scope_of(v) == "full" for v in calls)
        copies = "".join(
            line for line in text.splitlines() if " copy(" in line
        )
        assert f"bf16[1,{blocks}," not in copies
        assert "f32[3,32,30,96,192]" not in copies
        assert f"f32[3,{snaps},30,96,192]" not in copies
        assert "bf16[3,32,3,11520]" not in copies
        booked = {delta_scopes.scope_of(v) for v in scopes.values()}
        assert booked >= {"delta", "conv", "full"}
        if name == "jit_prefill":
            assert booked >= {"state", "snapshot"}
        m = c.memory_analysis()
        # the float32 state's 192 lanes pad to 256 on the device
        held = sum(logical.values()) + (
            logical["delta"] + logical["delta_snapshots"]
        ) // 3
        assert abs(m.alias_size_in_bytes - held) < 0.01 * held
        assert m.temp_size_in_bytes < 1.0e9
    cfg = serve_delta.delta_config(cfg_json)
    assert delta.kinds(cfg, cfg.compute_dtype, 64, 512, 32, 104) == {
        "delta_chunk": "jnp", "delta_decode": "state_kernel",
        "full_decode_attention": "pool_kernel",
        "full_chunk_attention": "pool_kernel",
    }
    assert delta.kinds(cfg, jnp.float32, 64, 512, 32, 104)[
        "full_decode_attention"
    ] == "gathered_view"
