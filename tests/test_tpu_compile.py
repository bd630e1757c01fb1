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


@pytest.fixture(autouse=True)
def _as_if_on_tpu(monkeypatch):
    """Take the TPU branches (flash kernel, non-interpret Pallas) and
    keep the persistent cache out: a described-device executable can be
    written to it but never read back."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(llama, "_ATTN_CACHE", {})
    monkeypatch.delenv("DLROVER_TPU_ATTN", raising=False)
    monkeypatch.delenv("DLROVER_TPU_MOE_DISPATCH", raising=False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


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
