"""The delta-rule scan's Pallas kernels (ops/kda_kernels.py), run in
interpret mode on the CPU at the published head size, against the
token-by-token recurrence and against the ``jax.numpy`` chunked form
they replace on a TPU; and the function that says which form runs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import kda, kda_kernels
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh

DIM = kda_kernels.LANES
# A slow head, and one that forgets e^-12 a token: a sub-chunk of it
# overflows float32 in any form that divides by a cumulated decay.
DECAY = (0.3, 12.0)


def _inputs(length, batch):
    ks = jax.random.split(jax.random.key(length), 6)
    shape = (batch, len(DECAY), length, DIM)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], shape))
    k = unit(jax.random.normal(ks[1], shape))
    v = jax.random.normal(ks[2], shape)
    g = -jax.nn.softplus(2 * jax.random.normal(ks[3], shape))
    g = g * jnp.asarray(DECAY)[None, :, None, None]
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], shape[:3]))
    return (q, k, v, g, beta), jax.random.normal(ks[5], shape)


def _pulled(fn, args, w):
    out, pull = jax.vjp(fn, *args)
    return (out,) + pull(w)


@pytest.fixture(scope="module")
def results():
    """{(length, batch): {form: (o, dq, dk, dv, dg, dbeta)}}, made once
    a shape: interpreting a kernel is the slow part."""
    cache = {}

    def get(length, batch):
        if (length, batch) not in cache:
            args, w = _inputs(length, batch)
            forms = {
                "kernels": lambda *a: kda.kda_chunked_kernels(
                    *a, interpret=True
                ),
                "recurrence": kda.kda_recurrent,
                "xla": kda.kda_chunked_xla,
            }
            cache[length, batch] = {
                name: jax.jit(lambda a, w, fn=fn: _pulled(fn, a, w))(args, w)
                for name, fn in forms.items()
            }
        return cache[length, batch]

    return get


# Two chunks; a tail that is padded (the padding leaves the state alone:
# the tokens before it read the same); shorter than a chunk; a batch.
SHAPES = [(128, 1), (150, 1), (7, 1), (70, 2)]


@pytest.mark.parametrize("against", ["recurrence", "xla"])
@pytest.mark.parametrize("length,batch", SHAPES)
def test_kernels_match(results, length, batch, against):
    """Forward and all five gradients, both heads."""
    got = results(length, batch)["kernels"]
    want = results(length, batch)[against]
    assert got[0].shape == (batch, len(DECAY), length, DIM)
    for a, r in zip(got, want):
        assert a.shape == r.shape
        assert bool(jnp.all(jnp.isfinite(a)))
        # Float32 rounding along the recurrence, by the tensor's scale:
        # the two references differ from each other by as much.
        scale = max(1.0, float(jnp.max(jnp.abs(r))))
        np.testing.assert_allclose(a, r, rtol=0, atol=3e-5 * scale)


def test_forward_kernel_keeps_the_state_entering_each_chunk():
    """What the backward walks on: chunk n's state is the recurrence's
    after 64 n tokens, and a padded tail does not move it."""
    (q, k, v, g, beta), _ = _inputs(128, 1)
    flat = (q, k, g, v, beta)
    _, states = kda_kernels.scan_forward(*flat, interpret=True)
    assert states.shape == (1, len(DECAY), 2, DIM, DIM)
    states = states[0]
    np.testing.assert_array_equal(states[:, 0], 0.0)

    def step(state, x):
        k_t, v_t, g_t, b_t = x
        state = state * jnp.exp(g_t)[..., None]
        u = b_t[..., None] * (v_t - jnp.einsum(
            "hk,hkv->hv", k_t, state, precision="highest"
        ))
        return state + k_t[..., None] * u[..., None, :], None

    xs = tuple(jnp.moveaxis(x[0, :, :64], 1, 0) for x in (k, v, g, beta))
    want, _ = jax.lax.scan(
        step, jnp.zeros((len(DECAY), DIM, DIM)), xs
    )
    np.testing.assert_allclose(
        jnp.swapaxes(states[:, 1], -1, -2), want, rtol=0, atol=2e-5
    )                                       # kept transposed, [dv, dk]
    padded = [
        jnp.pad(x, [(0, 0), (0, 0), (0, 64)] + [(0, 0)] * (x.ndim - 3))
        for x in flat
    ]
    _, more = kda_kernels.scan_forward(*padded, interpret=True)
    np.testing.assert_array_equal(more[0, :, :2], states)


@pytest.mark.parametrize("backend,dk,dv,mesh_devices,kind", [
    ("tpu", 128, 128, 0, "pallas"),
    ("tpu", 128, 128, 1, "pallas"),
    ("cpu", 128, 128, 0, "xla"),       # the runner's CPU rehearsal
    ("tpu", 16, 16, 0, "xla"),         # the tier-1 tests' tiny heads
    ("tpu", 128, 64, 0, "xla"),
    ("tpu", 256, 256, 0, "xla"),
    ("tpu", 128, 128, 2, "xla"),       # GSPMD cannot split a kernel
])
def test_the_scan_kind_is_read_from_platform_shape_and_mesh(
    monkeypatch, backend, dk, dv, mesh_devices, kind
):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if not mesh_devices:
        assert kda.kda_scan_kind(dk, dv) == kind
        return
    mesh = build_mesh(
        MeshConfig(dp=mesh_devices), devices=jax.devices()[:mesh_devices]
    )
    with mesh:
        assert kda.kda_scan_kind(dk, dv) == kind


@pytest.mark.parametrize("kind", ["pallas", "xla"])
def test_kda_chunked_runs_the_form_its_kind_names(monkeypatch, kind):
    ran = []
    monkeypatch.setattr(kda, "kda_scan_kind", lambda dk, dv: kind)
    monkeypatch.setattr(
        kda, "kda_chunked_kernels", lambda *a: ran.append("pallas")
    )
    monkeypatch.setattr(kda, "kda_chunked_xla", lambda *a: ran.append("xla"))
    kda.kda_chunked(*_inputs(7, 1)[0])
    assert ran == [kind]
