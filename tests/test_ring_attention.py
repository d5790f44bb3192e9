"""Ring attention (sequence parallelism) tests on the 8-device CPU mesh.

The sharded ring must match the single-device oracle bitwise-closely in
both outputs and gradients, causal and bidirectional."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import parallel as pp
from paddle_tpu.parallel.ring_attention import (
    ring_attention,
    scaled_dot_product_attention,
)

B, T, H, D = 2, 32, 2, 8


@pytest.fixture
def mesh_sp():
    return pp.make_mesh((8,), (pp.SP,))


def _qkv(seed=0):
    rng = np.random.RandomState(seed)
    return tuple(
        jnp.asarray(rng.randn(B, T, H, D).astype(np.float32) * 0.5)
        for _ in range(3)
    )


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_oracle(mesh_sp, causal):
    q, k, v = _qkv()
    want = scaled_dot_product_attention(q, k, v, causal=causal)
    got = ring_attention(q, k, v, mesh_sp, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_gradients_match_oracle(mesh_sp, causal):
    q, k, v = _qkv(1)

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh_sp, causal=causal) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(
            scaled_dot_product_attention(q, k, v, causal=causal) ** 2
        )

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gr, gf in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gf), atol=5e-4)


def test_ring_requires_divisible_T(mesh_sp):
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(B, 30, H, D).astype(np.float32))
    with pytest.raises(ValueError, match="not divisible"):
        ring_attention(q, q, q, mesh_sp)


def test_ring_under_jit_with_sharded_inputs(mesh_sp):
    """The intended deployment: inputs arrive already sharded over sp."""
    from jax.sharding import NamedSharding, PartitionSpec

    q, k, v = _qkv(3)
    sh = NamedSharding(mesh_sp, PartitionSpec(None, pp.SP, None, None))
    qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))
    f = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh_sp, causal=True))
    got = f(qs, ks, vs)
    want = scaled_dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert got.sharding.spec[1] == pp.SP  # output stays sequence-sharded


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_oracle(mesh_sp, causal):
    from paddle_tpu.parallel.ring_attention import ulysses_attention

    rng = np.random.RandomState(5)
    # H must be divisible by the axis size (8)
    q, k, v = (jnp.asarray(rng.randn(2, 16, 8, 4).astype(np.float32) * 0.5)
               for _ in range(3))
    want = scaled_dot_product_attention(q, k, v, causal=causal)
    got = ulysses_attention(q, k, v, mesh_sp, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_ulysses_gradients_match_oracle(mesh_sp):
    from paddle_tpu.parallel.ring_attention import ulysses_attention

    rng = np.random.RandomState(6)
    q, k, v = (jnp.asarray(rng.randn(2, 16, 8, 4).astype(np.float32) * 0.5)
               for _ in range(3))

    g_u = jax.grad(lambda q, k, v: jnp.sum(
        ulysses_attention(q, k, v, mesh_sp, causal=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(lambda q, k, v: jnp.sum(
        scaled_dot_product_attention(q, k, v, causal=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for gu, gr in zip(g_u, g_r):
        np.testing.assert_allclose(np.asarray(gu), np.asarray(gr), atol=5e-4)


def test_ulysses_requires_divisible_heads(mesh_sp):
    from paddle_tpu.parallel.ring_attention import ulysses_attention

    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(2, 16, 6, 4).astype(np.float32))
    with pytest.raises(ValueError, match="H=6 not divisible"):
        ulysses_attention(q, q, q, mesh_sp)
