import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kaito_tpu.engine.attention import prefill_attention
from kaito_tpu.parallel.mesh import build_mesh
from kaito_tpu.parallel.plan import make_mesh_spec
from kaito_tpu.parallel.ring_attention import ring_attention


@pytest.mark.parametrize("seq_degree,Hkv,G", [(4, 4, 1), (2, 2, 2), (8, 1, 4)])
def test_ring_matches_full_attention(cpu_devices, seq_degree, Hkv, G):
    mesh = build_mesh(make_mesh_spec(data=8 // seq_degree, sequence=seq_degree),
                      cpu_devices)
    rng = np.random.RandomState(0)
    B, T, D = 2, 32, 16
    H = Hkv * G
    q = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, T, Hkv, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, T, Hkv, D), jnp.float32)
    scale = 1.0 / np.sqrt(D)

    ref = prefill_attention(q, k, v, scale=scale)
    with jax.set_mesh(mesh) if hasattr(jax, "set_mesh") else mesh:
        out = ring_attention(q, k, v, mesh, scale=scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_non_causal(cpu_devices):
    mesh = build_mesh(make_mesh_spec(sequence=8), cpu_devices)
    rng = np.random.RandomState(1)
    B, T, H, D = 1, 64, 2, 8
    q = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    # non-causal reference: plain softmax attention
    s = jnp.einsum("bthd,bshd->bhts", q, k) * 0.3
    ref = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), v)
    out = ring_attention(q, k, v, mesh, scale=0.3, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# slow: 27 s alone under the check's command (training path)
@pytest.mark.slow
def test_ring_gradients_flow(cpu_devices):
    """Ring attention must be differentiable (training path)."""
    mesh = build_mesh(make_mesh_spec(sequence=4, data=2), cpu_devices)
    rng = np.random.RandomState(2)
    B, T, H, D = 1, 16, 2, 8
    q = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh, scale=0.35) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(prefill_attention(q, k, v, scale=0.35) ** 2)

    g_ring = jax.grad(loss_ring)(q, k, v)
    g_ref = jax.grad(loss_ref)(q, k, v)
    np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-4)
