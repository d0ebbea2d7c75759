"""Differentiability of the Pallas-backed ops (custom_vjp: pallas fwd +
oracle bwd) — gradients must match differentiating the pure-jnp reference."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_grad_matches_reference(causal):
    rng = np.random.default_rng(0)
    B, S, H, D = 1, 128, 2, 32
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)

    def via_kernel(q, k, v):
        return ops.flash_attention(q, k, v, causal=causal,
                                   block_q=64, block_k=64,
                                   interpret=True).sum()

    def via_ref(q, k, v):
        qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
        return ref.mha_reference(qt, kt, vt, causal=causal).sum()

    g1 = jax.grad(via_kernel, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(via_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_flash_attention_value_under_jit_grad():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((1, 64, 2, 16)), jnp.float32)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda q: ops.flash_attention(q, q, q, causal=True,
                                      block_q=64, block_k=64,
                                      interpret=True).sum()))(q)
    assert np.isfinite(float(loss))
    assert np.isfinite(np.asarray(grads)).all()
