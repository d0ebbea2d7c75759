"""Pallas kernels vs pure-jnp oracles: shape x dtype sweeps in interpret mode."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ref import decode_reference, mha_reference, rmsnorm_reference
from repro.kernels.rmsnorm import rmsnorm


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


def _rand(rng, shape, dtype):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32).astype(dtype)


FLASH_CASES = [
    # (B, Hq, Hkv, S, T, D, bq, bk)
    (1, 2, 2, 128, 128, 64, 64, 64),      # MHA
    (2, 4, 2, 256, 256, 64, 128, 64),     # GQA group=2
    (1, 8, 1, 128, 128, 128, 64, 128),    # MQA, MXU-aligned head dim
    (1, 4, 4, 512, 512, 32, 128, 128),    # long-ish seq
    (2, 2, 2, 64, 64, 8, 64, 64),         # tiny head dim
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(case, dtype, causal):
    B, Hq, Hkv, S, T, D, bq, bk = case
    rng = np.random.default_rng(hash((case, str(dtype), causal)) % 2 ** 31)
    q = _rand(rng, (B, Hq, S, D), dtype)
    k = _rand(rng, (B, Hkv, T, D), dtype)
    v = _rand(rng, (B, Hkv, T, D), dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                          interpret=True)
    want = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


DECODE_CASES = [
    # (B, Hq, Hkv, T, D, bk)
    (1, 4, 4, 128, 64, 64),
    (2, 8, 2, 256, 64, 128),     # GQA group=4
    (3, 8, 1, 512, 128, 256),    # MQA
    (2, 4, 4, 64, 32, 64),
]


@pytest.mark.parametrize("case", DECODE_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_matches_ref(case, dtype):
    B, Hq, Hkv, T, D, bk = case
    rng = np.random.default_rng(hash((case, str(dtype))) % 2 ** 31)
    q = _rand(rng, (B, Hq, D), dtype)
    k = _rand(rng, (B, Hkv, T, D), dtype)
    v = _rand(rng, (B, Hkv, T, D), dtype)
    # partial fills, including boundary crossing a block edge
    kv_len = jnp.asarray(rng.integers(1, T + 1, size=(B,)), jnp.int32)
    out = decode_attention(q, k, v, kv_len, block_k=bk, interpret=True)
    want = decode_reference(q, k, v, kv_len)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_decode_attention_full_cache():
    rng = np.random.default_rng(0)
    B, Hq, Hkv, T, D = 2, 4, 2, 256, 64
    q = _rand(rng, (B, Hq, D), jnp.float32)
    k = _rand(rng, (B, Hkv, T, D), jnp.float32)
    v = _rand(rng, (B, Hkv, T, D), jnp.float32)
    kv_len = jnp.full((B,), T, jnp.int32)
    out = decode_attention(q, k, v, kv_len, block_k=64, interpret=True)
    want = decode_reference(q, k, v, kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shape", [(8, 64), (3, 5, 128), (1, 256), (17, 96)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_matches_ref(shape, dtype):
    rng = np.random.default_rng(hash((shape, str(dtype))) % 2 ** 31)
    x = _rand(rng, shape, dtype)
    scale = _rand(rng, (shape[-1],), dtype) + 1.0
    out = rmsnorm(x, scale, interpret=True, block_rows=8)
    want = rmsnorm_reference(x, scale)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_flash_attention_vs_model_attention():
    """Kernel agrees with the model's chunked-jnp attention path."""
    from repro.configs import get_config
    from repro.models.layers import attention_core
    cfg = get_config("deepseek-7b").reduced().replace(attn_chunk=32)
    rng = np.random.default_rng(1)
    B, S, H, D = 2, 128, cfg.n_heads, cfg.head_dim
    q = _rand(rng, (B, S, H, D), jnp.float32)
    k = _rand(rng, (B, S, H, D), jnp.float32)
    v = _rand(rng, (B, S, H, D), jnp.float32)
    want = attention_core(cfg, q, k, v, causal=True)
    got = flash_attention(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                          jnp.swapaxes(v, 1, 2), causal=True,
                          block_q=64, block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(jnp.swapaxes(got, 1, 2)),
                               np.asarray(want), rtol=2e-4, atol=2e-4)


OPS_CALLS = {
    "flash_attention": lambda x: ops.flash_attention(x, x, x),
    "decode_attention": lambda x: ops.decode_attention(
        x[:, 0], x, x, jnp.full((x.shape[0],), 8, jnp.int32)),
    "rmsnorm": lambda x: ops.rmsnorm(x, jnp.ones((x.shape[-1],))),
}


@pytest.mark.parametrize("name", sorted(OPS_CALLS))
def test_ops_refuse_non_tpu_backend_without_interpret(name):
    """Off the chip a kernel runs only when the interpreter is asked for:
    no silent fallback to the interpreter or to the jnp reference."""
    assert jax.default_backend() != "tpu"
    x = jnp.ones((1, 8, 2, 16), jnp.float32)
    with pytest.raises(RuntimeError, match="interpret=True"):
        OPS_CALLS[name](x)
