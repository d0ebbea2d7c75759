"""Flash attention (forward) as a Pallas TPU kernel.

TPU adaptation of FlashAttention [arXiv:2205.14135]: the GPU algorithm tiles
over SMs with shared-memory staging; on TPU we tile HBM->VMEM with BlockSpec,
run the (block_q x block_k) score GEMMs on the MXU (128-aligned tiles), and
keep the online-softmax running max/sum and the fp32 output accumulator in
VMEM scratch across the sequential k-block grid dimension.

Grid: (B, Hq, nQ, nK) — the trailing dimension is 'arbitrary' (sequential on
TPU) so scratch accumulators carry across k blocks. GQA is expressed in the
k/v ``index_map`` (q-head -> kv-head), so no KV duplication is materialized.

Causal masking: blocks fully above the diagonal are skipped with ``pl.when``
(no MXU work wasted), diagonal blocks get an iota mask.

VMEM budget per program @ bq=bk=128, D=128, bf16 in / fp32 acc:
  q 32KiB + k 32KiB + v 32KiB + acc 64KiB + o 32KiB + m/l 1KiB  ≈ 193KiB
comfortably inside the ~16MiB v5e VMEM; larger D scales linearly.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                sm_scale: float, causal: bool, block_q: int, block_k: int,
                n_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                                # (bq, bk)
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, NEG_INF)

        m_prev = m_ref[...]                             # (bq, 1)
        m_cur = jnp.maximum(m_prev[:, 0], jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev[:, 0] - m_cur)
        p = jnp.exp(s - m_cur[:, None])                 # (bq, bk)
        l_ref[...] = l_ref[...] * alpha[:, None] + jnp.sum(p, axis=1)[:, None]
        v = v_ref[0, 0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_cur[:, None]

    if causal:
        # skip k blocks entirely above the diagonal
        pl.when(k_start <= q_start + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == n_k - 1)
    def _finish():
        l = l_ref[...]                                  # (bq, 1)
        l = jnp.where(l == 0.0, 1.0, l)                 # fully-masked rows
        o_ref[0, 0, :, :] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False) -> jnp.ndarray:
    """q: (B, Hq, S, D); k, v: (B, Hkv, T, D) -> (B, Hq, S, D)."""
    B, Hq, S, D = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    assert Hq % Hkv == 0, "GQA requires Hq % Hkv == 0"
    group = Hq // Hkv
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    assert S % block_q == 0 and T % block_k == 0, (S, T, block_q, block_k)
    n_q, n_k = S // block_q, T // block_k
    sm_scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                               block_q=block_q, block_k=block_k, n_k=n_k)
    grid = (B, Hq, n_q, n_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),   # output accumulator
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running sum
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
