"""Flash-decoding style single-token attention (Pallas TPU kernel).

The decode-path hot op TrIMS makes latency-critical (paper §6: once model
loading is eliminated, inference becomes compute/memory bound — this kernel
is that bound). One new token attends to a (possibly partially filled) KV
cache of length ``kv_len[b] <= T``.

TPU adaptation of FlashDecoding [arXiv:2311.01282]: the GPU version splits KV
across SMs and reduces partials in a second pass; on TPU the k-block grid
dimension is sequential per core, so partial (m, l, acc) reduction happens in
VMEM scratch — same math, no inter-core reduction needed. GQA query heads of
one KV head are packed into a single (group x D) MXU operand, so the kernel
does real matmuls instead of vector dots.

Grid: (B, Hkv, nK). ``kv_len`` is a scalar-prefetch operand (SMEM), read
per batch row; KV-length masking skips whole blocks past it (``pl.when``),
masking the boundary block with iota.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                   *, sm_scale: float, block_k: int, n_k: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    kv_len = len_ref[pl.program_id(0)]
    k_start = ki * block_k

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)             # (g, d)
        k = k_ref[0, 0].astype(jnp.float32)             # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                                 # (g, bk)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < kv_len, s, NEG_INF)

        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev[:, 0], jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev[:, 0] - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_ref[...] = l_ref[...] * alpha[:, None] + jnp.sum(p, axis=1)[:, None]
        v = v_ref[0, 0].astype(jnp.float32)              # (bk, d)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_cur[:, None]

    pl.when(k_start < kv_len)(_compute)

    @pl.when(ki == n_k - 1)
    def _finish():
        l = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        o_ref[0, 0, :, :] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     kv_len: jnp.ndarray, *, block_k: int = 256,
                     interpret: bool = False) -> jnp.ndarray:
    """q: (B, Hq, D); k, v: (B, Hkv, T, D); kv_len: (B,) -> (B, Hq, D)."""
    B, Hq, D = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    assert Hq % Hkv == 0
    group = Hq // Hkv
    block_k = min(block_k, T)
    assert T % block_k == 0, (T, block_k)
    n_k = T // block_k
    sm_scale = 1.0 / math.sqrt(D)

    qg = q.reshape(B, Hkv, group, D)
    kernel = functools.partial(_decode_kernel, sm_scale=sm_scale,
                               block_k=block_k, n_k=n_k)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, group, D), lambda b, h, ki, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, ki, lens: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, ki, lens: (b, h, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, D),
                               lambda b, h, ki, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, D), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(kv_len.astype(jnp.int32), qg, k, v)
    return out.reshape(B, Hq, D)
