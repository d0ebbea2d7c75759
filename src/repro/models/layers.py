"""Core pure-JAX layers: norms, RoPE, GQA attention, SwiGLU.

No flax — parameters are plain nested dicts of jnp arrays. Attention uses a
query-chunked online-softmax path (flash-attention algorithm in jnp) so that
long-context prefill never materializes the full (S x S) score matrix; on TPU
the Pallas kernels in ``repro.kernels`` take over via ``cfg.use_pallas``.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import partitioning as part

Params = Dict[str, jnp.ndarray]


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[0] if len(shape) >= 2 else 1
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_norm(cfg: ModelConfig, dim: Optional[int] = None) -> Params:
    d = dim if dim is not None else cfg.d_model
    if cfg.norm_type == "rmsnorm":
        return {"scale": jnp.ones((d,), cfg.pdtype)}
    if cfg.norm_type == "layernorm":
        return {"scale": jnp.ones((d,), cfg.pdtype), "bias": jnp.zeros((d,), cfg.pdtype)}
    if cfg.norm_type == "nonparametric_ln":  # olmo: no learned affine
        return {}
    raise ValueError(cfg.norm_type)


def apply_norm(cfg: ModelConfig, p: Params, x: jnp.ndarray) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if cfg.norm_type == "rmsnorm":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + cfg.norm_eps) * p["scale"].astype(jnp.float32)
    else:  # layernorm / nonparametric_ln
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        if p:
            out = out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return out.astype(x.dtype)


def rms_head_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """QK-norm over head_dim (qwen3)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                      # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]                # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def init_attention(cfg: ModelConfig, key) -> Params:
    ks = jax.random.split(key, 4)
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    p: Params = {
        "wq": dense_init(ks[0], (d, qd), cfg.pdtype),
        "wk": dense_init(ks[1], (d, kvd), cfg.pdtype),
        "wv": dense_init(ks[2], (d, kvd), cfg.pdtype),
        "wo": dense_init(ks[3], (qd, d), cfg.pdtype, scale=1.0 / math.sqrt(qd * 2 * cfg.n_layers)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((qd,), cfg.pdtype)
        p["bk"] = jnp.zeros((kvd,), cfg.pdtype)
        p["bv"] = jnp.zeros((kvd,), cfg.pdtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((cfg.head_dim,), cfg.pdtype)
        p["k_norm"] = jnp.ones((cfg.head_dim,), cfg.pdtype)
    return p


def qkv_project(cfg: ModelConfig, p: Params, x: jnp.ndarray,
                positions: Optional[jnp.ndarray], rope: bool = True
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    B, S, _ = x.shape
    q = x @ p["wq"].astype(x.dtype)
    k = x @ p["wk"].astype(x.dtype)
    v = x @ p["wv"].astype(x.dtype)
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = part.shard_heads(q.reshape(B, S, cfg.n_heads, cfg.head_dim))
    k = part.shard_heads(k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim))
    v = part.shard_heads(v.reshape(B, S, cfg.n_kv_heads, cfg.head_dim))
    if "q_norm" in p:
        q = rms_head_norm(q, p["q_norm"])
        k = rms_head_norm(k, p["k_norm"])
    if rope and positions is not None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _mha_chunk(q, k, v, *, causal: bool, q_offset, kv_len: Optional[jnp.ndarray]):
    """One dense attention block: q (B,Sq,H,hd), k (B,Skv,G,hd), v
    (B,Skv,G,vd) pre-broadcast.

    Returns (B, Sq, H, vd). fp32 softmax. ``kv_len`` masks a padded KV cache.
    """
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    G = k.shape[2]
    rep = H // G
    qg = q.reshape(B, Sq, G, rep, hd)
    scores = jnp.einsum("bqgrh,bkgh->bgrqk", qg, k).astype(jnp.float32)
    scores = scores / math.sqrt(hd)
    if causal:
        qpos = q_offset + jnp.arange(Sq)
        kpos = jnp.arange(Skv)
        mask = qpos[:, None] >= kpos[None, :]
        scores = jnp.where(mask[None, None, None], scores, -1e30)
    if kv_len is not None:
        valid = jnp.arange(Skv)[None, :] < kv_len[:, None]      # (B, Skv)
        scores = jnp.where(valid[:, None, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bgrqk,bkgh->bqgrh", probs, v)
    return out.reshape(B, Sq, H, v.shape[-1])


def attention_core(cfg: ModelConfig, q, k, v, *, causal: bool = True,
                   kv_len: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Memory-bounded attention: scan over query chunks (flash algorithm
    shape-wise; per-chunk softmax is exact since the full KV row is visible).

    q: (B,S,H,hd); k: (B,T,G,hd); v: (B,T,G,vd). Returns (B,S,H,vd).
    """
    if cfg.use_pallas:
        from repro.kernels import ops as kops
        if causal and kv_len is None and q.shape[1] == k.shape[1]:
            return kops.flash_attention(q, k, v, causal=True)
    B, S, H, hd = q.shape
    chunk = min(cfg.attn_chunk, S)
    if S % chunk != 0:
        chunk = S  # fall back to dense for ragged smoke shapes
    if chunk == S:
        return _mha_chunk(q, k, v, causal=causal, q_offset=0, kv_len=kv_len)
    n_chunks = S // chunk
    qc = q.reshape(B, n_chunks, chunk, H, hd)

    def body(carry, xs):
        i, qi = xs
        out = _mha_chunk(qi, k, v, causal=causal, q_offset=i * chunk, kv_len=kv_len)
        return carry, out

    _, outs = jax.lax.scan(body, None, (jnp.arange(n_chunks), jnp.moveaxis(qc, 1, 0)))
    return jnp.moveaxis(outs, 0, 1).reshape(B, S, H, v.shape[-1])


def decode_attention_core(cfg: ModelConfig, q, k_cache, v_cache, kv_len) -> jnp.ndarray:
    """Single-token attention against a padded KV cache.

    q: (B,1,H,hd); caches: (B,T,G,hd); kv_len: (B,) valid lengths.

    The cache layout is pinned to (B->fsdp, T, G->tensor, hd) at the read:
    without the constraint XLA's propagation prefers a T-sharded layout for
    the softmax reduction, oscillates against the K-sharded update layout,
    and falls back to 'involuntary full rematerialization' (a replicated
    fp32 staging copy of the whole cache — measured 2x 8 GiB/chip on
    deepseek-7b decode_32k).
    """
    if cfg.use_pallas:
        from repro.kernels import ops as kops
        return kops.decode_attention(q[:, 0], k_cache, v_cache, kv_len)[:, None]
    k_cache = part.shard_cache(k_cache)
    v_cache = part.shard_cache(v_cache)
    return _mha_chunk(q, k_cache, v_cache, causal=False, q_offset=0, kv_len=kv_len)


def attention_out(cfg: ModelConfig, p: Params, o: jnp.ndarray) -> jnp.ndarray:
    B, S = o.shape[:2]
    return o.reshape(B, S, cfg.q_dim) @ p["wo"].astype(o.dtype)


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------

def init_mlp(cfg: ModelConfig, key, d_ff: Optional[int] = None) -> Params:
    ks = jax.random.split(key, 3)
    d, f = cfg.d_model, d_ff or cfg.d_ff
    return {
        "w_gate": dense_init(ks[0], (d, f), cfg.pdtype),
        "w_up": dense_init(ks[1], (d, f), cfg.pdtype),
        "w_down": dense_init(ks[2], (f, d), cfg.pdtype, scale=1.0 / math.sqrt(f * 2 * cfg.n_layers)),
    }


def apply_mlp(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    g = jax.nn.silu(x @ p["w_gate"].astype(x.dtype))
    u = x @ p["w_up"].astype(x.dtype)
    h = part.shard_ffn(g * u)
    return h @ p["w_down"].astype(x.dtype)
