"""Multi-head latent attention (DeepSeek-V2/V3, Moonlight).

Queries come straight from the hidden state (no query low rank): per head a
``qk_nope_head_dim`` part and a ``qk_rope_head_dim`` part that takes RoPE.
Keys and values come from a latent: ``[c_kv, k_pe] = h @ wkv_a``, with
``c_kv`` (``kv_lora_rank`` wide) RMS-normed and ``k_pe`` one rotary key
shared by every head; ``[k_nope, v] = c_kv @ wkv_b`` per head. Scores are
scaled by ``1/sqrt(qk_nope_head_dim + qk_rope_head_dim)``.

Prefill and the training forward expand the latent into per-head K and V.
Decode absorbs ``wkv_b`` into the query and into the output, so that it
reads only the latent cache ``{c_kv: (B, T, kv_lora_rank), k_pe: (B, T,
qk_rope_head_dim)}`` of each layer.

RoPE rotates the two halves of the rotary part (dims ``i`` and ``i + 32``);
the published checkpoint pairs ``(2i, 2i + 1)``, which is the same up to a
fixed permutation of the rotary columns of ``wq`` and ``wkv_a``.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L

Params = Dict[str, jnp.ndarray]


def init_mla(cfg: ModelConfig, key) -> Params:
    ks = jax.random.split(key, 4)
    d, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "wq": L.dense_init(ks[0], (d, H * (nope + rope)), cfg.pdtype),
        "wkv_a": L.dense_init(ks[1], (d, r + rope), cfg.pdtype),
        "kv_norm": jnp.ones((r,), cfg.pdtype),
        "wkv_b": L.dense_init(ks[2], (r, H * (nope + vd)), cfg.pdtype),
        "wo": L.dense_init(ks[3], (H * vd, d), cfg.pdtype,
                           scale=1.0 / math.sqrt(H * vd * 2 * cfg.n_layers)),
    }


def cache_zeros(cfg: ModelConfig, B: int, T: int) -> Params:
    return {"c_kv": jnp.zeros((B, T, cfg.kv_lora_rank), cfg.cdtype),
            "k_pe": jnp.zeros((B, T, cfg.qk_rope_head_dim), cfg.cdtype)}


def _project(cfg: ModelConfig, p: Params, h, positions):
    """h (B, S, D) -> q_nope (B,S,H,nope), q_pe (B,S,H,rope) with RoPE,
    c_kv (B,S,r) normed, k_pe (B,S,rope) with RoPE."""
    B, S, _ = h.shape
    nope, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q = (h @ p["wq"].astype(h.dtype)).reshape(B, S, cfg.n_heads, -1)
    kv = h @ p["wkv_a"].astype(h.dtype)
    c_kv = L.rms_head_norm(kv[..., :r], p["kv_norm"], cfg.norm_eps)
    q_pe = L.apply_rope(q[..., nope:], positions, cfg.rope_theta)
    k_pe = L.apply_rope(kv[..., None, r:], positions, cfg.rope_theta)[:, :, 0]
    return q[..., :nope], q_pe, c_kv, k_pe


def _expand(cfg: ModelConfig, p: Params, q_nope, q_pe, c_kv, k_pe):
    """Causal attention with the latent expanded into per-head K and V;
    returns the output projection (B, S, D)."""
    B, S, H, _ = q_nope.shape
    nope = cfg.qk_nope_head_dim
    kv = (c_kv @ p["wkv_b"].astype(c_kv.dtype)).reshape(B, S, H, -1)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_pe[:, :, None], (B, S, H, k_pe.shape[-1]))], axis=-1)
    o = L.attention_core(cfg, q, k, kv[..., nope:], causal=True)
    return o.reshape(B, S, -1) @ p["wo"].astype(o.dtype)


def attend(cfg: ModelConfig, p: Params, h, positions) -> jnp.ndarray:
    """Training forward: h (B, S, D) -> attention output (B, S, D)."""
    with jax.named_scope("mla.forward"):
        return _expand(cfg, p, *_project(cfg, p, h, positions))


def prefill(cfg: ModelConfig, p: Params, h, positions):
    """Prefill of a whole prompt: the attention output and the latent
    ``(c_kv, k_pe)`` that :func:`write` puts in the cache."""
    with jax.named_scope("mla.prefill"):
        q_nope, q_pe, c_kv, k_pe = _project(cfg, p, h, positions)
        return _expand(cfg, p, q_nope, q_pe, c_kv, k_pe), (c_kv, k_pe)


def write(cl: Params, c_kv, k_pe, start) -> Params:
    """The latent cache with ``c_kv``/``k_pe`` written from ``start``."""
    return {"c_kv": jax.lax.dynamic_update_slice_in_dim(
                cl["c_kv"], c_kv.astype(cl["c_kv"].dtype), start, axis=1),
            "k_pe": jax.lax.dynamic_update_slice_in_dim(
                cl["k_pe"], k_pe.astype(cl["k_pe"].dtype), start, axis=1)}


def decode(cfg: ModelConfig, p: Params, h, cl: Params, pos
           ) -> Tuple[jnp.ndarray, Params]:
    """One token h (B, 1, D) at ``pos`` against the latent cache, with
    ``wkv_b`` absorbed: scores are ``(q_nope W_uk) . c_kv + q_pe . k_pe``,
    the output ``((probs . c_kv) W_uv) @ wo``."""
    with jax.named_scope("mla.decode"):
        B = h.shape[0]
        H, r, nope = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
        q_nope, q_pe, c_kv, k_pe = _project(
            cfg, p, h, jnp.full((1, 1), pos, jnp.int32))
        cl = write(cl, c_kv, k_pe, pos)
        w = p["wkv_b"].astype(h.dtype).reshape(r, H, -1)
        q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope, w[..., :nope])
        s = (jnp.einsum("bqhr,btr->bhqt", q_lat, cl["c_kv"],
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bqhe,bte->bhqt", q_pe, cl["k_pe"],
                          preferred_element_type=jnp.float32))
        s = s / math.sqrt(nope + cfg.qk_rope_head_dim)
        valid = jnp.arange(s.shape[-1]) <= pos
        probs = jax.nn.softmax(jnp.where(valid, s, -1e30), axis=-1)
        o_lat = jnp.einsum("bhqt,btr->bqhr", probs.astype(h.dtype), cl["c_kv"])
        o = jnp.einsum("bqhr,rhv->bqhv", o_lat, w[..., nope:])
        return o.reshape(B, 1, -1) @ p["wo"].astype(h.dtype), cl
