"""Plain float32 reference of a dense decoder block and its output head.

It follows the published architecture (pre-norm blocks, rotary position
embedding on the two halves of each head, causal softmax attention, SwiGLU
feed-forward, output head tied to the embedding) and imports nothing of the
program: ``bench/arch/dense.py`` draws its weights from the seed one layer at
a time (``bench/weights.py``) and applies :func:`block` and :func:`head`.
Every matrix product runs at ``Precision.HIGHEST``.

With ``fp8=True`` every matrix product takes its two inputs rounded to
float8 (e4m3, one scale per tensor) and accumulates in float32: the control,
one precision step below the bfloat16 the configurations state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _q8(x):
    """Round to float8 e4m3 with one scale for the tensor, back to float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(eq, a, b, fp8):
    if fp8:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(eq, a, b, precision=HI, preferred_element_type=jnp.float32)


def _norm(model, x, scale):
    if model["norm"] == "rmsnorm":
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + model["norm_eps"]) * scale
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + model["norm_eps"])


def _rope(x, theta):
    """x: (T, H, hd); rotation of the first half against the second."""
    T, _, hd = x.shape
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnums=(0, 3))
def block(mkey, w, x, fp8):
    """One decoder block over a whole sequence x: (T, d)."""
    model = dict(mkey)
    T = x.shape[0]
    H, G, hd = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    h = _norm(model, x, w.get("ln1/scale"))
    q = _mm("td,de->te", h, w["attn/wq"], fp8).reshape(T, H, hd)
    k = _mm("td,de->te", h, w["attn/wk"], fp8).reshape(T, G, hd)
    v = _mm("td,de->te", h, w["attn/wv"], fp8).reshape(T, G, hd)
    q, k = _rope(q, model["rope_theta"]), _rope(k, model["rope_theta"])
    rep = H // G
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = _mm("qhe,khe->hqk", q, k, fp8) / np.sqrt(hd)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = _mm("hqk,khe->qhe", p, v, fp8).reshape(T, H * hd)
    x = x + _mm("te,ed->td", o, w["attn/wo"], fp8)
    h = _norm(model, x, w.get("ln2/scale"))
    g = _mm("td,df->tf", h, w["ffn/w_gate"], fp8)
    u = _mm("td,df->tf", h, w["ffn/w_up"], fp8)
    return x + _mm("tf,fd->td", jax.nn.silu(g) * u, w["ffn/w_down"], fp8)


@functools.partial(jax.jit, static_argnums=(0, 5))
def head(mkey, embed, final_scale, x, rows, fp8):
    """Logits (len(rows), vocab) at positions ``rows`` of x."""
    model = dict(mkey)
    h = _norm(model, x[rows], final_scale)
    return _mm("td,vd->tv", h, embed[: model["vocab_size"]], fp8)
