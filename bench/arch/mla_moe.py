"""DeepSeek-V3-style decoder (``moonlight-16b-a3b``): pre-norm blocks of
multi-head latent attention, the first ``first_k_dense`` with a SwiGLU
feed-forward, the rest an expert layer (sigmoid router with a selection bias,
``noaux_tc`` with one group, top-k weights renormalized and scaled, shared
experts beside), RMSNorm, output head apart from the embedding.

The chip holds one share of each expert layer: experts ``[first_held_expert,
first_held_expert + experts_held)`` of ``n_experts_router``. The router keeps
its published width and top-k; only the held experts' part of the routed
output is added, in the program and in this reference alike.

Its leaves, the program fields it must show, the float32 reference and the
counts are all here.
"""
from __future__ import annotations

import functools
import math
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.reference import HI, _mm, _q8

BYTES = 2  # bfloat16


def program_fields(config: dict) -> dict:
    m = config["model"]
    return {"family": "moe", "n_layers": m["n_layers"],
            "first_k_dense": m["first_k_dense"], "d_model": m["d_model"],
            "n_heads": m["n_heads"], "kv_lora_rank": m["kv_lora_rank"],
            "qk_nope_head_dim": m["qk_nope_head_dim"],
            "qk_rope_head_dim": m["qk_rope_head_dim"],
            "v_head_dim": m["v_head_dim"], "d_ff_dense": m["d_ff_dense"],
            "d_ff": m["d_ff_expert"], "n_experts": m["n_experts_router"],
            "n_held_experts": m["experts_held"],
            "first_held_expert": m["first_held_expert"], "top_k": m["top_k"],
            "n_shared_experts": m["n_shared_experts"],
            "router_score": "sigmoid", "routed_scaling": m["routed_scaling"],
            "vocab_size": m["vocab_size"], "norm_type": "rmsnorm",
            "norm_eps": m["norm_eps"], "rope_theta": m["rope_theta"],
            "tie_embeddings": False, "param_dtype": m["dtype"],
            "compute_dtype": m["dtype"], "moe_impl": "ragged",
            "padded_vocab": config["program"]["embed_rows"],
            "use_pallas": False}


def _attn_leaves(m: dict) -> dict:
    d, H, r = m["d_model"], m["n_heads"], m["kv_lora_rank"]
    nope, rope, vd = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    return {
        "ln1/scale": ((d,), 0.0),
        "attn/wq": ((d, H * (nope + rope)), 1 / math.sqrt(d)),
        "attn/wkv_a": ((d, r + rope), 1 / math.sqrt(d)),
        "attn/kv_norm": ((r,), 0.0),
        "attn/wkv_b": ((r, H * (nope + vd)), 1 / math.sqrt(r)),
        "attn/wo": ((H * vd, d), 1 / math.sqrt(H * vd * 2 * m["n_layers"])),
        "ln2/scale": ((d,), 0.0),
    }


def _swiglu_leaves(prefix: str, d: int, f: int, L: int, lead=(),
                   down: float = 1.0) -> dict:
    """``{prefix}gate``, ``{prefix}up`` and ``{prefix}down``, each with the
    leading axes ``lead``; ``down`` scales the down projection's std."""
    return {f"{prefix}gate": (lead + (d, f), 1 / math.sqrt(d)),
            f"{prefix}up": (lead + (d, f), 1 / math.sqrt(d)),
            f"{prefix}down": (lead + (f, d), down / math.sqrt(f * 2 * L))}


def weight_groups(model: dict, embed_rows: int):
    """``embed``, ``lm_head`` and ``final_norm/scale``; then the group
    ``dense_layers`` (MLA and a SwiGLU of ``d_ff_dense``) and the group
    ``layers`` (MLA, the router and its bias, the held experts stacked on
    a leading axis, and the shared experts as one SwiGLU of
    ``n_shared_experts * d_ff_expert``). The stds are assumed (the
    configuration file's ``assumed``)."""
    m = model
    d, L = m["d_model"], m["n_layers"]
    f, E = m["d_ff_expert"], m["n_experts_router"]
    dense = {**_attn_leaves(m), **_swiglu_leaves("ffn/w_", d, m["d_ff_dense"], L)}
    moe = {**_attn_leaves(m),
           "ffn/router": ((d, E), m["router_std"]),
           "ffn/router_bias": ((E,), m["router_bias_std"]),
           **_swiglu_leaves("ffn/w_", d, f, L, (m["experts_held"],),
                            m["routed_down_scale"]),
           **_swiglu_leaves("ffn/shared_", d, m["n_shared_experts"] * f, L)}
    stem = {"embed": ((embed_rows, d), 0.02),
            "lm_head": ((embed_rows, d), 0.02),
            "final_norm/scale": ((d,), 0.0)}
    k = m["first_k_dense"]
    return stem, [("dense_layers", k, dense), ("layers", L - k, moe)]


def nest(flat: dict, model: dict) -> dict:
    return W.unflatten(flat)


# ------------------------------------------------------------- reference
def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (T, ..., e); the first half of the last axis rotated against the
    second, at positions 0..T-1."""
    T, e = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, e, 2, dtype=np.float32) / e))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((T,) + (1,) * (x.ndim - 2) + (e // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : e // 2], x[..., e // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(m, w, h, fp8):
    """MLA over a whole sequence h (T, d), the latent expanded into per-head
    keys and values, causal softmax; the output projection (T, d)."""
    T = h.shape[0]
    H, r = m["n_heads"], m["kv_lora_rank"]
    nope, rope = m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    q = _mm("td,de->te", h, w["attn/wq"], fp8).reshape(T, H, nope + rope)
    kv = _mm("td,de->te", h, w["attn/wkv_a"], fp8)
    c_kv = _rms(kv[:, :r], w["attn/kv_norm"], m["norm_eps"])
    k_pe = _rope(kv[:, r:], m["rope_theta"])                     # (T, rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], m["rope_theta"])], -1)
    kvb = _mm("tr,re->te", c_kv, w["attn/wkv_b"], fp8).reshape(T, H, -1)
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(k_pe[:, None], (T, H, rope))], -1)
    s = _mm("qhe,khe->hqk", q, k, fp8) / np.sqrt(nope + rope)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = _mm("hqk,khe->qhe", p, kvb[..., nope:], fp8).reshape(T, -1)
    return _mm("te,ed->td", o, w["attn/wo"], fp8)


def _swiglu(h, wg, wu, wd, fp8):
    g = _mm("td,df->tf", h, wg, fp8)
    u = _mm("td,df->tf", h, wu, fp8)
    return _mm("tf,fd->td", jax.nn.silu(g) * u, wd, fp8)


def route(m, w, h, fp8=False):
    """Sigmoid scores of every routed expert, the top-k chosen on score plus
    bias, and their weights: the unbiased scores, renormalized and scaled.
    Returns (ids (T, k), weights (T, k))."""
    if fp8:
        h = _q8(h)
    logits = jnp.dot(h, _q8(w["ffn/router"]) if fp8 else w["ffn/router"],
                     precision=HI)
    scores = jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(scores + w["ffn/router_bias"], m["top_k"])
    wts = jnp.take_along_axis(scores, ids, -1)
    return ids, wts / jnp.sum(wts, -1, keepdims=True) * m["routed_scaling"]


def _experts(m, w, h, fp8):
    """The held experts' part of the routed output, and the shared
    experts'."""
    ids, wts = route(m, w, h, fp8)
    held = jnp.arange(m["experts_held"]) + m["first_held_expert"]
    gate = jnp.sum(jnp.where(ids[:, :, None] == held[None, None], wts[..., None],
                             0.0), axis=1)                       # (T, held)
    g = _mm("td,edf->etf", h, w["ffn/w_gate"], fp8)
    u = _mm("td,edf->etf", h, w["ffn/w_up"], fp8)
    y = _mm("etf,efd->etd", jax.nn.silu(g) * u, w["ffn/w_down"], fp8)
    routed = jnp.einsum("te,etd->td", gate, y, precision=HI)
    return routed + _swiglu(h, w["ffn/shared_gate"], w["ffn/shared_up"],
                            w["ffn/shared_down"], fp8)


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def block(mkey, w, x, moe, fp8):
    """One decoder block over a whole sequence x: (T, d)."""
    m = dict(mkey)
    x = x + _attention(m, w, _rms(x, w["ln1/scale"], m["norm_eps"]), fp8)
    h = _rms(x, w["ln2/scale"], m["norm_eps"])
    if moe:
        return x + _experts(m, w, h, fp8)
    return x + _swiglu(h, w["ffn/w_gate"], w["ffn/w_up"], w["ffn/w_down"], fp8)


@functools.partial(jax.jit, static_argnums=(0, 5))
def head(mkey, lm_head, final_scale, x, rows, fp8):
    """Logits (len(rows), vocab) at positions ``rows`` of x."""
    m = dict(mkey)
    h = _rms(x[rows], final_scale, m["norm_eps"])
    return _mm("td,vd->tv", h, lm_head[: m["vocab_size"]], fp8)


def logits_at(seed: int, model: dict, embed_rows: int,
              seqs: Sequence[np.ndarray], rows: Sequence[np.ndarray],
              fp8: bool = False) -> List[np.ndarray]:
    """For each token sequence, the float32 logits at the given positions.

    Layers are drawn from the seed and applied one at a time across every
    sequence, so that the whole model never sits in float32 on the device."""
    mkey = tuple(sorted(model.items()))
    stem_specs, groups = weight_groups(model, embed_rows)
    stem = W.stem_f32(seed, stem_specs)
    xs = [stem["embed"][jnp.asarray(s, jnp.int32)] for s in seqs]
    for group in groups:
        for i in range(group[1]):
            w = W.layer_f32(seed, group, i)
            xs = [block(mkey, w, x, group[0] == "layers", fp8) for x in xs]
            del w
    out = [np.asarray(head(mkey, stem["lm_head"], stem["final_norm/scale"], x,
                           jnp.asarray(r, jnp.int32), fp8))
           for x, r in zip(xs, rows)]
    del stem, xs
    return out


# ---------------------------------------------------------------- counts
# Counted is the work the algorithm needs (bench/flops.py): attention up to
# each token's own position, logits over the vocabulary at the last position
# of a prompt, and of the routed experts the share a token is expected to
# reach here, top_k * experts_held / n_experts_router (1.5 of 16 held).

def _expected_held(m: dict) -> float:
    return m["top_k"] * m["experts_held"] / m["n_experts_router"]


def _attn_params(m: dict) -> int:
    d, H, r = m["d_model"], m["n_heads"], m["kv_lora_rank"]
    nope, rope, vd = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    return (d * H * (nope + rope) + d * (r + rope) + r * H * (nope + vd)
            + H * vd * d)


def _n_moe(m: dict) -> int:
    return m["n_layers"] - m["first_k_dense"]


def _expert_params(m: dict) -> int:
    return 3 * m["d_model"] * m["d_ff_expert"]


def _shared_params(m: dict) -> int:
    return m["n_shared_experts"] * _expert_params(m)


def _router_params(m: dict) -> int:
    return m["d_model"] * m["n_experts_router"]


def _dense_mlp_params(m: dict) -> int:
    return 3 * m["d_model"] * m["d_ff_dense"]


def _head_params(m: dict) -> int:
    return m["vocab_size"] * m["d_model"]


def param_count(model: dict) -> int:
    """Parameters as held on this chip: embedding and head, the dense
    layers, and per MoE layer attention, router and bias, the held experts
    and the shared experts; RMSNorm scales (two a layer, the latent's, the
    final one)."""
    m = model
    d, L = m["d_model"], m["n_layers"]
    attn = _attn_params(m) + m["kv_lora_rank"] + 2 * d
    moe = (attn + _router_params(m) + m["n_experts_router"]
           + m["experts_held"] * _expert_params(m) + _shared_params(m))
    return (2 * _head_params(m) + d + m["first_k_dense"]
            * (attn + _dense_mlp_params(m)) + _n_moe(m) * moe)


def weight_bytes(model: dict) -> int:
    return param_count(model) * BYTES


def _token_matmul_params(m: dict, attn: int) -> float:
    """Matrix parameters one token multiplies through, with ``attn`` the
    attention's, over every layer: the expected held experts only."""
    return (m["n_layers"] * attn + m["first_k_dense"] * _dense_mlp_params(m)
            + _n_moe(m) * (_router_params(m) + _shared_params(m)
                           + _expected_held(m) * _expert_params(m)))


def prefill_flops(model: dict, prompt_len: int) -> float:
    """A prompt of ``prompt_len`` tokens, the latent expanded into keys and
    values, logits at its last position."""
    m, S = model, prompt_len
    H = m["n_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    attn = 2 * m["n_layers"] * H * (qk + m["v_head_dim"]) * S * (S + 1) // 2
    return (2 * S * _token_matmul_params(m, _attn_params(m)) + attn
            + 2 * _head_params(m))


def _absorbed_attn_params(m: dict) -> int:
    """Per token in decode: wq, wkv_a, the query and output absorptions
    into the latent (in place of the expansion), and wo."""
    d, H, r = m["d_model"], m["n_heads"], m["kv_lora_rank"]
    nope, rope, vd = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    return (d * H * (nope + rope) + d * (r + rope) + H * nope * r + H * r * vd
            + H * vd * d)


def decode_flops(model: dict, pos: int) -> float:
    """One decode step writing position ``pos``, attention absorbed: scores
    over ``pos + 1`` latent entries (c_kv and k_pe) and the weighted sum of
    the c_kv."""
    m = model
    H, r = m["n_heads"], m["kv_lora_rank"]
    keys = pos + 1
    attn = 2 * m["n_layers"] * H * keys * (2 * r + m["qk_rope_head_dim"])
    return (2 * _token_matmul_params(m, _absorbed_attn_params(m)) + attn
            + 2 * _head_params(m))


def latent_bytes_per_token(model: dict) -> int:
    return model["n_layers"] * (model["kv_lora_rank"]
                                + model["qk_rope_head_dim"]) * BYTES


def decode_bytes(model: dict, pos: int) -> float:
    """One decode step at ``pos``: every weight outside the routed experts
    once (the embedding's one row), the shared experts, the expected held
    experts of each MoE layer, the head, and the latent cache read up to
    ``pos`` with the entry written."""
    m = model
    d = m["d_model"]
    held_all = _n_moe(m) * m["experts_held"] * _expert_params(m)
    weights = (param_count(m) - held_all - _head_params(m) + d
               + _n_moe(m) * _expected_held(m) * _expert_params(m))
    return weights * BYTES + latent_bytes_per_token(m) * (pos + 1)
