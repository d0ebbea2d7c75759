"""Unified model API over the 10-arch zoo.

Entry points (all pure functions over (cfg, params, ...)):

  init_params(cfg, key)                  -> params pytree
  forward(cfg, params, batch)            -> (logits, aux)     [training path]
  loss_fn(cfg, params, batch)            -> (loss, metrics)
  init_cache(cfg, batch, max_len)        -> decode cache pytree (zeros)
  prefill(cfg, params, batch, max_len)   -> (logits, cache)
  decode_step(cfg, params, cache, token, pos) -> (logits, cache)
                          [moe_counts=True: (logits, cache, counts)]
  decode_step_rows(cfg, params, caches, tokens, positions)
                                         -> (logits, caches)  [dense rows]

``batch`` is a dict: {"tokens": (B,S) int32, "labels": (B,S) int32,
optional "frontend": (B, S_src, D) precomputed modality embeddings (vlm/audio)}.

Layers are stacked along a leading axis and iterated with ``lax.scan``
(MaxText-style) so HLO stays compact for 100-layer models; bodies are wrapped
in ``jax.checkpoint`` per ``cfg.remat_policy``. A MoE model with leading
dense layers (``first_k_dense``) has two stacks, ``dense_layers`` then
``layers``, each scanned in turn. Attention is GQA, or MLA
(``models/mla.py``) where ``cfg.kv_lora_rank`` is set; an untied model reads
its logits from ``lm_head``.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (
    ModelConfig, DENSE, MOE, HYBRID, SSM, ENCDEC, VLM,
)
from repro.models import layers as L
from repro.models import mla as A
from repro.models import moe as M
from repro.models import mamba as S
from repro.models import partitioning as part

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# remat
# ---------------------------------------------------------------------------

def _maybe_remat(cfg: ModelConfig, fn):
    if cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "dots":
        pol = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=pol)
    return jax.checkpoint(fn)  # "nothing": save nothing


# ---------------------------------------------------------------------------
# per-layer init
# ---------------------------------------------------------------------------

def _init_attn_layer(cfg: ModelConfig, key, use_moe: bool,
                     d_ff: Optional[int] = None) -> Params:
    k1, k2 = jax.random.split(key)
    attn = A.init_mla(cfg, k1) if cfg.kv_lora_rank else L.init_attention(cfg, k1)
    p = {"ln1": L.init_norm(cfg), "attn": attn, "ln2": L.init_norm(cfg)}
    p["ffn"] = M.init_moe(cfg, k2) if use_moe else L.init_mlp(cfg, k2, d_ff)
    return p


def _stacks(cfg: ModelConfig) -> Tuple[Tuple[str, str, int], ...]:
    """The attention-family trunk's layer stacks, in order: (params key,
    cache key, layers). Leading dense layers stack apart from the rest."""
    if cfg.first_k_dense:
        return (("dense_layers", "dense_attn", cfg.first_k_dense),
                ("layers", "attn", cfg.n_layers - cfg.first_k_dense))
    return (("layers", "attn", cfg.n_layers),)


def _init_mamba_layer(cfg: ModelConfig, key, with_ffn: bool, use_moe: bool) -> Params:
    k1, k2 = jax.random.split(key)
    p = {"ln1": L.init_norm(cfg), "mamba": S.init_mamba(cfg, k1)}
    if with_ffn:
        p["ln2"] = L.init_norm(cfg)
        p["ffn"] = M.init_moe(cfg, k2) if use_moe else L.init_mlp(cfg, k2)
    return p


def _init_cross_layer(cfg: ModelConfig, key, gated: bool) -> Params:
    k1, k2 = jax.random.split(key)
    p = {"ln1": L.init_norm(cfg), "xattn": L.init_attention(cfg, k1),
         "ln2": L.init_norm(cfg), "ffn": L.init_mlp(cfg, k2)}
    if gated:
        p["gate_attn"] = jnp.zeros((), cfg.pdtype)
        p["gate_mlp"] = jnp.zeros((), cfg.pdtype)
    return p


def _stack(init_fn, key, n: int):
    return jax.vmap(init_fn)(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# per-layer apply (training/prefill path; cache-producing variants below)
# ---------------------------------------------------------------------------

def _apply_attn_layer(cfg: ModelConfig, p: Params, x, positions, *,
                      causal=True, rope=True, kv_out=False):
    h = L.apply_norm(cfg, p["ln1"], x)
    if cfg.kv_lora_rank:
        x = x + A.attend(cfg, p["attn"], h, positions)
    else:
        q, k, v = L.qkv_project(cfg, p["attn"], h, positions, rope=rope)
        o = L.attention_core(cfg, q, k, v, causal=causal)
        x = x + L.attention_out(cfg, p["attn"], o)
    h = L.apply_norm(cfg, p["ln2"], x)
    aux = jnp.zeros((), jnp.float32)
    if isinstance(p["ffn"], dict) and "router" in p["ffn"]:
        f, aux = M.apply_moe(cfg, p["ffn"], h)
    else:
        f = L.apply_mlp(p["ffn"], h)
    x = _res(cfg, x + f)
    if kv_out:
        return x, aux, (k, v)
    return x, aux


def _apply_mamba_layer(cfg: ModelConfig, p: Params, x, *, state_out=False):
    h = L.apply_norm(cfg, p["ln1"], x)
    if state_out:
        o, st = S.apply_mamba(cfg, p["mamba"], h, return_state=True)
    else:
        o, st = S.apply_mamba(cfg, p["mamba"], h), None
    x = x + o
    aux = jnp.zeros((), jnp.float32)
    if "ffn" in p:
        h = L.apply_norm(cfg, p["ln2"], x)
        if "router" in p["ffn"]:
            f, aux = M.apply_moe(cfg, p["ffn"], h)
        else:
            f = L.apply_mlp(p["ffn"], h)
        x = x + f
    x = _res(cfg, x)
    if state_out:
        return x, aux, st
    return x, aux


def _apply_cross_layer(cfg: ModelConfig, p: Params, x, ctx_kv, *, kv_out=False):
    """ctx_kv: (k, v) precomputed from context; gated residuals if present."""
    h = L.apply_norm(cfg, p["ln1"], x)
    q = (h @ p["xattn"]["wq"].astype(h.dtype)).reshape(
        *h.shape[:2], cfg.n_heads, cfg.head_dim)
    k, v = ctx_kv
    o = L.attention_core(cfg, q, k, v, causal=False)
    o = L.attention_out(cfg, p["xattn"], o)
    if "gate_attn" in p:
        o = jnp.tanh(p["gate_attn"].astype(o.dtype)) * o
    x = x + o
    h = L.apply_norm(cfg, p["ln2"], x)
    f = L.apply_mlp(p["ffn"], h)
    if "gate_mlp" in p:
        f = jnp.tanh(p["gate_mlp"].astype(f.dtype)) * f
    x = _res(cfg, x + f)
    if kv_out:
        return x, (k, v)
    return x


def _cross_kv(cfg: ModelConfig, p: Params, ctx):
    """Project context (B, S_ctx, D) to cross-attention K/V (no RoPE)."""
    B, Sc, _ = ctx.shape
    k = (ctx @ p["xattn"]["wk"].astype(ctx.dtype)).reshape(B, Sc, cfg.n_kv_heads, cfg.head_dim)
    v = (ctx @ p["xattn"]["wv"].astype(ctx.dtype)).reshape(B, Sc, cfg.n_kv_heads, cfg.head_dim)
    return k, v


# ---------------------------------------------------------------------------
# init_params
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key) -> Params:
    keys = jax.random.split(key, 8)
    params: Params = {
        "embed": L.embed_init(keys[0], (cfg.padded_vocab, cfg.d_model), cfg.pdtype),
        "final_norm": L.init_norm(cfg),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.embed_init(keys[7], (cfg.padded_vocab, cfg.d_model),
                                         cfg.pdtype)
    fam = cfg.family
    if fam in (DENSE, MOE):
        if cfg.first_k_dense:
            params["dense_layers"] = _stack(
                lambda k: _init_attn_layer(cfg, k, False, cfg.d_ff_dense),
                keys[2], cfg.first_k_dense)
        use_moe = cfg.n_experts > 0
        params["layers"] = _stack(
            lambda k: _init_attn_layer(cfg, k, use_moe), keys[1],
            cfg.n_layers - cfg.first_k_dense)
    elif fam == SSM:
        params["layers"] = _stack(
            lambda k: _init_mamba_layer(cfg, k, with_ffn=False, use_moe=False),
            keys[1], cfg.n_layers)
    elif fam == HYBRID:
        period, moe_every = cfg.attn_every, cfg.moe_every
        attn_idx = period - 1 if period else 0

        def init_period(k):
            ks = jax.random.split(k, period)
            blk = {}
            for i in range(period):
                use_moe = cfg.n_experts > 0 and (i % moe_every == moe_every - 1)
                if i == attn_idx:
                    blk[f"sub{i}"] = _init_attn_layer(cfg, ks[i], use_moe)
                else:
                    blk[f"sub{i}"] = _init_mamba_layer(cfg, ks[i], True, use_moe)
            return blk

        params["blocks"] = _stack(init_period, keys[1], cfg.n_layers // period)
    elif fam == VLM:
        period = cfg.cross_attn_every

        def init_period(k):
            ks = jax.random.split(k, period)
            blk = {f"self{i}": _init_attn_layer(cfg, ks[i], False)
                   for i in range(period - 1)}
            blk["cross"] = _init_cross_layer(cfg, ks[-1], gated=True)
            return blk

        params["blocks"] = _stack(init_period, keys[1], cfg.n_layers // period)
    elif fam == ENCDEC:
        def init_enc(k):
            return _init_attn_layer(cfg, k, False)

        def init_dec(k):
            k1, k2 = jax.random.split(k)
            p = _init_attn_layer(cfg, k1, False)
            kc1, kc2 = jax.random.split(k2)
            p["ln_x"] = L.init_norm(cfg)
            p["xattn"] = L.init_attention(cfg, kc1)
            return p

        params["enc_layers"] = _stack(init_enc, keys[1], cfg.n_enc_layers)
        params["dec_layers"] = _stack(init_dec, keys[2], cfg.n_dec_layers)
        params["enc_final_norm"] = L.init_norm(cfg)
    else:
        raise ValueError(fam)
    return params


# ---------------------------------------------------------------------------
# forward (training)
# ---------------------------------------------------------------------------

def _logits(cfg: ModelConfig, params: Params, x) -> jnp.ndarray:
    x = L.apply_norm(cfg, params["final_norm"], x)
    head = params["embed" if cfg.tie_embeddings else "lm_head"]
    logits = part.shard_logits(
        jnp.einsum("bsd,vd->bsv", x, head.astype(x.dtype)))
    if cfg.padded_vocab != cfg.vocab_size:  # mask Megatron-style vocab pad
        iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
        logits = jnp.where(iota < cfg.vocab_size, logits, -1e30)
    return logits


def _embed(cfg: ModelConfig, params: Params, tokens) -> jnp.ndarray:
    return _res(cfg, params["embed"][tokens].astype(cfg.cdtype))


def _res(cfg: ModelConfig, x) -> jnp.ndarray:
    """Residual-stream constraint: sequence-parallel for attention families."""
    return part.shard_residual(x, allow_seq=cfg.family not in (SSM, HYBRID))


def forward(cfg: ModelConfig, params: Params, batch: Dict[str, jnp.ndarray]
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    fam = cfg.family
    if fam == ENCDEC:
        return _forward_encdec(cfg, params, batch)

    tokens = batch["tokens"]
    B, Ssz = tokens.shape
    x = _embed(cfg, params, tokens)
    positions = jnp.arange(Ssz)[None, :]
    aux0 = jnp.zeros((), jnp.float32)

    if fam in (DENSE, MOE):
        def body(carry, layer):
            x, aux = carry
            x, a = _apply_attn_layer(cfg, layer, x, positions)
            return (x, aux + a), None

        aux = aux0
        for pk, _, _ in _stacks(cfg):
            (x, aux), _ = jax.lax.scan(_maybe_remat(cfg, body), (x, aux), params[pk])
    elif fam == SSM:
        def body(carry, layer):
            x, aux = carry
            x, a = _apply_mamba_layer(cfg, layer, x)
            return (x, aux + a), None

        (x, aux), _ = jax.lax.scan(_maybe_remat(cfg, body), (x, aux0), params["layers"])
    elif fam == HYBRID:
        period = cfg.attn_every
        attn_idx = period - 1
        # nested remat: the scan body saves only the period carry; each
        # SUBLAYER is checkpointed too, so the backward pass of one period
        # holds one sublayer's internals at a time (8 sublayers of d=8192
        # would otherwise be live simultaneously).
        attn_fn = _maybe_remat(cfg, lambda pp, xx: _apply_attn_layer(
            cfg, pp, xx, positions, rope=False))
        mamba_fn = _maybe_remat(cfg, lambda pp, xx: _apply_mamba_layer(cfg, pp, xx))

        def body(carry, blk):
            x, aux = carry
            for i in range(period):
                p = blk[f"sub{i}"]
                x, a = (attn_fn if i == attn_idx else mamba_fn)(p, x)
                aux = aux + a
            return (x, aux), None

        (x, aux), _ = jax.lax.scan(_maybe_remat(cfg, body), (x, aux0), params["blocks"])
    elif fam == VLM:
        frontend = batch["frontend"].astype(cfg.cdtype)
        period = cfg.cross_attn_every
        self_fn = _maybe_remat(cfg, lambda pp, xx: _apply_attn_layer(
            cfg, pp, xx, positions))
        cross_fn = _maybe_remat(cfg, lambda pp, xx: _apply_cross_layer(
            cfg, pp, xx, _cross_kv(cfg, pp, frontend)))

        def body(carry, blk):
            x, aux = carry
            for i in range(period - 1):
                x, a = self_fn(blk[f"self{i}"], x)
                aux = aux + a
            x = cross_fn(blk["cross"], x)
            return (x, aux), None

        (x, aux), _ = jax.lax.scan(_maybe_remat(cfg, body), (x, aux0), params["blocks"])
    else:
        raise ValueError(fam)
    return _logits(cfg, params, x), aux


def _encode(cfg: ModelConfig, params: Params, frontend) -> jnp.ndarray:
    x = frontend.astype(cfg.cdtype)
    positions = jnp.arange(x.shape[1])[None, :]

    def body(carry, layer):
        x, aux = carry
        x, a = _apply_attn_layer(cfg, layer, x, positions, causal=False)
        return (x, aux + a), None

    (x, _), _ = jax.lax.scan(_maybe_remat(cfg, body),
                             (x, jnp.zeros((), jnp.float32)), params["enc_layers"])
    return L.apply_norm(cfg, params["enc_final_norm"], x)


def _forward_encdec(cfg: ModelConfig, params: Params, batch):
    enc_out = _encode(cfg, params, batch["frontend"])
    tokens = batch["tokens"]
    B, Ssz = tokens.shape
    x = _embed(cfg, params, tokens)
    positions = jnp.arange(Ssz)[None, :]

    def body(carry, layer):
        x, aux = carry
        h = L.apply_norm(cfg, layer["ln1"], x)
        q, k, v = L.qkv_project(cfg, layer["attn"], h, positions)
        o = L.attention_core(cfg, q, k, v, causal=True)
        x = x + L.attention_out(cfg, layer["attn"], o)
        # cross attention
        h = L.apply_norm(cfg, layer["ln_x"], x)
        q = (h @ layer["xattn"]["wq"].astype(h.dtype)).reshape(
            B, Ssz, cfg.n_heads, cfg.head_dim)
        ck, cv = _cross_kv(cfg, {"xattn": layer["xattn"]}, enc_out)
        o = L.attention_core(cfg, q, ck, cv, causal=False)
        x = x + L.attention_out(cfg, layer["xattn"], o)
        h = L.apply_norm(cfg, layer["ln2"], x)
        x = _res(cfg, x + L.apply_mlp(layer["ffn"], h))
        return (x, aux), None

    (x, aux), _ = jax.lax.scan(_maybe_remat(cfg, body),
                               (x, jnp.zeros((), jnp.float32)), params["dec_layers"])
    return _logits(cfg, params, x), aux


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def softmax_cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """CE that stays vocab-sharded: no gather over the (model-sharded) vocab
    dim (take_along_axis would force XLA to all-gather full fp32 logits —
    measured 13 GiB/device on olmo train_4k). The label logit is extracted
    with an iota-compare that fuses into the reduction."""
    logits_f = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(logits_f, axis=-1, keepdims=True))
    shifted = logits_f - m
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    label_logit = jnp.sum(
        jnp.where(vocab_iota == labels[..., None].astype(jnp.int32), shifted, 0.0),
        axis=-1)
    return lse - label_logit


def loss_fn(cfg: ModelConfig, params: Params, batch
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    logits, aux = forward(cfg, params, batch)
    nll = softmax_cross_entropy(logits, batch["labels"])
    loss = jnp.mean(nll)
    total = loss + cfg.router_aux_coef * aux
    return total, {"loss": loss, "aux_loss": aux,
                   "perplexity": jnp.exp(jnp.minimum(loss, 20.0))}


# ---------------------------------------------------------------------------
# decode caches
# ---------------------------------------------------------------------------

def _attn_cache_zeros(cfg: ModelConfig, B: int, T: int):
    if cfg.kv_lora_rank:
        return A.cache_zeros(cfg, B, T)
    shape = (B, T, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.cdtype), "v": jnp.zeros(shape, cfg.cdtype)}


def _mamba_cache_zeros(cfg: ModelConfig, B: int):
    ci = cfg.d_inner + 2 * cfg.d_state
    return {"ssm": jnp.zeros((B, cfg.n_ssm_heads, cfg.d_state, cfg.ssm_headdim), jnp.float32),
            "conv": jnp.zeros((B, cfg.d_conv - 1, ci), cfg.cdtype)}


def init_cache(cfg: ModelConfig, B: int, max_len: int,
               n_ctx: Optional[int] = None) -> Params:
    """Zero-filled decode cache. ``n_ctx`` = cross-attention context length."""
    fam = cfg.family

    def stacked(fn, n):
        return jax.tree.map(lambda *xs: jnp.stack(xs), *([fn()] * n)) if n > 1 else \
            jax.tree.map(lambda x: x[None], fn())

    if fam in (DENSE, MOE):
        return {ck: stacked(lambda: _attn_cache_zeros(cfg, B, max_len), n)
                for _, ck, n in _stacks(cfg)}
    if fam == SSM:
        return {"mamba": stacked(lambda: _mamba_cache_zeros(cfg, B), cfg.n_layers)}
    if fam == HYBRID:
        period = cfg.attn_every
        nP = cfg.n_layers // period
        return {
            "attn": stacked(lambda: _attn_cache_zeros(cfg, B, max_len), nP),
            "mamba": stacked(
                lambda: jax.tree.map(lambda *xs: jnp.stack(xs),
                                     *([_mamba_cache_zeros(cfg, B)] * (period - 1))), nP),
        }
    if fam == VLM:
        period = cfg.cross_attn_every
        nP = cfg.n_layers // period
        nc = n_ctx or cfg.n_frontend_tokens
        xshape = (nP, B, nc, cfg.n_kv_heads, cfg.head_dim)
        return {
            "self": stacked(
                lambda: jax.tree.map(lambda *xs: jnp.stack(xs),
                                     *([_attn_cache_zeros(cfg, B, max_len)] * (period - 1))), nP),
            "xk": jnp.zeros(xshape, cfg.cdtype),
            "xv": jnp.zeros(xshape, cfg.cdtype),
        }
    if fam == ENCDEC:
        nc = n_ctx if n_ctx is not None else max_len
        xshape = (cfg.n_dec_layers, B, nc, cfg.n_kv_heads, cfg.head_dim)
        return {
            "attn": stacked(lambda: _attn_cache_zeros(cfg, B, max_len), cfg.n_dec_layers),
            "xk": jnp.zeros(xshape, cfg.cdtype),
            "xv": jnp.zeros(xshape, cfg.cdtype),
        }
    raise ValueError(fam)


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------

def _write_kv(cache_layer, k, v, start: int):
    """In-place KV append. Layout pinned on both sides of the DUS — see
    layers.decode_attention_core for the oscillation this prevents."""
    k_new = jax.lax.dynamic_update_slice_in_dim(
        part.shard_cache(cache_layer["k"]),
        k.astype(cache_layer["k"].dtype), start, axis=1)
    v_new = jax.lax.dynamic_update_slice_in_dim(
        part.shard_cache(cache_layer["v"]),
        v.astype(cache_layer["v"].dtype), start, axis=1)
    return {"k": part.shard_cache(k_new), "v": part.shard_cache(v_new)}


def prefill_attn_layer(cfg: ModelConfig, layer: Params, cl: Params,
                       x, positions) -> Tuple[jnp.ndarray, Params]:
    """One attention-family trunk layer of prefill: (x, kv-cache slot) ->
    (x', primed slot). Both the lax.scan prefill body and the streaming
    per-layer path (DESIGN.md §9) call this exact function, so streamed
    generation is mathematically identical to the batch path."""
    h = L.apply_norm(cfg, layer["ln1"], x)
    if cfg.kv_lora_rank:
        o, new = A.prefill(cfg, layer["attn"], h, positions)
        x, write = x + o, A.write
    else:
        q, k, v = L.qkv_project(cfg, layer["attn"], h, positions)
        o = L.attention_core(cfg, q, k, v, causal=True)
        x = x + L.attention_out(cfg, layer["attn"], o)
        new, write = (k, v), _write_kv
    h = L.apply_norm(cfg, layer["ln2"], x)
    if "router" in layer["ffn"]:
        f, _ = M.apply_moe(cfg, layer["ffn"], h)
    else:
        f = L.apply_mlp(layer["ffn"], h)
    return _res(cfg, x + f), write(cl, *new, 0)


def prefill(cfg: ModelConfig, params: Params, batch, max_len: int
            ) -> Tuple[jnp.ndarray, Params]:
    """Run the full prompt, return last-position logits + primed cache."""
    fam = cfg.family
    tokens = batch["tokens"]
    B, Ssz = tokens.shape
    cache = init_cache(cfg, B, max_len,
                       n_ctx=(batch["frontend"].shape[1]
                              if fam in (VLM, ENCDEC) and "frontend" in batch else None))
    x = _embed(cfg, params, tokens)
    positions = jnp.arange(Ssz)[None, :]

    if fam in (DENSE, MOE):
        def body(x, xs):
            layer, cl = xs
            return prefill_attn_layer(cfg, layer, cl, x, positions)

        primed = {}
        for pk, ck, _ in _stacks(cfg):
            x, primed[ck] = jax.lax.scan(body, x, (params[pk], cache[ck]))
        cache = primed
    elif fam == SSM:
        def body(x, xs):
            layer, cl = xs
            h = L.apply_norm(cfg, layer["ln1"], x)
            o, (ssm, conv) = S.apply_mamba(cfg, layer["mamba"], h, return_state=True)
            return x + o, {"ssm": ssm.astype(cl["ssm"].dtype),
                           "conv": conv.astype(cl["conv"].dtype)}

        x, mamba_cache = jax.lax.scan(body, x, (params["layers"], cache["mamba"]))
        cache = {"mamba": mamba_cache}
    elif fam == HYBRID:
        period = cfg.attn_every
        attn_idx = period - 1

        def body(x, xs):
            blk, cl = xs
            new_m = []
            kv = None
            mi = 0
            for i in range(period):
                p = blk[f"sub{i}"]
                if i == attn_idx:
                    h = L.apply_norm(cfg, p["ln1"], x)
                    q, k, v = L.qkv_project(cfg, p["attn"], h, positions, rope=False)
                    o = L.attention_core(cfg, q, k, v, causal=True)
                    x = x + L.attention_out(cfg, p["attn"], o)
                    h = L.apply_norm(cfg, p["ln2"], x)
                    if "router" in p["ffn"]:
                        f, _ = M.apply_moe(cfg, p["ffn"], h)
                    else:
                        f = L.apply_mlp(p["ffn"], h)
                    x = x + f
                    kv = _write_kv(cl["attn"], k, v, 0)
                else:
                    h = L.apply_norm(cfg, p["ln1"], x)
                    o, (ssm, conv) = S.apply_mamba(cfg, p["mamba"], h, return_state=True)
                    x = x + o
                    if "ffn" in p:
                        h = L.apply_norm(cfg, p["ln2"], x)
                        if "router" in p["ffn"]:
                            f, _ = M.apply_moe(cfg, p["ffn"], h)
                        else:
                            f = L.apply_mlp(p["ffn"], h)
                        x = x + f
                    new_m.append({"ssm": ssm.astype(jnp.float32),
                                  "conv": conv.astype(cfg.cdtype)})
                    mi += 1
            mstack = jax.tree.map(lambda *xs: jnp.stack(xs), *new_m)
            return x, {"attn": kv, "mamba": mstack}

        x, cache = jax.lax.scan(body, x, (params["blocks"], cache))
    elif fam == VLM:
        frontend = batch["frontend"].astype(cfg.cdtype)
        period = cfg.cross_attn_every

        def body(x, xs):
            blk, cl = xs
            kvs = []
            for i in range(period - 1):
                p = blk[f"self{i}"]
                h = L.apply_norm(cfg, p["ln1"], x)
                q, k, v = L.qkv_project(cfg, p["attn"], h, positions)
                o = L.attention_core(cfg, q, k, v, causal=True)
                x = x + L.attention_out(cfg, p["attn"], o)
                h = L.apply_norm(cfg, p["ln2"], x)
                x = _res(cfg, x + L.apply_mlp(p["ffn"], h))
                kvs.append(_write_kv(jax.tree.map(lambda a: a[i], cl["self"]), k, v, 0))
            ck, cv = _cross_kv(cfg, blk["cross"], frontend)
            x = _apply_cross_layer(cfg, blk["cross"], x, (ck, cv))
            return x, {"self": jax.tree.map(lambda *xs: jnp.stack(xs), *kvs),
                       "xk": ck.astype(cfg.cdtype), "xv": cv.astype(cfg.cdtype)}

        x, cache = jax.lax.scan(
            body, x, (params["blocks"],
                      {"self": cache["self"]}))
    elif fam == ENCDEC:
        enc_out = _encode(cfg, params, batch["frontend"])

        def body(x, xs):
            layer, cl = xs
            h = L.apply_norm(cfg, layer["ln1"], x)
            q, k, v = L.qkv_project(cfg, layer["attn"], h, positions)
            o = L.attention_core(cfg, q, k, v, causal=True)
            x = x + L.attention_out(cfg, layer["attn"], o)
            h = L.apply_norm(cfg, layer["ln_x"], x)
            q = (h @ layer["xattn"]["wq"].astype(h.dtype)).reshape(
                B, Ssz, cfg.n_heads, cfg.head_dim)
            ck, cv = _cross_kv(cfg, {"xattn": layer["xattn"]}, enc_out)
            o = L.attention_core(cfg, q, ck, cv, causal=False)
            x = x + L.attention_out(cfg, layer["xattn"], o)
            h = L.apply_norm(cfg, layer["ln2"], x)
            x = x + L.apply_mlp(layer["ffn"], h)
            return x, {**_write_kv(cl, k, v, 0),
                       "xk": ck.astype(cfg.cdtype), "xv": cv.astype(cfg.cdtype)}

        x, dec_cache = jax.lax.scan(body, x, (params["dec_layers"], cache["attn"]))
        cache = {"attn": {"k": dec_cache["k"], "v": dec_cache["v"]},
                 "xk": dec_cache["xk"], "xv": dec_cache["xv"]}
    else:
        raise ValueError(fam)

    logits = _logits(cfg, params, x[:, -1:, :])
    return logits[:, 0], cache


# ---------------------------------------------------------------------------
# decode step
# ---------------------------------------------------------------------------

def _attn_decode(cfg: ModelConfig, p: Params, x, cl, pos, *, rope=True,
                 counts=False, layer=None):
    """x: (B,1,D); cl: one layer's KV cache. Returns (x, new_cache), and
    with ``counts`` the MoE layer's routing counts after them. ``layer``:
    p's expert banks are the whole stack's, this layer's at that index."""
    B = x.shape[0]
    h = L.apply_norm(cfg, p["ln1"], x)
    if cfg.kv_lora_rank:
        o, cl = A.decode(cfg, p["attn"], h, cl, pos)
        x = x + o
    else:
        positions = jnp.full((1, 1), pos, jnp.int32)
        q, k, v = L.qkv_project(cfg, p["attn"], h, positions, rope=rope)
        cl = _write_kv(cl, k, v, pos)
        kv_len = jnp.full((B,), pos + 1, jnp.int32)
        o = L.decode_attention_core(cfg, q, cl["k"], cl["v"], kv_len)
        x = x + L.attention_out(cfg, p["attn"], o)
    h = L.apply_norm(cfg, p["ln2"], x)
    if counts:
        f, _, c = M.apply_moe(cfg, p["ffn"], h, counts=True, layer=layer)
        return x + f, cl, c
    if "router" in p["ffn"]:
        f, _ = M.apply_moe(cfg, p["ffn"], h, layer=layer)
    else:
        f = L.apply_mlp(p["ffn"], h)
    return x + f, cl


def _mamba_decode(cfg: ModelConfig, p: Params, x, cl):
    h = L.apply_norm(cfg, p["ln1"], x)
    o, ssm, conv = S.mamba_decode_step(cfg, p["mamba"], h, cl["ssm"], cl["conv"])
    x = x + o
    if "ffn" in p:
        h = L.apply_norm(cfg, p["ln2"], x)
        if "router" in p["ffn"]:
            f, _ = M.apply_moe(cfg, p["ffn"], h)
        else:
            f = L.apply_mlp(p["ffn"], h)
        x = x + f
    return x, {"ssm": ssm, "conv": conv}


def decode_step(cfg: ModelConfig, params: Params, cache: Params,
                token: jnp.ndarray, pos: jnp.ndarray, moe_counts: bool = False):
    """One decode step. token: (B,) int32; pos: scalar int32 (cache length so far).

    Returns (logits (B, V), new_cache); with ``moe_counts`` (MoE models)
    also (3,) int32, summed over the MoE layers: held experts that took a
    token, assignments that landed on held experts, and held experts whose
    weights were read.

    Where an expert layer reads only the experts its B tokens reach
    (``moe.reads_touched``), the MoE stack's scan leaves the expert banks
    out of what it slices and each layer reads its experts from the whole
    ``(L, n, ...)`` banks at its index: a bank sliced per layer by the scan
    is copied whole.
    """
    fam = cfg.family
    x = part.shard_btd(params["embed"][token][:, None, :].astype(cfg.cdtype))  # (B,1,D)

    if fam in (DENSE, MOE):
        def body(x, xs):
            layer, cl = xs
            x, ncl = _attn_decode(cfg, layer, x, cl, pos)
            return x, ncl

        def counted(x, xs):
            layer, cl = xs
            x, ncl, c = _attn_decode(cfg, layer, x, cl, pos, counts=True)
            return x, (ncl, c)

        def touched(x, xs, banks):
            layer, cl, i = xs
            layer = {**layer, "ffn": {**layer["ffn"], **banks}}
            x, ncl, c = _attn_decode(cfg, layer, x, cl, pos, counts=True,
                                     layer=i)
            return x, (ncl, c)

        new_cache = {}
        for pk, ck, n in _stacks(cfg):
            if pk == "layers" and M.reads_touched(cfg, x.shape[0]):
                ffn = params[pk]["ffn"]
                banks = {k: ffn[k] for k in M.BANKS}
                rest = {**params[pk], "ffn": {k: v for k, v in ffn.items()
                                              if k not in banks}}
                x, (new_cache[ck], c) = jax.lax.scan(
                    functools.partial(touched, banks=banks), x,
                    (rest, cache[ck], jnp.arange(n)))
                counts = jnp.sum(c, axis=0)
            elif moe_counts and pk == "layers":
                x, (new_cache[ck], c) = jax.lax.scan(
                    counted, x, (params[pk], cache[ck]))
                counts = jnp.sum(c, axis=0)
            else:
                x, new_cache[ck] = jax.lax.scan(body, x, (params[pk], cache[ck]))
    elif fam == SSM:
        def body(x, xs):
            layer, cl = xs
            x, ncl = _mamba_decode(cfg, layer, x, cl)
            return x, ncl

        x, new_m = jax.lax.scan(body, x, (params["layers"], cache["mamba"]))
        new_cache = {"mamba": new_m}
    elif fam == HYBRID:
        period = cfg.attn_every
        attn_idx = period - 1

        def body(x, xs):
            blk, cl = xs
            new_m, kv = [], None
            mi = 0
            for i in range(period):
                p = blk[f"sub{i}"]
                if i == attn_idx:
                    x, kv = _attn_decode(cfg, p, x, cl["attn"], pos, rope=False)
                else:
                    sub_cl = jax.tree.map(lambda a: a[mi], cl["mamba"])
                    x, ncl = _mamba_decode(cfg, p, x, sub_cl)
                    new_m.append(ncl)
                    mi += 1
            mstack = jax.tree.map(lambda *xs: jnp.stack(xs), *new_m)
            return x, {"attn": kv, "mamba": mstack}

        x, new_cache = jax.lax.scan(body, x, (params["blocks"], cache))
    elif fam == VLM:
        period = cfg.cross_attn_every

        def body(x, xs):
            blk, cl = xs
            kvs = []
            for i in range(period - 1):
                p = blk[f"self{i}"]
                sub_cl = jax.tree.map(lambda a: a[i], cl["self"])
                x, ncl = _attn_decode(cfg, p, x, sub_cl, pos)
                kvs.append(ncl)
            x = _apply_cross_layer(cfg, blk["cross"], x, (cl["xk"], cl["xv"]))
            return x, {"self": jax.tree.map(lambda *xs: jnp.stack(xs), *kvs),
                       "xk": cl["xk"], "xv": cl["xv"]}

        x, new_cache = jax.lax.scan(body, x, (params["blocks"], cache))
    elif fam == ENCDEC:
        def body(x, xs):
            layer, cl = xs
            B = x.shape[0]
            h = L.apply_norm(cfg, layer["ln1"], x)
            positions = jnp.full((1, 1), pos, jnp.int32)
            q, k, v = L.qkv_project(cfg, layer["attn"], h, positions)
            kv = _write_kv({"k": cl["k"], "v": cl["v"]}, k, v, pos)
            kv_len = jnp.full((B,), pos + 1, jnp.int32)
            o = L.decode_attention_core(cfg, q, kv["k"], kv["v"], kv_len)
            x = x + L.attention_out(cfg, layer["attn"], o)
            h = L.apply_norm(cfg, layer["ln_x"], x)
            q = (h @ layer["xattn"]["wq"].astype(h.dtype)).reshape(
                B, 1, cfg.n_heads, cfg.head_dim)
            o = L.attention_core(cfg, q, cl["xk"], cl["xv"], causal=False)
            x = x + L.attention_out(cfg, layer["xattn"], o)
            h = L.apply_norm(cfg, layer["ln2"], x)
            x = x + L.apply_mlp(layer["ffn"], h)
            return x, {**kv, "xk": cl["xk"], "xv": cl["xv"]}

        x, dec = jax.lax.scan(body, x, (params["dec_layers"],
                                        {"k": cache["attn"]["k"], "v": cache["attn"]["v"],
                                         "xk": cache["xk"], "xv": cache["xv"]}))
        new_cache = {"attn": {"k": dec["k"], "v": dec["v"]},
                     "xk": dec["xk"], "xv": dec["xv"]}
    else:
        raise ValueError(fam)

    logits = _logits(cfg, params, x)
    if moe_counts:
        return logits[:, 0], new_cache, counts
    return logits[:, 0], new_cache


def decode_step_rows(cfg: ModelConfig, params: Params, caches, tokens,
                     positions) -> Tuple[jnp.ndarray, Tuple[Params, ...]]:
    """One decode step over k rows of one model, each with its own cache.

    caches: k B=1 dense caches as ``prefill`` made them, each at its own
    ``max_len``; tokens: (k,) int32; positions: (k,) int32, each row's
    cache length so far. The rows' hidden states are stacked, so the norms
    and the QKV, output and MLP matrices are read once for all of them;
    RoPE, the KV write and attention run per row, against that row's cache
    alone. Row r's result depends only on row r's inputs.

    Returns (logits (k, V), the k new caches). Dense GQA only: an MoE
    layer's expert capacity can couple the rows of one batch, and an MLA
    layer's latent cache has no per-row path here.
    """
    if cfg.family != DENSE or cfg.n_experts:
        raise ValueError(f"decode_step_rows serves dense layers, not "
                         f"{cfg.family!r} with {cfg.n_experts} experts: an "
                         f"MoE layer's routing can couple the rows")
    if cfg.kv_lora_rank or cfg.first_k_dense:
        raise ValueError(f"decode_step_rows has no per-row path for MLA's "
                         f"latent cache or a mixed layout ({cfg.name})")
    k = len(caches)
    x = part.shard_btd(params["embed"][tokens][:, None, :].astype(cfg.cdtype))

    def body(x, xs):
        p, cls = xs
        h = L.apply_norm(cfg, p["ln1"], x)
        q, kk, v = L.qkv_project(cfg, p["attn"], h, None)
        new, outs = [], []
        for r in range(k):
            # RoPE row by row, as decode_step applies it: rotating the
            # stacked rows lets XLA fuse the rotation into the projection
            # with a transposed copy of wq and wk on every step (v5e)
            at = jnp.full((1, 1), positions[r], jnp.int32)
            qr = L.apply_rope(q[r:r + 1], at, cfg.rope_theta)
            kr = L.apply_rope(kk[r:r + 1], at, cfg.rope_theta)
            cl = _write_kv(cls[r], kr, v[r:r + 1], positions[r])
            outs.append(L.decode_attention_core(
                cfg, qr, cl["k"], cl["v"], positions[r:r + 1] + 1))
            new.append(cl)
        x = x + L.attention_out(cfg, p["attn"], jnp.concatenate(outs))
        h = L.apply_norm(cfg, p["ln2"], x)
        return x + L.apply_mlp(p["ffn"], h), tuple(new)

    x, new = jax.lax.scan(body, x, (params["layers"],
                                    tuple(c["attn"] for c in caches)))
    logits = _logits(cfg, params, x)
    return logits[:, 0], tuple({"attn": c} for c in new)


# ---------------------------------------------------------------------------
# streaming execution (DESIGN.md §9)
# ---------------------------------------------------------------------------
# Per-layer entry points for the attention families (DENSE/MOE): the serving
# engine jits each once and walks the trunk layer by layer, starting as soon
# as the stem + layer 0 windows of a streaming load are resident. Each step
# reuses the exact function the lax.scan bodies run (prefill_attn_layer /
# _attn_decode), so streamed generation matches the batch path token for
# token.

def stream_prefill_embed(cfg: ModelConfig, params: Params, tokens):
    """Stem half of prefill: (B, S) tokens -> residual stream (B, S, D).
    Needs only the stem window (``embed``)."""
    return _embed(cfg, params, tokens)


def stream_prefill_layer(cfg: ModelConfig, layer: Params, x, positions,
                         max_len: int):
    """One trunk layer of prefill; allocates and primes this layer's KV
    slot. Returns (x', cache_layer)."""
    cl = _attn_cache_zeros(cfg, x.shape[0], max_len)
    return prefill_attn_layer(cfg, layer, cl, x, positions)


def stream_logits(cfg: ModelConfig, params: Params, x):
    """Head half: last-position logits (B, V) from the residual stream.
    Needs only the stem window (``final_norm`` + tied ``embed``)."""
    return _logits(cfg, params, x[:, -1:, :])[:, 0]


def stream_decode_embed(cfg: ModelConfig, params: Params, token):
    """Stem half of a decode step: (B,) token -> (B, 1, D)."""
    return part.shard_btd(params["embed"][token][:, None, :].astype(cfg.cdtype))


def stream_decode_layer(cfg: ModelConfig, layer: Params, x, cl, pos):
    """One trunk layer of a decode step. Returns (x', new_cache_layer)."""
    return _attn_decode(cfg, layer, x, cl, pos)


def greedy_generate(cfg: ModelConfig, params: Params, batch,
                    n_steps: int, max_len: int):
    """Prefill + n greedy decode steps (reference path for tests/examples)."""
    logits, cache = prefill(cfg, params, batch, max_len)
    B, Ssz = batch["tokens"].shape
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    toks = [tok]
    for i in range(n_steps - 1):
        logits, cache = decode_step(cfg, params, cache, tok, jnp.int32(Ssz + i))
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        toks.append(tok)
    return jnp.stack(toks, axis=1)
