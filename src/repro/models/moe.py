"""Token-choice top-k Mixture-of-Experts with two execution strategies.

``capacity``  — GShard/Switch-style capacity-bounded scatter → batched einsum
                over (E, C, D) expert buffers. Static shapes, predictable SPMD
                partitioning; default for sharded lowering.
``ragged``    — sort-by-expert + ``jax.lax.ragged_dot`` grouped GEMM. No
                capacity drops; used on CPU smoke paths and as a hillclimb
                candidate on TPU. A call with fewer assignments than held
                experts (``T * top_k < n``, a decode step) loops over the
                held experts its tokens reach instead, reading only their
                weights: ``ragged_dot`` reads every group's, empty or not.

Router: softmax over expert logits, top-k, renormalized combine weights, plus
the standard load-balancing auxiliary loss (Switch Transformer eq. 4). Or, as
DeepSeek-V3 (``router_score="sigmoid"``, ``noaux_tc`` with one group):
float32 logits, sigmoid scores, top-k chosen on score plus a bias, weighted by
the unbiased scores, renormalized and scaled by ``routed_scaling``.

An expert layer may hold one chip's share of the routed experts
(``held_experts`` from ``first_held_expert``): it routes over all of them and
adds only its own experts' part of the result (the ``ragged`` path).
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import partitioning as part
from repro.models.layers import dense_init

Params = Dict[str, jnp.ndarray]


def init_moe(cfg: ModelConfig, key) -> Params:
    ks = jax.random.split(key, 5)
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_held_experts
    p: Params = {
        "router": dense_init(ks[0], (d, cfg.n_experts), jnp.float32, scale=0.02),
        "w_gate": dense_init(ks[1], (e, d, f), cfg.pdtype),
        "w_up": dense_init(ks[2], (e, d, f), cfg.pdtype),
        "w_down": dense_init(ks[3], (e, f, d), cfg.pdtype,
                             scale=1.0 / math.sqrt(f * 2 * cfg.n_layers)),
    }
    if cfg.n_shared_experts:
        sf = f * cfg.n_shared_experts
        kk = jax.random.split(ks[4], 3)
        p["shared_gate"] = dense_init(kk[0], (d, sf), cfg.pdtype)
        p["shared_up"] = dense_init(kk[1], (d, sf), cfg.pdtype)
        p["shared_down"] = dense_init(kk[2], (sf, d), cfg.pdtype)
    if cfg.router_score == "sigmoid":
        p["router_bias"] = jnp.zeros((cfg.n_experts,), jnp.float32)
    return p


def router_topk(cfg: ModelConfig, p: Params, x2d: jnp.ndarray
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (weights (T,k), indices (T,k), aux_loss scalar).

    Router matmul keeps x in bf16 with fp32 ACCUMULATION: upcasting the
    input would make XLA hoist the fp32 convert above the sequence-parallel
    all-gather and ship 2x the bytes (measured on qwen3-moe train)."""
    if cfg.router_score == "sigmoid":
        return _router_sigmoid(cfg, p, x2d)
    # bf16 dot + post-hoc fp32 cast: fp32 ACCUMULATION here would make the
    # VJP emit fp32 cotangents for x, doubling every sequence-parallel
    # boundary collective in the backward pass (measured on qwen3-moe)
    logits = (x2d @ p["router"].astype(x2d.dtype)).astype(jnp.float32)  # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, cfg.top_k)
    topw = topw / jnp.sum(topw, axis=-1, keepdims=True)
    # load-balancing aux: E * sum_e (frac_tokens_e * mean_prob_e)
    E = cfg.n_experts
    onehot = jax.nn.one_hot(topi, E, dtype=jnp.float32)          # (T,k,E)
    frac = jnp.mean(jnp.sum(onehot, axis=1), axis=0)             # (E,)
    mean_prob = jnp.mean(probs, axis=0)                          # (E,)
    aux = E * jnp.sum(frac * mean_prob)
    return topw.astype(x2d.dtype), topi, aux


def _router_sigmoid(cfg: ModelConfig, p: Params, x2d: jnp.ndarray
                    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """DeepSeek-V3 ``noaux_tc`` routing with one group: logits in float32 as
    the published gate computes them, experts chosen on sigmoid score plus
    the selection bias, weighted by the unbiased scores."""
    logits = jnp.dot(x2d.astype(jnp.float32), p["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)                              # (T,E)
    choice = scores + p["router_bias"].astype(jnp.float32)
    _, topi = jax.lax.top_k(choice, cfg.top_k)
    topw = jnp.take_along_axis(scores, topi, axis=-1)
    topw = topw / (jnp.sum(topw, axis=-1, keepdims=True) + 1e-20)
    topw = topw * cfg.routed_scaling
    E = cfg.n_experts
    frac = jnp.mean(jnp.sum(jax.nn.one_hot(topi, E, dtype=jnp.float32), 1), 0)
    mean_p = jnp.mean(scores / jnp.sum(scores, -1, keepdims=True), axis=0)
    return topw.astype(x2d.dtype), topi, E * jnp.sum(frac * mean_p)


def _expert_ffn_batched(p: Params, xe: jnp.ndarray, dtype) -> jnp.ndarray:
    """xe: (E, C, D) -> (E, C, D) via per-expert SwiGLU (expert-parallel)."""
    xe = part.shard_expert_tokens(xe)
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"].astype(dtype)))
    u = jnp.einsum("ecd,edf->ecf", xe, p["w_up"].astype(dtype))
    h = part.shard_expert_hidden(g * u)
    return part.shard_expert_tokens(
        jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(dtype)))


def moe_capacity(cfg: ModelConfig, p: Params, x2d: jnp.ndarray
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Capacity-bounded dispatch. x2d: (T, D). Returns (out (T,D), aux)."""
    x2d = part.shard_tokens2d(x2d)
    T, D = x2d.shape
    E, K = cfg.n_experts, cfg.top_k
    topw, topi, aux = router_topk(cfg, p, x2d)

    C = max(1, int(math.ceil(T * K / E * cfg.capacity_factor)))
    e_flat = topi.reshape(-1)                                    # (T*K,)
    w_flat = topw.reshape(-1)
    tok_flat = jnp.repeat(jnp.arange(T), K)                      # (T*K,)

    onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)          # (T*K, E)
    pos_in_e = jnp.cumsum(onehot, axis=0) - onehot               # count before me
    pos = jnp.sum(pos_in_e * onehot, axis=-1)                    # (T*K,)
    keep = pos < C
    slot = jnp.where(keep, e_flat * C + pos, E * C)              # overflow -> dropped row

    # scatter token ids into slots; slot E*C is a trash row
    slot_tok = jnp.full((E * C + 1,), T, jnp.int32).at[slot].set(tok_flat.astype(jnp.int32))
    slot_w = jnp.zeros((E * C + 1,), x2d.dtype).at[slot].set(w_flat)
    slot_tok, slot_w = slot_tok[:-1], slot_w[:-1]

    x_pad = jnp.concatenate([x2d, jnp.zeros((1, D), x2d.dtype)], axis=0)
    xe = part.shard_expert_tokens(x_pad[slot_tok].reshape(E, C, D))
    ye = _expert_ffn_batched(p, xe, x2d.dtype).reshape(E * C, D)
    ye = ye * slot_w[:, None]

    out = part.shard_tokens2d(
        jnp.zeros((T + 1, D), x2d.dtype).at[slot_tok].add(ye)[:T])
    if cfg.n_shared_experts:
        out = out + _shared_ffn(p, x2d)
    return out, aux


BANKS = ("w_gate", "w_up", "w_down")      # the held experts' stacked weights


def reads_touched(cfg: ModelConfig, tokens: int) -> bool:
    """Whether a ``ragged`` expert layer on ``tokens`` tokens reads only the
    held experts they reach: it has fewer assignments than held experts, so
    some of them take no token (a decode step; never a prefill here)."""
    return (cfg.n_experts > 0 and cfg.moe_impl == "ragged"
            and tokens * cfg.top_k < cfg.n_held_experts)


def moe_ragged(cfg: ModelConfig, p: Params, x2d: jnp.ndarray, layer=None
               ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Sort-by-expert + ragged_dot grouped GEMM. No token drops.

    Only assignments to the held experts are computed: the others sort
    last, outside every group, and add nothing. Where ``reads_touched``,
    the held experts that took a token are applied one by one instead
    (``_touched_experts``); only then may ``layer`` be given: p's expert
    banks keep their leading layer axis, and this layer's are at ``layer``.

    Returns (out, aux, counts): counts (3,) int32 are the held experts that
    took at least one token, the assignments that landed on held experts,
    and the held experts whose weights were read."""
    T, D = x2d.shape
    n, K = cfg.n_held_experts, cfg.top_k
    with jax.named_scope("moe.route"):
        topw, topi, aux = router_topk(cfg, p, x2d)
        e_flat = topi.reshape(-1) - cfg.first_held_expert
        held = (e_flat >= 0) & (e_flat < n)
        e_flat = jnp.where(held, e_flat, n)
        tok_flat = jnp.repeat(jnp.arange(T), K)
        w_flat = topw.reshape(-1)
        order = jnp.argsort(e_flat)
        xs = x2d[tok_flat[order]]                                # (T*K, D)
        group_sizes = jnp.sum(jax.nn.one_hot(e_flat, n, dtype=jnp.int32),
                              axis=0)                            # (n,)

    dt = x2d.dtype
    touched = jnp.sum(group_sizes > 0)
    if reads_touched(cfg, T):
        out = _touched_experts(cfg, p, x2d, e_flat, w_flat, group_sizes,
                               layer)
        read = touched
    else:
        with jax.named_scope("moe.experts"):
            g = jax.nn.silu(jax.lax.ragged_dot(xs, p["w_gate"].astype(dt), group_sizes))
            u = jax.lax.ragged_dot(xs, p["w_up"].astype(dt), group_sizes)
            ys = jax.lax.ragged_dot(g * u, p["w_down"].astype(dt), group_sizes)
            ys = jnp.where(held[order][:, None], ys * w_flat[order][:, None], 0)
            out = jnp.zeros((T, D), dt).at[tok_flat[order]].add(ys)
        read = n
    if cfg.n_shared_experts:
        with jax.named_scope("moe.shared"):
            out = out + _shared_ffn(p, x2d)
    counts = jnp.stack([touched, jnp.sum(group_sizes), jnp.asarray(read)])
    return out, aux, counts.astype(jnp.int32)


def _touched_experts(cfg: ModelConfig, p: Params, x2d, e_flat, w_flat,
                     group_sizes, layer) -> jnp.ndarray:
    """The held experts' part of the routed output, reading the weights of
    the held experts that took a token (``group_sizes > 0``) and no others.

    A loop over them in ascending order, as ``ragged_dot``'s groups sort;
    each applies its SwiGLU to every token, weighted by the token's combine
    weight for it (0 where not routed there). Each expert's matrices are
    sliced from the banks as a whole (with ``layer``, the stacked
    ``(L, n, ...)`` banks), so that the slice fuses into its matmul: a bank
    sliced per layer outside the loop is copied whole."""
    T, D = x2d.shape
    n, K = cfg.n_held_experts, cfg.top_k
    dt = x2d.dtype
    # (T, n) combine weights: a token's top-k picks are distinct experts
    gate = jnp.sum(jax.nn.one_hot(e_flat.reshape(T, K), n, dtype=dt)
                   * w_flat.reshape(T, K, 1), axis=1)
    ids = jnp.sort(jnp.where(group_sizes > 0, jnp.arange(n), n))  # touched first
    at = 0 if layer is None else layer
    banks = {k: p[k] if layer is not None else p[k][None] for k in BANKS}

    def matmul(a, name, e):
        b = banks[name]
        w = jax.lax.dynamic_slice(b, (at, e, 0, 0), (1, 1) + b.shape[2:])
        return jnp.dot(a, w[0, 0].astype(dt),
                       preferred_element_type=jnp.float32).astype(dt)

    def expert(i, out):
        e = ids[i]
        g = jax.nn.silu(matmul(x2d, "w_gate", e))
        y = matmul(g * matmul(x2d, "w_up", e), "w_down", e)
        return out + y * jax.lax.dynamic_index_in_dim(gate, e, 1)

    with jax.named_scope("moe.experts"):
        return jax.lax.fori_loop(0, jnp.sum(group_sizes > 0), expert,
                                 jnp.zeros((T, D), dt))


def _shared_ffn(p: Params, x2d: jnp.ndarray) -> jnp.ndarray:
    g = jax.nn.silu(x2d @ p["shared_gate"].astype(x2d.dtype))
    u = x2d @ p["shared_up"].astype(x2d.dtype)
    return (g * u) @ p["shared_down"].astype(x2d.dtype)


def moe_capacity_grouped(cfg: ModelConfig, p: Params, x: jnp.ndarray
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Group-local capacity dispatch: one dispatch problem PER BATCH ROW.

    The flat path computes capacity positions with a cumsum over ALL tokens,
    which makes every expert shard depend on every data shard — XLA SPMD
    all-gathers the full token table per layer (measured 6.7 TB/step
    collectives on qwen3-moe train_4k). Restricting dispatch to each batch
    row keeps it local: tokens stay data-sharded end to end, expert outputs
    combine with a TP-style psum over the expert/model axis. Capacity is
    per-row (C = ceil(S*k/E * cf)), the GSPMD-MoE 'group' pattern.

    All ops are explicitly batched over B (not vmapped) so the activation
    sharding constraints apply to the real (B, ...) shapes.
    """
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    x = part.shard_btd(x)

    logits = (x @ p["router"].astype(x.dtype)).astype(jnp.float32)  # (B,S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, K)                                # (B,S,K)
    topw = (topw / jnp.sum(topw, axis=-1, keepdims=True)).astype(x.dtype)
    onehot_f = jax.nn.one_hot(topi, E, dtype=jnp.float32)
    frac = jnp.mean(jnp.sum(onehot_f, axis=2), axis=(0, 1))
    aux = E * jnp.sum(frac * jnp.mean(probs, axis=(0, 1)))

    C = max(1, int(math.ceil(S * K / E * cfg.capacity_factor)))
    e_flat = topi.reshape(B, S * K)
    w_flat = topw.reshape(B, S * K)
    tok_flat = jnp.broadcast_to(jnp.repeat(jnp.arange(S), K)[None], (B, S * K))

    onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)       # (B, S*K, E)
    pos = jnp.sum((jnp.cumsum(onehot, axis=1) - onehot) * onehot, axis=-1)
    keep = pos < C
    slot = jnp.where(keep, e_flat * C + pos, E * C)           # (B, S*K)

    rows = jnp.arange(B)[:, None]
    # (B, E, C) slot tables, expert dim pinned to the tensor axis so the
    # gather/scatter below partition as (local-rows x local-experts)
    slot_tok = part.shard_bhd(
        jnp.full((B, E * C + 1), S, jnp.int32)
        .at[rows, slot].set(tok_flat.astype(jnp.int32))[:, :-1]
        .reshape(B, E, C), 1)
    slot_w = part.shard_bhd(
        jnp.zeros((B, E * C + 1), x.dtype)
        .at[rows, slot].set(w_flat)[:, :-1]
        .reshape(B, E, C), 1)

    x_pad = jnp.concatenate([x, jnp.zeros((B, 1, D), x.dtype)], axis=1)
    b3 = jnp.arange(B)[:, None, None]
    xe = part.shard_bhd(x_pad[b3, slot_tok], 1)               # (B,E,C,D)

    dt = x.dtype
    g = jax.nn.silu(jnp.einsum("becd,edf->becf", xe, p["w_gate"].astype(dt)))
    u = jnp.einsum("becd,edf->becf", xe, p["w_up"].astype(dt))
    h = part.shard_bhd(g * u, 1)
    ye = jnp.einsum("becf,efd->becd", h, p["w_down"].astype(dt))
    ye = part.shard_bhd(ye, 1) * slot_w[..., None]

    out = jnp.zeros((B, S + 1, D), dt).at[b3, slot_tok].add(ye)[:, :S]
    out = part.shard_btd(out)
    if cfg.n_shared_experts:
        out = out + _shared_ffn(p, x.reshape(B * S, D)).reshape(B, S, D)
    return out, aux


def moe_ep_shardmap(cfg: ModelConfig, p: Params, x: jnp.ndarray
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Explicit expert parallelism via shard_map over the tensor axis.

    Each model-shard owns E/TP experts; every shard sees the (replicated-
    over-model) token block, routes, computes ONLY assignments that land on
    its local experts, and the partial outputs combine with ONE psum over
    the model axis — the collective schedule is deterministic by
    construction instead of left to SPMD gather/scatter partitioning
    (EXPERIMENTS.md §Perf HC2.6). Falls back to the grouped path when no
    model axis is in scope.
    """
    from jax.sharding import PartitionSpec as P

    mesh = part._cur_mesh()
    if mesh is None or "model" not in dict(mesh.shape):
        return moe_capacity_grouped(cfg, p, x)
    tp = dict(mesh.shape)["model"]
    E, K = cfg.n_experts, cfg.top_k
    if E % tp != 0:
        return moe_capacity_grouped(cfg, p, x)
    E_local = E // tp
    B, S, D = x.shape
    C = max(1, int(math.ceil(S * K / E * cfg.capacity_factor)))

    def local_fn(xl, router, wg, wu, wd):
        m = jax.lax.axis_index("model")
        logits = (xl @ router.astype(xl.dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        topw, topi = jax.lax.top_k(probs, K)                     # (B,S,K)
        topw = (topw / jnp.sum(topw, -1, keepdims=True)).astype(xl.dtype)
        onehot_f = jax.nn.one_hot(topi, E, dtype=jnp.float32)
        frac = jnp.mean(jnp.sum(onehot_f, axis=2), axis=(0, 1))
        aux = E * jnp.sum(frac * jnp.mean(probs, axis=(0, 1)))

        rel = topi - m * E_local                                 # local ids
        valid = (rel >= 0) & (rel < E_local)
        rel = jnp.clip(rel, 0, E_local - 1).reshape(B, S * K)
        w_flat = jnp.where(valid, topw, 0).reshape(B, S * K)
        tok_flat = jnp.broadcast_to(
            jnp.repeat(jnp.arange(S), K)[None], (B, S * K))

        onehot = jnp.where(valid.reshape(B, S * K)[..., None],
                           jax.nn.one_hot(rel, E_local, dtype=jnp.int32), 0)
        pos = jnp.sum((jnp.cumsum(onehot, axis=1) - onehot) * onehot, -1)
        keep = valid.reshape(B, S * K) & (pos < C)
        slot = jnp.where(keep, rel * C + pos, E_local * C)

        rows = jnp.arange(B)[:, None]
        slot_tok = jnp.full((B, E_local * C + 1), S, jnp.int32) \
            .at[rows, slot].set(tok_flat.astype(jnp.int32))[:, :-1]
        slot_w = jnp.zeros((B, E_local * C + 1), xl.dtype) \
            .at[rows, slot].set(w_flat)[:, :-1]

        x_pad = jnp.concatenate([xl, jnp.zeros((B, 1, D), xl.dtype)], 1)
        xe = x_pad[rows, slot_tok].reshape(B, E_local, C, D)
        dt = xl.dtype
        g = jax.nn.silu(jnp.einsum("becd,edf->becf", xe, wg.astype(dt)))
        u = jnp.einsum("becd,edf->becf", xe, wu.astype(dt))
        ye = jnp.einsum("becf,efd->becd", g * u, wd.astype(dt))
        ye = ye.reshape(B, E_local * C, D) * slot_w[..., None]
        out = jnp.zeros((B, S + 1, D), dt).at[rows, slot_tok].add(ye)[:, :S]
        return jax.lax.psum(out, "model"), aux

    out, aux = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(), P(), P("model"), P("model"), P("model")),
        out_specs=(P(), P()),
        axis_names={"model"}, check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    if cfg.n_shared_experts:
        out = out + _shared_ffn(p, x.reshape(B * S, D)).reshape(B, S, D)
    return out, aux


def apply_moe(cfg: ModelConfig, p: Params, x: jnp.ndarray, counts: bool = False,
              layer=None):
    """x: (B, S, D) -> (out, aux_loss), and with ``counts`` the ragged
    path's (3,) routing counts (``moe_ragged``) after them. ``layer``: as
    ``moe_ragged`` takes it (stacked expert banks, the ragged path only)."""
    B, S, D = x.shape
    if cfg.moe_impl == "ragged":
        out, aux, c = moe_ragged(cfg, p, x.reshape(B * S, D), layer)
        return (out.reshape(B, S, D), aux) + ((c,) if counts else ())
    if (cfg.router_score != "softmax" or cfg.n_held_experts != cfg.n_experts
            or counts or layer is not None):
        raise ValueError(f"moe_impl {cfg.moe_impl!r} routes by softmax over "
                         f"every expert it holds; {cfg.name} needs 'ragged'")
    if cfg.moe_impl == "grouped":
        return moe_capacity_grouped(cfg, p, x)
    if cfg.moe_impl == "ep":
        return moe_ep_shardmap(cfg, p, x)
    out, aux = moe_capacity(cfg, p, x.reshape(B * S, D))
    return out.reshape(B, S, D), aux
