"""Operations and bytes that a dense decoder's work needs, from its shapes.

Counted is the work the algorithm needs, not what a program happens to
execute: attention over the keys up to each token's own position (not a
padded cache length, not the masked half of a square), the output head over
the model's vocabulary (not padded rows), and, in prefill, logits for the
last position only. A later program that does less padded work is then
judged against the same count. Weights and cache are bfloat16 (2 bytes).
"""
from __future__ import annotations

BYTES = 2  # bfloat16


def layer_params(model: dict) -> int:
    d, f = model["d_model"], model["d_ff"]
    qd = model["n_heads"] * model["head_dim"]
    kvd = model["n_kv_heads"] * model["head_dim"]
    norms = 2 * d if model["norm"] == "rmsnorm" else 0
    return d * qd + 2 * d * kvd + qd * d + 3 * d * f + norms


def layer_matmul_params(model: dict) -> int:
    norms = 2 * model["d_model"] if model["norm"] == "rmsnorm" else 0
    return layer_params(model) - norms


def head_params(model: dict) -> int:
    return model["vocab_size"] * model["d_model"]


def param_count(model: dict) -> int:
    """Parameters of the model as run (one tied embedding/head table)."""
    final = model["d_model"] if model["norm"] == "rmsnorm" else 0
    head = 0 if model["tie_embeddings"] else head_params(model)
    return (model["n_layers"] * layer_params(model) + head_params(model)
            + head + final)


def _attn_flops(model: dict, keys: int) -> int:
    """QK^T and PV for one query token over ``keys`` keys, all layers."""
    return 4 * model["n_layers"] * keys * model["n_heads"] * model["head_dim"]


def trunk_flops(model: dict) -> int:
    """Matrix products of all layers for one token, without attention."""
    return 2 * model["n_layers"] * layer_matmul_params(model)


def prefill_flops(model: dict, prompt_len: int) -> int:
    """A prompt of ``prompt_len`` tokens, logits at its last position."""
    S = prompt_len
    return (S * trunk_flops(model) + 2 * head_params(model)
            + sum(_attn_flops(model, p + 1) for p in range(S)))


def decode_flops(model: dict, pos: int) -> int:
    """One decode step writing position ``pos`` (so ``pos + 1`` keys)."""
    return (trunk_flops(model) + 2 * head_params(model)
            + _attn_flops(model, pos + 1))


def kv_bytes_per_token(model: dict) -> int:
    return 2 * model["n_layers"] * model["n_kv_heads"] * model["head_dim"] * BYTES


def weight_bytes(model: dict) -> int:
    """Weights a decode step reads: every layer, and the head table."""
    return (model["n_layers"] * layer_params(model) + head_params(model)
            + (model["d_model"] if model["norm"] == "rmsnorm" else 0)) * BYTES


def decode_bytes(model: dict, pos: int) -> int:
    """One decode step at ``pos``: the weights, the ``pos`` cached keys and
    values it reads, and the one it writes."""
    return weight_bytes(model) + kv_bytes_per_token(model) * (pos + 1)


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute bound
    and the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
