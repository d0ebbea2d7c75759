"""The dense decoder: pre-norm blocks of rotary GQA attention and a SwiGLU
feed-forward, one homogeneous stack, output head tied to the embedding
(``olmo-1b``, ``deepseek-7b``).

Its leaves and the program fields it must show are here; the reference's
block and head are in ``bench/reference.py`` and its counts in
``bench/flops.py``.
"""
from __future__ import annotations

import math
from typing import List, Sequence

import jax.numpy as jnp
import numpy as np

from bench import flops, reference, weights as W

NORMS = {"rmsnorm": "rmsnorm", "layernorm_nonparametric": "nonparametric_ln"}


def program_fields(config: dict) -> dict:
    m = config["model"]
    return {"n_layers": m["n_layers"], "d_model": m["d_model"],
            "n_heads": m["n_heads"], "n_kv_heads": m["n_kv_heads"],
            "head_dim": m["head_dim"], "d_ff": m["d_ff"],
            "vocab_size": m["vocab_size"], "norm_type": NORMS[m["norm"]],
            "norm_eps": m["norm_eps"], "rope_theta": m["rope_theta"],
            "tie_embeddings": m["tie_embeddings"], "param_dtype": m["dtype"],
            "compute_dtype": m["dtype"],
            "padded_vocab": config["program"]["embed_rows"],
            "family": "dense", "use_pallas": False}


def _has_norm_scales(model: dict) -> bool:
    return model["norm"] == "rmsnorm"


def weight_groups(model: dict, embed_rows: int):
    """``embed`` (and ``final_norm/scale``), then one stack ``layers`` of
    ``ln1``, ``attn/{wq,wk,wv,wo}``, ``ln2`` and
    ``ffn/{w_gate,w_up,w_down}``; norm scales only under RMSNorm."""
    d, f, L = model["d_model"], model["d_ff"], model["n_layers"]
    qd = model["n_heads"] * model["head_dim"]
    kvd = model["n_kv_heads"] * model["head_dim"]
    layer = {
        "attn/wq": ((d, qd), 1 / math.sqrt(d)),
        "attn/wk": ((d, kvd), 1 / math.sqrt(d)),
        "attn/wv": ((d, kvd), 1 / math.sqrt(d)),
        "attn/wo": ((qd, d), 1 / math.sqrt(qd * 2 * L)),
        "ffn/w_gate": ((d, f), 1 / math.sqrt(d)),
        "ffn/w_up": ((d, f), 1 / math.sqrt(d)),
        "ffn/w_down": ((f, d), 1 / math.sqrt(f * 2 * L)),
    }
    stem = {"embed": ((embed_rows, d), 0.02)}
    if _has_norm_scales(model):
        layer["ln1/scale"] = ((d,), 0.0)
        layer["ln2/scale"] = ((d,), 0.0)
        stem["final_norm/scale"] = ((d,), 0.0)
    return stem, [("layers", L, layer)]


def nest(flat: dict, model: dict) -> dict:
    """The program's tree; OLMo's parameter-free norms keep empty nodes."""
    empty = () if _has_norm_scales(model) else (
        "final_norm", "layers/ln1", "layers/ln2")
    return W.unflatten(flat, empty)


def logits_at(seed: int, model: dict, embed_rows: int,
              seqs: Sequence[np.ndarray], rows: Sequence[np.ndarray],
              fp8: bool = False) -> List[np.ndarray]:
    """For each token sequence, the float32 logits at the given positions.

    All sequences use the weights of ``seed``; layers are drawn and applied
    one at a time across every sequence, so that the whole model never sits
    in float32 on the device."""
    mkey = tuple(sorted(model.items()))
    stem_specs, groups = weight_groups(model, embed_rows)
    stem = W.stem_f32(seed, stem_specs)
    xs = [stem["embed"][jnp.asarray(s, jnp.int32)] for s in seqs]
    for group in groups:
        for i in range(group[1]):
            w = W.layer_f32(seed, group, i)
            xs = [reference.block(mkey, w, x, fp8) for x in xs]
            del w
    out = [np.asarray(reference.head(mkey, stem["embed"],
                                     stem.get("final_norm/scale"), x,
                                     jnp.asarray(r, jnp.int32), fp8))
           for x, r in zip(xs, rows)]
    del stem, xs
    return out


prefill_flops = flops.prefill_flops
decode_flops = flops.decode_flops
decode_bytes = flops.decode_bytes
param_count = flops.param_count
weight_bytes = flops.weight_bytes
