"""Seeded weights of a dense decoder, made by the benchmark and not the program.

One tensor per (leaf, layer) is drawn from its own key,
``fold_in(fold_in(base(seed), leaf_id), layer)``, so that
:func:`make_flat` (all layers at once, one jitted call on the device, in
the served dtype) and :func:`layer_f32` (one layer, for the reference) give
the same numbers. The tree uses the program's store layout: ``embed``,
``final_norm``, and ``layers`` stacked on a leading axis with ``ln1``,
``attn/{wq,wk,wv,wo}``, ``ln2`` and ``ffn/{w_gate,w_up,w_down}``.
"""
from __future__ import annotations

import functools
import math
import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

def base_key(seed: int) -> jax.Array:
    """A key for any whole-number seed, also those past 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _leaf_id(name: str) -> int:
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def _has_norm_scales(model: dict) -> bool:
    return model["norm"] == "rmsnorm"


def layer_specs(model: dict) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    """Per-layer leaves: name -> (shape, std). A std of 0 marks a norm scale,
    drawn as 1 + 0.1 * N(0, 1)."""
    d, f, L = model["d_model"], model["d_ff"], model["n_layers"]
    qd = model["n_heads"] * model["head_dim"]
    kvd = model["n_kv_heads"] * model["head_dim"]
    specs = {
        "attn/wq": ((d, qd), 1 / math.sqrt(d)),
        "attn/wk": ((d, kvd), 1 / math.sqrt(d)),
        "attn/wv": ((d, kvd), 1 / math.sqrt(d)),
        "attn/wo": ((qd, d), 1 / math.sqrt(qd * 2 * L)),
        "ffn/w_gate": ((d, f), 1 / math.sqrt(d)),
        "ffn/w_up": ((d, f), 1 / math.sqrt(d)),
        "ffn/w_down": ((f, d), 1 / math.sqrt(f * 2 * L)),
    }
    if _has_norm_scales(model):
        specs["ln1/scale"] = ((d,), 0.0)
        specs["ln2/scale"] = ((d,), 0.0)
    return specs


def stem_specs(model: dict, embed_rows: int) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    specs = {"embed": ((embed_rows, model["d_model"]), 0.02)}
    if _has_norm_scales(model):
        specs["final_norm/scale"] = ((model["d_model"],), 0.0)
    return specs


def _draw(key, shape, std):
    z = jax.random.normal(key, shape, jnp.float32)
    return 1.0 + 0.1 * z if std == 0.0 else z * std


def _nest(flat: Dict[str, object], empty: Tuple[str, ...]) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    for path in empty:  # parameter-free norms keep their (empty) node
        node = tree
        for p in path.split("/"):
            node = node.setdefault(p, {})
    return tree


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make(key, lspecs, sspecs, n_layers):
    dtype = jnp.bfloat16
    flat = {}
    for name, shape, std in sspecs:
        flat[name] = _draw(jax.random.fold_in(key, _leaf_id(name)), shape,
                           std).astype(dtype)
    for name, shape, std in lspecs:
        k = jax.random.fold_in(key, _leaf_id("layers/" + name))
        flat["layers/" + name] = jax.vmap(
            lambda i: _draw(jax.random.fold_in(k, i), shape, std).astype(dtype)
        )(jnp.arange(n_layers))
    return flat


def _frozen(specs):
    return tuple((n, tuple(s), float(std)) for n, (s, std) in specs.items())


def make_flat(seed: int, model: dict, embed_rows: int) -> Dict[str, jax.Array]:
    """Every weight of one model, flat by store name, made on the default
    device in one jitted call, in bfloat16."""
    if model["dtype"] != "bfloat16":
        raise ValueError(f"weights are made in bfloat16, not {model['dtype']}")
    return _make(base_key(seed), _frozen(layer_specs(model)),
                 _frozen(stem_specs(model, embed_rows)), model["n_layers"])


def nest(flat: Dict[str, object], model: dict) -> dict:
    """Flat weights as the program's nested parameter tree."""
    empty = () if _has_norm_scales(model) else (
        "final_norm", "layers/ln1", "layers/ln2")
    return _nest(flat, empty)


@functools.partial(jax.jit, static_argnums=(1,))
def _layer(key, lspecs, i):
    out = {}
    for name, shape, std in lspecs:
        k = jax.random.fold_in(key, _leaf_id("layers/" + name))
        out[name] = _draw(jax.random.fold_in(k, i), shape,
                          std).astype(jnp.bfloat16).astype(jnp.float32)
    return out


def layer_f32(seed: int, model: dict, i: int) -> Dict[str, jax.Array]:
    """Layer ``i``'s weights as :func:`make_flat` makes them, in float32."""
    return _layer(base_key(seed), _frozen(layer_specs(model)), jnp.int32(i))


@functools.partial(jax.jit, static_argnums=(1,))
def _stem(key, sspecs):
    return {name: _draw(jax.random.fold_in(key, _leaf_id(name)), shape, std)
            .astype(jnp.bfloat16).astype(jnp.float32)
            for name, shape, std in sspecs}


def stem_f32(seed: int, model: dict, embed_rows: int) -> Dict[str, jax.Array]:
    """Embedding (and final norm scale) as :func:`make_flat` makes them, in
    float32."""
    return _stem(base_key(seed), _frozen(stem_specs(model, embed_rows)))


@jax.jit
def _fingerprint(x):
    v = x.astype(jnp.float32).reshape(-1)
    w = (jnp.arange(v.shape[0], dtype=jnp.uint32) % 65521).astype(jnp.float32)
    return jnp.stack([jnp.sum(v * v), jnp.sum(v * w)])


def fingerprints(flat: Dict[str, object]) -> Dict[str, Tuple[float, float]]:
    """Two float32 sums of every tensor, computed on the device: any change
    of a value, of its place, or of the tensor moves at least one of them.
    Host (numpy) tensors are copied to the device first."""
    out = {}
    for name in sorted(flat):
        s = _fingerprint(jnp.asarray(flat[name]))
        out[name] = tuple(float(x) for x in jax.device_get(s))
    return out
