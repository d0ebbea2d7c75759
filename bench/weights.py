"""Seeded weights, made by the benchmark and not the program.

An architecture module (``bench/arch/``) states the leaves: the stem
``{leaf: (shape, std)}``, then stacked layer groups ``(prefix, count,
{leaf: (shape, std)})``. One tensor per (leaf, layer) is drawn from its own
key, ``fold_in(fold_in(base(seed), leaf_id(prefix + "/" + leaf)), layer)``
(a stem leaf from ``fold_in(base(seed), leaf_id(leaf))``), so that
:func:`make_flat` (every group at once, one jitted call on the device, in
the served dtype) and :func:`layer_f32` (one layer, for the reference) give
the same numbers. Names are the program's store names, a group's leaves
stacked on a leading axis under ``prefix/``.
"""
from __future__ import annotations

import functools
import zlib
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

Specs = Dict[str, Tuple[Tuple[int, ...], float]]
Group = Tuple[str, int, Specs]
Layout = Tuple[Specs, List[Group]]      # an architecture's weight_groups


def base_key(seed: int) -> jax.Array:
    """A key for any whole-number seed, also those past 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _leaf_id(name: str) -> int:
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def _draw(key, shape, std):
    """A std of 0 marks a norm scale, drawn as 1 + 0.1 * N(0, 1)."""
    z = jax.random.normal(key, shape, jnp.float32)
    return 1.0 + 0.1 * z if std == 0.0 else z * std


def unflatten(flat: Dict[str, object], empty: Tuple[str, ...] = ()) -> dict:
    """Flat weights as a nested tree; each path of ``empty`` (a node the
    program keeps without parameters) becomes an empty node."""
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    for path in empty:
        node = tree
        for p in path.split("/"):
            node = node.setdefault(p, {})
    return tree


def _frozen(specs: Specs):
    return tuple((n, tuple(s), float(std)) for n, (s, std) in specs.items())


def _frozen_groups(groups: List[Group]):
    return tuple((prefix, int(count), _frozen(specs))
                 for prefix, count, specs in groups)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make(key, sspecs, groups):
    dtype = jnp.bfloat16
    flat = {}
    for name, shape, std in sspecs:
        flat[name] = _draw(jax.random.fold_in(key, _leaf_id(name)), shape,
                           std).astype(dtype)
    for prefix, count, lspecs in groups:
        for name, shape, std in lspecs:
            k = jax.random.fold_in(key, _leaf_id(prefix + "/" + name))
            flat[prefix + "/" + name] = jax.vmap(
                lambda i: _draw(jax.random.fold_in(k, i), shape,
                                std).astype(dtype)
            )(jnp.arange(count))
    return flat


def make_flat(seed: int, layout: Layout) -> Dict[str, jax.Array]:
    """Every weight of one model, flat by store name, made on the default
    device in one jitted call, in bfloat16. ``layout`` is what the
    architecture's ``weight_groups`` returns."""
    stem, groups = layout
    return _make(base_key(seed), _frozen(stem), _frozen_groups(groups))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _layer(key, prefix, lspecs, i):
    out = {}
    for name, shape, std in lspecs:
        k = jax.random.fold_in(key, _leaf_id(prefix + "/" + name))
        out[name] = _draw(jax.random.fold_in(k, i), shape,
                          std).astype(jnp.bfloat16).astype(jnp.float32)
    return out


def layer_f32(seed: int, group: Group, i: int) -> Dict[str, jax.Array]:
    """Layer ``i`` of one group ``(prefix, count, specs)`` as
    :func:`make_flat` makes it, in float32, keyed by leaf."""
    prefix, _, specs = group
    return _layer(base_key(seed), prefix, _frozen(specs), jnp.int32(i))


@functools.partial(jax.jit, static_argnums=(1,))
def _stem(key, sspecs):
    return {name: _draw(jax.random.fold_in(key, _leaf_id(name)), shape, std)
            .astype(jnp.bfloat16).astype(jnp.float32)
            for name, shape, std in sspecs}


def stem_f32(seed: int, stem: Specs) -> Dict[str, jax.Array]:
    """The stem leaves as :func:`make_flat` makes them, in float32."""
    return _stem(base_key(seed), _frozen(stem))


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
