"""Activation sharding constraints.

XLA's sharding propagation picks pathological layouts for gather-rooted
graphs (measured: embedding lookup with a (vocab->model, d->data)-sharded
table makes every downstream activation batch-REPLICATED and d-sharded —
24 GiB/device forward on olmo-1b train_4k). The fix is the MaxText pattern:
pin activation layouts at block boundaries with with_sharding_constraint.

Model code stays mesh-agnostic: the launcher registers the physical axis
names here; when nothing is registered (unit tests, single device) every
constraint is a no-op.
"""
from __future__ import annotations

import threading
from typing import Optional, Sequence, Tuple, Union

import jax
from jax.sharding import PartitionSpec as P

_state = threading.local()


def set_activation_axes(batch: Union[None, str, Tuple[str, ...]],
                        model: Optional[str]) -> None:
    _state.batch = batch
    _state.model = model


def clear_activation_axes() -> None:
    _state.batch = None
    _state.model = None


def get_axes():
    return getattr(_state, "batch", None), getattr(_state, "model", None)


class activation_axes:
    """Context manager used by launchers around trace/lower calls."""

    def __init__(self, batch, model):
        self.axes = (batch, model)

    def __enter__(self):
        self.prev = get_axes()
        set_activation_axes(*self.axes)
        return self

    def __exit__(self, *exc):
        set_activation_axes(*self.prev)
        return False


def _constraint(x, spec: P):
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, TypeError, RuntimeError):
        return x  # no mesh context / axes missing: stay a no-op


def shard_btd(x):
    """(batch, seq, d_model) — replicated d, batch-sharded."""
    b, _ = get_axes()
    if b is None:
        return x
    return _constraint(x, P(b, *([None] * (x.ndim - 1))))


def shard_residual(x, allow_seq: bool = True):
    """Residual stream (batch, seq, d): sequence-parallel over the tensor
    axis when the family allows it (Korthikanti-style SP) — the L x B x S x D
    saved carries of a scanned stack shrink by the TP degree. SSM/hybrid
    residuals stay batch-only (their chunk scan must keep seq unsharded)."""
    b, m = get_axes()
    if b is None and m is None:
        return x
    if x.ndim != 3:
        return shard_btd(x)
    seq_ax = m if (allow_seq and m and x.shape[1] % _size(m) == 0) else None
    return _constraint(x, P(b, seq_ax, None))


def shard_heads(x):
    """(batch, seq, heads, head_dim) — heads on the tensor axis."""
    b, m = get_axes()
    if b is None and m is None:
        return x
    if x.ndim == 4:
        spec = P(b, None, m if m and x.shape[2] % _size(m) == 0 else None, None)
    else:
        return x
    return _constraint(x, spec)


def shard_ffn(x):
    """(batch, seq, ffn_hidden) — hidden on the tensor axis."""
    b, m = get_axes()
    if b is None and m is None:
        return x
    spec = P(b, None, m if m and x.shape[-1] % _size(m) == 0 else None)
    return _constraint(x, spec)


def shard_logits(x):
    """(..., vocab) — vocab on the tensor axis."""
    b, m = get_axes()
    if b is None and m is None:
        return x
    spec = P(*([b] + [None] * (x.ndim - 2) + [m if m and x.shape[-1] % _size(m) == 0 else None]))
    return _constraint(x, spec)


def shard_bhd(x, head_dim: int):
    """Batch on dim 0, tensor axis on ``head_dim``, rest replicated."""
    b, m = get_axes()
    if b is None and m is None:
        return x
    spec = [None] * x.ndim
    spec[0] = b
    if m and x.shape[head_dim] % _size(m) == 0:
        spec[head_dim] = m
    return _constraint(x, P(*spec))


def shard_cache(x):
    """KV cache (B, T, K, hd): batch on fsdp (or T when batch=1), K on the
    tensor axis (hd as fallback) — must match launch.sharding.cache_spec."""
    b, m = get_axes()
    if b is None and m is None:
        return x
    if x.ndim != 4:
        return x
    B, T, K, hd = x.shape
    spec = [None, None, None, None]
    if b:
        if B % _size(b) == 0:
            spec[0] = b
        elif T % _size(b) == 0:
            spec[1] = b
    if m:
        if K % _size(m) == 0:
            spec[2] = m
        elif hd % _size(m) == 0:
            spec[3] = m
    return _constraint(x, P(*spec))


def shard_tokens2d(x):
    """(tokens, d) flattened MoE token tables — tokens batch-sharded."""
    b, _ = get_axes()
    if b is None:
        return x
    return _constraint(x, P(b, *([None] * (x.ndim - 1))))


def shard_expert_tokens(x):
    """(experts, capacity, d) — experts on the tensor axis."""
    b, m = get_axes()
    if m is None:
        return x
    spec = [None] * x.ndim
    if x.shape[0] % _size(m) == 0:
        spec[0] = m
    return _constraint(x, P(*spec))


def shard_expert_hidden(x):
    """(experts, capacity, d_ff) — experts on the tensor axis, ffn dim on the
    batch/fsdp axes (matches the (E, D, F->fsdp) expert weight layout so the
    per-expert hidden never materializes unsharded)."""
    b, m = get_axes()
    if b is None and m is None:
        return x
    spec = [None] * x.ndim
    if m and x.shape[0] % _size(m) == 0:
        spec[0] = m
    if b and x.shape[-1] % _size(b) == 0:
        spec[-1] = b
    return _constraint(x, P(*spec))


def _size(ax) -> int:
    mesh = _cur_mesh()
    if mesh is None or ax is None:
        return 1
    sizes = dict(mesh.shape)  # works for Mesh and AbstractMesh
    if isinstance(ax, tuple):
        n = 1
        for a in ax:
            n *= sizes.get(a, 1)
        return n
    return sizes.get(ax, 1)


def _cur_mesh():
    m = jax.sharding.get_abstract_mesh()
    return m if m.axis_names else None
