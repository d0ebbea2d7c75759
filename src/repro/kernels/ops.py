"""Jit'd dispatch wrappers over the Pallas kernels.

Model code calls these when ``cfg.use_pallas`` is set. Layout adaptation
lives here (models use (B, S, H, D); kernels use (B, H, S, D)). Each wrapper
runs the compiled kernel on a TPU; on any other backend it runs the Pallas
interpreter only when the caller asks for it with ``interpret=True``, and
raises otherwise — there is no silent fallback.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as _decode_pallas
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.rmsnorm import rmsnorm as _rmsnorm_pallas


def _check_backend(interpret: bool) -> None:
    backend = jax.default_backend()
    if not interpret and backend != "tpu":
        raise RuntimeError(
            f"Pallas kernels compile for TPU only; the default backend is "
            f"{backend!r}. Pass interpret=True to run the interpreter.")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_vjp(qt, kt, vt, causal, block_q, block_k, interpret):
    return _flash_pallas(qt, kt, vt, causal=causal, block_q=block_q,
                         block_k=block_k, interpret=interpret)


def _flash_fwd(qt, kt, vt, causal, block_q, block_k, interpret):
    return (_flash_vjp(qt, kt, vt, causal, block_q, block_k, interpret),
            (qt, kt, vt))


def _flash_bwd(causal, block_q, block_k, interpret, res, g):
    # backward through the jnp oracle (recompute-form flash bwd): exact same
    # math, memory-bounded by the chunked form on TPU via remat
    qt, kt, vt = res
    _, vjp = jax.vjp(lambda q, k, v: ref.mha_reference(q, k, v, causal=causal),
                     qt, kt, vt)
    return vjp(g)


_flash_vjp.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = True,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q: (B, S, Hq, D); k, v: (B, T, Hkv, D) -> (B, S, Hq, D).

    Differentiable: Pallas forward + oracle backward (custom_vjp)."""
    _check_backend(interpret)
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = _flash_vjp(qt, kt, vt, causal, block_q, block_k, interpret)
    return jnp.swapaxes(out, 1, 2)


def decode_attention(q, k_cache, v_cache, kv_len, *, block_k: int = 256,
                     interpret: bool = False):
    """q: (B, Hq, D) or (B, 1, Hq, D); caches: (B, T, Hkv, D)."""
    _check_backend(interpret)
    squeeze = q.ndim == 4
    if squeeze:
        q = q[:, 0]
    kt = jnp.swapaxes(k_cache, 1, 2)
    vt = jnp.swapaxes(v_cache, 1, 2)
    out = _decode_pallas(q, kt, vt, kv_len, block_k=block_k,
                         interpret=interpret)
    return out[:, None] if squeeze else out


def rmsnorm(x, scale, eps: float = 1e-5, *, interpret: bool = False):
    _check_backend(interpret)
    return _rmsnorm_pallas(x, scale, eps=eps, interpret=interpret)
