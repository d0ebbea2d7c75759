"""Cross-pod gradient compression (int8 all-gather + local reduction).

Hierarchical layout: within a pod, parameters/optimizer are ZeRO-sharded
over "data" and gradients reduce over the fast intra-pod ICI in bf16; ACROSS
pods (the slow hop: data-center network or inter-slice links) gradients are
exchanged in int8 with a shared max-abs scale:

  scale  = pmax(|g|, pod) / 127          (tiny collective)
  q      = round(g / scale) : int8
  G      = all_gather(q, pod)            (wire bytes = 1/2 of bf16, 1/4 fp32)
  out    = sum(dequant(G)) * scale

Error is bounded by scale/2 per element (~0.4% of max |g|); the optimizer's
Adam normalization absorbs it (validated in tests against the exact sum).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp


def quantized_allreduce(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Inside shard_map: sum ``x`` over ``axis_name`` with int8 wire format."""
    xf = x.astype(jnp.float32)
    absmax = jax.lax.pmax(jnp.max(jnp.abs(xf)), axis_name)
    scale = jnp.maximum(absmax / 127.0, 1e-20)
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    gathered = jax.lax.all_gather(q, axis_name)          # int8 on the wire
    return (jnp.sum(gathered.astype(jnp.float32), axis=0) * scale).astype(x.dtype)


def tree_quantized_allreduce(tree: Any, axis_name: str) -> Any:
    return jax.tree.map(lambda g: quantized_allreduce(g, axis_name), tree)


def make_compressed_grad_fn(loss_fn, mesh, pod_axis: str = "pod"):
    """Returns grad_fn(params, batch) whose cross-pod gradient sync uses the
    int8 path. Parameters must be replicated across ``pod_axis`` (hierarchical
    ZeRO: shard over "data" only); the batch is sharded across pods.
    """
    inner_axes = frozenset(a for a in mesh.axis_names if a != pod_axis)

    def per_pod_grad(params, batch):
        # params replicated over pod; batch is this pod's shard
        grads = jax.grad(loss_fn)(params, batch)
        # mean over pods with int8 wire format
        n = mesh.shape[pod_axis]
        summed = tree_quantized_allreduce(grads, pod_axis)
        return jax.tree.map(lambda g: g / n, summed)

    return jax.shard_map(
        per_pod_grad, mesh=mesh,
        in_specs=(jax.sharding.PartitionSpec(),            # params: replicated over pod
                  jax.sharding.PartitionSpec(pod_axis)),   # batch dim 0 across pods
        out_specs=jax.sharding.PartitionSpec(),
        check_vma=False,
        axis_names={pod_axis},
    )
