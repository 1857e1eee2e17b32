"""Wrapper for the grouped GEMM: block-aligns ragged groups and dispatches.

``grouped_gemm(x_sorted, group_sizes, W)`` pads each expert's token segment
to a multiple of block_m (building the block-aligned buffer + per-block
expert ids), runs the kernel, and scatters back — the dropless-MoE building
block. On the CPU backend the kernel runs in interpret mode;
``impl="xla"`` uses jax.lax.ragged_dot.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .. import interpret_mode


def grouped_gemm(x: jnp.ndarray, group_sizes: jnp.ndarray, W: jnp.ndarray, *,
                 block_m: int = 128, impl: Optional[str] = None
                 ) -> jnp.ndarray:
    """x: [T, D] sorted by expert; group_sizes: [E]; W: [E, D, F] -> [T, F]."""
    if impl == "xla":
        return jax.lax.ragged_dot(x, W, group_sizes.astype(jnp.int32))
    if impl == "ref":
        from .ref import grouped_gemm_ref
        return grouped_gemm_ref(x, group_sizes, W)

    T, D = x.shape
    E, _, F = W.shape
    sizes = group_sizes.astype(jnp.int32)
    padded = -(-sizes // block_m) * block_m          # per-expert padded sizes
    p_offsets = jnp.cumsum(padded) - padded          # aligned segment starts
    offsets = jnp.cumsum(sizes) - sizes
    Tp = -(-T // block_m) * block_m + E * block_m    # safe upper bound

    # scatter rows into the block-aligned buffer
    tok = jnp.arange(T)
    expert_of = jnp.searchsorted(jnp.cumsum(sizes), tok, side="right")
    new_pos = p_offsets[expert_of] + (tok - offsets[expert_of])
    xb = jnp.zeros((Tp, D), x.dtype).at[new_pos].set(x)

    # per-block expert ids
    blk = jnp.arange(Tp // block_m) * block_m
    block_expert = jnp.searchsorted(jnp.cumsum(padded), blk, side="right")
    block_expert = jnp.clip(block_expert, 0, E - 1)

    from .kernel import grouped_gemm_pallas
    ob = grouped_gemm_pallas(xb, block_expert, W, block_m=block_m,
                             interpret=interpret_mode(impl))
    return ob[new_pos]
