"""Dispatching wrapper for flash-decode."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from .. import interpret_mode


def flash_decode(q, k_cache, v_cache, lengths, *, window: int = 0,
                 softcap: float = 0.0, scale: Optional[float] = None,
                 block_k: int = 512, impl: Optional[str] = None
                 ) -> jnp.ndarray:
    """q: [B,1,H,D]; caches [B,L,KV,D]; lengths [B] -> [B,1,H,D].

    ``impl``: "ref" (oracle), "interpret" (kernel in the interpreter), or
    None/"pallas" (the kernel; interpreted on the CPU backend only)."""
    if impl == "ref":
        from .ref import flash_decode_ref
        return flash_decode_ref(q, k_cache, v_cache, lengths, window=window,
                                softcap=softcap, scale=scale)
    from .kernel import flash_decode_pallas
    return flash_decode_pallas(
        q, k_cache, v_cache, lengths, window=window, softcap=softcap,
        scale=scale, block_k=block_k, interpret=interpret_mode(impl))
