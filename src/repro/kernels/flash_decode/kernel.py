"""Flash-decode as a Pallas TPU kernel.

Decode attention is HBM-bandwidth-bound (one [1, D] query vs a [L, KV, D]
cache), so the kernel streams the cache once through VMEM in [block_k, D]
tiles with fp32 (acc, m, l) scratch, processing all G q-heads of one kv head
per grid cell ([G, D] q tile — MXU-aligned when G*D >= 128).

Per-sequence valid lengths arrive via scalar prefetch (SMEM) — the grid's kv
loop masks positions >= length, so ragged continuous-batching batches decode
in one call.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _decode_kernel(lengths_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *,
                   scale: float, window: int, softcap: float,
                   block_k: int, num_kv_blocks: int):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = lengths_ref[b]
    q = q_ref[0, 0].astype(jnp.float32) * scale          # [G, D]
    k = k_ref[0, 0].astype(jnp.float32)                  # [bk, D]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [G, bk]
    if softcap > 0.0:
        s = jnp.tanh(s / softcap) * softcap
    kpos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos < length
    if window > 0:
        mask = mask & (kpos > length - 1 - window)
    s = jnp.where(mask, s, _NEG_INF)
    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None]) * mask
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * corr + p.sum(axis=-1)
    m_ref[...] = m_new
    pv = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0, 0],
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr[:, None] + pv

    @pl.when(j == num_kv_blocks - 1)
    def _emit():
        o_ref[0, 0] = (acc_ref[...]
                       / (l_ref[...][:, None] + 1e-30)).astype(o_ref.dtype)


def flash_decode_pallas(q: jnp.ndarray, k_cache: jnp.ndarray,
                        v_cache: jnp.ndarray, lengths: jnp.ndarray, *,
                        window: int = 0, softcap: float = 0.0,
                        scale: Optional[float] = None, block_k: int = 512,
                        interpret: bool = False) -> jnp.ndarray:
    """q: [B, 1, H, D]; caches: [B, L, KV, D]; lengths: [B] -> [B, 1, H, D]."""
    B, _, H, D = q.shape
    L, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    scale = scale if scale is not None else D ** -0.5
    bk = min(block_k, L)
    nk = -(-L // bk)
    Lp = nk * bk
    # [B, KV, G, D] query tile; caches [B, KV, L, D]
    qt = q.reshape(B, 1, KV, G, D)[:, 0].transpose(0, 1, 2, 3)
    kt = jnp.moveaxis(k_cache, 2, 1)
    vt = jnp.moveaxis(v_cache, 2, 1)
    if Lp != L:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, Lp - L), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, Lp - L), (0, 0)))

    kernel = functools.partial(
        _decode_kernel, scale=scale, window=window, softcap=softcap,
        block_k=bk, num_kv_blocks=nk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KV, nk),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, j, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, lens: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, lens: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, j, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qt, kt, vt)
    return out.reshape(B, 1, H, D)
