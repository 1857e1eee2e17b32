"""Flash attention as a Pallas TPU kernel.

TPU-native tiling: grid (B, H, num_q_blocks, num_kv_blocks); the innermost
kv dimension is sequential, so fp32 accumulators (acc, m, l) live in VMEM
scratch across kv steps (HBM->VMEM traffic is one pass over K/V per q block,
the flash property). Block shapes default to (128, head_dim): MXU-aligned
(128 lanes) and ~4 blocks x 128x128 x 4B = 256 KiB VMEM working set.

Supports GQA (kv head = q head // G via the k/v index_map), causal masking,
sliding windows (gemma2 local layers) and logit soft-capping.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                 scale: float, causal: bool, window: int, softcap: float,
                 q_offset: int, kv_len: int, block_q: int, block_k: int,
                 num_kv_blocks: int):
    ik = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # [bq, D]
    k = k_ref[0, 0].astype(jnp.float32)                  # [bk, D]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [bq, bk]
    if softcap > 0.0:
        s = jnp.tanh(s / softcap) * softcap

    qpos = q_offset + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = kpos < kv_len
    if causal:
        mask = mask & (kpos <= qpos)
    if window > 0:
        mask = mask & (kpos > qpos - window)
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    # explicit mask on p: fully-masked blocks must contribute exactly zero
    p = jnp.exp(s - m_new[:, None]) * mask
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * corr + p.sum(axis=-1)
    m_ref[...] = m_new
    pv = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0, 0],
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr[:, None] + pv

    @pl.when(ik == num_kv_blocks - 1)
    def _emit():
        o_ref[0, 0] = (acc_ref[...]
                       / (l_ref[...][:, None] + 1e-30)).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0,
                    softcap: float = 0.0, scale: Optional[float] = None,
                    q_offset: int = 0, block_q: int = 128,
                    block_k: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """q: [B, S, H, D]; k, v: [B, T, KV, D] -> [B, S, H, D]."""
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = scale if scale is not None else D ** -0.5
    bq = min(block_q, S)
    bk = min(block_k, T)
    nq = -(-S // bq)
    nk = -(-T // bk)
    Sp, Tp = nq * bq, nk * bk
    # layout: [B, H, S, D] so the (head, q-block) tile is contiguous
    qt = jnp.moveaxis(q, 2, 1)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if Sp != S:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
    if Tp != T:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, Tp - T), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, Tp - T), (0, 0)))

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, q_offset=q_offset, kv_len=T, block_q=bq,
        block_k=bk, num_kv_blocks=nk)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sp, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return jnp.moveaxis(out, 1, 2)[:, :S]
