"""Chunked RWKV6 wkv scan (GLA-style chunkwise linear attention).

Within a chunk of length C (default 16), with A_t = prod_{s<=t} w_s:

    out_t = (r_t . A_{t-1}) S_0
          + sum_{j<t} [(r_t . A_{t-1}) . (k_j / A_j)] v_j      (strict lower)
          + (r_t . u . k_t) v_t                                 (diagonal)
    S_C   = diag(A_C) S_0 + sum_j (A_C / A_j . k_j) v_j^T

All chunk terms are matmuls (MXU-shaped in the Pallas kernel). Stability:
log-decay is clamped to [-CLAMP, -1e-6]; with C=16, |cumsum| <= 16*CLAMP
stays inside fp32 exp range.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import interpret_mode

LOG_DECAY_CLAMP = 5.0
DEFAULT_CHUNK = 16


def rwkv6_scan(r: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
               w: jnp.ndarray, u: jnp.ndarray,
               state: Optional[jnp.ndarray] = None, *,
               chunk: int = DEFAULT_CHUNK,
               impl: Optional[str] = None
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """r,k,v,w: [B, S, H, D]; u: [H, D]. Returns (out, final_state)."""
    impl = impl or ("pallas" if jax.default_backend() == "tpu" else "xla")
    if impl in ("pallas", "interpret"):
        from .kernel import rwkv6_scan_pallas
        return rwkv6_scan_pallas(
            r, k, v, w, u, state, chunk=chunk,
            interpret=interpret_mode(impl))
    if impl == "ref":
        from .ref import rwkv6_scan_ref
        return rwkv6_scan_ref(r, k, v, w, u, state)
    return _rwkv6_xla(r, k, v, w, u, state, chunk=chunk)


def _rwkv6_xla(r, k, v, w, u, state, *, chunk: int):
    B, S, H, D = r.shape
    C = min(chunk, S)
    n = -(-S // C)
    Sp = n * C

    def pad(t):
        return jnp.pad(t, ((0, 0), (0, Sp - S), (0, 0), (0, 0))) if Sp != S else t

    rf = pad(r.astype(jnp.float32))
    kf = pad(k.astype(jnp.float32))
    vf = pad(v.astype(jnp.float32))
    # pad decay with w=1 (log 0) so padding does not decay the state
    logw = jnp.log(jnp.clip(w.astype(jnp.float32), 1e-30, 1.0))
    logw = jnp.clip(logw, -LOG_DECAY_CLAMP, -1e-6)
    logw = jnp.pad(logw, ((0, 0), (0, Sp - S), (0, 0), (0, 0))) if Sp != S else logw
    # padded keys must not contribute: zero k,v in padding (pad() already does)

    # [n, B, H, C, D]
    def chunked(t):
        return t.reshape(B, n, C, H, D).transpose(1, 0, 3, 2, 4)

    rc, kc, vc, lwc = chunked(rf), chunked(kf), chunked(vf), chunked(logw)
    uf = u.astype(jnp.float32)

    if state is None:
        state = jnp.zeros((B, H, D, D), jnp.float32)

    mask = jnp.tril(jnp.ones((C, C), jnp.float32), -1)        # strict lower

    def body(s, inp):
        rch, kch, vch, lw = inp                 # [B, H, C, D]
        cs = jnp.cumsum(lw, axis=2)             # log A_t
        a_prev = jnp.exp(cs - lw)               # A_{t-1}
        a_inv = jnp.exp(-cs)                    # 1 / A_t
        a_end = jnp.exp(cs[:, :, -1:, :])       # A_C
        r_t = rch * a_prev                      # [B,H,C,D]
        k_t = kch * a_inv
        att = jnp.einsum("bhcd,bhjd->bhcj", r_t, k_t) * mask
        out = jnp.einsum("bhcj,bhjd->bhcd", att, vch)
        out = out + jnp.einsum("bhcd,bhdv->bhcv", r_t, s)
        diag = jnp.einsum("bhcd,bhcd->bhc", rch * uf[None, :, None, :], kch)
        out = out + diag[..., None] * vch
        k_end = kch * jnp.exp(cs[:, :, -1:, :] - cs)          # A_C / A_j * k_j
        s_new = a_end[:, :, 0, :, None] * s + jnp.einsum(
            "bhjd,bhjv->bhdv", k_end, vch)
        return s_new, out

    # group-checkpointed unrolled scan: the [B,H,D,D] state carry only
    # round-trips HBM once per GROUP of chunks (the Pallas kernel keeps it
    # in VMEM scratch for the whole row); backward recomputes one group.
    group = 16
    while n % group:
        group //= 2
    ng = n // group

    def grouped(t):
        return t.reshape(ng, group, *t.shape[1:])

    def group_body(s, ginp):
        s, outs = jax.lax.scan(body, s, ginp, unroll=group)
        return s, outs

    group_body = jax.checkpoint(group_body)
    state, outs = jax.lax.scan(
        group_body, state, tuple(grouped(t) for t in (rc, kc, vc, lwc)))
    outs = outs.reshape(n, *outs.shape[2:])
    out = outs.transpose(1, 0, 3, 2, 4).reshape(B, Sp, H, D)[:, :S]
    return out.astype(r.dtype), state


def rwkv6_decode_step(r, k, v, w, u, state):
    """Single-token recurrence. r,k,v,w: [B, H, D]; state [B, H, D, D]."""
    rf, kf, vf = (t.astype(jnp.float32) for t in (r, k, v))
    wf = jnp.exp(jnp.clip(jnp.log(jnp.clip(w.astype(jnp.float32), 1e-30, 1.0)),
                          -LOG_DECAY_CLAMP, -1e-6))
    uf = u.astype(jnp.float32)
    kv = kf[..., :, None] * vf[..., None, :]
    out = jnp.einsum("bhk,bhkv->bhv", rf, state + uf[..., :, None] * kv)
    state = wf[..., :, None] * state + kv
    return out.astype(r.dtype), state
