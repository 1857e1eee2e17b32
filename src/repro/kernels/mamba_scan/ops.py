"""Chunked Mamba selective-scan.

Within a chunk of length C, with cs_t = cumsum(clamp(dt*A)) (log decay):

    h_t = exp(cs_t) * (h_0 + sum_{j<=t} exp(-cs_j) * db_j)

computed with a cumulative sum over the chunk — no [C, C] pairwise term is
possible for Mamba-1 (decay is per (channel, state)), so the chunk form is
cumsum-based rather than attention-based. Numerics: the clamp bounds
exp(-cs_j) <= exp(C * CLAMP); C=16 keeps it inside fp32 range.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import interpret_mode
from .ref import LOG_DECAY_CLAMP

DEFAULT_CHUNK = 16


def mamba_scan(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
               B: jnp.ndarray, C: jnp.ndarray, D: jnp.ndarray,
               state: Optional[jnp.ndarray] = None, *,
               chunk: int = DEFAULT_CHUNK,
               impl: Optional[str] = None
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x, dt: [Bt, S, DI]; A: [DI, N]; B, C: [Bt, S, N]; D: [DI]."""
    impl = impl or ("pallas" if jax.default_backend() == "tpu" else "xla")
    if impl in ("pallas", "interpret"):
        from .kernel import mamba_scan_pallas
        return mamba_scan_pallas(
            x, dt, A, B, C, D, state, chunk=chunk,
            interpret=interpret_mode(impl))
    if impl == "ref":
        from .ref import mamba_scan_ref
        return mamba_scan_ref(x, dt, A, B, C, D, state)
    return _mamba_xla(x, dt, A, B, C, D, state, chunk=chunk)


def _mamba_xla(x, dt, A, B, C, D, state, *, chunk: int):
    Bt, S, DI = x.shape
    N = A.shape[-1]
    Cn = min(chunk, S)
    n = -(-S // Cn)
    Sp = n * Cn

    def pad(t):
        return jnp.pad(t, ((0, 0), (0, Sp - S), (0, 0))) if Sp != S else t

    xf = pad(x.astype(jnp.float32))
    dtf = pad(dt.astype(jnp.float32))       # dt=0 in padding -> decay 1, db 0
    Bf = pad(B.astype(jnp.float32))
    Cf = pad(C.astype(jnp.float32))
    Af = A.astype(jnp.float32)
    Df = D.astype(jnp.float32)

    # [n, Bt, Cn, *]
    xs = xf.reshape(Bt, n, Cn, DI).transpose(1, 0, 2, 3)
    dts = dtf.reshape(Bt, n, Cn, DI).transpose(1, 0, 2, 3)
    Bs = Bf.reshape(Bt, n, Cn, N).transpose(1, 0, 2, 3)
    Cs = Cf.reshape(Bt, n, Cn, N).transpose(1, 0, 2, 3)

    if state is None:
        state = jnp.zeros((Bt, DI, N), jnp.float32)

    def body(h0, inp):
        xc, dtc, bc, cc = inp               # [Bt,Cn,DI], [Bt,Cn,N]
        lda = dtc[..., None] * Af[None, None]               # [Bt,Cn,DI,N]
        lda = jnp.where(dtc[..., None] > 0,
                        jnp.clip(lda, -LOG_DECAY_CLAMP, -1e-8), 0.0)
        cs = jnp.cumsum(lda, axis=1)
        db = dtc[..., None] * bc[:, :, None, :] * xc[..., None]
        contrib = db * jnp.exp(-cs)
        cum = jnp.cumsum(contrib, axis=1)
        h = jnp.exp(cs) * (h0[:, None] + cum)               # [Bt,Cn,DI,N]
        y = jnp.einsum("bcdn,bcn->bcd", h, cc) + Df * xc
        return h[:, -1], y

    # group-checkpointed unrolled scan (see rwkv6_scan/ops.py): the state
    # carry round-trips HBM once per group, not once per chunk.
    group = 16
    while n % group:
        group //= 2
    ng = n // group

    def grouped(t):
        return t.reshape(ng, group, *t.shape[1:])

    def group_body(s, ginp):
        s, ys = jax.lax.scan(body, s, ginp, unroll=group)
        return s, ys

    group_body = jax.checkpoint(group_body)
    state, ys = jax.lax.scan(
        group_body, state, tuple(grouped(t) for t in (xs, dts, Bs, Cs)))
    ys = ys.reshape(n, *ys.shape[2:])
    y = ys.transpose(1, 0, 2, 3).reshape(Bt, Sp, DI)[:, :S]
    return y.astype(x.dtype), state


def mamba_decode_step(x, dt, A, B, C, D, state):
    """Single-token recurrence. x, dt: [Bt, DI]; B, C: [Bt, N]."""
    xf, dtf, bf, cf = (t.astype(jnp.float32) for t in (x, dt, B, C))
    Af, Df = A.astype(jnp.float32), D.astype(jnp.float32)
    lda = jnp.clip(dtf[..., None] * Af[None], -LOG_DECAY_CLAMP, -1e-8)
    h = jnp.exp(lda) * state + dtf[..., None] * bf[:, None, :] * xf[..., None]
    y = jnp.einsum("bdn,bn->bd", h, cf) + Df * xf
    return y.astype(x.dtype), h
