"""Flash attention as Pallas TPU kernels: one forward, and its backward.

Layout: q [B, S, H, D] and k, v [B, T, KV, D] are read as [B, S, H*D] and
[B, T, KV*D] (a reshape, no copy). The G = H // KV q heads of one kv head
are adjacent columns, so a q block is (block_q rows, G*D columns): one
k/v block serves the whole group, and no transpose runs around the call.

Grids. Forward and dq: (B, KV, q blocks, kv blocks); dk/dv: (B, KV,
kv blocks, q blocks). The last grid dimension is sequential and the f32
accumulators (acc, m, l; dq; dk, dv) live in VMEM scratch across it, so a
score tile never reaches HBM. Each step loops over the G heads.

Precision: q, k, v, p and ds reach the MXU in their own dtype (bf16 from
the models) with f32 accumulation (``preferred_element_type``); the scale,
the soft-cap, the mask and the softmax statistics are f32. The backward
recomputes p from (q, k, lse) with lse = m + log(l) (f32, [B, H, 1, S]
rows), and uses delta = rowsum(dO * O): five tile matmuls (q.k, dO.v,
p.dO, ds.q, ds.k).

Block skipping: a kv block wholly above the causal diagonal (``q_offset``
counted) or wholly outside a sliding window is skipped by ``pl.when`` in
every kernel, and the index maps clamp to the nearest needed block, so a
skipped step issues no DMA. Blocks that the diagonal, the window edge or
the padded end of k cross are masked; the rest run without a mask.

Block sizes come from the shapes (``block_sizes``); a length no longer
than its block is one block and needs no padding. A head narrower than
128 lanes is zero-padded to 128 columns.

Supports GQA, causal masking, sliding windows (gemma2 local layers) and
logit soft-capping, in the forward and the backward.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_NT = (((1,), (1,)), ((), ()))          # a @ b.T
_NN = (((1,), (0,)), ((), ()))          # a @ b
# rows of a q block and of a k/v block for 128-wide bf16 heads, timed on a
# TPU v5e at B 2, S 4096, H 8, KV 4 (PERF.md §6, PR 14): within 4% of the
# fastest (1024, 1024), at under half its compile time
_BLOCK_Q, _BLOCK_K = 512, 1024
_VMEM_LIMIT = 64 * 1024 * 1024


def block_sizes(S: int, T: int, D: int, dtype) -> Tuple[int, int]:
    """(block_q, block_k) for q of S rows and k/v of T rows of width D.

    Wider heads get shorter blocks so the (block_q, block_k) f32 tiles and
    the accumulators stay within VMEM; a length no longer than the block
    is one whole block."""
    shrink = max(1, D // 128) * max(1, jnp.dtype(dtype).itemsize // 2)
    bq, bk = max(128, _BLOCK_Q // shrink), max(128, _BLOCK_K // shrink)
    return (S if S <= bq else bq), (T if T <= bk else bk)


# ------------------------------------------------------------ block pairs

@dataclasses.dataclass(frozen=True)
class _Blocks:
    """Which (q block i, kv block j) pairs a call needs, and their masks.
    ``i`` and ``j`` are grid indices, traced in the kernels and the index
    maps; a condition known without them is a Python bool."""
    causal: bool
    window: int
    q_offset: int
    kv_len: int
    bq: int
    bk: int
    nq: int
    nk: int

    def kv_span(self, i):
        """First and last kv block that q block ``i`` attends to."""
        hi = self.nk - 1
        if self.causal:
            hi = jnp.minimum(hi, (self.q_offset + (i + 1) * self.bq - 1)
                             // self.bk)
        lo = 0
        if self.window > 0:
            lo = jnp.minimum(jnp.maximum(
                self.q_offset + i * self.bq - self.window + 1, 0) // self.bk,
                hi)
        return lo, hi

    def q_span(self, j):
        """First and last q block that attends to kv block ``j``; last <
        first when none does (the block's dk, dv are zero)."""
        lo = 0
        if self.causal:
            lo = jnp.minimum(jnp.maximum(j * self.bk - self.q_offset, 0)
                             // self.bq, self.nq - 1)
        hi = self.nq - 1
        if self.window > 0:
            y = (j + 1) * self.bk - 1 - self.q_offset + self.window
            hi = jnp.where(y >= 1, jnp.minimum(
                (jnp.maximum(y, 1) - 1) // self.bq, self.nq - 1), -1)
        return lo, hi

    def kv_block(self, i, j):
        """kv block to fetch at step (i, j): a skipped step repeats a
        needed block's index, so it issues no DMA."""
        lo, hi = self.kv_span(i)
        return jnp.minimum(jnp.maximum(j, lo), hi)

    def q_block(self, i, j):
        lo, hi = self.q_span(j)
        return jnp.maximum(jnp.minimum(jnp.maximum(i, lo), hi), 0)

    def unmasked(self, i, j):
        """Whether no (query, key) pair of block (i, j) is masked."""
        ok = True
        if self.causal:
            ok = ok & ((j + 1) * self.bk - 1 <= self.q_offset + i * self.bq)
        if self.window > 0:
            ok = ok & (j * self.bk
                       > self.q_offset + (i + 1) * self.bq - 1 - self.window)
        if self.kv_len % self.bk:
            ok = ok & ((j + 1) * self.bk <= self.kv_len)
        return ok

    def mask(self, i, j, transposed=False):
        """Boolean mask of block (i, j): [bq, bk], or [bk, bq]."""
        shape, qa, ka = ((self.bk, self.bq), 1, 0) if transposed else \
            ((self.bq, self.bk), 0, 1)
        qpos = self.q_offset + i * self.bq + jax.lax.broadcasted_iota(
            jnp.int32, shape, qa)
        kpos = j * self.bk + jax.lax.broadcasted_iota(jnp.int32, shape, ka)
        mask = kpos < self.kv_len
        if self.causal:
            mask = mask & (kpos <= qpos)
        if self.window > 0:
            mask = mask & (kpos > qpos - self.window)
        return mask


def _when_needed(run, unmasked, step):
    """Run ``step(masked)`` when ``run``: without the mask where the block
    needs none."""
    if unmasked is True:
        pl.when(run)(lambda: step(False))
        return
    pl.when(run & unmasked)(lambda: step(False))
    pl.when(run & jnp.logical_not(unmasked))(lambda: step(True))


def _logits(a, b, dims, *, scale, softcap):
    """Scaled (and soft-capped) f32 logits of a bf16 matmul; also tanh's
    value where the backward needs its derivative."""
    s = jax.lax.dot_general(a, b, dims,
                            preferred_element_type=jnp.float32) * scale
    if softcap > 0.0:
        t = jnp.tanh(s / softcap)
        return t * softcap, t
    return s, None


def _column(row):
    """[1, n] -> [n, 1] (a transpose the vector unit supports)."""
    return jnp.transpose(jnp.broadcast_to(row, (8, row.shape[1])))[:, :1]


def _row(col):
    """[n, 1] -> [1, n]."""
    return jnp.transpose(jnp.broadcast_to(col, (col.shape[0], 128)))[:1, :]


# --------------------------------------------------------------- forward

def _fwd_kernel(q_ref, k_ref, v_ref, *refs, G, D, blocks, with_lse, scale,
                softcap):
    if with_lse:
        o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    else:
        o_ref, acc_ref, m_ref, l_ref = refs
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def step(masked):
        k, v = k_ref[...], v_ref[...]
        if masked:
            mask = blocks.mask(i, j)
        for g in range(G):
            cols = slice(g * D, (g + 1) * D)
            s, _ = _logits(q_ref[:, cols], k, _NT, scale=scale,
                           softcap=softcap)
            if masked:
                s = jnp.where(mask, s, _NEG_INF)
            m_prev, l_prev = m_ref[g], l_ref[g]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            if masked:   # a row with no key yet must gain exactly zero
                p = jnp.where(mask, p, 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_ref[g] = l_prev * corr + p.sum(axis=-1, keepdims=True)
            m_ref[g] = m_new
            acc_ref[g] = acc_ref[g] * corr + jax.lax.dot_general(
                p.astype(v.dtype), v, _NN,
                preferred_element_type=jnp.float32)

    lo, hi = blocks.kv_span(i)
    _when_needed((j >= lo) & (j <= hi), blocks.unmasked(i, j), step)

    @pl.when(j == blocks.nk - 1)
    def _emit():
        for g in range(G):
            l = l_ref[g]
            o_ref[:, g * D:(g + 1) * D] = (
                acc_ref[g] / (l + 1e-30)).astype(o_ref.dtype)
            if with_lse:
                lse_ref[g] = _row(m_ref[g] + jnp.log(jnp.maximum(l, 1e-30)))


# -------------------------------------------------------------- backward

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dq_ref, acc_ref,
               *, G, D, blocks, scale, softcap):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(masked):
        k, v = k_ref[...], v_ref[...]
        if masked:
            mask = blocks.mask(i, j)
        for g in range(G):
            cols = slice(g * D, (g + 1) * D)
            s, t = _logits(q_ref[:, cols], k, _NT, scale=scale,
                           softcap=softcap)
            p = jnp.exp(s - _column(lse_ref[g]))
            if masked:
                p = jnp.where(mask, p, 0.0)
            dp = jax.lax.dot_general(do_ref[:, cols], v, _NT,
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - _column(d_ref[g]))
            if t is not None:
                ds = ds * (1.0 - t * t)
            acc_ref[g] += jax.lax.dot_general(
                ds.astype(k.dtype), k, _NN,
                preferred_element_type=jnp.float32)

    lo, hi = blocks.kv_span(i)
    _when_needed((j >= lo) & (j <= hi), blocks.unmasked(i, j), step)

    @pl.when(j == blocks.nk - 1)
    def _emit():
        for g in range(G):
            dq_ref[:, g * D:(g + 1) * D] = (
                acc_ref[g] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *, G, D, blocks, scale, softcap):
    """Transposed tiles [bk, bq]: lse and delta broadcast as rows."""
    j, i = pl.program_id(2), pl.program_id(3)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def step(masked):
        k, v = k_ref[...], v_ref[...]
        if masked:
            mask = blocks.mask(i, j, transposed=True)
        dk = dv = 0.0
        for g in range(G):
            cols = slice(g * D, (g + 1) * D)
            q, do = q_ref[:, cols], do_ref[:, cols]
            s, t = _logits(k, q, _NT, scale=scale, softcap=softcap)
            p = jnp.exp(s - lse_ref[g])
            if masked:
                p = jnp.where(mask, p, 0.0)
            dv = dv + jax.lax.dot_general(
                p.astype(do.dtype), do, _NN,
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(v, do, _NT,
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - d_ref[g])
            if t is not None:
                ds = ds * (1.0 - t * t)
            dk = dk + jax.lax.dot_general(
                ds.astype(q.dtype), q, _NN,
                preferred_element_type=jnp.float32)
        dk_acc[...] += dk
        dv_acc[...] += dv

    lo, hi = blocks.q_span(j)
    _when_needed((i >= lo) & (i <= hi), blocks.unmasked(i, j), step)

    @pl.when(i == blocks.nq - 1)
    def _emit():
        dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


# --------------------------------------------------------------- calls

def _geometry(q, k, *, causal, window, q_offset, block_q, block_k):
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    D = -(-D // 128) * 128             # a head's columns fill whole lanes
    bq, bk = block_sizes(S, T, D, q.dtype)
    bq, bk = block_q or bq, block_k or bk
    nq, nk = -(-S // bq), -(-T // bk)
    blocks = _Blocks(causal, window, q_offset, T, bq, bk, nq, nk)
    return (B, S, H, D, T, KV, H // KV, nq, nk), blocks


def _rows(x, n, d):
    """[B, L, N, D] -> [B, n, N*d]: zero rows up to n, and zero columns
    up to d in each head (which leave q.k, p.v and their gradients as
    they are)."""
    B, L, N, D = x.shape
    if n != L or d != D:
        x = jnp.pad(x, ((0, 0), (0, n - L), (0, 0), (0, d - D)))
    return x.reshape(B, n, N * d)


def _heads(y, like):
    """[B, n, N*d] -> ``like``'s [B, L, N, D]."""
    B, L, N, D = like.shape
    return y.reshape(B, y.shape[1], N, -1)[:, :L, :, :D]


def _stats(x, n):
    """f32 [B, H, 1, L] -> [B, H, 1, n]."""
    L = x.shape[-1]
    return x if n == L else jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, n - L)))


def _params(semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def flash_attention_fwd(q, k, v, *, causal=True, window=0, softcap=0.0,
                        scale=None, q_offset=0, with_lse=False,
                        block_q=None, block_k=None, interpret=False):
    """q [B,S,H,D]; k, v [B,T,KV,D] -> out [B,S,H,D], and with ``with_lse``
    also lse, f32 [B, H, 1, S]."""
    (B, S, H, D, T, KV, G, nq, nk), blocks = _geometry(
        q, k, causal=causal, window=window, q_offset=q_offset,
        block_q=block_q, block_k=block_k)
    bq, bk = blocks.bq, blocks.bk
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    kernel = functools.partial(_fwd_kernel, G=G, D=D, blocks=blocks,
                               with_lse=with_lse, scale=scale,
                               softcap=softcap)
    q_spec = pl.BlockSpec((None, bq, G * D), lambda b, h, i, j: (b, i, h))
    kv_spec = pl.BlockSpec((None, bk, D),
                           lambda b, h, i, j: (b, blocks.kv_block(i, j), h))
    out_specs = [q_spec]
    out_shape = [jax.ShapeDtypeStruct((B, nq * bq, H * D), q.dtype)]
    if with_lse:
        out_specs.append(pl.BlockSpec((None, G, 1, bq),
                                      lambda b, h, i, j: (b, h, 0, i)))
        out_shape.append(jax.ShapeDtypeStruct((B, H, 1, nq * bq),
                                              jnp.float32))
    causal_share = 0.5 if causal else 1.0
    res = pl.pallas_call(
        kernel,
        grid=(B, KV, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((G, bq, D), jnp.float32),
                        pltpu.VMEM((G, bq, 1), jnp.float32),
                        pltpu.VMEM((G, bq, 1), jnp.float32)],
        compiler_params=_params(("parallel", "parallel", "parallel",
                                 "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=int(4 * B * H * S * T * D * causal_share),
            transcendentals=int(B * H * S * T * causal_share),
            bytes_accessed=int(2 * q.nbytes + k.nbytes + v.nbytes)),
        interpret=interpret,
        name="flash_fwd",
    )(_rows(q, nq * bq, D), _rows(k, nk * bk, D), _rows(v, nk * bk, D))
    out = _heads(res[0], q)
    return (out, res[1][..., :S]) if with_lse else out


def flash_attention_bwd(q, k, v, out, lse, dout, *, causal=True, window=0,
                        softcap=0.0, scale=None, q_offset=0, block_q=None,
                        block_k=None, interpret=False):
    """Gradients (dq, dk, dv) of ``flash_attention_fwd`` from its residuals
    (q, k, v, out, lse) and the output's cotangent."""
    (B, S, H, D, T, KV, G, nq, nk), blocks = _geometry(
        q, k, causal=causal, window=window, q_offset=q_offset,
        block_q=block_q, block_k=block_k)
    bq, bk = blocks.bq, blocks.bk
    Sp, Tp = nq * bq, nk * bk
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    delta = jnp.einsum("bshd,bshd->bhs", dout.astype(jnp.float32),
                       out.astype(jnp.float32))[:, :, None, :]
    args = (_rows(q, Sp, D), _rows(k, Tp, D), _rows(v, Tp, D),
            _rows(dout, Sp, D),
            _stats(lse, Sp), _stats(delta, Sp))
    kw = dict(G=G, D=D, blocks=blocks, scale=scale, softcap=softcap)
    causal_share = 0.5 if causal else 1.0
    flops = 4 * B * H * S * T * D * causal_share
    sem = _params(("parallel", "parallel", "parallel", "arbitrary"))

    # dq: grid (b, h, i, j), kv innermost
    q_spec = pl.BlockSpec((None, bq, G * D), lambda b, h, i, j: (b, i, h))
    kv_spec = pl.BlockSpec((None, bk, D),
                           lambda b, h, i, j: (b, blocks.kv_block(i, j), h))
    st_spec = pl.BlockSpec((None, G, 1, bq), lambda b, h, i, j: (b, h, 0, i))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **kw),
        grid=(B, KV, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, st_spec, st_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, Sp, H * D), q.dtype),
        scratch_shapes=[pltpu.VMEM((G, bq, D), jnp.float32)],
        compiler_params=sem,
        cost_estimate=pl.CostEstimate(
            flops=int(1.5 * flops),
            transcendentals=int(B * H * S * T * causal_share),
            bytes_accessed=int(3 * q.nbytes + k.nbytes + v.nbytes)),
        interpret=interpret,
        name="flash_dq",
    )(*args)

    # dk, dv: grid (b, h, j, i), q innermost
    q_spec = pl.BlockSpec((None, bq, G * D),
                          lambda b, h, j, i: (b, blocks.q_block(i, j), h))
    kv_spec = pl.BlockSpec((None, bk, D), lambda b, h, j, i: (b, j, h))
    st_spec = pl.BlockSpec((None, G, 1, bq),
                           lambda b, h, j, i: (b, h, 0, blocks.q_block(i, j)))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **kw),
        grid=(B, KV, nk, nq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, st_spec, st_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct((B, Tp, KV * D), k.dtype),
                   jax.ShapeDtypeStruct((B, Tp, KV * D), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        compiler_params=sem,
        cost_estimate=pl.CostEstimate(
            flops=int(2 * flops),
            transcendentals=int(B * H * S * T * causal_share),
            bytes_accessed=int(2 * q.nbytes + 3 * k.nbytes + 3 * v.nbytes)),
        interpret=interpret,
        name="flash_dkv",
    )(*args)
    return _heads(dq, q), _heads(dk, k), _heads(dv, v)


@functools.lru_cache(maxsize=None)
def _vjp(causal, window, softcap, scale, q_offset, block_q, block_k,
         interpret):
    kw = dict(causal=causal, window=window, softcap=softcap, scale=scale,
              q_offset=q_offset, block_q=block_q, block_k=block_k,
              interpret=interpret)

    @jax.custom_vjp
    def f(q, k, v):
        return flash_attention_fwd(q, k, v, **kw)

    def fwd(q, k, v):
        out, lse = flash_attention_fwd(q, k, v, with_lse=True, **kw)
        return out, (q, k, v, out, lse)

    def bwd(res, dout):
        # JAX traces a custom VJP's backward outside the transpose's name
        # stack; the scope tells its ops from the forward's in a profile
        with jax.named_scope("bwd"):
            return flash_attention_bwd(*res, dout, **kw)

    f.defvjp(fwd, bwd)
    return f


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0,
                    softcap: float = 0.0, scale: Optional[float] = None,
                    q_offset: int = 0, block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False) -> jnp.ndarray:
    """q: [B, S, H, D]; k, v: [B, T, KV, D] -> [B, S, H, D], differentiable
    (custom VJP: the backward kernels above). ``block_q``/``block_k``
    default to ``block_sizes``."""
    return _vjp(causal, window, softcap, scale, q_offset, block_q, block_k,
                interpret)(q, k, v)
