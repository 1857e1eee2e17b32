"""Dispatching wrapper for attention.

Implementations:
- "ref":     naive materialized softmax (oracle; small shapes only);
- "xla":     double-chunked online-softmax attention in pure jnp with a
             flash-style custom VJP — the path for CPU runs, 512-device
             dry-run lowering, and sequence-sharded attention;
- "pallas":  the Pallas TPU kernels (kernel.py): forward, and a backward
             of dq and dk/dv kernels under a custom VJP, so training and
             serving run the same forward; interpreted on the CPU backend
             only (``repro.kernels.interpret_mode``);
- "interpret": the Pallas kernels in the interpreter on any backend.

``impl=None`` auto-selects: pallas on TPU, xla elsewhere.

Both paths feed the MXU q, k, v in their own dtype (bf16 from the models),
accumulate in f32 and keep the scale, soft-cap and softmax statistics in
f32. The Pallas backward covers soft-capping and sliding windows itself:
``_mha_bwd_impl`` serves the XLA path alone.

Under active sharding rules the Pallas kernels run per chip inside a
``shard_map`` over the batch and head axes, when the rules leave the
sequence unsharded (``attn_seq``, ``kv_seq`` unbound) and bind ``heads``
and ``kv_heads`` alike: each chip then holds whole sequences of its heads
and their kv heads, and no collective runs around the call. Otherwise
(the ``context`` strategy, or kv heads replicated under sharded q heads)
the XLA path runs, which the partitioner can split along the sequence.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .. import interpret_mode
from .ref import mha_ref

_NEG_INF = -1e30


def _auto_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def mha(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
        causal: bool = True, window: int = 0, softcap: float = 0.0,
        scale: Optional[float] = None, q_offset: int = 0,
        q_chunk: int = 1024, kv_chunk: int = 1024,
        impl: Optional[str] = None) -> jnp.ndarray:
    """Multi-head (GQA) attention. q [B,S,H,D]; k,v [B,T,KV,D] -> [B,S,H,D]."""
    impl = impl or _auto_impl()
    if impl == "ref":
        return mha_ref(q, k, v, causal=causal, window=window, softcap=softcap,
                       scale=scale, q_offset=q_offset)
    if impl in ("pallas", "interpret"):
        from .kernel import flash_attention
        fn = functools.partial(
            flash_attention, causal=causal, window=window, softcap=softcap,
            scale=scale, q_offset=q_offset, interpret=interpret_mode(impl))
        from ...sharding.api import active_rules
        rules = active_rules()
        if rules is None:
            return fn(q, k, v)
        specs = _head_parallel_specs(rules)
        if specs is not None:
            q_spec, kv_spec = specs
            return jax.shard_map(
                fn, mesh=rules.mesh, in_specs=(q_spec, kv_spec, kv_spec),
                out_specs=q_spec, check_vma=False)(q, k, v)
        impl = "xla"
    if impl == "xla":
        return _mha_xla(q, k, v, causal=causal, window=window, softcap=softcap,
                        scale=scale, q_offset=q_offset,
                        q_chunk=q_chunk, kv_chunk=kv_chunk)
    raise ValueError(f"unknown attention impl: {impl}")


def _head_parallel_specs(rules):
    """(q spec, k/v spec) under which each chip attends whole sequences of
    its own heads, or None where the rules shard a sequence, or shard q
    heads and kv heads differently, or a caller's ``shard_map`` already
    holds mesh axes."""
    b = rules.bindings
    if (b.get("attn_seq") is not None or b.get("kv_seq") is not None
            or b.get("heads") != b.get("kv_heads")
            or jax.sharding.get_abstract_mesh().manual_axes):
        return None
    return (rules.spec(("batch", None, "heads", None)),
            rules.spec(("batch", None, "kv_heads", None)))


def _mha_xla(q, k, v, *, causal, window, softcap, scale, q_offset,
             q_chunk, kv_chunk):
    """Online-softmax attention with a flash-style custom VJP.

    Forward saves only (q, k, v, out, lse); the backward recomputes p per
    (q-chunk, kv-chunk) tile — O(S) memory for training, the property that
    lets 32k-token prefills and 4k train steps fit HBM."""
    fn = _mha_xla_vjp(causal, window, softcap, scale, q_offset,
                      q_chunk, kv_chunk)
    return fn(q, k, v)


@functools.lru_cache(maxsize=None)
def _mha_xla_vjp(causal, window, softcap, scale, q_offset, q_chunk, kv_chunk):
    kw = dict(causal=causal, window=window, softcap=softcap, scale=scale,
              q_offset=q_offset, q_chunk=q_chunk, kv_chunk=kv_chunk)

    @jax.custom_vjp
    def f(q, k, v):
        out, _ = _mha_fwd_impl(q, k, v, **kw)
        return out

    def fwd(q, k, v):
        out, lse = _mha_fwd_impl(q, k, v, **kw)
        return out, (q, k, v, out, lse)

    def bwd(res, dout):
        # JAX traces a custom VJP's backward outside the transpose's name
        # stack; the scope tells its ops from the forward's in a profile
        with jax.named_scope("bwd"):
            return _mha_bwd_impl(*res, dout, **kw)

    f.defvjp(fwd, bwd)
    return f


def _mha_fwd_impl(q, k, v, *, causal, window, softcap, scale, q_offset,
                  q_chunk, kv_chunk):
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = scale if scale is not None else D ** -0.5
    cq = min(q_chunk, S)
    ckv = min(kv_chunk, T)
    nq = -(-S // cq)
    nkv = -(-T // ckv)
    Sp, Tp = nq * cq, nkv * ckv

    # streams stay in the input dtype (bf16 from the models); accumulation
    # and softmax statistics are fp32 (same contract as the Pallas kernel)
    qf = q
    if Sp != S:
        qf = jnp.pad(qf, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    kf, vf = k, v
    if Tp != T:
        kf = jnp.pad(kf, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))

    # [nq, B, cq, KV, G, D] / [nkv, B, ckv, KV, D]
    qs = qf.reshape(B, nq, cq, KV, G, D).transpose(1, 0, 2, 3, 4, 5)
    ks = kf.reshape(B, nkv, ckv, KV, D).transpose(1, 0, 2, 3, 4)
    vs = vf.reshape(B, nkv, ckv, KV, D).transpose(1, 0, 2, 3, 4)

    kv_pos = jnp.arange(Tp).reshape(nkv, ckv)

    def q_body(_, q_in):
        qi, qidx = q_in
        qpos = qidx * cq + jnp.arange(cq) + q_offset

        def kv_body(carry, kv_in):
            acc, m, l = carry
            ki, vi, kpos = kv_in
            s = jnp.einsum("bsngd,btnd->bsngt", qi, ki,
                           preferred_element_type=jnp.float32) * scale
            if softcap > 0.0:
                s = jnp.tanh(s / softcap) * softcap
            mask = kpos[None, :] < T
            if causal:
                mask = mask & (kpos[None, :] <= qpos[:, None])
            if window > 0:
                mask = mask & (kpos[None, :] > qpos[:, None] - window)
            s = jnp.where(mask[None, :, None, None, :], s, _NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            # mask p explicitly: a fully-masked block would otherwise give
            # exp(-inf - -inf) = 1 and corrupt l (sliding-window prefill)
            p = jnp.exp(s - m_new[..., None]) * mask[None, :, None, None, :]
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bsngt,btnd->bsngd", p.astype(vi.dtype), vi,
                preferred_element_type=jnp.float32)
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((B, cq, KV, G, D), jnp.float32)
        m0 = jnp.full((B, cq, KV, G), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, cq, KV, G), jnp.float32)
        # unroll: the fp32 (acc,m,l) carry round-trips HBM once per 4 kv
        # chunks instead of every chunk (VMEM-resident in the Pallas kernel)
        (acc, m, l), _ = jax.lax.scan(kv_body, (acc0, m0, l0), (ks, vs, kv_pos),
                                      unroll=min(4, nkv))
        out = acc / (l[..., None] + 1e-30)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        return None, (out, lse)

    _, (outs, lses) = jax.lax.scan(q_body, None, (qs, jnp.arange(nq)))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sp, H, D)
    lse = lses.transpose(1, 0, 2, 3, 4).reshape(B, Sp, KV, G)
    return out[:, :S].astype(q.dtype), lse[:, :S]


def _mha_bwd_impl(q, k, v, out, lse, dout, *, causal, window, softcap,
                  scale, q_offset, q_chunk, kv_chunk):
    """Flash-style backward: recompute p per tile from (q, k, lse)."""
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    sc = scale if scale is not None else D ** -0.5
    cq = min(q_chunk, S)
    ckv = min(kv_chunk, T)
    nq = -(-S // cq)
    nkv = -(-T // ckv)
    Sp, Tp = nq * cq, nkv * ckv

    def padq(t):
        return jnp.pad(t, ((0, 0), (0, Sp - S)) + ((0, 0),) * (t.ndim - 2)) \
            if Sp != S else t

    def padk(t):
        return jnp.pad(t, ((0, 0), (0, Tp - T)) + ((0, 0),) * (t.ndim - 2)) \
            if Tp != T else t

    qf = padq(q)
    kf = padk(k)
    vf = padk(v)
    dof = padq(dout)
    outf = padq(out)
    lsef = padq(lse)
    # Delta_i = rowsum(dout_i * out_i), fp32
    delta = (dof.astype(jnp.float32) * outf.astype(jnp.float32)
             ).reshape(B, Sp, KV, G, D).sum(-1)                  # [B,Sp,KV,G]

    qs = qf.reshape(B, nq, cq, KV, G, D).transpose(1, 0, 2, 3, 4, 5)
    dos = dof.reshape(B, nq, cq, KV, G, D).transpose(1, 0, 2, 3, 4, 5)
    lss = lsef.reshape(B, nq, cq, KV, G).transpose(1, 0, 2, 3, 4)
    dls = delta.reshape(B, nq, cq, KV, G).transpose(1, 0, 2, 3, 4)
    ks = kf.reshape(B, nkv, ckv, KV, D).transpose(1, 0, 2, 3, 4)
    vs = vf.reshape(B, nkv, ckv, KV, D).transpose(1, 0, 2, 3, 4)

    def tile(qi, qpos, lsei, di, doi, ki, vi, kpos):
        """Recompute (p, ds) for one (q-chunk, kv-chunk) tile."""
        s_raw = jnp.einsum("bsngd,btnd->bsngt", qi, ki,
                           preferred_element_type=jnp.float32) * sc
        if softcap > 0.0:
            tanh_t = jnp.tanh(s_raw / softcap)
            s = tanh_t * softcap
        else:
            s = s_raw
        mask = kpos[None, :] < T
        if causal:
            mask = mask & (kpos[None, :] <= qpos[:, None])
        if window > 0:
            mask = mask & (kpos[None, :] > qpos[:, None] - window)
        p = jnp.exp(s - lsei[..., None]) * mask[None, :, None, None, :]
        dp = jnp.einsum("bsngd,btnd->bsngt", doi, vi,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - di[..., None])
        if softcap > 0.0:
            ds = ds * (1.0 - tanh_t * tanh_t)
        return p, ds

    # Pass 1 (dq): outer q, inner kv; carry is one dq chunk (flash-bwd
    # structure — never carries the full dk/dv through both loops).
    def dq_body(_, q_in):
        qi, doi, lsei, di, qidx = q_in
        qpos = qidx * cq + jnp.arange(cq) + q_offset

        def kv_body(dq_i, kv_in):
            ki, vi, kidx = kv_in
            kpos = kidx * ckv + jnp.arange(ckv)
            p, ds = tile(qi, qpos, lsei, di, doi, ki, vi, kpos)
            dq_i = dq_i + jnp.einsum(
                "bsngt,btnd->bsngd", ds.astype(ki.dtype), ki,
                preferred_element_type=jnp.float32) * sc
            return dq_i, None

        kv_body = jax.checkpoint(kv_body)
        dq0 = jnp.zeros((B, cq, KV, G, D), jnp.float32)
        dq_i, _ = jax.lax.scan(kv_body, dq0, (ks, vs, jnp.arange(nkv)),
                               unroll=min(4, nkv))
        return None, dq_i

    _, dqs = jax.lax.scan(dq_body, None, (qs, dos, lss, dls, jnp.arange(nq)))

    # Pass 2 (dk, dv): outer kv, inner q; carry is one (dk, dv) chunk.
    def dkv_body(_, kv_in):
        ki, vi, kidx = kv_in
        kpos = kidx * ckv + jnp.arange(ckv)

        def q_inner(carry, q_in):
            dk_j, dv_j = carry
            qi, doi, lsei, di, qidx = q_in
            qpos = qidx * cq + jnp.arange(cq) + q_offset
            p, ds = tile(qi, qpos, lsei, di, doi, ki, vi, kpos)
            dk_j = dk_j + jnp.einsum(
                "bsngt,bsngd->btnd", ds.astype(qi.dtype), qi,
                preferred_element_type=jnp.float32) * sc
            dv_j = dv_j + jnp.einsum(
                "bsngt,bsngd->btnd", p.astype(doi.dtype), doi,
                preferred_element_type=jnp.float32)
            return (dk_j, dv_j), None

        q_inner = jax.checkpoint(q_inner)
        dk0 = jnp.zeros((B, ckv, KV, D), jnp.float32)
        dv0 = jnp.zeros((B, ckv, KV, D), jnp.float32)
        (dk_j, dv_j), _ = jax.lax.scan(
            q_inner, (dk0, dv0), (qs, dos, lss, dls, jnp.arange(nq)),
            unroll=min(4, nq))
        return None, (dk_j, dv_j)

    _, (dks, dvs) = jax.lax.scan(dkv_body, None, (ks, vs, jnp.arange(nkv)))

    dq = dqs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sp, H, D)[:, :S]
    dk = dks.transpose(1, 0, 2, 3, 4).reshape(B, Tp, KV, D)[:, :T]
    dv = dvs.transpose(1, 0, 2, 3, 4).reshape(B, Tp, KV, D)[:, :T]
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


def decode_mha(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
               lengths: jnp.ndarray, *, window: int = 0, softcap: float = 0.0,
               scale: Optional[float] = None, kv_chunk: int = 2048,
               impl: Optional[str] = None) -> jnp.ndarray:
    """Single-token decode attention over a KV cache.

    q: [B, 1, H, D]; caches: [B, L, KV, D]; lengths: [B] (#valid entries,
    i.e. the new token's position + 1). Returns [B, 1, H, D].

    When sharding rules bind "cache_seq" to a mesh axis, the cache is
    sequence-sharded and the attention runs as a flash-decode: each shard
    computes partial (acc, m, l) over its cache slice; partials combine with
    a max-rescaled psum over the axis. Works for any head count (the
    universal decode TP strategy — see sharding/planner.py).
    """
    impl = impl or _auto_impl()
    from ...sharding.api import active_rules
    rules = active_rules()
    seq_axis = rules.bindings.get("cache_seq") if rules is not None else None
    if isinstance(seq_axis, str):
        return _decode_mha_seq_sharded(
            q, k_cache, v_cache, lengths, rules=rules, seq_axis=seq_axis,
            window=window, softcap=softcap, scale=scale, kv_chunk=kv_chunk,
            impl=impl)
    if impl in ("pallas", "interpret"):
        from ..flash_decode.ops import flash_decode
        return flash_decode(q, k_cache, v_cache, lengths, window=window,
                            softcap=softcap, scale=scale, impl=impl)
    B, _, H, D = q.shape
    acc, m, l = _decode_partials(q, k_cache, v_cache, lengths,
                                 pos_offset=None, window=window,
                                 softcap=softcap, scale=scale,
                                 kv_chunk=kv_chunk)
    out = acc / (l[..., None] + 1e-30)
    return out.reshape(B, 1, H, D).astype(q.dtype)


def _decode_partials(q, k_cache, v_cache, lengths, *, pos_offset,
                     window, softcap, scale, kv_chunk):
    """Online-softmax partials over (a slice of) the cache.

    pos_offset: global position of k_cache[:, 0] (None -> 0).
    Returns (acc [B,KV,G,D], m [B,KV,G], l [B,KV,G]) — unnormalized.
    """
    B, _, H, D = q.shape
    L, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    scale = scale if scale is not None else D ** -0.5
    ckv = min(kv_chunk, L)
    nkv = -(-L // ckv)
    Lp = nkv * ckv
    qf = q.reshape(B, KV, G, D)
    kf = k_cache
    vf = v_cache
    if Lp != L:
        kf = jnp.pad(kf, ((0, 0), (0, Lp - L), (0, 0), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, Lp - L), (0, 0), (0, 0)))
    ks = kf.reshape(B, nkv, ckv, KV, D).transpose(1, 0, 2, 3, 4)
    vs = vf.reshape(B, nkv, ckv, KV, D).transpose(1, 0, 2, 3, 4)
    off = 0 if pos_offset is None else pos_offset
    kv_pos = jnp.arange(Lp).reshape(nkv, ckv) + off

    def body(carry, kv_in):
        acc, m, l = carry
        ki, vi, kpos = kv_in
        s = jnp.einsum("bngd,btnd->bngt", qf, ki,
                       preferred_element_type=jnp.float32) * scale
        if softcap > 0.0:
            s = jnp.tanh(s / softcap) * softcap
        mask = kpos[None, :] < lengths[:, None]            # [B, ckv]
        if window > 0:
            mask = mask & (kpos[None, :] > lengths[:, None] - 1 - window)
        s = jnp.where(mask[:, None, None, :], s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None]) * mask[:, None, None, :]
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bngt,btnd->bngd", p.astype(vi.dtype), vi,
            preferred_element_type=jnp.float32)
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((B, KV, G, D), jnp.float32)
    m0 = jnp.full((B, KV, G), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), (ks, vs, kv_pos))
    return acc, m, l


def _decode_mha_seq_sharded(q, k_cache, v_cache, lengths, *, rules, seq_axis,
                            window, softcap, scale, kv_chunk, impl):
    """Flash-decode: cache sequence-sharded over ``seq_axis``; partial
    softmax per shard; max-rescaled psum combine."""
    from jax.sharding import PartitionSpec as P
    mesh = rules.mesh
    B, _, H, D = q.shape
    bspec = rules.spec(("batch",))
    batch_part = bspec[0] if len(bspec) else None

    def body(qi, kc, vc, lens):
        idx = jax.lax.axis_index(seq_axis)
        L_loc = kc.shape[1]
        acc, m, l = _decode_partials(
            qi, kc, vc, lens, pos_offset=idx * L_loc, window=window,
            softcap=softcap, scale=scale, kv_chunk=kv_chunk)
        m_g = jax.lax.pmax(m, seq_axis)
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, seq_axis)
        acc_g = jax.lax.psum(acc * corr[..., None], seq_axis)
        out = acc_g / (l_g[..., None] + 1e-30)
        return out.reshape(qi.shape[0], 1, H, D).astype(qi.dtype)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(batch_part), P(batch_part, seq_axis),
                  P(batch_part, seq_axis), P(batch_part)),
        out_specs=P(batch_part),
        check_vma=False)
    return fn(q, k_cache, v_cache, lengths)


def decode_mha_ref(q, k_cache, v_cache, lengths, *, window: int = 0,
                   softcap: float = 0.0, scale: Optional[float] = None):
    """Oracle for decode attention via the naive path."""
    B, _, H, D = q.shape
    outs = []
    for b in range(B):
        t = int(lengths[b])
        o = mha_ref(q[b:b + 1], k_cache[b:b + 1, :t], v_cache[b:b + 1, :t],
                    causal=True, window=window, softcap=softcap, scale=scale,
                    q_offset=t - 1)
        outs.append(o)
    return jnp.concatenate(outs, axis=0)
