"""Shared model primitives: norms, rotary embeddings, dense/GLU blocks,
embedding, and the memory-safe chunked cross-entropy loss.

All functions are pure; parameters are plain pytrees created by the ``init_*``
helpers (each has a ``*_axes`` twin returning the logical sharding axes with
the same tree structure — see repro.sharding.api).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..sharding.api import shard


def truncated_normal(key, shape, dtype=jnp.float32, stddev=0.02):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * stddev).astype(dtype)


# ----------------------------------------------------------------- norms

def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6,
             zero_centered: bool = False) -> jnp.ndarray:
    """RMSNorm in fp32, cast back to x.dtype. gemma2 uses (1 + scale)."""
    with jax.named_scope("norm"):
        xf = x.astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        xn = xf * jax.lax.rsqrt(var + eps)
        s = scale.astype(jnp.float32)
        if zero_centered:
            s = 1.0 + s
        return (xn * s).astype(x.dtype)


def group_norm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray,
               num_groups: int, eps: float = 64e-5) -> jnp.ndarray:
    """GroupNorm over the last dim (RWKV wkv output norm)."""
    *lead, d = x.shape
    xf = x.astype(jnp.float32).reshape(*lead, num_groups, d // num_groups)
    mu = xf.mean(axis=-1, keepdims=True)
    var = xf.var(axis=-1, keepdims=True)
    xn = ((xf - mu) * jax.lax.rsqrt(var + eps)).reshape(*lead, d)
    return (xn * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


# ----------------------------------------------------------------- rotary

def rope(x: jnp.ndarray, positions: jnp.ndarray,
         theta: float = 1e4) -> jnp.ndarray:
    """Rotary position embedding. x [..., S, H, D], positions [S] or [B,S]."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]  # [S,half]
        ang = ang[None, :, None, :]
    else:
        ang = positions.astype(jnp.float32)[..., None] * freqs  # [B,S,half]
        ang = ang[:, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


# ----------------------------------------------------------------- dense / GLU

def init_dense(key, d_in: int, d_out: int, *, bias: bool = False,
               stddev: Optional[float] = None) -> Dict[str, Any]:
    stddev = stddev if stddev is not None else d_in ** -0.5
    p = {"w": truncated_normal(key, (d_in, d_out), stddev=stddev)}
    if bias:
        p["b"] = jnp.zeros((d_out,), jnp.float32)
    return p


def dense_axes(ax_in: Optional[str], ax_out: Optional[str],
               bias: bool = False) -> Dict[str, Any]:
    p = {"w": (ax_in, ax_out)}
    if bias:
        p["b"] = (ax_out,)
    return p


def dense(x: jnp.ndarray, p: Dict[str, Any],
          compute_dtype=jnp.bfloat16) -> jnp.ndarray:
    out = x.astype(compute_dtype) @ p["w"].astype(compute_dtype)
    if "b" in p:
        out = out + p["b"].astype(compute_dtype)
    return out


def init_glu(key, d_model: int, d_ff: int) -> Dict[str, Any]:
    k1, k2, k3 = jax.random.split(key, 3)
    return {"wi": init_dense(k1, d_model, d_ff),
            "wg": init_dense(k2, d_model, d_ff),
            "wo": init_dense(k3, d_ff, d_model, stddev=d_ff ** -0.5)}


def glu_axes() -> Dict[str, Any]:
    return {"wi": dense_axes("embed", "mlp"),
            "wg": dense_axes("embed", "mlp"),
            "wo": dense_axes("mlp", "embed")}


def glu(x: jnp.ndarray, p: Dict[str, Any], act: str = "silu",
        compute_dtype=jnp.bfloat16) -> jnp.ndarray:
    """SwiGLU / GeGLU feed-forward.

    With active sharding rules binding seq and mlp to the same mesh axis,
    runs as EXPLICIT Megatron sequence parallelism (shard_map): all-gather
    the seq-sharded residual on entry, psum_scatter the output back — the
    scatter moves 1/axis of the bytes an all-reduce would (the automatic
    partitioner on some backends never forms reduce-scatter from psum+slice,
    so we write the collective we mean).
    """
    from ..sharding.api import active_rules
    rules = active_rules()
    seq_ax = rules.bindings.get("seq") if rules is not None else None
    mlp_ax = rules.bindings.get("mlp") if rules is not None else None
    with jax.named_scope("mlp"):
        if (rules is not None and isinstance(seq_ax, str) and seq_ax == mlp_ax
                and "b" not in p["wi"] and x.shape[1] > 1):
            return _glu_seqpar(x, p, act, compute_dtype, rules, seq_ax)
        return _glu_plain(x, p, act, compute_dtype)


def _glu_plain(x, p, act, compute_dtype):
    h = dense(x, p["wi"], compute_dtype)
    g = dense(x, p["wg"], compute_dtype)
    actfn = {"silu": jax.nn.silu,
             "gelu": lambda t: jax.nn.gelu(t, approximate=True),
             "relu": jax.nn.relu}[act]
    h = actfn(g.astype(jnp.float32)).astype(compute_dtype) * h
    h = shard(h, "batch", "act_seq", "mlp")
    out = dense(h, p["wo"], compute_dtype)
    return shard(out, "batch", "seq", "embed")


def _glu_seqpar(x, p, act, compute_dtype, rules, axis):
    from jax.sharding import PartitionSpec as P
    mesh = rules.mesh
    bspec = rules.spec(("batch",))
    bd = bspec[0] if len(bspec) else None             # batch mesh axes
    fa = rules.bindings.get("embed")                  # FSDP axis (or None)
    fa = fa if isinstance(fa, str) else None

    def body(x_loc, wi, wg, wo):
        # explicit SP + FSDP: gather seq on entry, gather params over the
        # fsdp axis, scatter-reduce the output back to seq shards
        xf = jax.lax.all_gather(x_loc, axis, axis=1, tiled=True)
        if fa is not None:
            wi = jax.lax.all_gather(wi, fa, axis=0, tiled=True)
            wg = jax.lax.all_gather(wg, fa, axis=0, tiled=True)
            wo = jax.lax.all_gather(wo, fa, axis=1, tiled=True)
        xf = xf.astype(compute_dtype)
        h = xf @ wi.astype(compute_dtype)
        g = xf @ wg.astype(compute_dtype)
        actfn = {"silu": jax.nn.silu,
                 "gelu": lambda t: jax.nn.gelu(t, approximate=True),
                 "relu": jax.nn.relu}[act]
        h = actfn(g.astype(jnp.float32)).astype(compute_dtype) * h
        partial = h @ wo.astype(compute_dtype)
        return jax.lax.psum_scatter(partial, axis, scatter_dimension=1,
                                    tiled=True)

    manual = {axis}
    if fa:
        manual.add(fa)
    if bd:
        manual.update((bd,) if isinstance(bd, str) else bd)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bd, axis, None), P(fa, axis), P(fa, axis), P(axis, fa)),
        out_specs=P(bd, axis, None),
        axis_names=manual, check_vma=False,
    )(x, p["wi"]["w"], p["wg"]["w"], p["wo"]["w"])


# ----------------------------------------------------------------- embedding

def init_embed(key, vocab: int, d_model: int) -> Dict[str, Any]:
    return {"table": truncated_normal(key, (vocab, d_model), stddev=1.0)}


def embed_axes() -> Dict[str, Any]:
    return {"table": ("vocab", "embed")}


def embed(tokens: jnp.ndarray, p: Dict[str, Any], *,
          scale_by_dim: bool = False, compute_dtype=jnp.bfloat16) -> jnp.ndarray:
    with jax.named_scope("embed"):
        tbl = p["table"].astype(compute_dtype)
        x = jnp.take(tbl, tokens, axis=0)
        if scale_by_dim:  # gemma embedding scaling
            x = x * jnp.asarray(tbl.shape[-1] ** 0.5, compute_dtype)
        return shard(x, "batch", "seq", "embed")


# ----------------------------------------------------------------- chunked loss

def chunked_softmax_xent(h: jnp.ndarray, vocab_w: jnp.ndarray,
                         labels: jnp.ndarray, *, mask: Optional[jnp.ndarray],
                         chunk: int = 512, final_softcap: float = 0.0,
                         valid_vocab: int = 0,
                         compute_dtype=jnp.bfloat16
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Cross-entropy without materializing [B, S, V] logits.

    Scans over sequence chunks; per chunk computes logits [B, C, V], the
    log-sum-exp and the label logit, discarding logits immediately (the
    backward pass recomputes them — the standard memory/compute trade).
    h: [B, S, D]; vocab_w: [D, V]; labels: [B, S].
    Returns (total_loss_sum, total_weight).
    """
    B, S, D = h.shape
    V = vocab_w.shape[-1]
    c = min(chunk, S)
    n = -(-S // c)
    Sp = n * c
    if mask is None:
        mask = jnp.ones((B, S), jnp.float32)
    if Sp != S:
        h = jnp.pad(h, ((0, 0), (0, Sp - S), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, Sp - S)))
        mask = jnp.pad(mask, ((0, 0), (0, Sp - S)))
    hs = h.reshape(B, n, c, D).transpose(1, 0, 2, 3)
    ls = labels.reshape(B, n, c).transpose(1, 0, 2)
    ms = mask.reshape(B, n, c).transpose(1, 0, 2)
    wv = vocab_w.astype(compute_dtype)

    def body(carry, inp):
        # named inside the body too: JAX hoists the vocab mask, which reads
        # no input, out of the scan and away from the caller's scope
        with jax.named_scope("loss"):
            loss_sum, w_sum = carry
            hc, lc, mc = inp
            logits = (hc.astype(compute_dtype) @ wv).astype(jnp.float32)
            if final_softcap > 0.0:
                logits = jnp.tanh(logits / final_softcap) * final_softcap
            if 0 < valid_vocab < V:     # padded rows stay out of the lse
                logits = jnp.where(jnp.arange(V) < valid_vocab, logits,
                                   -1e30)
            logits = shard(logits, "batch", "act_seq", "vocab")
            lse = jax.nn.logsumexp(logits, axis=-1)
            lab = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
            loss = (lse - lab) * mc
            return (loss_sum + loss.sum(), w_sum + mc.sum()), None

    # remat: the [B, c, V] logits are recomputed in the backward pass —
    # the whole point of chunking is never holding more than one chunk.
    body = jax.checkpoint(body)
    (loss_sum, w_sum), _ = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.float32(0.0)), (hs, ls, ms))
    return loss_sum, w_sum
