"""GQA attention block (projection params + cache handling).

Covers: GQA with kv replication, QKV bias (qwen2), RoPE, sliding-window local
layers + logit softcap (gemma2), cross-attention (seamless decoder), and
single-token decode against a KV cache (vmapped per-sequence scatter for
continuous batching).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels.flash_attention import ops as attn_ops
from ..sharding.api import shard
from .config import ModelConfig
from .layers import dense, dense_axes, init_dense, rope


def init_attn(key, cfg: ModelConfig, cross: bool = False) -> Dict[str, Any]:
    kq, kk, kv, ko = jax.random.split(key, 4)
    d, hd = cfg.d_model, cfg.head_dim
    return {
        "wq": init_dense(kq, d, cfg.n_heads * hd, bias=cfg.qkv_bias),
        "wk": init_dense(kk, d, cfg.n_kv_heads * hd, bias=cfg.qkv_bias),
        "wv": init_dense(kv, d, cfg.n_kv_heads * hd, bias=cfg.qkv_bias),
        "wo": init_dense(ko, cfg.n_heads * hd, d,
                         stddev=(cfg.n_heads * hd) ** -0.5),
    }


def attn_axes(cfg: ModelConfig) -> Dict[str, Any]:
    return {
        "wq": dense_axes("embed", "heads_flat", cfg.qkv_bias),
        "wk": dense_axes("embed", "kv_flat", cfg.qkv_bias),
        "wv": dense_axes("embed", "kv_flat", cfg.qkv_bias),
        "wo": dense_axes("heads_flat", "embed"),
    }


def attn_apply(p: Dict[str, Any], x: jnp.ndarray, *, cfg: ModelConfig,
               kind: str = "g", positions: Optional[jnp.ndarray] = None,
               causal: bool = True,
               kv_x: Optional[jnp.ndarray] = None,
               cache: Optional[Dict[str, jnp.ndarray]] = None,
               lengths: Optional[jnp.ndarray] = None,
               impl: Optional[str] = None,
               compute_dtype=jnp.bfloat16
               ) -> Tuple[jnp.ndarray, Optional[Dict[str, jnp.ndarray]]]:
    """Self/cross attention.

    x: [B, S, D]. kv_x: encoder output for cross-attention (no RoPE, no cache
    update — cache holds precomputed enc K/V). cache: {"k","v"} [B, L, KV, hd]
    with ``lengths`` [B] = #valid tokens incl. the current one (decode).
    Returns (out [B, S, D], updated cache or None).
    """
    B, S, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    window = cfg.sliding_window if kind == "l" else 0
    is_cross = kv_x is not None
    with jax.named_scope("attn"):
        with jax.named_scope("proj"):
            q = dense(x, p["wq"], compute_dtype).reshape(B, S, H, hd)
        if is_cross and cache is not None:
            # decode-time cross attention: K/V precomputed at prefill
            k, v = cache["k"], cache["v"]
            new_cache = cache
            with jax.named_scope("proj"):
                q = shard(q, "batch", "attn_seq", "heads", None)
            with jax.named_scope("core"):
                out = attn_ops.mha(q, k, v, causal=False,
                                   softcap=cfg.attn_softcap, impl=impl)
        else:
            src = kv_x if is_cross else x
            Skv = src.shape[1]
            with jax.named_scope("proj"):
                k = dense(src, p["wk"], compute_dtype).reshape(B, Skv, KV, hd)
                v = dense(src, p["wv"], compute_dtype).reshape(B, Skv, KV, hd)
                if not is_cross and cfg.use_rope:
                    if positions is None:
                        positions = jnp.arange(S)
                    q = rope(q, positions, cfg.rope_theta)
                    k = rope(k, positions, cfg.rope_theta)
                q = shard(q, "batch", "attn_seq", "heads", None)
                k = shard(k, "batch", "kv_seq", "kv_heads", None)
                v = shard(v, "batch", "kv_seq", "kv_heads", None)
            if cache is None:
                with jax.named_scope("core"):
                    out = attn_ops.mha(q, k, v, causal=causal and not is_cross,
                                       window=window, softcap=cfg.attn_softcap,
                                       impl=impl)
                new_cache = None
            elif S == 1 and not is_cross:
                # single-token decode: scatter new K/V at lengths-1, attend
                # to cache
                assert lengths is not None
                idx = lengths - 1
                upd = jax.vmap(
                    lambda c, kv1, i: jax.lax.dynamic_update_slice_in_dim(
                        c, kv1, i, axis=0))
                k_cache = upd(cache["k"], k[:, 0:1].astype(cache["k"].dtype)
                              .reshape(B, 1, KV, hd), idx)
                v_cache = upd(cache["v"], v[:, 0:1].astype(cache["v"].dtype)
                              .reshape(B, 1, KV, hd), idx)
                k_cache = shard(k_cache, "batch", "cache_seq", "kv_heads", None)
                v_cache = shard(v_cache, "batch", "cache_seq", "kv_heads", None)
                new_cache = {"k": k_cache, "v": v_cache}
                with jax.named_scope("core"):
                    out = attn_ops.decode_mha(q, k_cache, v_cache, lengths,
                                              window=window,
                                              softcap=cfg.attn_softcap,
                                              impl=impl)
            else:
                # prefill into an empty cache (S tokens at positions [0, S))
                k_cache = jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], k.astype(cache["k"].dtype), 0, axis=1)
                v_cache = jax.lax.dynamic_update_slice_in_dim(
                    cache["v"], v.astype(cache["v"].dtype), 0, axis=1)
                new_cache = {"k": k_cache, "v": v_cache}
                with jax.named_scope("core"):
                    out = attn_ops.mha(q, k, v, causal=True, window=window,
                                       softcap=cfg.attn_softcap, impl=impl)

        with jax.named_scope("proj"):
            out = shard(out, "batch", "attn_seq", "heads", None)
            out = out.reshape(B, S, H * hd)
            proj = _out_proj(out, p["wo"], cfg, compute_dtype)
    return proj, new_cache


def _out_proj(out, wo, cfg, compute_dtype):
    """Attention output projection.

    tp_heads layout: ``out`` is head-sharded on the model axis and the wo
    contraction is partial across it — emit an explicit psum_scatter to the
    seq-sharded residual layout (reduce-scatter: 1/axis the bytes of the
    all-reduce the automatic partitioner would otherwise produce)."""
    from ..sharding.api import active_rules
    rules = active_rules()
    axis = rules.bindings.get("heads") if rules is not None else None
    seq_ax = rules.bindings.get("seq") if rules is not None else None
    B, S, _ = out.shape
    if (rules is None or not isinstance(axis, str) or axis != seq_ax
            or S == 1 or "b" in wo):
        proj = dense(out, wo, compute_dtype)
        return shard(proj, "batch", "seq", "embed")

    from jax.sharding import PartitionSpec as P
    mesh = rules.mesh
    bspec = rules.spec(("batch",))
    bd = bspec[0] if len(bspec) else None
    fa = rules.bindings.get("embed")
    fa = fa if isinstance(fa, str) else None

    def body(o_loc, w_loc):
        if fa is not None:
            w_loc = jax.lax.all_gather(w_loc, fa, axis=1, tiled=True)
        partial = o_loc.astype(compute_dtype) @ w_loc.astype(compute_dtype)
        return jax.lax.psum_scatter(partial, axis, scatter_dimension=1,
                                    tiled=True)

    manual = {axis}
    if fa:
        manual.add(fa)
    if bd:
        manual.update((bd,) if isinstance(bd, str) else bd)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bd, None, axis), P(axis, fa)),
        out_specs=P(bd, axis, None),
        axis_names=manual, check_vma=False,
    )(out, wo["w"])


def init_cross_kv_cache(p: Dict[str, Any], enc_out: jnp.ndarray,
                        cfg: ModelConfig,
                        compute_dtype=jnp.bfloat16) -> Dict[str, jnp.ndarray]:
    """Precompute cross-attention K/V from encoder output (decode cache)."""
    B, Senc, _ = enc_out.shape
    KV, hd = cfg.n_kv_heads, cfg.head_dim
    k = dense(enc_out, p["wk"], compute_dtype).reshape(B, Senc, KV, hd)
    v = dense(enc_out, p["wv"], compute_dtype).reshape(B, Senc, KV, hd)
    return {"k": k, "v": v}
