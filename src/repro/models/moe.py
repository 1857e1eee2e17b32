"""Mixture-of-Experts FFN with expert parallelism.

TPU adaptation of capacity-based MoE (GShard lineage, megablocks-informed):
tokens are routed with top-k, sort-dispatched into a static [E, C, D] buffer
(sort + rank-in-expert, NOT the O(S*E*C) one-hot einsum), all-to-all'd to
expert shards along the EP mesh axis, processed as one batched GLU matmul per
shard (MXU-friendly [E_loc, P*C, D] x [E_loc, D, F]), and all-to-all'd back.

Without active sharding rules the same code runs single-shard (CPU smoke
tests). The Pallas grouped-GEMM kernel (kernels/grouped_gemm) is a drop-in
for the batched expert matmul on the dropless path.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..sharding.api import active_rules, shard
from .config import ModelConfig
from .layers import truncated_normal


def init_moe(key, cfg: ModelConfig) -> Dict[str, Any]:
    kr, k1, kg, k2 = jax.random.split(key, 4)
    d, f, e = cfg.d_model, cfg.d_ff_expert, cfg.n_experts
    return {
        "router": truncated_normal(kr, (d, e), stddev=d ** -0.5),
        "w1": truncated_normal(k1, (e, d, f), stddev=d ** -0.5),
        "wg": truncated_normal(kg, (e, d, f), stddev=d ** -0.5),
        "w2": truncated_normal(k2, (e, f, d), stddev=f ** -0.5),
    }


def moe_axes() -> Dict[str, Any]:
    return {"router": ("embed", None),
            "w1": ("expert", "embed", "mlp"),
            "wg": ("expert", "embed", "mlp"),
            "w2": ("expert", "mlp", "embed")}


def _capacity(tokens: int, cfg: ModelConfig) -> int:
    c = int(tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor)
    return max(8, -(-c // 8) * 8)


def _moe_local(x: jnp.ndarray, router: jnp.ndarray, w1, wg, w2,
               cfg: ModelConfig, ep_axis: Optional[str],
               compute_dtype=jnp.bfloat16) -> jnp.ndarray:
    """Per-shard MoE body. x: [B_loc, S_loc, D] local tokens (flattened
    HERE, per shard — flattening (batch, seq) globally would mix two mesh
    axes in one dim, which SPMD cannot shard without a full gather).
    Runs inside shard_map when ep_axis is set (w1/wg/w2 then hold
    E_loc = E/ep experts)."""
    Bl, Sl, D = x.shape
    x = x.reshape(Bl * Sl, D)
    T = Bl * Sl
    E, K = cfg.n_experts, cfg.top_k
    C = _capacity(T, cfg)

    # --- route (fp32) ---
    logits = x.astype(jnp.float32) @ router.astype(jnp.float32)     # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, K)                            # [T, K]
    if cfg.router_renorm:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-9)

    # --- sort-based dispatch into [E, C, D] ---
    e_flat = eidx.reshape(-1)                                        # [T*K]
    t_flat = jnp.repeat(jnp.arange(T), K)
    g_flat = gates.reshape(-1)
    order = jnp.argsort(e_flat)                                      # stable
    e_s, t_s, g_s = e_flat[order], t_flat[order], g_flat[order]
    counts = jnp.bincount(e_flat, length=E)
    offsets = jnp.cumsum(counts) - counts
    rank = jnp.arange(T * K) - offsets[e_s]                          # pos in expert
    keep = rank < C
    rank_c = jnp.where(keep, rank, 0)
    e_c = jnp.where(keep, e_s, 0)

    xt = x.astype(compute_dtype)
    dispatch = jnp.zeros((E, C, D), compute_dtype)
    dispatch = dispatch.at[e_c, rank_c].add(
        xt[t_s] * keep[:, None].astype(compute_dtype))

    # --- to expert shards ---
    if ep_axis is not None:
        recv = jax.lax.all_to_all(dispatch, ep_axis, split_axis=0,
                                  concat_axis=1, tiled=True)         # [E_loc, P*C, D]
    else:
        recv = dispatch

    # --- batched expert GLU (one MXU-shaped matmul per projection) ---
    h = jnp.einsum("ecd,edf->ecf", recv, w1.astype(compute_dtype))
    g = jnp.einsum("ecd,edf->ecf", recv, wg.astype(compute_dtype))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(compute_dtype) * h
    y = jnp.einsum("ecf,efd->ecd", h, w2.astype(compute_dtype))

    # --- back to token shards & combine ---
    if ep_axis is not None:
        y = jax.lax.all_to_all(y, ep_axis, split_axis=1,
                               concat_axis=0, tiled=True)            # [E, C, D]
    vals = y[e_c, rank_c] * (g_s * keep)[:, None].astype(compute_dtype)
    out = jnp.zeros((T, D), compute_dtype).at[t_s].add(vals)
    return out.reshape(Bl, Sl, D)


def moe_apply(p: Dict[str, Any], x: jnp.ndarray, cfg: ModelConfig,
              compute_dtype=jnp.bfloat16) -> jnp.ndarray:
    """x: [B, S, D] -> [B, S, D]."""
    B, S, D = x.shape
    rules = active_rules()
    if rules is None:
        local = jax.checkpoint(functools.partial(
            _moe_local, cfg=cfg, ep_axis=None, compute_dtype=compute_dtype))
        out = local(x, p["router"], p["w1"], p["wg"], p["w2"])
        return out.astype(x.dtype)

    mesh = rules.mesh
    ep_axis = rules.bindings.get("expert")
    assert isinstance(ep_axis, str) or ep_axis is None
    # x stays 3D at the shard_map boundary: (batch, seq) are sharded on
    # DIFFERENT mesh axes, so they must not be flattened into one dim here.
    bspec = rules.spec(("batch",))
    sspec = rules.spec(("seq",))
    b_part = bspec[0] if len(bspec) else None
    s_part = sspec[0] if len(sspec) else None
    ep_part = ep_axis if ep_axis else None
    body = functools.partial(_moe_local, cfg=cfg, ep_axis=ep_axis,
                             compute_dtype=compute_dtype)
    # remat: dispatch/expert intermediates ([E,C,D] buffers, [E,PC,F]
    # activations) are recomputed in the backward pass instead of saved.
    out = jax.checkpoint(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(b_part, s_part, None),
                  P(None, None),
                  P(ep_part, None, None),
                  P(ep_part, None, None),
                  P(ep_part, None, None)),
        out_specs=P(b_part, s_part, None),
        check_vma=False,
    ))(x, p["router"], p["w1"], p["wg"], p["w2"])
    return out.astype(x.dtype)


def moe_ref(p: Dict[str, Any], x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Dense oracle: every expert computed for every token, masked combine.
    O(T*E*F) — tiny shapes only (property tests vs moe_apply)."""
    B, S, D = x.shape
    xt = x.reshape(B * S, D).astype(jnp.float32)
    logits = xt @ p["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, cfg.top_k)
    if cfg.router_renorm:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-9)
    h = jnp.einsum("td,edf->tef", xt, p["w1"].astype(jnp.float32))
    g = jnp.einsum("td,edf->tef", xt, p["wg"].astype(jnp.float32))
    y = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * h,
                   p["w2"].astype(jnp.float32))
    mask = jnp.zeros((xt.shape[0], cfg.n_experts))
    t = jnp.arange(xt.shape[0])[:, None]
    mask = mask.at[t, eidx].add(gates)
    out = jnp.einsum("ted,te->td", y, mask)
    return out.reshape(B, S, D).astype(x.dtype)
