"""Unified decoder-LM covering all ten assigned architectures.

A model is a tiled stack of "blocks": each block instantiates
``cfg.layer_pattern`` (e.g. "g" dense global attention, "lg" gemma2
local/global alternation, "mmmmammm" jamba mamba/attention interleave,
"r" rwkv6). Blocks are scanned with ``jax.lax.scan`` over stacked params
(MaxText-style) for O(1) compile time and clean remat boundaries; caches
ride the scan as xs/ys.

Encoder-decoder (seamless) adds an encoder stack + cross attention; VLM and
audio frontends are stubs per the assignment (precomputed patch/frame
embeddings enter through ``frontend_proj``).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..sharding.api import shard
from .attention import attn_apply, attn_axes, init_attn, init_cross_kv_cache
from .config import ModelConfig
from .layers import (chunked_softmax_xent, embed, embed_axes, glu, glu_axes,
                     init_dense, dense_axes, init_embed, init_glu, rms_norm,
                     truncated_normal)
from .mamba import init_mamba_block, mamba_apply, mamba_block_axes
from .moe import init_moe, moe_apply, moe_axes
from .rwkv6 import (channel_mix, init_rwkv_block, rwkv_block_axes, time_mix)


# ---------------------------------------------------------------- block init

def _moe_static(cfg: ModelConfig, i: int) -> bool:
    """MoE-ness of sub-layer i must not depend on the block index."""
    if not cfg.is_moe:
        return False
    assert cfg.block_period % cfg.moe_every == 0 or cfg.moe_every == 1, \
        f"{cfg.name}: moe_every must divide the block period"
    return i % cfg.moe_every == cfg.moe_offset


def init_block(key, cfg: ModelConfig, decoder: bool = True) -> Dict[str, Any]:
    sub_params: Dict[str, Any] = {}
    keys = jax.random.split(key, cfg.block_period)
    d = cfg.d_model
    for i, kind in enumerate(cfg.layer_pattern):
        k1, k2, k3, k4 = jax.random.split(keys[i], 4)
        sub: Dict[str, Any] = {"ln1": jnp.zeros((d,), jnp.float32)
                               if cfg.zero_centered_norm
                               else jnp.ones((d,), jnp.float32)}
        ln = (lambda: jnp.zeros((d,), jnp.float32)) if cfg.zero_centered_norm \
            else (lambda: jnp.ones((d,), jnp.float32))
        if kind in ("g", "l"):
            sub["attn"] = init_attn(k1, cfg)
        elif kind == "m":
            sub["mamba"] = init_mamba_block(k1, cfg)
        elif kind == "r":
            sub["rwkv"] = init_rwkv_block(k1, cfg)
        else:
            raise ValueError(f"unknown layer kind {kind}")
        if cfg.is_encdec and decoder and kind in ("g", "l"):
            sub["ln_cross"] = ln()
            sub["cross"] = init_attn(k3, cfg, cross=True)
        if kind != "r":
            sub["ln2"] = ln()
            if _moe_static(cfg, i):
                sub["ffn"] = init_moe(k2, cfg)
            else:
                sub["ffn"] = init_glu(k2, cfg.d_model, cfg.d_ff)
        else:
            sub["ln2"] = ln()
        if cfg.post_norms:
            sub["post_ln1"] = ln()
            sub["post_ln2"] = ln()
        sub_params[f"sub{i}"] = sub
    return sub_params


def block_axes(cfg: ModelConfig, decoder: bool = True) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for i, kind in enumerate(cfg.layer_pattern):
        sub: Dict[str, Any] = {"ln1": (None,)}
        if kind in ("g", "l"):
            sub["attn"] = attn_axes(cfg)
        elif kind == "m":
            sub["mamba"] = mamba_block_axes(cfg)
        elif kind == "r":
            sub["rwkv"] = rwkv_block_axes(cfg)
        if cfg.is_encdec and decoder and kind in ("g", "l"):
            sub["ln_cross"] = (None,)
            sub["cross"] = attn_axes(cfg)
        sub["ln2"] = (None,)
        if kind != "r":
            sub["ffn"] = moe_axes() if _moe_static(cfg, i) else glu_axes()
        if cfg.post_norms:
            sub["post_ln1"] = (None,)
            sub["post_ln2"] = (None,)
        out[f"sub{i}"] = sub
    return out


# ---------------------------------------------------------------- model init

def init_params(key, cfg: ModelConfig) -> Dict[str, Any]:
    keys = jax.random.split(key, 6)
    params: Dict[str, Any] = {
        "embed": init_embed(keys[0], cfg.padded_vocab, cfg.d_model),
        "final_norm": jnp.zeros((cfg.d_model,), jnp.float32)
        if cfg.zero_centered_norm else jnp.ones((cfg.d_model,), jnp.float32),
    }
    bkeys = jax.random.split(keys[1], cfg.n_blocks)
    params["blocks"] = jax.vmap(
        lambda k: init_block(k, cfg, decoder=True))(bkeys)
    if not cfg.tie_embeddings:
        params["lm_head"] = init_dense(keys[2], cfg.d_model, cfg.padded_vocab)
    if cfg.is_encdec:
        n_enc_blocks = cfg.n_enc_layers  # encoder pattern: all-global, period 1
        ekeys = jax.random.split(keys[3], n_enc_blocks)
        enc_cfg = cfg
        params["enc_blocks"] = jax.vmap(
            lambda k: _init_enc_block(k, enc_cfg))(ekeys)
        params["enc_final_norm"] = jnp.ones((cfg.d_model,), jnp.float32)
    if cfg.frontend:
        params["frontend_proj"] = init_dense(keys[4], cfg.frontend_dim,
                                             cfg.d_model)
    return params


def _init_enc_block(key, cfg: ModelConfig) -> Dict[str, Any]:
    k1, k2 = jax.random.split(key)
    return {"ln1": jnp.ones((cfg.d_model,), jnp.float32),
            "attn": init_attn(k1, cfg),
            "ln2": jnp.ones((cfg.d_model,), jnp.float32),
            "ffn": init_glu(k2, cfg.d_model, cfg.d_ff)}


# the scope of the layer that reads each top-level parameter entry
PARAM_SCOPES = {"embed": "embed", "frontend_proj": "frontend",
                "blocks": "blocks", "enc_blocks": "blocks",
                "final_norm": "norm", "enc_final_norm": "norm",
                "lm_head": "loss"}


def cast_params(params: Dict[str, Any]) -> Dict[str, Any]:
    """``params`` with float32 leaves cast to bfloat16, each top-level
    entry under the scope of the layer that reads it, so that the cast and
    its transpose in a train step are named with that layer. Entries go in
    sorted order, the order ``jax.tree.map`` casts them in."""
    out = {}
    for k in sorted(params):
        with jax.named_scope(PARAM_SCOPES[k]):
            out[k] = jax.tree.map(lambda p: p.astype(jnp.bfloat16)
                                  if p.dtype == jnp.float32 else p, params[k])
    return out


# leaves that the model reads only through ``.astype(compute_dtype)``, by
# (parent key, key); besides these, every dense ``w`` and ``b`` but those
# of mamba's ``dt_proj``, which ``mamba_apply`` reads in float32
_COMPUTE_READ = {("embed", "table"), ("ffn", "w1"), ("ffn", "wg"),
                 ("ffn", "w2")}


def compute_read(params: Dict[str, Any]) -> Dict[str, Any]:
    """A tree beside ``params`` that is True at each leaf the model reads
    only through ``.astype(compute_dtype)``: the dense, GLU, attention,
    MoE-expert and head weights and biases, and the embedding table. A
    copy of such a leaf cast ahead once gives the very values every use
    would cast. False at leaves read in float32: norm scales, the MoE
    router, mamba's ``dt_proj``, conv and SSM leaves, rwkv's mixing and
    decay weights."""
    def read(path, _):
        keys = tuple(k.key for k in path)
        parent, name = (("",) + keys)[-2:]
        if name in ("w", "b"):
            return parent != "dt_proj"
        return (parent, name) in _COMPUTE_READ
    return jax.tree_util.tree_map_with_path(read, params)


def param_axes(cfg: ModelConfig) -> Dict[str, Any]:
    axes: Dict[str, Any] = {
        "embed": embed_axes(),
        "final_norm": (None,),
    }
    baxes = block_axes(cfg, decoder=True)
    axes["blocks"] = jax.tree.map(
        lambda t: ("layers",) + tuple(t),
        baxes, is_leaf=lambda t: isinstance(t, tuple))
    if not cfg.tie_embeddings:
        axes["lm_head"] = dense_axes("embed", "vocab")
    if cfg.is_encdec:
        eaxes = {"ln1": (None,), "attn": attn_axes(cfg), "ln2": (None,),
                 "ffn": glu_axes()}
        axes["enc_blocks"] = jax.tree.map(
            lambda t: ("layers",) + tuple(t),
            eaxes, is_leaf=lambda t: isinstance(t, tuple))
        axes["enc_final_norm"] = (None,)
    if cfg.frontend:
        axes["frontend_proj"] = dense_axes(None, "embed")
    return axes


# ---------------------------------------------------------------- cache

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               enc_len: int = 0, dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Stacked decode cache: one entry per sub-layer per block."""
    def one_block() -> Dict[str, Any]:
        c: Dict[str, Any] = {}
        for i, kind in enumerate(cfg.layer_pattern):
            if kind in ("g", "l"):
                sub = {"k": jnp.zeros((batch, max_len, cfg.n_kv_heads,
                                       cfg.head_dim), dtype),
                       "v": jnp.zeros((batch, max_len, cfg.n_kv_heads,
                                       cfg.head_dim), dtype)}
                if cfg.is_encdec:
                    sub["cross_k"] = jnp.zeros(
                        (batch, enc_len, cfg.n_kv_heads, cfg.head_dim), dtype)
                    sub["cross_v"] = jnp.zeros(
                        (batch, enc_len, cfg.n_kv_heads, cfg.head_dim), dtype)
                c[f"sub{i}"] = sub
            elif kind == "m":
                c[f"sub{i}"] = {
                    "conv": jnp.zeros((batch, cfg.mamba_d_conv - 1,
                                       cfg.mamba_d_inner), jnp.float32),
                    "ssm": jnp.zeros((batch, cfg.mamba_d_inner,
                                      cfg.mamba_d_state), jnp.float32)}
            elif kind == "r":
                H = cfg.d_model // cfg.rwkv_head_size
                c[f"sub{i}"] = {
                    "shift_tm": jnp.zeros((batch, 1, cfg.d_model), jnp.float32),
                    "shift_cm": jnp.zeros((batch, 1, cfg.d_model), jnp.float32),
                    "wkv": jnp.zeros((batch, H, cfg.rwkv_head_size,
                                      cfg.rwkv_head_size), jnp.float32)}
        return c

    one = one_block()
    return jax.tree.map(
        lambda t: jnp.broadcast_to(t[None], (cfg.n_blocks,) + t.shape), one)


def cache_axes(cfg: ModelConfig) -> Dict[str, Any]:
    """Logical axes for the cache pytree (same structure as init_cache)."""
    c: Dict[str, Any] = {}
    for i, kind in enumerate(cfg.layer_pattern):
        if kind in ("g", "l"):
            sub = {"k": ("layers", "batch", "cache_seq", "kv_heads", None),
                   "v": ("layers", "batch", "cache_seq", "kv_heads", None)}
            if cfg.is_encdec:
                sub["cross_k"] = ("layers", "batch", "cache_seq", "kv_heads", None)
                sub["cross_v"] = ("layers", "batch", "cache_seq", "kv_heads", None)
            c[f"sub{i}"] = sub
        elif kind == "m":
            c[f"sub{i}"] = {"conv": ("layers", "batch", None, "inner"),
                            "ssm": ("layers", "batch", "inner", None)}
        elif kind == "r":
            c[f"sub{i}"] = {"shift_tm": ("layers", "batch", None, None),
                            "shift_cm": ("layers", "batch", None, None),
                            "wkv": ("layers", "batch", "heads", None, None)}
    return c


# ---------------------------------------------------------------- forward

def _block_body(x, p_block, c_block, *, cfg: ModelConfig,
                positions, lengths, enc_out, has_cache: bool,
                impl: Optional[str], compute_dtype):
    new_cache: Dict[str, Any] = {}
    for i, kind in enumerate(cfg.layer_pattern):
        sub = p_block[f"sub{i}"]
        c_in = c_block.get(f"sub{i}") if has_cache else None
        zc = cfg.zero_centered_norm
        if kind in ("g", "l"):
            h = rms_norm(x, sub["ln1"], cfg.norm_eps, zc)
            attn_cache = ({"k": c_in["k"], "v": c_in["v"]}
                          if c_in is not None else None)
            out, c_new = attn_apply(
                sub["attn"], h, cfg=cfg, kind=kind, positions=positions,
                cache=attn_cache, lengths=lengths, impl=impl,
                compute_dtype=compute_dtype)
            if cfg.post_norms:
                out = rms_norm(out, sub["post_ln1"], cfg.norm_eps, zc)
            x = x + out
            nc = dict(c_new) if c_new is not None else {}
            if cfg.is_encdec:
                h = rms_norm(x, sub["ln_cross"], cfg.norm_eps, zc)
                if has_cache and enc_out is None:
                    cross_cache = {"k": c_in["cross_k"], "v": c_in["cross_v"]}
                    out, _ = attn_apply(sub["cross"], h, cfg=cfg,
                                        kv_x=h,  # ignored: cache path
                                        cache=cross_cache, impl=impl,
                                        compute_dtype=compute_dtype)
                    nc["cross_k"], nc["cross_v"] = cross_cache["k"], cross_cache["v"]
                else:
                    out, _ = attn_apply(sub["cross"], h, cfg=cfg, kv_x=enc_out,
                                        impl=impl, compute_dtype=compute_dtype)
                    if has_cache:
                        ck = init_cross_kv_cache(sub["cross"], enc_out, cfg,
                                                 compute_dtype)
                        nc["cross_k"], nc["cross_v"] = ck["k"], ck["v"]
                x = x + out
            if has_cache:
                new_cache[f"sub{i}"] = nc
            h = rms_norm(x, sub["ln2"], cfg.norm_eps, zc)
            if _moe_static(cfg, i):
                out = moe_apply(sub["ffn"], h, cfg, compute_dtype)
            else:
                out = glu(h, sub["ffn"], cfg.act, compute_dtype)
            if cfg.post_norms:
                out = rms_norm(out, sub["post_ln2"], cfg.norm_eps, zc)
            x = x + out
        elif kind == "m":
            h = rms_norm(x, sub["ln1"], cfg.norm_eps, zc)
            out, conv_s, ssm_s = mamba_apply(
                sub["mamba"], h, cfg,
                conv_state=c_in["conv"] if c_in else None,
                ssm_state=c_in["ssm"] if c_in else None,
                impl=impl, compute_dtype=compute_dtype)
            x = x + out
            if has_cache:
                new_cache[f"sub{i}"] = {"conv": conv_s, "ssm": ssm_s}
            h = rms_norm(x, sub["ln2"], cfg.norm_eps, zc)
            if _moe_static(cfg, i):
                out = moe_apply(sub["ffn"], h, cfg, compute_dtype)
            else:
                out = glu(h, sub["ffn"], cfg.act, compute_dtype)
            x = x + out
        elif kind == "r":
            h = rms_norm(x, sub["ln1"], cfg.norm_eps, zc)
            out, shift_tm, wkv = time_mix(
                sub["rwkv"], h, cfg,
                shift_state=c_in["shift_tm"] if c_in else None,
                wkv_state=c_in["wkv"] if c_in else None,
                impl=impl, compute_dtype=compute_dtype)
            x = x + out
            h = rms_norm(x, sub["ln2"], cfg.norm_eps, zc)
            out, shift_cm = channel_mix(
                sub["rwkv"], h, cfg,
                shift_state=c_in["shift_cm"] if c_in else None,
                compute_dtype=compute_dtype)
            x = x + out
            if has_cache:
                new_cache[f"sub{i}"] = {"shift_tm": shift_tm,
                                        "shift_cm": shift_cm, "wkv": wkv}
        x = shard(x, "batch", "seq", "embed")
    return x, new_cache


def _encode(params, frames, cfg: ModelConfig, impl, compute_dtype):
    """Audio encoder: frames [B, S, fd] -> [B, S, D] (bidirectional)."""
    x = frames.astype(compute_dtype) @ params["frontend_proj"]["w"].astype(
        compute_dtype)
    x = shard(x, "batch", "seq", "embed")
    positions = jnp.arange(frames.shape[1])

    def body(h, p_block):
        a = rms_norm(h, p_block["ln1"], cfg.norm_eps)
        out, _ = attn_apply(p_block["attn"], a, cfg=cfg, causal=False,
                            positions=positions, impl=impl,
                            compute_dtype=compute_dtype)
        h = h + out
        a = rms_norm(h, p_block["ln2"], cfg.norm_eps)
        h = h + glu(a, p_block["ffn"], cfg.act, compute_dtype)
        return shard(h, "batch", "seq", "embed"), None

    body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["enc_blocks"])
    return rms_norm(x, params["enc_final_norm"], cfg.norm_eps)


def forward(params, cfg: ModelConfig, *, tokens=None, positions=None,
            cache=None, lengths=None, frames=None, patches=None,
            remat: bool = False, impl: Optional[str] = None,
            compute_dtype=jnp.bfloat16):
    """Run the decoder stack. Returns (hidden [B,S,D], new_cache|None)."""
    x = embed(tokens, params["embed"], scale_by_dim=cfg.embed_scale,
              compute_dtype=compute_dtype)
    if cfg.frontend == "vit_stub" and patches is not None:
        with jax.named_scope("frontend"):
            pe = patches.astype(compute_dtype) @ params["frontend_proj"][
                "w"].astype(compute_dtype)
            x = jnp.concatenate([pe, x[:, patches.shape[1]:]], axis=1)
            x = shard(x, "batch", "seq", "embed")
    enc_out = None
    if cfg.is_encdec and frames is not None:
        enc_out = _encode(params, frames, cfg, impl, compute_dtype)

    B, S, _ = x.shape
    has_cache = cache is not None

    def scan_body(carry, xs):
        p_block, c_block = xs
        h, new_c = body_fn(carry, p_block, c_block)
        return h, new_c

    if remat:
        scan_body = jax.checkpoint(
            scan_body, policy=jax.checkpoint_policies.nothing_saveable)

    with jax.named_scope("blocks"):
        if positions is None:
            positions = (jnp.arange(S) if lengths is None or S > 1
                         else (lengths - 1)[:, None])
        body_fn = functools.partial(
            _block_body, cfg=cfg, positions=positions, lengths=lengths,
            enc_out=enc_out, has_cache=has_cache, impl=impl,
            compute_dtype=compute_dtype)
        c_in = cache if has_cache else {
            # dummy xs aligned with blocks; body ignores it
            "_": jnp.zeros((cfg.n_blocks,), jnp.float32)}
        x, new_cache = jax.lax.scan(scan_body, x, (params["blocks"], c_in))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps, cfg.zero_centered_norm)
    return x, (new_cache if has_cache else None)


def logits_head(params, cfg: ModelConfig, h: jnp.ndarray,
                compute_dtype=jnp.bfloat16) -> jnp.ndarray:
    with jax.named_scope("loss"):
        w = (params["embed"]["table"].T if cfg.tie_embeddings
             else params["lm_head"]["w"])
        logits = (h.astype(compute_dtype) @ w.astype(compute_dtype)).astype(
            jnp.float32)
        if cfg.final_softcap > 0:
            logits = jnp.tanh(logits / cfg.final_softcap) * cfg.final_softcap
        if cfg.padded_vocab != cfg.vocab:   # mask padding out of the softmax
            logits = jnp.where(jnp.arange(cfg.padded_vocab) < cfg.vocab,
                               logits, -1e30)
        return shard(logits, "batch", "act_seq", "vocab")


def loss_fn(params, batch: Dict[str, jnp.ndarray], cfg: ModelConfig, *,
            remat: bool = True, impl: Optional[str] = None,
            compute_dtype=jnp.bfloat16) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """Next-token cross entropy (chunked — [B,S,V] never materialized)."""
    tokens = batch["tokens"]
    h, _ = forward(params, cfg, tokens=tokens,
                   frames=batch.get("frames"), patches=batch.get("patches"),
                   remat=remat, impl=impl, compute_dtype=compute_dtype)
    with jax.named_scope("loss"):
        labels = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
        mask = batch.get("mask")
        if mask is None:
            mask = jnp.ones(tokens.shape, jnp.float32)
        mask = mask.at[:, -1].set(0.0)
        w = (params["embed"]["table"].T if cfg.tie_embeddings
             else params["lm_head"]["w"])
        loss_sum, w_sum = chunked_softmax_xent(
            h, w, labels, mask=mask, final_softcap=cfg.final_softcap,
            valid_vocab=cfg.vocab, compute_dtype=compute_dtype)
        loss = loss_sum / jnp.maximum(w_sum, 1.0)
    return loss, {"loss_sum": loss_sum, "weight": w_sum}


def prefill(params, cfg: ModelConfig, tokens, cache, *, lengths=None,
            frames=None, patches=None, impl: Optional[str] = None,
            compute_dtype=jnp.bfloat16):
    """Fill the cache with S tokens; return (last-token logits, cache, lengths).

    ``lengths`` ([B] int32, optional) marks per-row true prompt lengths for
    right-padded ragged batches: logits are gathered at each row's last
    *valid* position instead of S-1 and the returned lengths echo the true
    lengths. Pad garbage beyond a row's length is masked out of decode by
    the length-aware attention kernels (recurrent layers are NOT pad-safe —
    callers bucket those by exact length, see ``serving.engine``).
    """
    B, S = tokens.shape[0], tokens.shape[1]
    h, cache = forward(params, cfg, tokens=tokens, cache=cache,
                       frames=frames, patches=patches, impl=impl,
                       compute_dtype=compute_dtype)
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
        h_last = h[:, -1:]
    else:
        lengths = jnp.asarray(lengths, jnp.int32)
        h_last = jnp.take_along_axis(
            h, (lengths - 1).astype(jnp.int32)[:, None, None], axis=1)
    logits = logits_head(params, cfg, h_last, compute_dtype)
    return logits, cache, lengths


def decode_step(params, cfg: ModelConfig, tokens, cache, lengths, *,
                impl: Optional[str] = None, compute_dtype=jnp.bfloat16):
    """One decode step. tokens [B,1]; lengths [B] = position+1 of new token."""
    h, cache = forward(params, cfg, tokens=tokens, cache=cache,
                       lengths=lengths, impl=impl, compute_dtype=compute_dtype)
    logits = logits_head(params, cfg, h, compute_dtype)
    return logits, cache, lengths + 1
