"""Plain reference of the Qwen2 decoder (Hugging Face ``Qwen2ForCausalLM``,
arXiv:2407.10671), in float32 at the highest matmul precision.

No kernels, cache or batching: one sequence, full causal attention over
it (in blocks of query rows), every layer written out. It imports
nothing of the program. It reads the weights the benchmark made from the
seed, in the tree the benchmark hands the program:

    embed.table [V', d]            lm_head.w [d, V']      final_norm [d]
    blocks.sub0.ln1 / ln2 [L, d]
    blocks.sub0.attn.{wq,wk,wv}.{w,b} [L, d, heads*128] / [L, heads*128]
    blocks.sub0.attn.wo.w [L, H*128, d]
    blocks.sub0.ffn.{wg,wi}.w [L, d, ff]    blocks.sub0.ffn.wo.w [L, ff, d]

(V' >= vocab_size rows; only the first vocab_size are tokens.) The MLP is
``down(silu(gate(x)) * up(x))`` with gate ``wg`` and up ``wi``. RoPE
rotates the two halves of each head, inverse frequencies theta^(-i/half).

``low=True`` is the control: every matmul takes operands rounded to
float8 e4m3, with a per-tensor scale for weights and a per-row scale for
activations, the step below the bfloat16 the configuration computes in.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0
BLOCK = 1024         # query rows of attention scores held at a time


def _q8(x, axis=None):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.maximum(amax, 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, w, low: bool):
    if low:
        a, w = _q8(a, axis=-1), _q8(w)
    return jnp.matmul(a, w, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, low: bool):
    """q [S, H, D], k/v [S, KV, D]: causal, each KV head shared by
    H/KV consecutive query heads; taken in blocks of ``BLOCK`` query rows,
    so that one block's scores, not all of them, are held at a time."""
    S, H, D = q.shape
    g = H // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    if low:
        q, k, v = _q8(q, axis=-1), _q8(k, axis=-1), _q8(v)
    blk = min(S, BLOCK)
    pad = (-S) % blk
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, blk, H, D)
    rows = jnp.arange(S + pad).reshape(-1, blk)
    cols = jnp.arange(S)

    def block(args):
        qi, ri = args
        s = jnp.einsum("shd,thd->hst", qi, k, precision=HIGHEST) / jnp.sqrt(
            jnp.float32(D))
        causal = ri[:, None] >= cols[None, :]
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        if low:
            p = _q8(p, axis=-1)
        return jnp.einsum("hst,thd->shd", p, v, precision=HIGHEST)

    return jax.lax.map(block, (qb, rows)).reshape(S + pad, H, D)[:S]


def logits(params: Dict[str, Any], conf: Dict[str, Any], tokens, read,
           low: bool = False):
    """Logits [len(read), vocab_size] at positions ``read`` of the
    sequence ``tokens`` [S]. Positions after the last one read do not
    change what is read (causal), so ``tokens`` may be padded at the end."""
    d = conf["hidden_size"]
    H, KV = conf["num_attention_heads"], conf["num_key_value_heads"]
    D = d // H
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    S = tokens.shape[0]
    pos = jnp.arange(S)
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    x = f32(params["embed"]["table"])[tokens]
    blocks = params["blocks"]["sub0"]
    for layer in range(conf["num_hidden_layers"]):
        p = jax.tree.map(lambda a: f32(a[layer]), blocks)
        a = p["attn"]
        h = _rms(x, p["ln1"], eps)
        q = (_mm(h, a["wq"]["w"], low) + a["wq"]["b"]).reshape(S, H, D)
        k = (_mm(h, a["wk"]["w"], low) + a["wk"]["b"]).reshape(S, KV, D)
        v = (_mm(h, a["wv"]["w"], low) + a["wv"]["b"]).reshape(S, KV, D)
        o = _attention(_rope(q, pos, theta), _rope(k, pos, theta), v, low)
        x = x + _mm(o.reshape(S, H * D), a["wo"]["w"], low)
        h = _rms(x, p["ln2"], eps)
        f = p["ffn"]
        x = x + _mm(jax.nn.silu(_mm(h, f["wg"]["w"], low))
                    * _mm(h, f["wi"]["w"], low), f["wo"]["w"], low)
    x = _rms(x, f32(params["final_norm"]), eps)
    head = f32(params["lm_head"]["w"])[:, :conf["vocab_size"]]
    return _mm(x[read], head, low)
