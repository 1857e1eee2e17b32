"""Plain reference of a decoder whose every feed-forward is a mixture of
SwiGLU experts, a token taking its top ``num_experts_per_tok`` of them by
a softmax router (Mixtral, arXiv:2401.04088); ``norm_topk_prob`` says
whether the chosen gates are renormalised to sum to 1 (Mixtral and
Qwen3-MoE) or kept as the softmax gave them (OLMoE, arXiv:2409.02060).
No QK norm and no shared expert. In float32 at the highest matmul
precision.

Attention, norms and RoPE are the Qwen2 reference's (``qwen2.py``), with
no QKV bias unless the weights hold one. It imports nothing of the
program. It reads the weights the benchmark made from the seed, in the
tree the benchmark hands the program:

    embed.table [V', d]            lm_head.w [d, V']      final_norm [d]
    blocks.sub0.ln1 / ln2 [L, d]
    blocks.sub0.attn.{wq,wk,wv}.w [L, d, heads*D]   attn.wo.w [L, H*D, d]
    blocks.sub0.ffn.router [L, d, E]
    blocks.sub0.ffn.{w1,wg} [L, E, d, f]    blocks.sub0.ffn.w2 [L, E, f, d]

An expert is ``w2(silu(wg(x)) * w1(x))``. Every expert here is computed
for every token and weighted by the token's gate for it, 0 where the
expert is not among its top k: plain, and no token is ever dropped.

``low=True`` is the control: every matmul, the router's included, takes
operands rounded to float8 e4m3, the step below the bfloat16 the
configuration computes in.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from bench.reference.qwen2 import _attention, _mm, _rms, _rope


def _experts(h, f, k: int, renorm: bool, low: bool):
    """h [S, d] -> [S, d]: the gated sum of each token's top-k experts."""
    S = h.shape[0]
    probs = jax.nn.softmax(_mm(h, f["router"], low), axis=-1)     # [S, E]
    gates, idx = jax.lax.top_k(probs, k)
    if renorm:
        gates = gates / gates.sum(-1, keepdims=True)
    weight = jnp.zeros_like(probs).at[jnp.arange(S)[:, None], idx].set(gates)

    def expert(acc, e):
        w1, wg, w2, g = e
        y = _mm(jax.nn.silu(_mm(h, wg, low)) * _mm(h, w1, low), w2, low)
        return acc + g[:, None] * y, None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                          (f["w1"], f["wg"], f["w2"], weight.T))
    return out


def logits(params: Dict[str, Any], conf: Dict[str, Any], tokens, read,
           low: bool = False):
    """Logits [len(read), vocab_size] at positions ``read`` of the
    sequence ``tokens`` [S]. Positions after the last one read do not
    change what is read (causal), so ``tokens`` may be padded at the end."""
    d = conf["hidden_size"]
    H, KV = conf["num_attention_heads"], conf["num_key_value_heads"]
    D = conf.get("head_dim", d // H)
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    k, renorm = conf["num_experts_per_tok"], conf["norm_topk_prob"]
    S = tokens.shape[0]
    pos = jnp.arange(S)
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    x = f32(params["embed"]["table"])[tokens]
    blocks = params["blocks"]["sub0"]

    def proj(h, p, n):
        y = _mm(h, p["w"], low)
        return (y + p["b"] if "b" in p else y).reshape(S, n, D)

    for layer in range(conf["num_hidden_layers"]):
        p = jax.tree.map(lambda a: f32(a[layer]), blocks)
        a = p["attn"]
        h = _rms(x, p["ln1"], eps)
        q, kk, v = proj(h, a["wq"], H), proj(h, a["wk"], KV), \
            proj(h, a["wv"], KV)
        o = _attention(_rope(q, pos, theta), _rope(kk, pos, theta), v, low)
        x = x + _mm(o.reshape(S, H * D), a["wo"]["w"], low)
        x = x + _experts(_rms(x, p["ln2"], eps), p["ffn"], k, renorm, low)
    x = _rms(x, f32(params["final_norm"]), eps)
    head = f32(params["lm_head"]["w"])[:, :conf["vocab_size"]]
    return _mm(x[read], head, low)
