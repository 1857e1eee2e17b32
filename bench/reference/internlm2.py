"""Plain reference of a training step of the InternLM2 decoder as InternVL2
runs it (Hugging Face ``InternLM2ForCausalLM``, arXiv:2403.17297; InternVL2,
arXiv:2404.16821): next-token cross-entropy, its gradients, and one AdamW
step, in float32 at the highest matmul precision.

It imports nothing of the program. It reads the weights the benchmark made
from the seed, in the tree the benchmark hands the program:

    embed.table [V', d]     lm_head.w [d, V']     final_norm [d]
    frontend_proj.w [fd, d]                  (image features -> width d)
    blocks.sub0.ln1 / ln2 [L, d]
    blocks.sub0.attn.{wq,wk,wv}.w [L, d, heads*D]    attn.wo.w [L, H*D, d]
    blocks.sub0.ffn.{wg,wi}.w [L, d, ff]             ffn.wo.w [L, ff, d]

(V' >= vocab_size rows; only the first vocab_size are tokens.) Layers are
pre-norm: ``x += wo(attn(rope(q), rope(k), v))``, then ``x += down(silu(
gate(x)) * up(x))`` with gate ``wg`` and up ``wi``; no biases. RoPE rotates
the two halves of each head by theta^(-i/half). Attention is causal, each KV
head shared by H/KV consecutive query heads, scaled by 1/sqrt(D). The first
``image_tokens`` positions hold image features projected by
``frontend_proj`` in place of token embeddings (InternViT and its MLP
projector are stubbed by that one map, in the program and here alike).
The loss is the mean over positions whose mask is 1 of -log p(next token);
the last position has no next token.

The optimizer is AdamW as the configuration's ``optimizer`` states it:
gradients clipped to a global norm, bias-corrected moments, decoupled
weight decay on the leaves the configuration names, linear warm-up.

So that it fits four 16 GB chips, each chip holds a quarter of every leaf
(split along its last axis) and one sequence of the batch; a layer's
weights are gathered whole when the layer runs, and each layer is
recomputed in the backward pass. That changes where numbers live, not
what is computed.

``low=True`` is the control: every matmul takes operands rounded to
float8 e4m3, with a per-tensor scale for weights and a per-row scale for
activations, the step below the bfloat16 the configuration computes in.
"""
from __future__ import annotations

import functools
import json
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0
AXIS = "r"


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


def _rope(x, theta):
    S, half = x.shape[0], x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, low: bool, block: int):
    """q [S, H, D], k/v [S, KV, D]: causal, in blocks of ``block`` query
    rows, each recomputed in the backward pass."""
    S, H, D = q.shape
    g = H // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    if low:
        k, v = _q8(k, axis=-1), _q8(v)

    @jax.checkpoint
    def rows(qb, start):
        if low:
            qb = _q8(qb, axis=-1)
        s = jnp.einsum("shd,thd->hst", qb, k, precision=HIGHEST) / jnp.sqrt(
            jnp.float32(D))
        pos = start + jnp.arange(qb.shape[0])
        causal = jnp.arange(S)[None, :] <= pos[:, None]
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        if low:
            p = _q8(p, axis=-1)
        return jnp.einsum("hst,thd->shd", p, v, precision=HIGHEST)

    b = min(block, S)
    return jnp.concatenate([rows(q[i:i + b], i) for i in range(0, S, b)])


def _layer(x, p, conf, low: bool):
    S = x.shape[0]
    H, KV = conf["num_attention_heads"], conf["num_key_value_heads"]
    D = conf["hidden_size"] // H
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    a, f = p["attn"], p["ffn"]
    h = _rms(x, p["ln1"], eps)
    q = _mm(h, a["wq"]["w"], low).reshape(S, H, D)
    k = _mm(h, a["wk"]["w"], low).reshape(S, KV, D)
    v = _mm(h, a["wv"]["w"], low).reshape(S, KV, D)
    o = _attention(_rope(q, theta), _rope(k, theta), v, low,
                   conf["reference_block"])
    x = x + _mm(o.reshape(S, H * D), a["wo"]["w"], low)
    h = _rms(x, p["ln2"], eps)
    return x + _mm(jax.nn.silu(_mm(h, f["wg"]["w"], low))
                   * _mm(h, f["wi"]["w"], low), f["wo"]["w"], low)


def _gather(t):
    """A leaf held a quarter a chip, made whole on every chip."""
    return jax.lax.all_gather(t, AXIS, axis=t.ndim - 1, tiled=True)


def seq_loss(params, conf, tokens, patches, mask, low: bool,
             gather=lambda t: t) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(sum of the masked losses, sum of the mask) of one sequence:
    tokens [S], patches [image_tokens, fd], mask [S]."""
    V = conf["vocab_size"]
    eps = conf["rms_norm_eps"]
    n_img = patches.shape[0]
    x = gather(params["embed"]["table"])[tokens]
    pe = _mm(patches, gather(params["frontend_proj"]["w"]), low)
    x = jnp.concatenate([pe, x[n_img:]], axis=0)

    @jax.checkpoint
    def layer(x, p):
        return _layer(x, jax.tree.map(gather, p), conf, low), None

    x, _ = jax.lax.scan(layer, x, params["blocks"]["sub0"])
    x = _rms(x, gather(params["final_norm"]), eps)
    head = gather(params["lm_head"]["w"])[:, :V]
    labels = jnp.concatenate([tokens[1:], tokens[:1]])
    mask = mask.at[-1].set(0.0)

    @jax.checkpoint
    def chunk(xc, lc, mc):
        lg = _mm(xc, head, low)
        nll = jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(
            lg, lc[:, None], axis=-1)[:, 0]
        return jnp.sum(nll * mc)

    S, b = x.shape[0], conf["reference_block"]
    total = sum(chunk(x[i:i + b], labels[i:i + b], mask[i:i + b])
                for i in range(0, S, b))
    return total, jnp.sum(mask)


# ------------------------------------------------------------- the step

def leaf_spec(ndim: int) -> P:
    return P(*([None] * (ndim - 1) + [AXIS]))


def shardings(mesh, params) -> Any:
    """Each leaf split along its last axis over the chips."""
    return jax.tree.map(lambda t: NamedSharding(mesh, leaf_spec(t.ndim)),
                        params)


def decayed(path: str, conf: Dict[str, Any]) -> bool:
    return path not in conf["optimizer"]["no_decay"]


def _paths(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [".".join(str(getattr(k, "key", k)) for k in p) for p, _ in flat]


def leaf_norms(tree) -> Dict[str, jnp.ndarray]:
    """Norm of each leaf; a layer-stacked leaf (``blocks.*``) gives one
    norm per layer."""
    out = {}
    for name, t in zip(_paths(tree), jax.tree.leaves(tree)):
        t = t.astype(jnp.float32)
        if name.startswith("blocks."):
            out[name] = jnp.sqrt(jnp.sum(t * t, axis=tuple(range(1, t.ndim))))
        else:
            out[name] = jnp.sqrt(jnp.sum(t * t))[None]
    return out


def lr_at(opt: Dict[str, Any], t):
    """Linear warm-up to ``peak_lr`` over ``warmup_steps``, then cosine to
    ``min_lr_ratio`` of it at ``total_steps``; ``t`` counts from 1."""
    t = t.astype(jnp.float32)
    warm = opt["peak_lr"] * t / opt["warmup_steps"]
    prog = jnp.clip((t - opt["warmup_steps"])
                    / (opt["total_steps"] - opt["warmup_steps"]), 0.0, 1.0)
    cos = opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"]) * 0.5 * (
        1 + jnp.cos(jnp.pi * prog))
    return jnp.where(t < opt["warmup_steps"], warm, opt["peak_lr"] * cos)


@functools.lru_cache(maxsize=None)
def make_step(conf_json: str, mesh, low: bool):
    """step(params, m, v, t, batch) -> (params, m, v, loss, norms of the
    clipped gradient per leaf), jitted over ``mesh`` (one axis, ``r``);
    ``conf_json`` is the configuration as JSON."""
    conf = json.loads(conf_json)
    opt = conf["optimizer"]

    def local(params, tokens, patches, mask):
        ls, ws = jax.vmap(lambda t, p, m: seq_loss(
            params, conf, t, p, m, low, gather=_gather))(tokens, patches, mask)
        return (jax.lax.psum(jnp.sum(ls), AXIS),
                jax.lax.psum(jnp.sum(ws), AXIS))

    def loss(params, batch):
        specs = jax.tree.map(lambda t: leaf_spec(t.ndim), params)
        ls, ws = jax.shard_map(
            local, mesh=mesh,
            in_specs=(specs, P(AXIS), P(AXIS), P(AXIS)),
            out_specs=(P(), P()))(params, batch["tokens"], batch["patches"],
                                  batch["mask"])
        return ls / ws

    def step(params, m, v, t, batch):
        value, g = jax.value_and_grad(loss)(params, batch)
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        g = jax.tree.map(
            lambda x: x * jnp.minimum(1.0, opt["clip_norm"] / (gnorm + 1e-6)),
            g)
        lr = lr_at(opt, t)
        b1, b2 = opt["b1"], opt["b2"]
        tf = t.astype(jnp.float32)
        m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
        v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
        names = _paths(params)
        flat_p, tree = jax.tree.flatten(params)
        new = []
        for name, p_, m_, v_ in zip(names, flat_p, jax.tree.leaves(m),
                                    jax.tree.leaves(v)):
            upd = (m_ / (1 - b1 ** tf)) / (jnp.sqrt(v_ / (1 - b2 ** tf))
                                           + opt["eps"])
            if decayed(name, conf):
                upd = upd + opt["weight_decay"] * p_
            new.append(p_ - lr * upd)
        return (jax.tree.unflatten(tree, new), m, v, value, leaf_norms(g))

    return jax.jit(step, donate_argnums=(0, 1, 2))
