"""Train/serve step factories.

``make_train_step`` returns a pure function (params, opt_state, batch) ->
(params, opt_state, metrics) implementing: bf16-compute forward with remat +
scan-over-layers, chunked cross-entropy, AdamW(fp32 moments), global-norm
clip, warmup+cosine LR.

Optional cross-pod int8 gradient compression: the gradient is computed
pod-locally (shard_map manual on the pod axis, all other axes automatic) and
mean-reduced over pods with int8 + error feedback (training/grad_compress).
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..models import cast_params
from ..models import decode_step as model_decode_step
from ..models import loss_fn as model_loss_fn
from ..models import prefill as model_prefill
from ..models.config import ModelConfig
from .grad_compress import init_error_state
from .optimizer import OptimizerConfig, adamw_update, init_opt_state


def make_train_step(cfg: ModelConfig, opt_cfg: OptimizerConfig, *,
                    mesh: Optional[Mesh] = None,
                    grad_compress_pod: bool = False,
                    remat: bool = True,
                    microbatches: int = 1) -> Callable:
    """Returns train_step(params, opt_state, batch) -> (params, opt, metrics).

    Attention takes ``ops.mha``'s own choice: on a TPU the Pallas flash
    kernels, whose custom VJP runs dq and dk/dv kernels that recompute p
    per tile, each chip on its own heads under a ``shard_map`` where the
    sharding rules leave the sequence whole; elsewhere, and under a
    sequence-sharded (``context``) layout, the XLA flash path.

    ``microbatches`` > 1 splits the global batch and accumulates gradients
    over a lax.scan (activation memory / n at unchanged math). When
    ``grad_compress_pod`` and the mesh has a "pod" axis, gradients are
    reduced across pods in int8 with error feedback; ``opt_state`` then
    carries an extra "ef" residual tree.
    """

    # the recurrent scans' Pallas kernels have no VJP: a model with such
    # layers keeps every kernel on its XLA path
    impl = "xla" if set(cfg.layer_pattern) & {"m", "r"} else None

    def loss_of(params, batch):
        # cast fp32 masters to bf16 BEFORE use: FSDP all-gathers then move
        # bf16, halving gather bytes and buffers
        params = cast_params(params)
        loss, aux = model_loss_fn(params, batch, cfg, remat=remat,
                                  impl=impl)
        return loss, aux

    use_compress = (grad_compress_pod and mesh is not None
                    and "pod" in mesh.axis_names)

    def plain_grads(params, batch):
        if microbatches <= 1:
            (loss, aux), grads = jax.value_and_grad(loss_of, has_aux=True)(
                params, batch)
            return loss, aux, grads, {}
        # gradient accumulation: scan over microbatches, fp32 accumulators
        mb_batch = jax.tree.map(
            lambda t: t.reshape((microbatches, t.shape[0] // microbatches)
                                + t.shape[1:]), batch)

        def acc_body(carry, mb):
            g_acc, loss_acc, w_acc = carry
            (loss, aux), grads = jax.value_and_grad(loss_of, has_aux=True)(
                params, mb)
            g_acc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32), g_acc, grads)
            return (g_acc, loss_acc + aux["loss_sum"],
                    w_acc + aux["weight"]), None

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (grads, loss_sum, weight), _ = jax.lax.scan(
            acc_body, (g0, jnp.float32(0.0), jnp.float32(0.0)), mb_batch)
        grads = jax.tree.map(lambda g: g / microbatches, grads)
        loss = loss_sum / jnp.maximum(weight, 1.0)
        return loss, {"loss_sum": loss_sum, "weight": weight}, grads, {}

    def compressed_grads(params, batch, ef):
        npod = mesh.shape["pod"]

        def body(params, batch, ef):
            (loss, aux), grads = jax.value_and_grad(loss_of, has_aux=True)(
                params, batch)

            def reduce_one(g, e):
                gf = g.astype(jnp.float32) + e
                scale = jnp.max(jnp.abs(gf)) / 127.0 + 1e-30
                smax = jax.lax.pmax(scale, "pod")
                q = jnp.clip(jnp.round(gf / smax), -127, 127).astype(jnp.int8)
                total = jax.lax.psum(q.astype(jnp.int32), "pod")
                mean = total.astype(jnp.float32) * smax / npod
                return mean, gf - q.astype(jnp.float32) * smax

            pairs = jax.tree.map(reduce_one, grads, ef)
            gmean = jax.tree.map(lambda t: t[0], pairs,
                                 is_leaf=lambda t: isinstance(t, tuple))
            ef_new = jax.tree.map(lambda t: t[1], pairs,
                                  is_leaf=lambda t: isinstance(t, tuple))
            loss = jax.lax.pmean(loss, "pod")
            aux = jax.tree.map(lambda a: jax.lax.pmean(a, "pod"), aux)
            return loss, aux, gmean, ef_new

        batch_specs = jax.tree.map(lambda _: P("pod"), batch)
        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(), params), batch_specs,
                      jax.tree.map(lambda _: P(), ef)),
            out_specs=(P(), jax.tree.map(lambda _: P(), {"loss_sum": 0,
                                                         "weight": 0}),
                       jax.tree.map(lambda _: P(), params),
                       jax.tree.map(lambda _: P(), ef)),
            axis_names={"pod"}, check_vma=False)
        loss, aux, grads, ef_new = fn(params, batch, ef)
        return loss, aux, grads, {"ef": ef_new}

    def train_step(params, opt_state, batch):
        if use_compress:
            loss, aux, grads, extra = compressed_grads(
                params, batch, opt_state["ef"])
        else:
            loss, aux, grads, extra = plain_grads(params, batch)
        with jax.named_scope("optimizer"):
            new_params, new_opt, metrics = adamw_update(
                opt_cfg, params, grads, opt_state)
        new_opt.update(extra)
        metrics = dict(metrics)
        metrics["loss"] = loss
        metrics["tokens"] = aux["weight"]
        return new_params, new_opt, metrics

    return train_step


def make_opt_state(params, *, grad_compress_pod: bool = False):
    state = init_opt_state(params)
    if grad_compress_pod:
        state["ef"] = init_error_state(params)
    return state


def make_prefill_step(cfg: ModelConfig, *, impl: Optional[str] = None
                      ) -> Callable:
    def prefill_step(params, tokens, cache, frames=None, patches=None):
        return model_prefill(params, cfg, tokens, cache, frames=frames,
                             patches=patches, impl=impl)
    return prefill_step


def make_decode_step(cfg: ModelConfig, *, impl: Optional[str] = None
                     ) -> Callable:
    def serve_step(params, tokens, cache, lengths):
        return model_decode_step(params, cfg, tokens, cache, lengths,
                                 impl=impl)
    return serve_step
