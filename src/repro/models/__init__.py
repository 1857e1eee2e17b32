"""Model substrate: the ten assigned architectures as one composable stack."""
from .config import SHAPES, ModelConfig, ShapeConfig, reduced
from .transformer import (cache_axes, cast_params, compute_read,
                          decode_step, forward, init_cache, init_params,
                          logits_head, loss_fn, param_axes, prefill)

__all__ = ["ModelConfig", "ShapeConfig", "SHAPES", "reduced", "init_params",
           "param_axes", "forward", "loss_fn", "prefill", "decode_step",
           "init_cache", "cache_axes", "logits_head", "cast_params",
           "compute_read"]
