"""The program's model config from a configuration file, and its weights
made on the device from the seed.

The benchmark makes the weights itself, so that the reference reads
nothing the program made. It takes only the tree's structure and shapes
from the program (``jax.eval_shape`` of its ``init_params``) and fills
every leaf in one jitted call, by a rule on the leaf's name: a leaf with
no rule is an error, so a change of the program's layout is caught here
and not read wrongly by the reference.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp


def program_config(conf: Dict[str, Any]):
    from repro.models.config import ModelConfig
    return ModelConfig(**conf["program"])


def _path(path) -> str:
    return ".".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def _rule(name: str, shape, cfg):
    """(kind, std) of one leaf: "normal" leaves are N(0, std^2),
    "scale" leaves 1 + std * N(0, 1)."""
    last = name.rsplit(".", 1)[-1]
    if name == "embed.table":
        return "normal", 1.0
    if last in ("ln1", "ln2", "final_norm"):
        return "scale", 0.1
    if last == "b":
        return "normal", 0.5
    if last == "w":
        return "normal", float(shape[-2]) ** -0.5     # 1/sqrt(fan_in)
    raise KeyError(f"no weight rule for leaf {name!r} {shape}")


def make_params(cfg, seed: int, dtype=jnp.bfloat16, shardings=None):
    """Weights from the seed: drawn in float32, rounded to ``dtype`` (the
    checkpoint's), held in float32 as the program's own init makes them,
    and laid out on the devices as ``shardings`` says (a tree like the
    weights'; default: one device)."""
    from repro.models import init_params
    shapes = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    rules = [_rule(_path(p), s.shape, cfg) for p, s in flat]

    def build(key):
        keys = jax.random.split(key, len(flat))
        out = []
        for (_, s), (kind, std), k in zip(flat, rules, keys):
            z = jax.random.normal(k, s.shape, jnp.float32) * std
            v = 1.0 + z if kind == "scale" else z
            out.append(v.astype(dtype).astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    from .common import seed32
    key = jax.random.PRNGKey(seed32(seed, 1))
    return jax.jit(build, out_shardings=shardings)(key)

