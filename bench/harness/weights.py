"""The program's model config from a configuration file, and its weights
made on the device from the seed.

The benchmark makes the weights itself, so that the reference reads
nothing the program made. It takes only the tree's structure and shapes
from the program (``jax.eval_shape`` of its ``init_params``) and fills
every leaf in one jitted call, by a rule on the leaf's name: a leaf with
no rule is an error, so a change of the program's layout is caught here
and not read wrongly by the reference.

The rules are the built-in table :data:`BUILT_IN` and a configuration
file's own ``weight_rules``, which maps a leaf's name to a rule:

    {"kind": "normal", "std": <number> | "fan_in"}   N(0, std^2); "fan_in"
                                                     is shape[-2] ** -0.5
    {"kind": "scale", "std": <number>}               1 + std * N(0, 1)
    {"kind": "const", "value": <number>}             that value everywhere

A rule's name is a leaf's last path component, or a dotted suffix of its
path where that is needed to tell two leaves apart; of the names that
match a leaf, the longest wins. A name may not be in both tables, nor
may a leaf match a name of each: a file adds rules and overrides none.
Every value is drawn from the seed or given in the file.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

BUILT_IN: Dict[str, Dict[str, Any]] = {
    "embed.table": {"kind": "normal", "std": 1.0},
    "ln1": {"kind": "scale", "std": 0.1},
    "ln2": {"kind": "scale", "std": 0.1},
    "final_norm": {"kind": "scale", "std": 0.1},
    "b": {"kind": "normal", "std": 0.5},
    "w": {"kind": "normal", "std": "fan_in"},
}

_PARAM = {"normal": "std", "scale": "std", "const": "value"}


def program_config(conf: Dict[str, Any]):
    from repro.models.config import ModelConfig
    return ModelConfig(**conf["program"])


def _path(path) -> str:
    return ".".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def _number(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool)


def file_rules(conf: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """The configuration file's ``weight_rules``, checked: each a rule of a
    known kind with its one number, and none named as a built-in one."""
    rules = conf.get("weight_rules", {})
    where = f"\"weight_rules\" of configuration {conf['program']['name']!r}"
    for name, rule in rules.items():
        kind = rule.get("kind")
        if kind not in _PARAM or set(rule) != {"kind", _PARAM[kind]}:
            raise ValueError(
                f"{where}: rule {name!r} {rule} is not one of "
                + ", ".join(f"{{kind: {k}, {p}}}" for k, p in _PARAM.items()))
        x = rule[_PARAM[kind]]
        if not (_number(x) or (kind == "normal" and x == "fan_in")):
            raise ValueError(f"{where}: rule {name!r} has {_PARAM[kind]} "
                             f"{x!r}, not a number")
        if name in BUILT_IN:
            raise ValueError(f"{where}: {name!r} is a built-in rule, which a "
                             f"configuration file may not override")
    return rules


def _match(name: str, table: Dict[str, Any]) -> Optional[str]:
    """The longest dotted suffix of ``name`` that ``table`` has."""
    parts = name.split(".")
    for n in range(len(parts), 0, -1):
        key = ".".join(parts[-n:])
        if key in table:
            return key
    return None


def leaf_rules(conf: Dict[str, Any], shapes) -> List[Tuple[str, float]]:
    """(kind, number) of each leaf of ``shapes``, in ``tree_flatten`` order:
    "normal" leaves are N(0, number^2), "scale" leaves 1 + number * N(0, 1),
    "const" leaves the number."""
    extra = file_rules(conf)
    out: List[Tuple[str, float]] = []
    for path, s in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        name = _path(path)
        mine, theirs = _match(name, BUILT_IN), _match(name, extra)
        if mine is not None and theirs is not None:
            raise ValueError(
                f"leaf {name!r} matches the built-in rule {mine!r} and rule "
                f"{theirs!r} of \"weight_rules\" of configuration "
                f"{conf['program']['name']!r}: a file may not override a "
                f"built-in rule")
        if mine is None and theirs is None:
            raise ValueError(
                f"no weight rule for leaf {name!r} {tuple(s.shape)}: neither "
                f"the built-in rules ({', '.join(BUILT_IN)}) nor "
                f"\"weight_rules\" in the file of configuration "
                f"{conf['program']['name']!r} name it")
        rule = BUILT_IN[mine] if mine is not None else extra[theirs]
        kind = rule["kind"]
        x = rule[_PARAM[kind]]
        if x == "fan_in":
            if len(s.shape) < 2:
                raise ValueError(f"leaf {name!r} {tuple(s.shape)} has no "
                                 f"fan-in for rule {mine or theirs!r}")
            x = float(s.shape[-2]) ** -0.5
        out.append((kind, float(x)))
    return out


def make_params(conf: Dict[str, Any], seed: int, dtype=jnp.bfloat16,
                shardings=None):
    """Weights of the configuration's program from the seed, by its rules:
    drawn in float32, rounded to ``dtype`` (the checkpoint's), held in
    float32 as the program's own init makes them, and laid out on the
    devices as ``shardings`` says (a tree like the weights'; default: one
    device)."""
    from repro.models import init_params
    cfg = program_config(conf)
    shapes = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten(shapes)
    rules = leaf_rules(conf, shapes)

    def build(key):
        keys = jax.random.split(key, len(flat))
        out = []
        for s, (kind, x), k in zip(flat, rules, keys):
            if kind == "const":
                v = jnp.full(s.shape, x, jnp.float32)
            else:
                z = jax.random.normal(k, s.shape, jnp.float32) * x
                v = 1.0 + z if kind == "scale" else z
            out.append(v.astype(dtype).astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    from .common import seed32
    key = jax.random.PRNGKey(seed32(seed, 1))
    return jax.jit(build, out_shardings=shardings)(key)
