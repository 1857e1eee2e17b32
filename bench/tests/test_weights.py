"""The benchmark's weights: the two committed configurations make, from
the same seed, the weights they made before configuration files could
carry rules of their own; a configuration with sparse experts gets its
experts' and router's weights from its file's ``weight_rules``; and a
leaf with no rule, or a rule in both tables, is refused by name. No chip
is needed.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_weights.py
"""
import dataclasses
import json
import math
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench.harness.weights import make_params  # noqa: E402

# the program widths the serving and training tests cut the two cells to
# (bench/tests/test_checks.py, test_train_checks.py)
TINY = {"d_model": 64, "n_heads": 4, "n_kv_heads": 2, "head_dim": 16,
        "d_ff": 128, "vocab": 256, "n_layers": 2}
COMMITTED = [("qwen2-7b-d4", jnp.bfloat16, 2**31 + 12, {}),
             ("internvl2-2b-fsdp2x2", np.float32, 2**33 + 7,
              {"frontend_tokens": 4, "frontend_dim": 8})]

# Each leaf's float64 sum and sum of squares, as make_params made them
# from these seeds, dtypes and widths before the rules were read from a
# configuration's file (the rule on a leaf's name was then a function of
# the harness alone).
DIGESTS = {
    "qwen2-7b-d4": {
        "blocks.sub0.attn.wk.b": (-0.9987258911132812, 14.023140820034314),
        "blocks.sub0.attn.wk.w": (-1.5064561367034912, 61.878436196018185),
        "blocks.sub0.attn.wo.w": (-21.070573806762695, 129.7364159749601),
        "blocks.sub0.attn.wq.b": (-1.6435546875, 30.08170069567859),
        "blocks.sub0.attn.wq.w": (-0.9023491144180298, 123.13979673624273),
        "blocks.sub0.attn.wv.b": (2.261322021484375, 16.38460882101208),
        "blocks.sub0.attn.wv.w": (8.398325741291046, 63.53990014046539),
        "blocks.sub0.ffn.wg.w": (-14.847573399543762, 254.64493692852983),
        "blocks.sub0.ffn.wi.w": (-9.57620319724083, 252.79844365159298),
        "blocks.sub0.ffn.wo.w": (2.486744001507759, 128.66256278532956),
        "blocks.sub0.ln1": (128.00390625, 129.28990173339844),
        "blocks.sub0.ln2": (128.19140625, 129.64186096191406),
        "embed.table": (-176.93756717443466, 16077.955491988487),
        "final_norm": (64.17578125, 65.21098327636719),
        "lm_head.w": (-15.381969064474106, 255.52716935033982),
    },
    "internvl2-2b-fsdp2x2": {
        "blocks.sub0.attn.wk.w": (2.0946976691120653, 63.54501514156172),
        "blocks.sub0.attn.wo.w": (1.2175883218205854, 124.7427532595089),
        "blocks.sub0.attn.wq.w": (-6.465283923964307, 125.67287348274722),
        "blocks.sub0.attn.wv.w": (-3.336983804769261, 64.34781908537042),
        "blocks.sub0.ffn.wg.w": (31.261274935749952, 253.76981389319013),
        "blocks.sub0.ffn.wi.w": (22.29303286743925, 253.22119639237624),
        "blocks.sub0.ffn.wo.w": (-9.306371956330622, 127.4022091866732),
        "blocks.sub0.ln1": (127.82600104808807, 128.67211279626926),
        "blocks.sub0.ln2": (128.16133552789688, 129.49435813236988),
        "embed.table": (-121.08003942579671, 16136.862953152586),
        "final_norm": (62.54276257753372, 61.764541423009106),
        "frontend_proj.w": (-0.42750065377913415, 69.2538300727881),
        "lm_head.w": (31.35521042545861, 255.5221365879425),
    },
}


def named(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {".".join(str(k.key) for k in path): x for path, x in flat}


def leaves(params):
    return {k: np.asarray(x, np.float64) for k, x in named(params).items()}


def load_conf(path: str):
    return json.loads((ROOT / path).read_text())


@pytest.mark.parametrize("name,dtype,seed,extra", COMMITTED,
                         ids=[c[0] for c in COMMITTED])
def test_committed_configuration_makes_the_same_weights(name, dtype, seed,
                                                         extra):
    conf = load_conf(f"bench/configs/{name}.json")
    conf["program"].update(TINY, **extra)
    got = {k: (float(a.sum()), float((a * a).sum()))
           for k, a in leaves(make_params(conf, seed, dtype=dtype)).items()}
    assert got == DIGESTS[name]


def moe_conf(arch: str):
    """tiny-moe's configuration file, or its rules over the program's
    reduced size of another sparse-expert architecture."""
    conf = load_conf("bench/tests/data/tiny-moe.json")
    if arch != "tiny-moe":
        from repro.configs import REGISTRY, reduced
        conf["program"] = dataclasses.asdict(reduced(REGISTRY[arch]))
    return conf


def expected(name: str, shape):
    """(mean, std) of a leaf by its rule, written out here: the built-in
    rules and tiny-moe's file, whose experts and router are normal with
    std 1/sqrt(fan_in)."""
    last = name.rsplit(".", 1)[-1]
    if name == "embed.table":
        return 0.0, 1.0
    if last in ("ln1", "ln2", "final_norm"):
        return 1.0, 0.1
    if last == "b":
        return 0.0, 0.5
    assert last in ("w", "router", "w1", "wg", "w2"), name
    return 0.0, shape[-2] ** -0.5


MOE = ["tiny-moe", "olmoe-1b-7b", "qwen3-moe-30b-a3b"]


@pytest.mark.parametrize("arch", MOE)
def test_sparse_experts_get_their_weights_from_the_file(arch):
    from repro.models import init_params
    from bench.harness.weights import program_config
    conf = moe_conf(arch)
    cfg = program_config(conf)
    shapes = named(jax.eval_shape(lambda k: init_params(k, cfg),
                                  jax.random.PRNGKey(0)))
    got = leaves(make_params(conf, 2**40 + 3))
    assert got.keys() == shapes.keys()
    assert {"router", "w1", "wg", "w2"} <= {k.rsplit(".", 1)[-1]
                                            for k in got}
    for name, a in got.items():
        assert a.shape == shapes[name].shape, name
        mean, std = expected(name, a.shape)
        n = a.size
        # sampling error of a mean and of a standard deviation, at five
        # sigma, and bfloat16's rounding of each value
        assert abs(a.mean() - mean) <= 5 * std / math.sqrt(n) + 2**-8 * (
            abs(mean) + std), name
        assert abs(a.std() / std - 1) <= 5 / math.sqrt(2 * n) + 2**-7, name


def test_const_rule_fills_its_value():
    conf = moe_conf("tiny-moe")
    conf["weight_rules"]["router"] = {"kind": "const", "value": 0.25}
    got = leaves(make_params(conf, 7))
    router = [a for k, a in got.items() if k.endswith(".router")]
    assert router and all((a == 0.25).all() for a in router)


def test_leaf_without_a_rule_is_named():
    conf = moe_conf("tiny-moe")
    del conf["weight_rules"]
    with pytest.raises(ValueError, match=r"'blocks\.sub0\.ffn\.router'.*"
                       r"configuration 'tiny-moe'"):
        make_params(conf, 7)


@pytest.mark.parametrize("name,rule", [
    ("w", {"kind": "normal", "std": 0.02}),          # a built-in name
    ("attn.wq.w", {"kind": "normal", "std": 0.02}),  # overrides "w" there
    ("ffn.wg", {"kind": "scale", "std": 0.1}),       # "wg" twice: longest
], ids=["built-in-name", "built-in-leaf", "longest-suffix"])
def test_rule_in_both_tables_or_by_its_longest_suffix(name, rule):
    conf = moe_conf("tiny-moe")
    conf["weight_rules"][name] = rule
    if name == "ffn.wg":           # file against file: the longer wins
        wg = [a for k, a in leaves(make_params(conf, 7)).items()
              if k.endswith(".ffn.wg")]
        assert wg and all(abs(a.mean() - 1) < 0.01 for a in wg)
        return
    with pytest.raises(ValueError, match="built-in"):
        make_params(conf, 7)


@pytest.mark.parametrize("rule", [
    {"kind": "uniform", "std": 0.1},
    {"kind": "normal", "value": 0.1},
    {"kind": "normal", "std": "fan_out"},
    {"kind": "scale", "std": 0.1, "value": 1.0},
])
def test_malformed_rule_is_refused(rule):
    conf = moe_conf("tiny-moe")
    conf["weight_rules"]["router"] = rule
    with pytest.raises(ValueError, match="'router'"):
        make_params(conf, 7)


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))
