"""The Pallas kernels compile for a TPU v5e at the widths they run at:
serving's at qwen2-7b widths, and training's attention (forward and
backward, per chip under ``shard_map``) at internvl2-2b's train4k shapes.

Interpret mode (every other kernel test) cannot see what the chip's compiler
refuses: these tests compile the kernels with ``interpret=False`` for a
described ``v5e:2x2`` topology, which needs the TPU compiler but no chip.
The topology is described only inside fixtures: one process at a time may
load the TPU library, so nothing here may touch it while the module is
imported or collected, and every test that compiles for the described chip
lives in this file (the train step's scope labels too).
"""
import os

import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding

from repro.kernels.flash_attention import ops as attn_ops
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_decode.kernel import flash_decode_pallas

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

# qwen2-7b: 28 q heads over 4 KV heads (G=7), head_dim 128; engine slots and
# max_len as in chip_smoke.py
SLOTS, MAX_LEN, KV, G, D = 8, 1024, 4, 7, 128


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep the cache off around these."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(one_chip, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def test_flash_decode_compiles_for_v5e(one_chip):
    q = _shape(one_chip, (SLOTS, 1, KV * G, D))
    cache = _shape(one_chip, (SLOTS, MAX_LEN, KV, D))
    lengths = _shape(one_chip, (SLOTS,), jnp.int32)
    compiled = jax.jit(
        lambda q, k, v, n: flash_decode_pallas(q, k, v, n, interpret=False)
    ).lower(q, cache, cache, lengths).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("seq", [16, 128])
def test_flash_attention_compiles_for_v5e(one_chip, seq):
    q = _shape(one_chip, (1, seq, KV * G, D))
    kv = _shape(one_chip, (1, seq, KV, D))
    compiled = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, interpret=False)
    ).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture
def chip_kernels(monkeypatch):
    """``ops.mha`` as on the chip: auto picks Pallas, compiled for real
    (the backend here is still the CPU's)."""
    monkeypatch.setattr(attn_ops, "_auto_impl", lambda: "pallas")
    monkeypatch.setattr(attn_ops, "interpret_mode", lambda impl: False)


def _train_plan(topo, cfg, seq, batch):
    from repro.models.config import ShapeConfig
    from repro.sharding.planner import plan_for
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    return plan_for(cfg, ShapeConfig(cfg.name, seq, batch, "train"), mesh)


def test_sharded_train_attention_compiles_for_v5e(topo, chip_kernels):
    """train4k's attention, forward and backward, as the train step calls
    it: global B 4, S 4096, H 16, KV 8, D 128 over data x model. Each chip
    runs the kernels on its own batch rows and heads, and the wrapper adds
    no collective around q, k or v."""
    from repro.configs import get_config
    from repro.sharding.api import use_rules
    cfg = get_config("internvl2-2b")
    B, S, H, KV, D = 4, 4096, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    plan = _train_plan(topo, cfg, S, B)
    q_sh = plan.named("batch", "attn_seq", "heads", None)
    kv_sh = plan.named("batch", "kv_seq", "kv_heads", None)
    q = jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16, sharding=q_sh)
    kv = jax.ShapeDtypeStruct((B, S, KV, D), jnp.bfloat16, sharding=kv_sh)

    def fwd_bwd(q, k, v, dout):
        out, vjp = jax.vjp(lambda q, k, v: attn_ops.mha(q, k, v), q, k, v)
        return (out,) + vjp(dout)

    with use_rules(plan.rules):
        text = jax.jit(fwd_bwd).lower(q, kv, kv, q).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') >= 3
    added = re.findall(r"= \S+ (all-gather|all-to-all|all-reduce|"
                       r"collective-permute|reduce-scatter)", text)
    assert not added, added


def test_train_step_attention_kernels_carry_their_scope(topo, chip_kernels):
    """The train step compiled for the described 2x2 mesh (internvl2-2b
    widths, 2 layers): every Pallas call is labelled ``attn/core`` by the
    benchmark's reader, in the forward, remat's recompute and the backward,
    so ``attn_core_share.train`` reads them."""
    import dataclasses
    from bench.harness import scopes
    from bench.harness.trace import parse_op
    from repro.configs import get_config
    from repro.models import init_params
    from repro.sharding.api import use_rules
    from repro.sharding.planner import train_shardings
    from repro.training import (OptimizerConfig, make_opt_state,
                                make_train_step)
    cfg = dataclasses.replace(get_config("internvl2-2b"), n_layers=2)
    B, S = 4, 1024
    plan = _train_plan(topo, cfg, S, B)
    sh = train_shardings(plan, cfg)

    def put(tree, shardings):
        return jax.tree.map(lambda t, s: jax.ShapeDtypeStruct(
            t.shape, t.dtype, sharding=s), tree, shardings)

    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    opt = put(jax.eval_shape(make_opt_state, params), sh["opt"])
    params = put(params, sh["params"])
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32,
                                            sharding=sh["batch"]["tokens"]),
             "mask": jax.ShapeDtypeStruct((B, S), jnp.float32,
                                          sharding=sh["batch"]["mask"]),
             "patches": jax.ShapeDtypeStruct(
                 (B, cfg.frontend_tokens, cfg.frontend_dim), jnp.float32,
                 sharding=sh["batch"]["patches"])}
    step = make_train_step(cfg, OptimizerConfig(), mesh=plan.rules.mesh)
    with use_rules(plan.rules):
        text = jax.jit(step).lower(params, opt, batch).compile().as_text()
    labels = scopes.parse_hlo_text(text).labels()
    calls = [parse_op(line.strip().removeprefix("ROOT "))[0]
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    seen = {labels[c] for c in calls}
    assert seen == {"fwd/attn/core", "remat/attn/core", "bwd/attn/core"}, \
        seen
