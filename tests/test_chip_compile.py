"""The serving path's Pallas kernels compile for a TPU v5e at qwen2-7b widths.

Interpret mode (every other kernel test) cannot see what the chip's compiler
refuses: these tests compile the kernels with ``interpret=False`` for a
described ``v5e:2x2`` topology, which needs the TPU compiler but no chip.
The topology is described only inside fixtures: one process at a time may
load the TPU library, so nothing here may touch it while the module is
imported or collected.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_decode.kernel import flash_decode_pallas

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
