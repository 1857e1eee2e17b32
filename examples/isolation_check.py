"""Data-plane isolation proof: tenant programs cannot talk across slices.

The paper's Kata/VPC guarantee, TPU-native: a tenant's compiled XLA program
may only issue collectives whose replica groups stay inside its mesh slice.
We split the host's devices into two tenant slices, compile a sharded
train-ish program per tenant, and run MeshRouter.validate_isolation over
the REAL optimized HLO — then show a cross-slice program being caught.
On the CPU the host is given 8 virtual devices; on a four-chip TPU host the
slices are 2 chips each.

    PYTHONPATH=src python examples/isolation_check.py
"""
import os

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import IsolationViolation, MeshRouter


def tenant_program(mesh):
    """A small sharded forward+psum program compiled for one slice."""
    def fn(x, w):
        h = jnp.tanh(x @ w)
        return h.sum()

    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 64), jnp.float32)
    with mesh:
        return jax.jit(
            jax.grad(fn),
            in_shardings=(NamedSharding(mesh, P("data", None)),
                          NamedSharding(mesh, P(None, "model"))),
        ).lower(x, w).compile()


def check_isolation(devices) -> None:
    """Validate two half-host tenant slices; raise unless a full-mesh
    program is rejected against one of them. Needs a multiple of 4
    devices (each slice is a (n/4, 2) data x model mesh)."""
    n = len(devices)
    if n % 4:
        raise ValueError(f"need a multiple of 4 devices, have {n}")
    devices = np.array(devices)
    half = n // 2
    slice_a = Mesh(devices[:half].reshape(half // 2, 2), ("data", "model"))
    slice_b = Mesh(devices[half:].reshape(half // 2, 2), ("data", "model"))
    full = Mesh(devices.reshape(2, half), ("data", "model"))

    for name, mesh in (("tenant-A", slice_a), ("tenant-B", slice_b)):
        compiled = tenant_program(mesh)
        order = [d.id for d in mesh.devices.flatten()]   # logical -> physical
        n_coll = MeshRouter.validate_isolation(compiled.as_text(), order,
                                               order)
        if n_coll == 0:
            raise AssertionError(f"{name}: program has no collectives")
        print(f"[{name}] slice devices {sorted(order)}: {n_coll} "
              f"collectives, all inside the slice OK")

    # a program spanning the full mesh must NOT validate against one slice
    compiled = tenant_program(full)
    order = [d.id for d in full.devices.flatten()]
    slice_a_ids = [d.id for d in slice_a.devices.flatten()]
    try:
        MeshRouter.validate_isolation(compiled.as_text(), slice_a_ids, order)
    except IsolationViolation as e:
        print(f"[full-mesh program vs tenant-A slice] correctly rejected: "
              f"{e}")
    else:
        raise AssertionError("cross-slice program passed validation")


def main():
    check_isolation(jax.devices())
    print("done")


if __name__ == "__main__":
    # virtual devices for a CPU run; read when jax first initialises
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    main()
