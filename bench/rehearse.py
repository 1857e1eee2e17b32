#!/usr/bin/env python3
"""Compile a cell's programs for a described TPU v5e, without the chip,
and print what each holds in device memory.

    JAX_PLATFORMS=cpu python bench/rehearse.py --workload qwen2-7b-d4.chat [--ks 1,8]
    JAX_PLATFORMS=cpu python bench/rehearse.py --workload internvl2-2b-fsdp2x2.train4k

For a serving cell it compiles the engine's step program, its admission
program for every (rows, length bucket) the cell's traffic can produce,
and the reference's program, each with the Pallas kernels as the chip runs
them. For a training cell it compiles the program's train step over the
cell's mesh of described chips, the weights' program, and the reference's
step and its float8 control over the same chips, and lists the step's
collectives. Nothing runs; the compiler refuses what the chip would
refuse, and ``memory_analysis()`` gives each program's arguments and
temporaries on one chip.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def _as_chip_kernels():
    """Lower the Pallas kernels as on the chip, though the backend here is
    the CPU's."""
    from repro.kernels.flash_attention import ops as fa
    from repro.kernels.flash_decode import ops as fd
    fa.interpret_mode = fd.interpret_mode = lambda impl: False
    fa._auto_impl = lambda: "pallas"


def _mem(compiled) -> str:
    m = compiled.memory_analysis()
    return (f"args {m.argument_size_in_bytes / 1e9:.3f} GB, temps "
            f"{m.temp_size_in_bytes / 1e9:.3f} GB, out "
            f"{m.output_size_in_bytes / 1e9:.3f} GB, alias "
            f"{m.alias_size_in_bytes / 1e9:.3f} GB")


def rehearse_train(cell, conf, mix, topo) -> None:
    import collections
    import re
    from bench.harness import train
    from bench.harness.common import reference_conf
    from bench.harness.weights import make_params
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = topo.devices[:cell["chips"]]
    prog = train.build(conf, mix, devices)
    cfg, sh = prog.cfg, prog.shardings
    shapes = jax.eval_shape(lambda: make_params(conf, 0, np.float32))
    put = lambda t, s: jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=s)  # noqa
    params = jax.tree.map(put, shapes, sh["params"])
    from repro.training import make_opt_state
    opt = jax.tree.map(put, jax.eval_shape(make_opt_state, shapes), sh["opt"])
    batch = jax.tree.map(put, jax.eval_shape(
        lambda: train.batch_of(mix, cfg, 0, 0)), sh["batch"])
    with prog.context():
        t = time.monotonic()
        c = prog.step.lower(params, opt, batch).compile()
    text = c.as_text()
    ops = collections.Counter(re.findall(
        r"= \S+ ((?:all-gather|all-reduce|reduce-scatter|"
        r"collective-permute|all-to-all)[\w-]*)\(", text))
    print(f"train step: compiled in {time.monotonic() - t:.1f} s; {_mem(c)}; "
          f"collectives {dict(ops)}", flush=True)
    t = time.monotonic()
    c = jax.jit(lambda: make_params(conf, 0, np.float32,
                                    shardings=sh["params"])).lower().compile()
    print(f"weights: {time.monotonic() - t:.1f} s; {_mem(c)}", flush=True)

    ref = train.load_reference(conf["reference"])
    mesh = Mesh(np.asarray(devices), (ref.AXIS,))
    psh = ref.shardings(mesh, shapes)
    rp = jax.tree.map(put, shapes, psh)
    bsh = NamedSharding(mesh, P(ref.AXIS))
    rb = jax.tree.map(lambda t: put(t, bsh), jax.eval_shape(
        lambda: train.batch_of(mix, cfg, 0, 0)))
    step_no = jax.ShapeDtypeStruct((), jnp.int32)
    with jax.default_matmul_precision("highest"):
        for low in (False, True):
            t = time.monotonic()
            c = ref.make_step(reference_conf(conf), mesh, low).lower(
                rp, rp, rp, step_no, rb).compile()
            print(f"reference{' float8 control' if low else ''}: "
                  f"{time.monotonic() - t:.1f} s; {_mem(c)}", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--ks", default="", help="rows to compile (default all)")
    args = ap.parse_args()
    from bench.run import find_cell, load_benchmark
    from bench.harness.common import reference_conf
    from bench.harness.serve import _gap_fns
    from bench.harness.traffic import serve_schedule
    from bench.harness.weights import program_config
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    _as_chip_kernels()
    cell, conf, mix = find_cell(load_benchmark(), args.workload)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    if mix["driver"] == "train":
        rehearse_train(cell, conf, mix, topo)
        return 0
    cfg = program_config(conf)
    sv = conf["serve"]
    slots, max_len = sv["slots"], sv["max_len"]
    chip = SingleDeviceSharding(topo.devices[0])

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)

    from repro.models import init_cache, init_params
    from repro.serving.engine import GenerationEngine, _compiled
    params = jax.tree.map(sds, jax.eval_shape(
        lambda k: init_params(k, cfg), jax.random.PRNGKey(0)))
    cache = jax.tree.map(sds, jax.eval_shape(
        lambda: init_cache(cfg, slots, max_len, enc_len=max_len)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=chip)  # noqa
    state = (cache, i32(slots), i32(slots),
             jax.ShapeDtypeStruct((slots,), bool, sharding=chip),
             i32(slots, 1))
    admit, step = _compiled(cfg, max_len, jnp.bfloat16)

    t = time.monotonic()
    c = step.lower(params, *state).compile()
    print(f"step: compiled in {time.monotonic() - t:.1f} s; {_mem(c)}; "
          f"pallas kernel: {'tpu_custom_call' in c.as_text()}", flush=True)

    sched = serve_schedule(mix, 51.0, cfg.vocab, np.random.default_rng(0))
    probe = object.__new__(GenerationEngine)
    probe._exact_buckets, probe.max_len = False, max_len
    buckets = sorted({probe._bucket(len(r.prompt)) for r in sched})
    ks = [int(k) for k in args.ks.split(",")] if args.ks else \
        list(range(1, slots + 1))
    print(f"buckets {buckets}, rows {ks}: {len(buckets) * len(ks)} "
          f"admission programs", flush=True)
    for b in buckets:
        for k in ks:
            t = time.monotonic()
            c = admit.lower(params, *state, i32(k, b), i32(k), i32(k),
                            i32(k)).compile()
            print(f"admit k={k} bucket={b}: {time.monotonic() - t:.1f} s; "
                  f"{_mem(c)}", flush=True)

    served_gap, control_gap = _gap_fns(reference_conf(conf))
    S = int(mix["prompt_len"]["max"]) + int(mix["output_len"]["max"])
    R = int(mix["output_len"]["max"])
    with jax.default_matmul_precision("highest"):
        t = time.monotonic()
        c = served_gap.lower(params, i32(S), i32(R), i32(R)).compile()
        print(f"reference: {time.monotonic() - t:.1f} s; {_mem(c)}",
              flush=True)
        c = control_gap.lower(params, i32(S), i32(R)).compile()
        print(f"reference + float8 control: {_mem(c)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
