#!/usr/bin/env python3
"""Bring-up smoke run of the multi-tenant serving path on one TPU v5e.

One chip (no arguments): qwen2-7b at its published widths, cut to 4 layers,
with random weights from ``--seed``, served through the normal entry points:
tenant control planes -> ``ServingFleet`` (2 replicas as WorkUnits) ->
``GenerationEngine`` -> ``repro.models`` -> Pallas kernels. It prints the
device, compile seconds (set-up), per-tenant requests/tokens/TTFT, a decode
step time, peak device memory, whether the served decode step holds the
Pallas kernels (``tpu_custom_call``), and a Pallas-vs-XLA logits check on
the same chip. Step times and TTFTs are bring-up readings, not benchmarks.

``--chips 4`` (one four-chip host) runs only what exists across chips: a
tenant's sharded train step (internvl2-2b widths, 1 layer) on a 2x2
("data", "model") mesh against the same step on one chip, and tenant
mesh-slice isolation over two 2-chip slices.

Any failure exits non-zero. It also exits non-zero, printing no result,
when JAX finds no TPU. On success the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

    python chip_smoke.py [--chips 4] [--seed 0]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.models import decode_step, init_cache, init_params, prefill  # noqa: E402

SLOTS = 8
MAX_LEN = 1024
SERVE_LAYERS = 4          # one layer_pattern period ("g") is 1; 28 in full
PROMPT_LENS = (9, 16)     # every prompt pads to the one 16-token bucket
MAX_NEW = 16
REQUESTS = 16             # 8 per replica: each admits one full batch
TENANTS = {"tenant-a": 1, "tenant-b": 2}     # WRR weights
TIMED_STEPS = 8
# Pallas vs XLA logits: both paths take bf16 operands with f32
# accumulation and differ in summation order and the bf16 rounding of the
# softmax weights; allow 8 bf16 ulps (2^-8 each) of the largest logit.
LOGIT_TOL = 8 * 2.0 ** -8

TRAIN_LAYERS = 1
TRAIN_BATCH, TRAIN_SEQ = 8, 512
# same bounds as tests/test_sharded_exec.py
LOSS_TOL, PARAM_TOL = 5e-3, 5e-2

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Counts XLA backend compiles and their seconds, process-wide."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == _BACKEND_COMPILE:
            self.count += 1
            self.seconds += duration

    def mark(self):
        return self.count, self.seconds

    def since(self, mark):
        return self.count - mark[0], self.seconds - mark[1]


def log(msg: str) -> None:
    print(msg, flush=True)


def serve_config():
    return dataclasses.replace(get_config("qwen2-7b"), n_layers=SERVE_LAYERS)


def make_params(cfg, seed: int):
    return jax.jit(init_params, static_argnums=1)(jax.random.PRNGKey(seed),
                                                  cfg)


def _prompts(cfg, rng, n):
    return [rng.integers(0, cfg.vocab, int(rng.integers(*PROMPT_LENS) + 1))
            for _ in range(n)]


def warm_engine(cfg, params, clock: CompileClock, rng) -> None:
    """Compile the engine's step and admit programs once, report them as
    set-up, check the served step holds the Pallas kernels, and time a few
    decode steps over 8 active slots."""
    from repro.serving import GenerationEngine, Request

    eng = GenerationEngine(cfg, params, slots=SLOTS, max_len=MAX_LEN)
    # the engine's own jitted step, lowered for its current state: the
    # program every replica runs (compiled once, shared through the cache)
    state = (eng.params, eng.cache, eng._slot_lengths, eng._budget,
             eng._active, eng._last)
    t0 = time.monotonic()
    step_text = eng._step_fn.lower(*state).compile().as_text()
    log(f"setup: step program compile {time.monotonic() - t0:.3f} s")
    has_kernel = "tpu_custom_call" in step_text
    log(f"decode step HLO contains tpu_custom_call: {has_kernel}")
    if not has_kernel:
        raise AssertionError("served decode step holds no Pallas kernel")

    reqs = [Request(i, p, MAX_NEW)
            for i, p in enumerate(_prompts(cfg, rng, SLOTS))]
    mark, t0 = clock.mark(), time.monotonic()
    eng.admit_many(reqs)
    n, secs = clock.since(mark)
    log(f"setup: admit program first call {time.monotonic() - t0:.3f} s "
        f"({n} backend compiles, {secs:.3f} s compiling)")

    eng.step()                                   # first step after admit
    t0 = time.monotonic()
    for _ in range(TIMED_STEPS):
        eng.step()
    jax.block_until_ready(eng.cache)
    dt = (time.monotonic() - t0) / TIMED_STEPS
    log(f"bring-up reading (not a benchmark): decode step {dt * 1e3:.3f} ms "
        f"over {TIMED_STEPS} steps, {eng.active_slots()} of {SLOTS} slots "
        f"active, host sync per step included")


def serve(cfg, params, clock: CompileClock, rng) -> None:
    """Two tenants' requests through the control plane and ServingFleet."""
    from repro.core import VirtualClusterFramework
    from repro.serving import GenerationEngine, ServingFleet

    # replicas start at 0 and are resized to 2 once every request is queued,
    # so each replica admits one full 8-prompt batch: the same shapes the
    # warm-up compiled, hence no compile inside the serving window
    fleet = ServingFleet(
        lambda: GenerationEngine(cfg, params, slots=SLOTS, max_len=MAX_LEN),
        replicas=0, scan_interval=0.1)
    fw = VirtualClusterFramework(num_nodes=2, scan_interval=0.0,
                                 heartbeat_interval=3600)
    fleet.attach(fw)
    with fw:
        for name, weight in TENANTS.items():
            fleet.register_tenant(fw.add_tenant(name, weight=weight))
        names = list(TENANTS)
        tenant_of = {}
        for i, prompt in enumerate(_prompts(cfg, rng, REQUESTS)):
            tenant = names[i % len(names)]
            tenant_of[fleet.submit(tenant, prompt, MAX_NEW)] = tenant
        mark, t0 = clock.mark(), time.monotonic()
        fleet.resize(2)
        fleet.wait_replicas(2, timeout=300)
        done = fleet.wait_completed(REQUESTS, timeout=600)
        wall = time.monotonic() - t0
        n_compiles, _ = clock.since(mark)
        units = fw.super_api.list("WorkUnit", fleet.namespace)
        placed = {u.metadata.name: (u.status.node,
                                    fleet.replica(u.metadata.key)
                                    .engine.admitted)
                  for u in units}

    log(f"fleet: unit -> (node, requests admitted) {placed}")
    by_tenant = defaultdict(list)
    for uid, req in done.items():
        if req.tenant != tenant_of[uid]:
            raise AssertionError(f"request {uid} served for {req.tenant}")
        if len(req.tokens) != MAX_NEW:
            raise AssertionError(f"request {uid}: {len(req.tokens)} tokens, "
                                 f"expected {MAX_NEW}")
        if not all(0 <= t < cfg.vocab for t in req.tokens):
            raise AssertionError(f"request {uid}: token outside vocab")
        by_tenant[req.tenant].append(req)
    for tenant in names:
        reqs = by_tenant[tenant]
        if not reqs:
            raise AssertionError(f"{tenant}: no request served")
        ttft = sorted(r.first_token_at - r.submitted_at for r in reqs)
        log(f"tenant {tenant} (weight {TENANTS[tenant]}): {len(reqs)} "
            f"requests, {sum(len(r.tokens) for r in reqs)} tokens, TTFT "
            f"min {ttft[0] * 1e3:.3f} ms max {ttft[-1] * 1e3:.3f} ms "
            f"(from submit; includes replica start-up)")
    log(f"served {len(done)} requests in {wall:.3f} s wall; backend "
        f"compiles inside the serving window: {n_compiles}")


def logits_check(cfg, params, rng) -> None:
    """prefill + one decode_step, Pallas (default on TPU) vs impl="xla"."""
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (SLOTS, PROMPT_LENS[1])),
                       jnp.int32)
    nxt = jnp.asarray(rng.integers(0, cfg.vocab, (SLOTS, 1)), jnp.int32)

    def run(impl):
        def f(params, toks, nxt):
            cache = init_cache(cfg, SLOTS, MAX_LEN)
            l0, cache, lengths = prefill(params, cfg, toks, cache, impl=impl)
            l1, _, _ = decode_step(params, cfg, nxt, cache, lengths + 1,
                                   impl=impl)
            return l0[..., :cfg.vocab], l1[..., :cfg.vocab]
        return jax.jit(f)(params, toks, nxt)

    ok = True
    for name, pal, ref in zip(("prefill", "decode"), run(None), run("xla")):
        pal, ref = np.asarray(pal), np.asarray(ref)
        scale = float(np.abs(ref).max())
        err = float(np.abs(pal - ref).max())
        good = bool(np.isfinite(pal).all()) and err <= LOGIT_TOL * scale
        ok &= good
        log(f"logits {name} {pal.shape}: pallas vs xla max |diff| {err:.6g}"
            f" vs max |logit| {scale:.6g} (tol {LOGIT_TOL:.6g} x max): "
            f"{'OK' if good else 'FAIL'}")
    if not ok:
        raise AssertionError("Pallas logits disagree with the XLA path")


def one_chip(seed: int) -> None:
    clock = CompileClock()
    rng = np.random.default_rng(seed)
    cfg = serve_config()
    log(f"model: {cfg.name} d_model {cfg.d_model} heads {cfg.n_heads}/"
        f"{cfg.n_kv_heads} head_dim {cfg.head_dim} d_ff {cfg.d_ff} vocab "
        f"{cfg.vocab}, {cfg.n_layers} layers (of 28), slots {SLOTS}, "
        f"max_len {MAX_LEN}, bf16 compute, f32 params")
    t0 = time.monotonic()
    params = make_params(cfg, seed)
    jax.block_until_ready(params)
    n = sum(x.size for x in jax.tree.leaves(params))
    log(f"setup: params {n / 1e9:.3f} B ({n * 4 / 1e9:.3f} GB f32) made on "
        f"device in {time.monotonic() - t0:.3f} s")
    warm_engine(cfg, params, clock, rng)
    serve(cfg, params, clock, rng)
    logits_check(cfg, params, rng)
    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')}")


def sharded_train(devices, seed: int) -> None:
    """One train step sharded over a 2x2 mesh vs the same step on
    devices[0]: loss and updated params must agree."""
    from repro.launch.mesh import make_test_mesh
    from repro.models.config import ShapeConfig
    from repro.sharding.api import use_rules
    from repro.sharding.planner import plan_for, train_shardings
    from repro.training import OptimizerConfig, make_opt_state, \
        make_train_step

    cfg = dataclasses.replace(get_config("internvl2-2b"),
                              n_layers=TRAIN_LAYERS)
    key = jax.random.PRNGKey(seed)
    k_tok, k_patch = jax.random.split(jax.random.fold_in(key, 1))
    batch = {
        "tokens": jax.random.randint(k_tok, (TRAIN_BATCH, TRAIN_SEQ), 0,
                                     cfg.vocab),
        "mask": jnp.ones((TRAIN_BATCH, TRAIN_SEQ), jnp.float32),
        "patches": jax.random.normal(
            k_patch, (TRAIN_BATCH, cfg.frontend_tokens, cfg.frontend_dim)),
    }
    log(f"train model: {cfg.name} d_model {cfg.d_model} heads "
        f"{cfg.n_heads}/{cfg.n_kv_heads} d_ff {cfg.d_ff} vocab {cfg.vocab}, "
        f"{cfg.n_layers} layer, batch {TRAIN_BATCH} x seq {TRAIN_SEQ}")
    opt_cfg = OptimizerConfig()

    # reference: one chip; inputs donated so params+opt are held once
    params = make_params(cfg, seed)
    t0 = time.monotonic()
    step_ref = jax.jit(make_train_step(cfg, opt_cfg), donate_argnums=(0, 1))
    p_ref, _, m_ref = step_ref(params, make_opt_state(params), batch)
    p_ref = jax.device_get(p_ref)
    loss_ref = float(m_ref["loss"])
    log(f"one-chip step (compile + run) {time.monotonic() - t0:.3f} s, "
        f"loss {loss_ref:.6f}")

    mesh = make_test_mesh((2, 2))
    shape = ShapeConfig("smoke", TRAIN_SEQ, TRAIN_BATCH, "train")
    plan = plan_for(cfg, shape, mesh)
    sh = train_shardings(plan, cfg)
    t0 = time.monotonic()
    with use_rules(plan.rules), mesh:
        params = jax.jit(init_params, static_argnums=1,
                         out_shardings=sh["params"])(key, cfg)
        opt = jax.jit(make_opt_state, out_shardings=sh["opt"])(params)
        step = make_train_step(cfg, opt_cfg, mesh=mesh)
        bs = {k: sh["batch"].get(k, sh["replicated"]) for k in batch}
        fn = jax.jit(step, in_shardings=(sh["params"], sh["opt"], bs),
                     donate_argnums=(0, 1))
        p_sh, _, m_sh = fn(params, opt, batch)
        loss_sh = float(m_sh["loss"])
    log(f"2x2-mesh step (compile + run) {time.monotonic() - t0:.3f} s, "
        f"loss {loss_sh:.6f}")

    leaves = jax.tree.leaves(p_sh)
    spanned = set().union(*(x.sharding.device_set for x in leaves))
    split = sum(not x.sharding.is_fully_replicated for x in leaves)
    log(f"sharded params span {len(spanned)} devices; {split} of "
        f"{len(leaves)} leaves split across devices")
    if spanned != set(devices) or not split:
        raise AssertionError("sharded params do not span the mesh")
    err = max(float(np.max(np.abs(np.asarray(a, np.float32)
                                  - np.asarray(b, np.float32))))
              for a, b in zip(jax.tree.leaves(p_ref), leaves))
    dloss = abs(loss_ref - loss_sh)
    log(f"sharded vs one-chip: |loss diff| {dloss:.6g} (tol {LOSS_TOL}), "
        f"max param diff {err:.6g} (tol {PARAM_TOL})")
    if dloss >= LOSS_TOL or err >= PARAM_TOL:
        raise AssertionError("sharded train step disagrees with one chip")


def four_chips(devices, seed: int) -> None:
    sys.path.insert(0, str(ROOT / "examples"))
    from isolation_check import check_isolation

    sharded_train(devices, seed)
    check_isolation(devices)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded-train and isolation checks")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              f"device(s)", file=sys.stderr)
        return 2
    log(f"compile cache: {enable_compile_cache()}")
    log(f"device: {dev.device_kind} x {len(devices)}")
    if args.chips == 4:
        four_chips(devices, args.seed)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
