"""Driver of training cells: one run of a tenant's training job, the
program's own train step sharded over the cell's mesh, fed from the seed.

Set-up builds one object, the compiled step with its state, and drives it
through its first steps, through the same call and the same feed as the
window, on batches that all differ. It reads the loss of each, the first
gradient as the optimizer took it (from Adam's first moment after one
step) and the change of the parameters after the first steps; then it
hands that same object to the window. Once the window has closed and the
program's state is freed, the plain reference (``bench/reference``) takes
the same first steps from the same weights and batches, and the run
compares the two.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import importlib.util
import math
import queue
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from .common import (BENCH, OUT, Check, CompileClock, device_info, log, now,
                     reference_conf)
from .traffic import train_batch
from .weights import make_params, program_config


@functools.lru_cache(maxsize=None)
def load_reference(name: str):
    path = BENCH / "reference" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_ref_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------- readings

@dataclass
class Readings:
    """What the first steps leave: the loss of each step, the norm of the
    first gradient per leaf (a layer-stacked leaf per layer), and the norm
    of each leaf's change over the first steps."""
    losses: List[float]
    grad: Dict[str, np.ndarray]
    change: Dict[str, np.ndarray]


def _host(tree) -> Dict[str, np.ndarray]:
    import jax
    return {k: np.asarray(v, np.float64)
            for k, v in jax.device_get(tree).items()}


def compare(got: Readings, ref: Readings, floor: float = 1e-3
            ) -> Dict[str, float]:
    """The numbers a training cell compares, each the worst over its
    steps or leaves:

    - ``loss_gap``: |loss - reference loss| of each first step;
    - ``grad_norm_gap``: the gap between a leaf's first-gradient norm and
      the reference's, over the reference's norm of that leaf or of the
      median leaf, whichever is larger;
    - ``update_norm_gap``: the same for each leaf's change over the first
      steps, over the leaves whose reference gradient is at least
      ``floor`` of the median leaf's (the others move by round-off
      alone)."""
    g_ref = np.concatenate([ref.grad[k] for k in sorted(ref.grad)])
    g_med = float(np.median(g_ref))
    c_ref = np.concatenate([ref.change[k] for k in sorted(ref.change)])
    c_med = float(np.median(c_ref))

    def worst(a: Dict[str, np.ndarray], b: Dict[str, np.ndarray], med: float,
              keep: Callable[[str], np.ndarray]) -> float:
        out = 0.0
        for k in sorted(b):
            sel = keep(k)
            if not sel.any():
                continue
            gap = np.abs(a[k] - b[k]) / np.maximum(b[k], med)
            out = max(out, float(np.max(np.where(sel, gap, 0.0))))
        return out

    moved = lambda k: ref.grad[k] >= floor * g_med   # noqa: E731
    everything = lambda k: np.ones_like(ref.grad[k], bool)   # noqa: E731
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(got.losses, ref.losses)),
        "grad_norm_gap": worst(got.grad, ref.grad, g_med, everything),
        "update_norm_gap": worst(got.change, ref.change, c_med, moved),
    }


# -------------------------------------------------------------- the feed

class Feed:
    """Makes batch i+1 on the host and puts it on the devices while step i
    runs: one batch ahead, on a thread of its own."""

    def __init__(self, make: Callable[[int], Dict[str, np.ndarray]],
                 shardings: Dict[str, Any], first: int = 0):
        self._make, self._sh = make, shardings
        self._q: "queue.Queue" = queue.Queue(maxsize=1)
        self._stop = threading.Event()
        self._next = first
        self._thread = threading.Thread(target=self._run, name="bench-feed",
                                        daemon=True)
        self._thread.start()

    def _run(self) -> None:
        import jax
        try:
            while not self._stop.is_set():
                batch = self._make(self._next)
                with jax.profiler.TraceAnnotation("bench.feed",
                                                  step=self._next):
                    dev = {k: jax.device_put(v, self._sh[k])
                           for k, v in batch.items()}
                self._next += 1
                while not self._stop.is_set():
                    try:
                        self._q.put(dev, timeout=0.1)
                        break
                    except queue.Full:
                        pass
        except BaseException as e:      # handed to the consumer
            self._q.put(e)

    def get(self) -> Dict[str, Any]:
        item = self._q.get()
        if isinstance(item, BaseException):
            raise RuntimeError("feed failed") from item
        return item

    def stop(self) -> None:
        self._stop.set()
        with contextlib.suppress(queue.Empty):
            while True:
                self._q.get_nowait()
        self._thread.join(10)


# ------------------------------------------------------------- the program

@dataclass
class Program:
    """The program's compiled train step over the cell's mesh, with its
    state."""
    cfg: Any
    mesh: Any
    rules: Any
    shardings: Dict[str, Any]
    step: Callable
    params: Any = None
    opt: Any = None

    def context(self):
        from repro.sharding.api import use_rules
        stack = contextlib.ExitStack()
        stack.enter_context(use_rules(self.rules))
        stack.enter_context(self.mesh)
        return stack


def optimizer(conf: Dict[str, Any]):
    from repro.training import OptimizerConfig
    names = {f.name for f in dataclasses.fields(OptimizerConfig)}
    return OptimizerConfig(**{k: v for k, v in conf["optimizer"].items()
                              if k in names})


def build(conf: Dict[str, Any], mix: Dict[str, Any], devices,
          make_step: Optional[Callable] = None) -> Program:
    import jax
    from jax.sharding import AxisType, Mesh
    from repro.models.config import ShapeConfig
    from repro.sharding.planner import plan_for, train_shardings
    from repro.training import make_train_step

    cfg = program_config(conf)
    tr = conf["train"]
    shape = tuple(tr["mesh"])
    mesh = Mesh(np.asarray(devices).reshape(shape), tuple(tr["mesh_axes"]),
                axis_types=(AxisType.Auto,) * len(shape))
    plan = plan_for(cfg, ShapeConfig(cfg.name, int(mix["seq_len"]),
                                     int(mix["global_batch"]), "train"), mesh)
    sh = train_shardings(plan, cfg)
    make_step = make_step or make_train_step
    fn = make_step(cfg, optimizer(conf), mesh=mesh)
    batch_sh = {k: sh["batch"][k] for k in ("tokens", "mask", "patches")}
    step = jax.jit(fn, in_shardings=(sh["params"], sh["opt"], batch_sh),
                   out_shardings=(sh["params"], sh["opt"], sh["replicated"]),
                   donate_argnums=(0, 1))
    sh = dict(sh, batch=batch_sh)
    return Program(cfg, mesh, plan.rules, sh, step)


@functools.lru_cache(maxsize=None)
def _norm_fns(reference: str):
    """Jitted per-leaf norms of a tree (scaled), and of a difference, leaf
    by leaf as the reference reads them."""
    import jax
    import jax.numpy as jnp
    ref = load_reference(reference)
    norms = jax.jit(lambda t, scale: ref.leaf_norms(
        jax.tree.map(lambda x: x * scale, t)))
    change = jax.jit(lambda a, b: ref.leaf_norms(
        jax.tree.map(jnp.subtract, a, b)))
    return norms, change


def reading_checks(conf: Dict[str, Any], readings: Dict[str, float]
                   ) -> List[Check]:
    """Each number :func:`compare` gives, beside the cell's limit for it."""
    limits = conf["check"]["limits"]
    return [Check(k, readings[k], limits[k]) for k in sorted(limits)]


def first_steps(prog: Program, conf: Dict[str, Any], seed: int,
                feed: Feed, n: int) -> Readings:
    """Fresh state from the seed, then ``n`` steps through the window's own
    call and feed; the readings of them."""
    import jax
    from repro.training import make_opt_state
    sh = prog.shardings
    prog.params = make_params(conf, seed, dtype=np.float32,
                              shardings=sh["params"])
    prog.opt = jax.jit(make_opt_state, out_shardings=sh["opt"])(prog.params)
    b1 = float(conf["optimizer"]["b1"])
    norms, change_of = _norm_fns(conf["reference"])
    losses: List[float] = []
    grad: Dict[str, np.ndarray] = {}
    for i in range(n):
        batch = feed.get()
        with jax.profiler.TraceAnnotation("bench.train", call=-1 - i):
            prog.params, prog.opt, met = prog.step(prog.params, prog.opt,
                                                   batch)
        losses.append(float(met["loss"]))
        if i == 0:       # m = (1 - b1) * g after one step
            grad = _host(norms(prog.opt["m"], 1.0 / (1.0 - b1)))
    p0 = make_params(conf, seed, dtype=np.float32, shardings=sh["params"])
    change = _host(change_of(prog.params, p0))
    del p0
    return Readings(losses, grad, change)


def reference_readings(conf: Dict[str, Any], mix: Dict[str, Any], seed: int,
                       devices, n: int, low: bool = False) -> Readings:
    """The plain reference's first ``n`` steps from the same weights and
    batches (``low``: the control, in float8)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding
    ref = load_reference(conf["reference"])
    cfg = program_config(conf)
    mesh = Mesh(np.asarray(devices), (ref.AXIS,))
    shapes = jax.eval_shape(lambda: make_params(conf, seed, np.float32))
    psh = ref.shardings(mesh, shapes)
    bsh = NamedSharding(mesh, jax.sharding.PartitionSpec(ref.AXIS))
    step = ref.make_step(reference_conf(conf), mesh, low)
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t),
                    out_shardings=psh)
    losses: List[float] = []
    grad: Dict[str, np.ndarray] = {}
    with jax.default_matmul_precision("highest"):
        p = make_params(conf, seed, dtype=np.float32, shardings=psh)
        m, v = zeros(p), zeros(p)
        for i in range(n):
            b = batch_of(mix, cfg, seed, i)
            b = {k: jax.device_put(x, bsh) for k, x in b.items()}
            p, m, v, loss, g = step(p, m, v, jnp.int32(i + 1), b)
            losses.append(float(loss))
            if i == 0:
                grad = _host(g)
        del m, v
        p0 = make_params(conf, seed, dtype=np.float32, shardings=psh)
        change = _host(_norm_fns(conf["reference"])[1](p, p0))
    del p, p0
    return Readings(losses, grad, change)


def batch_of(mix: Dict[str, Any], cfg, seed: int, i: int
             ) -> Dict[str, np.ndarray]:
    return train_batch(mix, seed, i, cfg.vocab, cfg.frontend_tokens,
                       cfg.frontend_dim)


# ------------------------------------------------------------------- a run

@dataclass
class TrainRun:
    """What one run leaves for the metric readers and the checks."""
    cfg: Any
    setup_s: float
    steps: int                      # dispatched in the window, all done
    tokens_per_step: int
    window_s: float                 # window open -> last step done
    flops_per_step: float
    chips: int
    checks: List[Check]
    attempted: int
    failed: int
    trace: Any = None
    device: Dict[str, Any] = field(default_factory=dict)
    extra: Dict[str, Any] = field(default_factory=dict)


def run(*, conf: Dict[str, Any], mix: Dict[str, Any], seed: int,
        seconds: float, trace: bool, t_process: float, devices,
        hooks: Optional[Dict[str, Callable]] = None) -> TrainRun:
    import jax
    from .readers import load_count

    hooks = hooks or {}
    gc.collect()
    CompileClock.get()
    n_first = int(conf["check"]["first_steps"])
    prog = build(conf, mix, devices, hooks.get("make_step"))
    cfg = prog.cfg
    alter = hooks.get("batch", lambda b: b)
    t = now()
    with prog.context():
        feed = Feed(lambda i: alter(batch_of(mix, cfg, seed, i)),
                    prog.shardings["batch"])
        try:
            mine = first_steps(prog, conf, seed, feed, n_first)
            log(f"setup: first {n_first} steps (compile or load included) "
                f"in {now() - t:.3f} s; losses {mine.losses}")
            compiles0 = CompileClock.get().count
            w0 = now()
            setup_s = w0 - t_process
            w1 = w0 + seconds
            prof = xplane = None
            span = min(seconds, float(mix.get("trace_s", 10.0)))
            t_on = w0 + (seconds - span) / 2
            losses: List[float] = []
            prev = met = batch = None
            n = 0
            while now() < w1:
                if trace and prof is None and now() >= t_on:
                    from .trace import Profiler
                    prof = Profiler(OUT / "trace")
                    prof.start()
                if prof is not None and xplane is None and now() >= t_on + span:
                    xplane = prof.stop()
                batch = feed.get()
                with jax.profiler.TraceAnnotation("bench.train", call=n):
                    prog.params, prog.opt, met = prog.step(
                        prog.params, prog.opt, batch)
                if prev is not None:        # at most two steps in flight
                    losses.append(float(prev["loss"]))
                prev, n = met, n + 1
            if prev is not None:
                losses.append(float(prev["loss"]))
            jax.block_until_ready((prog.params, prog.opt))
            w_end = now()
            compiles = CompileClock.get().count - compiles0
            if prof is not None and xplane is None:
                xplane = prof.stop()
        finally:
            feed.stop()
    info = device_info(devices)          # read before the reference runs
    prog.params = prog.opt = None
    del feed, batch, met, prev
    gc.collect()

    t = now()
    ref = reference_readings(conf, mix, seed, devices, n_first)
    got = compare(mine, ref)
    log(f"reference: {n_first} steps in {now() - t:.3f} s; losses "
        f"{ref.losses}")
    extra: Dict[str, Any] = {"readings": got, "reference_s": now() - t}
    if "after" in hooks:
        extra.update(hooks["after"](mine=mine, ref=ref))
    bad = sum(1 for x in losses if not math.isfinite(x))
    checks = reading_checks(conf, got)
    checks.append(Check("nonfinite_window_losses", bad, 0))
    tokens = int(mix["global_batch"]) * int(mix["seq_len"])
    count = load_count("train_step")
    run_ = TrainRun(
        cfg=cfg, setup_s=setup_s, steps=n, tokens_per_step=tokens,
        window_s=w_end - w0,
        flops_per_step=count.step_flops(cfg, int(mix["global_batch"]),
                                        int(mix["seq_len"])),
        chips=len(devices), checks=checks, attempted=n, failed=bad,
        device=info, extra=extra)
    log(f"window: {n} steps in {run_.window_s:.3f} s; {compiles} programs "
        f"reached the backend in it")
    if trace:
        from .trace import read_xplane
        t = now()
        run_.trace = read_xplane(xplane)
        log(f"trace: read {len(run_.trace.ops)} device ops and "
            f"{len(run_.trace.spans)} spans in {now() - t:.3f} s")
    return run_


END_TO_END = {
    "train_tok_s": lambda r: r.steps * r.tokens_per_step / r.window_s,
    "setup_s": lambda r: r.setup_s,
}
