"""Driver of serving cells: one run of a model served through the tenant
control plane and ``ServingFleet``, under an open-loop schedule, with
WorkUnit churn in the control plane beside it.

The harness keeps its own clock. It hands ``ServingFleet`` an engine
factory that puts each ``GenerationEngine`` behind :class:`EngineProxy`,
which wraps the two public calls the fleet drives (``admit_many`` and
``step``) and stamps, on the harness clock, every token each call appends
to a request's ``tokens`` and every request handed to ``admit_many``.
"""
from __future__ import annotations

import functools
import gc
import heapq
import importlib.util
import json
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from .common import (BENCH, OUT, Check, CompileClock, device_info, log, now,
                     percentile, reference_conf, seed32)
from .traffic import ServeRequest, UnitCreate, serve_schedule, unit_schedule
from .weights import make_params, program_config


# ------------------------------------------------------------- engine proxy

@dataclass
class Call:
    kind: str                 # "admit" | "step"
    call: int                 # call number, also in the trace span
    replica: int
    t0: float
    t1: float
    lengths: List[int]        # admit: true prompt lengths; step: attended


class Recorder:
    """Everything the proxies and generators stamp, on the harness clock."""

    def __init__(self):
        self._lock = threading.Lock()
        self._ncall = 0
        self.calls: List[Call] = []
        self.received: Dict[int, float] = {}       # uid -> handed to admit
        self.tokens: Dict[int, List[float]] = {}   # uid -> token stamps

    def next_call(self) -> int:
        with self._lock:
            self._ncall += 1
            return self._ncall


class EngineProxy:
    """The program's engine behind the two calls the harness times."""

    def __init__(self, engine, rec: Recorder, replica: int):
        self._engine = engine
        self._rec = rec
        self._replica = replica
        self._live: Dict[int, Any] = {}            # uid -> request in a slot

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def _stamp(self, reqs, before, t1) -> None:
        rec = self._rec
        for r, n in zip(reqs, before):
            new = len(r.tokens) - n
            if new > 0:
                rec.tokens.setdefault(r.uid, []).extend([t1] * new)

    def admit_many(self, reqs):
        import jax
        if not reqs:                   # the drive loop polls with none
            return self._engine.admit_many(reqs)
        rec = self._rec
        n = rec.next_call()
        t0 = now()
        for r in reqs:
            rec.received.setdefault(r.uid, t0)
        before = [len(r.tokens) for r in reqs]
        with jax.profiler.TraceAnnotation("bench.admit", call=n):
            taken = self._engine.admit_many(reqs)
        t1 = now()
        self._stamp(reqs, before, t1)
        for r in taken:
            if not r.done:
                self._live[r.uid] = r
        rec.calls.append(Call("admit", n, self._replica, t0, t1,
                              [int(np.asarray(r.prompt).size)
                               for r in taken]))
        return taken

    def step(self):
        import jax
        rec = self._rec
        n = rec.next_call()
        reqs = list(self._live.values())
        # a slot attends its prompt and every token served so far
        attended = [int(np.asarray(r.prompt).size) + len(r.tokens)
                    for r in reqs]
        before = [len(r.tokens) for r in reqs]
        t0 = now()
        with jax.profiler.TraceAnnotation("bench.step", call=n):
            finished = self._engine.step()
        t1 = now()
        self._stamp(reqs, before, t1)
        for r in finished:
            self._live.pop(r.uid, None)
        if reqs:
            rec.calls.append(Call("step", n, self._replica, t0, t1,
                                  attended))
        return finished


class GcPauses:
    """Pauses of Python's cyclic collector while registered in
    ``gc.callbacks``: (generation, start, seconds) on the harness clock.
    Logged, so that a stall of the host in the window can be told apart
    from one of the device."""

    def __init__(self):
        self.pauses: List[Tuple[int, float, float]] = []
        self._t0: Optional[float] = None

    def __call__(self, phase: str, info: Dict[str, Any]) -> None:
        if phase == "start":
            self._t0 = now()
        elif self._t0 is not None:
            self.pauses.append((int(info["generation"]), self._t0,
                                now() - self._t0))
            self._t0 = None

    def summary(self, w0: float) -> str:
        if not self.pauses:
            return "no collections"
        gen, t, longest = max(self.pauses, key=lambda p: p[2])
        full = sum(1 for p in self.pauses if p[0] == 2)
        return (f"{len(self.pauses)} collections ({full} of generation 2), "
                f"{sum(p[2] for p in self.pauses) * 1e3:.3f} ms in all; "
                f"longest {longest * 1e3:.3f} ms (generation {gen}, at "
                f"+{t - w0:.3f} s)")


# ---------------------------------------------------------- load generators

class OpenLoop(threading.Thread):
    """Submits each request at its due time, whatever the fleet is doing."""

    def __init__(self, fleet, schedule: List[ServeRequest], w0: float):
        super().__init__(name="bench-openloop", daemon=True)
        self.fleet, self.schedule, self.w0 = fleet, schedule, w0
        self.due: Dict[int, float] = {}            # uid -> due time
        self.sent: Dict[int, float] = {}           # uid -> submit time
        self.index: Dict[int, int] = {}            # uid -> schedule index
        self.error: Optional[BaseException] = None

    def run(self) -> None:
        import jax
        try:
            for r in self.schedule:
                due = self.w0 + r.offset
                wait = due - now()
                if wait > 0:
                    time.sleep(wait)
                with jax.profiler.TraceAnnotation("bench.submit"):
                    t = now()
                    uid = self.fleet.submit(r.tenant, r.prompt, r.max_new)
                self.due[uid], self.sent[uid] = due, t
                self.index[uid] = r.index
        except BaseException as e:   # reported by the main thread
            self.error = e


class ControlPlaneLoad:
    """WorkUnit churn: creates on an open-loop schedule in each tenant's
    own plane, watches each plane for the Ready condition, and deletes
    every unit a fixed time after it was seen Ready.

    Propagation latency is the paper's creation -> Ready, taken as a
    tenant's client sees it: from just before its create call to the
    moment its watch on its own plane delivers the unit Ready."""

    NS = "bench"

    def __init__(self, fw, planes: Dict[str, Any], units: List[UnitCreate],
                 w0: float, delete_after: float):
        self.fw, self.planes, self.units = fw, planes, units
        self.w0, self.delete_after = w0, delete_after
        self.created: Dict[Tuple[str, str], float] = {}
        self.ready: Dict[Tuple[str, str], float] = {}
        self.deleted: Dict[Tuple[str, str], float] = {}
        self._deletes: List[Tuple[float, Tuple[str, str]]] = []
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._flush = threading.Event()           # delete all now
        self._flushed = threading.Event()
        self.error: Optional[BaseException] = None
        self._watches = []
        for tenant, plane in planes.items():
            w = plane.api.watch("WorkUnit", self.NS, copy=False)
            w.set_waker(self._wake.set)
            self._watches.append((tenant, w))
        self._driver = threading.Thread(target=self._guard(self._drive),
                                        name="bench-cp-driver", daemon=True)
        self._watcher = threading.Thread(target=self._guard(self._watch),
                                         name="bench-cp-watch", daemon=True)

    def _guard(self, fn: Callable[[], None]) -> Callable[[], None]:
        def run():
            try:
                fn()
            except BaseException as e:   # reported by the main thread
                self.error = e
        return run

    def start(self) -> None:
        self._watcher.start()
        self._driver.start()

    def _watch(self) -> None:
        while not self._stop.is_set():
            self._wake.wait(0.05)
            self._wake.clear()
            for tenant, w in self._watches:
                while True:
                    ev = w.poll()
                    if ev is None:
                        break
                    obj = ev.object
                    if obj is None or obj.status.phase != "Ready":
                        continue
                    key = (tenant, obj.metadata.name)
                    t = now()
                    with self._lock:
                        if key in self.ready or key not in self.created:
                            continue
                        self.ready[key] = t
                        heapq.heappush(self._deletes,
                                       (t + self.delete_after, key))

    def _delete(self, key: Tuple[str, str]) -> None:
        import jax
        from repro.core import NotFoundError
        tenant, name = key
        with jax.profiler.TraceAnnotation("bench.cp"):
            try:
                self.planes[tenant].api.delete("WorkUnit", self.NS, name)
            except NotFoundError:
                pass
        self.deleted[key] = now()

    def _drive(self) -> None:
        import jax
        i = 0
        units = self.units
        while True:
            t = now()
            while i < len(units) and self.w0 + units[i].offset <= t:
                u = units[i]
                i += 1
                unit = self.fw.make_unit(u.name, self.NS, chips=0)
                key = (u.tenant, u.name)
                with jax.profiler.TraceAnnotation("bench.cp"):
                    with self._lock:
                        self.created[key] = now()
                    self.planes[u.tenant].api.create(unit)
            due: List[Tuple[str, str]] = []
            with self._lock:
                flush = self._flush.is_set()
                while self._deletes and (flush or self._deletes[0][0] <= t):
                    due.append(heapq.heappop(self._deletes)[1])
                if flush:
                    due += [k for k in self.created if k not in self.ready
                            and k not in self.deleted]
            for key in due:
                self._delete(key)
            if flush and i >= len(units):
                self._flushed.set()
                return
            nxt = (self.w0 + units[i].offset - now()) if i < len(units) \
                else 0.05
            self._stop.wait(min(max(nxt, 0.0), 0.05))

    def wait_ready(self, deadline: float) -> None:
        while now() < deadline:
            with self._lock:
                if len(self.ready) >= len(self.created) and \
                        len(self.created) == len(self.units):
                    return
            time.sleep(0.01)

    def flush(self, deadline: float) -> None:
        """Delete every unit not yet deleted, and wait for the driver."""
        self._flush.set()
        self._flushed.wait(max(0.0, deadline - now()))

    def stop(self) -> None:
        self._stop.set()
        self._wake.set()
        self._flush.set()
        self._driver.join(10)
        self._watcher.join(10)
        for _, w in self._watches:
            w.close()

    def leftover(self, serving_ns: str, deadline: float) -> int:
        """WorkUnits of the churn still in the super cluster, after waiting
        until ``deadline`` for them to go."""
        while True:
            n = sum(1 for u in self.fw.super_api.list("WorkUnit", copy=False)
                    if u.metadata.namespace != serving_ns)
            if n == 0 or now() >= deadline:
                return n
            time.sleep(0.02)


# -------------------------------------------------------------- reference

def load_reference(name: str):
    path = BENCH / "reference" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_ref_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def _gap_fns(conf_json: str):
    """The reference's jitted gap functions for one configuration, handed
    as :func:`reference_conf` gives it."""
    import jax
    import jax.numpy as jnp
    conf = json.loads(conf_json)
    ref = load_reference(conf["reference"])

    @jax.jit
    def served_gap(params, tokens, read, served):
        lg = ref.logits(params, conf, tokens, read)
        pick = jnp.take_along_axis(lg, served[:, None], axis=-1)[:, 0]
        return lg.max(axis=-1) - pick

    @jax.jit
    def control_gap(params, tokens, read):
        hi = ref.logits(params, conf, tokens, read)
        lo = ref.logits(params, conf, tokens, read, low=True)
        pick = jnp.take_along_axis(hi, lo.argmax(axis=-1)[:, None],
                                   axis=-1)[:, 0]
        return hi.max(axis=-1) - pick

    return served_gap, control_gap


def logit_gaps(params, conf, mix, samples, *, control: bool = False
               ) -> List[float]:
    """For each sampled (prompt, served tokens): the gap by which each
    served token's reference logit lies below the reference's best at its
    position (``control``: the token the float8 reference puts first,
    instead of the served one)."""
    import jax
    import jax.numpy as jnp
    served_gap, control_gap = _gap_fns(reference_conf(conf))
    S = int(mix["prompt_len"]["max"]) + int(mix["output_len"]["max"])
    R = int(mix["output_len"]["max"])
    out: List[float] = []
    with jax.default_matmul_precision("highest"):
        for prompt, served in samples:
            p, s = np.asarray(prompt, np.int32), np.asarray(served, np.int32)
            n = len(s)
            seq = np.zeros(S, np.int32)
            body = np.concatenate([p, s[:-1]])
            seq[:len(body)] = body
            read = np.zeros(R, np.int32)
            read[:n] = len(p) - 1 + np.arange(n)
            tok = np.zeros(R, np.int32)
            tok[:n] = s
            if control:
                g = control_gap(params, jnp.asarray(seq), jnp.asarray(read))
            else:
                g = served_gap(params, jnp.asarray(seq), jnp.asarray(read),
                               jnp.asarray(tok))
            out += [float(x) for x in np.asarray(g)[:n]]
    return out


def gap_check(conf: Dict[str, Any], gap: float) -> Check:
    """The widest logit gap, beside the cell's limit for it."""
    return Check("logit_gap", gap, conf["check"]["logit_gap_limit"])


# ------------------------------------------------------------------- a run

@dataclass
class ServeRun:
    """What one run leaves for the metric readers and the checks."""
    cfg: Any
    w0: float
    w1: float
    deadline: float
    setup_s: float
    requests: List[Dict[str, Any]]
    calls: List[Call]
    units: List[Dict[str, Any]]
    window_compiles: int
    meter: Optional[Dict[str, Dict[str, float]]]
    checks: List[Check]
    attempted: int
    failed: int
    trace: Any = None
    device: Dict[str, Any] = field(default_factory=dict)
    extra: Dict[str, Any] = field(default_factory=dict)

    def window_calls(self, kind: str) -> List[Call]:
        return [c for c in self.calls
                if c.kind == kind and self.w0 <= c.t0 and c.t1 <= self.w1]


def _meter_totals(fw) -> Optional[Dict[str, Dict[str, float]]]:
    if fw.meter is None:
        return None
    return {t: dict(v) for t, v in fw.meter.totals().items()}


def _warm(cfg, params, sched: List[ServeRequest], slots: int, max_len: int
          ) -> int:
    """Compile (or load) every program this traffic drives: admission of
    1..k rows in each length bucket its prompts fall in, k the least of
    the slots and the bucket's prompts (every seed has the same sizes), and
    the step. Returns the number of admission shapes."""
    from repro.serving import GenerationEngine, Request
    eng = GenerationEngine(cfg, params, slots=slots, max_len=max_len)
    longest: Dict[int, int] = {}
    count: Dict[int, int] = {}
    for r in sched:
        b = eng._bucket(len(r.prompt))
        longest[b] = max(longest.get(b, 0), len(r.prompt))
        count[b] = count.get(b, 0) + 1
    uid = shapes = 0
    for b in sorted(longest):
        for k in range(1, min(slots, count[b]) + 1):
            shapes += 1
            reqs = []
            for _ in range(k):
                uid += 1
                reqs.append(Request(uid, np.zeros(longest[b], np.int32), 1))
            eng.admit_many(reqs)          # one-token budget: no slot held
    eng.admit_many([Request(uid + 1, np.zeros(8, np.int32), 2)])
    eng.step()
    del eng
    return shapes


def run(*, conf: Dict[str, Any], mix: Dict[str, Any], seed: int,
        seconds: float, trace: bool, t_process: float, devices,
        hooks: Optional[Dict[str, Callable]] = None) -> ServeRun:
    import jax
    from repro.core import Namespace, VirtualClusterFramework
    from repro.serving import GenerationEngine, ServingFleet

    hooks = hooks or {}
    gc.collect()                         # an earlier run's weights, if any
    clock = CompileClock.get()
    cfg = program_config(conf)
    sv = conf["serve"]
    slots, max_len, replicas = sv["slots"], sv["max_len"], sv["replicas"]
    t = now()
    params = make_params(conf, seed)
    jax.block_until_ready(params)
    log(f"setup: weights made on device in {now() - t:.3f} s")

    rng = np.random.default_rng(seed32(seed, 2))
    sched = serve_schedule(mix, seconds, cfg.vocab, rng)
    cp = mix["control_plane"]
    units = unit_schedule(cp, seconds, np.random.default_rng(seed32(seed, 3)))

    t = now()
    shapes = _warm(cfg, params, sched, slots, max_len)
    log(f"setup: warmed {shapes} admission shapes and the step in "
        f"{now() - t:.3f} s ({clock.count} programs so far, "
        f"{clock.count - clock.hits} of them compiled)")

    rec = Recorder()
    nrep = iter(range(1 << 30))

    def factory():
        eng = GenerationEngine(cfg, params, slots=slots, max_len=max_len)
        if "engine" in hooks:
            eng = hooks["engine"](eng)
        return EngineProxy(eng, rec, next(nrep))

    fw = VirtualClusterFramework(
        num_nodes=cp["nodes"], downward_workers=20, upward_workers=100,
        fair_queuing=True, scan_interval=0.0, router_scan_interval=0.0,
        heartbeat_interval=3600.0, metering=trace)
    fleet = ServingFleet(factory, replicas=0)
    fleet.attach(fw)
    # the meter keeps the control plane's fair-queue waits only: the slot
    # scheduler's waits are read from the proxy's stamps
    fleet.meter = None
    weights = {t["name"]: t["weight"] for t in mix["tenants"]}
    load = gen = None
    pauses = GcPauses()
    try:
        fw.start()
        planes = {}
        for name in cp["tenants"]:
            planes[name] = fw.add_tenant(name, weight=weights.get(name, 1))
            ns = Namespace()
            ns.metadata.name = ControlPlaneLoad.NS
            planes[name].api.create(ns)
        for name in weights:
            fleet.register_tenant(planes[name])
        fleet.resize(replicas)
        fleet.wait_replicas(replicas, timeout=300)

        w0 = now() + 0.05
        load = ControlPlaneLoad(fw, planes, units, w0,
                                cp["delete_after_ready_s"])
        gen = OpenLoop(fleet, sched, w0)
        meter0 = _meter_totals(fw)
        compiles0 = clock.count
        setup_s = w0 - t_process
        gc.callbacks.append(pauses)
        gen.start()
        load.start()
        prof = None
        w1 = w0 + seconds
        if trace:
            from .trace import Profiler
            span = min(seconds, float(mix.get("trace_s", 10.0)))
            t_on = w0 + (seconds - span) / 2
            time.sleep(max(0.0, t_on - now()))
            prof = Profiler(OUT / "trace")
            prof.start()
            time.sleep(max(0.0, t_on + span - now()))
            xplane = prof.stop()
        time.sleep(max(0.0, w1 - now()))
        gc.callbacks.remove(pauses)
        window_compiles = clock.count - compiles0
        meter1 = _meter_totals(fw)

        # drain: every request due in the window, and every unit created
        deadline = w1 + float(mix["drain_s"])
        gen.join(max(0.0, deadline - now()))
        try:
            fleet.wait_completed(len(sched), timeout=max(0.0,
                                                         deadline - now()))
        except TimeoutError as e:
            log(f"drain: {e}")
        done = dict(fleet.completed)
        load.wait_ready(w1 + 30.0)
        load.flush(w1 + 60.0)
        leftover = load.leftover(fleet.namespace, now() + 30.0)
        if gen.error or load.error or fleet.failures:
            raise RuntimeError(f"load or fleet failed: {gen.error!r} "
                               f"{load.error!r} {fleet.failures!r}")
    finally:
        if pauses in gc.callbacks:
            gc.callbacks.remove(pauses)
        if load is not None:
            load.stop()
        fw.stop()
    info = device_info(devices)          # read before the reference runs
    del fleet, fw, factory
    gc.collect()

    # ---- requests and their stamps
    requests = []
    for uid, due in gen.due.items():
        r = done.get(uid)
        stamps = rec.tokens.get(uid, [])
        requests.append({
            "uid": uid, "due": due, "sent": gen.sent[uid],
            "received": rec.received.get(uid),
            "stamps": stamps, "finished": r is not None,
            "tokens": list(r.tokens) if r is not None else [],
            "prompt": sched[gen.index[uid]].prompt,
            "max_new": sched[gen.index[uid]].max_new,
            "tenant": sched[gen.index[uid]].tenant})
    unit_rows = [{"created": load.created.get((u.tenant, u.name)),
                  "ready": load.ready.get((u.tenant, u.name)),
                  "tenant": u.tenant} for u in units]

    # ---- checks
    unfinished = len(sched) - sum(1 for q in requests if q["finished"])
    short = sum(1 for q in requests
                if q["finished"] and len(q["tokens"]) != q["max_new"])
    not_ready = sum(1 for u in unit_rows if u["ready"] is None)
    ok_reqs = [q for q in requests
               if q["finished"] and len(q["tokens"]) == q["max_new"]]
    srng = np.random.default_rng(seed32(seed, 4))
    k = int(conf["check"]["sample_requests"])
    sample: List[Dict[str, Any]] = []
    if ok_reqs:
        longest = max(ok_reqs, key=lambda q: len(q["tokens"]))
        rest = [q for q in ok_reqs if q is not longest]
        pick = srng.permutation(len(rest))[:k - 1]
        sample = [longest] + [rest[i] for i in pick]
    t = now()
    gaps = logit_gaps(params, conf, mix,
                      [(q["prompt"], q["tokens"]) for q in sample])
    extra: Dict[str, Any] = {"sampled_tokens": len(gaps),
                             "reference_s": now() - t}
    if "after" in hooks:
        extra.update(hooks["after"](params=params, sample=sample,
                                    requests=requests))
    gap = max(gaps) if gaps else float("inf")
    log(f"reference: {len(sample)} requests, {len(gaps)} served tokens "
        f"compared in {extra['reference_s']:.3f} s")
    checks = [gap_check(conf, gap),
              Check("unfinished_requests", unfinished, 0),
              Check("wrong_length_outputs", short, 0),
              Check("units_never_ready", not_ready, 0),
              Check("units_left_after_delete", leftover, 0)]
    late = max(requests, key=lambda q: q["sent"] - q["due"], default=None)
    if late is not None:
        log(f"generator: at most {(late['sent'] - late['due']) * 1e3:.3f} "
            f"ms late (due at +{late['due'] - w0:.3f} s)")
    log(f"gc in the window: {pauses.summary(w0)}")
    run_ = ServeRun(
        cfg=cfg, w0=w0, w1=w1,
        deadline=deadline, setup_s=setup_s, requests=requests,
        calls=rec.calls, units=unit_rows, window_compiles=window_compiles,
        meter=None if meter0 is None else {
            t: {k: meter1.get(t, {}).get(k, 0.0) - meter0.get(t, {}).get(k, 0.0)
                for k in ("queue_items", "queue_wait_s")}
            for t in cp["tenants"]},
        checks=checks,
        attempted=len(sched) + len(units),
        failed=unfinished + not_ready + leftover, device=info, extra=extra)
    log(f"tails (ms): {tails_line(run_)}")
    if trace:
        from .trace import read_xplane
        t = now()
        run_.trace = read_xplane(xplane)
        log(f"trace: read {len(run_.trace.ops)} device ops and "
            f"{len(run_.trace.spans)} spans in {now() - t:.3f} s")
    return run_


# ------------------------------------------------------- end-to-end metrics

def ttft_ms(r: ServeRun) -> List[float]:
    """Due time -> first token stamp, for every request due in the
    window; one with no first token enters at the drain deadline."""
    return [((q["stamps"][0] if q["stamps"] else r.deadline) - q["due"])
            * 1e3 for q in r.requests]


def itl_ms(r: ServeRun) -> List[float]:
    out: List[float] = []
    for q in r.requests:
        s = q["stamps"]
        out += [(b - a) * 1e3 for a, b in zip(s, s[1:])]
    return out


def propagation_ms(r: ServeRun) -> List[float]:
    return [((u["ready"] if u["ready"] is not None else r.deadline)
             - u["created"]) * 1e3 for u in r.units
            if u["created"] is not None]


TAILS = {"ttft": ttft_ms, "itl": itl_ms, "propagation": propagation_ms}
PERCENTILES = (50, 75, 90, 95, 99)

END_TO_END: Dict[str, Callable[[ServeRun], float]] = {
    "setup_s": lambda r: r.setup_s}
for _name, _values in TAILS.items():
    for _q in PERCENTILES:
        END_TO_END[f"{_name}_p{_q}_ms"] = (
            lambda r, f=_values, q=_q: percentile(f(r), q))


def tails_line(r: ServeRun) -> str:
    """Every tail at every percentile, for the log."""
    out = []
    for name, f in TAILS.items():
        vals = f(r)
        out.append(f"{name} ({len(vals)}) " + " ".join(
            f"p{q} {percentile(vals, q):.3f}" for q in PERCENTILES if vals))
    return "; ".join(out)
