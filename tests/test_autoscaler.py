"""Closed-loop autoscaler: SignalWindow aggregation, policy hysteresis /
cooldown / bounds, grow-under-burst + shrink-after-idle on a live syncer
fleet with no lost keys, resize_shards serialization under concurrent
callers (autoscaler tick vs. operator), and the /healthz loop state."""
import json
import threading
import time
import urllib.request

from repro.core import (APIServer, Autoscaler, CooperativeExecutor, Namespace,
                        ScalingPolicy, Syncer, TenantControlPlane,
                        VirtualClusterFramework, WorkUnit)
from repro.core.autoscaler import SignalWindow, _Actuator


def wait_for(cond, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.005)
    return False


# ------------------------------------------------------------- SignalWindow

def test_signal_window_ewma_and_percentile():
    w = SignalWindow(horizon=100.0, alpha=0.5)
    for v in (0.0, 10.0, 20.0, 30.0, 40.0, 50.0, 60.0, 70.0, 80.0, 90.0):
        w.observe(v, now=0.0)
    assert len(w) == 10
    assert w.last() == 90.0
    assert w.percentile(0.9) == 90.0
    assert w.percentile(0.0) == 0.0
    # EWMA is smoothed: far below the last sample after a ramp
    assert 0.0 < w.ewma() < 90.0


def test_signal_window_horizon_eviction():
    w = SignalWindow(horizon=10.0)
    w.observe(100.0, now=0.0)
    w.observe(1.0, now=20.0)      # first sample is now out of horizon
    assert len(w) == 1
    assert w.percentile(0.9) == 1.0


def test_signal_window_empty_is_zero():
    w = SignalWindow()
    assert w.ewma() == 0.0
    assert w.percentile(0.9) == 0.0
    assert w.last() == 0.0


# ------------------------------------------------- policy/actuator decisions

def _policy(**kw):
    base = dict(min_shards=1, max_shards=8, hysteresis=2,
                up_cooldown_s=1.0, down_cooldown_s=5.0, grow_factor=2.0)
    base.update(kw)
    return ScalingPolicy(**base)


def _shards_actuator(**kw):
    p = _policy(**kw)
    return _Actuator("shards", p, p.clamp_shards)


def test_actuator_hysteresis_needs_consecutive_breaches():
    a = _shards_actuator()
    assert a.decide(2, True, False, now=0.0) is None     # 1st breach: hold
    assert a.decide(2, True, False, now=0.1) == 4        # 2nd: grow ×2
    a.committed(0.1)
    # a clean tick resets the streak
    a2 = _shards_actuator()
    assert a2.decide(2, True, False, now=0.0) is None
    assert a2.decide(2, False, False, now=0.1) is None   # streak broken
    assert a2.decide(2, True, False, now=0.2) is None    # back to 1st breach


def test_actuator_cooldown_spaces_actions():
    a = _shards_actuator()
    assert a.decide(2, True, False, now=0.0) is None
    assert a.decide(2, True, False, now=0.1) == 4
    a.committed(0.1)
    # breaches keep arriving, but the up-cooldown (1 s) gates the next step
    assert a.decide(4, True, False, now=0.2) is None
    assert a.decide(4, True, False, now=0.3) is None
    assert a.decide(4, True, False, now=1.2) == 8
    a.committed(1.2)
    # shrink needs the longer down-cooldown (5 s) since the last action
    assert a.decide(8, False, True, now=1.3) is None
    assert a.decide(8, False, True, now=2.0) is None
    assert a.decide(8, False, True, now=6.3) == 4


def test_actuator_respects_bounds():
    a = _shards_actuator()
    assert a.decide(8, True, False, now=0.0) is None
    assert a.decide(8, True, False, now=0.1) is None     # already at max
    b = _shards_actuator()
    assert b.decide(1, False, True, now=0.0) is None
    assert b.decide(1, False, True, now=0.1) is None     # already at min
    # growth from 1 doubles but is clamped to max
    c = _shards_actuator(max_shards=3)
    c.decide(2, True, False, now=0.0)
    assert c.decide(2, True, False, now=0.1) == 3
    # bounds are read from the policy LIVE: widening max after
    # construction is honored at the next decision
    c.policy.max_shards = 6
    c.committed(0.1)
    assert c.decide(3, True, False, now=1.2) is None
    assert c.decide(3, True, False, now=1.3) == 6


# ----------------------------------------------------- closed loop (live rig)

def _mk_unit(name, ns="bench"):
    u = WorkUnit()
    u.metadata.name = name
    u.metadata.namespace = ns
    return u


def _rig(tenants=8, pool=2, shards=1):
    ex = CooperativeExecutor(pool_size=pool, name="as-test")
    super_api = APIServer("super")
    syncer = Syncer(super_api, downward_workers=4, upward_workers=2,
                    scan_interval=0.0, shards=shards, downward_batch=4,
                    executor=ex)
    planes = [TenantControlPlane(f"t{i:02d}") for i in range(tenants)]
    for i, p in enumerate(planes):
        syncer.register_tenant(p, f"uid-{i:02d}")
    syncer.start()
    for p in planes:
        ns = Namespace()
        ns.metadata.name = "bench"
        p.api.create(ns)
    return ex, super_api, syncer, planes


def _fast_policy():
    return ScalingPolicy(min_shards=1, max_shards=4, shard_up_depth=8.0,
                         shard_down_depth=1.0, min_pool=2, max_pool=8,
                         pool_up_backlog=2.0, pool_down_backlog=0.25,
                         hysteresis=2, up_cooldown_s=0.1, down_cooldown_s=0.4,
                         window_s=1.5)


def test_autoscaler_grows_under_burst_and_shrinks_idle_no_lost_keys():
    """The acceptance loop: burst -> fleet and pool grow; idle -> both
    shrink to min; every created object converges to the super cluster."""
    ex, super_api, syncer, planes = _rig()
    scaler = Autoscaler(syncer, ex, policy=_fast_policy(), interval=0.05)
    scaler.start()
    try:
        per_tenant = 250
        threads = [threading.Thread(
            target=lambda p=p: [p.api.create(_mk_unit(f"u{j:04d}"))
                                for j in range(per_tenant)])
            for p in planes]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        total = len(planes) * per_tenant
        # no lost keys: every tenant create converges downward
        assert wait_for(
            lambda: super_api.store.count("WorkUnit") >= total, timeout=60.0)
        events = scaler.scale_events()
        ups = [d for d in events if d["direction"] == "up"]
        assert any(d["actuator"] == "shards" for d in ups)
        assert any(d["actuator"] == "executor_pool" for d in ups)
        assert syncer.num_shards > 1
        # idle cooldown: both actuators return to their minimums
        assert wait_for(lambda: syncer.num_shards == 1, timeout=30.0)
        assert wait_for(lambda: ex.pool_size == 2, timeout=30.0)
        assert wait_for(lambda: ex.thread_count() == 2, timeout=10.0)
        downs = [d for d in scaler.scale_events() if d["direction"] == "down"]
        assert any(d["actuator"] == "shards" for d in downs)
        assert any(d["actuator"] == "executor_pool" for d in downs)
        # decisions are visible in the registry
        reg = syncer.controllers[0].metrics
        assert reg.counter("autoscaler_scale_total", controller="autoscaler",
                           actuator="shards", direction="up") >= 1
        assert reg.counter("autoscaler_scale_total", controller="autoscaler",
                           actuator="executor_pool", direction="up") >= 1
    finally:
        scaler.stop()
        syncer.stop()
        ex.shutdown()
        super_api.close()


def test_autoscaler_state_reports_decisions_targets_cooldowns():
    ex, super_api, syncer, planes = _rig(tenants=2)
    scaler = Autoscaler(syncer, ex, policy=_fast_policy(), interval=0.05)
    scaler.start()
    try:
        st = scaler.state()
        assert st["last_decision"] is None
        assert st["targets"] == {"shards": 1, "upward_shards": 1,
                                 "executor_pool": 2,
                                 "engine_replicas": None}
        assert set(st["cooldown_remaining_s"]) == {"shards", "upward_shards",
                                                   "executor_pool",
                                                   "engine_replicas"}
        assert wait_for(lambda: scaler.state()["ticks"] >= 3)
        assert set(st["signals"]) == {"shard_depth", "reconcile_latency_s",
                                      "upward_depth", "upward_latency_s",
                                      "backlog_per_thread",
                                      "quantum_latency_s",
                                      "engine_pending", "engine_ttft_s"}
        # force a decision and check it surfaces
        for p in planes:
            for j in range(400):
                p.api.create(_mk_unit(f"u{j:04d}"))
        assert wait_for(lambda: scaler.state()["last_decision"] is not None,
                        timeout=20.0)
        last = scaler.state()["last_decision"]
        assert {"actuator", "from", "to", "direction", "reason",
                "age_s"} <= set(last)
    finally:
        scaler.stop()
        syncer.stop()
        ex.shutdown()
        super_api.close()


def test_autoscaler_without_executor_scales_shards_only():
    """Legacy thread mode: no pool to size, the shard loop still closes."""
    super_api = APIServer("super")
    syncer = Syncer(super_api, downward_workers=2, upward_workers=2,
                    scan_interval=0.0, shards=1, executor=None)
    planes = [TenantControlPlane(f"t{i}") for i in range(4)]
    for i, p in enumerate(planes):
        syncer.register_tenant(p, f"uid-{i}")
    syncer.start()
    scaler = Autoscaler(syncer, None, policy=_fast_policy(), interval=0.05)
    scaler.start()
    try:
        for p in planes:
            ns = Namespace()
            ns.metadata.name = "bench"
            p.api.create(ns)
            for j in range(300):
                p.api.create(_mk_unit(f"u{j:04d}"))
        assert wait_for(
            lambda: super_api.store.count("WorkUnit") >= 1200, timeout=60.0)
        assert wait_for(lambda: any(
            d["actuator"] == "shards" and d["direction"] == "up"
            for d in scaler.scale_events()), timeout=20.0)
        assert scaler.state()["targets"]["executor_pool"] is None
        # no pool to size: only the two shard-fleet actuators may fire
        assert all(d["actuator"] in ("shards", "upward_shards")
                   for d in scaler.scale_events())
    finally:
        scaler.stop()
        syncer.stop()
        super_api.close()


# ------------------------------------------- third actuator: upward fleet


def test_upward_actuator_grows_on_status_storm_and_shrinks_idle():
    """The third actuator: a status storm (rapid super-side flaps) must grow
    the UPWARD shard fleet, every tenant must converge to the final status,
    and idle cooldown must shrink the fleet back to its floor."""
    ex = CooperativeExecutor(pool_size=4, name="as-up-test")
    super_api = APIServer("super")
    syncer = Syncer(super_api, downward_workers=4, upward_workers=4,
                    scan_interval=0.0, shards=1, downward_batch=4,
                    upward_shards=1, executor=ex)
    planes = [TenantControlPlane(f"t{i:02d}") for i in range(6)]
    for i, p in enumerate(planes):
        syncer.register_tenant(p, f"uid-{i:02d}")
    syncer.start()
    policy = ScalingPolicy(min_upward_shards=1, max_upward_shards=4,
                           upward_up_depth=8.0, upward_down_depth=1.0,
                           hysteresis=2, up_cooldown_s=0.1,
                           down_cooldown_s=0.4, window_s=1.5,
                           # keep the other actuators parked so the test
                           # isolates the upward loop
                           shard_up_depth=1e9, min_pool=4, max_pool=4,
                           pool_up_backlog=1e9)
    scaler = Autoscaler(syncer, ex, policy=policy, interval=0.05)
    scaler.start()
    try:
        per_tenant = 120
        for p in planes:
            ns = Namespace()
            ns.metadata.name = "bench"
            p.api.create(ns)
        threads = [threading.Thread(
            target=lambda p=p: [p.api.create(_mk_unit(f"u{j:04d}"))
                                for j in range(per_tenant)])
            for p in planes]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        total = len(planes) * per_tenant
        assert wait_for(
            lambda: super_api.store.count("WorkUnit") >= total, timeout=60.0)
        prefixes = {p.name: syncer.tenants[p.name].prefix for p in planes}

        def storm(p):
            ns = f"{prefixes[p.name]}-bench"
            for j in range(per_tenant):
                for phase in ("Running", "Ready"):
                    super_api.update_status(
                        "WorkUnit", ns, f"u{j:04d}",
                        lambda u, ph=phase: setattr(u.status, "phase", ph))
        threads = [threading.Thread(target=storm, args=(p,)) for p in planes]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

        def converged(p):
            units = p.api.list("WorkUnit", "bench")
            return (len(units) >= per_tenant
                    and all(u.status.phase == "Ready" for u in units))
        assert wait_for(lambda: all(converged(p) for p in planes),
                        timeout=60.0)
        ups = [d for d in scaler.scale_events()
               if d["actuator"] == "upward_shards" and d["direction"] == "up"]
        assert ups, "upward actuator never grew the fleet"
        # idle cooldown: the upward fleet returns to its floor
        assert wait_for(lambda: syncer.num_upward_shards == 1, timeout=30.0)
        reg = syncer.controllers[0].metrics
        assert reg.counter("autoscaler_scale_total", controller="autoscaler",
                           actuator="upward_shards", direction="up") >= 1
    finally:
        scaler.stop()
        syncer.stop()
        ex.shutdown()
        super_api.close()


# ------------------------------------------- WRR weight autotune (satellite)


def test_weight_autotune_boosts_waiting_tenant_within_bounds():
    """Per-tenant wait metrics feed back into live WRR weights, bounded to
    [0.5x, 4x] of the configured weight."""
    ex, super_api, syncer, planes = _rig(tenants=2)
    scaler = Autoscaler(syncer, ex, policy=_fast_policy(), interval=3600)
    try:
        q = syncer.down.shards[0].queue
        slow, fastt = planes[0].name, planes[1].name
        # synthetic wait samples: tenant 0 waits 8x longer than tenant 1
        # at EQUAL throughput (same sample count) -> genuinely under-served
        q.per_tenant_wait.setdefault(slow, []).extend([0.8] * 10)
        q.per_tenant_wait.setdefault(fastt, []).extend([0.1] * 10)
        changed = scaler._autotune_weights()
        assert changed >= 1
        base = syncer.tenants[slow].plane.weight
        # slow tenant boosted, but never past 4x its configured weight
        assert q._weights[slow] > base
        assert q._weights[slow] <= 4 * base
        # fast tenant floored at 0.5x (rounds to >= 1)
        assert q._weights[fastt] >= max(1, round(0.5 * base))
        # samples were drained: a second tick with no new waits is a no-op
        assert not q.per_tenant_wait
        assert scaler._autotune_weights() == 0
        # autotune off: weights stay wherever they are
        scaler.policy.autotune_weights = False
        q.per_tenant_wait.setdefault(slow, []).extend([9.9] * 5)
        assert scaler._autotune_weights() == 0
    finally:
        syncer.stop()
        ex.shutdown()
        super_api.close()


def test_weight_autotune_does_not_reward_queue_flooder():
    """A flooding tenant's long waits are self-inflicted (and come with a
    proportionally large sample count): demand normalization cancels the
    wait excess, so the flooder gains no weight over a quiet tenant."""
    ex, super_api, syncer, planes = _rig(tenants=2)
    scaler = Autoscaler(syncer, ex, policy=_fast_policy(), interval=3600)
    try:
        q = syncer.down.shards[0].queue
        flooder, quiet = planes[0].name, planes[1].name
        base = syncer.tenants[flooder].plane.weight
        # flooder: 8x the throughput AND 8x the wait (self-inflicted)
        q.per_tenant_wait.setdefault(flooder, []).extend([0.8] * 80)
        q.per_tenant_wait.setdefault(quiet, []).extend([0.1] * 10)
        scaler._autotune_weights()
        # wait/overall (~1.78x) is cancelled by its count share (~0.56x):
        # no boost beyond the configured weight
        assert q._weights[flooder] <= base
        # and the quiet tenant is not starved below its floor
        assert q._weights[quiet] >= max(1, round(0.5 * base))
    finally:
        syncer.stop()
        ex.shutdown()
        super_api.close()


# --------------------------------------- resize_shards concurrency (satellite)

def test_resize_shards_concurrent_callers_serialize_no_lost_keys():
    """Operator resizes (blocking) race autoscaler-style resizes
    (block=False) while tenants burst: the fleet must end consistent —
    controllers match num_shards, every tenant sits on its ring shard,
    and every created object converges."""
    ex, super_api, syncer, planes = _rig(tenants=8)
    stop = threading.Event()
    errors = []

    def operator():
        sizes = [2, 4, 3, 1, 4, 2]
        try:
            for n in sizes:
                syncer.resize_shards(n)
                time.sleep(0.02)
        except Exception as e:                      # pragma: no cover
            errors.append(e)

    def autoscaler_like():
        try:
            i = 0
            while not stop.is_set():
                out = syncer.resize_shards(1 + (i % 4), block=False)
                assert out is None or isinstance(out, dict)
                i += 1
                time.sleep(0.005)
        except Exception as e:                      # pragma: no cover
            errors.append(e)

    def burst(p):
        try:
            for j in range(200):
                p.api.create(_mk_unit(f"u{j:04d}"))
        except Exception as e:                      # pragma: no cover
            errors.append(e)

    try:
        threads = ([threading.Thread(target=operator)]
                   + [threading.Thread(target=autoscaler_like)]
                   + [threading.Thread(target=burst, args=(p,))
                      for p in planes])
        for t in threads[1:]:
            t.start()
        threads[0].start()
        threads[0].join()
        stop.set()
        for t in threads[1:]:
            t.join()
        assert not errors
        # quiesce to a known size through the same contended interface
        assert syncer.resize_shards(2) is not None
        assert syncer.resize_shards(2) == {}        # idempotent no-op
        assert syncer.num_shards == 2
        assert len(syncer.down.shards) == 2
        assert [c.shard_id for c in syncer.down.shards] == [0, 1]
        for reg in syncer.tenants.values():
            shard = syncer.down.owner[reg.plane.name]
            assert shard in syncer.down.shards
            assert shard.shard_id == syncer.down.ring.shard_for(reg.uid)
        total = len(planes) * 200
        assert wait_for(
            lambda: super_api.store.count("WorkUnit") >= total, timeout=60.0)
    finally:
        syncer.stop()
        ex.shutdown()
        super_api.close()


# ------------------------------------------------- framework + /healthz wire

def test_framework_autoscale_off_is_fixed_size():
    fw = VirtualClusterFramework(num_nodes=2, scan_interval=0.0,
                                 heartbeat_interval=0.5, syncer_shards=2)
    assert fw.autoscaler is None
    with fw:
        plane = fw.add_tenant("fixed")
        fw.submit(plane, fw.make_unit("job", chips=1))
        fw.wait_ready(plane, "default", "job", timeout=30)
        assert fw.syncer.num_shards == 2            # exactly as configured
        assert fw.executor.pool_size == 8


def test_framework_autoscale_healthz_reports_loop_state():
    policy = ScalingPolicy(min_shards=1, max_shards=4, min_pool=2, max_pool=8)
    fw = VirtualClusterFramework(num_nodes=2, scan_interval=0.0,
                                 heartbeat_interval=0.5, autoscale=True,
                                 autoscale_policy=policy,
                                 autoscale_interval=0.05)
    assert fw.autoscaler is not None
    with fw:
        port = fw.serve_metrics(port=0)
        plane = fw.add_tenant("scaled")
        fw.submit(plane, fw.make_unit("job", chips=1))
        fw.wait_ready(plane, "default", "job", timeout=30)
        assert wait_for(lambda: fw.autoscaler.state()["ticks"] >= 2)
        health = json.load(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=5))
        assert all(health["controllers"].values())
        assert "autoscaler" in health["controllers"]    # sixth controller
        scaler = health["autoscaler"]
        assert scaler["targets"]["shards"] >= 1
        assert scaler["targets"]["executor_pool"] >= 2
        assert "cooldown_remaining_s" in scaler
        assert "last_decision" in scaler
        snap = json.load(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5))
        assert "autoscaler_target_shards" in snap["gauges"]
        assert "autoscaler_target_pool" in snap["gauges"]
        assert "autoscaler_ticks" in snap["gauges"]
