"""Audit trail + usage metering: exact per-tenant attribution through the
batched downward/upward fast lanes and the serving path, rolling-window
semantics, the dominant-share noisy-neighbor detector, advisory WRR
dampening, bounded audit rings with filters, and the observe_n batched
bookkeeping regression."""
import threading
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import (APIServer, AuditLog, Autoscaler, Namespace,
                        ScalingPolicy, Syncer, TenantControlPlane, UsageMeter,
                        VirtualClusterFramework, WorkUnit)
from repro.core.metering import DETECTOR_AXES
from repro.models import init_params
from repro.serving import GenerationEngine, ServingFleet


def wait_for(cond, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.005)
    return False


def mk_unit(name, ns="bench"):
    u = WorkUnit()
    u.metadata.name = name
    u.metadata.namespace = ns
    return u


# --------------------------------------------------------------- UsageMeter

def test_meter_window_expiry_and_exact_totals():
    t = [100.0]
    m = UsageMeter(window_s=10.0, buckets=5, clock=lambda: t[0])
    m.add("a", "api_requests", 3.0)
    t[0] = 104.0
    m.add("a", "api_requests", 2.0)
    assert m.windowed("a", "api_requests") == 5.0
    # first sample ages out of the window; lifetime totals never do
    t[0] = 111.0
    assert m.windowed("a", "api_requests") == 2.0
    t[0] = 200.0
    assert m.windowed("a", "api_requests") == 0.0
    assert m.totals() == {"a": {"api_requests": 5.0}}


def test_noisy_detector_dominant_share_scoring():
    t = [50.0]
    m = UsageMeter(window_s=100.0, clock=lambda: t[0])
    # 3 tenants active on the tokens axis; "hog" holds ~89% of it
    m.add("hog", "tokens", 800.0)
    m.add("b", "tokens", 50.0)
    m.add("c", "tokens", 50.0)
    shares = m.dominant_shares()
    score, rec = shares["hog"]
    assert rec["axis"] == "tokens"
    assert score == pytest.approx((800 / 900) / (1 / 3))
    noisy = m.noisy()          # default threshold 2.0
    assert [r["tenant"] for r in noisy] == ["hog"]
    assert noisy[0]["score"] >= 2.0
    # balanced tenants never alert
    assert all(shares[x][0] < 2.0 for x in ("b", "c"))


def test_noisy_detector_lone_tenant_and_latency_axes_excluded():
    m = UsageMeter()
    m.add("solo", "tokens", 1e9)
    assert m.noisy() == []       # lone tenant IS its fair share
    # latency-shaped series never participate in scoring
    m.add("slow", "ttft_s", 1e9)
    m.add("fast", "ttft_s", 1.0)
    assert "ttft_s" not in DETECTOR_AXES
    assert m.noisy() == []


def test_meter_state_payload_shape():
    m = UsageMeter()
    m.add_many("a", (("api_requests", 2.0), ("object_bytes", 100.0)))
    st = m.state()
    assert st["window"]["api_requests"] == {"a": 2.0}
    assert st["totals"]["a"]["object_bytes"] == 100.0
    assert "a" in st["dominant_share"]
    assert st["noisy"] == []
    ns = m.noisy_state()
    assert ns["noisy_threshold"] == 2.0 and ns["noisy"] == []


# ----------------------------------------------------------------- AuditLog

def test_audit_ring_bounded_counts_exact_and_filters():
    a = AuditLog(per_tenant_capacity=8)
    for i in range(20):
        a.record("a", "create", "WorkUnit", "ns", f"u{i}", "ok", 0.001)
    a.record("a", "delete", "WorkUnit", "ns", "u0", "ok", 0.001)
    a.record("b", "create", "Namespace", "", "ns", "ok", 0.001)
    # ring evicts, counters do not
    assert a.stats()["retained"] == 8 + 1
    assert a.counts()["a"] == {"create": 20, "delete": 1}
    assert a.counts()["b"] == {"create": 1}
    assert len(a.records(tenant="a", verb="create")) == 7   # 8-ring, 1 delete
    assert len(a.records(kind="Namespace")) == 1
    assert len(a.records(tenant="a", limit=3)) == 3
    recs = a.records(tenant="a")
    assert recs == sorted(recs, key=lambda r: r["seq"])
    # a batch of N counts N
    a.record("a", "update_status_batch", "WorkUnit", "ns", "u1", "ok",
             0.002, count=5)
    assert a.counts()["a"]["update_status_batch"] == 5


def test_audit_attach_and_failure_outcome():
    api = APIServer("t0")
    a = AuditLog()
    a.attach(api, "t0")
    ns = Namespace()
    ns.metadata.name = "bench"
    api.create(ns)
    api.create(mk_unit("u0"))
    with pytest.raises(Exception):
        api.get("WorkUnit", "bench", "nope")
    recs = a.records(tenant="t0")
    assert [r["verb"] for r in recs] == ["create", "create", "get"]
    assert recs[1]["kind"] == "WorkUnit" and recs[1]["name"] == "u0"
    assert recs[1]["outcome"] == "ok" and recs[1]["latency_s"] >= 0.0
    assert recs[2]["outcome"] == "NotFoundError"
    api.close()


# ------------------------------------------- sync-lane attribution (exact)

@pytest.fixture
def metered_rig():
    """Sharded syncer with batched fast lanes, meter + audit wired the way
    the framework wires them (syncer property, plane clients, plane
    stores)."""
    meter = UsageMeter()
    audit = AuditLog()
    super_api = APIServer("super")
    syncer = Syncer(super_api, downward_workers=4, upward_workers=4,
                    scan_interval=0.0, shards=2, downward_batch=8,
                    upward_shards=2, upward_batch=8)
    syncer.meter = meter
    planes = [TenantControlPlane(f"t{i:02d}") for i in range(3)]
    for i, p in enumerate(planes):
        p.api.meter = meter
        p.api.audit = audit
        p.api.store.meter = meter
        syncer.register_tenant(p, f"uid-{i:02d}")
    syncer.start()
    for p in planes:
        ns = Namespace()
        ns.metadata.name = "bench"
        p.api.create(ns)
    yield meter, audit, super_api, syncer, planes
    syncer.stop()
    super_api.close()


def test_downward_batched_attribution_exact(metered_rig):
    """3 tenants x 12 creates through the batched downward fast lane
    (shards=2, batch=8): every tenant must be attributed EXACTLY 12
    down_items — none lost, none credited to a neighbor — and the audit
    trail must show exactly 12 WorkUnit creates per tenant."""
    meter, audit, super_api, syncer, planes = metered_rig
    per_tenant = 12
    threads = [threading.Thread(
        target=lambda p=p: [p.api.create(mk_unit(f"u{j:03d}"))
                            for j in range(per_tenant)])
        for p in planes]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = len(planes) * per_tenant
    assert wait_for(
        lambda: super_api.store.count("WorkUnit") >= total, timeout=30.0)
    assert wait_for(lambda: all(
        meter.windowed(p.name, "down_items") >= per_tenant for p in planes),
        timeout=10.0)
    for p in planes:
        assert meter.windowed(p.name, "down_items") == float(per_tenant)
        assert meter.windowed(p.name, "down_bytes") > 0.0
        # tenant store writes metered as object bytes
        assert meter.windowed(p.name, "object_bytes") > 0.0
        # every API request attributed (creates + ns create at minimum)
        assert meter.windowed(p.name, "api_requests") >= per_tenant + 1
        assert len(audit.records(tenant=p.name, verb="create",
                                 kind="WorkUnit")) == per_tenant
    # nothing attributed to tenants that don't exist
    assert set(meter.totals()) == {p.name for p in planes}


def test_upward_batched_attribution_exact():
    """Deterministic upward workload (the status_storm staging trick): both
    sides pre-staged and every super copy flapped to Ready BEFORE the
    syncer starts, so the cold informer replay yields exactly one upward
    key per object — the coalesced lane must commit each through
    update_status_batch on the right tenant's OWN apiserver, landing audit
    batch counts and up_items at exactly 12 per tenant with zero
    duplicates."""
    meter = UsageMeter()
    audit = AuditLog()
    super_api = APIServer("super")
    syncer = Syncer(super_api, downward_workers=4, upward_workers=4,
                    scan_interval=0.0, shards=2, downward_batch=8,
                    upward_shards=2, upward_batch=8)
    syncer.meter = meter
    planes = [TenantControlPlane(f"t{i:02d}") for i in range(3)]
    for i, p in enumerate(planes):
        p.api.meter = meter
        p.api.audit = audit
        p.api.store.meter = meter
        syncer.register_tenant(p, f"uid-{i:02d}")
    per_tenant = 12
    prefixes = {p.name: syncer.tenants[p.name].prefix for p in planes}
    try:
        for p in planes:
            ns = Namespace()
            ns.metadata.name = "bench"
            p.api.create(ns)
            super_ns = f"{prefixes[p.name]}-bench"
            sns = Namespace()
            sns.metadata.name = super_ns
            super_api.create(sns)
            for j in range(per_tenant):
                p.api.create(mk_unit(f"u{j:03d}"))
                proj = mk_unit(f"u{j:03d}")
                proj.metadata.namespace = super_ns
                super_api.create(proj)
            for j in range(per_tenant):
                super_api.update_status(
                    "WorkUnit", super_ns, f"u{j:03d}",
                    lambda u: setattr(u.status, "phase", "Ready"))
        # audit counts so far are the tenant-side staging writes only
        staged = audit.counts()
        assert all(staged[p.name]["create"] == per_tenant + 1
                   for p in planes)
        syncer.start()

        def converged(p):
            units = p.api.list("WorkUnit", "bench")
            return (len(units) >= per_tenant
                    and all(u.status.phase == "Ready" for u in units))
        assert wait_for(lambda: all(converged(p) for p in planes),
                        timeout=30.0)
        counts = audit.counts()
        for p in planes:
            up = meter.windowed(p.name, "up_items")
            batched = (counts[p.name].get("update_status_batch", 0)
                       + counts[p.name].get("update_status", 0))
            # the two independent hooks (meter at the lane, audit at the
            # tenant apiserver) must agree exactly: one commit per object
            assert up == float(per_tenant)
            assert batched == per_tenant
            # batched fast lane actually exercised: at least one multi-item
            # update_status_batch record, each attributed to its own tenant
            recs = audit.records(tenant=p.name, verb="update_status_batch")
            assert recs and max(r["count"] for r in recs) > 1
            assert all(r["tenant"] == p.name for r in recs)
            # fair-queue occupancy accrued per tenant on the sync lanes
            assert meter.windowed(p.name, "queue_items") > 0.0
    finally:
        syncer.stop()
        super_api.close()


def test_meter_off_leaves_no_attribution(metered_rig):
    """The OFF contract: a plane whose hooks are detached mid-flight stops
    accruing, while attached planes keep exact attribution."""
    meter, audit, super_api, syncer, planes = metered_rig
    dark = planes[0]
    dark.api.meter = None
    dark.api.audit = None
    dark.api.store.meter = None
    before = meter.windowed(dark.name, "api_requests")
    dark.api.create(mk_unit("dark0"))
    assert meter.windowed(dark.name, "api_requests") == before
    assert audit.records(tenant=dark.name, verb="create",
                         kind="WorkUnit") == []


# ----------------------------------------------------- serving-path metering

@pytest.fixture(scope="module")
def model():
    cfg = reduced(get_config("qwen2-7b"), n_layers=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_serving_path_attribution_exact(model):
    """Data-plane axes: requests, generated tokens, slot-seconds, and TTFT
    attributed per tenant at request finish — exact token/request counts
    for a deterministic workload."""
    cfg, params = model
    fleet = ServingFleet(
        lambda: GenerationEngine(cfg, params, slots=2, max_len=48,
                                 compute_dtype=jax.numpy.float32),
        replicas=1, scan_interval=0.05)
    fw = VirtualClusterFramework(num_nodes=2, scan_interval=0.0,
                                 heartbeat_interval=3600, metering=True)
    fleet.attach(fw)
    with fw:
        fleet.register_tenant("alpha")
        fleet.register_tenant("beta")
        assert wait_for(lambda: fleet.live_replicas() == 1, timeout=20)
        rng = np.random.default_rng(7)
        for _ in range(3):
            fleet.submit("alpha", rng.integers(0, cfg.vocab, 8),
                         max_new_tokens=4)
        fleet.submit("beta", rng.integers(0, cfg.vocab, 8), max_new_tokens=4)
        done = fleet.wait_completed(4, timeout=60)
        assert len(done) == 4
        m = fw.meter
        assert m.windowed("alpha", "serving_requests") == 3.0
        assert m.windowed("alpha", "tokens") == 12.0
        assert m.windowed("beta", "serving_requests") == 1.0
        assert m.windowed("beta", "tokens") == 4.0
        assert m.windowed("alpha", "slot_seconds") > 0.0
        assert m.windowed("alpha", "ttft_s") >= 0.0


def test_scan_observe_n_regression_and_queue_metering(model):
    """``scan()`` flushes scheduler wait stats with observe_n's
    PER-OBSERVATION value: n=4 waits of mean 0.25s must land as sum=1.0,
    count=4, max=0.25 (the old code passed mean*n and inflated sum to
    mean*n^2), and the meter sees 4 queue_items / 1.0 queue_wait_s."""
    cfg, params = model
    fleet = ServingFleet(
        lambda: GenerationEngine(cfg, params, slots=2, max_len=48,
                                 compute_dtype=jax.numpy.float32),
        replicas=0, scan_interval=3600)
    m = UsageMeter()
    fleet.meter = m
    fleet.scheduler.tenant_wait_stats = lambda: {"a": (4, 0.25)}
    fleet.scan()
    s = fleet.metrics.summary("serving_queue_wait_seconds", tenant="a")
    assert s["sum"] == pytest.approx(1.0)
    assert s["count"] == 4
    assert s["max"] == pytest.approx(0.25)
    assert m.windowed("a", "queue_items") == 4.0
    assert m.windowed("a", "queue_wait_s") == pytest.approx(1.0)


# ------------------------------------------------ advisory autotune dampening

def test_autotune_dampens_noisy_tenant_weights():
    """The detector is advisory input to the WRR autotuner: with equal wait
    profiles nobody's weight moves, but a tenant flagged noisy is dampened
    to noisy_dampen x its configured weight (before clamping)."""
    super_api = APIServer("super")
    syncer = Syncer(super_api, downward_workers=2, upward_workers=2,
                    scan_interval=0.0, shards=1)
    planes = [TenantControlPlane(f"t{i}", weight=4) for i in range(3)]
    for i, p in enumerate(planes):
        syncer.register_tenant(p, f"uid-{i}")
    syncer.start()
    meter = UsageMeter()
    policy = ScalingPolicy()
    scaler = Autoscaler(syncer, None, policy=policy, interval=3600)
    scaler.meter = meter
    try:
        q = syncer.down.shards[0].queue
        # equal wait profiles: every tenant's boost factor is exactly 1.0
        for p in planes:
            q.per_tenant_wait.setdefault(p.name, []).extend([0.2] * 10)
        # t0 hogs ~96% of the tokens axis -> dominant share 2.88 >= 2.0
        meter.add("t0", "tokens", 960.0)
        meter.add("t1", "tokens", 20.0)
        meter.add("t2", "tokens", 20.0)
        assert [r["tenant"] for r in meter.noisy()] == ["t0"]
        scaler._autotune_weights()
        # noisy tenant halved (round(4 * 1.0 * 0.5) = 2); peers untouched
        assert q._weights["t0"] == 2
        assert q._weights.get("t1", 4) == 4
        assert q._weights.get("t2", 4) == 4
        # surfaced for /healthz via autoscaler state
        assert "t0" in scaler.state()["noisy_neighbors"]
        reg = syncer.controllers[0].metrics
        assert reg.counter("autoscaler_noisy_dampened", tenant="t0") >= 1
    finally:
        scaler.stop()
        syncer.stop()
        super_api.close()


def test_autotune_without_meter_unchanged():
    """No meter attached: equal wait profiles leave every weight alone
    (the advisory path is strictly additive)."""
    super_api = APIServer("super")
    syncer = Syncer(super_api, downward_workers=2, upward_workers=2,
                    scan_interval=0.0, shards=1)
    planes = [TenantControlPlane(f"t{i}", weight=4) for i in range(3)]
    for i, p in enumerate(planes):
        syncer.register_tenant(p, f"uid-{i}")
    syncer.start()
    scaler = Autoscaler(syncer, None, policy=ScalingPolicy(), interval=3600)
    try:
        q = syncer.down.shards[0].queue
        for p in planes:
            q.per_tenant_wait.setdefault(p.name, []).extend([0.2] * 10)
        changed = scaler._autotune_weights()
        assert changed == 0
        assert all(q._weights.get(p.name, 4) == 4 for p in planes)
        assert scaler.state()["noisy_neighbors"] == {}
    finally:
        scaler.stop()
        syncer.stop()
        super_api.close()


# ------------------------------------------------- concurrent scrape safety

def test_concurrent_meter_and_audit_scrapes_never_tear():
    """Hammer reads (state/records/counts/noisy) against concurrent writes:
    no exceptions, monotone counters, and the final exact counts match the
    writes issued."""
    m = UsageMeter(window_s=60.0)
    a = AuditLog(per_tenant_capacity=64)
    stop = threading.Event()
    errors = []

    def writer(tenant):
        for i in range(400):
            m.add_many(tenant, (("api_requests", 1.0), ("tokens", 2.0)))
            a.record(tenant, "create", "WorkUnit", "ns", f"u{i}", "ok", 0.0)

    def reader():
        while not stop.is_set():
            try:
                m.state()
                m.noisy()
                a.state(limit=16)
                a.records(verb="create")
            except Exception as e:          # pragma: no cover
                errors.append(e)
                return

    readers = [threading.Thread(target=reader) for _ in range(3)]
    writers = [threading.Thread(target=writer, args=(f"t{i}",))
               for i in range(4)]
    for t in readers + writers:
        t.start()
    for t in writers:
        t.join()
    stop.set()
    for t in readers:
        t.join()
    assert errors == []
    assert all(m.windowed(f"t{i}", "api_requests") == 400.0
               for i in range(4))
    counts = a.counts()
    assert all(counts[f"t{i}"]["create"] == 400 for i in range(4))
    assert a.stats()["retained"] == 4 * 64       # rings stayed bounded
