"""Upward pipeline v2: Event dedup/aggregation, tenant-visible events,
sharded upward routing, latest-wins coalescing + batched status writes,
live upward fleet resizing, and the per-item fallback mode."""
import time

import pytest

from repro.core import (APIServer, EventRecorder, Namespace, Syncer,
                        TenantControlPlane, WorkUnit)
from repro.core.upward import event_name


def wait_for(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.01)
    return False


def mk_unit(name, ns="default"):
    u = WorkUnit()
    u.metadata.name = name
    u.metadata.namespace = ns
    return u


# ------------------------------------------------------------- EventRecorder

def test_event_recorder_compresses_repeats():
    api = APIServer("super")
    rec = EventRecorder(api, "kubelet", host="node-0")
    for i in range(5):
        rec.record("WorkUnit", "ns1", "job", "Started", f"attempt {i}")
    events = api.list("Event", "ns1")
    assert len(events) == 1                       # 5 records, ONE object
    ev = events[0]
    assert ev.count == 5
    assert ev.reason == "Started"
    assert ev.involved_name == "job"
    assert ev.message == "attempt 4"              # latest message wins
    assert ev.first_timestamp <= ev.last_timestamp
    api.close()


def test_event_recorder_distinct_reasons_do_not_collide():
    api = APIServer("super")
    rec = EventRecorder(api, "kubelet")
    rec.record("WorkUnit", "ns1", "job", "Started")
    rec.record("WorkUnit", "ns1", "job", "Failed", type="Warning")
    events = api.list("Event", "ns1")
    assert len(events) == 2
    assert {e.reason for e in events} == {"Started", "Failed"}
    assert all(e.count == 1 for e in events)
    api.close()


def test_event_name_deterministic():
    assert (event_name("WorkUnit", "job", "Started", "kubelet")
            == event_name("WorkUnit", "job", "Started", "kubelet"))
    assert (event_name("WorkUnit", "job", "Started", "kubelet")
            != event_name("WorkUnit", "job", "Failed", "kubelet"))


# ---------------------------------------------------------- upward pipeline

@pytest.fixture
def rig():
    """4 upward shards, coalescing on — the default architecture."""
    super_api = APIServer("super")
    syncer = Syncer(super_api, downward_workers=4, upward_workers=8,
                    scan_interval=0.0, shards=2, downward_batch=4,
                    upward_shards=4, upward_batch=8)
    planes = [TenantControlPlane(f"t{i:02d}") for i in range(8)]
    prefixes = [syncer.register_tenant(p, f"uid-{i}")
                for i, p in enumerate(planes)]
    syncer.start()
    for p in planes:
        ns = Namespace()
        ns.metadata.name = "default"
        p.api.create(ns)
    yield super_api, syncer, planes, prefixes
    syncer.stop()
    super_api.close()


def test_upward_shards_partition_tenants(rig):
    super_api, syncer, planes, prefixes = rig
    assert syncer.num_upward_shards == 4
    shard_ids = {syncer.up.owner[p.name].shard_id for p in planes}
    assert len(shard_ids) > 1          # 8 tenants over 4 shards: must spread
    for p in planes:
        reg = syncer.tenants[p.name]
        shard = syncer.up.owner[p.name]
        assert p.name in shard.queue._weights
        # upward and downward placements are independent rings — but both
        # must agree with their own ring
        assert shard.shard_id == syncer.up.ring.shard_for(reg.uid)


def test_status_syncs_up_through_shards(rig):
    super_api, syncer, planes, prefixes = rig
    for p in planes:
        p.api.create(mk_unit("job"))
    assert wait_for(lambda: super_api.store.count("WorkUnit") == 8)
    for pre in prefixes:
        super_api.update_status("WorkUnit", f"{pre}-default", "job",
                                lambda u: setattr(u.status, "phase", "Ready"))
    assert wait_for(lambda: all(
        p.api.get("WorkUnit", "default", "job").status.phase == "Ready"
        for p in planes))


def test_status_storm_coalesces_to_final_state(rig):
    """Latest-wins: rapid flaps on many units converge every tenant copy to
    the final phase, with queue dedup absorbing intermediate flaps."""
    super_api, syncer, planes, prefixes = rig
    per_tenant = 20
    for p in planes:
        for j in range(per_tenant):
            p.api.create(mk_unit(f"u{j:03d}"))
    total = len(planes) * per_tenant
    assert wait_for(lambda: super_api.store.count("WorkUnit") == total)
    for pre in prefixes:
        ns = f"{pre}-default"
        for j in range(per_tenant):
            for phase in ("Running", "Pending", "Running", "Ready"):
                super_api.update_status(
                    "WorkUnit", ns, f"u{j:03d}",
                    lambda u, ph=phase: setattr(u.status, "phase", ph))

    def converged(p):
        units = p.api.list("WorkUnit", "default")
        return (len(units) == per_tenant
                and all(u.status.phase == "Ready" for u in units))
    assert wait_for(lambda: all(converged(p) for p in planes), timeout=30.0)


def test_super_events_visible_in_tenant_plane(rig):
    """The tenant-visibility story: Events recorded in the super cluster
    appear in the owning tenant's control plane with their dedup counts."""
    super_api, syncer, planes, prefixes = rig
    p, pre = planes[0], prefixes[0]
    p.api.create(mk_unit("job"))
    assert wait_for(lambda: super_api.store.count("WorkUnit") >= 1)
    rec = EventRecorder(super_api, "kubelet", host="node-0")
    for _ in range(3):
        rec.record("WorkUnit", f"{pre}-default", "job", "Started",
                   "container started")

    def tenant_event():
        evs = p.api.list("Event", "default")
        return (len(evs) == 1 and evs[0].count == 3
                and evs[0].reason == "Started"
                and evs[0].involved_namespace == "default")
    assert wait_for(tenant_event)
    # other tenants never see it
    assert all(not q.api.list("Event", "default") for q in planes[1:])


def test_resize_upward_shards_live_migration(rig):
    super_api, syncer, planes, prefixes = rig
    per_tenant = 10
    for p in planes:
        for j in range(per_tenant):
            p.api.create(mk_unit(f"u{j:03d}"))
    total = len(planes) * per_tenant
    assert wait_for(lambda: super_api.store.count("WorkUnit") == total)
    # flap mid-resize: grow 4 -> 6, then shrink back to 2
    for pre in prefixes:
        ns = f"{pre}-default"
        for j in range(per_tenant):
            super_api.update_status(
                "WorkUnit", ns, f"u{j:03d}",
                lambda u: setattr(u.status, "phase", "Running"))
    moved = syncer.resize_upward_shards(6)
    assert isinstance(moved, dict)
    assert syncer.num_upward_shards == 6
    assert len(syncer.up.shards) == 6
    for pre in prefixes:
        ns = f"{pre}-default"
        for j in range(per_tenant):
            super_api.update_status(
                "WorkUnit", ns, f"u{j:03d}",
                lambda u: setattr(u.status, "phase", "Ready"))
    assert syncer.resize_upward_shards(2) == {} or True  # may move tenants
    assert syncer.num_upward_shards == 2
    # downward fleet untouched by upward resizes
    assert syncer.num_shards == 2
    for p in planes:
        reg = syncer.tenants[p.name]
        shard = syncer.up.owner[p.name]
        assert shard in syncer.up.shards
        assert shard.shard_id == syncer.up.ring.shard_for(reg.uid)

    def converged(p):
        units = p.api.list("WorkUnit", "default")
        return (len(units) == per_tenant
                and all(u.status.phase == "Ready" for u in units))
    assert wait_for(lambda: all(converged(p) for p in planes), timeout=30.0)


def test_resize_upward_idempotent_and_nonblocking():
    super_api = APIServer("super")
    syncer = Syncer(super_api, downward_workers=2, upward_workers=2,
                    scan_interval=0.0, upward_shards=2)
    try:
        assert syncer.resize_upward_shards(2) == {}     # no-op at current
        with syncer._resize_lock:
            # contended non-blocking call defers instead of parking
            assert syncer.resize_upward_shards(4, block=False) is None
        assert syncer.num_upward_shards == 2
    finally:
        super_api.close()


def test_per_item_mode_still_syncs():
    """upward_batch=1: the per-item baseline path stays correct."""
    super_api = APIServer("super")
    syncer = Syncer(super_api, downward_workers=4, upward_workers=4,
                    scan_interval=0.0, upward_batch=1)
    plane = TenantControlPlane("acme")
    prefix = syncer.register_tenant(plane, "uid-1")
    syncer.start()
    try:
        ns = Namespace()
        ns.metadata.name = "default"
        plane.api.create(ns)
        plane.api.create(mk_unit("job"))
        assert wait_for(lambda: super_api.store.count("WorkUnit") == 1)
        super_api.update_status("WorkUnit", f"{prefix}-default", "job",
                                lambda u: setattr(u.status, "phase", "Ready"))
        assert wait_for(lambda: plane.api.get(
            "WorkUnit", "default", "job").status.phase == "Ready")
        rec = EventRecorder(super_api, "kubelet")
        rec.record("WorkUnit", f"{prefix}-default", "job", "Ready")
        rec.record("WorkUnit", f"{prefix}-default", "job", "Ready")
        assert wait_for(lambda: any(
            e.count == 2 for e in plane.api.list("Event", "default")))
    finally:
        syncer.stop()
        super_api.close()


def test_scan_expires_stale_events_by_ttl():
    """k8s-style event TTL: the periodic scan drops Events (super and
    tenant copies) whose last_timestamp is older than event_ttl, so a
    churning tenant cannot accumulate events without bound."""
    super_api = APIServer("super")
    syncer = Syncer(super_api, downward_workers=2, upward_workers=2,
                    scan_interval=0.0, event_ttl=3600.0)
    plane = TenantControlPlane("acme")
    prefix = syncer.register_tenant(plane, "uid-1")
    syncer.start()
    try:
        ns = Namespace()
        ns.metadata.name = "default"
        plane.api.create(ns)
        plane.api.create(mk_unit("job"))
        assert wait_for(lambda: super_api.store.count("WorkUnit") == 1)
        rec = EventRecorder(super_api, "kubelet")
        rec.record("WorkUnit", f"{prefix}-default", "job", "Started")
        rec.record("WorkUnit", f"{prefix}-default", "job", "Fresh")
        assert wait_for(
            lambda: len(plane.api.list("Event", "default")) == 2)
        # age ONE super event (and its tenant copy) past the TTL
        for api in (super_api, plane.api):
            evs = [e for e in api.list("Event") if e.reason == "Started"]
            assert len(evs) == 1
            api.update_status(
                "Event", evs[0].metadata.namespace, evs[0].metadata.name,
                lambda e: setattr(e, "last_timestamp", time.time() - 7200))
        syncer.scan_once()
        assert {e.reason for e in super_api.list("Event")} == {"Fresh"}
        assert {e.reason for e in plane.api.list("Event")} == {"Fresh"}
        assert syncer.metrics.events_expired == 2
    finally:
        syncer.stop()
        super_api.close()


def test_unregister_tenant_sweeps_super_events():
    super_api = APIServer("super")
    syncer = Syncer(super_api, downward_workers=2, upward_workers=2,
                    scan_interval=0.0)
    plane = TenantControlPlane("acme")
    prefix = syncer.register_tenant(plane, "uid-1")
    syncer.start()
    try:
        ns = Namespace()
        ns.metadata.name = "default"
        plane.api.create(ns)
        plane.api.create(mk_unit("job"))
        assert wait_for(lambda: super_api.store.count("WorkUnit") == 1)
        EventRecorder(super_api, "kubelet").record(
            "WorkUnit", f"{prefix}-default", "job", "Started")
        assert super_api.store.count("Event") == 1
        syncer.unregister_tenant("acme")
        assert super_api.store.count("Event") == 0
        assert super_api.store.count("WorkUnit") == 0
    finally:
        syncer.stop()
        super_api.close()
