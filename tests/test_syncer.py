"""Syncer: downward/upward synchronization, namespace translation, race
remediation via the periodic scan, vNode lifecycle."""
import time

import pytest

from repro.core import (APIServer, Namespace, Secret, Service,
                        Syncer, TenantControlPlane, WorkUnit, ns_prefix)


@pytest.fixture
def rig():
    super_api = APIServer("super")
    syncer = Syncer(super_api, downward_workers=4, upward_workers=4,
                    scan_interval=0.0)
    plane = TenantControlPlane("acme")
    prefix = syncer.register_tenant(plane, "uid-1")
    syncer.start()
    yield super_api, syncer, plane, prefix
    syncer.stop()
    super_api.close()


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


def test_ns_prefix_deterministic():
    assert ns_prefix("a", "uid") == ns_prefix("a", "uid")
    assert ns_prefix("a", "uid1") != ns_prefix("a", "uid2")


def test_downward_sync_creates_prefixed_objects(rig):
    super_api, syncer, plane, prefix = rig
    ns = Namespace()
    ns.metadata.name = "default"
    plane.api.create(ns)
    plane.api.create(mk_unit("job"))
    assert wait_for(lambda: super_api.store.count("WorkUnit") == 1)
    sobj = super_api.list("WorkUnit")[0]
    assert sobj.metadata.namespace == f"{prefix}-default"
    assert sobj.metadata.annotations["vc/tenant"] == "acme"
    # the super namespace object was auto-created
    super_api.get("Namespace", "", f"{prefix}-default")


def test_secrets_and_services_sync_down(rig):
    super_api, syncer, plane, prefix = rig
    sec = Secret()
    sec.metadata.name = "tok"
    sec.metadata.namespace = "default"
    sec.data["k"] = "v"
    plane.api.create(sec)
    svc = Service()
    svc.metadata.name = "svc"
    svc.metadata.namespace = "default"
    svc.virtual_ip = "10.0.0.1"
    plane.api.create(svc)
    assert wait_for(lambda: super_api.store.count("Secret") == 1)
    assert wait_for(lambda: super_api.store.count("Service") == 1)


def test_upward_status_sync(rig):
    super_api, syncer, plane, prefix = rig
    plane.api.create(mk_unit("job"))
    assert wait_for(lambda: super_api.store.count("WorkUnit") == 1)
    super_api.update_status("WorkUnit", f"{prefix}-default", "job",
                            lambda u: setattr(u.status, "phase", "Ready"))
    assert wait_for(lambda: plane.api.get(
        "WorkUnit", "default", "job").status.phase == "Ready")


def test_tenant_delete_propagates_down(rig):
    super_api, syncer, plane, prefix = rig
    plane.api.create(mk_unit("job"))
    assert wait_for(lambda: super_api.store.count("WorkUnit") == 1)
    plane.api.delete("WorkUnit", "default", "job")
    assert wait_for(lambda: super_api.store.count("WorkUnit") == 0)


def test_spec_update_propagates_down(rig):
    super_api, syncer, plane, prefix = rig
    plane.api.create(mk_unit("job"))
    assert wait_for(lambda: super_api.store.count("WorkUnit") == 1)
    u = plane.api.get("WorkUnit", "default", "job")
    u.spec.chips = 7
    plane.api.update(u)
    assert wait_for(lambda: super_api.list("WorkUnit")[0].spec.chips == 7)


def test_scan_remediates_out_of_band_super_deletion(rig):
    """Paper §III-C: rare permanent inconsistencies are remediated by the
    periodic scan re-sending objects to the worker queues."""
    super_api, syncer, plane, prefix = rig
    plane.api.create(mk_unit("job"))
    assert wait_for(lambda: super_api.store.count("WorkUnit") == 1)
    # someone deletes the super copy behind the syncer's back
    super_api.delete("WorkUnit", f"{prefix}-default", "job")
    assert super_api.store.count("WorkUnit") == 0
    fixes = syncer.scan_once()
    assert fixes >= 1
    assert wait_for(lambda: super_api.store.count("WorkUnit") == 1)


def test_scan_remediates_orphaned_super_object(rig):
    super_api, syncer, plane, prefix = rig
    # an orphan appears in the super cluster in the tenant's namespace
    orphan = mk_unit("ghost", f"{prefix}-default")
    super_api.create(orphan)
    syncer.scan_once()
    assert wait_for(lambda: super_api.store.count("WorkUnit") == 0)


def test_unregister_tenant_cleans_super(rig):
    super_api, syncer, plane, prefix = rig
    plane.api.create(mk_unit("job"))
    assert wait_for(lambda: super_api.store.count("WorkUnit") == 1)
    syncer.unregister_tenant("acme")
    assert super_api.store.count("WorkUnit") == 0


# ------------------------------------------------------------ sharded syncer

@pytest.fixture
def sharded_rig():
    super_api = APIServer("super")
    syncer = Syncer(super_api, downward_workers=8, upward_workers=4,
                    scan_interval=0.0, shards=4, downward_batch=4)
    planes = [TenantControlPlane(f"t{i:02d}") for i in range(8)]
    prefixes = [syncer.register_tenant(p, f"uid-{i}")
                for i, p in enumerate(planes)]
    syncer.start()
    yield super_api, syncer, planes, prefixes
    syncer.stop()
    super_api.close()


def test_sharded_downward_sync_all_tenants(sharded_rig):
    super_api, syncer, planes, prefixes = sharded_rig
    for p in planes:
        for j in range(5):
            p.api.create(mk_unit(f"job{j}"))
    assert wait_for(lambda: super_api.store.count("WorkUnit") == 40)
    # every tenant's objects landed under its own prefix
    namespaces = {u.metadata.namespace for u in super_api.list("WorkUnit")}
    assert namespaces == {f"{pre}-default" for pre in prefixes}


def test_sharded_upward_sync_routes_back_to_owner(sharded_rig):
    super_api, syncer, planes, prefixes = sharded_rig
    for p in planes:
        p.api.create(mk_unit("job"))
    assert wait_for(lambda: super_api.store.count("WorkUnit") == 8)
    for pre in prefixes:
        super_api.update_status("WorkUnit", f"{pre}-default", "job",
                                lambda u: setattr(u.status, "phase", "Ready"))
    assert wait_for(lambda: all(
        p.api.get("WorkUnit", "default", "job").status.phase == "Ready"
        for p in planes))


def test_sharded_tenants_partition_covers_multiple_shards(sharded_rig):
    super_api, syncer, planes, prefixes = sharded_rig
    shard_ids = {syncer.down.owner[p.name].shard_id for p in planes}
    assert len(shard_ids) > 1          # 8 tenants over 4 shards: must spread
    # tenants on the same shard share that shard's fair queue registration
    for p in planes:
        assert p.name in syncer.down.owner[p.name].queue._weights


def test_sharded_scan_remediates_to_owning_shard(sharded_rig):
    super_api, syncer, planes, prefixes = sharded_rig
    planes[0].api.create(mk_unit("job"))
    assert wait_for(lambda: super_api.store.count("WorkUnit") == 1)
    super_api.delete("WorkUnit", f"{prefixes[0]}-default", "job")
    fixes = syncer.scan_once()
    assert fixes >= 1
    assert wait_for(lambda: super_api.store.count("WorkUnit") == 1)


def test_sharded_burst_no_starvation(sharded_rig):
    """Liveness under a greedy burst sharing a shard: the regular tenant's
    single unit syncs promptly and the burst still completes."""
    super_api, syncer, planes, prefixes = sharded_rig
    # find two tenants on the same shard
    by_shard = {}
    for p in planes:
        by_shard.setdefault(syncer.down.owner[p.name].shard_id, []).append(p)
    cohabitants = next(v for v in by_shard.values() if len(v) >= 2)
    greedy, regular = cohabitants[0], cohabitants[1]
    for j in range(200):
        greedy.api.create(mk_unit(f"g{j:04d}"))
    regular.api.create(mk_unit("r0"))
    rpre = syncer.tenants[regular.name].prefix
    gpre = syncer.tenants[greedy.name].prefix
    assert wait_for(lambda: _count_ns(super_api, f"{rpre}-default") >= 1,
                    timeout=10)
    assert wait_for(
        lambda: _count_ns(super_api, f"{gpre}-default") == 200, timeout=30)


def test_sharded_mixed_churn_fast_lane(sharded_rig):
    """Create/update/delete mix through the batched fast lane: end state in
    the super cluster matches the tenants' final specs exactly."""
    super_api, syncer, planes, prefixes = sharded_rig
    per_tenant = 12
    for p in planes:
        for j in range(per_tenant):
            p.api.create(mk_unit(f"u{j:02d}"))
    total = len(planes) * per_tenant
    assert wait_for(lambda: super_api.store.count("WorkUnit") == total)
    # churn: update the first third, delete the last third
    k = per_tenant // 3
    for p in planes:
        for j in range(k):
            u = p.api.get("WorkUnit", "default", f"u{j:02d}")
            u.spec.chips = 42
            p.api.update(u)
        for j in range(per_tenant - k, per_tenant):
            p.api.delete("WorkUnit", "default", f"u{j:02d}")
    expected = len(planes) * (per_tenant - k)
    assert wait_for(lambda: super_api.store.count("WorkUnit") == expected)
    assert wait_for(lambda: sum(
        1 for u in super_api.list("WorkUnit") if u.spec.chips == 42
    ) == len(planes) * k)
    # updated super copies keep their identity (update, not delete+create)
    for pre in prefixes:
        u = super_api.get("WorkUnit", f"{pre}-default", "u00")
        assert u.spec.chips == 42
        assert u.metadata.uid


def test_batched_update_preserves_super_status(sharded_rig):
    """The batched spec-update path must not clobber super-owned status."""
    super_api, syncer, planes, prefixes = sharded_rig
    p, pre = planes[0], prefixes[0]
    p.api.create(mk_unit("job"))
    assert wait_for(lambda: _count_ns(super_api, f"{pre}-default") == 1)
    super_api.update_status("WorkUnit", f"{pre}-default", "job",
                            lambda u: setattr(u.status, "phase", "Ready"))
    assert wait_for(lambda: super_api.get(
        "WorkUnit", f"{pre}-default", "job").status.phase == "Ready")
    # wait until the super informer cache has seen the status write, so the
    # batched update builds on it
    sup_inf = syncer._super_informers["WorkUnit"]
    assert wait_for(lambda: (
        (c := sup_inf.cache.get(f"{pre}-default", "job")) is not None
        and c.status.phase == "Ready"))
    u = p.api.get("WorkUnit", "default", "job")
    u.spec.chips = 3
    p.api.update(u)
    assert wait_for(lambda: super_api.get(
        "WorkUnit", f"{pre}-default", "job").spec.chips == 3)
    assert super_api.get(
        "WorkUnit", f"{pre}-default", "job").status.phase == "Ready"


def test_wrr_fairness_deterministic_under_batching():
    """Fig.11 guarantee at the queue level, with batch draining: a regular
    tenant's item is dispatched within one WRR round (== a few batches) of a
    200-item greedy backlog, never behind the whole burst."""
    from repro.core import FairWorkQueue
    q = FairWorkQueue("wrr", fair=True)
    q.register_tenant("greedy", 1)
    q.register_tenant("regular", 1)
    for j in range(200):
        q.add("greedy", f"g{j:04d}")
    q.add("regular", "r0")
    batch_size = 8
    dispatched_before_regular = 0
    for _ in range(200 + 1):
        batch = q.get_batch(batch_size, timeout=0.1)
        assert batch, "queue drained without dispatching the regular item"
        if any(t == "regular" for t, _ in batch):
            break
        dispatched_before_regular += len(batch)
        for item in batch:
            q.done(item)
    # one WRR quantum of the greedy backlog at most, not the full 200
    assert dispatched_before_regular <= 2 * batch_size


def _count_ns(api, ns):
    return sum(1 for u in api.list("WorkUnit") if u.metadata.namespace == ns)
