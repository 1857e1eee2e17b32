"""Consistent-hash shard ring + live resize: placement stability across
restarts, ~1/N remap on growth, no lost work mid-migration, per-shard
super-API clients."""
import time

import pytest

from repro.core import (APIServer, ShardRing, Syncer,
                        TenantControlPlane, WorkUnit, shard_for)


def wait_for(cond, timeout=15.0):
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


# ------------------------------------------------------------------- the ring

def test_ring_is_deterministic_across_instances():
    uids = [f"uid-{i}" for i in range(128)]
    a, b = ShardRing(4), ShardRing(4)
    assert [a.shard_for(u) for u in uids] == [b.shard_for(u) for u in uids]
    assert [shard_for(u, 4) for u in uids] == [a.shard_for(u) for u in uids]


def test_ring_spreads_and_stays_in_range():
    uids = [f"uid-{i}" for i in range(512)]
    placed = [ShardRing(8).shard_for(u) for u in uids]
    assert all(0 <= s < 8 for s in placed)
    assert len(set(placed)) == 8


def test_ring_growth_remaps_about_one_over_n():
    """N -> N+1 shards must move ~1/(N+1) of tenants, not ~all (the modulo
    failure mode)."""
    uids = [f"uid-{i}" for i in range(600)]
    for n in (2, 4, 8):
        before = ShardRing(n)
        after = ShardRing(n + 1)
        moved = sum(1 for u in uids
                    if before.shard_for(u) != after.shard_for(u))
        expected = len(uids) / (n + 1)
        assert moved <= 2 * expected, (
            f"{moved}/{len(uids)} moved going {n}->{n + 1}; "
            f"expected about {expected:.0f}")
        # movers must land ONLY on the new shard (consistent hashing: old
        # shards never trade tenants among themselves)
        for u in uids:
            if before.shard_for(u) != after.shard_for(u):
                assert after.shard_for(u) == n


def test_syncer_placement_survives_restart():
    """Same tenant -> same shard across independent syncer processes."""
    placements = []
    for _ in range(2):
        api = APIServer("super")
        syncer = Syncer(api, downward_workers=4, upward_workers=2,
                        scan_interval=0.0, shards=4)
        try:
            for i in range(10):
                p = TenantControlPlane(f"t{i}")
                syncer.register_tenant(p, f"uid-{i}")
            placements.append(
                {t: s.shard_id for t, s in syncer.down.owner.items()})
        finally:
            syncer.stop()
            api.close()
    assert placements[0] == placements[1]


# -------------------------------------------------------------------- resize

@pytest.fixture
def live_rig():
    super_api = APIServer("super")
    syncer = Syncer(super_api, downward_workers=8, upward_workers=4,
                    scan_interval=0.0, shards=2, downward_batch=4)
    planes = [TenantControlPlane(f"t{i:02d}", weight=1 + i % 3)
              for i in range(12)]
    for i, p in enumerate(planes):
        syncer.register_tenant(p, f"uid-{i}")
    syncer.start()
    yield super_api, syncer, planes
    syncer.stop()
    super_api.close()


# The four resize tests run against each direction's pipeline: downward
# items are tenant creates that must reach the super cluster, upward items
# are super-side status changes that must reach the tenant planes.

@pytest.fixture(params=["down", "up"])
def direction(request):
    return request.param


def _fleet(syncer, direction):
    """The direction's pipeline and its resize call."""
    if direction == "down":
        return syncer.down, syncer.resize_shards
    return syncer.up, syncer.resize_upward_shards


def _ready(u):
    u.status.phase = "Ready"


def _push(super_api, syncer, planes, direction, names):
    """One item per tenant and name: a tenant create downward; upward, a
    Ready status on the (already synced-down) super copy."""
    for p in planes:
        if direction == "down":
            for n in names:
                p.api.create(mk_unit(n))
        else:
            ns = f"{syncer.tenants[p.name].prefix}-default"
            for n in names:
                super_api.update_status("WorkUnit", ns, n, _ready)


def _landed(super_api, syncer, planes, direction, names):
    for p in planes:
        if direction == "down":
            ns = f"{syncer.tenants[p.name].prefix}-default"
            synced = {u.metadata.name for u in super_api.list("WorkUnit", ns)}
            if not synced >= set(names):
                return False
        elif not all(p.api.get("WorkUnit", "default", n).status.phase
                     == "Ready" for n in names):
            return False
    return True


def _prepare(super_api, syncer, planes, direction, names):
    """Upward items need their units synced down first."""
    if direction == "up":
        _push(super_api, syncer, planes, "down", names)
        assert wait_for(
            lambda: _landed(super_api, syncer, planes, "down", names),
            timeout=30)


def test_resize_moves_at_most_a_fraction_and_keeps_weights(live_rig,
                                                           direction):
    super_api, syncer, planes = live_rig
    pipe, resize = _fleet(syncer, direction)
    other = syncer.up if pipe is syncer.down else syncer.down
    before = {p.name: pipe.owner[p.name].shard_id for p in planes}
    moved = resize(3)
    assert pipe.num_shards == 3
    assert len(pipe.shards) == 3
    assert other.num_shards == 2          # the other direction is untouched
    # ~1/N remap: at most half the tenants move for 2 -> 3 shards (expected
    # fraction is 1/3; allow sampling slack on 12 tenants)
    assert len(moved) <= len(planes) // 2
    for tenant, new_shard in moved.items():
        assert new_shard != before[tenant]
        shard = pipe.owner[tenant]
        assert shard.shard_id == new_shard
        # WRR weight preserved on the destination queue
        assert shard.queue._weights[tenant] == syncer.tenants[tenant].plane.weight
    # stayers keep their registration on the original queue
    for p in planes:
        if p.name not in moved:
            assert p.name in pipe.owner[p.name].queue._weights


def test_resize_agrees_with_fresh_syncer_at_new_count(live_rig, direction):
    super_api, syncer, planes = live_rig
    pipe, resize = _fleet(syncer, direction)
    resize(3)
    for i, p in enumerate(planes):
        assert pipe.owner[p.name].shard_id == shard_for(f"uid-{i}", 3)


def test_resize_mid_burst_loses_no_items(live_rig, direction):
    """Items queued and in flight when the fleet grows must all still sync."""
    super_api, syncer, planes = live_rig
    _, resize = _fleet(syncer, direction)
    burst = [f"u{j:03d}" for j in range(40)]
    after = [f"u{j:03d}" for j in range(40, 45)]
    _prepare(super_api, syncer, planes, direction, burst + after)
    _push(super_api, syncer, planes, direction, burst)
    resize(3)                      # mid-burst: queues are non-empty
    # post-resize traffic follows the movers
    _push(super_api, syncer, planes, direction, after)
    assert wait_for(
        lambda: _landed(super_api, syncer, planes, direction, burst + after),
        timeout=30), f"{direction}: {super_api.store.count('WorkUnit')} units"


def test_resize_shrink_drains_removed_shards(live_rig, direction):
    super_api, syncer, planes = live_rig
    pipe, resize = _fleet(syncer, direction)
    _prepare(super_api, syncer, planes, direction, ["a", "b"])
    resize(3)
    _push(super_api, syncer, planes, direction, ["a"])
    assert wait_for(
        lambda: _landed(super_api, syncer, planes, direction, ["a"]))
    resize(1)
    assert pipe.num_shards == 1
    assert len(pipe.shards) == 1
    # every tenant must now live on shard 0
    assert all(s.shard_id == 0 for s in pipe.owner.values())
    _push(super_api, syncer, planes, direction, ["b"])
    assert wait_for(
        lambda: _landed(super_api, syncer, planes, direction, ["a", "b"]))


# ------------------------------------------------------- per-shard API clients

def test_each_shard_gets_its_own_super_client(live_rig):
    super_api, syncer, planes = live_rig
    clients = [c.api for c in syncer.down.shards]
    assert len({id(c) for c in clients}) == len(clients)
    for c in clients:
        assert c is not super_api
        assert c.store is super_api.store          # shared storage layer
        assert c._bucket is not super_api._bucket  # dedicated token bucket
    for p in planes:
        p.api.create(mk_unit("c"))
    assert wait_for(
        lambda: super_api.store.count("WorkUnit") == len(planes))
    # downward writes were issued via the shard clients, not the shared one
    assert sum(c.request_count for c in clients) > 0
