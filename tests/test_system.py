"""System behaviour tests mirroring the paper's claims at reduced scale.

Each test asserts a *shape* from the paper's evaluation (§IV): queuing
breakdown structure, vNode semantics, dedup, scan cost — the quantitative
validation lives in benchmarks/ (EXPERIMENTS.md)."""
import statistics
import threading
import time

import pytest

from repro.core import VirtualClusterFramework


@pytest.fixture(scope="module")
def burst_rig():
    """One shared burst: 8 tenants x 25 units on 20 nodes."""
    fw = VirtualClusterFramework(num_nodes=20, scan_interval=0.0,
                                 heartbeat_interval=3600)
    fw.start()
    planes = [fw.add_tenant(f"t{i}") for i in range(8)]

    def submit(plane):
        for j in range(25):
            fw.submit(plane, fw.make_unit(f"u{j:03d}", "default", chips=0))

    threads = [threading.Thread(target=submit, args=(p,)) for p in planes]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for p in planes:
        fw.wait_all_ready(p, "default", 25, timeout=120)
    yield fw, planes
    fw.stop()


def test_all_units_reach_ready(burst_rig):
    fw, planes = burst_rig
    for p in planes:
        units = p.api.list("WorkUnit", "default")
        assert len(units) == 25
        assert all(u.status.phase == "Ready" for u in units)


def test_latency_breakdown_has_paper_structure(burst_rig):
    """Paper Fig.8: queue phases dominate sync-processing phases; the
    downward/upward *processing* times are trivial."""
    fw, planes = burst_rig
    tls = [tl for tl in fw.syncer.metrics.timelines.values() if tl.complete]
    assert len(tls) == 200
    means = {}
    for phase in ("DWS-Queue", "DWS-Process", "Super-Sched", "UWS-Queue",
                  "UWS-Process"):
        means[phase] = statistics.mean(tl.phases()[phase] for tl in tls)
    assert means["DWS-Process"] < max(means["DWS-Queue"],
                                      means["Super-Sched"])
    assert means["UWS-Process"] < 0.5


def test_every_unit_bound_to_virtual_node(burst_rig):
    """vNode semantics: each Ready unit's node maps 1:1 to a physical node
    that exists as a VirtualNode object in the tenant plane."""
    fw, planes = burst_rig
    for p in planes:
        vnodes = {v.metadata.name for v in p.api.list("VirtualNode")}
        for u in p.api.list("WorkUnit", "default"):
            assert u.status.node in vnodes
        for v in p.api.list("VirtualNode"):
            assert v.physical_node == v.metadata.name  # 1:1 mapping


def test_dedup_reduces_sync_work(burst_rig):
    """Paper §III-C: "the client-go worker queue has the capability of
    deduplicating the incoming requests". Back-to-back updates of the same
    key land while the first add is still queued/processing, so the second
    is absorbed. (Label-only updates: no spec change reaches the super
    cluster, so this is pure sync-queue traffic.)"""
    fw, planes = burst_rig
    q = fw.syncer.down.shards[0].queue

    def churn(plane):
        for j in range(25):
            for rev in ("a", "b"):
                u = plane.api.get("WorkUnit", "default", f"u{j:03d}")
                u.metadata.labels["rev"] = rev
                plane.api.update(u)

    threads = [threading.Thread(target=churn, args=(p,)) for p in planes]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline and q.deduped == 0:
        time.sleep(0.01)
    assert q.deduped > 0          # duplicate sync requests were absorbed
    assert q.added > q.deduped


def test_periodic_scan_is_cheap_and_idempotent(burst_rig):
    fw, planes = burst_rig
    t0 = time.monotonic()
    fixes = fw.syncer.scan_once()
    dur = time.monotonic() - t0
    assert dur < 5.0              # paper: <2 s for 10k pods (we have 200)
    assert fixes == 0             # steady state: nothing to remediate
