"""Unified controller runtime: lifecycle (no leaked threads), retry/backoff,
metrics registry, manager health, fair-queue batching, and tenant->shard
partition stability."""
import threading
import time


from repro.core import (APIServer, Controller, ControllerManager,
                        FairWorkQueue, MetricsRegistry, NotFoundError, Syncer,
                        TenantControlPlane, WorkUnit, shard_for)
from repro.core.workqueue import DelayingQueue, WorkQueue


def wait_for(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.01)
    return False


class Recorder(Controller):
    """Test controller: records reconciled keys, fails on demand."""

    def __init__(self, name="rec", queue=None, fail_times=0, **kw):
        super().__init__(name, queue=queue or DelayingQueue(name), **kw)
        self.seen = []
        self.fail_times = fail_times
        self._fails = {}
        self.scans = 0

    def reconcile(self, key):
        n = self._fails.get(key, 0)
        if n < self.fail_times:
            self._fails[key] = n + 1
            raise RuntimeError(f"induced failure {n} for {key}")
        self.seen.append(key)

    def scan(self):
        self.scans += 1
        return 0


# ----------------------------------------------------------------- lifecycle

def test_controller_start_idle_stop_no_leaked_threads():
    before = threading.active_count()
    c = Recorder(workers=3)
    c.start()
    assert c.healthy()
    c.queue.add("k1")
    assert wait_for(lambda: c.seen == ["k1"])
    c.stop()
    assert not c.healthy()
    assert wait_for(lambda: threading.active_count() <= before)


def test_controller_stop_is_idempotent_and_restart_safe():
    c = Recorder()
    c.start()
    c.stop()
    c.stop()          # second stop is a no-op
    assert not c.running


def test_controller_restart_reconciles_again():
    c = Recorder(workers=1)
    c.start()
    c.queue.add("first")
    assert wait_for(lambda: c.seen == ["first"])
    c.stop()
    c.start()         # fresh stop event + reopened queue: workers live again
    assert c.healthy()
    c.queue.add("second")
    assert wait_for(lambda: c.seen == ["first", "second"])
    c.stop()


def test_manager_starts_in_order_and_stops_in_reverse():
    order = []

    class Tracked(Recorder):
        def on_start(self):
            order.append(("start", self.name))

        def on_stop(self):
            order.append(("stop", self.name))

    m = ControllerManager()
    a, b = Tracked("a"), Tracked("b")
    m.add(a, b)
    with m:
        assert order == [("start", "a"), ("start", "b")]
        health = m.healthy()
        assert health == {"a": True, "b": True}
    assert order[2:] == [("stop", "b"), ("stop", "a")]


def test_manager_adopts_metrics_and_late_add_starts():
    m = ControllerManager()
    a = Recorder("a")
    m.add(a)
    assert a.metrics is m.metrics
    m.start()
    late = Recorder("late")
    m.add(late)                      # added after start -> starts immediately
    assert late.running
    late.queue.add("x")
    assert wait_for(lambda: late.seen == ["x"])
    m.stop()


def test_informers_declared_on_controller_feed_queue():
    api = APIServer("s")

    class UnitWatcher(Recorder):
        def __init__(self):
            super().__init__("uw", queue=WorkQueue("uw"))
            self.add_informer(api, "WorkUnit",
                              handler=lambda ev, o: self.queue.add(
                                  (o.metadata.namespace, o.metadata.name)))

    c = UnitWatcher()
    c.start()
    try:
        u = WorkUnit()
        u.metadata.name = "j"
        u.metadata.namespace = "ns"
        api.create(u)
        assert wait_for(lambda: ("ns", "j") in c.seen)
    finally:
        c.stop()
        api.close()


# -------------------------------------------------------------- retry policy

def test_retry_with_backoff_until_success():
    c = Recorder(fail_times=3, workers=1)
    c.start()
    try:
        c.queue.add("flaky")
        assert wait_for(lambda: c.seen == ["flaky"])
        assert c.metrics.counter("reconcile_retries", controller=c.name) == 3
        # success forgets the key: backoff state is reset
        assert c.limiter.retries("flaky") == 0
    finally:
        c.stop()


def test_drop_on_exceptions_are_not_retried():
    class Dropper(Controller):
        def __init__(self):
            super().__init__("drop", queue=DelayingQueue("drop"),
                             drop_on=(NotFoundError,))
            self.calls = 0

        def reconcile(self, key):
            self.calls += 1
            raise NotFoundError(key)

    c = Dropper()
    c.start()
    try:
        c.queue.add("gone")
        assert wait_for(lambda: c.metrics.counter(
            "reconcile_dropped", controller="drop") == 1)
        time.sleep(0.1)
        assert c.calls == 1
    finally:
        c.stop()


def test_max_retries_exhausts():
    c = Recorder(fail_times=100, workers=1, max_retries=2)
    c.start()
    try:
        c.queue.add("doomed")
        assert wait_for(lambda: c.metrics.counter(
            "reconcile_exhausted", controller=c.name) == 1)
        assert not c.seen
    finally:
        c.stop()


def test_periodic_scan_runs_and_is_metered():
    c = Recorder(scan_interval=0.02)
    c.start()
    try:
        assert wait_for(lambda: c.scans >= 3)
        assert c.metrics.counter("scan_runs", controller=c.name) >= 3
        assert c.metrics.summary("scan_seconds", controller=c.name)["count"] >= 3
    finally:
        c.stop()


# ------------------------------------------------------------------- metrics

def test_metrics_registry_counters_summaries_gauges():
    m = MetricsRegistry()
    m.inc("reqs", controller="x")
    m.inc("reqs", 2.0, controller="x")
    m.observe("lat", 0.1, controller="x")
    m.observe("lat", 0.3, controller="x")
    m.register_gauge("depth", lambda: 7, controller="x")
    assert m.counter("reqs", controller="x") == 3.0
    s = m.summary("lat", controller="x")
    assert s["count"] == 2 and abs(s["mean"] - 0.2) < 1e-9 and s["max"] == 0.3
    snap = m.snapshot()
    assert snap["counters"]["reqs{controller=x}"] == 3.0
    assert snap["gauges"]["depth{controller=x}"] == 7.0


def test_queue_depth_gauge_reports_live_depth():
    c = Recorder(workers=0)          # no workers: items stay queued
    c.start()
    try:
        c.queue.add("a")
        c.queue.add("b")
        snap = c.metrics.snapshot()
        assert snap["gauges"][f"queue_depth{{controller={c.name}}}"] == 2.0
    finally:
        c.stop()


# ----------------------------------------------------- fair queue batching

def test_fair_queue_get_batch_drains_one_tenant():
    q = FairWorkQueue("b")
    for t in ("a", "b"):
        q.register_tenant(t, 1)
    for i in range(4):
        q.add("a", f"a{i}")
    q.add("b", "b0")
    batch = q.get_batch(8, timeout=0.1)
    # one tenant per batch; the other tenant's item is untouched
    assert {t for t, _ in batch} == {batch[0][0]}
    rest = q.get_batch(8, timeout=0.1)
    for item in batch + rest:
        q.done(item)
    assert {i[0] for i in batch} != {i[0] for i in rest}
    assert len(batch) + len(rest) == 5
    assert len(q) == 0


def test_fifo_queue_get_batch_stays_single_tenant():
    """Even in FIFO (unfair) mode a batch must hold one tenant only — the
    syncer's batched reconcile assumes it."""
    q = FairWorkQueue("fifo", fair=False)
    q.add("a", "a0")
    q.add("a", "a1")
    q.add("b", "b0")
    q.add("a", "a2")
    batch = q.get_batch(8, timeout=0.1)
    assert batch == [("a", "a0"), ("a", "a1")]
    for item in batch:
        q.done(item)
    assert q.get_batch(8, timeout=0.1) == [("b", "b0")]
    q.done(("b", "b0"))
    assert q.get_batch(8, timeout=0.1) == [("a", "a2")]


def test_fair_queue_batch_respects_dedup_and_reprocess():
    q = FairWorkQueue("b2")
    q.register_tenant("t", 1)
    q.add("t", "k")
    q.add("t", "k")                   # dedup while queued
    assert q.deduped == 1
    [item] = q.get_batch(4, timeout=0.1)
    q.add("t", "k")                   # re-added while processing
    q.done(item)                      # -> requeued
    assert q.get_batch(4, timeout=0.2) == [("t", "k")]


# ------------------------------------------------------- shard partitioning

def test_shard_for_is_stable_and_spreads():
    uids = [f"uid-{i}" for i in range(256)]
    first = [shard_for(u, 8) for u in uids]
    assert first == [shard_for(u, 8) for u in uids]      # deterministic
    assert all(0 <= s < 8 for s in first)
    assert len(set(first)) == 8                          # all shards used
    assert all(shard_for(u, 1) == 0 for u in uids)


def test_syncer_assigns_tenant_to_stable_shard():
    api = APIServer("super")
    syncer = Syncer(api, downward_workers=4, upward_workers=2,
                    scan_interval=0.0, shards=4)
    try:
        planes = [TenantControlPlane(f"t{i}") for i in range(6)]
        for i, p in enumerate(planes):
            syncer.register_tenant(p, f"uid-{i}")
        for i, p in enumerate(planes):
            shard = syncer.down.owner[p.name]
            assert shard.shard_id == syncer.down.ring.shard_for(f"uid-{i}")
            # a second syncer with the same shard count agrees
            assert shard.shard_id == shard_for(f"uid-{i}", 4)
    finally:
        syncer.stop()
        api.close()
