"""The sharded sync pipeline, instantiated once per syncer direction.

The syncer moves objects two ways: tenant specs down into the super cluster
(paper §III-C) and super statuses and Events up into the tenant planes
(paper §IV). Both directions scale the same way. Tenants hash by UID onto
the shards of a consistent-hash :class:`~repro.core.ring.ShardRing`; each
shard is a :class:`~repro.core.runtime.Controller` with its own per-tenant
:class:`~repro.core.fairqueue.FairWorkQueue` (WRR dispatch, so the Fig.11
fairness holds per shard) and its own super-API client (a dedicated token
bucket, so shards never serialize on one bucket lock). Same-tenant bursts
drain as one batch through the direction's fast path. Resizing the fleet
live-migrates only the ~1/N tenants whose ring slot changed, without
dropping queued or in-flight work.

:class:`ShardedPipeline` holds that machinery once. A direction supplies
only what differs: its per-item reconcile, its batched fast path returning
``(fast, slow)``, its timeline stamps, the errors it retries, and a hook
run when a tenant migrates.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, Hashable, List, Optional, Tuple, Type

from .fairqueue import FairWorkQueue
from .ring import ShardRing
from .runtime import Controller

Key = Tuple[str, str, str]      # (kind, namespace, name) in the source plane


class SyncShard(Controller):
    """One shard: a fair queue + workers for the tenants hashed onto it.

    Items are ``(tenant, key)``. A key that changes again while queued is
    deduplicated by the fair queue and reconciled once, from the latest
    informer-cache state.
    """

    def __init__(self, pipe: "ShardedPipeline", shard_id: int, workers: int):
        super().__init__(f"syncer-{pipe.tag}-{shard_id}",
                         queue=FairWorkQueue(f"{pipe.queue_name}-{shard_id}",
                                             fair=pipe.fair),
                         workers=workers, batch_size=pipe.batch_size,
                         retry_on=pipe.retry_on, drop_on=())
        self.shard_id = shard_id
        self.api = pipe.syncer.super_api.client(f"{pipe.tag}-{shard_id}")
        # shards created by a resize inherit the live meter
        self.queue.meter = pipe.meter
        # the direction's hooks, bound once: nothing branches per key
        self._owner = pipe.owner
        self._sync_one = pipe.sync_one
        self._sync_fast = pipe.sync_fast
        self._stamp_dequeue = pipe.stamp_dequeue
        self._stamp_done = pipe.stamp_done

    def _retry_queue(self, item: Any) -> Any:
        """Retries re-enter the tenant's CURRENT shard: if a resize migrated
        the tenant while this item was in flight, re-adding to our own
        (drained, possibly about-to-stop) queue would strand the key."""
        shard = self._owner.get(item[0])     # GIL-atomic dict read
        return shard.queue if shard is not None else self.queue

    def reconcile(self, item: Any) -> None:
        tenant, key = item
        tl = self._stamp_dequeue(tenant, key)
        self._sync_one(tenant, *key, api=self.api)
        # stamped only on success: a raise above means the item is retried,
        # and a finally-stamp would make fig7/fig8 undercount retried syncs
        if tl is not None:
            self._stamp_done(tl)

    def reconcile_batch(self, items: List[Any]) -> None:
        """Coalesce a same-tenant burst through the direction's fast path;
        leftovers (``slow``) take the authoritative per-item path."""
        if len(items) == 1:
            return self._reconcile_one(items[0])
        tenant = items[0][0]
        now = time.time()
        tls = {}
        for _, key in items:
            tl = self._stamp_dequeue(tenant, key, now)
            if tl is not None:
                tls[key] = tl
        t0 = time.monotonic()
        try:
            fast, slow = self._sync_fast(tenant, [key for _, key in items],
                                         api=self.api)
        except Exception:
            # fast path failed as a unit; fall back to per-item reconciles
            # below, but surface the failure in metrics
            self.metrics.inc("fast_path_errors", controller=self.name)
            fast, slow = [], [key for _, key in items]
        dur = time.monotonic() - t0
        done = time.time()
        fast_items = []
        for key in fast:
            fast_items.append((tenant, key))
            tl = tls.get(key)
            if tl is not None:
                self._stamp_done(tl, done)
        if fast_items:
            # batch the bookkeeping too: one lock round each instead of a
            # limiter + two metric + one queue lock round PER KEY
            self.limiter.forget_many(fast_items)
            self.metrics.inc("reconcile_total", float(len(fast_items)),
                             controller=self.name)
            self.metrics.observe_n("reconcile_seconds", dur / len(items),
                                   n=len(fast_items), controller=self.name)
            self.queue.done_batch(fast_items)
        for key in slow:
            self._reconcile_one((tenant, key))


MigrateHook = Callable[[str, SyncShard, SyncShard], None]


class ShardedPipeline:
    """One direction's shard fleet: the ring, the shards, and the tenant ->
    shard map that every producer and retry resolves at add time.

    Owned by :class:`~repro.core.syncer.Syncer`, which provides the tenant
    registry, the resize lock, the executor, and the owning
    ``ControllerManager``. Shards are named ``syncer-<tag>-<i>``, their
    queues ``<queue_name>-<i>`` and their API clients ``<tag>-<i>``.
    """

    def __init__(self, syncer: Any, *, tag: str, queue_name: str,
                 shards: int, total_workers: int, fair: bool,
                 batch_size: int,
                 retry_on: Tuple[Type[BaseException], ...],
                 sync_one: Callable[..., None],
                 sync_fast: Callable[..., Tuple[List[Key], List[Key]]],
                 stamp_dequeue: Callable[..., Optional[Any]],
                 stamp_done: Callable[..., None],
                 on_migrate: Optional[MigrateHook] = None):
        self.syncer = syncer
        self.tag = tag
        self.queue_name = queue_name
        self.fair = fair
        self.batch_size = max(1, int(batch_size))
        self.retry_on = retry_on
        self.sync_one = sync_one
        self.sync_fast = sync_fast
        self.stamp_dequeue = stamp_dequeue
        self.stamp_done = stamp_done
        self.on_migrate = on_migrate
        # tenant -> owning shard; written under the syncer's resize lock,
        # read lock-free (GIL-atomic dict reads) on the per-key paths
        self.owner: Dict[str, SyncShard] = {}
        self._meter: Optional[Any] = None
        self.num_shards = max(1, int(shards))
        self.ring = ShardRing(self.num_shards)
        per_shard = max(1, int(total_workers) // self.num_shards)
        self.shards: List[SyncShard] = [
            SyncShard(self, i, per_shard) for i in range(self.num_shards)]

    @property
    def meter(self) -> Optional[Any]:
        return self._meter

    @meter.setter
    def meter(self, m: Optional[Any]) -> None:
        self._meter = m
        for c in self.shards:
            c.queue.meter = m

    # ------------------------------------------------------------- routing

    def register(self, tenant: str, uid: str, weight: int) -> SyncShard:
        shard = self.shards[self.ring.shard_for(uid)]
        shard.queue.register_tenant(tenant, weight)
        self.owner[tenant] = shard
        return shard

    def unregister(self, tenant: str) -> None:
        shard = self.owner.pop(tenant, None)
        if shard is not None:
            _release(shard, tenant)

    def enqueue(self, tenant: str, key: Key) -> None:
        """Add ``key`` on the tenant's current shard; unregistered tenants
        are skipped. If a migration races the add (the old queue may already
        be drained or even shut down), re-add on the new shard: the
        destination queue dedups, so a double add is harmless."""
        owner = self.owner
        while True:
            shard = owner.get(tenant)
            if shard is None:
                return
            shard.queue.add(tenant, key)
            if owner.get(tenant) is shard:
                return

    def coalesced_total(self) -> int:
        """Keys absorbed by queue dedup (changes that never cost a write)."""
        return sum(c.queue.deduped for c in self.shards)

    # ------------------------------------------------------------ resizing

    def resize_locked(self, n: int) -> Dict[str, int]:
        """Resize the fleet to ``n`` shards; the caller holds the syncer's
        resize lock. Returns ``{tenant: new_shard_id}`` for the movers."""
        if n == self.num_shards:
            return {}
        sy = self.syncer
        running = any(c.running for c in self.shards)
        # new shards match the existing per-shard worker count so the fleet
        # stays uniform (growing the fleet grows total capacity; sizing new
        # shards to total // n would leave old shards with several times the
        # workers of their peers)
        per_shard = self.shards[0].workers
        while len(self.shards) < n:
            c = SyncShard(self, len(self.shards), per_shard)
            c.metrics = self.shards[0].metrics
            c.executor = sy.executor
            self.shards.append(c)
            sy.controllers.append(c)
            if running:
                c.start()   # must run before tenants route onto it
            if sy.manager is not None:
                sy.manager.add(c)   # start() above is idempotent
        new_ring = ShardRing(n)
        with sy._tenants_lock:
            regs = list(sy.tenants.values())
        moved: Dict[str, int] = {}
        for reg in regs:
            tenant = reg.plane.name
            target = new_ring.shard_for(reg.uid)
            if target != self.owner[tenant].shard_id:
                self._migrate(tenant, reg.plane.weight, self.shards[target])
                moved[tenant] = target
        self.ring = new_ring
        self.num_shards = n
        # shrink: only tail shards retire, now empty, so shard 0 (which
        # holds the super informers) never does
        for c in self.shards[n:]:
            c.stop()
            sy.controllers.remove(c)
            if sy.manager is not None:
                sy.manager.remove(c)
        del self.shards[n:]
        return moved

    def _migrate(self, tenant: str, weight: int, new: SyncShard) -> None:
        old = self.owner[tenant]
        new.queue.register_tenant(tenant, weight)   # same WRR weight
        self.owner[tenant] = new     # producers resolve the shard here
        for key in _release(old, tenant):
            new.queue.add(tenant, key)
        if self.on_migrate is not None:
            self.on_migrate(tenant, old, new)
        # A producer that read the owner just before the swap may have added
        # to the old queue after the drain, auto-re-registering the tenant
        # there as a ghost. Its re-check routes the item to the new queue
        # too (dedup makes the double add harmless), so this second pass
        # only clears the ghost entry.
        _release(old, tenant)


def _release(shard: SyncShard, tenant: str) -> List[Hashable]:
    """Take the tenant off ``shard``'s queue; returns its pending keys."""
    pending = shard.queue.drain_tenant(tenant)
    shard.queue.unregister_tenant(tenant)
    return pending
