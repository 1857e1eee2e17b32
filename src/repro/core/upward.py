"""Upward status/event sync: super-cluster state -> tenant planes (paper §IV).

The paper's syncer is bidirectional; upward synchronization (super-cluster
status -> tenant control planes) is the half tenants actually *watch* — a
tenant polls its own apiserver for WorkUnit phases, Service endpoints, and
Events, so upward latency is directly tenant-visible (the Fig.8 breakdown
carries the UWS queue as a first-class phase). The upward direction runs on
the syncer's second :class:`~repro.core.pipeline.ShardedPipeline` (tenant-hash
shards, per-shard fair queues and API clients, live resizing); this module
holds what is upward-specific:

- **Events** (:class:`EventRecorder`): node agents record Kubernetes-style
  :class:`~repro.core.objects.Event` objects on WorkUnit phase transitions
  and node heartbeats. Repeats of the same (involved, reason, component)
  tuple compress into one object (``count`` increments, ``last_timestamp``
  advances) — kubelet event-aggregation semantics. Events are synced upward
  so tenants can "kubectl get events" inside their own control planes.
- **Latest-wins coalescing + batched writes** (:meth:`UpwardSync.
  reconcile_fast`): a key is queued at most once (fair-queue dedup), and
  reconcile reads the *current* super informer cache — N rapid status flaps
  collapse into one write of the latest state. Same-tenant bursts drain as
  one batch and commit with ONE ``update_status_batch`` per tenant plane
  (``ObjectStore.update_status_many``: a single lock round), with Events
  created/bumped the same way.
- **Per-item sync** (:meth:`UpwardSync.reconcile_one`): the authoritative
  path, and the whole path with ``upward_batch=1``.
"""
from __future__ import annotations

import hashlib
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from .objects import Event, deepcopy_obj, status_equal
from .store import AlreadyExistsError, NotFoundError
from .trace import TRACEPARENT_KEY, sampled_carrier

UpKey = Tuple[str, str, str]           # (kind, super_ns, name)


def event_name(involved_kind: str, involved_name: str, reason: str,
               component: str) -> str:
    """Deterministic dedup name: repeats of one (involved, reason, source)
    tuple always address the same Event object."""
    h = hashlib.sha256(
        f"{involved_kind}/{involved_name}/{reason}/{component}"
        .encode()).hexdigest()[:10]
    return f"{involved_name}.{h}"


class EventRecorder:
    """Records deduplicated Events against one apiserver (kubelet analogue).

    ``record`` is a read-modify-write: an existing Event for the same
    (involved object, reason, component) gets ``count += 1`` and a fresh
    ``last_timestamp`` (compression); a first occurrence creates the object.
    Safe under concurrent recorders — a create race falls back to the bump.
    """

    def __init__(self, api: Any, component: str, host: str = ""):
        self.api = api
        self.component = component
        self.host = host
        self.recorded = 0

    def record(self, involved_kind: str, namespace: str, involved_name: str,
               reason: str, message: str = "", type: str = "Normal") -> Any:
        name = event_name(involved_kind, involved_name, reason,
                          self.component)
        now = time.time()

        def bump(e: Event) -> None:
            e.count += 1
            e.last_timestamp = now
            e.message = message
            e.type = type

        self.recorded += 1
        try:
            return self.api.update_status("Event", namespace, name, bump)
        except NotFoundError:
            pass
        ev = Event()
        ev.metadata.name = name
        ev.metadata.namespace = namespace
        ev.involved_kind = involved_kind
        ev.involved_namespace = namespace
        ev.involved_name = involved_name
        ev.reason = reason
        ev.message = message
        ev.type = type
        ev.source_component = self.component
        ev.source_host = self.host
        ev.count = 1
        ev.first_timestamp = ev.last_timestamp = now
        try:
            return self.api.create(ev)
        except AlreadyExistsError:   # lost the create race: bump instead
            return self.api.update_status("Event", namespace, name, bump)


class UpwardSync:
    """The upward direction's reconcilers and timeline stamps, bound into
    the syncer's upward :class:`~repro.core.pipeline.ShardedPipeline`.

    Reads the tenant registry, namespace resolution, vNode manager, and
    super informers from :class:`~repro.core.syncer.Syncer`. Keys are
    ``(kind, super_ns, name)``.
    """

    def __init__(self, syncer: Any):
        self.syncer = syncer

    # ------------------------------------------------------ timeline stamps

    def stamp_dequeue(self, tenant: str, key: UpKey,
                      now: Optional[float] = None) -> Optional[Any]:
        kind, super_ns, name = key
        if kind != "WorkUnit":
            return None
        sy = self.syncer
        resolved = sy._resolve_super_ns(super_ns)
        if resolved is None:
            return None
        tl = sy.metrics.timeline(resolved[0], resolved[1], name)
        if tl.uws_dequeue == 0.0 and tl.super_ready > 0.0:
            tl.uws_dequeue = now or time.time()
        return tl

    @staticmethod
    def stamp_done(tl: Any, now: Optional[float] = None) -> None:
        if tl.uws_done == 0.0 and tl.super_ready > 0.0:
            tl.uws_done = now or time.time()

    # --------------------------------------------------------- reconcilers

    def reconcile_one(self, tenant: str, kind: str, super_ns: str, name: str,
                      api: Optional[Any] = None) -> None:
        """Authoritative per-item upward sync (also the slow path under
        coalescing): super status/event is the source of truth -> project
        back into the tenant plane."""
        sy = self.syncer
        resolved = sy._resolve_super_ns(super_ns)
        if resolved is None:
            return
        tenant_ns = resolved[1]
        with sy._tenants_lock:
            reg = sy.tenants.get(tenant)
        if reg is None:
            return
        inf = sy._super_informers.get(kind)
        super_obj = inf.cache.get(super_ns, name) if inf is not None else None
        if super_obj is None:
            return  # deletion downward is handled by the downward reconciler
        if kind in ("WorkUnit", "Service"):
            self._sync_status_up(reg, kind, tenant_ns, name, super_obj,
                                 api=api)
        elif kind == "Event":
            self._sync_event_up(reg, tenant_ns, name, super_obj)
        sy.metrics.inc_upward()
        m = sy._meter
        if m is not None:
            m.add(tenant, "up_items", 1.0)

    def reconcile_fast(self, tenant: str, keys: List[UpKey],
                       api: Optional[Any] = None
                       ) -> Tuple[List[UpKey], List[UpKey]]:
        """Coalesced upward pass over a same-tenant burst.

        Latest states are read from the super informer caches; unchanged
        objects are skipped (echo suppression), and the rest are committed
        with ONE ``update_status_batch`` per tenant plane — plus one
        ``update_status_batch`` + ``create_batch`` round for Events.
        Returns ``(fast, slow)``: ``slow`` keys (unknown kinds, event create
        races) need the authoritative per-item reconcile.
        """
        sy = self.syncer
        tr = sy.tracer
        t0 = time.monotonic() if tr is not None else 0.0
        traced: List[Tuple[UpKey, Any, str]] = []
        fast: List[UpKey] = []
        slow: List[UpKey] = []
        with sy._tenants_lock:
            reg = sy.tenants.get(tenant)
        if reg is None:
            return list(keys), slow
        status_updates: List[Tuple[str, str, str, Callable]] = []
        status_keys: List[UpKey] = []
        ev_updates: List[Tuple[str, str, str, Callable]] = []
        ev_sources: List[Tuple[UpKey, Any, str]] = []
        synced = 0
        # same-tenant batches share a namespace almost always: memoize the
        # reverse-map hit so a batch costs one resolve, not one per key
        ns_memo: Dict[str, Any] = {}
        for key in keys:
            kind, super_ns, name = key
            resolved = ns_memo.get(super_ns)
            if resolved is None:
                resolved = sy._resolve_super_ns(super_ns)
                ns_memo[super_ns] = resolved if resolved is not None else False
            if resolved is False or resolved is None:
                fast.append(key)        # tenant gone: nothing to project
                continue
            tenant_ns = resolved[1]
            inf = sy._super_informers.get(kind)
            sobj = inf.cache.get(super_ns, name) if inf is not None else None
            if sobj is None:
                fast.append(key)        # deleted upstream: downward cleans up
                continue
            if kind in ("WorkUnit", "Service"):
                mutate = self._status_mutator(reg, kind, tenant_ns, name,
                                              sobj, api=api)
                if mutate is None:
                    fast.append(key)    # echo: tenant already shows it
                    continue
                status_updates.append((kind, tenant_ns, name, mutate))
                status_keys.append(key)
                if tr is not None:
                    tp = sobj.metadata.annotations.get(TRACEPARENT_KEY)
                    if tp and sampled_carrier(tp):
                        traced.append((key, sobj, tenant_ns))
            elif kind == "Event":
                ev_updates.append(("Event", tenant_ns, name,
                                   _event_bump(sobj)))
                ev_sources.append((key, sobj, tenant_ns))
            else:
                slow.append(key)
        if status_updates:
            updated, missing = reg.plane.api.update_status_batch(
                status_updates)
            # missing == tenant deleted it mid-flight: same as the per-item
            # path's NotFound pass — the downward reconciler cleans up
            fast.extend(status_keys)
            synced += len(updated)
            if traced:
                miss = set(missing)
                for key, sobj, t_ns in traced:
                    if (key[0], t_ns, key[2]) not in miss:
                        self._trace_up(sobj, t0, tenant, key[0], t_ns,
                                       key[2], batch=len(keys))
        if ev_updates:
            updated, missing = reg.plane.api.update_status_batch(ev_updates)
            synced += len(updated)
            miss = set(missing)
            creates: List[Event] = []
            create_keys: List[UpKey] = []
            for key, sobj, tenant_ns in ev_sources:
                if ("Event", tenant_ns, key[2]) in miss:
                    creates.append(self._project_event(sobj, tenant_ns))
                    create_keys.append(key)
                else:
                    fast.append(key)
            if creates:
                created, conflicted = reg.plane.api.create_batch(creates)
                synced += len(created)
                lost = {(o.metadata.namespace, o.metadata.name)
                        for o in conflicted}
                for key, obj in zip(create_keys, creates):
                    if (obj.metadata.namespace, obj.metadata.name) in lost:
                        slow.append(key)    # create race: per-item retry
                    else:
                        fast.append(key)
        if synced:
            sy.metrics.inc_upward(synced)
            m = sy._meter
            if m is not None:
                # the whole coalesced burst attributes to its (single)
                # tenant: N landed commits -> N up_items, exactly
                m.add(tenant, "up_items", float(synced))
        return fast, slow

    # -------------------------------------------------------------- tracing

    def _trace_up(self, sobj: Any, t0: float, tenant: str, kind: str,
                  tenant_ns: str, name: str, batch: int = 0) -> None:
        """Record a "syncer.up" child span for a traced object whose status
        just landed in the tenant plane, and — since a landed status IS the
        end of the paper's propagation path — close the pending end-to-end
        span, feeding its duration to the propagation histogram and the
        per-tenant SLO tracker. Echo-suppressed keys never reach here, so
        the e2e span closes on the FIRST real status return only."""
        sy = self.syncer
        tr = sy.tracer
        if tr is None:
            return
        tp = sobj.metadata.annotations.get(TRACEPARENT_KEY)
        if not tp:
            return
        if not sampled_carrier(tp):
            # head-unsampled: nothing was registered at the root, no child
            # can be retained, and the SLO/histogram feeds run on the
            # sampled subset — the unsampled path pays zero tracer calls
            return
        end = time.monotonic()
        attrs: Dict[str, Any] = {"kind": kind, "ns": tenant_ns, "name": name}
        if batch:
            attrs["batch"] = batch
        tr.record_from(tp, "syncer.up", t0, end, tenant=tenant, attrs=attrs)
        root = tr.finish_pending(tp, end)
        if root is None:
            return      # already closed (or never opened here)
        dur = max(0.0, root.end - root.start)
        m = sy.controllers[0].metrics
        m.histogram("propagation_seconds").observe(dur)
        m.histogram("propagation_seconds", tenant=tenant).observe(dur)
        if sy.slo is not None:
            sy.slo.observe("propagation", tenant, dur)

    # ------------------------------------------------------ kind projectors

    def _project_unit_status(self, reg: Any, tenant_ns: str, name: str,
                             super_obj: Any,
                             api: Optional[Any] = None) -> Any:
        """Super WorkUnit status with the physical node mapped to a vNode."""
        sy = self.syncer
        vnode_name = ""
        if super_obj.status.node:
            node_inf = sy._super_informers.get("Node")
            pnode = None
            if node_inf is not None:
                pnode = node_inf.cache.get("", super_obj.status.node)
            if pnode is None:
                try:
                    pnode = (api or sy.super_api).get(
                        "Node", "", super_obj.status.node)
                except NotFoundError:
                    pnode = None
            if pnode is not None:
                vnode_name = sy.vnodes.bind(reg.plane, pnode, tenant_ns, name)
        status = deepcopy_obj(super_obj.status)
        if vnode_name:
            status.node = vnode_name
        return status

    def _status_mutator(self, reg: Any, kind: str, tenant_ns: str,
                        name: str, super_obj: Any,
                        api: Optional[Any] = None
                        ) -> Optional[Callable[[Any], None]]:
        """The mutator that lands a super WorkUnit's or Service's status on
        the tenant copy, or None when the tenant's informer cache already
        shows it (echo)."""
        inf = reg.informers.get(kind)
        if kind == "WorkUnit":
            status = self._project_unit_status(reg, tenant_ns, name,
                                               super_obj, api=api)
            cached = inf.cache.get(tenant_ns, name) if inf is not None else None
            if cached is not None and status_equal(cached.status, status):
                return None

            def mutate(u: Any) -> None:
                u.status = status
            return mutate
        eps, vip = list(super_obj.endpoints), super_obj.virtual_ip
        cached = inf.cache.get(tenant_ns, name) if inf is not None else None
        if (cached is not None and cached.endpoints == eps
                and cached.virtual_ip == vip):
            return None

        def mutate_service(s: Any) -> None:
            s.endpoints = eps
            s.virtual_ip = vip
        return mutate_service

    def _sync_status_up(self, reg: Any, kind: str, tenant_ns: str, name: str,
                        super_obj: Any, api: Optional[Any] = None) -> None:
        t0 = time.monotonic() if self.syncer.tracer is not None else 0.0
        mutate = self._status_mutator(reg, kind, tenant_ns, name, super_obj,
                                      api=api)
        if mutate is None:
            return
        try:
            reg.plane.api.update_status(kind, tenant_ns, name, mutate)
        except NotFoundError:
            pass  # tenant deleted it mid-flight; scan/downward will clean up
        else:
            self._trace_up(super_obj, t0, reg.plane.name, kind, tenant_ns,
                           name)

    def _sync_event_up(self, reg: Any, tenant_ns: str, name: str,
                       super_obj: Any) -> None:
        """Project one super Event into the tenant plane (latest-wins:
        count/lastTimestamp compression carries over verbatim)."""
        try:
            reg.plane.api.update_status("Event", tenant_ns, name,
                                        _event_bump(super_obj))
            return
        except NotFoundError:
            pass
        ev = self._project_event(super_obj, tenant_ns)
        try:
            reg.plane.api.create(ev)
        except AlreadyExistsError:
            reg.plane.api.update_status("Event", tenant_ns, name,
                                        _event_bump(super_obj))

    @staticmethod
    def _project_event(super_obj: Any, tenant_ns: str) -> Event:
        ev = deepcopy_obj(super_obj)
        ev.metadata.namespace = tenant_ns
        ev.metadata.uid = ""
        ev.metadata.resource_version = 0
        ev.metadata.creation_timestamp = 0.0
        ev.involved_namespace = tenant_ns
        return ev


def _event_bump(super_obj: Any) -> Callable[[Event], None]:
    """Mutator copying the super event's compressed counters onto the
    tenant copy (latest wins — never an increment, so replays are safe)."""
    count = super_obj.count
    last = super_obj.last_timestamp
    message = super_obj.message
    type_ = super_obj.type

    def mutate(e: Event) -> None:
        e.count = count
        e.last_timestamp = last
        e.message = message
        e.type = type_
    return mutate
