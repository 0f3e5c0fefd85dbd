"""Shared cluster state: one informer set + one feature cache for ALL
profile engines.

The reference runs ONE scheduler struct with many profiles
(reference scheduler/scheduler.go:97-142): cluster watching and cache
state are shared, only the per-profile plugin pipelines differ. The
rebuild mirrors that here — a single ``SharedClusterState`` owns the
``NodeFeatureCache`` (node features, bind accounting, topology-key
registry, orphaned-bind re-adoption) and the one ``InformerFactory``
whose handlers maintain the cache ONCE and fan requeue signals out to
every registered engine's queue. Engines keep their own queues, compiled
steps, binders and metrics. Before this, each profile engine duplicated
a full 50k-node cache (tens of MB host + HBM per profile) and a
redundant watch stream — and, worse, each profile accounted binds only
in its own cache, so two profiles could jointly over-commit a node that
either alone would have refused.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Set

import numpy as np

from ..encode import NodeFeatureCache
from ..encode import features as F
from ..state.objects import pod_requests
from ..errors import NotFoundError
from ..state.events import (ActionType, ClusterEvent, GVK,
                            node_update_narrows_only, watch_to_cluster_event)
from ..state.informer import InformerFactory, ResourceEventHandlers
from ..state.store import EventType, WatchEvent


class SharedClusterState:
    """Cache + informers shared by every profile engine of one service."""

    def __init__(self, store):
        self.store = store
        self.cache = NodeFeatureCache()
        self.informer_factory = InformerFactory(store)
        self._engines: List = []
        self._lock = threading.Lock()
        self._started = False
        # node name → pod keys that were bound to a deleted incarnation
        # (re-adopted if a same-named node returns; see on_node_added)
        self._orphaned_binds: Dict[str, Set[str]] = {}
        _add_all_event_handlers(self, self.informer_factory)

    # ---- engine registration / lifecycle --------------------------------

    def register(self, engine) -> None:
        with self._lock:
            if self._started:
                raise RuntimeError(
                    "cannot register an engine after informers started")
            self._engines.append(engine)

    def engines(self) -> List:
        with self._lock:
            return list(self._engines)

    def ensure_started(self) -> None:
        """Start informers once (idempotent); every engine must already
        be registered — a later registration would miss the initial
        sync's pod routing."""
        with self._lock:
            if self._started:
                return
            self._started = True
        self.informer_factory.start()
        self.informer_factory.wait_for_cache_sync()

    def shutdown(self) -> None:
        self.informer_factory.shutdown()
        with self._lock:
            self._engines.clear()
            self._started = False

    # ---- node lifecycle (informer thread; was Scheduler.on_node_*) ------

    def on_node_added(self, node) -> None:
        """Node appeared: encode it, and RE-ADOPT any pods still bound (in
        the store) to a previous same-named incarnation — without this the
        recreated node starts at full free capacity while the store still
        charges those pods to its name, and every new bind over-commits
        it. Adoption happens inside the cache's upsert lock hold."""
        name = node.metadata.name
        adopt = []
        for key in self._orphaned_binds.pop(name, ()):
            try:
                pod = self.store.get("Pod", key)
            except NotFoundError:
                continue  # deleted while the node was gone
            if pod.spec.node_name == name:
                adopt.append(pod)
        self.cache.upsert_node(node, bound_pods=adopt)

    def on_node_removed(self, name: str) -> None:
        gone = self.cache.remove_node(name)
        if gone:
            self._orphaned_binds.setdefault(name, set()).update(gone)

    def on_bind_miss(self, pod) -> None:
        """A bound pod's node has no cache row (bound to a node that was
        deleted, or that the cache never saw — e.g. a pre-bound pod to a
        not-yet-created node). Park it for re-adoption: if a same-named
        node appears, ``on_node_added`` re-accounts it; until then it is
        correctly absent from capacity/topology counts (the node does not
        exist)."""
        if pod.spec.node_name:
            self._orphaned_binds.setdefault(
                pod.spec.node_name, set()).add(pod.key)

    def on_bound_pod_deleted(self, pod) -> None:
        self.cache.account_unbind(pod.key)
        orphans = self._orphaned_binds.get(pod.spec.node_name)
        if orphans is not None:
            orphans.discard(pod.key)
            if not orphans:
                del self._orphaned_binds[pod.spec.node_name]


def _request_rows(bound) -> np.ndarray:
    """(len(bound), R) request vectors for account_bind_bulk's vectorized
    fast path, memoized by request signature — a synced 100k-pod bound
    corpus is a few deployments sharing a handful of request shapes, so
    the per-pod dict walk collapses to dict hits (VERDICT r4 #7: the
    corpus must sync without per-pod encoding cost). Pods with volumes
    compute directly (pod_requests folds attach slots in; the bulk path
    routes them through the claim table anyway)."""
    memo: Dict[tuple, np.ndarray] = {}
    rows = np.empty((len(bound), F.NUM_RESOURCES), dtype=np.float32)
    for k, (pod, _node) in enumerate(bound):
        if pod.spec.volumes:
            rows[k] = F.resources_vector(pod_requests(pod))
            continue
        sig = tuple(sorted(pod.spec.requests.items()))
        row = memo.get(sig)
        if row is None:
            row = memo[sig] = F.resources_vector(pod_requests(pod))
        rows[k] = row
    return rows


def _add_all_event_handlers(state: SharedClusterState,
                            factory: InformerFactory) -> None:
    """Informer wiring (rebuild of reference minisched/eventhandler.go:
    14-90): cache maintenance happens ONCE on the shared state; queue
    adds route to the engine whose profile wants the pod; requeue
    signals fan out to every engine's queue."""

    def move_all(ev: ClusterEvent) -> None:
        for e in state.engines():
            e.queue.move_all_to_active_or_backoff(ev)

    # --- pods: unscheduled → owning engine's queue; bound → cache -------
    def pod_add(pod):
        if not pod.spec.node_name:
            for e in state.engines():
                if e.wants_pod(pod):
                    e.queue.add(pod)
                    break
            if pod.spec.pod_group:
                move_all(ClusterEvent(GVK.POD, ActionType.ADD))
        else:
            if not state.cache.account_bind(pod):
                state.on_bind_miss(pod)
            move_all(ClusterEvent(GVK.POD, ActionType.ADD))

    def pod_update(old, new):
        if not new.spec.node_name:
            for e in state.engines():
                if e.wants_pod(new):
                    e.queue.update(old, new)
                    break
        elif not old.spec.node_name:
            # became bound: idempotent accounting (an engine assumes the
            # pod at selection time; this is the confirm path)
            if not state.cache.account_bind(new):
                state.on_bind_miss(new)
        else:
            move_all(ClusterEvent(GVK.POD, ActionType.UPDATE))

    def pod_delete(pod):
        if pod.spec.node_name:
            state.on_bound_pod_deleted(pod)
            move_all(ClusterEvent(GVK.POD, ActionType.DELETE))
        else:
            for e in state.engines():
                e.queue.delete(pod)
                e.drop_nomination(pod.key)

    def pod_add_many(pods):
        """Bulk pod_add: one queue transaction per engine for the burst,
        one cache transaction for bound arrivals, one coalesced move."""
        per_engine: Dict[int, list] = {}
        bound, move = [], False
        engines = state.engines()
        for pod in pods:
            if not pod.spec.node_name:
                for idx, e in enumerate(engines):
                    if e.wants_pod(pod):
                        per_engine.setdefault(idx, []).append(pod)
                        break
                if pod.spec.pod_group:
                    move = True
            else:
                bound.append((pod, ""))
                move = True
        for idx, batch in per_engine.items():
            engines[idx].queue.add_many(batch)
        if bound:
            for m in state.cache.account_bind_bulk(
                    bound, req_rows=_request_rows(bound)):
                state.on_bind_miss(bound[m][0])
        if move:
            move_all(ClusterEvent(GVK.POD, ActionType.ADD))

    def pod_update_many(pairs):
        """Bulk pod_update for MODIFIED bursts (a 10k bulk bind emits 10k
        back-to-back MODIFIED events): became-bound pods confirm in ONE
        cache transaction; requeue signals coalesce to one move."""
        became_bound, move = [], False
        engines = state.engines()
        for old, new in pairs:
            if not new.spec.node_name:
                for e in engines:
                    if e.wants_pod(new):
                        e.queue.update(old, new)
                        break
            elif not old.spec.node_name:
                became_bound.append((new, ""))
            else:
                move = True
        if became_bound:
            for m in state.cache.account_bind_bulk(
                    became_bound, req_rows=_request_rows(became_bound)):
                state.on_bind_miss(became_bound[m][0])
        if move:
            move_all(ClusterEvent(GVK.POD, ActionType.UPDATE))

    factory.add_handlers("Pod", ResourceEventHandlers(
        on_add=pod_add, on_update=pod_update, on_delete=pod_delete,
        on_add_many=pod_add_many, on_update_many=pod_update_many))

    # --- nodes: shared feature cache + requeue gating --------------------
    def node_add(node):
        state.on_node_added(node)
        move_all(ClusterEvent(GVK.NODE, ActionType.ADD))

    def node_add_many(nodes):
        """Bulk node_add for the initial sync / re-list: memoized bulk
        encode (cache.upsert_nodes_bulk) + ONE coalesced requeue signal —
        this is the 50k-node restart-to-first-batch path. Nodes with
        orphaned binds awaiting re-adoption take the per-node path (the
        adoption must happen inside the upsert's lock hold)."""
        plain = [n for n in nodes
                 if n.metadata.name not in state._orphaned_binds]
        state.cache.upsert_nodes_bulk(plain)
        for n in nodes:
            if n.metadata.name in state._orphaned_binds:
                state.on_node_added(n)
        move_all(ClusterEvent(GVK.NODE, ActionType.ADD))

    def node_update(old, new):
        state.cache.upsert_node(new)
        # Drain/cordon-aware requeue (lifecycle churn): a purely
        # NARROWING update — cordon, taints grown, allocatable shrunk,
        # nothing else changed — cannot make any parked pod schedulable;
        # fanning it out would revive the whole unschedulableQ per
        # cordon and bump every engine's move cycle (in-flight batches
        # would then route terminal verdicts to backoff, thrashing
        # forever under sustained churn). The cache still observes it.
        if node_update_narrows_only(old, new):
            return
        move_all(watch_to_cluster_event(
            WatchEvent(EventType.MODIFIED, GVK.NODE, new, old)))

    def node_delete(node):
        state.on_node_removed(node.metadata.name)
        move_all(ClusterEvent(GVK.NODE, ActionType.DELETE))

    factory.add_handlers("Node", ResourceEventHandlers(
        on_add=node_add, on_update=node_update, on_delete=node_delete,
        on_add_many=node_add_many))

    # --- volumes: requeue gating only ------------------------------------
    for kind in (GVK.PERSISTENT_VOLUME, GVK.PERSISTENT_VOLUME_CLAIM):
        factory.add_handlers(kind, ResourceEventHandlers(
            on_add=lambda o, k=kind: move_all(
                ClusterEvent(k, ActionType.ADD)),
            on_update=lambda old, new, k=kind: move_all(
                ClusterEvent(k, ActionType.UPDATE)),
            on_delete=lambda o, k=kind: move_all(
                ClusterEvent(k, ActionType.DELETE)),
        ))
