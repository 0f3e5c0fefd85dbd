"""Persistent-volume controller.

Analog of reference pvcontroller/pvcontroller.go:16-44, which runs the real
upstream PV controller (hostpath/local plugins, 1s sync, dynamic
provisioning on) beside the scheduler, coordinating only through apiserver
state. This rebuild keeps that shape: a watch-driven loop over the store
that binds pending PVCs to matching PVs (capacity + storage class) and
dynamically provisions a PV when none matches — never talking to the
scheduler directly (SURVEY §1: hub-and-spoke through shared state).
"""
from __future__ import annotations

import itertools
import logging
import threading
from typing import Optional

from ..errors import ConflictError, NotFoundError
from ..state import objects as obj
from ..state.store import ClusterStore

log = logging.getLogger(__name__)


class PVController:
    def __init__(self, store: ClusterStore, *, sync_period_s: float = 0.1,
                 dynamic_provisioning: bool = True):
        self._store = store
        self._sync = sync_period_s  # reference uses 1s (pvcontroller.go:31)
        self._dynamic = dynamic_provisioning
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._prov_seq = itertools.count(1)
        # Written by the controller thread only; stats() reads them.
        self._events = 0
        self._syncs = 0
        self._pods_watched = False

    def start(self) -> None:
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="pv-controller")
        self._thread.start()

    def shutdown(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None

    def stats(self) -> dict:
        """Events taken off the watch, syncs run, and whether the watch
        follows pods now (a WaitForFirstConsumer claim waits)."""
        return {"events_total": self._events, "syncs_total": self._syncs,
                "pods_watched": self._pods_watched}

    # ---- sync loop ------------------------------------------------------

    ZONE_KEY = "topology.kubernetes.io/zone"
    # What the controller works on. Pods join the watch only while a
    # WaitForFirstConsumer claim waits for its consumer to be scheduled
    # (upstream late binding), so volumeless pod churn never reaches it.
    KINDS = ("PersistentVolumeClaim", "PersistentVolume")
    BURST = 1024  # events drained per store-lock acquisition

    def _run(self) -> None:
        watcher = self._store.watch(kinds=list(self.KINDS))
        follow = self._sync_once()
        while not self._stop.is_set():
            kinds = list(self.KINDS) + (["Pod"] if follow else [])
            try:
                if follow != self._pods_watched:
                    # Reopen at the old cursor: one stream, no gap, and
                    # no event delivered twice.
                    watcher.stop()
                    watcher = self._store.watch(
                        kinds, from_version=watcher.cursor)
                    self._pods_watched = follow
                evs = watcher.next_events(self.BURST, timeout=self._sync)
            except ValueError:
                # The cursor left the retained log. Each sync re-lists
                # what it needs, so a watch from now misses nothing.
                watcher.stop()
                watcher = self._store.watch(kinds)
                self._pods_watched = follow
                evs = []
            self._events += len(evs)
            if evs and not any(map(self._prompts_sync, evs)):
                continue  # no claim's consumer newly scheduled
            # a burst worth a sync, or a quiet period (the backstop)
            follow = self._sync_once()
        watcher.stop()

    @staticmethod
    def _prompts_sync(ev) -> bool:
        """A claim or volume changed, or a pod with claims is bound."""
        if ev.kind != "Pod":
            return True
        return bool(ev.object.spec.node_name and obj.claim_keys(ev.object))

    def _sync_once(self) -> bool:
        """Bind every pending claim that can be bound. True while a
        WaitForFirstConsumer claim still waits for a scheduled consumer."""
        self._syncs += 1
        try:
            pvcs = self._store.list("PersistentVolumeClaim")
            pvs = self._store.list("PersistentVolume")
        except Exception:
            return self._pods_watched
        available = [pv for pv in pvs if pv.phase == "Available"]
        consumer_zones = None  # lazy: only listed when a WFFC claim pends
        waiting = False
        for pvc in pvcs:
            if pvc.phase == "Bound":
                continue
            zone = None
            if pvc.binding_mode == "WaitForFirstConsumer":
                if consumer_zones is None:
                    consumer_zones = self._scheduled_consumer_zones()
                if pvc.key not in consumer_zones:
                    waiting = True  # no scheduled consumer yet: wait
                    continue
                zone = consumer_zones[pvc.key]
            match = self._find_match(pvc, available, zone=zone)
            if match is None and self._dynamic:
                match = self._provision(pvc, zone=zone)
            if match is not None:
                self._bind(pvc, match)
                available = [pv for pv in available if pv.key != match.key]
        return waiting

    def _scheduled_consumer_zones(self):
        """PVC key → zone of the node its scheduled consumer landed on
        ("" when the node has no zone label)."""
        zones = {}
        try:
            node_zone = {n.metadata.name: n.metadata.labels.get(self.ZONE_KEY, "")
                         for n in self._store.list("Node")}
            for pod in self._store.list("Pod"):
                if not pod.spec.node_name:
                    continue
                for ck in obj.claim_keys(pod):
                    zones[ck] = node_zone.get(pod.spec.node_name, "")
        except Exception:
            pass
        return zones

    def _find_match(self, pvc, available, zone=None):
        want = pvc.request.get("ephemeral-storage", 0)
        candidates = [
            pv for pv in available
            if pv.storage_class == pvc.storage_class
            and pv.capacity.get("ephemeral-storage", 0) >= want]
        if zone:
            # Late binding is topology-aware: prefer a PV in the consumer
            # pod's zone; fall back to zoneless PVs (attachable anywhere).
            in_zone = [pv for pv in candidates
                       if pv.metadata.labels.get(self.ZONE_KEY) == zone]
            candidates = in_zone or [
                pv for pv in candidates
                if not pv.metadata.labels.get(self.ZONE_KEY)]
        # smallest adequate volume, upstream's match heuristic
        return min(candidates,
                   key=lambda pv: pv.capacity.get("ephemeral-storage", 0),
                   default=None)

    def _provision(self, pvc, zone=None):
        labels = {self.ZONE_KEY: zone} if zone else {}
        pv = obj.PersistentVolume(
            metadata=obj.ObjectMeta(
                name=f"pv-provisioned-{next(self._prov_seq)}",
                labels=labels),
            capacity=dict(pvc.request),
            storage_class=pvc.storage_class,
            phase="Available")
        try:
            return self._store.create(pv)
        except Exception:
            return None

    def _bind(self, pvc, pv) -> None:
        try:
            pv.claim_ref = pvc.key
            pv.phase = "Bound"
            self._store.update(pv)
        except (ConflictError, NotFoundError):
            return
        try:
            pvc.volume_name = pv.metadata.name
            pvc.phase = "Bound"
            self._store.update(pvc)
            log.info("bound PVC %s to PV %s", pvc.key, pv.metadata.name)
        except (ConflictError, NotFoundError):
            # PVC vanished mid-bind: roll the PV back to Available so its
            # capacity isn't stranded behind a dangling claim_ref.
            try:
                pv.claim_ref = ""
                pv.phase = "Available"
                self._store.update(pv)
            except (ConflictError, NotFoundError):
                pass
