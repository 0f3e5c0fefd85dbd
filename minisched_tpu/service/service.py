"""Scheduler lifecycle service.

Rebuild of reference scheduler/scheduler.go: NewSchedulerService (:36),
StartScheduler (:50-80: informer factory + event broadcaster + minisched.New
+ start informers + go Run), RestartScheduler (:40-47: shutdown + start with
the retained config), ShutdownScheduler (:82-87), GetSchedulerConfig (:89).

Multi-profile: start_scheduler also accepts a SchedulerConfiguration (or a
list of Profiles). Each profile gets its own engine instance; pods select
a profile with spec.scheduler_name (reference KubeSchedulerProfile
semantics, scheduler.go:97-142). All engines share the one store — capacity
accounting stays globally consistent because every engine's informers see
every bind.
"""
from __future__ import annotations

import logging
import os
import threading
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Union

from ..config import SchedulerConfig
from ..engine.clusterstate import SharedClusterState
from ..engine.queue import bucket_major_quotas, weighted_gather
from ..engine.scheduler import Scheduler
from ..explain.resultstore import ResultStore
from ..faults import FaultWorkerDeath
from .config import SchedulerConfiguration
from .defaultconfig import Profile, default_scheduler_profile

log = logging.getLogger(__name__)

ProfileSpec = Union[Profile, Sequence[Profile], SchedulerConfiguration, None]


class SchedulerService:
    def __init__(self, store, *, checkpoint_path: Optional[str] = None,
                 checkpoint_interval_s: float = 30.0):
        """``checkpoint_path`` wires the etcd-durability analog into the
        service lifecycle (reference: state persists ambiently in etcd,
        k8sapiserver/k8sapiserver.go:93-105): the store is checkpointed
        on an interval while the scheduler runs and once more on
        shutdown; boot the store with state.persistence.open_or_restore
        to resume after a crash/restart. In-process stores only — a
        RemoteStore client's durability belongs to its server."""
        self._store = store
        self._scheds: Dict[str, Scheduler] = {}
        self._shared_state: Optional[SharedClusterState] = None
        self._profiles: List[Profile] = []
        self._multi = False
        self._config: Optional[SchedulerConfig] = None
        self.result_store: Optional[ResultStore] = None
        # Replicated-fleet mode (fleet/supervisor.py): N engine replicas
        # with shard leases instead of one engine. Single-profile only —
        # profiles partition by scheduler_name, shards by pod-key hash;
        # crossing the two routing schemes is undefined and refused.
        self._fleet = None
        self._fleet_n = 0
        # Out-of-process fleet (fleet/procfleet.py): N replica PROCESSES
        # over RemoteStore; the service owns a main apiserver when the
        # store is in-process (replicas need a wire to reach it).
        self._fleet_proc_n = 0
        self._proc_api = None
        # RemoteStore also has a snapshot() (the /snapshot verb), so the
        # duck check must be the checkpointer's ACTUAL surface —
        # resource_version() is the store-local half RemoteStore lacks.
        if checkpoint_path and not (hasattr(store, "snapshot")
                                    and hasattr(store, "resource_version")):
            raise ValueError(
                "checkpoint_path requires an in-process ClusterStore; "
                "remote stores persist on the serving side")
        self._checkpoint_path = checkpoint_path
        self._checkpoint_interval_s = checkpoint_interval_s
        self._checkpointer = None

    @property
    def scheduler(self) -> Optional[Scheduler]:
        """The first (or only) running engine — the single-profile API.
        Fleet mode: the first LIVE replica's engine."""
        if self._fleet is not None:
            return self._fleet.scheduler
        return next(iter(self._scheds.values()), None)

    @property
    def schedulers(self) -> Dict[str, Scheduler]:
        """Profile name → engine (fleet mode: replica id → engine,
        live replicas only — kills/restarts keep this view fresh)."""
        if self._fleet is not None:
            return self._fleet.engines()
        return dict(self._scheds)

    @property
    def fleet(self):
        """The FleetSupervisor when fleet mode is on, else None (the
        lifecycle kill/restart generators reach the fleet here)."""
        return self._fleet

    def metrics(self) -> Dict[str, float]:
        """Engine cycle metrics across every profile, flattened for one
        /metrics scrape (APIServer.metrics_providers): single-profile
        services expose the engine's keys unprefixed (the common case,
        stable dashboards); MULTI-PROFILE configurations prefix each key
        with the profile name — keyed on the config style (``_multi``,
        the same bit that decides pod routing), not the engine count, so
        a one-profile multi-config keeps stable prefixed names when a
        second profile is added later. The engine's non-numeric
        diagnostic fields (batch_sizes list, last_shapes tuple) are
        dropped here so the annotation is honest — diagnostics stay on
        Scheduler.metrics(), where bench/tests read them."""

        def numeric(m: Dict) -> Dict[str, float]:
            return {k: v for k, v in m.items()
                    if isinstance(v, (int, float)) and not isinstance(v, bool)}

        if self._fleet is not None:
            # Fleet: counters summed across live replicas (the
            # fleet-wide totals), plus lease/takeover gauges.
            return self._fleet.metrics()
        scheds = self.schedulers
        if not scheds:
            return {}
        if not self._multi:
            return numeric(next(iter(scheds.values())).metrics())
        out: Dict[str, float] = {}
        for name, engine in scheds.items():
            for k, v in numeric(engine.metrics()).items():
                out[f"{name}_{k}"] = v
        return out

    def metrics_histograms(self) -> Dict[str, dict]:
        """Per-pod lifecycle latency histogram snapshots across every
        profile (obs.Histogram snapshots: bounds/counts/sum/count),
        keyed like :meth:`metrics` — unprefixed for single-profile,
        profile-prefixed for multi. This is the
        ``APIServer.histogram_providers`` feed for the native Prometheus
        histogram exposition (`_bucket`/`_sum`/`_count`); ``metrics()``
        itself stays ``Dict[str, float]`` (a pinned contract — the flat
        gauges must remain scrape-compatible)."""
        if self._fleet is not None:
            return self._fleet.histograms()
        scheds = self.schedulers
        if not scheds:
            return {}
        if not self._multi:
            m = next(iter(scheds.values())).metrics()
            return dict(m.get("histograms", {}))
        out: Dict[str, dict] = {}
        for name, engine in scheds.items():
            for k, v in engine.metrics().get("histograms", {}).items():
                out[f"{name}_{k}"] = v
        return out

    def admission_reject_reason(self) -> Optional[str]:
        """The apiserver's overload admission provider
        (``APIServer.admission_providers``): the first engine whose
        overload controller is at/past its HTTP-reject rung supplies
        the typed 429 reason; None admits. With MINISCHED_OVERLOAD
        unset this is a handful of attribute tests per pod create."""
        for engine in self.schedulers.values():
            reason = engine.overload_reject_reason()
            if reason:
                return reason
        return None

    def timeline(self, since: int = 0) -> Dict[str, dict]:
        """Per-profile temporal-telemetry documents (the ``GET
        /timeline`` payload): profile name → ``Scheduler.timeline()``
        dict (snapshot ring + SLO alert log). Always keyed by profile
        name — the timeline is a diagnostic surface, and an explicit
        key survives a later second profile without renaming (unlike
        metrics(), whose unprefixed single-profile names are a pinned
        scrape contract). ``since`` is the per-profile row cursor
        (``?since=<seq>``; poll with each document's ``next_seq``).
        Seq spaces are independent per profile, so a multi-profile
        scraper polls one profile per request
        (``?profile=<name>&since=<seq>`` on the endpoint) — one scalar
        cursor across profiles would starve the slower profile."""
        return {name: engine.timeline(since)
                for name, engine in self.schedulers.items()}

    def journal(self, since: int = 0) -> Dict:
        """The ``GET /journal`` payload (``APIServer.journal_providers``
        feed): the process-wide decision journal — one causal event log
        shared by every profile engine, each event tagged with its
        serving profile. Empty-but-valid with MINISCHED_JOURNAL unset.
        Under a PROCESS fleet the supervisor's merged cross-process
        stream answers instead (source-tagged, re-sequenced), so one
        ``GET /journal`` narrates the whole fleet."""
        if self._fleet is not None and hasattr(self._fleet, "journal"):
            return self._fleet.journal(since)
        from ..obs.journal import JOURNAL

        return JOURNAL.to_doc(since)

    def provenance(self, pod_key: str):
        """The ``GET /provenance/<pod>`` record
        (``APIServer.provenance_providers`` feed): the first profile
        engine holding a decision-provenance record for the pod answers
        (profiles share no pods, replicas share no shards); None = no
        record. A process fleet fans the lookup out to the replica
        sidecars (record attributed with the serving replica)."""
        if self._fleet is not None and hasattr(self._fleet,
                                               "provenance"):
            return self._fleet.provenance(pod_key)
        for engine in self.schedulers.values():
            rec = engine.provenance(pod_key)
            if rec is not None:
                return rec
        return None

    def start_scheduler(self, profile: ProfileSpec = None,
                        config: Optional[SchedulerConfig] = None,
                        fleet: Optional[int] = None,
                        fleet_proc: Optional[int] = None) -> Scheduler:
        """``fleet``: run N replicated engines with shard leases instead
        of one (fleet/supervisor.py); None reads ``MINISCHED_FLEET``
        (0/1 = off). ``fleet_proc``: run N replica PROCESSES over
        RemoteStore instead (fleet/procfleet.py); None reads
        ``MINISCHED_FLEET_PROC`` and wins over ``fleet`` when both are
        set — process isolation subsumes thread isolation. Both fleet
        modes are single-profile only. In process-fleet mode there is
        no in-process engine: this returns None and the fleet surfaces
        live on :attr:`fleet`."""
        if self._scheds or self._fleet is not None:
            raise RuntimeError("scheduler already running")
        if isinstance(profile, SchedulerConfiguration):
            profiles, self._multi = list(profile.profiles), True
            if (config is not None
                    and profile.percentage_of_nodes_to_score
                    != type(profile)().percentage_of_nodes_to_score):
                import dataclasses as _dc

                config = _dc.replace(
                    config, percentage_of_nodes_to_score=(
                        profile.percentage_of_nodes_to_score))
            elif config is None and profile.percentage_of_nodes_to_score:
                config = SchedulerConfig(percentage_of_nodes_to_score=(
                    profile.percentage_of_nodes_to_score))
        elif isinstance(profile, (list, tuple)):
            profiles, self._multi = list(profile), True
        else:
            profiles = [profile or default_scheduler_profile()]
            self._multi = False
        if not profiles:
            profiles = [default_scheduler_profile()]
        names = [p.name for p in profiles]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate profile names: {names}")

        self._profiles = profiles
        self._config = config or SchedulerConfig()
        recorder = None
        if self._config.explain:
            # Engine mode: flush annotations on a background worker (the
            # reference's off-hot-path informer-event flush pattern).
            self.result_store = recorder = ResultStore(self._store,
                                                       async_flush=True)
        from ..fleet.shardmap import fleet_from_env, fleet_proc_from_env

        n_proc = (int(fleet_proc) if fleet_proc is not None
                  else fleet_proc_from_env())
        if n_proc >= 2:
            if self._multi:
                raise ValueError(
                    "fleet mode is single-profile: profiles partition "
                    "pods by scheduler_name, fleet shards by pod-key "
                    "hash — one routing scheme at a time")
            return self._start_proc_fleet(profiles[0], n_proc)
        n_fleet = int(fleet) if fleet is not None else fleet_from_env()
        if n_fleet >= 2:
            if self._multi:
                raise ValueError(
                    "fleet mode is single-profile: profiles partition "
                    "pods by scheduler_name, fleet shards by pod-key "
                    "hash — one routing scheme at a time")
            return self._start_fleet(profiles[0], recorder, n_fleet)
        self._fleet_n = 0
        # Build every PluginSet BEFORE starting any engine so a bad later
        # profile (unknown plugin, bad args) can't leave a half-started
        # service behind.
        built = [(p, p.build()) for p in profiles]
        # ONE cluster state (feature cache + informer set) for every
        # profile engine (reference: one scheduler struct, many profiles,
        # scheduler.go:97-142) — per-profile caches would multiply
        # tens-of-MB node state AND let two profiles jointly over-commit
        # a node neither would alone. All engines must register before
        # the first start() syncs the informers.
        self._shared_state = SharedClusterState(self._store)
        if recorder is not None:
            # Reference resultstore contract (store.go:60-68): pod-update
            # informer events drive annotation flushes. The recorder's
            # worker already flushes after ingest; this event hook
            # re-drives pods whose flush exhausted its CAS retries, so
            # results still land on the pod's next update.
            from ..state.informer import ResourceEventHandlers

            self._shared_state.informer_factory.add_handlers(
                "Pod", ResourceEventHandlers(
                    on_update=lambda old, new: recorder.on_pod_event(
                        new.key),
                    # bulk-bind MODIFIED bursts: one lock acquisition for
                    # the whole run, not one per pod on the dispatch
                    # thread
                    on_update_many=lambda pairs: recorder.on_pod_events(
                        [new.key for _old, new in pairs]),
                    # terminal sweep: a deleted pod's recorded results
                    # can never flush or be queried — evict both tiers
                    # so lifecycle churn cannot grow the store
                    # (resultstore retention bound; counted in
                    # resultstore_evictions)
                    on_delete=lambda pod: recorder.delete_data(pod.key)))
        for p, plugin_set in built:
            # In multi-profile mode each engine only takes pods naming its
            # profile; a single profile keeps the accept-everything legacy
            # behavior.
            sched = Scheduler(
                self._store, plugin_set, self._config, recorder=recorder,
                scheduler_names={p.name} if self._multi else None,
                shared=self._shared_state, profile=p.name)
            self._scheds[p.name] = sched
        for sched in self._scheds.values():
            sched.start()
        if self._checkpoint_path:
            from ..state.persistence import Checkpointer

            self._checkpointer = Checkpointer(
                self._store, self._checkpoint_path,
                interval_s=self._checkpoint_interval_s)
        log.info("scheduler started (profiles=%s)", names)
        return self.scheduler

    def _start_fleet(self, p: Profile, recorder, n: int) -> Scheduler:
        """Replicated-fleet wiring: N engines, each with its OWN private
        cluster state (informers + feature cache) against the one store
        — independent optimistic views, races resolved at the store's
        bind CAS — supervised by a FleetSupervisor driving the shard
        leases. The checkpointer (when configured) is created first so
        takeovers can persist post-claim ownership promptly."""
        from ..fleet.shardmap import shards_from_env
        from ..fleet.supervisor import FleetSupervisor

        if self._checkpoint_path:
            from ..state.persistence import Checkpointer

            self._checkpointer = Checkpointer(
                self._store, self._checkpoint_path,
                interval_s=self._checkpoint_interval_s)

        def factory(rid: str, _p=p, _rec=recorder) -> Scheduler:
            return Scheduler(self._store, _p.build(), self._config,
                             recorder=_rec, profile=_p.name, replica=rid)

        self._fleet = FleetSupervisor(
            self._store, engine_factory=factory, replicas=n,
            n_shards=shards_from_env(n),
            checkpointer=self._checkpointer)
        self._fleet_n = n
        self._fleet.start()
        log.info("scheduler fleet started (%d replicas, profile=%s, "
                 "%d shards)", n, p.name, self._fleet.n_shards)
        return self.scheduler

    def _start_proc_fleet(self, p: Profile, n: int):
        """Out-of-process fleet wiring: N replica processes, each a full
        engine over RemoteStore. With an in-process store the service
        boots (and owns) the main apiserver the replicas dial; with a
        RemoteStore the replicas dial its address directly — the serving
        side already exists."""
        import dataclasses as _dc

        from ..fleet.procfleet import (ProcFleetSupervisor,
                                       rebalance_from_env)
        from ..fleet.shardmap import shards_from_env

        if self._checkpoint_path:
            from ..state.persistence import Checkpointer

            self._checkpointer = Checkpointer(
                self._store, self._checkpoint_path,
                interval_s=self._checkpoint_interval_s)
        addr = getattr(self._store, "address", None)
        if addr is None or hasattr(self._store, "resource_version"):
            # In-process store: serve it over the wire ourselves.
            from ..apiserver.server import APIServer

            self._proc_api = APIServer(self._store).start()
            addr = self._proc_api.address
        self._fleet = ProcFleetSupervisor(
            self._store, addr, replicas=n,
            n_shards=shards_from_env(n),
            config_overrides=_dc.asdict(self._config),
            profile=p, rebalance=rebalance_from_env())
        self._fleet_proc_n = n
        self._fleet.start()
        log.info("out-of-process scheduler fleet started (%d replica "
                 "processes, profile=%s, %d shards, apiserver=%s)",
                 n, p.name, self._fleet.n_shards, addr)
        return None

    def shutdown_scheduler(self) -> None:
        if self._fleet is not None:
            self._fleet.shutdown()
            self._fleet = None
            log.info("scheduler fleet shut down")
        if self._proc_api is not None:
            self._proc_api.shutdown()
            self._proc_api = None
        for name, sched in list(self._scheds.items()):
            sched.shutdown()
            log.info("scheduler %s shut down", name)
        if self._scheds and self._shared_state is not None:
            self._shared_state.shutdown()
            self._shared_state = None
        self._scheds.clear()
        if self._checkpointer is not None:
            # Final checkpoint AFTER the engines stop: every in-flight
            # bind has committed, so the snapshot is the state a restart
            # resumes from (reference: shutdown leaves etcd consistent).
            self._checkpointer.close()
            self._checkpointer = None

    def restart_scheduler(self) -> Scheduler:
        """Shutdown + start with the retained profile/config (reference
        RestartScheduler scheduler.go:40-47). Queue/cache state is rebuilt
        from surviving store state, same as the reference."""
        profiles, config, multi = self._profiles, self._config, self._multi
        fleet_n, proc_n = self._fleet_n, self._fleet_proc_n
        self.shutdown_scheduler()
        self._profiles, self._config = [], None
        self._fleet_proc_n = 0
        spec: ProfileSpec = profiles if multi else (profiles[0] if profiles
                                                    else None)
        return self.start_scheduler(spec, config, fleet=fleet_n or None,
                                    fleet_proc=proc_n or None)

    def get_scheduler_profile(self) -> Optional[Profile]:
        """reference GetSchedulerConfig (scheduler.go:89-91)."""
        return self._profiles[0] if self._profiles else None

    def get_scheduler_profiles(self) -> List[Profile]:
        return list(self._profiles)


# ---- fused multi-tenant arbitration (ISSUE 16) --------------------------


def tenants_fuse_from_env() -> int:
    """``MINISCHED_TENANTS_FUSE``: the fused-tranche width cap (how many
    tenants one vmapped dispatch may serve). 0/1/unset = fusion off —
    the coordinator then steps each tenant sequentially, which is also
    the bit-identity baseline the fused mode is measured against."""
    try:
        return int(os.environ.get("MINISCHED_TENANTS_FUSE", "0") or 0)
    except ValueError:
        return 0


@dataclass
class Tenant:
    """One virtual cluster in a fused multi-tenant serving group: its
    OWN ClusterStore (tenants share no objects, unlike profiles, which
    partition one store's pods), its fair-share weight for the fused
    batch-slot gather, and an optional plugin profile."""

    name: str
    store: object
    weight: float = 1.0
    profile: Optional[Profile] = None


class TenantFusionCoordinator:
    """Serve T virtual clusters from ONE arbitration dispatch per round
    (ROADMAP "fused multi-tenant arbitration").

    Each tenant gets a full private engine — own store, own
    SharedClusterState/feature cache (so per-tenant sparse deltas route
    to the owning tenant's slab by construction), own queue, own
    overload controller (``OverloadController(name=profile)``, so the
    per-profile shed_priority overrides land per tenant) — but NO run
    thread: the coordinator drives every engine's prepare/resolve/commit
    phases from one serve thread, with a ``TenantCacheMux``
    (encode/cache.py) installed at the dispatch seam when fusion is on.

    One round:

      1. ``pending_count`` per tenant → tenants group by the pod pad
         bucket their demand serves at, and ``weighted_gather`` splits
         the config's ``max_batch_size`` batch slots by tenant weight
         INSIDE each bucket group (engine/queue.bucket_major_quotas —
         one hot tenant cannot starve its group's fused slot, and a
         small tenant never pads to a huge one's bucket).
      2. Pop each tenant's quota; ``mux.round_pods`` is set per bucket
         group to that GROUP's common pod bucket so its ragged tenant
         batches harmonize by masked-row padding (the pinned pad
         invariant: pad rows are invalid and change no real row's
         decision).
      3. Each engine's prepare runs — a fusable batch SUBMITS its
         staged step inputs to the mux; anything gated out (gangs,
         nominations, degraded rungs, sampling, explain, mesh, spread)
         dispatches solo inside prepare exactly as before.
      4. ``mux.dispatch()`` fires one vmapped step per compatibility
         group and hands every lane its decision planes.
      5. Resolve + commit per tenant, in tenant order — each engine's
         own settlement machinery, journal/provenance attribution
         riding the engine's profile label as always; a lane's resolve
         fault engages only THAT engine's supervised replay.

    With ``fuse < 2`` (``MINISCHED_TENANTS_FUSE`` unset) no mux is
    installed and the same loop steps each tenant's batch through its
    own full dispatch — the sequential baseline. Decisions are
    bit-identical between the two modes in every engine config
    (tests/test_tenants.py pins it); only the dispatch/fetch counts
    differ, which is the whole point (BENCH_TENANTS.json's >=5x claim).
    """

    def __init__(self, tenants: Sequence[Tenant],
                 config: Optional[SchedulerConfig] = None,
                 fuse: Optional[int] = None):
        from ..encode.cache import TenantCacheMux

        if fuse is None:
            fuse = tenants_fuse_from_env()
        self.fuse = max(0, int(fuse))
        self.fused = self.fuse >= 2
        names = [t.name for t in tenants]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate tenant names: {names}")
        if not tenants:
            raise ValueError("at least one tenant required")
        self._tenants = list(tenants)
        self._config = config or SchedulerConfig()
        self._weights = [float(t.weight) for t in self._tenants]
        self.mux = TenantCacheMux() if self.fused else None
        if self.mux is not None:
            self.mux.max_lanes = self.fuse
        self._engines: Dict[str, Scheduler] = {}
        for t in self._tenants:
            pset = (t.profile or default_scheduler_profile()).build()
            eng = Scheduler(t.store, pset, self._config, profile=t.name)
            if self.mux is not None:
                eng._tenant_mux = self.mux
            self._engines[t.name] = eng
        self._rounds = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    @property
    def engines(self) -> Dict[str, Scheduler]:
        return dict(self._engines)

    def engine(self, name: str) -> Scheduler:
        return self._engines[name]

    def store(self, name: str):
        for t in self._tenants:
            if t.name == name:
                return t.store
        raise KeyError(name)

    # ---- lifecycle ------------------------------------------------------

    def start(self) -> None:
        """Sync every tenant's informers, then start ONE serve thread.
        Engines never get their own run loop (``Scheduler.start`` is not
        called) — the coordinator owns the phase sequencing, which is
        what lets one round's prepares rendezvous at the mux."""
        for eng in self._engines.values():
            eng._shared.ensure_started()
        self._thread = threading.Thread(target=self._serve, daemon=True,
                                        name="tenant-coordinator")
        self._thread.start()
        log.info("tenant coordinator started (%d tenants, fuse=%d)",
                 len(self._tenants), self.fuse)

    def shutdown(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        for eng in self._engines.values():
            eng.shutdown()

    def _serve(self) -> None:
        while not self._stop.is_set():
            try:
                served = self.serve_round()
            except Exception:
                log.exception("tenant round failed")
                served = False
            if not served:
                self._stop.wait(0.01)

    # ---- one fused round ------------------------------------------------

    def serve_round(self) -> bool:
        """Drive one coordinated round across every tenant. Returns
        False when no tenant had poppable work (the serve thread then
        idles briefly). Public so tests can single-step rounds without
        the thread.

        BUCKET-MAJOR grouping (ISSUE 20): tenants are grouped by the
        pod pad bucket their pending demand would serve at, and slots
        apportion per group (engine/queue.bucket_major_quotas), not
        over one global bucket — mixed-size tenants fuse WITHIN their
        bucket instead of every lane padding to the widest tenant's
        shape. Each group's prepares run at that group's common pad
        (mux.round_pods), then ONE mux.dispatch() fires every group's
        fused tranche — a mixed round issues one vmapped dispatch PER
        bucket group, never a solo regression. The sequential
        (``fuse < 2``) coordinator walks the identical group order and
        quotas, so both modes pop identical pods per round — the
        bit-identity precondition."""
        from ..encode.cache import step_bucket

        engines = [self._engines[t.name] for t in self._tenants]
        demands = [eng.queue.pending_count() for eng in engines]
        if not any(demands):
            return False
        cap = self._config.max_batch_size
        buckets = [step_bucket(min(d, cap), self._config.pod_bucket_min)
                   if d else 0 for d in demands]
        lanes = []
        for _bucket, idxs, quotas in bucket_major_quotas(
                demands, self._weights, cap, buckets):
            work = []
            for i, quota in zip(idxs, quotas):
                if quota <= 0:
                    continue
                batch = engines[i].queue.pop_batch(quota, timeout=0.05)
                if batch:
                    work.append((engines[i], batch))
            if not work:
                continue
            if self.mux is not None:
                # The GROUP's common pod pad: every lane in this bucket
                # group encodes at the group's widest batch so its
                # stacked (T, P, ...) tranche is rectangular; a
                # different group's pad differs — its lanes land in a
                # different compat group at the mux by shape signature.
                self.mux.round_pods = step_bucket(
                    max(len(b) for _eng, b in work),
                    self._config.pod_bucket_min)
            for eng, batch in work:
                lanes.append((eng, eng._prepare_batch(batch)))
        if not lanes:
            return False
        if self.mux is not None:
            self.mux.dispatch()
        for eng, inf in lanes:
            try:
                eng._resolve_batch(inf)
            except Exception:
                # Per-lane containment, the engine's own resolve-guard
                # idiom: a resolve fault (e.g. a fetch-sanity
                # EngineDesync on a corrupted readback) engages THAT
                # engine's supervised replay — rewound, escalated,
                # re-run bit-identically on its degraded solo rung —
                # while the round's other tenants settle undisturbed.
                log.exception("tenant lane resolve failed; engaging "
                              "that engine's supervisor")
                eng._supervised_retry(inf.batch, inf)
                continue
            try:
                eng._commit_batch(inf)
            except FaultWorkerDeath:
                # Same containment as the engine's synchronous cycle:
                # requeue the flush tranche, keep the coordinator alive.
                for qpi, _plugins, _msg, _retry in inf.failures:
                    eng.queue.requeue_backoff(qpi)
        self._rounds += 1
        return True

    # ---- observability --------------------------------------------------

    def metrics(self) -> Dict[str, float]:
        """Tenant-prefixed engine metrics + the mux's fusion ledger +
        the cross-tenant dispatch/fetch totals the bench's >=5x claim
        compares between fused and sequential modes."""
        out: Dict[str, float] = {}
        total_disp = 0.0
        total_fetch = 0.0
        for name, eng in self._engines.items():
            m = eng.metrics()
            for k, v in m.items():
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    out[f"{name}_{k}"] = v
            total_disp += m.get("steps_dispatched", 0)
            total_fetch += m.get("decision_fetches", 0)
        if self.mux is not None:
            out.update(self.mux.counters)
            total_disp += self.mux.counters["tenant_dispatches"]
            total_fetch += self.mux.counters["tenant_fetches"]
        out["steps_dispatched_total"] = total_disp
        out["decision_fetches_total"] = total_fetch
        out["tenant_rounds_served"] = self._rounds
        return out

    def provenance(self, pod_key: str):
        """First tenant engine holding a record answers (tenants share
        no pods — disjoint stores)."""
        for eng in self._engines.values():
            rec = eng.provenance(pod_key)
            if rec is not None:
                return rec
        return None
