"""Core scheduling engine: batched scheduling cycles over the XLA step.

Rebuild of reference minisched/minisched.go + initialize.go. One cycle
(reference scheduleOne, minisched.go:32-112) becomes one *batch* cycle:

  pop batch (queue) → encode pods → snapshot node features (cache) →
  jitted step: filters ∧ → scores → normalize → weigh → sum → greedy
  capacity-aware assignment → per-pod: permit plugins (host) →
  async binding cycle (thread pool) → bind CAS into the store.

The scheduler "assumes" a pod onto its node at selection time (cache
accounting) and unassumes on any later failure — upstream kube-scheduler's
assume/forget model, which the reference skips (its sequential loop re-Lists
nodes every pod, minisched.go:40, so stale capacity only costs retries).

Failure path mirrors ErrorFunc (minisched.go:283-298): record the rejecting
plugins on the pod status, emit a FailedScheduling event, park the pod in
unschedulableQ keyed by those plugins for event-driven revival.
"""
from __future__ import annotations

import logging
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Set

import jax
import numpy as np

from ..config import SchedulerConfig
from ..encode import NodeFeatureCache, encode_pods
from ..encode.cache import bucket_for, step_bucket
from ..encode.features import NodeFeatures
from ..errors import ConflictError, NotFoundError
from ..faults import FAULTS, FaultWorkerDeath
from ..obs import (Histogram, batch_step, gc_collections_total,
                   gc_full_pause_s_total, gc_pause_s_total, instant, span,
                   watch_gc)
from ..obs import bundle as bundle_mod
from ..obs import slo as slo_mod
from ..obs.journal import JOURNAL, ProvenanceStore
from ..obs.journal import note as jnote
from ..obs.timeseries import TIMELINE, TimelineTracker
from ..ops.pipeline import (Decision, arm_compile_cache, build_loop_step,
                            build_step)
from ..ops.residency import (I16_SAT, apply_rows, apply_rows_bytes,
                             pack_decision_i32, pack_decision_slim,
                             unpack_decision_i32, unpack_decision_slim)
from ..plugins.base import PluginSet
from ..state.events import ActionType, ClusterEvent, EventBroadcaster, GVK
from ..state.objects import Pod, claim_keys, gang_key
from . import overload as overload_mod
from .queue import (BATCH_CAPACITY, COSCHEDULING, QueuedPodInfo,
                    SchedulingQueue)
from .waitingpod import WaitingPod

log = logging.getLogger(__name__)

# Shared by the arbitration and repair-leftover failure paths — the two
# must never diverge on reason text or plugin attribution.
_SPREAD_REVOKE_MSG = (
    "placement would breach a topology constraint (max_skew / required "
    "anti-affinity) within this batch; retrying against committed counts")


class EngineDesync(RuntimeError):
    """A supervisor DETECTOR verdict — the engine's view of device state
    failed a sanity/cross check (decision readback out of range,
    non-finite capacity after a device-debit replay, resident carry
    diverged from the host mirror). Contained like any batch fault:
    rollback, degrade, retry."""


#: The supervisor's degradation ladder, fastest first. Level indexes it.
DEGRADATION_LADDER = ("resident", "upload", "sync", "quarantine")

#: SLO early-warning pre-arming (obs/slo.py → _Supervisor.early_warning):
#: a burning SLO arms the per-batch watchdog at this fallback deadline
#: for this many batches even when MINISCHED_WATCHDOG is unset — the
#: sentinel's trend verdict buys the ladder a tripwire BEFORE a wedged
#: step forces the exception path.
SLO_PREARM_BATCHES = 64
SLO_PREARM_WATCHDOG_S = 30.0


class _Supervisor:
    """Fault detection + containment state for one engine.

    The engine's fast paths (device-resident carry, two-deep pipeline)
    are retried down a counted degradation ladder when a batch faults:

        0 resident    full fast path (device residency + pipeline)
        1 upload      residency dropped; every batch uploads dynamic
                      leaves (the MINISCHED_DEVICE_RESIDENT=0 shape)
        2 sync        additionally no pipelining: one batch at a time,
                      prepare→resolve→commit inline (MINISCHED_PIPELINE=0
                      shape)
        3 quarantine  the poisoned batch is requeued at the backoff
                      ceiling instead of retried; subsequent traffic
                      keeps running at the sync rung

    ``level`` is written ONLY on the scheduling thread (resolve,
    supervised retry, commit-await) — the one thread that also reads it
    for gating — so it needs no lock; counters live in the engine's
    metrics dict under its lock. After ``probation_batches`` consecutive
    clean batches at a degraded level the supervisor re-escalates one
    rung back toward the full fast path."""

    __slots__ = ("_sched", "level", "_clean", "prearm")

    def __init__(self, sched: "Scheduler"):
        self._sched = sched
        self.level = 0
        self._clean = 0
        # Batches left on the SLO early-warning posture: while > 0 the
        # watchdog runs at SLO_PREARM_WATCHDOG_S even with the knob
        # unset. Scheduling-thread only, like ``level``.
        self.prearm = 0

    def allows_residency(self) -> bool:
        return self.level == 0

    def sync_only(self) -> bool:
        return self.level >= 2

    def escalate(self, reason: str) -> None:
        self._clean = 0
        if self.level >= len(DEGRADATION_LADDER) - 1:
            return
        self.level += 1
        self._sched._sup_count("supervisor_escalations")
        instant("supervisor.escalate", to=DEGRADATION_LADDER[self.level],
                level=self.level, reason=reason)
        if JOURNAL.enabled:
            s = self._sched
            jnote("supervisor.escalate", profile=s.profile, replica=s.replica,
                  frm=DEGRADATION_LADDER[self.level - 1],
                  to=DEGRADATION_LADDER[self.level], level=self.level,
                  reason=reason, batch=s._batch_seq,
                  step=s._step_counter)
        log.warning("supervisor: degraded to %r (%s)",
                    DEGRADATION_LADDER[self.level], reason)

    def early_warning(self, reason: str) -> None:
        """SLO sentinel input (obs/slo.py): a burning objective is
        treated as a leading indicator of the faults the ladder exists
        to contain. Two counted reactions, both cheap and reversible:
        the probation counter resets (a degraded engine cannot climb
        back toward the fast path while its SLO burns — extending
        probation), and the per-batch watchdog is pre-armed for the
        next SLO_PREARM_BATCHES batches even when MINISCHED_WATCHDOG is
        unset. No rung changes here — the sentinel warns, the detectors
        decide."""
        self._clean = 0
        self.prearm = SLO_PREARM_BATCHES
        self._sched._sup_count("supervisor_early_warnings")
        instant("supervisor.early_warning", reason=reason,
                level=self.level)
        if JOURNAL.enabled:
            jnote("supervisor.early_warning",
                  profile=self._sched.profile, replica=self._sched.replica, reason=reason,
                  level=self.level, batch=self._sched._batch_seq)
        log.warning("supervisor: SLO early warning (%s); probation "
                    "extended, watchdog pre-armed for %d batches",
                    reason, SLO_PREARM_BATCHES)

    def note_clean(self) -> None:
        """One batch resolved with no fault. Probation bookkeeping.
        While any SLO is burning the engine cannot climb — fault-free
        batches during a burn don't count toward probation (the
        'probation extension' contract early_warning announces; the
        rising-edge alert alone would let a CONTINUOUS burn lapse after
        one reset), and the watchdog pre-arm stays topped up."""
        burning = self._sched._slo_burning_any()
        if burning:
            # Topped up BEFORE the level-0 early return: a continuous
            # burn on a healthy engine fires exactly one rising-edge
            # alert, and without this the pre-armed watchdog would
            # lapse after SLO_PREARM_BATCHES while the burn persists.
            self.prearm = SLO_PREARM_BATCHES
        if self.level == 0:
            return
        if burning:
            self._clean = 0
            return
        self._clean += 1
        if self._clean >= max(1, self._sched.config.probation_batches):
            self._clean = 0
            self.level -= 1
            self._sched._sup_count("supervisor_recoveries")
            instant("supervisor.recover",
                    to=DEGRADATION_LADDER[self.level], level=self.level)
            if JOURNAL.enabled:
                jnote("supervisor.recover",
                      profile=self._sched.profile, replica=self._sched.replica,
                      frm=DEGRADATION_LADDER[self.level + 1],
                      to=DEGRADATION_LADDER[self.level],
                      level=self.level, batch=self._sched._batch_seq)
            log.info("supervisor: probation passed; re-escalated to %r",
                     DEGRADATION_LADDER[self.level])


class _InflightBatch:
    """One batch moving through the prepare → resolve → commit phases of
    the engine cycle (Scheduler._run_pipelined). Slots keep field drift
    between the phases loud instead of silent."""

    __slots__ = ("batch", "pods", "vol_memo", "fail_closed", "eb", "names",
                 "row_incs", "nf", "af", "key", "sample_k", "decision",
                 "packed_dev", "spread_dev", "failures", "n_assigned",
                 "shapes", "seq", "t0", "t_encode", "t_dispatch",
                 "t_fetch_start", "t_step", "t_resolved", "commit_t0",
                 "commit_t1", "res_carried", "assumed", "detached",
                 "sl_repairs", "step_share", "free_after", "scored_rows",
                 "loop_slot", "tenant_ticket", "nom_reserved")

    def __init__(self):
        self.failures: List[tuple] = []  # (qpi, plugins, message, retryable)
        # Supervisor rollback ledger: pod key → qpi for every assume this
        # batch made that is still the batch's to reverse; keys move to
        # ``detached`` once handed to an async owner (binder bulk commit,
        # permit wait) — an aborted batch unassumes ``assumed`` and its
        # supervised retry excludes ``detached``.
        self.assumed: Dict[str, QueuedPodInfo] = {}
        self.detached: Set[str] = set()
        self.seq = 0
        self.n_assigned = 0
        self.shapes = (0, 0, 0)
        self.t0 = self.t_encode = self.t_dispatch = 0.0
        self.t_fetch_start = 0.0
        self.t_step = self.t_resolved = 0.0
        self.commit_t0 = self.commit_t1 = 0.0
        self.decision: Optional[Decision] = None
        self.spread_dev = None
        self.sample_k = None
        self.sl_repairs = 0  # shortlist repairs (folded at commit)
        # This batch's free/used_ports input is the device-resident
        # chain (_DeviceResidency) — its free_after must be carried and
        # its debits replayed into the host mirror at resolve time.
        self.res_carried = False
        # Nomination-window carry (device (N,R) or None): the
        # reservation correction the prepare phase subtracted from the
        # carried free INPUT; note_debits adds it back before adopting
        # free_after so the chain keeps un-nominated cache truth.
        self.nom_reserved = None
        # A fused tenant lane's carried free_after (the batch has no
        # Decision; the mux's dispatch fills it).
        self.free_after = None
        # Plugin-evaluation work this batch paid, in pod-row × node-row
        # units: a full step books P_pad·N_pad (or P_pad·K sampled).
        self.scored_rows = 0
        # Loop-mode slot: this batch's share of its tranche's fused
        # device window (tranche window / slots). Non-None overrides
        # the dispatch→fetch stamps in the watchdog and step_s
        # accounting — a depth-8 tranche must not book (or trip) an
        # 8-batch window against one batch's deadline.
        self.step_share: Optional[float] = None
        # Provenance tag (obs/journal.ProvenanceStore): which ring slot
        # served this batch (None = per-batch dispatch).
        self.loop_slot: Optional[int] = None
        # Fused multi-tenant lane ticket (encode/cache.TenantCacheMux):
        # non-None between the prepare-phase submit and the mux's fused
        # dispatch, which fills packed_dev/free_after and clears
        # it. A lane must never reach resolve with the ticket still
        # armed — the resolve phase guards it.
        self.tenant_ticket = None


# Fuse the per-pod step outputs into one (6+F, P) i32 array so the
# host fetches ONE buffer per batch. Every separate np.asarray is a
# device round trip; six fetches of small arrays cost ~5 extra latencies — measured ~0.27 s/batch at 10k pods,
# on par with the entire device compute. The jitted pack itself lives
# in ops/residency.py since the device loop stacks the same layout.
_pack_decision = pack_decision_i32


@jax.jit
def _pack_spread(pre, dom, mn, scan_groups):
    """Spread-arbitration inputs as one (2P+2, G) f32 fetch: pre-counts,
    chosen-domain ids, per-group pre-batch min, and the in-scan
    enforcement flags (rows the host arbitration may skip). Domain ids
    and counts are < 2^24, exact in f32."""
    import jax.numpy as jnp

    return jnp.concatenate(
        [pre, dom.astype(jnp.float32), mn[None, :],
         scan_groups.astype(jnp.float32)[None, :]], axis=0)


class _DeviceResidency:
    """Loop-carried device residency of the DYNAMIC node-feature leaves
    (``free`` / ``used_ports`` — NodeFeatureCache.DYNAMIC_NF_FIELDS),
    mirroring the static-leaf protocol of ``_with_device_static``: the
    jitted step's ``free_after`` stays on device as the next batch's
    input, and the host uploads only sparse host-truth corrections
    (ops/residency.apply_rows) for the rows where its authoritative
    cache diverged from the device's optimistic view — revoked
    placements, failed binds/unassume, informer churn, node lifecycle,
    claim/PV mutations all surface through the cache's
    DynDeltaListener. ``used_ports`` carries its own optimistic update
    (ROADMAP residency follow-up (d)): the engine models the batch's
    host-port insertions on the resident copy with the cache's exact
    first-zero-slot rule (ops/residency.insert_ports) and replays them
    into the host mirror in the same integer op order (note_ports), so
    a port-heavy workload's steady state stays zero-upload — the bind's
    cache-side port write then matches the mirror and the delta check
    elides the row, exactly like the free carry.

    Invariants (the correctness argument, asserted end-to-end by
    tests/test_device_residency.py):

      I1. the host mirrors equal the device arrays numerically at all
          times (±0.0 aside): the mirror replay is an ORDER-FREE
          per-node commutative debit aggregate — the batch's requests
          are summed per debited node column (``np.add.at`` into a
          zeroed aggregate) and applied as ONE subtract per node.
          Under the system's resource grammar every request/capacity
          component is an integer-valued f32 well inside the 2**24
          exact-integer window, so the aggregate equals ANY
          application order bitwise: the greedy scan's sequential
          pod-order ``free.at[row].add(-req)`` carry, and the
          auction's round-order one-winner-per-node einsum subtracts
          alike. This is what lifts the old greedy-only residency
          gate — the auction's parallel bidding rounds have no pod
          order, and with a commutative mirror they don't need one.
          Outside the exact-integer grammar the equality is verified
          rather than structural: the MINISCHED_RESIDENT_CHECK_EVERY
          cross-check compares mirror against device at cadence, and
          a mismatch walks the repair ladder (counted desync → full
          re-upload → supervised replay), never a silent divergence.
      I2. after ``attach`` the device arrays equal the cache's truth on
          every row, so the step consumes exactly what the
          MINISCHED_DEVICE_RESIDENT=0 upload-every-batch path would
          feed it — decisions are bit-identical by construction.
      I3. the correction candidate set is complete: a row diverges only
          through a host mutation (the cache marks it into the
          listener) or a device debit (``note_debits`` records it with
          its pre-replay truth); a row in neither set changed on
          neither side. The epoch counter carried on both sides turns
          any protocol break into a full re-upload (counted in
          ``residency_resyncs``), never a silent desync — and the
          scheme self-heals across failed cycles: an exception anywhere
          leaves mirror == device, and the next delta re-converges
          device to truth.
    """

    __slots__ = ("listener", "epoch", "pad", "free_dev", "ports_dev",
                 "mirror_free", "mirror_ports", "pending_rows",
                 "pending_pre", "pending_prows", "pending_ppre")

    def __init__(self, listener):
        self.listener = listener
        self.epoch = -1          # engine-side epoch; -1 = no device state
        self.pad = -1
        self.free_dev = None     # device (N,R) f32 — next step input
        self.ports_dev = None    # device (N,PORT) i32
        self.mirror_free = None  # host twins of the device arrays
        self.mirror_ports = None
        self.pending_rows = None  # rows the last step debited (unique)
        self.pending_pre = None   # their PRE-replay mirror rows == truth
        #                           at the last snapshot for rows the
        #                           host never otherwise touched
        self.pending_prows = None  # used_ports twin of pending_rows:
        self.pending_ppre = None   # rows the last batch's device-side
        #                            port insertion touched + their
        #                            pre-insert mirror values

    def attach(self, eng, nf, delta):
        """Bring the device-resident dynamic leaves up to host truth for
        this batch and splice them into ``nf``. ``delta`` None = full
        rebase (the snapshot returned real leaves and rebased the
        listener); else apply the sparse correction. Raises on epoch
        desync — the caller drops residency and re-snapshots."""
        if delta is None:
            free_np, ports_np = nf.free, nf.used_ports
            self.free_dev = jax.device_put(free_np,
                                           eng._nf_sharding("free"))
            self.ports_dev = jax.device_put(ports_np,
                                            eng._nf_sharding("used_ports"))
            # The mirrors are copies of the snapshot: on the CPU backend
            # device_put may alias an aligned host buffer, and the host
            # replay (note_debits / note_ports) writes the mirrors in
            # place — into the device array too, were they shared.
            self.mirror_free, self.mirror_ports = (free_np.copy(),
                                                   ports_np.copy())
            self.pad = int(free_np.shape[0])
            self.epoch = self.listener.epoch
            self.pending_rows = self.pending_pre = None
            self.pending_prows = self.pending_ppre = None
            eng._res_count(resync=True,
                           h2d=free_np.nbytes + ports_np.nbytes)
            return nf._replace(free=self.free_dev,
                               used_ports=self.ports_dev)
        if delta.epoch != self.epoch + 1 or self.free_dev is None:
            raise RuntimeError(
                f"residency epoch desync: device at {self.epoch}, delta "
                f"at {delta.epoch}")
        self.epoch = delta.epoch
        h2d = 0
        rows = delta.rows.astype(np.int64)
        vals = delta.free
        if self.pending_rows is not None:
            # Device-debited rows the host never touched: their truth is
            # the pre-replay mirror value (unchanged since the last
            # snapshot — had it changed, the cache would have marked the
            # row into the delta, which wins below by exclusion here).
            extra = ~np.isin(self.pending_rows, rows)
            if extra.any():
                rows = np.concatenate([rows, self.pending_rows[extra]])
                vals = np.concatenate([vals, self.pending_pre[extra]])
        self.pending_rows = self.pending_pre = None
        if rows.size:
            diff = np.any(vals != self.mirror_free[rows], axis=1)
            if diff.any():
                up_r = rows[diff].astype(np.int32)
                up_v = np.ascontiguousarray(vals[diff])
                # No donation: free_dev is (usually) Decision.free_after,
                # still referenced by the in-flight batch until commit.
                self.free_dev = apply_rows(self.free_dev, up_r, up_v)
                self.mirror_free[up_r] = up_v
                h2d += apply_rows_bytes(up_r.shape[0], up_v)
        prows = delta.rows.astype(np.int64)
        pvals = delta.used_ports
        if self.pending_prows is not None:
            # Rows the device-side port insertion touched that the host
            # never otherwise mutated: their truth is the pre-insert
            # mirror value — the same exclusion rule as the free carry
            # (a cache-mutated row lands in the delta and wins here).
            extra = ~np.isin(self.pending_prows, prows)
            if extra.any():
                prows = np.concatenate([prows, self.pending_prows[extra]])
                pvals = np.concatenate([pvals, self.pending_ppre[extra]])
        self.pending_prows = self.pending_ppre = None
        if prows.size:
            pdiff = np.any(pvals != self.mirror_ports[prows], axis=1)
            if pdiff.any():
                up_r = prows[pdiff].astype(np.int32)
                up_v = np.ascontiguousarray(pvals[pdiff])
                # ports_dev is engine-private (establish/apply output
                # only) — safe to donate so XLA reuses the buffer.
                self.ports_dev = apply_rows(self.ports_dev, up_r, up_v,
                                            donate=True)
                self.mirror_ports[up_r] = up_v
                h2d += apply_rows_bytes(up_r.shape[0], up_v)
        eng._res_count(resync=False, h2d=h2d)
        return nf._replace(free=self.free_dev, used_ports=self.ports_dev)

    def note_debits(self, chosen, assigned, requests, free_after_dev,
                    add_back=None):
        """Record the step's device-side debits: fold them into the
        host mirror as the per-node commutative aggregate (exact — see
        I1) and adopt ``free_after`` as the carried device array. Must
        run on the PRE-residual-merge chosen/assigned (the carried
        array is the MAIN step's output; residual/repair placements
        reach the device as next-batch corrections via the cache
        listener). ``add_back`` (device (N,R), optional) reverses a
        pre-step nomination-reservation correction (the carry subtracted
        it from the step's ``free`` input only): it is added back on
        device so the adopted array returns to un-nominated cache truth
        — the plane the mirror tracks."""
        rows = chosen[assigned].astype(np.int64)
        if rows.size:
            reqs = requests[assigned]
            uniq = np.unique(rows)
            self.pending_pre = self.mirror_free[uniq].copy()
            self.pending_rows = uniq
            # Order-free commutative aggregate: sum each node's debits,
            # then ONE subtract per debited node. Bitwise equal to the
            # device's own application order under the exact-integer
            # grammar (I1); the cadence cross-check covers the rest.
            agg = np.zeros((uniq.shape[0], reqs.shape[1]),
                           dtype=self.mirror_free.dtype)
            np.add.at(agg, np.searchsorted(uniq, rows), reqs)
            self.mirror_free[uniq] -= agg
            if FAULTS.hit("auction_mirror") == "corrupt":
                # Mis-TARGETED aggregate: a phantom debit lands on a
                # node row the batch never debited (the scatter
                # off-by-one failure mode of the order-free replay).
                # Deliberately NOT a mis-valued debit on a debited row —
                # the host touches those rows at bind, so the next
                # attach overwrites the mirror from delta truth and the
                # scribble self-heals (the delta protocol working, not a
                # detector gap). The mis-target hits a row no delta will
                # ever correct; it is invisible to every per-decision
                # certificate and ONLY the MINISCHED_RESIDENT_CHECK_EVERY
                # carry cross-check can see it.
                self.mirror_free[-1, 0] -= 1.0
            if not np.isfinite(self.mirror_free[uniq]).all():
                # Supervisor NaN detector: a non-finite request/feature
                # reached the carried chain — abort before the poisoned
                # mirror is trusted (the batch retries with residency
                # dropped, which also resets these mirrors).
                raise EngineDesync(
                    "non-finite free capacity after device-debit replay")
        else:
            self.pending_rows = self.pending_pre = None
        if add_back is not None:
            # Exact under the integer grammar: (carried - reserved)
            # - batch_debits + reserved == carried - batch_debits.
            free_after_dev = free_after_dev + add_back
        self.free_dev = free_after_dev

    def note_ports(self, rows: np.ndarray, ports: np.ndarray) -> int:
        """Model the batch's host-port insertions on the resident
        used_ports (ROADMAP residency follow-up (d)): run the device
        insertion op and the bit-exact host replay
        (ops/residency.insert_ports / replay_ports_host — integer
        first-zero-slot writes in pod order, the cache's _add_ports
        rule), tracking touched rows like the free carry's pending set.
        ``rows`` is (P,) chosen with -1 for pods that insert nothing.
        Returns the host→device bytes the insertion uploaded."""
        from ..ops.residency import (insert_ports, insert_ports_bytes,
                                     replay_ports_host)

        uniq = np.unique(rows[rows >= 0])
        self.pending_prows = uniq
        self.pending_ppre = self.mirror_ports[uniq].copy()
        replay_ports_host(self.mirror_ports, rows, ports)
        self.ports_dev = insert_ports(self.ports_dev, rows, ports)
        return insert_ports_bytes(rows.shape[0], ports.shape[1])

    def drop(self, reason: str) -> None:
        """Abandon the device state; the next residency batch does a
        full re-upload (the listener rebases itself at collection)."""
        if self.epoch >= 0:
            log.info("device residency dropped (%s); next batch "
                     "re-uploads the dynamic leaves", reason)
            jnote("residency.drop", reason=reason)
        self.epoch = -1
        self.free_dev = self.ports_dev = None
        self.mirror_free = self.mirror_ports = None
        self.pending_rows = self.pending_pre = None
        self.pending_prows = self.pending_ppre = None
        self.listener.invalidate()


def arbitrate_rwo(batch: List[QueuedPodInfo], assigned, chosen,
                  vol_memo: Dict[str, tuple]):
    """In-batch RWO arbitration → (revoked pod indices, parked gang keys).

    The VolumeRestrictions filter pins pods to a claim's existing mount
    node, but an UNUSED claim shared by several pods in one batch could be
    jointly assigned to different nodes. Walk assignments in priority
    order; the first surviving pod pins each unused claim, later pods
    choosing a different node are revoked and retried (next cycle sees the
    pinned claim — sequential RWO semantics without splitting gangs out of
    the batch).

    "Unused" is judged from the ENCODE-time claim rows the filter itself
    evaluated (``vol_memo``: pod key → ``_volume_state`` tuple), not a
    second live cache read: an informer event mounting a claim between
    encode and commit would make a live read skip arbitration and let two
    batch pods bind the same RWO claim to different nodes.

    A pin is only binding while its owner survives arbitration: a pinner
    revoked later (gang atomicity over another claim) must not keep
    revoking claim-mates against a placement that never commits. Two
    stages:

    1. an optimistic fixed-point loop where only surviving pods pin
       (revoked pods are re-checked against live pins each pass, so a pod
       stays revoked only while a live pin justifies it) — this rescues
       spuriously-revoked pods;
    2. a monotone safety closure (pins from survivors, conflicts only ADD
       revocations, repeated until stable) — at a converged stage-1
       fixpoint it is a no-op, and in the pathological non-converged case
       it restores the invariant that no two committed pods bind one
       claim to two nodes.
    """
    from ..state.objects import CLAIM_UNUSED

    parked_gangs: Set[str] = set()  # intra-gang conflicts: unsatisfiable

    def unused_claims(pod: Pod):
        st = vol_memo.get(pod.key)
        if st is None:
            # No encode-time record (a pod without volumes has no claims
            # either) — nothing to arbitrate.
            return []
        return [ck for ck, r in zip(claim_keys(pod), st[1])
                if r == CLAIM_UNUSED]

    def scan(dead: Set[int], monotone: bool) -> Set[int]:
        """One arbitration pass. Pods in ``dead`` never pin; they are
        still checked against live pins unless ``monotone`` (where dead is
        sticky and needs no re-justification). Returns the revocation set
        implied by live pins."""
        claim_pin: Dict[str, tuple] = {}  # ck → (row, pinner's gang)
        conflicted: Set[int] = set()
        for i, qpi in enumerate(batch):
            if not assigned[i] or (monotone and i in dead):
                continue
            row = int(chosen[i])
            gk = gang_key(qpi.pod)
            alive = i not in dead and not (gk and gk in parked_gangs)
            for ck in unused_claims(qpi.pod):
                pin = claim_pin.get(ck)
                if pin is None:
                    if alive:
                        claim_pin[ck] = (row, gk)
                elif pin[0] != row:
                    conflicted.add(i)
                    if gk and gk == pin[1]:
                        # The conflict is INSIDE one gang: its members
                        # demand the claim on different nodes; retrying
                        # reproduces it forever — park the gang
                        # (terminal, sticky).
                        parked_gangs.add(gk)
                    break
        # Gang atomicity: revoking one member revokes its whole gang —
        # peers binding at sub-quorum is the partial-allocation deadlock
        # gang scheduling exists to prevent.
        gangs = {gang_key(batch[i].pod) for i in conflicted
                 if batch[i].pod.spec.pod_group} | parked_gangs
        out = set(conflicted)
        if gangs:
            for i, qpi in enumerate(batch):
                if assigned[i] and gang_key(qpi.pod) in gangs:
                    out.add(i)
        return out

    revoked: Set[int] = set()
    for _ in range(8):  # stage 1: rescue loop
        new_revoked = scan(revoked, monotone=False)
        if new_revoked == revoked:
            break
        revoked = new_revoked
    while True:  # stage 2: safety closure (monotone, terminates)
        grown = revoked | scan(revoked, monotone=True)
        if grown == revoked:
            break
        revoked = grown
    return revoked, parked_gangs


def batch_group_match(batch: List[QueuedPodInfo], gf) -> np.ndarray:
    """(P_live, G) bool: batch pod i's namespace+labels match selector
    group g — the HOST twin of ops.topology.group_assigned_match (same
    hash functions, same all-zero-selector = match-all and namespace-set
    semantics), evaluated over the batch pods themselves
    (their labels are host objects; the device only encodes groups).
    Label-pair rows are memoized per distinct signature — a deployment's
    replicas share one."""
    from ..encode import features as F

    P, G = len(batch), gf.valid.shape[0]
    sel = np.asarray(gf.sel_pairs, dtype=np.int64)   # (G,QT)
    gvalid = np.asarray(gf.valid)
    gns = np.asarray(gf.ns_set, dtype=np.int64)      # (G,NS), 0 unused
    ns_any = (gns == 0).all(axis=1)
    ns_memo: Dict[str, int] = {}
    # per distinct label signature: the (G,) selector-match row
    sig_memo: Dict[tuple, np.ndarray] = {}
    match = np.zeros((P, G), dtype=bool)
    for i, qpi in enumerate(batch):
        pod = qpi.pod
        sig = tuple(pod.metadata.labels.items())
        sel_ok = sig_memo.get(sig)
        if sel_ok is None:
            s = {F.pair_hash(k, v) for k, v in sig}
            sel_ok = np.array([
                all((int(p) in s) for p in sel[g] if p != 0)
                for g in range(G)])
            sig_memo[sig] = sel_ok
        nsv = ns_memo.get(pod.metadata.namespace)
        if nsv is None:
            nsv = ns_memo[pod.metadata.namespace] = (
                F._h(pod.metadata.namespace) if pod.metadata.namespace else 0)
        match[i] = gvalid & (ns_any | ((gns == nsv) & (gns != 0)).any(
            axis=1)) & sel_ok
    return match


class _SpreadGroupState:
    """Running per-domain count table for ONE selector group — the exact
    sequential-semantics core of arbitrate_spread. Maintains the count
    of every topology domain plus the global min via a count-histogram,
    so each admission is O(1) and the min is always exact (never the
    conservative pre-batch min, which on a skew-constrained burst
    admitted only ~(domains x max_skew) pods per cycle — round-3 verdict
    weak #1: 9,968/10,000 revocations at max_skew=1)."""

    __slots__ = ("counts", "hist", "min")

    def __init__(self, counts_row: np.ndarray, exist_row: np.ndarray):
        self.counts = counts_row.astype(np.int64)  # (D,) private copy
        vals, freq = np.unique(self.counts[exist_row], return_counts=True)
        self.hist = dict(zip(vals.tolist(), freq.tolist()))
        self.min = int(vals[0]) if vals.size else 0

    def admit(self, d: int) -> None:
        c = int(self.counts[d])
        self.counts[d] = c + 1
        n = self.hist.get(c, 0) - 1
        if n:
            self.hist[c] = n
        else:
            self.hist.pop(c, None)
        self.hist[c + 1] = self.hist.get(c + 1, 0) + 1
        if c == self.min and n <= 0:
            # every domain that sat at the min has moved up; the next
            # occupied histogram bucket is the new exact min
            while self.hist.get(self.min, 0) == 0:
                self.min += 1


def arbitrate_spread(batch: List[QueuedPodInfo], assigned, pf, gf,
                     spread_pre, spread_dom, spread_min,
                     dead: Set[int], anti_enabled: bool = True,
                     exact_tables=None,
                     scan_enforced=None,
                     anti_revoked: Optional[Set[int]] = None) -> Set[int]:
    """Intra-batch topology arbitration → additional revoked indices.

    Every batch pod was filtered/scored against PRE-batch topology counts,
    so a burst can jointly commit constraints none violates alone (the
    sequential reference sees each prior placement):

      * hard (DoNotSchedule) spread: a burst can stack one domain past
        max_skew;
      * required anti-affinity: two mutually-exclusive batch pods can
        both land in one domain — direct (the later pod's own anti term
        matches an earlier placement) and symmetric (an earlier pod's
        anti term matches the later pod).

    Walk assignments in priority order carrying in-batch per-(group,
    domain) state — membership updates fed by EVERY matching assigned
    pod, constraint or not, and anti-term deltas by each survivor's own
    anti terms. Skew is judged with EXACT sequential semantics when
    ``exact_tables`` supplies the step's full per-domain count tables
    (``() -> (cdom (G,D) f32, dexist (G,D) bool)``, fetched lazily —
    only batches with hard constraints pay the transfer): a running
    count table + histogram-tracked min per group reproduces what a
    sequential scheduler placing the same pods in the same order would
    admit, so a skew-constrained burst drains in one cycle instead of
    max_skew-per-domain per cycle. Without the tables it falls back to
    judging against the conservative pre-batch min (in-batch additions
    only raise the true min, so the fallback never under-revokes — it
    over-revokes and converges over more cycles). Violators are revoked
    and retried next cycle, where the committed counts are visible —
    required AFFINITY needs no arbitration: in-batch blindness can only
    under-admit, and the parked pod is revived by the peer's bind event.
    Gang atomicity: one revoked member revokes its whole gang.

    Inputs: pf/gf (host-side encoded batch), spread_pre/dom (P,G) and
    spread_min (G,) from the step (state at each pod's chosen node),
    ``dead`` = indices already revoked upstream (they never commit, so
    they contribute no deltas).

    ``scan_enforced`` ((G,) bool, Decision.scan_groups): groups whose
    hard skew the in-scan domain caps (ops/spreadcap.py) already judged
    against running counts AT CHOICE TIME, in this same batch order —
    the host replay is skipped for them, and a batch whose hard groups
    are all scan-enforced never calls ``exact_tables`` at all (the
    (G,D) transfer exists solely to rebuild the running state the scan
    already had).

    ``anti_revoked`` (optional out-param) receives the revoked pods whose
    own verdict was a required anti-affinity conflict with an earlier
    pod of the batch (not a gang cascade or a skew violation)."""
    from ..encode import features as F

    if spread_pre.shape[0] == 0:
        return set()
    P = len(batch)
    hard = ((pf.spread_group >= 0)
            & (pf.spread_mode == F.SPREAD_DO_NOT_SCHEDULE))[:P]
    anti = pf.anti_req_group[:P]                     # (P,T), -1 unused
    # Anti terms are always encoded, but only the InterPodAffinity filter
    # ENFORCES them — arbitrating them in a profile that ignores them
    # would revoke pods the next cycle happily co-locates anyway.
    has_anti = anti_enabled and bool((anti >= 0).any())
    if not hard.any() and not has_anti:
        return set()
    match = batch_group_match(batch, gf)

    hard_gids = {int(g) for g in np.unique(pf.spread_group[:P][hard])
                 if g >= 0}
    # (G,D) tables are fetched at most once across every walk iteration.
    tables = {"fetched": False, "cdom": None, "dexist": None}

    def fetch_tables():
        if not tables["fetched"]:
            tables["fetched"] = True
            if exact_tables is not None:
                fetched = exact_tables()
                if fetched is not None and fetched[0].shape[0]:
                    tables["cdom"], tables["dexist"] = fetched
        return tables["cdom"], tables["dexist"]

    def _walk(dead_all: Set[int]) -> Set[int]:
        """One exact sequential replay with ``dead_all`` contributing
        nothing. Mutable enforcement view: a group's scan verdict is
        trusted only while every admission the scan COUNTED for it
        survives. A host-side revocation (RWO/gang ``dead_all``, or an
        anti revocation made in this very walk) removes a contribution
        the scan's running counts relied on — lowering a domain min that
        later admissions were judged against — so those groups fall back
        to the exact replay, reconstructed mid-walk from the survivor
        deltas."""
        enf = (np.array(scan_enforced, dtype=bool, copy=True)
               if scan_enforced is not None
               else np.zeros(gf.valid.shape[0], dtype=bool))
        delta: Dict[tuple, int] = {}      # (g,d) → matching pods placed
        anti_delta: Dict[tuple, int] = {}  # (g,d) → anti terms placed in d
        gstates: Dict[int, _SpreadGroupState] = {}

        def build_state(g: int) -> None:
            """Exact running state for group g AT THE CURRENT WALK
            POSITION: pre-batch tables plus every surviving admission so
            far (delta already tracks them for all matching groups,
            enforced or not)."""
            cdom, dexist = fetch_tables()
            if cdom is None:
                return  # fallback mode: pre-batch-min check (over-revokes)
            st = _SpreadGroupState(cdom[g], dexist[g])
            for (g2, d), cnt in delta.items():
                if g2 == g:
                    for _ in range(cnt):
                        st.admit(d)
            gstates[g] = st

        def un_enforce(rows) -> None:
            """Stop trusting the scan for every hard group the given
            revoked pods match; rebuild their exact state from deltas."""
            for i in rows:
                for g in np.nonzero(match[i])[0]:
                    gi = int(g)
                    if enf[gi] and gi in hard_gids:
                        enf[gi] = False
                        build_state(gi)

        # Pre-walk: revocations known before this walk were counted by
        # the scan from their row onward — replay their groups from row
        # 0 (the sequential scheduler would have rejected them at their
        # turn).
        dead_assigned = [i for i in dead_all if i < P and assigned[i]]
        if dead_assigned:
            un_enforce(dead_assigned)
        for g in sorted(hard_gids):
            if not enf[g] and g not in gstates:
                build_state(g)

        revoked: Set[int] = set()
        anti_viol.clear()
        for i in range(P):
            if not assigned[i] or i in dead_all:
                continue
            viol = False
            for c in np.nonzero(hard[i])[0]:
                g = int(pf.spread_group[i, c])
                if enf[g]:
                    # the scan judged this admission against running
                    # counts at choice time, and every admission it
                    # counted so far survives — replaying is redundant
                    continue
                d = int(spread_dom[i, g])
                st = gstates.get(g)
                if st is not None:
                    if d >= 0 and (int(st.counts[d]) + 1 - st.min
                                   > int(pf.spread_max_skew[i, c])):
                        viol = True
                        break
                else:
                    after = (float(spread_pre[i, g])
                             + delta.get((g, d), 0) + 1)
                    if after - float(spread_min[g]) > float(
                            pf.spread_max_skew[i, c]):
                        viol = True
                        break
            if not viol and has_anti:
                for t in np.nonzero(anti[i] >= 0)[0]:
                    g = int(anti[i, t])
                    d = int(spread_dom[i, g])
                    # direct: an earlier matching placement in my domain
                    if d >= 0 and delta.get((g, d), 0) > 0:
                        viol = True
                        break
                if not viol:
                    # symmetric: an earlier pod's anti term targets ME
                    for g in np.nonzero(match[i])[0]:
                        d = int(spread_dom[i, int(g)])
                        if d >= 0 and anti_delta.get((int(g), d), 0) > 0:
                            viol = True
                            break
                if viol:
                    anti_viol.add(i)
            if viol:
                revoked.add(i)
                # this pod's admission WAS in the scan's running counts —
                # groups it matches can no longer trust the scan verdict
                # for the remaining rows
                un_enforce((i,))
                continue
            for g in np.nonzero(match[i])[0]:
                gi = int(g)
                d = int(spread_dom[i, gi])
                if d >= 0:  # node lacks the key → no domain membership
                    # delta tracks IN-BATCH placements for the anti path
                    # in both modes; the exact group states additionally
                    # carry the running counts + min for the skew check.
                    delta[(gi, d)] = delta.get((gi, d), 0) + 1
                    st = gstates.get(gi)
                    if st is not None:
                        st.admit(d)
            if has_anti:
                for t in np.nonzero(anti[i] >= 0)[0]:
                    g = int(anti[i, t])
                    d = int(spread_dom[i, g])
                    if d >= 0:
                        anti_delta[(g, d)] = anti_delta.get((g, d), 0) + 1
        return revoked

    # Fixpoint over gang atomicity: a revoked member revokes its whole
    # gang, and each revoked gang member's admission was counted by BOTH
    # the scan and this walk's running state — later pods may hold
    # placements only legal because of it. Re-walk with the gang's
    # members dead until no new revocation appears (bounded by the
    # number of gangs; a batch with no gang revocations exits after one
    # pass, identical to the single-walk behavior).
    extra: Set[int] = set()
    anti_viol: Set[int] = set()  # the last walk's anti verdicts
    while True:
        revoked = _walk(dead | extra) | extra
        gangs = {gang_key(batch[i].pod) for i in revoked
                 if batch[i].pod.spec.pod_group}
        cascade = {i for i, qpi in enumerate(batch)
                   if (assigned[i] and i not in dead and i not in revoked
                       and gang_key(qpi.pod) in gangs)}
        if not cascade:
            if anti_revoked is not None:
                anti_revoked.update(anti_viol)
            return revoked
        extra = revoked | cascade


class Scheduler:
    def __init__(self, store, plugin_set: PluginSet,
                 config: Optional[SchedulerConfig] = None,
                 recorder=None, scheduler_names: Optional[Set[str]] = None,
                 shared=None, profile: Optional[str] = None,
                 replica: Optional[str] = None):
        from .clusterstate import SharedClusterState

        self.store = store
        self.plugin_set = plugin_set
        self.config = config or SchedulerConfig()
        self.recorder = recorder  # explainability hook (explain/resultstore)
        # Multi-profile routing: when set, only pods whose
        # spec.scheduler_name is in this set are queued here (reference
        # KubeSchedulerProfile.SchedulerName selection); None = accept all
        # (single-profile mode).
        self.scheduler_names = scheduler_names
        # Serving-profile label for per-profile attribution: journal
        # events, timeline rows, and provenance records all carry it so
        # a multi-profile service's shared surfaces stay attributable
        # (the multi-tenant per-tenant dimension, pre-staged). The
        # service passes the profile's name explicitly; a directly
        # constructed engine derives it from its routing set.
        self.profile = profile or (sorted(scheduler_names)[0]
                                   if scheduler_names else "default")
        # Fleet replica id (fleet/supervisor.py): rides next to the
        # profile on every journal event and provenance record so a
        # replicated run's shared surfaces stay attributable per
        # replica. "" = not a fleet member (solo engine / service).
        self.replica = replica or ""
        # Fleet shard ownership: (n_shards, owned frozenset, epoch) read
        # as ONE tuple on the wants_pod hot path (a single attribute
        # load — replacement-only, so informer threads never observe a
        # half-updated pair). n_shards == 0 disables sharding entirely
        # (the solo default: own every pod).
        self._shard_view = (0, frozenset(), 0)
        # Fleet bind fencing: callable(pod_key) -> bool installed by the
        # fleet supervisor; a False verdict at commit time means this
        # engine no longer owns the pod's shard — the bind is withheld
        # and the pod handed back (the new owner's takeover sweep
        # re-gathers it from the store). None = no fencing (solo).
        self._bind_guard = None
        # Cluster state (feature cache + informers) is SHARED across the
        # service's profile engines (reference: one scheduler struct,
        # many profiles, scheduler.go:97-142) — a solo engine owns a
        # private instance, so direct construction keeps working.
        self._shared = shared or SharedClusterState(store)
        self._owns_shared = shared is None
        watch_gc()  # process-wide collection pauses (gc_pause_s_total)
        self.cache = self._shared.cache
        self._shared.register(self)
        self.broadcaster = EventBroadcaster(store)

        event_map = plugin_set.cluster_event_map()
        # In-batch capacity losses and bind conflicts are revivable by any
        # node add/update or assigned-pod delete (capacity freed).
        cap_interest = {
            ClusterEvent(GVK.NODE, ActionType.ADD | ActionType.UPDATE),
            ClusterEvent(GVK.POD, ActionType.DELETE),
        }
        for ev in cap_interest:
            event_map.setdefault(ev, set()).add(BATCH_CAPACITY)
        # Gang-rejected pods revive when a new member arrives (pod add),
        # capacity frees (pod delete), or nodes appear/change.
        cos_interest = {
            ClusterEvent(GVK.POD, ActionType.ADD | ActionType.DELETE),
            ClusterEvent(GVK.NODE, ActionType.ADD | ActionType.UPDATE),
        }
        for ev in cos_interest:
            event_map.setdefault(ev, set()).add(COSCHEDULING)

        self.queue = SchedulingQueue(
            event_map,
            backoff_initial=self.config.backoff_initial_s,
            backoff_max=self.config.backoff_max_s)

        # Multi-chip product path (SchedulerConfig.mesh): the step runs
        # over the ("pod", "node") device mesh via parallel/sharded.py.
        # Built lazily on the first batch — the sharding specs need input
        # pytree templates (rank information) the engine only has then —
        # but the CONFIG is validated here so a bad mesh/assignment fails
        # at start_scheduler, not as an endless retry loop on the
        # scheduling thread.
        self._mesh = self.config.mesh
        if self._mesh is not None:
            from jax.sharding import Mesh

            from ..parallel.mesh import NODE_AXIS, POD_AXIS

            if (not isinstance(self._mesh, Mesh)
                    or set(self._mesh.axis_names) != {POD_AXIS, NODE_AXIS}):
                raise ValueError(
                    "SchedulerConfig.mesh must be a jax.sharding.Mesh "
                    "with ('pod', 'node') axes (parallel.mesh.make_mesh); "
                    f"got {self._mesh!r}")
            if self.config.assignment not in ("greedy", "auction"):
                raise ValueError(
                    f"unknown assignment strategy "
                    f"{self.config.assignment!r}; expected 'greedy' or "
                    "'auction'")
        self._sharded_step = None
        # Shortlist-compressed arbitration: single-device-only — the
        # mesh's static shardings keep full (P,N) rows (documented
        # gate; decisions are knob-independent there by construction).
        # The greedy scan takes ops/select.greedy_assign_shortlist; the
        # auction takes the bid shortlist (ops/bid_select) with the
        # same certify-or-repair contract. None = off. Mutated only on
        # the scheduling thread: the certification cross-check
        # (_check_shortlist) permanently reverts a desynced engine to
        # the full-width scan.
        self._shortlist_k = (self.config.shortlist_k
                             if (self.config.shortlist
                                 and self._mesh is None)
                             else None)
        self._sl_check_tick = 0
        self._step = (None if self._mesh is not None else
                      build_step(plugin_set, explain=self.config.explain,
                                 assignment=self.config.assignment,
                                 shortlist=self._shortlist_k))
        self._key = jax.random.PRNGKey(self.config.seed)
        self._step_counter = 0
        self._prep_step0 = 0  # supervisor replay anchor (see _prepare_batch)
        self._batch_seq = 0  # prepare-order sequence (scheduling thread)
        self.waiting_pods: Dict[str, WaitingPod] = {}
        self._waiting_lock = threading.Lock()
        self._binder = ThreadPoolExecutor(
            max_workers=self.config.bind_workers, thread_name_prefix="binder")
        # Commit worker for the pipelined cycle (_run_pipelined): batch
        # k-1's failure flush runs here while the scheduling thread
        # encodes batch k+1 and the device executes batch k. ONE worker —
        # commits must apply in batch order — and the pipeline is bounded
        # at one commit in flight (_await_commit).
        self._committer = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="commit")
        # Gather worker for the pipelined cycle: batch k+1's queue pop —
        # including its full batch-formation window — runs here while
        # the scheduling thread resolves/commits batch k. Popping on the
        # scheduling thread would stall k's binds and failure verdicts
        # for up to batch_window_s whenever arrivals trickle.
        self._gatherer = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="gather")
        # Deferred-failure sink: while the scheduling thread resolves a
        # batch, _handle_failure APPENDS verdicts here instead of paying
        # a store round-trip per pod; _commit_batch flushes them through
        # the bulk machinery (store.fail_pods / queue.requeue_failures /
        # failed_scheduling_many). Thread-gated: binder/permit threads
        # always take the immediate path.
        self._fail_sink: Optional[List[tuple]] = None
        self._fail_sink_tid = 0
        # In-batch RWO arbitration only applies when the plugin enforcing
        # claim exclusivity is part of the profile.
        self._rwo_enabled = any(p.name == "VolumeRestrictions"
                                for p in plugin_set.plugins)
        # Intra-batch topology arbitration (hard spread + required
        # anti-affinity, arbitrate_spread) applies when either topology
        # plugin is in the profile.
        self._spread_enabled = any(
            p.name in ("PodTopologySpread", "InterPodAffinity")
            for p in plugin_set.plugins)
        # Symmetric existing-pod anti-affinity is enforced by the
        # InterPodAffinity filter over the anti-class count plane
        # (encode.anti_cls_group, cache.anti_classes_for).
        self._anti_enabled = any(p.name == "InterPodAffinity"
                                 for p in plugin_set.plugins)
        self._anti_class_slots = 0  # metrics gauge, set per encode
        # SelectorSpread consumes owner-derived selector groups; encoding
        # them is gated on the profile so batches never grow the group
        # axis (and the (G,N) topology tables) for a plugin nobody runs.
        # The shared assigned corpus must then carry owner pairs too —
        # enabled here, BEFORE the informers sync (engines construct
        # before any start()).
        self._selspread_enabled = any(p.name == "SelectorSpread"
                                      for p in plugin_set.plugins)
        if self._selspread_enabled:
            self.cache.enable_owner_pairs()
        # PostFilter preemption (upstream DefaultPreemption): enabled by
        # the marker plugin; terminally-unschedulable pods get a batched
        # victim-candidate search before parking.
        self._preempt_enabled = bool(plugin_set.postfilter_plugins)
        # Outstanding nominations: pod key → (node name, request vector,
        # expiry). Freed capacity stays reserved for its preemptor until
        # it binds, vanishes, or the TTL lapses (a crashed retry must not
        # pin capacity forever). Guarded by its own lock — the binder
        # thread clears entries while the scheduling thread debits them.
        self._nominations: Dict[str, tuple] = {}
        # preemption wins per pending pod without a successful bind
        # (cleared on bind/delete; see _PREEMPT_MAX_ROUNDS)
        self._preempt_rounds: Dict[str, int] = {}
        self._nom_lock = threading.Lock()
        # Which encode-side fail-closed verdicts apply: only constraints
        # this profile's plugin set actually enforces may park a pod.
        self._fail_closed_plugins = {
            "InterPodAffinity": self._anti_enabled,
            "PodTopologySpread": any(p.name == "PodTopologySpread"
                                     for p in plugin_set.plugins)}
        # WFFC candidate-zone memo: pvc key → (zones, computed_at).
        self._wffc_memo: Dict[str, tuple] = {}
        self._stop = threading.Event()
        # Crash-stop flag (abandon()): checked BETWEEN device-loop slots
        # so a "killed" replica leaves its staged-but-unresolved ring
        # tranche as debris for the adopter, instead of committing it on
        # the way down like the graceful shutdown() path does.
        self._abandoned = False
        self._thread: Optional[threading.Thread] = None
        self.filter_names = [p.name for p in plugin_set.filter_plugins]
        # Device-resident static node features, keyed on
        # (cache.static_version, pad) — see _with_device_static. Touched
        # only by the scheduling thread.
        self._nf_static_device = None
        # Slim decision readback (bit-packed bools + saturating i16
        # counts in ONE u8 fetch buffer, ops/residency.py) rides the
        # same knob as residency so MINISCHED_DEVICE_RESIDENT=0 restores
        # the PR-1 transfer behavior exactly for regression triage. The
        # first slim fetch is cross-checked against direct leaf fetches
        # (byte-order/packbits insurance on new backends) and falls back
        # to the i32 layout on mismatch.
        self._slim = bool(self.config.device_resident)
        self._slim_verified = False
        # Device-resident DYNAMIC leaves (free/used_ports loop-carried
        # as the next batch's input; see _DeviceResidency). Open to the
        # greedy scan AND the auction: the mirror replay is an
        # order-free per-node debit aggregate (I1), so no assignment
        # order is assumed. Touched only by the scheduling thread.
        self._residency = None
        if self.config.device_resident:
            self._residency = _DeviceResidency(
                self.cache.register_dyn_listener())
        # Persistent on-device engine loop (MINISCHED_DEVICE_LOOP): the
        # multi-batch fused-dispatch tranche machinery
        # (_maybe_run_tranche). Gated to the single-device non-explain
        # engine; the greedy scan and the auction are both ring-eligible
        # — the between-slot validator replays debits with the same
        # order-free aggregate as residency's I1 (auction slot k+1's
        # prices start fresh, but its ``free`` input IS slot k's
        # ``free_after``). The loop-private dyn listener feeds
        # the between-slot divergence validator (cache.drain_dyn_rows);
        # it is never handed to snapshot_resident, so the residency
        # epoch protocol is untouched. _loop_cooldown is the ladder's
        # loop→pipelined rung: a tranche-machinery fault disables loop
        # engagement for probation_batches considerations (slot-level
        # batch faults ride the existing degradation ladder unchanged).
        self._loop_enabled = (self.config.device_loop
                              and self._mesh is None
                              and not self.config.explain)
        self._loop_listener = (self.cache.register_dyn_listener()
                               if self._loop_enabled else None)
        self._loop_cooldown = 0
        # Fused multi-tenant arbitration (MINISCHED_TENANTS_FUSE;
        # encode/cache.TenantCacheMux): installed by the service's
        # fusion coordinator on each tenant engine it serves. When
        # armed, a fusable batch's prepare SUBMITS its fully-staged
        # step inputs to the mux instead of dispatching, and the
        # coordinator's one vmapped dispatch per tranche fills the
        # lane's decision planes before resolve. None = solo engine
        # (every existing path, bit-identical).
        self._tenant_mux = None
        # Arm jax's persistent compilation cache BEFORE the first step
        # compile so restarts reuse executables (one rule, see
        # ops/pipeline.arm_compile_cache); raises when it cannot be armed.
        self._compile_cache_dir = arm_compile_cache()
        # Engine supervisor: watchdog + fault/NaN/desync detection +
        # the counted degradation ladder (see _Supervisor). Level state
        # is scheduling-thread-only; counters ride _metrics.
        self._sup = _Supervisor(self)
        # Resolve-phase assume ledger (rollback on abort): the inflight
        # batch currently in resolve on the scheduling thread, thread-
        # gated exactly like _fail_sink.
        self._track: Optional[_InflightBatch] = None
        # Batch-scoped provenance path (journal armed only): set by
        # _resolve_batch beside _fail_sink, consumed by the placement
        # stamp sites on the same thread. None = journal unarmed.
        self._prov_batch: Optional[dict] = None
        # Pods CURRENTLY owned by an async owner (binder bulk commit,
        # permit wait): added at hand-off, removed when the owner
        # concludes (bound / requeued / forgotten). A supervised retry
        # strips these before EVERY attempt — an _InflightBatch.detached
        # set only covers the attempt that built it, but a pod can be
        # handed off by any attempt (including the synchronous cycle,
        # which exposes no inflight to the outer handler) and
        # re-scheduling it would double-assume and race the owner's
        # bind. Lock-guarded: owners conclude on binder threads.
        self._detached_live: Set[str] = set()
        self._detached_lock = threading.Lock()
        # Residency carry cross-check cadence counter
        # (config.resident_check_every; scheduling thread only).
        self._res_check_tick = 0
        # Armed trace request (see trace_next_batch). The lock covers the
        # arm/consume pair: an unlocked read-then-clear swap on the
        # scheduling thread could clobber a concurrent arm with None.
        self._trace_lock = threading.Lock()
        self._trace_dir: Optional[str] = None
        # Timing/counter metrics (beyond the reference's klog-only
        # observability, SURVEY §5): cumulative sums + last-batch values,
        # guarded by a dedicated lock (read from any thread).
        self._metrics_lock = threading.Lock()
        # Pipelined-mode metric bookkeeping (guarded by _metrics_lock):
        # commits can complete out of batch order (a no-failure batch
        # folds inline while the previous batch's worker flush is still
        # running), so last_* fields only accept the highest batch
        # sequence seen; the prepare window lets the commit side compute
        # the encode-vs-flush overlap regardless of which commit path
        # the NEXT batch takes.
        self._last_committed_seq = -1
        self._prep_window: tuple = (0.0, 0.0)
        # Per-pod lifecycle latency histograms (obs.Histogram), fed from
        # the QueuedPodInfo stamps (queued=added_at, gathered_at,
        # decided_at) and observed exactly where pods_bound increments,
        # so create_to_bound's count always equals the bound decisions.
        # Always on: the cost is a bisect per bound pod, off the device
        # path — the MINISCHED_TRACE knob gates only the span stream.
        self._hists: Dict[str, Histogram] = {
            # created → first enqueued (the informer's lag): with the
            # three below it splits created → bound.
            "pod_informer_lag_s": Histogram(),
            "pod_queue_wait_s": Histogram(),
            "pod_decide_s": Histogram(),
            "pod_bind_s": Histogram(),
            "pod_create_to_bound_s": Histogram(),
        }
        self._metrics: Dict[str, float] = {
            "batches": 0, "pods_seen": 0, "pods_assigned": 0,
            "pods_failed": 0, "pods_bound": 0, "bind_conflicts": 0,
            # pods arbitrate_spread revoked for a required anti-affinity
            # conflict with an earlier pod of their own batch
            "anti_revoked_total": 0,
            # Fleet bind fencing: commits withheld because this replica
            # lost the pod's shard lease between decision and commit
            # (the pod is handed back; the new owner re-gathers it).
            "stale_owner_binds": 0,
            # Apiserver-outage ride-through: post-reattach reconciles of
            # the queue against store truth (fleet/election.py drives
            # reconcile_store after RemoteStore.reattach fires).
            "store_reconciles": 0,
            "encode_s_total": 0.0, "step_s_total": 0.0,
            "step_dispatch_s_total": 0.0, "commit_s_total": 0.0,
            "gap_s_total": 0.0,
            # engine_gap_s decomposition: every gap_s_total booking
            # routes through _book_gap tagged with the glue component it
            # measured, so these four PARTITION gap_s_total exactly —
            # gather = blocking queue-pop waits; encode = batch-formation
            # glue (gang pull + priority sort) before the metered encode
            # window; fetch = the dispatch→fetch turnaround (pipeline
            # hand-off before the decision readback blocks); commit =
            # the scheduling thread's blocking wait on the previous
            # batch's commit flush.
            "gap_gather_s_total": 0.0, "gap_encode_s_total": 0.0,
            "gap_fetch_s_total": 0.0, "gap_commit_s_total": 0.0,
            # Pipelined-cycle overlap accounting (_run_pipelined): host
            # work that ran CONCURRENTLY with other pipeline stages —
            # commit_overlap_s = commit-flush time hidden behind the next
            # batch's device step / host stages; encode_overlap_s = the
            # slice of encode+dispatch that ran while the previous
            # batch's commit was still flushing. Both stay 0 in
            # synchronous mode (MINISCHED_PIPELINE=0).
            "encode_overlap_s": 0.0, "commit_overlap_s": 0.0,
            "last_batch_size": 0, "last_encode_s": 0.0,
            "last_step_s": 0.0, "last_commit_s": 0.0,
            # Transfer observability (node-feature traffic; the pod-
            # feature encode upload is identical across modes and not
            # counted): host→device bytes — static-leaf uploads, full
            # dynamic-leaf uploads (fallback mode / residency resyncs),
            # sparse residency corrections — and device→host bytes for
            # every decision/spread/exact-table/residual fetch; plus the
            # residency protocol's hit (delta-corrected batch) and
            # resync (full re-upload) counters.
            "h2d_bytes_total": 0.0, "fetch_bytes_total": 0.0,
            "residency_hits": 0, "residency_resyncs": 0,
            # Supervisor / robustness observability: detected batch
            # faults and the inline degraded retries they triggered,
            # watchdog deadline trips, ladder transitions, batches
            # requeued at the quarantine rung, simulated/real commit
            # worker deaths, and the residency carry cross-check's
            # run/trip counters (MINISCHED_RESIDENT_CHECK_EVERY).
            "batch_faults": 0, "batch_retries": 0, "watchdog_trips": 0,
            "supervisor_escalations": 0, "supervisor_recoveries": 0,
            "slim_readback_reversions": 0,
            "quarantined_batches": 0, "worker_deaths": 0,
            "resident_checks": 0, "residency_desyncs": 0,
            # Nomination-window carry: batches whose outstanding
            # preemption reservations rode the carried chain as an
            # order-free correction instead of forcing the
            # upload-every-batch fallback.
            "residency_nomination_carries": 0,
            # Shortlist-compressed arbitration observability.
            # shortlist_repairs counts full-row repair RESCAN EVENTS —
            # the main step, the residual pass, and every spread-repair
            # iteration each count their own rescans, so a pod re-run
            # across passes can contribute more than once (it genuinely
            # paid more than one (N,)-wide scan); shortlist_certified
            # is the per-batch complement clamped at 0. The cross-check
            # run/trip counters ride MINISCHED_SHORTLIST_CHECK_EVERY.
            "shortlist_repairs": 0, "shortlist_certified": 0,
            "shortlist_checks": 0, "shortlist_desyncs": 0,
            "last_shortlist_repairs": 0,
            # Temporal telemetry + SLO sentinel (obs/timeseries,
            # obs/slo): burn-rate alerts fired (total + per-objective
            # keys created on first fire) and the supervisor's counted
            # early-warning reactions.
            "slo_alerts_total": 0, "supervisor_early_warnings": 0,
            # Persistent device loop (MINISCHED_DEVICE_LOOP):
            # steps_dispatched counts MAIN-step device dispatches (one
            # per batch on the per-batch path, one per TRANCHE in loop
            # mode — steps_dispatched/batches < 1 is the fused-dispatch
            # claim); loop_iterations counts slots consumed through
            # fused loops, loop_tranches the fused dispatches,
            # loop_breaks the mid-tranche divergence/fault break-outs
            # back to per-batch dispatch; decision_fetches counts
            # blocking decision readback TRANSFERS (one per batch
            # per-batch, one per tranche fused — the one-readback-per-
            # tranche byte-ledger claim).
            "steps_dispatched": 0, "loop_tranches": 0,
            "loop_iterations": 0, "loop_breaks": 0,
            "decision_fetches": 0,
            # scored_rows_total is the engine-wide plugin-evaluation
            # ledger in pod-row × node-row units — last_scored_rows is
            # the newest batch's share.
            "scored_rows_total": 0, "last_scored_rows": 0,
            # Fused multi-tenant arbitration (MINISCHED_TENANTS_FUSE):
            # tenant_fused_lanes counts batches this engine served as
            # one LANE of a fused tenant dispatch (the coordinator's
            # mux books the single dispatch/fetch per tranche on its
            # own counters); tenant_solo_fallbacks counts fusion-
            # submitted batches re-dispatched solo — bit-identically —
            # after a mid-tranche cache mutation raced the collect
            # window; tenant_races counts those races.
            "tenant_fused_lanes": 0, "tenant_solo_fallbacks": 0,
            "tenant_races": 0,
        }
        # Rolling time-series ring of metrics() snapshots
        # (MINISCHED_TIMELINE; obs/timeseries.py). The tracker always
        # exists — cheap — and tick() is gated on the process-wide
        # enabled attribute at the one call site (_resolve_batch), so
        # the disarmed hot-path cost is a single attribute test.
        self._timeline = TimelineTracker(self.metrics, name=self.profile)
        # Per-pod decision provenance (obs/journal.ProvenanceStore):
        # bounded LRU beside the resultstore. Always constructed
        # (cheap); records are written only while MINISCHED_JOURNAL is
        # armed (JOURNAL.enabled attribute test at the stamp sites), so
        # the unarmed hot path pays one attribute test per batch.
        self._provenance = ProvenanceStore()
        # SLO sentinel, built lazily from the epoch-current process
        # config at first armed tick (tests re-arm between runs).
        self._slo_sentinel: Optional[slo_mod.SLOSentinel] = None
        self._slo_epoch = -1
        # Adaptive overload controller (engine/overload.py,
        # MINISCHED_OVERLOAD): SLO-actuated admission control,
        # adaptive batch/shortlist tuning, and the brownout ladder.
        # Always constructed (cheap ints); every hook gates on the
        # process-wide enabled flag or the controller's level, so the
        # disarmed hot-path cost is one attribute/int test and
        # decisions stay bit-identical (tests/test_overload.py).
        # Named for the serving profile so per-tenant shed_priority
        # overrides (MINISCHED_OVERLOAD ...;profile:shed_priority=N)
        # resolve against THIS engine's tenant.
        self._overload = overload_mod.OverloadController(name=self.profile)
        # Base shortlist width the tuner retunes around; a permanent
        # certification revert (_disable_shortlist → None) wins over
        # any tuner target. Revisited widths cost no recompile:
        # ops/pipeline's process-wide _STEP_CACHE keys on ``shortlist``.
        self._sl_base = self._shortlist_k
        self.queue.set_admission(
            self._overload.admits,
            backoff_fn=lambda: (overload_mod.OVERLOAD.shed_backoff,
                                overload_mod.OVERLOAD.shed_backoff_max))

    def _sup_count(self, key: str, n: int = 1) -> None:
        # get-based: per-objective SLO alert counters are created on
        # first fire (the objective catalog is env-configurable).
        with self._metrics_lock:
            self._metrics[key] = self._metrics.get(key, 0) + n

    def _book_gap(self, component: str, dt: float) -> None:
        """Book inter-batch glue into gap_s_total, tagged with its
        component (gather/encode/fetch/commit — see the metric-dict
        comment)."""
        if dt <= 0.0:
            return
        with self._metrics_lock:
            self._metrics["gap_s_total"] += dt
            self._metrics[f"gap_{component}_s_total"] += dt

    def _res_count(self, *, resync: bool, h2d: int) -> None:
        with self._metrics_lock:
            self._metrics["h2d_bytes_total"] += h2d
            if resync:
                self._metrics["residency_resyncs"] += 1
            else:
                self._metrics["residency_hits"] += 1

    def _count_fetch(self, nbytes: int) -> None:
        with self._metrics_lock:
            self._metrics["fetch_bytes_total"] += nbytes

    def _check_resident_carry(self, res: "_DeviceResidency", nf) -> None:
        """Every ``resident_check_every`` carried batches, fetch the
        device-carried free array and compare it to the host replay
        mirror BEFORE the step consumes it (ROADMAP residency follow-up
        (b): the slim cross-check covered the readback, not the carry).
        Raises EngineDesync on any divergence — including NaN, which
        np.array_equal rejects — and the caller resyncs + degrades."""
        self._res_check_tick += 1
        if self._res_check_tick % self.config.resident_check_every:
            return
        dev = np.asarray(nf.free)
        self._count_fetch(dev.nbytes)
        self._sup_count("resident_checks")
        if res.mirror_free is not None and not np.array_equal(
                dev, res.mirror_free):
            bad = int(np.sum(np.any(dev != res.mirror_free, axis=1)))
            raise EngineDesync(
                f"device-carried free diverged from the host mirror on "
                f"{bad} row(s) at epoch {res.epoch}")

    def _check_shortlist(self, inf: "_InflightBatch", chosen,
                         assigned) -> None:
        """Every ``shortlist_check_every`` batches, re-run THIS batch's
        exact inputs through the full-width scan and compare decisions —
        the certification invariant made executable. The certificate
        already proves bit-equality inside the jitted step; this check
        covers defects OUTSIDE the proof (scribbled readback between
        device and host — the shortlist_repair:corrupt gate — or a
        backend whose gather/top_k lowering is broken). A divergence
        counts a shortlist_desync, permanently reverts the engine to the
        full scan, and aborts the batch into the supervised retry, which
        replays it bit-identically on the reverted path."""
        if not self.config.shortlist_check_every:
            return
        self._sl_check_tick += 1
        if self._sl_check_tick % self.config.shortlist_check_every:
            return
        self._sup_count("shortlist_checks")
        sample = inf.sample_k
        check_step = build_step(
            self.plugin_set, explain=self.config.explain,
            assignment=self.config.assignment, sample_nodes=sample,
            shortlist=None)
        d = check_step(inf.eb, inf.nf, inf.af, inf.key)
        ref_chosen = np.asarray(d.chosen)
        ref_assigned = np.asarray(d.assigned)
        self._count_fetch(ref_chosen.nbytes + ref_assigned.nbytes)
        L = len(inf.batch)
        if (np.array_equal(chosen[:L], ref_chosen[:L])
                and np.array_equal(assigned[:L], ref_assigned[:L])):
            return
        bad = int(np.sum((chosen[:L] != ref_chosen[:L])
                         | (assigned[:L] != ref_assigned[:L])))
        self._sup_count("shortlist_desyncs")
        instant("shortlist.desync", pods=bad)
        jnote("shortlist.desync", profile=self.profile, replica=self.replica, pods=bad,
              batch=inf.seq)
        self._disable_shortlist(
            f"decisions diverged from the full scan on {bad} pod(s)")
        raise EngineDesync(
            "shortlist certification cross-check failed: decisions "
            f"diverged from the full-width scan on {bad} pod(s)")

    def _disable_shortlist(self, reason: str) -> None:
        """Permanently revert to the full-width scan (the slim-fetch
        revert idiom): rebuild the main step without the shortlist
        stage; sampled steps consult ``_shortlist_k`` per batch."""
        log.error("disabling shortlist-compressed arbitration (%s); "
                  "reverting to the full-width scan", reason)
        jnote("shortlist.disable", profile=self.profile, replica=self.replica, reason=reason,
              batch=self._batch_seq)
        bundle_mod.capture("shortlist_revert", scheduler=self,
                           reason=reason)
        self._shortlist_k = None
        if self._mesh is None:
            self._step = build_step(self.plugin_set,
                                    explain=self.config.explain,
                                    assignment=self.config.assignment,
                                    shortlist=None)

    def _count_h2d(self, nbytes: int) -> None:
        with self._metrics_lock:
            self._metrics["h2d_bytes_total"] += nbytes

    def _pack_dec(self, decision: Decision):
        """Dispatch the fused decision pack — slim (u8 bit-planes + i16
        counts) or the legacy all-i32 layout — WITHOUT fetching. On a
        MESH the Decision is returned unpacked: jitting the mixed-shape
        pack concats over the shard_map step's outputs makes GSPMD
        insert a spurious cross-shard sum on some toolchains (observed
        on jax 0.4 CPU SPMD: every packed value scaled by the node-axis
        size), so mesh mode fetches per leaf."""
        if self._mesh is not None:
            return decision
        pack = pack_decision_slim if self._slim else _pack_decision
        return pack(decision.chosen, decision.assigned,
                    decision.gang_rejected, decision.feasible_counts,
                    decision.feasible_static, decision.reject_counts,
                    decision.shortlist_repaired)

    def _spread_payload(self, d: Decision):
        """Stage ``d``'s spread-arbitration table for _fetch_spread:
        the raw Decision on a mesh (no device-side pack over shard_map
        outputs — see _pack_dec), the jitted packed buffer otherwise.
        EVERY spread fetch — main batch, residual merge, repair
        iterations — must route through this, or a mesh toolchain with
        the GSPMD concat-sum defect feeds node-axis-scaled counts into
        host arbitration."""
        if self._mesh is not None:
            return d
        return _pack_spread(d.spread_pre, d.spread_dom, d.spread_min,
                            d.scan_groups)

    def _fetch_spread(self, payload):
        """Flight-recorded wrapper: ``fetch.spread`` covers the blocking
        spread-table readback (None payload records nothing)."""
        if payload is None:
            return None
        with span("fetch.spread"):
            return self._fetch_spread_impl(payload)

    def _fetch_spread_impl(self, payload):
        """Materialize the (2P+2, G) spread-arbitration table from
        either form _prepare_batch staged: the device-packed buffer
        (single fetch, off-mesh) or the raw Decision (mesh: per-leaf
        fetch + host assembly — see _pack_dec on why the device-side
        pack cannot run over shard_map outputs)."""
        if payload is None:
            return None
        if isinstance(payload, Decision):
            d = payload
            sp = np.concatenate(
                [np.asarray(d.spread_pre),
                 np.asarray(d.spread_dom).astype(np.float32),
                 np.asarray(d.spread_min)[None, :].astype(np.float32),
                 np.asarray(d.scan_groups).astype(np.float32)[None, :]],
                axis=0)
        else:
            sp = np.array(payload)
        self._count_fetch(sp.nbytes)
        return sp

    def _fetch_decision(self, packed_dev, p: int, f: int, decision=None):
        """Flight-recorded wrapper: ``fetch.decision`` covers the
        blocking device readback + slim/i32 decode for every call site
        (main batch, residual pass, repair iterations, cross-checks)."""
        with span("fetch.decision"):
            return self._fetch_decision_impl(packed_dev, p, f, decision)

    def _fetch_decision_impl(self, packed_dev, p: int, f: int,
                             decision=None):
        """Block on the ONE packed decision fetch and unpack it into
        writable host arrays: (chosen i32, assigned bool, gang_rejected
        bool, feasible i32, feasible_static i32, rejects (F,P) i32,
        repaired bool — the shortlist repair ledger).
        A raw Decision (mesh mode, _pack_dec) is fetched per leaf.
        The first slim fetch is verified against direct leaf fetches
        when ``decision`` is supplied; a mismatch (exotic backend byte
        order) logs, permanently reverts to the i32 layout, and refetches
        this batch through it — decisions are never at risk."""
        if type(packed_dev) is tuple:
            # Loop-mode slot: the tranche resolver already fetched the
            # whole stacked buffer in ONE transfer (counted there, fetch
            # fault gate applied there) and pre-unpacked this slot's
            # planes — nothing left to move or count here. Exact-type
            # check: a mesh batch passes the Decision NAMEDTUPLE, which
            # must keep taking the per-leaf fetch below.
            return packed_dev
        # Fault gate: slim decision fetch. ``corrupt`` scribbles the
        # chosen plane with absurd node rows — exercising the sanity
        # DETECTOR downstream (resolve range check / names indexing),
        # not just the exception path.
        act = FAULTS.hit("fetch")
        self._sup_count("decision_fetches")
        if isinstance(packed_dev, Decision):
            d = packed_dev
            out = (np.array(d.chosen), np.array(d.assigned),
                   np.array(d.gang_rejected),
                   np.array(d.feasible_counts),
                   np.array(d.feasible_static),
                   np.array(d.reject_counts),
                   np.array(d.shortlist_repaired))
            self._count_fetch(sum(a.nbytes for a in out))
            if act == "corrupt":
                out[0][:] = 0x7F7F7F7F
            return out
        buf = np.array(packed_dev)  # writable: residual merge mutates
        self._count_fetch(buf.nbytes)
        if not self._slim:
            if act == "corrupt":
                buf[0] = 0x7F7F7F7F       # chosen plane → absurd rows
            return (buf[0], buf[1].astype(bool), buf[2].astype(bool),
                    buf[3], buf[4], buf[6:], buf[5].astype(bool))
        out = unpack_decision_slim(buf, p, f)
        if not self._slim_verified and decision is not None:
            self._slim_verified = True
            ok = (np.array_equal(out[0], np.asarray(decision.chosen))
                  and np.array_equal(out[1],
                                     np.asarray(decision.assigned))
                  and np.array_equal(
                      out[3], np.minimum(
                          np.asarray(decision.feasible_counts), I16_SAT)))
            if not ok:
                # Counted so a chip run can fail on it (chip_smoke.py).
                self._sup_count("slim_readback_reversions")
                log.error(
                    "slim decision readback failed its first-batch "
                    "cross-check on this backend; reverting to the i32 "
                    "packed fetch")
                self._slim = False
                return self._fetch_decision(
                    _pack_decision(
                        decision.chosen, decision.assigned,
                        decision.gang_rejected, decision.feasible_counts,
                        decision.feasible_static, decision.reject_counts,
                        decision.shortlist_repaired),
                    p, f)
        if act == "corrupt":
            # Scribble AFTER the first-batch byte-order cross-check: the
            # injected corruption must reach the resolve sanity DETECTOR
            # — on batch 1 it would otherwise be misread as an exotic
            # backend and silently absorbed by the permanent i32 revert.
            out[0][:] = 0x7F7F7F7F
        return out

    def wants_pod(self, pod: Pod) -> bool:
        """Does this scheduler handle the pod? Profile routing by
        spec.scheduler_name, then — in fleet mode — the deterministic
        shard filter: the pod's hash shard (fleet/shardmap.py) must be
        in this replica's owned set. The shard view is one tuple load,
        so the hot path needs no lock and no store round-trip."""
        if not (self.scheduler_names is None
                or pod.spec.scheduler_name in self.scheduler_names):
            return False
        n_shards, owned, _epoch = self._shard_view
        if n_shards:
            from ..fleet.shardmap import shard_of

            return shard_of(pod.key, n_shards) in owned
        return True

    # ---- fleet shard ownership (fleet/supervisor.py) --------------------

    @property
    def shard_view(self):
        """(n_shards, owned frozenset, epoch) — the fleet ownership
        view. (0, frozenset(), 0) when sharding is off."""
        return self._shard_view

    def set_shards(self, owned, n_shards: int, *, epoch: int = 0) -> None:
        """Atomically replace this replica's owned-shard set. Must be
        called BEFORE start() for the initial assignment (the informer's
        initial sync consults wants_pod at delivery); later calls are
        the takeover/handoff path (adopt_shards / release_shards)."""
        self._shard_view = (int(n_shards), frozenset(owned), int(epoch))

    def set_bind_guard(self, fn) -> None:
        """Install the fleet bind fence: ``fn(pod_key) -> bool`` (False
        = this engine lost the pod's shard; withhold the commit)."""
        self._bind_guard = fn

    def adopt_shards(self, shards, *, epoch: int = 0,
                     reason: str = "") -> int:
        """Live-takeover entry point: extend the owned-shard set and
        drain the dead owner's pending work — every unbound store pod
        that now routes here is re-gathered into the active queue (the
        queue's keyed dedupe skips pods already queued or in flight).
        Returns the number of pods adopted."""
        n_shards, owned, _ = self._shard_view
        self.set_shards(owned | set(shards), n_shards, epoch=epoch)
        adopted = [p for p in self.store.list("Pod")
                   if not p.spec.node_name and self.wants_pod(p)]
        if adopted:
            self.queue.add_many(adopted)
        jnote("fleet.adopt", profile=self.profile, replica=self.replica,
              shards=",".join(str(s) for s in sorted(shards)),
              epoch=epoch, pods=len(adopted), reason=reason)
        return len(adopted)

    def release_shards(self, shards, *, epoch: int = 0,
                       reason: str = "") -> int:
        """Shard handoff on lease loss: shrink the owned set and drop
        every QUEUED pod this replica no longer owns (in-flight pods are
        untouched — their binds resolve through the store CAS / bind
        fence). Returns the number of pods released."""
        n_shards, owned, _ = self._shard_view
        self.set_shards(owned - set(shards), n_shards, epoch=epoch)
        released = self.queue.release_unwanted(self.wants_pod)
        jnote("fleet.release", profile=self.profile, replica=self.replica,
              shards=",".join(str(s) for s in sorted(shards)),
              epoch=epoch, pods=len(released), reason=reason)
        return len(released)

    def burn_signal(self) -> tuple:
        """The per-replica burn signal a fleet replica publishes on its
        heartbeats (fleet/election.py): ``(overload_level,
        "obj1,obj2")`` — the overload-ladder rung plus the last window's
        burning SYMPTOM objectives. Cross-thread safe (immutable int +
        frozenset reads)."""
        return (int(self._overload.level),
                ",".join(sorted(self._overload.last_burning)))

    def reconcile_store(self, *, reason: str = "") -> Dict[str, int]:
        """Post-outage reconciliation against store truth (the
        apiserver-outage ride-through, fleet/election.py): drop every
        QUEUED pod the store already shows bound (a bind that committed
        before the outage must not be re-attempted — the store CAS would
        reject it anyway, but the queue should not carry zombies), then
        re-gather every unbound owned pod the outage may have orphaned
        (the queue's keyed dedupe skips pods already queued/in-flight).
        Nothing lost, nothing doubly bound — both halves re-derived from
        the store, never from this replica's pre-outage memory."""
        pods = self.store.list("Pod")
        bound = {p.key for p in pods if p.spec.node_name}
        dropped = self.queue.release_unwanted(
            lambda p: p.key not in bound and self.wants_pod(p))
        requeue = [p for p in pods
                   if not p.spec.node_name and self.wants_pod(p)]
        if requeue:
            self.queue.add_many(requeue)
        self._metrics["store_reconciles"] += 1
        jnote("engine.reconcile", profile=self.profile,
              replica=self.replica, dropped=len(dropped),
              requeued=len(requeue), reason=reason)
        return {"dropped": len(dropped), "requeued": len(requeue)}

    # ---- lifecycle ------------------------------------------------------

    def start(self) -> None:
        """Start the shared informers (once across all profile engines)
        + this engine's scheduling loop (reference scheduler.go:72-75:
        factory.Start, WaitForCacheSync, go sched.Run). With multiple
        profiles, the SERVICE must construct every engine before starting
        any — a late registration would miss the initial sync."""
        self._shared.ensure_started()
        jnote("engine.start", profile=self.profile, replica=self.replica,
              mode="pipelined" if self.config.pipeline else "sync",
              resident=bool(self._residency is not None),
              shortlist_k=int(self._shortlist_k or 0),
              loop=bool(self._loop_enabled))
        self._thread = threading.Thread(target=self.run, daemon=True,
                                        name="scheduling-loop")
        self._thread.start()

    def abandon(self) -> None:
        """Crash-stop: the SIGKILL model for an in-process replica. Sets
        the abandon flag (honoured between device-loop slots — staged
        slots past the crash point are dropped WITHOUT committing, the
        debris an adopter's ``adopt_shards`` re-gather must drain) and
        stops the loop, but deliberately skips every graceful drain:
        no commit-flush wait, no recorder drain, no broadcaster flush.
        Whatever was in flight stays wherever the crash left it —
        exactly what a dead process leaves behind. The caller (fleet
        supervisor's crash kill) drops leases FIRST so peers can claim
        the debris through the epoch fence."""
        self._abandoned = True
        self._stop.set()
        self.queue.close()
        jnote("engine.abandon", profile=self.profile,
              replica=self.replica)
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        # Cut the executors loose without waiting: a real SIGKILL would
        # not flush them either. The binder threads that already hold a
        # bind will finish it (kernel-level in-flight RPCs land too);
        # queued-but-unstarted work is dropped.
        self._binder.shutdown(wait=False)
        self._committer.shutdown(wait=False)
        self._gatherer.shutdown(wait=False)
        if self._owns_shared:
            self._shared.shutdown()
        if self.recorder is not None:
            self.recorder.close()
        self.broadcaster.close()

    def shutdown(self) -> None:
        self._stop.set()
        self.queue.close()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        if self._owns_shared:
            self._shared.shutdown()
        self._binder.shutdown(wait=False)
        # Wait for the last commit flush: shutdown must leave failure
        # statuses/queue state fully applied (tests and checkpoints read
        # them right after). The run thread exited above, so no new
        # submissions can race this. The gatherer needs no wait — the
        # closed queue unblocks its pop immediately.
        self._committer.shutdown(wait=True)
        self._gatherer.shutdown(wait=False)
        if self.recorder is not None:
            # Budget past one flush's full retry backoff (~6 s at defaults)
            # so a mid-retry flush isn't abandoned silently.
            if not self.recorder.drain(timeout=8.0):
                log.warning("unflushed scheduling results at shutdown: %s",
                            self.recorder.pending_keys()[:10])
            self.recorder.close()
        # Drain recorded events, then stop the sink worker so it releases
        # its store reference (a service that restarts schedulers must not
        # accumulate parked threads pinning old stores). Binder tasks still
        # running after this record into a closed sink and are dropped —
        # events are best-effort, like upstream's broadcaster at shutdown.
        self.broadcaster.flush(timeout=2.0)
        self.broadcaster.close()

    def run(self) -> None:
        """The scheduling loop (reference minisched.go:28-30
        wait.UntilWithContext(ctx, scheduleOne, 0)) — here each iteration
        schedules a whole batch. With ``config.pipeline`` (the default)
        the loop is the bounded two-deep pipeline of _run_pipelined;
        MINISCHED_PIPELINE=0 keeps the strictly synchronous cycle."""
        if self.config.pipeline:
            self._run_pipelined()
            return
        last_done = None
        while not self._stop.is_set():
            max_n, window, idle = self._pop_params()
            batch = self.queue.pop_batch(
                max_n, timeout=0.2, gather_window=window,
                gather_idle=idle)
            if not batch:
                # Genuine idle (no pending pods) is not inter-batch
                # overhead; only back-to-back batches feed the gap metric.
                last_done = None
                continue
            # Batch-to-batch dead time (queue pop + informer lag): the
            # sustained-throughput diagnostic the per-phase timers
            # inside schedule_batch can't see. The whole window is spent
            # inside pop_batch — gather glue.
            if last_done is not None:
                self._book_gap("gather", time.perf_counter() - last_done)
            if self._maybe_run_tranche(batch):
                # Fused device-loop tranche consumed the batch (plus any
                # further ready batches) in one dispatch.
                last_done = time.perf_counter()
                continue
            self._schedule_guarded(batch)
            last_done = time.perf_counter()

    def _run_pipelined(self) -> None:
        """Bounded two-deep pipelined scheduling loop.

        While batch k's jitted step executes on device (JAX async
        dispatch — nothing blocks on results until the resolve fetch),
        the host (a) flushes batch k-1's commit work on the dedicated
        commit worker and (b) gathers batch k+1 from the queue. Batch
        k+1 is ENCODED only after batch k's arbitration + assume
        accounting (_resolve_batch) — the batch-internal causality rule:
        encode sees cache state that already includes k's *assumed*
        placements (it waits on k's arbitration, not on its store
        commit), so decisions are bit-identical to the synchronous loop
        (tests/test_pipeline_engine.py). In-flight work is bounded: one
        dispatched step + one commit flush, never more.

        Stage timeline for batch k (sched = scheduling thread):

            sched:  ...| pop k+1 | resolve k | commit k-1 wait | enc k+1 |
            device:    [........ step k ..........]   [...... step k+1 ...
            commit:    [... flush k-1 (worker) ...]        [... flush k ...
        """
        inflight = None            # prepared + dispatched, not resolved
        pending = None             # (future, inflight) commit in flight
        gather_fut = None          # in-flight pop on the gather worker
        last_done = None

        def pop():
            max_n, window, idle = self._pop_params()
            return self.queue.pop_batch(
                max_n, timeout=0.2, gather_window=window,
                gather_idle=idle)

        try:
            while not self._stop.is_set():
                if inflight is None:
                    if gather_fut is not None:
                        # plain result(): the last_done gap booking below
                        # already covers this wait (using _take_gather
                        # here would double-count it). Span it though —
                        # this is where the scheduling thread sits for
                        # the whole inter-burst idle, and an unspanned
                        # idle would read as unattributed time in the
                        # flight recorder's coverage.
                        with span("gather.wait"):
                            batch = gather_fut.result()
                        gather_fut = None
                    else:
                        batch = pop()
                    if not batch:
                        last_done = None
                        pending = self._await_commit(pending)
                        continue
                    if last_done is not None:
                        self._book_gap("gather",
                                       time.perf_counter() - last_done)
                    inflight, pending = self._prepare_or_trace(batch,
                                                               pending)
                    continue
                # Device is executing `inflight`: start batch k+1's pop
                # — with its FULL batch-formation window — on the gather
                # worker, so it overlaps the device step AND this
                # batch's resolve/commit. Popping here on the scheduling
                # thread would delay k's binds and failure verdicts by
                # up to batch_window_s whenever arrivals trickle.
                if gather_fut is None and not self._stop.is_set():
                    try:
                        gather_fut = self._gatherer.submit(pop)
                    except RuntimeError:  # executor torn down (shutdown)
                        gather_fut = None
                if self._resolve_guarded(inflight):
                    if inflight.failures:
                        pending = self._await_commit(pending)
                        pending = self._submit_commit(inflight)
                    else:
                        # Nothing to flush — the commit is just a metrics
                        # fold. Run it inline: two thread handoffs per
                        # batch cost more than the fold itself, and with
                        # no queue/store side effects the ordering
                        # against an in-flight worker commit is
                        # immaterial.
                        self._commit_guarded(inflight)
                last_done = time.perf_counter()
                # Consume the overlapped pop; this blocks only when the
                # loop genuinely has to wait for work — the same point
                # the synchronous loop blocks in its own pop, and the
                # wait is booked to gap_s like the sync loop's pop wait
                # (per-stage numbers must stay comparable across modes).
                nxt = []
                if gather_fut is not None and not self._stop.is_set():
                    nxt, gather_fut = self._take_gather(gather_fut)
                    nxt = nxt or []
                if nxt:
                    inflight, pending = self._prepare_or_trace(nxt, pending)
                else:
                    inflight = None
        finally:
            # Drain: a dispatched batch is completed (sync semantics —
            # the synchronous loop also finishes its in-flight batch
            # before honoring stop), then the last commit is awaited. A
            # gather that raced the stop and popped pods must not lose
            # them: requeue (a no-op once the queue is closed; a restart
            # re-lists pending pods from the store either way).
            if inflight is not None:
                if self._resolve_guarded(inflight):
                    if inflight.failures:
                        pending = self._await_commit(pending)
                        pending = self._submit_commit(inflight)
                    else:
                        self._commit_guarded(inflight)
            if gather_fut is not None:
                for qpi in gather_fut.result():
                    self.queue.requeue_backoff(qpi)
            self._await_commit(pending)

    def _pop_params(self):
        """(max_n, gather_window, gather_idle) for the next queue pop:
        the config bases, unless the overload tuner is engaged — then
        the effective knobs (batch stepped down toward ``min_batch``,
        formation window stepped up) apply. At tune depth 0 (the
        disarmed/normal state) the bases pass through untouched, so
        decision streams are bit-identical to an untuned engine."""
        cfg = self.config
        ov = self._overload
        if ov.tune_steps == 0:
            return cfg.max_batch_size, cfg.batch_window_s, cfg.batch_idle_s
        return (ov.effective_max_batch(cfg.max_batch_size),
                ov.effective_window(cfg.batch_window_s),
                ov.effective_idle(cfg.batch_idle_s))

    def _take_gather(self, gather_fut):
        """Consume an overlapped pop, booking the BLOCKING portion of a
        PRODUCTIVE wait into gap_s_total — the synchronous loop's
        between-batch pop waits land there too, so the metric stays
        comparable across modes. An empty result is genuine idle (sync
        resets its gap clock for those) and books nothing."""
        t0 = time.perf_counter()
        with span("gather.wait"):
            batch = gather_fut.result()
        waited = time.perf_counter() - t0
        if batch and waited > 0.0:
            self._book_gap("gather", waited)
        return batch, None

    def _prepare_or_trace(self, batch, pending):
        """Prepare (encode + dispatch) a popped batch, or — when a
        profiler trace is armed — drain the pipeline and run the whole
        cycle synchronously under the trace scope. Returns
        (inflight | None, pending)."""
        with self._trace_lock:
            trace_armed = self._trace_dir is not None
        if (trace_armed or "schedule_batch" in self.__dict__
                or self._sup.sync_only()):
            # A trace request needs the whole cycle inside one profiler
            # scope; an instance-patched schedule_batch (test
            # instrumentation wraps cycles that way) must keep seeing
            # whole cycles; and at the supervisor's "sync" rung the
            # engine deliberately runs one batch at a time. All drain
            # the pipeline and run this batch synchronously.
            pending = self._await_commit(pending)
            self._schedule_guarded(batch)
            return None, pending
        if self._loop_gates_open() and self._loop_safe(batch):
            # Fused device-loop tranche: its commits run inline on the
            # scheduling thread, so the previous batch's worker flush
            # must land first (commit order). A decline (no second
            # ready batch) falls through to the normal prepare with the
            # pipeline merely drained one slot early.
            pending = self._await_commit(pending)
            if self._maybe_run_tranche(batch, checked=True):
                return None, pending
        try:
            return self._prepare_batch(batch), pending
        except Exception:
            log.exception("batch prepare failed; engaging supervisor")
            self._supervised_retry(batch)
            return None, pending

    def _resolve_guarded(self, inflight) -> bool:
        """_resolve_batch with the supervisor's failure contract: an
        exception aborts the batch (assumes already rolled back by
        _resolve_batch), which then retries down the degradation ladder
        and skips this pipeline commit."""
        try:
            self._resolve_batch(inflight)
            return True
        except Exception:
            log.exception("batch resolve failed; engaging supervisor")
            self._supervised_retry(inflight.batch, inflight)
            return False

    def _supervised_retry(self, batch: List[QueuedPodInfo],
                          inf: Optional["_InflightBatch"] = None) -> None:
        """Contain a batch fault. The aborted attempt's assumes were
        already rolled back (_resolve_batch) so capacity accounting is
        exact; pods it handed to async owners (binder bulk commit,
        permit waits — ``inf.detached``) are excluded, so nothing can
        double-bind. The remainder retries INLINE down the counted
        degradation ladder — each escalation drops one fast path — and a
        batch that still fails at the bottom rung is quarantined:
        requeued at the backoff ceiling rather than retried, so a poison
        batch can neither wedge the loop nor lose its pods."""
        self._sup_count("batch_faults")
        # The aborted attempt's PRNG anchor (captured before the retry's
        # own prepare re-anchors it): every replay below rewinds to it,
        # so the retry draws the SAME randomness the fault-free run
        # would have — recovered decision streams stay bit-identical.
        anchor = self._prep_step0
        retry = list(batch)
        if inf is not None and inf.detached:
            retry = [q for q in retry if q.pod.key not in inf.detached]
        while True:
            # Strip pods an async owner holds RIGHT NOW — any attempt
            # (the aborted original, a failed degraded retry, or the
            # synchronous cycle, whose inflight never reaches this
            # handler) may have handed pods off before faulting, and
            # retrying OR quarantining one would double-assume it and
            # race the owner's bind/requeue. An owner that already
            # concluded bound or requeued the pod itself — either way
            # it is not this retry's to replay.
            with self._detached_lock:
                live = self._detached_live
                retry = [q for q in retry if q.pod.key not in live]
            if not retry:
                return
            self._sup.escalate("batch fault")
            if self._sup.level >= len(DEGRADATION_LADDER) - 1:
                self._sup_count("quarantined_batches")
                self._step_counter = anchor  # no decision consumed it
                for qpi in retry:
                    self.queue.quarantine(qpi)
                jnote("supervisor.quarantine", profile=self.profile, replica=self.replica,
                      pods=len(retry), batch=self._batch_seq,
                      step=anchor)
                bundle_mod.capture(
                    "quarantine", scheduler=self,
                    reason=f"degradation ladder exhausted; "
                           f"{len(retry)} pod(s) quarantined")
                log.error(
                    "supervisor: exhausted the degradation ladder; "
                    "quarantined %d pods (requeued at backoff ceiling)",
                    len(retry))
                return
            self._sup_count("batch_retries")
            self._step_counter = anchor  # replay, don't advance
            try:
                self.schedule_batch(list(retry))
                jnote("supervisor.retry", profile=self.profile, replica=self.replica,
                      outcome="ok",
                      rung=DEGRADATION_LADDER[self._sup.level],
                      pods=len(retry), batch=self._batch_seq,
                      step=anchor)
                return
            except Exception:
                jnote("supervisor.retry", profile=self.profile, replica=self.replica,
                      outcome="failed",
                      rung=DEGRADATION_LADDER[self._sup.level],
                      pods=len(retry), batch=self._batch_seq,
                      step=anchor)
                log.exception("degraded retry failed at rung %r; "
                              "escalating further",
                              DEGRADATION_LADDER[self._sup.level])

    def _submit_commit(self, inflight):
        """Hand a resolved batch to the commit worker; inline fallback
        when the executor is already torn down (shutdown race)."""
        try:
            return self._committer.submit(self._commit_guarded, inflight), \
                inflight
        except RuntimeError:
            self._commit_guarded(inflight)
            return None

    def _commit_guarded(self, inflight) -> None:
        try:
            self._commit_batch(inflight)
        except FaultWorkerDeath:
            raise  # worker death: _await_commit drains + restarts
        except Exception:
            log.exception("batch commit flush failed")

    def _await_commit(self, pending):
        """Bound the pipeline at ONE commit in flight and account
        commit_overlap_s — the flush time the scheduling thread did NOT
        have to wait for (it ran behind the device step / host stages).
        encode_overlap_s is booked by _commit_batch itself, which knows
        the flush window regardless of which commit path the next batch
        takes."""
        if pending is None:
            return None
        fut, done = pending
        t0 = time.perf_counter()
        try:
            with span("commit.wait"):
                fut.result()  # _commit_guarded re-raises only worker death
        except FaultWorkerDeath:
            self._restart_commit_worker(done)
            return None
        waited = time.perf_counter() - t0
        # The EXPOSED flush wait is inter-batch glue the per-stage meters
        # miss (commit_s books the flush itself on the worker; overlap
        # books the hidden part) — the commit slot of the gap
        # decomposition.
        self._book_gap("commit", waited)
        flush = max(0.0, done.commit_t1 - done.commit_t0)
        with self._metrics_lock:
            self._metrics["commit_overlap_s"] += max(0.0, flush - waited)
        return None

    def _restart_commit_worker(self, done: "_InflightBatch") -> None:
        """Commit worker died mid-flush: replace the executor (worker
        restart), requeue the dead flush's tranche with backoff (its
        status writes / events never applied — the pods are popped, so
        nothing else would ever revive them), and degrade. The pipeline
        drains through the normal _await_commit bound — the pending slot
        is cleared here, so the loop continues with a fresh worker."""
        log.error("commit worker died mid-flush; restarting the worker "
                  "and requeueing its %d-pod tranche", len(done.failures))
        self._sup_count("worker_deaths")
        self._sup.escalate("commit worker death")
        try:
            self._committer.shutdown(wait=False)
        except Exception:
            pass
        self._committer = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="commit")
        for qpi, _plugins, _msg, _retry in done.failures:
            self.queue.requeue_backoff(qpi)

    # ---- persistent on-device engine loop (MINISCHED_DEVICE_LOOP) -------

    def _schedule_guarded(self, batch: List[QueuedPodInfo]) -> None:
        """One guarded per-batch cycle — the run loops' try/supervise
        pattern as a callable (loop break-outs and held batches replay
        through it)."""
        try:
            self.schedule_batch(batch)
        except Exception:
            log.exception("schedule_batch failed; engaging supervisor")
            self._supervised_retry(batch)

    def _effective_loop_depth(self) -> int:
        """Work-ring depth for the next tranche: the configured depth,
        stepped down by the overload tuner (halved per tune step, floor
        1 = loop disengaged) — the batch/K dials and the ring compose."""
        return self._overload.effective_loop_depth(self.config.loop_depth)

    def _loop_gates_open(self) -> bool:
        """Cheap engagement gates for the fused device loop — everything
        that must hold REGARDLESS of the batch's pods. The loop is the
        fastest rung of the ladder: any degradation, outstanding
        nomination reservation, permit profile, explain recorder, armed
        shortlist cross-check (its full-scan replay needs the per-batch
        nf the ring doesn't materialize), unverified slim layout (the
        first-batch byte-order insurance runs per-batch), or active
        cooldown (the loop→pipelined rung) keeps per-batch dispatch."""
        if not self._loop_enabled or self._loop_cooldown > 0:
            return False
        if (self.recorder is not None or self.plugin_set.permit_plugins
                or self._nominations or self._sup.level != 0
                or self.config.shortlist_check_every):
            return False
        # An armed profiler trace must capture a whole per-batch cycle
        # (schedule_batch is the only consumer of _trace_dir), and an
        # instance-patched schedule_batch (test instrumentation) must
        # keep seeing every batch — the pipelined loop drains for these
        # before considering a tranche; this gate covers sync mode too.
        with self._trace_lock:
            if self._trace_dir is not None:
                return False
        if "schedule_batch" in self.__dict__:
            return False
        if self._slim and not self._slim_verified:
            return False
        return self._effective_loop_depth() >= 2

    def _loop_safe(self, batch: List[QueuedPodInfo]) -> bool:
        """May this batch ride the work ring? True only when every pod's
        decision is provably independent of the host state the ring
        cannot carry: no gangs (quorum accounting spans batches), no
        pod-affinity/anti-affinity terms and no spread constraints
        (their scores/filters read the assigned corpus, which the ring
        shares tranche-wide), no volumes (RWO arbitration + claim-table
        accounting are host-side), no host ports (the cache's bulk
        assume debits port pods out of pod order, which would break the
        bitwise mirror-vs-truth validation), no owner references
        when SelectorSpread runs (owner groups read the corpus too), and
        no pod a running pod's anti class repels (its class slots read
        the corpus and set the pod rows' width batch by batch).
        The per-pod walk is shared with tenant fusion (_ring_safe_pods —
        the same host-state-independence property gates both fast
        paths). A batch the per-batch path would node-SAMPLE is unsafe
        as well — the ring runs the full axis and sampling draws a
        different key path, so fusing it would change decisions."""
        if not self._ring_safe_pods(batch):
            return False
        n_pad = self._node_pad(self.cache.rows_high_water())
        if self._sampled_step(n_pad, len(batch), False)[0] is not None:
            return False
        return True

    def _ring_safe_pods(self, batch: List[QueuedPodInfo]) -> bool:
        """The per-pod half of the fast-path safety walk, shared by the
        device loop's work ring and tenant fusion (_tenant_fusable)."""
        for q in batch:
            pod = q.pod
            s = pod.spec
            if (s.pod_group or s.topology_spread_constraints or s.volumes
                    or s.ports):
                return False
            a = s.affinity
            if a is not None and (a.pod_affinity is not None
                                  or a.pod_anti_affinity is not None):
                return False
            if self._selspread_enabled and pod.metadata.owner_references:
                return False
        # A pod that running pods' anti classes repel carries class
        # slots read off the corpus, their width set batch by batch: no
        # fixed ring layout. Pods no class matches keep the fast paths
        # (the match is memoized per pod signature).
        if self._anti_enabled and self.cache.anti_class_count():
            for q in batch:
                if self.cache.anti_classes_for(q.pod) != ((), None):
                    return False
        return True

    def _tenant_fusable(self, batch: List[QueuedPodInfo], hard_spread: bool,
                        fail_closed) -> bool:
        """Per-batch fusion gates for the multi-tenant vmapped step —
        the loop posture: fast rung only (a degraded engine drops
        speculation first), no nominations (their debits modify the
        step's free input outside the fused staging), no explain
        recorder, no armed shortlist cross-check (its attribution must
        stay per-batch), no fail-closed verdicts, no hard-spread host
        arbitration, and the shared per-pod safety
        walk (no gangs / topology / volumes / ports / pod-affinity /
        owner groups — which also keeps spread_dev None, matching the
        sequential engine). Gated-out batches dispatch solo inside
        prepare: the coordinator's per-profile fallback."""
        if (self.config.assignment != "greedy" or self._mesh is not None
                or self.config.explain or self.recorder is not None):
            return False
        if (self._sup.level != 0 or self._nominations or fail_closed
                or hard_spread or self.config.shortlist_check_every):
            return False
        return self._ring_safe_pods(batch)

    def _maybe_run_tranche(self, batch: List[QueuedPodInfo], *,
                           checked: bool = False) -> bool:
        """Try to consume ``batch`` — plus up to depth-1 further READY
        queue batches — as ONE fused device-loop tranche. Returns True
        when the pods were consumed (fused, or replayed per-batch after
        a break); False = caller schedules ``batch`` itself. Ring
        filling pops with timeout 0: only immediately-available pods
        join a tranche, so a shallow stream degenerates to per-batch
        dispatch with zero added latency. ``checked=True`` = the caller
        already ran the per-pod safety walk (the pipelined loop runs it
        before draining its commit slot) — skip repeating it on the hot
        path; the cheap gate flags ALWAYS re-check, because the commit
        drain between the caller's check and this call can escalate the
        supervisor, and a degraded engine must not open a tranche."""
        if not (self._loop_gates_open()
                and (checked or self._loop_safe(batch))):
            return False
        depth = self._effective_loop_depth()
        max_n, _window, _idle = self._pop_params()
        slots: List[List[QueuedPodInfo]] = [batch]
        held: Optional[List[QueuedPodInfo]] = None
        while len(slots) < depth:
            nxt = self.queue.pop_batch(max_n, timeout=0.0)
            if not nxt:
                break
            if not self._loop_safe(nxt):
                held = nxt
                break
            slots.append(nxt)
        if len(slots) < 2:
            if held is None:
                return False
            # A second batch was popped but cannot ride the ring: run
            # both through the guarded per-batch path in pop order.
            self._schedule_guarded(batch)
            self._schedule_guarded(held)
            return True
        self._run_tranche(slots)
        if held is not None:
            self._schedule_guarded(held)
        return True

    def _loop_break(self, reason: str, *, slot: int) -> None:
        """Break the ring back to per-batch dispatch: counted, traced,
        the carried residency chain dropped (the device free_final
        reflects every staged slot's debits, including ones the break
        just invalidated)."""
        self._sup_count("loop_breaks")
        instant("loop.break", reason=reason, slot=slot)
        jnote("loop.break", profile=self.profile, replica=self.replica, reason=reason,
              slot=slot, batch=self._batch_seq)
        res = self._residency
        if res is not None:
            res.drop(f"device-loop break: {reason}")

    def _loop_probation(self) -> None:
        """Engage the ladder's loop→pipelined rung AFTER a fault's
        containment finished: set here (not inside the break) because
        every resolved batch — including the break's own per-batch
        replay tail — pays one cooldown tick, and a depth-sized replay
        would otherwise consume the whole probation before any NEW
        traffic ran at the per-batch rung."""
        self._loop_cooldown = max(1, self.config.probation_batches)

    def _replay_tail(self, slot_batches, start: int,
                     anchor: Optional[int]) -> None:
        """Replay the un-consumed slots through the guarded per-batch
        path with their ORIGINAL PRNG draws. With ``anchor`` the step
        counter rewinds to the first unconsumed slot's draw (staging
        advanced it past every staged slot); the slot-fault path passes
        None — _supervised_retry already left the counter exactly where
        a never-fused run would have it (consumed on a successful
        degraded retry, rewound on quarantine), and forcing it forward
        here would shift every tail batch's tie-break stream."""
        if anchor is not None:
            self._step_counter = anchor + start
        for b in slot_batches[start:]:
            self._schedule_guarded(b)

    def _run_tranche(self, slot_batches: List[List[QueuedPodInfo]]) -> None:
        """One fused device-loop tranche end to end, with the
        containment contract: a machinery fault (staging, dispatch,
        stacked fetch, validator) never loses a pod — every slot that
        did not consume its decision replays per-batch."""
        progress = {"done": 0}
        anchor = self._step_counter
        try:
            self._run_tranche_impl(slot_batches, progress, anchor)
        except Exception:
            log.exception("device-loop tranche failed; replaying the "
                          "remaining slots per-batch")
            self._loop_break("tranche machinery fault",
                             slot=progress["done"])
            self._replay_tail(slot_batches, progress["done"], anchor)
            self._loop_probation()

    def _run_tranche_impl(self, slot_batches, progress, anchor) -> None:
        cfg = self.config
        n_slots = len(slot_batches)
        res = self._residency

        # Baseline-drain the loop listener BEFORE the snapshot: marks
        # landing in the window between drain and snapshot are already
        # inside the snapshot's truth, so re-seeing them at slot-0
        # validation costs at worst a false (conservative) break —
        # draining after the snapshot could instead DISCARD a
        # post-snapshot mutation and miss a real divergence.
        self.cache.drain_dyn_rows(self._loop_listener)

        # ---- one snapshot + carry attach for the whole tranche --------
        cached = self._nf_static_device
        res_live = (res is not None and not self._nominations
                    and self._sup.allows_residency())
        if res_live:
            nf, names, static_v, row_incs, dyn_delta = (
                self.cache.snapshot_resident(
                    pad=self._node_pad,
                    known_static=cached[0] if cached else None,
                    dyn=res.listener))
        else:
            nf, names, static_v, row_incs = self.cache.snapshot_versioned(
                pad=self._node_pad,
                known_static=cached[0] if cached else None)
            dyn_delta = None
        nf = self._with_device_static(nf, static_v, row_incs.shape[0])
        carried = False
        if res_live:
            # Same residency fault-gate semantics as the per-batch
            # prepare; any attach/cross-check failure propagates to the
            # tranche containment (replay per-batch re-snapshots).
            act = FAULTS.hit("residency")
            with span("h2d.dyn"):
                nf = res.attach(self, nf, dyn_delta)
            carried = True
            if act == "corrupt" and res.mirror_free is not None:
                res.mirror_free[0, :] += 1.0
            if cfg.resident_check_every:
                self._check_resident_carry(res, nf)

        # Tranche-local mirrors (host twins of the carried chain): each
        # slot's debits replay into ``mirror`` in pod order — the same
        # IEEE op sequence as the scan's carry and the cache's bulk
        # assume — and the between-slot validator compares marked rows'
        # host truth against it. ``pmirror`` is compared only (the ring
        # stages no port pods, so used_ports is tranche-invariant).
        if carried:
            mirror = res.mirror_free.copy()
            pmirror = res.mirror_ports
        else:
            mirror = np.array(nf.free, copy=True)
            pmirror = np.asarray(nf.used_ports)
            # Upload-mode ledger: ONE full dynamic upload per tranche
            # (the fused win over per-batch's per-dispatch upload).
            self._count_h2d(nf.free.nbytes + pmirror.nbytes)
        af = self.cache.snapshot_assigned(pad=self._af_pad)

        # ---- stage the ring: encode every slot at ONE fixed pod pad ---
        P_ring = step_bucket(max(len(b) for b in slot_batches),
                             cfg.pod_bucket_min)
        infs: List[_InflightBatch] = []
        counters: List[int] = []
        for b in slot_batches:
            # Per-slot dispatch-seam fault gate: the ring consumes one
            # gate hit per batch, like the per-batch path — an ``err``
            # here aborts into containment (everything replays
            # per-batch down the ladder).
            FAULTS.hit("step")
            inf = self._stage_slot(b, P_ring, nf, names, af, row_incs)
            infs.append(inf)
            counters.append(self._step_counter)

        # ---- ONE fused dispatch + ONE stacked fetch -------------------
        loop_fn = build_loop_step(self.plugin_set,
                                  assignment=cfg.assignment,
                                  shortlist=self._shortlist_k,
                                  slim=self._slim)
        # The scan's program shape includes the depth axis: pad ragged
        # tranches to the power-of-two bucket with masked no-op slots
        # (all rows invalid — they assign nothing and carry ``free``
        # through bit-exactly, like a ragged slot's pad rows), so the
        # compile set per pod bucket stays {2, 4, 8, ...} instead of
        # one synchronous retrace for every depth the queue fill
        # happens to produce.
        d_ring = bucket_for(n_slots, 2)
        slot_ebs = [i.eb for i in infs]
        if d_ring > n_slots:
            eb_noop = jax.tree_util.tree_map(np.zeros_like, infs[0].eb)
            slot_ebs += [eb_noop] * (d_ring - n_slots)
            counters = counters + [0] * (d_ring - n_slots)
        eb_stack = jax.tree_util.tree_map(
            lambda *xs: np.stack(xs), *slot_ebs)
        ctr = np.asarray(counters, dtype=np.uint32)
        t_disp0 = time.perf_counter()
        with span("loop.dispatch", slots=n_slots, ring=d_ring):
            packed_dev, free_final = loop_fn(eb_stack, nf, af, ctr,
                                             self._key)
        self._sup_count("steps_dispatched")
        self._sup_count("loop_tranches")
        self._sup_count("loop_iterations", n_slots)
        with span("fetch.loop", slots=n_slots):
            # ONE blocking d2h transfer; pad slots' buffers stay on
            # device (they hold no decisions).
            stack = np.array(packed_dev[:n_slots])
        t_fetched = time.perf_counter()
        self._count_fetch(stack.nbytes)
        self._sup_count("decision_fetches")
        if FAULTS.hit("fetch") == "corrupt":
            # Scribble every slot's chosen plane — the per-batch
            # fetch:corrupt semantics applied to the stacked buffer; the
            # resolve sanity detector must catch slot 0 and the
            # containment must replay the rest without losing a pod.
            if self._slim:
                stack[:, :4 * P_ring] = 0x7F
            else:
                stack[:, 0, :] = 0x7F7F7F7F
        share = max(0.0, t_fetched - t_disp0) / n_slots

        # ---- per-slot resolve + commit + between-slot validation ------
        n_filters = len(self.filter_names)
        for j, inf in enumerate(infs):
            if self._abandoned:
                # Crash-stop (abandon()): slots [j:] are STAGED — their
                # decisions exist only in this process's memory — but
                # never resolved or committed, so their pods stay
                # unbound in the store. That is the debris an adopting
                # replica's adopt_shards re-gather drains. No replay
                # tail, no carry adoption: a dead process does neither.
                self._sup_count("loop_abandoned_slots", n_slots - j)
                jnote("loop.abandon", profile=self.profile,
                      replica=self.replica, slot=j,
                      slots_staged=n_slots,
                      pods_staged=sum(len(b)
                                      for b in slot_batches[j:]))
                return
            buf = stack[j]
            tup = (unpack_decision_slim(buf, P_ring, n_filters)
                   if self._slim else unpack_decision_i32(buf))
            inf.packed_dev = tup
            inf.step_share = share
            inf.loop_slot = j
            inf.t_dispatch = t_disp0
            self._prep_step0 = int(counters[j]) - 1
            try:
                self._resolve_batch(inf)
            except Exception:
                log.exception("device-loop slot resolve failed; "
                              "engaging supervisor")
                self._supervised_retry(inf.batch, inf)
                progress["done"] = j + 1
                self._loop_break("slot fault", slot=j)
                # anchor=None: _supervised_retry left the counter where
                # a never-fused run would (consumed on success, rewound
                # on quarantine) — forcing it would shift the tail's
                # tie-break streams.
                self._replay_tail(slot_batches, j + 1, None)
                self._loop_probation()
                return
            # The slot is CONSUMED once resolve returns (assumes made,
            # binds submitted): containment past this point must never
            # re-schedule it, whatever the commit below does.
            progress["done"] = j + 1
            try:
                self._commit_batch(inf)
            except FaultWorkerDeath:
                # Inline commit — the synchronous-cycle contract:
                # requeue the tranche, degrade, keep going.
                log.error("commit flush died in a device-loop slot; "
                          "requeueing its %d-pod tranche",
                          len(inf.failures))
                self._sup_count("worker_deaths")
                self._sup.escalate("commit flush death")
                for qpi, _plugins, _msg, _retry in inf.failures:
                    self.queue.requeue_backoff(qpi)

            # Validation: did host truth move off the carried chain?
            # Fold this slot's device debits into the mirror as the
            # order-free per-node aggregate (_DeviceResidency I1 —
            # bitwise the greedy scan's sequential carry AND the
            # auction's round-order einsum subtracts under the
            # exact-integer grammar), then compare every row the cache
            # mutated since the last slot against it. Any mismatch —
            # assume miss, failed bind, informer churn, revocation —
            # means slot j+1's decisions were computed against inputs
            # per-batch dispatch would not have fed it: break and
            # replay the tail bit-identically.
            ch, asg = tup[0], tup[1]
            rows_deb = ch[asg].astype(np.int64)
            if rows_deb.size:
                uniq_deb = np.unique(rows_deb)
                agg = np.zeros((uniq_deb.shape[0], mirror.shape[1]),
                               dtype=mirror.dtype)
                np.add.at(agg, np.searchsorted(uniq_deb, rows_deb),
                          inf.eb.pf.requests[asg])
                mirror[uniq_deb] -= agg
            diverged = bool(
                rows_deb.size
                and not np.isfinite(mirror[np.unique(rows_deb)]).all())
            rows, fvals, pvals = self.cache.drain_dyn_rows(
                self._loop_listener)
            if not diverged and rows.size:
                # A row the tranche's pad cannot represent (node add
                # that grew the cache mid-tranche) is divergence by
                # definition — per-batch dispatch would re-snapshot at
                # the bigger pad and could place pods there.
                if int(rows[-1]) >= mirror.shape[0]:
                    diverged = True
                else:
                    diverged = (not np.array_equal(fvals, mirror[rows])
                                or not np.array_equal(pvals,
                                                      pmirror[rows]))
            if not diverged and self._nominations:
                # A preemption nomination reserves capacity the carried
                # chain cannot represent (same stand-down as residency).
                diverged = True
            if diverged:
                if j < n_slots - 1:
                    self._loop_break("carry divergence", slot=j)
                    self._replay_tail(slot_batches, j + 1, anchor)
                else:
                    # Tail divergence: every decision is consumed, only
                    # the carry adoption is off — drop it (next batch
                    # re-uploads) without a per-batch replay.
                    self._loop_break("tail divergence", slot=j)
                return

        # ---- clean completion: adopt the fused carry ------------------
        if carried:
            res.free_dev = free_final
            res.mirror_free = mirror
            res.pending_rows = res.pending_pre = None
            res.pending_prows = res.pending_ppre = None

    def _encode_batch(self, batch: List[QueuedPodInfo], pods: List[Pod],
                      P_pad: int, *, seq: int, loop_slot: bool = False):
        """The encode block shared by per-batch prepare and ring-slot
        staging (``batch`` already priority-sorted, ``pods`` its pod
        list). One store pass per pod resolves every volume-derived
        input (readiness, claim mount rows, zone requirement); both
        encode callbacks share it via the returned per-batch memo.
        ``fail_closed`` maps pod key → (plugin, reason) for pods whose
        required anti-affinity/affinity term or DoNotSchedule spread
        constraint cannot fit the encoding slots (or whom running pods'
        anti classes repel beyond what the count plane can judge) —
        they must be rejected
        after the step rather than scheduled against a silently
        weakened constraint. Only constraints this profile's plugin set
        actually ENFORCES fail closed: a profile without
        InterPodAffinity ignores affinity terms entirely (encode always
        records them; only the filter enforces), so an unrepresentable
        term must not park the pod under a plugin that can never regate
        it. Returns (vol_memo, fail_closed, eb)."""
        vol_memo: Dict[str, tuple] = {}

        def vol_state(pod: Pod) -> tuple:
            st = vol_memo.get(pod.key)
            if st is None:
                st = vol_memo[pod.key] = self._volume_state(pod)
            return st

        fail_closed: Dict[str, tuple] = {}  # pod key → (plugin, reason)
        anti_fn = None
        if self._anti_enabled and self.cache.anti_class_count():
            def anti_fn(pod: Pod) -> tuple:
                # once per pod signature (encode_pods' prototype memo)
                with span("encode.anti", seq=seq):
                    return self.cache.anti_classes_for(pod)

        encode_hard: Dict[int, tuple] = {}
        with span("encode.pods", pods=len(pods), seq=seq,
                  **({"loop_slot": 1} if loop_slot else {})):
            eb = encode_pods(pods, P_pad, cfg=self.cache.cfg,
                             registry=self.cache.registry,
                             overflow=self.cache.overflow,
                             volumes_ready_fn=lambda p: vol_state(p)[0],
                             gang_bound_fn=self.cache.gang_bound_count,
                             volume_info_fn=lambda p: vol_state(p)[1:],
                             anti_classes_fn=anti_fn,
                             hard_failed=encode_hard,
                             selector_spread=self._selspread_enabled)
        self._anti_class_slots = int(eb.pf.anti_cls_group.shape[1])
        for idx, infos in encode_hard.items():
            for info in infos:
                if self._fail_closed_plugins.get(info[0], True):
                    fail_closed.setdefault(batch[idx].pod.key, info)
                    break
        return vol_memo, fail_closed, eb

    def _stage_slot(self, batch: List[QueuedPodInfo], P_ring: int,
                    nf, names, af, row_incs) -> "_InflightBatch":
        """Encode one ring slot at the tranche's fixed pod pad — the
        prepare phase minus snapshot and dispatch. Ragged slots pad with
        masked (invalid) rows; the shortlist/greedy bodies mask them, so
        decisions for the real rows are bit-identical to the slot's
        natural bucket (pinned by tests/test_device_loop.py)."""
        t_in = time.perf_counter()
        self._prep_step0 = self._step_counter
        self._step_counter += 1
        inf = _InflightBatch()
        self._batch_seq += 1
        inf.seq = self._batch_seq
        batch = sorted(batch, key=lambda q: -q.pod.spec.priority)
        pods = [q.pod for q in batch]
        t0 = time.perf_counter()
        self._book_gap("encode", t0 - t_in)
        vol_memo, fail_closed, eb = self._encode_batch(
            batch, pods, P_ring, seq=inf.seq, loop_slot=True)
        if fail_closed or eb.pf.anti_cls_group.shape[1]:
            # Loop-safe pods cannot trip slot constraints by
            # construction, and no anti class repels them when the ring
            # fills (_ring_safe_pods); one born or widened mid-tranche
            # would give this slot another layout. Containment replays
            # everything per-batch, where the fail-closed machinery
            # applies.
            raise EngineDesync(
                "loop slot hit a fail-closed encode verdict or an anti "
                "class")
        inf.batch, inf.pods = batch, pods
        inf.vol_memo, inf.fail_closed = vol_memo, {}
        inf.eb, inf.names, inf.row_incs = eb, names, row_incs
        inf.nf, inf.af = nf, af
        # Scored-rows ledger: every ring slot pays the full (P_ring, N)
        # filter+score pass inside the fused scan body.
        inf.scored_rows = int(P_ring) * int(nf.valid.shape[0])
        inf.key = jax.random.fold_in(self._key, self._step_counter)
        inf.sample_k = None
        inf.decision = None
        inf.spread_dev = None
        inf.t0, inf.t_encode = t0, time.perf_counter()
        inf.t_dispatch = inf.t_encode
        with self._metrics_lock:
            self._prep_window = (t0, inf.t_dispatch)
        return inf

    # ---- one batched scheduling cycle ----------------------------------

    def trace_next_batch(self, trace_dir: str) -> None:
        """Capture a jax profiler trace (device + host timeline, viewable
        in TensorBoard/Perfetto) of the NEXT scheduling batch into
        ``trace_dir``. The reference's observability is klog lines only
        (SURVEY §5 'no pprof, no timing metrics'); this is the rebuild's
        deep-dive profiling tool alongside the always-on phase metrics."""
        with self._trace_lock:
            self._trace_dir = trace_dir

    def dump_trace(self, path: str) -> str:
        """Export the process-wide flight recorder (obs.TRACE ring
        buffers — spans at every engine seam, fault/ladder instants) as
        Chrome trace-event JSON, Perfetto-loadable. Arm the recorder
        with MINISCHED_TRACE=1 (or obs.configure) first; an unarmed dump
        writes a valid but empty trace. Returns ``path``."""
        from ..obs import TRACE

        return TRACE.export_chrome(path)

    def schedule_batch(self, batch: List[QueuedPodInfo]) -> Decision:
        with self._trace_lock:
            trace_dir, self._trace_dir = self._trace_dir, None
        if trace_dir:
            with jax.profiler.trace(trace_dir):
                return self._schedule_batch_impl(batch)
        return self._schedule_batch_impl(batch)

    def _schedule_batch_impl(self, batch: List[QueuedPodInfo]) -> Decision:
        """One synchronous cycle: the three pipeline phases back-to-back
        on the calling thread. The pipelined run loop calls the phases
        directly so they interleave across batches; results are
        identical either way (the phase cut points are the batch-internal
        causality boundaries)."""
        inf = self._prepare_batch(batch)
        self._resolve_batch(inf)
        try:
            self._commit_batch(inf)
        except FaultWorkerDeath:
            # No worker thread to restart in the synchronous cycle —
            # contain the death like the flush fallback would: requeue
            # the tranche (retrying the WHOLE batch here would re-schedule
            # pods the binder already owns) and degrade.
            log.error("commit flush died in the synchronous cycle; "
                      "requeueing its %d-pod tranche", len(inf.failures))
            self._sup_count("worker_deaths")
            self._sup.escalate("commit flush death")
            for qpi, _plugins, _msg, _retry in inf.failures:
                self.queue.requeue_backoff(qpi)
        return inf.decision

    def _prepare_batch(self, batch: List[QueuedPodInfo]) -> "_InflightBatch":
        """Flight-recorded wrapper: the ``prepare`` span covers gang
        pull → encode → snapshot → dispatch on the scheduling thread.
        The batch id (``seq``) is assigned here, first, so every span of
        the batch can carry it."""
        self._batch_seq += 1
        seq = self._batch_seq
        with span("prepare", seq=seq) as sp:
            inf = self._prepare_batch_impl(batch, seq)
            sp.set(pods=len(inf.batch))
            return inf

    def _prepare_batch_impl(self, batch: List[QueuedPodInfo],
                            seq: int) -> "_InflightBatch":
        """PREPARE: gang pull → encode → snapshot → async step dispatch.
        Returns with the device executing the batch (JAX async dispatch;
        nothing here blocks on device results), so the pipelined loop can
        overlap the previous batch's commit and the next pop with it."""
        t_in = time.perf_counter()
        # Supervisor replay anchor: prepares are strictly sequential on
        # the scheduling thread (encode-after-arbitration), so at any
        # batch fault this is the step-counter value the aborted attempt
        # started from. _supervised_retry rewinds to it, handing the
        # degraded replay the aborted attempt's PRNG draw — which keeps
        # the post-recovery decision stream bit-identical to a
        # fault-free run (tie-breaks fold in the step counter).
        self._prep_step0 = self._step_counter
        inf = _InflightBatch()
        inf.seq = seq
        cfg = self.config
        # Pull queued gang-mates so no batch boundary splits a gang (the
        # step would reject the partial group for missing quorum). This may
        # push the batch past max_batch_size — a split gang can never meet
        # quorum, so the pull wins — but the overflow (bigger pad bucket →
        # possible recompile + memory spike) should be visible.
        for group in {gang_key(q.pod) for q in batch
                      if q.pod.spec.pod_group}:
            batch.extend(self.queue.pop_group(group))
        if len(batch) > cfg.max_batch_size:
            log.warning(
                "batch grew to %d pods (> max_batch_size %d) pulling gang "
                "mates; padding bucket may recompile", len(batch),
                cfg.max_batch_size)
        batch = sorted(batch, key=lambda q: -q.pod.spec.priority)
        pods = [q.pod for q in batch]

        t0 = time.perf_counter()
        # Batch-formation glue (gang pull + priority sort + per-batch
        # setup) between the pop and the metered encode window — the
        # encode slot of the gap decomposition.
        self._book_gap("encode", t0 - t_in)
        with self._metrics_lock:
            # prepare STARTED; end published when dispatch returns (None
            # end = still encoding — the commit worker's encode-overlap
            # booking clips such a window at its own flush end)
            self._prep_window = (t0, None)
        # Encode pods FIRST: constraints may register new topology keys,
        # which the node snapshot's domain tables must reflect.
        p_req = step_bucket(len(pods), cfg.pod_bucket_min)
        if self._tenant_mux is not None:
            # Ragged tenant batches harmonize to the fusion round's
            # common pod pad (the vmapped lanes must share one P).
            # Masked-row padding: the extra rows are invalid, so the
            # real rows' decisions are unchanged — the invariant the
            # device loop's _stage_slot already leans on.
            p_req = max(p_req, self._tenant_mux.round_pods)
        vol_memo, fail_closed, eb = self._encode_batch(batch, pods, p_req,
                                                       seq=seq)
        # Versioned snapshot: the static version is observed under the
        # snapshot lock (the snapshot's own topology refresh can bump it),
        # and the cache skips host copies of static leaves we already hold
        # on device (known_static hit). With device residency live, the
        # DYNAMIC leaves are elided too: the cache hands back only the
        # rows it mutated since the last batch (DynDelta) and the
        # resident free/used_ports arrays are corrected in place.
        cached = self._nf_static_device
        res = self._residency
        res_live = res is not None and self._sup.allows_residency()
        if res is not None and not res_live:
            # Supervisor degradation (level ≥ "upload") drops the carry;
            # probation re-escalation re-establishes it through a
            # counted full re-upload. (Nominated-capacity reservations
            # no longer force this fallback: they ride the carry as an
            # order-free per-node correction below — subtracted from the
            # step's free INPUT only, added back before the carried
            # adoption, so the chain keeps representing un-nominated
            # cache truth and a reservation that expires without any
            # cache mutation costs nothing.)
            res.drop("supervisor degradation")
        if res_live:
            nf, names, static_v, row_incs, dyn_delta = (
                self.cache.snapshot_resident(
                    pad=self._node_pad,
                    known_static=cached[0] if cached else None,
                    dyn=res.listener))
        else:
            nf, names, static_v, row_incs = self.cache.snapshot_versioned(
                pad=self._node_pad,
                known_static=cached[0] if cached else None)
            dyn_delta = None
        af = self.cache.snapshot_assigned(pad=self._af_pad)
        nf = self._with_device_static(nf, static_v, row_incs.shape[0])
        carried = False
        if res_live:
            try:
                # Fault gate: residency delta upload/carry. err → the
                # resync fallback below; corrupt → diverge the HOST
                # mirror from the device truth so the carry cross-check
                # (the supervisor's desync detector) has a real defect
                # to catch.
                act = FAULTS.hit("residency")
                with span("h2d.dyn"):
                    nf = res.attach(self, nf, dyn_delta)
                carried = True
                if act == "corrupt" and res.mirror_free is not None:
                    res.mirror_free[0, :] += 1.0
                if self.config.resident_check_every:
                    self._check_resident_carry(res, nf)
            except EngineDesync as e:
                # ROADMAP residency follow-up (b): the device-carried
                # free diverged from the host replay mirror — count a
                # desync, force a full re-upload, and degrade.
                log.warning("resident carry cross-check failed (%s); "
                            "forcing a full re-upload", e)
                self._sup_count("residency_desyncs")
                instant("residency.desync", reason=str(e))
                jnote("residency.desync", profile=self.profile, replica=self.replica,
                      reason=str(e), batch=self._batch_seq)
                self._sup.escalate("resident carry desync")
                carried = False
                res.drop("carry cross-check mismatch")
                cached = self._nf_static_device
                nf, names, static_v, row_incs = (
                    self.cache.snapshot_versioned(
                        pad=self._node_pad,
                        known_static=cached[0] if cached else None))
                nf = self._with_device_static(nf, static_v,
                                              row_incs.shape[0])
            except Exception:
                log.exception("device residency attach failed; resyncing "
                              "through a full snapshot")
                carried = False
                res.drop("attach error")
                cached = self._nf_static_device
                nf, names, static_v, row_incs = (
                    self.cache.snapshot_versioned(
                        pad=self._node_pad,
                        known_static=cached[0] if cached else None))
                nf = self._with_device_static(nf, static_v,
                                              row_incs.shape[0])
        if not carried and isinstance(nf.free, np.ndarray):
            # Upload-every-batch path: the jitted step transfers the
            # full dynamic leaves host→device on dispatch.
            self._count_h2d(nf.free.nbytes + nf.used_ports.nbytes)
        # Nominated-capacity protection (upstream nominatedNodeName
        # semantics): capacity a preemption freed is RESERVED for its
        # preemptor — reservations of pods NOT in this batch are debited
        # from the snapshot's free so the batch cannot steal them; a
        # nominee in the batch sees its own reservation as available.
        nom_reserved_dev = None
        if self._nominations:
            reserved = self._nomination_debits(
                {q.pod.key for q in batch}, names, nf)
            if reserved is not None:
                if carried:
                    # Nomination-window carry: apply the reservation as
                    # an order-free per-node correction to the CARRIED
                    # free — a fresh device array feeds the step while
                    # res.free_dev keeps the un-nominated truth the
                    # mirror tracks. The resolve phase adds the same
                    # correction back (note_debits add_back) before
                    # adopting free_after, an exact round-trip under
                    # the integer grammar, so the chain never learns
                    # the reservation existed. (The cross-check above
                    # already ran against the pre-correction arrays.)
                    nom_reserved_dev = jax.device_put(
                        reserved, self._nf_sharding("free"))
                    nf = nf._replace(free=nf.free - nom_reserved_dev)
                    self._sup_count("residency_nomination_carries")
                    self._count_h2d(reserved.nbytes)
                else:
                    nf = nf._replace(free=nf.free - reserved)
        t_encode = time.perf_counter()

        self._step_counter += 1
        key = jax.random.fold_in(self._key, self._step_counter)
        L_b = len(batch)
        # Hard (DoNotSchedule) spread rows, known host-side from the
        # encode: they pick the full-axis step (the in-scan domain caps
        # judge skew against RUNNING counts at choice time — sampling
        # would disable the caps and push every admission through the
        # host replay plus its (G,D) table fetch) and gate the spread
        # arbitration fetch below.
        hard_spread = False
        if self._spread_enabled:
            from ..encode import features as _F

            hard_spread = bool(
                ((eb.pf.spread_group[:L_b] >= 0)
                 & (eb.pf.spread_mode[:L_b] == _F.SPREAD_DO_NOT_SCHEDULE)
                 ).any())
        # Node-axis sampling (percentage_of_nodes_to_score): a small batch
        # against a huge cluster runs the pipeline on the top-K candidate
        # subset; pods the sample finds 0-feasible are re-checked below
        # against the full axis before any terminal verdict.
        has_gang = any(q.pod.spec.pod_group for q in batch)
        if self._mesh is not None:
            step_fn, sample_k = self._mesh_step(eb, nf, af), None
        else:
            step_fn, sample_k = self._sampled_step(
                nf.free.shape[0], len(batch), has_gang or hard_spread)
            step_fn = step_fn or self._step
        # Fault gate: jitted step dispatch (err → supervised retry down
        # the ladder; stall → lands in the watchdog's step window).
        FAULTS.hit("step")
        # Fused multi-tenant arbitration (MINISCHED_TENANTS_FUSE): when
        # the fusion coordinator armed this engine's lane on the tenant
        # cache mux, a fusable batch SUBMITS its fully-staged step
        # inputs instead of dispatching — the mux issues ONE vmapped
        # step over every submitted lane (encode/cache.TenantCacheMux.
        # dispatch) and fills this lane's decision planes before the
        # coordinator resolves it.
        if (self._tenant_mux is not None and sample_k is None
                and self._tenant_fusable(batch, hard_spread, fail_closed)):
            inf.tenant_ticket = self._tenant_mux.submit(
                self, inf, eb, nf, af, key)
            decision = None
            packed_dev = None
            spread_dev = None
        else:
            with batch_step(seq), span("step.dispatch", seq=seq):
                decision = step_fn(eb, nf, af, key)
            self._sup_count("steps_dispatched")
            # Scored-rows ledger (pod-row × node-row plugin-evaluation
            # units — scored_rows_total): the full step pays the whole
            # (P_pad, N) matrix; sampling narrows N to its K.
            inf.scored_rows += int(eb.pf.valid.shape[0]) * int(
                sample_k if sample_k is not None else nf.valid.shape[0])
            # Pack every per-pod output into ONE device buffer before
            # fetching: each np.asarray is a full device round trip, and
            # five separate fetches of tiny arrays cost ~4 extra latencies per batch (measured ~0.27 s at 10k pods
            # — comparable to the whole device compute). The slim layout
            # (default) additionally bit-packs the bool planes and
            # narrows the counts to i16, ~2.4× fewer bytes than the i32
            # stack.
            packed_dev = self._pack_dec(decision)
            # The spread/anti arbitration inputs are fetched only when
            # the batch actually carries something the host must
            # arbitrate: a hard (DoNotSchedule) spread slot or a
            # required anti-affinity term. A soft-only topology batch
            # (the common ScheduleAnyway case) pays neither the pack
            # dispatch nor the (2P+2, G) transfer — arbitrate_spread
            # would return empty for it anyway.
            needs_arb = hard_spread or bool(
                self._spread_enabled and self._anti_enabled
                and (eb.pf.anti_req_group[:L_b] >= 0).any())
            spread_dev = (self._spread_payload(decision) if needs_arb
                          else None)
        # Dispatch returns before the device finishes (jax async); the
        # first np.asarray in _resolve_batch blocks. Splitting the two
        # reveals whether step time is host→device feeding or device
        # compute — and is what the pipelined loop overlaps against.
        inf.batch, inf.pods = batch, pods
        inf.vol_memo, inf.fail_closed = vol_memo, fail_closed
        inf.eb, inf.names, inf.row_incs = eb, names, row_incs
        inf.nf, inf.af, inf.key, inf.sample_k = nf, af, key, sample_k
        inf.res_carried = carried
        inf.nom_reserved = nom_reserved_dev
        inf.decision = decision
        inf.packed_dev, inf.spread_dev = packed_dev, spread_dev
        inf.t0, inf.t_encode = t0, t_encode
        inf.t_dispatch = time.perf_counter()
        with self._metrics_lock:
            # published for the commit worker's encode-overlap booking
            self._prep_window = (t0, inf.t_dispatch)
        return inf

    def _resolve_batch(self, inf: "_InflightBatch") -> None:
        """RESOLVE: block on the device fetch, then run every host stage
        the NEXT batch's encode depends on — residual pass, RWO/spread
        arbitration, assume accounting, in-cycle repair, preemption —
        and submit the bulk bind. Failure verdicts are DECIDED here (they
        feed gang atomicity and the arbitration dead sets) but their side
        effects — store status writes, queue requeues, events — are
        deferred into ``inf.failures`` for _commit_batch, which the
        pipelined loop overlaps with the next batch's device step."""
        self._fail_sink = inf.failures
        self._fail_sink_tid = threading.get_ident()
        self._track = inf
        try:
            with span("resolve", pods=len(inf.batch), seq=inf.seq):
                self._resolve_batch_impl(inf)
        except BaseException:
            # Crash-consistent abort: reverse every assume this batch
            # made that no async owner took over, so a supervised retry
            # can never double-debit capacity and an abort never leaks
            # an assume.
            self._rollback_assumed(inf)
            raise
        finally:
            self._fail_sink = None
            self._track = None
            self._prov_batch = None
        inf.t_resolved = time.perf_counter()
        self._watchdog_check(inf)
        self._sup.note_clean()
        if self._loop_cooldown > 0:
            # The loop→pipelined rung's probation: one clean resolved
            # batch pays one cooldown tick (scheduling thread only).
            self._loop_cooldown -= 1
        if TIMELINE.enabled:
            self._timeline_tick()

    def _timeline_tick(self) -> None:
        """Temporal-telemetry cadence point (scheduling thread, one per
        resolved batch, gated on TIMELINE.enabled at the call site).
        When the cadence elapses the tracker appends a snapshot row and
        the SLO sentinel evaluates its burn windows over the ring; a
        rising-edge alert is counted, emitted as a trace instant,
        appended to the /timeline alerts list, and fed to the
        supervisor as an early warning."""
        entry = self._timeline.tick()
        if entry is None:
            return
        cfg = slo_mod.SLO
        if not cfg.enabled:
            self._overload_disarm_check()
            return
        if self._slo_sentinel is None or self._slo_epoch != cfg.epoch:
            self._slo_sentinel = slo_mod.SLOSentinel.from_config(cfg)
            self._slo_epoch = cfg.epoch
        for alert in self._slo_sentinel.evaluate(self._timeline.entries()):
            self._sup_count("slo_alerts_total")
            self._sup_count(f"slo_alerts_{alert['slo']}")
            instant("slo.burn", **{k: v for k, v in alert.items()
                                   if isinstance(v, (int, float, str))})
            jnote("slo.burn", profile=self.profile, replica=self.replica,
                  batch=self._batch_seq,
                  **{k: v for k, v in alert.items()
                     if isinstance(v, (int, float, str))})
            self._timeline.note_alert(alert)
            self._sup.early_warning(f"slo:{alert['slo']}")
        for name in self._slo_sentinel.last_cleared:
            instant("slo.clear", slo=name)
            jnote("slo.clear", profile=self.profile, replica=self.replica, slo=name,
                  batch=self._batch_seq)
        if overload_mod.OVERLOAD.enabled:
            self._drive_overload(entry)
        else:
            self._overload_disarm_check()

    def _overload_disarm_check(self) -> None:
        """A runtime disarm (overload.configure("")) must not leave the
        controller's latched actuation applied: every cross-thread hook
        already gates on the enabled flag, and this snapshot-cadence
        check neutralizes the stateful residue — the controller's level
        machine, the timeline stretch, a retuned shortlist width, and
        any parked shed pods. (After a FULL telemetry disarm no ticks
        run at all, but then the enabled-gated hooks alone restore every
        effective knob, the flusher re-admits shed pods via the open
        gate, and a tuner-moved shortlist width — exact at any K —
        persists only until restart or re-arm.)"""
        if not self._overload.note_window(set()):
            return
        self._timeline.stretch = 1
        want = self._sl_base
        if (self._shortlist_k is not None and want is not None
                and self._shortlist_k != want and self._mesh is None):
            self._shortlist_k = want
            self._step = build_step(self.plugin_set,
                                    explain=self.config.explain,
                                    assignment=self.config.assignment,
                                    shortlist=want)
        n = self.queue.release_shed()
        log.info("overload controller disarmed at runtime; actuation "
                 "neutralized (%d shed pod(s) released)", n)

    def _drive_overload(self, entry: dict) -> None:
        """Feed the overload controller one snapshot window (scheduling
        thread, at sentinel cadence) and apply whatever actuation
        changed. The controller sees only the sentinel's SYMPTOM burn
        verdicts — the degraded-posture objective is excluded for the
        same livelock reason the supervisor's probation gate excludes
        it (load shedding must not hold itself engaged just because the
        fault ladder is off the fast path)."""
        sent = self._slo_sentinel
        burning = {s.name for s in sent.specs
                   if s.kind != "degraded" and sent.burning.get(s.name)}
        ov = self._overload
        prev_shedding = ov.shedding
        prev_brownout = ov.brownout_active
        if not ov.note_window(burning,
                              entry.get("d_shortlist_repairs", 0.0)):
            return
        if ov.brownout_active and not prev_brownout:
            # Brownout ENTRY is one of the bundle-trigger incident
            # classes: the deepest overload rung means quality is being
            # shed — freeze the state that explains how we got here.
            bundle_mod.capture(
                "brownout", scheduler=self,
                reason=f"overload ladder entered brownout "
                       f"(burning: {', '.join(sorted(burning))})")
        # Shortlist retune: always within the certified machinery (any
        # K is exact — repairs absorb a narrow one); a permanent
        # certification revert (_shortlist_k = None) wins forever.
        want = ov.shortlist_target(self._sl_base)
        if (self._shortlist_k is not None and want is not None
                and want != self._shortlist_k and self._mesh is None):
            log.warning("overload tuner: shortlist K %d -> %d",
                        self._shortlist_k, want)
            self._shortlist_k = want
            # build_step memoizes process-wide on the shortlist width,
            # so ladder revisits reuse the compiled step
            self._step = build_step(self.plugin_set,
                                    explain=self.config.explain,
                                    assignment=self.config.assignment,
                                    shortlist=want)
        # Brownout quality shed: stretch the timeline cadence while
        # level 3 holds (restored on recovery).
        self._timeline.stretch = ov.timeline_stretch
        # Recovery below the shedding rung: re-admit every parked pod
        # now rather than waiting out each shed backoff.
        if prev_shedding and not ov.shedding:
            n = self.queue.release_shed()
            if n:
                log.info("overload recovered below shedding; re-admitted "
                         "%d shed pod(s)", n)

    def _slo_burning_any(self) -> bool:
        """Is any SYMPTOM objective of the CURRENT sentinel burning?
        (The supervisor's probation gate; scheduling-thread reads of
        the sentinel's own last-evaluate state.) The degraded-posture
        objective is excluded by construction: it burns BECAUSE the
        engine is degraded, and gating the climb on it would livelock
        the ladder at the degraded rung forever — the gate heeds what
        the users feel (latency, desyncs, faults, invariants), never
        the containment posture itself."""
        sent = self._slo_sentinel
        if (sent is None or not slo_mod.SLO.enabled
                or self._slo_epoch != slo_mod.SLO.epoch):
            return False
        return any(sent.burning.get(s.name) for s in sent.specs
                   if s.kind != "degraded")

    def timeline(self, since: int = 0) -> Dict:
        """The GET /timeline JSON payload for this engine: the snapshot
        ring (gauges + window deltas + histogram-delta quantiles +
        attribution tags) and the SLO alert log. Empty-but-valid when
        MINISCHED_TIMELINE is unset. ``since`` returns only rows with
        ``seq > since`` (the /journal cursor contract — scrapers stop
        re-downloading the full ring every poll)."""
        return self._timeline.to_doc(since)

    def overload_reject_reason(self) -> Optional[str]:
        """The apiserver admission provider's per-engine verdict: a
        reason string while this engine's overload controller is at or
        past its HTTP-reject rung (counted in admission_rejects_total),
        else None. Any-thread safe (int reads)."""
        return self._overload.http_reject_reason()

    # ---- per-pod decision provenance (obs/journal.ProvenanceStore) -------

    def _prov_path(self, inf: "_InflightBatch") -> dict:
        """The batch-scoped half of a provenance record: the path that
        served this batch — engine mode, ring slot, ladder rungs,
        shortlist width, residency posture — computed once per
        resolved batch (journal armed only) and shared by every pod the
        batch settles."""
        return {
            "profile": self.profile,
            "replica": self.replica,
            "batch": inf.seq,
            "step": self._prep_step0 + 1,
            "mode": ("loop" if inf.step_share is not None
                     else "pipelined" if self.config.pipeline
                     else "sync"),
            "loop_slot": inf.loop_slot,
            "rung": DEGRADATION_LADDER[self._sup.level],
            "resident": bool(inf.res_carried),
            "shortlist_k": int(self._shortlist_k or 0),
            "overload_level": self._overload.level,
            "decided_unix": round(time.time(), 3),
        }

    def _prov_stamp(self, qpi: QueuedPodInfo, node_name: str, *,
                    repaired: bool = False,
                    spread_repaired: bool = False) -> None:
        """Stamp a pod's decision provenance onto its QueuedPodInfo at
        placement time (scheduling thread, inside resolve — the one
        window where the chosen node and the batch path are both
        known). The bound/failed settlement sites then publish it into
        the LRU with the outcome. Callers gate on ``_prov_batch`` so
        the unarmed path never even makes the call."""
        path = self._prov_batch
        if path is None:
            return
        qpi.prov = {**path, "pod": qpi.pod.key, "node": node_name,
                    "attempts": qpi.attempts,
                    "shed_count": qpi.shed_count,
                    "shortlist_repaired": bool(repaired),
                    "spread_repaired": bool(spread_repaired)}

    def _prov_settle_failure(self, qpi: QueuedPodInfo, plugins,
                             message: str, retryable: bool) -> None:
        """Publish a failed/requeued pod's provenance record (journal
        armed only; callers gate on JOURNAL.enabled). A pod that never
        reached a placement stamp still gets the batch path when the
        verdict lands on the scheduling thread mid-resolve."""
        base = qpi.prov
        qpi.prov = None  # consumed — see the bound-settlement twin
        if base is None:
            path = (self._prov_batch
                    if threading.get_ident() == self._fail_sink_tid
                    else None)
            base = {**path, "pod": qpi.pod.key} if path else {
                "profile": self.profile, "replica": self.replica,
                "pod": qpi.pod.key}
        self._provenance.record(qpi.pod.key, {
            **base, "outcome": "requeued" if retryable else "failed",
            "plugins": sorted(plugins), "message": message[:200],
            "attempts": qpi.attempts,
            "settled_unix": round(time.time(), 3)})

    def provenance(self, pod_key: str) -> Optional[dict]:
        """The ``GET /provenance/<pod>`` record for one pod, or None.
        Empty store when MINISCHED_JOURNAL was never armed. (The
        journal itself is process-wide — SchedulerService.journal
        serves it; there is deliberately no per-engine proxy.)"""
        return self._provenance.get(pod_key)

    def _rollback_assumed(self, inf: "_InflightBatch") -> None:
        if not inf.assumed:
            return
        n = 0
        for key in list(inf.assumed):
            inf.assumed.pop(key, None)
            try:
                self.cache.account_unbind(key)
                n += 1
            except Exception:  # rollback must reverse the rest regardless
                log.exception("rollback unassume failed for %s", key)
        log.warning("rolled back %d assumed placement(s) from an aborted "
                    "batch", n)

    def _watchdog_check(self, inf: "_InflightBatch") -> None:
        """Per-batch device-step watchdog: the dispatch→fetch window
        (minus the pipelined gather gap, same accounting as step_s)
        exceeding the deadline counts a trip and degrades one rung. The
        batch itself completed — nothing is retried; the point is that
        the NEXT batches stop leaning on a path that just took 100× its
        budget (a hung device, a thrashing backend)."""
        wd = self.config.watchdog_s
        if self._sup.prearm > 0:
            # SLO early-warning posture: run with the fallback deadline
            # (or the configured one if tighter) for the pre-armed
            # batches, then stand down.
            self._sup.prearm -= 1
            wd = min(wd, SLO_PREARM_WATCHDOG_S) if wd else \
                SLO_PREARM_WATCHDOG_S
        if not wd:
            return
        if inf.step_share is not None:
            # Loop-mode slot: the deadline is judged against this
            # batch's SHARE of the tranche's fused device window — the
            # per-batch deadline thereby scales with loop depth (a
            # depth-8 tranche compares window/8 per slot, so a deadline
            # sized for one batch doesn't falsely trip on eight).
            step_window = inf.step_share
        else:
            gather_gap = max(0.0, inf.t_fetch_start - inf.t_dispatch)
            step_window = (inf.t_step - inf.t_encode) - gather_gap
        if step_window > wd:
            self._sup_count("watchdog_trips")
            instant("watchdog.trip", window_s=round(step_window, 6),
                    deadline_s=wd)
            jnote("watchdog.trip", profile=self.profile, replica=self.replica,
                  window_s=round(step_window, 6), deadline_s=wd,
                  batch=inf.seq)
            self._sup.escalate(
                f"watchdog: device step took {step_window:.3f}s "
                f"(deadline {wd}s)")

    def _note_assumed(self, qpi: QueuedPodInfo) -> None:
        t = self._track
        if t is not None and threading.get_ident() == self._fail_sink_tid:
            t.assumed[qpi.pod.key] = qpi

    def _note_detached(self, key: str) -> None:
        """An async owner (binder bulk commit, permit wait) now owns the
        pod's placement: it leaves the rollback ledger and is excluded
        from any supervised retry of this batch."""
        t = self._track
        if t is not None and threading.get_ident() == self._fail_sink_tid:
            t.assumed.pop(key, None)
            t.detached.add(key)
            with self._detached_lock:
                self._detached_live.add(key)

    def _resolve_batch_impl(self, inf: "_InflightBatch") -> None:
        batch, pods, eb, names = inf.batch, inf.pods, inf.eb, inf.names
        nf, af, key, sample_k = inf.nf, inf.af, inf.key, inf.sample_k
        vol_memo, fail_closed = inf.vol_memo, inf.fail_closed
        spread_dev = inf.spread_dev

        # In pipelined mode the next batch's queue gather sits between
        # dispatch and this fetch; stamping the fetch start keeps that
        # host-side gap out of the step metric (it books as gap time).
        inf.t_fetch_start = time.perf_counter()
        if inf.tenant_ticket is not None:
            # A fused tenant lane must be dispatched by the mux before
            # the coordinator resolves it — reaching here with the
            # ticket armed is a coordinator sequencing defect, and
            # np.array(None) below would fail unintelligibly instead.
            raise EngineDesync(
                "fused tenant lane reached resolve with its ticket "
                "still armed (mux.dispatch did not run)")
        decision, row_incs = inf.decision, inf.row_incs
        # decision is None for a loop-mode slot (the tranche resolver
        # pre-unpacked the stacked fetch); the filter count is a static
        # profile property either way.
        n_filters = (decision.reject_counts.shape[0]
                     if decision is not None else len(self.filter_names))
        (chosen, assigned, gang_rejected, feasible, feasible_static,
         rejects, sl_repaired) = self._fetch_decision(
            inf.packed_dev, eb.pf.valid.shape[0], n_filters, decision)
        # Supervisor fetch-sanity detector — BEFORE the residency replay
        # trusts ``chosen``: a corrupted readback (defective transport,
        # injected fetch:corrupt) must abort the batch, not poison the
        # carried mirror past the name table.
        L0 = len(batch)
        if assigned[:L0].any():
            ch = chosen[:L0][assigned[:L0]]
            if int(ch.min()) < 0 or int(ch.max()) >= len(names):
                raise EngineDesync(
                    "decision readback failed its sanity check: chosen "
                    f"node row outside [0, {len(names)})")
        if self._shortlist_k is not None:
            # Fault gate: shortlist decision accounting. ``corrupt``
            # re-points one assigned pod at a DIFFERENT valid node row —
            # the signature of a shortlist mispick the certificate
            # should have repaired (scribbled candidate gather, broken
            # backend top_k). It passes the range sanity check above by
            # construction; only the full-scan certification
            # cross-check below can catch it.
            if (FAULTS.hit("shortlist_repair") == "corrupt"
                    and assigned[:L0].any()):
                j = int(np.argmax(assigned[:L0]))
                chosen[j] = (int(chosen[j]) + 1) % len(names)
            self._check_shortlist(inf, chosen, assigned)
            inf.sl_repairs += int(sl_repaired[:L0].sum())
        sp = self._fetch_spread(spread_dev)
        # Provenance path (journal armed only): computed before any
        # placement stamp.
        self._prov_batch = (self._prov_path(inf) if JOURNAL.enabled
                            else None)
        if inf.res_carried:
            # Replay the MAIN step's device debits into the host mirror
            # and adopt free_after as the carried next-batch input —
            # before the residual merge mutates chosen/assigned (the
            # carried array is the main step's output; residual/repair
            # placements reach the device as next-batch corrections).
            # A fused tenant lane has no Decision — its carried array
            # is its slice of the fused step's free_after.
            res = self._residency
            res.note_debits(chosen, assigned, eb.pf.requests,
                            decision.free_after if decision is not None
                            else inf.free_after,
                            add_back=inf.nom_reserved)
            # ROADMAP residency follow-up (d): model the batch's
            # host-port insertions on the device-resident used_ports
            # (and its mirror, identical integer op order) so a
            # port-heavy steady state uploads nothing — previously every
            # bind's cache-side port write forced a row correction the
            # next batch. Same PRE-residual-merge discipline as the free
            # debits; revoked/failed placements re-converge through the
            # cache listener delta exactly like free rows do.
            ports = np.asarray(eb.pf.ports)
            live = assigned & (ports != 0).any(axis=1)
            if live.any():
                # Gather to the port-carrying pods only (pow2 bucket,
                # -1 pad rows are skipped by the insert): the upload is
                # proportional to port pods, and a no-port batch — the
                # common case — never reaches this line at all.
                idx = np.nonzero(live)[0]
                k = bucket_for(idx.size, 16)
                rows_pad = np.full((k,), -1, dtype=np.int32)
                rows_pad[:idx.size] = chosen[idx]
                ports_pad = np.zeros((k, ports.shape[1]),
                                     dtype=ports.dtype)
                ports_pad[:idx.size] = ports[idx]
                self._count_h2d(res.note_ports(rows_pad, ports_pad))

        if sample_k is not None:
            # Residual pass: a pod with zero feasible nodes IN THE SAMPLE
            # may still fit elsewhere (pinned claim row, node selector,
            # scarce taint tolerance outside the top-K) — re-evaluate
            # those pods against the full axis with the sample's capacity
            # already subtracted, and merge. Terminal unschedulable
            # verdicts therefore never come from a sample.
            L = len(batch)
            res_rows = np.nonzero((feasible[:L] == 0) & ~assigned[:L])[0]
            if res_rows.size:
                self._run_residual(
                    eb, nf, af, key, res_rows, decision,
                    chosen, assigned, gang_rejected, feasible,
                    feasible_static, rejects, sp)
        t_step = time.perf_counter()
        # Lifecycle stamp: the device's verdict for this batch exists
        # from here on — decided_at feeds the pod_decide/pod_bind
        # histograms when the pod later binds.
        now_mono = time.monotonic()
        for qpi in batch:
            qpi.decided_at = now_mono

        if self.recorder is not None and not self._overload.explain_skip():
            # Brownout (overload level 3) pauses explain ingestion —
            # optional quality shed before latency; the skip is counted
            # (overload_explain_skipped) so the result-store gap stays
            # attributable.
            self.recorder.record_batch(pods, names, decision, self.plugin_set)

        with span("resolve.arbitrate", seq=inf.seq):
            revoked, parked_gangs = (
                arbitrate_rwo(batch, assigned, chosen, vol_memo)
                if self._rwo_enabled else (set(), set()))
            for i in revoked:
                if gang_key(batch[i].pod) in parked_gangs:
                    self._handle_failure(
                        batch[i], {COSCHEDULING},
                        "gang members demand the same RWO claim on different "
                        "nodes", retryable=False)
                else:
                    self._handle_failure(
                        batch[i], {BATCH_CAPACITY},
                        "RWO claim pinned by an earlier pod in this batch",
                        retryable=True)

            if fail_closed:
                # BEFORE the spread arbitration: fail-closed revocations (and
                # their gang cascades) must be in its dead set — their scan-
                # counted admissions otherwise leave a later placement
                # committed over max_skew (the assume-miss staleness class,
                # reachable with no node deletion at all). This order also
                # guarantees fail-closed pods park TERMINALLY: the old
                # post-arbitration placement let a spread-revoked fail-closed
                # pod be requeued retryable first and skipped here.
                # Gang atomicity: failing one member closed parks its whole
                # gang — peers binding at sub-quorum is the partial-
                # allocation deadlock gang scheduling exists to prevent.
                dead_gangs = {gang_key(q.pod) for q in batch
                              if q.pod.key in fail_closed
                              and q.pod.spec.pod_group}
                for i, qpi in enumerate(batch):
                    if i in revoked:
                        continue
                    info = fail_closed.get(qpi.pod.key)
                    gk = gang_key(qpi.pod)
                    if info is None and gk not in dead_gangs:
                        continue
                    if info is not None:
                        plugins, reason = {info[0]}, info[1]
                    else:
                        plugins = set()
                        reason = (f"gang {qpi.pod.spec.pod_group} member "
                                  "failed closed on an unrepresentable hard "
                                  "constraint")
                    if gk in dead_gangs:
                        plugins.add(COSCHEDULING)
                    self._handle_failure(qpi, plugins, reason, retryable=False)
                    revoked = revoked | {i}

            repair_rows: List[int] = []
            if self._spread_enabled and sp is not None:
                s_revoked = self._arbitrate_packed(
                    batch, assigned, eb, decision, sp, dead=revoked)
                from ..state.objects import CLAIM_UNUSED
                for i in sorted(s_revoked):
                    qpi = batch[i]
                    st = vol_memo.get(qpi.pod.key)
                    # In-cycle repair candidates: re-placed against refreshed
                    # counts after the survivors are assumed (_repair_spread)
                    # instead of paying a full queue round-trip + backoff per
                    # tranche. Excluded: gang members (repairing one member
                    # alone breaks gang atomicity) and pods holding unused RWO
                    # claims (a repair could move them off the node their
                    # claim was arbitrated against). Fail-closed pods never
                    # appear here — they were parked terminally above and are
                    # in the arbitration's dead set.
                    if (self.config.spread_repair_iters
                            and not qpi.pod.spec.pod_group
                            and not (st is not None
                                     and CLAIM_UNUSED in st[1])):
                        repair_rows.append(i)
                    else:
                        self._handle_failure(qpi, {BATCH_CAPACITY},
                                             _SPREAD_REVOKE_MSG,
                                             retryable=True)
                revoked = revoked | s_revoked

        to_bind: List[tuple] = []  # permit-free (qpi, node_name) pairs
        # With no permit plugins in the profile (the common case) the
        # per-pod binding cycle reduces to assume + enqueue: batch the
        # assumes into one cache-lock acquisition reusing the encoder's
        # request rows — at 10k pods/batch the per-pod account_bind walk
        # was the largest host-side slice of the cycle.
        bulk_assume = not self.plugin_set.permit_plugins
        assume_items: List[tuple] = []
        assume_rows: List[int] = []
        assume_incs: List[int] = []  # snapshot row incarnations per item
        # Rows whose SCAN-COUNTED admission vanished after the fact:
        # assume misses (node deleted mid-cycle, both paths) and
        # synchronous permit rejections. Either way later placements may
        # be legal only because of them — see the post-assume block.
        lost_rows: List[int] = []
        preempt_rows: List[int] = []          # deferred terminal verdicts
        preempt_plugins: Dict[int, Set[str]] = {}
        # Python-int views: per-element numpy scalar indexing inside a
        # 10k-iteration loop costs real milliseconds on the commit path.
        with span("resolve.verdicts", seq=inf.seq):
            chosen_l = chosen[:len(batch)].tolist()
            assigned_l = assigned[:len(batch)].tolist()
            gang_rejected_l = gang_rejected[:len(batch)].tolist()
            feasible_l = feasible[:len(batch)].tolist()
            static_l = feasible_static[:len(batch)].tolist()
            n_ghost = 0  # assigned rows lost to a mid-cycle node deletion
            for i, qpi in enumerate(batch):
                if i in revoked:
                    continue
                gk = gang_key(qpi.pod) if parked_gangs else None
                if gk and gk in parked_gangs:
                    # Unassigned members of a parked gang would otherwise fall
                    # through to the retryable BATCH_CAPACITY path and thrash
                    # one extra cycle before being gang-rejected — park the
                    # whole gang in one cycle (assigned members are already in
                    # ``revoked`` via gang atomicity).
                    self._handle_failure(
                        qpi, {COSCHEDULING},
                        "gang members demand the same RWO claim on different "
                        "nodes", retryable=False)
                    continue
                if assigned_l[i]:
                    node_name = names[chosen_l[i]]
                    if self._prov_batch is not None:
                        self._prov_stamp(qpi, node_name,
                                         repaired=bool(sl_repaired[i]))
                    if bulk_assume:
                        assume_items.append((qpi.pod, node_name))
                        assume_rows.append(i)
                        assume_incs.append(int(row_incs[chosen_l[i]]))
                        to_bind.append((qpi, node_name))
                    else:
                        pair, ghost, rej = self._start_binding_cycle(
                            qpi, node_name,
                            expected_inc=int(row_incs[chosen_l[i]]))
                        if ghost:
                            n_ghost += 1
                            lost_rows.append(i)
                        elif rej:
                            lost_rows.append(i)
                        if pair is not None:
                            to_bind.append(pair)
                elif gang_rejected_l[i]:
                    # The pod's gang missed quorum — park the whole member
                    # set under Coscheduling (plus any real filter
                    # rejections, for precise event gating) until a new
                    # member or capacity event.
                    plugins = {COSCHEDULING}
                    if feasible_l[i] == 0:
                        plugins |= {self.filter_names[f]
                                    for f in range(rejects.shape[0])
                                    if rejects[f, i] > 0}
                    self._handle_failure(
                        qpi, plugins,
                        f"gang {qpi.pod.spec.pod_group} missed quorum "
                        f"{qpi.pod.spec.pod_group_min}", retryable=False)
                elif feasible_l[i] > 0 and static_l[i] > 0:
                    # Nodes were feasible but earlier pods in the batch took
                    # the capacity — retryable, not unschedulable (SURVEY §7
                    # "batch-internal causality").
                    self._handle_failure(
                        qpi, {BATCH_CAPACITY},
                        "ran out of capacity within scheduling batch",
                        retryable=True)
                else:
                    plugins = {self.filter_names[f]
                               for f in range(rejects.shape[0])
                               if rejects[f, i] > 0} or {BATCH_CAPACITY}
                    if feasible_l[i] > 0:
                        # The in-scan caps deferred the static skew check, so
                        # the filter passed nodes the scan then refused under
                        # the SAME pre-batch counts (feasible_static == 0):
                        # the pod is statically over-skew everywhere, not
                        # batch-contended — a terminal PodTopologySpread
                        # verdict (which preemption below may cure by
                        # evicting matching pods), never an endless
                        # BATCH_CAPACITY retry loop.
                        plugins = {"PodTopologySpread"}
                    # PostFilter (DefaultPreemption): defer the terminal
                    # verdict — a batched victim-candidate search may free
                    # capacity by evicting lower-priority pods. Gang members
                    # never preempt (group-level victim math is out of scope;
                    # plugins/preemption.py docstring).
                    if (self._preempt_enabled
                            and not qpi.pod.spec.pod_group):
                        preempt_rows.append(i)
                        preempt_plugins[i] = plugins
                        continue
                    self._handle_failure(
                        qpi, plugins,
                        f"0/{self.cache.node_count()} nodes are available: "
                        f"rejected by {sorted(plugins)}",
                        retryable=False)

        with span("resolve.assume", seq=inf.seq):
            if assume_items:
                missed = self.cache.account_bind_bulk(
                    assume_items, req_rows=eb.pf.requests[assume_rows],
                    expected_inc=assume_incs)
                if missed:
                    # The chosen node's cache row vanished between the cycle's
                    # snapshot and this assume (node deleted mid-cycle). Bind
                    # would commit the pod to a ghost node the model can never
                    # account — and if a same-named node later returned, the
                    # pod would silently distort its capacity AND its topology
                    # domain counts (observed as a hard-skew violation under
                    # node churn). Requeue instead; next cycle's snapshot has
                    # live nodes only.
                    n_ghost += len(missed)
                    dead_keys = set()
                    for m in missed:
                        pod, node_name = assume_items[m]
                        dead_keys.add(pod.key)
                        self._handle_failure(
                            batch[assume_rows[m]], {BATCH_CAPACITY},
                            f"chosen node {node_name} was deleted during the "
                            "scheduling cycle", retryable=True)
                    lost_rows.extend(assume_rows[m] for m in missed)
                    to_bind = [(q, n) for q, n in to_bind
                               if q.pod.key not in dead_keys]
                missed_set = set(missed) if missed else ()
                for j in range(len(assume_items)):
                    if j not in missed_set:
                        self._note_assumed(batch[assume_rows[j]])

        if lost_rows:
            with span("resolve.arbitrate", seq=inf.seq):
                # Post-assume staleness: the scan (and the host replay)
                # COUNTED the lost rows' admissions — assume misses and
                # synchronous permit rejections alike — so a later
                # same-batch placement may be legal only because of a
                # contribution that just vanished. Two consequences:
                #   * gang atomicity — a lost member's siblings must not
                #     bind at sub-quorum;
                #   * hard-spread exactness — re-arbitrate with the lost
                #     rows dead; a newly violating survivor is revoked
                #     (into the in-cycle repair pass when eligible).
                # Revocations go through _revoke_post_assume, which also
                # aborts an in-flight permit wait (non-bulk path); to_bind
                # has not been submitted yet, so dropped pairs never bind.
                from ..state.objects import CLAIM_UNUSED
                g_set = set(lost_rows)
                bind_keys = {q.pod.key for q, _ in to_bind}
                drop_keys: Set[str] = set()
                lost_gangs = {gang_key(batch[i].pod) for i in g_set
                              if batch[i].pod.spec.pod_group}
                if lost_gangs:
                    for j, qpi in enumerate(batch):
                        if (j in g_set or j in revoked or not assigned_l[j]
                                or gang_key(qpi.pod) not in lost_gangs):
                            continue
                        if self._revoke_post_assume(
                                qpi, {COSCHEDULING, BATCH_CAPACITY},
                                f"gang {qpi.pod.spec.pod_group} member lost "
                                "its placement during the scheduling cycle",
                                in_bind=qpi.pod.key in bind_keys):
                            drop_keys.add(qpi.pod.key)
                            revoked = revoked | {j}
                if sp is not None:
                    # re_rev includes gang siblings of any member it revokes
                    # (arbitrate_spread's internal gang-atomicity fixpoint)
                    re_rev = self._arbitrate_packed(
                        batch, assigned, eb, decision, sp,
                        dead=revoked | g_set)
                    for i in sorted(re_rev):
                        qpi = batch[i]
                        st = vol_memo.get(qpi.pod.key)
                        if (self.config.spread_repair_iters
                                and not qpi.pod.spec.pod_group
                                and qpi.pod.key in bind_keys
                                and not (st is not None
                                         and CLAIM_UNUSED in st[1])):
                            # same in-cycle repair offer the first-pass
                            # revocations get — no queue round-trip
                            self._unassume(qpi)
                            drop_keys.add(qpi.pod.key)
                            repair_rows.append(i)
                            revoked = revoked | {i}
                        elif self._revoke_post_assume(
                                qpi, {BATCH_CAPACITY}, _SPREAD_REVOKE_MSG,
                                in_bind=qpi.pod.key in bind_keys):
                            drop_keys.add(qpi.pod.key)
                            revoked = revoked | {i}
                if drop_keys:
                    to_bind = [(q, n) for q, n in to_bind
                               if q.pod.key not in drop_keys]

        n_repaired = 0
        if repair_rows:
            # In-cycle repair: with the survivors assumed, the refreshed
            # snapshot carries the committed counts — re-run the step on
            # the revoked rows so the next tranche places NOW rather than
            # after a queue round-trip + backoff per tranche.
            more_bind, leftover, n_repaired = self._repair_spread(
                batch, repair_rows, eb)
            to_bind.extend(more_bind)
            for i in leftover:
                self._handle_failure(batch[i], {BATCH_CAPACITY},
                                     _SPREAD_REVOKE_MSG, retryable=True)

        if preempt_rows:
            # AFTER assume accounting, against a FRESH snapshot: victim
            # sets must cover the preemptor's need against capacity as
            # it stands once this batch's survivors AND repair
            # placements are debited. decision.free_after would be
            # stale here — it debits pods the arbitration later revoked
            # and misses pods the repair loop re-placed elsewhere; the
            # cache's assumed state is the committed truth.
            cached = self._nf_static_device
            nf_p, names_p, sv_p, _incs_p = self.cache.snapshot_versioned(
                pad=self._node_pad,
                known_static=cached[0] if cached else None)
            nf_p = self._with_device_static(nf_p, sv_p, _incs_p.shape[0])
            won = self._try_preempt(
                batch, preempt_rows, eb, nf_p,
                self.cache.snapshot_assigned(pad=self._af_pad), names_p)
            for i in preempt_rows:
                if i not in won:
                    self._handle_failure(
                        batch[i], preempt_plugins[i],
                        f"0/{self.cache.node_count()} nodes are available: "
                        f"rejected by {sorted(preempt_plugins[i])}; "
                        "preemption found no candidates",
                        retryable=False)

        if to_bind:
            # One bulk commit for all permit-free pods: a single store-lock
            # acquisition via bind_pods instead of one executor task + CAS
            # per pod (at 10k pods/batch the per-pod path is 10k lock
            # round-trips the batch design exists to avoid). Still async so
            # the scheduling loop proceeds, like the reference's per-pod
            # binding goroutine (minisched.go:96-112).
            for q, _n in to_bind:
                self._note_detached(q.pod.key)
            self._binder.submit(self._bind_many, to_bind, inf.seq)

        inf.t_step = t_step
        inf.n_assigned = (int(assigned[:len(batch)].sum())
                          - sum(1 for i in revoked if assigned[i])
                          - n_ghost + n_repaired)
        # Padded step shapes (P, N, A) — the pad-efficiency audit trail
        # for the eighth-step buckets (encode/cache.step_bucket)
        inf.shapes = (int(eb.pf.valid.shape[0]),
                      int(nf.valid.shape[0]),
                      int(af.valid.shape[0]))

    def _commit_batch(self, inf: "_InflightBatch") -> None:
        """Flight-recorded wrapper: ``commit`` covers the flush + metric
        fold (on the commit worker's own trace lane in pipelined mode)."""
        with span("commit", seq=inf.seq, failures=len(inf.failures)):
            self._commit_batch_impl(inf)

    def _commit_batch_impl(self, inf: "_InflightBatch") -> None:
        """COMMIT: flush the deferred failure verdicts through the bulk
        machinery (one store transaction, one queue lock hold, one event
        payload for the whole tranche) and fold the cycle's metrics. Runs
        on the commit worker in pipelined mode — everything here is
        thread-safe against the scheduling thread's next prepare/resolve
        and against the binder pool."""
        inf.commit_t0 = time.perf_counter()
        if inf.failures:
            try:
                with span("commit.flush", pods=len(inf.failures)):
                    self._flush_failures(inf.failures)
            except FaultWorkerDeath:
                # Simulated worker death (faults.py commit:die): escapes
                # every guard so the supervisor's drain/restart path —
                # not the tranche-requeue fallback — handles it.
                raise
            except Exception:
                # A flush error (transient wire failure on a RemoteStore,
                # store teardown race) must not strand the tranche: the
                # pods are popped, so nothing else will ever requeue
                # them. Fall back to the synchronous loop's contract —
                # backoff-requeue every failed pod; status/events land on
                # the retry.
                log.exception("bulk failure flush failed; requeueing the "
                              "tranche with backoff")
                for qpi, _plugins, _msg, _retry in inf.failures:
                    self.queue.requeue_backoff(qpi)
        t_flush = time.perf_counter()
        inf.commit_t1 = t_flush
        batch, t_step = inf.batch, inf.t_step
        # commit_s keeps its historical meaning — everything after the
        # step fetch: arbitration + assume + repair + preemption (the
        # resolve tail) plus this flush.
        commit_s = (inf.t_resolved - t_step) + (t_flush - inf.commit_t0)
        # step_s keeps its sync-mode meaning — dispatch + device + fetch
        # only. In pipelined mode the next batch's queue gather runs
        # between dispatch and the fetch; that slice is inter-stage gap,
        # not device time (booking it as step_s would corrupt the
        # sync-vs-pipelined per-stage comparison). A loop-mode slot
        # books its tranche-window SHARE instead: its own stamps span
        # the whole fused dispatch, and booking the full window per
        # slot would count the tranche's device time depth times.
        if inf.step_share is not None:
            gather_gap = 0.0
            step_s = inf.step_share
        else:
            gather_gap = max(0.0, inf.t_fetch_start - inf.t_dispatch)
            step_s = (t_step - inf.t_encode) - gather_gap
        with self._metrics_lock:
            m = self._metrics
            m["batches"] += 1
            m["pods_seen"] += len(batch)
            m["pods_assigned"] += inf.n_assigned
            m["pods_failed"] += len(batch) - inf.n_assigned
            m["encode_s_total"] += inf.t_encode - inf.t0
            m["step_s_total"] += step_s
            m["step_dispatch_s_total"] += inf.t_dispatch - inf.t_encode
            # dispatch→fetch turnaround: the fetch slot of the gap
            # decomposition (booked here, where the window is known —
            # it cannot route through _book_gap's scheduling-thread
            # pending dict because commits may run on the worker).
            m["gap_s_total"] += gather_gap
            m["gap_fetch_s_total"] += gather_gap
            m["commit_s_total"] += commit_s
            m["shortlist_repairs"] += inf.sl_repairs
            m["shortlist_certified"] += max(0,
                                            len(batch) - inf.sl_repairs)
            # Scored-rows ledger: plugin-evaluation work this batch
            # paid (pod-row × node-row units) — the full step's P_pad·N.
            m["scored_rows_total"] += inf.scored_rows
            if inf.failures:
                # Encode-vs-flush overlap, booked HERE where the flush
                # window is known: the NEXT batch's prepare may take
                # either commit path, so _await_commit cannot see every
                # overlap. A still-encoding prepare (end None) is
                # clipped at this flush's end.
                w0, w1 = self._prep_window
                if w1 is None:
                    w1 = t_flush
                m["encode_overlap_s"] += max(
                    0.0, min(t_flush, w1) - max(inf.commit_t0, w0))
            if inf.seq > self._last_committed_seq:
                # Commits may finish out of batch order (inline
                # no-failure commits vs worker flushes); only the newest
                # batch writes the last_* diagnostics.
                self._last_committed_seq = inf.seq
                m["last_batch_size"] = len(batch)
                sizes = m.setdefault("batch_sizes", [])
                if len(sizes) < 16:  # bounded diagnostic trail
                    sizes.append(len(batch))
                m["last_encode_s"] = inf.t_encode - inf.t0
                m["last_step_s"] = step_s
                m["last_commit_s"] = commit_s
                m["last_shapes"] = inf.shapes
                m["last_shortlist_repairs"] = int(inf.sl_repairs)
                m["last_scored_rows"] = int(inf.scored_rows)

    def _flush_failures(self, items: List[tuple]) -> None:
        """Apply a cycle's deferred failure verdicts in bulk — the
        vectorized twin of _handle_failure's per-pod body: one
        FailedScheduling event payload, one store transaction for the
        status writes (per-pod get/update fallback when the store lacks
        the bulk verb — RemoteStore), one queue lock hold for the
        requeues. Pods deleted mid-flight are forgotten, exactly like
        the per-pod NotFound path."""
        FAULTS.hit("commit")  # fault gate: commit-worker failure flush
        self.broadcaster.failed_scheduling_many(
            [(qpi.pod.key, qpi.pod.metadata.namespace, msg)
             for qpi, _plugins, msg, _retry in items])
        fail_bulk = getattr(self.store, "fail_pods", None)
        missing: Set[str] = set()
        if fail_bulk is not None:
            missing = set(fail_bulk(
                [(qpi.pod.key, plugins, msg)
                 for qpi, plugins, msg, _retry in items]))
        else:
            for qpi, plugins, msg, _retry in items:
                try:
                    fresh = self.store.get("Pod", qpi.pod.key)
                    if not fresh.spec.node_name:
                        fresh.status.unschedulable_plugins = sorted(plugins)
                        fresh.status.message = msg
                        self.store.update(fresh)
                        qpi.pod = fresh
                except NotFoundError:
                    missing.add(qpi.pod.key)
        retryable: List[QueuedPodInfo] = []
        unsched: List[tuple] = []
        for qpi, plugins, _msg, retry in items:
            if qpi.pod.key in missing:
                self.queue.forget(qpi.pod.key)
                self.drop_nomination(qpi.pod.key)
            elif retry:
                retryable.append(qpi)
            else:
                unsched.append((qpi, plugins))
        if retryable or unsched:
            self.queue.requeue_failures(retryable, unsched)

    # ---- multi-chip step (SchedulerConfig.mesh) --------------------------

    def _mesh_step(self, eb, nf, af):
        """The sharded scheduling step, built once from the first batch's
        pytree templates (sharding specs are rank-based, so every later
        shape bucket reuses the same jitted function and just retraces).
        ``config.assignment`` picks the sharded assignment stage:
        "greedy" (the engine default) = the chunked-gather scan,
        bit-identical to the single-device engine (tests/test_parallel.py
        asserts the e2e equality); "auction" = the priority-tiered
        auction, the faster opt-in for throughput configs
        (SHARDED_BENCH.json: 1.30x single-device vs 4.6x for the sharded
        greedy scan)."""
        if self._sharded_step is None:
            from ..parallel.sharded import build_sharded_step

            self._sharded_step = build_sharded_step(
                self.plugin_set, self._mesh, eb, nf, af,
                explain=self.config.explain,
                assignment=self.config.assignment)
        return self._sharded_step

    # ---- node-axis sampling (percentage_of_nodes_to_score) --------------

    def _arbitrate_packed(self, batch, assigned, eb, decision, sp,
                          dead: Set[int]) -> Set[int]:
        """arbitrate_spread over the packed (2P+2, G) spread fetch — the
        ONE place that decodes _pack_spread's row layout (pre | dom |
        min | scan_groups). The (G,D) exact tables stay lazy: only a
        batch with hard rows the in-scan caps did not enforce pays the
        transfer."""
        sp_p = decision.spread_pre.shape[0]

        def exact_tables():
            cd = np.asarray(decision.spread_cdom)
            de = np.asarray(decision.spread_dexist)
            self._count_fetch(cd.nbytes + de.nbytes)
            return cd, de

        anti_revoked: Set[int] = set()
        revoked = arbitrate_spread(
            batch, assigned, eb.pf, eb.gf,
            sp[:sp_p], sp[sp_p:2 * sp_p].astype(np.int32), sp[2 * sp_p],
            dead=dead, anti_enabled=self._anti_enabled,
            exact_tables=exact_tables,
            scan_enforced=sp[2 * sp_p + 1].astype(bool),
            anti_revoked=anti_revoked)
        if anti_revoked:
            with self._metrics_lock:
                self._metrics["anti_revoked_total"] += len(anti_revoked)
        return revoked

    def _node_pad(self, hw: int) -> int:
        """Node-axis pad for this engine's step shapes: the eighth-step
        bucket of the cache's row high-water instead of the pow2 capacity
        (50k nodes: 53248 vs 65536 — every (P,N) pass in the step is 23%
        cheaper for free). High-water is monotonic, so the pad — and with
        it the step's compile cache and the device-resident static-leaf
        cache — only moves when the cluster actually grows. Passed as the
        snapshot's ``pad`` CALLABLE so the bucket is resolved from the
        high-water mark under the snapshot lock — a stale read could
        otherwise race a concurrent node add past the pad."""
        return step_bucket(max(hw, 1), self.config.node_bucket_min)

    def _af_pad(self, hw: int) -> int:
        """Assigned-corpus pad from ITS high-water mark — the big win is
        not snapshotting/matching the cache's full pow2 capacity when the
        corpus is small (an empty corpus used to memcpy a 65536-row
        snapshot every batch at 50k nodes). Buckets stay pow2, not
        eighth-step: the corpus only GROWS in steady state, every bucket
        crossing recompiles the step, and the (G,A)/(Pf,A) terms are too
        cheap for the tighter ladder to pay for 3× the compile points."""
        return bucket_for(max(hw, 1), 16)

    def _sampled_step(self, n_pad: int, batch_len: int,
                      full_axis: bool):
        """(step_fn, K) for this batch, or (None, None) when sampling
        doesn't apply. ``full_axis`` forces the full node set: gangs
        (quorum must be judged against one consistent node set — a
        member failing only because the sample missed its nodes would
        wrongly reject the whole gang) and hard-spread batches (the
        in-scan domain caps only run unsampled; a sampled hard batch
        would fall back to host replay + the (G,D) table fetch). Explain
        mode disables sampling too (per-node annotation columns would
        misalign with the full name table)."""
        cfg = self.config
        if cfg.explain or full_axis:
            return None, None
        # Brownout (overload level 3) pulls the dial down to
        # ``brownout_pct`` — the percentageOfNodesToScore knob engaged
        # as a load-shed actuation instead of a static setting.
        pct = self._overload.effective_pct_nodes(
            cfg.percentage_of_nodes_to_score)
        if pct >= 100:
            return None, None
        n_real = self.cache.node_count()
        if n_real < 2 * cfg.min_sample_nodes:
            return None, None
        if pct <= 0:  # auto: upstream's adaptive formula
            pct = max(5, 50 - n_real // 125)
        if pct >= 100:
            return None, None
        want = max(cfg.min_sample_nodes, (n_real * pct) // 100,
                   2 * batch_len)
        k = bucket_for(want, cfg.node_bucket_min)
        if k >= n_pad // 2:
            # A sample over half the cluster saves less than the gather +
            # residual machinery costs (measured: a 10k-pod batch at 50k
            # nodes sampled K=32768 ran SLOWER than the full axis) —
            # sampling exists for small batches against huge clusters.
            return None, None
        return build_step(self.plugin_set, explain=False,
                          assignment=cfg.assignment, sample_nodes=k,
                          shortlist=self._shortlist_k), k

    def _run_residual(self, eb, nf, af, key, rows, decision,
                      chosen, assigned, gang_rejected, feasible,
                      feasible_static, rejects, sp) -> None:
        """Full-axis re-evaluation of sampled-out pods, merged in place.

        The residual sub-batch reuses the batch's group tables (same gf/
        naf, so group ids and spread columns stay aligned) with gangs
        stripped (sampling is disabled for gang batches), and sees the
        cluster's free capacity AFTER the sampled assignments
        (decision.free_after is full-size under sampling)."""
        n_res = len(rows)
        eb2, P2 = self._slice_eb(eb, rows)
        free2 = np.asarray(decision.free_after)
        self._count_fetch(free2.nbytes)
        nf2 = nf._replace(free=free2)
        d2: Decision = self._step(eb2, nf2, af,
                                  jax.random.fold_in(key, 0x5e5))
        (ch2, as2, gr2, fc2, fs2, rj2, rep2) = self._fetch_decision(
            self._pack_dec(d2), P2, d2.reject_counts.shape[0], d2)
        if self._track is not None:
            self._track.sl_repairs += int(rep2[:n_res].sum())
        chosen[rows] = ch2[:n_res]
        assigned[rows] = as2[:n_res]
        gang_rejected[rows] = gr2[:n_res]
        feasible[rows] = fc2[:n_res]
        feasible_static[rows] = fs2[:n_res]
        rejects[:, rows] = rj2[:, :n_res]
        if sp is not None:
            # Only the per-pod pre/dom rows merge; the batch's
            # spread_min/scan_groups rows stay as the MAIN step computed
            # them. That is sound only because hard-spread batches never
            # sample (_sampled_step full_axis invariant) — a residual
            # exists only for soft-spread batches, where min/scan rows
            # are advisory.
            assert not decision.scan_groups.any(), \
                "residual merge on a hard-spread (scan-enforced) batch"
            sp2 = self._fetch_spread(self._spread_payload(d2))
            sp_p = decision.spread_pre.shape[0]
            if d2.spread_pre.shape[0]:
                sp[rows] = sp2[:P2][:n_res]
                sp[sp_p + rows] = sp2[P2:2 * P2][:n_res]

    def _repair_spread(self, batch, rows: List[int], eb):
        """In-cycle repair of topology-revoked pods → (bind pairs,
        leftover rows, admitted count — includes permit-parked pods,
        which bind via their own async cycle).

        Each iteration re-snapshots node/assigned state (the survivors
        and earlier repair tranches are assumed, so the step's filter and
        the exact arbitration see the committed counts), re-runs the
        step on the remaining rows, arbitrates the sub-batch, and
        assumes the admitted pods. Rows the step finds infeasible stay
        in the loop while the iteration made progress — a zone at its
        skew cap re-opens as other domains catch up and the min rises —
        and the loop stops on no-progress or after
        ``spread_repair_iters`` iterations; leftovers take the normal
        requeue/backoff path. Explain mode: repair outcomes are not
        re-recorded — a repaired pod's annotations reflect the cycle's
        first evaluation (documented trade; the recorder is off the
        decision path)."""
        rows = list(rows)
        out_bind: List[tuple] = []
        n_admitted = 0
        step_fn = (self._sharded_step if self._mesh is not None
                   else self._step)
        bulk = not self.plugin_set.permit_plugins
        for _ in range(self.config.spread_repair_iters):
            if not rows or step_fn is None:
                break
            cached = self._nf_static_device
            nf, names, static_v, row_incs = self.cache.snapshot_versioned(
                pad=self._node_pad,
                known_static=cached[0] if cached else None)
            af = self.cache.snapshot_assigned(pad=self._af_pad)
            nf = self._with_device_static(nf, static_v,
                                          row_incs.shape[0])
            if self._nominations:
                reserved = self._nomination_debits(
                    {batch[i].pod.key for i in rows}, names, nf)
                if reserved is not None:
                    nf = nf._replace(free=nf.free - reserved)
            # Pad to the MAIN batch's bucket: repair tranches shrink
            # through many sizes, and a per-tranche pow2 ladder would pay
            # one fresh XLA compile (~7 s for the topology profile) per
            # size; the batch's own bucket is already compiled, so repair
            # costs only device time (the padded rows are invalid).
            eb2, _P2 = self._slice_eb(eb, np.asarray(rows, dtype=np.int64),
                                      bucket=eb.pf.valid.shape[0])
            self._step_counter += 1
            d2 = step_fn(eb2, nf, af,
                         jax.random.fold_in(self._key, self._step_counter))
            (chosen2, assigned2, _gr2, _fc2, _fs2, _rj2, rep2) = (
                self._fetch_decision(self._pack_dec(d2),
                                     eb2.pf.valid.shape[0],
                                     d2.reject_counts.shape[0], d2))
            if self._track is not None:
                self._track.sl_repairs += int(rep2[:len(rows)].sum())
            n_r = len(rows)
            sub = [batch[i] for i in rows]
            sp2 = self._fetch_spread(self._spread_payload(d2))
            rev2 = self._arbitrate_packed(
                sub, assigned2, eb2, d2, sp2, dead=set())
            items, req_rows, next_rows = [], [], []
            iter_incs: List[int] = []  # snapshot incarnation per item
            iter_rows: List[int] = []  # batch row per ``items`` entry
            iter_bind: List[tuple] = []
            ghost_js: List[int] = []   # sub-rows lost to assume misses
            for j in range(n_r):
                i = rows[j]
                if assigned2[j] and j not in rev2:
                    # Counted admitted regardless of the permit outcome —
                    # the main cycle's n_assigned counts permit-parked
                    # pods the same way, so the two paths agree.
                    n_admitted += 1
                    node_name = names[int(chosen2[j])]
                    if self._prov_batch is not None:
                        self._prov_stamp(batch[i], node_name,
                                         repaired=bool(rep2[j]),
                                         spread_repaired=True)
                    if bulk:
                        items.append((batch[i].pod, node_name))
                        req_rows.append(j)
                        iter_incs.append(int(row_incs[int(chosen2[j])]))
                        iter_rows.append(i)
                        iter_bind.append((batch[i], node_name))
                    else:
                        pair, ghost, rej = self._start_binding_cycle(
                            batch[i], node_name,
                            expected_inc=int(row_incs[int(chosen2[j])]))
                        if ghost:
                            # not placed at all — the row goes back into
                            # the loop like a bulk-path miss
                            n_admitted -= 1
                            next_rows.append(i)
                            ghost_js.append(j)
                        elif rej:
                            # synchronous permit rejection: terminal for
                            # the pod (handled inside the cycle call) but
                            # its scan-counted admission vanished — dead
                            # for this iteration's re-arbitration.
                            # (Still counted admitted, matching the main
                            # cycle's accounting for permit outcomes.)
                            ghost_js.append(j)
                        elif pair is not None:
                            out_bind.append(pair)
                else:
                    # still contended (rev2) or currently infeasible —
                    # both can succeed next iteration once this
                    # iteration's admissions raise the domain min
                    next_rows.append(i)
            if items:
                missed = self.cache.account_bind_bulk(
                    items, req_rows=eb2.pf.requests[req_rows],
                    expected_inc=iter_incs)
                if missed:
                    # Chosen node deleted mid-cycle (see the main cycle's
                    # assume-miss path): not accounted, must not bind —
                    # push back into the loop; the next iteration's fresh
                    # snapshot no longer offers the dead node.
                    n_admitted -= len(missed)
                    dead = set(missed)  # membership filter below
                    next_rows.extend(iter_rows[m] for m in missed)
                    ghost_js.extend(req_rows[m] for m in missed)
                    iter_bind = [p for m, p in enumerate(iter_bind)
                                 if m not in dead]
                missed_set = set(missed) if missed else ()
                for m in range(len(items)):
                    if m not in missed_set:
                        self._note_assumed(batch[iter_rows[m]])
            if ghost_js:
                # Same assume-miss staleness as the main cycle: this
                # iteration's walk counted the ghosts' admissions, so a
                # surviving placement may be legal only because of them.
                # Re-arbitrate with the ghosts dead; newly violating
                # survivors are unassumed and re-loop (their bind pairs
                # are still unsubmitted), permit-waiting ones are
                # revoked through their async continuation.
                re3 = self._arbitrate_packed(
                    sub, assigned2, eb2, d2, sp2,
                    dead=rev2 | set(ghost_js)) - rev2 - set(ghost_js)
                if re3:
                    pair_keys = ({p[0].pod.key for p in iter_bind}
                                 | {p[0].pod.key for p in out_bind})
                    kill: Set[str] = set()
                    for j in sorted(re3):
                        qpi = batch[rows[j]]
                        k = qpi.pod.key
                        if k in pair_keys:
                            self._unassume(qpi)
                            kill.add(k)
                            next_rows.append(rows[j])
                            n_admitted -= 1
                        elif self._revoke_post_assume(
                                qpi, {BATCH_CAPACITY},
                                _SPREAD_REVOKE_MSG, in_bind=False):
                            n_admitted -= 1
                    if kill:
                        iter_bind = [p for p in iter_bind
                                     if p[0].pod.key not in kill]
                        out_bind = [p for p in out_bind
                                    if p[0].pod.key not in kill]
            out_bind.extend(iter_bind)
            rows = next_rows
            if len(next_rows) == n_r:  # no progress; stop burning steps
                break
        return out_bind, rows, n_admitted

    def _slice_eb(self, eb, rows, bucket: Optional[int] = None):
        """(eb_sub, P2): row-sliced pod features padded to a fresh bucket
        (or the caller-pinned ``bucket``), with the batch's group tables
        (gf/naf) SHARED so group ids stay aligned, and gangs stripped
        (callers — the sampling residual pass, preemption, and spread
        repair — exclude gang pods by construction)."""
        from ..encode.features import GangFeatures

        n = len(rows)
        P2 = bucket or bucket_for(n, self.config.pod_bucket_min)

        def take(a):
            a = np.asarray(a)
            out = np.zeros((P2,) + a.shape[1:], dtype=a.dtype)
            out[:n] = a[rows]
            return out

        pf2 = type(eb.pf)(*[take(getattr(eb.pf, f))
                            for f in eb.pf._fields])
        gang2 = GangFeatures(
            group=np.full(P2, -1, dtype=np.int32),
            min_count=np.asarray(eb.gang.min_count))
        return eb._replace(pf=pf2, gang=gang2), P2

    # ---- preemption (upstream DefaultPreemption PostFilter) -------------

    def _try_preempt(self, batch, rows, eb, nf, af, names) -> Set[int]:
        """Batched candidate search (ops/preempt.py) + host-side minimal
        victim commit for terminally-unschedulable pods. Returns the rows
        successfully queued behind a preemption (victims evicted,
        nominated_node recorded, preemptor requeued retryably)."""
        from ..ops.preempt import build_preempt_op

        op = build_preempt_op(self.plugin_set, cfg=self.cache.cfg)
        eb2, _p2 = self._slice_eb(eb, rows)
        chosen_d, ok_d, _cnt, sev_d = op(eb2, nf, af)
        chosen = np.asarray(chosen_d)
        ok = np.asarray(ok_d)
        spread_evict = np.asarray(sev_d)

        won: Set[int] = set()
        taken: Set[str] = set()  # victims already evicted this cycle
        # One live PDB accounting pass shared by every preemptor of the
        # cycle (earlier evictions debit the budgets later ones see).
        pdb_state = self._pdb_state()
        for j, i in enumerate(rows):
            if not ok[j]:
                continue
            qpi = batch[i]
            node_name = names[int(chosen[j])]
            if node_name is None:
                continue
            # Re-check the preemptor BEFORE any eviction: a pod deleted
            # (or bound by a competing scheduler) since the step snapshot
            # must not cost real workloads their capacity (upstream
            # re-verifies preemptor freshness the same way).
            try:
                fresh = self.store.get("Pod", qpi.pod.key)
            except NotFoundError:
                self.queue.forget(qpi.pod.key)
                self.drop_nomination(qpi.pod.key)
                won.add(i)  # nothing further to do for this row
                continue
            if fresh.spec.node_name:
                self.drop_nomination(qpi.pod.key)
                won.add(i)  # already bound elsewhere — no verdict needed
                continue
            # Rounds cap: a cure the host could not honor (unevictable
            # repeller, device hashed-match broader than exact host
            # semantics) would otherwise evict-and-retry forever; after
            # _PREEMPT_MAX_ROUNDS wins without a bind, the terminal
            # verdict stands.
            if (self._preempt_rounds.get(qpi.pod.key, 0)
                    >= self._PREEMPT_MAX_ROUNDS):
                log.warning("preemption: %s exceeded %d rounds without "
                            "binding; giving up", qpi.pod.key,
                            self._PREEMPT_MAX_ROUNDS)
                self.drop_nomination(qpi.pod.key)
                continue
            victims = self._select_victims(qpi.pod, node_name, taken,
                                           pdb_state,
                                           spread_evict=spread_evict[j])
            if victims is None:
                continue  # candidates raced away — terminal verdict stands
            if not victims:
                # The node now fits outright (state moved since the
                # step): no eviction needed, just retry promptly.
                self._handle_failure(
                    qpi, {BATCH_CAPACITY},
                    f"capacity freed on {node_name} since the scheduling "
                    "attempt; retrying", retryable=True)
                won.add(i)
                continue
            for vk in victims:
                try:
                    self.store.delete("Pod", vk)
                except NotFoundError:
                    pass
                else:
                    # Account the eviction NOW (idempotent with the
                    # informer's later delete-event unbind): a second
                    # preemptor in this same cycle must see the freed
                    # capacity, or the nomination debit double-counts
                    # against stale free and over-evicts.
                    self.cache.account_unbind(vk)
                taken.add(vk)
                self.broadcaster.record(
                    involved=f"Pod:{vk}", reason="Preempted",
                    message=f"Preempted by {qpi.pod.key} on {node_name}",
                    type_="Warning",
                    namespace=vk.split("/", 1)[0])
            try:
                fresh.status.nominated_node_name = node_name
                self.store.update(fresh)
                qpi.pod = fresh
            except (NotFoundError, ConflictError):
                pass
            # Reserve the freed capacity for the preemptor until it
            # binds or the TTL lapses (upstream nominated-pod handling).
            from ..encode import features as F2
            from ..state.objects import pod_requests as _preq

            with self._nom_lock:
                self._nominations[qpi.pod.key] = (
                    node_name, F2.resources_vector(_preq(qpi.pod)),
                    time.monotonic() + self._NOMINATION_TTL_S)
            self._handle_failure(
                qpi, {"DefaultPreemption"},
                f"preempted {len(victims)} lower-priority pod(s) on "
                f"{node_name}; waiting for the freed capacity",
                retryable=True)
            log.info("preemption: %s evicted %d pod(s) on %s",
                     qpi.pod.key, len(victims), node_name)
            self._preempt_rounds[qpi.pod.key] = (
                self._preempt_rounds.get(qpi.pod.key, 0) + 1)
            won.add(i)
        return won

    _NOMINATION_TTL_S = 60.0
    _PREEMPT_MAX_ROUNDS = 3

    def drop_nomination(self, pod_key: str) -> None:
        """Release a preemptor's capacity reservation (pod bound, deleted,
        or otherwise gone) — the informer's pod-delete path and the
        failure funnel call this so a vanished preemptor cannot pin the
        freed capacity for the rest of the TTL."""
        if self._nominations:
            with self._nom_lock:
                self._nominations.pop(pod_key, None)
        self._preempt_rounds.pop(pod_key, None)

    def _nomination_debits(self, batch_keys: Set[str], names, nf):
        """(N,R) capacity reserved by OUT-OF-BATCH nominees (expired and
        orphaned nominations pruned), or None when nothing to debit."""
        now = time.monotonic()
        debits = None
        with self._nom_lock:
            drop = []
            row_of = None
            for key, (node, req, exp) in self._nominations.items():
                if exp < now:
                    drop.append(key)
                    continue
                if key in batch_keys:
                    continue  # the nominee itself sees its reservation
                if row_of is None:
                    row_of = {n: j for j, n in enumerate(names)
                              if n is not None}
                j = row_of.get(node)
                if j is None:  # nominated node is gone
                    drop.append(key)
                    continue
                if debits is None:
                    # Explicit host allocation: nf.free may be the
                    # device-carried array (nomination-window carry) and
                    # zeros_like would round-trip it through the host.
                    debits = np.zeros(
                        (int(nf.free.shape[0]), int(nf.free.shape[1])),
                        dtype=np.float32)
                debits[j] += req
            for k in drop:
                del self._nominations[k]
        return debits

    def _pdb_state(self) -> Optional[List[list]]:
        """Live PodDisruptionBudget accounting for one preemption pass:
        ``[namespace, selector, allowed_disruptions]`` rows, where
        allowed = currently-bound matching pods − min_available (the
        upstream disruptionsAllowed computed from live state — the
        simulator has no PDB status controller). None when no PDBs
        exist, so the common no-PDB path costs nothing."""
        pdbs = self.store.list("PodDisruptionBudget")
        if not pdbs:
            return None
        counts = [0] * len(pdbs)

        def visit(p):
            if not p.spec.node_name:
                return
            for i, b in enumerate(pdbs):
                if (p.metadata.namespace == b.metadata.namespace
                        and (b.spec.selector is None
                             or b.spec.selector.matches(p.metadata.labels))):
                    counts[i] += 1

        # Read-only visitor: counting labels over a 100k-pod corpus via
        # list() would deep-copy every object tree per preemption cycle.
        # RemoteStore (engine-over-the-wire) has no visitor; its list()
        # objects are already private decoded copies.
        fe = getattr(self.store, "for_each", None)
        if fe is not None:
            fe("Pod", visit)
        else:
            for p in self.store.list("Pod"):
                visit(p)
        return [[b.metadata.namespace, b.spec.selector,
                 c - int(b.spec.min_available)]
                for b, c in zip(pdbs, counts)]

    def _select_victims(self, pod, node_name: str, taken: Set[str],
                        pdb_state: Optional[List[list]] = None,
                        spread_evict=None) -> Optional[List[str]]:
        """Victim set on ``node_name``: the MANDATORY topology victims
        (pods whose presence rejects the preemptor — its own required
        anti-affinity matches, the symmetric repelling-term owners, and
        ``spread_evict[c]`` matching pods per over-skew spread slot),
        then lowest-priority-first capacity top-up until the node's free
        vector covers the preemptor's request on every axis (upstream's
        order). None when the candidates no longer suffice (state raced
        since the device search) or a mandatory victim is unavailable.

        PodDisruptionBudgets (upstream policy/v1): a victim whose
        eviction would drop a matching budget below min_available is
        skipped in the first pass and permitted only when no
        non-violating victim set suffices — upstream DefaultPreemption's
        minimize-violations ordering (violating victims rank last but
        preemption is not forbidden outright; a PDB-protected MANDATORY
        victim therefore fails pass 1 outright). On success the shared
        ``pdb_state`` rows are debited so later preemptors in the SAME
        cycle see the budget the earlier evictions consumed."""
        from ..encode import features as F
        from ..state.objects import pod_requests

        free0 = self.cache.free_of(node_name)
        if free0 is None:
            return None
        # Capacity reserved by OTHER pods' nominations on this node is
        # not available to this preemptor — sizing victims against raw
        # free would double-book the node (and a node that only "fits"
        # because of someone else's reservation must still evict).
        with self._nom_lock:
            now = time.monotonic()
            for k, (n2, req2, exp) in self._nominations.items():
                if n2 == node_name and k != pod.key and exp >= now:
                    free0 = free0 - req2
        need = F.resources_vector(pod_requests(pod))
        cands = [(k, r) for k, r, _p in self.cache.victims_below(
            node_name, pod.spec.priority) if k not in taken]

        anti = (pod.spec.affinity.pod_anti_affinity.required
                if (pod.spec.affinity
                    and pod.spec.affinity.pod_anti_affinity) else [])
        spread_slots = []  # (constraint, count) with count > 0
        if spread_evict is not None:
            cons = pod.spec.topology_spread_constraints
            for c, e in enumerate(np.asarray(spread_evict).tolist()):
                if e > 0 and c < len(cons):
                    spread_slots.append((cons[c], int(np.ceil(e))))

        req_of = dict(cands)

        # Candidate pod identity (namespace, labels) fetched ONCE — not
        # per pass per candidate; store.get deep-copies the object tree.
        # The anti-affinity cure check needs identity for EVERY bound pod
        # on the node, not just the evictable pool: an unevictable
        # repeller (gang member, priority race, a device/host selector-
        # semantics gap) must fail the cure closed, never be skipped.
        meta: Dict[str, tuple] = {}
        meta_keys: List[str] = [k for k, _ in cands]
        if anti:
            seen = set(meta_keys)
            meta_keys += [k for k in self.cache.bound_keys_on(node_name)
                          if k not in seen and k not in taken]
        if pdb_state or anti or spread_slots:
            for key in meta_keys:
                try:
                    vp = self.store.get("Pod", key)
                except NotFoundError:
                    continue
                meta[key] = (vp.metadata.namespace, vp.metadata.labels)

        # Mandatory topology victims (preemption-curable rejections —
        # ops/preempt.py verified curability against the step snapshot;
        # unavailable mandatory victims here mean the state raced, a
        # repeller is unevictable, or the device's hashed match was
        # broader than the exact host semantics → None, no speculative
        # eviction).
        mandatory: List[str] = []
        mset: Set[str] = set()

        def _mand(key: str) -> bool:
            if key in mset:
                return True
            if key in req_of:
                mset.add(key)
                mandatory.append(key)
                return True
            return False  # not an eligible victim (anymore)

        pod_ns = pod.metadata.namespace
        for term in anti:
            term_ns = set(term.namespaces) if term.namespaces else {pod_ns}
            for key in meta_keys:
                m = meta.get(key)
                if m is None or m[0] not in term_ns:
                    continue
                if (term.label_selector is None
                        or term.label_selector.matches(m[1])):
                    if not _mand(key):
                        return None
        for owner in self.cache.repelling_owners_on(node_name, pod):
            if owner not in taken and not _mand(owner):
                return None
        for tsc, count in spread_slots:
            got = sum(1 for key in mset
                      if (m := meta.get(key)) is not None
                      and m[0] == pod_ns
                      and (tsc.label_selector is None
                           or tsc.label_selector.matches(m[1])))
            for key, _req in cands:  # lowest priority first
                if got >= count:
                    break
                if key in mset:
                    continue
                m = meta.get(key)
                if (m is not None and m[0] == pod_ns
                        and (tsc.label_selector is None
                             or tsc.label_selector.matches(m[1]))):
                    if _mand(key):
                        got += 1
            if got < count:
                return None  # not enough matching victims anymore

        def attempt(allow_violations: bool):
            acc = free0
            victims: List[str] = []
            budgets = [list(b) for b in (pdb_state or [])]
            deferred: List[tuple] = []
            # Mandatory victims first — they are the cure, not a
            # capacity choice, so the fits-already early-exit below must
            # never skip them. A PDB-protected mandatory victim fails
            # pass 1 outright (there is no alternative victim).
            for key in mandatory:
                if budgets:
                    m = meta.get(key)
                    hit = ([b for b in budgets
                            if b[0] == m[0]
                            and (b[1] is None or b[1].matches(m[1]))]
                           if m is not None else [])
                    if any(b[2] <= 0 for b in hit) and not allow_violations:
                        return None
                    for b in hit:
                        b[2] -= 1
                acc = acc + req_of[key]
                victims.append(key)
            for key, req in cands:
                if key in mset:
                    continue
                if np.all(acc >= need):
                    break
                if budgets:
                    m = meta.get(key)
                    if m is None:
                        continue
                    hit = [b for b in budgets
                           if b[0] == m[0]
                           and (b[1] is None or b[1].matches(m[1]))]
                    if any(b[2] <= 0 for b in hit):
                        if allow_violations:
                            # violating victims rank LAST (upstream's
                            # minimize-violations order): taken below
                            # only if the non-violating set is short
                            deferred.append((key, req, hit))
                        continue
                    for b in hit:
                        b[2] -= 1
                acc = acc + req
                victims.append(key)
            for key, req, hit in deferred:
                if np.all(acc >= need):
                    break
                for b in hit:
                    b[2] -= 1
                acc = acc + req
                victims.append(key)
            return (victims, budgets) if np.all(acc >= need) else None

        got = attempt(False)
        if got is None and pdb_state:
            got = attempt(True)
        if got is None:
            return None
        victims, budgets = got
        if pdb_state is not None:
            for row, new in zip(pdb_state, budgets):
                row[2] = new[2]
        return victims

    # Node lifecycle (informer thread) lives on the shared cluster state
    # (engine/clusterstate.py) — one cache, one re-adoption table, all
    # profile engines.

    # NodeFeatures leaves that change only on node events / topology
    # refresh — derived from the cache's authoritative dynamic list so the
    # two sides of the elision protocol can never disagree.
    _STATIC_NF_FIELDS = tuple(
        f for f in NodeFeatures._fields
        if f not in NodeFeatureCache.DYNAMIC_NF_FIELDS)

    def _with_device_static(self, nf, static_version: int, pad: int):
        """Swap the static node-feature leaves for device-resident copies
        cached per (static_version, pad). The per-batch host→device
        transfer then carries only free/used_ports (~a few MB) instead of
        the full ~tens-of-MB snapshot, whose upload would otherwise be
        a fixed cost of every engine step. (With dynamic
        residency live — _DeviceResidency — even those leaves stay on
        device and only sparse corrections move.)

        ``pad`` is the snapshot's resolved node pad (the incarnation
        column's length — reliable even when every array leaf was
        elided). On a cache hit the snapshot's static leaves are None
        (the cache elided their host copies —
        snapshot_versioned(known_static=...)); on a miss they are real
        arrays to upload. The leaves can never be None on a miss: the
        cache elides only when the caller-supplied key equals the key
        computed here."""
        key = (static_version, pad)
        cached = self._nf_static_device
        if cached is None or cached[0] != key:
            with span("h2d.static", static_version=static_version,
                      pad=pad):
                leaves = {name: jax.device_put(getattr(nf, name),
                                               self._nf_sharding(name))
                          for name in self._STATIC_NF_FIELDS}
            self._nf_static_device = cached = (key, leaves)
            self._count_h2d(sum(getattr(nf, name).nbytes
                                for name in self._STATIC_NF_FIELDS))
        return nf._replace(**cached[1])

    def _nf_sharding(self, name: str):
        """Placement for a device-resident node-feature leaf (static or
        dynamic): the mesh's canonical node-axis sharding in multi-chip
        mode (so the resident copy already matches the sharded step's
        in_shardings — no per-batch reshard), None (default device)
        otherwise."""
        if self._mesh is None:
            return None
        from ..parallel.mesh import leaf_sharding

        return leaf_sharding(self._mesh, name)

    def metrics(self) -> Dict[str, float]:
        """Cumulative and last-batch scheduling metrics plus current queue
        depths — the timing observability the reference lacks entirely
        (SURVEY §5: klog lines only)."""
        with self._metrics_lock:
            out = dict(self._metrics)
            if "batch_sizes" in out:
                # dict() is shallow; the live list must not escape the lock
                out["batch_sizes"] = list(out["batch_sizes"])
        out.update({f"queue_{k}": v for k, v in self.queue.stats().items()})
        out["waiting_pods"] = len(self.waiting_pods)
        # Per-pod lifecycle latency histograms (obs.Histogram snapshots:
        # bounds/counts/sum/count). Non-numeric by design — the service
        # layer surfaces them through metrics_histograms() for the
        # apiserver's native Prometheus histogram exposition, and bench
        # derives p50/p95/p99 from the counts (obs.hist_quantile), not
        # from sampled windows.
        out["histograms"] = {name: h.snapshot()
                             for name, h in self._hists.items()}
        # Shortlist-compressed arbitration gauge: the active top-K width
        # (0 = off — knob, auction/mesh gate, or a certification desync
        # reverted the engine to the full-width scan).
        out["shortlist_width"] = int(self._shortlist_k or 0)
        # Persistent device loop gauges: the ring depth the NEXT tranche
        # would use (0 = loop disabled/ineligible; the overload tuner
        # steps it down under ``tuned``).
        out["loop_depth_effective"] = (self._effective_loop_depth()
                                       if self._loop_enabled else 0)
        # Existing-pod anti-affinity plane: live anti classes in the
        # cache, and the padded class-slot width of the last batch
        # encoded (0 = no pod of it was repelled: the filter compiled
        # without the plane).
        out["anti_classes"] = self.cache.anti_class_count()
        out["anti_class_slots"] = self._anti_class_slots
        out["compile_cache_dir"] = self._compile_cache_dir
        # Where pods wait before the queue: seconds the informer thread
        # spent delivering bursts, seconds callers waited for the store
        # lock and how often they took it while the flight recorder was
        # armed (an in-process store only; a RemoteStore has no such
        # lock), and the process's collection pauses (all of them, and the
        # full collections' count and pause apart).
        out["informer_busy_s_total"] = (
            self._shared.informer_factory.busy_s_total)
        lock_wait = getattr(self.store, "lock_wait_s_total", None)
        if callable(lock_wait):
            out["store_lock_wait_s_total"] = lock_wait()
            out["store_lock_acquisitions_total"] = (
                self.store.lock_acquisitions_total())
        out["gc_pause_s_total"] = gc_pause_s_total()
        out["gc_full_total"] = gc_collections_total()[2]
        out["gc_full_pause_s_total"] = gc_full_pause_s_total()
        # Supervisor state: the ladder rung as a gauge (0 = full fast
        # path; exposed on /metrics via the service provider) plus its
        # name for humans/tests (non-numeric — dropped from exposition).
        out["degradation_level"] = self._sup.level
        out["degradation_state"] = DEGRADATION_LADDER[self._sup.level]
        # Overload-controller state (engine/overload.py): the actuation
        # rung, transition/tuner counters, brownout flag, admission
        # rejects, and the live effective knobs — with the flat
        # ``shed_total`` alias beside the queue_-prefixed stats so the
        # shed ledger has one canonical scrape name. All zeros / bases
        # with MINISCHED_OVERLOAD unset.
        out.update(self._overload.metrics())
        out["shed_total"] = out.get("queue_shed_total", 0)
        out["overload_max_batch"] = self._overload.effective_max_batch(
            self.config.max_batch_size)
        out["overload_window_s"] = self._overload.effective_window(
            self.config.batch_window_s)
        out["overload_shortlist_k"] = int(self._shortlist_k or 0)
        # RemoteStore circuit-breaker state (utils/breaker.py) when this
        # engine runs as a pure network client: closed→open→half-open
        # gauge + transition/fast-fail/probe counters, so one scrape of
        # a co-located /metrics shows whether the client is probing a
        # down apiserver instead of hammering it.
        breaker_stats = getattr(self.store, "breaker_stats", None)
        if callable(breaker_stats):
            for k, v in breaker_stats().items():
                out[f"store_{k}"] = v
        # Apiserver-outage ride-through counters (RemoteStore.reattach):
        # outages detected, reattach arcs completed, last outage length.
        reattach_stats = getattr(self.store, "reattach_stats", None)
        if callable(reattach_stats):
            for k, v in reattach_stats().items():
                out[f"store_{k}"] = v
        # Temporal telemetry: snapshot/drop counts for the timeline
        # ring and the per-objective burning gauges (1 while an SLO's
        # burn windows are both over threshold — the sentinel clears
        # them on recovery). Alert counters live in the metrics dict
        # itself (slo_alerts_total + slo_alerts_<name>).
        out["timeline_snapshots"] = self._timeline.snapshots()
        out["timeline_dropped"] = self._timeline.dropped()
        # Burning gauges only while the sentinel that computed them is
        # the CURRENT one: after a disarm/reconfigure evaluate() never
        # runs again, and exporting the retired sentinel's dict would
        # pin a stale "burning" 1 on /metrics forever (the series
        # disappearing on disarm is the standard exposition shape).
        # Re-derived at the CURRENT clock (burning_now): an idle engine
        # resolves no batches, so the batch-driven evaluate() alone
        # would latch a stale 1 after the queue drains.
        sent = self._slo_sentinel
        if (sent is not None and slo_mod.SLO.enabled
                and self._slo_epoch == slo_mod.SLO.epoch):
            live = sent.burning_now(self._timeline.entries(),
                                    self._timeline.now_t())
            for name, burning in live.items():
                out[f"slo_burning_{name}"] = int(burning)
        # Explainability-store retention (explain/resultstore.py): live
        # record/bitmask counts and the eviction counter the churn
        # bound is pinned by. Only meaningful with explain mode on.
        if self.recorder is not None:
            for k, v in self.recorder.stats().items():
                out[f"resultstore_{k}"] = v
        # Decision-journal + provenance surfaces (obs/journal.py): the
        # process-wide event count/drop ledger and this engine's
        # provenance LRU occupancy. All zeros with MINISCHED_JOURNAL
        # unset.
        out["journal_events"] = JOURNAL.next_seq()
        out["journal_dropped"] = JOURNAL.dropped()
        out["journal_dropped_by_fault"] = JOURNAL.dropped_by_fault
        pstats = self._provenance.stats()
        out["provenance_records"] = pstats["records"]
        out["provenance_evictions"] = pstats["evictions"]
        # Per-gate fault-injection fire counts (PROCESS-wide registry —
        # shared across co-located engines; with MINISCHED_FAULTS unset
        # all zeros, proving a run was fault-free).
        for gate, n in FAULTS.counts().items():
            out[f"fault_fires_{gate}"] = n
        return out

    ZONE_KEY = "topology.kubernetes.io/zone"
    IMPOSSIBLE_DOMAIN = -2  # matches no node (multi-zone PVs, registry full)

    def _volume_state(self, pod: Pod):
        """Single store pass resolving every volume-derived encode input:
        (ready, claim_rows, claim_typed, zone_key_idx, zone_dom).

        ready      — all referenced PVCs Bound (VolumeBinding input).
                     A pending WaitForFirstConsumer claim does NOT block
                     (upstream volumebinding late binding): the PV
                     controller binds it after the pod schedules.
        claim_rows — per-claim current mount row (VolumeRestrictions RWO)
        zone       — required zone domain from the bound PVs' zone labels
                     (VolumeZone); for a pending WFFC claim whose candidate
                     PVs all live in ONE zone, that zone becomes the
                     requirement (topology-aware late binding). Candidates
                     spread over several zones imply most placements can
                     bind — no constraint (the single-domain zone encoding
                     can't express a small allowed set; documented
                     fail-open). PVs in several DISTINCT zones, or a
                     zone key that can't be registered (topology-key
                     registry full), yield IMPOSSIBLE_DOMAIN under the
                     always-present hostname slot — fail CLOSED: no node
                     matches, the pod parks under VolumeZone rather than
                     binding somewhere its volume can't attach."""
        from ..state.objects import CLOUD_VOLUME_AXES

        ready = True
        claim_rows = []
        claim_typed = []
        typed_by_key: Dict[str, bool] = {}
        for v in pod.spec.volumes:
            k = f"{pod.metadata.namespace}/{v.claim_name}"
            typed_by_key[k] = (typed_by_key.get(k, False)
                               or v.volume_type in CLOUD_VOLUME_AXES)
        zones_seen = set()
        impossible = False
        for ck in claim_keys(pod):
            claim_rows.append(self.cache.claim_node_row(ck))
            claim_typed.append(typed_by_key.get(ck, False))
            try:
                pvc = self.store.get("PersistentVolumeClaim", ck)
            except NotFoundError:
                ready = False
                continue
            if pvc.phase != "Bound":
                if pvc.binding_mode == "WaitForFirstConsumer":
                    # Zero candidate PVs = assume dynamic provisioning will
                    # create one in the pod's zone after placement (the PV
                    # controller's default mode); with provisioning off AND
                    # no candidates the claim would pend forever — the
                    # upstream equivalent of a class with no provisioner.
                    zones = self._wffc_candidate_zones(pvc)
                    if len(zones) == 1:
                        zones_seen |= zones
                        if len(zones_seen) > 1:
                            impossible = True
                else:
                    ready = False
            if not pvc.volume_name:
                continue
            try:
                pv = self.store.get("PersistentVolume", pvc.volume_name)
            except NotFoundError:
                continue
            zone = pv.metadata.labels.get(self.ZONE_KEY)
            if zone:
                zones_seen.add(zone)
                if len(zones_seen) > 1:
                    impossible = True
        return (ready, claim_rows, claim_typed,
                *self._zone_requirement(zones_seen, impossible))

    def _zone_requirement(self, zones_seen, impossible):
        """(zone_key_idx, zone_dom) for the encoder from the set of zones
        the pod's volumes demand."""
        from ..encode.features import pair_hash

        if not zones_seen:
            return -1, -1
        idx = self.cache.registry.index_of(self.ZONE_KEY, self.cache.overflow)
        if impossible or idx < 0:
            return 0, self.IMPOSSIBLE_DOMAIN
        (zone,) = zones_seen
        return idx, pair_hash(self.ZONE_KEY, zone) % self.cache.cfg.domain_buckets

    def _wffc_candidate_zones(self, pvc) -> Set[str]:
        """Distinct zones of Available PVs that could satisfy a pending
        WaitForFirstConsumer claim (class + capacity match). Memoized per
        claim with a short TTL so a batch of pods sharing pending WFFC
        claims doesn't rescan the PV list O(P) times on the hot path."""
        now = time.monotonic()
        hit = self._wffc_memo.get(pvc.key)
        if hit is not None and now - hit[1] < 0.5:
            return hit[0]
        want = pvc.request.get("ephemeral-storage", 0)
        zones: Set[str] = set()
        for pv in self.store.list("PersistentVolume"):
            if (pv.phase == "Available"
                    and pv.storage_class == pvc.storage_class
                    and pv.capacity.get("ephemeral-storage", 0) >= want):
                zone = pv.metadata.labels.get(self.ZONE_KEY)
                if zone:
                    zones.add(zone)
        self._wffc_memo[pvc.key] = (zones, now)
        return zones

    # ---- permit + binding cycle ----------------------------------------

    def _start_binding_cycle(self, qpi: QueuedPodInfo, node_name: str,
                             expected_inc: Optional[int] = None):
        """Assume + permit. Returns (pair, ghost, rejected): ``pair`` is
        (qpi, node_name) when the pod is permit-free so the caller can
        bulk-commit the whole batch in one store transaction, None when
        the pod was parked for a permit wait (bound later, per-pod) or
        failed permit; ``ghost`` is True when the pod was NOT placed at
        all because its chosen node's row vanished mid-cycle (the caller
        must not count it as assigned); ``rejected`` is True when a
        permit plugin rejected SYNCHRONOUSLY — the pod was unassumed,
        so like a ghost its scan-counted admission vanished and the
        caller must feed it to the post-assume re-arbitration."""
        pod = qpi.pod
        # Assume the pod onto the node immediately so the next batch's
        # snapshot sees the capacity taken (upstream assume/forget model).
        if not self.cache.account_bind(pod, node_name=node_name,
                                       expected_inc=expected_inc):
            # Node row deleted between snapshot and assume — binding now
            # would commit a ghost placement the model can never account
            # (see the bulk-assume miss path). Requeue for a fresh cycle.
            self._handle_failure(
                qpi, {BATCH_CAPACITY},
                f"chosen node {node_name} was deleted during the "
                "scheduling cycle", retryable=True)
            return None, True, False
        self._note_assumed(qpi)

        waits = []
        for plugin in self.plugin_set.permit_plugins:
            try:
                status, delay, timeout = plugin.permit(pod, node_name)
            except Exception:
                log.exception("permit plugin %s failed", plugin.name)
                status, delay, timeout = "reject", 0.0, 0.0
            if status == "reject":
                self._unassume(qpi)
                self._handle_failure(
                    qpi, {plugin.name},
                    f"pod rejected by permit plugin {plugin.name}",
                    retryable=False)
                return None, False, True
            if status == "wait":
                waits.append((plugin.name, delay, timeout))

        if waits:
            # Park the pod (reference RunPermitPlugins Wait status →
            # WaitingPod + timers, minisched.go:228-234), then bind async.
            wp = WaitingPod(pod, node_name, waits)
            with self._waiting_lock:
                self.waiting_pods[pod.key] = wp
            max_timeout = max(t for _, _, t in waits)
            self._note_detached(pod.key)  # the wait owns the placement now
            self._binder.submit(self._wait_and_bind, qpi, wp, max_timeout)
            return None, False, False
        return (qpi, node_name), False, False

    def _wait_and_bind(self, qpi: QueuedPodInfo, wp: WaitingPod,
                       max_timeout: float) -> None:
        try:
            self._wait_and_bind_impl(qpi, wp, max_timeout)
        finally:
            # The wait no longer owns the placement (bound, requeued, or
            # parked): release the supervised-retry exclusion.
            with self._detached_lock:
                self._detached_live.discard(qpi.pod.key)

    def _wait_and_bind_impl(self, qpi: QueuedPodInfo, wp: WaitingPod,
                            max_timeout: float) -> None:
        sig = wp.get_signal(timeout=max_timeout + 1.0)
        with self._waiting_lock:
            self.waiting_pods.pop(qpi.pod.key, None)
        revoked = getattr(wp, "engine_revoked", None)
        if sig is None or not sig.allowed:
            reason = sig.reason if sig else "permit wait timed out"
            self._unassume(qpi)
            if revoked is not None:
                # engine-side revocation (_revoke_post_assume), not a
                # permit verdict: retryable with the engine's attribution
                self._handle_failure(qpi, revoked[0], revoked[1],
                                     retryable=True)
                return
            self._handle_failure(
                qpi, {name for name, _, _ in wp.waits},
                f"WaitOnPermit failed: {reason}", retryable=False)
            return
        if revoked is not None:
            # The permit ALLOW signal raced the engine's reject (the
            # signal channel is first-send-wins, so the reject was
            # dropped) — but engine_revoked is set under _waiting_lock
            # strictly before this pop, so honoring it here closes the
            # window: the revocation must win or the pod binds at
            # sub-quorum / over max_skew.
            self._unassume(qpi)
            self._handle_failure(qpi, revoked[0], revoked[1],
                                 retryable=True)
            return
        self._bind(qpi, wp.node_name)

    def _observe_bound(self, qpis) -> None:
        """Feed the per-pod lifecycle histograms for pods that just
        BOUND. Called at every site that increments ``pods_bound`` (and
        only there), so ``pod_create_to_bound_s.count`` equals the bound
        decisions by construction. Stage windows come from the
        QueuedPodInfo stamps (queued=added_at → gathered_at →
        decided_at → now); create→bound pairs the store's wall-clock
        creation stamp with wall-clock now, the same definition the
        bench's sampled windows use, and create→enqueued (the informer
        lag) pairs it with the queue's first-entry stamp."""
        now_m = time.monotonic()
        now_w = time.time()
        lag, qw, dec, bnd, c2b = [], [], [], [], []
        for qpi in qpis:
            if qpi.gathered_at:
                qw.append(max(0.0, qpi.gathered_at - qpi.added_at))
                if qpi.decided_at:
                    dec.append(max(0.0, qpi.decided_at - qpi.gathered_at))
            if qpi.decided_at:
                bnd.append(max(0.0, now_m - qpi.decided_at))
            created = getattr(qpi.pod.metadata, "creation_timestamp",
                              0.0) or now_w
            c2b.append(max(0.0, now_w - created))
            lag.append(max(0.0, (qpi.enqueued_unix or created) - created))
        h = self._hists
        h["pod_informer_lag_s"].observe_many(lag)
        if qw:
            h["pod_queue_wait_s"].observe_many(qw)
        if dec:
            h["pod_decide_s"].observe_many(dec)
        if bnd:
            h["pod_bind_s"].observe_many(bnd)
        h["pod_create_to_bound_s"].observe_many(c2b)
        if JOURNAL.enabled:
            # Settle the per-pod provenance records: every pods_bound
            # site funnels through here, so "record exists and matches
            # store truth for every bound pod" holds by construction.
            for qpi in qpis:
                rec = qpi.prov
                if rec is not None:
                    # The stamp is consumed at settlement: a later
                    # attempt of a requeued pod must never publish this
                    # attempt's node/batch tags under its own verdict.
                    qpi.prov = None
                    self._provenance.record(qpi.pod.key, {
                        **rec, "outcome": "bound",
                        "bound_unix": round(now_w, 3)})

    def _dispose_stale_owner(self, items: List[tuple]) -> None:
        """Fleet bind fence tripped: this replica lost the shard lease
        between decision and commit. Withhold the bind — unassume (the
        capacity bookkeeping must not leak) and forget, WITHOUT
        requeueing: the pod belongs to the shard's new owner now, whose
        takeover sweep re-gathers it from the store. A true epoch race
        (both replicas believe they hold) is still safe without this
        fence — the store's bind CAS lets exactly one commit win."""
        for qpi, _node in items:
            self._unassume(qpi)
            self.queue.forget(qpi.pod.key)
        with self._metrics_lock:
            self._metrics["stale_owner_binds"] += len(items)
        jnote("fleet.stale_bind", profile=self.profile,
              replica=self.replica, pods=len(items))

    def _fence_binds(self, items: List[tuple]) -> List[tuple]:
        """Partition a bind tranche through the fleet bind guard (no-op
        without one): stale-owner placements are disposed, the rest
        proceed to the store commit."""
        guard = self._bind_guard
        if guard is None:
            return items
        live, stale = [], []
        for it in items:
            try:
                ok = guard(it[0].pod.key)
            except Exception:
                ok = True  # a broken fence must not drop commits
            (live if ok else stale).append(it)
        if stale:
            self._dispose_stale_owner(stale)
        return live

    def _bind(self, qpi: QueuedPodInfo, node_name: str) -> None:
        if not self._fence_binds([(qpi, node_name)]):
            return
        pod = qpi.pod
        try:
            with span("bind.pod"):
                bound = self.store.bind_pod(pod.key, node_name)
        except (ConflictError, NotFoundError) as e:
            self._bind_failed(qpi, node_name, e)
            return
        self.queue.forget(pod.key)
        with self._metrics_lock:
            self._metrics["pods_bound"] += 1
        self._observe_bound((qpi,))
        self.broadcaster.scheduled(bound, node_name)
        log.info("bound %s to %s", pod.key, node_name)

    def _bind_many(self, items: List[tuple], seq: int) -> None:
        """Bulk binding commit with failure containment: the task runs on
        the binder pool, where an unhandled exception would silently
        swallow the whole tranche — pods popped, assumed, never bound,
        never requeued (lost) with their capacity pinned forever. Any
        failure (wire fault on a RemoteStore, injected ``bind`` gate)
        reconciles per pod against store truth instead."""
        live = items
        try:
            live = self._fence_binds(items)
            if live:
                FAULTS.hit("bind")  # fault gate: bulk binding task
                with span("bind.bulk", pods=len(live), seq=seq):
                    self._bind_many_impl(live)
        except Exception:
            log.exception("bulk bind task failed; reconciling %d "
                          "placement(s) against store truth", len(live))
            self._reconcile_bind_failure(live)
        finally:
            # The bulk commit concluded for every pod (bound, requeued,
            # or forgotten): release the supervised-retry exclusions.
            with self._detached_lock:
                self._detached_live.difference_update(
                    q.pod.key for q, _n in items)

    def _reconcile_bind_failure(self, items: List[tuple]) -> None:
        """Per-pod recovery for an aborted bulk bind: the store is the
        truth — a pod the half-applied transaction DID bind keeps its
        assume (that assume IS the bound accounting) and is forgotten;
        an unbound pod is unassumed and requeued with backoff; a deleted
        pod releases everything. No pod is lost, none doubly bound."""
        for qpi, node_name in items:
            key = qpi.pod.key
            try:
                fresh = self.store.get("Pod", key)
            except NotFoundError:
                self._unassume(qpi)
                self.queue.forget(key)
                continue
            except Exception:
                # Store unreachable: keep the assume (the capacity may
                # genuinely be taken — unassuming a bound pod would let
                # the node over-commit) and requeue; the retry's bind
                # conflict machinery reconciles once the store answers.
                log.exception("bind reconcile: store unreachable for %s; "
                              "requeueing with the assume held", key)
                self.queue.requeue_backoff(qpi)
                continue
            if fresh.spec.node_name:
                self.queue.forget(key)
                with self._metrics_lock:
                    self._metrics["pods_bound"] += 1
                self._observe_bound((qpi,))
            else:
                self._bind_failed(qpi, node_name, "bulk bind task aborted")

    def _bind_many_impl(self, items: List[tuple]) -> None:
        """Bulk binding commit for permit-free pods: one store.bind_pods
        transaction (state/store.py) for the whole batch, then per-pod
        bookkeeping. Pods the store skipped (deleted mid-flight, bound by
        a competing scheduler, node gone) fall back to the per-pod failure
        handling of _bind."""
        # Compute each pod key ONCE (it's an f-string property) and reuse
        # it for the store commit, the bound diff, and the event payload.
        keyed = [(qpi.pod.key, qpi, node_name) for qpi, node_name in items]
        bound_keys = set(self.store.bind_pods(
            [(k, n) for k, _, n in keyed]))
        with self._metrics_lock:
            self._metrics["pods_bound"] += len(bound_keys)
        self._observe_bound([qpi for k, qpi, _n in keyed
                             if k in bound_keys])
        self.queue.forget_many(bound_keys)
        if self._nominations:  # a bound nominee releases its reservation
            with self._nom_lock:
                for k in bound_keys:
                    self._nominations.pop(k, None)
                    self._preempt_rounds.pop(k, None)
        ok = keyed
        if len(bound_keys) != len(keyed):  # rare: some skipped mid-flight
            ok = []
            for k, qpi, node_name in keyed:
                if k in bound_keys:
                    ok.append((k, qpi, node_name))
                else:
                    self._bind_failed(qpi, node_name,
                                      "skipped by bulk commit")
        self.broadcaster.scheduled_many(
            [(k, qpi.pod.metadata.namespace, n) for k, qpi, n in ok])
        if bound_keys:
            log.info("bulk-bound %d pods", len(bound_keys))

    def _bind_failed(self, qpi: QueuedPodInfo, node_name: str,
                     reason) -> None:
        """Shared conflict path: unassume, then drop (pod deleted) or
        requeue with backoff (capacity/visibility race)."""
        self._unassume(qpi)
        with self._metrics_lock:
            self._metrics["bind_conflicts"] += 1
        try:
            self.store.get("Pod", qpi.pod.key)
        except NotFoundError:
            self.queue.forget(qpi.pod.key)  # pod is gone; drop it
            return
        log.warning("bind of %s to %s failed: %s", qpi.pod.key, node_name,
                    reason)
        self.queue.requeue_backoff(qpi)

    def _revoke_post_assume(self, qpi: QueuedPodInfo, plugins: Set[str],
                            msg: str, *, in_bind: bool) -> bool:
        """Reverse an assume made THIS cycle (ghost-gang atomicity /
        ghost-spread staleness). Returns True when the revocation took.

        ``in_bind``: the pod sits in the cycle's unsubmitted to_bind
        list — unassume + requeue is race-free (the bulk bind commits
        strictly after this point). Otherwise the pod is on the async
        permit path: an in-flight wait is rejected (its _wait_and_bind
        continuation unassumes and requeues with OUR attribution via
        the engine_revoked mark); a wait that already resolved may have
        bound — too late to revoke, upstream's own assumed-pod race —
        so the revocation is declined."""
        if in_bind:
            self._unassume(qpi)
            self._handle_failure(qpi, plugins, msg, retryable=True)
            return True
        with self._waiting_lock:
            wp = self.waiting_pods.get(qpi.pod.key)
            if wp is None:
                log.info("post-assume revocation of %s declined: permit "
                         "wait already resolved", qpi.pod.key)
                return False
            wp.engine_revoked = (set(plugins), msg)
        wp.reject("engine", msg)
        return True

    def _unassume(self, qpi: QueuedPodInfo) -> None:
        self.cache.account_unbind(qpi.pod.key)
        t = self._track
        if t is not None and threading.get_ident() == self._fail_sink_tid:
            t.assumed.pop(qpi.pod.key, None)

    # ---- failure path (reference ErrorFunc minisched.go:283-298) --------

    def _handle_failure(self, qpi: QueuedPodInfo, plugins: Set[str],
                        message: str, *, retryable: bool) -> None:
        if JOURNAL.enabled:
            self._prov_settle_failure(qpi, plugins, message, retryable)
        # Resolve-phase verdicts defer into the cycle's failure sink and
        # flush in bulk at commit (_flush_failures) — a skew-constrained
        # burst otherwise pays two store round-trips per revocation on
        # the scheduling thread. Thread-gated: binder/permit threads (no
        # sink of their own) keep the immediate path.
        sink = self._fail_sink
        if sink is not None and threading.get_ident() == self._fail_sink_tid:
            sink.append((qpi, set(plugins), message, retryable))
            return
        pod = qpi.pod
        self.broadcaster.failed_scheduling(pod, message)
        try:
            fresh = self.store.get("Pod", pod.key)
            if not fresh.spec.node_name:
                fresh.status.unschedulable_plugins = sorted(plugins)
                fresh.status.message = message
                self.store.update(fresh)
                qpi.pod = fresh
        except NotFoundError:
            self.queue.forget(pod.key)
            self.drop_nomination(pod.key)
            return
        if retryable:
            self.queue.requeue_backoff(qpi)
        else:
            self.queue.add_unschedulable(qpi, plugins)
