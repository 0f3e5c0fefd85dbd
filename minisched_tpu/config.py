"""Configuration.

Two tiers, mirroring the reference (SURVEY §5 "Config / flag system"):
  1. Process-level env config (reference config/config.go:14-75 — PORT,
     ETCD_URL, FRONTEND_URL, all mandatory with typed errors). The rebuild
     needs no network endpoints; the env tier carries the TPU-path toggles
     BASELINE.json assigns to config (backend selection, explain mode).
  2. Scheduler profiles (KubeSchedulerConfiguration analog) live in
     minisched_tpu/service/defaultconfig.py.
"""
from __future__ import annotations

import os
from dataclasses import dataclass, field


class EmptyEnvError(ValueError):
    """reference config.ErrEmptyEnv (config/config.go:18)."""


@dataclass
class SchedulerConfig:
    """Engine tuning knobs."""

    max_batch_size: int = 1024       # pods per scheduling step
    # Batch formation window (s): after the first pod arrives, keep
    # gathering until max_batch_size or this much time passes. 0 = pop
    # immediately (lowest latency); bursty arrival benefits from a small
    # window (full deterministic batches → stable pad buckets, no
    # mid-burst recompiles).
    batch_window_s: float = 0.0
    # Idle-exit for the gather window (engine/queue.py pop_batch): stop
    # gathering once no pod has arrived for this long — the burst's TAIL
    # batch otherwise stalls for the whole window. Only meaningful with
    # batch_window_s > 0; size it above expected informer stalls (a
    # too-small grace splits straggler batches onto fresh pad buckets,
    # costing compiles). 0 = pure-window behavior.
    batch_idle_s: float = 0.0
    pod_bucket_min: int = 16         # bucket ladder minimum (pad P)
    node_bucket_min: int = 16        # bucket ladder minimum (pad N)
    backoff_initial_s: float = 1.0   # reference queue.go:218-221
    backoff_max_s: float = 10.0
    explain: bool = False            # return full per-plugin matrices
    # Host-selection strategy: "greedy" (priority-faithful sequential
    # semantics; scan or pallas kernel) or "auction" (parallel bidding
    # rounds, aggregate-score-seeking — ops/auction.py docstring lists
    # the semantic deviations). Both families ride the same residency
    # carry, work ring, and shortlist seams (the order-free debit
    # mirror — engine/scheduler._DeviceResidency — made the host
    # mirror assignment-order-independent); MINISCHED_ASSIGNMENT.
    assignment: str = "greedy"
    seed: int = 0                    # PRNG seed for tie-breaking parity
    bind_workers: int = 16           # async binding-cycle pool size
    platform: str = ""               # "" = whatever jax picks; or cpu/tpu
    # Node-axis sampling for the scoring step — the upstream
    # percentageOfNodesToScore analog (adaptive default; surfaced ignored
    # at the reference's scheduler_test.go:79). 0 = auto (upstream's
    # 50 - nodes/125, floored at 5); 100 = always evaluate every node.
    # A sampled batch that finds a pod 0-feasible re-checks it against
    # the full axis in the same cycle, so terminal verdicts never come
    # from a sample.
    percentage_of_nodes_to_score: int = 0
    # Never sample below this many candidate nodes (upstream
    # minFeasibleNodesToFind), and only bother sampling at all when the
    # cluster is at least twice this size.
    min_sample_nodes: int = 256
    # Multi-chip: a SINGLE-PROCESS jax.sharding.Mesh
    # (parallel.mesh.make_mesh) to run the scheduling step over. The
    # (P,N) plugin matrices partition over the ("pod", "node") axes and
    # XLA inserts the collectives (parallel/sharded.py); ``assignment``
    # selects the sharded assignment stage — "greedy" (the default) is
    # the exact chunked-gather scan (bit-identical to single-device),
    # "auction" the faster priority-tiered auction. None = single
    # device. (A multi-PROCESS hybrid mesh would leave the engine's
    # decision readback non-addressable from one host; the store/
    # informer stack is single-process — multi-host serving composes by
    # sharding CLUSTERS across schedulers, not one engine across hosts.)
    # Node-axis sampling is DISABLED on a mesh: the sampled gather would
    # have to re-partition a data-dependent node subset every batch,
    # defeating the static shardings — and the mesh exists for clusters
    # big enough that the node axis is worth splitting, where each
    # shard's slice is already the sample-sized problem.
    mesh: object = None
    # Pipelined engine cycle (engine/scheduler.py _run_pipelined): while
    # batch k's jitted step executes on device (JAX async dispatch), the
    # host flushes batch k-1's commit work (store status writes, queue
    # requeues, event emission) on a dedicated worker and gathers batch
    # k+1 from the queue; batch k+1 is encoded only AFTER batch k's
    # arbitration + assume accounting (the batch-internal causality
    # rule), so decisions are identical to the synchronous loop. False
    # (MINISCHED_PIPELINE=0) restores the strictly synchronous cycle —
    # the debugging/regression-triage fallback.
    pipeline: bool = True
    # Device-resident dynamic cluster state + slim decision readback
    # (engine/scheduler.py _DeviceResidency, ops/residency.py): the
    # dynamic node-feature leaves (free/used_ports) stay loop-carried on
    # device — the jitted step's free_after IS the next batch's input —
    # and the host uploads only sparse correction rows where its
    # authoritative cache diverged from the device's optimistic view
    # (revocations, failed binds, informer churn); the per-batch
    # decision fetch packs bool planes as bits and narrows counts to
    # i16. Decisions are bit-identical either way
    # (tests/test_device_residency.py). False (MINISCHED_DEVICE_RESIDENT
    # =0) restores the upload-every-batch path and the all-i32 fetch —
    # the regression-triage fallback.
    device_resident: bool = True
    # Intra-cycle repair for topology-revoked pods: after the batch's
    # survivors are assumed, re-run the step on the revoked rows against
    # the refreshed counts up to this many times before falling back to
    # the requeue/backoff path. A skew-constrained burst (hard
    # DoNotSchedule under contention) otherwise drains at roughly
    # (domains x max_skew) pods per QUEUE cycle, each paying backoff
    # latency; repair iterations drain the same tranches within one
    # cycle. 0 disables.
    spread_repair_iters: int = 8
    # Engine supervisor (engine/scheduler.py _Supervisor): per-batch
    # device-step watchdog deadline in seconds — a batch whose
    # dispatch→fetch window exceeds it counts a watchdog trip and
    # degrades the engine one ladder rung (the step completed; nothing
    # is retried). 0 disables the deadline; fault/NaN/desync detection
    # and the degradation ladder stay active regardless.
    watchdog_s: float = 0.0
    # Probation length for the degradation ladder: after this many
    # consecutive CLEAN batches at a degraded level, the supervisor
    # re-escalates one rung back toward the full fast path
    # (resident → upload-every-batch → synchronous → quarantine).
    probation_batches: int = 8
    # Shortlist-compressed arbitration (ops/select.py
    # greedy_assign_shortlist, wired through ops/pipeline.build_step):
    # the greedy scan's sequential per-pod argmax runs over per-pod
    # top-K candidate shortlists computed in one parallel pass, with an
    # exactness certificate per step and a counted full-row repair
    # rescan where it fails — decisions are bit-identical to the full
    # scan (tests/test_shortlist.py). False (MINISCHED_SHORTLIST=0)
    # restores the PR-2 full-width scan — the regression-triage
    # fallback. The auction path takes its own analog
    # (ops/bid_select.auction_assign_shortlist: per-pod top-K bid rows
    # with the same certify-or-repair contract over the price
    # dynamics); mesh and enforced-domain-caps batches keep full rows
    # regardless.
    shortlist: bool = True
    # Shortlist width K (MINISCHED_SHORTLIST_K): per-step sequential
    # argmax width, clamped to the node pad. 128 cuts the 50k-node
    # step's scan width ~390×; widen it if shortlist_repairs climbs
    # (contention exhausting K candidates forces full-row rescans).
    shortlist_k: int = 128
    # Shortlist certification cross-check (MINISCHED_SHORTLIST_CHECK
    # _EVERY): every N batches re-run the SAME inputs through the
    # full-width scan and compare decisions — a divergence counts a
    # shortlist_desync, permanently reverts the engine to the full
    # scan, and aborts the batch into the supervised retry. 0 disables
    # (the certificate already proves equality per step; this check
    # covers defects OUTSIDE the proof — a scribbled readback, a broken
    # backend gather — and is what the shortlist_repair:corrupt fault
    # gate exercises).
    shortlist_check_every: int = 0
    # Persistent on-device engine loop (engine/scheduler.py tranche
    # machinery + ops/pipeline.build_loop_step, MINISCHED_DEVICE_LOOP):
    # when the queue holds multiple ready batches of loop-safe pods
    # (no gangs/pod-affinity/spread constraints/volumes/ports — the
    # workloads whose decisions are provably independent of the host
    # state the ring cannot carry), the engine stages up to
    # ``loop_depth`` pre-encoded fixed-shape batches into a device-side
    # work ring and dispatches ONE fused lax.scan that carries ``free``
    # across iterations and emits one stacked decision buffer fetched
    # in a single d2h transfer — dispatches-per-batch drops below 1.
    # Between slots the engine validates host truth against the carried
    # chain (cache.drain_dyn_rows) and BREAKS back to per-batch
    # dispatch on any divergence (revocation, failed bind, informer
    # churn, nominations), replaying the un-consumed slots through the
    # normal path with their original PRNG draws — decisions are
    # bit-identical loop on/off (tests/test_device_loop.py). False
    # (the default, MINISCHED_DEVICE_LOOP=0) keeps per-batch dispatch
    # exactly; opt-in until the TPU capture validates the win.
    device_loop: bool = False
    # Work-ring depth: max batches fused per device dispatch
    # (MINISCHED_LOOP_DEPTH). The overload tuner steps the effective
    # depth down (halved per tune step) under the ``tuned`` rung.
    loop_depth: int = 8
    # Residency carry cross-check (ROADMAP follow-up (b)): every N
    # device-resident batches, fetch the device-carried free array and
    # compare it to the host mirror BEFORE the step consumes it; a
    # mismatch counts a desync, forces a full re-upload, and signals the
    # supervisor. 0 disables (the versioned delta protocol already makes
    # host-side desync structurally impossible — this check covers the
    # DEVICE side of the carry, e.g. a defective scatter/backend, plus
    # the two cases the order-free debit mirror cannot prove or heal:
    # mirror arithmetic OUTSIDE the integer-valued-f32 resource grammar,
    # and a mis-TARGETED mirror write on a row no correction delta will
    # ever visit — what the auction_mirror fault gate exercises).
    resident_check_every: int = 0


def config_from_env() -> SchedulerConfig:
    """Build SchedulerConfig from MINISCHED_* env vars (the reference reads
    all config from env, config/config.go:22-44)."""

    def _req(name: str, default: str) -> str:
        v = os.environ.get(name, default)
        if v == "":
            raise EmptyEnvError(f"env {name} is empty")
        return v

    mesh = None
    mesh_devices = int(os.environ.get("MINISCHED_MESH_DEVICES", "0"))
    if mesh_devices:
        # Lazy jax import: the env tier must stay importable without
        # touching the backend (tests hard-pin JAX_PLATFORMS first).
        import jax

        from .parallel.mesh import make_mesh

        devs = jax.devices()
        if len(devs) < mesh_devices:
            # Silently truncating would run a smaller layout than the
            # operator asked for — fail the misconfiguration loudly.
            raise ValueError(
                f"MINISCHED_MESH_DEVICES={mesh_devices} but only "
                f"{len(devs)} devices are visible")
        mesh = make_mesh(devs[:mesh_devices])
    return SchedulerConfig(
        max_batch_size=int(_req("MINISCHED_MAX_BATCH", "1024")),
        batch_window_s=float(_req("MINISCHED_BATCH_WINDOW", "0.0")),
        batch_idle_s=float(_req("MINISCHED_BATCH_IDLE", "0.0")),
        explain=_req("MINISCHED_EXPLAIN", "0") == "1",
        assignment=_req("MINISCHED_ASSIGNMENT", "greedy"),
        seed=int(_req("MINISCHED_SEED", "0")),
        backoff_initial_s=float(_req("MINISCHED_BACKOFF_INITIAL", "1.0")),
        backoff_max_s=float(_req("MINISCHED_BACKOFF_MAX", "10.0")),
        platform=os.environ.get("MINISCHED_PLATFORM", ""),
        percentage_of_nodes_to_score=int(
            _req("MINISCHED_PCT_NODES_TO_SCORE", "0")),
        pipeline=_req("MINISCHED_PIPELINE", "1") != "0",
        device_resident=_req("MINISCHED_DEVICE_RESIDENT", "1") != "0",
        shortlist=_req("MINISCHED_SHORTLIST", "1") != "0",
        shortlist_k=int(_req("MINISCHED_SHORTLIST_K", "128")),
        shortlist_check_every=int(
            _req("MINISCHED_SHORTLIST_CHECK_EVERY", "0")),
        device_loop=_req("MINISCHED_DEVICE_LOOP", "0") == "1",
        loop_depth=int(_req("MINISCHED_LOOP_DEPTH", "8")),
        watchdog_s=float(_req("MINISCHED_WATCHDOG", "0.0")),
        probation_batches=int(_req("MINISCHED_PROBATION_BATCHES", "8")),
        resident_check_every=int(
            _req("MINISCHED_RESIDENT_CHECK_EVERY", "0")),
        mesh=mesh,
    )
