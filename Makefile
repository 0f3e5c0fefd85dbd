# Dev tooling (analog of the reference Makefile: `make test` = go test ./...,
# `make start` = build + etcd + run scenario; reference Makefile:1-31,
# hack/start_simulator.sh:32-35 — here no etcd is needed: the cluster store
# is in-process).

PY ?= python
# JAX_PLATFORMS=cpu, set before jax is imported: CPU-only runs.
CPU_MESH := XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu

# tier1 uses pipefail/PIPESTATUS (bash-isms).
SHELL := /bin/bash

.PHONY: test tier1 fault-smoke shortlist-smoke trace-smoke slo-smoke \
        churn-smoke overload-smoke loop-smoke journal-smoke \
        fleet-smoke fleet-proc-smoke election-smoke tenant-smoke \
        auction-smoke profile-smoke start \
        start-remote \
        start-client-engine \
        demo docs \
        bench bench_sharded bench-cpu bench-pipeline bench-residency \
        bench-shortlist bench-trace bench-slo bench-churn bench-overload \
        bench-deviceloop bench-coldstart bench-journal \
        bench-fleet bench-tenants bench-auction \
        bench-check dryrun dryrun-dcn soak soak-faults soak-churn \
        soak-overload

# Unit + integration suite on a virtual 8-device CPU mesh.
test:
	$(CPU_MESH) $(PY) -m pytest tests/ -x -q

# Fast deterministic shortlist equality suite (~45 s): bit-identity of
# the shortlist-compressed scan vs the full-width scan at the op, step,
# and engine level (sync/pipelined/resident/mesh), adversarial
# contention repairs, degenerate K widths. A tier-1 prerequisite: the
# hottest kernel's exactness contract gates everything else.
shortlist-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_shortlist.py -x -q \
	  -p no:cacheprovider -p no:randomly

# Fast deterministic flight-recorder suite (~40 s): off-mode is a
# bit-identical no-op across pipelined/resident/shortlist modes, span
# nesting holds under the two-deep pipeline, fault fires + ladder
# escalations surface as instants, histogram counts equal bound
# decisions, exported traces validate against the Chrome trace-event
# schema. A tier-1 prerequisite: the measurement layer every later perf
# PR reports against must not perturb decisions.
trace-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_obs.py -x -q \
	  -p no:cacheprovider -p no:randomly

# Fast deterministic temporal-telemetry suite (~60 s): timeline ring
# cadence/wrap, histogram-delta quantiles, decisions bit-identical
# armed-vs-unarmed per engine mode, SLO burn-window logic + the
# faulted-churn early-warning chain (alert before quarantine, counted
# supervisor reaction), the /timeline endpoint, the resultstore
# retention bound, and the bench_compare regression gate. A tier-1
# prerequisite alongside trace-smoke: the layer that DECIDES whether
# the engine regressed must itself be pinned.
slo-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_timeline.py -x -q \
	  -p no:cacheprovider -p no:randomly

# Fast deterministic lifecycle suite (~60 s): seed determinism
# (byte-identical event stream + canonical final state), per-generator
# invariants on clean live runs, the cordon/drain facade verbs,
# faulted-churn recovery, and the adversarial PDB overlap. A tier-1
# prerequisite alongside fault-smoke/trace-smoke: the scenario oracle
# every soak leans on must itself be deterministic and sound.
churn-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_lifecycle.py -x -q \
	  -p no:cacheprovider -p no:randomly

# Fast deterministic overload-control suite (~2 min): controller-off
# bit-identity per engine mode, ladder hysteresis (no flapping under
# an oscillating burn/clean input), saturating-burst shedding that
# loses nothing (oracle-checked), brownout engage/recover in ladder
# order, the apiserver 429 verdict, and the RemoteStore circuit
# breaker. A tier-1 prerequisite after slo-smoke: the layer that
# ACTUATES on the sentinel's verdicts must itself be pinned.
overload-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_overload.py -x -q \
	  -p no:cacheprovider -p no:randomly

# Fast deterministic device-loop suite (~25 s): bit-identity of the
# fused multi-batch loop vs per-batch dispatch in every engine mode
# (sync/pipelined/resident/upload/shortlist-off) incl. ragged final
# tranches, fused-dispatch + one-readback-per-tranche ledgers,
# crash-consistent fault break-outs, overload-tuner depth composition,
# depth-scaled watchdog, timeline cadence, the compile-cache bootstrap,
# and the raw-op loop-vs-chained-step equality. A tier-1 prerequisite
# after overload-smoke: the ring must never change a decision.
loop-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_device_loop.py -x -q \
	  -p no:cacheprovider -p no:randomly

# Fast deterministic decision-journal suite (~60 s): journal unarmed is
# a bit-identical no-op per engine mode (sync/pipelined/resident/
# shortlist/loop), seq monotonicity holds under the two-deep
# pipeline + commit-worker threads, the JSONL sink and incident bundles
# validate against the postmortem schema (empty/unarmed included),
# provenance records match store truth for every bound pod in a faulted
# churn run, the journal fault gate never touches decisions, and the
# /journal + /provenance + /timeline?since cursors hold. A tier-1
# prerequisite after loop-smoke: the black-box recorder every incident
# postmortem leans on must itself be pinned.
journal-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_journal.py -x -q \
	  -p no:cacheprovider -p no:randomly

# Fast deterministic replicated-fleet suite (~60 s): shard map purity/
# totality, lease epochs monotone under concurrent claimants, clean
# 2-replica partition with zero cross-shard binds, kill-mid-burst
# takeover oracle-green within one lease TTL, restart rejoins without
# stealing, decisions bit-identical to a single-engine run on the same
# shard. A tier-1 prerequisite after journal-smoke: the HA control
# plane rides on the journal's takeover provenance.
fleet-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_fleet.py -x -q \
	  -p no:cacheprovider -p no:randomly

# Fast deterministic fused multi-tenant suite (~60 s): per-tenant
# placements bit-identical between the fused coordinator and the
# sequential baseline in every engine config (sync/pipelined/upload),
# ragged tenant batches harmonized by masked-row padding, mid-tranche
# delta races falling back solo and counted, fair-share and
# bucket-major slot apportionment never starving a tenant, mixed-size
# tenants fusing one dispatch per pod-pad bucket, provenance/journal
# attribution never crossing tenants, and the profile-scoped shed
# budget holding under a one-tenant overload burst. A tier-1
# prerequisite after fleet-smoke: the mux rides the same dispatch seam
# the fleet's shard engines do.
tenant-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_tenants.py -x -q \
	  -p no:cacheprovider -p no:randomly

# Fast deterministic auction-unification suite (~60 s): auction
# decisions bit-identical with the order-free debit mirror carrying
# ``free`` across batches (sync/pipelined × upload/resident), auction
# tranches fusing into the work ring (ragged tails + fault break-outs
# recovered bit-identically), the bid shortlist's certify-or-repair
# contract at the op and engine level (plateau zero-repair, adversarial
# contention repairs counted), and the nomination-window carry. A
# tier-1 prerequisite after tenant-smoke: the auction path now rides
# the same carry/ring/shortlist seams the greedy path does, and none
# of them may change a decision.
auction-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_auction.py -x -q \
	  -p no:cacheprovider -p no:randomly

# Out-of-process fleet suite (~40 s): real replica PROCESSES over
# RemoteStore against one apiserver — spawn/census/respawn lifecycle,
# SIGKILL failover exactly-once with the takeover journaled in the
# merged cross-process stream, elastic ShardMove handoff executing
# donor-release/recipient-adopt across processes, provenance fan-out
# with per-replica attribution, plus the rebalancer's structural
# no-flap hysteresis and the directive protocol unit tests. Includes
# the slow-marked integration tests tier-1's `-m 'not slow'`
# deselects. A tier-1 prerequisite after auction-smoke: process
# supervision rides every seam below it.
fleet-proc-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_fleet_proc.py -x -q \
	  -p no:cacheprovider -p no:randomly

# Self-governing fleet suite (~50 s): supervisor-less steward election
# over the shared store — CAS crown races admit exactly one winner,
# expiry succession epoch-fences stale directives, steward duties
# (census/mourn/respawn) hand off exactly-once across a SIGKILL'd
# steward, burn-signal rebalance migrates under sustained skew and
# holds still under oscillation, and the counted store.reattach arc
# rides out a full apiserver restart. Includes the slow-marked
# detached-fleet E2Es tier-1's `-m 'not slow'` deselects. A tier-1
# prerequisite after fleet-proc-smoke: the elected steward replaces the
# parent supervisor that fleet-proc pins, so that layer must already
# hold.
election-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_election.py -x -q \
	  -p no:cacheprovider -p no:randomly

# The EXACT ROADMAP tier-1 verify command (dots count + exit code
# preserved) — what the driver runs after every PR; run it locally
# before shipping. shortlist-smoke runs first: the arbitration
# exactness contract gates the rest of the suite; trace-smoke next: the
# measurement layer must not perturb decisions; overload-smoke after
# slo-smoke (the actuator rides the sentinel); churn-smoke last: the
# lifecycle oracle rides on all of them; loop-smoke after
# overload-smoke (the ring composes with the tuner's dials and must
# never change a decision); journal-smoke after loop-smoke
# (the black-box recorder hooks every layer above and must never change
# a decision); fleet-smoke after journal-smoke (lease takeovers journal
# their provenance through the recorder); tenant-smoke after
# fleet-smoke (the fused-tenant mux must never change a decision
# either); auction-smoke after tenant-smoke (the auction path now
# shares the carry/ring/shortlist seams and must stay bit-identical
# across them); fleet-proc-smoke after auction-smoke (process
# supervision is the outermost layer — replicas run the full engine
# stack, so every seam below must already hold); election-smoke after
# fleet-proc-smoke (the elected steward replaces the parent supervisor,
# so the supervised fleet layer must already hold).
tier1: shortlist-smoke trace-smoke slo-smoke overload-smoke loop-smoke \
       journal-smoke fleet-smoke tenant-smoke auction-smoke \
       fleet-proc-smoke election-smoke churn-smoke
	set -o pipefail; rm -f /tmp/_t1.log; \
	timeout -k 10 870 env JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q \
	  -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
	  -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; \
	rc=$${PIPESTATUS[0]}; \
	echo DOTS_PASSED=$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' \
	  /tmp/_t1.log | tr -cd . | wc -c); exit $$rc

# Fast robustness smoke (~20 s): the deterministic fault-schedule suite
# (faults.py + the engine supervisor) — every gate fired at least once,
# recovered decisions bit-identical to a fault-free run, zero pods lost
# or doubly bound. Part of tier-1 (tests/test_faults.py); run it alone
# before shipping engine changes.
fault-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_faults.py -x -q \
	  -p no:cacheprovider -p no:randomly

# Pass-ladder attribution smoke at CPU shapes (headline + topology
# profiles): catches step/pass-cost regressions in the marginal-cost
# ladder without TPU hardware (tools/profile_step.py --passes).
profile-smoke:
	JAX_PLATFORMS=cpu $(PY) tools/profile_step.py --nodes 512 --pods 128 \
	  --passes
	JAX_PLATFORMS=cpu $(PY) tools/profile_step.py --nodes 512 --pods 128 \
	  --passes --c4

# Run the README scenario end-to-end (reference `make start`): 9
# unschedulable nodes + 1 pod pending → node10 added → pod bound.
start:
	$(CPU_MESH) $(PY) -m minisched_tpu.scenario.runner

# README scenario over the WIRE: a subprocess boots store + scheduler +
# HTTP apiserver; the client drives it purely through the socket
# (reference k8sapiserver + client-go pairing). Runs with bearer-token
# auth + flow control on, proving the reference's loopback-auth shape
# (k8sapiserver.go:139-153, :203-208).
start-remote:
	MINISCHED_API_TOKEN=dev-loopback-token MINISCHED_API_MAX_INFLIGHT=64 \
	  $(CPU_MESH) $(PY) -m minisched_tpu.scenario.remote

# The reference's true process shape (scheduler/scheduler.go:54-75): a
# store-only apiserver subprocess; the ENGINE runs in the client process
# as a pure network client (informers long-poll /watch, bindings commit
# through /bind), then the README scenario runs over the same wire.
start-client-engine:
	$(CPU_MESH) $(PY) -m minisched_tpu.scenario.remote --client-engine

# Advanced-feature demo: zone spread (with intra-batch skew arbitration),
# gang quorum, explain annotations.
demo:
	$(CPU_MESH) $(PY) -m minisched_tpu.scenario.demo

# Regenerate README's measured-numbers block from the committed
# BENCH_TPU.json + the plugin registry (tests/test_docs_numbers.py fails
# the suite when the committed prose drifts from the artifact).
docs:
	$(CPU_MESH) $(PY) tools/gen_docs.py

# Headline benchmark (BASELINE.md): 50k nodes x 10k pods on whatever
# accelerator jax picks. MINISCHED_BENCH_{NODES,PODS,REPEATS} override.
bench:
	$(PY) bench.py

# Sharded-step benchmark on the virtual 8-device CPU mesh (greedy chunked
# scan vs single device vs auction). MINISCHED_SHARDED_{NODES,PODS} override.
bench_sharded:
	$(PY) bench_sharded.py

# Bench-harness smoke at reduced shapes on CPU: every phase must produce
# a number (labelled platform cpu; no device metric comes from it).
bench-cpu:
	MINISCHED_BENCH_NODES=2000 MINISCHED_BENCH_PODS=500 \
	  MINISCHED_BENCH_PHASE_BUDGET=1200 JAX_PLATFORMS=cpu $(PY) bench.py

# Pipelined-vs-synchronous engine comparison at CPU shapes (the
# committed BENCH_PIPELINE.json modes section).
bench-pipeline:
	JAX_PLATFORMS=cpu $(PY) tools/bench_pipeline.py

# Device-residency before/after at CPU shapes, interleaved off/on
# rounds (the committed BENCH_RESIDENCY.json): per-batch h2d/fetch
# bytes + engine throughput, MINISCHED_DEVICE_RESIDENT=0 vs 1.
bench-residency:
	JAX_PLATFORMS=cpu $(PY) tools/bench_residency.py

# Shortlist-compressed arbitration before/after at CPU shapes,
# interleaved off/on rounds (the committed BENCH_SHORTLIST.json):
# decision-equality ledger, repair rate, and the sequential-scan-width
# reduction, MINISCHED_SHORTLIST=0 vs 1. The scan-width win is the TPU
# prize; the CPU artifact proves the equality + repair claims.
bench-shortlist:
	JAX_PLATFORMS=cpu $(PY) tools/bench_shortlist.py

# Flight-recorder contract bench at CPU shapes, interleaved off/on
# rounds (the committed BENCH_TRACE.json): recorder overhead ≤5% on the
# create→bound window, the engine_gap_s decomposition summing to the
# gap within 2%, the exported Chrome trace schema-valid with ≥95%
# scheduling-loop span coverage, and histogram counts covering every
# bound decision.
bench-trace:
	JAX_PLATFORMS=cpu $(PY) tools/bench_trace.py

# Temporal-telemetry contract bench at CPU shapes, interleaved off/on
# rounds (the committed BENCH_SLO.json): timeline+sentinel overhead
# ≤5% on the create→bound window at the worst-case every-batch
# cadence, zero alerts on clean rounds, and the faulted-churn round's
# early-warning chain (burn-rate alert BEFORE quarantine, counted
# supervisor reaction, per-generator attribution tags on the rows).
bench-slo:
	JAX_PLATFORMS=cpu $(PY) tools/bench_slo.py

# Overload-control contract bench (the committed BENCH_OVERLOAD.json):
# interleaved controller-off/on rounds of the same saturating
# priority-mixed churn phase — off: unbounded p99 growth baseline; on:
# counted low-priority shedding with the high-priority p99 bounded,
# zero invariant violations, every shed pod re-admitted, and a full
# brownout engage→recover cycle with the timeline-derived no-flap
# check. The armed round's stable keys append to BENCH_LEDGER.json
# (source bench-overload) so bench-check gates them.
bench-overload:
	JAX_PLATFORMS=cpu $(PY) tools/bench_overload.py

# Cross-run perf-regression gate: capture a fresh interleaved
# min-of-N run at the check shape (500 x 250 CPU) and diff it against
# the newest comparable entry of the committed BENCH_LEDGER.json with
# noise-aware per-key-class thresholds (tools/bench_compare.py),
# then a one-round overload capture gated on its CLAIM contract
# (tools/bench_overload.py --check; the cross-run key diff is
# advisory — overload keys scale with host speed). Nonzero exit =
# regression/claim failure. Bootstrap/refresh the baselines with
# `python tools/bench_compare.py --capture --update` /
# `python tools/bench_overload.py --check --update`.
bench-check:
	JAX_PLATFORMS=cpu $(PY) tools/bench_compare.py --capture
	JAX_PLATFORMS=cpu $(PY) tools/bench_overload.py --check
	JAX_PLATFORMS=cpu $(PY) tools/bench_deviceloop.py --check
	JAX_PLATFORMS=cpu $(PY) tools/bench_coldstart.py --check
	JAX_PLATFORMS=cpu $(PY) tools/bench_journal.py --check
	JAX_PLATFORMS=cpu $(PY) tools/bench_fleet.py --check
	JAX_PLATFORMS=cpu $(PY) tools/bench_fleet_proc.py --check
	JAX_PLATFORMS=cpu $(PY) tools/bench_election.py --check
	JAX_PLATFORMS=cpu $(PY) tools/bench_tenants.py --check
	JAX_PLATFORMS=cpu $(PY) tools/bench_auction.py --check

# Persistent device-loop before/after (the committed
# BENCH_DEVICELOOP.json): interleaved off/on min-of-4 rounds of the
# streaming phase at depth 8 — steps_dispatched per bound pod down
# ≥4×, one stacked decision readback per tranche
# (decision_fetches == steps_dispatched), a paired identical-workload
# run diffing every placement, and a fault-injected round proving the
# mid-tranche break-out replays per-batch with nothing lost and
# placements unchanged. Stable stream keys append to BENCH_LEDGER.json
# (source bench-deviceloop) so `make bench-check` gates them.
bench-deviceloop:
	JAX_PLATFORMS=cpu $(PY) tools/bench_deviceloop.py

# Decision-journal contract bench (the committed BENCH_JOURNAL.json):
# interleaved journal-off/on min-of-4 rounds — armed overhead ≤5% on
# the create→bound window with provenance recorded for every settled
# pod — plus one deterministic faulted round whose consecutive
# step-dispatch errors walk the ladder to quarantine, auto-capture a
# schema-valid incident bundle (tools/postmortem.py exits 0 on it), and
# whose causal narrative names the injected gate. Stable stream keys
# append to BENCH_LEDGER.json (source bench-journal) so `make
# bench-check` gates them.
bench-journal:
	JAX_PLATFORMS=cpu $(PY) tools/bench_journal.py

# Replicated-fleet contract bench (the committed BENCH_FLEET.json):
# the same saturated burst at 1/2/4 replicas (median-of-N wall-clock;
# the ≥1.5x 2-replica scaling claim gates only on ≥2-core hosts — on
# one core the gate is the ≤25% replication-tax bound, recorded as
# not-expressible in the artifact), the 2-replica clean-partition
# contract (zero stale-owner disposals, both shards served), and a
# kill-mid-burst failover phase: zero pods lost, exactly-once binds,
# journaled takeover within 2×TTL + scan slack, p99-under-failover
# bounded by the clean p99 + takeover budget. Stable keys append to
# BENCH_LEDGER.json (source bench-fleet) so `make bench-check` gates
# them.
bench-fleet:
	JAX_PLATFORMS=cpu $(PY) tools/bench_fleet.py

# Fused multi-tenant before/after (the committed BENCH_TENANTS.json):
# interleaved sequential/fused min-of-4 rounds of T=8 small virtual
# clusters — step dispatches per served tenant batch down ≥5× (one
# vmapped tranche serves the whole compat group; mid-tranche races fall
# back solo, counted), every paired placement bit-identical PER TENANT,
# a journal-armed probe proving zero cross-tenant provenance leakage,
# and a one-tenant overload burst held by the profile-scoped shed
# budget. Stable keys append to BENCH_LEDGER.json (source
# bench-tenants) so `make bench-check` gates them.
bench-tenants:
	JAX_PLATFORMS=cpu $(PY) tools/bench_tenants.py

# Auction-mode unification before/after (the committed
# BENCH_AUCTION.json): interleaved split/unified min-of-4 rounds of the
# streaming phase with MINISCHED_ASSIGNMENT=auction in both — the
# order-free debit mirror's residency carry (steady-state dynamic h2d
# per batch down ≥10×, batch 0 excluded), auction tranches fusing into
# the depth-8 ring (steps_dispatched per bound pod down ≥2×), the bid
# shortlist engaged with zero certification desyncs, a paired
# identical-workload run diffing every placement, and an
# auction_mirror:corrupt round proving the carry cross-check detects a
# scribbled mirror with placements unchanged. Stable stream keys append
# to BENCH_LEDGER.json (source bench-auction) so `make bench-check`
# gates them.
bench-auction:
	JAX_PLATFORMS=cpu $(PY) tools/bench_auction.py

# Cross-process compile-cache proof (the committed BENCH_COLDSTART.json;
# ROADMAP cold-start item): two child processes share one
# JAX_COMPILATION_CACHE_DIR directory — the first pays the real XLA
# compiles and populates it, the second (a fresh process) must load
# executables instead of compiling (warmup compile seconds ≈ 0). Keys
# append to BENCH_LEDGER.json (source bench-coldstart).
bench-coldstart:
	JAX_PLATFORMS=cpu $(PY) tools/bench_coldstart.py

# p99-under-churn bench (the committed BENCH_CHURN.json): interleaved
# clean/faulted lifecycle-churn rounds through bench.churn_bench —
# clean rounds must run undegraded (resident, zero fault fires),
# faulted rounds must exercise the supervisor ladder (escalations > 0)
# and recover to resident; every lifecycle invariant enforced after
# every event; latency keys histogram-derived over every bound pod.
bench-churn:
	JAX_PLATFORMS=cpu $(PY) tools/bench_churn.py

# Compile-check the flagship single-chip step and the multi-chip sharded
# step on an 8-device virtual mesh.
dryrun:
	$(CPU_MESH) $(PY) __graft_entry__.py

# Multi-PROCESS (DCN) dryrun: two OS processes federate their CPU devices
# via jax.distributed; the product sharded step runs over the hybrid
# (pod=DCN, node=ICI) mesh with cross-process collectives and must match
# single-device bit-for-bit (minisched_tpu/parallel/dcn_dryrun.py).
dryrun-dcn:
	JAX_PLATFORMS=cpu $(PY) -m minisched_tpu.parallel.dcn_dryrun

# Concurrency soak: repeat the chaos suite (threaded churn + invariants).
# SOAK_N overrides the repeat count.
SOAK_N ?= 5
soak:
	@for i in $$(seq 1 $(SOAK_N)); do \
	  $(CPU_MESH) $(PY) -m pytest tests/test_chaos.py -x -q || exit 1; \
	done

# Chaos soak under a low AMBIENT fault rate (the faulted churn variant
# in tests/test_chaos.py): each iteration reseeds the fault PRNG so
# successive runs land faults on different race interleavings, while
# any failing iteration replays exactly from its seed
# (MINISCHED_FAULT_SEED=<i>).
soak-faults:
	@for i in $$(seq 1 $(SOAK_N)); do \
	  echo "soak-faults iteration $$i (MINISCHED_FAULT_SEED=$$i)"; \
	  MINISCHED_FAULT_SEED=$$i $(CPU_MESH) $(PY) -m pytest \
	    tests/test_chaos.py -x -q || exit 1; \
	done

# Lifecycle-churn soak: repeat the scenario-engine suite reseeding the
# generator streams (and the fault PRNG for the faulted-churn case)
# per iteration — successive runs explore different workload-dynamics
# interleavings while any failing iteration replays exactly from its
# seed (MINISCHED_LIFECYCLE_SEED=<i>).
soak-churn:
	@for i in $$(seq 1 $(SOAK_N)); do \
	  echo "soak-churn iteration $$i (MINISCHED_LIFECYCLE_SEED=$$i)"; \
	  MINISCHED_LIFECYCLE_SEED=$$i MINISCHED_FAULT_SEED=$$i $(CPU_MESH) \
	    $(PY) -m pytest tests/test_lifecycle.py -x -q || exit 1; \
	done

# Composed fault+overload ladder soak: repeat the overload suite
# reseeding the lifecycle generator streams AND the fault PRNG per
# iteration — each run lands the injected faults and the saturation
# curve on different interleavings of the two ladders, while any
# failing iteration replays exactly from its seeds.
soak-overload:
	@for i in $$(seq 1 $(SOAK_N)); do \
	  echo "soak-overload iteration $$i (MINISCHED_LIFECYCLE_SEED=$$i)"; \
	  MINISCHED_LIFECYCLE_SEED=$$i MINISCHED_FAULT_SEED=$$i \
	    JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_overload.py -x -q \
	    -p no:cacheprovider -p no:randomly || exit 1; \
	done
