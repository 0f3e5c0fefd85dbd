"""Deterministic fault-schedule suite (faults.py + the engine supervisor).

The acceptance bar this file pins: with faults injected at every gate on
a deterministic schedule, the engine completes the workload with zero
lost and zero doubly-bound pods, decisions after recovery bit-identical
to a fault-free run (the supervisor rewinds the PRNG step counter so a
degraded replay draws the aborted attempt's randomness), and
``Scheduler.metrics()`` reports the exact injected fire counts. With no
spec armed the gates are no-ops.

Layout: grammar/registry units first, then one focused engine test per
containment path (inline ladder retry, residency desync detector, bulk
bind reconcile, commit-worker death drain/restart, quarantine rung),
then the out-of-engine gates (http over a REAL flaky server, checkpoint
crash-consistency), and finally a whole-suite assertion that every gate
in the catalog fired at least once (meaningful on a full-file run, the
tier-1 shape).
"""
import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from minisched_tpu import faults
from minisched_tpu.apiserver import APIServer, RemoteStore
from minisched_tpu.config import SchedulerConfig
from minisched_tpu.engine.scheduler import DEGRADATION_LADDER, _Supervisor
from minisched_tpu.faults import (FAULTS, GATES, FaultInjected,
                                  FaultWorkerDeath, parse_spec)
from minisched_tpu.scenario import Cluster
from minisched_tpu.service.defaultconfig import Profile
from minisched_tpu.state import objects as obj
from minisched_tpu.state.persistence import Checkpointer
from minisched_tpu.state.store import ClusterStore

#: Per-gate fires accumulated across the whole module run — evidence
#: for test_zz_every_gate_fired. configure() resets the registry's own
#: counters, so every reconfigure must bank through _configure below.
FIRED = {g: 0 for g in GATES}


def _bank():
    for g, n in FAULTS.counts().items():
        FIRED[g] += n


def _configure(spec, seed=0):
    _bank()
    faults.configure(spec, seed)


@pytest.fixture(autouse=True)
def registry():
    """Every test starts disarmed and leaves disarmed; whatever it fired
    is banked into FIRED on the way out."""
    _configure("")
    yield FAULTS
    _configure("")


# ---- grammar / registry units -------------------------------------------


def test_spec_grammar_accepts_catalog_forms():
    rules = parse_spec("step:err@0.02,fetch:corrupt@3,commit:die@once,"
                       "informer:stall@2s,bind:err@5,"
                       "residency:stall@50msx0.25")
    by_gate = {r.gate: r for r in rules}
    assert by_gate["step"].prob == pytest.approx(0.02)
    assert by_gate["fetch"].nth == 3 and by_gate["fetch"].action == "corrupt"
    assert by_gate["commit"].nth == 1 and by_gate["commit"].action == "die"
    assert by_gate["informer"].stall_s == pytest.approx(2.0)
    assert by_gate["informer"].nth == 1  # bare duration = fire once
    assert by_gate["bind"].nth == 5
    r = by_gate["residency"]
    assert r.stall_s == pytest.approx(0.05) and r.prob == pytest.approx(0.25)


@pytest.mark.parametrize("bad", [
    "nope:err@1",        # unknown gate
    "step:frob@1",       # unknown action
    "step:err@zzz",      # junk trigger
    "step:err@1.5",      # probability must be < 1
    "step:err@0",        # call numbers are 1-based
    "step:stall@3",      # stall needs a duration
    "step:err",          # no trigger at all
])
def test_spec_grammar_rejects_junk(bad):
    with pytest.raises(ValueError):
        parse_spec(bad)


def test_unarmed_registry_is_noop(registry):
    assert not registry.enabled
    assert registry.hit("step") is None
    # unarmed hits are not even counted — the gate is a single attribute
    # test on the hot path
    assert registry.calls()["step"] == 0
    assert all(v == 0 for v in registry.counts().values())


def test_nth_trigger_fires_exactly_once(registry):
    _configure("step:err@3")
    fired = 0
    for _ in range(10):
        try:
            registry.hit("step")
        except FaultInjected:
            fired += 1
    assert fired == 1 and registry.counts()["step"] == 1
    assert registry.calls()["step"] == 10


def test_probability_trigger_is_seed_reproducible(registry):
    def pattern():
        _configure("step:err@0.5", seed=42)
        out = []
        for _ in range(64):
            try:
                registry.hit("step")
                out.append(0)
            except FaultInjected:
                out.append(1)
        return out

    first, second = pattern(), pattern()
    assert first == second
    assert 0 < sum(first) < 64  # it genuinely fires sometimes, not always


@pytest.mark.parametrize("gate,offset", [
    ("journal", 12), ("lease", 13), ("auction_mirror", 14), ("proc", 15),
    ("election", 16)])
def test_gate_stream_keeps_its_seed_offset(registry, gate, offset):
    """A gate's probability stream under a fixed seed does not move when
    another gate leaves the catalog: these gates sat after the retired
    ``index`` gate and keep the streams their old catalog positions
    seeded."""
    import random

    _configure(f"{gate}:corrupt@0.5", seed=42)
    got = [registry.hit(gate) == "corrupt" for _ in range(64)]
    rng = random.Random((42 << 8) ^ offset)
    assert got == [rng.random() < 0.5 for _ in range(64)]


def test_stall_action_sleeps_and_counts(registry):
    _configure("step:stall@60ms")
    t0 = time.perf_counter()
    assert registry.hit("step") is None  # stall returns, never raises
    assert time.perf_counter() - t0 >= 0.05
    assert registry.counts()["step"] == 1


def test_die_action_is_distinguishable(registry):
    _configure("commit:die@once")
    with pytest.raises(FaultWorkerDeath):
        registry.hit("commit")
    # FaultWorkerDeath IS a FaultInjected (generic containment still
    # catches it where that is the right behavior)
    assert issubclass(FaultWorkerDeath, FaultInjected)


def test_supervisor_ladder_and_probation_unit():
    class _FakeSched:
        config = SchedulerConfig(probation_batches=2)

        def __init__(self):
            self.counts = {}

        def _sup_count(self, k, n=1):
            self.counts[k] = self.counts.get(k, 0) + n

        def _slo_burning_any(self):
            # no SLO sentinel in this unit harness (the burning gate
            # has its own suite in tests/test_timeline.py)
            return False

    fake = _FakeSched()
    sup = _Supervisor(fake)
    assert DEGRADATION_LADDER[sup.level] == "resident"
    assert sup.allows_residency() and not sup.sync_only()
    for expect in ("upload", "sync", "quarantine"):
        sup.escalate("test")
        assert DEGRADATION_LADDER[sup.level] == expect
    sup.escalate("test")  # bottom rung is sticky, not an overflow
    assert DEGRADATION_LADDER[sup.level] == "quarantine"
    assert sup.sync_only() and not sup.allows_residency()
    # probation: 2 clean batches per rung on the way back up
    for expect in ("sync", "upload", "resident"):
        sup.note_clean()
        sup.note_clean()
        assert DEGRADATION_LADDER[sup.level] == expect
    sup.note_clean()  # clean at the top is a no-op
    assert sup.level == 0
    assert fake.counts["supervisor_escalations"] == 3
    assert fake.counts["supervisor_recoveries"] == 3
    # a mid-probation fault resets the clean streak
    sup.escalate("test")
    sup.note_clean()
    sup.escalate("test")
    sup.note_clean()
    assert DEGRADATION_LADDER[sup.level] == "sync"


# ---- engine containment (one Cluster burst per path) --------------------

PLUGINS = ["NodeUnschedulable", "NodeResourcesFit",
           "NodeResourcesLeastAllocated"]
N_SCHED, N_DOOMED = 18, 2


def _config(pipeline=True, **kw):
    kw.setdefault("max_batch_size", 6)
    kw.setdefault("batch_window_s", 0.3)
    kw.setdefault("backoff_initial_s", 0.05)
    kw.setdefault("backoff_max_s", 0.3)
    kw.setdefault("probation_batches", 1)
    return SchedulerConfig(pipeline=pipeline, **kw)


def _make_nodes(c):
    # Distinct capacities: LeastAllocated fractions diverge as soon as a
    # node hosts anything, keeping score ties (PRNG territory) rare.
    for i, cpu in enumerate((64000, 48000, 40000, 36000)):
        c.create_node(f"n{i}", cpu=cpu)


def _make_pods():
    """18 schedulable pods with unique priorities/sizes (deterministic
    pop + scan order) followed by 2 doomed ones (cpu no node carries) at
    the LOWEST priorities — they form the final batch and give the
    commit path a real failure tranche to flush."""
    pods, pri = [], 500
    for i in range(N_SCHED):
        pods.append(obj.Pod(
            metadata=obj.ObjectMeta(name=f"p{i}", namespace="default"),
            spec=obj.PodSpec(requests={"cpu": 100 + 17 * i}, priority=pri)))
        pri -= 1
    for i in range(N_DOOMED):
        pods.append(obj.Pod(
            metadata=obj.ObjectMeta(name=f"doom{i}", namespace="default"),
            spec=obj.PodSpec(requests={"cpu": 10 ** 9}, priority=pri)))
        pri -= 1
    return pods


def _run_burst(spec, config, seed=0, settle_s=120):
    """One full engine run under fault spec ``spec``; returns
    (schedulable placements {name: node}, final metrics)."""
    c = Cluster()
    try:
        c.start(profile=Profile(plugins=list(PLUGINS)), config=config,
                with_pv_controller=False)
        _configure(spec, seed)
        _make_nodes(c)
        c.create_objects(_make_pods())
        sched_names = [f"p{i}" for i in range(N_SCHED)]
        doom_names = [f"doom{i}" for i in range(N_DOOMED)]
        deadline = time.monotonic() + settle_s
        placements, parked = {}, set()
        while time.monotonic() < deadline:
            placements, parked = {}, set()
            for p in c.list_pods():
                if p.spec.node_name:
                    placements[p.metadata.name] = p.spec.node_name
                elif p.status.unschedulable_plugins:
                    parked.add(p.metadata.name)
            if (all(n in placements for n in sched_names)
                    and all(n in parked for n in doom_names)):
                break
            time.sleep(0.05)
        assert all(n in placements for n in sched_names), {
            n for n in sched_names if n not in placements}
        assert all(n in parked for n in doom_names), parked
        m = c.service.scheduler.metrics()
        # zero lost (asserted above), zero doubly-bound: every bind the
        # engine counted corresponds to exactly one uniquely-placed pod
        assert m["pods_bound"] == len(placements), (
            m["pods_bound"], len(placements))
        # let the supervisor walk probation back to the full fast path,
        # feeding it clean probe batches as needed
        sched = c.service.scheduler
        probe = 0
        deadline = time.monotonic() + 30
        while (sched.metrics()["degradation_state"] != "resident"
               and time.monotonic() < deadline):
            c.create_pod(f"probe{probe}", cpu=10)
            c.wait_for_pod_bound(f"probe{probe}", timeout=30)
            probe += 1
            time.sleep(0.1)
        m = sched.metrics()
        return placements, m
    finally:
        _configure("")
        c.shutdown()


@pytest.mark.parametrize("pipeline", [False, True])
def test_engine_gates_fire_and_recovered_decisions_are_bit_identical(
        pipeline):
    """The flagship schedule: a step fault (exception containment), a
    corrupted decision readback (DETECTOR containment), a commit-flush
    fault, and an informer dispatch fault — each fired exactly once at a
    deterministic call. The engine must absorb all of them, finish at
    degradation-state "resident", report the EXACT fire counts, and
    place every pod on the node the fault-free run chose (the
    supervisor's PRNG-rewind replay contract)."""
    cfg = _config(pipeline=pipeline)
    ref_placed, ref_m = _run_burst("", cfg)
    assert ref_m["batch_faults"] == 0 and ref_m["watchdog_trips"] == 0
    assert all(v == 0 for k, v in ref_m.items()
               if k.startswith("fault_fires_"))
    assert ref_m["degradation_state"] == "resident"

    spec = "step:err@2,fetch:corrupt@3,commit:err@1,informer:err@1"
    placed, m = _run_burst(spec, cfg)
    for gate in ("step", "fetch", "commit", "informer"):
        assert m[f"fault_fires_{gate}"] == 1, (gate, m)
    for gate in ("residency", "bind", "http", "checkpoint"):
        assert m[f"fault_fires_{gate}"] == 0, (gate, m)
    assert m["batch_faults"] >= 1
    assert m["batch_retries"] >= 1
    assert m["supervisor_escalations"] >= 1
    assert m["supervisor_recoveries"] >= 1
    assert m["degradation_state"] == "resident"
    assert placed == ref_placed  # bit-identical recovery


def test_fetch_corrupt_on_first_batch_hits_detector_not_slim_revert():
    """A corrupt readback on the ENGINE'S FIRST fetch must trip the
    resolve sanity detector like any other batch — not be misread by the
    first-batch byte-order cross-check as an exotic backend (which would
    silently absorb the injection, skip the supervisor entirely, and
    permanently revert the slim fast path)."""
    cfg = _config(pipeline=False)
    ref_placed, _ = _run_burst("", cfg)
    placed, m = _run_burst("fetch:corrupt@1", cfg)
    assert m["fault_fires_fetch"] == 1
    assert m["batch_faults"] >= 1      # the DETECTOR saw it
    assert m["supervisor_escalations"] >= 1
    assert m["degradation_state"] == "resident"
    assert placed == ref_placed


def test_residency_corrupt_trips_desync_detector_and_resyncs():
    """ROADMAP residency follow-up (b): with the carry cross-check armed
    (resident_check_every=1), a scribbled host mirror — the seam-specific
    ``residency:corrupt`` payload — must be DETECTED before the step
    consumes the carry, counted as a desync, and healed by a full
    re-upload; decisions stay bit-identical to the fault-free run."""
    cfg = _config(pipeline=False, resident_check_every=1)
    ref_placed, ref_m = _run_burst("", cfg)
    assert ref_m["resident_checks"] >= 2  # the detector genuinely ran
    assert ref_m["residency_desyncs"] == 0

    placed, m = _run_burst("residency:corrupt@2", cfg)
    assert m["fault_fires_residency"] == 1
    assert m["residency_desyncs"] >= 1
    assert m["supervisor_escalations"] >= 1
    assert m["degradation_state"] == "resident"
    assert placed == ref_placed


def test_auction_mirror_corrupt_trips_desync_detector_and_resyncs():
    """Auction-unification detector: ``auction_mirror:corrupt``
    scribbles ONE node's aggregate debit inside the order-free host
    mirror replay (_DeviceResidency.note_debits) — certificate-invisible
    by construction (the mirror is pure host bookkeeping; no in-step
    check ever sees it). With the carry cross-check armed
    (resident_check_every=1) on an AUCTION engine, the mirror-vs-device
    comparison must catch the divergence before a step consumes the
    carry, count a residency desync, heal by full re-upload, and the
    supervised replay must land every pod on the fault-free run's
    node."""
    cfg = _config(pipeline=False, assignment="auction",
                  resident_check_every=1)
    ref_placed, ref_m = _run_burst("", cfg)
    assert ref_m["resident_checks"] >= 2  # the detector genuinely ran
    assert ref_m["residency_desyncs"] == 0

    placed, m = _run_burst("auction_mirror:corrupt@2", cfg)
    assert m["fault_fires_auction_mirror"] == 1
    assert m["residency_desyncs"] >= 1
    assert m["supervisor_escalations"] >= 1
    assert m["degradation_state"] == "resident"
    assert placed == ref_placed


def test_shortlist_corrupt_caught_by_certification_cross_check():
    """Shortlist tentpole detector: ``shortlist_repair:corrupt``
    re-points an assigned pod's fetched chosen row at a DIFFERENT valid
    node — a shortlist mispick the in-step certificate should have
    repaired, deliberately invisible to the range sanity check. With the
    certification cross-check armed (shortlist_check_every=1) the
    full-scan comparison must catch it, count a shortlist_desync,
    permanently revert the engine to the full-width scan
    (shortlist_width gauge -> 0), and the supervised replay must land
    every pod on the fault-free run's node."""
    cfg = _config(pipeline=False, shortlist_check_every=1)
    ref_placed, ref_m = _run_burst("", cfg)
    assert ref_m["shortlist_checks"] >= 2   # the detector genuinely ran
    assert ref_m["shortlist_desyncs"] == 0
    assert ref_m["shortlist_width"] > 0

    placed, m = _run_burst("shortlist_repair:corrupt@2", cfg)
    assert m["fault_fires_shortlist_repair"] == 1
    assert m["shortlist_desyncs"] == 1
    assert m["shortlist_width"] == 0        # reverted to the full scan
    assert m["batch_faults"] >= 1
    assert m["supervisor_escalations"] >= 1
    assert m["degradation_state"] == "resident"
    assert placed == ref_placed


def test_bind_gate_reconciles_without_losing_or_double_binding():
    """An aborted bulk bind task reconciles per pod against store truth:
    unbound pods are unassumed + requeued (never lost), already-bound
    pods keep exactly one bind (never doubled). _run_burst's
    pods_bound == placements assertion is the double-bind sentinel."""
    placed, m = _run_burst("bind:err@1", _config(pipeline=True))
    assert m["fault_fires_bind"] == 1
    assert len(placed) >= N_SCHED


def test_commit_worker_death_drains_restarts_and_stays_live():
    """commit:die escapes the commit worker's normal exception guard
    like a dying thread: the supervisor must drain the pipeline slot,
    restart the worker, requeue the dead flush's tranche, and keep the
    engine serving — the doomed pods still get their terminal verdicts
    (flushed by the RESTARTED worker) and fresh traffic still binds."""
    placed, m = _run_burst("commit:die@once", _config(pipeline=True))
    assert m["fault_fires_commit"] == 1
    assert m["worker_deaths"] == 1
    assert m["supervisor_escalations"] >= 1
    assert m["degradation_state"] == "resident"
    assert len(placed) >= N_SCHED


def test_quarantine_rung_requeues_and_still_never_loses_pods():
    """Three consecutive step faults exhaust the ladder
    (resident → upload → sync → quarantine): the poisoned batch is
    requeued at the backoff ceiling instead of retried, the loop stays
    un-wedged, and when the pods return past the quiet window they bind
    normally — zero pods lost at the bottom rung."""
    placed, m = _run_burst("step:err@1,step:err@2,step:err@3",
                           _config(pipeline=True))
    assert m["fault_fires_step"] == 3
    assert m["quarantined_batches"] >= 1
    assert m["supervisor_escalations"] >= 3
    assert m["degradation_state"] == "resident"  # probation walked back
    assert len(placed) >= N_SCHED


# ---- RemoteStore under a flaky server (satellite) -----------------------


class _FlakyHandler(BaseHTTPRequestHandler):
    """Scripted failure server: per-instance class state set by the
    fixture. ``script`` is a list consumed one entry per request —
    "reset" (close without answering), an int status (JSON error body),
    or "ok" (echo a minimal success payload)."""

    script = []
    seen = []

    def _take(self):
        self.seen.append((self.command, self.path))
        return self.script.pop(0) if self.script else "ok"

    def _respond(self, status, body: bytes):
        self.send_response(status)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _handle(self):
        n = int(self.headers.get("Content-Length") or 0)
        body = self.rfile.read(n) if n else b""
        step = self._take()
        if step == "reset":
            # hard connection abort mid-exchange
            self.connection.close()
            return
        if step == "ok":
            if self.command == "GET":
                self._respond(200, json.dumps(
                    {"items": [], "resource_version": 0}).encode())
            else:
                self._respond(200, body or b"{}")  # echo (create contract)
            return
        reason = ("ServiceUnavailable" if step == 503 else None)
        self._respond(step, json.dumps(
            {"error": f"injected {step}", "reason": reason}).encode())

    do_GET = do_POST = do_PUT = do_DELETE = _handle

    def log_message(self, *a):  # keep pytest output clean
        pass


@pytest.fixture
def flaky():
    class H(_FlakyHandler):
        script, seen = [], []

    srv = ThreadingHTTPServer(("127.0.0.1", 0), H)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    rs = RemoteStore(f"http://127.0.0.1:{srv.server_address[1]}",
                     qps=0, retry_deadline_s=5.0)
    yield H, rs
    srv.shutdown()
    srv.server_close()


def test_remote_store_get_retries_reset_and_5xx(flaky):
    H, rs = flaky
    H.script[:] = ["reset", 500, "ok"]
    assert rs.list("Pod") == []
    # one logical call, three wire attempts
    assert len(H.seen) == 3


def test_remote_store_mutation_5xx_is_not_blindly_retried(flaky):
    H, rs = flaky
    # a bare 500 on a mutation is ambiguous (may have applied): propagate
    H.script[:] = [500, "ok"]
    pod = obj.Pod(metadata=obj.ObjectMeta(name="x", namespace="default"),
                  spec=obj.PodSpec(requests={"cpu": 1}))
    with pytest.raises(RuntimeError):
        rs.create(pod)
    assert len(H.seen) == 1
    # but a 503 drain reject answered WITHOUT touching the store is
    # provably-unapplied and retries
    H.seen.clear()
    H.script[:] = [503, "ok"]
    out = rs.create(pod)
    assert out.metadata.name == "x"
    assert len(H.seen) == 2


def test_remote_store_retry_deadline_bounds_the_absorption(flaky):
    H, rs = flaky
    rs.retry_deadline_s = 0.4
    H.script[:] = [500] * 50
    t0 = time.monotonic()
    with pytest.raises(RuntimeError):
        rs.list("Pod")
    assert 0.3 <= time.monotonic() - t0 <= 5.0


def test_http_gate_fault_absorbed_by_retry(registry):
    store = ClusterStore()
    api = APIServer(store).start()
    try:
        rs = RemoteStore(api.address, retry_deadline_s=5.0)
        _configure("http:err@1")
        assert rs.list("Node") == []  # injected wire fault absorbed
        assert registry.counts()["http"] == 1
        # with absorption disabled the same fault is caller-visible
        _configure("http:err@1")
        rs.retry_deadline_s = 0.0
        with pytest.raises(FaultInjected):
            rs.list("Node")
    finally:
        api.shutdown()


# ---- checkpoint gate: crash consistency (satellite) ---------------------


def test_checkpoint_fault_preserves_previous_snapshot(tmp_path, registry):
    path = str(tmp_path / "state.json")
    store = ClusterStore()
    store.create(obj.Node(metadata=obj.ObjectMeta(name="ck-n0"),
                          spec=obj.NodeSpec(),
                          status=obj.NodeStatus(allocatable={"cpu": 1})))
    cp = Checkpointer(store, path)
    assert cp.checkpoint() is True
    rv0 = json.load(open(path))["resource_version"]
    store.create(obj.Node(metadata=obj.ObjectMeta(name="ck-n1"),
                          spec=obj.NodeSpec(),
                          status=obj.NodeStatus(allocatable={"cpu": 1})))
    _configure("checkpoint:err@1")
    with pytest.raises(FaultInjected):
        cp.checkpoint()
    # the fault fired BEFORE any disk touch: the previous complete
    # snapshot is byte-for-byte still there
    assert json.load(open(path))["resource_version"] == rv0
    assert registry.counts()["checkpoint"] == 1
    _configure("")
    assert cp.checkpoint() is True
    assert json.load(open(path))["resource_version"] > rv0
    cp.close()


# ---- lifecycle gate: scenario-driver step faults (PR 7) -----------------


def test_lifecycle_gate_skips_steps_but_loses_nothing(registry):
    """The ``lifecycle`` gate composes workload churn with the fault
    registry: an err at the scenario driver's step seam skips the tick
    (counted) and retries it shortly after — the generator still
    completes its schedule and the ledger stays whole."""
    from minisched_tpu.lifecycle import LifecycleDriver, PoissonArrivals
    from minisched_tpu.scenario import Cluster

    c = Cluster()  # no engine: pure generation
    d = LifecycleDriver(c, seed=3)
    d.add(PoissonArrivals("arrivals", rate_pps=40, duration_s=1.0,
                          prefix="flt"))
    d.install_default_invariants()
    _configure("lifecycle:err@2,lifecycle:err@5")
    d.run()
    assert registry.counts()["lifecycle"] == 2
    assert d.faulted_steps == 2
    assert d.view.counters.get("pods_created", 0) > 5
    d.check_invariants()


# ---- admission gate: queue-ingress shed path (PR 10) --------------------


def test_admission_gate_corrupt_sheds_and_flusher_readmits(registry):
    """``admission:corrupt`` force-sheds an ingress transaction into the
    overload shed lane even with the controller OFF — the chaos handle
    on the shed path. Nothing is lost: the backoff flusher re-offers the
    pod to the (absent) gate and re-admits it; ``err`` models the
    verdict machinery dying and FAILS OPEN (the pod is admitted)."""
    from minisched_tpu.engine.queue import SchedulingQueue

    q = SchedulingQueue({}, backoff_initial=0.05, backoff_max=0.2)
    q.set_admission(None, backoff_fn=lambda: (0.1, 0.5))
    try:
        _configure("admission:corrupt@1,admission:err@2")
        p1 = obj.Pod(metadata=obj.ObjectMeta(name="shed-me",
                                             namespace="default"),
                     spec=obj.PodSpec(requests={"cpu": 10}))
        q.add(p1)  # corrupt fires: force-shed
        st = q.stats()
        assert st["shed"] == 1 and st["shed_total"] == 1
        assert st["active"] == 0
        p2 = obj.Pod(metadata=obj.ObjectMeta(name="fail-open",
                                             namespace="default"),
                     spec=obj.PodSpec(requests={"cpu": 10}))
        q.add(p2)  # err fires: ingress fails open, pod is admitted
        assert q.stats()["active"] == 1
        assert registry.counts()["admission"] == 2
        # never dropped: the flusher re-admits the shed pod
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            st = q.stats()
            if st["shed"] == 0 and st["active"] == 2:
                break
            time.sleep(0.02)
        st = q.stats()
        assert st["shed"] == 0 and st["active"] == 2, st
        assert st["shed_readmitted"] == 1
        batch = q.pop_batch(4, timeout=1.0)
        assert {b.pod.metadata.name for b in batch} == {"shed-me",
                                                        "fail-open"}
    finally:
        q.close()


# ---- journal gate (obs/journal.py) ---------------------------------------


def test_journal_gate_err_drops_event_corrupt_scribbles_seq(registry):
    """The ``journal`` gate sits on the event write: err drops the
    event (counted — history lost, nothing else), corrupt scribbles
    the recorded seq field while the internal order stays exact, and
    the registry's own fault.journal fire event never re-traverses the
    gate (recursion guard)."""
    from minisched_tpu.obs import journal as journal_mod

    journal_mod.configure("1")
    try:
        _configure("journal:err@1")
        journal_mod.note("test.dropped")
        assert journal_mod.JOURNAL.dropped_by_fault == 1
        # the gate's own fire event IS recorded (it skips the gate);
        # the original event is what the err dropped
        assert [e["kind"] for e in journal_mod.JOURNAL.entries()] == [
            "fault.journal"]
        journal_mod.note("test.kept")  # gate call #2: no fire
        assert [e["kind"] for e in journal_mod.JOURNAL.entries()] == [
            "fault.journal", "test.kept"]

        journal_mod.configure("1")
        _configure("journal:corrupt@1")
        journal_mod.note("test.scribbled")
        ents = journal_mod.JOURNAL.entries()
        # the gate's own fire event lands first (it skips the gate),
        # then the scribbled-seq original
        assert [e["kind"] for e in ents] == ["fault.journal",
                                             "test.scribbled"]
        assert ents[0]["seq"] == 1
        assert ents[1]["seq"] >= (1 << 30)  # observable scribble
    finally:
        journal_mod.configure("")


def test_journal_fault_never_touches_decisions(registry):
    """Bit-identity under an err'd journal: a run whose every journal
    write fails must place every pod exactly where the clean run did —
    the recorder is an observer, never an input."""
    from minisched_tpu.obs import journal as journal_mod

    def run():
        c = Cluster()
        try:
            c.start(profile=Profile(plugins=[
                        "NodeUnschedulable", "NodeResourcesFit",
                        "NodeResourcesLeastAllocated"]),
                    config=SchedulerConfig(max_batch_size=8,
                                           batch_window_s=0.3,
                                           batch_idle_s=0.1,
                                           backoff_initial_s=0.05,
                                           backoff_max_s=0.3),
                    with_pv_controller=False)
            for i, cpu in enumerate((64000, 48000)):
                c.create_node(f"n{i}", cpu=cpu)
            c.create_objects([obj.Pod(
                metadata=obj.ObjectMeta(name=f"jf{i}",
                                        namespace="default"),
                spec=obj.PodSpec(requests={"cpu": 100 + 13 * i}))
                for i in range(12)])
            deadline = time.monotonic() + 60
            placed = {}
            while time.monotonic() < deadline:
                placed = {p.metadata.name: p.spec.node_name
                          for p in c.list_pods() if p.spec.node_name}
                if len(placed) == 12:
                    break
                time.sleep(0.05)
            assert len(placed) == 12
            return placed
        finally:
            c.shutdown()

    base = run()
    journal_mod.configure("1")
    try:
        # nth-form rules: the first two journal writes deterministically
        # err (engine.start is write #1 — losing the run marker must
        # still not move a placement)
        _configure("journal:err@1,journal:err@2")
        armed = run()
        assert armed == base
        assert journal_mod.JOURNAL.dropped_by_fault >= 1
    finally:
        journal_mod.configure("")


# ---- lease gate (fleet/lease.py) -----------------------------------------


def test_lease_gate_err_drops_heartbeat(registry):
    """``lease:err`` drops the renewal before it reaches the store —
    counted, journaled, and the store's ``renewed_at`` stamp unmoved.
    Miss enough in a row and the lease expires under a live holder: the
    degraded-network failure mode the takeover scan is built for."""
    from minisched_tpu.fleet.lease import LeaseManager

    store = ClusterStore()
    clk = [0.0]
    mgr = LeaseManager(store, "rA", ttl_s=10.0, clock=lambda: clk[0])
    assert mgr.try_acquire(0)
    stamp = store.get("Lease", "shard-0").renewed_at
    _configure("lease:err@1")
    clk[0] = 1.0
    assert mgr.renew(0) is False
    assert mgr.counters["heartbeats_dropped"] == 1
    # The write never left the replica: store truth is untouched.
    assert store.get("Lease", "shard-0").renewed_at == stamp
    assert mgr.holds(0)  # a dropped heartbeat is not a loss
    # Gate consumed (nth-form): the next heartbeat lands cleanly.
    assert mgr.renew(0) is True
    assert store.get("Lease", "shard-0").renewed_at == 1.0


def test_lease_gate_corrupt_stale_heartbeat_loses_cas(registry):
    """``lease:corrupt`` sends the heartbeat with a REWOUND
    resource_version — the store CAS must reject it by construction.
    The rejection is counted and store truth (holder, epoch, stamp)
    stays exactly as the last honest write left it."""
    from minisched_tpu.fleet.lease import LeaseManager

    store = ClusterStore()
    clk = [0.0]
    mgr = LeaseManager(store, "rA", ttl_s=10.0, clock=lambda: clk[0])
    assert mgr.try_acquire(0)
    before = store.get("Lease", "shard-0")
    _configure("lease:corrupt@1")
    clk[0] = 1.0
    assert mgr.renew(0) is False
    assert mgr.counters["stale_heartbeats_rejected"] == 1
    after = store.get("Lease", "shard-0")
    assert (after.holder, after.epoch, after.renewed_at) == \
        ("rA", before.epoch, before.renewed_at)
    # The replica itself is undecided, not deposed: the next CLEAN
    # renewal re-reads store truth and recommits honestly.
    assert mgr.renew(0) is True
    assert store.get("Lease", "shard-0").renewed_at == 1.0


def test_corrupted_lease_cannot_mint_two_owners(registry):
    """Containment: a zombie holder whose every heartbeat is corrupt can
    never keep its shard against a live peer, and at NO point does the
    store name two owners or let the epoch move without a CAS win. The
    zombie window (both replicas locally believing they hold) is real —
    and exactly what the epoch fence + bind CAS make harmless — but
    store truth is singular throughout."""
    from minisched_tpu.fleet.lease import LeaseManager

    store = ClusterStore()
    clk = [0.0]
    zombie = LeaseManager(store, "rZ", ttl_s=1.0, clock=lambda: clk[0])
    peer = LeaseManager(store, "rP", ttl_s=1.0, clock=lambda: clk[0])
    assert zombie.try_acquire(0)
    # Every zombie heartbeat from here on is a stale-rv write.
    _configure("lease:corrupt@1,lease:corrupt@2,lease:corrupt@3")
    clk[0] = 0.5
    assert zombie.renew(0) is False  # rejected; lease ages on
    assert store.get("Lease", "shard-0").renewed_at == 0.0
    clk[0] = 1.5  # past TTL: the un-renewed lease is now expired
    assert peer.try_acquire(0)  # honest claim, epoch 1 -> 2
    truth = store.get("Lease", "shard-0")
    assert (truth.holder, truth.epoch) == ("rP", 2)
    # Zombie window: both hold locally, but store truth is singular and
    # the zombie's next heartbeat — corrupt or not — discovers the
    # supersession BEFORE it could write anything.
    assert zombie.holds(0) and peer.holds(0)
    assert zombie.renew(0) is False
    assert not zombie.holds(0)  # deposed: lease.lose journaled
    assert zombie.counters["losses"] == 1
    truth = store.get("Lease", "shard-0")
    assert (truth.holder, truth.epoch) == ("rP", 2)
    # Epochs only ever moved through CAS wins: 1 (create) -> 2 (claim).
    assert peer.epoch_of(0) == 2 and zombie.epoch_of(0) == 0


def test_proc_gate_err_fails_spawn_with_capped_backoff(registry):
    """``proc:err`` fails a replica-process SPAWN before fork: counted,
    journaled, and the respawn backoff doubles up to its cap — the
    crashloop / fork-bomb guard. No OS process is ever created."""
    from minisched_tpu.fleet.procfleet import ProcFleetSupervisor, _Proc

    sup = ProcFleetSupervisor(ClusterStore(), "http://127.0.0.1:1",
                              replicas=1, respawn=False, prewarm=False,
                              backoff0_s=0.25, backoff_cap_s=1.0)
    sup._procs["p0"] = _Proc(rid="p0")
    _configure("proc:err@1,proc:err@2,proc:err@3")
    for _ in range(3):
        assert sup._spawn("p0") is False
    assert sup.counters["spawn_failures"] == 3
    assert sup.counters["spawns"] == 0
    p = sup._procs["p0"]
    assert p.popen is None and not p.alive
    assert p.backoff_s == 1.0  # 0.25 -> 0.5 -> 1.0 (capped)


def test_proc_gate_err_drops_heartbeat(registry):
    """``proc:err`` on the heartbeat seam: the CAS write never leaves
    the replica — counted, journaled, census object untouched. Miss
    enough and the supervisor's census reads the replica stale, which
    is the intended degraded-network failure mode."""
    from minisched_tpu.fleet.procfleet import push_heartbeat

    store = ClusterStore()
    counters = {}
    assert push_heartbeat(store, "pX", {"ready": True, "renewed_at": 1.0},
                          counters=counters)
    _configure("proc:err@1")
    assert push_heartbeat(store, "pX", {"renewed_at": 2.0},
                          counters=counters) is False
    assert counters["heartbeats_dropped"] == 1
    assert store.get("ReplicaStatus", "replica-pX").renewed_at == 1.0
    # Gate consumed: the next heartbeat lands cleanly.
    assert push_heartbeat(store, "pX", {"renewed_at": 2.0},
                          counters=counters)
    assert store.get("ReplicaStatus", "replica-pX").renewed_at == 2.0


def test_proc_gate_corrupt_heartbeat_loses_cas(registry):
    """``proc:corrupt`` sends the heartbeat with a REWOUND
    resource_version: the store CAS rejects it by construction (the
    lease:corrupt proof applied to the census object) — the supervisor's
    census can be starved by corruption, never poisoned."""
    from minisched_tpu.fleet.procfleet import push_heartbeat

    store = ClusterStore()
    counters = {}
    assert push_heartbeat(store, "pY", {"ready": True, "renewed_at": 1.0,
                                        "queue_depth": 2},
                          counters=counters)
    _configure("proc:corrupt@1")
    assert push_heartbeat(store, "pY", {"renewed_at": 9.0,
                                        "queue_depth": 99},
                          counters=counters) is False
    assert counters["stale_heartbeats_rejected"] == 1
    st = store.get("ReplicaStatus", "replica-pY")
    assert (st.renewed_at, st.queue_depth) == (1.0, 2)
    assert push_heartbeat(store, "pY", {"renewed_at": 9.0},
                          counters=counters)
    assert store.get("ReplicaStatus", "replica-pY").renewed_at == 9.0


def test_proc_gate_die_outside_replica_is_distinguishable(registry,
                                                          monkeypatch):
    """``proc:die`` consulted OUTSIDE a replica process propagates as
    FaultWorkerDeath (never a SIGKILL of the test runner); the spawn
    seam treats it as a spawn failure. Inside a real replica the same
    rule is a genuine SIGKILL — pinned by the process-level suite."""
    from minisched_tpu.fleet.procfleet import proc_gate

    monkeypatch.delenv("MINISCHED_PROC_REPLICA", raising=False)
    _configure("proc:die@once")
    with pytest.raises(FaultWorkerDeath):
        proc_gate()


def test_election_gate_err_drops_cas_election_call(registry):
    """``election:err`` drops the would-be steward's CAS attempt: the
    tick is counted and skipped, the store is never touched, and the
    next clean tick claims normally — a flaky challenger can only delay
    its own coronation, never corrupt the crown."""
    from minisched_tpu.fleet.election import StewardElection

    store = ClusterStore()
    elect = StewardElection(store, "pe", ttl_s=5.0, clock=lambda: 100.0)
    _configure("election:err@1")
    assert elect.tick() is False
    assert elect.counters["elections_dropped"] == 1
    with pytest.raises(Exception):
        store.get("Lease", "steward")  # no lease was ever written
    _configure("")
    assert elect.tick() is True  # clean tick: coronation proceeds


def test_election_gate_die_outside_replica_is_distinguishable(
        registry, monkeypatch):
    """``election:die`` consulted OUTSIDE a replica process propagates
    as FaultWorkerDeath (never a SIGKILL of the test runner). Inside a
    real replica the same rule is a genuine SIGKILL of the would-be
    steward at claim time — pinned by the process-level suite."""
    from minisched_tpu.fleet.election import election_gate

    monkeypatch.delenv("MINISCHED_PROC_REPLICA", raising=False)
    _configure("election:die@once")
    with pytest.raises(FaultWorkerDeath):
        election_gate()


def test_election_gate_corrupt_scribbles_burn_signal(registry):
    """``election:corrupt`` scribbles the published burn signal with an
    implausible level; the rebalancer's plausibility clamp discards it —
    a corrupted signal can starve the burn trigger, never steer it."""
    from minisched_tpu.fleet.election import burn_fields
    from minisched_tpu.fleet.procfleet import (MAX_PLAUSIBLE_BURN,
                                               RebalanceSpec,
                                               ShardRebalancer)

    class _Eng:
        def burn_signal(self):
            return 2, "slo-p99"

    counters = {}
    _configure("election:corrupt@1")
    hb = burn_fields(_Eng(), counters=counters)
    assert hb["overload_level"] > MAX_PLAUSIBLE_BURN
    assert hb["burning"] == "scribbled"
    assert counters["burn_scribbles"] == 1
    _configure("")
    assert burn_fields(_Eng()) == {"overload_level": 2,
                                   "burning": "slo-p99"}
    # Downstream containment: the scribble is clamped out of the load
    # signal and can never nominate a move.
    store = ClusterStore()
    reb = ShardRebalancer(store, RebalanceSpec(skew=1e9, hold=1))
    sts = {
        "pa": obj.ReplicaStatus(
            metadata=obj.ObjectMeta(name="replica-pa"),
            ready=True, renewed_at=time.time(),
            overload_level=hb["overload_level"],
            burning=hb["burning"]),
        "pb": obj.ReplicaStatus(
            metadata=obj.ObjectMeta(name="replica-pb"),
            ready=True, renewed_at=time.time()),
    }
    assert reb.observe(sts, {0: "pa", 1: "pb"}) is None
    assert reb.counters["burn_scribbles_ignored"] == 1


# ---- whole-suite coverage ------------------------------------------------


def test_zz_every_gate_fired_at_least_once_in_this_suite():
    """Catalog coverage: meaningful on a full-file run (the tier-1 and
    ``make fault-smoke`` shape) — every named gate in faults.GATES was
    genuinely driven to fire by some test above."""
    missing = [g for g in GATES if FIRED.get(g, 0) < 1]
    assert not missing, f"gates never fired this run: {missing}"
