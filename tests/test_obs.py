"""Flight-recorder suite (minisched_tpu/obs + the engine seams).

The acceptance bar this file pins: with ``MINISCHED_TRACE`` unset the
recorder is a no-op (decisions bit-identical trace-on vs trace-off
across the pipelined/resident/shortlist engine modes; the disabled span
is one shared object behind a single attribute test); armed, the span
stream nests correctly under the two-deep pipeline, fault fires and
supervisor ladder transitions surface as instants, the exported JSON
validates against the Chrome trace-event schema, the per-pod lifecycle
histograms count exactly the bound decisions, and the engine_gap_s
decomposition partitions gap_s_total exactly.
"""
import gc
import glob
import json
import os
import sys
import time

import pytest

from minisched_tpu import faults, obs
from minisched_tpu.config import SchedulerConfig
from minisched_tpu.obs import Histogram, hist_quantile
from minisched_tpu.scenario import Cluster
from minisched_tpu.service.defaultconfig import Profile
from minisched_tpu.state import objects as obj

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))
import trace_view  # noqa: E402

RESOLVE_PHASES = ("resolve.verdicts", "resolve.arbitrate", "resolve.assume")


@pytest.fixture(autouse=True)
def recorder():
    """Every test starts and leaves with the recorder disarmed and the
    fault registry clean — armed state leaking across tests would slow
    (and noise) the rest of the tier-1 run."""
    obs.configure(False)
    faults.configure("")
    yield obs.TRACE
    obs.configure(False)
    faults.configure("")


# ---- recorder units -------------------------------------------------------


def test_off_mode_span_is_shared_noop():
    assert not obs.TRACE.enabled
    s1, s2 = obs.span("a"), obs.span("b", pods=3)
    assert s1 is s2  # the singleton null span: zero allocation per seam
    with s1:
        s1.set(pods=1)  # no-op, must not raise
    obs.instant("nothing", x=1)
    assert obs.TRACE.events() == []


def test_armed_span_and_instant_record():
    obs.configure(True, buf=256)
    with obs.span("outer", seq=1):
        time.sleep(0.002)
        with obs.span("inner") as sp:
            sp.set(pods=7)
        obs.instant("mark", gate="step")
    evs = obs.TRACE.events()
    names = [e["name"] for e in evs]
    assert set(names) == {"outer", "inner", "mark"}
    by = {e["name"]: e for e in evs}
    assert by["mark"]["ph"] == "i"
    assert by["inner"]["args"] == {"pods": 7}
    assert by["outer"]["args"] == {"seq": 1}
    # containment: inner ⊆ outer on the same thread
    o, i = by["outer"], by["inner"]
    assert o["tid"] == i["tid"]
    assert o["ts_ns"] <= i["ts_ns"]
    assert i["ts_ns"] + i["dur_ns"] <= o["ts_ns"] + o["dur_ns"]
    assert o["dur_ns"] >= 2_000_000  # the sleep is inside the span


def test_ring_wraps_keeping_newest():
    obs.configure(True, buf=16)
    for k in range(50):
        obs.instant(f"e{k}")
    evs = obs.TRACE.events()
    assert len(evs) == 16
    assert {e["name"] for e in evs} == {f"e{k}" for k in range(34, 50)}
    assert obs.TRACE.dropped() == 34


def test_reconfigure_clears_rings():
    obs.configure(True, buf=64)
    obs.instant("old")
    obs.configure(True, buf=64)
    obs.instant("new")
    assert [e["name"] for e in obs.TRACE.events()] == ["new"]


def test_histogram_observe_snapshot_quantile():
    h = Histogram(bounds=(1.0, 2.0, 4.0))
    h.observe(0.5)
    h.observe_many([1.5, 3.0, 8.0])
    snap = h.snapshot()
    assert snap["counts"] == [1, 1, 1, 1]
    assert snap["count"] == 4
    assert snap["sum"] == pytest.approx(13.0)
    # quantiles interpolate inside the holding bucket; the +Inf bucket
    # answers its lower bound (the last finite boundary)
    assert 0.0 < hist_quantile(snap, 0.25) <= 1.0
    assert 1.0 < hist_quantile(snap, 0.5) <= 2.0
    assert hist_quantile(snap, 1.0) == pytest.approx(4.0)
    assert hist_quantile({"bounds": [1.0], "counts": [0, 0], "sum": 0.0,
                          "count": 0}, 0.5) == 0.0


# ---- engine bursts --------------------------------------------------------

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


def _pods(n=N_PODS):
    """Unique priorities/sizes: deterministic pop + scan order, so two
    identical runs place identically (the same discipline
    tests/test_faults.py relies on for its bit-identical claims)."""
    return [obj.Pod(
        metadata=obj.ObjectMeta(name=f"p{i}", namespace="default"),
        spec=obj.PodSpec(requests={"cpu": 100 + 17 * i},
                         priority=500 - i)) for i in range(n)]


def _run_burst(config, n_pods=N_PODS, settle_s=60, dump_to=None):
    """One engine burst; returns (placements {name: node}, metrics)."""
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
        # metrics AFTER all binds are visible (binder threads stamp
        # pods_bound before the store write becomes listable, so the
        # placement wait above is the ordering barrier)
        m = c.service.scheduler.metrics()
        if dump_to is not None:
            c.service.scheduler.dump_trace(dump_to)
        return placements, m
    finally:
        c.shutdown()


@pytest.mark.parametrize("mode", [
    {},                             # pipelined + resident + shortlist
    {"pipeline": False},            # strictly synchronous cycle
    {"device_resident": False},     # upload-every-batch + i32 fetch
    {"shortlist": False},           # full-width scan
])
def test_decisions_bit_identical_trace_on_off(mode):
    """MINISCHED_TRACE=0 vs =1 must not move a single placement: the
    recorder sits outside the decision path by construction (no PRNG
    draw, no input mutation), and this pins it per engine mode."""
    obs.configure(False)
    base, m0 = _run_burst(_config(**mode))
    obs.configure(True, buf=1 << 15)
    traced, m1 = _run_burst(_config(**mode))
    assert traced == base
    assert m1["pods_bound"] == m0["pods_bound"] == N_PODS
    names = {e["name"] for e in obs.TRACE.events()}
    assert names, "armed run recorded nothing"
    # the armed seams were live: the resolve phases recorded, the store
    # lock timed; unarmed, the lock timing added nothing
    assert set(RESOLVE_PHASES) <= names, sorted(names)
    assert m0["store_lock_wait_s_total"] == 0.0
    assert m1["store_lock_wait_s_total"] > 0.0
    assert m0["store_lock_acquisitions_total"] == 0
    assert m1["store_lock_acquisitions_total"] > 0


def test_span_nesting_and_ordering_under_pipeline():
    """Two-deep pipelined run: spans on each thread must be properly
    nested (disjoint or contained — a half-overlapping pair would mean
    a broken begin/end pairing), per-seq prepare→resolve ordering
    holds, and the seam catalog's core names all appear."""
    obs.configure(True, buf=1 << 15)
    _run_burst(_config())  # max_batch_size=7 → ≥2 batches via pipeline
    evs = obs.TRACE.events()
    names = {e["name"] for e in evs}
    for expected in ("queue.pop", "prepare", "encode.pods",
                     "cache.snapshot_assigned", "step.dispatch",
                     "resolve", "fetch.decision", "commit", "bind.bulk"):
        assert expected in names, (expected, sorted(names))
    spans = [e for e in evs if e["ph"] == "X"]
    by_tid = {}
    for e in spans:
        by_tid.setdefault(e["tid"], []).append(e)
    for tid, lst in by_tid.items():
        lst.sort(key=lambda e: (e["ts_ns"], -e["dur_ns"]))
        for i, a in enumerate(lst):
            for b in lst[i + 1:]:
                a0, a1 = a["ts_ns"], a["ts_ns"] + a["dur_ns"]
                b0, b1 = b["ts_ns"], b["ts_ns"] + b["dur_ns"]
                assert b0 >= a1 or b1 <= a1, (
                    f"half-overlapping spans on tid {tid}: "
                    f"{a['name']} vs {b['name']}")
    # per-batch ordering by the seq arg the engine attaches
    starts = {}
    for e in spans:
        seq = (e["args"] or {}).get("seq")
        if seq is not None:
            starts[(e["name"], seq)] = e["ts_ns"]
    seqs = {s for (n, s) in starts if n == "prepare"}
    assert seqs, "no prepare spans carried a seq"
    for s in seqs:
        if ("resolve", s) in starts:
            assert starts[("prepare", s)] < starts[("resolve", s)]


def test_fault_fires_and_ladder_as_instants():
    """Compose with MINISCHED_FAULTS: a step fault must appear as a
    ``fault.step`` instant and the supervised containment as a
    ``supervisor.escalate`` instant on the same timeline."""
    obs.configure(True, buf=1 << 15)
    faults.configure("step:err@2")
    _run_burst(_config(probation_batches=1))
    kinds = {e["name"] for e in obs.TRACE.events() if e["ph"] == "i"}
    assert "fault.step" in kinds, kinds
    assert "supervisor.escalate" in kinds, kinds


def test_histogram_counts_equal_bound_decisions():
    _, m = _run_burst(_config())
    hists = m["histograms"]
    assert hists["pod_create_to_bound_s"]["count"] == m["pods_bound"]
    assert hists["pod_informer_lag_s"]["count"] == m["pods_bound"]
    assert hists["pod_queue_wait_s"]["count"] == m["pods_bound"]
    assert hists["pod_bind_s"]["count"] == m["pods_bound"]
    assert m["pods_bound"] == N_PODS
    # the windows are real (sum > 0) and the quantile is readable
    snap = hists["pod_create_to_bound_s"]
    assert snap["sum"] > 0.0
    assert hist_quantile(snap, 0.5) >= 0.0


def test_informer_lag_once_per_bound_pod_requeue_included():
    """created → first enqueued is observed once per bound pod, inside
    its created → bound; a pod that fails, parks and is revived by a
    node add is still observed once (a requeue keeps the stamp)."""
    c = Cluster()
    try:
        c.start(profile=Profile(plugins=list(PLUGINS)), config=_config(),
                with_pv_controller=False)
        c.create_node("n0", cpu=4000)
        c.create_objects([
            obj.Pod(metadata=obj.ObjectMeta(name="fits", namespace="default"),
                    spec=obj.PodSpec(requests={"cpu": 1000})),
            obj.Pod(metadata=obj.ObjectMeta(name="big", namespace="default"),
                    spec=obj.PodSpec(requests={"cpu": 30000}))])
        sched = c.service.scheduler
        deadline = time.monotonic() + 60
        while (sched.metrics()["pods_failed"] < 1
               and time.monotonic() < deadline):
            time.sleep(0.05)
        assert sched.metrics()["pods_failed"] >= 1  # "big" was refused
        c.create_node("n1", cpu=64000)  # revives and binds "big"
        while (sched.metrics()["pods_bound"] < 2
               and time.monotonic() < deadline):
            time.sleep(0.05)
        m = sched.metrics()
    finally:
        c.shutdown()
    assert m["pods_bound"] == 2
    lag = m["histograms"]["pod_informer_lag_s"]
    c2b = m["histograms"]["pod_create_to_bound_s"]
    assert lag["count"] == 2
    assert 0.0 <= lag["sum"] <= c2b["sum"]
    assert m["informer_busy_s_total"] > 0.0


def test_gc_pause_total_and_full_collection_span():
    """Every collection adds to gc_pause_s_total; armed, a full one is
    one ``gc`` span (gen 2, with its collected count)."""
    obs.watch_gc()
    obs.watch_gc()  # idempotent: one hook per process
    assert sum(cb == obs._GC._on_gc for cb in gc.callbacks) == 1
    t0 = obs.gc_pause_s_total()
    gc.collect()
    assert obs.gc_pause_s_total() > t0
    was = gc.isenabled()
    gc.disable()  # no automatic collection inside the armed part
    try:
        obs.configure(True, buf=256)
        gc.collect()
        evs = [e for e in obs.TRACE.events() if e["name"] == "gc"]
    finally:
        if was:
            gc.enable()
    assert len(evs) == 1
    assert evs[0]["ph"] == "X" and evs[0]["args"]["gen"] == 2
    assert evs[0]["args"]["collected"] >= 0


def test_full_collections_counted_apart_and_in_metrics():
    """A forced full collection adds one to the generation-2 count and
    its pause to both ``gc_full_pause_s_total`` and ``gc_pause_s_total``;
    ``Scheduler.metrics()`` carries ``gc_full_total`` and
    ``gc_full_pause_s_total`` beside ``gc_pause_s_total``."""
    obs.watch_gc()
    was = gc.isenabled()
    gc.disable()  # only the forced collection runs in between
    try:
        n0 = obs.gc_collections_total()
        full0, all0 = obs.gc_full_pause_s_total(), obs.gc_pause_s_total()
        gc.collect()
        n1 = obs.gc_collections_total()
        full1, all1 = obs.gc_full_pause_s_total(), obs.gc_pause_s_total()
    finally:
        if was:
            gc.enable()
    assert n1[2] == n0[2] + 1 and n1[:2] == n0[:2]
    assert full1 > full0
    assert all1 - all0 == pytest.approx(full1 - full0)
    c = Cluster()
    try:
        c.start(profile=Profile(plugins=list(PLUGINS)), config=_config(),
                with_pv_controller=False)
        m = c.service.scheduler.metrics()
    finally:
        c.shutdown()
    assert m["gc_full_total"] >= n1[2]
    assert full1 <= m["gc_full_pause_s_total"] <= m["gc_pause_s_total"]


def test_recorder_and_profiler_copies_share_one_clock(tmp_path):
    """The join rule of the obs docstring: each span's profiler copy
    starts one constant offset after its recorder stamp, so any recorder
    event can be placed on the device trace's clock. Matched by (name,
    order); the offsets agree within 0.5 ms, the ``gc`` span included."""
    import jax
    from jax.profiler import ProfileData

    obs.watch_gc()
    obs.configure(True, buf=1024)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for k in range(3):
            with obs.span("clock.outer", k=k):
                time.sleep(0.002)
                with obs.span("clock.inner"):
                    time.sleep(0.001)
                if k == 1:
                    gc.collect()
    finally:
        jax.profiler.stop_trace()
    rec = {}
    for e in obs.TRACE.events():
        if e["ph"] == "X":
            rec.setdefault(e["name"], []).append(e["ts_ns"])
    assert {"clock.outer", "clock.inner", "gc"} <= set(rec), sorted(rec)
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))
    assert path, "the profiler wrote no xplane"
    host = {}
    for plane in ProfileData.from_file(path[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in rec:
                        host.setdefault(ev.name, []).append(
                            int(ev.start_ns))
    offsets = []
    for name, stamps in rec.items():
        copies = sorted(host.get(name, []))
        assert len(copies) == len(stamps), (name, copies, stamps)
        offsets += [x - t for x, t in zip(copies, sorted(stamps))]
    assert max(offsets) - min(offsets) < 500_000, offsets


def test_one_batch_id_from_prepare_to_bind():
    """Every span of one batch carries the seq its prepare was given:
    prepare, encode.pods, step.dispatch, resolve, commit and the
    binder's bind.bulk."""
    obs.configure(True, buf=1 << 15)
    _run_burst(_config())
    evs = obs.TRACE.events()
    per_batch = ("prepare", "encode.pods", "step.dispatch", "resolve",
                 "commit", "bind.bulk") + RESOLVE_PHASES
    by_seq = {}
    for e in evs:
        if e["ph"] == "X" and e["name"] in per_batch:
            assert "seq" in (e["args"] or {}), e
            by_seq.setdefault(e["args"]["seq"], []).append(e)
    assert len(by_seq) >= 2
    for seq, spans in by_seq.items():
        names = [e["name"] for e in spans]
        for n in ("prepare", "encode.pods", "step.dispatch", "resolve",
                  "commit", "bind.bulk"):
            assert names.count(n) == 1, (seq, sorted(names))
        prep = next(e for e in spans if e["name"] == "prepare")
        res = next(e for e in spans if e["name"] == "resolve")
        bind = next(e for e in spans if e["name"] == "bind.bulk")
        assert prep["ts_ns"] < res["ts_ns"] < bind["ts_ns"]
        assert bind["thread"].startswith("binder")


def test_resolve_phases_nest_and_keep_resolve_self_time():
    """Each resolve with pods has its three phase spans inside it, on
    its thread, with its seq; none starts with ``fetch.``, so the
    benchmark's resolve self time (without fetch.* children) reads what
    the plain definition reads."""
    sys.path.insert(0, REPO)
    from benchmark.layers import self_seconds

    obs.configure(True, buf=1 << 15)
    _run_burst(_config())
    evs = [e for e in obs.TRACE.events() if e["ph"] == "X"]
    resolves = [e for e in evs if e["name"] == "resolve"
                and e["args"]["pods"] > 0]
    assert resolves
    for r in resolves:
        end = r["ts_ns"] + r["dur_ns"]
        kids = [e for e in evs if e["tid"] == r["tid"]
                and e["name"] in RESOLVE_PHASES
                and r["ts_ns"] <= e["ts_ns"]
                and e["ts_ns"] + e["dur_ns"] <= end]
        assert {e["name"] for e in kids} == set(RESOLVE_PHASES), r
        assert {e["args"]["seq"] for e in kids} == {r["args"]["seq"]}

    def plain(names):
        total = 0
        for p in evs:
            if p["name"] not in names:
                continue
            end = p["ts_ns"] + p["dur_ns"]
            total += p["dur_ns"] - sum(
                c["dur_ns"] for c in evs
                if c is not p and c["tid"] == p["tid"]
                and c["name"].startswith("fetch.")
                and p["ts_ns"] <= c["ts_ns"]
                and c["ts_ns"] + c["dur_ns"] <= end)
        return total / 1e9

    class _Run:
        spans = evs

    got = self_seconds(_Run, ("resolve", "commit"), "fetch.")
    assert got == pytest.approx(plain({"resolve", "commit"}), abs=1e-9)
    assert got > 0.0


def test_gap_decomposition_partitions_gap_total():
    """gather/encode/fetch/commit must PARTITION gap_s_total — every
    booking is component-tagged, so the identity is exact, not a 2%
    approximation (the bench criterion is the loose outer bound)."""
    _, m = _run_burst(_config())
    parts = (m["gap_gather_s_total"] + m["gap_encode_s_total"]
             + m["gap_fetch_s_total"] + m["gap_commit_s_total"])
    assert parts == pytest.approx(m["gap_s_total"], abs=1e-9)


def test_exported_trace_validates_and_loads(tmp_path):
    obs.configure(True, buf=1 << 15)
    path = str(tmp_path / "trace.json")
    _run_burst(_config(), dump_to=path)
    doc = json.load(open(path, encoding="utf-8"))
    trace_view.validate(doc)  # raises on any schema violation
    evs = doc["traceEvents"]
    assert any(e["ph"] == "M" and e["name"] == "thread_name"
               for e in evs), "thread-name metadata missing"
    assert any(e["ph"] == "X" for e in evs)
    # the summary/coverage tooling consumes the same file
    spans = trace_view.span_summary(doc)
    assert spans.get("resolve", {}).get("count", 0) >= 1
    cov = trace_view.thread_coverage(doc)
    sched = [v for k, v in cov.items() if "scheduling-loop" in k]
    assert sched and max(sched) > 0.5, cov


def test_unarmed_dump_writes_valid_empty_trace(tmp_path):
    path = str(tmp_path / "empty.json")
    _, _m = _run_burst(_config(), dump_to=path)
    doc = json.load(open(path, encoding="utf-8"))
    trace_view.validate(doc)
    assert [e for e in doc["traceEvents"] if e["ph"] != "M"] == []


# ---- exposition -----------------------------------------------------------


def test_apiserver_typed_exposition_with_histograms():
    """/metrics carries # HELP + # TYPE for every series and native
    histogram exposition (_bucket with CUMULATIVE le labels, _sum,
    _count) for histogram providers, while the flat names stay
    scrape-compatible."""
    import urllib.request

    from minisched_tpu.apiserver import APIServer
    from minisched_tpu.state.store import ClusterStore

    h = Histogram(bounds=(0.001, 0.01))
    h.observe_many([0.0005, 0.005, 0.5])
    api = APIServer(ClusterStore())
    api.metrics_providers.append(lambda: {"pods_bound": 3, "batches": 2})
    api.histogram_providers.append(
        lambda: {"pod_create_to_bound_s": h.snapshot()})
    api.start()
    try:
        text = urllib.request.urlopen(
            f"{api.address}/metrics", timeout=5).read().decode()
    finally:
        api.shutdown()
    # typed: HELP + TYPE for flat series, names unchanged
    assert "# HELP minisched_engine_pods_bound" in text
    assert "# TYPE minisched_engine_batches gauge" in text
    assert "minisched_engine_batches 2" in text
    assert "# TYPE minisched_store_objects gauge" in text
    # native histogram exposition with cumulative buckets
    name = "minisched_engine_pod_create_to_bound_s"
    assert f"# TYPE {name} histogram" in text
    assert f'{name}_bucket{{le="0.001"}} 1' in text
    assert f'{name}_bucket{{le="0.01"}} 2' in text
    assert f'{name}_bucket{{le="+Inf"}} 3' in text
    assert f"{name}_count 3" in text
    assert f"{name}_sum" in text
    # exposition validity: one TYPE line per metric name (strict
    # parsers reject the whole scrape on a duplicate)
    type_lines = [ln for ln in text.splitlines()
                  if ln.startswith("# TYPE")]
    assert len(type_lines) == len(set(type_lines))


def _parse_prometheus_strict(text: str):
    """Strict text-format (0.0.4) pass — the checks a picky scraper
    applies before accepting a body: every sample line belongs to a
    family announced by exactly one # HELP and one # TYPE line (with a
    known type and non-empty help text), every value parses as a
    float, and every histogram's buckets are strictly-le-ordered,
    CUMULATIVE-monotone, end at +Inf, and agree with _count. Returns
    (types, samples) for content assertions."""
    import re as _re

    helps, types = {}, {}
    samples = []
    for ln in text.splitlines():
        if not ln:
            continue
        if ln.startswith("# HELP "):
            parts = ln.split(" ", 3)
            assert len(parts) == 4 and parts[3].strip(), ln
            assert parts[2] not in helps, f"duplicate HELP {parts[2]}"
            helps[parts[2]] = parts[3]
        elif ln.startswith("# TYPE "):
            parts = ln.split(" ")
            assert len(parts) == 4, ln
            name, mtype = parts[2], parts[3]
            assert mtype in ("counter", "gauge", "histogram",
                             "summary", "untyped"), ln
            assert name not in types, f"duplicate TYPE {name}"
            assert name in helps, f"TYPE before HELP for {name}"
            types[name] = mtype
        elif ln.startswith("#"):
            continue
        else:
            m = _re.match(
                r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{.*\})?\s(\S+)$', ln)
            assert m, f"unparseable sample line: {ln!r}"
            name, labels, val = m.group(1), m.group(2) or "", m.group(3)
            samples.append((name, labels, float(val)))
    hist: dict = {}
    for name, labels, val in samples:
        fam = name
        if name not in types:
            for suf in ("_bucket", "_sum", "_count"):
                if name.endswith(suf) and name[:-len(suf)] in types:
                    fam = name[:-len(suf)]
                    break
        assert fam in types, f"sample {name} has no HELP/TYPE family"
        if types[fam] == "histogram":
            h = hist.setdefault(fam, {"buckets": [], "count": None,
                                      "sum": None})
            if name.endswith("_bucket"):
                m = _re.search(r'le="([^"]+)"', labels)
                assert m, f"bucket without le label: {labels}"
                le = (float("inf") if m.group(1) == "+Inf"
                      else float(m.group(1)))
                h["buckets"].append((le, val))
            elif name.endswith("_count"):
                h["count"] = val
            elif name.endswith("_sum"):
                h["sum"] = val
            else:
                raise AssertionError(
                    f"bare sample {name} under histogram family {fam}")
    for fam, h in hist.items():
        assert h["buckets"], f"histogram {fam} has no buckets"
        les = [le for le, _ in h["buckets"]]
        assert les == sorted(les) and len(set(les)) == len(les), (
            f"{fam}: le labels not strictly increasing")
        assert les[-1] == float("inf"), f"{fam}: missing +Inf bucket"
        cums = [c for _, c in h["buckets"]]
        assert cums == sorted(cums), (
            f"{fam}: bucket counts not cumulative-monotone")
        assert h["count"] is not None and cums[-1] == h["count"], (
            f"{fam}: +Inf bucket != _count")
        assert h["sum"] is not None, f"{fam}: missing _sum"
    return types, samples


def test_metrics_strict_parse_under_concurrent_scrape_burst():
    """The FULL /metrics output of a live engine (store gauges, fault
    counters, engine provider, native histograms) must survive a
    strict format pass — HELP/TYPE on every series, histogram bucket
    monotonicity — on every response of a concurrent scrape burst (a
    Prometheus fleet scrapes without coordinating; a torn or
    interleaved body would poison the fleet's view)."""
    import urllib.request
    from concurrent.futures import ThreadPoolExecutor

    from minisched_tpu.apiserver import APIServer
    from minisched_tpu.service.service import SchedulerService
    from minisched_tpu.state.store import ClusterStore

    store = ClusterStore()
    svc = SchedulerService(store)
    svc.start_scheduler(
        Profile(name="default-scheduler", plugins=list(PLUGINS)),
        _config())
    api = APIServer(store)
    api.metrics_providers.append(svc.metrics)
    api.histogram_providers.append(svc.metrics_histograms)
    api.start()
    try:
        for i, cpu in enumerate((64000, 48000)):
            store.create(obj.Node(
                metadata=obj.ObjectMeta(name=f"n{i}"),
                status=obj.NodeStatus(allocatable={"cpu": cpu})))
        store.create_many(_pods(8))
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if svc.metrics().get("pods_bound", 0) >= 8:
                break
            time.sleep(0.05)

        def scrape(_i):
            body = urllib.request.urlopen(
                f"{api.address}/metrics", timeout=10).read().decode()
            return _parse_prometheus_strict(body)

        with ThreadPoolExecutor(max_workers=8) as pool:
            results = list(pool.map(scrape, range(32)))
        for types, samples in results:
            names = {n for n, _l, _v in samples}
            # the whole surface is present on every response
            assert "minisched_engine_pods_bound" in names
            assert "minisched_store_resource_version" in names
            assert "minisched_fault_fires_total" in names
            assert types.get("minisched_engine_pod_create_to_bound_s") \
                == "histogram"
            assert any(n.startswith("minisched_apiserver_")
                       for n in names)
    finally:
        api.shutdown()
        svc.shutdown_scheduler()


def test_service_histogram_provider_surface():
    """SchedulerService.metrics() stays Dict[str, float] (pinned
    contract) while metrics_histograms() carries the snapshots."""
    from minisched_tpu.service.service import SchedulerService
    from minisched_tpu.state.store import ClusterStore

    svc = SchedulerService(ClusterStore())
    assert svc.metrics_histograms() == {}
    svc.start_scheduler(
        Profile(name="default-scheduler", plugins=list(PLUGINS)),
        _config())
    try:
        hists = svc.metrics_histograms()
        assert "pod_create_to_bound_s" in hists
        assert set(hists["pod_create_to_bound_s"]) == {
            "bounds", "counts", "sum", "count"}
        assert all(isinstance(v, (int, float)) and not isinstance(v, bool)
                   for v in svc.metrics().values())
    finally:
        svc.shutdown_scheduler()


def test_anti_plane_span_and_counters():
    """Armed, the per-signature match against running pods' anti
    classes records an ``encode.anti`` span inside its batch's
    ``encode.pods`` (same thread, same seq); ``anti_classes`` gauges the
    live classes and ``anti_revoked_total`` counts a pod revoked for an
    anti conflict with an earlier pod of its own batch."""
    green = obj.Affinity(pod_anti_affinity=obj.PodAntiAffinity(required=[
        obj.PodAffinityTerm(
            label_selector=obj.LabelSelector(match_labels={"c": "g"}),
            topology_key="kubernetes.io/hostname")]))
    obs.configure(True, buf=1 << 15)
    c = Cluster()
    try:
        c.start(profile=Profile(plugins=["NodeUnschedulable",
                                         "NodeResourcesFit", "NodeName",
                                         "InterPodAffinity"]),
                config=SchedulerConfig(max_batch_size=8,
                                       batch_window_s=0.3,
                                       backoff_initial_s=0.05,
                                       backoff_max_s=0.2),
                with_pv_controller=False)
        c.create_node("n0", cpu=4000)
        c.create_node("n1", cpu=4000)
        c.create_pod("owner", cpu=100, labels={"c": "g"}, affinity=green,
                     required_node_name="n0")
        c.wait_for_pod_bound("owner", timeout=30)
        sched = c.service.scheduler
        assert sched.metrics()["anti_classes"] == 1
        # both can only take n1; the later one conflicts in-batch
        c.create_objects([obj.Pod(
            metadata=obj.ObjectMeta(name=f"g{i}", namespace="default",
                                    labels={"c": "g"}),
            spec=obj.PodSpec(requests={"cpu": 100}, affinity=green))
            for i in range(2)])
        from minisched_tpu.scenario import wait_until

        def nodes_of_green():
            return [p.spec.node_name for p in c.list_pods()
                    if p.metadata.name in ("g0", "g1") and p.spec.node_name]

        assert wait_until(lambda: nodes_of_green()
                          and sched.metrics()["anti_revoked_total"], 30)
        assert nodes_of_green() == ["n1"]
        m = sched.metrics()
        assert m["anti_revoked_total"] >= 1
        assert m["anti_classes"] == 1 and m["anti_class_slots"] >= 1
    finally:
        c.shutdown()
    evs = [e for e in obs.TRACE.events() if e["ph"] == "X"]
    anti = [e for e in evs if e["name"] == "encode.anti"]
    assert anti
    for a in anti:
        seq = a["args"]["seq"]
        assert any(e["name"] == "encode.pods" and e["args"]["seq"] == seq
                   and e["tid"] == a["tid"]
                   and e["ts_ns"] <= a["ts_ns"]
                   and a["ts_ns"] + a["dur_ns"] <= e["ts_ns"] + e["dur_ns"]
                   for e in evs)

