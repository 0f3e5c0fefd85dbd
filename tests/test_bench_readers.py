"""The benchmark's readers of the engine's starvation counters and the
resolve phase spans, on hand-built runs: each reads its number from the
engine's counters, histograms and spans, and reads nothing (None, no
error) from a program that lacks them."""
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from benchmark.run import read_metric  # noqa: E402
from benchmark.session import Run  # noqa: E402
from benchmark.trace_reduce import Trace  # noqa: E402

BOUNDS = [0.001, 0.01, 0.1]


def _hist(total, count):
    return {"bounds": BOUNDS, "counts": [0, count, 0, 0], "sum": total,
            "count": count}


def _span(name, ts_ms, dur_ms, tid=1, **args):
    return {"ph": "X", "name": name, "ts_ns": int(ts_ms * 1e6),
            "dur_ns": int(dur_ms * 1e6), "tid": tid, "thread": "loop",
            "args": args or None}


def _run():
    """Window 0..50 s, traced part the first 6 s with 10 batches."""
    run = Run()
    run.t0, run.t1 = 0.0, 50.0
    run.engine0 = {"batches": 10, "informer_busy_s_total": 1.0,
                   "store_lock_wait_s_total": 0.5, "gc_pause_s_total": 2.0,
                   "store_lock_acquisitions_total": 1000,
                   "pods_bound": 100, "anti_revoked_total": 4,
                   "anti_classes": 1,
                   "histograms": {"pod_informer_lag_s": _hist(1.0, 100)}}
    run.traced1 = {"batches": 20, "informer_busy_s_total": 2.5,
                   "store_lock_wait_s_total": 0.55, "gc_pause_s_total": 2.1,
                   "store_lock_acquisitions_total": 3000,
                   "pods_bound": 900, "anti_revoked_total": 20,
                   "anti_classes": 1,
                   "histograms": {"pod_informer_lag_s": _hist(3.0, 300)}}
    run.engine1 = {"batches": 100, "informer_busy_s_total": 9.0,
                   "store_lock_wait_s_total": 0.55, "gc_pause_s_total": 7.1,
                   "store_lock_acquisitions_total": 9000,
                   "pods_bound": 5000, "anti_revoked_total": 60,
                   "anti_classes": 1,
                   "histograms": {"pod_informer_lag_s": _hist(9.0, 900)}}
    run.trace = Trace(window_s=6.0, busy_s=0.5, devices=1)
    run.spans = [
        _span("resolve", 0.0, 20.0, seq=1, pods=8),
        _span("fetch.decision", 0.5, 2.0),
        _span("resolve.arbitrate", 3.0, 4.0, seq=1),
        _span("resolve.verdicts", 8.0, 2.0, seq=1),
        _span("resolve.assume", 11.0, 3.0, seq=1),
        _span("resolve", 100.0, 30.0, seq=2, pods=8),
        _span("resolve.arbitrate", 101.0, 6.0, seq=2),
        _span("resolve.verdicts", 108.0, 2.0, seq=2),
        _span("resolve.assume", 111.0, 5.0, seq=2),
        _span("encode.pods", 200.0, 10.0, seq=3, pods=8),
        _span("encode.anti", 201.0, 1.5, seq=3),
        _span("encode.anti", 204.0, 0.5, seq=3),
    ]
    return run


def _parent_run():
    """What a program without these counters and spans gives."""
    run = _run()
    for snap in (run.engine0, run.traced1, run.engine1):
        for k in ("informer_busy_s_total", "store_lock_wait_s_total",
                  "gc_pause_s_total", "store_lock_acquisitions_total",
                  "anti_revoked_total", "anti_classes"):
            del snap[k]
        snap["histograms"] = {"pod_queue_wait_s": _hist(1.0, 10)}
    run.spans = [e for e in run.spans if not e["name"].startswith(
        ("resolve.", "encode.anti"))]
    return run


READINGS = [
    # Δsum / Δcount over the traced part: (3.0 − 1.0) / 200 s
    ("informer_lag_ms.drain", 10.0),
    ("informer_lag_ms.rate", 10.0),
    # (2.5 − 1.0) s of a 6 s traced window
    ("informer_busy_pct.drain", 25.0),
    # (0.55 − 0.5) s over 10 traced batches
    ("store_lock_wait_ms.drain", 5.0),
    # (3000 − 1000) acquisitions over 10 traced batches
    ("store_lock_acq.drain", 200.0),
    # (7.1 − 2.0) s of the whole 50 s window
    ("gc_pause_pct.drain", 10.2),
    ("gc_pause_pct.rate", 10.2),
    # (3 + 5) ms of resolve.assume over 10 batches
    ("assume_ms.drain", 0.8),
    # (4 + 6) ms of resolve.arbitrate over 10 batches
    ("arbitrate_ms.rate", 1.0),
    # (1.5 + 0.5) ms of encode.anti over 10 batches
    ("anti_match_ms.anti", 0.2),
    # (20 − 4) anti revocations over (900 − 100) pods bound
    ("anti_revoked_per_bind.anti", 0.02),
]


@pytest.mark.parametrize("name,want", READINGS)
def test_reader_reads_the_engine(name, want):
    assert read_metric(name, _run()) == pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("name", [n for n, _v in READINGS])
def test_reader_is_silent_without_the_counter(name):
    assert read_metric(name, _parent_run()) is None


def _traced_anti_run(run):
    """`run` with 0.1 s of jit_step in its trace and the anti-affinity
    configuration's cluster shape."""
    from benchmark.workload import Cluster

    run.trace.module_s = {"jit_step": 0.1}
    run.device_kind = "TPU v5 lite"
    green = {"namespace": "sched-1", "labels": {"color": "green"},
             "requests": {"cpu": 100},
             "affinity": {"pod_anti_affinity": {"required": [
                 {"match_labels": {"color": "green"},
                  "topology_key": "kubernetes.io/hostname",
                  "namespaces": ["sched-1", "sched-0"]}]}}}
    run.cluster = Cluster(
        node_names=[f"n{i}" for i in range(5000)], node_labels=[],
        node_template={}, resources=["cpu", "memory", "pods"], alloc=None,
        templates={"green": green, "default": {"labels": {}}},
        standing_keys=[f"s{i}" for i in range(151000)])
    run.template = "green"
    run.engine0["pods_seen"], run.traced1["pods_seen"] = 0, 800
    return run


def test_anti_step_readers_read_the_trace():
    from benchmark.roofline_anti import least_seconds_anti

    run = _traced_anti_run(_run())
    # 0.1 s of jit_step over 10 traced batches
    assert read_metric("step_device_ms.rate", run) == pytest.approx(10.0)
    least, _bound = least_seconds_anti(10, 800, 5000, 3, 0, 151000, 1, 1,
                                       1, "TPU v5 lite")
    assert read_metric("step_roofline.anti", run) == pytest.approx(
        least / 0.1 * 100.0)
    assert 0 < read_metric("step_roofline.anti", run) < 100


def test_anti_step_readers_are_silent_without_the_plane():
    run = _traced_anti_run(_parent_run())
    assert read_metric("step_roofline.anti", run) is None
    run.trace = None
    assert read_metric("step_device_ms.rate", run) is None


def test_least_seconds_anti_extends_least_seconds():
    """With no term, class or corpus the anti count is the plain one;
    the corpus read makes it longer, and a class never shortens it (at
    these shapes the bytes bound it, which classes do not add to)."""
    from benchmark.roofline import least_seconds
    from benchmark.roofline_anti import least_seconds_anti

    base = least_seconds(10, 800, 5000, 3, 0, "TPU v5 lite")
    assert least_seconds_anti(10, 800, 5000, 3, 0, 0, 0, 0, 0,
                              "TPU v5 lite") == base
    one = least_seconds_anti(10, 800, 5000, 3, 0, 151000, 1, 1, 1,
                             "TPU v5 lite")
    two = least_seconds_anti(10, 800, 5000, 3, 0, 151000, 1, 2, 1,
                             "TPU v5 lite")
    assert base[0] < one[0] <= two[0]
    assert one[1] == "bytes"
