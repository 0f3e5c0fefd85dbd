"""One run of a cell: set-up, warm-up, the measured window, the grace.

`measure` returns a `Run`: what the window saw, as host-clock stamps from
the store, deltas of the engine's own counters, and (traced runs) the
reduced profiler trace and flight-recorder spans. The metric readers and
the check read nothing else.
"""
from __future__ import annotations

import collections
import gc
import logging
import os
import shutil
import time
from typing import Dict, List

import numpy as np

from .traffic import Driver, arrivals
from .workload import (build_cluster, to_program_nodes,
                       to_program_standing)

#: Watch-log retention of the store, in events: a pod costs about five
#: (create, bind, delete, its Scheduled event, a requeue), and no watcher
#: may fall this far behind in a window.
STORE_LOG = 2_000_000
WARMUP_TIMEOUT_S = 900.0   # the first batches compile
STALL_S = 60.0             # no batch for this long: the engine stalled
#: How long after the window a due pod may still bind.
GRACE_S = 60.0
#: Seconds of the window a traced run records (from its start).
TRACE_SECONDS = 6.0


class Run:
    """What one run measured. Every stamp is time.time() (the store's
    clock); `t0`/`t1` bound the measured window."""

    def __init__(self):
        self.seed = 0
        self.seconds = 0.0
        self.t0 = self.t1 = 0.0
        self.setup_seconds = 0.0
        self.open_loop = False
        self.window_keys: List[str] = []    # open loop: pods due in window
        self.binds: Dict[str, tuple] = {}
        self.created: Dict[str, tuple] = {}
        self.deleted: Dict[str, float] = {}
        self.put_back: Dict[str, tuple] = {}  # key -> (node, created)
        self.template_of: Dict[str, dict] = {}  # standing, put back
        self.rebinds = 0
        self.late: List[float] = []
        self.engine0: dict = {}
        self.engine1: dict = {}
        self.traced1: dict = {}             # engine at the trace's end
        self.compiles_in_window = 0
        self.compiles_in_setup = 0
        self.trace = None                   # trace_reduce.Trace
        self.spans: List[dict] = []         # flight-recorder events
        self.cluster = None                 # workload.Cluster
        self.template = ""                  # incoming pods' template
        self.store_pods: List[tuple] = []   # (key, node, requests) at end
        self.device_kind = ""
        self.notes: List[str] = []
        self.deadline = 0.0                 # close + grace
        self._cleanup = []

    # ---- counts -----------------------------------------------------------

    def delta(self, name: str) -> float:
        return float(self.engine1.get(name, 0)) - float(
            self.engine0.get(name, 0))

    def layer_delta(self, name: str) -> float:
        """Delta of an engine counter for a per-layer metric: over the
        traced part of the window in a traced run, else the window."""
        end = self.traced1 or self.engine1
        return float(end.get(name, 0)) - float(self.engine0.get(name, 0))

    def hist_delta(self, name: str) -> dict:
        """A histogram's counts over the same span as `layer_delta`."""
        h0 = self.engine0["histograms"][name]
        h1 = (self.traced1 or self.engine1)["histograms"][name]
        return {"bounds": h1["bounds"],
                "counts": [b - a for a, b in zip(h0["counts"],
                                                 h1["counts"])],
                "count": h1["count"] - h0["count"]}

    def due_pods(self) -> List[str]:
        """The pods whose answers are due: in an open loop those due in
        the window, in a closed loop every pod the traffic created."""
        return self.window_keys if self.open_loop else list(self.created)

    def attempted(self) -> int:
        return len(self.due_pods())

    def failed(self) -> int:
        return sum(1 for k in self.due_pods() if k not in self.binds)

    def release(self) -> None:
        for fn in self._cleanup:
            fn()
        self._cleanup = []


def _engine_numbers(sched) -> dict:
    m = sched.metrics()
    out = {k: v for k, v in m.items() if isinstance(v, (int, float))}
    out["histograms"] = m["histograms"]
    return out


class _Compiles(logging.Handler):
    """Counts XLA compile requests (persistent-cache hits included), each
    a program this process had not built yet, and keeps the names jax
    logs for them."""

    def __init__(self):
        super().__init__()
        import jax

        self.n = 0
        self.hits = 0
        self.names: List[str] = []
        jax.monitoring.register_event_listener(self._on_event)
        jax.config.update("jax_log_compiles", True)
        logging.getLogger("jax").addHandler(self)

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.n += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.names.append(" ".join(msg.split()[1:])[:300])


class _GcPauses:
    """Python's cyclic collections while the window runs (observed, not
    changed): every thread stops for them."""

    def __init__(self):
        self.t = {}
        self.spent = collections.defaultdict(list)
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        g = info["generation"]
        if phase == "start":
            self.t[g] = time.perf_counter()
        elif g in self.t:
            self.spent[g].append(time.perf_counter() - self.t.pop(g))

    def close(self) -> None:
        gc.callbacks.remove(self._cb)

    def summary(self) -> str:
        return "gc in the window: " + ", ".join(
            f"gen{g} {len(v)}x {sum(v):.3f} s (max {max(v):.3f} s)"
            for g, v in sorted(self.spent.items()) if v) if any(
            self.spent.values()) else "gc in the window: none"


def _wait(pred, timeout: float, what: str, poll: float = 0.01) -> None:
    end = time.monotonic() + timeout
    while not pred():
        if time.monotonic() > end:
            raise TimeoutError(f"timed out after {timeout:.0f} s: {what}")
        time.sleep(poll)


def _batches(sched, n: int, timeout: float = STALL_S) -> bool:
    """Wait until n more batches have been committed; False if none
    came for `timeout` seconds (a stalled engine: the window will show)."""
    b0 = last = sched.metrics()["batches"]
    t_last = time.monotonic()
    while last - b0 < n:
        time.sleep(0.02)
        b = sched.metrics()["batches"]
        if b != last:
            last, t_last = b, time.monotonic()
        elif time.monotonic() - t_last > timeout:
            return False
    return True


def _warm_up(drv: Driver, traffic: dict, args, scale: int, sched) -> bool:
    """Meet, before the window, every shape the window will: one burst of
    each size in `warmup_bursts` (each a pad bucket of a partial batch),
    then the cell's own traffic, with `warmup_refresh` (batches that meet
    that many changed nodes) while it runs, for `warmup_batches` batches
    and `warmup_s` seconds. False if the engine stalled in it."""
    for n in traffic.get("warmup_bursts", []):
        keys = drv.burst(max(1, n // scale))
        end = time.monotonic() + WARMUP_TIMEOUT_S
        while drv.n_bound(keys) < len(keys):
            if time.monotonic() > end:
                return False
            time.sleep(0.01)
    if traffic["arrivals"] == "closed":
        drv.closed_loop(max(1, traffic["backlog"] // scale))
    else:
        drv.open_loop(time.time(),
                      arrivals(traffic, 3600.0, args.seed + 1, scale))
    if not _batches(sched, traffic.get("warmup_batches", 0),
                    WARMUP_TIMEOUT_S):
        return False
    stalled: List[bool] = []

    def pause():
        if not stalled and not _batches(sched, 2):
            stalled.append(True)

    for n in traffic.get("warmup_refresh", []):
        drv.refresh(max(1, n // scale), pause)
        if stalled:
            return False
    time.sleep(traffic["warmup_s"])
    return True


def measure(cfg: dict, traffic: dict, args, scale: int, t_process: float,
            trace_dir: str) -> Run:
    from minisched_tpu.config import SchedulerConfig
    from minisched_tpu.obs import configure as configure_spans
    from minisched_tpu.scenario import Cluster
    from minisched_tpu.service.defaultconfig import Profile
    from minisched_tpu.state.store import ClusterStore

    run = Run()
    run.seed = args.seed
    compiles = _Compiles()
    marks = [("imports", time.perf_counter())]
    cl = build_cluster(cfg, args.seed, scale)
    run.cluster = cl
    run.template = cfg["incoming"]["template"]
    store = ClusterStore(max_log=STORE_LOG)
    objects = to_program_nodes(cl) + to_program_standing(cl)
    marks.append(("build objects", time.perf_counter()))
    store.create_many(objects)
    del objects
    marks.append(("store", time.perf_counter()))
    prof = cfg["profile"]
    engine = dict(cfg.get("engine", {}))
    engine.setdefault("percentage_of_nodes_to_score",
                      prof.get("percentage_of_nodes_to_score", 0))
    cluster = Cluster(store=store)
    cluster.start(profile=Profile(name=prof["name"],
                                  plugins=list(prof["plugins"]),
                                  weights=dict(prof["weights"])),
                  config=SchedulerConfig(**engine))
    run._cleanup.append(cluster.shutdown)
    sched = cluster.service.scheduler
    _wait(lambda: sched.cache.node_count() >= cl.n_nodes, 600.0,
          "informer sync")
    marks.append(("start + informer sync", time.perf_counter()))
    drv = Driver(store, cfg["pod_templates"][run.template], traffic,
                 {k: cl.node_names[r] for k, r in
                  zip(cl.standing_keys, cl.standing_node)},
                 {k: cl.templates[t] for k, t in
                  zip(cl.standing_keys, cl.standing_template)})
    drv.start()
    try:
        if not _warm_up(drv, traffic, args, scale, sched):
            run.notes.append("the warm-up stalled: no batch for "
                             f"{STALL_S:.0f} s")
        run.setup_seconds = time.perf_counter() - t_process
        marks.append(("warm-up", time.perf_counter()))
        t = t_process
        run.notes.append("set-up by phase: " + ", ".join(
            f"{name} {m - t_prev:.3f} s" for (name, m), t_prev in
            zip(marks, [t] + [m for _n, m in marks[:-1]])))
        run.compiles_in_setup = compiles.n
        named = len(compiles.names)
        _window(run, drv, sched, traffic, args, scale, trace_dir,
                configure_spans)
        run.compiles_in_window = compiles.n - run.compiles_in_setup
        if run.compiles_in_window:
            run.notes.append("compiled in the window: "
                             + ", ".join(compiles.names[named:]))
        drv.idle()
        if run.open_loop:
            run.window_keys = [k for k, (_c, due) in drv.created.items()
                               if run.t0 <= due < run.t1]
        run.deadline = run.t1 + GRACE_S
        due = (run.window_keys if run.open_loop else list(drv.created))
        _wait(lambda: all(k in drv.binds for k in due)
              or time.time() > run.deadline, GRACE_S + 5.0,
              "the grace after the window", poll=0.05)
        time.sleep(0.3)  # the last bind events reach the driver
    finally:
        drv.stop()
    run.binds, run.created, run.deleted = drv.binds, drv.created, drv.deleted
    run.put_back, run.template_of = drv.put_back, drv.template_of
    run.rebinds, run.late = drv.rebinds, drv.late
    store.for_each("Pod", lambda p: run.store_pods.append(
        (p.key, p.spec.node_name, p.spec.requests)))
    run.notes.append(
        f"set-up {run.setup_seconds:.3f} s ({run.compiles_in_setup} "
        f"compiles, {compiles.hits} from the persistent cache), window {run.seconds:.3f} s "
        f"({run.compiles_in_window} compiles), "
        f"{len(drv.created)} pods created, {len(drv.binds)} bound, "
        f"{len(drv.deleted)} deleted, {run.delta('batches'):.0f} batches "
        "in the window")
    if run.late:
        run.notes.append(
            f"generator lateness: mean {np.mean(run.late) * 1e3:.3f} ms, "
            f"max {np.max(run.late) * 1e3:.3f} ms over {len(run.late)} pods")
    return run


def _window(run: Run, drv: Driver, sched, traffic: dict, args, scale: int,
            trace_dir: str, configure_spans) -> None:
    import jax

    run.seconds = float(args.seconds)
    tracing = bool(args.trace)
    if tracing:
        if os.path.isdir(trace_dir):
            shutil.rmtree(trace_dir)
        os.makedirs(trace_dir)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        configure_spans(True)
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    # A full collection closes the set-up, so every window starts at the
    # same point of the collector's cycle: the full collections the
    # window then meets are those its own work causes.
    gc.collect()
    pauses = _GcPauses()
    run.engine0 = _engine_numbers(sched)
    run.t0 = time.time()
    pending0 = drv.pending()
    if traffic["arrivals"] != "closed":
        run.open_loop = True
        drv.open_loop(run.t0, arrivals(traffic, run.seconds, args.seed,
                                       scale))
    t_end = run.t0 + run.seconds
    if tracing:
        # The trace covers the window's first TRACE_SECONDS: enough
        # batches to reduce, at a fraction of the trace's size.
        with jax.profiler.TraceAnnotation("bench.window"):
            time.sleep(max(0.0, min(t_end, run.t0 + TRACE_SECONDS)
                           - time.time()))
        run.traced1 = _engine_numbers(sched)
        jax.profiler.stop_trace()
        from minisched_tpu.obs import TRACE

        run.spans = TRACE.events()
        configure_spans(False)
    time.sleep(max(0.0, t_end - time.time()))
    run.t1 = time.time()
    run.engine1 = _engine_numbers(sched)
    pauses.close()
    run.notes.append(f"pending pods: {pending0} at the window's open, "
                     f"{drv.pending()} at its close")
    run.notes.append(pauses.summary())
    if tracing:
        from .trace_reduce import reduce_file

        try:
            run.trace = reduce_file(trace_dir, "bench.window",
                                    {e["name"] for e in run.spans})
        except ValueError as e:
            if not args.rehearse:
                raise
            run.notes.append(f"rehearsal: no device trace ({e})")

