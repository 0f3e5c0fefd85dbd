"""Run one benchmark cell once, on the machine this starts on.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration file and its traffic file are found by name
through BENCHMARK.json; each metric is read by benchmark/metrics/<name>.py.
The run builds its cluster from the seed, starts the scheduler through the
product path (ClusterStore -> SchedulerService -> informer -> queue ->
encode -> jitted step -> arbitration -> bulk bind), warms up with the
cell's own traffic, measures for --seconds, waits for every pod due in the
window, checks the placements against benchmark/reference, and prints one
JSON line last on stdout. --trace 1 records a profiler trace of the window
and reports the per-layer metrics instead of the end-to-end ones.

    --rehearse   allow the CPU and divide the cluster by 50 (never a
                 device result: a rehearsal reports no metrics)
    --control    also print the control's verdict on the same batches
                 (benchmark/check.py); `correct` stays the program's

Finding no TPU, or fewer chips than the cell asks for, without --rehearse
exits non-zero and prints no result.
"""
import time

T_PROCESS = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
REHEARSAL_SCALE = 50
RUN_DEADLINE_S = 1100.0


class CellError(Exception):
    pass


def load_cell(name: str) -> tuple:
    """(benchmark, cell, configuration entry, configuration, traffic)."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark.workload import load_json

    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise CellError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(os.path.join(ROOT, entry["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic",
                                     cell["traffic"] + ".json"))
    return bench, cell, entry, cfg, traffic


def metrics_for(bench: dict, cell: dict, trace: bool) -> list:
    """The metric entries this run reports: end-to-end without a trace,
    per-layer with one, each only where its `workloads` (or, without the
    key, the end-to-end metric it moves) covers the cell."""
    e2e = [m for m in bench["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    if not trace:
        return e2e
    mine = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell["name"] in m["workloads"] if "workloads" in m
                else m["moves"] in mine)]


def read_metric(name: str, run) -> object:
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", action="store_true")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    bench, cell, _entry, cfg, traffic = load_cell(args.workload)
    # The compile cache lives at one fixed path inside the checkout, so
    # that only a cell's first run in a checkout compiles.
    cache = os.path.join(ROOT, ".jax_cache")
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    import jax

    # Every program goes to the cache, and nothing is evicted from it.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    devs = jax.devices()
    if not args.rehearse and devs[0].platform != "tpu":
        print(f"run.py: JAX found no TPU (platform {devs[0].platform!r}); "
              "--rehearse runs on the CPU", file=sys.stderr)
        return 2
    if len(devs) < cell["chips"]:
        print(f"run.py: the cell asks for {cell['chips']} chips, JAX sees "
              f"{len(devs)}", file=sys.stderr)
        return 2
    from benchmark import check, session

    # Dump every thread's stack rather than overrun the run's limit.
    import faulthandler

    faulthandler.dump_traceback_later(RUN_DEADLINE_S, exit=True)
    scale = REHEARSAL_SCALE if args.rehearse else 1
    run = session.measure(cfg, traffic, args, scale, T_PROCESS,
                          trace_dir=os.path.join(
                              ROOT, ".bench_traces", cell["name"],
                              f"seed-{args.seed}"))
    used = devs[:cell["chips"]]
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": max(
                  (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in used)}
    run.device_kind = device["kind"]
    run.release()
    gc.collect()
    verdict, control = check.judge(run, cfg)
    metrics = {}
    breakdown = None
    for m in metrics_for(bench, cell, bool(args.trace)):
        v = read_metric(m["name"], run)
        if v is None:
            continue
        if args.rehearse:  # a CPU number never goes under a metric's name
            run.notes.append(f"rehearsal reading {m['name']} = {v!r}")
        else:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    if args.trace and run.trace is not None and not args.rehearse:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        breakdown = run.trace.breakdown()
    if args.rehearse:
        run.notes.append(f"run.py: rehearsal on the CPU at 1/"
                         f"{REHEARSAL_SCALE} of the cluster; no device "
                         "result")
    for line in run.notes:
        print(line, file=sys.stderr)
    if args.control:
        for name, value, limit, ok in control.rows:
            print(f"control {name} = {value!r} (limit {limit!r}) "
                  f"{'ok' if ok else 'FAIL'}", file=sys.stderr)
    for name, value, limit, ok in verdict.rows:
        print(f"check {name} = {value!r} (limit {limit!r}) "
              f"{'ok' if ok else 'FAIL'}", file=sys.stderr)
    out = {"correct": verdict.correct,
           "attempted": run.attempted(), "failed": run.failed(),
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if args.control:
        out["control"] = {"correct": control.correct, "checks": {
            name: {"value": value, "limit": limit}
            for name, value, limit, _ok in control.rows}}
    out["checks"] = {name: {"value": value, "limit": limit}
                     for name, value, limit, _ok in verdict.rows}
    print(json.dumps(out, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except CellError as e:
        print(f"run.py: {e}", file=sys.stderr)
        code = 2
    sys.stdout.flush()
    # Engine pools are daemon threads; every one was shut down above.
    for t in threading.enumerate():
        if t is not threading.main_thread() and not t.daemon:
            t.join(timeout=10)
    sys.exit(code)
