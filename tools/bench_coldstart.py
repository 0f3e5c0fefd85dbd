"""Cross-process compile-cache proof (ROADMAP cold-start item).

The engine arms jax's persistent compilation cache at init
(ops/pipeline.arm_compile_cache) and bench.py exports per-run warmup compile seconds
(``*_warmup_compile_s``), but nothing ever proved the cache works
ACROSS PROCESSES — the cold-start claim is precisely that a restarted
scheduler's first batches skip XLA compilation. This harness runs the
same single-burst engine phase in TWO child processes sharing one
cache directory:

    run 1 (cold)  — empty cache: the warmup pass pays the real XLA
                    compiles and populates the cache;
    run 2 (warm)  — fresh process, hot cache: the warmup pass loads
                    executables instead of compiling, so its measured
                    compile seconds must collapse toward zero.

Claim contract (exit 1 under --check when violated):

  * run 1 genuinely compiled (cold compile seconds above a floor —
    otherwise the proof is vacuous);
  * run 2's compile seconds ≤ max(25% of run 1's, a 2 s host-noise
    floor) — "warmup compile seconds ≈ 0" made operational;
  * the cache directory is non-empty after run 1.

The cold/warm compile keys append to BENCH_LEDGER.json (source
bench-coldstart) so `make bench-check` regression-gates the cold
compile cost cross-run like any other seconds key.

    JAX_PLATFORMS=cpu python tools/bench_coldstart.py [> BENCH_COLDSTART.json]
    JAX_PLATFORMS=cpu python tools/bench_coldstart.py --check
    JAX_PLATFORMS=cpu python tools/bench_coldstart.py --check --update
"""
import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

LEDGER_KEYS = ("coldstart_cold_compile_s", "coldstart_warm_compile_s",
               "coldstart_cold_total_s", "coldstart_warm_total_s")


def _child() -> None:
    """One engine burst in THIS process (invoked via --child): warmup
    pass (compiles land here) + measured pass, keys on stdout's last
    line. JAX_COMPILATION_CACHE_DIR comes from the parent's env."""
    import bench
    from bench_workload import BENCH_PLUGINS, make_workload

    n = int(os.environ["MINISCHED_BENCH_NODES"])
    p = int(os.environ["MINISCHED_BENCH_PODS"])
    mn, mp = make_workload(n, p)
    out = bench.engine_bench(n, p, mn, mp, BENCH_PLUGINS, prefix="cold")
    print(json.dumps(out))


def run_child(n: int, p: int, cache_dir: str) -> dict:
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=cache_dir,
               # persist the sub-second CPU compiles of this small shape
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               MINISCHED_BENCH_NODES=str(n),
               MINISCHED_BENCH_PODS=str(p))
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child"],
        env=env, capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        raise RuntimeError(
            f"coldstart child failed rc={proc.returncode}: "
            f"{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def capture(n: int, p: int) -> dict:
    cache_dir = tempfile.mkdtemp(prefix="minisched-coldstart-")
    try:
        cold = run_child(n, p, cache_dir)
        entries = sum(len(files) for _r, _d, files in os.walk(cache_dir))
        warm = run_child(n, p, cache_dir)
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
    cold_s = float(cold.get("cold_warmup_compile_s") or 0.0)
    warm_s = float(warm.get("cold_warmup_compile_s") or 0.0)
    doc = {
        "nodes": n, "pods": p, "platform": "cpu",
        "methodology":
            "two child PROCESSES share one persistent-compilation-cache "
            "directory; each runs the identical single-burst engine "
            "phase (warmup pass + measured pass); compile seconds = "
            "warmup wall clock minus the warmed measured pass "
            "(bench.engine_bench's *_warmup_compile_s)",
        "coldstart_cold_compile_s": round(cold_s, 4),
        "coldstart_warm_compile_s": round(warm_s, 4),
        "coldstart_cold_total_s": float(cold.get("cold_warmup_s") or 0.0),
        "coldstart_warm_total_s": float(warm.get("cold_warmup_s") or 0.0),
        "cache_entries_after_cold": entries,
        "compile_cache_armed":
            cold.get("cold_compile_cache_dir") == cache_dir,
        "warm_over_cold_ratio": (round(warm_s / cold_s, 4)
                                 if cold_s else None),
    }
    bad = []
    if not doc["compile_cache_armed"]:
        bad.append("the child did not arm JAX_COMPILATION_CACHE_DIR")
    if entries < 1:
        bad.append("cold run left an empty compilation cache")
    if cold_s < 1.0:
        bad.append(f"cold run compiled only {cold_s}s — the proof is "
                   "vacuous at this shape")
    if warm_s > max(0.25 * cold_s, 2.0):
        bad.append(f"hot-cache process still paid {warm_s}s of warmup "
                   f"compile (cold: {cold_s}s) — the cache did not "
                   "carry across processes")
    doc["claims_failed"] = bad
    doc["ok"] = not bad
    return doc


def main() -> None:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--check", action="store_true",
                    help="claim-contract gate (exit 1 on failure) + "
                         "advisory ledger diff")
    ap.add_argument("--update", action="store_true",
                    help="append this capture to the ledger as the new "
                         "bench-coldstart baseline")
    ap.add_argument("--ledger",
                    default=os.path.join(REPO, "BENCH_LEDGER.json"))
    args = ap.parse_args()
    if args.child:
        _child()
        return
    n = int(os.environ.get("MINISCHED_BENCH_NODES", "400"))
    p = int(os.environ.get("MINISCHED_BENCH_PODS", "200"))
    doc = capture(n, p)

    import bench
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from bench_compare import compare, latest_baseline

    keys = {k: doc[k] for k in LEDGER_KEYS
            if isinstance(doc.get(k), (int, float))}
    entry = {"ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
             "source": "bench-coldstart", "platform": "cpu",
             "nodes": n, "pods": p, "keys": keys}
    try:
        with open(args.ledger, encoding="utf-8") as f:
            ledger = json.load(f)
    except (OSError, json.JSONDecodeError):
        ledger = {"schema": 1, "runs": []}
    base = latest_baseline(ledger, n, p, "cpu", source="bench-coldstart")
    if base is not None:
        # Advisory: compile seconds scale with host speed; the hard
        # gate is the claim contract (warm ≈ 0 relative to cold).
        doc["ledger_diff"] = compare(keys, base.get("keys") or {})
    if args.update or (not args.check and base is None):
        bench.append_ledger(entry, args.ledger)
        doc["ledger_appended"] = True
    print(json.dumps(doc, indent=1))
    if args.check and not doc["ok"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
