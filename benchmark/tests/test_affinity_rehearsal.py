"""The harness on pods that carry anti-affinity terms (CPU rehearsal).

benchmark/tests/data/anti-affinity-rehearsal.json is upstream's
SchedulingPodAntiAffinity at 5000Nodes with both pod groups in one
namespace, and its traffic file open-loop green pods under the incoming
churn; neither is a cell of BENCHMARK.json. Its standing green group
is cut to 50 pods (1 at this scale) and its bursts left out: the
program fails a pod closed once more than 4 bound pods' anti-affinity
terms repel it. Each case drives the scheduler through
`session.measure` and `check.judge` at 1/50 of the cluster, in a
process of its own: the sound run must be correct, keep the standing
green pods and hold the incoming ones level, and the `altered` fault of
benchmark/tests/faulty.py must fail on anti-affinity. Slow: each case is
one run of the harness, 20-40 s on a CPU.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SCALE = 50

SCRIPT = """
import argparse, json, os, sys, time
T = time.perf_counter()
root, fault, seed, scale = sys.argv[1], sys.argv[2], int(sys.argv[3]), \\
    int(sys.argv[4])
sys.path.insert(0, root)
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")
from benchmark.tests import faulty
if fault != "none":
    faulty.plant(fault)
from benchmark import check, session
from benchmark.workload import load_json
data = os.path.join(root, "benchmark", "tests", "data")
cfg = load_json(os.path.join(data, "anti-affinity-rehearsal.json"))
traffic = load_json(os.path.join(data,
                                 "anti-affinity-rehearsal.traffic.json"))
args = argparse.Namespace(seed=seed, seconds=8.0, trace=0, rehearse=True)
run = session.measure(cfg, traffic, args, scale, T,
                      trace_dir=os.path.join(root, ".bench_traces", "x"))
run.release()
v, _ctl = check.judge(run, cfg)
for line in run.notes:
    print(line, file=sys.stderr)
import collections
cl = run.cluster
stamps = sorted(s for _n, s in run.binds.values() if s >= run.t0)
gone = {k: run.deleted.get(k, float("inf")) for k in run.binds}
alive = [sum(1 for k, (_n, s) in run.binds.items() if s <= t < gone[k])
         for t in stamps]
print(json.dumps({
    "correct": v.correct,
    "checks": {n: x for n, x, _l, _ok in v.rows},
    "judged": len(stamps),
    "incoming_alive_max": max(alive, default=0),
    "largest_batch": max(collections.Counter(stamps).values(), default=0),
    "standing_deleted": {t: sum(1 for k, m in zip(cl.standing_keys,
                                                  cl.standing_template)
                                if m == t and k in run.deleted)
                         for t in set(cl.standing_template)},
    "put_back": len(run.put_back)}))
"""


def rehearse(fault: str, seed: int) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", SCRIPT, ROOT, fault,
                        str(seed), str(SCALE)],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def traffic():
    with open(os.path.join(ROOT, "benchmark", "tests", "data",
                           "anti-affinity-rehearsal.traffic.json")) as f:
        return json.load(f)


def test_the_sound_run_is_correct_and_holds_its_populations(traffic):
    out = rehearse("none", 4100000021)
    assert out["correct"] is True, out
    assert out["checks"]["anti_affinity"] == 1
    assert out["checks"]["filter_fail"] == 0
    assert out["judged"] > 50
    # the standing green pods stay; the refresh touched pod-default only
    assert out["standing_deleted"]["pod-with-pod-anti-affinity"] == 0
    assert out["put_back"] == max(1, traffic["warmup_refresh"][0] // SCALE)
    # the incoming green pods stay at what the bursts bound, give or take
    # the pods of the batches the driver had not yet churned: at every
    # judged bind stamp of the window
    level = sum(max(1, n // SCALE) for n in traffic["warmup_bursts"])
    assert level <= out["incoming_alive_max"] <= (
        level + 2 * out["largest_batch"])


def test_the_altered_fault_breaks_anti_affinity():
    out = rehearse("altered", 4100000022)
    assert out["correct"] is False, out
    assert out["checks"]["anti_affinity"] >= 2
    assert out["checks"]["filter_fail"] > 0
