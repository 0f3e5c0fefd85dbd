"""A run with the timed path broken underneath must not come out correct.

Each case drives the whole harness (CPU rehearsal, 1/50 of the cluster)
with one fault planted by benchmark/tests/faulty.py, and the control case
checks that the control placements fail the check where the program's
pass. Slow: each case is one run of the harness, 20-40 s on a CPU.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run(args, fault=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = ([sys.executable, "-m", "benchmark.tests.faulty", fault]
           if fault else [sys.executable, "benchmark/run.py"])
    p = subprocess.run(cmd + args + ["--rehearse"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


CELL = ["--workload", "basic-5k.drain", "--seconds", "3", "--trace", "0"]


@pytest.mark.parametrize("fault", ["altered", "half", "stale"])
def test_a_planted_fault_is_not_correct(fault):
    out = run(CELL + ["--seed", "4100000001"], fault)
    assert out["correct"] is False, out["checks"]


CONTROL = """
import sys
from benchmark import run
run.REHEARSAL_SCALE = 5
sys.exit(run.main(sys.argv[1:]))
"""


def test_the_control_is_not_correct_where_the_program_is():
    # at 1/5 of the cluster (1,000 nodes): at 1/50 the nodes' loads differ
    # too little for a random placement to show against the limit
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-c", CONTROL, "--workload", "basic-5k.drain",
         "--seconds", "20", "--trace", "0", "--seed", "4100000002",
         "--control", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True, out["checks"]
    assert out["control"]["correct"] is False, out["control"]
