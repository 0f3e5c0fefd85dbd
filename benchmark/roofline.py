"""The least work a scheduling step must do, and the chip's peaks.

Counted from the semantics, not from an implementation: whichever path
assigns the pods (full scan, shortlist, Pallas kernel), a batch of P pods
against N nodes has to

- read each node's allocatable and free resources once (2 x N x R f32)
  and each pod's requests (P x R f32), and write one node per pod;
- run Filter and Score of the profile's resource plugins for every pod on
  the nodes upstream's percentageOfNodesToScore makes it consider
  (numFeasibleNodesToFind: 50 - N/125 percent, at least 5 percent and at
  least 100 nodes), and pick the best of them.

Operations per (pod, node): NodeResourcesFit 2 per tracked axis (add,
compare); LeastAllocated 3 per scored axis + 1; BalancedAllocation 5 per
scored axis + 2; PodTopologySpread 3 per constraint; selection 1. The
least time is the larger of operations over the peak rate and bytes over
HBM bandwidth: a lower bound on any step's device time.
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
SCORED_AXES = 2  # cpu, memory


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json"), encoding="utf-8") as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "benchmark/peaks.json")
    return table[device_kind]


def nodes_to_score(n: int) -> int:
    if n < 100:
        return n
    pct = max(5, 50 - n // 125)
    return min(n, max(100, n * pct // 100))


def ops_per_pair(axes: int, constraints: int) -> int:
    return (2 * axes + (3 * SCORED_AXES + 1) + (5 * SCORED_AXES + 2)
            + 3 * constraints + 1)


def least_seconds(batches: float, pods: float, nodes: int, axes: int,
                  constraints: int, device_kind: str) -> tuple:
    """(least seconds, "flops" or "bytes": which bound sets it) for
    `batches` batches holding `pods` pods in all."""
    pk = peaks(device_kind)
    ops = pods * nodes_to_score(nodes) * ops_per_pair(axes, constraints)
    nbytes = batches * 2 * nodes * axes * 4 + pods * (axes * 4 + 4)
    t_ops = ops / pk["bf16_flop_per_s"]
    t_bytes = nbytes / pk["hbm_bytes_per_s"]
    return (t_ops, "flops") if t_ops >= t_bytes else (t_bytes, "bytes")
