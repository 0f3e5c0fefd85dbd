"""Sharded-step benchmark on a virtual 8-device CPU mesh.

Measures the full sharded scheduling step (GSPMD filter/score math + the
shard_map chunked-gather assignment, parallel/sharded_assign.py) at
realistic shapes against the single-device step on the same host —
VERDICT round-1 item 3: the sharded 2k×8k step time must be recorded and
within a small constant of single-device (the CPU mesh shares one
machine's FLOPs, so parity, not speedup, is the bar; on real TPU ICI the
same program distributes memory and bandwidth).

Writes one JSON line; run via `make bench_sharded`, artifact committed as
SHARDED_BENCH.json.
"""
import json
import os
import sys
import time

# This benchmark runs on the virtual 8-device CPU mesh by construction
# (the four-chip mesh path is exercised by `chip_smoke.py --chips 4`).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax  # noqa: E402
import numpy as np  # noqa: E402


def main() -> None:
    n_nodes = int(os.environ.get("MINISCHED_SHARDED_NODES", "8192"))
    n_pods = int(os.environ.get("MINISCHED_SHARDED_PODS", "2048"))
    repeats = int(os.environ.get("MINISCHED_SHARDED_REPEATS", "3"))

    from bench_workload import bench_plugin_set, make_workload
    from minisched_tpu.encode import NodeFeatureCache, encode_pods
    from minisched_tpu.ops import build_step
    from minisched_tpu.parallel import (build_sharded_step, make_mesh,
                                        shard_features)

    make_nodes, make_pods = make_workload(n_nodes, n_pods)
    cache = NodeFeatureCache(capacity=n_nodes)
    for node in make_nodes():
        cache.upsert_node(node)
    pods = make_pods()
    plugin_set = bench_plugin_set()
    eb = encode_pods(pods, n_pods, registry=cache.registry)
    nf, _names = cache.snapshot(pad=n_nodes)
    af = cache.snapshot_assigned()
    key = jax.random.PRNGKey(0)

    out = {"nodes": n_nodes, "pods": n_pods,
           "devices": len(jax.devices()),
           "platform": jax.devices()[0].platform}

    def time_step(step_fn, args):
        """Warm call (eats the compile), then min-of-repeats wall time.
        Returns (seconds, last decision)."""
        d = step_fn(*args)
        jax.block_until_ready(d.chosen)
        t = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            d = step_fn(*args)
            jax.block_until_ready(d.chosen)
            t.append(time.perf_counter() - t0)
        return round(min(t), 4), d

    # single-device reference
    single = build_step(plugin_set, explain=False, pallas=False)
    out["single_device_s"], d = time_step(single, (eb, nf, af, key))
    chosen_single = np.asarray(d.chosen)

    # sharded step on the ("pod","node") mesh — greedy mode pinned for the
    # exact-parity row (the DEFAULT sharded assignment is now the
    # priority-tiered auction, measured below as sharded_auction_s)
    mesh = make_mesh(jax.devices())
    step = build_sharded_step(plugin_set, mesh, eb, nf, af,
                              assignment="greedy")
    eb_d, nf_d, af_d = shard_features(mesh, eb, nf, af)
    out["sharded_step_s"], ds = time_step(step, (eb_d, nf_d, af_d, key))
    out["mesh"] = f"{mesh.devices.shape} {mesh.axis_names}"
    out["equal_to_single_device"] = bool(
        np.array_equal(np.asarray(ds.chosen), chosen_single))
    out["ratio_sharded_vs_single"] = round(
        out["sharded_step_s"] / max(out["single_device_s"], 1e-9), 2)
    out["scheduled"] = int(np.asarray(ds.assigned).sum())

    # auction mode under plain GSPMD (BASELINE config 5): parallel bidding
    # rounds — one collective per round instead of per pod.
    step_a = build_sharded_step(plugin_set, mesh, eb, nf, af,
                                assignment="auction")
    out["sharded_auction_s"], da = time_step(step_a, (eb_d, nf_d, af_d, key))
    out["auction_scheduled"] = int(np.asarray(da.assigned).sum())

    # Apples-to-apples for the auction: the same algorithm single-device.
    # The greedy scan replicates its P-row scan on every virtual device
    # (free on real chips, serialized on a shared-core host), so
    # ratio_sharded_vs_single is lower-bounded by devices/cores there;
    # the auction divides its per-round work across shards, so its ratio
    # isolates the true collective overhead.
    single_a = build_step(plugin_set, explain=False, pallas=False,
                          assignment="auction")
    out["single_auction_s"], _du = time_step(single_a, (eb, nf, af, key))
    out["ratio_auction_sharded_vs_single"] = round(
        out["sharded_auction_s"] / max(out["single_auction_s"], 1e-9), 2)
    out["host_cores"] = os.cpu_count()
    print(json.dumps(out))


if __name__ == "__main__":
    main()
