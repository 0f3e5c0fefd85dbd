"""Multi-process (DCN) dryrun: the PRODUCT sharded step over a
``jax.distributed`` mesh spanning OS processes.

SURVEY §2's distributed answer is ICI mesh collectives *within* a slice
plus DCN *across* hosts. The single-process virtual mesh proves the ICI
half; this module proves the DCN half the same way the driver's
``dryrun_multichip`` proves single-process sharding: N real OS processes
each own a disjoint set of CPU devices, ``jax.distributed.initialize``
federates them into one global mesh via ``make_hybrid_mesh`` (pod axis =
DCN/process boundary, node axis = ICI within a process —
parallel/mesh.py:51-95 documents why the heavy node-axis collectives
must stay intra-host), and ``build_sharded_step`` runs with cross-
process collectives (Gloo on CPU; the same program rides ICI+DCN on TPU
pods). Every process must observe the identical replicated decision, and
that decision must match a plain single-device recompute bit-for-bit.

Run it standalone:  JAX_PLATFORMS=cpu python -m minisched_tpu.parallel.dcn_dryrun
(``make dryrun-dcn``; also ``__graft_entry__.dryrun_multichip_dcn()``;
tests/test_dcn.py runs it in CI.)
"""
from __future__ import annotations

import os
import subprocess
import sys
import time

DEVS_PER_PROC = 4


def _worker_inputs():
    """Tiny but load-bearing workload: capacity-1 nodes (double-booking
    detectable), ~1/7 unschedulable, every process builds the identical
    inputs from the same deterministic spec (the multi-host contract: each
    host encodes the same replicated cluster state its informers sync)."""
    from ..encode import NodeFeatureCache, encode_pods
    from ..state.objects import (Node, NodeSpec, NodeStatus, ObjectMeta,
                                 Pod, PodSpec)

    n_nodes, n_pods = 64, 16
    cache = NodeFeatureCache(capacity=n_nodes)
    for i in range(n_nodes):
        cache.upsert_node(Node(
            metadata=ObjectMeta(name=f"node{i}"),
            spec=NodeSpec(unschedulable=(i % 7 == 0)),
            status=NodeStatus(allocatable={
                "cpu": 4000 + (i % 5) * 500, "memory": 16 << 30,
                "pods": 1})))
    pods = [Pod(metadata=ObjectMeta(name=f"pod{i}", namespace="default"),
                spec=PodSpec(requests={"cpu": 100 + (i % 3) * 50,
                                       "memory": 1 << 30}))
            for i in range(n_pods)]
    eb = encode_pods(pods, n_pods, registry=cache.registry)
    nf, _ = cache.snapshot(pad=n_nodes)
    af = cache.snapshot_assigned()
    return eb, nf, af, n_nodes, n_pods


# The worker BOOTSTRAP runs via ``python -c`` rather than ``-m``:
# importing this module imports the parallel package, whose module-level
# jnp constants initialize the XLA backend — and
# jax.distributed.initialize() must run first. The bootstrap orders it:
# env → distributed init → THEN the heavy product imports.
_BOOTSTRAP = """
import os, sys
sys.path.insert(0, {repo!r})
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count={devs}").strip()
import jax
jax.distributed.initialize(coordinator_address="127.0.0.1:{port}",
                           num_processes={nprocs}, process_id={proc_id},
                           initialization_timeout=60)
try:
    from minisched_tpu.parallel.dcn_dryrun import worker_body
    worker_body({proc_id}, {nprocs})
finally:
    jax.distributed.shutdown()
"""


def worker_body(proc_id: int, nprocs: int) -> None:
    """One DCN participant (after jax.distributed.initialize — see
    _BOOTSTRAP). Prints ``DCN-OK <proc_id>`` on success."""
    import jax
    import numpy as np

    assert jax.process_count() == nprocs
    assert jax.device_count() == nprocs * DEVS_PER_PROC

    from ..service.defaultconfig import full_scheduler_profile
    from .mesh import feature_shardings, make_hybrid_mesh
    from .sharded import build_sharded_step

    mesh = make_hybrid_mesh()  # pod axis = DCN (process), node = ICI
    assert mesh.devices.shape == (nprocs, DEVS_PER_PROC)

    eb, nf, af, n_nodes, n_pods = _worker_inputs()
    ps = full_scheduler_profile().build()
    key = jax.random.PRNGKey(0)

    # Global arrays: every process holds the SAME full host copy and
    # donates its addressable shards (jax.make_array_from_callback —
    # device_put would try to address remote shards).
    eb_sh, nf_sh, af_sh = feature_shardings(mesh, eb, nf, af)

    def globalize(tree, shardings):
        def put(arr, sh):
            a = np.asarray(arr)
            return jax.make_array_from_callback(
                a.shape, sh, lambda idx, _a=a: _a[idx])
        return jax.tree_util.tree_map(put, tree, shardings)

    step = build_sharded_step(ps, mesh, eb, nf, af)
    decision = step(globalize(eb, eb_sh), globalize(nf, nf_sh),
                    globalize(af, af_sh), key)
    jax.block_until_ready(decision)

    # Decision outputs are pod- or fully-replicated-sharded; pull the
    # pod-axis outputs to host (pod axis = DCN: each process holds its
    # rows; allgather via jax.experimental.multihost_utils).
    from jax.experimental import multihost_utils

    chosen = np.asarray(multihost_utils.process_allgather(
        decision.chosen, tiled=True))
    assigned = np.asarray(multihost_utils.process_allgather(
        decision.assigned, tiled=True))

    n_assigned = int(assigned.sum())
    if n_assigned != n_pods:
        raise RuntimeError(
            f"proc {proc_id}: only {n_assigned}/{n_pods} assigned")
    picked = chosen[assigned.astype(bool)].tolist()
    if len(set(picked)) != len(picked):
        raise RuntimeError(f"proc {proc_id}: double-booked capacity-1 "
                           f"nodes: {picked}")
    bad = [j for j in picked if j % 7 == 0]
    if bad:
        raise RuntimeError(
            f"proc {proc_id}: pods on unschedulable nodes {bad}")

    # Cross-host agreement AND single-device parity: the DCN result
    # must equal a plain local recompute (same auction assignment,
    # same key) — the collectives changed the schedule of the math,
    # not the math.
    from ..ops import build_step

    d_local = build_step(ps, pallas=False, assignment="auction")(
        eb, nf, af, key)
    for field in ("chosen", "assigned", "gang_rejected"):
        a = np.asarray(getattr(d_local, field))
        b = np.asarray(multihost_utils.process_allgather(
            getattr(decision, field), tiled=True))
        if not np.array_equal(a, b):
            raise RuntimeError(
                f"proc {proc_id}: DCN {field} diverges from "
                f"single-device: {b.tolist()} vs {a.tolist()}")
    print(f"DCN-OK {proc_id}: mesh {mesh.devices.shape} "
          f"{mesh.axis_names} over {nprocs} processes x "
          f"{DEVS_PER_PROC} devices; {n_assigned}/{n_pods} scheduled, "
          "distinct capacity-1 nodes, DCN == single-device",
          flush=True)


def run_dcn_dryrun(nprocs: int = 2, timeout_s: float = 300.0,
                   port: int = 0) -> str:
    """Spawn ``nprocs`` worker processes and assert they all print DCN-OK.
    Returns the combined stdout. Raises on any failure/timeout."""
    import socket

    if port == 0:  # pick a free port for the coordinator
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=repo + os.pathsep + os.environ.get("PYTHONPATH",
                                                             ""))
    env.pop("XLA_FLAGS", None)  # the bootstrap sets its own device count
    procs = [subprocess.Popen(
        [sys.executable, "-u", "-c", _BOOTSTRAP.format(
            repo=repo, devs=DEVS_PER_PROC, port=port, nprocs=nprocs,
            proc_id=i)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=repo) for i in range(nprocs)]
    deadline = time.monotonic() + timeout_s
    outs = []
    try:
        for p in procs:
            remaining = max(1.0, deadline - time.monotonic())
            out, _ = p.communicate(timeout=remaining)
            outs.append(out)
            if p.returncode != 0:
                raise RuntimeError(
                    f"DCN worker failed (rc={p.returncode}):\n{out}")
    except subprocess.TimeoutExpired:
        raise RuntimeError("DCN dryrun timed out:\n" + "\n".join(outs))
    finally:
        # ON ANY failure path: a worker whose peer died blocks forever in
        # a Gloo collective — kill the survivors or they leak (one
        # spinning process per failed CI run).
        for p in procs:
            if p.poll() is None:
                p.kill()
                try:
                    p.wait(timeout=5)
                except Exception:
                    pass
    combined = "\n".join(outs)
    for i in range(nprocs):
        if f"DCN-OK {i}" not in combined:
            raise RuntimeError(
                f"worker {i} did not report DCN-OK:\n{combined}")
    return combined


if __name__ == "__main__":
    print(run_dcn_dryrun())
