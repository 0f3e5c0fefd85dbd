"""Chip smoke: drive the scheduler's product path once on a TPU.

    python chip_smoke.py                 # one chip, full size (what the driver runs)
    python chip_smoke.py --chips 4       # the four-chip mesh path and its reference only
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse [--chips 4]
                                         # CPU rehearsal at a tiny size; never reports ok

Phases (each prints its checks; any failed check exits non-zero):

1. device   -- jax.devices(); anything but a TPU fails (unless --rehearse).
2. scenario -- the README scenario through minisched_tpu.scenario.Cluster.
3. burst    -- 50,000 nodes x 10,000 pending pods (bench_workload, seed 0,
               BENCH_PLUGINS) through Cluster(...).start(config=config_from_env()):
               store -> informer -> queue -> batched jitted step -> arbitration ->
               bulk bind. Every pod bound once, node capacity respected (checked
               on the host from the store), no supervisor escalation, no slim
               readback reversion, compile cache armed.
4. kernels  -- one encoded batch of 10,240 x 50,176 (padded) through the Pallas
               kernel step, the full lax.scan step and the default shortlist
               step; chosen/assigned/free_after must be bit-identical.
5. mesh     -- (--chips 4 only) phase 3's burst with MINISCHED_MESH_DEVICES=4
               against the same burst on device 0, in this process, both with
               a batch gather window (same batches) and the full node axis
               scored (a mesh never samples nodes): bindings identical,
               every device holding shards.

Everything runs in this one process: the chip belongs to one process at a
time, so nothing here starts a child that needs JAX. The last line of a
passing chip run is exactly {"ok": true, "device": {...}}.
"""
import argparse
import faulthandler
import gc
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

FULL = {"nodes": 50_000, "pods": 10_000}
TINY = {"nodes": 1_000, "pods": 500}
PAD = 256          # bench.py's pad quantum: 10,000 -> 10,240, 50,000 -> 50,176
BOUND_DEADLINE_S = 600.0


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    print(f"  [{'ok' if ok else 'FAIL'}] {what}", flush=True)
    if not ok:
        raise SmokeFailure(what)


def _pad(n: int) -> int:
    return -(-n // PAD) * PAD


def phase_device(jax, want_chips: int, rehearse: bool) -> dict:
    devs = jax.devices()
    d0 = devs[0]
    dev = {"platform": d0.platform, "kind": d0.device_kind,
           "count": len(devs)}
    print(f"phase 1 device: {devs}", flush=True)
    print(f"  platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if not rehearse and dev["platform"] != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev['platform']!r}); "
              "this check needs the chip (--rehearse runs on the CPU)",
              file=sys.stderr)
        sys.exit(2)
    check(dev["count"] >= want_chips,
          f"{want_chips} device(s) wanted, {dev['count']} visible")
    return dev


def phase_scenario() -> None:
    from minisched_tpu.scenario.runner import default_scenario, run_scenario

    print("phase 2 scenario: README scenario through Cluster", flush=True)
    try:
        run_scenario(default_scenario)
    except AssertionError as e:
        check(False, f"scenario: {e}")
    check(True, "pod1 stayed pending, then bound to node10")


def _profile():
    from bench_workload import BENCH_PLUGINS
    from minisched_tpu.service.defaultconfig import Profile

    return Profile(name="smoke", plugins=BENCH_PLUGINS,
                   plugin_args={"NodeResourcesFit": {"score_strategy": None}})


def engine_burst(jax, size: dict, label: str, *,
                 require_cache_entries: bool):
    """One burst through the product path. Returns (pod key -> node name,
    each device's bytes in use while the burst's cluster is alive)."""
    from bench_workload import make_workload
    from minisched_tpu.config import config_from_env
    from minisched_tpu.scenario import Cluster

    n_nodes, n_pods = size["nodes"], size["pods"]
    make_nodes, make_pods = make_workload(n_nodes, n_pods, seed=0)
    nodes = make_nodes()
    c = Cluster()
    c.store.create_many(nodes)
    t0 = time.perf_counter()
    c.start(profile=_profile(), config=config_from_env())
    sync_s = time.perf_counter() - t0
    sched = c.service.scheduler
    try:
        pods = make_pods()
        t1 = time.perf_counter()
        c.store.create_many(pods)
        deadline = time.monotonic() + BOUND_DEADLINE_S
        while time.monotonic() < deadline:
            if sched.metrics()["pods_bound"] >= n_pods:
                break
            time.sleep(0.02)
        bound_s = time.perf_counter() - t1
        time.sleep(0.5)  # let the event recorder drain its queue
        m = sched.metrics()
        stored = c.store.list("Pod")
        bindings = {p.key: p.spec.node_name for p in stored}
        dev_s = m.get("batch_series", {}).get("device_s", [])
        print(f"  {label}: informer sync {sync_s:.3f} s, create->bound "
              f"{bound_s:.3f} s, {m['batches']} batches, first-batch step "
              f"(compile included) {dev_s[0] if dev_s else 'n/a'} s, later "
              f"batches max {max(dev_s[1:]) if len(dev_s) > 1 else 'n/a'} s",
              flush=True)
        print(f"  {label}: batch sizes {m.get('batch_sizes', [])}, "
              f"per-batch step s {dev_s}", flush=True)

        n_bound = sum(1 for v in bindings.values() if v)
        check(n_bound == n_pods, f"{label}: {n_bound}/{n_pods} pods bound")
        check(int(m["pods_bound"]) == n_bound,
              f"{label}: engine counted {m['pods_bound']} binds for "
              f"{n_bound} bound pods (none bound twice)")
        sched_events = {}
        for e in c.store.list("Event"):
            if e.reason == "Scheduled":
                sched_events[e.involved_object] = \
                    sched_events.get(e.involved_object, 0) + 1
        check(max(sched_events.values(), default=0) <= 1,
              f"{label}: at most one Scheduled event per pod "
              f"({len(sched_events)} pods with one)")
        check(_capacity_ok(nodes, stored),
              f"{label}: every node's bound requests fit its allocatable")
        check(m["supervisor_escalations"] == 0,
              f"{label}: supervisor_escalations == "
              f"{m['supervisor_escalations']}")
        check(m["slim_readback_reversions"] == 0,
              f"{label}: slim_readback_reversions == "
              f"{m['slim_readback_reversions']}")
        cache_dir = m["compile_cache_dir"]
        n_entries = (len(os.listdir(cache_dir))
                     if os.path.isdir(cache_dir) else 0)
        check(bool(cache_dir) and (n_entries > 0 or not require_cache_entries),
              f"{label}: compile cache armed at {cache_dir} "
              f"({n_entries} entries)")
        return bindings, _bytes_in_use(jax)
    finally:
        c.shutdown()


def _capacity_ok(nodes, pods) -> bool:
    from minisched_tpu.state.objects import RESOURCES

    alloc = {n.metadata.name: n.status.allocatable for n in nodes}
    used: dict = {}
    for p in pods:
        if not p.spec.node_name:
            continue
        u = used.setdefault(p.spec.node_name, {"pods": 0.0})
        u["pods"] += 1.0
        for r, v in p.spec.requests.items():
            u[r] = u.get(r, 0.0) + v
    bad = [(n, r) for n, u in used.items() for r, v in u.items()
           if r in RESOURCES and v > alloc[n].get(r, 0.0)]
    for n, r in bad[:5]:
        print(f"    over capacity: node {n} resource {r}: "
              f"{used[n][r]} > {alloc[n].get(r, 0.0)}", flush=True)
    return not bad


def phase_burst(jax, size: dict, rehearse: bool) -> None:
    from minisched_tpu.native import load

    print(f"phase 3 burst: {size['nodes']} nodes x {size['pods']} pods",
          flush=True)
    print(f"  native fastclone loaded: {load() is not None}", flush=True)
    _, held = engine_burst(jax, size, "burst",
                           require_cache_entries=not rehearse)
    print(f"  bytes_in_use during the burst: {held}", flush=True)


def phase_kernels(jax, size: dict) -> None:
    import numpy as np

    from bench_workload import bench_plugin_set, make_workload
    from minisched_tpu.encode import NodeFeatureCache, encode_pods
    from minisched_tpu.ops import build_step

    n_nodes, n_pods = size["nodes"], size["pods"]
    p_pad, n_pad = _pad(n_pods), _pad(n_nodes)
    print(f"phase 4 kernels: one batch {p_pad} x {n_pad} (padded)",
          flush=True)
    make_nodes, make_pods = make_workload(n_nodes, n_pods, seed=0)
    cache = NodeFeatureCache(capacity=n_nodes)
    for node in make_nodes():
        cache.upsert_node(node)
    eb = encode_pods(make_pods(), p_pad, registry=cache.registry)
    nf, _names = cache.snapshot(pad=n_pad)
    af = cache.snapshot_assigned()
    key = jax.random.PRNGKey(0)
    plugin_set = bench_plugin_set()
    outs = {}
    for name, kw in (("pallas", {"pallas": True}),
                     ("scan", {"pallas": False}),
                     ("shortlist", {"shortlist": 128})):
        step = build_step(plugin_set, explain=False, **kw)
        t0 = time.perf_counter()
        d = step(eb, nf, af, key)
        jax.block_until_ready(d.chosen)
        first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        d = step(eb, nf, af, key)
        jax.block_until_ready(d.chosen)
        warm_s = time.perf_counter() - t0
        # Host copies only: each Decision holds (P, N) planes, and three
        # of them at full size would not fit next to the next step.
        outs[name] = (np.asarray(d.chosen), np.asarray(d.assigned),
                      np.asarray(d.free_after))
        del d
        gc.collect()
        print(f"  {name}: first call (compile included) {first_s:.3f} s, "
              f"second call {warm_s:.4f} s, "
              f"{int(outs[name][1].sum())} assigned", flush=True)
    ref = outs["scan"]
    check(int(ref[1].sum()) > 0, "the scan assigned pods")
    for name in ("pallas", "shortlist"):
        got = outs[name]
        same = (np.array_equal(got[0], ref[0])
                and np.array_equal(got[1], ref[1])
                and np.array_equal(got[2].view(np.uint32),
                                   ref[2].view(np.uint32)))
        check(same, f"{name} == scan bit for bit "
                    "(chosen, assigned, free_after)")


def _bytes_in_use(jax) -> list:
    return [(d.memory_stats() or {}).get("bytes_in_use") for d in
            jax.devices()]


def phase_mesh(jax, size: dict, rehearse: bool) -> None:
    print(f"phase 5 mesh: {size['nodes']} x {size['pods']} burst, "
          "MINISCHED_MESH_DEVICES=4 vs device 0", flush=True)
    # Both runs must form the same batches (each batch's PRNG draw and
    # carried capacity depend on it): a gather window holds every pop
    # until a full batch is queued, so arrival timing cannot split one.
    os.environ.setdefault("MINISCHED_BATCH_WINDOW", "5")
    # And score the same nodes: a mesh always scores the full node axis,
    # while one device at 50k nodes by default scores a 5% sample
    # (upstream percentageOfNodesToScore). 100 = the full axis on both.
    os.environ["MINISCHED_PCT_NODES_TO_SCORE"] = "100"
    os.environ.pop("MINISCHED_MESH_DEVICES", None)
    single, _ = engine_burst(jax, size, "device 0",
                             require_cache_entries=not rehearse)
    gc.collect()
    before = _bytes_in_use(jax)
    os.environ["MINISCHED_MESH_DEVICES"] = "4"
    try:
        from minisched_tpu.config import config_from_env

        mesh = config_from_env().mesh
        print(f"  mesh: {mesh} over {[d.id for d in mesh.devices.flat]}",
              flush=True)
        check(sorted(d.id for d in mesh.devices.flat)
              == sorted(d.id for d in jax.devices()[:4]),
              "the mesh spans four devices")
        del mesh
        meshed, held = engine_burst(jax, size, "mesh 2x2",
                                    require_cache_entries=not rehearse)
    finally:
        os.environ.pop("MINISCHED_MESH_DEVICES", None)
    diff = [k for k in single if single[k] != meshed.get(k)]
    check(not diff and len(single) == len(meshed),
          f"mesh bindings identical to device 0 "
          f"({len(diff)} of {len(single)} differ)")
    print(f"  bytes_in_use before mesh burst: {before}", flush=True)
    print(f"  bytes_in_use during mesh burst: {held}", flush=True)
    if rehearse and not any(held):
        print("  (no memory_stats on this backend; shard check skipped)",
              flush=True)
        return
    grown = [(h or 0) - (b or 0) for h, b in zip(held[:4], before[:4])]
    print(f"  bytes added per device by the mesh burst: {grown}",
          flush=True)
    check(len(grown) == 4 and all(g > 0 for g in grown),
          "all four devices hold shards of the mesh burst")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the mesh path and its reference")
    ap.add_argument("--rehearse", action="store_true",
                    help="allow the CPU and shrink the cluster; never "
                         "reports ok")
    args = ap.parse_args()
    if args.rehearse and args.chips == 4:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4").strip()
    # Dump every thread's stack and exit non-zero well inside the 1200 s
    # the driver allows, rather than be killed without a word.
    faulthandler.dump_traceback_later(1100, exit=True)
    sys.path.insert(0, REPO)
    import jax

    size = TINY if args.rehearse else FULL
    try:
        dev = phase_device(jax, args.chips, args.rehearse)
        if args.chips == 4:
            phase_mesh(jax, size, args.rehearse)
        else:
            phase_scenario()
            phase_burst(jax, size, args.rehearse)
            gc.collect()
            print(f"  bytes_in_use after releasing the burst: "
                  f"{_bytes_in_use(jax)}", flush=True)
            phase_kernels(jax, size)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    if args.rehearse:
        print(f"chip_smoke: rehearsal passed on {dev['platform']} at "
              f"{size['nodes']} x {size['pods']} (no chip result)",
              flush=True)
        return 0
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
