"""Headline benchmark: pods-scheduled/sec at 50k nodes × 10k pending pods.

The reference publishes no numbers (BASELINE.md); the anchor is the driver's
north star: 50k nodes × 10k pods *scored and bound* in < 1 s on one TPU host
versus > 60 s for the reference's sequential Go loop (BASELINE.json).

Two measured paths:
  * raw step — encode 10k pods → one XLA step (filter masks + scores +
    normalize + weighted sum + capacity-aware greedy assignment over the
    full (P × N) matrix) → read back choices → bulk-commit bindings.
  * engine-through — the same pods created in the store and scheduled by
    the real engine (queue → informers → batched cycle → permit → bulk
    bind), reported from scheduler.metrics(). This measures the product,
    not a hand-rolled loop.

Runs in ONE process on the platform JAX starts with: the chip, or the
CPU only when JAX_PLATFORMS=cpu asks for it. ``detail.platform`` and
``detail.device_kind`` label every result; nothing falls back.

Prints a JSON line after each phase; the last one is the result:
  {"metric": "pods_scheduled_per_sec@50k_nodes", "value": ..., "unit":
   "pods/s", "vs_baseline": <speedup over the 60 s Go-loop anchor>, ...}

Env overrides: MINISCHED_BENCH_NODES, MINISCHED_BENCH_PODS,
MINISCHED_BENCH_REPEATS, MINISCHED_BENCH_PHASE_BUDGET (s after start
past which supplementary phases are skipped).
"""
import gc
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)


# ---------------------------------------------------------------------------
# the benchmark
# ---------------------------------------------------------------------------

def _pad_to(n: int, multiple: int = 256) -> int:
    return ((n + multiple - 1) // multiple) * multiple


def run() -> None:
    t_bench0 = time.perf_counter()
    import jax
    import numpy as np

    n_nodes = int(os.environ.get("MINISCHED_BENCH_NODES", "50000"))
    n_pods = int(os.environ.get("MINISCHED_BENCH_PODS", "10000"))
    repeats = int(os.environ.get("MINISCHED_BENCH_REPEATS", "3"))

    detail = {"nodes": n_nodes, "pods": n_pods}
    result = {"metric": f"pods_scheduled_per_sec@{n_nodes // 1000}k_nodes",
              "value": 0.0, "unit": "pods/s", "vs_baseline": 0.0,
              "detail": detail}

    dev = jax.devices()[0]
    if dev.platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        sys.exit(f"bench.py: JAX found no TPU (platform {dev.platform!r}); "
                 "a CPU run needs JAX_PLATFORMS=cpu")
    detail["platform"] = dev.platform
    detail["device"] = str(dev)
    detail["device_kind"] = dev.device_kind
    detail["device_count"] = len(jax.devices())
    detail["host_cores"] = os.cpu_count()

    from bench_workload import BENCH_PLUGINS, bench_plugin_set, make_workload
    from minisched_tpu.encode import NodeFeatureCache, encode_pods
    from minisched_tpu.ops import build_step
    from minisched_tpu.ops.pipeline import arm_compile_cache
    from minisched_tpu.state.store import ClusterStore

    # Before the raw step's first compile, as the engine does at init.
    detail["compile_cache_dir"] = arm_compile_cache()

    make_nodes, make_pods = make_workload(n_nodes, n_pods)
    plugins = BENCH_PLUGINS
    plugin_set = bench_plugin_set()
    detail["profile"] = plugins

    # ---- raw-step bench ------------------------------------------------
    t_setup = time.perf_counter()
    # Default log depth: a 10k-pod bind burst must not outrun the informer
    # and force a mid-run 60k-object re-list.
    store = ClusterStore()
    cache = NodeFeatureCache(capacity=max(64, n_nodes))
    nodes = make_nodes()
    store.create_many(nodes)
    for node in nodes:
        cache.upsert_node(node)
    pods = make_pods()
    store.create_many(pods)
    detail["setup_s"] = round(time.perf_counter() - t_setup, 2)
    # The 60k-object cluster is immortal for the run: freeze it out of the
    # GC's gen-2 scans, whose multi-hundred-ms pauses otherwise land at
    # random inside measured phases (steady-state serving GC tuning).
    gc.collect()
    gc.freeze()

    p_pad, n_pad = _pad_to(n_pods), _pad_to(n_nodes)
    key = jax.random.PRNGKey(0)
    step = build_step(plugin_set, explain=False)

    eb = encode_pods(pods, p_pad, registry=cache.registry)
    nf, names = cache.snapshot(pad=n_pad)
    af = cache.snapshot_assigned()

    t0 = time.perf_counter()
    d = step(eb, nf, af, key)
    jax.block_until_ready(d.chosen)
    detail["compile_s"] = round(time.perf_counter() - t0, 2)

    times = {"encode": [], "device": [], "commit": [], "total": []}
    runs = []
    for r in range(repeats):
        t_start = time.perf_counter()
        eb = encode_pods(pods, p_pad, registry=cache.registry)
        t_enc = time.perf_counter()
        d = step(eb, nf, af, jax.random.fold_in(key, r))
        chosen = np.asarray(d.chosen)
        assigned = np.asarray(d.assigned)
        t_dev = time.perf_counter()
        assignments = [(pods[i].key, names[int(chosen[i])])
                       for i in range(n_pods) if assigned[i]]
        scheduled = len(store.bind_pods(assignments))
        t_end = time.perf_counter()
        times["encode"].append(t_enc - t_start)
        times["device"].append(t_dev - t_enc)
        times["commit"].append(t_end - t_dev)
        times["total"].append(t_end - t_start)
        runs.append((scheduled, t_end - t_start))
        # reset (untimed): return pods to pending for the next repeat
        for key_, _node in assignments:
            p = store.get("Pod", key_)
            p.spec.node_name = ""
            p.status.phase = "Pending"
            store.update(p)

    scheduled, best_total = max(runs, key=lambda x: x[0] / max(x[1], 1e-9))
    raw_pps = scheduled / best_total if best_total > 0 else 0.0
    detail.update({
        "scheduled": int(scheduled), "total_s": round(best_total, 4),
        "encode_s": round(min(times["encode"]), 4),
        "device_s": round(min(times["device"]), 4),
        "commit_s": round(min(times["commit"]), 4),
    })
    # Machine-efficiency accounting (round-3 verdict #3): wall-clock
    # alone can't show whether the step is near what the chip could do.
    # device_s includes the decision readback; the model covers the
    # 2 filters + 2 scorers of the headline profile.
    detail["roofline_headline"] = roofline(
        min(times["device"]), p_pad, n_pad, 2, 2,
        detail.get("device_kind", ""))
    # Anchor: the Go loop takes >60 s for this config (BASELINE.json) —
    # i.e. ≤ n_pods/60 pods/s. vs_baseline = speedup over that anchor.
    result["value"] = round(raw_pps, 1)
    result["vs_baseline"] = round(raw_pps / (n_pods / 60.0), 2)
    # Incremental emission: the headline number exists NOW. Print it so a
    # later phase blowing the attempt timeout doesn't discard it — the
    # parent parses the LAST valid JSON line of whatever stdout it got.
    print(json.dumps(result))
    sys.stdout.flush()

    # ---- engine-through bench (the product number: right after the ----
    # headline so a budget overrun can only cost supplementary phases).
    # Burst phases repeat lat_samples times so the published p50/p99
    # come from ≥ 20 distinct create→bind windows (verdict r5 #8).
    lat_samples = int(os.environ.get("MINISCHED_BENCH_LAT_SAMPLES", "20"))
    try:
        detail.update(engine_bench(n_nodes, n_pods, make_nodes, make_pods,
                                   plugins, lat_samples=lat_samples))
    except Exception as e:
        detail["engine_error"] = f"{type(e).__name__}: {e}"[:300]
    print(json.dumps(result))
    sys.stdout.flush()

    # Supplementary phases run only while inside the soft budget, so a
    # caller's time limit costs skipped phases rather than the result.
    phase_budget = float(os.environ.get("MINISCHED_BENCH_PHASE_BUDGET",
                                        "600"))

    def in_budget(label: str) -> bool:
        if time.perf_counter() - t_bench0 < phase_budget:
            return True
        detail[label] = "skipped (phase budget)"
        return False

    def warm_and_time(step_fn, *args):
        """Shared phase methodology: one warm call (eats the compile),
        then one timed call. Returns (device_s, decision)."""
        dw = step_fn(*args)
        jax.block_until_ready(dw.chosen)
        t0 = time.perf_counter()
        dw = step_fn(*args)
        jax.block_until_ready(dw.chosen)
        return round(time.perf_counter() - t0, 4), dw

    # ---- config-4 THROUGH THE ENGINE: the north star on the profile ----
    # that's actually hard (round-3 verdict #1). Topology spread +
    # inter-pod affinity + fit + preemption enabled, 50k x 10k, burst AND
    # sustained streaming — create→bound through the real product path.
    try:
        from bench_workload import C4_PLUGINS, make_c4_workload

        if in_budget("engine_c4_sched_s"):
            c4e_nodes, c4e_pods = make_c4_workload(n_nodes, n_pods)
            detail.update(engine_bench(
                n_nodes, n_pods, c4e_nodes, c4e_pods, C4_PLUGINS,
                prefix="engine_c4", lat_samples=lat_samples))
            # The verdict's named key: p50 create→bound on the c4 profile.
            if "engine_c4_p50_latency_s" in detail:
                detail["engine_c4_p50"] = detail["engine_c4_p50_latency_s"]
        if in_budget("stream_c4_pods_per_sec"):
            c4e_nodes, c4e_pods = make_c4_workload(n_nodes, n_pods)
            detail.update(engine_bench(
                n_nodes, n_pods, c4e_nodes, c4e_pods, C4_PLUGINS,
                batch_size=max(256, n_pods // 5), prefix="stream_c4",
                window_s=0.25))
    except Exception as e:
        detail["engine_c4_error"] = f"{type(e).__name__}: {e}"[:300]
    print(json.dumps(result))
    sys.stdout.flush()

    # ---- skew-constrained streaming: the convergence worst case --------
    # DoNotSchedule max_skew=1 over 16 zones — every placement is gated
    # by the intra-batch skew arbitration. With the exact sequential-
    # semantics arbitration (Decision.spread_cdom tables) a burst drains
    # in a handful of cycles; the pre-batch-min approximation admitted
    # only ~(domains x max_skew) pods per cycle (round-3 verdict weak #1
    # measured 9,968/10,000 revocations in one cycle). Reported:
    # cycles-to-drain (batches), failed attempts (revocations), and
    # effective pods/s for this worst case.
    try:
        if in_budget("skew_stream_pods_per_sec"):
            sk_nodes, sk_pods = make_c4_workload(
                n_nodes, n_pods, max_skew=1, hard=True)
            detail.update(engine_bench(
                n_nodes, n_pods, sk_nodes, sk_pods, C4_PLUGINS,
                batch_size=max(256, n_pods // 5), prefix="skew_stream",
                window_s=0.25, backoff_s=0.05))
            if "skew_stream_batches" in detail:
                detail["skew_stream_cycles"] = detail["skew_stream_batches"]
    except Exception as e:
        detail["skew_stream_error"] = f"{type(e).__name__}: {e}"[:300]
    print(json.dumps(result))
    sys.stdout.flush()

    # ---- pallas vs scan: equality + timings (TPU only) -----------------
    try:
        from minisched_tpu.ops.pallas_select import pallas_supported

        if not in_budget("pallas_equals_scan"):
            pass
        elif pallas_supported(n_pad):
            d_scan = None
            for name, flag in (("pallas", True), ("scan", False)):
                v_step = build_step(plugin_set, explain=False, pallas=flag)
                detail[f"device_s_{name}"], dv = warm_and_time(
                    v_step, eb, nf, af, key)
                if flag:
                    d_pallas = dv
                else:
                    d_scan = dv
            eq = (np.array_equal(np.asarray(d_pallas.chosen),
                                 np.asarray(d_scan.chosen))
                  and np.array_equal(np.asarray(d_pallas.assigned),
                                     np.asarray(d_scan.assigned)))
            detail["pallas_equals_scan"] = bool(eq)
            if not eq:
                detail["error"] = "pallas kernel disagrees with lax.scan"
            # Kernel-only roofline: time the kernel STANDALONE at the
            # headline shape (synthetic inputs) — its traffic floor is
            # one streaming read of the (P,N) score matrix (the free
            # matrix stays resident in VMEM), ~22 flops/elem for the
            # R-row fits reduce + argmax + masked update.
            from minisched_tpu.ops.pallas_select import greedy_assign_pallas
            from minisched_tpu.ops.select import NEG as _NEG

            import jax.numpy as jnp
            from minisched_tpu.state.objects import RESOURCES as _RES

            rng_k = np.random.default_rng(3)
            ks = rng_k.random((p_pad, n_pad)).astype(np.float32) * 100
            ks[rng_k.random((p_pad, n_pad)) < 0.2] = float(_NEG)
            kreq = (rng_k.integers(1, 4, (p_pad, len(_RES))) * 100).astype(
                np.float32)
            kfree = (rng_k.integers(1, 5, (n_pad, len(_RES))) * 250).astype(
                np.float32)
            kargs = (jnp.array(ks), jnp.array(kreq), jnp.array(kfree),
                     jax.random.PRNGKey(9))
            kfn = jax.jit(greedy_assign_pallas)
            jax.block_until_ready(kfn(*kargs).chosen)
            t0 = time.perf_counter()
            jax.block_until_ready(kfn(*kargs).chosen)
            detail["pallas_kernel_s"] = round(time.perf_counter() - t0, 4)
            detail["roofline_pallas_kernel"] = roofline(
                detail["pallas_kernel_s"], p_pad, n_pad, 0, 0,
                detail.get("device_kind", ""), flops_per_elem=22.0)
        else:
            detail["pallas_equals_scan"] = "skipped (platform/tiling)"
    except Exception as e:
        detail["pallas_error"] = f"{type(e).__name__}: {e}"[:300]

    # ---- pallas kernel shape matrix (hardware) -------------------------
    # One headline shape is not evidence: sweep the kernel's tiling edges
    # — N at one lane tile, P tiny/odd (sub-POD_BLOCK padding), P > N,
    # square, large-N, and the formerly-unsupported off-lane-tile N
    # (16x64, 256x127, 256x129 — now lane-padded inside the wrapper, so
    # every shape must report "equal") — against the scan on REAL
    # hardware.
    try:
        if (in_budget("pallas_shapes")
                and jax.default_backend() == "tpu"):
            import jax.numpy as jnp

            from minisched_tpu.ops.pallas_select import (
                greedy_assign_pallas, pallas_supported)
            from minisched_tpu.ops.select import NEG, greedy_assign

            table = {}
            rng = np.random.default_rng(0)
            for sp, sn in ((8, 128), (3, 128), (17, 384), (512, 256),
                           (128, 6400), (1024, 1024), (16, 64),
                           (256, 127), (256, 129)):
                label = f"{sp}x{sn}"
                if not pallas_supported(sn):
                    # Every swept shape must be kernel-eligible since the
                    # wrapper lane-pads; a refusal here is a regression.
                    table[label] = "UNSUPPORTED(regression)"
                    detail["error"] = "pallas_supported refused a shape"
                    continue
                scores = rng.random((sp, sn)).astype(np.float32) * 100
                scores[rng.random((sp, sn)) < 0.2] = float(NEG)
                req = (rng.integers(1, 4, (sp, 4)) * 100).astype(np.float32)
                free = (rng.integers(1, 5, (sn, 4)) * 250).astype(np.float32)
                args = (jnp.array(scores), jnp.array(req),
                        jnp.array(free), jax.random.PRNGKey(5))
                a = jax.jit(greedy_assign_pallas)(*args)
                b = jax.jit(greedy_assign)(*args)
                ok = (np.array_equal(np.asarray(a.chosen),
                                     np.asarray(b.chosen))
                      and np.array_equal(np.asarray(a.assigned),
                                         np.asarray(b.assigned)))
                table[label] = "equal" if ok else "MISMATCH"
            detail["pallas_shapes"] = table
            if any(v == "MISMATCH" for v in table.values()):
                detail["error"] = "pallas kernel mismatch in shape sweep"
    except Exception as e:
        detail["pallas_shapes_error"] = f"{type(e).__name__}: {e}"[:300]

    # ---- BASELINE config 5: gang scheduling at full scale --------------
    # (all-or-nothing joint assignment: pods in gangs of 8, quorum = 8;
    # the step is the SAME compiled program as the headline — gang inputs
    # are always traced — so this phase costs no new compile)
    try:
        if in_budget("config5_device_s"):
            pods5 = make_pods()
            for i, p in enumerate(pods5):
                p.spec.pod_group = f"gang-{i // 8}"
                p.spec.pod_group_min = 8
            eb5 = encode_pods(pods5, p_pad, registry=cache.registry)
            step5 = build_step(plugin_set, explain=False)
            detail["config5_device_s"], d5 = warm_and_time(
                step5, eb5, nf, af, key)
            detail["config5_scheduled"] = int(np.asarray(d5.assigned).sum())
            detail["config5_gang_rejected_pods"] = int(
                np.asarray(d5.gang_rejected).sum())
    except Exception as e:
        detail["config5_error"] = f"{type(e).__name__}: {e}"[:300]
    print(json.dumps(result))
    sys.stdout.flush()

    # ---- BASELINE configs 2 + 3 (staged-ladder completeness) -----------
    # Config 2: 1k nodes × 100 pods, NodeNumber score only (the "first TPU
    # smoke" config). Config 3: 10k × 1k, NodeResourcesFit +
    # LeastAllocated (dense constraint/score matrix). The headline subsumes
    # both computationally; measuring them makes BENCH_TPU.json cover the
    # whole BASELINE ladder explicitly.
    try:
        from minisched_tpu.plugins import (NodeNumber, NodeResourcesFit,
                                           NodeResourcesLeastAllocated,
                                           NodeUnschedulable, PluginSet)

        for label, (cn, cp, ps_small) in {
            "config2": (1000, 100, PluginSet([NodeUnschedulable(),
                                              NodeNumber()])),
            "config3": (10000, 1000, PluginSet(
                [NodeUnschedulable(),
                 NodeResourcesFit(score_strategy=None),
                 NodeResourcesLeastAllocated()])),
        }.items():
            # Per-config budget gate (each pays its own XLA compile), and
            # shapes clamp to the attempt's global shape so the CPU
            # fallback's deliberate reduction applies here too.
            if not in_budget(f"{label}_device_s"):
                continue
            cn, cp = min(cn, n_nodes), min(cp, n_pods)
            c_make_nodes, c_make_pods = make_workload(cn, cp)
            c_cache = NodeFeatureCache(capacity=cn)
            for node in c_make_nodes():
                c_cache.upsert_node(node)
            c_eb = encode_pods(c_make_pods(), _pad_to(cp),
                               registry=c_cache.registry)
            c_nf, _ = c_cache.snapshot(pad=_pad_to(cn))
            c_af = c_cache.snapshot_assigned()
            c_step = build_step(ps_small, explain=False)
            detail[f"{label}_shape"] = [cn, cp]
            detail[f"{label}_device_s"], dc = warm_and_time(
                c_step, c_eb, c_nf, c_af, key)
            detail[f"{label}_scheduled"] = int(np.asarray(dc.assigned).sum())
    except Exception as e:
        detail["config23_error"] = f"{type(e).__name__}: {e}"[:300]
    print(json.dumps(result))
    sys.stdout.flush()

    # ---- auction assignment mode -------------------------------------
    try:
        if in_budget("device_s_auction"):
            a_step = build_step(plugin_set, explain=False,
                                assignment="auction")
            detail["device_s_auction"], da = warm_and_time(
                a_step, eb, nf, af, key)
            detail["auction_scheduled"] = int(np.asarray(da.assigned).sum())
            # The utilization counterpart to roofline_headline: the
            # auction replaces the greedy scan's P-step sequential argmax
            # chain (the measured floor — tools/profile_step.py --passes
            # attributes ~95% of the greedy step to it) with a handful of
            # dense bidding rounds, so THIS number shows what the same
            # passes achieve when the assignment stage parallelizes.
            # extra_passes=8: the auction's bidding loop re-reads the
            # (P,N) matrix each round (~2 passes/round: bid argmax +
            # price update), and the headline shape measures ~4 rounds
            # to full assignment (ops/auction.py) — without this the
            # model undercounts auction traffic and understates its
            # utilization vs roofline_headline.
            detail["roofline_auction"] = roofline(
                detail["device_s_auction"], p_pad, n_pad, 2, 2,
                detail.get("device_kind", ""), extra_passes=8)
    except Exception as e:
        detail["auction_error"] = f"{type(e).__name__}: {e}"[:300]
    print(json.dumps(result))
    sys.stdout.flush()

    # ---- BASELINE config 4: PodTopologySpread + InterPodAffinity -------
    # (masked psum-style group/domain reductions). Runs at its own reduced
    # default shape: this is the one extra phase needing a fresh XLA
    # compile of a different plugin set, and full 50k-scale compiles of it
    # through the remote TPU compile service have blown the attempt
    # budget. MINISCHED_BENCH_C4_{NODES,PODS} override.
    try:
        if in_budget("config4_device_s"):
            from minisched_tpu.plugins import (InterPodAffinity,
                                               NodeResourcesFit,
                                               NodeUnschedulable,
                                               PluginSet, PodTopologySpread)
            from minisched_tpu.state.objects import (
                Affinity, LabelSelector, PodAffinity, PodAffinityTerm,
                TopologySpreadConstraint, WeightedPodAffinityTerm)

            # Full BASELINE config-4 shape. Fits one v5e chip only because
            # the step evaluates pod CHUNKS above the pipeline's memory
            # threshold (single-pass spread/affinity temps need ~25.5G HBM
            # vs 15.75G available, measured).
            c4_nodes = int(os.environ.get("MINISCHED_BENCH_C4_NODES",
                                          str(n_nodes)))
            c4_pods = int(os.environ.get("MINISCHED_BENCH_C4_PODS",
                                         str(n_pods)))
            detail["config4_shape"] = [c4_nodes, c4_pods]
            c4_make_nodes, c4_make_pods = make_workload(c4_nodes, c4_pods)
            cache4 = NodeFeatureCache(capacity=c4_nodes)
            for node in c4_make_nodes():
                cache4.upsert_node(node)
            ps4 = PluginSet([NodeUnschedulable(),
                             NodeResourcesFit(score_strategy=None),
                             PodTopologySpread(), InterPodAffinity()])
            pods4 = c4_make_pods()
            sel = LabelSelector(match_labels={"app": "bench"})
            for i, p in enumerate(pods4):
                p.metadata.labels["app"] = "bench"
                p.spec.topology_spread_constraints = [
                    TopologySpreadConstraint(
                        max_skew=8, topology_key="zone",
                        when_unsatisfiable="ScheduleAnyway",
                        label_selector=sel)]
                if i % 2 == 0:
                    p.spec.affinity = Affinity(pod_affinity=PodAffinity(
                        preferred=[WeightedPodAffinityTerm(
                            weight=10, term=PodAffinityTerm(
                                label_selector=sel, topology_key="zone"))]))
            eb4 = encode_pods(pods4, _pad_to(c4_pods),
                              registry=cache4.registry)
            nf4, _ = cache4.snapshot(pad=_pad_to(c4_nodes))
            af4 = cache4.snapshot_assigned()
            step4 = build_step(ps4, explain=False)
            t0 = time.perf_counter()
            jax.block_until_ready(step4(eb4, nf4, af4, key).chosen)
            detail["config4_compile_s"] = round(time.perf_counter() - t0, 2)
            detail["config4_device_s"], d4 = warm_and_time(
                step4, eb4, nf4, af4, key)
            detail["config4_scheduled"] = int(np.asarray(d4.assigned).sum())
            # 4 filter points + 2 score points + ~6 extra (P,N) passes of
            # topology/affinity slot math (chunked, so HBM-resident).
            detail["roofline_config4"] = roofline(
                detail["config4_device_s"], _pad_to(c4_pods),
                _pad_to(c4_nodes), 4, 2,
                detail.get("device_kind", ""), extra_passes=6)
    except Exception as e:
        detail["config4_error"] = f"{type(e).__name__}: {e}"[:300]
    print(json.dumps(result))
    sys.stdout.flush()

    # ---- sustained multi-batch engine throughput ----------------------
    # Same workload, but the engine chews it in ~5 back-to-back cycles
    # (batch_size = n_pods/5): the steady-state serving number — pad
    # bucket reuse, carried assume accounting, queue churn between
    # batches — vs the one-shot burst above.
    try:
        if in_budget("stream_pods_per_sec"):
            # Short gather window: a partial straggler batch (remainder,
            # or a capacity-requeue) must not stall its cycle for the
            # burst-mode 15s window.
            detail.update(engine_bench(
                n_nodes, n_pods, make_nodes, make_pods, plugins,
                batch_size=max(256, n_pods // 5), prefix="stream",
                window_s=0.25))
    except Exception as e:
        detail["stream_error"] = f"{type(e).__name__}: {e}"[:300]
    print(json.dumps(result))
    sys.stdout.flush()

    # ---- p99 under churn: cluster-lifecycle scenario engine ------------
    # Production-shaped workload dynamics (autoscaling pool, reclamation
    # waves, rolling upgrade under a disruption budget, diurnal + tenant
    # arrivals) driving the real engine with every lifecycle invariant
    # enforced; the latency keys come from the always-on create→bound
    # histogram. Clean here (no faults): the artifact must prove
    # degradation_state=resident with zero fires. The faulted
    # counterpart lives in tools/bench_churn.py / BENCH_CHURN.json.
    try:
        if in_budget("churn_hist_p99_s"):
            detail.update(churn_bench())
    except Exception as e:
        detail["churn_error"] = f"{type(e).__name__}: {e}"[:300]
    print(json.dumps(result))
    sys.stdout.flush()

    # ---- explain-mode overhead -----------------------------------------
    # Same engine run at 1k nodes with and without the explainability
    # recorder (off-thread ingest, top-k annotations): the per-decision
    # observability must stay a small tax, not a second workload.
    try:
        if in_budget("explain_overhead_pct"):
            xn, xp = min(n_nodes, 1000), min(n_pods, 1000)
            x_nodes, x_pods = make_workload(xn, xp)
            base = engine_bench(xn, xp, x_nodes, x_pods, plugins,
                                prefix="xbase")
            expl = engine_bench(xn, xp, x_nodes, x_pods, plugins,
                                prefix="xexpl", explain=True)
            s0 = base.get("xbase_sched_s")
            s1 = expl.get("xexpl_sched_s")
            detail["explain_base_sched_s"] = s0
            detail["explain_sched_s"] = s1
            if s0 and s1:
                detail["explain_overhead_pct"] = round(
                    100.0 * (s1 - s0) / s0, 1)
                # Absolute overhead too: at the 1k scale this phase runs
                # at (full-fidelity explain cannot materialize (F,P,N)
                # stacks at 50k x 10k — that regime uses the byte-
                # budgeted filter-bitmask tier, measured below), a small
                # base makes the percentage look dramatic while the
                # absolute cost is tens of milliseconds.
                detail["explain_overhead_abs_s"] = round(s1 - s0, 4)
    except Exception as e:
        detail["explain_error"] = f"{type(e).__name__}: {e}"[:300]
    print(json.dumps(result))
    sys.stdout.flush()

    # ---- preemption candidate search at scale (verdict #7a) ------------
    # 50k nodes, >=100k-pod assigned corpus, a 256-row failed bucket
    # through the batched candidate op with the topology-heavy filter set
    # — the steady-state serving shape ops/preempt.py's cost model
    # (O(Pf·A + R·A + R·Pf·N)) describes but round 3 never measured.
    try:
        if in_budget("preempt_device_s"):
            from minisched_tpu.ops.preempt import build_preempt_op
            from minisched_tpu.plugins import (InterPodAffinity,
                                               NodeResourcesFit,
                                               NodeUnschedulable,
                                               PluginSet, PodTopologySpread)
            from minisched_tpu.state.objects import (ObjectMeta, Pod,
                                                     PodSpec)

            a_n = int(os.environ.get("MINISCHED_BENCH_PREEMPT_CORPUS",
                                     str(max(100_000, 2 * n_pods))))
            pcache = NodeFeatureCache(capacity=max(64, n_nodes))
            pnodes = make_nodes()
            pcache.upsert_nodes_bulk(pnodes)
            # The corpus arrives through the PRODUCT bulk-sync path (the
            # informer's pod_add_many → account_bind_bulk with encoded
            # request rows), not a per-pod loop: the assigned matrix is
            # patched incrementally in one lock hold — there is no full
            # rebuild (VERDICT r4 #7).
            from minisched_tpu.engine.clusterstate import _request_rows

            t0 = time.perf_counter()
            vics = [(Pod(metadata=ObjectMeta(name=f"vic-{i}",
                                             namespace="bench",
                                             labels={"app": "bench"}),
                         spec=PodSpec(requests={"cpu": 250.0},
                                      priority=0)),
                     pnodes[i % n_nodes].metadata.name)
                    for i in range(a_n)]
            detail["preempt_corpus_objs_s"] = round(
                time.perf_counter() - t0, 2)
            t0 = time.perf_counter()
            missed = pcache.account_bind_bulk(
                vics, req_rows=_request_rows(vics))
            assert not missed
            detail["preempt_corpus_build_s"] = round(
                time.perf_counter() - t0, 2)
            detail["preempt_corpus"] = a_n
            ps_p = PluginSet([NodeUnschedulable(),
                              NodeResourcesFit(score_strategy=None),
                              PodTopologySpread(), InterPodAffinity()])
            hi = [Pod(metadata=ObjectMeta(name=f"hi-{i}",
                                          namespace="bench"),
                      spec=PodSpec(requests={"cpu": 4000.0},
                                   priority=100))
                  for i in range(256)]
            ebp = encode_pods(hi, 256, registry=pcache.registry)
            nfp, _ = pcache.snapshot(pad=n_pad)
            afp = pcache.snapshot_assigned()
            pop = build_preempt_op(ps_p)
            chosen_p, ok_p, _cnt, _sev = pop(ebp, nfp, afp)
            jax.block_until_ready(chosen_p)
            t0 = time.perf_counter()
            chosen_p, ok_p, _cnt, _sev = pop(ebp, nfp, afp)
            jax.block_until_ready(chosen_p)
            detail["preempt_device_s"] = round(time.perf_counter() - t0, 4)
            detail["preempt_candidates_found"] = int(np.asarray(ok_p).sum())
    except Exception as e:
        detail["preempt_error"] = f"{type(e).__name__}: {e}"[:300]
    print(json.dumps(result))
    sys.stdout.flush()

    # ---- full-N filter-bitmask retention at headline scale (#7b) -------
    # Host-side: ingest one 10k x 50k explain batch into the ResultStore
    # and measure what the byte-budgeted verdict retention ACTUALLY holds
    # (rows are copies since round 4 — residency must track the budget,
    # not the 2 GB batch array).
    try:
        if in_budget("explain_bitmask_mb"):
            from minisched_tpu.explain.resultstore import ResultStore

            class _K:
                __slots__ = ("key",)

                def __init__(self, k):
                    self.key = k

            class _PS:
                filter_plugins = [type("F", (), {"name": "NodeResourcesFit"})()]
                score_plugins = []

                @staticmethod
                def weight_of(p):
                    return 1.0

            class _D:
                pass

            bm_p, bm_n = n_pods, n_pad
            d_fake = _D()
            rng_b = np.random.default_rng(1)
            d_fake.filter_masks = rng_b.random((1, bm_p, bm_n)) > 0.1
            d_fake.raw_scores = np.zeros((0, bm_p, bm_n), np.float32)
            d_fake.norm_scores = d_fake.raw_scores
            names_b = [f"n{i}" for i in range(bm_n)]
            # top_k = N skips the per-pod annotation top-k selection (a
            # (P,N) float64 argpartition — not what this phase measures);
            # only the bitmask ingest path runs.
            rs_b = ResultStore(ClusterStore(), flush=False, top_k=bm_n)
            t0 = time.perf_counter()
            rs_b.record_batch([_K(f"bench/bm{i}") for i in range(bm_p)],
                              names_b, d_fake, _PS())
            detail["explain_bitmask_ingest_s"] = round(
                time.perf_counter() - t0, 3)
            held = sum(v[1].nbytes for v in rs_b._filter_bits.values())
            detail["explain_bitmask_mb"] = round(held / 1e6, 1)
            detail["explain_bitmask_budget_mb"] = round(
                rs_b._full_n_budget / 1e6, 1)
            detail["explain_bitmask_rows"] = len(rs_b._filter_bits)
            if held > rs_b._full_n_budget * 1.05:
                detail["error"] = "bitmask retention exceeded its budget"
    except Exception as e:
        detail["bitmask_error"] = f"{type(e).__name__}: {e}"[:300]

    # ---- engine over the WIRE (the reference's process shape with ------
    # auth + flow control ON): store behind the HTTP apiserver, the
    # scheduler attached as a pure network client. Modest scale — the
    # long-poll informer pump, JSON codec, bind subresource, and the
    # client token bucket are the system under test here, not XLA.
    try:
        if in_budget("wire_pods_per_sec"):
            from bench_workload import make_workload as _mw

            # Stable wire shape across ambient/fallback runs: the CPU
            # fallback halves pods (2000x1000), which would shrink the
            # wire burst and skew wire_vs_inprocess_pct low (fixed
            # per-run costs amortize over fewer pods). Allowing up to 2x
            # the configured pod budget restores 2000x2000 for BOTH the
            # ambient (10k-pod) and fallback (1k-pod) runs while keeping
            # explicit tiny-budget smoke runs bounded.
            w_n = min(n_nodes, 2000)
            w_p = min(w_n, 2 * n_pods)
            w_nodes, w_pods = _mw(w_n, w_p, seed=7)
            detail.update(engine_bench(w_n, w_p, w_nodes, w_pods,
                                       plugins, prefix="wire", wire=True))
            # Same-shape in-process comparator: the r4 verdict compared
            # the wire number against a DIFFERENT-shape in-process one;
            # this makes "wire ≥ 50% of in-process" checkable directly.
            detail.update(engine_bench(w_n, w_p, w_nodes, w_pods,
                                       plugins, prefix="inproc_wshape"))
            wp = detail.get("wire_pods_per_sec", 0)
            ip = detail.get("inproc_wshape_pods_per_sec", 0)
            if wp and ip:
                detail["wire_vs_inprocess_pct"] = round(100.0 * wp / ip, 1)
    except Exception as e:
        detail["wire_error"] = f"{type(e).__name__}: {e}"[:300]

    print(json.dumps(result))  # flush bitmask/wire numbers before the
    sys.stdout.flush()         # multi-second persist phase can be killed

    # ---- durability cost at headline shape (round-5 persistence) -------
    # Checkpoint + restore of the MAIN store (already holding every node
    # and the whole pod population): the two halves of
    # restart-to-first-batch the lifecycle now owns (interval/shutdown
    # checkpoints; open_or_restore at boot). Bulk node sync
    # (engine_sync_s above) is the third term.
    try:
        if in_budget("persist_save_s"):
            import tempfile

            from minisched_tpu.state.persistence import (Checkpointer,
                                                         open_or_restore)

            with tempfile.TemporaryDirectory() as td:
                ppath = os.path.join(td, "bench-snap.json")
                cp = Checkpointer(store, ppath)
                t0 = time.perf_counter()
                cp.checkpoint()
                detail["persist_save_s"] = round(time.perf_counter() - t0, 3)
                detail["persist_snapshot_mb"] = round(
                    os.path.getsize(ppath) / 1e6, 1)
                t0 = time.perf_counter()
                restored = open_or_restore(ppath)
                detail["persist_restore_s"] = round(
                    time.perf_counter() - t0, 3)
                counts = restored.stats()["objects"]
                if (counts["Node"] != n_nodes or counts["Pod"] != n_pods
                        or restored.resource_version()
                        != store.resource_version()):
                    # setdefault: never clobber an earlier phase's error
                    detail.setdefault("error", "persist roundtrip mismatch")
                cp.close()
    except Exception as e:
        detail["persist_error"] = f"{type(e).__name__}: {e}"[:300]

    print(json.dumps(result))
    sys.stdout.flush()
    maybe_append_ledger(result)
    # Skip interpreter teardown of the phases' leftover engine threads
    # and device buffers; everything worth keeping is written above.
    os._exit(0)


# ---------------------------------------------------------------------------
# cross-run perf ledger (BENCH_LEDGER.json): normalized key series appended
# per run so tools/bench_compare.py can diff a fresh run against the
# committed trajectory — the committed BENCH_*.json artifacts alone are
# point-in-time and were never compared, so a perf regression landed
# silently. `make bench-check` gates on it.
# ---------------------------------------------------------------------------

LEDGER_SCHEMA = 1

#: The normalized, cross-run-comparable key set. Direction is derived
#: from the name by tools/bench_compare.py: *_pods_per_sec higher is
#: better; *_s / *_bytes lower is better.
LEDGER_DETAIL_KEYS = (
    "device_s", "encode_s", "commit_s",
    "engine_pods_per_sec", "engine_sched_s",
    "engine_hist_p50_s", "engine_hist_p95_s", "engine_hist_p99_s",
    "engine_gap_s", "engine_step_s", "engine_encode_s",
    "engine_commit_s", "engine_h2d_bytes", "engine_fetch_bytes",
    "stream_pods_per_sec", "stream_hist_p99_s", "stream_gap_s",
    "churn_pods_per_sec", "churn_hist_p50_s", "churn_hist_p95_s",
    "churn_hist_p99_s",
)


def ledger_keys(detail: dict, headline_value: float = 0.0) -> dict:
    """Extract the normalized key series from a bench detail dict —
    only numeric, non-zero keys make the series (a skipped phase must
    not record a fake 0 that every later run would 'regress' against)."""
    keys = {}
    if headline_value:
        keys["raw_pods_per_sec"] = headline_value
    for k in LEDGER_DETAIL_KEYS:
        v = detail.get(k)
        if isinstance(v, (int, float)) and not isinstance(v, bool) and v:
            keys[k] = v
    return keys


def append_ledger(entry: dict, path: str) -> None:
    """Append one run entry ({ts, platform, nodes, pods, keys}) to the
    ledger at ``path`` (created if absent), atomically — a killed bench
    must not leave a torn JSON that poisons every later compare."""
    doc = {"schema": LEDGER_SCHEMA, "runs": []}
    try:
        with open(path, encoding="utf-8") as f:
            loaded = json.load(f)
        if isinstance(loaded, dict) and isinstance(loaded.get("runs"),
                                                   list):
            doc = loaded
    except (OSError, json.JSONDecodeError):
        pass
    doc["runs"].append(entry)
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    os.replace(tmp, path)


def ledger_entry_from_result(parsed: dict) -> dict:
    detail = parsed.get("detail", {}) or {}
    return {
        "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        # Methodology stamp: full-bench phases and the bench-check
        # capture use different batch sizes / windows / lat_samples at
        # the same shape — tools/bench_compare.latest_baseline matches
        # on this so the noise thresholds only ever compare
        # like-for-like runs.
        "source": "bench",
        "platform": detail.get("platform", "unknown"),
        "nodes": detail.get("nodes", 0),
        "pods": detail.get("pods", 0),
        "keys": ledger_keys(detail, float(parsed.get("value", 0.0))),
    }


def check_phases(n_nodes: int, n_pods: int, lat_samples: int = 2) -> dict:
    """The check-shape phase pair every cross-run comparison tool runs
    (tools/bench_compare.py capture, tools/bench_slo.py off/on rounds):
    one engine burst + one sustained-stream round through the real
    product path. ONE definition — tools hand-coding the pair would
    drift apart and silently break off/on-vs-ledger comparability."""
    from bench_workload import BENCH_PLUGINS, make_workload

    out = {}
    mk_nodes, mk_pods = make_workload(n_nodes, n_pods)
    out.update(engine_bench(n_nodes, n_pods, mk_nodes, mk_pods,
                            BENCH_PLUGINS, lat_samples=lat_samples))
    out.update(engine_bench(n_nodes, n_pods, mk_nodes, mk_pods,
                            BENCH_PLUGINS,
                            batch_size=max(64, n_pods // 4),
                            prefix="stream", window_s=0.25))
    return out


def maybe_append_ledger(parsed: dict) -> None:
    """Append this run to the ledger unless disabled.
    MINISCHED_BENCH_LEDGER: unset/default → BENCH_LEDGER.json beside
    this file; ``0`` disables; any other value is the path.

    Baseline hygiene: a run with injected faults armed, fault fires
    recorded, or a degraded engine state is NOT a baseline — appending
    it would make it the newest same-shape entry bench_compare diffs
    against, and the gate would then bless exactly the regression it
    exists to catch. Such runs are skipped (the fault counters in the
    bench JSON itself still record that the run was faulted)."""
    target = os.environ.get("MINISCHED_BENCH_LEDGER", "BENCH_LEDGER.json")
    if not target or target == "0":
        return
    if os.environ.get("MINISCHED_FAULTS"):
        return  # fault-armed runs are never baselines
    detail = parsed.get("detail", {}) or {}
    for prefix in ("engine", "stream", "churn"):
        if detail.get(f"{prefix}_fault_fires"):
            return
        state = detail.get(f"{prefix}_degradation_state")
        if state not in (None, "resident"):
            return
    if not os.path.isabs(target):
        target = os.path.join(REPO, target)
    entry = ledger_entry_from_result(parsed)
    if not entry["keys"]:
        return  # a dead run records nothing
    try:
        append_ledger(entry, target)
    except Exception as e:  # the ledger must never fail the bench
        print(f"ledger append failed: {type(e).__name__}: {e}",
              file=sys.stderr)


_HBM_PEAK_GBPS = {
    # device_kind substring → HBM bandwidth (GB/s), from Google Cloud's
    # published TPU pages ("TPU v5e": 819 GB/s; v4, v5p, v6e likewise).
    "v4": 1228.0, "v5 lite": 819.0, "v5e": 819.0, "v5p": 2765.0,
    "v6 lite": 1640.0, "v6e": 1640.0,
}


def roofline(seconds: float, p: int, n: int, n_filters: int,
             n_scorers: int, device_kind: str, *, extra_passes: int = 0,
             flops_per_elem: float = 6.0) -> dict:
    """Coarse, EXPLICIT machine-efficiency accounting for one step.

    Traffic model (f32, fusion-optimistic): each filter materializes one
    (P,N) pass (write+read of the running mask is fused; feature reads
    are O(N·R), negligible), each scorer two passes (score + normalize
    reduction re-read), the weighted total one write, and the assignment
    stage one streaming read of the score matrix — plus ``extra_passes``
    for profile-specific (P,N) temps (topology/affinity slot math).
    FLOPs ≈ flops_per_elem per (P,N) element per plugin pass (compares,
    selects, multiply-adds — VPU work; the step has no MXU matmuls, so
    the relevant peak is HBM bandwidth, not TensorCore FLOPs). The point
    is auditability (which regime each phase is in, and whether a change
    regressed arithmetic intensity), not cycle accuracy."""
    passes = n_filters + 2 * n_scorers + 2 + extra_passes
    if n_filters == 0 and n_scorers == 0:
        # kernel-only accounting: one streaming read of the score matrix
        passes = 1 + extra_passes
    bytes_moved = passes * p * n * 4.0
    flops = passes * p * n * flops_per_elem
    kind = (device_kind or "").lower()
    if kind == "cpu":
        return {"not_measured": "cpu run: no device roofline"}
    peak = next((v for k, v in _HBM_PEAK_GBPS.items() if k in kind), None)
    if peak is None:
        raise ValueError(f"no HBM peak on record for device_kind "
                         f"{device_kind!r}; add it to _HBM_PEAK_GBPS")
    gbps = bytes_moved / max(seconds, 1e-9) / 1e9
    return {
        "model": f"{passes} fused (PxN) f32 passes "
                 f"({n_filters}F+2x{n_scorers}S+2+{extra_passes} extra), "
                 f"{flops_per_elem} flops/elem",
        "bytes_gb": round(bytes_moved / 1e9, 2),
        "achieved_gbps": round(gbps, 1),
        "pct_hbm_peak": round(100.0 * gbps / peak, 1),
        "hbm_peak_gbps": peak,
        "achieved_gflops": round(flops / max(seconds, 1e-9) / 1e9, 1),
        "regime": ("bandwidth-bound (VPU elementwise; no MXU matmuls)"
                   if gbps / peak > 0.25 else
                   "latency/overhead-bound (under 25% of HBM peak — "
                   "dispatch, scan sequentialization, or readback "
                   "dominates)"),
    }


def _hist_latency_keys(m: dict, prefix: str) -> dict:
    """p50/p95/p99 create→bound from the engine's fixed-bucket lifecycle
    histogram (Scheduler.metrics()["histograms"]) — interpolated from
    bucket counts (obs.hist_quantile), covering EVERY bound pod of the
    run rather than the sampled windows."""
    from minisched_tpu.obs import hist_quantile

    snap = (m.get("histograms") or {}).get("pod_create_to_bound_s")
    if not snap or not snap.get("count"):
        return {}
    return {
        f"{prefix}_hist_p50_s": round(hist_quantile(snap, 0.50), 4),
        f"{prefix}_hist_p95_s": round(hist_quantile(snap, 0.95), 4),
        f"{prefix}_hist_p99_s": round(hist_quantile(snap, 0.99), 4),
        f"{prefix}_hist_bound_count": int(snap["count"]),
    }


def engine_bench(n_nodes, n_pods, make_nodes, make_pods, plugins,
                 batch_size=None, prefix="engine", window_s=15.0,
                 explain=False, backoff_s=None, wire=False,
                 lat_samples=1) -> dict:
    """Schedule the same workload through the REAL engine: store + informers
    + queue + batched cycle + bulk bind; throughput from scheduler.metrics().
    Two passes — the first eats XLA compiles for the engine's pad buckets,
    the second (fresh store, warm step cache) is the measurement.

    ``batch_size`` < n_pods turns the single-burst measurement into a
    SUSTAINED multi-batch one: the engine chews through the same workload
    in n_pods/batch_size back-to-back cycles (pad bucket reused, assume
    accounting carried across batches) — the steady-state serving number
    rather than the one-shot burst number. Output keys take ``prefix``.

    ``wire=True`` runs the ENGINE AS A PURE NETWORK CLIENT (the
    reference's process shape, scheduler/scheduler.go:54-75): the store
    sits behind the HTTP apiserver with bearer-token auth + flow control
    ON, the scheduler attaches via RemoteStore (informers long-polling
    /watch, bindings through /bind), and the pod burst is submitted over
    the wire too.

    ``lat_samples`` > 1 repeats the measured burst that many times
    (fresh uniquely-named pods per round, previous round's pods deleted
    so capacity and pad buckets stay constant): single-burst phases
    otherwise commit every pod in ONE bulk transaction — one
    scheduled_time stamp — and the published p50/p99 collapse to one
    sample dressed as a distribution (round-5 verdict weak #6). The
    latency percentiles then span ≥ lat_samples distinct
    creation→bind windows BY CONSTRUCTION; throughput keys keep their
    historical first-round meaning."""
    from minisched_tpu.config import SchedulerConfig
    from minisched_tpu.service.defaultconfig import Profile
    from minisched_tpu.service.service import SchedulerService
    from minisched_tpu.state.store import ClusterStore

    batch_size = batch_size or n_pods
    profile = Profile(name="bench", plugins=plugins,
                      plugin_args={"NodeResourcesFit":
                                   {"score_strategy": None}})
    out = {}
    for attempt in ("warmup", "measured"):
        # Default log depth: a 10k-pod bind burst must not outrun the
        # informer and force a mid-run 60k-object re-list.
        store = ClusterStore()
        store.create_many(make_nodes())
        api = client = None
        if wire:
            from minisched_tpu.apiserver import APIServer, RemoteStore

            api = APIServer(store, token="bench-token",
                            max_inflight=256).start()
            client = RemoteStore(api.address, token="bench-token")
        svc = SchedulerService(client if wire else store)
        t0 = time.perf_counter()
        # The gather window lets the whole pod burst form ONE full-sized
        # batch (deterministic pad bucket, warmed by the warmup pass)
        # instead of fragmenting into partial batches that each pay a
        # fresh XLA compile. Gathering terminates exactly when all
        # n_pods are queued; the window is only the stall-tolerant cap.
        # Idle-exit at 100 ms for the STREAMING phases (batch < n_pods):
        # the burst's tail batch must not stall for the whole gather
        # window (a 1000-pod burst at batch 256 paid the full window on
        # its 232-pod tail — ~half the measured stream window at the
        # CPU-fallback shape was that artifact). The grace sits AT the
        # pop_batch docstring's informer-stall floor (gen-2 GC / wire
        # long-poll hiccups): smaller would risk splitting a straggler
        # batch onto a cold pad bucket and absorbing its XLA compile
        # into the measured window. Single-batch BURST phases keep the
        # pure window: their batch fills and pops on the count check,
        # and an idle heuristic could only ever split them.
        cfg = SchedulerConfig(max_batch_size=batch_size,
                              batch_window_s=window_s, explain=explain,
                              batch_idle_s=(0.1 if batch_size < n_pods
                                            else 0.0),
                              # honor the engine's sync-fallback knob so
                              # pipelined-vs-synchronous comparisons run
                              # through the same harness, and the
                              # residency fallback knob likewise
                              # (tools/bench_residency.py toggles it)
                              pipeline=os.environ.get(
                                  "MINISCHED_PIPELINE", "1") != "0",
                              device_resident=os.environ.get(
                                  "MINISCHED_DEVICE_RESIDENT", "1") != "0",
                              # shortlist knobs likewise
                              # (tools/bench_shortlist.py toggles them)
                              shortlist=os.environ.get(
                                  "MINISCHED_SHORTLIST", "1") != "0",
                              shortlist_k=int(os.environ.get(
                                  "MINISCHED_SHORTLIST_K", "128")),
                              # persistent device-loop knobs likewise
                              # (tools/bench_deviceloop.py toggles them)
                              device_loop=os.environ.get(
                                  "MINISCHED_DEVICE_LOOP", "0") == "1",
                              # assignment strategy likewise
                              # (tools/bench_auction.py runs the
                              # auction path through the same harness)
                              assignment=os.environ.get(
                                  "MINISCHED_ASSIGNMENT", "greedy"),
                              loop_depth=int(os.environ.get(
                                  "MINISCHED_LOOP_DEPTH", "8")))
        if backoff_s is not None:
            # Skew-style convergence workloads retry revoked pods across
            # cycles; the reference's 1 s initial backoff would dominate
            # the measured drain time rather than the scheduler.
            cfg.backoff_initial_s = backoff_s
        sched = svc.start_scheduler(profile, cfg)
        # Cold-start boundary: the scheduler has synced the 50k-node
        # cluster; everything after this point is steady-state serving.
        # engine_total_s includes this bootstrap, engine_sched_s (the
        # create→all-bound window) does not.
        sync_s = time.perf_counter() - t0
        base_assigned = sched.cache.assigned_count()
        # Freeze the synced cluster out of gen-2 GC (see raw-step bench);
        # unfrozen, collection pauses over ~10^6 long-lived objects land
        # randomly inside the measured window and dominate its variance.
        gc.collect()
        gc.freeze()
        # Build the workload objects BEFORE the clock starts: the
        # create→bound window measures the scheduler from submission,
        # not the client's own object construction.
        # Warmup runs TWO rounds when latency sampling is on: round 2 is
        # the first to see the post-bind assigned-corpus pad bucket, and
        # its XLA compile must land in the warmup pass, not in the
        # measured p99.
        rounds = lat_samples if attempt == "measured" else min(
            2, lat_samples)
        per_pod_lat: list = []
        round_times: list = []
        short = [None]  # non-convergence note from any measured round
        sched_s = 0.0
        bound = 0
        deadline_s = float(
            os.environ.get("MINISCHED_BENCH_ENGINE_DEADLINE", "240"))
        for r in range(max(1, rounds)):
            pod_objs = make_pods()
            if r:
                # fresh identities per extra latency round (same shape)
                for p in pod_objs:
                    p.metadata.name = f"{p.metadata.name}-r{r}"
            t_pods = time.perf_counter()
            # Bulk submission: the workload burst arrives as one store
            # transaction (one watch wake-up); the informer drains it in
            # batches — the creation loop is off the critical path.
            (client if wire else store).create_many(pod_objs)
            deadline = time.time() + deadline_s
            target = n_pods * (r + 1)
            while time.time() < deadline:
                m = sched.metrics()
                bound = int(m["pods_bound"])
                if bound >= target:
                    break
                time.sleep(0.02)
            round_s = time.perf_counter() - t_pods
            round_times.append(round_s)
            if r == 0:
                # throughput keys keep their historical single-burst
                # meaning: the FIRST round's create→all-bound window
                sched_s = round_s
                bound_r0 = min(bound, n_pods)
            if attempt == "measured":
                keys = {p.key for p in pod_objs}
                per_pod_lat.extend(
                    p.status.scheduled_time - p.metadata.creation_timestamp
                    for p in store.list("Pod")
                    if p.status.scheduled_time and p.key in keys)
            if bound < target:
                # Surface the shortfall explicitly: the first-round keys
                # would otherwise publish a healthy-looking benchmark
                # while later latency rounds silently stalled.
                short[0] = (f"round {r} bound {bound - r * n_pods}"
                            f"/{n_pods} at deadline")
                break  # did not converge; stop burning rounds
            if r < rounds - 1:
                # Return to the pre-burst cluster (untimed): capacity,
                # assigned-corpus high water, and pad buckets stay
                # constant, so every round measures the same problem.
                for p in pod_objs:
                    try:
                        store.delete("Pod", p.key)
                    except Exception:
                        pass
                # Barrier: wait for the engine to PROCESS the unbinds
                # (informer drain + cache accounting) so the cleanup's
                # asynchronous tail cannot bleed into the next round's
                # timed create→bind window.
                cleanup_dl = time.time() + 30
                while time.time() < cleanup_dl:
                    if sched.cache.assigned_count() <= base_assigned:
                        break
                    time.sleep(0.01)
        total_s = time.perf_counter() - t0
        if attempt == "warmup":
            # Cold-start ledger (ROADMAP cold-start item): the warmup
            # pass is where XLA compiles land — its wall clock minus the
            # warmed measured pass approximates compile seconds, which
            # is what the persistent compile cache exists to eliminate
            # across process restarts.
            warmup_total_s = total_s
        m = sched.metrics()
        svc.shutdown_scheduler()
        if api is not None:
            api.shutdown()
        gc.unfreeze()  # let the torn-down cluster actually be collected
        if attempt == "warmup" and bound < n_pods:
            # Warm-up couldn't bind everything inside the deadline; the
            # measured pass would only repeat that. Report the warm-up
            # pass (marked) instead of burning a second deadline.
            return {f"{prefix}_bound": bound,
                    f"{prefix}_batches": int(m["batches"]),
                    f"{prefix}_total_s": round(total_s, 4),
                    f"{prefix}_note":
                        "warmup pass reported; did not converge"}
        if attempt == "measured":
            # Per-pod schedule latency: creation → binding commit stamps
            # (the BASELINE metric "p50 schedule-one latency @ 50k
            # nodes"), collected per round so multi-round burst phases
            # span lat_samples distinct creation→bind windows.
            import numpy as _np

            pcts = (_np.percentile(per_pod_lat, [50, 99])
                    if per_pod_lat else (0.0, 0.0))
            out = {
                f"{prefix}_p50_latency_s": round(float(pcts[0]), 4),
                f"{prefix}_p99_latency_s": round(float(pcts[1]), 4),
                f"{prefix}_lat_samples": len(round_times),
                **({f"{prefix}_note": f"did not converge: {short[0]}"}
                   if short[0] else {}),
                f"{prefix}_bound": bound_r0,
                f"{prefix}_total_s": round(total_s, 4),
                # Warmup/compile visibility (persistent compile cache):
                # the warmup pass's wall clock and its excess over the
                # warmed measured pass (≈ XLA compile seconds this
                # process paid — near zero when the persistent cache
                # already held the executables).
                f"{prefix}_warmup_s": round(warmup_total_s, 4),
                f"{prefix}_warmup_compile_s":
                    round(max(0.0, warmup_total_s - total_s), 4),
                f"{prefix}_compile_cache_dir":
                    m.get("compile_cache_dir", ""),
                f"{prefix}_sync_s": round(sync_s, 4),
                f"{prefix}_sched_s": round(sched_s, 4),
                f"{prefix}_pods_per_sec":
                    round(bound_r0 / max(sched_s, 1e-9), 1),
                f"{prefix}_batches": int(m["batches"]),
                f"{prefix}_batch_sizes": m.get("batch_sizes", []),
                f"{prefix}_encode_s": round(m["encode_s_total"], 4),
                f"{prefix}_step_s": round(m["step_s_total"], 4),
                f"{prefix}_step_dispatch_s":
                    round(m["step_dispatch_s_total"], 4),
                f"{prefix}_pad_shapes": list(m.get("last_shapes", ())),
                f"{prefix}_commit_s": round(m["commit_s_total"], 4),
                # Pipelined-cycle overlap evidence (engine/scheduler.py):
                # host work hidden behind the device step / later stages.
                f"{prefix}_encode_overlap_s":
                    round(m.get("encode_overlap_s", 0.0), 4),
                f"{prefix}_commit_overlap_s":
                    round(m.get("commit_overlap_s", 0.0), 4),
                f"{prefix}_gap_s": round(m.get("gap_s_total", 0.0), 4),
                # engine_gap_s decomposition (flight-recorder layer): the
                # four components PARTITION gap_s — every booking is
                # tagged gather (queue-pop waits) / encode (batch-
                # formation glue) / fetch (dispatch→fetch turnaround) /
                # commit (blocking flush wait) — so their sum equals
                # gap_s by construction (BENCH_TRACE.json proves it
                # within rounding).
                f"{prefix}_gap_gather_s":
                    round(m.get("gap_gather_s_total", 0.0), 4),
                f"{prefix}_gap_encode_s":
                    round(m.get("gap_encode_s_total", 0.0), 4),
                f"{prefix}_gap_fetch_s":
                    round(m.get("gap_fetch_s_total", 0.0), 4),
                f"{prefix}_gap_commit_s":
                    round(m.get("gap_commit_s_total", 0.0), 4),
                f"{prefix}_batch_gap_gather_s":
                    m.get("batch_series", {}).get("gap_gather_s", []),
                f"{prefix}_batch_gap_encode_s":
                    m.get("batch_series", {}).get("gap_encode_s", []),
                f"{prefix}_batch_gap_fetch_s":
                    m.get("batch_series", {}).get("gap_fetch_s", []),
                f"{prefix}_batch_gap_commit_s":
                    m.get("batch_series", {}).get("gap_commit_s", []),
                # create→bound percentiles from the engine's fixed-bucket
                # lifecycle HISTOGRAM (obs.Histogram) — derived from
                # bucket counts over every bound pod, not from the
                # lat_samples sampled windows above (which stay for
                # cross-round comparability).
                **_hist_latency_keys(m, prefix),
                # Transfer observability (engine/scheduler.py counters):
                # host→device node-feature bytes (static uploads, full
                # dynamic uploads, residency correction deltas) and
                # device→host decision/spread-fetch bytes, plus the
                # residency protocol's hit/resync counts — the
                # per-batch upload/readback claim, measurable on CPU.
                f"{prefix}_h2d_bytes": int(m.get("h2d_bytes_total", 0)),
                f"{prefix}_fetch_bytes": int(m.get("fetch_bytes_total", 0)),
                f"{prefix}_residency_hits": int(m.get("residency_hits", 0)),
                f"{prefix}_residency_resyncs":
                    int(m.get("residency_resyncs", 0)),
                # Per-batch series (ROADMAP ask for the next TPU
                # capture): device window, uploaded/fetched bytes, and
                # shortlist repairs PER BATCH — totals hide exactly the
                # first-batch-vs-steady-state split the residency and
                # shortlist claims are about.
                f"{prefix}_batch_device_s":
                    m.get("batch_series", {}).get("device_s", []),
                f"{prefix}_batch_h2d_bytes":
                    m.get("batch_series", {}).get("h2d_bytes", []),
                f"{prefix}_batch_fetch_bytes":
                    m.get("batch_series", {}).get("fetch_bytes", []),
                f"{prefix}_batch_shortlist_repairs":
                    m.get("batch_series", {}).get("shortlist_repairs", []),
                # Shortlist-compressed arbitration ledger: active top-K
                # width (0 = full scan), counted repair rescans, and the
                # certified fraction — the decision-equality bench
                # (tools/bench_shortlist.py) turns these into the
                # scan-width-reduction claim.
                f"{prefix}_shortlist_width":
                    int(m.get("shortlist_width", 0)),
                f"{prefix}_shortlist_repairs":
                    int(m.get("shortlist_repairs", 0)),
                f"{prefix}_shortlist_certified":
                    int(m.get("shortlist_certified", 0)),
                f"{prefix}_shortlist_desyncs":
                    int(m.get("shortlist_desyncs", 0)),
                # Persistent device loop (MINISCHED_DEVICE_LOOP): main-
                # step device dispatches vs batches (the fused-dispatch
                # claim is steps_dispatched/batches < 1), fused tranche
                # /iteration/break counts, and blocking decision-fetch
                # TRANSFERS (one per tranche fused — the one-readback
                # byte-ledger claim rides decision_fetches).
                f"{prefix}_steps_dispatched":
                    int(m.get("steps_dispatched", 0)),
                f"{prefix}_loop_tranches": int(m.get("loop_tranches", 0)),
                f"{prefix}_loop_iterations":
                    int(m.get("loop_iterations", 0)),
                f"{prefix}_loop_breaks": int(m.get("loop_breaks", 0)),
                f"{prefix}_decision_fetches":
                    int(m.get("decision_fetches", 0)),
                f"{prefix}_loop_depth_effective":
                    int(m.get("loop_depth_effective", 0)),
                # The scored-rows ledger: pod-row × node-row plugin
                # evaluations over the run.
                f"{prefix}_scored_rows": int(m.get("scored_rows_total", 0)),
                f"{prefix}_bind_conflicts": int(m["bind_conflicts"]),
                # revocations + terminal failures summed over cycles —
                # the skew-convergence diagnostic (how much work the
                # arbitration threw back)
                f"{prefix}_failed_attempts": int(m["pods_failed"]),
                # Robustness provenance (engine supervisor + fault
                # gates): a clean artifact proves the fast paths ran
                # undegraded end-to-end — "resident" state, zero fault
                # fires, zero watchdog trips — so a wedged-probe
                # fallback is distinguishable from an injected fault.
                f"{prefix}_degradation_state":
                    m.get("degradation_state", "resident"),
                f"{prefix}_fault_fires": int(sum(
                    v for k, v in m.items()
                    if k.startswith("fault_fires_"))),
                f"{prefix}_batch_faults": int(m.get("batch_faults", 0)),
                f"{prefix}_watchdog_trips":
                    int(m.get("watchdog_trips", 0)),
                f"{prefix}_escalations":
                    int(m.get("supervisor_escalations", 0)),
                f"{prefix}_quarantined":
                    int(m.get("quarantined_batches", 0)),
                # Temporal telemetry (obs/timeseries + obs/slo): ring
                # rows taken, burn-rate alerts fired, and the
                # supervisor's counted early-warning reactions — all 0
                # with MINISCHED_TIMELINE unset (the overhead artifact
                # BENCH_SLO.json interleaves on/off on these).
                f"{prefix}_timeline_snapshots":
                    int(m.get("timeline_snapshots", 0)),
                f"{prefix}_slo_alerts": int(m.get("slo_alerts_total", 0)),
                f"{prefix}_early_warnings":
                    int(m.get("supervisor_early_warnings", 0)),
                # Decision journal + provenance (obs/journal.py) — all
                # 0 with MINISCHED_JOURNAL unset (the overhead artifact
                # BENCH_JOURNAL.json interleaves on/off on these).
                f"{prefix}_journal_events":
                    int(m.get("journal_events", 0)),
                f"{prefix}_provenance_records":
                    int(m.get("provenance_records", 0)),
            }
    return out


def churn_bench(n_base_nodes=16, duration_s=6.0, seed=None, prefix="churn",
                faults_spec="", max_unavailable=2, settle_timeout_s=60.0,
                probation=2, recovery_deadline_s=30.0) -> dict:
    """p99-under-churn phase: drive the REAL engine with the
    cluster-lifecycle scenario subsystem (minisched_tpu/lifecycle) —
    diurnal arrivals + a priority tenant mix over an autoscaling pool
    under reclamation waves and a rolling upgrade sharing one
    max-unavailable disruption budget — with every lifecycle invariant
    enforced after every event. The published p50/p95/p99 come from the
    engine's always-on create→bound histogram (every bound pod, not
    sampled windows), and the supervisor/fault counters prove whether
    the run was clean (``degradation_state=resident``, zero fires) or
    exercised the degradation ladder (``faults_spec`` armed:
    escalations > 0, then a post-churn probation pump must recover the
    engine to ``resident``).

    Env: MINISCHED_LIFECYCLE_SEED seeds the generator streams;
    MINISCHED_LIFECYCLE_RATE / MINISCHED_LIFECYCLE_AMPLITUDE scale the
    arrival curve."""
    from minisched_tpu import faults as _faults
    from minisched_tpu.config import SchedulerConfig
    from minisched_tpu.lifecycle import (AutoscalerLoop, LifecycleDriver,
                                         PoissonArrivals, ReclamationWave,
                                         RollingUpgrade, TenantMix,
                                         seed_from_env)
    from minisched_tpu.scenario import Cluster
    from minisched_tpu.service.defaultconfig import Profile

    seed = seed_from_env() if seed is None else int(seed)
    rate = float(os.environ.get("MINISCHED_LIFECYCLE_RATE", "40"))
    amplitude = float(os.environ.get("MINISCHED_LIFECYCLE_AMPLITUDE", "0.6"))

    c = Cluster()
    c.start(
        profile=Profile(name="churn",
                        plugins=["NodeUnschedulable", "NodeResourcesFit",
                                 "NodeResourcesLeastAllocated",
                                 "DefaultPreemption"]),
        config=SchedulerConfig(backoff_initial_s=0.05, backoff_max_s=0.2,
                               max_batch_size=128,
                               probation_batches=probation,
                               resident_check_every=(1 if faults_spec
                                                     else 0)),
        with_pv_controller=False)
    sched = c.service.scheduler
    out = {}
    try:
        # The base pool exists before churn so the first arrivals have
        # somewhere to land; faults arm AFTER boot (the sync path is not
        # under test here).
        driver = LifecycleDriver(c, seed=seed, pace=1.0, settle_s=8.0)
        budget = driver.budget("base", max_unavailable=max_unavailable)
        for _ in range(n_base_nodes):
            driver.view.create_pool_node("base", cpu=4000)
        driver.add(PoissonArrivals(
            "arrivals", rate_pps=rate, duration_s=duration_s,
            amplitude=amplitude, period_s=duration_s / 2, cpu=100,
            prefix="ch"))
        driver.add(TenantMix(
            "tenants", rate_pps=rate / 2, duration_s=duration_s, cpu=150))
        driver.add(AutoscalerLoop(
            "autoscaler", pool="as", interval_s=0.4, min_nodes=2,
            max_nodes=8, scale_up_pending=12, idle_rounds=2, cpu=4000,
            drain_grace_s=0.3))
        driver.add(ReclamationWave(
            "reclaim", pool="base", interval_s=duration_s / 3,
            wave_frac=0.2, grace_s=0.4,
            waves=max(1, int(duration_s // 2)), budget=budget))
        driver.add(RollingUpgrade(
            "upgrade", pool="base", budget=budget, grace_s=0.3,
            retry_s=0.25, start_after_s=0.5))
        driver.install_default_invariants()
        _faults.FAULTS.reset_counts()
        if faults_spec:
            _faults.configure(faults_spec, seed)
        t0 = time.perf_counter()
        driver.run(until_s=duration_s)
        # Snapshot fires BEFORE disarming: configure("") resets the
        # registry counters the metrics surface reads live.
        fault_fires = sum(_faults.FAULTS.counts().values())
        if faults_spec:
            # Faults stop with the churn: quiescence below is recovery.
            _faults.configure("")
        settled = driver.settle(timeout=settle_timeout_s)
        driver.check_invariants()
        churn_s = time.perf_counter() - t0

        # Recovery pump: the probation ladder re-escalates only on CLEAN
        # batches, and a drained queue produces none — feed small bursts
        # until the engine climbs back to the full fast path.
        # ``recovery_deadline_s`` needs headroom when an SLO sentinel
        # is armed: the probation gate refuses to climb while the burn
        # windows still hold, so recovery = burn-clear + probation, not
        # just probation (tools/bench_slo.py passes a longer deadline).
        pumped = 0
        if faults_spec:
            deadline = time.time() + recovery_deadline_s
            while (sched.metrics()["degradation_state"] != "resident"
                   and time.time() < deadline):
                for i in range(8):
                    driver.view.create_pod(f"pump-{pumped}-{i}", cpu=10)
                pumped += 1
                driver.settle(timeout=10)
            driver.check_invariants()

        m = sched.metrics()
        out = {
            f"{prefix}_seed": seed,
            f"{prefix}_events": len(driver.events),
            f"{prefix}_steps": driver.steps,
            f"{prefix}_invariant_checks": driver.invariant_checks,
            f"{prefix}_violations": 0,  # check_invariants raised otherwise
            f"{prefix}_settled": bool(settled),
            f"{prefix}_wall_s": round(churn_s, 3),
            f"{prefix}_pods_bound": int(m["pods_bound"]),
            f"{prefix}_pods_per_sec": round(
                m["pods_bound"] / max(churn_s, 1e-9), 1),
            f"{prefix}_batches": int(m["batches"]),
            f"{prefix}_degradation_state": m["degradation_state"],
            f"{prefix}_escalations": int(m.get("supervisor_escalations", 0)),
            f"{prefix}_recoveries": int(m.get("supervisor_recoveries", 0)),
            f"{prefix}_quarantined": int(m.get("quarantined_batches", 0)),
            f"{prefix}_watchdog_trips": int(m.get("watchdog_trips", 0)),
            f"{prefix}_fault_fires": int(fault_fires),
            f"{prefix}_faulted_steps": driver.faulted_steps,
            f"{prefix}_queue_moves": int(m.get("queue_moves", 0)),
            f"{prefix}_queue_move_skips": int(m.get("queue_move_skips", 0)),
            f"{prefix}_budget_denials": budget.denials,
            f"{prefix}_budget_high_water": budget.high_water,
            f"{prefix}_recovery_pumps": pumped,
            # Temporal telemetry: snapshot rows, burn-rate alerts, and
            # early-warning reactions (all 0 with MINISCHED_TIMELINE
            # unset; tools/bench_slo.py arms the sentinel and proves an
            # alert fires BEFORE the ladder reaches quarantine).
            f"{prefix}_timeline_snapshots":
                int(m.get("timeline_snapshots", 0)),
            f"{prefix}_slo_alerts": int(m.get("slo_alerts_total", 0)),
            f"{prefix}_early_warnings":
                int(m.get("supervisor_early_warnings", 0)),
            **_hist_latency_keys(m, prefix),
        }
        tl = sched.timeline()
        if tl.get("alerts"):
            first = tl["alerts"][0]
            out[f"{prefix}_first_alert"] = {
                "slo": first.get("slo"), "t": first.get("t"),
                "degradation_level": first.get("degradation_level")}
        if tl.get("entries"):
            out[f"{prefix}_timeline_entries"] = len(tl["entries"])
            # attribution evidence: the union of generator tags the
            # ring attributed windows to (a reclamation wave is visible
            # as its generator's tag on the rows where latency moved)
            tags = sorted({t for e in tl["entries"]
                           for t in (e.get("tags") or {})})
            if tags:
                out[f"{prefix}_timeline_tags"] = tags
        for k in ("pods_created", "pods_evicted", "pods_recreated",
                  "nodes_added", "nodes_deleted", "nodes_reclaimed",
                  "nodes_upgraded", "cordons", "uncordons",
                  "autoscaler_scale_ups", "autoscaler_scale_downs"):
            out[f"{prefix}_{k}"] = driver.view.counters.get(k, 0)
    finally:
        _faults.configure("")
        c.shutdown()
    return out


def overload_bench(duration_s=6.0, seed=None, armed=False,
                   prefix="overload", rate=None, settle_timeout_s=180.0,
                   recovery_deadline_s=120.0) -> dict:
    """Saturating-churn phase for the overload controller
    (engine/overload.py): an open-loop priority-mixed arrival curve
    deliberately faster than the throttled engine (max_batch 2, so the
    backlog — and with it queue-wait p99 — grows for the whole burst),
    driven through the lifecycle scenario engine with every invariant
    enforced after every event.

    ``armed=False``: ingress is unbounded — the published per-priority
    create→bound p99 grows with the burst duration (the unprotected
    baseline). ``armed=True``: the timeline + sentinel + controller arm
    (aggressive CPU-scale windows); the ladder climbs, low-priority
    arrivals shed into the counted lane, and the HIGH-priority class's
    p99 stays bounded near batch latency. After the burst, a recovery
    pump (clean windows only) walks the ladder back to normal and the
    shed lane drains — the artifact proves at least one full
    engage→recover cycle, a nonzero counted shed fraction with ZERO
    pods lost (oracle-checked), and no actuation flapping between
    consecutive snapshot windows (timeline-derived)."""
    from minisched_tpu.config import SchedulerConfig
    from minisched_tpu.engine import overload as overload_mod
    from minisched_tpu.lifecycle import LifecycleDriver, seed_from_env
    from minisched_tpu.obs import slo as slo_mod
    from minisched_tpu.obs import timeseries
    from minisched_tpu.scenario import Cluster
    from minisched_tpu.service.defaultconfig import Profile

    import random as _random

    seed = seed_from_env() if seed is None else int(seed)
    rate = float(rate if rate is not None else
                 os.environ.get("MINISCHED_OVERLOAD_RATE", "900"))
    c = Cluster()
    c.start(
        profile=Profile(name="overload",
                        plugins=["NodeUnschedulable", "NodeResourcesFit",
                                 "NodeResourcesLeastAllocated"]),
        config=SchedulerConfig(max_batch_size=2, backoff_initial_s=0.05,
                               backoff_max_s=0.2, probation_batches=2),
        with_pv_controller=False)
    sched = c.service.scheduler
    out = {}
    try:
        # The lifecycle driver serves as ledger + invariant ORACLE here;
        # arrivals are an open-loop fixed-rate curve created directly
        # (running them through driver.run would invariant-check after
        # every event and throttle the "saturating" burst to the oracle's
        # own store-scan speed).
        driver = LifecycleDriver(c, seed=seed, pace=1.0, settle_s=8.0)
        driver.install_default_invariants()
        for _ in range(8):
            driver.view.create_pool_node("base", cpu=400000, pods=100000)
        # Symmetric warmup in BOTH modes, BEFORE any arming: eats the
        # XLA compiles for the engine's pad buckets so the off/on
        # latency contrast measures the CONTROLLER, not compile warmth —
        # and so the warmup's compile-stalled create→bound windows can't
        # pre-burn the sentinel before the burst even starts.
        for i in range(32):
            driver.view.create_pod(f"{prefix}-warm-{i}", cpu=10,
                                   priority=1000)
        driver.settle(timeout=settle_timeout_s)
        driver.check_invariants()
        if armed:
            timeseries.configure(True, every="1", capacity=2048)
            slo_mod.configure(
                "queue_wait_p95=0.3,short=0.5,long=1.5,burn=0.3")
            overload_mod.configure(
                "shed_priority=500,min_batch=2,hold=4,probation=3,"
                "shed_backoff=0.2,shed_backoff_max=0.5")

        from minisched_tpu.state import objects as _obj

        rng = _random.Random(seed)
        t0 = time.perf_counter()
        wave = 0
        created_n = 0
        next_check = t0 + 0.75
        while True:
            now = time.perf_counter()
            if now - t0 >= duration_s:
                break
            # Owed-based pacing: the loop period jitters (sleep
            # granularity, oracle pauses), so a fixed per-tick count
            # silently undershoots the nominal rate — and an undershoot
            # that lands below engine capacity never saturates at all.
            owed = int(rate * (now - t0)) - created_n
            if owed > 0:
                driver.view.create_pods([_obj.Pod(
                    metadata=_obj.ObjectMeta(name=f"{prefix}-b{wave}-{j}",
                                             namespace="default"),
                    spec=_obj.PodSpec(
                        requests={"cpu": 10},
                        priority=1000 if rng.random() < 0.1 else 0))
                    for j in range(owed)])
                created_n += owed
                wave += 1
            if now > next_check:  # the oracle runs DURING the burst too
                driver.check_invariants()
                next_check = now + 0.75
            time.sleep(0.01)
        settled = driver.settle(timeout=settle_timeout_s)
        driver.check_invariants()
        burst_s = time.perf_counter() - t0

        # Recovery pump (armed only): clean windows walk the ladder
        # back down; the shed lane must drain to zero.
        pumped = 0
        if armed:
            deadline = time.time() + recovery_deadline_s
            while time.time() < deadline:
                m = sched.metrics()
                if (m["overload_level"] == 0 and m["queue_shed"] == 0
                        and m["degradation_state"] == "resident"):
                    break
                for i in range(3):
                    driver.view.create_pod(f"pump-{pumped}-{i}", cpu=10,
                                           priority=1000)
                pumped += 1
                driver.settle(timeout=15)
            driver.check_invariants()

        m = sched.metrics()
        # Per-priority create→bound latency straight from store truth
        # (scheduled_time − creation_timestamp, epoch seconds): the
        # engine histogram aggregates both classes, and the protected-
        # class bound is the whole point of priority-weighted shedding.
        hi, lo = [], []
        unbound = 0
        for p in c.list_pods():
            if (p.metadata.name.startswith(f"{prefix}-warm")
                    or p.metadata.name.startswith("pump-")):
                continue  # warmup/recovery-pump pods are not the
                #           measured burst traffic
            if not p.spec.node_name or not p.status.scheduled_time:
                unbound += 1
                continue
            lat = p.status.scheduled_time - p.metadata.creation_timestamp
            (hi if p.spec.priority >= 500 else lo).append(lat)

        def pct(xs, q):
            if not xs:
                return 0.0
            xs = sorted(xs)
            return round(xs[min(len(xs) - 1, int(q * len(xs)))], 4)

        # burst traffic only (warmup + recovery pumps excluded from the
        # shed-fraction denominator)
        created = len(hi) + len(lo) + unbound
        shed_total = int(m["shed_total"])
        out = {
            f"{prefix}_seed": seed,
            f"{prefix}_armed": bool(armed),
            f"{prefix}_rate_pps": rate,
            f"{prefix}_pods_created": created,
            f"{prefix}_pods_bound": int(m["pods_bound"]),
            f"{prefix}_unbound": unbound,
            f"{prefix}_settled": bool(settled),
            f"{prefix}_violations": 0,  # check_invariants raised otherwise
            f"{prefix}_burst_wall_s": round(burst_s, 3),
            f"{prefix}_pods_per_sec": round(
                m["pods_bound"] / max(burst_s, 1e-9), 1),
            f"{prefix}_high_p50_s": pct(hi, 0.50),
            f"{prefix}_high_p99_s": pct(hi, 0.99),
            f"{prefix}_low_p99_s": pct(lo, 0.99),
            f"{prefix}_shed_total": shed_total,
            f"{prefix}_shed_pods": int(m.get("queue_shed_pods", 0)),
            f"{prefix}_shed_frac": round(
                m.get("queue_shed_pods", 0) / max(created, 1), 4),
            f"{prefix}_shed_readmitted": int(m.get("queue_shed_readmitted",
                                                   0)),
            f"{prefix}_shed_left": int(m.get("queue_shed", 0)),
            f"{prefix}_escalations": int(m.get("overload_escalations", 0)),
            f"{prefix}_recoveries": int(m.get("overload_recoveries", 0)),
            f"{prefix}_transitions": int(m.get("overload_transitions", 0)),
            f"{prefix}_brownouts": int(m.get("overload_brownouts", 0)),
            f"{prefix}_level_final": int(m.get("overload_level", 0)),
            f"{prefix}_tuner_adjustments": int(
                m.get("overload_tuner_adjustments", 0)),
            f"{prefix}_recovery_pumps": pumped,
            f"{prefix}_slo_alerts": int(m.get("slo_alerts_total", 0)),
            **_hist_latency_keys(m, prefix),
        }
        tl = sched.timeline()
        entries = tl.get("entries") or []
        if entries:
            levels = [e.get("overload_level", 0) for e in entries]
            signs = [0 if b == a else (1 if b > a else -1)
                     for a, b in zip(levels, levels[1:])]
            # flap = an engage and a disengage in ADJACENT windows —
            # exactly what the hold/probation hysteresis forbids
            flap = any(s1 and s2 and s1 != s2
                       for s1, s2 in zip(signs, signs[1:]))
            out[f"{prefix}_level_max"] = max(levels)
            out[f"{prefix}_flap_free"] = not flap
            out[f"{prefix}_timeline_entries"] = len(entries)
    finally:
        c.shutdown()
        if armed:
            overload_mod.configure("")
            slo_mod.configure("")
            timeseries.configure(False)
    return out


if __name__ == "__main__":
    run()
