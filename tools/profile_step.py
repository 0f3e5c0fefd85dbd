"""Step/readback breakdown profiler — where does an engine cycle's device
window actually go?

The engine's ``step_s`` metric spans dispatch → packed-decision fetch →
(optional) spread fetch; on a chip each piece mixes compute, transfer,
and round-trip latency. This tool times them separately at
engine-realistic shapes so a regression can be attributed instead of
guessed at:

    python tools/profile_step.py [--nodes 50000] [--pods 10000] [--c4]

Phases reported per shape:
  step_s        one warm jitted step, block on chosen (device compute)
  pack_fetch_s  _pack_decision dispatch + (5+F, P) i32 host fetch
  slim_fetch_s  pack_decision_slim dispatch + (B,) u8 host fetch — the
                default engine readback (MINISCHED_DEVICE_RESIDENT=1)
  sp_fetch_s    _pack_spread dispatch + (2P+2, G) f32 host fetch
  cdom_fetch_s  the (G,D) exact-table transfer (hard-spread batches that
                the in-scan caps could not enforce pay this)

Plus a per-batch transfer table (h2d = what each engine batch uploads,
d2h = what it fetches) for both MINISCHED_DEVICE_RESIDENT modes, so the
residency/slim-readback byte claim is verifiable on CPU without TPU
hardware: the resident mode's steady-state h2d is the sparse correction
delta (0 bytes when nothing diverged), vs the full free/used_ports
matrices every batch in fallback mode.

Run it whenever the engine's measured step_s diverges from the raw-step
bench phase — the delta must be explainable by the fetch lines. Uses
engine pads (encode.cache.step_bucket) so numbers match the product
path, not the bench's 256-multiple pads.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=50_000)
    ap.add_argument("--pods", type=int, default=10_000)
    ap.add_argument("--c4", action="store_true",
                    help="profile the config-4 topology profile instead "
                         "of the resources-only headline profile")
    ap.add_argument("--loop", type=int, default=0, metavar="DEPTH",
                    help="also profile the persistent device loop "
                         "(ops/pipeline.build_loop_step) at this ring "
                         "depth: one fused dispatch+stacked fetch over "
                         "DEPTH copies of the batch vs DEPTH per-batch "
                         "dispatch/fetch cycles — the dispatches-per-"
                         "batch claim at raw-op level, plus the loop "
                         "depth/iteration/break counters an engine run "
                         "exposes via metrics()")
    ap.add_argument("--tenants", type=int, default=0, metavar="T",
                    help="also profile fused multi-tenant arbitration "
                         "(ops/pipeline.build_tenant_step) at T tenants: "
                         "one vmapped dispatch + one stacked fetch over "
                         "T copies of the batch vs T per-tenant "
                         "dispatch/fetch cycles — the dispatches-per-"
                         "served-batch claim at raw-op level "
                         "(MINISCHED_TENANTS_FUSE; engine counters "
                         "tenant_dispatches / tenant_fetches / "
                         "tenant_fused_lanes on the live coordinator)")
    ap.add_argument("--passes", action="store_true",
                    help="per-pass attribution ladder: time the step "
                         "with an increasing plugin subset; successive "
                         "deltas attribute each plugin's (P,N) pass, and "
                         "the first rung (a trivial mask + the greedy "
                         "scan) bounds the assignment stage — the "
                         "roofline's 'bound by X' evidence (VERDICT r4 "
                         "#6)")
    args = ap.parse_args()

    import jax
    import numpy as np

    from bench_workload import (BENCH_PLUGINS, C4_PLUGINS, make_c4_workload,
                                make_workload)
    from minisched_tpu.encode import NodeFeatureCache, encode_pods
    from minisched_tpu.encode.cache import step_bucket
    from minisched_tpu.engine.scheduler import _pack_decision, _pack_spread
    from minisched_tpu.ops import build_step
    from minisched_tpu.service.defaultconfig import Profile

    print(f"platform: {jax.devices()[0]}", flush=True)
    if args.c4:
        make_nodes, make_pods = make_c4_workload(args.nodes, args.pods)
        plugins = C4_PLUGINS
    else:
        make_nodes, make_pods = make_workload(args.nodes, args.pods)
        plugins = BENCH_PLUGINS
    pset = Profile(name="prof", plugins=plugins,
                   plugin_args={"NodeResourcesFit":
                                {"score_strategy": None}}).build()

    cache = NodeFeatureCache(capacity=max(64, args.nodes))
    for nd in make_nodes():
        cache.upsert_node(nd)
    pods = make_pods()
    p_pad = step_bucket(len(pods))
    n_pad = step_bucket(cache.rows_high_water())
    eb = encode_pods(pods, p_pad, registry=cache.registry)
    nf, names = cache.snapshot(pad=n_pad)
    af = cache.snapshot_assigned(pad=16)
    key = jax.random.PRNGKey(0)
    from minisched_tpu.config import config_from_env

    cfg_env = config_from_env()
    sl_k = cfg_env.shortlist_k if cfg_env.shortlist else None
    step = build_step(pset, explain=False, shortlist=sl_k)
    print(f"shapes: P={p_pad} N={n_pad} A={af.valid.shape[0]} "
          f"G={eb.gf.valid.shape[0]}", flush=True)
    print(f"shortlist: width={min(sl_k, n_pad) if sl_k else 0} "
          f"(sequential scan width {n_pad} -> "
          f"{min(sl_k, n_pad) if sl_k else n_pad} per step; "
          "MINISCHED_SHORTLIST / MINISCHED_SHORTLIST_K)", flush=True)

    # Overload-control posture (MINISCHED_OVERLOAD, engine/overload.py):
    # the actuation each ladder rung would apply AT THIS SHAPE — the
    # attribution row for a run whose /metrics shows overload_level > 0.
    from minisched_tpu.engine.overload import (OVERLOAD, OVERLOAD_LADDER,
                                               OverloadController)
    if OVERLOAD.enabled:
        probe = OverloadController()
        base_batch = cfg_env.max_batch_size
        print("overload actuation ladder (armed):", flush=True)
        for lvl, state in enumerate(OVERLOAD_LADDER):
            probe.level = lvl
            probe.tune_steps = min(OVERLOAD.tune_max, lvl)
            print(f"  level {lvl} {state:<9s} max_batch="
                  f"{probe.effective_max_batch(base_batch):<6d} "
                  f"window={probe.effective_window(cfg_env.batch_window_s):.3f}s "
                  f"shed={'y' if probe.shedding else 'n'}"
                  f"(prio<{OVERLOAD.shed_priority}) "
                  f"pct_nodes={probe.effective_pct_nodes(cfg_env.percentage_of_nodes_to_score)}",
                  flush=True)
    else:
        print("overload: disarmed (MINISCHED_OVERLOAD unset — ingress "
              "unbounded, no brownout ladder)", flush=True)

    stages = {}  # label → seconds, for the per-stage table below

    def timed(label, fn):
        out = fn()
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out)
        stages[label] = time.perf_counter() - t0
        print(f"{label} = {stages[label]:.4f} s", flush=True)
        return out

    if args.passes:
        # Ladder: each rung adds one plugin; the step-time delta is that
        # plugin's marginal pass cost at these shapes (fusion included —
        # which is the honest number: XLA may fold a pass into a
        # neighbor, and then its marginal cost IS ~0). Rung 0 ≈ the
        # assignment scan + dispatch floor.
        prev = None
        for k in range(1, len(plugins) + 1):
            if k == len(plugins):
                substep = step  # the full profile is already compiled
            else:
                sub = Profile(name=f"prof{k}", plugins=plugins[:k],
                              plugin_args={"NodeResourcesFit":
                                           {"score_strategy": None}}
                              ).build()
                substep = build_step(sub, explain=False, shortlist=sl_k)
            out = substep(eb, nf, af, key)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            out = substep(eb, nf, af, key)
            jax.block_until_ready(out)
            dt = time.perf_counter() - t0
            delta = "" if prev is None else f"  (+{dt - prev:.4f} marginal)"
            print(f"pass_ladder[{k}] {plugins[k-1]:32s} = {dt:.4f} s"
                  f"{delta}", flush=True)
            prev = dt

    d = timed("step_s", lambda: step(eb, nf, af, key))
    n_rep = int(np.asarray(d.shortlist_repaired).sum())
    live = len(pods)
    print(f"shortlist_repairs = {n_rep}/{live} pods "
          f"(certified-step fraction {1.0 - n_rep / max(live, 1):.4f})",
          flush=True)
    legacy = timed("pack_fetch_s", lambda: np.array(_pack_decision(
        d.chosen, d.assigned, d.gang_rejected, d.feasible_counts,
        d.feasible_static, d.reject_counts, d.shortlist_repaired)))
    from minisched_tpu.ops.residency import pack_decision_slim

    slim = timed("slim_fetch_s", lambda: np.array(pack_decision_slim(
        d.chosen, d.assigned, d.gang_rejected, d.feasible_counts,
        d.feasible_static, d.reject_counts, d.shortlist_repaired)))
    # Per-batch transfer budget, both residency modes (engine counters
    # measure the same quantities live; this is the shape-exact model):
    dyn_h2d = nf.free.nbytes + nf.used_ports.nbytes
    print("h2d/batch dynamic leaves (RESIDENT=0, every batch) = "
          f"{dyn_h2d} B ({nf.free.nbytes} free + {nf.used_ports.nbytes} "
          "used_ports)", flush=True)
    print("h2d/batch residency steady state (RESIDENT=1) = correction "
          "deltas only; 0 B when no placement was revoked and no "
          "informer event landed (engine metric h2d_bytes_total)",
          flush=True)
    print(f"d2h/batch decision fetch = {slim.nbytes} B slim vs "
          f"{legacy.nbytes} B i32 ({legacy.nbytes / max(slim.nbytes, 1):.2f}x)",
          flush=True)
    if args.loop > 1:
        # Persistent device loop (MINISCHED_DEVICE_LOOP): DEPTH copies
        # of this batch through ONE fused lax.scan dispatch + ONE
        # stacked fetch, vs the same work as DEPTH per-batch cycles.
        # Raw-op twin of the engine counters: an engine run reports
        # the live versions as metrics() steps_dispatched /
        # loop_tranches / loop_iterations / loop_breaks (and
        # `make bench-deviceloop` commits them).
        from minisched_tpu.ops.pipeline import build_loop_step
        from minisched_tpu.ops.residency import (pack_decision_slim as
                                                 _slim_pack)

        depth = args.loop
        loop_fn = build_loop_step(pset, shortlist=sl_k, slim=True)
        eb_stack = jax.tree_util.tree_map(
            lambda a: np.broadcast_to(a, (depth,) + a.shape).copy(), eb)
        ctrs = np.arange(1, depth + 1, dtype=np.uint32)

        def fused():
            packs, _free = loop_fn(eb_stack, nf, af, ctrs, key)
            return np.array(packs)   # ONE stacked d2h transfer

        stack = timed(f"loop_fused_s[{depth}]", fused)

        def per_batch():
            bufs = []
            for c in ctrs:           # DEPTH dispatches + DEPTH fetches
                dd = step(eb, nf, af, jax.random.fold_in(key, int(c)))
                bufs.append(np.array(_slim_pack(
                    dd.chosen, dd.assigned, dd.gang_rejected,
                    dd.feasible_counts, dd.feasible_static,
                    dd.reject_counts, dd.shortlist_repaired)))
            return bufs

        timed(f"loop_perbatch_s[{depth}]", per_batch)
        fused_s = stages[f"loop_fused_s[{depth}]"]
        pb_s = stages[f"loop_perbatch_s[{depth}]"]
        print(f"device_loop: depth={depth} iterations={depth} "
              f"dispatches=1 fetches=1 breaks=0 (raw op; a live engine "
              "counts breaks via metrics()['loop_breaks'])", flush=True)
        print(f"device_loop: dispatches/batch {1.0 / depth:.3f} fused "
              f"vs 1.0 per-batch; stacked fetch {stack.nbytes} B once "
              f"vs {stack.nbytes // depth} B x{depth}; wall "
              f"{fused_s:.4f} s fused vs {pb_s:.4f} s per-batch "
              f"({pb_s / max(fused_s, 1e-9):.2f}x; a CPU run proves "
              "the dispatch ledger, not a device time)",
              flush=True)

    if args.tenants > 1:
        # Fused multi-tenant arbitration (MINISCHED_TENANTS_FUSE): T
        # tenants' batches through ONE vmapped dispatch + ONE (T,6+F,P)
        # stacked fetch, vs T per-tenant dispatch/fetch cycles. Statics
        # broadcast (in_axes=None) — T tenants, one node encoding.
        from minisched_tpu.encode.cache import NodeFeatureCache as _NFC
        from minisched_tpu.ops.pipeline import build_tenant_step
        from minisched_tpu.ops.residency import pack_decision_i32

        t = args.tenants
        fused_fn = build_tenant_step(pset, shortlist=sl_k)
        eb_stack = jax.tree_util.tree_map(
            lambda a: np.broadcast_to(a, (t,) + a.shape).copy(), eb)
        af_stack = jax.tree_util.tree_map(
            lambda a: np.broadcast_to(a, (t,) + a.shape).copy(), af)
        nf_stack = nf._replace(**{
            f: np.broadcast_to(np.asarray(getattr(nf, f)),
                               (t,) + getattr(nf, f).shape).copy()
            for f in _NFC.DYNAMIC_NF_FIELDS})
        keys = np.stack([np.asarray(jax.random.fold_in(key, i))
                         for i in range(t)])
        w_row = np.asarray([pset.weight_of(p) for p in pset.score_plugins],
                           dtype=np.float32)
        w_stack = np.broadcast_to(w_row, (t,) + w_row.shape).copy()

        def fused_tenants():
            packs, _free = fused_fn(eb_stack, nf_stack, af_stack, keys,
                                    w_stack)
            return np.array(packs)   # ONE stacked d2h transfer

        stack_t = timed(f"tenants_fused_s[{t}]", fused_tenants)

        def sequential_tenants():
            bufs = []
            for i in range(t):       # T dispatches + T fetches
                dd = step(eb, nf, af, jax.random.fold_in(key, i))
                bufs.append(np.array(pack_decision_i32(
                    dd.chosen, dd.assigned, dd.gang_rejected,
                    dd.feasible_counts, dd.feasible_static,
                    dd.reject_counts, dd.shortlist_repaired)))
            return bufs

        seq_bufs = timed(f"tenants_seq_s[{t}]", sequential_tenants)
        ident = all(np.array_equal(stack_t[i], seq_bufs[i])
                    for i in range(t))
        fused_s = stages[f"tenants_fused_s[{t}]"]
        seq_s = stages[f"tenants_seq_s[{t}]"]
        print(f"tenants: T={t} dispatches 1 fused vs {t} sequential "
              f"({t:.1f}x fewer); fetches 1 ({stack_t.nbytes} B stacked) "
              f"vs {t}; bit-identical per tenant: "
              f"{'yes' if ident else 'NO'}", flush=True)
        print(f"tenants: wall {fused_s:.4f} s fused vs {seq_s:.4f} s "
              f"sequential ({seq_s / max(fused_s, 1e-9):.2f}x; a CPU run "
              "proves the dispatch ledger, not a device time)", flush=True)

    if d.spread_pre.shape[0]:
        timed("sp_fetch_s", lambda: np.array(_pack_spread(
            d.spread_pre, d.spread_dom, d.spread_min, d.scan_groups)))
        # +0 forces a FRESH device array per call: np.asarray on the same
        # jax.Array caches the host copy (_npy_value), so timing the raw
        # conversion twice would report the cached no-op, not the (G,D)
        # transfer this phase exists to attribute
        timed("cdom_fetch_s", lambda: (np.asarray(d.spread_cdom + 0),
                                       np.asarray(d.spread_dexist ^ False)))
    else:
        print("sp_fetch_s / cdom_fetch_s skipped: no topology plugin in "
              "this profile (rerun with --c4)", flush=True)

    # Per-stage table — the same decomposition the engine's flight
    # recorder (minisched_tpu/obs) and the bench's engine_gap_s
    # components report (gather/encode/h2d/dispatch/fetch/commit), here
    # as the raw-step analogs at identical pads: step compute plus each
    # readback path, with its share of the accounted total. Run the
    # engine with MINISCHED_TRACE=1 + Scheduler.dump_trace (or `make
    # bench-trace`) for the live-timeline twin of this table.
    total = sum(stages.values()) or 1.0
    print("\nper-stage table (raw-step attribution at engine pads):",
          flush=True)
    print(f"  {'stage':<16s} {'seconds':>9s} {'% accounted':>12s}",
          flush=True)
    for label, secs in stages.items():
        print(f"  {label:<16s} {secs:>9.4f} {100.0 * secs / total:>11.1f}%",
              flush=True)


if __name__ == "__main__":
    main()
