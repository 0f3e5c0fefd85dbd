"""Maintained arbitration index (MINISCHED_INDEX; ops/index.py +
engine/scheduler._ArbIndex / _index_dispatch / _settle_index).

The contract under test, end to end:

  * bit-equality — with the maintained device-resident index on, the
    engine commits EXACTLY the placements the index-off engine commits,
    in every engine mode (sync / pipelined / device-resident /
    upload-fallback / shortlist-off / device-loop), including batches
    the index must DISCARD (adversarial contention past the shortlist,
    unassigned rows, registry overflow) and batches AFTER a residency
    resync;
  * inverted dataflow — steady-state batches are served from the (C,K)
    index repaired in place by the sparse delta protocol: scored rows
    per batch drop from P_pad·N to C_pad·R_bucket (the
    batch_series.scored_rows ledger), rebuilds happen only on fresh
    classes / widening invalidations / K-dial widens, and narrowing
    node updates repair in place while widening ones rebuild
    (encode/cache.IndexDeltaListener classification);
  * repair ladder — an uncertified or unassigned row discards the whole
    speculative result and re-dispatches the ORIGINAL full step with
    the batch's original PRNG draw (counted fallback), a fallback storm
    parks the index on a probation cooldown (the full-rescore rung),
    and a residency-carry desync invalidates the index (rebuilt,
    counted) before it ever serves again;
  * composition — the overload tuner's K-dial narrows the scan width
    for free (certificate-folded) and widens through a counted rebuild;
    a device-loop tranche break leaves the index consistent (the delta
    protocol covers the tranche's debits like any other mutation).
"""
import time

import numpy as np
import pytest

from minisched_tpu import faults
from minisched_tpu.config import SchedulerConfig
from minisched_tpu.scenario import Cluster
from minisched_tpu.service.defaultconfig import Profile
from minisched_tpu.state import objects as obj

PLUGINS = ["NodeUnschedulable", "NodeResourcesFit",
           "NodeResourcesLeastAllocated"]


def _profile(plugins=None):
    return Profile(name="idx", plugins=list(plugins or PLUGINS))


def _config(index: bool, **kw):
    kw.setdefault("max_batch_size", 6)
    kw.setdefault("batch_window_s", 0.3)
    kw.setdefault("backoff_initial_s", 0.05)
    kw.setdefault("backoff_max_s", 0.2)
    kw.setdefault("index_k", 8)
    return SchedulerConfig(index=index, **kw)


def _pods(n: int, *, shapes: int = 0, cpu0: int = 100, pri0: int = 1000):
    """Index-safe pods. ``shapes=0``: unique request+priority per pod
    (deterministic pop + scan order, one class per pod). ``shapes=k``:
    only k distinct feature rows — pods share classes ACROSS batches
    (same priority, same trailing name digit), the steady-state shape
    the maintained index exists for."""
    pods = []
    for i in range(n):
        if shapes:
            name, pri = f"p{i}x0", pri0
            cpu = cpu0 + (i % shapes) * 50
        else:
            name, pri = f"p-{i}", pri0 - i
            cpu = cpu0 + 17 * i
        pods.append(obj.Pod(
            metadata=obj.ObjectMeta(name=name, namespace="default"),
            spec=obj.PodSpec(requests={"cpu": cpu}, priority=pri)))
    return pods


def _run(config, pods, *, plugins=None, node_cpus=(64000, 48000, 40000,
                                                   36000),
         node_taints=None, fault_spec="", between=None, timeout=120.0):
    """One engine run → (placements {pod: node}, final metrics).
    ``pods`` may be a list of bursts; ``between(cluster, i)`` runs after
    burst i settles (cordon/uncordon hooks for the narrowing/widening
    tests)."""
    bursts = pods if isinstance(pods[0], list) else [pods]
    c = Cluster()
    try:
        c.start(profile=_profile(plugins), config=config,
                with_pv_controller=False)
        if fault_spec:
            faults.configure(fault_spec)
        for i, cpu in enumerate(node_cpus):
            c.create_node(f"n{i}", cpu=cpu,
                          taints=(node_taints or {}).get(i))
        placements = {}
        want = 0
        for bi, burst in enumerate(bursts):
            c.create_objects(burst)
            want += len(burst)
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                placements = {p.metadata.name: p.spec.node_name
                              for p in c.list_pods() if p.spec.node_name}
                if len(placements) == want:
                    break
                time.sleep(0.05)
            assert len(placements) == want, (bi, len(placements), want)
            if between is not None and bi < len(bursts) - 1:
                between(c, bi)
                time.sleep(0.4)  # let the informer land the node update
        m = c.service.scheduler.metrics()
        assert sorted(p.metadata.name for p in c.list_pods()) == sorted(
            q.metadata.name for b in bursts for q in b)
        return placements, m
    finally:
        faults.configure("")
        c.shutdown()


# ---- raw-op invariants (ops/index.py) ------------------------------------


def _raw_setup(n_nodes=12, n_pods=8, k=4, seed=3):
    """Encoded features + compiled index ops + the reference full-step
    machinery for one eligible profile at tiny shapes."""
    import jax

    from minisched_tpu.encode import NodeFeatureCache, encode_pods
    from minisched_tpu.ops.index import build_index_ops, index_eligible

    rng = np.random.default_rng(seed)
    cache = NodeFeatureCache(capacity=max(16, n_nodes))
    for i in range(n_nodes):
        cache.upsert_node(obj.Node(
            metadata=obj.ObjectMeta(name=f"n{i}"),
            spec=obj.NodeSpec(),
            status=obj.NodeStatus(allocatable={
                "cpu": float(4000 + 1000 * int(rng.integers(0, 8))),
                "memory": float(64 << 30), "pods": 110.0})))
    pods = [obj.Pod(metadata=obj.ObjectMeta(name=f"p{i}x0",
                                            namespace="default"),
                    spec=obj.PodSpec(requests={
                        "cpu": float(250 * (1 + int(rng.integers(0, 3))))}))
            for i in range(n_pods)]
    pset = _profile().build()
    assert index_eligible(pset)
    eb = encode_pods(pods, 16, registry=cache.registry)
    nf, _names = cache.snapshot(pad=16)
    af = cache.snapshot_assigned(pad=16)
    ops = build_index_ops(pset, k)
    key = jax.random.PRNGKey(7)
    return pset, eb, nf, af, ops, key, cache


def _full_reference(pset, eb, nf, af, key):
    """The index-off truth: the per-batch full step's decisions."""
    from minisched_tpu.ops.pipeline import build_step

    d = build_step(pset, explain=False)(eb, nf, af, key)
    return (np.asarray(d.chosen), np.asarray(d.assigned),
            np.asarray(d.free_after))


def test_raw_op_build_assign_matches_full_step():
    """A freshly built index serves the identical decisions (and the
    bitwise-identical free carry) the full (P,N) step computes — the
    cached class rows ARE the step's masked_total rows bitwise, and the
    indexed scan is the PR 4 certified machinery over them."""
    from minisched_tpu.ops.index import unpack_index_decision

    pset, eb, nf, af, (build, _refresh, _append, assign), key, _c = (
        _raw_setup())
    state = build(eb.pf, nf, af)  # classes == the pod rows themselves
    cls = np.arange(16, dtype=np.int32)
    packed, free_after = assign(state, cls, eb.pf.valid,
                                eb.pf.requests, nf.free, key)
    chosen, assigned, _rep = unpack_index_decision(
        np.array(packed), 16)
    ref_c, ref_a, ref_f = _full_reference(pset, eb, nf, af, key)
    assert assigned.sum() > 0
    np.testing.assert_array_equal(chosen, ref_c)
    np.testing.assert_array_equal(assigned, ref_a)
    # the carried free is bit-equal too (identical debit op sequence)
    np.testing.assert_array_equal(np.asarray(free_after), ref_f)


def test_raw_op_refresh_repairs_changed_columns_exactly():
    """Delta repair invariant I1/I2: after mutating node columns (a
    debit lowering scores AND a credit raising a column into the global
    winner), a refresh over exactly those rows makes the maintained
    matrix equal a fresh build against the new truth — and the indexed
    scan's decisions equal the full recompute's."""
    from minisched_tpu.ops.index import unpack_index_decision

    # n_nodes == the pad bucket: column N-1 is a REAL node, so the pad
    # sentinels in rows_pad exercise the duplicate-scatter hazard (a
    # clipped sentinel would collide with the genuine last-column
    # repair; refresh must drop out-of-range slots instead).
    pset, eb, nf, af, (build, refresh, _append, assign), key, _c = (
        _raw_setup(n_nodes=16, k=3))
    state0 = build(eb.pf, nf, af)
    free = np.array(nf.free)
    # Narrow two columns (debits) and widen two (eviction credits that
    # turn previously mid-ranked nodes — including the LAST column —
    # into winners).
    free[2] *= 0.25
    free[5] *= 0.5
    free[9] = free[9] * 4.0 + 100000.0
    free[15] = free[15] * 4.0 + 200000.0
    nf2 = nf._replace(free=free)
    rows_pad = np.full((8,), 16, dtype=np.int32)
    rows_pad[:4] = (2, 5, 9, 15)
    state1 = refresh(state0, eb.pf, nf2, af, rows_pad)
    # the repaired matrix IS a fresh build against the new truth
    np.testing.assert_array_equal(np.asarray(state1.score),
                                  np.asarray(build(eb.pf, nf2, af).score))
    cls = np.arange(16, dtype=np.int32)
    packed, _fa = assign(state1, cls, eb.pf.valid, eb.pf.requests,
                         free, key)
    chosen, assigned, _rep = unpack_index_decision(np.array(packed), 16)
    ref_c, ref_a, _ = _full_reference(pset, eb, nf2, af, key)
    np.testing.assert_array_equal(chosen, ref_c)
    np.testing.assert_array_equal(assigned, ref_a)


def test_raw_op_any_scan_width_is_exact():
    """The K-dial contract: the indexed scan is exact at ANY width —
    a width-1 scan repairs its way to the full scan's decisions (the
    PR 4 certificate + in-scan full-row body), including plateau-heavy
    inputs where every empty node ties."""
    from minisched_tpu.ops.index import (build_index_ops,
                                         unpack_index_decision)

    pset, eb, nf, af, (build, _r, _ap, _a), key, _c = _raw_setup(k=6)
    state = build(eb.pf, nf, af)
    for k_eff in (1, 2, 16):
        _b2, _r2, _ap2, assign_k = build_index_ops(pset, k_eff)
        cls = np.arange(16, dtype=np.int32)
        packed, _fa = assign_k(state, cls, eb.pf.valid,
                               eb.pf.requests, nf.free, key)
        chosen, assigned, _rep = unpack_index_decision(
            np.array(packed), 16)
        ref_c, ref_a, _ = _full_reference(pset, eb, nf, af, key)
        np.testing.assert_array_equal(chosen, ref_c, err_msg=str(k_eff))
        np.testing.assert_array_equal(assigned, ref_a,
                                      err_msg=str(k_eff))


def test_index_eligibility_gates():
    """Topology/affinity state and non-column-local plugins are exactly
    what the column-local certificate cannot cover — those profiles
    must never engage. Row-LOCAL normalize overrides are covered since
    the maintained-max split (pre-normalize planes + full finalize);
    an UNDECLARED override stays fail-closed out."""
    from minisched_tpu.ops.index import index_eligible
    from minisched_tpu.plugins.base import PluginSet
    from minisched_tpu.plugins.tainttoleration import TaintToleration

    assert index_eligible(_profile().build())
    assert not index_eligible(_profile(
        PLUGINS + ["PodTopologySpread"]).build())
    assert not index_eligible(_profile(
        PLUGINS + ["NodeAffinity"]).build())
    # TaintToleration's min-shift normalize reads only its own row and
    # declares normalize_row_local — since the maintained-max split the
    # index stores its raw untolerated counts per column and re-derives
    # the row shift in finalize, so the profile is eligible.
    assert index_eligible(_profile(
        PLUGINS + ["TaintToleration"]).build())

    # A normalize override WITHOUT the row-local declaration must stay
    # out (fail-closed, like a forgotten column_local).
    class _Undeclared(TaintToleration):
        name = "UndeclaredNormalize"
        normalize_row_local = False

    base = _profile().build()
    assert not index_eligible(
        PluginSet(base.plugins + [_Undeclared()], base.weights))
    # NodeNumber (suffix equality, identity normalize) IS column-local:
    # the reference's own demo profile can ride the index.
    assert index_eligible(_profile(
        ["NodeUnschedulable", "NodeResourcesFit", "NodeNumber"]).build())


def test_index_serves_row_normalized_profile_bit_identical():
    """Maintained-max in action end to end: TaintToleration's min-shift
    normalize rides the index — the raw untolerated counts are
    maintained per node column, the row shift is re-derived by the
    finalize pass — and with a PreferNoSchedule taint skewing one
    column the indexed engine commits exactly the index-off
    placements."""
    taints = {0: [obj.Taint(key="ded", value="gpu",
                            effect="PreferNoSchedule")]}
    kw = dict(plugins=PLUGINS + ["TaintToleration"], node_taints=taints)
    pods = _pods(18)
    off, m_off = _run(_config(False), _pods(18), **kw)
    on, m_on = _run(_config(True), pods, **kw)
    assert on == off
    assert m_off["index_hits"] == 0 and m_off["index_width"] == 0
    assert m_on["index_hits"] >= 1, m_on
    # the taint genuinely skewed decisions away from n0's capacity win
    assert any(v != "n0" for v in off.values())


# ---- engine bit-identity across modes -------------------------------------


@pytest.mark.parametrize("mode,kw", [
    ("sync", dict(pipeline=False)),
    ("pipelined", dict(pipeline=True)),
    ("upload", dict(device_resident=False)),
    ("shortlist_off", dict(shortlist=False)),
    ("device_loop", dict(device_loop=True, loop_depth=4)),
])
def test_index_bit_identical_per_mode(mode, kw):
    pods = _pods(18)
    off, m_off = _run(_config(False, **kw), _pods(18))
    on, m_on = _run(_config(True, **kw), pods)
    assert on == off, mode
    assert m_off["index_hits"] == 0 and m_off["index_width"] == 0
    if mode != "device_loop":
        # the ring takes precedence over the index when both are on —
        # per-batch modes must genuinely serve from the index
        assert m_on["index_hits"] >= 1, m_on
        assert m_on["index_desyncs"] == 0


def test_index_off_engine_has_no_index_listener_cost():
    """MINISCHED_INDEX=0 (the default) must not even register the
    listener — the per-batch dataflow is untouched."""
    _placed, m = _run(_config(False), _pods(8))
    assert m["index_hits"] == 0 and m["index_rebuilds"] == 0
    assert m["scored_rows_total"] > 0  # the full-step ledger still runs


def test_ineligible_profile_keeps_per_batch_dataflow():
    """index=1 on a topology profile: the engine logs and declines —
    decisions are the plain per-batch ones, gauges stay zero."""
    placed, m = _run(_config(True), _pods(10),
                     plugins=PLUGINS + ["PodTopologySpread"])
    assert len(placed) == 10
    assert m["index_width"] == 0 and m["index_hits"] == 0


def test_steady_state_served_by_refresh_not_rebuild():
    """The inversion claim: bursts of repeated pod classes are served
    from the maintained index with IN-PLACE delta repairs — one rebuild
    for the first sighting of the classes, refreshes after, and the
    per-batch scored-rows ledger collapses from P_pad·N to the repair
    cost."""
    bursts = [_pods(24, shapes=2) for _ in range(3)]
    for i, b in enumerate(bursts):
        for p in b:
            p.metadata.name = f"b{i}{p.metadata.name}"
    cfg = _config(True, pipeline=False, max_batch_size=24,
                  index_classes=32)
    placed_on, m_on = _run(cfg, bursts)
    off_bursts = [[obj.Pod(metadata=obj.ObjectMeta(
        name=p.metadata.name, namespace="default"),
        spec=obj.PodSpec(requests=dict(p.spec.requests),
                         priority=p.spec.priority)) for p in b]
        for b in bursts]
    placed_off, m_off = _run(_config(False, pipeline=False,
                                     max_batch_size=24), off_bursts)
    assert placed_on == placed_off
    assert m_on["index_hits"] >= 2
    assert m_on["index_repair_rows"] >= 1     # in-place delta repairs ran
    assert m_on["index_desyncs"] == 0
    # the ledger: served batches paid C_pad·R_bucket / C_pad·N, not
    # P_pad·N — every batch the index served cost strictly less than
    # the full step's P_pad·N at these shapes (the ≥10× steady-state
    # reduction claim lives at the bench shape, tools/bench_index.py)
    assert m_on["scored_rows_total"] < m_off["scored_rows_total"]
    full_cost = (m_off["scored_rows_total"]
                 / max(1, int(m_off["batches"])))
    per_batch = m_on["scored_rows_total"] / max(1, int(m_on["batches"]))
    assert 0 < per_batch < full_cost, (per_batch, full_cost)
    assert 0 <= m_on["last_scored_rows"] < full_cost


def test_adversarial_contention_repairs_in_scan_bit_identically():
    """Forced-repair path: K=1 shortlists + same-class pods contending
    for one best node — capacity debits exhaust the per-batch shortlist
    mid-scan, the certificate refuses, and the step repairs with the
    ORIGINAL full-row body in-scan (counted per pod). Decisions stay
    bit-identical and the batch still serves from the index."""
    pods = _pods(10, shapes=1, cpu0=3000)  # 10 × 3000m against small nodes
    cpus = (8000, 7000, 6500, 6000, 9000, 7500)
    on, m_on = _run(_config(True, index_k=1, pipeline=False), pods,
                    node_cpus=cpus)
    off, m_off = _run(_config(False, pipeline=False),
                      _pods(10, shapes=1, cpu0=3000), node_cpus=cpus)
    assert on == off
    assert m_on["index_hits"] >= 1, m_on
    assert m_on["index_uncertified"] >= 1   # counted in-scan repairs
    assert m_on["index_desyncs"] == 0


def test_unassigned_row_discards_and_redispatches_full_step():
    """The engine-level repair rung: a batch containing a pod no node
    fits must NOT be served from the index (the failure verdict needs
    the per-plugin reject attribution only the full step computes) —
    the speculative result is discarded, the full step re-runs with the
    same PRNG draw, and the doomed pod parks with real attribution
    while its batch-mates place exactly as the index-off engine placed
    them."""
    def burst():
        pods = _pods(5, shapes=1)
        pods.append(obj.Pod(
            metadata=obj.ObjectMeta(name="doom", namespace="default"),
            spec=obj.PodSpec(requests={"cpu": 10 ** 9}, priority=1)))
        return pods

    results = {}
    for index in (True, False):
        c = Cluster()
        try:
            c.start(profile=_profile(),
                    config=_config(index, pipeline=False),
                    with_pv_controller=False)
            for i, cpu in enumerate((64000, 48000)):
                c.create_node(f"n{i}", cpu=cpu)
            c.create_objects(burst())
            deadline = time.monotonic() + 60
            placed, parked = {}, set()
            while time.monotonic() < deadline:
                placed, parked = {}, set()
                for p in c.list_pods():
                    if p.spec.node_name:
                        placed[p.metadata.name] = p.spec.node_name
                    elif p.status.unschedulable_plugins:
                        parked.add(p.metadata.name)
                if len(placed) == 5 and "doom" in parked:
                    break
                time.sleep(0.05)
            assert len(placed) == 5 and "doom" in parked, (placed,
                                                           parked)
            doomed = [p for p in c.list_pods()
                      if p.metadata.name == "doom"][0]
            results[index] = (placed,
                              list(doomed.status.unschedulable_plugins),
                              c.service.scheduler.metrics())
        finally:
            c.shutdown()
    on, off = results[True], results[False]
    assert on[0] == off[0]          # batch-mates placed identically
    assert on[1] == off[1] and on[1]  # real plugin attribution, both
    assert on[2]["index_fallbacks"] >= 1
    assert on[2]["index_desyncs"] == 0


def test_registry_overflow_is_a_counted_fallback():
    """More distinct pod classes than MINISCHED_INDEX_CLASSES: the
    batch takes the full step (counted), nothing breaks."""
    placed, m = _run(_config(True, index_classes=2, pipeline=False),
                     _pods(12))
    assert len(placed) == 12
    assert m["index_fallbacks"] >= 1
    assert m["index_desyncs"] == 0


def test_clean_cross_check_passes():
    """MINISCHED_INDEX_CHECK_EVERY=1 on a clean run: every served batch
    re-verified against the full step, zero desyncs, index stays on."""
    placed, m = _run(_config(True, index_check_every=1, pipeline=False),
                     _pods(12))
    assert len(placed) == 12
    assert m["index_checks"] >= 1
    assert m["index_desyncs"] == 0
    assert m["index_width"] > 0


# ---- index / residency interaction ----------------------------------------


def test_index_survives_residency_resync_via_counted_rebuild():
    """A residency-carry desync (corrupt gate + every-batch carry
    cross-check) invalidates the index — its last refresh scored
    against a now-distrusted carry — and the next index batch REBUILDS
    (counted) instead of serving stale state; recovered placements are
    bit-identical to the fault-free index-off run."""
    cfg = _config(True, pipeline=False, resident_check_every=1,
                  probation_batches=1)
    # Two bursts: the corrupt gate fires inside burst 1; burst 2 runs
    # strictly AFTER the desync + probation, so a post-desync index
    # batch exists no matter which batch the fault landed on.
    def bursts():
        second = _pods(6, cpu0=700)
        for p in second:
            p.metadata.name = f"b2{p.metadata.name}"
        return [_pods(18), second]

    off, _m = _run(_config(False, pipeline=False), bursts())
    on, m = _run(cfg, bursts(), fault_spec="residency:corrupt@2")
    assert on == off
    assert m["residency_desyncs"] >= 1
    assert m["index_rebuilds"] >= 2   # initial build + post-desync rebuild
    assert m["index_desyncs"] == 0


def test_node_update_narrowing_repairs_widening_rebuilds():
    """The IndexDeltaListener classification end to end: a CORDON
    (narrowing — scores on that row can only drop) is absorbed as an
    in-place row repair with NO rebuild; the UNCORDON (widening) bumps
    the invalidation epoch and the next index batch rebuilds. Decisions
    track the index-off engine through both."""
    rebuilds = []

    def between(c, i):
        m = c.service.scheduler.metrics()
        rebuilds.append(int(m["index_rebuilds"]))
        if i == 0:
            c.cordon("n1")
        else:
            c.uncordon("n1")

    bursts = [_pods(6, shapes=2) for _ in range(3)]
    for i, b in enumerate(bursts):
        for p in b:
            p.metadata.name = f"b{i}{p.metadata.name}"
    cfg = _config(True, pipeline=False, max_batch_size=8,
                  index_classes=32)
    on, m_on = _run(cfg, bursts, between=between)
    off_bursts = [[obj.Pod(metadata=obj.ObjectMeta(
        name=p.metadata.name, namespace="default"),
        spec=obj.PodSpec(requests=dict(p.spec.requests),
                         priority=p.spec.priority)) for p in b]
        for b in bursts]
    off, _m_off = _run(_config(False, pipeline=False, max_batch_size=8),
                       off_bursts, between=lambda c, i: (
                           c.cordon("n1") if i == 0 else c.uncordon("n1")))
    assert on == off
    assert not any(v == "n1" for k, v in on.items()
                   if k.startswith("b1"))  # the cordon really narrowed
    # burst 2 ran after the narrowing cordon: repaired in place, same
    # rebuild count as before the cordon; burst 3 ran after the
    # widening uncordon: exactly one more rebuild.
    assert int(m_on["index_rebuilds"]) == rebuilds[1] + 1, (
        rebuilds, m_on["index_rebuilds"])
    assert m_on["index_repair_rows"] >= 1
    assert m_on["index_desyncs"] == 0


def test_loop_tranche_break_leaves_index_consistent():
    """Device loop + index composed, with a step fault breaking a
    tranche mid-run: the ring's containment replays per-batch, the
    delta protocol keeps the index consistent across the break, and the
    whole run's placements equal the fault-free index-off loop-off
    run's (the supervised-retry rewind contract, with the index
    riding)."""
    cfg = _config(True, device_loop=True, loop_depth=4,
                  probation_batches=1)
    off, _m = _run(_config(False), _pods(18))
    on, m = _run(cfg, _pods(18), fault_spec="step:err@2")
    assert on == off
    assert m["fault_fires_step"] == 1
    assert m["index_desyncs"] == 0


# ---- K-dial composition ----------------------------------------------------


def test_k_dial_moves_are_live_exact_and_rebuild_free():
    """The overload K-dial applied to the indexed-scan width: both
    directions take effect at the very next batch with NO state rebuild
    (the maintained state is the full class row; any scan width is
    exact — in-scan repairs absorb a narrow one). Decisions stay
    bit-identical to the index-off engine at every width."""
    dial = {"narrowed": None, "widened": None}

    def between(c, i):
        sched = c.service.scheduler
        idx = sched._index
        assert idx is not None
        if i == 0:
            idx.k_target = 1             # tuner narrow: live, free
            dial["narrowed"] = int(sched.metrics()["index_rebuilds"])
        else:
            idx.k_target = idx.k_base * 4  # tuner widen: live, free
            dial["widened"] = int(sched.metrics()["index_rebuilds"])

    bursts = [_pods(6, shapes=2) for _ in range(3)]
    for i, b in enumerate(bursts):
        for p in b:
            p.metadata.name = f"b{i}{p.metadata.name}"
    cfg = _config(True, pipeline=False, max_batch_size=8,
                  index_classes=32)
    on, m_on = _run(cfg, bursts, between=between)
    off_bursts = [[obj.Pod(metadata=obj.ObjectMeta(
        name=p.metadata.name, namespace="default"),
        spec=obj.PodSpec(requests=dict(p.spec.requests),
                         priority=p.spec.priority)) for p in b]
        for b in bursts]
    off, _m = _run(_config(False, pipeline=False, max_batch_size=8),
                   off_bursts)
    assert on == off
    # neither dial move cost a rebuild: the total stays whatever the
    # class/churn machinery did before the first dial move
    assert int(m_on["index_rebuilds"]) == dial["narrowed"] == (
        dial["widened"]), (dial, m_on["index_rebuilds"])
    assert m_on["index_desyncs"] == 0


# ---- incremental per-class ADD (ops/index.append) -------------------------


def test_raw_op_append_extends_build_exactly():
    """The append invariant: building from a class subset and APPENDING
    the remaining rows yields the bitwise-identical matrix a full build
    computes — a fresh class costs O(|fresh|·N) evaluations, never the
    O(C·N) rebuild, and pre-existing rows keep their values untouched.
    The rows_pad sentinels (>= C) exercise the same raw-index +
    mode="drop" scatter discipline refresh pins."""
    pset, eb, nf, af, (build, _refresh, append, assign), key, _c = (
        _raw_setup())
    full = build(eb.pf, nf, af)
    split = 5
    part_valid = np.array(eb.pf.valid).copy()
    part_valid[split:] = False
    state0 = build(eb.pf._replace(valid=part_valid), nf, af)
    # the subset build genuinely differs where the missing rows live
    assert not np.array_equal(np.asarray(state0.score),
                              np.asarray(full.score))
    rows_pad = np.full((16,), 16, dtype=np.int32)   # sentinel == C
    rows_pad[:16 - split] = np.arange(split, 16, dtype=np.int32)
    state1 = append(state0, eb.pf, nf, af, rows_pad)
    np.testing.assert_array_equal(np.asarray(state1.score),
                                  np.asarray(full.score))
    # and the appended matrix serves the full step's decisions
    from minisched_tpu.ops.index import unpack_index_decision

    cls = np.arange(16, dtype=np.int32)
    packed, _fa = assign(state1, cls, eb.pf.valid, eb.pf.requests,
                         nf.free, key)
    chosen, assigned, _rep = unpack_index_decision(np.array(packed), 16)
    ref_c, ref_a, _ = _full_reference(pset, eb, nf, af, key)
    np.testing.assert_array_equal(chosen, ref_c)
    np.testing.assert_array_equal(assigned, ref_a)


def test_fresh_class_in_bucket_appends_without_rebuild():
    """A later burst introducing NEW pod classes inside the current
    class-pad bucket is served by the incremental ADD: index_appends
    counts the fresh rows, the rebuild total stays at the single cold
    build, and decisions equal the index-off engine's."""
    bursts = [_pods(12, shapes=2), _pods(12, shapes=4)]
    for i, b in enumerate(bursts):
        for p in b:
            p.metadata.name = f"b{i}{p.metadata.name}"
    cfg = _config(True, pipeline=False, max_batch_size=24,
                  index_classes=32)
    on, m_on = _run(cfg, bursts)
    off_bursts = [[obj.Pod(metadata=obj.ObjectMeta(
        name=p.metadata.name, namespace="default"),
        spec=obj.PodSpec(requests=dict(p.spec.requests),
                         priority=p.spec.priority)) for p in b]
        for b in bursts]
    off, _m = _run(_config(False, pipeline=False, max_batch_size=24),
                   off_bursts)
    assert on == off
    # shapes=4 ⊃ shapes=2: burst 1 brings exactly 2 fresh class rows,
    # both inside the 16-row class-pad bucket
    assert m_on["index_appends"] >= 1, m_on
    assert m_on["index_rebuilds"] == 1, m_on   # the cold build only
    assert m_on["index_desyncs"] == 0


def test_class_pad_crossing_rebuilds_with_pinned_cause():
    """Fresh classes that CROSS the class-pad bucket cannot append (the
    maintained matrix must grow) — that one rebuild is taken, and its
    journal event pins the cause chain: kind index.rebuild with
    cause == "class-pad", not "cold"/"invalidated"/"node-pad"."""
    from minisched_tpu.obs import journal as journal_mod

    # burst 0: 11 classes (class pad 16); burst 1: +12 disjoint classes
    # → its FIRST batch crosses to pad 32, so BOTH the in-bucket append
    # path and the crossing rebuild fire. (Crossing on a later batch of
    # the burst is timing-fragile: that batch can race the previous
    # batch's bind confirmations — a counted index_races fallback that
    # registers no classes — and then never cross.)
    bursts = [_pods(11, shapes=11), _pods(12, shapes=12, cpu0=4000)]
    for i, b in enumerate(bursts):
        for p in b:
            p.metadata.name = f"b{i}{p.metadata.name}"
    journal_mod.configure("1")
    try:
        on, m_on = _run(_config(True, pipeline=False, index_classes=32),
                        bursts)
        causes = [e.get("cause") for e in journal_mod.JOURNAL.entries()
                  if e["kind"] == "index.rebuild"]
    finally:
        journal_mod.configure("")
    off_bursts = [[obj.Pod(metadata=obj.ObjectMeta(
        name=p.metadata.name, namespace="default"),
        spec=obj.PodSpec(requests=dict(p.spec.requests),
                         priority=p.spec.priority)) for p in b]
        for b in bursts]
    off, _m = _run(_config(False, pipeline=False), off_bursts)
    assert on == off
    assert "class-pad" in causes, (causes, m_on)
    assert m_on["index_rebuilds"] == len(causes) >= 2
    assert m_on["index_desyncs"] == 0
