"""Shortlist-compressed arbitration (ops/select.greedy_assign_shortlist,
wired through ops/pipeline.build_step and engine/scheduler.py).

The contract under test, end to end:

  * bit-equality — with MINISCHED_SHORTLIST=1 (per-pod top-K candidate
    shortlists + the K-wide certified scan) the engine commits EXACTLY
    the placements the full-width scan (=0) commits, in sync, pipelined,
    device-resident, and mesh modes, including gangs, hard DoNotSchedule
    spread (the caps-scan runtime gate) and degenerate widths K=1 / K≥N;
  * certified repair — adversarial contention (every pod chasing one
    tiny node set until the K candidates are capacity-exhausted) forces
    full-row repair rescans that are COUNTED (repaired flags, engine
    shortlist_repairs metric) while decisions stay bit-identical;
  * the sequential-scan-width claim — a certified step consults K
    columns, not N; the engine's shortlist_width gauge and per-batch
    repair series are the audit trail the bench exports.

(The shortlist_repair fault gate + certification cross-check live in
tests/test_faults.py with the rest of the fault catalog.)
"""
import time

import jax
import numpy as np
import pytest

from minisched_tpu.config import SchedulerConfig
from minisched_tpu.ops.select import (NEG, greedy_assign,
                                      greedy_assign_shortlist)
from minisched_tpu.scenario import Cluster
from minisched_tpu.service.defaultconfig import Profile
from minisched_tpu.state import objects as obj

ZONE = "topology.kubernetes.io/zone"


# ---- op-level bit-equality ----------------------------------------------


def _equal(a, b):
    np.testing.assert_array_equal(np.asarray(a.chosen),
                                  np.asarray(b.chosen))
    np.testing.assert_array_equal(np.asarray(a.assigned),
                                  np.asarray(b.assigned))
    np.testing.assert_array_equal(np.asarray(a.free_after),
                                  np.asarray(b.free_after))


def _random_problem(P, N, R, seed, *, plateau=False, contend=False):
    rng = np.random.default_rng(seed)
    scores = (rng.integers(0, 5, (P, N)).astype(np.float32) * 25.0)
    if plateau:
        # max-normalized plugin plateaus: every feasible node ties at the
        # top — the regime the noise-ordered boundary selection exists
        # for (a naive score-only top-K would repair every pod here)
        scores[:] = 100.0
    scores[rng.random((P, N)) < 0.05] = float(NEG)
    requests = (rng.integers(1, 4, (P, R)) * 0.25).astype(np.float32)
    free = (rng.integers(1, 6, (N, R)) * 0.5).astype(np.float32)
    if contend:
        # every pod's candidates are capacity-starved: K exhausts and
        # the certificate must route through full-row repairs
        free[:] = 0.25
        free[: max(2, N // 64)] = 1000.0
    return scores, requests, free


@pytest.mark.parametrize("k", [1, 16, 128])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_bit_equality_random(seed, k):
    scores, req, free = _random_problem(96, 384, 3, seed)
    key = jax.random.PRNGKey(seed)
    full = greedy_assign(scores, req, free, key)
    sl = greedy_assign_shortlist(scores, req, free, key, k=k)
    _equal(full, sl)


def test_bit_equality_plateau_is_certified():
    """A plateau wider than K stays fully certified: the shortlist holds
    the K max-noise plateau members, and the scan's winner is by
    construction one of them while any still fits."""
    scores, req, free = _random_problem(128, 512, 3, 7, plateau=True)
    key = jax.random.PRNGKey(7)
    full = greedy_assign(scores, req, free, key)
    sl = greedy_assign_shortlist(scores, req, free, key, k=16)
    _equal(full, sl)
    assert not np.asarray(sl.repaired).any()


def test_adversarial_contention_forces_counted_repairs():
    scores, req, free = _random_problem(128, 512, 3, 3, contend=True)
    key = jax.random.PRNGKey(3)
    full = greedy_assign(scores, req, free, key)
    sl = greedy_assign_shortlist(scores, req, free, key, k=8)
    _equal(full, sl)
    assert np.asarray(sl.repaired).sum() > 0  # the ledger saw them


@pytest.mark.parametrize("k", [1, 384, 4096])
def test_degenerate_widths(k):
    """K=1 (certificate can never beat its own boundary → every live pod
    repairs) and K≥N (the shortlist IS the row) both stay bit-exact."""
    scores, req, free = _random_problem(64, 384, 3, 9)
    key = jax.random.PRNGKey(9)
    full = greedy_assign(scores, req, free, key)
    sl = greedy_assign_shortlist(scores, req, free, key, k=k)
    _equal(full, sl)
    if k == 1:
        assert np.asarray(sl.repaired).sum() > 0


def test_step_shortlist_knob_bit_equality():
    """build_step(shortlist=K) vs the default full scan on the same
    encoded inputs — the Decision must match leaf-for-leaf and carry
    the repair ledger."""
    from minisched_tpu.encode import NodeFeatureCache, encode_pods
    from minisched_tpu.ops import build_step
    from tests.test_encode import node, pod

    c = NodeFeatureCache(capacity=64)
    for i in range(48):
        c.upsert_node(node(f"n{i}", cpu=1000 + (i % 7) * 100))
    nf, _names = c.snapshot(pad=64)
    pods = [pod(f"p{i}", cpu=100 + (i % 3) * 50) for i in range(32)]
    eb = encode_pods(pods, 32, registry=c.registry)
    af = c.snapshot_assigned()
    from minisched_tpu.plugins import NodeNumber, NodeUnschedulable, PluginSet

    ps = PluginSet([NodeUnschedulable(), NodeNumber()])
    key = jax.random.PRNGKey(5)
    d_full = build_step(ps)(eb, nf, af, key)
    d_sl = build_step(ps, shortlist=8)(eb, nf, af, key)
    np.testing.assert_array_equal(np.asarray(d_full.chosen),
                                  np.asarray(d_sl.chosen))
    np.testing.assert_array_equal(np.asarray(d_full.assigned),
                                  np.asarray(d_sl.assigned))
    np.testing.assert_array_equal(np.asarray(d_full.free_after),
                                  np.asarray(d_sl.free_after))
    assert not np.asarray(d_full.shortlist_repaired).any()
    assert d_sl.shortlist_repaired.shape == d_sl.assigned.shape


def test_shortlist_rejects_assign_fn_but_serves_auction():
    import jax

    from minisched_tpu.ops import build_step
    from minisched_tpu.plugins import NodeUnschedulable, PluginSet

    ps = PluginSet([NodeUnschedulable()])
    # A custom assign_fn keeps full (P,N) rows: a silently ignored
    # shortlist knob would let a config claim compression it never ran.
    with pytest.raises(ValueError, match="built-in assignments only"):
        build_step(ps, shortlist=64,
                   assign_fn=lambda *a: None, assign_key="custom")
    # The auction, by contrast, takes its own certified analog
    # (ops/bid_select.auction_assign_shortlist) — building the step
    # must succeed and compression equality is pinned end-to-end by
    # tests/test_auction.py.
    assert build_step(ps, assignment="auction", shortlist=64) is not None


# ---- engine bit-equality across modes -----------------------------------


def _profile():
    return Profile(name="sl", plugins=["NodeUnschedulable",
                                       "NodeResourcesFit",
                                       "PodTopologySpread"],
                   plugin_args={"NodeResourcesFit":
                                {"score_strategy": None}})


def _config(shortlist: bool, *, pipeline=True, resident=True, k=128,
            **kw):
    kw.setdefault("max_batch_size", 8)
    kw.setdefault("batch_window_s", 0.3)
    kw.setdefault("backoff_initial_s", 0.05)
    kw.setdefault("backoff_max_s", 0.2)
    return SchedulerConfig(shortlist=shortlist, shortlist_k=k,
                           pipeline=pipeline, device_resident=resident,
                           **kw)


def _make_nodes(c: Cluster) -> None:
    for i, zone in enumerate(("a", "a", "b", "b", "c", "c")):
        c.create_node(f"n{i}", cpu=64000, labels={ZONE: zone})


def _make_pods() -> list:
    """24 pods with unique priorities (deterministic pop + scan order):
    8 hard-spread (the caps-scan runtime gate), 4 gang (quorum 4 — the
    per-attempt shortlist rebuild), 12 plain."""
    pods = []
    pri = 100
    for i in range(8):
        pods.append(obj.Pod(
            metadata=obj.ObjectMeta(name=f"sp-{i}", namespace="default",
                                    labels={"app": "spread"}),
            spec=obj.PodSpec(
                requests={"cpu": 100}, priority=pri,
                topology_spread_constraints=[obj.TopologySpreadConstraint(
                    max_skew=1, topology_key=ZONE,
                    when_unsatisfiable="DoNotSchedule",
                    label_selector=obj.LabelSelector(
                        match_labels={"app": "spread"}))])))
        pri -= 1
    for i in range(4):
        pods.append(obj.Pod(
            metadata=obj.ObjectMeta(name=f"g-{i}", namespace="default"),
            spec=obj.PodSpec(requests={"cpu": 200}, priority=pri,
                             pod_group="gang1", pod_group_min=4)))
        pri -= 1
    for i in range(12):
        pods.append(obj.Pod(
            metadata=obj.ObjectMeta(name=f"pl-{i}", namespace="default"),
            spec=obj.PodSpec(requests={"cpu": 150 + 13 * i},
                             priority=pri)))
        pri -= 1
    return pods


def _run_engine(config, *, seed=0, settle_s=90):
    c = Cluster()
    try:
        c.start(profile=_profile(), config=config,
                with_pv_controller=False)
        _make_nodes(c)
        c.create_objects(_make_pods())
        names = ([f"sp-{i}" for i in range(8)]
                 + [f"g-{i}" for i in range(4)]
                 + [f"pl-{i}" for i in range(12)])
        deadline = time.monotonic() + settle_s
        placements = {}
        while time.monotonic() < deadline:
            placements = {p.metadata.name: p.spec.node_name
                          for p in c.list_pods() if p.spec.node_name}
            if all(n in placements for n in names):
                break
            time.sleep(0.05)
        assert all(n in placements for n in names), (
            sorted(set(names) - set(placements)))
        return placements, c.service.scheduler.metrics()
    finally:
        c.shutdown()


@pytest.mark.parametrize("pipeline,resident", [
    (False, False),   # strictly synchronous, upload-every-batch
    (True, False),    # pipelined
    (True, True),     # pipelined + device-resident (the full fast path)
])
def test_engine_bit_equality_modes(pipeline, resident):
    ref, ref_m = _run_engine(_config(False, pipeline=pipeline,
                                     resident=resident))
    assert ref_m["shortlist_width"] == 0
    sl, m = _run_engine(_config(True, pipeline=pipeline,
                                resident=resident))
    assert m["shortlist_width"] > 0
    assert sl == ref
    # audit trail present: every pod a batch saw is certified or
    # repaired, and the totals count them all
    assert m["batches"] >= 1 and m["shortlist_repairs"] >= 0
    assert (m["shortlist_repairs"] + m["shortlist_certified"]
            == m["pods_seen"])


@pytest.mark.parametrize("k", [1, 4096])
def test_engine_degenerate_widths(k):
    ref, _ = _run_engine(_config(False))
    sl, m = _run_engine(_config(True, k=k))
    assert sl == ref
    if k == 1:
        # K=1 cannot self-certify an assignment: the repair counter
        # must show the scan fell back (and decisions still matched)
        assert m["shortlist_repairs"] > 0


def test_engine_contention_repairs_counted():
    """All pods hammer one node set: 6 nodes, every pod fits anywhere,
    tiny K → capacity debits exhaust the shortlist mid-batch and the
    engine's repair counters must see it; placements stay identical."""
    cfg_off = _config(False, k=1)
    cfg_on = _config(True, k=1)
    ref, _ = _run_engine(cfg_off)
    sl, m = _run_engine(cfg_on)
    assert sl == ref
    assert m["shortlist_repairs"] > 0
    assert m["last_shortlist_repairs"] >= 0
    assert m["shortlist_repairs"] <= m["pods_seen"]


def test_engine_mesh_mode_knob_equality(request):
    """Mesh mode keeps full (P,N) rows (the documented gate): the
    shortlist knob must change NOTHING — identical placements, width
    gauge 0 — while the sharded step actually runs."""
    devs = jax.devices("cpu")
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    from minisched_tpu.parallel import make_mesh

    def run(shortlist):
        mesh = make_mesh(devs[:8])
        cfg = _config(shortlist, pipeline=False, resident=False)
        cfg.mesh = mesh
        return _run_engine(cfg, settle_s=120)

    on, m_on = run(True)
    off, m_off = run(False)
    assert m_on["shortlist_width"] == 0 == m_off["shortlist_width"]
    assert on == off


def test_sampled_step_composes_with_shortlist():
    """Node sampling gathers a (P,K_sample) problem; the shortlist then
    compresses the SAMPLED axis — decisions must equal the sampled run
    without shortlist (both equal by the same certificate argument)."""
    from minisched_tpu.service.service import SchedulerService
    from minisched_tpu.state.store import ClusterStore

    def run(shortlist):
        store = ClusterStore()
        for i in range(600):
            store.create(obj.Node(
                metadata=obj.ObjectMeta(name=f"n{i:03d}"),
                spec=obj.NodeSpec(),
                status=obj.NodeStatus(allocatable={
                    "cpu": 4000.0 + (i % 5) * 500, "pods": 110.0})))
        for i in range(32):
            store.create(obj.Pod(
                metadata=obj.ObjectMeta(name=f"p{i:02d}",
                                        namespace="default"),
                spec=obj.PodSpec(requests={"cpu": 100.0 + (i % 3) * 50},
                                 priority=100 - i)))
        svc = SchedulerService(store)
        svc.start_scheduler(
            Profile(name="default-scheduler",
                    plugins=["NodeUnschedulable", "NodeResourcesFit",
                             "NodeResourcesLeastAllocated"]),
            SchedulerConfig(shortlist=shortlist, shortlist_k=16,
                            max_batch_size=32, batch_window_s=0.3,
                            percentage_of_nodes_to_score=34,
                            min_sample_nodes=64, seed=11))
        try:
            deadline = time.time() + 90
            while time.time() < deadline:
                pods = store.list("Pod")
                if all(p.spec.node_name for p in pods):
                    break
                time.sleep(0.05)
            return {p.key: p.spec.node_name for p in store.list("Pod")}
        finally:
            svc.shutdown_scheduler()

    on = run(True)
    off = run(False)
    assert all(v for v in off.values())
    assert on == off
