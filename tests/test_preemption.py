"""DefaultPreemption (PostFilter): batched victim-candidate search +
minimal host-side eviction. Upstream-semantics capability BEYOND the
reference (its minisched wraps only Filter/Score/Permit — SURVEY §2)."""
import time

import jax
import numpy as np
import pytest

from minisched_tpu.config import SchedulerConfig
from minisched_tpu.encode import NodeFeatureCache, encode_pods
from minisched_tpu.ops.preempt import build_preempt_op
from minisched_tpu.plugins import (DefaultPreemption, NodeResourcesFit,
                                   NodeUnschedulable, PluginSet,
                                   TaintToleration)
from minisched_tpu.scenario import Cluster, wait_until
from minisched_tpu.service.defaultconfig import Profile
from minisched_tpu.state import objects as obj
from tests.test_encode import node, pod


# ---- op level -----------------------------------------------------------

def _corpus(n_nodes=4, cpu=400):
    c = NodeFeatureCache()
    for i in range(n_nodes):
        c.upsert_node(node(f"pr-n{i}", cpu=cpu))
    return c


def _op_inputs(c, pods):
    eb = encode_pods(pods, 8, registry=c.registry)
    nf, names = c.snapshot()
    af = c.snapshot_assigned()
    return eb, nf, af, names


def test_op_finds_node_with_fewest_lower_priority_victims():
    c = _corpus(3, cpu=300)
    # n0: three prio-1 pods (100 each); n1: one prio-1 pod + filled by
    # a HIGH-priority pod (not a victim); n2: three prio-5 pods
    for i in range(3):
        p = pod(f"v0-{i}", cpu=100); p.spec.priority = 1
        c.account_bind(p, node_name="pr-n0")
    p = pod("v1-a", cpu=100); p.spec.priority = 1
    c.account_bind(p, node_name="pr-n1")
    p = pod("v1-b", cpu=200); p.spec.priority = 50
    c.account_bind(p, node_name="pr-n1")
    for i in range(3):
        p = pod(f"v2-{i}", cpu=100); p.spec.priority = 5
        c.account_bind(p, node_name="pr-n2")

    ps = PluginSet([NodeUnschedulable(), NodeResourcesFit()])
    pr = pod("preemptor", cpu=100); pr.spec.priority = 10
    eb, nf, af, names = _op_inputs(c, [pr])
    chosen, ok, cnt, _sev = build_preempt_op(ps)(eb, nf, af)
    assert bool(np.asarray(ok)[0])
    # n1 has exactly ONE evictable lower-priority victim (fewest)
    assert names[int(np.asarray(chosen)[0])] == "pr-n1"
    assert float(np.asarray(cnt)[0]) == 1.0


def test_op_respects_non_capacity_filters_and_priority_bar():
    c = NodeFeatureCache()
    c.upsert_node(node("pt-bad", cpu=300,
                       taints=[obj.Taint(key="k", value="v",
                                         effect="NoSchedule")]))
    c.upsert_node(node("pt-high", cpu=300))
    for i in range(3):  # tainted node full of prio-1 pods
        p = pod(f"tb-{i}", cpu=100); p.spec.priority = 1
        c.account_bind(p, node_name="pt-bad")
    for i in range(3):  # other node full of HIGHER-priority pods
        p = pod(f"th-{i}", cpu=100); p.spec.priority = 99
        c.account_bind(p, node_name="pt-high")
    ps = PluginSet([NodeUnschedulable(), TaintToleration(),
                    NodeResourcesFit()])
    pr = pod("pr2", cpu=100); pr.spec.priority = 10
    eb, nf, af, _names = _op_inputs(c, [pr])
    _chosen, ok, _cnt, _sev = build_preempt_op(ps)(eb, nf, af)
    # tainted node is a hard blocker; the other has no lower-prio victims
    assert not bool(np.asarray(ok)[0])


# ---- engine level -------------------------------------------------------

def _cluster():
    c = Cluster()
    c.start(profile=Profile(plugins=["NodeUnschedulable", "NodeResourcesFit",
                                     "NodeResourcesLeastAllocated",
                                     "DefaultPreemption"]),
            config=SchedulerConfig(backoff_initial_s=0.05, backoff_max_s=0.2,
                                   max_batch_size=64, batch_window_s=0.0))
    return c


def test_engine_preempts_lowest_priority_victims_end_to_end():
    c = _cluster()
    try:
        c.create_node("pe-n0", cpu=300)
        for i in range(3):
            c.create_pod(f"low{i}", cpu=100, priority=1)
        for i in range(3):
            c.wait_for_pod_bound(f"low{i}", timeout=20)
        # cluster full; a high-priority pod must evict exactly one victim
        c.create_pod("vip", cpu=100, priority=100)
        bound = c.wait_for_pod_bound("vip", timeout=30)
        assert bound.spec.node_name == "pe-n0"
        assert bound.status.nominated_node_name == "pe-n0"
        # exactly the minimal victim set was evicted (one pod)
        remaining = [p for p in c.list_pods()
                     if p.metadata.name.startswith("low")]
        assert len(remaining) == 2, [p.metadata.name for p in remaining]
        # a Preempted event was recorded
        wait_until(lambda: any(
            e.reason == "Preempted" and "vip" in e.message
            for e in c.store.list("Event")), timeout=10)
    finally:
        c.shutdown()


def test_engine_no_preemption_without_lower_priority_victims():
    c = _cluster()
    try:
        c.create_node("pn-n0", cpu=300)
        for i in range(3):
            c.create_pod(f"peer{i}", cpu=100, priority=50)
        for i in range(3):
            c.wait_for_pod_bound(f"peer{i}", timeout=20)
        # same priority: not eligible victims (strictly-lower rule)
        c.create_pod("equal", cpu=100, priority=50)
        p = c.wait_for_pod_pending("equal", timeout=20)
        assert "preemption found no candidates" in p.status.message
        time.sleep(0.5)
        assert len([q for q in c.list_pods()
                    if q.metadata.name.startswith("peer")]) == 3
    finally:
        c.shutdown()


def test_engine_preemption_disabled_without_plugin():
    c = Cluster()
    c.start(profile=Profile(plugins=["NodeUnschedulable",
                                     "NodeResourcesFit"]),
            config=SchedulerConfig(backoff_initial_s=0.05,
                                   backoff_max_s=0.2, batch_window_s=0.0))
    try:
        c.create_node("pd-n0", cpu=300)
        for i in range(3):
            c.create_pod(f"prey{i}", cpu=100, priority=1)
        for i in range(3):
            c.wait_for_pod_bound(f"prey{i}", timeout=20)
        c.create_pod("wolf", cpu=100, priority=100)
        c.wait_for_pod_pending("wolf", timeout=20)
        time.sleep(0.5)
        assert len([q for q in c.list_pods()
                    if q.metadata.name.startswith("prey")]) == 3
    finally:
        c.shutdown()


def test_nominated_capacity_protected_from_racing_lower_priority_pod():
    """After preemption frees capacity, a LOWER-priority pod arriving
    before the preemptor's retry must not steal the reservation
    (upstream nominatedNodeName semantics): the vip binds, the thief
    pends."""
    c = _cluster()
    try:
        c.create_node("nr-n0", cpu=300)
        for i in range(3):
            c.create_pod(f"base{i}", cpu=100, priority=10)
        for i in range(3):
            c.wait_for_pod_bound(f"base{i}", timeout=20)
        c.create_pod("vip2", cpu=100, priority=100)
        # wait until the preemption actually happened (a victim is gone),
        # then race a low-priority thief at the freed slot
        wait_until(lambda: len([p for p in c.list_pods()
                                if p.metadata.name.startswith("base")]) == 2,
                   timeout=20)
        c.create_pod("thief", cpu=100, priority=1)
        bound = c.wait_for_pod_bound("vip2", timeout=30)
        assert bound.spec.node_name == "nr-n0"
        # the thief must still be pending (it must not have taken the
        # freed slot, and nothing else fits)
        thief = c.get_pod("thief")
        assert thief.spec.node_name == "", thief.spec.node_name
    finally:
        c.shutdown()


def test_gang_members_are_never_victims():
    """Evicting one gang member would strand its group below quorum —
    gang pods are excluded from victim pools even when lower priority."""
    c = _cluster()
    try:
        c.create_node("gv-n0", cpu=300)
        for i in range(3):
            c.create_pod(f"gmember{i}", cpu=100, priority=1,
                         pod_group="sacred", pod_group_min=3)
        for i in range(3):
            c.wait_for_pod_bound(f"gmember{i}", timeout=20)
        c.create_pod("bully", cpu=100, priority=100)
        p = c.wait_for_pod_pending("bully", timeout=20)
        assert "preemption found no candidates" in p.status.message
        time.sleep(0.5)
        assert len([q for q in c.list_pods()
                    if q.metadata.name.startswith("gmember")]) == 3
    finally:
        c.shutdown()


def test_preemption_composes_with_node_sampling():
    """Sampling and preemption in one engine: the sampled step's residual
    pass renders the terminal verdict, and preemption then still fires
    off it — a high-priority pod evicts on a full cluster that sampling
    alone would only have parked."""
    c = Cluster()
    c.start(profile=Profile(plugins=["NodeUnschedulable",
                                     "NodeResourcesFit",
                                     "NodeResourcesLeastAllocated",
                                     "DefaultPreemption"]),
            config=SchedulerConfig(backoff_initial_s=0.05,
                                   backoff_max_s=0.2,
                                   max_batch_size=128, batch_window_s=0.05,
                                   percentage_of_nodes_to_score=10,
                                   min_sample_nodes=16))
    try:
        # 64 nodes, every one exactly full of low-priority pods
        c.create_objects([obj.Node(
            metadata=obj.ObjectMeta(name=f"sp-n{i:03d}"),
            status=obj.NodeStatus(allocatable={"cpu": 200, "pods": 110}))
            for i in range(64)])
        fillers = [obj.Pod(
            metadata=obj.ObjectMeta(name=f"sp-f{i}", namespace="default"),
            spec=obj.PodSpec(requests={"cpu": 100}, priority=1))
            for i in range(128)]
        c.create_objects(fillers)
        assert wait_until(
            lambda: all(p.spec.node_name for p in c.list_pods()),
            timeout=60)
        c.create_pod("sp-vip", cpu=200, priority=100)  # needs 2 evictions
        bound = c.wait_for_pod_bound("sp-vip", timeout=30)
        assert bound.status.nominated_node_name == bound.spec.node_name
        # event recording is async: wait for the sink to drain
        assert wait_until(lambda: len(
            [e for e in c.store.list("Event")
             if e.reason == "Preempted"]) == 2, timeout=10)
    finally:
        c.shutdown()


# ---- PodDisruptionBudgets (upstream policy/v1 semantics) ----------------

def _pdb(name, min_available, match_labels, ns="default"):
    return obj.PodDisruptionBudget(
        metadata=obj.ObjectMeta(name=name, namespace=ns),
        spec=obj.PDBSpec(min_available=min_available,
                         selector=obj.LabelSelector(
                             match_labels=match_labels)))


def test_pdb_protected_victims_skipped_when_alternatives_exist():
    """Two eligible victims; the PDB-protected one must survive and the
    unprotected one be evicted, even though the protected pod is
    lower-priority (upstream: violating victims rank last)."""
    c = _cluster()
    try:
        c.create_node("pdb-n0", cpu=300)
        guarded = c.create_pod("guarded", cpu=100, priority=1)
        guarded = c.store.get("Pod", guarded.key)
        guarded.metadata.labels = {"app": "db"}
        c.store.update(guarded)
        c.create_pod("loose", cpu=100, priority=2)
        c.create_pod("other", cpu=100, priority=50)
        for n in ("guarded", "loose", "other"):
            c.wait_for_pod_bound(n, timeout=20)
        # min_available=1 and exactly 1 matching bound pod → 0 allowed
        c.store.create(_pdb("db-pdb", 1, {"app": "db"}))
        c.create_pod("vip", cpu=100, priority=100)
        c.wait_for_pod_bound("vip", timeout=30)
        names = {p.metadata.name for p in c.list_pods()}
        assert "guarded" in names, "PDB-protected pod was evicted"
        assert "loose" not in names, "unprotected victim should be evicted"
    finally:
        c.shutdown()


def test_pdb_violated_only_as_last_resort():
    """When EVERY sufficient victim set violates the budget, preemption
    still proceeds (upstream permits violations, ranked last)."""
    c = _cluster()
    try:
        c.create_node("pdb2-n0", cpu=200)
        for i in range(2):
            p = c.create_pod(f"db{i}", cpu=100, priority=1)
            p = c.store.get("Pod", p.key)
            p.metadata.labels = {"app": "db"}
            c.store.update(p)
        for i in range(2):
            c.wait_for_pod_bound(f"db{i}", timeout=20)
        c.store.create(_pdb("db-pdb", 2, {"app": "db"}))  # 0 allowed
        c.create_pod("vip", cpu=100, priority=100)
        c.wait_for_pod_bound("vip", timeout=30)
        remaining = [p for p in c.list_pods()
                     if p.metadata.name.startswith("db")]
        assert len(remaining) == 1  # one violation, minimal set
    finally:
        c.shutdown()


def test_pdb_budget_shared_across_preemptors_in_one_cycle():
    """A budget with ONE allowed disruption and two preemptors in the
    same cycle: the first may consume the budget, the second must prefer
    its non-matching alternative victim (first-pass skip), exercising
    the shared pdb_state debit in _select_victims."""
    from minisched_tpu.engine.scheduler import Scheduler

    store = __import__("minisched_tpu.state.store",
                       fromlist=["ClusterStore"]).ClusterStore()
    ps = PluginSet([NodeUnschedulable(),
                    NodeResourcesFit(score_strategy=None),
                    DefaultPreemption()])
    eng = Scheduler(store, ps, SchedulerConfig())
    try:
        for n in ("sh-a", "sh-b"):
            store.create(node(n, cpu=200))
            eng.cache.upsert_node(store.get("Node", n))

        def bound_pod(name, node_name, labels, prio):
            p = pod(name, cpu=100)
            p.metadata.labels = labels
            p.spec.priority = prio
            p.spec.node_name = node_name
            store.create(p)
            eng.cache.account_bind(store.get("Pod", p.key),
                                   node_name=node_name)
            return p

        # each node: one PDB-matching victim (LOWER priority — greedily
        # preferred) + one unprotected victim
        bound_pod("m1", "sh-a", {"app": "web"}, 1)
        bound_pod("x1", "sh-a", {}, 2)
        bound_pod("m2", "sh-b", {"app": "web"}, 1)
        bound_pod("x2", "sh-b", {}, 2)
        store.create(obj.PodDisruptionBudget(
            metadata=obj.ObjectMeta(name="web-pdb", namespace="default"),
            spec=obj.PDBSpec(min_available=1,
                             selector=obj.LabelSelector(
                                 match_labels={"app": "web"}))))
        pre0 = pod("vip0", cpu=100)
        pre0.spec.priority = 100
        pdb_state = eng._pdb_state()
        v1 = eng._select_victims(pre0, "sh-a", set(), pdb_state)
        # budget allows ONE disruption: the lowest-priority (matching)
        # victim is taken and the budget is debited to zero
        assert v1 == ["default/m1"], v1
        v2 = eng._select_victims(pre0, "sh-b", {"default/m1"}, pdb_state)
        # second preemptor in the SAME cycle: m2 now violates, so the
        # unprotected x2 must be chosen instead
        assert v2 == ["default/x2"], v2
        # and with no alternative at all, violation is the last resort
        v3 = eng._select_victims(pre0, "sh-b", {"default/m1", "default/x2"},
                                 pdb_state)
        assert v3 == ["default/m2"], v3
    finally:
        eng.shutdown()


def test_pdb_last_resort_minimizes_violations():
    """When the need can only be covered WITH a violation, the selection
    must still prefer non-violating victims first — one protected + one
    unprotected, not two protected (upstream ranks violating victims
    last; round-4 review finding)."""
    from minisched_tpu.engine.scheduler import Scheduler
    from minisched_tpu.state.store import ClusterStore

    store = ClusterStore()
    ps = PluginSet([NodeUnschedulable(),
                    NodeResourcesFit(score_strategy=None),
                    DefaultPreemption()])
    eng = Scheduler(store, ps, SchedulerConfig())
    try:
        store.create(node("lr-a", cpu=300))
        eng.cache.upsert_node(store.get("Node", "lr-a"))

        def bound_pod(name, labels, prio):
            p = pod(name, cpu=100)
            p.metadata.labels = labels
            p.spec.priority = prio
            p.spec.node_name = "lr-a"
            store.create(p)
            eng.cache.account_bind(store.get("Pod", p.key),
                                   node_name="lr-a")

        bound_pod("p1", {"app": "web"}, 1)   # protected, lowest prio
        bound_pod("p2", {"app": "web"}, 2)   # protected
        bound_pod("u", {}, 3)                # unprotected, highest prio
        store.create(obj.PodDisruptionBudget(
            metadata=obj.ObjectMeta(name="web-pdb", namespace="default"),
            spec=obj.PDBSpec(min_available=2,
                             selector=obj.LabelSelector(
                                 match_labels={"app": "web"}))))
        pre = pod("vip", cpu=200)
        pre.spec.priority = 100
        v = eng._select_victims(pre, "lr-a", set(), eng._pdb_state())
        # budget allows 0 disruptions; need 2 victims: the minimal-
        # violation set is {u, one protected}, NOT {p1, p2}
        assert v is not None and len(v) == 2
        assert "default/u" in v, v
        assert sorted(v) != ["default/p1", "default/p2"], v
    finally:
        eng.shutdown()


# ---- topology-curable preemption (upstream SelectVictimsOnNode parity) --

def _anti(term_labels, key="kubernetes.io/hostname"):
    return obj.Affinity(pod_anti_affinity=obj.PodAntiAffinity(required=[
        obj.PodAffinityTerm(
            label_selector=obj.LabelSelector(match_labels=term_labels),
            topology_key=key)]))


def _topo_cluster(extra=()):
    c = Cluster()
    c.start(profile=Profile(plugins=["NodeUnschedulable", "NodeResourcesFit",
                                     "InterPodAffinity", "PodTopologySpread",
                                     "DefaultPreemption", *extra]),
            config=SchedulerConfig(backoff_initial_s=0.05, backoff_max_s=0.2,
                                   max_batch_size=64, batch_window_s=0.0),
            with_pv_controller=False)
    return c


def test_engine_preemption_cures_own_anti_affinity():
    """A low-priority pod whose labels match the preemptor's required
    anti-affinity is a MANDATORY victim: capacity alone would fit both,
    so only the topology cure explains the eviction (upstream
    DefaultPreemption simulates removal and places the preemptor)."""
    c = _topo_cluster()
    try:
        c.create_node("ca-n0", cpu=64000)  # capacity is NOT the problem
        c.create_pod("victim", cpu=100, priority=1,
                     labels={"app": "db"})
        c.wait_for_pod_bound("victim", timeout=20)
        c.create_pod("vip", cpu=100, priority=100,
                     affinity=_anti({"app": "db"}))
        bound = c.wait_for_pod_bound("vip", timeout=30)
        assert bound.spec.node_name == "ca-n0"
        # the repelling pod was evicted (the cure), not co-located
        assert all(p.metadata.name != "victim" for p in c.list_pods())
    finally:
        c.shutdown()


def test_engine_anti_cure_requires_outranking_every_repeller():
    c = _topo_cluster()
    try:
        c.create_node("cb-n0", cpu=64000)
        c.create_pod("guard", cpu=100, priority=100,
                     labels={"app": "db"})
        c.wait_for_pod_bound("guard", timeout=20)
        c.create_pod("mid", cpu=100, priority=10,
                     affinity=_anti({"app": "db"}))
        p = c.wait_for_pod_pending("mid", timeout=10)
        assert "InterPodAffinity" in p.status.unschedulable_plugins
        assert any(q.metadata.name == "guard" for q in c.list_pods())
    finally:
        c.shutdown()


def test_engine_anti_cure_blocked_by_offnode_domain_matcher():
    """Zone-scoped anti term: a matching pod on ANOTHER node of the zone
    cannot be evicted by a node-local victim set (upstream scope), so
    preemption must not fire and the preemptor parks."""
    ZONE = "topology.kubernetes.io/zone"
    c = _topo_cluster()
    try:
        c.create_node("cz-n0", cpu=64000, labels={ZONE: "z1"})
        c.create_node("cz-n1", cpu=64000, labels={ZONE: "z1"})
        c.create_pod("m0", cpu=100, priority=1, labels={"app": "db"},
                     node_selector={"kubernetes.io/hostname": "cz-n0"})
        c.create_pod("m1", cpu=100, priority=1, labels={"app": "db"},
                     node_selector={"kubernetes.io/hostname": "cz-n1"})
        c.wait_for_pod_bound("m0", timeout=20)
        c.wait_for_pod_bound("m1", timeout=20)
        c.create_pod("vip", cpu=100, priority=100,
                     affinity=_anti({"app": "db"}, key=ZONE))
        p = c.wait_for_pod_pending("vip", timeout=10)
        assert "InterPodAffinity" in p.status.unschedulable_plugins
        assert sum(1 for q in c.list_pods()
                   if q.metadata.name.startswith("m")) == 2
    finally:
        c.shutdown()


def test_engine_preemption_cures_symmetric_anti_affinity():
    """A RUNNING low-priority pod whose own required anti term repels
    the preemptor (existing-pod anti-affinity) is evicted — the owner's
    anti class, stamped on the preemptor, carries the owner's location
    and rank to the device op."""
    c = _topo_cluster()
    try:
        c.create_node("cs-n0", cpu=64000)
        c.create_pod("hermit", cpu=100, priority=1,
                     affinity=_anti({"app": "web"}))
        c.wait_for_pod_bound("hermit", timeout=20)
        c.create_pod("vip", cpu=100, priority=100,
                     labels={"app": "web"})
        bound = c.wait_for_pod_bound("vip", timeout=30)
        assert bound.spec.node_name == "cs-n0"
        assert all(p.metadata.name != "hermit" for p in c.list_pods())
    finally:
        c.shutdown()


def test_engine_symmetric_anti_not_cured_against_higher_owner():
    c = _topo_cluster()
    try:
        c.create_node("ch-n0", cpu=64000)
        c.create_pod("hermit", cpu=100, priority=100,
                     affinity=_anti({"app": "web"}))
        c.wait_for_pod_bound("hermit", timeout=20)
        c.create_pod("mid", cpu=100, priority=10, labels={"app": "web"})
        p = c.wait_for_pod_pending("mid", timeout=10)
        assert "InterPodAffinity" in p.status.unschedulable_plugins
        assert any(q.metadata.name == "hermit" for q in c.list_pods())
    finally:
        c.shutdown()


def test_engine_preemption_cures_spread_skew():
    """Statically over-skew everywhere (the in-scan caps defer the
    static check, so this also pins the feasible_static terminal
    classification): evicting enough MATCHING pods from the chosen
    node's zone brings it back under max_skew."""
    ZONE = "topology.kubernetes.io/zone"
    c = _topo_cluster()
    try:
        c.create_node("sp-n0", cpu=64000, labels={ZONE: "za"})
        c.create_node("sp-n1", cpu=64000, labels={ZONE: "zb"},
                      unschedulable=True)  # zb exists but unschedulable
        for i in range(2):
            c.create_pod(f"m{i}", cpu=100, priority=1,
                         labels={"app": "s"})
            c.wait_for_pod_bound(f"m{i}", timeout=20)
        # za count=2, zb count=0 → skew_after on sp-n0 = 3 > 1; sp-n1 is
        # cordoned → statically blocked everywhere. Cure: evict 2
        # matching pods from sp-n0.
        c.create_pod("vip", cpu=100, priority=100, labels={"app": "s"},
                     topology_spread_constraints=[
                         obj.TopologySpreadConstraint(
                             max_skew=1, topology_key=ZONE,
                             when_unsatisfiable="DoNotSchedule",
                             label_selector=obj.LabelSelector(
                                 match_labels={"app": "s"}))])
        bound = c.wait_for_pod_bound("vip", timeout=30)
        assert bound.spec.node_name == "sp-n0"
        remaining = [p.metadata.name for p in c.list_pods()
                     if p.metadata.name.startswith("m")]
        assert len(remaining) == 0, remaining  # both matching pods evicted
    finally:
        c.shutdown()


def test_engine_spread_block_parks_terminally_without_preemption():
    """Same static skew block with preemption DISABLED: the pod must park
    as unschedulable under PodTopologySpread (and revive on the pod
    delete event) — not spin forever on BATCH_CAPACITY retries."""
    ZONE = "topology.kubernetes.io/zone"
    c = Cluster()
    try:
        c.start(profile=Profile(plugins=["NodeUnschedulable",
                                         "NodeResourcesFit",
                                         "PodTopologySpread"]),
                config=SchedulerConfig(backoff_initial_s=0.05,
                                       backoff_max_s=0.2,
                                       max_batch_size=64,
                                       batch_window_s=0.0),
                with_pv_controller=False)
        c.create_node("st-n0", cpu=64000, labels={ZONE: "za"})
        c.create_node("st-n1", cpu=64000, labels={ZONE: "zb"},
                      unschedulable=True)
        for i in range(2):
            c.create_pod(f"m{i}", cpu=100, priority=1, labels={"app": "s"})
            c.wait_for_pod_bound(f"m{i}", timeout=20)
        c.create_pod("late", cpu=100, labels={"app": "s"},
                     topology_spread_constraints=[
                         obj.TopologySpreadConstraint(
                             max_skew=1, topology_key=ZONE,
                             when_unsatisfiable="DoNotSchedule",
                             label_selector=obj.LabelSelector(
                                 match_labels={"app": "s"}))])
        p = c.wait_for_pod_pending("late", timeout=10)
        assert "PodTopologySpread" in p.status.unschedulable_plugins
        # revival contract: with zb pinned at 0 by the cordon, za only
        # admits when empty — deleting both matching pods frees the skew
        # and the Pod DELETE events revive the parked pod
        c.delete_pod("m0")
        c.delete_pod("m1")
        c.wait_for_pod_bound("late", timeout=20)
    finally:
        c.shutdown()


def test_engine_anti_cure_fails_closed_on_unevictable_gang_repeller():
    """The device op counts every lower-priority pod as evictable, but
    gang members are never victims: the host cure-verification must
    scan ALL bound pods on the node and fail closed — no eviction of
    unrelated pods, no endless evict-retry loop."""
    c = _topo_cluster()
    try:
        c.create_node("cg-n0", cpu=64000)
        # gang member with the repelling labels (priority 1 — the device
        # sees it as evictable; the host must refuse)
        c.create_pod("gmember", cpu=100, priority=1, labels={"app": "db"},
                     pod_group="g1", pod_group_min=1)
        c.wait_for_pod_bound("gmember", timeout=20)
        # innocent bystander the broken path would have evicted
        c.create_pod("bystander", cpu=100, priority=1)
        c.wait_for_pod_bound("bystander", timeout=20)
        c.create_pod("vip", cpu=100, priority=100,
                     affinity=_anti({"app": "db"}))
        p = c.wait_for_pod_pending("vip", timeout=10)
        assert "InterPodAffinity" in p.status.unschedulable_plugins
        names = {q.metadata.name for q in c.list_pods()}
        assert {"gmember", "bystander"} <= names
    finally:
        c.shutdown()
