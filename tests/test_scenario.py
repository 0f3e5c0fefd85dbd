"""End-to-end scenario tests — the reference's README scenario and variants
(reference sched.go:70-143; SURVEY §7 "minimum end-to-end slice")."""
import pytest

from minisched_tpu.config import SchedulerConfig
from minisched_tpu.scenario import Cluster, wait_until
from minisched_tpu.service.defaultconfig import Profile


def fast_config(**kw):
    kw.setdefault("backoff_initial_s", 0.05)
    kw.setdefault("backoff_max_s", 0.2)
    return SchedulerConfig(**kw)


@pytest.fixture
def cluster():
    c = Cluster()
    yield c
    c.shutdown()


def test_readme_scenario(cluster):
    """9 unschedulable nodes + pod1 → pending with NodeUnschedulable
    recorded; add schedulable node10 → pod revives and binds to node10
    (reference sched.go:74-143 exactly)."""
    # NodeNumber's permit would delay binding by the node-digit; node10's
    # trailing digit is 0 so the delay is 0 (reference semantics kept).
    cluster.start(config=fast_config())
    for i in range(9):
        cluster.create_node(f"node{i}", unschedulable=True)
    cluster.create_pod("pod1", cpu=100)

    pending = cluster.wait_for_pod_pending("pod1", timeout=30)
    assert pending.status.unschedulable_plugins == ["NodeUnschedulable"]
    assert pending.spec.node_name == ""

    cluster.create_node("node10")
    bound = cluster.wait_for_pod_bound("pod1", timeout=5)
    assert bound.spec.node_name == "node10"
    assert bound.status.phase == "Running"

    # Scheduled event recorded (reference broadcaster capability)
    events = cluster.store.list("Event")
    assert any(e.reason == "Scheduled" and "node10" in e.message for e in events)
    assert any(e.reason == "FailedScheduling" for e in events)


def test_suffix_scoring_prefers_matching_node(cluster):
    cluster.start(config=fast_config())
    cluster.create_node("nodeA7")
    cluster.create_node("nodeB3")
    cluster.create_pod("web3")
    bound = cluster.wait_for_pod_bound("web3")
    assert bound.spec.node_name == "nodeB3"


def test_permit_delay_parks_pod_then_binds(cluster):
    """NodeNumber permit waits {digit}s before allowing (reference
    nodenumber.go:102-119): pod on node with suffix 1 binds after ~1s."""
    cluster.start(config=fast_config())
    cluster.create_node("node1")
    cluster.create_pod("app1", cpu=100)
    sched = cluster.service.scheduler
    assert wait_until(lambda: "default/app1" in sched.waiting_pods, timeout=3)
    pod = cluster.get_pod("app1")
    assert pod.spec.node_name == ""  # parked, not yet bound
    bound = cluster.wait_for_pod_bound("app1", timeout=5)
    assert bound.spec.node_name == "node1"


def test_many_pods_spread_capacity(cluster):
    cluster.start(config=fast_config())
    for i in range(4):
        cluster.create_node(f"worker-{i}x", cpu=250)  # fits 2 pods of 100
    for i in range(8):
        cluster.create_pod(f"job-{i}x", cpu=100)
    for i in range(8):
        cluster.wait_for_pod_bound(f"job-{i}x", timeout=10)
    counts = {}
    for p in cluster.list_pods():
        counts[p.spec.node_name] = counts.get(p.spec.node_name, 0) + 1
    assert all(v == 2 for v in counts.values()), counts


def test_bulk_workload_submission(cluster):
    """A whole workload applied as one store transaction via the scenario
    facade (Cluster.create_objects → store.create_many): the burst flows
    through the bulk informer/queue path and every pod binds."""
    from minisched_tpu.state import objects as obj

    cluster.start(config=fast_config(max_batch_size=64, batch_window_s=0.2))
    cluster.create_objects([
        obj.Node(metadata=obj.ObjectMeta(name=f"bw-n{i}"),
                 spec=obj.NodeSpec(),
                 status=obj.NodeStatus(allocatable={
                     "cpu": 1000, "memory": 8 << 30, "pods": 110}))
        for i in range(4)])
    cluster.create_objects([
        obj.Pod(metadata=obj.ObjectMeta(name=f"bw-p{i}", namespace="default",
                                        labels={"app": "burst"}),
                spec=obj.PodSpec(requests={"cpu": 100}))
        for i in range(32)])
    for i in range(32):
        cluster.wait_for_pod_bound(f"bw-p{i}", timeout=15)
    nodes_used = {p.spec.node_name for p in cluster.list_pods()}
    assert nodes_used <= {f"bw-n{i}" for i in range(4)}


def test_capacity_exhausted_then_node_added(cluster):
    cluster.start(config=fast_config())
    cluster.create_node("tiny0", cpu=100)
    cluster.create_pod("a0", cpu=100)
    cluster.wait_for_pod_bound("a0", timeout=5)
    cluster.create_pod("b0", cpu=100)
    # b0 can't fit; NodeResourcesFit isn't in the default profile but the
    # batch-capacity path must keep retrying via backoff without binding.
    assert not wait_until(
        lambda: bool(cluster.get_pod("b0").spec.node_name), timeout=0.6)
    cluster.create_node("fresh0", cpu=100)
    bound = cluster.wait_for_pod_bound("b0", timeout=5)
    assert bound.spec.node_name == "fresh0"


def test_pod_deleted_while_pending(cluster):
    cluster.start(config=fast_config())
    cluster.create_node("full", unschedulable=True)
    cluster.create_pod("doomed", cpu=100)
    cluster.wait_for_pod_pending("doomed", timeout=30)
    cluster.delete_pod("doomed")
    # a new pod with the same name must be schedulable after a node appears
    cluster.create_node("open0")
    cluster.create_pod("doomed", cpu=100)
    bound = cluster.wait_for_pod_bound("doomed", timeout=5)
    assert bound.spec.node_name == "open0"


def test_restart_scheduler_resumes(cluster):
    """reference RestartScheduler (scheduler/scheduler.go:40-47): pending
    work survives restart via store state."""
    cluster.start(config=fast_config())
    cluster.create_node("blocked", unschedulable=True)
    cluster.create_pod("waiting1", cpu=100)
    cluster.wait_for_pod_pending("waiting1", timeout=30)

    cluster.service.restart_scheduler()
    cluster.create_node("rescue1")
    bound = cluster.wait_for_pod_bound("waiting1", timeout=5)
    assert bound.spec.node_name == "rescue1"


def test_restart_rebuilds_bind_accounting(cluster):
    """Bound-pod capacity accounting must survive a restart: the informer's
    initial sync delivers Nodes before Pods so account_bind lands."""
    cluster.start(config=fast_config())
    cluster.create_node("packed", cpu=1000)
    cluster.create_pod("occupant", cpu=800)
    cluster.wait_for_pod_bound("occupant", timeout=10)

    cluster.service.restart_scheduler()
    sched = cluster.service.scheduler
    assert wait_until(lambda: sched.cache.node_count() == 1, timeout=5)
    row = sched.cache.row_of("packed")
    nf, _ = sched.cache.snapshot()
    assert nf.free[row, 0] == 200  # 1000 - 800 re-accounted after restart


def test_cordoned_node_tolerated_by_exists_toleration():
    """Upstream semantics: a pod tolerating the unschedulable taint may land
    on a cordoned node; an Equal toleration with a non-empty value must NOT
    match the implicit taint (its value is empty)."""
    import jax

    from minisched_tpu.encode import NodeFeatureCache, encode_pods
    from minisched_tpu.ops import build_step
    from minisched_tpu.plugins import NodeUnschedulable, PluginSet
    from minisched_tpu.state.objects import Toleration
    from tests.test_encode import node, pod

    c = NodeFeatureCache()
    c.upsert_node(node("cordoned", unsched=True))
    nf, _ = c.snapshot()

    tolerant = pod("tolerant")
    tolerant.spec.tolerations = [Toleration(
        key="node.kubernetes.io/unschedulable", operator="Exists",
        effect="NoSchedule")]
    wrong_value = pod("wrongval")
    wrong_value.spec.tolerations = [Toleration(
        key="node.kubernetes.io/unschedulable", operator="Equal",
        value="true", effect="NoSchedule")]
    plain = pod("plain")

    eb = encode_pods([tolerant, wrong_value, plain], 16, registry=c.registry)
    d = build_step(PluginSet([NodeUnschedulable()]), explain=True)(
        eb, nf, c.snapshot_assigned(), jax.random.PRNGKey(0))
    import numpy as np

    mask = np.asarray(d.filter_masks[0])
    row = 0  # single node row 0
    assert mask[0, row]       # Exists toleration → allowed
    assert not mask[1, row]   # Equal with wrong value → rejected
    assert not mask[2, row]   # no toleration → rejected


def test_explain_annotations_recorded():
    """Explainability parity (reference resultstore → pod annotations)."""
    import json

    from minisched_tpu.explain import (FILTER_RESULT_KEY,
                                       FINAL_SCORE_RESULT_KEY,
                                       SCORE_RESULT_KEY)

    c = Cluster()
    try:
        c.start(config=fast_config(explain=True))
        c.create_node("good1")
        c.create_node("bad2", unschedulable=True)
        c.create_pod("query1")
        c.wait_for_pod_bound("query1", timeout=5)
        assert wait_until(
            lambda: FILTER_RESULT_KEY in c.get_pod("query1").metadata.annotations,
            timeout=3)
        pod = c.get_pod("query1")
        fr = json.loads(pod.metadata.annotations[FILTER_RESULT_KEY])
        assert fr["good1"]["NodeUnschedulable"] == "passed"
        assert fr["bad2"]["NodeUnschedulable"] != "passed"
        sr = json.loads(pod.metadata.annotations[SCORE_RESULT_KEY])
        assert sr["good1"]["NodeNumber"] == 10.0  # suffix match
        fs = json.loads(pod.metadata.annotations[FINAL_SCORE_RESULT_KEY])
        assert fs["good1"]["NodeNumber"] == 10.0
    finally:
        c.shutdown()


def test_pv_controller_binds_claims(cluster):
    cluster.start(config=fast_config())
    from minisched_tpu.state import objects as obj

    pv = obj.PersistentVolume(
        metadata=obj.ObjectMeta(name="pv1"),
        capacity={"ephemeral-storage": 10 << 30}, storage_class="standard")
    cluster.store.create(pv)
    pvc = obj.PersistentVolumeClaim(
        metadata=obj.ObjectMeta(name="claim1", namespace="default"),
        request={"ephemeral-storage": 5 << 30}, storage_class="standard")
    cluster.store.create(pvc)
    assert wait_until(
        lambda: cluster.store.get("PersistentVolumeClaim", "default/claim1").phase == "Bound",
        timeout=3)
    got = cluster.store.get("PersistentVolumeClaim", "default/claim1")
    assert got.volume_name == "pv1"
    # dynamic provisioning when nothing matches
    pvc2 = obj.PersistentVolumeClaim(
        metadata=obj.ObjectMeta(name="claim2", namespace="default"),
        request={"ephemeral-storage": 50 << 30}, storage_class="standard")
    cluster.store.create(pvc2)
    assert wait_until(
        lambda: cluster.store.get("PersistentVolumeClaim", "default/claim2").phase == "Bound",
        timeout=3)


def test_node_recreate_readopts_bound_pods(cluster):
    """A node deleted and recreated under the same name must NOT offer
    full capacity again while pods from its previous incarnation are
    still bound to that name in the store (the chaos-suite over-commit:
    cache accounting was dropped at delete and never restored)."""
    cluster.start(config=fast_config(max_batch_size=16, batch_window_s=0.0))
    cluster.create_node("rc-n", cpu=300)  # fits 3 pods of 100
    for i in range(3):
        cluster.create_pod(f"rc-a{i}", cpu=100)
    for i in range(3):
        cluster.wait_for_pod_bound(f"rc-a{i}", timeout=15)

    import time

    cluster.delete_node("rc-n")
    assert wait_until(
        lambda: cluster.service.scheduler.cache.row_of("rc-n") is None,
        timeout=10), "node-delete event never reached the feature cache"
    cluster.create_node("rc-n", cpu=300)  # same name, fresh allocatable

    # The recreated node is FULL (3 × 100 still bound to the name):
    # a fresh pod must pend, not over-commit.
    cluster.create_pod("rc-late", cpu=100)
    time.sleep(1.0)
    p = cluster.get_pod("rc-late")
    assert not p.spec.node_name, (
        f"rc-late bound to {p.spec.node_name} — recreated node "
        "over-committed (bound incarnation-1 pods not re-adopted)")

    # Deleting one incarnation-1 pod frees a slot; rc-late then binds.
    cluster.delete_pod("rc-a0")
    cluster.wait_for_pod_bound("rc-late", timeout=15)

    # Store-level invariant: total bound requests ≤ allocatable.
    used = sum(pp.spec.requests.get("cpu", 0)
               for pp in cluster.list_pods()
               if pp.spec.node_name == "rc-n")
    assert used <= 300


def test_intra_batch_spread_arbitration():
    """A one-batch burst must not jointly breach a DoNotSchedule max_skew:
    every pod scores against pre-batch counts, so without host-side
    arbitration a 6-pod burst lands unbalanced (observed 3-2-1); revoked
    violators retry against committed counts and converge to ≤ max_skew."""
    from minisched_tpu.state import objects as obj

    zone = "topology.kubernetes.io/zone"
    c = Cluster()
    try:
        c.start(profile=Profile(plugins=["NodeUnschedulable",
                                         "NodeResourcesFit",
                                         "PodTopologySpread"]),
                config=fast_config(max_batch_size=16, batch_window_s=0.3))
        for i in range(6):
            c.create_node(f"sp-n{i}", cpu=2000, labels={zone: f"z{i % 3}"})
        sel = obj.LabelSelector(match_labels={"app": "sp"})
        spread = obj.TopologySpreadConstraint(
            max_skew=1, topology_key=zone,
            when_unsatisfiable="DoNotSchedule", label_selector=sel)
        c.create_objects([
            obj.Pod(metadata=obj.ObjectMeta(name=f"sp-p{i}",
                                            namespace="default",
                                            labels={"app": "sp"}),
                    spec=obj.PodSpec(requests={"cpu": 100},
                                     topology_spread_constraints=[spread]))
            for i in range(6)])
        zones = {f"z{i}": 0 for i in range(3)}  # count EVERY zone
        for i in range(6):
            p = c.wait_for_pod_bound(f"sp-p{i}", timeout=20)
            zones[c.get_node(p.spec.node_name).metadata.labels[zone]] += 1
        assert max(zones.values()) - min(zones.values()) <= 1, zones
    finally:
        c.shutdown()


def test_demo_scenario_runs():
    """The advanced-feature demo (make demo) as a regression test."""
    from minisched_tpu.scenario.demo import main

    main()


def test_spread_arbitration_counts_unconstrained_matching_pods():
    """A matching batch pod WITHOUT any spread constraint must still feed
    the in-batch domain deltas: pod A (plain, app=sp2) and pod B (hard
    DoNotSchedule max_skew=1, selector app=sp2) land in one batch; if A's
    placement were invisible, both could stack into one zone and commit a
    skew-2 violation the sequential reference would have filtered."""
    from minisched_tpu.state import objects as obj

    zone = "topology.kubernetes.io/zone"
    c = Cluster()
    try:
        c.start(profile=Profile(plugins=["NodeUnschedulable",
                                         "NodeResourcesFit",
                                         "PodTopologySpread"]),
                config=fast_config(max_batch_size=16, batch_window_s=0.3))
        # two zones, one node each; plenty of capacity
        c.create_node("sa-n0", cpu=2000, labels={zone: "za"})
        c.create_node("sa-n1", cpu=2000, labels={zone: "zb"})
        sel = obj.LabelSelector(match_labels={"app": "sp2"})
        spread = obj.TopologySpreadConstraint(
            max_skew=1, topology_key=zone,
            when_unsatisfiable="DoNotSchedule", label_selector=sel)
        c.create_objects([
            obj.Pod(metadata=obj.ObjectMeta(name="plain-a",
                                            namespace="default",
                                            labels={"app": "sp2"}),
                    spec=obj.PodSpec(requests={"cpu": 100})),
            obj.Pod(metadata=obj.ObjectMeta(name="plain-b",
                                            namespace="default",
                                            labels={"app": "sp2"}),
                    spec=obj.PodSpec(requests={"cpu": 100})),
            obj.Pod(metadata=obj.ObjectMeta(name="hard-c",
                                            namespace="default",
                                            labels={"app": "sp2"}),
                    spec=obj.PodSpec(requests={"cpu": 100},
                                     topology_spread_constraints=[spread])),
        ])
        for name in ("plain-a", "plain-b", "hard-c"):
            c.wait_for_pod_bound(name, timeout=20)
        per_zone = {}
        for p in c.list_pods():
            z = c.get_node(p.spec.node_name).metadata.labels[zone]
            per_zone[z] = per_zone.get(z, 0) + 1
        # 3 matching pods over 2 zones: the only ≤1-skew split is 2/1,
        # and hard-c must not be the one creating a 3/0 or a 2-vs-0 split.
        assert max(per_zone.values()) - min(per_zone.get(z, 0)
                                            for z in ("za", "zb")) <= 1, per_zone
    finally:
        c.shutdown()


def test_intra_batch_required_anti_affinity():
    """Two mutually-exclusive pods arriving in ONE batch must not both
    bind into the same zone — direct (B's own anti term matches A's
    placement) and symmetric (A's anti term matches B) directions. The
    device filter only sees pre-batch counts; the engine arbitration
    walks the batch in priority order."""
    from minisched_tpu.state import objects as obj

    zone = "topology.kubernetes.io/zone"
    c = Cluster()
    try:
        c.start(profile=Profile(plugins=["NodeUnschedulable",
                                         "NodeResourcesFit",
                                         "InterPodAffinity"]),
                config=fast_config(max_batch_size=16, batch_window_s=0.3))
        c.create_node("aa-n0", cpu=2000, labels={zone: "za"})
        c.create_node("aa-n1", cpu=2000, labels={zone: "zb"})
        anti = obj.Affinity(pod_anti_affinity=obj.PodAntiAffinity(
            required=[obj.PodAffinityTerm(
                label_selector=obj.LabelSelector(match_labels={"app": "xc"}),
                topology_key=zone)]))
        # direct: both carry the anti term AND the label
        c.create_objects([
            obj.Pod(metadata=obj.ObjectMeta(name=f"xc-{i}",
                                            namespace="default",
                                            labels={"app": "xc"}),
                    spec=obj.PodSpec(requests={"cpu": 100}, affinity=anti))
            for i in range(2)])
        c.wait_for_pod_bound("xc-0", timeout=20)
        c.wait_for_pod_bound("xc-1", timeout=20)
        z0 = c.get_node(c.get_pod("xc-0").spec.node_name).metadata.labels[zone]
        z1 = c.get_node(c.get_pod("xc-1").spec.node_name).metadata.labels[zone]
        assert z0 != z1, (z0, z1)

        # symmetric: A carries the anti term vs app=sy but NOT the label;
        # B carries the label but no constraint. One batch; B must avoid
        # A's zone (or A must avoid B's) — never co-located.
        anti_sy = obj.Affinity(pod_anti_affinity=obj.PodAntiAffinity(
            required=[obj.PodAffinityTerm(
                label_selector=obj.LabelSelector(match_labels={"app": "sy"}),
                topology_key=zone)]))
        c.create_objects([
            obj.Pod(metadata=obj.ObjectMeta(name="guard",
                                            namespace="default",
                                            labels={"app": "other"}),
                    spec=obj.PodSpec(requests={"cpu": 100},
                                     affinity=anti_sy, priority=10)),
            obj.Pod(metadata=obj.ObjectMeta(name="intruder",
                                            namespace="default",
                                            labels={"app": "sy"}),
                    spec=obj.PodSpec(requests={"cpu": 100})),
        ])
        c.wait_for_pod_bound("guard", timeout=20)
        c.wait_for_pod_bound("intruder", timeout=20)
        zg = c.get_node(c.get_pod("guard").spec.node_name).metadata.labels[zone]
        zi = c.get_node(c.get_pod("intruder").spec.node_name).metadata.labels[zone]
        assert zg != zi, (zg, zi)
    finally:
        c.shutdown()


def test_symmetric_anti_affinity_vs_running_pod():
    """Upstream existing-pod anti-affinity: a RUNNING pod's required anti
    term must repel later arrivals that match it — the guard binds FIRST
    (separate cycle), then the intruder arrives and must land in the
    other zone; with only one zone available it must stay pending."""
    from minisched_tpu.state import objects as obj

    zone = "topology.kubernetes.io/zone"
    c = Cluster()
    try:
        c.start(profile=Profile(plugins=["NodeUnschedulable",
                                         "NodeResourcesFit",
                                         "InterPodAffinity"]),
                config=fast_config(max_batch_size=16, batch_window_s=0.0))
        c.create_node("sr-n0", cpu=2000, labels={zone: "za"})
        anti = obj.Affinity(pod_anti_affinity=obj.PodAntiAffinity(
            required=[obj.PodAffinityTerm(
                label_selector=obj.LabelSelector(match_labels={"app": "ry"}),
                topology_key=zone)]))
        c.create_pod("sr-guard", cpu=100, affinity=anti)
        c.wait_for_pod_bound("sr-guard", timeout=15)

        # Intruder matches the guard's anti term; only zone za exists →
        # it must NOT bind (the guard's term forbids its own zone).
        c.create_objects([obj.Pod(
            metadata=obj.ObjectMeta(name="sr-intruder2", namespace="default",
                                    labels={"app": "ry"}),
            spec=obj.PodSpec(requests={"cpu": 100}))])
        p = c.wait_for_pod_pending("sr-intruder2", timeout=20)
        assert "InterPodAffinity" in p.status.unschedulable_plugins

        # A second zone appears → the intruder binds there, not in za.
        c.create_node("sr-n1", cpu=2000, labels={zone: "zb"})
        bound = c.wait_for_pod_bound("sr-intruder2", timeout=20)
        assert bound.spec.node_name == "sr-n1"

        # The guard leaving frees its domain: a third matching pod can
        # then use za again (table decrements on unbind).
        c.delete_pod("sr-guard")
        c.create_objects([obj.Pod(
            metadata=obj.ObjectMeta(name="sr-late", namespace="default",
                                    labels={"app": "ry"}),
            spec=obj.PodSpec(requests={"cpu": 100}))])
        # sr-late matches intruder2's... intruder2 has NO anti term, so za
        # (now empty of anti terms) must admit sr-late.
        bound2 = c.wait_for_pod_bound("sr-late", timeout=20)
        assert bound2.spec.node_name in ("sr-n0", "sr-n1")
    finally:
        c.shutdown()


@pytest.mark.parametrize("free_zone", [False, True])
def test_anti_affinity_forbidden_domain_overflow_fails_closed(free_zone):
    """A pod repelled by running pods' required anti terms in many
    distinct domains (64 zones, far beyond any per-domain slot count) is
    judged exactly by the anti-class count plane: it must pend under
    InterPodAffinity while every zone is forbidden, and bind in the one
    zone left free when there is one."""
    from minisched_tpu.state import objects as obj

    zone = "topology.kubernetes.io/zone"
    n_zones = 64
    c = Cluster()
    try:
        c.start(profile=Profile(plugins=["NodeUnschedulable",
                                         "NodeResourcesFit",
                                         "NodeName",
                                         "InterPodAffinity"]),
                config=fast_config(max_batch_size=16, batch_window_s=0.0))
        anti = obj.Affinity(pod_anti_affinity=obj.PodAntiAffinity(
            required=[obj.PodAffinityTerm(
                label_selector=obj.LabelSelector(match_labels={"fc": "1"}),
                topology_key=zone)]))
        # One guard pinned per zone: every guarded zone becomes a
        # forbidden domain for pods labeled fc=1.
        for i in range(n_zones + int(free_zone)):
            c.create_node(f"fc-n{i}", cpu=2000, labels={zone: f"fz{i}"})
        for i in range(n_zones):
            c.create_pod(f"fc-guard{i}", cpu=100, affinity=anti,
                         required_node_name=f"fc-n{i}")
        for i in range(n_zones):
            c.wait_for_pod_bound(f"fc-guard{i}", timeout=30)

        c.create_objects([obj.Pod(
            metadata=obj.ObjectMeta(name="fc-victim", namespace="default",
                                    labels={"fc": "1"}),
            spec=obj.PodSpec(requests={"cpu": 100}))])
        if free_zone:
            bound = c.wait_for_pod_bound("fc-victim", timeout=20)
            assert bound.spec.node_name == f"fc-n{n_zones}"
            return
        p = c.wait_for_pod_pending("fc-victim", timeout=20)
        assert "InterPodAffinity" in p.status.unschedulable_plugins
        # It must stay pending (all domains forbidden, none truncated away).
        import time
        time.sleep(1.0)
        assert c.get_pod("fc-victim").spec.node_name == ""
    finally:
        c.shutdown()


def test_own_required_anti_term_unregistrable_key_fails_closed():
    """A pending pod whose OWN required anti-affinity term references a
    topology key the full registry cannot register must fail closed —
    not schedule with the hard constraint silently dropped."""
    from minisched_tpu.state import objects as obj

    c = Cluster()
    try:
        c.start(profile=Profile(plugins=["NodeUnschedulable",
                                         "NodeResourcesFit",
                                         "InterPodAffinity"]),
                config=fast_config(max_batch_size=16, batch_window_s=0.0))
        eng = next(iter(c.service._scheds.values()))
        reg = eng.cache.registry
        while reg.index_of(f"junk/{len(reg.keys())}") >= 0:
            pass  # fill the registry to max
        c.create_node("ou-n0", cpu=2000)
        anti = obj.Affinity(pod_anti_affinity=obj.PodAntiAffinity(
            required=[obj.PodAffinityTerm(
                label_selector=obj.LabelSelector(match_labels={"x": "1"}),
                topology_key="unregistrable/key")]))
        c.create_pod("ou-victim", cpu=100, affinity=anti)
        p = c.wait_for_pod_pending("ou-victim", timeout=20)
        assert "InterPodAffinity" in p.status.unschedulable_plugins
        import time
        time.sleep(0.8)
        assert c.get_pod("ou-victim").spec.node_name == ""
    finally:
        c.shutdown()
