"""In-scan hard-spread domain capacity (ops/spreadcap.py).

The greedy scan carries running per-(group, domain) counts so each pod's
CHOICE respects DoNotSchedule skew sequentially — a skew-constrained
burst assigns maximally in one device pass instead of draining
~(domains x max_skew) per cycle through revoke/repair."""
import jax
import numpy as np
import pytest

from minisched_tpu.encode import NodeFeatureCache, encode_pods
from minisched_tpu.ops.pipeline import build_step
from minisched_tpu.plugins import (NodeResourcesFit, NodeUnschedulable,
                                   PluginSet, PodTopologySpread)
from minisched_tpu.state import objects as obj

ZONE = "topology.kubernetes.io/zone"


def _cluster(n_nodes=16, zones=4, pods_cap=110.0):
    c = NodeFeatureCache(capacity=n_nodes)
    for i in range(n_nodes):
        c.upsert_node(obj.Node(
            metadata=obj.ObjectMeta(name=f"n{i:02d}",
                                    labels={ZONE: f"z{i % zones}"}),
            status=obj.NodeStatus(allocatable={"cpu": 64000.0,
                                               "pods": pods_cap})))
    return c


def _spread_pod(name, max_skew=1, labels=None):
    return obj.Pod(
        metadata=obj.ObjectMeta(name=name, namespace="default",
                                labels=labels or {"app": "s"}),
        spec=obj.PodSpec(
            requests={"cpu": 100.0},
            topology_spread_constraints=[obj.TopologySpreadConstraint(
                max_skew=max_skew, topology_key=ZONE,
                when_unsatisfiable="DoNotSchedule",
                label_selector=obj.LabelSelector(
                    match_labels={"app": "s"}))]))


def _ps():
    return PluginSet([NodeUnschedulable(),
                      NodeResourcesFit(score_strategy=None),
                      PodTopologySpread()])


def _run(cache, pods, p_pad=None):
    eb = encode_pods(pods, p_pad or max(16, len(pods)),
                     registry=cache.registry)
    nf, names = cache.snapshot(pad=16)
    af = cache.snapshot_assigned()
    step = build_step(_ps(), explain=False)
    d = step(eb, nf, af, jax.random.PRNGKey(0))
    return d, names


def _zone_counts(d, names, n, zones=4):
    chosen = np.asarray(d.chosen)[:n]
    assigned = np.asarray(d.assigned)[:n]
    counts = {z: 0 for z in range(zones)}
    for i in range(n):
        if assigned[i]:
            counts[int(names[int(chosen[i])][1:]) % zones] += 1
    return counts, int(assigned.sum())


def test_skew_burst_fully_assigns_in_one_pass():
    """48 max_skew=1 pods over 4 empty balanced zones: a sequential
    scheduler places ALL of them; with in-scan caps so does one step
    (the static filter alone admits everything but the host arbitration
    would then revoke most — here the CHOICES already respect skew)."""
    cache = _cluster()
    pods = [_spread_pod(f"p{i:02d}") for i in range(48)]
    d, names = _run(cache, pods, p_pad=64)
    counts, n_assigned = _zone_counts(d, names, len(pods))
    assert n_assigned == 48, counts
    assert max(counts.values()) - min(counts.values()) <= 1, counts


def test_caps_respect_prebatch_imbalance():
    """Zone z0 starts 3 matching pods ahead: nothing may land there
    until the others catch up IN THE SAME PASS, then z0 reopens."""
    cache = _cluster()
    for j in range(3):
        p = obj.Pod(metadata=obj.ObjectMeta(name=f"pre{j}",
                                            namespace="default",
                                            labels={"app": "s"}),
                    spec=obj.PodSpec(requests={"cpu": 100.0}))
        cache.account_bind(p, node_name="n00")  # z0
    pods = [_spread_pod(f"q{i:02d}") for i in range(13)]
    d, names = _run(cache, pods, p_pad=16)
    counts, n_assigned = _zone_counts(d, names, len(pods))
    assert n_assigned == 13, counts
    # final totals incl. the 3 pre-bound: z0=3+x others catch up to 4
    totals = {z: counts[z] + (3 if z == 0 else 0) for z in counts}
    assert max(totals.values()) - min(totals.values()) <= 1, totals


def test_unconstrained_matching_pods_move_counts():
    """A matching pod WITHOUT a constraint still occupies a domain slot
    for later constrained pods (membership semantics — mirror of the
    host arbitration)."""
    cache = _cluster(n_nodes=4, zones=4, pods_cap=1.0)
    free_rider = obj.Pod(
        metadata=obj.ObjectMeta(name="rider", namespace="default",
                                labels={"app": "s"}),
        spec=obj.PodSpec(requests={"cpu": 100.0}, priority=100))
    pods = [free_rider] + [_spread_pod(f"c{i}", max_skew=1)
                           for i in range(4)]
    d, names = _run(cache, pods, p_pad=16)
    assigned = np.asarray(d.assigned)[:5]
    # 4 capacity-1 nodes: rider takes one; 3 of the 4 constrained pods
    # fill the remaining zones (skew: rider's zone at 1 each... all
    # zones reach 1); the 5th pod has no node left (capacity).
    assert assigned[0], "priority rider must place"
    assert int(assigned.sum()) == 4


def test_skew_violation_still_rejected_in_scan():
    """All candidate nodes in ONE zone: only min+skew may place there
    even though the static filter (pre-counts all zero) admits all."""
    cache = _cluster(n_nodes=4, zones=1)
    pods = [_spread_pod(f"v{i}", max_skew=2) for i in range(8)]
    d, names = _run(cache, pods, p_pad=8)
    # one existing domain: min == count of that domain → skew check is
    # count+1-count <= 2: always true — single-domain never violates.
    assert int(np.asarray(d.assigned)[:8].sum()) == 8


def test_two_domains_one_empty_blocks_at_cap():
    """Two zones, all of z1's nodes full (capacity), z0 open: pods can
    only go to z0, and may exceed z1's count only by max_skew."""
    cache = _cluster(n_nodes=8, zones=2, pods_cap=110.0)
    # occupy z1 nodes fully so only z0 has capacity: bind non-matching
    # pods to z1 nodes (they do not move matching counts)
    for i in range(1, 8, 2):  # z1 nodes n01,n03,...
        for s in range(110):
            blocker = obj.Pod(
                metadata=obj.ObjectMeta(name=f"b{i}-{s}",
                                        namespace="default"),
                spec=obj.PodSpec(requests={"cpu": 1.0}))
            cache.account_bind(blocker, node_name=f"n{i:02d}")
    pods = [_spread_pod(f"w{i}", max_skew=2) for i in range(8)]
    d, names = _run(cache, pods, p_pad=8)
    counts, n_assigned = _zone_counts(d, names, 8, zones=2)
    # z1 matching count stays 0 and z1 has no capacity → z0 may take
    # exactly max_skew = 2 pods (0 + 2 - 0 <= 2; a third violates)
    assert counts[0] == 2 and n_assigned == 2, (counts, n_assigned)


def test_scan_matches_host_arbitration_exactly():
    """The scan's admissions equal what the exact host arbitration
    (engine/scheduler._SpreadGroupState) would admit replaying the same
    choices — zero revocations when the engine re-checks."""
    from minisched_tpu.engine.queue import QueuedPodInfo
    from minisched_tpu.engine.scheduler import arbitrate_spread

    cache = _cluster()
    pods = [_spread_pod(f"m{i:02d}") for i in range(24)]
    eb = encode_pods(pods, 32, registry=cache.registry)
    nf, names = cache.snapshot(pad=16)
    af = cache.snapshot_assigned()
    step = build_step(_ps(), explain=False)
    d = step(eb, nf, af, jax.random.PRNGKey(3))
    batch = [QueuedPodInfo(pod=p) for p in pods]
    assigned = np.asarray(d.assigned)[:24]
    sp_pre = np.asarray(d.spread_pre)
    sp_dom = np.asarray(d.spread_dom)
    revoked = arbitrate_spread(
        batch, assigned, eb.pf, eb.gf, sp_pre, sp_dom,
        np.asarray(d.spread_min), dead=set(),
        exact_tables=lambda: (np.asarray(d.spread_cdom),
                              np.asarray(d.spread_dexist)))
    assert revoked == set(), f"arbitration revoked {revoked}"
    assert int(assigned.sum()) == 24


def test_dispatch_cache_stability_across_same_shape_batches():
    """Regression: with the caps trace, jax-0.9's cpp-pjit dispatch
    produced 'supplied N buffers but compiled program expected M' when a
    third call reused a signature with different CONTENT (module-level
    jnp constants in spreadcap leaked into the executable's parameter
    list as device consts; they are Python literals now). Three calls,
    shapes (64,16), (16,16), (16,16), alternating content — all must
    run (a mismatch raises; the step has no retry path)."""
    cache_a = _cluster()
    d, _ = _run(cache_a, [_spread_pod(f"da{i}") for i in range(48)],
                p_pad=64)
    cache_b = _cluster()
    for j in range(3):
        p = obj.Pod(metadata=obj.ObjectMeta(name=f"db{j}",
                                            namespace="default",
                                            labels={"app": "s"}),
                    spec=obj.PodSpec(requests={"cpu": 100.0}))
        cache_b.account_bind(p, node_name="n00")
    _run(cache_b, [_spread_pod(f"dc{i}") for i in range(13)], p_pad=16)
    cache_c = _cluster(n_nodes=4, zones=4, pods_cap=1.0)
    rider = obj.Pod(
        metadata=obj.ObjectMeta(name="dd", namespace="default",
                                labels={"app": "s"}),
        spec=obj.PodSpec(requests={"cpu": 100.0}, priority=100))
    d3, _ = _run(cache_c,
                 [rider] + [_spread_pod(f"de{i}") for i in range(4)],
                 p_pad=16)
    assert int(np.asarray(d3.assigned)[:5].sum()) == 4


def test_decision_exports_scan_groups():
    """Decision.scan_groups marks exactly the groups the caps-scan
    enforced: the hard group on a hard batch, nothing on a soft-only
    batch (pallas/no-caps branch ⇒ the host arbitration must replay)."""
    cache = _cluster()
    d, _ = _run(cache, [_spread_pod(f"sg{i}") for i in range(8)], p_pad=16)
    sg = np.asarray(d.scan_groups)
    assert sg.any(), "hard-spread batch must report scan enforcement"

    soft = [obj.Pod(
        metadata=obj.ObjectMeta(name=f"soft{i}", namespace="default",
                                labels={"app": "s"}),
        spec=obj.PodSpec(
            requests={"cpu": 100.0},
            topology_spread_constraints=[obj.TopologySpreadConstraint(
                max_skew=1, topology_key=ZONE,
                when_unsatisfiable="ScheduleAnyway",
                label_selector=obj.LabelSelector(
                    match_labels={"app": "s"}))]))
        for i in range(8)]
    d2, _ = _run(cache, soft, p_pad=16)
    assert not np.asarray(d2.scan_groups).any()
