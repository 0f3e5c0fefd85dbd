"""Required (anti-)affinity placements against the plain reference.

Seeded random clusters (64 nodes over 4 zones, 3 namespaces, a few
hundred bound pods, some of them owners of required anti-affinity terms
on the hostname or the zone key with one to three namespaces and match
expressions) receive one batch of pods through the served path
(`Cluster`: store -> informer -> queue -> encode -> step -> bind), and
every verdict is compared with `benchmark.reference`, judged against the
cluster as the batch found it:

- every placement passes the reference's filter;
- every pod the reference can place binds (no fail-closed verdict);
- a pod the reference cannot place stays pending under InterPodAffinity.

The bound pods' terms are often asymmetric: the incoming pod matches a
bound pod's term but the bound pod does not match the incoming pod's
(or it has none), so the symmetric count plane is exercised on its own.
The batch's own pods are drawn not to exclude each other, so each is
judged against the same cluster.
"""
import os
import sys

import numpy as np
import pytest

from minisched_tpu.config import SchedulerConfig
from minisched_tpu.scenario import Cluster, wait_until
from minisched_tpu.service.defaultconfig import Profile
from minisched_tpu.state import objects as obj

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from benchmark import reference as ref  # noqa: E402

ZONE = "topology.kubernetes.io/zone"
HOST = "kubernetes.io/hostname"
NAMESPACES = ("ns-a", "ns-b", "ns-c")
N_NODES, N_ZONES, N_BOUND, N_INCOMING = 64, 4, 240, 16
PLUGINS = ("NodeUnschedulable", "NodeResourcesFit", "NodeName",
           "InterPodAffinity")


def _labels(rng):
    out = {"app": str(rng.choice(["x", "y", "z"]))}
    if rng.random() < 0.7:
        out["tier"] = str(rng.choice(["1", "2"]))
    return out


def _term(rng, exprs: bool, own_ns: bool) -> dict:
    """A required term: hostname mostly, 1-3 namespaces (or, where
    `own_ns`, sometimes none: the owner's)."""
    term = {"match_labels": {"app": str(rng.choice(["x", "y", "z"]))},
            "match_expressions": [],
            "topology_key": HOST if rng.random() < 0.85 else ZONE,
            "namespaces": []}
    if not own_ns or rng.random() < 0.75:
        k = int(rng.integers(1, 4))
        term["namespaces"] = [str(n) for n in
                              rng.choice(NAMESPACES, size=k, replace=False)]
    if exprs and rng.random() < 0.4:
        op = str(rng.choice(["In", "NotIn", "Exists", "DoesNotExist"]))
        term["match_expressions"] = [{
            "key": "tier", "operator": op,
            "values": ["1"] if op in ("In", "NotIn") else []}]
    return term


def _template(ns, labels, terms=()) -> dict:
    t = {"namespace": ns, "labels": labels, "requests": {"cpu": 10}}
    if terms:
        t["affinity"] = {"pod_anti_affinity": {"required": list(terms)}}
    return t


def _scenario(seed: int):
    """(zones, bound [(template, row)], incoming [template])."""
    rng = np.random.default_rng(seed)
    zones = [f"z{i % N_ZONES}" for i in range(N_NODES)]
    # Bound owners share their terms as a deployment's replicas do: a
    # pool of 6, so no pod is repelled by more anti classes than the
    # encoder's 8 slots (beyond them it fails closed by design).
    pool = [_term(rng, exprs=True, own_ns=False) for _ in range(6)]
    bound = []
    for _ in range(N_BOUND):
        terms = ([pool[int(rng.integers(len(pool)))]]
                 if rng.random() < 0.25 else [])
        bound.append((_template(str(rng.choice(NAMESPACES)), _labels(rng),
                                terms), int(rng.integers(N_NODES))))
    # Planted: a zone term owned in every zone, selecting role=blocked in
    # two of the three namespaces (a pod of the third must still place).
    blocker = {"match_labels": {"role": "blocked"}, "match_expressions": [],
               "topology_key": ZONE, "namespaces": ["ns-a", "ns-c"]}
    for z in range(N_ZONES):
        bound.append((_template("ns-b", {"app": "w"}, [blocker]), z))
    incoming = []
    for i in range(N_INCOMING):
        ns, labels = str(rng.choice(NAMESPACES)), _labels(rng)
        if i < 3:
            labels["role"] = "blocked"
            ns = ("ns-a", "ns-b", "ns-c")[i]
        terms = ([_term(rng, exprs=False, own_ns=True)]
                 if rng.random() < 0.5 else [])
        incoming.append(_template(ns, labels, terms))
    # The batch's own pods must not exclude one another.
    for t in incoming:
        own = ref.terms(t, "pod_anti_affinity", True)
        keep = [term for term, _w in own
                if not any(ref.term_matches(term, t["namespace"], q)
                           for q in incoming if q is not t)]
        if len(keep) < len(own):
            if keep:
                t["affinity"] = {"pod_anti_affinity": {"required": keep}}
            else:
                t.pop("affinity")
    for t in incoming:
        for term, _w in ref.terms(t, "pod_anti_affinity", True):
            assert not any(ref.term_matches(term, t["namespace"], q)
                           for q in incoming if q is not t)
    return zones, bound, incoming


def _pod(template: dict, name: str, node: str = "") -> obj.Pod:
    terms = [obj.PodAffinityTerm(
        label_selector=obj.LabelSelector(
            match_labels=dict(t["match_labels"]),
            match_expressions=[obj.NodeSelectorRequirement(
                key=e["key"], operator=e["operator"],
                values=list(e["values"]))
                for e in t["match_expressions"]]),
        topology_key=t["topology_key"], namespaces=list(t["namespaces"]))
        for t, _w in ref.terms(template, "pod_anti_affinity", True)]
    pod = obj.Pod(
        metadata=obj.ObjectMeta(name=name, namespace=template["namespace"],
                                labels=dict(template["labels"])),
        spec=obj.PodSpec(
            requests=dict(template["requests"]),
            affinity=obj.Affinity(pod_anti_affinity=obj.PodAntiAffinity(
                required=terms)) if terms else None))
    if node:
        pod.spec.node_name = node
        pod.status.phase = obj.PodPhase.RUNNING
    return pod


@pytest.mark.parametrize("seed", [11, 23, 37, 41, 59, 2147483659])
def test_anti_affinity_matches_reference_on_random_clusters(seed):
    zones, bound, incoming = _scenario(seed)
    names = [f"node-{i}" for i in range(N_NODES)]
    nodes = ref.Nodes(resources=["cpu", "memory", "pods"],
                      alloc=np.tile([1e6, 1e12, 110.0], (N_NODES, 1)),
                      labels=[{ZONE: z} for z in zones],
                      unschedulable=np.zeros(N_NODES, dtype=bool),
                      taints=[[] for _ in range(N_NODES)])
    view = [(t, np.array([row])) for t, row in bound]
    verdicts = [ref.affinity_ok(t, nodes, view)
                & ref.anti_affinity_ok(t, nodes, view) for t in incoming]
    placeable = [bool(v.any()) for v in verdicts]
    # the drawn cluster exercises both verdicts, the planted ones first
    assert placeable[:3] == [False, True, False]
    asym = sum(1 for t in incoming for owner, _row in bound
               for term, _w in ref.terms(owner, "pod_anti_affinity", True)
               if ref.term_matches(term, owner["namespace"], t)
               and not any(ref.term_matches(x, t["namespace"], owner)
                           for x, _w in ref.terms(t, "pod_anti_affinity",
                                                  True)))
    assert asym > 0

    c = Cluster()
    try:
        for name, z in zip(names, zones):
            c.create_node(name, cpu=1e6, memory=1e12, labels={ZONE: z})
        c.create_objects([_pod(t, f"bound-{i}", names[row])
                          for i, (t, row) in enumerate(bound)])
        c.start(profile=Profile(plugins=list(PLUGINS)),
                config=SchedulerConfig(backoff_initial_s=0.05,
                                       backoff_max_s=0.2,
                                       max_batch_size=N_INCOMING,
                                       batch_window_s=0.2))
        sched = c.service.scheduler
        assert wait_until(lambda: sched.cache.assigned_count() >= len(bound),
                          30)
        c.create_objects([_pod(t, f"in-{i}")
                          for i, t in enumerate(incoming)])
        for i, t in enumerate(incoming):
            if placeable[i]:
                pod = c.wait_for_pod_bound(f"in-{i}", t["namespace"],
                                           timeout=60)
                row = names.index(pod.spec.node_name)
                assert verdicts[i][row], (i, t, pod.spec.node_name)
            else:
                pod = c.wait_for_pod_pending(f"in-{i}", t["namespace"],
                                             timeout=60)
                assert "InterPodAffinity" in pod.status.unschedulable_plugins
    finally:
        c.shutdown()
