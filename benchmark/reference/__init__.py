"""Plain reference of one scheduling cycle: Filter -> Score -> select.

Written from the plugin contracts, not from the scheduler's code: it
imports nothing of the program and reads only the benchmark's own
description of the cluster (benchmark/workload.py). One pod template is
judged against one node state at a time, in float64 NumPy over the node
axis; `best_nodes` is the sequential cycle's choice for that pod.

A node state is the cluster as a scheduling cycle sees it: per node the
summed requests of the pods on it (`used`, on the tracked resource axes)
and, per topology-spread selector, the matching pods per domain.

Semantics, per enabled plugin (the upstream v1beta2 default profile):

- NodeUnschedulable: a node marked unschedulable is refused.
- NodeName, NodeAffinity, NodePorts, TaintToleration (filter), the volume
  plugins: the configurations' pods name no node, carry no node
  selector, node affinity, host port, toleration or volume, and no node
  is tainted, so each passes every node; a template that carries one is
  refused here (NotImplementedError) rather than judged wrongly.
- NodeResourcesFit: requests + used <= allocatable on every axis (a pod
  takes one `pods` slot). Score, LeastAllocated over cpu and memory:
  100 x mean((alloc - used - request) / alloc).
- NodeResourcesBalancedAllocation: u = clip((used + request) / alloc, 0,
  1) over cpu and memory; 100 - 100 x std(u).
- ImageLocality: 100 x share of the pod's images the node holds (nodes
  here list none).
- PodTopologySpread: filter (DoNotSchedule): the node has the key, and
  count(domain) + 1 - min over domains <= maxSkew. Score, for every
  constraint: max over domains - count(domain), 0 where the node lacks
  the key, scaled so the best feasible node has 100.
- InterPodAffinity (Kubernetes v1.22's plugin), over the pods in view,
  each known by its template's labels, namespace and terms and by its
  node. A term's namespaces default to its owner's; a term matches a
  pod in one of them whose labels its selector selects (`match_labels`
  and `match_expressions`, In / NotIn / Exists / DoesNotExist; an empty
  selector selects every pod). A node's domain under a key is its label
  value; under kubernetes.io/hostname every node is its own domain.
  Filter: required affinity: the node carries every term's key and,
  for every term, a pod that matches all the terms sits in the node's
  domain; unless no such pod sits anywhere (on a node with the key) and
  the incoming pod matches all its own terms, when it passes. Required
  anti-affinity: no pod matching the term in the node's domain (a node
  without the key passes). Symmetric: a pod in view whose required anti
  term matches the incoming pod forbids its own domain under that key.
  Score: per node, the sum over pods in its domain of: +weight for each
  of the incoming pod's preferred affinity terms they match, -weight
  for each preferred anti term; and for each term of a pod in view that
  matches the incoming pod, +1 (hardPodAffinityWeight) for a required
  affinity term, +weight for a preferred affinity term, -weight for a
  preferred anti term. Normalised over the nodes that passed the filter
  as upstream does: 100 x (s - lo) / (hi - lo) with lo = min(0, min s)
  and hi = max(0, max s), 0 everywhere where hi = lo. A pod with no terms
  among pods with none scores 0 on every node.
- TaintToleration, NodeAffinity scores: constant over nodes for these
  pods (no taints or node preferences).

Weights multiply each plugin's 0..100 score and the weighted sum ranks
the nodes. Ties are equal; `best_nodes` returns the whole top set. Like
the engine, the reference scores a batch once against the cluster it
found: the pods in view are those bound before it, not its own.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

SCORED = ("cpu", "memory")
CONSTANT_SCORERS = {"TaintToleration": 100.0, "NodeAffinity": 0.0}
PASS_FILTERS = ("NodeName", "NodeAffinity", "NodePorts", "TaintToleration",
                "VolumeRestrictions", "EBSLimits", "GCEPDLimits",
                "NodeVolumeLimits", "AzureDiskLimits", "VolumeBinding",
                "VolumeZone")
HOSTNAME = "kubernetes.io/hostname"
HARD_POD_AFFINITY_WEIGHT = 1.0   # kube-scheduler v1.22's default

#: The pods in view, grouped by template: (template, node row of each).
View = Sequence[Tuple[dict, np.ndarray]]


@dataclass
class Nodes:
    """The static side of the cluster: allocatable, labels, flags."""

    resources: List[str]
    alloc: np.ndarray                 # (N, R)
    labels: List[Dict[str, str]]
    unschedulable: np.ndarray         # (N,) bool
    taints: List[list]
    _domains: Dict[str, np.ndarray] = field(default_factory=dict,
                                            repr=False, compare=False)

    def domains(self, key: str) -> np.ndarray:
        """(N,) domain index of each node under `key`, -1 without it.
        Under kubernetes.io/hostname each node is its own domain, as the
        kubelet's label makes it."""
        if key not in self._domains:
            if key == HOSTNAME:
                dom = np.arange(len(self.labels))
            else:
                vals = sorted({lab[key] for lab in self.labels
                               if key in lab})
                idx = {v: i for i, v in enumerate(vals)}
                dom = np.array([idx.get(lab.get(key), -1)
                                for lab in self.labels])
            self._domains[key] = dom
        return self._domains[key]


def supported(template: dict) -> None:
    """Refuse a pod template whose plugins the reference does not model."""
    aff = template.get("affinity") or {}
    if set(aff) - {"pod_affinity", "pod_anti_affinity"}:
        raise NotImplementedError("reference: pod template sets "
                                  f"{sorted(aff)}")
    for k in ("node_name", "node_selector", "tolerations",
              "volumes", "host_ports"):
        if template.get(k):
            raise NotImplementedError(f"reference: pod template sets {k}")


def request(template: dict, resources: List[str]) -> np.ndarray:
    req = dict(template["requests"])
    req.setdefault("pods", 1)
    return np.array([float(req.get(r, 0.0)) for r in resources])


def matches(labels: Dict[str, str], selector: Dict[str, str]) -> bool:
    return all(labels.get(k) == v for k, v in selector.items())


# ---- InterPodAffinity ---------------------------------------------------

def selects(term: dict, labels: Dict[str, str]) -> bool:
    """A term's label selector: every match label, every expression."""
    if not matches(labels, term.get("match_labels", {})):
        return False
    for e in term.get("match_expressions", []):
        has, val = e["key"] in labels, labels.get(e["key"])
        op = e["operator"]
        if op == "In":
            ok = has and val in e["values"]
        elif op == "NotIn":
            ok = not has or val not in e["values"]
        elif op == "Exists":
            ok = has
        elif op == "DoesNotExist":
            ok = not has
        else:
            raise NotImplementedError(f"reference: operator {op!r}")
        if not ok:
            return False
    return True


def term_matches(term: dict, owner_ns: str, pod: dict) -> bool:
    """Does `term`, on a pod of namespace `owner_ns`, match a pod of
    template `pod`?"""
    return (pod["namespace"] in (term.get("namespaces") or [owner_ns])
            and selects(term, pod.get("labels", {})))


def terms(template: dict, kind: str, hard: bool) -> List[tuple]:
    """(term, weight) of one kind (`pod_affinity`, `pod_anti_affinity`):
    the required terms (weight 1) or the preferred ones."""
    spec = (template.get("affinity") or {}).get(kind) or {}
    if hard:
        return [(t, 1.0) for t in spec.get("required", [])]
    return [(w["term"], float(w["weight"]))
            for w in spec.get("preferred", [])]


def in_domain(nodes: Nodes, key: str, rows: np.ndarray) -> np.ndarray:
    """(N,) how many of the pods on `rows` sit in each node's domain
    under `key`; 0 on a node without the key."""
    dom = nodes.domains(key)
    d = dom[rows]
    d = d[d >= 0]
    n_dom = int(dom.max()) + 1 if dom.size and dom.max() >= 0 else 0
    cnt = np.bincount(d, minlength=n_dom).astype(float)
    return np.where(dom >= 0, cnt[np.clip(dom, 0, None)] if n_dom else 0.0,
                    0.0)


def affinity_ok(template: dict, nodes: Nodes, pods: View,
                anywhere: Optional[View] = None) -> np.ndarray:
    """(N,) the required pod-affinity filter. The first-pod exception
    looks for a matching pod in `anywhere` (default: `pods`)."""
    req = terms(template, "pod_affinity", True)
    n = len(nodes.labels)
    if not req:
        return np.ones(n, dtype=bool)
    ns = template["namespace"]

    def all_match(pod):
        return all(term_matches(t, ns, pod) for t, _w in req)

    has_keys = np.ones(n, dtype=bool)
    in_dom = np.ones(n, dtype=bool)
    for t, _w in req:
        has_keys &= nodes.domains(t["topology_key"]) >= 0
        cnt = np.zeros(n)
        for pod, rows in pods:
            if all_match(pod):
                cnt += in_domain(nodes, t["topology_key"], rows)
        in_dom &= cnt > 0
    first = all_match(template) and not any(
        (nodes.domains(t["topology_key"])[rows] >= 0).any()
        for pod, rows in (pods if anywhere is None else anywhere)
        if all_match(pod) for t, _w in req)
    return has_keys & (in_dom | first)


def anti_affinity_ok(template: dict, nodes: Nodes,
                     pods: View) -> np.ndarray:
    """(N,) the required anti-affinity filter, the incoming pod's own
    terms and the symmetric terms of the pods in view."""
    ok = np.ones(len(nodes.labels), dtype=bool)
    ns = template["namespace"]
    for t, _w in terms(template, "pod_anti_affinity", True):
        for pod, rows in pods:
            if term_matches(t, ns, pod):
                ok &= in_domain(nodes, t["topology_key"], rows) == 0
    for pod, rows in pods:
        for t, _w in terms(pod, "pod_anti_affinity", True):
            if term_matches(t, pod["namespace"], template):
                ok &= in_domain(nodes, t["topology_key"], rows) == 0
    return ok


def affinity_parts(template: dict, nodes: Nodes, pods: View) -> tuple:
    """((N,) the positive part, (N,) the negative part) of the raw
    InterPodAffinity score: the score is plus - minus."""
    n = len(nodes.labels)
    plus, minus = np.zeros(n), np.zeros(n)
    ns = template["namespace"]
    for kind, part in (("pod_affinity", plus), ("pod_anti_affinity", minus)):
        for t, w in terms(template, kind, False):
            for pod, rows in pods:
                if term_matches(t, ns, pod):
                    part += w * in_domain(nodes, t["topology_key"], rows)
    for pod, rows in pods:
        theirs = ([(t, HARD_POD_AFFINITY_WEIGHT, plus) for t, _w in
                   terms(pod, "pod_affinity", True)]
                  + [(t, w, plus) for t, w in
                     terms(pod, "pod_affinity", False)]
                  + [(t, w, minus) for t, w in
                     terms(pod, "pod_anti_affinity", False)])
        for t, w, part in theirs:
            if term_matches(t, pod["namespace"], template):
                part += w * in_domain(nodes, t["topology_key"], rows)
    return plus, minus


def normalize_affinity(raw: np.ndarray, feasible: np.ndarray) -> np.ndarray:
    """Upstream's NormalizeScore: 100 x (s - lo) / (hi - lo) over the
    feasible nodes, lo = min(0, min s), hi = max(0, max s)."""
    lo = min(0.0, raw[feasible].min()) if feasible.any() else 0.0
    hi = max(0.0, raw[feasible].max()) if feasible.any() else 0.0
    if hi <= lo:
        return np.zeros_like(raw)
    return 100.0 * (raw - lo) / (hi - lo)


def static_filter(template: dict, nodes: Nodes, plugins) -> np.ndarray:
    """Filters whose verdict does not depend on what is bound."""
    supported(template)
    ok = np.ones(len(nodes.labels), dtype=bool)
    if "NodeUnschedulable" in plugins:
        ok &= ~nodes.unschedulable
    if "TaintToleration" in plugins and any(nodes.taints):
        raise NotImplementedError("reference: tainted nodes")
    if "PodTopologySpread" in plugins:
        for c in template.get("topology_spread_constraints", []):
            if c["when_unsatisfiable"] == "DoNotSchedule":
                ok &= nodes.domains(c["topology_key"]) >= 0
    return ok


def fits(req: np.ndarray, used: np.ndarray, alloc: np.ndarray) -> np.ndarray:
    """NodeResourcesFit: (N,) True where the pod's requests fit."""
    return np.all(used + req[None, :] <= alloc + 1e-9, axis=1)


def skew_ok(constraint: dict, counts: np.ndarray,
            dom: np.ndarray) -> np.ndarray:
    """PodTopologySpread filter for one DoNotSchedule constraint: (N,)
    True where placing the pod keeps the skew within maxSkew. `counts` is
    the matching pods per domain, `dom` each node's domain."""
    if counts.size == 0:
        return dom >= 0
    after = np.where(dom >= 0, counts[np.clip(dom, 0, None)] + 1, np.inf)
    return after - counts.min() <= constraint["max_skew"]


def scores(template: dict, nodes: Nodes, used: np.ndarray,
           spread_counts: Dict[int, np.ndarray], feasible: np.ndarray,
           weights: Dict[str, float], plugins,
           pods: View = ()) -> np.ndarray:
    """(N,) weighted score of every node for one pod of `template`.
    `spread_counts[i]` is constraint i's matching pods per domain, `pods`
    the bound pods InterPodAffinity counts."""
    res = nodes.resources
    req = request(template, res)
    axes = [res.index(r) for r in SCORED]
    alloc = nodes.alloc[:, axes]
    util = np.where(alloc > 0, (used[:, axes] + req[axes]) /
                    np.maximum(alloc, 1e-9), 0.0)
    present = alloc > 0
    n_present = np.maximum(present.sum(axis=1), 1)
    total = np.zeros(len(nodes.labels))
    if "NodeResourcesFit" in plugins:
        least = np.where(present, 1.0 - util, 0.0).sum(axis=1) / n_present
        total += weights.get("NodeResourcesFit", 1.0) * 100.0 * least
    if "NodeResourcesBalancedAllocation" in plugins:
        u = np.where(present, np.clip(util, 0.0, 1.0), 0.0)
        mean = u.sum(axis=1) / n_present
        var = np.where(present, (u - mean[:, None]) ** 2,
                       0.0).sum(axis=1) / n_present
        total += weights.get("NodeResourcesBalancedAllocation", 1.0) * (
            100.0 - 100.0 * np.sqrt(var))
    if "ImageLocality" in plugins and template.get("images"):
        total += 0.0  # no node lists an image
    if "PodTopologySpread" in plugins:
        raw = np.zeros(len(nodes.labels))
        for i, c in enumerate(template.get("topology_spread_constraints",
                                           [])):
            cnt = spread_counts[i]
            if cnt.size == 0:
                continue
            dom = nodes.domains(c["topology_key"])
            raw += np.where(dom >= 0,
                            cnt.max() - cnt[np.clip(dom, 0, None)], 0.0)
        top = raw[feasible].max() if feasible.any() else 0.0
        norm = raw * (100.0 / top) if top > 0 else raw
        total += weights.get("PodTopologySpread", 2.0) * norm
    if "InterPodAffinity" in plugins:
        plus, minus = affinity_parts(template, nodes, pods)
        total += weights.get("InterPodAffinity", 1.0) * normalize_affinity(
            plus - minus, feasible)
    for name, value in CONSTANT_SCORERS.items():
        if name in plugins:
            total += weights.get(name, 1.0) * value
    return total


def spread_counts(template: dict, nodes: Nodes, pod_node: np.ndarray,
                  pod_labels: List[Dict[str, str]],
                  pod_ns: List[str]) -> Dict[int, np.ndarray]:
    """Per constraint of `template`, matching bound pods per domain."""
    out = {}
    for i, c in enumerate(template.get("topology_spread_constraints", [])):
        dom = nodes.domains(c["topology_key"])
        n_dom = int(dom.max()) + 1 if dom.size and dom.max() >= 0 else 0
        hit = np.array([ns == template["namespace"]
                        and matches(lab, c["match_labels"])
                        for lab, ns in zip(pod_labels, pod_ns)], dtype=bool)
        d = dom[pod_node[hit]] if hit.any() else np.zeros(0, dtype=int)
        out[i] = np.bincount(d[d >= 0], minlength=n_dom).astype(float)
    return out


def best_nodes(template: dict, nodes: Nodes, used: np.ndarray,
               counts: Dict[int, np.ndarray], weights, plugins,
               pods: View = ()) -> tuple:
    """The sequential cycle for one pod: (feasible mask, weighted scores,
    indices of the top-scoring feasible nodes). `pods` are the bound pods
    InterPodAffinity sees."""
    req = request(template, nodes.resources)
    ok = static_filter(template, nodes, plugins) & fits(req, used,
                                                        nodes.alloc)
    if "PodTopologySpread" in plugins:
        for i, c in enumerate(template.get("topology_spread_constraints",
                                           [])):
            if c["when_unsatisfiable"] == "DoNotSchedule":
                ok &= skew_ok(c, counts[i], nodes.domains(c["topology_key"]))
    if "InterPodAffinity" in plugins:
        ok &= affinity_ok(template, nodes, pods)
        ok &= anti_affinity_ok(template, nodes, pods)
    s = scores(template, nodes, used, counts, ok, weights, plugins, pods)
    if not ok.any():
        return ok, s, np.zeros(0, dtype=int)
    top = s[ok].max()
    return ok, s, np.flatnonzero(ok & (s >= top))
