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
  plugins, InterPodAffinity: the configurations' pods name no node, carry
  no node selector, affinity, host port, toleration or volume, and no
  node is tainted, so each passes every node; a template that carries
  one is refused here (NotImplementedError) rather than judged wrongly.
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
- TaintToleration, NodeAffinity, InterPodAffinity scores: constant over
  nodes for these pods (no taints, preferences or affinity terms).

Weights multiply each plugin's 0..100 score and the weighted sum ranks
the nodes. Ties are equal; `best_nodes` returns the whole top set.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List

import numpy as np

SCORED = ("cpu", "memory")
CONSTANT_SCORERS = {"TaintToleration": 100.0, "NodeAffinity": 0.0,
                    "InterPodAffinity": 0.0}
PASS_FILTERS = ("NodeName", "NodeAffinity", "NodePorts", "TaintToleration",
                "VolumeRestrictions", "EBSLimits", "GCEPDLimits",
                "NodeVolumeLimits", "AzureDiskLimits", "VolumeBinding",
                "VolumeZone", "InterPodAffinity")


@dataclass
class Nodes:
    """The static side of the cluster: allocatable, labels, flags."""

    resources: List[str]
    alloc: np.ndarray                 # (N, R)
    labels: List[Dict[str, str]]
    unschedulable: np.ndarray         # (N,) bool
    taints: List[list]

    def domains(self, key: str) -> np.ndarray:
        """(N,) domain index of each node under `key`, -1 without it."""
        vals = sorted({lab[key] for lab in self.labels if key in lab})
        idx = {v: i for i, v in enumerate(vals)}
        return np.array([idx.get(lab.get(key), -1) for lab in self.labels])


def supported(template: dict) -> None:
    """Refuse a pod template whose plugins the reference does not model."""
    for k in ("node_name", "node_selector", "affinity", "tolerations",
              "volumes", "host_ports"):
        if template.get(k):
            raise NotImplementedError(f"reference: pod template sets {k}")


def request(template: dict, resources: List[str]) -> np.ndarray:
    req = dict(template["requests"])
    req.setdefault("pods", 1)
    return np.array([float(req.get(r, 0.0)) for r in resources])


def matches(labels: Dict[str, str], selector: Dict[str, str]) -> bool:
    return all(labels.get(k) == v for k, v in selector.items())


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
           weights: Dict[str, float], plugins) -> np.ndarray:
    """(N,) weighted score of every node for one pod of `template`.
    `spread_counts[i]` is constraint i's matching pods per domain."""
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
               counts: Dict[int, np.ndarray], weights, plugins) -> tuple:
    """The sequential cycle for one pod: (feasible mask, weighted scores,
    indices of the top-scoring feasible nodes)."""
    req = request(template, nodes.resources)
    ok = static_filter(template, nodes, plugins) & fits(req, used,
                                                        nodes.alloc)
    if "PodTopologySpread" in plugins:
        for i, c in enumerate(template.get("topology_spread_constraints",
                                           [])):
            if c["when_unsatisfiable"] == "DoNotSchedule":
                ok &= skew_ok(c, counts[i], nodes.domains(c["topology_key"]))
    s = scores(template, nodes, used, counts, ok, weights, plugins)
    if not ok.any():
        return ok, s, np.zeros(0, dtype=int)
    top = s[ok].max()
    return ok, s, np.flatnonzero(ok & (s >= top))
