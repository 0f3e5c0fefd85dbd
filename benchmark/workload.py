"""The cluster a configuration file describes, built from a seed.

Everything here is plain data (names, label dicts, numpy arrays) that the
reference reads as it is; `to_program_*` turn it into the scheduler's own
objects. The seed orders things and never changes sizes: every seed gives
the same node count, the same zone sizes and the same standing
population, with zones and creation order permuted.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


@dataclass
class Cluster:
    """Nodes and the standing population of one run."""

    node_names: List[str]
    node_labels: List[Dict[str, str]]
    node_template: dict
    resources: List[str]             # resource axes the reference tracks
    alloc: np.ndarray                # (N, R) allocatable per node
    templates: Dict[str, dict]       # pod templates by name
    standing_keys: List[str] = field(default_factory=list)
    standing_node: Optional[np.ndarray] = None   # (S,) node row per pod
    standing_template: str = ""

    @property
    def n_nodes(self) -> int:
        return len(self.node_names)


def pod_request_vector(template: dict, resources: List[str]) -> np.ndarray:
    """A pod's requests on the tracked axes; every pod takes one `pods`
    slot."""
    req = dict(template["requests"])
    req.setdefault("pods", 1)
    return np.array([float(req.get(r, 0.0)) for r in resources])


def resources_of(cfg: dict) -> List[str]:
    axes = ["cpu", "memory", "pods"]
    for t in list(cfg["node_templates"].values()):
        axes += [r for r in t["allocatable"] if r not in axes]
    for t in cfg["pod_templates"].values():
        axes += [r for r in t["requests"] if r not in axes]
    return axes


def build_cluster(cfg: dict, seed: int, scale: int = 1) -> Cluster:
    """Nodes (zones permuted by the seed) and the standing population,
    `per_node` pods on every node, in a creation order drawn from the
    seed. `scale` > 1 divides the node count (CPU rehearsals only)."""
    rng = np.random.default_rng(seed)
    nodes = cfg["nodes"]
    n = max(1, nodes["count"] // scale)
    tmpl = cfg["node_templates"][nodes["template"]]
    resources = resources_of(cfg)
    labels = [dict(tmpl.get("labels", {})) for _ in range(n)]
    zones = nodes.get("zones")
    if zones:
        vals = zones["values"]
        zone_of = np.array([vals[i % len(vals)] for i in range(n)])
        rng.shuffle(zone_of)
        for i in range(n):
            labels[i][zones["key"]] = str(zone_of[i])
    alloc = np.tile([float(tmpl["allocatable"].get(r, 0.0))
                     for r in resources], (n, 1))
    names = [f"{nodes['name_prefix']}{i}" for i in range(n)]
    c = Cluster(names, labels, tmpl, resources, alloc,
                cfg["pod_templates"])
    st = cfg.get("standing")
    if st and st["per_node"]:
        rows = np.repeat(np.arange(n), st["per_node"])
        rng.shuffle(rows)
        t = cfg["pod_templates"][st["template"]]
        c.standing_keys = [f"{t['namespace']}/standing-{i}"
                           for i in range(len(rows))]
        c.standing_node = rows
        c.standing_template = st["template"]
    return c


# ---- the program's objects --------------------------------------------

def to_program_nodes(c: Cluster) -> list:
    from minisched_tpu.state import objects as o

    t = c.node_template
    out = []
    for name, labels in zip(c.node_names, c.node_labels):
        out.append(o.Node(
            metadata=o.ObjectMeta(name=name, labels=dict(labels)),
            spec=o.NodeSpec(unschedulable=bool(t.get("unschedulable")),
                            taints=[o.Taint(**x) for x in t.get("taints", [])]),
            status=o.NodeStatus(allocatable=dict(t["allocatable"]),
                                capacity=dict(t["allocatable"]))))
    return out


def to_program_pod(template: dict, name: str, node_name: str = "") -> object:
    from minisched_tpu.state import objects as o

    spread = [o.TopologySpreadConstraint(
        max_skew=x["max_skew"], topology_key=x["topology_key"],
        when_unsatisfiable=x["when_unsatisfiable"],
        label_selector=o.LabelSelector(match_labels=dict(x["match_labels"])))
        for x in template.get("topology_spread_constraints", [])]
    pod = o.Pod(
        metadata=o.ObjectMeta(name=name, namespace=template["namespace"],
                              labels=dict(template.get("labels", {}))),
        spec=o.PodSpec(
            requests=dict(template["requests"]),
            images=list(template.get("images", [])),
            ports=[o.ContainerPort(container_port=p)
                   for p in template.get("container_ports", [])],
            topology_spread_constraints=spread))
    if node_name:
        pod.spec.node_name = node_name
        pod.status.phase = o.PodPhase.RUNNING
    return pod


def to_program_standing(c: Cluster) -> list:
    t = c.templates[c.standing_template]
    return [to_program_pod(t, k.split("/", 1)[1], c.node_names[r])
            for k, r in zip(c.standing_keys, c.standing_node)]
