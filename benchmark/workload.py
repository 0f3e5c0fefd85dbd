"""The cluster a configuration file describes, built from a seed.

Everything here is plain data (names, label dicts, numpy arrays) that the
reference reads as it is; `to_program_*` turn it into the scheduler's own
objects. The seed orders things and never changes sizes: every seed gives
the same node count, the same zone sizes and the same standing
population, with zones, creation order and a `count` group's nodes
permuted.

A pod template names what `to_program_pod` maps onto the program's pod:
`namespace`, `requests`, `labels`, `images`, `container_ports`,
`topology_spread_constraints` and `affinity` (`from` and `name_prefix`
are the harness's own). Any other key raises rather than being dropped.
`affinity` holds `pod_affinity` and/or `pod_anti_affinity`, each
`{"required": [term], "preferred": [{"weight": w, "term": term}]}`; a
term is `{"match_labels": {}, "match_expressions": [{"key", "operator":
In|NotIn|Exists|DoesNotExist, "values"}], "topology_key", "namespaces":
[]}`, and empty `namespaces` means the pod's own.

`standing` is one group or a list of groups: `{"per_node": k,
"template"}` puts k pods on every node, `{"count": n, "template",
"distinct_nodes": true}` puts n pods on n distinct nodes drawn from the
seed. Groups are created in list order, each in its own drawn order.
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
    standing_template: List[str] = field(default_factory=list)  # (S,)

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
    groups = st if isinstance(st, list) else [st] if st else []
    all_rows = []
    for g in groups:
        rows = _standing_rows(g, n, scale, rng)
        t = cfg["pod_templates"][g["template"]]
        first = len(c.standing_keys)
        c.standing_keys += [f"{t['namespace']}/standing-{first + i}"
                            for i in range(len(rows))]
        c.standing_template += [g["template"]] * len(rows)
        all_rows.append(rows)
    c.standing_node = (np.concatenate(all_rows) if all_rows
                       else np.zeros(0, dtype=np.int64))
    return c


def _standing_rows(group: dict, n: int, scale: int,
                   rng: np.random.Generator) -> np.ndarray:
    """The node row of each pod of one standing group, in creation
    order."""
    if set(group) == {"per_node", "template"}:
        rows = np.repeat(np.arange(n), group["per_node"])
        rng.shuffle(rows)
        return rows
    if set(group) == {"count", "template", "distinct_nodes"} and \
            group["distinct_nodes"] is True:
        k = max(1, group["count"] // scale)
        if k > n:
            raise ValueError(f"standing group of {k} pods on distinct "
                             f"nodes, but only {n} nodes")
        return rng.choice(n, size=k, replace=False)
    raise ValueError(f"standing group {group!r}: expected per_node + "
                     "template, or count + template + distinct_nodes true")


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


#: Template keys `to_program_pod` maps, and the harness's own.
POD_KEYS = frozenset({"from", "name_prefix", "namespace", "requests",
                      "labels", "images", "container_ports",
                      "topology_spread_constraints", "affinity"})
SPREAD_KEYS = frozenset({"max_skew", "topology_key", "when_unsatisfiable",
                         "match_labels"})
TERM_KEYS = frozenset({"match_labels", "match_expressions", "topology_key",
                       "namespaces"})
OPERATORS = ("In", "NotIn", "Exists", "DoesNotExist")


def _refuse_unmapped(what: str, keys, mapped) -> None:
    extra = set(keys) - mapped
    if extra:
        raise ValueError(f"{what}: the harness does not map "
                         f"{sorted(extra)}")


def check_template(template: dict) -> None:
    """Raise on any key of a pod template that `to_program_pod` would
    not carry over to the program's pod."""
    _refuse_unmapped("pod template", template, POD_KEYS)
    for x in template.get("topology_spread_constraints", []):
        _refuse_unmapped("topology spread constraint", x, SPREAD_KEYS)
    aff = template.get("affinity") or {}
    _refuse_unmapped("affinity", aff, {"pod_affinity", "pod_anti_affinity"})
    for kind, spec in aff.items():
        _refuse_unmapped(kind, spec, {"required", "preferred"})
        terms = list(spec.get("required", []))
        for w in spec.get("preferred", []):
            _refuse_unmapped(kind + " preferred", w, {"weight", "term"})
            terms.append(w["term"])
        for term in terms:
            _refuse_unmapped("pod affinity term", term, TERM_KEYS)
            for e in term.get("match_expressions", []):
                _refuse_unmapped("match expression", e,
                                 {"key", "operator", "values"})
                if e["operator"] not in OPERATORS:
                    raise ValueError("match expression operator "
                                     f"{e['operator']!r}")


def _term(o, term: dict):
    return o.PodAffinityTerm(
        label_selector=o.LabelSelector(
            match_labels=dict(term.get("match_labels", {})),
            match_expressions=[o.NodeSelectorRequirement(
                key=e["key"], operator=e["operator"],
                values=list(e.get("values", [])))
                for e in term.get("match_expressions", [])]),
        topology_key=term["topology_key"],
        namespaces=list(term.get("namespaces", [])))


def _affinity(o, aff: dict):
    out = o.Affinity()
    for key, cls in (("pod_affinity", o.PodAffinity),
                     ("pod_anti_affinity", o.PodAntiAffinity)):
        if key in aff:
            setattr(out, key, cls(
                required=[_term(o, t) for t in aff[key].get("required", [])],
                preferred=[o.WeightedPodAffinityTerm(
                    weight=int(w["weight"]), term=_term(o, w["term"]))
                    for w in aff[key].get("preferred", [])]))
    return out


def to_program_pod(template: dict, name: str, node_name: str = "") -> object:
    check_template(template)
    return program_pod(template, name, node_name)


def program_pod(template: dict, name: str, node_name: str = "") -> object:
    """`to_program_pod` for a template that `check_template` has passed:
    the paths that make many pods of one template check it once."""
    from minisched_tpu.state import objects as o

    spread = [o.TopologySpreadConstraint(
        max_skew=x["max_skew"], topology_key=x["topology_key"],
        when_unsatisfiable=x["when_unsatisfiable"],
        label_selector=o.LabelSelector(match_labels=dict(x["match_labels"])))
        for x in template.get("topology_spread_constraints", [])]
    aff = template.get("affinity")
    pod = o.Pod(
        metadata=o.ObjectMeta(name=name, namespace=template["namespace"],
                              labels=dict(template.get("labels", {}))),
        spec=o.PodSpec(
            requests=dict(template["requests"]),
            images=list(template.get("images", [])),
            ports=[o.ContainerPort(container_port=p)
                   for p in template.get("container_ports", [])],
            topology_spread_constraints=spread,
            affinity=_affinity(o, aff) if aff else None))
    if node_name:
        pod.spec.node_name = node_name
        pod.status.phase = o.PodPhase.RUNNING
    return pod


def to_program_standing(c: Cluster) -> list:
    for t in set(c.standing_template):
        check_template(c.templates[t])
    return [program_pod(c.templates[t], k.split("/", 1)[1], c.node_names[r])
            for k, r, t in zip(c.standing_keys, c.standing_node,
                               c.standing_template)]
