"""Is what the timed path produced correct?

Every run is judged, after its window has closed and the program's state
is freed, on the answers it gave:

- `rebinds`: bind transitions seen for a pod already bound (limit 0);
- `unbound`: pods due whose bind never came by the window's close plus
  the grace (limit 0);
- `over_capacity`: nodes whose bound requests exceed allocatable in the
  store at the end (limit 0);
- `filter_fail`: judged pods whose node surely fails a filter of the
  profile (limit 0);
- `score_gap`: the widest amount by which a judged pod's node scores below
  the best node that surely had room for it, by the reference
  (benchmark/reference), against the cluster as the pod's batch found it
  (limit from the configuration's `check` section);
- `zone_skew` (configurations that state a zone-skew guarantee): the
  largest count of matching bound pods a zone surely held above the least
  zone right after a batch bound (limit: the configuration's maxSkew).

A judged pod is one the traffic created and the scheduler bound at or
after the window opened. Pods that the store stamped with one bind time
went in one bulk bind: one batch. The batch's view of the cluster is
rebuilt from the stamps: a pod bound more than `bind_s` before the batch
and not deleted before its bind was surely there (state A); one bound up
to `bind_s` after it, or deleted up to `delete_s` before it, may have been
(state B adds those). A node "surely better" scores higher under both
states than the chosen node under either, has room for the pod under B
with the whole batch placed, and shares the chosen node's domain under
every topology-spread constraint of the pod (where the spread score and
the skew verdict are the same for both). So a gap is never the product
of not knowing what the engine saw, only of a placement the reference
would not make. The zone skew is exact at the end of the run while no
matching pod was deleted; otherwise only what the stamps make sure.

The control (reported with `--control`) puts each judged pod on a node drawn at random
from those that surely had room and passed every filter: the reference
with its Score step taken out, which breaks the placement guarantee.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List

import numpy as np

from . import reference as ref

MAX_GROUPS = 400


@dataclass
class Verdict:
    rows: List[tuple] = field(default_factory=list)  # name, value, limit, ok

    def add(self, name: str, value, limit) -> None:
        self.rows.append((name, value, limit, value <= limit))

    @property
    def correct(self) -> bool:
        return all(ok for *_x, ok in self.rows)


def _nodes(cl) -> ref.Nodes:
    t = cl.node_template
    return ref.Nodes(cl.resources, cl.alloc, cl.node_labels,
                     np.full(cl.n_nodes, bool(t.get("unschedulable"))),
                     [t.get("taints", [])] * cl.n_nodes)


class Universe:
    """Every pod that was ever bound in the run, as arrays."""

    def __init__(self, run, nodes: ref.Nodes):
        cl = run.cluster
        row = {n: i for i, n in enumerate(cl.node_names)}
        names = sorted(cl.templates)
        self.templates = [cl.templates[n] for n in names]
        tid = {n: i for i, n in enumerate(names)}
        keys, node, tmpl, t_bind = [], [], [], []
        for k, r in zip(cl.standing_keys, cl.standing_node):
            keys.append(k)
            node.append(int(r))
            tmpl.append(tid[cl.standing_template])
            t_bind.append(-math.inf)
        for k, (n, stamp) in run.put_back.items():
            keys.append(k)
            node.append(row[n])
            tmpl.append(tid[cl.standing_template])
            t_bind.append(stamp)
        incoming = tid[run.template]
        for k, (n, stamp) in run.binds.items():
            keys.append(k)
            node.append(row[n])
            tmpl.append(incoming)
            t_bind.append(stamp)
        self.node = np.array(node, dtype=np.int64)
        self.tmpl = np.array(tmpl, dtype=np.int64)
        self.t_bind = np.array(t_bind)
        self.t_del = np.array([run.deleted.get(k, math.inf) for k in keys])
        reqs = np.stack([ref.request(t, nodes.resources)
                         for t in self.templates])
        self.req = reqs[self.tmpl]
        self.n_nodes = len(nodes.labels)

    def used(self, mask: np.ndarray) -> np.ndarray:
        return np.stack([np.bincount(self.node[mask], self.req[mask, r],
                                     minlength=self.n_nodes)
                         for r in range(self.req.shape[1])], axis=1)


def judge(run, cfg: dict) -> tuple:
    """(the run's verdict, the control's verdict on the same batches)."""
    v = Verdict()
    limits = cfg["check"]
    v.add("rebinds", int(run.rebinds), 0)
    v.add("unbound", run.failed(), 0)
    v.add("over_capacity", _over_capacity(run), 0)
    nodes = _nodes(run.cluster)
    u = Universe(run, nodes)
    gap, ctl_gap, bad, skew = _placements(run, cfg, nodes, u, limits)
    v.add("filter_fail", bad, 0)
    v.add("score_gap", gap, limits["score_gap"])
    zs = cfg["guarantees"].get("zone_skew")
    if zs:
        v.add("zone_skew", skew, zs["max_skew"])
    ctl = Verdict()
    ctl.add("score_gap", ctl_gap, limits["score_gap"])
    return v, ctl


def _over_capacity(run) -> int:
    cl = run.cluster
    row = {n: i for i, n in enumerate(cl.node_names)}
    used = np.zeros_like(cl.alloc)
    axis = {r: i for i, r in enumerate(cl.resources)}
    for _key, node, requests in run.store_pods:
        if not node:
            continue
        i = row[node]
        used[i, axis["pods"]] += 1
        for r, q in requests.items():
            if r in axis and r != "pods":
                used[i, axis[r]] += q
    return int(np.any(used > cl.alloc + 1e-9, axis=1).sum())


def _placements(run, cfg, nodes: ref.Nodes, u: Universe, limits) -> tuple:
    """(widest score gap, the control's widest gap, surely-failing
    placements, widest sure zone skew) over the judged batches."""
    plugins = set(cfg["profile"]["plugins"])
    weights = cfg["profile"]["weights"]
    wb, wd = limits["bind_s"], limits["delete_s"]
    judged = (u.t_bind >= run.t0) & np.isfinite(u.t_bind)
    stamps = np.unique(u.t_bind[judged])
    rng = np.random.default_rng(run.seed)
    if len(stamps) > MAX_GROUPS:
        stamps = np.sort(rng.choice(stamps, MAX_GROUPS, replace=False))
    zs = cfg["guarantees"].get("zone_skew")
    z_dom = nodes.domains(zs["key"]) if zs else None
    z_match = (np.array([ref.matches(t.get("labels", {}), zs["match_labels"])
                         for t in u.templates])[u.tmpl] if zs else None)
    worst_gap = worst_ctl = 0.0
    bad = 0
    worst_skew = -math.inf if zs else 0
    for T in stamps:
        group = u.t_bind == T
        certain = (u.t_bind < T - wb) & (u.t_del > T) & ~group
        possible = (u.t_bind <= T + wb) & (u.t_del >= T - wd) & ~group
        used_a, used_b = u.used(certain), u.used(possible)
        placed = u.used(group)
        if zs:
            n_dom = int(z_dom.max()) + 1
            def zc(mask):
                d = z_dom[u.node[mask & z_match]]
                return np.bincount(d[d >= 0], minlength=n_dom)
            lo_end = zc(certain) + zc(group)
            hi_end = zc(possible) + zc(group)
            worst_skew = max(worst_skew, int(lo_end.max() - hi_end.min()))
        for t_i in np.unique(u.tmpl[group]):
            t = u.templates[t_i]
            rows = u.node[group & (u.tmpl == t_i)]
            g, c, b = _judge_template(t, nodes, u, certain, possible,
                                      used_a, used_b, placed, rows,
                                      weights, plugins, rng)
            worst_gap, worst_ctl = max(worst_gap, g), max(worst_ctl, c)
            bad += b
    if zs:
        # Exact once nothing is in flight, while no matching pod was
        # deleted: the zone counts of everything bound.
        alive = z_match & ~np.isfinite(u.t_del)
        if not (z_match & np.isfinite(u.t_del)).any():
            d = z_dom[u.node[alive]]
            end = np.bincount(d[d >= 0], minlength=int(z_dom.max()) + 1)
            worst_skew = max(worst_skew, int(end.max() - end.min()))
    if not np.isfinite(worst_skew):
        worst_skew = 0
    return float(worst_gap), float(worst_ctl), int(bad), worst_skew


def _judge_template(t, nodes, u, certain, possible, used_a, used_b,
                    placed, rows, weights, plugins, rng) -> tuple:
    """(widest gap, the control's widest gap, surely-failing placements)
    for the pods of one template in one batch, placed on `rows`.

    A pod under topology-spread constraints is compared only with nodes
    in its node's domains: there the spread score and the domain's skew
    verdict are the same for both nodes, whatever the unknown counts, so
    only the node's own scores (resources, images) can differ."""
    req = ref.request(t, nodes.resources)
    static = ref.static_filter(t, nodes, plugins)
    local = plugins - {"PodTopologySpread"}
    s_a = ref.scores(t, nodes, used_a, {}, static, weights, local)
    s_b = ref.scores(t, nodes, used_b, {}, static, weights, local)
    lo, hi = np.minimum(s_a, s_b), np.maximum(s_a, s_b)
    valid = static & ref.fits(req, used_b + placed, nodes.alloc)
    dom = np.zeros(len(nodes.labels), dtype=np.int64)
    for c in t.get("topology_spread_constraints", []):
        d = nodes.domains(c["topology_key"])
        dom = dom * (int(d.max()) + 2) + (d + 1)
    best = np.full(int(dom.max()) + 1, -math.inf)
    np.maximum.at(best, dom[valid], lo[valid])
    gap = float(np.max(best[dom[rows]] - hi[rows], initial=0.0))
    # surely fails: a static filter, or no room even under A with the
    # whole batch placed
    over = ~ref.fits(np.zeros_like(req), used_a + placed, nodes.alloc)
    bad = int((~static[rows] | over[rows]).sum())
    ctl = 0.0
    if valid.any():
        pick = rng.choice(np.flatnonzero(valid), size=len(rows))
        ctl = float(np.max(best[dom[pick]] - hi[pick], initial=0.0))
    return max(gap, 0.0), max(ctl, 0.0), bad
