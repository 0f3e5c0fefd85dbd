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
  zone right after a batch bound (limit: the configuration's maxSkew);
- `anti_affinity` (configurations that state an anti-affinity
  guarantee): the largest number of mutually exclusive pods alive at
  once in one domain, read at each judged pod's bind stamp from every
  pod's [bind, delete) stamps (limit 1). Two pods are mutually exclusive
  under a key where a required anti-affinity term of either matches the
  other. Exact from the stamps.

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

Inter-pod affinity is judged from the same two states: every term's
matching pods per domain lie between their counts under A and under B.
A placement surely fails where it fails the filter under every state in
between (a matching pod under A in an anti term's domain, no matching
pod under B in an affinity term's, a pod under A whose anti term
forbids the domain) or shares a domain with a pod of its own batch that
it excludes or that excludes it. A node is surely better only where it
surely passes the filter and no pod of the batch that excludes the
pod's kind sits in its domain. The InterPodAffinity score is bounded per
node by taking each term's positive contributions from A and negative
ones from B (and the reverse), then through upstream's normalisation
with the least and largest raw scores each bounded over the nodes that
surely and that possibly pass, so a correct engine is never charged.

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
        by_id = {id(t): i for i, t in enumerate(self.templates)}
        keys, node, tmpl, t_bind = [], [], [], []
        for k, r, t in zip(cl.standing_keys, cl.standing_node,
                           cl.standing_template):
            keys.append(k)
            node.append(int(r))
            tmpl.append(tid[t])
            t_bind.append(-math.inf)
        for k, (n, stamp) in run.put_back.items():
            keys.append(k)
            node.append(row[n])
            tmpl.append(by_id[id(run.template_of[k])])
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

    def view(self, mask: np.ndarray) -> list:
        """The pods of `mask` as the reference's view: (template, node
        rows) per template."""
        return [(t, self.node[mask & (self.tmpl == i)])
                for i, t in enumerate(self.templates)]


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
    if cfg["guarantees"].get("anti_affinity"):
        v.add("anti_affinity", _anti_affinity(run, nodes, u), 1)
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
    aff = any(t.get("affinity") for t in u.templates)
    worst_gap = worst_ctl = 0.0
    bad = 0
    worst_skew = -math.inf if zs else 0
    for T in stamps:
        group = u.t_bind == T
        certain = (u.t_bind < T - wb) & (u.t_del > T) & ~group
        possible = (u.t_bind <= T + wb) & (u.t_del >= T - wd) & ~group
        views = ((u.view(certain), u.view(possible), u.view(group))
                 if aff else None)
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
                                      weights, plugins, rng, views)
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
                    placed, rows, weights, plugins, rng, views) -> tuple:
    """(widest gap, the control's widest gap, surely-failing placements)
    for the pods of one template in one batch, placed on `rows`.

    A pod under topology-spread constraints is compared only with nodes
    in its node's domains: there the spread score and the domain's skew
    verdict are the same for both nodes, whatever the unknown counts, so
    only the node's own scores (resources, images) can differ. `views`
    (certain, possible, the batch) as the reference's views, where a
    template of the run carries affinity terms, else None."""
    req = ref.request(t, nodes.resources)
    static = ref.static_filter(t, nodes, plugins)
    local = plugins - {"PodTopologySpread", "InterPodAffinity"}
    s_a = ref.scores(t, nodes, used_a, {}, static, weights, local)
    s_b = ref.scores(t, nodes, used_b, {}, static, weights, local)
    lo, hi = np.minimum(s_a, s_b), np.maximum(s_a, s_b)
    valid = static & ref.fits(req, used_b + placed, nodes.alloc)
    # surely fails: a static filter, or no room even under A with the
    # whole batch placed
    over = ~ref.fits(np.zeros_like(req), used_a + placed, nodes.alloc)
    failing = ~static[rows] | over[rows]
    if views is not None and "InterPodAffinity" in plugins:
        a, b, batch = views
        sure_fail = (~ref.affinity_ok(t, nodes, b, anywhere=a)
                     | ~ref.anti_affinity_ok(t, nodes, a))
        sure_pass = (ref.affinity_ok(t, nodes, a, anywhere=b)
                     & ref.anti_affinity_ok(t, nodes, b))
        block = _excluding(t, nodes, batch)
        # each pod of the batch counts itself once per term by which its
        # kind excludes itself
        others = block[rows] - len(_exclusions(t, t))
        failing |= sure_fail[rows] | (others > 0)
        valid &= sure_pass & (block == 0)
        maybe = static & ref.fits(req, used_a, nodes.alloc) & ~sure_fail
        maybe[rows] = True
        plus_a, minus_a = ref.affinity_parts(t, nodes, a)
        plus_b, minus_b = ref.affinity_parts(t, nodes, b)
        n_lo, n_hi = _normalized_bounds(plus_a - minus_b, plus_b - minus_a,
                                        valid, maybe)
        w = weights.get("InterPodAffinity", 1.0)
        lo, hi = lo + w * n_lo, hi + w * n_hi
    dom = np.zeros(len(nodes.labels), dtype=np.int64)
    for c in t.get("topology_spread_constraints", []):
        d = nodes.domains(c["topology_key"])
        dom = dom * (int(d.max()) + 2) + (d + 1)
    best = np.full(int(dom.max()) + 1, -math.inf)
    np.maximum.at(best, dom[valid], lo[valid])
    gap = float(np.max(best[dom[rows]] - hi[rows], initial=0.0))
    bad = int(failing.sum())
    ctl = 0.0
    if valid.any():
        pick = rng.choice(np.flatnonzero(valid), size=len(rows))
        ctl = float(np.max(best[dom[pick]] - hi[pick], initial=0.0))
    return max(gap, 0.0), max(ctl, 0.0), bad


def _exclusions(t, pod):
    """The topology keys under which a pod of template `t` and one of
    `pod` exclude each other: a required anti term of either that
    matches the other, once per term."""
    keys = [x["topology_key"] for x, _w in ref.terms(t, "pod_anti_affinity",
                                                     True)
            if ref.term_matches(x, t["namespace"], pod)]
    keys += [x["topology_key"] for x, _w in ref.terms(
        pod, "pod_anti_affinity", True)
        if ref.term_matches(x, pod["namespace"], t)]
    return keys


def _excluding(t, nodes, view) -> np.ndarray:
    """(N,) pods of `view` in each node's domain that a pod of `t`
    excludes or is excluded by, summed over the terms."""
    out = np.zeros(len(nodes.labels))
    for pod, rows in view:
        for key in _exclusions(t, pod):
            out += ref.in_domain(nodes, key, rows)
    return out


def _normalized_bounds(raw_lo, raw_hi, sure, maybe) -> tuple:
    """Bounds on upstream's normalised InterPodAffinity score, 100 x (s -
    m) / (M - m) with m = min(0, min s), M = max(0, max s) over the
    feasible nodes, where each node's raw score s lies in [raw_lo,
    raw_hi] and the feasible nodes include `sure` and lie in `maybe`."""
    def least(x, mask):
        return min(0.0, float(x[mask].min())) if mask.any() else 0.0

    def most(x, mask):
        return max(0.0, float(x[mask].max())) if mask.any() else 0.0

    m_lo, m_hi = least(raw_lo, maybe), least(raw_hi, sure)
    big_lo, big_hi = most(raw_lo, sure), most(raw_hi, maybe)
    span_hi, span_lo = big_hi - m_lo, big_lo - m_hi
    lo = (np.clip(raw_lo - m_hi, 0.0, None) / span_hi if span_hi > 0
          else np.zeros_like(raw_lo))
    top = raw_hi - m_lo
    hi = np.where(top <= 0, 0.0,
                  np.minimum(100.0, 100.0 * top / span_lo) if span_lo > 0
                  else 100.0)
    return 100.0 * lo, hi


def _anti_affinity(run, nodes: ref.Nodes, u: Universe) -> int:
    """The largest number of mutually exclusive pods alive at once in one
    domain, read at each judged pod's bind stamp."""
    judged = (u.t_bind >= run.t0) & np.isfinite(u.t_bind)
    worst = 0
    for i in np.unique(u.tmpl[judged]):
        t = u.templates[i]
        p = np.flatnonzero(judged & (u.tmpl == i))
        worst = max(worst, 1)
        for j, pod in enumerate(u.templates):
            for key in _exclusions(t, pod):
                dom = nodes.domains(key)
                q = np.flatnonzero(u.tmpl == j)
                alive = _alive(dom[u.node[p]], u.t_bind[p], dom[u.node[q]],
                               u.t_bind[q], u.t_del[q])
                if i == j:   # a pod alive at its own bind counts itself
                    alive -= 1
                alive = np.where(dom[u.node[p]] >= 0, alive, 0)
                worst = max(worst, 1 + int(alive.max(initial=0)))
    return worst


def _alive(p_dom, p_t, q_dom, q_bind, q_del) -> np.ndarray:
    """For each p, the q in p's domain with q_bind <= p_t < q_del."""
    times = np.unique(np.concatenate([p_t, q_bind, q_del]))

    def key(d, x):   # one sorted axis: domain first, then time
        return d * (len(times) + 1) + np.searchsorted(times, x)

    bound = np.sort(key(q_dom, q_bind))
    gone = np.sort(key(q_dom, q_del))
    at = key(p_dom, p_t)
    return (np.searchsorted(bound, at, side="right")
            - np.searchsorted(gone, at, side="right"))
