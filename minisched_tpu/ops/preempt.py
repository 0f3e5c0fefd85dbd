"""Batched preemption candidate search — DefaultPreemption's device math.

Upstream DefaultPreemption walks nodes per preemptor in Go, simulating
victim removals pod by pod (``SelectVictimsOnNode`` — victims come from
the CANDIDATE NODE only). The batched formulation evaluates every
(failed pod, node) pair at once:

  1. incurable feasibility: AND of every filter marked
     ``capacity_only=False`` EXCEPT the anti-affinity and hard-spread
     checks below — taints, selectors, node affinity, unschedulable,
     names, required pod AFFINITY (eviction can only remove pods, never
     create the match a required affinity needs);
  2. curable topology rejections (upstream parity — node-local victim
     simulation, closing the round-3/4 documented deviation):
       * required anti-affinity (the preemptor's own terms): node n is
         curable iff EVERY matching assigned pod in n's domain sits on n
         itself with priority strictly below the preemptor's — evicting
         them removes the rejection. Matching pods elsewhere in the
         domain can never be evicted by a node-local victim set, so they
         keep the node infeasible (exactly upstream's scope).
       * symmetric existing-pod anti-affinity: each anti class stamped
         on the preemptor (encode.anti_cls_group) is a selector group
         counting the class's owners, so the same per-(class, domain)
         reductions apply — a node in a domain holding owners is
         curable iff every owner in that domain sits on the node itself
         (a single owner node) with priority strictly below the
         preemptor's (it outranks every owner).
       * DoNotSchedule topology spread: placing on node n is over-skew
         by ``over = count(d(n)) + 1 - min - max_skew`` pods; node-local
         eviction of ``over`` lower-priority MATCHING pods lowers
         count(d(n)) by exactly that much (the global min can only stay
         or drop, so judging against the pre-eviction min is
         conservative and sound). Curable iff n holds >= over matching
         evictable pods; the per-slot counts are returned so the host
         selects that many matching victims (``spread_evict``).
  3. victim release: for each failed pod p, the resources that evicting
     ALL strictly-lower-priority bound pods on node n would free —
     per-resource segment-sums of the assigned corpus (A-axis), one
     (Pf, N) matrix per resource axis, never a (Pf, N, R) tensor. The
     mandatory topology victims above are lower-priority pods on n, so
     their release is already inside this pool;
  4. fits: free + release covers p's request on every axis;
  5. candidate nodes = (1) ∧ (2) ∧ (4); choose the node minimizing the
     victim COUNT (upstream's fewest-victims criterion; the engine then
     selects the mandatory topology victims plus a minimal capacity
     prefix host-side, lowest priority first).

Shapes: Pf = failed-pod bucket (small), N = nodes, A = assigned corpus,
G = selector groups. Cost is O(G·A + Pf·A·(T+C) + R·Pf·N) — linear in
the corpus, no P×N plugin matrices beyond the (Pf, N) masks.

Remaining documented deviation: upstream re-runs ALL filters after
removing victims, so it also notices a victim whose eviction would
BREAK the preemptor's own required affinity (the affinity-supplying pod
chosen as a capacity victim); here the host's victim selection orders
by priority only and does not protect affinity-supplying victims.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..encode.features import DEFAULT_ENCODING, SPREAD_DO_NOT_SCHEDULE, \
    EncodingConfig
from ..plugins.base import PluginSet
from .topology import gather_group_rows, group_assigned_match, \
    group_topology_state

_PREEMPT_CACHE: dict = {}


def build_preempt_op(plugin_set: PluginSet, *,
                     cfg: EncodingConfig = DEFAULT_ENCODING):
    """Jitted ``op(eb_failed, nf, af) -> (chosen_node (Pf,) i32,
    ok (Pf,) bool, victim_count (Pf,) f32, spread_evict (Pf,C) f32)``.

    ``spread_evict[p, c]`` is how many pods MATCHING constraint slot c's
    selector the host must evict from the chosen node to cure that
    slot's skew (0 when the slot is inactive or already within skew).

    eb_failed is a failed-pod sub-batch (rows beyond the live set padded
    invalid); nf/af are full-axis snapshots — the engine passes a FRESH
    post-assume snapshot (survivors and in-cycle repairs debited), and
    the host victim-selection stage re-validates every candidate against
    live cache state before any eviction."""
    key = (tuple(p.trace_key() for p in plugin_set.filter_plugins), cfg)
    cached = _PREEMPT_CACHE.get(key)
    if cached is not None:
        return cached

    hard_filters = [p for p in plugin_set.filter_plugins
                    if not p.capacity_only]
    anti_cure = any(p.name == "InterPodAffinity" for p in hard_filters)
    spread_cure = any(p.name == "PodTopologySpread" for p in hard_filters)
    incurable_filters = [p for p in hard_filters
                         if p.name not in ("InterPodAffinity",
                                           "PodTopologySpread")]
    needs_topology = any(p.needs_topology for p in hard_filters)
    needs_node_affinity = any(p.needs_node_affinity for p in hard_filters)

    def op(eb, nf, af):
        pf = eb.pf
        N = nf.valid.shape[0]
        Pf = pf.valid.shape[0]
        C = pf.spread_group.shape[1]

        ctx = {"af": af, "gf": eb.gf, "naf": eb.naf}
        if needs_topology:
            num_domains = max(N, cfg.domain_buckets)
            ctx.update(group_topology_state(nf, af, eb.gf, num_domains))
        if needs_node_affinity:
            from ..plugins.nodeaffinity import (group_preferred_score,
                                                group_required_match)

            ctx["na_req_match"] = group_required_match(eb.naf, nf)
            ctx["na_pref_score"] = group_preferred_score(eb.naf, nf)

        cand = pf.valid[:, None] & nf.valid[None, :]
        for p in incurable_filters:
            cand = cand & p.filter(pf, nf, ctx)

        # Victim pool per failed pod: assigned pods STRICTLY below its
        # priority (upstream's victim eligibility).
        lower = (af.valid[None, :]
                 & (af.priority[None, :] < pf.priority[:, None]))  # (Pf,A)
        lower_f = lower.astype(jnp.float32)
        node_ids = jnp.clip(af.node_row, 0, N - 1)

        def by_node(weights):  # (A,) → (N,) segment sum
            return jax.ops.segment_sum(weights, node_ids, num_segments=N)

        spread_evict = jnp.zeros((Pf, C), dtype=jnp.float32)
        if (anti_cure or spread_cure) and needs_topology:
            match = group_assigned_match(eb.gf, af)          # (G,A)
            G = eb.gf.valid.shape[0]

            def local_evictable(groups):
                """(Pf,) group idx → (Pf, N): lower-priority assigned
                pods MATCHING the group sitting on each node."""
                gsafe = jnp.clip(groups, 0, G - 1)
                msel = match[gsafe]                          # (Pf,A)
                return jax.vmap(by_node)(msel * lower_f)     # (Pf,N)

        if anti_cure:
            T = pf.anti_req_group.shape[1]
            # The preemptor's own required anti terms, then the anti
            # classes of running pods' terms stamped on it (existing-pod
            # symmetry, counting the class's owners): the same cure.
            anti = jnp.concatenate([pf.anti_req_group, pf.anti_cls_group],
                                   axis=1)
            for t in range(anti.shape[1]):
                ag = anti[:, t]                              # (Pf,)
                acounts = gather_group_rows(ag, ctx["counts_node"])
                adom = gather_group_rows(
                    ag, ctx["dom_valid"].astype(jnp.float32)) > 0
                blocked = adom & (acounts > 0)
                loc_low = local_evictable(ag)
                # all of the domain's matching pods are ON this node and
                # evictable → evicting them cures the term
                curable = blocked & (acounts == loc_low)
                cand = cand & jnp.where((ag >= 0)[:, None],
                                        (~blocked) | curable, True)
            # Required AFFINITY terms are incurable (eviction cannot
            # create the required match) — same formula as the plugin.
            for t in range(T):
                g = pf.aff_req_group[:, t]
                counts = gather_group_rows(g, ctx["counts_node"])
                dom_ok = gather_group_rows(
                    g, ctx["dom_valid"].astype(jnp.float32)) > 0
                gsafe = jnp.clip(g, 0, ctx["has_match"].shape[0] - 1)
                self_ok = (pf.aff_req_self[:, t]
                           & ~ctx["has_match"][gsafe])[:, None]
                cand = cand & jnp.where(
                    (g >= 0)[:, None],
                    (dom_ok & (counts > 0)) | self_ok, True)

        if spread_cure:
            for c in range(C):
                g = pf.spread_group[:, c]
                active = ((g >= 0)
                          & (pf.spread_mode[:, c] == SPREAD_DO_NOT_SCHEDULE))
                counts = gather_group_rows(g, ctx["counts_node"])
                dom_ok = gather_group_rows(
                    g, ctx["dom_valid"].astype(jnp.float32)) > 0
                gsafe = jnp.clip(g, 0, ctx["min_count"].shape[0] - 1)
                over = (counts + 1.0 - ctx["min_count"][gsafe][:, None]
                        - pf.spread_max_skew[:, c].astype(
                            jnp.float32)[:, None])             # (Pf,N)
                blocked = over > 0
                loc_low = local_evictable(g)
                curable = blocked & (loc_low >= over)
                cand = cand & jnp.where(active[:, None],
                                        dom_ok & ((~blocked) | curable),
                                        True)
                # per-slot eviction counts are gathered at the chosen
                # node AFTER the argmax below

        fits = cand
        for r in range(pf.requests.shape[1]):  # static small resource loop
            rel_r = jax.vmap(lambda m: by_node(m * af.requests[:, r])
                             )(lower_f)                          # (Pf,N)
            fits = fits & ((nf.free[None, :, r] + rel_r)
                           >= pf.requests[:, r][:, None])
        vcnt = jax.vmap(by_node)(lower_f)                        # (Pf,N)

        ok = fits.any(axis=1) & pf.valid
        score = jnp.where(fits, -vcnt, -jnp.inf)
        chosen = jnp.argmax(score, axis=1).astype(jnp.int32)
        chosen = jnp.where(ok, chosen, -1)
        cnt = jnp.where(ok, jnp.take_along_axis(
            vcnt, jnp.clip(chosen, 0, N - 1)[:, None], axis=1)[:, 0], 0.0)

        if spread_cure:
            # Gather each slot's per-node eviction need at the chosen node.
            chosen_safe = jnp.clip(chosen, 0, N - 1)[:, None]
            evicts = []
            for c in range(C):
                g = pf.spread_group[:, c]
                active = ((g >= 0)
                          & (pf.spread_mode[:, c] == SPREAD_DO_NOT_SCHEDULE))
                counts = gather_group_rows(g, ctx["counts_node"])
                gsafe = jnp.clip(g, 0, ctx["min_count"].shape[0] - 1)
                over = (counts + 1.0 - ctx["min_count"][gsafe][:, None]
                        - pf.spread_max_skew[:, c].astype(
                            jnp.float32)[:, None])
                need = jnp.take_along_axis(
                    jnp.maximum(over, 0.0), chosen_safe, axis=1)[:, 0]
                evicts.append(jnp.where(active & ok, need, 0.0))
            spread_evict = jnp.stack(evicts, axis=1)             # (Pf,C)

        return chosen, ok, cnt, spread_evict

    jitted = jax.jit(op)
    _PREEMPT_CACHE[key] = jitted
    return jitted
