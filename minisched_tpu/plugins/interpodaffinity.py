"""InterPodAffinity: required filter + weighted preference score.

Batched counterpart of the upstream interpodaffinity plugin (wrapped by the
reference's registry; BASELINE config 4 pairs it with PodTopologySpread at
50k nodes). Uses the shared topology cycle state: for a term with selector
group g, "a matching pod exists in the node's domain" ⇔ counts_node[g] > 0.

  required affinity:      node's domain must contain ≥1 matching pod.
  required anti-affinity: node's domain must contain none (nodes missing
                          the topology key can't violate — allowed).
  preferred (anti-)affinity: ± weight × matching-pod count per domain
                          (upstream sums term weight per matching existing
                          pod).

Counts see pods bound before this batch (same batching semantics as
PodTopologySpread); intra-batch required-anti-affinity conflicts — direct
and symmetric between two pods of the SAME batch — are caught by the
engine's priority-order arbitration (engine.scheduler.arbitrate_spread)
and retried. The SYMMETRIC check against already-RUNNING pods (upstream's
existing-pod anti-affinity) reads a device count plane: the node cache
keeps bound pods' required anti terms as anti CLASSES (distinct topology
key, namespace set and selector) and tags each owner's corpus row with
its class, so a class is one more selector group whose per-domain count
is upstream's existingAntiAffinityCounts. The encoder stamps each pod
with the class groups whose terms match it (once per pod signature,
cache.anti_classes_for), and the filter refuses a node whose domain
holds an owner of any stamped class — however many domains that is.
"""
from __future__ import annotations

import jax.numpy as jnp

from ..ops.topology import gather_group_rows
from ..state.events import ActionType, ClusterEvent, GVK
from .base import BatchedPlugin


class InterPodAffinity(BatchedPlugin):
    name = "InterPodAffinity"
    default_weight = 2.0  # upstream default
    needs_topology = True

    def events_to_register(self):
        return [ClusterEvent(GVK.POD, ActionType.ALL),
                ClusterEvent(GVK.NODE, ActionType.ADD | ActionType.UPDATE_NODE_LABEL)]

    def filter(self, pf, nf, ctx) -> jnp.ndarray:
        T = pf.aff_req_group.shape[1]
        P, N = pf.valid.shape[0], nf.valid.shape[0]
        ok = jnp.ones((P, N), dtype=bool)
        for t in range(T):
            g = pf.aff_req_group[:, t]
            counts = gather_group_rows(g, ctx["counts_node"])
            dom_ok = gather_group_rows(g, ctx["dom_valid"].astype(jnp.float32)) > 0
            gsafe = jnp.clip(g, 0, ctx["has_match"].shape[0] - 1)
            # Upstream special case: if NO pod anywhere matches the term but
            # the incoming pod matches its own selector, the term passes
            # (otherwise the first replica of a self-affine workload could
            # never schedule).
            self_ok = (pf.aff_req_self[:, t] & ~ctx["has_match"][gsafe])[:, None]
            ok = ok & jnp.where((g >= 0)[:, None],
                                (dom_ok & (counts > 0)) | self_ok, True)

            ag = pf.anti_req_group[:, t]
            acounts = gather_group_rows(ag, ctx["counts_node"])
            adom = gather_group_rows(ag, ctx["dom_valid"].astype(jnp.float32)) > 0
            ok = ok & jnp.where((ag >= 0)[:, None], ~(adom & (acounts > 0)), True)

        # Symmetric existing-pod anti-affinity (upstream parity): a
        # stamped class with an owner in the node's domain forbids it.
        # S is 0 in a batch nobody is repelled in: no work compiles.
        for s in range(pf.anti_cls_group.shape[1]):
            cg = pf.anti_cls_group[:, s]
            owners = gather_group_rows(cg, ctx["counts_node"])
            ok = ok & ~((cg >= 0)[:, None] & (owners > 0))
        return ok

    def score(self, pf, nf, ctx) -> jnp.ndarray:
        T = pf.aff_pref_group.shape[1]
        P, N = pf.valid.shape[0], nf.valid.shape[0]
        score = jnp.zeros((P, N), dtype=jnp.float32)
        for t in range(T):
            g = pf.aff_pref_group[:, t]
            score = score + (pf.aff_pref_weight[:, t:t + 1]
                             * gather_group_rows(g, ctx["counts_node"]))
            ag = pf.anti_pref_group[:, t]
            score = score - (pf.anti_pref_weight[:, t:t + 1]
                             * gather_group_rows(ag, ctx["counts_node"]))
        return score

    def normalize(self, scores, feasible):
        # Upstream normalizes by the max absolute score per pod; scores can
        # be negative (anti-affinity), so shift-and-scale into 0..100.
        masked = jnp.where(feasible, scores, 0.0)
        lo = masked.min(axis=1, keepdims=True)
        hi = masked.max(axis=1, keepdims=True)
        span = jnp.maximum(hi - lo, 1e-30)
        return jnp.where(hi > lo, 100.0 * (scores - lo) / span,
                         jnp.zeros_like(scores))
