"""NodeAffinity: required filter + preferred score.

Batched counterpart of the upstream nodeaffinity plugin (wrapped in the
reference's registry, scheduler/plugin/plugins.go:24-70). Matching runs per
node-affinity GROUP (distinct node_selector + affinity signatures — see
encode.NodeAffinityGroups) and pods gather their group's row, keeping the
cost O(G2 × N) instead of O(P × N) term evaluations.
"""
from __future__ import annotations

import jax.numpy as jnp

from ..ops import matchers
from ..ops.topology import gather_group_rows
from ..state.events import ActionType, ClusterEvent, GVK
from .base import BatchedPlugin


def group_required_match(naf, nf) -> jnp.ndarray:
    """(G2, N): node_selector pairs ⊆ labels AND (required terms match if
    present)."""
    sel_ok = matchers.pairs_subset(naf.sel_pairs, nf.label_pairs)
    terms_ok = matchers.term_matches(naf.req_op, naf.req_key, naf.req_vals,
                                     nf.label_pairs, nf.label_keys)
    return sel_ok & jnp.where(naf.req_has[:, None], terms_ok, True)


def group_preferred_score(naf, nf) -> jnp.ndarray:
    """(G2, N): Σ weight × [preferred term matches] (upstream scoring)."""
    T2 = naf.pref_op.shape[1]
    score = jnp.zeros((naf.valid.shape[0], nf.valid.shape[0]), jnp.float32)
    for t in range(T2):  # static tiny loop
        m = matchers.term_matches(naf.pref_op[:, t:t + 1],
                                  naf.pref_key[:, t:t + 1],
                                  naf.pref_vals[:, t:t + 1],
                                  nf.label_pairs, nf.label_keys)
        score = score + naf.pref_weight[:, t:t + 1] * m
    return score


class NodeAffinity(BatchedPlugin):
    name = "NodeAffinity"
    needs_node_affinity = True

    def events_to_register(self):
        return [ClusterEvent(GVK.NODE, ActionType.ADD | ActionType.UPDATE_NODE_LABEL)]

    def filter(self, pf, nf, ctx) -> jnp.ndarray:
        return gather_group_rows(pf.na_group, ctx["na_req_match"], fill=1.0) > 0

    def score(self, pf, nf, ctx) -> jnp.ndarray:
        return gather_group_rows(pf.na_group, ctx["na_pref_score"], fill=0.0)

    def normalize(self, scores, feasible):
        from ..ops.pipeline import max_normalize_100

        return max_normalize_100(scores, feasible)
