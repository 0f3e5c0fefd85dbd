"""SelectorSpread: spread replicas of one controller across nodes/zones.

Batched counterpart of upstream's SelectorSpread score plugin (wrapped by
the reference's registry, scheduler/plugin/plugins.go:24-70; upstream
1.21+ ships it registered-but-disabled in favor of PodTopologySpread's
default constraints — the rebuild mirrors that: registered in
service/defaultconfig, not in the default profile). Upstream scores by
counting existing pods selected by the pod's Service/RC/RS/StatefulSet
selectors; the rebuild scopes the population by CONTROLLER OWNER
identity — replicas of one controller share it, which is the population
those selectors select.

Mechanically it rides the existing selector-group machinery end-to-end:

  * bind accounting appends the synthetic owner pair (``owner_spread_pair``)
    to the assigned corpus's label rows (encode/cache.py);
  * ``encode_pods(selector_spread=True)`` registers per-owner selector
    groups — slot 0 under kubernetes.io/hostname, slot 1 under the zone
    key (``PodFeatures.selspread_group``);
  * the shared topology cycle state (ops.topology.group_topology_state)
    then counts the owner population per domain like any other group.

Score: fewer same-owner pods in the node's domain → higher, weighted
1/3 node + 2/3 zone (upstream's zoneWeighting ratio); nodes lacking the
zone key simply contribute no zone term. Score-only — there is no
filter point, so owner groups never reach the hard-spread arbitration.
"""
from __future__ import annotations

import jax.numpy as jnp

from ..ops.topology import gather_group_rows
from ..state.events import ActionType, ClusterEvent, GVK
from .base import BatchedPlugin

# upstream zoneWeighting = 2.0/3.0: zone spreading dominates node
# spreading when zones exist
_ZONE_WEIGHT = 2.0 / 3.0
_NODE_WEIGHT = 1.0 - _ZONE_WEIGHT


class SelectorSpread(BatchedPlugin):
    name = "SelectorSpread"
    needs_topology = True

    def events_to_register(self):
        # Population changes on any pod lifecycle event; zone/hostname
        # domains change on node add / label update.
        return [ClusterEvent(GVK.POD, ActionType.ALL),
                ClusterEvent(GVK.NODE,
                             ActionType.ADD | ActionType.UPDATE_NODE_LABEL)]

    def score(self, pf, nf, ctx) -> jnp.ndarray:
        P, N = pf.valid.shape[0], nf.valid.shape[0]
        score = jnp.zeros((P, N), dtype=jnp.float32)
        for c, w in ((0, _NODE_WEIGHT), (1, _ZONE_WEIGHT)):
            g = pf.selspread_group[:, c]
            counts = gather_group_rows(g, ctx["counts_node"])
            dom_ok = gather_group_rows(
                g, ctx["dom_valid"].astype(jnp.float32)) > 0
            gsafe = jnp.clip(g, 0, ctx["max_count"].shape[0] - 1)
            spread = ctx["max_count"][gsafe][:, None] - counts
            score = score + w * jnp.where(
                (g >= 0)[:, None] & dom_ok, spread, 0.0)
        return score

    def normalize(self, scores, feasible):
        from ..ops.pipeline import max_normalize_100

        return max_normalize_100(scores, feasible)
