"""PodTopologySpread: DoNotSchedule filter + ScheduleAnyway score.

Batched counterpart of the upstream podtopologyspread plugin (wrapped by
the reference's registry; BASELINE config 4 names it for the 50k-node
masked-psum configuration). Consumes the shared topology cycle state
(ops.topology.group_topology_state): for constraint slot c with selector
group g,

  filter:  placing the pod must keep skew within max_skew —
           count(node's domain) + 1 - min(count over existing domains)
           ≤ max_skew; nodes missing the topology key are filtered
           (upstream semantics).
  score:   domains with fewer matching pods score higher
           (max_count - count, normalized 0..100).

Counts see pods bound *before* this batch; same-batch placements don't
update them (documented batching semantics — capacity stays exact via the
greedy scan, spread counts lag one batch).
"""
from __future__ import annotations

import jax.numpy as jnp

from ..encode import features as F
from ..ops.topology import gather_group_rows
from ..state.events import ActionType, ClusterEvent, GVK
from .base import BatchedPlugin


class PodTopologySpread(BatchedPlugin):
    name = "PodTopologySpread"
    default_weight = 2.0  # upstream default
    needs_topology = True

    def events_to_register(self):
        return [ClusterEvent(GVK.POD, ActionType.ALL),
                ClusterEvent(GVK.NODE, ActionType.ADD | ActionType.UPDATE_NODE_LABEL)]

    def filter(self, pf, nf, ctx) -> jnp.ndarray:
        C = pf.spread_group.shape[1]
        P, N = pf.valid.shape[0], nf.valid.shape[0]
        scan_g = ctx.get("spread_scan_groups")
        ok = jnp.ones((P, N), dtype=bool)
        for c in range(C):  # static small loop; (P,N) transient per slot
            g = pf.spread_group[:, c]
            active = (g >= 0) & (pf.spread_mode[:, c] == F.SPREAD_DO_NOT_SCHEDULE)
            counts = gather_group_rows(g, ctx["counts_node"])
            dom_ok = gather_group_rows(g, ctx["dom_valid"].astype(jnp.float32)) > 0
            gsafe = jnp.clip(g, 0, ctx["min_count"].shape[0] - 1)
            skew_after = counts + 1.0 - ctx["min_count"][gsafe][:, None]
            within = skew_after <= pf.spread_max_skew[:, c][:, None]
            if scan_g is not None:
                # Slots the greedy scan enforces with RUNNING counts
                # (ops/spreadcap.py) skip the frozen pre-batch check —
                # the running-count verdict can legally admit nodes this
                # static one would reject. Missing-key rejection (dom_ok)
                # stays static either way.
                within = within | scan_g[gsafe][:, None]
            ok = ok & jnp.where(active[:, None], dom_ok & within, True)
        return ok

    def score(self, pf, nf, ctx) -> jnp.ndarray:
        C = pf.spread_group.shape[1]
        P, N = pf.valid.shape[0], nf.valid.shape[0]
        score = jnp.zeros((P, N), dtype=jnp.float32)
        for c in range(C):
            g = pf.spread_group[:, c]
            active = g >= 0  # upstream scores every constraint
            counts = gather_group_rows(g, ctx["counts_node"])
            dom_ok = gather_group_rows(g, ctx["dom_valid"].astype(jnp.float32)) > 0
            gsafe = jnp.clip(g, 0, ctx["max_count"].shape[0] - 1)
            spread = ctx["max_count"][gsafe][:, None] - counts
            # nodes missing the topology key score 0 (upstream), not max
            score = score + jnp.where(active[:, None] & dom_ok, spread, 0.0)
        return score

    def normalize(self, scores, feasible):
        from ..ops.pipeline import max_normalize_100

        return max_normalize_100(scores, feasible)
