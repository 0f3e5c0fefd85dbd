"""NodeUnschedulable filter.

Batched counterpart of the upstream plugin the reference instantiates at
minisched/initialize.go:198: rejects nodes with spec.unschedulable unless
the pod tolerates the node.kubernetes.io/unschedulable:NoSchedule taint.
One boolean mask column in the batched filter matrix (SURVEY §2 row
"NodeUnschedulable filter").
"""
from __future__ import annotations

import jax.numpy as jnp

from ..encode import features as F
from ..state.events import ActionType, ClusterEvent, GVK
from .base import BatchedPlugin

_UNSCHED_KEY = "node.kubernetes.io/unschedulable"
_UNSCHED_KEY_HASH = F.key_hash(_UNSCHED_KEY)
# The implicit taint's value is "" — an Equal toleration must match it
# (upstream v1.Toleration.ToleratesTaint; same semantics as
# objects.Toleration.tolerates).
_UNSCHED_PAIR_HASH = F.pair_hash(_UNSCHED_KEY, "")


class NodeUnschedulable(BatchedPlugin):
    name = "NodeUnschedulable"

    def events_to_register(self):
        # Upstream registers {Node, Add | UpdateNodeTaint}.
        return [ClusterEvent(GVK.NODE, ActionType.ADD | ActionType.UPDATE_NODE_TAINT)]

    def filter(self, pf, nf, ctx) -> jnp.ndarray:
        # Pod tolerates the implicit unschedulable:NoSchedule taint iff a
        # toleration matches its key (or empty-key Exists), its empty value
        # (for Equal), and the NoSchedule effect.
        exists_ok = (pf.tol_ops == F.TOL_EXISTS) & (
            (pf.tol_keys == 0) | (pf.tol_keys == _UNSCHED_KEY_HASH))
        equal_ok = (pf.tol_ops == F.TOL_EQUAL) & (
            pf.tol_pairs == _UNSCHED_PAIR_HASH)
        effect_ok = (pf.tol_effects == F.EFFECT_NONE) | (
            pf.tol_effects == F.EFFECT_NO_SCHEDULE)
        tolerates = ((exists_ok | equal_ok) & effect_ok).any(axis=1)  # (P,)
        return ~nf.unschedulable[None, :] | tolerates[:, None]
