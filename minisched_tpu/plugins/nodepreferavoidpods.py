"""NodePreferAvoidPods score plugin.

Batched counterpart of upstream's NodePreferAvoidPods (in the k8s-1.22
in-tree registry the reference's simulator layer wraps,
scheduler/plugin/plugins.go:24-70): nodes carrying the
``scheduler.alpha.kubernetes.io/preferAvoidPods`` annotation score 0 for
workload pods, everything else scores the max. Upstream gives it weight
10000 so it dominates other scorers — effectively a soft filter; the
default_weight here mirrors that. Avoidance is scoped exactly as
upstream scopes it: only pods CONTROLLED by a ReplicationController or
ReplicaSet (metadata.ownerReferences with controller=true; encoded as
pf.rc_owned) are steered away — bare pods score every node equally, the
upstream behavior for pods with no matching controllerRef.
"""
from __future__ import annotations

import jax.numpy as jnp

from ..state.events import ActionType, ClusterEvent, GVK
from .base import BatchedPlugin


class NodePreferAvoidPods(BatchedPlugin):
    name = "NodePreferAvoidPods"
    default_weight = 10000.0

    def events_to_register(self):
        return [ClusterEvent(GVK.NODE, ActionType.ADD | ActionType.UPDATE)]

    def score(self, pf, nf, ctx) -> jnp.ndarray:
        # (P,N): 100 everywhere except (RC/RS-owned pod, annotated node)
        # cells, which score 0 (upstream scores {0, MaxNodeScore} and
        # only for pods with a RC/RS controllerRef).
        avoid = nf.avoid_pods[None, :] & pf.rc_owned[:, None]
        return jnp.where(avoid, 0.0, 100.0).astype(jnp.float32)
