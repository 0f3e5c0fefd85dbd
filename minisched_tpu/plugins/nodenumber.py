"""NodeNumber score + permit plugin.

Batched counterpart of the reference's demo custom plugin (reference
minisched/plugins/score/nodenumber/nodenumber.go):

  * PreScore parses the pod name's trailing digit (nodenumber.go:50-64) —
    here that's done once in feature encoding (pf.name_suffix).
  * Score returns 10 iff the node name's trailing digit equals the pod's
    (nodenumber.go:73-95) — a dense equality over the suffix vectors, the
    "trivially vectorizable suffix-match" SURVEY §2 calls out.
  * Permit delays binding by {node digit} seconds with a 10s timeout
    (nodenumber.go:102-119) — host-side async, handled by the waiting-pod
    machinery.
  * Registers interest in {Node, Add} events (nodenumber.go:66-70).
"""
from __future__ import annotations

import jax.numpy as jnp

from ..encode.features import name_suffix_digit
from ..state.events import ActionType, ClusterEvent, GVK
from .base import BatchedPlugin


class NodeNumber(BatchedPlugin):
    name = "NodeNumber"

    def __init__(self, permit_delay: bool = True, timeout_s: float = 10.0):
        self._permit_delay = permit_delay
        self._timeout = timeout_s

    def events_to_register(self):
        return [ClusterEvent(GVK.NODE, ActionType.ADD)]

    def score(self, pf, nf, ctx) -> jnp.ndarray:
        match = (pf.name_suffix[:, None] == nf.name_suffix[None, :]) & (
            pf.name_suffix[:, None] >= 0)
        return jnp.where(match, 10.0, 0.0)

    def permit(self, pod, node_name: str):
        if not self._permit_delay:
            return ("allow", 0.0, 0.0)
        digit = name_suffix_digit(node_name)
        delay = float(digit) if digit > 0 else 0.0
        if delay == 0.0:
            return ("allow", 0.0, 0.0)
        # Park the pod; auto-Allow fires after `delay`, auto-Reject at the
        # 10s timeout (reference nodenumber.go:112-118).
        return ("wait", delay, self._timeout)
