"""NodePorts filter: requested host ports must be free on the node
(upstream nodeports, wrapped by the reference's registry)."""
from __future__ import annotations

import jax.numpy as jnp

from ..state.events import ActionType, ClusterEvent, GVK
from .base import BatchedPlugin


class NodePorts(BatchedPlugin):
    name = "NodePorts"

    def events_to_register(self):
        return [ClusterEvent(GVK.POD, ActionType.DELETE),
                ClusterEvent(GVK.NODE, ActionType.ADD)]

    def filter(self, pf, nf, ctx) -> jnp.ndarray:
        # conflict iff any requested port equals any in-use port (0 = empty)
        want = pf.ports[:, :, None, None]          # (P,PP,1,1)
        used = nf.used_ports[None, None, :, :]     # (1,1,N,PORT)
        conflict = ((want != 0) & (want == used)).any(axis=(1, 3))
        return ~conflict
