"""ImageLocality score: prefer nodes that already hold the pod's images
(upstream imagelocality, wrapped by the reference's registry)."""
from __future__ import annotations

import jax.numpy as jnp

from .base import BatchedPlugin


class ImageLocality(BatchedPlugin):
    name = "ImageLocality"

    def score(self, pf, nf, ctx) -> jnp.ndarray:
        want = pf.images[:, :, None, None]       # (P,I,1,1)
        have = nf.images[None, None, :, :]       # (1,1,N,I)
        present = ((want != 0) & (want == have)).any(axis=3)  # (P,I,N)
        n_images = jnp.maximum((pf.images != 0).sum(axis=1), 1)  # (P,)
        return 100.0 * present.sum(axis=1) / n_images[:, None]
