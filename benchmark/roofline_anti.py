"""The least work of a scheduling step that also judges InterPodAffinity's
required terms: what `benchmark.roofline.least_seconds` counts, plus, per
batch, each of the incoming pod's own required term groups and each anti
class of bound pods (the distinct topology key, namespace set and selector
of their required anti terms) matched over the bound corpus, and per
(pod, node scored) one compare for each of them.

Counted from the semantics like `least_seconds`, which it leaves alone:
matching a group over the corpus takes one compare per label slot and one
add of the per-domain segment sum per bound pod, and reads each bound
pod's labels, namespace and node once.
"""
from __future__ import annotations

from benchmark.roofline import nodes_to_score, ops_per_pair, peaks


def least_seconds_anti(batches: float, pods: float, nodes: int, axes: int,
                       constraints: int, corpus: int, label_slots: int,
                       classes: int, own_terms: int,
                       device_kind: str) -> tuple:
    """(least seconds, "flops" or "bytes") for `batches` batches holding
    `pods` pods against `corpus` bound pods with `label_slots` label
    slots, `classes` live anti classes and `own_terms` required terms
    on each incoming pod."""
    pk = peaks(device_kind)
    scored = pods * nodes_to_score(nodes)
    groups = own_terms + classes
    ops = (scored * (ops_per_pair(axes, constraints) + groups)
           + batches * groups * corpus * (label_slots + 1))
    nbytes = (batches * (2 * nodes * axes * 4 + corpus * (label_slots + 2) * 4)
              + pods * (axes * 4 + 4))
    t_ops = ops / pk["bf16_flop_per_s"]
    t_bytes = nbytes / pk["hbm_bytes_per_s"]
    return (t_ops, "flops") if t_ops >= t_bytes else (t_bytes, "bytes")
