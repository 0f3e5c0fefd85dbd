"""Pods entering batches per batch committed in the window."""
from benchmark.layers import per_batch


def read(run):
    return per_batch(run, "pods_seen")
