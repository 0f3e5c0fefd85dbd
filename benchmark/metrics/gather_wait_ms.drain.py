"""Blocking queue-pop wait per batch (the engine's gap_gather_s_total)."""
from benchmark.layers import per_batch


def read(run):
    v = per_batch(run, "gap_gather_s_total")
    return None if v is None else v * 1e3
