"""Encode (prepare start to dispatch) per batch (encode_s_total)."""
from benchmark.layers import per_batch


def read(run):
    v = per_batch(run, "encode_s_total")
    return None if v is None else v * 1e3
