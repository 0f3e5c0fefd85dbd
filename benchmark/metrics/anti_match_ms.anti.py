"""Self time of the engine's encode.anti spans (matching a pod
signature against the running pods' anti classes) per batch. None where
the program records no such span."""
from benchmark.layers import per_traced_batch, self_seconds

SPAN = "encode.anti"


def read(run):
    if not any(e["name"] == SPAN for e in run.spans):
        return None
    v = per_traced_batch(run, self_seconds(run, (SPAN,), "fetch."))
    return None if v is None else v * 1e3
