"""Self time of the engine's resolve.assume spans (bulk assume
accounting) per batch. None where the program records no such span."""
from benchmark.layers import per_traced_batch, self_seconds

SPAN = "resolve.assume"


def read(run):
    if not any(e["name"] == SPAN for e in run.spans):
        return None
    v = per_traced_batch(run, self_seconds(run, (SPAN,), "fetch."))
    return None if v is None else v * 1e3
