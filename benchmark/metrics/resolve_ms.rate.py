"""Self time of the engine's resolve and commit spans, without their
fetch.* children (blocking device readbacks), per batch."""
from benchmark.layers import per_traced_batch, self_seconds


def read(run):
    if not run.spans:
        return None
    v = per_traced_batch(run, self_seconds(run, ("resolve", "commit"),
                                            "fetch."))
    return None if v is None else v * 1e3
