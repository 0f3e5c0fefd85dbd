"""Device time of the scheduling step's program (XLA module jit_step),
from the profiler trace, per batch committed in the window."""
from benchmark.layers import per_traced_batch


def read(run):
    if run.trace is None:
        return None
    v = per_traced_batch(run, run.trace.module_time("jit_step"))
    return None if not v else v * 1e3
