"""The step's least time (benchmark/roofline.py: the bytes and operations
the semantics require at the cell's shapes, over the chip's peaks) as a
share of its device time in the trace."""
from benchmark.roofline import least_seconds


def read(run):
    if run.trace is None:
        return None
    device = run.trace.module_time("jit_step")
    if device <= 0:
        return None
    cl = run.cluster
    t = cl.templates[run.template]
    least, _bound = least_seconds(
        run.layer_delta("batches"), run.layer_delta("pods_seen"), cl.n_nodes,
        len(cl.resources), len(t.get("topology_spread_constraints", [])),
        run.device_kind)
    return least / device * 100.0
