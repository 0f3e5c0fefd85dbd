"""The step's least time with InterPodAffinity's required work
(benchmark/roofline_anti.py least_seconds_anti: the resource and selection
work of least_seconds plus the term and anti-class groups over the
corpus and their per-node compares, at the cell's shapes) as a share of
its device time in the trace. None where the engine reports no live
anti classes."""
from benchmark.roofline_anti import least_seconds_anti


def read(run):
    if run.trace is None:
        return None
    end = run.traced1 or run.engine1
    if "anti_classes" not in end:
        return None
    device = run.trace.module_time("jit_step")
    if device <= 0:
        return None
    cl = run.cluster
    t = cl.templates[run.template]
    own = len(((t.get("affinity") or {}).get("pod_anti_affinity") or {})
              .get("required", []))
    label_slots = max(len(x.get("labels", {})) for x in cl.templates.values())
    least, _bound = least_seconds_anti(
        run.layer_delta("batches"), run.layer_delta("pods_seen"), cl.n_nodes,
        len(cl.resources), len(t.get("topology_spread_constraints", [])),
        len(cl.standing_keys), label_slots, int(end["anti_classes"]), own,
        run.device_kind)
    return least / device * 100.0
