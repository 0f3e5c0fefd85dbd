"""Engine scenarios across engine modes: the greedy shortlist path under
node churn, shape reuse and contention.

One 4-node cluster on a 3-plugin profile (NodeUnschedulable,
NodeResourcesFit, NodeResourcesLeastAllocated) whose pods the device
loop accepts. Each scenario runs pod bursts with an optional node event
between them, in every engine mode: synchronous, pipelined, upload
(``device_resident=False``), full-width scan (``shortlist=False``) and
the device loop. Every case asserts that every pod binds, that no node
is over its CPU, that no pod lands on a node cordoned or deleted before
its burst, and that the placements equal the synchronous engine's on
the same scenario (the synchronous run is made once per scenario).
"""
import time

import pytest

from minisched_tpu.config import SchedulerConfig
from minisched_tpu.scenario import Cluster
from minisched_tpu.service.defaultconfig import Profile
from minisched_tpu.state import objects as obj

PLUGINS = ["NodeUnschedulable", "NodeResourcesFit",
           "NodeResourcesLeastAllocated"]
NODE_CPUS = (64000, 48000, 40000, 36000)
#: Node CPUs where a scenario needs other sizes.
SMALL_NODES = {"contention": (9000, 8000, 7500, 6500)}


def _pods(n: int, tag: str, *, shapes: int = 0, cpu0: int = 100):
    """``shapes=0``: a distinct request and priority per pod, so pop and
    scan order are fixed and every pod is its own shape. ``shapes=k``:
    k request shapes at one priority, repeated within and across
    bursts."""
    pods = []
    for i in range(n):
        if shapes:
            name, pri, cpu = f"{tag}p{i}x0", 1000, cpu0 + (i % shapes) * 50
        else:
            name, pri, cpu = f"{tag}p-{i}", 1000 - i, cpu0 + 17 * i
        pods.append(obj.Pod(
            metadata=obj.ObjectMeta(name=name, namespace="default"),
            spec=obj.PodSpec(requests={"cpu": cpu}, priority=pri)))
    return pods


def _cordon(c, _nodes, gone):
    c.cordon("n1")
    gone.add("n1")


def _uncordon(c, _nodes, gone):
    c.uncordon("n1")
    gone.discard("n1")


def _delete(c, _nodes, gone):
    c.delete_node("n1")
    gone.add("n1")


def _add(c, nodes, _gone):
    c.create_node("n4", cpu=96000)
    nodes["n4"] = 96000


#: name → (bursts as (tag, n, shapes, cpu0), event after burst 0,
#: nodes cordoned from the start, nodes a later burst must use, config).
SCENARIOS = {
    "shared_shapes": ([("a", 12, 2, 100), ("b", 12, 2, 100)],
                      None, (), (), {}),
    # 10 pods of one shape on nodes that hold 2-3 of them, through a
    # 2-wide shortlist: earlier pods fill later pods' shortlisted nodes
    # mid-scan, so the scan repairs with full rows.
    "contention": ([("a", 10, 1, 2500)], None, (), (),
                   {"shortlist_k": 2}),
    "cordon": ([("a", 6, 2, 100), ("b", 6, 2, 100)], _cordon, (), (), {}),
    "uncordon": ([("a", 6, 2, 100), ("b", 6, 2, 100)], _uncordon,
                 ("n1",), ("n1",), {}),
    "delete": ([("a", 6, 2, 100), ("b", 6, 2, 100)], _delete, (), (), {}),
    "add": ([("a", 6, 2, 100), ("b", 6, 2, 100)], _add, (), ("n4",), {}),
    "many_shapes": ([("a", 72, 0, 100)], None, (), (),
                    {"max_batch_size": 24}),
}

MODES = {
    "sync": {"pipeline": False},
    "pipelined": {"pipeline": True},
    "upload": {"device_resident": False},
    "shortlist_off": {"shortlist": False},
    "device_loop": {"device_loop": True, "loop_depth": 4},
    # a 1-wide shortlist, narrower than every batch
    "narrow_shortlist": {"shortlist_k": 1},
}

CASES = [(s, m) for s in SCENARIOS for m in MODES if m != "narrow_shortlist"]
CASES.append(("contention", "narrow_shortlist"))

_SYNC: dict = {}


def _config(**kw):
    kw.setdefault("max_batch_size", 6)
    kw.setdefault("batch_window_s", 0.3)
    kw.setdefault("backoff_initial_s", 0.05)
    kw.setdefault("backoff_max_s", 0.2)
    return SchedulerConfig(**kw)


def _run(scenario: str, mode: str, timeout: float = 60.0):
    """One engine run of ``scenario`` in ``mode`` → (placements, final
    metrics), with the scenario's invariants asserted."""
    bursts, event, cordoned, must_use, cfg = SCENARIOS[scenario]
    nodes = {f"n{i}": cpu for i, cpu in
             enumerate(SMALL_NODES.get(scenario, NODE_CPUS))}
    gone = set(cordoned)
    c = Cluster()
    try:
        c.start(profile=Profile(name="scenario", plugins=list(PLUGINS)),
                config=_config(**{**cfg, **MODES[mode]}),
                with_pv_controller=False)
        for name, cpu in nodes.items():
            c.create_node(name, cpu=cpu, unschedulable=name in gone)
        placements = {}
        want = 0
        for bi, (tag, n, shapes, cpu0) in enumerate(bursts):
            if bi == 1 and event is not None:
                event(c, nodes, gone)
                time.sleep(0.3)  # let the informer land the node event
            c.create_objects(_pods(n, tag, shapes=shapes, cpu0=cpu0))
            want += n
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                placements = {p.metadata.name: p.spec.node_name
                              for p in c.list_pods() if p.spec.node_name}
                if len(placements) == want:
                    break
                time.sleep(0.02)
            assert len(placements) == want, (scenario, mode, bi,
                                             len(placements), want)
            landed = {v for k, v in placements.items()
                      if k.startswith(tag)}
            assert not landed & gone, (scenario, mode, tag, landed, gone)
            if bi > 0:
                assert set(must_use) <= landed, (scenario, mode, landed)
        used = {}
        for p in c.list_pods():
            used[p.spec.node_name] = (used.get(p.spec.node_name, 0)
                                      + p.spec.requests["cpu"])
        for name, cpu in used.items():
            assert cpu <= nodes[name], (scenario, mode, name, cpu)
        return placements, c.service.scheduler.metrics()
    finally:
        c.shutdown()


@pytest.mark.parametrize("scenario,mode", CASES)
def test_scenario_places_like_the_sync_engine(scenario, mode):
    if scenario not in _SYNC:
        _SYNC[scenario] = _run(scenario, "sync")
    placed, m = _SYNC[scenario] if mode == "sync" else _run(scenario, mode)
    assert placed == _SYNC[scenario][0], (scenario, mode)
    if scenario == "contention" and mode != "shortlist_off":
        assert m["shortlist_repairs"] >= 1, m   # the contention is real
