"""The check on runs whose answers are made by hand."""
import copy

import numpy as np

from benchmark import check
from benchmark.session import Run
from benchmark.workload import build_cluster

GI = 1 << 30
CFG = {
    "nodes": {"count": 4, "name_prefix": "n", "template": "node",
              "zones": None},
    "node_templates": {"node": {"allocatable": {"cpu": 1000,
                                                "memory": 10 * GI,
                                                "pods": 10},
                                "labels": {}, "taints": [],
                                "unschedulable": False}},
    "pod_templates": {"p": {"name_prefix": "p-", "namespace": "default",
                            "requests": {"cpu": 100, "memory": GI},
                            "labels": {}, "topology_spread_constraints": []}},
    "standing": {"per_node": 2, "template": "p"},
    "incoming": {"template": "p"},
    "profile": {"plugins": ["NodeUnschedulable", "NodeResourcesFit",
                            "NodeResourcesBalancedAllocation",
                            "PodTopologySpread", "TaintToleration"],
                "weights": {"NodeResourcesFit": 1.0}},
    "check": {"score_gap": 1.0, "bind_s": 0.5, "delete_s": 3.0},
    "guarantees": {"zone_skew": None},
}


def make_run(binds, deleted=None, cfg=CFG):
    """A run whose traffic created the pods in `binds` (key -> node row or
    None for never bound) at t=90, window [100, 110)."""
    run = Run()
    run.cluster = build_cluster(cfg, seed=1)
    run.template = "p"
    run.t0, run.t1, run.seconds, run.deadline = 100.0, 110.0, 10.0, 170.0
    names = run.cluster.node_names
    for k, (row, stamp) in binds.items():
        run.created[k] = (90.0, 90.0)
        if row is not None:
            run.binds[k] = (names[row], stamp)
    run.deleted = dict(deleted or {})
    gone = set(run.deleted)
    for k, r in zip(run.cluster.standing_keys, run.cluster.standing_node):
        if k not in gone:
            run.store_pods.append((k, names[r], {"cpu": 100, "memory": GI}))
    for k, (n, _s) in run.binds.items():
        if k not in gone:
            run.store_pods.append((k, n, {"cpu": 100, "memory": GI}))
    return run


def judge_v(run, cfg, control=False):
    v, ctl = check.judge(run, cfg)
    if control:
        v.rows += [("control_" + r[0],) + r[1:] for r in ctl.rows]
    return v


def rows(verdict):
    return {name: value for name, value, _limit, _ok in verdict.rows}


def test_best_placements_are_correct():
    # every node holds 2 standing pods; one pod lands on each node
    v = judge_v(make_run({f"default/p-{i}": (i, 101.0)
                              for i in range(4)}), CFG)
    assert v.correct, v.rows
    assert rows(v)["score_gap"] == 0.0


def test_a_worse_node_shows_as_a_score_gap():
    # two batches: the second puts its pod on the node the first filled
    # while three emptier nodes had room
    v = judge_v(make_run({"default/p-0": (0, 101.0),
                              "default/p-1": (0, 105.0)}), CFG)
    assert not v.correct
    assert rows(v)["score_gap"] > 1.0


def test_a_deletion_the_engine_may_not_have_seen_is_no_fault():
    # a standing pod of node 1 deleted just before the bind: node 1 may
    # have looked emptier, but the engine need not have seen it
    run = make_run({"default/p-0": (0, 101.0)})
    on_1 = [k for k, r in zip(run.cluster.standing_keys,
                              run.cluster.standing_node) if r == 1]
    run = make_run({"default/p-0": (0, 101.0)}, {on_1[0]: 100.9})
    assert judge_v(run, CFG).correct


def test_unbound_and_over_capacity_are_counted():
    v = judge_v(make_run({"default/p-0": (0, 101.0),
                              "default/p-1": (None, 0.0)}), CFG)
    assert rows(v)["unbound"] == 1
    tight = copy.deepcopy(CFG)
    tight["node_templates"]["node"]["allocatable"]["pods"] = 2
    v = judge_v(make_run({"default/p-0": (0, 101.0)}, cfg=tight), tight)
    assert rows(v)["over_capacity"] == 1
    assert rows(v)["filter_fail"] == 1


def test_the_control_reads_a_gap():
    # sound placements: node 3 is the emptiest after the first batch, so
    # the whole second batch (static scores) piles onto it; the control's
    # random nodes mostly are not node 3
    binds = {f"default/p-{i}": (i, 101.0) for i in range(3)}
    binds.update({f"default/p-{i}": (3, 102.0) for i in range(3, 7)})
    v = judge_v(make_run(binds), CFG, control=True)
    assert rows(v)["score_gap"] == 0.0
    assert rows(v)["control_score_gap"] > 1.0
    assert np.isfinite(rows(v)["control_score_gap"])
