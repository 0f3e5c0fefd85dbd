"""The check on runs whose answers are made by hand."""
import copy

import numpy as np
import pytest

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


# ---- inter-pod affinity ---------------------------------------------------

HOST = "kubernetes.io/hostname"


def _term(**labels):
    return {"match_labels": labels, "match_expressions": [],
            "topology_key": HOST, "namespaces": []}


AFF = copy.deepcopy(CFG)
AFF["pod_templates"].update({
    "g": {"name_prefix": "g-", "namespace": "default",
          "requests": {"cpu": 100, "memory": GI},
          "labels": {"color": "green"}, "topology_spread_constraints": [],
          "affinity": {"pod_anti_affinity": {
              "required": [_term(color="green")]}}},
    "foo": {"name_prefix": "foo-", "namespace": "default",
            "requests": {"cpu": 100, "memory": GI}, "labels": {"foo": ""},
            "topology_spread_constraints": []},
    "f": {"name_prefix": "f-", "namespace": "default",
          "requests": {"cpu": 100, "memory": GI}, "labels": {},
          "topology_spread_constraints": [],
          "affinity": {"pod_affinity": {"preferred": [
              {"weight": 1, "term": _term(foo="")}]}}},
})
AFF["profile"]["plugins"].append("InterPodAffinity")
AFF["profile"]["weights"]["InterPodAffinity"] = 1.0
AFF["guarantees"] = {"zone_skew": None,
                     "anti_affinity": {"text": "no two green pods on a node"}}


def affinity_run(incoming, binds, deleted=None, standing=None):
    cfg = copy.deepcopy(AFF)
    cfg["incoming"]["template"] = incoming
    if standing:
        cfg["standing"] = [cfg["standing"], standing]
    run = make_run(binds, deleted, cfg=cfg)
    run.template = incoming
    return run, cfg


def test_two_green_pods_alive_on_one_node_break_anti_affinity():
    run, cfg = affinity_run("g", {"default/g-0": (0, 101.0),
                                  "default/g-1": (0, 105.0)})
    v = judge_v(run, cfg)
    assert not v.correct
    assert rows(v)["anti_affinity"] == 2
    assert rows(v)["filter_fail"] > 0


def test_two_green_pods_of_one_batch_on_one_node_surely_fail():
    run, cfg = affinity_run("g", {"default/g-0": (1, 101.0),
                                  "default/g-1": (1, 101.0),
                                  "default/g-2": (2, 101.0)})
    v = judge_v(run, cfg)
    assert rows(v)["anti_affinity"] == 2
    assert rows(v)["filter_fail"] == 2   # both pods of the pair


def test_a_green_pod_bound_after_the_others_delete_stamp_passes():
    run, cfg = affinity_run("g", {"default/g-0": (0, 101.0),
                                  "default/g-1": (0, 105.0)},
                            {"default/g-0": 103.0})
    v = judge_v(run, cfg)
    assert v.correct, v.rows
    assert rows(v)["anti_affinity"] == 1


def test_a_placement_against_a_preferred_affinity_pull_shows_a_gap():
    # one foo pod stands on a node drawn from the seed; the incoming pod
    # prefers its node (weight 1: 100 points, less 10 for the extra pod)
    # and goes elsewhere
    run, cfg = affinity_run("f", {}, standing={
        "count": 1, "template": "foo", "distinct_nodes": True})
    foo = int(run.cluster.standing_node[-1])
    away = (foo + 1) % 4
    run, cfg = affinity_run("f", {"default/f-0": (away, 105.0)},
                            standing={"count": 1, "template": "foo",
                                      "distinct_nodes": True})
    v = judge_v(run, cfg)
    assert rows(v)["score_gap"] == pytest.approx(90.0)
    assert not v.correct
    # on the foo pod's node it is correct
    run, cfg = affinity_run("f", {"default/f-0": (foo, 105.0)},
                            standing={"count": 1, "template": "foo",
                                      "distinct_nodes": True})
    assert judge_v(run, cfg).correct


def test_a_deletion_the_engine_may_not_have_seen_is_no_affinity_fault():
    # the foo pod went 0.1 s before the bind: the engine may still have
    # seen it and followed its pull
    run, cfg = affinity_run("f", {}, standing={
        "count": 1, "template": "foo", "distinct_nodes": True})
    foo_key, foo = run.cluster.standing_keys[-1], int(
        run.cluster.standing_node[-1])
    run, cfg = affinity_run("f", {"default/f-0": (foo, 105.0)},
                            {foo_key: 104.9},
                            standing={"count": 1, "template": "foo",
                                      "distinct_nodes": True})
    assert judge_v(run, cfg).correct
    # a green pod deleted just before another lands on its node: the
    # engine saw the deletion, or it would not have placed it there
    run, cfg = affinity_run("g", {"default/g-0": (0, 101.0),
                                  "default/g-1": (0, 105.0)},
                            {"default/g-0": 104.9})
    v = judge_v(run, cfg)
    assert v.correct, v.rows


def test_configurations_without_terms_judge_as_before():
    # the same runs judged with InterPodAffinity in the profile and out of
    # it: templates without terms add exactly 0
    binds = {f"default/p-{i}": (i % 4, 101.0 + i // 4) for i in range(9)}
    with_ipa = copy.deepcopy(CFG)
    with_ipa["profile"]["plugins"].append("InterPodAffinity")
    a = judge_v(make_run(binds), CFG, control=True)
    b = judge_v(make_run(binds), with_ipa, control=True)
    assert a.rows == b.rows
