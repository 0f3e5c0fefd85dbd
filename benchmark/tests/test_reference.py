"""The reference cycle against placements worked out by hand."""
import numpy as np
import pytest

from benchmark import reference as ref

RES = ["cpu", "memory", "pods"]
GI = 1 << 30
POD = {"namespace": "default", "requests": {"cpu": 100, "memory": GI},
       "labels": {}}
PLUGINS = {"NodeUnschedulable", "NodeResourcesFit",
           "NodeResourcesBalancedAllocation", "PodTopologySpread",
           "TaintToleration", "InterPodAffinity", "NodeAffinity",
           "ImageLocality"}
WEIGHTS = {"NodeResourcesFit": 1.0, "NodeResourcesBalancedAllocation": 1.0,
           "PodTopologySpread": 2.0, "TaintToleration": 1.0}


def nodes(n, zones=None, unschedulable=()):
    labels = [({"zone": zones[i]} if zones else {}) for i in range(n)]
    return ref.Nodes(RES, np.tile([1000.0, 10.0 * GI, 10.0], (n, 1)),
                     labels, np.array([i in unschedulable
                                       for i in range(n)]), [[]] * n)


def used(rows):
    return np.array(rows, dtype=float)


def test_least_and_balanced_by_hand():
    nd = nodes(2)
    u = used([[400, 4 * GI, 4], [0, 8 * GI, 1]])
    s = ref.scores(POD, nd, u, {}, np.ones(2, bool), WEIGHTS, PLUGINS)
    # node 0: util cpu .5, mem .5 -> least 50, balanced 100, taint 100
    # node 1: util cpu .1, mem .9 -> least 50, balanced 100 - 40 = 60
    assert s[0] == pytest.approx(50 + 100 + 100)
    assert s[1] == pytest.approx(50 + 60 + 100)


def test_best_is_least_loaded_and_fit_refuses_a_full_node():
    nd = nodes(3)
    u = used([[300, 3 * GI, 3], [100, GI, 1], [950, GI, 1]])
    ok, s, best = ref.best_nodes(POD, nd, u, {}, WEIGHTS, PLUGINS)
    assert ok.tolist() == [True, True, False]  # node 2: 950 + 100 > 1000
    assert best.tolist() == [1]


def test_ties_return_the_whole_top_set_and_unschedulable_is_refused():
    nd = nodes(3, unschedulable=(2,))
    ok, _s, best = ref.best_nodes(POD, nd, used(np.zeros((3, 3))), {},
                                  WEIGHTS, PLUGINS)
    assert ok.tolist() == [True, True, False]
    assert best.tolist() == [0, 1]


def spread_pod(max_skew):
    return dict(POD, labels={"color": "blue"}, topology_spread_constraints=[
        {"max_skew": max_skew, "topology_key": "zone",
         "when_unsatisfiable": "DoNotSchedule",
         "match_labels": {"color": "blue"}}])


def test_spread_filter_and_score_by_hand():
    nd = nodes(3, zones=["a", "b", "c"])
    t = spread_pod(1)
    counts = {0: np.array([2.0, 0.0, 1.0])}
    ok, s, best = ref.best_nodes(t, nd, used(np.zeros((3, 3))), counts,
                                 WEIGHTS, PLUGINS)
    # skew after placing: a 3-0=3 > 1, b 1-0=1, c 2-0=2 > 1
    assert ok.tolist() == [False, True, False]
    assert best.tolist() == [1]
    # score: max - count = [0, 2, 1], over the feasible max 2 -> [0,100,50]
    assert s[1] - s[2] == pytest.approx(2.0 * 50)


def test_spread_counts_match_selector_and_namespace():
    nd = nodes(4, zones=["a", "a", "b", "c"])
    t = spread_pod(5)
    c = ref.spread_counts(t, nd, np.array([0, 1, 2, 2, 3]),
                          [{"color": "blue"}, {"color": "blue"},
                           {"color": "red"}, {"color": "blue"}, {}],
                          ["default", "other", "default", "default",
                           "default"])
    assert c[0].tolist() == [1.0, 1.0, 0.0]


def test_a_pod_the_reference_cannot_judge_is_refused():
    with pytest.raises(NotImplementedError):
        ref.static_filter(dict(POD, affinity={"x": 1}), nodes(1), PLUGINS)
