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
        ref.static_filter(dict(POD, affinity={"node_affinity": {}}),
                          nodes(1), PLUGINS)
    with pytest.raises(NotImplementedError):
        ref.static_filter(dict(POD, tolerations=[{"key": "x"}]), nodes(1),
                          PLUGINS)


# ---- InterPodAffinity ----------------------------------------------------

HOST = "kubernetes.io/hostname"


def term(key="zone", namespaces=(), exprs=(), **labels):
    return {"match_labels": labels, "match_expressions": list(exprs),
            "topology_key": key, "namespaces": list(namespaces)}


def pod(labels=None, ns="default", aff=None, anti=None):
    out = dict(POD, namespace=ns, labels=dict(labels or {}))
    spec = {}
    if aff:
        spec["pod_affinity"] = aff
    if anti:
        spec["pod_anti_affinity"] = anti
    if spec:
        out["affinity"] = spec
    return out


def view(*groups):
    return [(t, np.array(rows, dtype=int)) for t, rows in groups]


def test_hostname_is_one_domain_per_node():
    nd = nodes(3, zones=["a", "a", "b"])
    assert nd.domains(HOST).tolist() == [0, 1, 2]
    assert nd.domains("zone").tolist() == [0, 0, 1]
    assert nd.domains("rack").tolist() == [-1, -1, -1]


def test_required_affinity_and_the_first_pod_exception():
    nd = nodes(4, zones=["a", "a", "b", "c"])
    web = pod({"app": "web"})
    t = pod({"app": "db"}, aff={"required": [term(app="web")]})
    # a web pod on node 1: zone a (nodes 0, 1) passes, b and c do not
    got = ref.affinity_ok(t, nd, view((web, [1])))
    assert got.tolist() == [True, True, False, False]
    # no web pod anywhere, and the pod is not a web pod: nothing passes
    assert not ref.affinity_ok(t, nd, view((web, []))).any()
    # no web pod anywhere and the pod matches its own term: all pass
    self_aff = pod({"app": "web"}, aff={"required": [term(app="web")]})
    assert ref.affinity_ok(self_aff, nd, view((web, []))).all()
    # once one exists, the exception is gone
    assert ref.affinity_ok(self_aff, nd, view((web, [3]))).tolist() == [
        False, False, False, True]


def test_required_affinity_needs_the_key_on_the_node():
    nd = ref.Nodes(RES, np.tile([1000.0, 10.0 * GI, 10.0], (3, 1)),
                   [{"zone": "a"}, {}, {"zone": "a"}],
                   np.zeros(3, bool), [[]] * 3)
    self_aff = pod({"app": "web"}, aff={"required": [term(app="web")]})
    # the first-pod exception applies, but node 1 lacks the key
    assert ref.affinity_ok(self_aff, nd, []).tolist() == [True, False, True]


def test_required_anti_affinity_and_a_node_without_the_key():
    nd = ref.Nodes(RES, np.tile([1000.0, 10.0 * GI, 10.0], (4, 1)),
                   [{"zone": "a"}, {"zone": "a"}, {"zone": "b"}, {}],
                   np.zeros(4, bool), [[]] * 4)
    green = pod({"color": "green"})
    t = pod({"color": "green"}, anti={"required": [term(color="green")]})
    got = ref.anti_affinity_ok(t, nd, view((green, [0])))
    # zone a holds a green pod; zone b is free; node 3 has no zone key
    assert got.tolist() == [False, False, True, True]
    # a pod on the keyless node counts in no domain
    assert ref.anti_affinity_ok(t, nd, view((green, [3]))).all()


def test_symmetric_anti_affinity_of_a_bound_pod():
    nd = nodes(3)
    # the bound pod repels blue pods from its node; the incoming pod has
    # no terms of its own
    repeller = pod({"app": "x"}, anti={"required": [term(HOST,
                                                         color="blue")]})
    blue = pod({"color": "blue"})
    red = pod({"color": "red"})
    v = view((repeller, [1]))
    assert ref.anti_affinity_ok(blue, nd, v).tolist() == [True, False, True]
    assert ref.anti_affinity_ok(red, nd, v).all()


def test_preferred_terms_of_the_incoming_pod():
    nd = nodes(3, zones=["a", "b", "c"])
    web, cache = pod({"app": "web"}), pod({"app": "cache"})
    t = pod(aff={"preferred": [{"weight": 3, "term": term(app="web")}]},
            anti={"preferred": [{"weight": 2, "term": term(app="cache")}]})
    plus, minus = ref.affinity_parts(t, nd, view((web, [0, 0, 1]),
                                                  (cache, [1, 2])))
    # zone a: 2 web pods x 3; zone b: 1 web x 3, 1 cache x 2; c: 1 cache
    assert plus.tolist() == [6.0, 3.0, 0.0]
    assert minus.tolist() == [0.0, 2.0, 2.0]


def test_terms_of_bound_pods_that_match_the_incoming_pod():
    nd = nodes(3)
    t = pod({"app": "web"})
    hard = pod({"x": "1"}, aff={"required": [term(HOST, app="web")]})
    soft = pod({"x": "2"}, aff={"preferred": [
        {"weight": 5, "term": term(HOST, app="web")}]})
    away = pod({"x": "3"}, anti={"preferred": [
        {"weight": 4, "term": term(HOST, app="web")}]})
    other = pod({"x": "4"}, aff={"preferred": [
        {"weight": 7, "term": term(HOST, app="db")}]})
    plus, minus = ref.affinity_parts(
        t, nd, view((hard, [0]), (soft, [0, 1]), (away, [2]),
                    (other, [2])))
    # hardPodAffinityWeight 1 on node 0, 5 per soft pod, -4 on node 2;
    # the db term does not match a web pod
    assert plus.tolist() == [6.0, 5.0, 0.0]
    assert minus.tolist() == [0.0, 0.0, 4.0]


def test_two_namespaces_against_the_empty_list():
    nd = nodes(3)
    mine, theirs = pod({"app": "web"}), pod({"app": "web"}, ns="other")
    pods = view((mine, [0]), (theirs, [1]))
    own = pod(anti={"required": [term(HOST, app="web")]})
    both = pod(anti={"required": [term(HOST, ["default", "other"],
                                       app="web")]})
    only = pod(anti={"required": [term(HOST, ["other"], app="web")]})
    assert ref.anti_affinity_ok(own, nd, pods).tolist() == [False, True,
                                                            True]
    assert ref.anti_affinity_ok(both, nd, pods).tolist() == [False, False,
                                                             True]
    assert ref.anti_affinity_ok(only, nd, pods).tolist() == [True, False,
                                                             True]


@pytest.mark.parametrize("op,values,hits", [
    ("In", ["a", "b"], [True, True, False, False]),
    ("NotIn", ["a"], [False, True, True, True]),
    ("Exists", [], [True, True, True, False]),
    ("DoesNotExist", [], [False, False, False, True]),
])
def test_match_expression_operators(op, values, hits):
    labels = [{"k": "a"}, {"k": "b"}, {"k": "c"}, {}]
    x = term(exprs=[{"key": "k", "operator": op, "values": values}])
    assert [ref.selects(x, lab) for lab in labels] == hits


def test_match_labels_and_expressions_both_hold():
    x = term(exprs=[{"key": "tier", "operator": "In", "values": ["1"]}],
             app="web")
    assert ref.selects(x, {"app": "web", "tier": "1"})
    assert not ref.selects(x, {"app": "web", "tier": "2"})
    assert not ref.selects(x, {"tier": "1"})
    assert ref.selects(term(), {})   # an empty selector selects all


def test_normalisation_by_hand():
    feasible = np.array([True, True, True, False])
    # lo = min(0, -2) = -2, hi = max(0, 6) = 6; the infeasible node's 100
    # sets neither
    got = ref.normalize_affinity(np.array([6.0, -2.0, 2.0, 100.0]),
                                 feasible)
    assert got[:3].tolist() == [100.0, 0.0, 50.0]
    # every feasible node raw 3: lo = 0, hi = 3, so all score 100
    got = ref.normalize_affinity(np.array([3.0, 3.0, 3.0, 0.0]), feasible)
    assert got[:3].tolist() == [100.0, 100.0, 100.0]


def test_normalisation_where_min_equals_max_is_zero():
    got = ref.normalize_affinity(np.zeros(3), np.ones(3, bool))
    assert got.tolist() == [0.0, 0.0, 0.0]
    got = ref.normalize_affinity(np.array([0.0, 0.0, 9.0]),
                                 np.array([True, True, False]))
    assert got.tolist() == [0.0, 0.0, 0.0]


def test_preferred_affinity_ranks_the_nodes_in_best_nodes():
    nd = nodes(3)
    foo = pod({"foo": ""})
    t = pod({"foo": ""}, aff={"preferred": [
        {"weight": 1, "term": term(HOST, foo="")}]})
    u = used(np.zeros((3, 3)))
    ok, s, best = ref.best_nodes(t, nd, u, {}, WEIGHTS, PLUGINS,
                                 view((foo, [2, 2, 1])))
    assert ok.all() and best.tolist() == [2]
    # raw [0, 1, 2] -> 0, 50, 100 at weight 1 (the default)
    assert s[2] - s[1] == pytest.approx(50.0)
    assert s[1] - s[0] == pytest.approx(50.0)


def test_anti_affinity_refuses_nodes_in_best_nodes():
    nd = nodes(3)
    green = pod({"color": "green"},
                anti={"required": [term(HOST, color="green")]})
    ok, _s, best = ref.best_nodes(green, nd, used(np.zeros((3, 3))), {},
                                  WEIGHTS, PLUGINS, view((green, [0, 2])))
    assert ok.tolist() == [False, True, False]
    assert best.tolist() == [1]


@pytest.mark.parametrize("name", ["sched-perf-basic-5k",
                                  "sched-perf-spread-5k"])
def test_templates_without_terms_score_as_before(name):
    # InterPodAffinity was a constant 0 for these configurations; it
    # must still add exactly 0 on every node, over a view of every
    # standing pod, and leave the filter as it was
    import os

    from benchmark.workload import build_cluster, load_json
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    cfg = load_json(os.path.join(root, "benchmark", "configs",
                                 name + ".json"))
    cl = build_cluster(cfg, seed=11, scale=100)
    nd = ref.Nodes(cl.resources, cl.alloc, cl.node_labels,
                   np.zeros(cl.n_nodes, bool), [[]] * cl.n_nodes)
    rng = np.random.default_rng(3)
    u = rng.uniform(0.0, 0.8, cl.alloc.shape) * cl.alloc
    pods = view(*[(cl.templates[n], [r for r, m in zip(
        cl.standing_node, cl.standing_template) if m == n])
        for n in sorted(set(cl.standing_template))])
    plugins = set(cfg["profile"]["plugins"])
    w = cfg["profile"]["weights"]
    for t in cl.templates.values():
        counts = ref.spread_counts(t, nd, cl.standing_node,
                                   [{}] * len(cl.standing_node),
                                   ["default"] * len(cl.standing_node))
        with_ipa = ref.best_nodes(t, nd, u, counts, w, plugins, pods)
        without = ref.best_nodes(t, nd, u, counts, w,
                                 plugins - {"InterPodAffinity"})
        assert np.array_equal(with_ipa[0], without[0])
        assert np.array_equal(with_ipa[1], without[1])
        assert np.array_equal(with_ipa[2], without[2])
