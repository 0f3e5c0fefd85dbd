"""Templates, standing groups and churn as the harness builds them."""
import copy

import numpy as np
import pytest

from benchmark import workload as wl
from benchmark.traffic import Driver

GI = 1 << 30
HOST = "kubernetes.io/hostname"
GREEN = {
    "name_prefix": "green-", "namespace": "sched-0",
    "requests": {"cpu": 100, "memory": GI}, "labels": {"color": "green"},
    "affinity": {
        "pod_anti_affinity": {"required": [
            {"match_labels": {"color": "green"}, "match_expressions": [],
             "topology_key": HOST, "namespaces": ["sched-1", "sched-0"]}]},
        "pod_affinity": {"preferred": [
            {"weight": 7, "term": {
                "match_labels": {},
                "match_expressions": [{"key": "foo", "operator": "Exists",
                                       "values": []}],
                "topology_key": "zone", "namespaces": []}}]}},
}
PLAIN = {"name_prefix": "p-", "namespace": "default",
         "requests": {"cpu": 100, "memory": GI}, "labels": {}}
CFG = {
    "nodes": {"count": 10, "name_prefix": "n", "template": "node",
              "zones": None},
    "node_templates": {"node": {"allocatable": {"cpu": 1000,
                                                "memory": 10 * GI,
                                                "pods": 10},
                                "labels": {}, "taints": [],
                                "unschedulable": False}},
    "pod_templates": {"p": PLAIN, "g": GREEN},
    "standing": [{"per_node": 2, "template": "p"},
                 {"count": 4, "template": "g", "distinct_nodes": True}],
}


def test_affinity_terms_reach_the_program_intact():
    pod = wl.to_program_pod(GREEN, "green-0")
    anti = pod.spec.affinity.pod_anti_affinity
    assert len(anti.required) == 1 and not anti.preferred
    t = anti.required[0]
    assert t.topology_key == HOST
    assert t.namespaces == ["sched-1", "sched-0"]
    assert t.label_selector.match_labels == {"color": "green"}
    pref = pod.spec.affinity.pod_affinity.preferred
    assert [w.weight for w in pref] == [7]
    sel = pref[0].term.label_selector
    assert [(r.key, r.operator) for r in sel.match_expressions] == [
        ("foo", "Exists")]
    assert pref[0].term.namespaces == []
    assert pod.spec.affinity.node_affinity is None
    assert sel.matches({"foo": ""}) and not sel.matches({"bar": ""})
    # no affinity key: no Affinity object, as before
    assert wl.to_program_pod(PLAIN, "p-0").spec.affinity is None


@pytest.mark.parametrize("path,value", [
    (("node_selector",), {"disk": "ssd"}),
    (("tolerations",), [{"key": "x", "operator": "Exists"}]),
    (("priority",), 100),
    (("affinity", "node_affinity"), {"required": []}),
    (("affinity", "pod_affinity", "required"),
     [{"match_labels": {}, "topology_key": HOST, "label_selector": {}}]),
    (("affinity", "pod_anti_affinity", "required", 0, "match_expressions"),
     [{"key": "k", "operator": "Gt", "values": ["1"]}]),
    (("topology_spread_constraints",),
     [{"max_skew": 1, "topology_key": "zone",
       "when_unsatisfiable": "DoNotSchedule", "match_labels": {},
       "min_domains": 2}]),
])
def test_a_key_the_harness_cannot_map_raises(path, value):
    t = copy.deepcopy(GREEN)
    at = t
    for k in path[:-1]:
        at = at[k]
    at[path[-1]] = value
    with pytest.raises(ValueError):
        wl.to_program_pod(t, "x-0")


def test_count_groups_go_on_distinct_nodes_drawn_from_the_seed():
    a = wl.build_cluster(CFG, seed=4)
    b = wl.build_cluster(CFG, seed=5)
    green = [r for r, t in zip(a.standing_node, a.standing_template)
             if t == "g"]
    assert len(green) == 4 and len(set(green)) == 4
    assert a.standing_template == ["p"] * 20 + ["g"] * 4
    assert len(set(a.standing_keys)) == 24
    assert a.standing_keys[-4:] == [f"sched-0/standing-{i}"
                                    for i in range(20, 24)]
    assert np.bincount(a.standing_node[:20]).tolist() == [2] * 10
    green_b = [r for r, t in zip(b.standing_node, b.standing_template)
               if t == "g"]
    assert green != green_b   # the seed draws the nodes
    again = wl.build_cluster(CFG, seed=4)
    assert again.standing_node.tolist() == a.standing_node.tolist()
    pods = wl.to_program_standing(a)
    assert [p.spec.affinity is not None for p in pods] == [False] * 20 + [
        True] * 4
    assert pods[-1].spec.node_name == a.node_names[a.standing_node[-1]]


def test_the_single_group_form_draws_what_a_one_group_list_draws():
    one = dict(CFG, standing={"per_node": 2, "template": "p"})
    listed = dict(CFG, standing=[{"per_node": 2, "template": "p"}])
    a, b = wl.build_cluster(one, seed=9), wl.build_cluster(listed, seed=9)
    assert a.standing_keys == b.standing_keys
    assert a.standing_node.tolist() == b.standing_node.tolist()


@pytest.mark.parametrize("group", [
    {"count": 4, "template": "g"},
    {"count": 4, "template": "g", "distinct_nodes": False},
    {"count": 11, "template": "g", "distinct_nodes": True},
    {"per_node": 1, "template": "g", "spread": "zone"},
])
def test_a_standing_group_the_harness_does_not_know_raises(group):
    with pytest.raises(ValueError):
        wl.build_cluster(dict(CFG, standing=[group]), seed=1)


def _driver(churn):
    from minisched_tpu.state.store import ClusterStore

    c = wl.build_cluster(CFG, seed=2)
    store = ClusterStore()
    store.create_many(wl.to_program_nodes(c) + wl.to_program_standing(c))
    drv = Driver(store, PLAIN, {"churn": churn},
                 {k: c.node_names[r] for k, r in
                  zip(c.standing_keys, c.standing_node)},
                 {k: c.templates[t] for k, t in
                  zip(c.standing_keys, c.standing_template)})
    return c, store, drv


def _bind(store, drv, keys, node):
    store.bind_pods([(k, node) for k in keys])
    return drv._observe(drv._watch.next_events(1000, timeout=1.0))


@pytest.mark.parametrize("churn", ["delete_oldest_per_bind",
                                   "delete_oldest_incoming_per_bind"])
def test_churn_deletes_the_oldest_bound_pod_of_its_kind(churn):
    c, store, drv = _driver(churn)
    try:
        first = drv._create(3, None)
        assert _bind(store, drv, first, "n0") == 3
        second = drv._create(2, None)
        assert _bind(store, drv, second, "n1") == 2
        drv._churn(2)
        if churn == "delete_oldest_per_bind":
            assert sorted(drv.deleted) == sorted(c.standing_keys[:2])
        else:
            # the standing population stays; the incoming pods bound
            # first go first
            assert sorted(drv.deleted) == sorted(first[:2])
        drv._churn(10)
        left = {p.key for p in store.list("Pod")}
        if churn == "delete_oldest_incoming_per_bind":
            assert left == set(c.standing_keys)
    finally:
        drv._watch.stop()


def test_a_put_back_pod_takes_the_template_of_the_pod_it_replaces():
    c, store, drv = _driver("delete_oldest_incoming_per_bind")
    try:
        # the green standing pods are the newest: take everything
        gone = drv._delete_distinct(10)
        gone += drv._delete_distinct(10)
        gone += drv._delete_distinct(10)
        drv._put_back(gone)
        green = [k for k in drv.put_back if drv.template_of[k] is GREEN]
        assert len(green) == 4
        for k in green:
            pod = store.get("Pod", k)
            assert pod.metadata.namespace == "sched-0"
            assert pod.spec.affinity.pod_anti_affinity.required
    finally:
        drv._watch.stop()


def test_an_unknown_churn_raises():
    with pytest.raises(ValueError):
        _driver("delete_newest_per_bind")
