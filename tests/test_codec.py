"""Compiled JSON codec (state/codec.py) parity with the reflective
reference implementations it replaces: dataclasses.asdict for encode,
objects._build_typed for decode. The wire layer and snapshot/restore are
exactly as correct as this equivalence."""
import dataclasses

import pytest

from minisched_tpu.state import codec
from minisched_tpu.state import objects as obj
from minisched_tpu.state.objects import _build_typed


def _rich_pod() -> obj.Pod:
    return obj.Pod(
        metadata=obj.ObjectMeta(
            name="p1", namespace="ns", labels={"app": "web", "tier": "fe"},
            annotations={"k": "v"},
            owner_references=[obj.OwnerReference(kind="ReplicaSet",
                                                 name="rs1",
                                                 controller=True),
                              obj.OwnerReference(kind="Job", name="j1")]),
        spec=obj.PodSpec(
            requests={"cpu": 500.0, "memory": float(2 << 30)},
            node_selector={"zone": "z1"},
            tolerations=[obj.Toleration(key="dedicated", operator="Equal",
                                        value="gpu", effect="NoSchedule")],
            affinity=obj.Affinity(
                node_affinity=obj.NodeAffinity(
                    required=obj.NodeSelector(node_selector_terms=[
                        obj.NodeSelectorTerm(match_expressions=[
                            obj.NodeSelectorRequirement(
                                key="zone", operator="In",
                                values=["z1", "z2"])])]),
                    preferred=[obj.PreferredSchedulingTerm(
                        weight=5, preference=obj.NodeSelectorTerm())]),
                pod_affinity=obj.PodAffinity(required=[
                    obj.PodAffinityTerm(
                        topology_key="zone",
                        label_selector=obj.LabelSelector(
                            match_labels={"app": "web"}))]),
                pod_anti_affinity=obj.PodAntiAffinity(
                    preferred=[obj.WeightedPodAffinityTerm(
                        weight=3, term=obj.PodAffinityTerm())])),
            topology_spread_constraints=[obj.TopologySpreadConstraint(
                max_skew=1, topology_key="zone",
                when_unsatisfiable="DoNotSchedule",
                label_selector=obj.LabelSelector(
                    match_labels={"app": "web"}))],
            ports=[obj.ContainerPort(host_port=8080, container_port=80)],
            volumes=[obj.VolumeClaim(claim_name="c1"),
                     obj.VolumeClaim(claim_name="c2",
                                     volume_type="aws-ebs")],
            scheduler_name="custom", priority=7, pod_group="g1",
            pod_group_min=3),
        status=obj.PodStatus(phase="Pending",
                             unschedulable_plugins=["NodeResourcesFit"]))


def _objects():
    yield _rich_pod()
    yield obj.Node(
        metadata=obj.ObjectMeta(name="n1", labels={"zone": "z1"}),
        spec=obj.NodeSpec(unschedulable=True, taints=[
            obj.Taint(key="dedicated", value="gpu", effect="NoSchedule")]),
        status=obj.NodeStatus(allocatable={"cpu": 4000.0, "pods": 110.0}))
    yield obj.PersistentVolume(
        metadata=obj.ObjectMeta(name="pv1", labels={"z": "1"}),
        capacity={"ephemeral-storage": float(1 << 30)},
        storage_class="fast", phase="Available")
    yield obj.PersistentVolumeClaim(
        metadata=obj.ObjectMeta(name="c1", namespace="ns"),
        request={"ephemeral-storage": float(1 << 30)}, phase="Pending",
        binding_mode="WaitForFirstConsumer")
    yield obj.Event(metadata=obj.ObjectMeta(name="e1", namespace="ns"),
                    reason="Scheduled", message="ok",
                    involved_object="Pod:ns/p1", type="Normal")
    yield obj.PodDisruptionBudget(
        metadata=obj.ObjectMeta(name="b1", namespace="ns"),
        spec=obj.PDBSpec(min_available=2, selector=obj.LabelSelector(
            match_labels={"app": "web"})))


def test_dump_matches_asdict_every_kind():
    for o in _objects():
        assert codec.dump(o) == dataclasses.asdict(o), type(o).__name__


def test_build_matches_reflective_roundtrip_every_kind():
    for o in _objects():
        d = dataclasses.asdict(o)
        built = codec.build(type(o), d)
        ref = _build_typed(type(o), d)
        assert built == ref == o, type(o).__name__
        # and the rebuilt object re-encodes identically
        assert codec.dump(built) == d


def test_build_partial_dict_uses_defaults():
    p = codec.build(obj.Pod, {"metadata": {"name": "x"}})
    assert p.metadata.name == "x"
    assert p.spec.requests == {} and p.status.phase == "Pending"
    # missing uid field → default_factory runs (fresh uid)
    assert p.metadata.uid.startswith("uid-")


def test_full_dict_preserves_wire_uid_without_burning_counter():
    d = dataclasses.asdict(_rich_pod())
    d["metadata"]["uid"] = "uid-424242"
    before = obj.to_dict(obj.Pod(metadata=obj.ObjectMeta(name="t")))[
        "metadata"]["uid"]
    built = codec.build(obj.Pod, d)
    after = obj.to_dict(obj.Pod(metadata=obj.ObjectMeta(name="t")))[
        "metadata"]["uid"]
    assert built.metadata.uid == "uid-424242"
    # exactly one uid consumed (by the two probe pods, not the decode)
    assert int(after[4:]) == int(before[4:]) + 1


def test_dump_returns_fresh_containers():
    p = _rich_pod()
    d = codec.dump(p)
    d["metadata"]["labels"]["app"] = "MUTATED"
    d["spec"]["tolerations"][0]["key"] = "MUTATED"
    assert p.metadata.labels["app"] == "web"
    assert p.spec.tolerations[0].key == "dedicated"


@pytest.mark.parametrize("kind", sorted(obj.KIND_OF.values()))
def test_dump_build_roundtrip_every_kind_keeps_wire_uid(kind):
    """Every stored kind round-trips through the slotted codec: equal
    fields, the wire uid kept, no instance __dict__ built on the way."""
    cls = obj._KIND_CLASS[kind]
    o = cls(metadata=obj.ObjectMeta(name="r1", namespace="ns",
                                    uid="uid-777777", labels={"a": "b"}))
    d = codec.dump(o)
    assert d == dataclasses.asdict(o)
    built = codec.build(cls, d)
    assert built == o and built.metadata.uid == "uid-777777"
    assert not hasattr(built, "__dict__")
    assert not hasattr(built.metadata, "__dict__")
    assert codec.dump(built) == d
