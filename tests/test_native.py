"""Native fastclone extension: build, equivalence with the Python clone
over the whole object-tree shape space, and graceful fallback."""
import os
import subprocess
import sys

import pytest

from minisched_tpu.native import load
from minisched_tpu.state import objects as obj
from minisched_tpu.state.objects import _clone, deepcopy_obj


def _rich_pod():
    return obj.Pod(
        metadata=obj.ObjectMeta(name="np", namespace="ns",
                                labels={"a": "b", "c": "d"},
                                annotations={"k": "v"}),
        spec=obj.PodSpec(
            requests={"cpu": 100.0, "memory": 1 << 30},
            priority=7,
            tolerations=[obj.Toleration(key="t", operator="Exists",
                                        effect="NoSchedule")],
            ports=[obj.ContainerPort(host_port=80)],
            volumes=[obj.VolumeClaim(claim_name="vc")],
            topology_spread_constraints=[obj.TopologySpreadConstraint(
                max_skew=1, topology_key="zone",
                when_unsatisfiable="DoNotSchedule",
                label_selector=obj.LabelSelector(
                    match_labels={"x": "y"}))],
            affinity=obj.Affinity(
                node_affinity=obj.NodeAffinity(
                    required=obj.NodeSelector(node_selector_terms=[
                        obj.NodeSelectorTerm(match_expressions=[
                            obj.NodeSelectorRequirement(
                                key="k", operator="In",
                                values=["v1", "v2"])])]),
                    preferred=[obj.PreferredSchedulingTerm(
                        weight=3,
                        preference=obj.NodeSelectorTerm())]),
                pod_anti_affinity=obj.PodAntiAffinity(required=[
                    obj.PodAffinityTerm(
                        label_selector=obj.LabelSelector(
                            match_labels={"q": "r"}),
                        topology_key="zone",
                        namespaces=["n1", "n2"])])),
        ),
        status=obj.PodStatus(unschedulable_plugins=["A", "B"],
                             message="m", nominated_node_name="n"))


SAMPLES = [
    _rich_pod(),
    obj.Node(metadata=obj.ObjectMeta(name="nn"),
             spec=obj.NodeSpec(unschedulable=True,
                               taints=[obj.Taint(key="a", value="b",
                                                 effect="NoExecute")]),
             status=obj.NodeStatus(allocatable={"cpu": 1.5, "pods": 9})),
    obj.PersistentVolume(metadata=obj.ObjectMeta(name="pv"),
                         capacity={"ephemeral-storage": 5.0},
                         storage_class="sc", phase="Available"),
    obj.Event(metadata=obj.ObjectMeta(name="ev", namespace="d"),
              reason="r", message="m", involved_object="Pod:d/x"),
]


def test_native_builds_and_matches_python_clone():
    mod = load()
    if mod is None:
        pytest.skip("native toolchain unavailable")
    for sample in SAMPLES:
        got = deepcopy_obj(sample)          # native path (via objects.py)
        ref = _clone(sample)                # pure-Python walk
        assert obj.to_dict(got) == obj.to_dict(ref)
        # isolation: mutating the clone leaves the original untouched
        got.metadata.labels["mut"] = "x"
        assert "mut" not in sample.metadata.labels


def test_native_shares_immutables_and_rebuilds_containers():
    mod = load()
    if mod is None:
        pytest.skip("native toolchain unavailable")
    p = _rich_pod()
    c = mod and deepcopy_obj(p)
    assert c.metadata.name is p.metadata.name          # str shared
    assert c.metadata.labels is not p.metadata.labels  # dict rebuilt
    assert c.spec.tolerations is not p.spec.tolerations
    assert c.spec is not p.spec


def test_fallback_without_native(monkeypatch):
    """MINISCHED_NO_NATIVE pins the pure-Python clone; the store keeps
    working end-to-end."""
    env = dict(os.environ, MINISCHED_NO_NATIVE="1",
               JAX_PLATFORMS="cpu")
    code = (
        "from minisched_tpu.state.store import ClusterStore\n"
        "from minisched_tpu.state import objects as obj\n"
        "import minisched_tpu.native as n\n"
        "assert n.load() is None\n"
        "s = ClusterStore()\n"
        "s.create(obj.Pod(metadata=obj.ObjectMeta(name='x',"
        " namespace='d'), spec=obj.PodSpec(requests={'cpu': 1})))\n"
        "assert s.get('Pod', 'd/x').spec.requests == {'cpu': 1}\n"
        "print('fallback ok')\n")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "fallback ok" in r.stdout


def test_unregistered_type_falls_back_to_python_walk():
    mod = load()
    if mod is None:
        pytest.skip("native toolchain unavailable")

    class Weird:
        def __init__(self):
            self.x = 1

    # deepcopy_obj must survive a type the native module never saw
    out = deepcopy_obj({"w": Weird()})
    assert out["w"].x == 1 and out["w"] is not None


def test_deep_nesting_raises_instead_of_crashing():
    """Pathological nesting must surface as RecursionError (the Python
    walk's failure mode), never a C-stack segfault."""
    mod = load()
    if mod is None:
        pytest.skip("native toolchain unavailable")
    deep = cur = []
    for _ in range(200_000):
        nxt = []
        cur.append(nxt)
        cur = nxt
    with pytest.raises(RecursionError):
        mod.clone(deep)


def test_native_build_is_named_by_source_hash(tmp_path, monkeypatch):
    """The built file's name carries a hash of fastclone.c, so a copied
    tree's stale _build/ (fresh-looking mtimes) can never be loaded for
    a changed source."""
    from minisched_tpu import native

    src = tmp_path / "fastclone.c"
    src.write_text("/* a */")
    monkeypatch.setattr(native, "_src_path", lambda: str(src))
    first = native._so_path()
    src.write_text("/* b */")
    assert native._so_path() != first
    src.write_text("/* a */")
    assert native._so_path() == first
