"""Cluster-store unit tests: CRUD, optimistic concurrency, watch streams,
binding CAS, snapshot/restore (reference capability: apiserver+etcd,
k8sapiserver/k8sapiserver.go:43-105)."""
import threading
import time

import pytest

from minisched_tpu.errors import AlreadyExistsError, ConflictError, NotFoundError
from minisched_tpu.state import (
    ClusterStore,
    EventType,
    Node,
    NodeSpec,
    NodeStatus,
    ObjectMeta,
    Pod,
    PodSpec,
)


def make_node(name, unschedulable=False, cpu=4000):
    return Node(
        metadata=ObjectMeta(name=name),
        spec=NodeSpec(unschedulable=unschedulable),
        status=NodeStatus(allocatable={"cpu": cpu, "memory": 16 << 30, "pods": 110}),
    )


def make_pod(name, ns="default", cpu=100):
    return Pod(metadata=ObjectMeta(name=name, namespace=ns),
               spec=PodSpec(requests={"cpu": cpu}))


def test_crud_roundtrip():
    s = ClusterStore()
    s.create(make_node("node1"))
    got = s.get("Node", "node1")
    assert got.metadata.name == "node1"
    assert got.metadata.resource_version == 1

    got.spec.unschedulable = True
    s.update(got)
    assert s.get("Node", "node1").spec.unschedulable is True
    assert s.get("Node", "node1").metadata.resource_version == 2

    s.delete("Node", "node1")
    with pytest.raises(NotFoundError):
        s.get("Node", "node1")


def test_create_duplicate_and_update_missing():
    s = ClusterStore()
    s.create(make_pod("p"))
    with pytest.raises(AlreadyExistsError):
        s.create(make_pod("p"))
    with pytest.raises(NotFoundError):
        s.update(make_pod("ghost"))


def test_returned_objects_are_copies():
    s = ClusterStore()
    s.create(make_node("n"))
    a = s.get("Node", "n")
    a.spec.unschedulable = True  # mutating the copy must not leak into store
    assert s.get("Node", "n").spec.unschedulable is False


def test_optimistic_concurrency():
    s = ClusterStore()
    s.create(make_pod("p"))
    a = s.get("Pod", "default/p")
    b = s.get("Pod", "default/p")
    a.spec.priority = 1
    s.update(a, check_version=True)
    b.spec.priority = 2
    with pytest.raises(ConflictError):
        s.update(b, check_version=True)


def test_bind_pod_cas():
    s = ClusterStore()
    s.create(make_node("n1"))
    s.create(make_pod("p"))
    s.bind_pod("default/p", "n1")
    pod = s.get("Pod", "default/p")
    assert pod.spec.node_name == "n1"
    assert pod.status.phase == "Running"
    with pytest.raises(ConflictError):
        s.bind_pod("default/p", "n1")  # already bound
    s.create(make_pod("q"))
    with pytest.raises(NotFoundError):
        s.bind_pod("default/q", "ghost-node")


def test_watch_sees_ordered_events():
    s = ClusterStore()
    w = s.watch(kinds=["Node"])
    s.create(make_node("n1"))
    s.create(make_pod("p1"))  # filtered out by kind
    n = s.get("Node", "n1")
    n.spec.unschedulable = True
    s.update(n)
    s.delete("Node", "n1")

    evs = [w.next_event(timeout=1) for _ in range(3)]
    assert [e.type for e in evs] == [EventType.ADDED, EventType.MODIFIED,
                                     EventType.DELETED]
    assert all(e.kind == "Node" for e in evs)
    assert evs[1].old_object.spec.unschedulable is False
    assert evs[1].object.spec.unschedulable is True
    assert w.next_event(timeout=0.05) is None


def test_watch_replay_from_version():
    s = ClusterStore()
    s.create(make_node("n1"))
    rv = s.resource_version()
    s.create(make_node("n2"))
    w = s.watch(kinds=["Node"], from_version=rv)
    ev = w.next_event(timeout=1)
    assert ev.object.metadata.name == "n2"


def test_watch_blocks_then_wakes():
    s = ClusterStore()
    w = s.watch()
    got = []

    def consume():
        got.append(w.next_event(timeout=5))

    t = threading.Thread(target=consume)
    t.start()
    s.create(make_node("late"))
    t.join(timeout=5)
    assert got and got[0].object.metadata.name == "late"


def test_snapshot_restore_roundtrip(tmp_path):
    s = ClusterStore()
    s.create(make_node("n1", unschedulable=True))
    p = make_pod("p1", cpu=250)
    p.spec.tolerations = []
    s.create(p)
    s.bind_pod("default/p1", "n1")

    path = str(tmp_path / "snap.json")
    s.save(path)
    s2 = ClusterStore.load(path)

    assert s2.get("Node", "n1").spec.unschedulable is True
    pod = s2.get("Pod", "default/p1")
    assert pod.spec.node_name == "n1"
    assert pod.spec.requests == {"cpu": 250}
    assert s2.resource_version() == s.resource_version()
    # restored store keeps working
    s2.create(make_node("n2"))
    assert s2.count("Node") == 2


def test_create_many_bulk_semantics():
    """Bulk create matches per-object create: rv-contiguous watch log,
    ADDED events for every object, atomic duplicate rejection."""
    store = ClusterStore()
    w = store.watch(kinds=["Pod"])
    pods = [make_pod(f"p{i}") for i in range(50)]
    store.create_many(pods)
    evs = w.next_events(100, timeout=1.0)
    assert [e.object.metadata.name for e in evs] == [f"p{i}" for i in range(50)]
    rvs = [e.resource_version for e in evs]
    assert rvs == list(range(rvs[0], rvs[0] + 50))
    assert store.count("Pod") == 50

    # duplicate anywhere in the batch → nothing from the batch lands
    with pytest.raises(AlreadyExistsError):
        store.create_many([make_pod("q1"), make_pod("p3")])
    assert store.count("Pod") == 50
    with pytest.raises(AlreadyExistsError):  # intra-batch duplicate too
        store.create_many([make_pod("r1"), make_pod("r1")])
    assert store.count("Pod") == 50


def test_next_events_batch_drain():
    """next_events returns up to max_n matching events per call and never
    skips matches past the cap; kind filtering advances the cursor."""
    store = ClusterStore()
    w = store.watch(kinds=["Pod"])
    store.create(make_node("n1"))  # filtered out
    store.create_many([make_pod(f"p{i}") for i in range(7)])
    first = w.next_events(3, timeout=1.0)
    assert [e.object.metadata.name for e in first] == ["p0", "p1", "p2"]
    rest = w.next_events(100, timeout=1.0)
    assert [e.object.metadata.name for e in rest] == ["p3", "p4", "p5", "p6"]
    assert w.next_events(10, timeout=0.05) == []


def _hold_lock(store, seconds):
    """Hold the store lock on another thread for `seconds`; returns
    once it is held."""
    held = threading.Event()

    def hold():
        with store._cond:
            held.set()
            time.sleep(seconds)

    t = threading.Thread(target=hold)
    t.start()
    held.wait()
    return t


def test_lock_wait_counted_only_while_armed():
    """A bind that waits 50 ms behind another holder of the store lock
    adds that wait to lock_wait_s_total while the flight recorder is
    armed, and nothing while it is not."""
    from minisched_tpu import obs

    store = ClusterStore()
    store.create(make_node("n1"))
    store.create_many([make_pod("a"), make_pod("b")])
    try:
        obs.configure(True, buf=64)
        t = _hold_lock(store, 0.05)
        w0 = store.lock_wait_s_total()
        assert store.bind_pods([("default/a", "n1")]) == ["default/a"]
        t.join()
        assert store.lock_wait_s_total() - w0 >= 0.04
        obs.configure(False)
        w1 = store.lock_wait_s_total()
        t = _hold_lock(store, 0.05)
        assert store.bind_pods([("default/b", "n1")]) == ["default/b"]
        t.join()
        assert store.lock_wait_s_total() == w1
        assert store.stats()["lock_wait_s_total"] == w1
    finally:
        obs.configure(False)


def test_lock_wait_sum_loses_no_update_under_contention(monkeypatch):
    """Many threads taking the store lock at once, each acquisition
    reading a wait of exactly one tick from a per-thread clock: the
    armed sum must count every one (it is updated under the lock)."""
    import sys
    import types

    from minisched_tpu import obs
    from minisched_tpu.state import store as store_mod

    local = threading.local()

    def ticks():
        local.t = getattr(local, "t", 0.0) + 1.0
        return local.t

    monkeypatch.setattr(store_mod, "time", types.SimpleNamespace(
        perf_counter=ticks, time=time.time, monotonic=time.monotonic))
    store = ClusterStore()
    n_threads, per_thread = 16, 300
    start = threading.Barrier(n_threads)

    def work():
        start.wait()
        for _ in range(per_thread):
            store.count("Pod")

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    obs.configure(True, buf=64)
    try:
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        obs.configure(False)
        sys.setswitchinterval(old)
    assert store.lock_wait_s_total() == float(n_threads * per_thread)
