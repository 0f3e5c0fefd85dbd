"""The PV controller watches claims and volumes, and follows pods only
while a WaitForFirstConsumer claim waits for its consumer: volumeless
pod churn never reaches it, late binding still happens, and reopening
the watch neither drops nor repeats an event."""
import itertools
import time

import pytest

from minisched_tpu import obs
from minisched_tpu.pvcontroller import PVController
from minisched_tpu.state import objects as obj
from minisched_tpu.state.store import ClusterStore, Watcher

ZONE = PVController.ZONE_KEY
SC = "wffc-class"


def _node(name, zone=""):
    return obj.Node(
        metadata=obj.ObjectMeta(name=name,
                                labels={ZONE: zone} if zone else {}),
        status=obj.NodeStatus(allocatable={"cpu": 4000, "pods": 110}))


def _pv(name, zone):
    return obj.PersistentVolume(
        metadata=obj.ObjectMeta(name=name, labels={ZONE: zone}),
        capacity={"ephemeral-storage": float(1 << 30)}, storage_class=SC)


def _pvc(name, mode="WaitForFirstConsumer"):
    return obj.PersistentVolumeClaim(
        metadata=obj.ObjectMeta(name=name, namespace="default"),
        request={"ephemeral-storage": float(1 << 30)}, storage_class=SC,
        binding_mode=mode)


def _pod(name, *claims):
    return obj.Pod(
        metadata=obj.ObjectMeta(name=name, namespace="default"),
        spec=obj.PodSpec(requests={"cpu": 100},
                         volumes=[obj.VolumeClaim(claim_name=c)
                                  for c in claims]))


def _wait(pred, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.01)
    return pred()


def _claim(store, name):
    return store.get("PersistentVolumeClaim", f"default/{name}")


def _bound(store, name):
    return lambda: _claim(store, name).phase == "Bound"


@pytest.fixture
def running():
    """Start a controller over a store; shut every one down after."""
    started = []

    def start(store, cls=PVController):
        c = cls(store, sync_period_s=0.05)
        c.start()
        started.append(c)
        return c

    yield start
    for c in started:
        c.shutdown()


def test_volumeless_pod_churn_never_reaches_the_controller(running):
    """2,000 pods created, bound and deleted in a store with no claim:
    the controller takes no Pod event, never follows pods, and adds
    almost nothing to the store lock's acquisitions."""
    store = ClusterStore()
    store.create(_node("n1"))
    ctl = running(store)
    assert _wait(lambda: ctl.stats()["syncs_total"] >= 1)
    obs.configure(True, buf=64)
    try:
        a0 = store.lock_acquisitions_total()
        pods = [_pod(f"p{i}") for i in range(2000)]
        for p in pods:
            store.create(p)
        assert len(store.bind_pods([(p.key, "n1") for p in pods])) == 2000
        for p in pods:
            store.delete("Pod", p.key)
        time.sleep(0.2)
        acquired = store.lock_acquisitions_total() - a0
    finally:
        obs.configure(False)
    st = ctl.stats()
    assert st["events_total"] < 10
    assert st["pods_watched"] is False
    # 4,001 store calls by this thread; one acquisition per Pod event
    # taken would add 6,000 more
    assert 4001 <= acquired < 4001 + 200


def test_waiting_claim_follows_pods_until_it_binds(running):
    """A pending WaitForFirstConsumer claim turns the pod watch on; once
    its consumer is bound the claim binds to a PV in the consumer's
    zone, and the pod watch turns off again."""
    store = ClusterStore()
    store.create_many([_node("n-a", "za"), _node("n-b", "zb"),
                       _pv("pv-a", "za"), _pv("pv-b", "zb")])
    ctl = running(store)
    store.create(_pvc("data"))
    assert _wait(lambda: ctl.stats()["pods_watched"], timeout=5)
    assert _claim(store, "data").phase == "Pending"
    store.create(_pod("consumer", "data"))
    store.bind_pod("default/consumer", "n-b")
    assert _wait(_bound(store, "data"), timeout=10)
    assert _claim(store, "data").volume_name == "pv-b"
    assert _wait(lambda: not ctl.stats()["pods_watched"], timeout=5)
    assert store.get("PersistentVolume", "pv-a").phase == "Available"


def test_claim_whose_consumer_was_bound_first_still_binds(running):
    """The consumer is bound while the controller follows no pod, so its
    events never reach the controller; the claim appearing later still
    binds, because every sync reads the scheduled consumers."""
    store = ClusterStore()
    store.create_many([_node("n-a", "za"), _pv("pv-a", "za")])
    ctl = running(store)
    store.create(_pod("early", "late-claim"))
    store.bind_pod("default/early", "n-a")
    time.sleep(0.1)
    assert ctl.stats()["events_total"] == 0
    store.create(_pvc("late-claim"))
    assert _wait(_bound(store, "late-claim"), timeout=10)
    assert _claim(store, "late-claim").volume_name == "pv-a"
    assert _wait(lambda: not ctl.stats()["pods_watched"], timeout=5)


def test_rewatch_neither_drops_nor_repeats(running, monkeypatch):
    """A claim created between the sync that decides to widen (or
    narrow) the watch and the reopened watch is taken exactly once, and
    every claim and volume event in the store's log is taken once."""
    kinds = list(PVController.KINDS)
    taken = []
    drain = Watcher.next_events

    def recording(self, max_n, timeout=None):
        evs = drain(self, max_n, timeout)
        taken.extend(evs)  # the controller's watch is the only one
        return evs

    monkeypatch.setattr(Watcher, "next_events", recording)
    store = ClusterStore()
    store.create_many([_node("n-a", "za"), _pv("pv-a", "za")])
    seq = itertools.count(1)

    class Between(PVController):
        def _sync_once(self):
            waiting = super()._sync_once()
            if waiting != self._pods_watched:  # the watch reopens next
                store.create(_pvc(f"between-{next(seq)}", mode="Immediate"))
            return waiting

    rv0 = store.resource_version()
    ctl = running(store, Between)
    assert _wait(lambda: ctl.stats()["syncs_total"] >= 1)  # watch open
    store.create(_pvc("data"))
    assert _wait(lambda: ctl.stats()["pods_watched"], timeout=5)
    assert _wait(_bound(store, "between-1"), timeout=10)
    store.create(_pod("consumer", "data"))
    store.bind_pod("default/consumer", "n-a")
    assert _wait(_bound(store, "data"), timeout=10)
    assert _wait(lambda: not ctl.stats()["pods_watched"], timeout=5)
    assert _wait(_bound(store, "between-2"), timeout=10)

    def logged():
        return {e.resource_version for e in drain(
            store.watch(kinds, from_version=rv0), 1 << 20, 0)}

    assert _wait(lambda: logged() <= {e.resource_version for e in taken})
    rvs = [e.resource_version for e in taken]
    assert rvs == sorted(set(rvs))  # in order, none twice
    pod_evs = [(e.type, e.object.spec.node_name) for e in taken
               if e.kind == "Pod"]
    assert pod_evs == [("ADDED", ""), ("MODIFIED", "n-a")]
    assert ctl.stats()["events_total"] == len(taken)
    assert next(seq) == 3


def test_cursor_that_left_the_log_rewatches_from_now(running):
    """When the store's log no longer holds the watch's cursor, the
    controller watches on from the current version, and the waiting
    claim still binds once its consumer is bound."""
    store = ClusterStore(max_log=64)
    store.create_many([_node("n-a", "za"), _pv("pv-a", "za")])

    class Flood(PVController):
        flooded = False

        def _sync_once(self):
            waiting = super()._sync_once()
            if waiting and not self.flooded:
                self.flooded = True  # push the cursor out of the log
                store.create_many([_pod(f"f{i}") for i in range(200)])
            return waiting

    ctl = running(store, Flood)
    store.create(_pvc("data"))
    assert _wait(lambda: ctl.stats()["pods_watched"], timeout=5)
    assert ctl.flooded
    store.create(_pod("consumer", "data"))
    store.bind_pod("default/consumer", "n-a")
    assert _wait(_bound(store, "data"), timeout=10)
    assert _wait(lambda: not ctl.stats()["pods_watched"], timeout=5)


def test_lock_acquisitions_counted_only_while_armed():
    """Every store-lock acquisition counts while the flight recorder is
    armed, and none while it is not."""
    store = ClusterStore()
    try:
        for _ in range(3):
            store.count("Pod")
        assert store.lock_acquisitions_total() == 0
        obs.configure(True, buf=64)
        for _ in range(5):
            store.count("Pod")
        store.create(_pod("a"))
        assert store.lock_acquisitions_total() == 6
        obs.configure(False)
        store.count("Pod")
        store.delete("Pod", "default/a")
        assert store.lock_acquisitions_total() == 6
        assert store.stats()["lock_acquisitions_total"] == 6
    finally:
        obs.configure(False)
