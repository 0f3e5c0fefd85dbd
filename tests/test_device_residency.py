"""Device-resident dynamic cluster state + slim decision readback
(engine/scheduler.py _DeviceResidency, ops/residency.py,
encode/cache.py snapshot_resident).

The contract under test, end to end:

  * bit-equality — with MINISCHED_DEVICE_RESIDENT=1 (loop-carried
    free/used_ports on device, sparse correction deltas, slim u8
    readback) the engine commits EXACTLY the placements the
    upload-every-batch fallback (=0) commits, across gangs, hard
    DoNotSchedule spread, a preemption burst, and with the pipelined
    cycle both on and off;
  * steady-state elision — a multi-batch burst performs ONE full
    dynamic-leaf upload (the establish resync); every later batch is a
    delta-corrected hit carrying zero full re-uploads, asserted by the
    h2d byte counters;
  * divergence self-healing — failed binds (unassume), node delete
    mid-stream, and claim-table mutations surface as listener rows and
    re-converge the device view without ever desyncing (the epoch
    protocol), while the engine keeps binding.
"""
import threading
import time

import numpy as np
import pytest

from minisched_tpu.config import SchedulerConfig
from minisched_tpu.scenario import Cluster, wait_until
from minisched_tpu.service.defaultconfig import Profile
from minisched_tpu.state import objects as obj

ZONE = "topology.kubernetes.io/zone"


def _profile(preempt: bool = False):
    plugins = ["NodeUnschedulable", "NodeResourcesFit", "PodTopologySpread"]
    if preempt:
        plugins.append("DefaultPreemption")
    return Profile(name="res", plugins=plugins,
                   plugin_args={"NodeResourcesFit":
                                {"score_strategy": None}})


def _config(resident: bool, pipeline: bool = True, **kw):
    kw.setdefault("max_batch_size", 8)
    kw.setdefault("batch_window_s", 0.3)
    kw.setdefault("backoff_initial_s", 0.05)
    kw.setdefault("backoff_max_s", 0.2)
    return SchedulerConfig(device_resident=resident, pipeline=pipeline,
                           **kw)


def _make_nodes(c: Cluster) -> None:
    for i, zone in enumerate(("a", "a", "b", "b", "c", "c")):
        c.create_node(f"n{i}", cpu=64000, labels={ZONE: zone})


def _spread_spec(priority: int) -> obj.PodSpec:
    return obj.PodSpec(
        requests={"cpu": 100}, priority=priority,
        topology_spread_constraints=[obj.TopologySpreadConstraint(
            max_skew=1, topology_key=ZONE,
            when_unsatisfiable="DoNotSchedule",
            label_selector=obj.LabelSelector(
                match_labels={"app": "spread"}))])


def _make_pods() -> list:
    """24 pods with UNIQUE priorities (deterministic pop + scan order):
    8 hard-spread, 4 gang (quorum 4), 12 plain — three 8-pod batches
    exercising arbitration, gang atomicity and the deferred flush."""
    pods = []
    pri = 100
    for i in range(8):
        pods.append(obj.Pod(
            metadata=obj.ObjectMeta(name=f"sp-{i}", namespace="default",
                                    labels={"app": "spread"}),
            spec=_spread_spec(priority=pri)))
        pri -= 1
    for i in range(4):
        pods.append(obj.Pod(
            metadata=obj.ObjectMeta(name=f"gang-{i}", namespace="default"),
            spec=obj.PodSpec(requests={"cpu": 200}, priority=pri,
                             pod_group="team", pod_group_min=4)))
        pri -= 1
    for i in range(12):
        pods.append(obj.Pod(
            metadata=obj.ObjectMeta(name=f"plain-{i}", namespace="default"),
            spec=obj.PodSpec(requests={"cpu": 150}, priority=pri)))
        pri -= 1
    return pods


def _run_burst(resident: bool, pipeline: bool = True, fault=None):
    """Create nodes + burst, wait for every pod to bind; returns
    ({pod name: node}, engine metrics)."""
    c = Cluster()
    try:
        c.start(profile=_profile(),
                config=_config(resident, pipeline=pipeline),
                with_pv_controller=False)
        _make_nodes(c)
        sched = c.service.scheduler
        if fault is not None:
            fault(sched)
        pods = _make_pods()
        c.create_objects(pods)
        deadline = time.monotonic() + 120
        names = [p.metadata.name for p in pods]
        placements = {}
        while time.monotonic() < deadline:
            placements = {p.metadata.name: p.spec.node_name
                          for p in c.list_pods()}
            if all(placements.get(n) for n in names):
                break
            time.sleep(0.05)
        assert all(placements.get(n) for n in names), {
            n: placements.get(n) for n in names if not placements.get(n)}
        metrics = sched.metrics()
        return placements, metrics
    finally:
        c.shutdown()


@pytest.mark.parametrize("pipeline", [False, True])
def test_resident_bit_identical_to_fallback(pipeline):
    """Gang + hard-spread multi-batch burst: the device-resident engine
    must commit exactly the fallback's placements in the SAME pipeline
    mode — the resident step consumes corrected device leaves that
    equal the fallback's host snapshot bit-for-bit (invariant I2), and
    the slim readback changes bytes, not values."""
    base, base_m = _run_burst(resident=False, pipeline=pipeline)
    res, res_m = _run_burst(resident=True, pipeline=pipeline)
    assert res == base
    assert res_m["batches"] >= 3 and base_m["batches"] >= 3
    # the resident run actually exercised the protocol
    assert res_m["residency_resyncs"] >= 1
    assert res_m["residency_hits"] >= 1
    # the fallback never touches it
    assert base_m["residency_hits"] == 0
    assert base_m["residency_resyncs"] == 0


def test_slim_readback_reversion_is_counted(monkeypatch):
    """A slim readback that fails its first-batch cross-check reverts
    to the i32 fetch, still commits the right placements, and is
    COUNTED in metrics() so a chip run can fail on it (chip_smoke.py)
    instead of absorbing it in a log line."""
    from minisched_tpu.engine import scheduler as sched_mod

    real = sched_mod.unpack_decision_slim
    calls = []

    def first_call_scribbled(buf, p, f):
        out = real(buf, p, f)
        if not calls:
            out[0][:] = -1  # every chosen row lost: the check must trip
        calls.append(1)
        return out

    base, base_m = _run_burst(resident=True)
    assert base_m["slim_readback_reversions"] == 0
    monkeypatch.setattr(sched_mod, "unpack_decision_slim",
                        first_call_scribbled)
    got, m = _run_burst(resident=True)
    assert m["slim_readback_reversions"] == 1
    assert got == base


def test_steady_state_uploads_only_deltas():
    """A clean burst (no revocation churn beyond arbitration, no node
    events) performs exactly ONE full dynamic-leaf upload — the
    establish — and every later batch is a delta-corrected hit. The h2d
    byte counter stays far below the fallback's (which re-uploads the
    full free/used_ports matrices every batch): the acceptance
    criterion 'no full free re-upload on the steady-state path'."""
    _placed, fb = _run_burst(resident=False)
    _placed2, rs = _run_burst(resident=True)
    assert rs["residency_resyncs"] == 1, rs
    assert rs["residency_hits"] == rs["batches"] - 1
    # Fallback pays the full dynamic upload per batch; resident pays it
    # once plus sparse corrections. Same workload, same static uploads,
    # so the gap is the dynamic-leaf traffic.
    assert rs["h2d_bytes_total"] < fb["h2d_bytes_total"], (rs, fb)
    # And the readback is slimmer batch-for-batch.
    assert (rs["fetch_bytes_total"] / rs["batches"]
            < fb["fetch_bytes_total"] / fb["batches"])


def test_preemption_burst_bit_identical_and_resyncs():
    """Preemption exercises the two hardest protocol paths: evictions
    mutate free outside any batch (informer-side corrections), and
    nominated-capacity reservations ride the carried chain as an
    order-free per-node correction (the nomination-window carry) —
    subtracted from the step's free INPUT only and added back before
    the carried adoption, so residency never stands down for them."""
    def run(resident: bool):
        c = Cluster()
        try:
            c.start(profile=_profile(preempt=True),
                    config=_config(resident),
                    with_pv_controller=False)
            c.create_node("pr-n0", cpu=300)
            c.create_node("pr-n1", cpu=300)
            for i in range(6):
                c.create_pod(f"low{i}", cpu=100, priority=1)
            for i in range(6):
                c.wait_for_pod_bound(f"low{i}", timeout=30)
            # cluster full: the vip must evict exactly one victim
            c.create_pod("vip", cpu=100, priority=100)
            vip = c.wait_for_pod_bound("vip", timeout=60)
            survivors = sorted(p.metadata.name for p in c.list_pods()
                               if p.metadata.name.startswith("low"))
            # one more pod AFTER the nomination window drained, onto a
            # fresh node (no second preemption): the resident engine
            # must re-establish (second resync)
            c.create_node("pr-n2", cpu=300)
            c.create_pod("after", cpu=50, priority=5)
            c.wait_for_pod_bound("after", timeout=30)
            m = c.service.scheduler.metrics()
            return vip.spec.node_name, survivors, m
        finally:
            c.shutdown()

    node_fb, low_fb, _m_fb = run(resident=False)
    node_rs, low_rs, m_rs = run(resident=True)
    assert node_rs == node_fb
    assert low_rs == low_fb
    # The nomination window no longer forces a stand-down: ONE resync
    # (the establish) for the whole run — the eviction churn rides the
    # delta corrections and the reservation rides the carried chain.
    assert m_rs["residency_resyncs"] == 1, m_rs


def test_nomination_window_carry_is_order_free_and_counted():
    """A batch prepared while ANOTHER pod's nomination is outstanding
    keeps the carry: the reservation is applied as a per-node
    correction to the step's free input (the batch cannot steal the
    nominated capacity) and reversed before the carried adoption, so
    the chain still equals un-nominated cache truth bitwise."""
    c = Cluster()
    sched = None
    try:
        c.start(profile=_profile(), config=_config(True),
                with_pv_controller=False)
        c.create_node("nc-n0", cpu=1000)
        c.create_node("nc-n1", cpu=1000)
        # Establish the carry.
        c.create_pod("warm", cpu=100)
        c.wait_for_pod_bound("warm", timeout=30)
        sched = c.service.scheduler
        # Outstanding reservation for a pod that is NOT in any batch:
        # 900 cpu on nc-n0 — with warm's 100 already bound there (or
        # not), the reservation makes nc-n0 unable to take 300-cpu pods.
        from minisched_tpu.encode import features as F
        from minisched_tpu.state.objects import pod_requests
        ghost = obj.Pod(metadata=obj.ObjectMeta(name="ghost",
                                                namespace="default"),
                        spec=obj.PodSpec(requests={"cpu": 900}))
        with sched._nom_lock:
            sched._nominations["default/ghost"] = (
                "nc-n0", F.resources_vector(pod_requests(ghost)),
                time.monotonic() + 60.0)
        for i in range(3):
            c.create_pod(f"bys-{i}", cpu=300)
        for i in range(3):
            p = c.wait_for_pod_bound(f"bys-{i}", timeout=30)
            # the reservation held: nothing lands on the nominated node
            assert p.spec.node_name == "nc-n1", p.spec.node_name
        m = sched.metrics()
        assert m["residency_nomination_carries"] >= 1, m
        # the carry NEVER stood down: establish-only resyncs, and the
        # chain still matches cache truth (clean cross-check would have
        # counted a desync otherwise)
        assert m["residency_resyncs"] == 1, m
        assert m["residency_desyncs"] == 0, m
        res = sched._residency
        if res is not None and res.epoch >= 0:
            # white-box: the carried device array equals the
            # UN-nominated mirror (the add-back round-tripped exactly)
            np.testing.assert_array_equal(
                np.asarray(res.free_dev), res.mirror_free)
    finally:
        if sched is not None:
            with sched._nom_lock:
                sched._nominations.pop("default/ghost", None)
        c.shutdown()


def test_failed_bind_divergence_corrects_without_resync():
    """A bind conflict unassumes the pod AFTER the device optimistically
    debited its row: host truth reverts, the device view does not — the
    listener marks the row, the next batch uploads the correction, and
    the pod binds on retry. No resync needed (counted as hits), nothing
    desyncs."""
    c = Cluster()
    try:
        c.start(profile=_profile(), config=_config(True),
                with_pv_controller=False)
        _make_nodes(c)
        sched = c.service.scheduler
        store = c.store
        orig_bind = store.bind_pods
        tripped = threading.Event()

        def flaky_bind(items):
            if not tripped.is_set():
                tripped.set()
                return orig_bind(items[: len(items) // 2])  # rest conflict
            return orig_bind(items)

        store.bind_pods = flaky_bind
        pods = _make_pods()
        c.create_objects(pods)
        names = [p.metadata.name for p in pods]
        wait_until(lambda: all(
            p.spec.node_name for p in c.list_pods()
            if p.metadata.name in names), timeout=120)
        m = sched.metrics()
        assert tripped.is_set() and m["bind_conflicts"] > 0
        assert m["residency_resyncs"] == 1, m  # establish only
        assert m["residency_hits"] >= 2
    finally:
        c.shutdown()


def test_node_delete_mid_stream_stays_consistent():
    """Deleting a node between batches drops its row (a dynamic dirty
    row + a static version bump): the resident engine must keep binding
    every later pod onto live nodes only."""
    c = Cluster()
    try:
        c.start(profile=_profile(), config=_config(True),
                with_pv_controller=False)
        _make_nodes(c)
        for i in range(6):
            c.create_pod(f"wave1-{i}", cpu=100)
        for i in range(6):
            c.wait_for_pod_bound(f"wave1-{i}", timeout=30)
        c.store.delete("Node", "n5")
        wait_until(lambda: c.service.scheduler.cache.row_of("n5") is None,
                   timeout=10)
        for i in range(6):
            c.create_pod(f"wave2-{i}", cpu=100)
        for i in range(6):
            p = c.wait_for_pod_bound(f"wave2-{i}", timeout=30)
            assert p.spec.node_name != "n5"
        m = c.service.scheduler.metrics()
        assert m["residency_hits"] >= 1
    finally:
        c.shutdown()


# ---- cache protocol unit tests -----------------------------------------

def _node(name, cpu=1000, labels=None):
    return obj.Node(
        metadata=obj.ObjectMeta(name=name, labels=labels or {}),
        spec=obj.NodeSpec(),
        status=obj.NodeStatus(allocatable={"cpu": cpu, "memory": 1 << 30,
                                           "pods": 100}))


def _pod(name, cpu=100, volumes=()):
    return obj.Pod(
        metadata=obj.ObjectMeta(name=name, namespace="default"),
        spec=obj.PodSpec(requests={"cpu": cpu},
                         volumes=[obj.VolumeClaim(claim_name=v)
                                  for v in volumes]))


def test_listener_collects_marks_and_rebases():
    from minisched_tpu.encode import NodeFeatureCache

    cache = NodeFeatureCache()
    for i in range(4):
        cache.upsert_node(_node(f"m{i}"))
    lst = cache.register_dyn_listener()
    # First collection rebases (no valid base yet): full leaves.
    nf, _names, _sv, incs, delta = cache.snapshot_resident(pad=16, dyn=lst)
    assert delta is None and nf.free is not None
    e0 = lst.epoch
    # Bind → the node's row is dirty; collection elides the leaves and
    # hands back exactly that row with authoritative values.
    cache.account_bind(_pod("a", cpu=250), node_name="m2")
    nf2, _n2, _sv2, _incs2, d2 = cache.snapshot_resident(pad=16, dyn=lst)
    assert nf2.free is None and nf2.used_ports is None
    assert d2.epoch == e0 + 1
    row = cache.row_of("m2")
    assert row in d2.rows.tolist()
    k = d2.rows.tolist().index(row)
    assert d2.free[k][obj.RESOURCE_INDEX["cpu"]] == 750.0
    # Clean cycle: empty delta, epoch still advances (liveness signal).
    _nf3, _n3, _sv3, _i3, d3 = cache.snapshot_resident(pad=16, dyn=lst)
    assert d3.rows.size == 0 and d3.epoch == e0 + 2
    # Unbind (the failed-bind/unassume path) re-dirties the row.
    cache.account_unbind("default/a")
    _nf4, _n4, _sv4, _i4, d4 = cache.snapshot_resident(pad=16, dyn=lst)
    assert row in d4.rows.tolist()
    # Invalidate → next collection is a full rebase again.
    lst.invalidate()
    nf5, _n5, _sv5, _i5, d5 = cache.snapshot_resident(pad=16, dyn=lst)
    assert d5 is None and nf5.free is not None


def test_listener_marks_claim_mutations():
    """Claim-table traffic (the PV/VolumeRestrictions attach-slot
    accounting) mutates the generic volume axis of free — the rows must
    reach the listener like any other divergence source."""
    from minisched_tpu.encode import NodeFeatureCache

    cache = NodeFeatureCache()
    cache.upsert_node(_node("v0"))
    lst = cache.register_dyn_listener()
    cache.snapshot_resident(pad=16, dyn=lst)  # establish base
    cache.account_bind(_pod("pv-user", volumes=("claim-1",)),
                       node_name="v0")
    _nf, _n, _sv, _i, d = cache.snapshot_resident(pad=16, dyn=lst)
    row = cache.row_of("v0")
    assert row in d.rows.tolist()
    k = d.rows.tolist().index(row)
    vol = obj.RESOURCE_INDEX["attachable-volumes"]
    # one generic attach slot consumed on that row
    assert d.free[k][vol] == obj.DEFAULT_ATTACHABLE_VOLUMES - 1


def test_pad_change_forces_rebase():
    from minisched_tpu.encode import NodeFeatureCache

    cache = NodeFeatureCache()
    for i in range(4):
        cache.upsert_node(_node(f"p{i}"))
    lst = cache.register_dyn_listener()
    _nf, _n, _sv, _i, d = cache.snapshot_resident(pad=16, dyn=lst)
    assert d is None
    nf2, _n2, _sv2, _i2, d2 = cache.snapshot_resident(pad=32, dyn=lst)
    assert d2 is None and nf2.free is not None  # rebase at the new pad
    _nf3, _n3, _sv3, _i3, d3 = cache.snapshot_resident(pad=32, dyn=lst)
    assert d3 is not None  # and the new base carries deltas again


# ---- ops unit tests -----------------------------------------------------

# P=4/5/13 exercise the ceil(P/8) bit-plane path: a small
# pod_bucket_min or a tiny residual-pass pad produces pads that do not
# divide by 8, and pack (ceil bytes) and unpack (floor would misalign
# every later plane) must agree byte-for-byte.
@pytest.mark.parametrize("P", [4, 5, 13, 64])
def test_slim_pack_roundtrip_matches_legacy(P):
    import jax.numpy as jnp

    from minisched_tpu.ops.residency import (I16_SAT, pack_decision_slim,
                                             slim_buffer_bytes,
                                             unpack_decision_slim)

    rng = np.random.default_rng(7)
    F = 3
    chosen = rng.integers(-1, 60_000, P).astype(np.int32)
    assigned = rng.random(P) > 0.4
    gang = rng.random(P) > 0.8
    feasible = rng.integers(0, 70_000, P).astype(np.int32)
    static = rng.integers(0, 70_000, P).astype(np.int32)
    rejects = rng.integers(0, 70_000, (F, P)).astype(np.int32)
    repaired = rng.random(P) > 0.9
    buf = np.array(pack_decision_slim(
        jnp.array(chosen), jnp.array(assigned), jnp.array(gang),
        jnp.array(feasible), jnp.array(static), jnp.array(rejects),
        jnp.array(repaired)))
    assert buf.dtype == np.uint8
    assert buf.nbytes == slim_buffer_bytes(P, F)
    ch, a, g, fc, fs, rj, rep = unpack_decision_slim(buf, P, F)
    np.testing.assert_array_equal(ch, chosen)
    np.testing.assert_array_equal(a, assigned)
    np.testing.assert_array_equal(g, gang)
    np.testing.assert_array_equal(rep, repaired)
    # counts saturate at I16_SAT — positivity (all the engine reads)
    # survives exactly
    np.testing.assert_array_equal(fc, np.minimum(feasible, I16_SAT))
    np.testing.assert_array_equal(fs, np.minimum(static, I16_SAT))
    np.testing.assert_array_equal(rj, np.minimum(rejects, I16_SAT))
    # ~2.4× slimmer than the (6+F, P) i32 stack it replaces
    assert buf.nbytes < (6 + F) * P * 4 / 2


def test_insert_ports_matches_host_replay_and_cache_rule():
    """ROADMAP residency follow-up (d): the device port-insertion op,
    the numpy replay, and the cache's _add_ports rule agree bitwise —
    first zero slot per nonzero port, pod order, duplicates written
    twice, overflow dropped."""
    import jax.numpy as jnp

    from minisched_tpu.ops.residency import insert_ports, replay_ports_host

    N, PORT, PP = 6, 4, 3
    state = np.zeros((N, PORT), dtype=np.int32)
    state[2] = [80, 0, 443, 0]          # partially occupied row
    state[5] = [1, 2, 3, 4]             # full row: inserts must drop
    rows = np.array([2, 2, 5, -1, 0], dtype=np.int32)
    ports = np.array([[8080, 0, 0],
                      [8080, 9090, 0],   # duplicate port value
                      [7070, 0, 0],      # overflow: row 5 is full
                      [1234, 0, 0],      # -1 row: skipped entirely
                      [0, 0, 0]],        # no ports: no-op
                     dtype=np.int32)
    mirror = state.copy()
    replay_ports_host(mirror, rows, ports)
    dev = np.asarray(insert_ports(jnp.array(state), rows, ports))
    np.testing.assert_array_equal(dev, mirror)
    # the rule itself: row 2 filled in slot order, row 5 unchanged
    np.testing.assert_array_equal(mirror[2], [80, 8080, 443, 8080])
    np.testing.assert_array_equal(mirror[5], [1, 2, 3, 4])
    assert 9090 not in mirror[2] or (mirror[2] == 9090).sum() <= 1
    np.testing.assert_array_equal(mirror[0], 0)


def test_establish_mirrors_share_no_memory_with_the_device():
    """The host replay writes the mirrors in place. On the CPU backend
    device_put may alias an aligned host buffer, so the mirrors an
    establish keeps must be copies: a port insertion replayed on the
    host then never reaches the device twice (once through the shared
    buffer, once through insert_ports)."""
    from types import SimpleNamespace

    from minisched_tpu.engine.scheduler import _DeviceResidency

    class _Eng:
        def _nf_sharding(self, name):
            return None

        def _res_count(self, **kw):
            pass

    N, R, PORT = 8, 3, 4
    free = np.full((N, R), 64.0, dtype=np.float32)
    ports = np.zeros((N, PORT), dtype=np.int32)
    nf = SimpleNamespace(free=free, used_ports=ports,
                         _replace=lambda **kw: kw)
    res = _DeviceResidency(SimpleNamespace(epoch=0))
    res.attach(_Eng(), nf, None)
    assert not np.shares_memory(res.mirror_free, free)
    assert not np.shares_memory(res.mirror_ports, ports)
    res.note_ports(np.array([2, 2], dtype=np.int32),
                   np.array([[20001, 30001], [20002, 0]], dtype=np.int32))
    np.testing.assert_array_equal(np.asarray(res.ports_dev),
                                  res.mirror_ports)
    np.testing.assert_array_equal(res.mirror_ports[2],
                                  [20001, 30001, 20002, 0])


def test_port_heavy_steady_state_keeps_residency():
    """Port-heavy workloads keep the zero-correction steady state
    (follow-up (d)): with insertion modeled on device + mirror, a burst
    of host-port pods establishes ONCE and every later batch is a
    delta-corrected hit whose used_ports correction is empty (mirror ==
    cache truth at bind time) — and placements equal the fallback's."""
    def run(resident: bool):
        c = Cluster()
        try:
            c.start(profile=Profile(
                        name="ports",
                        plugins=["NodeUnschedulable", "NodeResourcesFit",
                                 "NodePorts"],
                        plugin_args={"NodeResourcesFit":
                                     {"score_strategy": None}}),
                    config=_config(resident), with_pv_controller=False)
            for i in range(4):
                c.create_node(f"pn{i}", cpu=64000)
            pods, pri = [], 200
            for i in range(24):
                pods.append(obj.Pod(
                    metadata=obj.ObjectMeta(name=f"pp-{i}",
                                            namespace="default"),
                    spec=obj.PodSpec(
                        requests={"cpu": 100 + i}, priority=pri,
                        ports=[obj.ContainerPort(host_port=20000 + i),
                               obj.ContainerPort(host_port=30000 + i)])))
                pri -= 1
            c.create_objects(pods)
            deadline = time.monotonic() + 90
            placements = {}
            while time.monotonic() < deadline:
                placements = {p.metadata.name: p.spec.node_name
                              for p in c.list_pods() if p.spec.node_name}
                if len(placements) == 24:
                    break
                time.sleep(0.05)
            assert len(placements) == 24, placements
            sched = c.service.scheduler
            m = sched.metrics()
            res = sched._residency
            if resident and res is not None and res.epoch >= 0:
                # white-box convergence: device == mirror bitwise after
                # the burst (the I1 invariant, extended to ports)
                np.testing.assert_array_equal(
                    np.asarray(res.ports_dev), res.mirror_ports)
                # 48 ports over 4 nodes overflow the 8-slot rows; the
                # tracked prefix (both sides drop overflow identically)
                # still occupies most of every row
                assert (res.mirror_ports != 0).sum() >= 24
            return placements, m
        finally:
            c.shutdown()

    fb, _m_fb = run(resident=False)
    rs, m_rs = run(resident=True)
    assert rs == fb
    assert m_rs["batches"] >= 3
    # steady state held: one establish, every later batch a hit — the
    # port churn never forced a resync or a correction-path divergence
    assert m_rs["residency_resyncs"] == 1, m_rs
    assert m_rs["residency_hits"] == m_rs["batches"] - 1, m_rs


def test_apply_rows_scatter_and_bucketing():
    import jax.numpy as jnp

    from minisched_tpu.ops.residency import apply_rows

    state = jnp.arange(24.0).reshape(6, 4)
    rows = np.array([1, 4], dtype=np.int32)
    vals = np.full((2, 4), -7.0, dtype=np.float32)
    out = np.asarray(apply_rows(state, rows, vals))
    expect = np.arange(24.0).reshape(6, 4)
    expect[[1, 4]] = -7.0
    np.testing.assert_array_equal(out, expect)
    # empty correction: identity, no row disturbed by the sentinel pad
    out2 = np.asarray(apply_rows(jnp.array(expect),
                                 np.zeros((0,), np.int32),
                                 np.zeros((0, 4), np.float32)))
    np.testing.assert_array_equal(out2, expect)
