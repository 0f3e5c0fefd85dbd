"""Fused multi-tenant arbitration (ISSUE 16).

The acceptance bar this file pins: a TenantFusionCoordinator serving T
virtual clusters from ONE vmapped dispatch per round makes decisions
BIT-IDENTICAL to stepping each tenant sequentially — in every engine
config (sync/pipelined/upload), for ragged tenant batch sizes
(masked-row padding), and across mid-tranche delta races (counted solo
fallbacks). Attribution never crosses tenants (provenance/journal rows
carry the owning tenant's profile), fair-share slot apportionment never
lets one hot tenant starve the fused slot, and the per-profile shed
budget (``MINISCHED_OVERLOAD`` profile overrides) holds per tenant —
one noisy tenant's overload burst sheds only ITS low-priority arrivals
while a quiet tenant binds everything.

Note the shared node NAMES across tenant stores: ``name_hash`` is a
static feature leaf, so tenants only land in one compatibility group
(one fused dispatch) when their virtual clusters use the same node
names. Differing names are correct but unfused — the mux's grouping is
deliberately conservative.
"""
import time

import numpy as np
import pytest

from minisched_tpu.config import SchedulerConfig
from minisched_tpu.engine import overload
from minisched_tpu.encode.cache import step_bucket
from minisched_tpu.engine.queue import bucket_major_quotas, weighted_gather
from minisched_tpu.service.service import (Tenant, TenantFusionCoordinator,
                                           tenants_fuse_from_env)
from minisched_tpu.state import objects as obj
from minisched_tpu.state.store import ClusterStore


@pytest.fixture(autouse=True)
def _clean_overload():
    overload.configure("")
    yield
    overload.configure("")


def _mk_store(node_cpus=(64000, 48000, 40000, 36000)):
    """One tenant's virtual cluster. Node NAMES are deliberately
    identical across tenants (see module docstring)."""
    s = ClusterStore()
    for i, cpu in enumerate(node_cpus):
        s.create(obj.Node(
            metadata=obj.ObjectMeta(name=f"vn-n{i}"),
            spec=obj.NodeSpec(),
            status=obj.NodeStatus(allocatable={
                "cpu": float(cpu), "memory": float(64 << 30),
                "pods": 110.0})))
    return s


def _pods(n, tag, *, cpu0=100, prio=None):
    """Deterministic per-tenant pods: unique priorities pin pop + scan
    order, so placements are reproducible across fused/sequential."""
    return [obj.Pod(
        metadata=obj.ObjectMeta(name=f"{tag}-p{i}", namespace="default"),
        spec=obj.PodSpec(requests={"cpu": float(cpu0 + 17 * i)},
                         priority=(1000 - i if prio is None else prio)))
        for i in range(n)]


def _config(**kw):
    kw.setdefault("max_batch_size", 24)
    kw.setdefault("batch_window_s", 0.3)
    kw.setdefault("backoff_initial_s", 0.05)
    kw.setdefault("backoff_max_s", 0.2)
    return SchedulerConfig(**kw)


def _run_tenants(fuse, config, pod_counts, *, weights=None, timeout=120.0):
    """One coordinator run → (per-tenant placements, final metrics)."""
    names = [f"t{i}" for i in range(len(pod_counts))]
    tenants = [Tenant(name=nm, store=_mk_store(),
                      weight=(weights[i] if weights else 1.0))
               for i, nm in enumerate(names)]
    coord = TenantFusionCoordinator(tenants, config, fuse=fuse)
    try:
        coord.start()
        want = 0
        for nm, n in zip(names, pod_counts):
            coord.store(nm).create_many(_pods(n, nm))
            want += n
        placements = _wait_bound(coord, names, want, timeout)
        return placements, coord.metrics()
    finally:
        coord.shutdown()


def _wait_bound(coord, names, want, timeout=120.0):
    deadline = time.monotonic() + timeout
    placements = {}
    while time.monotonic() < deadline:
        placements = {
            nm: {p.metadata.name: p.spec.node_name
                 for p in coord.store(nm).list("Pod") if p.spec.node_name}
            for nm in names}
        if sum(len(v) for v in placements.values()) == want:
            return placements
        time.sleep(0.05)
    raise AssertionError(f"bound {placements}, wanted {want}")


# ---- fair-share slot apportionment (engine/queue.weighted_gather) ---------


def test_weighted_gather_properties():
    """Invariants: never over capacity, never over a tenant's demand,
    leftover slots recirculate to tenants with unmet demand."""
    for demands, weights, cap in [
        ([10, 10, 10], [1, 1, 1], 12),
        ([3, 0, 9], [1, 1, 1], 24),
        ([5, 5], [3, 1], 4),
        ([7], [1], 100),
        ([2, 2, 2, 2], [1, 2, 3, 4], 5),
    ]:
        alloc = weighted_gather(demands, weights, cap)
        assert len(alloc) == len(demands)
        assert sum(alloc) <= cap
        assert all(0 <= a <= d for a, d in zip(alloc, demands))
        # work-conserving: capacity left over only when demand ran out
        assert sum(alloc) == min(cap, sum(demands))


def test_weighted_gather_is_proportional():
    assert weighted_gather([100, 100, 100], [2, 1, 1], 100) == [50, 25, 25]


def test_hot_tenant_cannot_starve_the_fused_slot():
    """The fairness claim: one tenant with a huge backlog takes only
    its share plus what the others left on the table."""
    assert weighted_gather([1000, 5, 5], [1, 1, 1], 30) == [20, 5, 5]


def test_zero_weight_tenant_gets_only_leftovers():
    assert weighted_gather([10, 10], [1, 0], 12) == [10, 2]
    assert weighted_gather([20, 10], [1, 0], 12) == [12, 0]


# ---- bucket-major slot apportionment (engine/queue.bucket_major_quotas) ---


def test_bucket_major_quotas_groups_and_apportions():
    """Tenants group by their pod-pad bucket in ascending-bucket order;
    each group runs the full weighted_gather discipline over the round
    capacity; zero-demand tenants are absent."""
    demands = [5, 0, 40, 8, 30]
    weights = [1.0, 1.0, 2.0, 1.0, 1.0]
    buckets = [16, 0, 48, 16, 48]
    out = bucket_major_quotas(demands, weights, 24, buckets)
    assert [b for b, _i, _q in out] == [16, 48]
    b16, b48 = out
    assert b16[1] == [0, 3] and b16[2] == [5, 8]      # demand-capped
    assert b48[1] == [2, 4]
    assert b48[2] == weighted_gather([40, 30], [2.0, 1.0], 24)
    assert sum(b48[2]) == 24                           # work-conserving
    for _b, idxs, quotas in out:
        assert all(q <= demands[i] for i, q in zip(idxs, quotas))


def test_bucket_major_quotas_single_bucket_matches_global_gather():
    """Homogeneous demand degenerates to the ISSUE 16 global gather —
    the backward-compatibility property the bit-identity tests lean
    on."""
    demands, weights = [10, 10, 10], [1.0, 1.0, 1.0]
    out = bucket_major_quotas(demands, weights, 12, [16, 16, 16])
    assert out == [(16, [0, 1, 2], weighted_gather(demands, weights, 12))]


# ---- fused vs sequential bit-identity -------------------------------------


@pytest.mark.parametrize("mode,kw", [
    ("sync", dict(pipeline=False)),
    ("pipelined", dict(pipeline=True)),
    ("upload", dict(device_resident=False)),
])
def test_fused_matches_sequential_per_mode(mode, kw):
    """The tentpole claim: per tenant, the fused coordinator's
    placements equal the sequential (fuse=0) coordinator's, in every
    engine config — and fusion genuinely engaged (lanes served by a
    shared vmapped dispatch, minus any counted mid-tranche races)."""
    counts = (10, 10, 10)
    seq, _m_seq = _run_tenants(0, _config(**kw), counts)
    fused, m_f = _run_tenants(8, _config(**kw), counts)
    assert fused == seq, mode
    assert m_f["tenant_rounds"] >= 1
    assert m_f["tenant_lanes_fused"] >= 2, m_f
    assert m_f["tenant_lanes_fused"] + m_f["tenant_solo_fallbacks"] >= 3


def test_ragged_tenant_batches_bit_identical():
    """Ragged tenant demand (3/11/6 pods) harmonizes by masked-row
    padding — the pinned pad invariant — and every tenant's placements
    still equal its sequential run's."""
    counts = (3, 11, 6)
    seq, _ = _run_tenants(0, _config(), counts)
    fused, m_f = _run_tenants(8, _config(), counts)
    assert fused == seq
    assert m_f["tenant_lanes_fused"] >= 2, m_f


def test_fused_issues_fewer_dispatches():
    """The perf shape at test scale: one fused tranche serves T lanes,
    so total dispatches collapse versus the sequential run (the >=5x
    ledger claim lives at the bench shape, tools/bench_tenants.py)."""
    counts = (8, 8, 8, 8)
    _seq, m_s = _run_tenants(0, _config(), counts)
    _fused, m_f = _run_tenants(8, _config(), counts)
    assert m_f["steps_dispatched_total"] < m_s["steps_dispatched_total"], (
        m_f["steps_dispatched_total"], m_s["steps_dispatched_total"])


def test_mid_tranche_race_falls_back_solo_and_stays_identical():
    """A delta landing between a lane's submit and the fused dispatch
    (cache version moved) must NOT be served from the stale staged
    snapshot: the lane re-dispatches solo against its own live cache,
    the race is counted, and placements still equal the sequential
    run's."""
    counts = (6, 6, 6)
    seq, _ = _run_tenants(0, _config(), counts)
    names = ["t0", "t1", "t2"]
    tenants = [Tenant(name=nm, store=_mk_store()) for nm in names]
    coord = TenantFusionCoordinator(tenants, _config(), fuse=8)
    fired = []

    def hook():
        if not fired:
            fired.append(1)
            coord.engine("t0").cache.version += 1  # a mid-tranche delta

    coord.mux._pre_dispatch_hook = hook
    try:
        coord.start()
        for nm, n in zip(names, counts):
            coord.store(nm).create_many(_pods(n, nm))
        fused = _wait_bound(coord, names, sum(counts))
        m = coord.metrics()
    finally:
        coord.shutdown()
    assert fused == seq
    assert fired
    assert m["tenant_races"] >= 1, m
    assert m["tenant_solo_fallbacks"] >= 1, m
    assert m["t0_tenant_races"] >= 1, {k: v for k, v in m.items()
                                       if "race" in k}


# ---- attribution never crosses tenants ------------------------------------


def test_provenance_and_journal_attribution_stay_per_tenant():
    """Zero cross-tenant leakage: with the journal armed, every bound
    pod's provenance record carries the OWNING tenant's profile, only
    the owning engine holds the record, and the journal's batch events
    are tagged per tenant profile."""
    from minisched_tpu.obs import journal as journal_mod

    journal_mod.configure("1")
    names = ["t0", "t1"]
    tenants = [Tenant(name=nm, store=_mk_store()) for nm in names]
    coord = TenantFusionCoordinator(tenants, _config(), fuse=8)
    try:
        coord.start()
        for nm in names:
            coord.store(nm).create_many(_pods(5, nm))
        _wait_bound(coord, names, 10)
        for nm, other in (("t0", "t1"), ("t1", "t0")):
            for i in range(5):
                key = f"default/{nm}-p{i}"
                rec = coord.engine(nm).provenance(key)
                assert rec is not None, key
                assert rec["profile"] == nm, rec
                assert rec["pod"] == key
                assert coord.engine(other).provenance(key) is None, key
                assert coord.provenance(key)["profile"] == nm
        profiles = {e.get("profile")
                    for e in journal_mod.JOURNAL.entries()
                    if e["kind"].startswith("batch")}
        assert profiles <= set(names), profiles
    finally:
        coord.shutdown()
        journal_mod.configure("")


# ---- bucket-major grouping: mixed-size tenants fuse per bucket ------------


def _wait_pending(coord, names, counts, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        got = [coord.engine(nm).queue.pending_count() for nm in names]
        if got == list(counts):
            return
        time.sleep(0.02)
    raise AssertionError(f"pending {got}, wanted {counts}")


def _flat_pods(n, tag, *, cpu0=100):
    """Pods of 8 cycled request shapes: constant priority and a
    non-digit name tail (name_suffix stays -1), so only the request
    shape distinguishes them."""
    return [obj.Pod(
        metadata=obj.ObjectMeta(name=f"{tag}-{i}x", namespace="default"),
        spec=obj.PodSpec(requests={"cpu": float(cpu0 + 17 * (i % 8))},
                         priority=0))
        for i in range(n)]


def _drain_rounds(coord):
    while any(eng.queue.pending_count()
              for eng in coord.engines.values()):
        if not coord.serve_round():
            time.sleep(0.02)


def test_mixed_bucket_round_fuses_two_groups():
    """Heterogeneous tenant sizes (two tenants at a small pod bucket,
    two at a large one) no longer pad to one global bucket: one serve
    round issues one fused dispatch PER bucket group — >=2 groups, zero
    solo regressions — and placements equal the sequential
    coordinator's. A warm-up wave binds first, so the mixed round
    meets nodes that already carry load."""
    names = [f"t{i}" for i in range(4)]
    counts = (3, 3, 20, 20)   # buckets: step_bucket(3)=16, step_bucket(20)=24
    warm = 8                  # one pod per class row
    assert step_bucket(3, 16) != step_bucket(20, 16)

    def scenario(fuse):
        tenants = [Tenant(name=nm, store=_mk_store()) for nm in names]
        # Capacity >= the widest bucket group's total demand (20+20), so
        # the large tenants pop their FULL backlog in the mixed round
        # and genuinely pad to the 24-bucket while the small tenants pad
        # to 16 — two shape groups in one round.
        coord = TenantFusionCoordinator(
            tenants, _config(max_batch_size=48), fuse=fuse)
        try:
            for eng in coord.engines.values():
                eng._shared.ensure_started()
            for nm in names:
                coord.store(nm).create_many(_flat_pods(warm, f"{nm}-warm"))
            _wait_pending(coord, names, (warm,) * len(names))
            _drain_rounds(coord)
            _wait_bound(coord, names, warm * len(names))
            for nm, n in zip(names, counts):
                coord.store(nm).create_many(_flat_pods(n, nm))
            _wait_pending(coord, names, counts)
            assert coord.serve_round()
            _drain_rounds(coord)
            return (_wait_bound(coord, names,
                                warm * len(names) + sum(counts)),
                    coord.metrics())
        finally:
            coord.shutdown()

    seq, _ = scenario(0)
    fused, m = scenario(8)
    assert fused == seq
    assert m["tenant_groups_round_max"] >= 2, m
    assert m["tenant_solo_fallbacks"] == 0, m
    assert m["tenant_lanes_fused"] >= 4, m


# ---- per-tenant shed budgets (MINISCHED_OVERLOAD profile overrides) -------


def test_quiet_tenant_shed_budget_holds_under_noisy_burst():
    """A noisy tenant's overload burst sheds only ITS low-priority
    arrivals (profile-scoped ``shed_priority`` override); the quiet
    tenant's identical-priority pods all bind. hold/probation are
    latched high so the forced level cannot recover mid-test."""
    overload.configure("shed_priority=0,hold=99,probation=99;"
                       "noisy:shed_priority=500")
    names = ["quiet", "noisy"]
    tenants = [Tenant(name=nm, store=_mk_store()) for nm in names]
    coord = TenantFusionCoordinator(tenants, _config(), fuse=8)
    try:
        coord.start()
        # the noisy tenant's controller is at the shedding rung
        coord.engine("noisy")._overload.level = 2
        coord.store("quiet").create_many(_pods(4, "quiet", prio=0))
        coord.store("noisy").create_many(_pods(4, "noisy", prio=0))
        coord.store("noisy").create_many(
            _pods(2, "noisy-hi", prio=1000, cpu0=200))
        # quiet's low pods + noisy's high pods bind; noisy's low
        # arrivals went to the counted shed lane
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            quiet_bound = {p.metadata.name
                           for p in coord.store("quiet").list("Pod")
                           if p.spec.node_name}
            noisy_bound = {p.metadata.name
                           for p in coord.store("noisy").list("Pod")
                           if p.spec.node_name}
            if len(quiet_bound) == 4 and len(noisy_bound) >= 2:
                break
            time.sleep(0.05)
        assert len(quiet_bound) == 4, quiet_bound
        assert {f"noisy-hi-p{i}" for i in range(2)} <= noisy_bound
        m = coord.metrics()
        assert m["noisy_shed_total"] >= 1, m
        assert m["quiet_shed_total"] == 0, m
    finally:
        coord.shutdown()


def test_shed_priority_override_grammar():
    """The extended MINISCHED_OVERLOAD grammar: base knobs, then
    ``profile:shed_priority=N`` segments."""
    from minisched_tpu.engine.overload import parse_spec_overrides

    knobs, ov = parse_spec_overrides(
        "shed_priority=100,hold=3;noisy:shed_priority=500;b:shed_priority=0")
    assert knobs["shed_priority"] == 100 and knobs["hold"] == 3
    assert ov == {"noisy": 500, "b": 0}
    knobs, ov = parse_spec_overrides("1")
    assert ov == {}
    with pytest.raises(ValueError):
        parse_spec_overrides("1;noisy:hold=3")       # only shed_priority
    with pytest.raises(ValueError):
        parse_spec_overrides("1;:shed_priority=3")   # empty profile
    with pytest.raises(ValueError):
        parse_spec_overrides("1;noisy=3")            # malformed segment
    overload.configure("shed_priority=7;noisy:shed_priority=900")
    assert overload.OVERLOAD.shed_priority_for("noisy") == 900
    assert overload.OVERLOAD.shed_priority_for("anyone-else") == 7


# ---- env knob -------------------------------------------------------------


def test_tenants_fuse_env_parsing(monkeypatch):
    monkeypatch.delenv("MINISCHED_TENANTS_FUSE", raising=False)
    assert tenants_fuse_from_env() == 0
    monkeypatch.setenv("MINISCHED_TENANTS_FUSE", "8")
    assert tenants_fuse_from_env() == 8
    monkeypatch.setenv("MINISCHED_TENANTS_FUSE", "junk")
    assert tenants_fuse_from_env() == 0
    monkeypatch.setenv("MINISCHED_TENANTS_FUSE", "")
    assert tenants_fuse_from_env() == 0


def test_coordinator_rejects_duplicate_and_empty_tenants():
    with pytest.raises(ValueError):
        TenantFusionCoordinator([], fuse=0)
    with pytest.raises(ValueError):
        TenantFusionCoordinator(
            [Tenant(name="x", store=_mk_store()),
             Tenant(name="x", store=_mk_store())], fuse=0)
