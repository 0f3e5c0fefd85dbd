"""Feature-encoding and node-cache tests (SURVEY §7 step 2)."""
import numpy as np

from minisched_tpu.encode import NodeFeatureCache, encode_pods, name_suffix_digit, pair_hash
from minisched_tpu.encode.cache import bucket_for
from minisched_tpu.state.objects import (
    ContainerPort,
    Node,
    NodeSpec,
    NodeStatus,
    ObjectMeta,
    Pod,
    PodSpec,
    Taint,
    Toleration,
)


def node(name, cpu=4000, labels=None, taints=None, unsched=False):
    return Node(metadata=ObjectMeta(name=name, labels=labels or {}),
                spec=NodeSpec(unschedulable=unsched, taints=taints or []),
                status=NodeStatus(allocatable={"cpu": cpu, "memory": 16 << 30, "pods": 110}))


def pod(name, cpu=100, ns="default", **spec_kw):
    return Pod(metadata=ObjectMeta(name=name, namespace=ns),
               spec=PodSpec(requests={"cpu": cpu}, **spec_kw))


def test_name_suffix_last_char_semantics():
    # Reference nodenumber.go:50-64 uses the LAST character only.
    assert name_suffix_digit("node1") == 1
    assert name_suffix_digit("node10") == 0
    assert name_suffix_digit("node") == -1
    assert name_suffix_digit("") == -1


def test_bucket_ladder():
    assert bucket_for(1) == 16
    assert bucket_for(16) == 16
    assert bucket_for(17) == 32
    assert bucket_for(50_000) == 65536


def test_cache_upsert_remove_reuse():
    c = NodeFeatureCache()
    c.upsert_node(node("a", cpu=1000))
    c.upsert_node(node("b", cpu=2000))
    ia, ib = c.row_of("a"), c.row_of("b")
    assert ia != ib
    c.remove_node("a")
    nf, names = c.snapshot()
    assert not nf.valid[ia]
    c.upsert_node(node("c", cpu=3000))
    assert c.row_of("c") == ia  # slot reuse
    nf, names = c.snapshot()
    assert names[ia] == "c"
    assert nf.allocatable[ia, 0] == 3000


def test_cache_growth_preserves_rows():
    c = NodeFeatureCache(capacity=4)
    for i in range(20):
        c.upsert_node(node(f"n{i}", cpu=1000 + i))
    nf, names = c.snapshot()
    for i in range(20):
        row = c.row_of(f"n{i}")
        assert nf.allocatable[row, 0] == 1000 + i


def test_bind_accounting_and_unbind():
    c = NodeFeatureCache()
    c.upsert_node(node("n1", cpu=1000))
    p = pod("p1", cpu=300)
    p.spec.node_name = "n1"
    p.spec.ports = [ContainerPort(host_port=8080)]
    c.account_bind(p)
    nf, _ = c.snapshot()
    row = c.row_of("n1")
    assert nf.free[row, 0] == 700
    assert nf.free[row, 2] == 109  # implicit pods slot
    assert 8080 in nf.used_ports[row]
    # double-account is a no-op
    c.account_bind(p)
    nf, _ = c.snapshot()
    assert nf.free[row, 0] == 700
    c.account_unbind(p.key)
    nf, _ = c.snapshot()
    assert nf.free[row, 0] == 1000
    assert 8080 not in nf.used_ports[row]


def test_account_bind_bulk_matches_sequential():
    """The bulk assume path (one lock, encoder request rows reused) must
    leave the cache in exactly the state the per-pod path produces —
    including volume-bearing pods, which take the claim-table slow path."""
    from minisched_tpu.state.objects import VolumeClaim

    def build(pods, bulk):
        c = NodeFeatureCache()
        for i in range(4):
            c.upsert_node(node(f"n{i}", cpu=10_000))
        if bulk:
            eb = encode_pods(pods, 16, registry=c.registry)
            items = [(p, f"n{i % 4}") for i, p in enumerate(pods)]
            c.account_bind_bulk(items, req_rows=eb.pf.requests[:len(pods)])
        else:
            for i, p in enumerate(pods):
                c.account_bind(p, node_name=f"n{i % 4}")
        return c

    pods = [pod(f"b{i}", cpu=100 + i * 10) for i in range(6)]
    pods[0].metadata.labels = {"app": "web", "tier": "a"}
    pods[1].metadata.labels = {"app": "web", "tier": "a"}  # shared signature
    pods[1].metadata.namespace = "other"  # distinct ns, same label signature
    pods[2].spec.ports = [ContainerPort(host_port=9000)]
    pods[3].spec.volumes = [VolumeClaim(claim_name="cl-a")]
    pods[4].spec.volumes = [VolumeClaim(claim_name="cl-a")]
    pods[5].spec.pod_group, pods[5].spec.pod_group_min = "gg", 1

    seq, blk = build(pods, bulk=False), build(pods, bulk=True)
    nf_s, _ = seq.snapshot()
    nf_b, _ = blk.snapshot()
    assert np.array_equal(nf_s.free, nf_b.free)
    assert np.array_equal(nf_s.used_ports, nf_b.used_ports)
    assert seq.claim_node_row("default/cl-a") == blk.claim_node_row("default/cl-a")
    assert seq.gang_bound_count("default/gg") == blk.gang_bound_count("default/gg")
    # assigned-pod corpus parity (fast path fills ns_hash/label_pairs via
    # memoized rows): compare per-pod rows, which may sit at different
    # physical indices between the two allocation orders
    af_s, af_b = seq.snapshot_assigned(), blk.snapshot_assigned()

    def rows(c, af):
        return {k: (af.node_row[a], af.ns_hash[a], tuple(af.label_pairs[a]))
                for k, a in c._a_row.items()}

    assert rows(seq, af_s) == rows(blk, af_b)
    # unbind symmetry: releasing every pod restores full capacity both ways
    for c in (seq, blk):
        for p in pods:
            c.account_unbind(p.key)
        nf, _ = c.snapshot()
        assert np.array_equal(nf.free, nf.allocatable[: nf.free.shape[0]])


def test_node_update_recomputes_free_with_bound_pods():
    c = NodeFeatureCache()
    c.upsert_node(node("n1", cpu=1000))
    p = pod("p1", cpu=400)
    p.spec.node_name = "n1"
    c.account_bind(p)
    # allocatable shrinks; free must reflect bound pod against new allocatable
    c.upsert_node(node("n1", cpu=800))
    nf, _ = c.snapshot()
    assert nf.free[c.row_of("n1"), 0] == 400


def test_pod_encoding_fields():
    p = pod("web3", cpu=250)
    p.spec.node_selector = {"disk": "ssd"}
    p.spec.tolerations = [Toleration(key="dedicated", operator="Equal",
                                     value="ml", effect="NoSchedule")]
    p.spec.ports = [ContainerPort(host_port=9000)]
    pf, gf, naf, _gang = encode_pods([p], 4)
    assert pf.valid.tolist() == [True, False, False, False]
    assert pf.requests[0, 0] == 250
    assert pf.requests[0, 2] == 1  # implicit pods:1
    assert pf.name_suffix[0] == 3
    assert pf.na_group[0] == 0  # node_selector landed in a group
    assert naf.sel_pairs[0, 0] == pair_hash("disk", "ssd")
    assert pf.ports[0, 0] == 9000


def test_overflow_reporting():
    p = pod("p")
    p.spec.node_selector = {f"k{i}": "v" for i in range(10)}
    overflow = []
    encode_pods([p], 2, overflow=overflow)
    assert any("node_selector" in o for o in overflow)


def test_taint_encoding():
    overflow = []
    c = NodeFeatureCache()
    c.upsert_node(node("n", taints=[Taint(key="a", value="b", effect="NoExecute")]))
    nf, _ = c.snapshot()
    row = c.row_of("n")
    assert nf.taint_pairs[row, 0] == pair_hash("a", "b")
    assert nf.taint_effects[row, 0] == 3  # NoExecute


def test_snapshot_versioned_static_elision_and_atomicity():
    """snapshot_versioned returns the static version observed under its
    own lock (the snapshot's topology refresh bumps it — a version read
    BEFORE the call would key stale device copies), and elides static
    leaf copies only on an exact (version, pad) match."""
    c = NodeFeatureCache()
    c.upsert_node(node("n0"))
    nf, names, sv, incs = c.snapshot_versioned()
    assert all(getattr(nf, f) is not None for f in nf._fields)

    # Hit: same version+pad → static leaves elided, dynamic ones present.
    nf2, _, sv2, _ = c.snapshot_versioned(known_static=(sv, nf.free.shape[0]))
    assert sv2 == sv
    assert nf2.allocatable is None and nf2.topo_domains is None
    assert nf2.free is not None and nf2.used_ports is not None

    # A new topology key registered since the last snapshot bumps the
    # static version INSIDE snapshot_versioned → the stale key must miss
    # (full copies returned) and the new version must be the one returned.
    c.registry.index_of("example.com/rack")
    nf3, _, sv3, _ = c.snapshot_versioned(known_static=(sv, nf.free.shape[0]))
    assert sv3 > sv
    assert nf3.topo_domains is not None  # fresh copy, not elided

    # Bind accounting must NOT bump the static version.
    c.account_bind(pod("p0", cpu=10), node_name="n0")
    _, _, sv4, _ = c.snapshot_versioned()
    assert sv4 == sv3


def test_anti_term_table_bind_unbind_refcount():
    """The cache's anti-class table must refcount per class: two pods
    with the same term share ONE class, tag both corpus rows with it, and
    keep it live until BOTH leave; anti_classes_for matches only pods the
    selector + namespace actually cover."""
    from minisched_tpu.state.objects import (Affinity, LabelSelector,
                                             PodAffinityTerm, PodAntiAffinity)

    zone = "topology.kubernetes.io/zone"
    c = NodeFeatureCache()
    c.upsert_node(node("an-1", labels={zone: "za"}))
    anti = Affinity(pod_anti_affinity=PodAntiAffinity(required=[
        PodAffinityTerm(label_selector=LabelSelector(match_labels={"a": "x"}),
                        topology_key=zone)]))
    p1, p2 = pod("ap1", affinity=anti), pod("ap2", affinity=anti)
    c.account_bind(p1, node_name="an-1")
    c.account_bind(p2, node_name="an-1")
    assert c.anti_class_count() == 1

    victim = pod("vic")
    victim.metadata.labels = {"a": "x"}
    classes, reason = c.anti_classes_for(victim)
    assert reason is None and len(classes) == 1
    key_idx, tag = classes[0]
    assert c.registry.keys()[key_idx] == zone
    af = c.snapshot_assigned()
    tagged = af.valid & (af.label_pairs == tag).any(axis=1)
    assert int(tagged.sum()) == 2  # each owner's row carries the tag
    other_ns = pod("vic2", ns="other")
    other_ns.metadata.labels = {"a": "x"}
    assert c.anti_classes_for(other_ns) == ((), None)  # term ns = owner's
    nomatch = pod("vic3")
    nomatch.metadata.labels = {"a": "y"}
    assert c.anti_classes_for(nomatch) == ((), None)

    c.account_unbind(p1.key)
    assert c.anti_classes_for(victim) == (classes, None)  # p2 still holds it
    c.account_unbind(p2.key)
    assert c.anti_classes_for(victim) == ((), None)
    assert c.anti_class_count() == 0


def _anti_term(ns_list, key="kubernetes.io/hostname", labels=None):
    from minisched_tpu.state.objects import (Affinity, LabelSelector,
                                             PodAffinityTerm, PodAntiAffinity)

    return Affinity(pod_anti_affinity=PodAntiAffinity(required=[
        PodAffinityTerm(label_selector=LabelSelector(
            match_labels=labels or {"color": "green"}),
            topology_key=key, namespaces=list(ns_list))]))


def test_multi_namespace_group_matches_exactly():
    """A term naming two namespaces is ONE group whose namespace set the
    device matches exactly (ops.topology.group_assigned_match): pods of
    either namespace, and no other; the set pads to the pow2 bucket of
    the batch's widest group, and a single-namespace batch keeps one
    slot."""
    import jax.numpy as jnp

    from minisched_tpu.encode.features import _h, empty_assigned_features
    from minisched_tpu.ops.topology import group_assigned_match

    p = pod("g", ns="sched-1", affinity=_anti_term(["sched-1", "sched-0"]))
    p.metadata.labels = {"color": "green"}
    eb = encode_pods([p], 8)
    g = int(eb.pf.anti_req_group[0, 0])
    assert g >= 0 and eb.gf.ns_set.shape[1] == 2
    assert sorted(eb.gf.ns_set[g].tolist()) == sorted(
        [_h("sched-0"), _h("sched-1")])
    af = empty_assigned_features(8)
    green = pair_hash("color", "green")
    for a, (ns, lab) in enumerate([("sched-0", green), ("sched-1", green),
                                   ("sched-2", green), ("sched-0", 0),
                                   ("", green)]):
        af.valid[a] = True
        af.ns_hash[a] = _h(ns) if ns else 0
        af.label_pairs[a, 0] = lab
    gf = type(eb.gf)(*(jnp.asarray(x) for x in eb.gf))
    af_d = type(af)(*(jnp.asarray(x) for x in af))
    match = np.asarray(group_assigned_match(gf, af_d))[g]
    assert match[:6].tolist() == [True, True, False, False, False, False]

    q = pod("h", ns="sched-1", affinity=_anti_term([]))
    assert encode_pods([q], 8).gf.ns_set.shape[1] == 1


def test_anti_term_beyond_namespace_slots_fails_closed():
    """An anti term naming more namespaces than max_term_namespaces would
    under-repel if truncated: the pod fails closed; an affinity term may
    narrow (it only rejects more) and does not."""
    from minisched_tpu.encode.features import DEFAULT_ENCODING

    many = [f"ns-{i}" for i in range(DEFAULT_ENCODING.max_term_namespaces + 1)]
    hard: dict = {}
    encode_pods([pod("a", affinity=_anti_term(many))], 8, hard_failed=hard)
    assert [plugin for plugin, _r in hard[0]] == ["InterPodAffinity"]
    hard = {}
    encode_pods([pod("b", affinity=_anti_term(many[:4]))], 8,
                hard_failed=hard)
    assert hard == {}


def test_anti_class_slots_bucket_and_overflow_fail_closed():
    """Stamped anti classes pad to the pow2 bucket of the most-repelled
    pod (0 when none is repelled); a pod repelled by more classes than
    max_anti_classes fails closed with its own reason."""
    from minisched_tpu.encode.features import DEFAULT_ENCODING

    S = DEFAULT_ENCODING.max_anti_classes
    p0, p1, p2 = pod("c0"), pod("c1"), pod("c2")
    classes = {p0.key: [], p1.key: [(0, 101), (0, 102), (0, 103)],
               p2.key: [(0, 200 + i) for i in range(S + 1)]}

    def fn(p):
        return tuple(classes[p.key]), None

    # distinct signatures: the class callback runs once per signature
    for i, p in enumerate((p0, p1, p2)):
        p.metadata.labels = {"k": str(i)}
    eb = encode_pods([p0], 8, anti_classes_fn=fn)
    assert eb.pf.anti_cls_group.shape == (8, 0)
    hard: dict = {}
    eb = encode_pods([p0, p1], 8, anti_classes_fn=fn, hard_failed=hard)
    assert eb.pf.anti_cls_group.shape == (8, 4) and hard == {}
    gids = eb.pf.anti_cls_group[1, :3]
    assert (gids >= 0).all() and (eb.gf.sel_pairs[gids, 0] == [101, 102,
                                                             103]).all()
    assert (eb.gf.ns_set[gids] == 0).all()  # owners counted anywhere
    hard = {}
    eb = encode_pods([p0, p1, p2], 8, anti_classes_fn=fn, hard_failed=hard)
    assert eb.pf.anti_cls_group.shape == (8, S)
    assert list(hard) == [2] and hard[2][0][0] == "InterPodAffinity"
    assert "classes" in hard[2][0][1]


def test_step_bucket_geometry():
    from minisched_tpu.encode.cache import step_bucket

    # power-of-two below/at 2048
    assert step_bucket(1) == 16
    assert step_bucket(17) == 32
    assert step_bucket(2048) == 2048
    # eighth-steps above: ≤12.5% waste, multiples of 256
    assert step_bucket(2049) == 2304
    assert step_bucket(10_000) == 10240
    assert step_bucket(50_000) == 53248
    assert step_bucket(65_536) == 65536
    for n in (3000, 10_000, 50_000, 100_000, 123_457):
        b = step_bucket(n)
        assert b >= n and b % 256 == 0
        assert b <= n * 1.125, (n, b)
    # monotone, idempotent on its own outputs
    assert step_bucket(step_bucket(50_000)) == step_bucket(50_000)
    # a minimum above 2048 is a hard floor (pinned shapes), never
    # undercut by the eighth-step ladder
    assert step_bucket(1, 4096) == 4096
    assert step_bucket(3000, 4096) == 4096
    assert step_bucket(5000, 4096) == 5120
    # a non-pow2 minimum is rounded UP to a power of two first — the
    # alignment guarantees (256-multiples, pow2-mesh divisibility) derive
    # from pow2 octaves and would silently break otherwise
    assert step_bucket(1, 24) == 32
    assert step_bucket(100, 24) == 128
    for n in (3000, 10_000, 50_000):
        assert step_bucket(n, 3000) % 256 == 0


def test_rows_high_water_tracks_allocations():
    from minisched_tpu.encode.cache import NodeFeatureCache, step_bucket
    from minisched_tpu.state import objects as obj

    c = NodeFeatureCache(capacity=16)
    assert c.rows_high_water() == 0
    for i in range(10):
        c.upsert_node(obj.Node(metadata=obj.ObjectMeta(name=f"n{i}"),
                               status=obj.NodeStatus(
                                   allocatable={"cpu": 1000.0})))
    assert c.rows_high_water() == 10
    # deletes never shrink the mark (monotonic: keeps pads stable)
    c.remove_node("n9")
    assert c.rows_high_water() == 10
    # snapshot at the tight bucket is legal and row-aligned
    nf, names = c.snapshot(pad=step_bucket(c.rows_high_water()))
    assert nf.valid.shape[0] == 16 and len(names) == 16
    # callable pad: resolved from the high-water mark UNDER the lock
    nf2, names2 = c.snapshot(pad=lambda hw: step_bucket(max(hw, 1)))
    assert nf2.valid.shape[0] == 16
    af = c.snapshot_assigned(pad=lambda hw: step_bucket(max(hw, 1)))
    assert af.valid.shape[0] == 16
    # assigned-corpus twin
    p = obj.Pod(metadata=obj.ObjectMeta(name="p0", namespace="d"),
                spec=obj.PodSpec(requests={"cpu": 1.0}))
    assert c.assigned_high_water() == 0
    c.account_bind(p, node_name="n0")
    assert c.assigned_high_water() == 1


def test_upsert_nodes_bulk_matches_per_node_exactly():
    """The memoized bulk-sync encoder (VERDICT r4 #7: restart-to-first-
    batch) must produce byte-identical snapshots to the per-node path —
    across labels, taints, images, annotations, unschedulable flags, and
    the hostname topo slot — and route already-present nodes through the
    re-encode path."""
    from minisched_tpu.state.objects import Taint as T

    def mk(i):
        return Node(
            metadata=ObjectMeta(
                name=f"bn{i}",
                labels=({"zone": f"z{i % 4}", "tier": "a"} if i % 3
                        else {"zone": f"z{i % 4}"}),
                annotations=({"scheduler.alpha.kubernetes.io/"
                              "preferAvoidPods": "x"} if i % 11 == 0
                             else {})),
            spec=NodeSpec(unschedulable=(i % 7 == 0),
                          taints=([T(key="ded", value="gpu")] if i % 5 == 0
                                  else [])),
            status=NodeStatus(allocatable={
                "cpu": 4000.0 + (i % 3) * 1000, "memory": 16 << 30,
                "pods": 110.0}))

    ns = [mk(i) for i in range(200)]
    c1, c2 = NodeFeatureCache(capacity=64), NodeFeatureCache(capacity=64)
    for n in ns:
        c1.upsert_node(n)
    c2.upsert_nodes_bulk(ns)
    f1, names1 = c1.snapshot(pad=256)
    f2, names2 = c2.snapshot(pad=256)
    assert names1 == names2
    for field, a, b in zip(f1._fields, f1, f2):
        assert np.array_equal(np.asarray(a), np.asarray(b)), field
    # re-upsert through the bulk path (existing rows) also matches
    ns[5].status.allocatable["cpu"] = 99000.0
    c1.upsert_node(ns[5])
    c2.upsert_nodes_bulk([ns[5]])
    fa, _ = c1.snapshot(pad=256)
    fb, _ = c2.snapshot(pad=256)
    for field, a, b in zip(fa._fields, fa, fb):
        assert np.array_equal(np.asarray(a), np.asarray(b)), field


def test_upsert_nodes_bulk_grows_capacity():
    c = NodeFeatureCache(capacity=4)
    c.upsert_nodes_bulk([node(f"g{i}") for i in range(100)])
    f, names = c.snapshot(pad=128)
    assert sum(1 for n in names if n) == 100
    assert int(np.asarray(f.valid).sum()) == 100


def test_upsert_nodes_bulk_duplicate_name_in_batch():
    """A name duplicated WITHIN one bulk batch must update, not ghost: one
    valid row, indexed, reflecting the LAST occurrence."""
    c = NodeFeatureCache(capacity=8)
    a = node("dup", cpu=1000)
    b = node("dup", cpu=9000)
    c.upsert_nodes_bulk([a, b])
    f, names = c.snapshot(pad=16)
    assert sum(1 for n in names if n == "dup") == 1
    assert int(np.asarray(f.valid).sum()) == 1
    row = names.index("dup")
    from minisched_tpu.state.objects import RESOURCE_INDEX
    assert float(np.asarray(f.allocatable)[row, RESOURCE_INDEX["cpu"]]) \
        == 9000.0


def test_pod_sig_keys_on_derived_rc_owned_not_owner_identity():
    """The encode-memo signature must not fragment per ReplicaSet: 100
    otherwise-identical pods owned by 100 different RS share ONE
    signature (only the derived rc_owned bit reaches the encoding),
    while owned vs bare pods differ."""
    from minisched_tpu.encode.features import _make_pod_sig
    from minisched_tpu.state.objects import OwnerReference

    sig = _make_pod_sig()

    def owned(i):
        return Pod(metadata=ObjectMeta(
            name=f"o{i}", namespace="d",
            owner_references=[OwnerReference(kind="ReplicaSet",
                                             name=f"rs{i}",
                                             controller=True)]),
            spec=PodSpec(requests={"cpu": 100.0}))

    sigs = {sig(owned(i)) for i in range(100)}
    assert len(sigs) == 1
    bare = Pod(metadata=ObjectMeta(name="b0", namespace="d"),
               spec=PodSpec(requests={"cpu": 100.0}))
    assert sig(bare) not in sigs
