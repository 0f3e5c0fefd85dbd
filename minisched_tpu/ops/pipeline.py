"""The batched scheduling step: one XLA program per profile.

Replaces THE hot loop of the reference — scheduleOne's nested
pods × nodes × plugins iteration plus per-pod argmax (reference
minisched/minisched.go:32-112, SURVEY §3.3) — with a single jitted function:

    filter masks (AND over plugins) → per-plugin scores → normalize →
    weighted sum → capacity-aware greedy assignment (select.py).

Per-plugin attribution survives batching (SURVEY §7 hard part "event
semantics under batching"): the step returns per-plugin reject counts per
pod — enough to reconstruct UnschedulablePlugins for requeue gating — and,
in explain mode, the full per-plugin mask/score stacks for the
explainability store (reference scheduler/plugin/resultstore capability).

Weights are applied after normalization, fixing the reference's TODO at
minisched/minisched.go:187; NormalizeScore runs once per plugin over the
full matrix, fixing the in-loop quirk at minisched.go:178-183.
"""
from __future__ import annotations

import os
import threading
from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..encode.features import DEFAULT_ENCODING, EncodingConfig
from ..plugins.base import PluginSet
from .gang import GangResult, gang_assign
from .select import NEG, greedy_assign_shortlist
from .topology import group_topology_state


class Decision(NamedTuple):
    """Output of one batched scheduling step (arrays padded to P/N buckets)."""

    chosen: jnp.ndarray           # (P,) i32 node row, -1 unassigned
    assigned: jnp.ndarray         # (P,) bool
    gang_rejected: jnp.ndarray    # (P,) bool — pod's gang missed quorum
    feasible_counts: jnp.ndarray  # (P,) i32 nodes passing all filters
    # Nodes passing all filters WITH the UNDEFERRED hard-spread check
    # (== feasible_counts when no in-scan caps are active). The in-scan
    # spread caps (ops/spreadcap.py) defer the static skew check into the
    # greedy scan, so a statically-over-skew pod shows feasible_counts>0
    # yet the scan cannot place it; the engine uses THIS count to tell
    # real in-batch contention (retry) from a static skew block
    # (terminal → preemption / unschedulable with PodTopologySpread).
    feasible_static: jnp.ndarray  # (P,) i32
    reject_counts: jnp.ndarray    # (F,P) i32 nodes rejected per filter plugin
    total_scores: jnp.ndarray     # explain: (P,N) f32 weighted sum (NEG on
    #   infeasible); else (0,N) placeholder — nothing on the scheduling
    #   path reads it, and a P×N output buffer is HBM the big configs need
    free_after: jnp.ndarray       # (N,R) f32
    # Per-pod × per-selector-GROUP state at the CHOSEN node, for the
    # engine's intra-batch skew arbitration (sequential spread semantics
    # the batch can't see: every pod scored against pre-batch counts, so
    # a burst can jointly violate a DoNotSchedule constraint none
    # violates alone). Group space, not constraint-slot space: the
    # arbitration must also count matching batch pods that carry no hard
    # constraint themselves. (P,G)/(G,) when the profile runs topology
    # plugins, else zero-size:
    spread_pre: jnp.ndarray       # (P,G) f32 pre-batch count in chosen's
    #                               domain under each group's key
    spread_dom: jnp.ndarray       # (P,G) i32 chosen node's domain id (-1
    #                               = node lacks the key / unassigned)
    spread_min: jnp.ndarray       # (G,) f32 pre-batch min over domains
    # Full per-domain tables for EXACT host-side skew arbitration (the
    # engine replays admissions sequentially against a running count
    # table + running min, matching what a sequential scheduler would
    # see): fetched on demand only when the batch carries hard
    # DoNotSchedule constraints. (G,D)/(G,D) when topology runs, else
    # zero-size:
    spread_cdom: jnp.ndarray      # (G,D) f32 pre-batch matching count per
    #                               domain
    spread_dexist: jnp.ndarray    # (G,D) bool domain exists on some node
    # (G,) bool — group's hard skew was enforced by the in-scan domain
    # caps THIS batch (ops/spreadcap.py; False everywhere when the caps
    # didn't run: pallas branch taken, sampling, auction, mesh, explain).
    # The host arbitration skips the skew replay — and the (G,D)
    # exact-table fetch — for these groups: the scan already judged every
    # admission against running counts in batch order.
    scan_groups: jnp.ndarray
    # (P,) bool — the shortlist-compressed scan's repair ledger
    # (ops/select.greedy_assign_shortlist): True where the step's
    # exactness certificate could not prove the true argmax was inside
    # the pod's top-K shortlist and a full-row rescan ran instead.
    # All-False when the shortlist stage is off (full scan, pallas,
    # auction, sharded/mesh, enforced domain caps).
    shortlist_repaired: jnp.ndarray
    # explain mode only (else zero-size placeholders):
    filter_masks: jnp.ndarray     # (F,P,N) bool per-plugin pass mask
    raw_scores: jnp.ndarray       # (S,P,N) f32 pre-normalize
    norm_scores: jnp.ndarray      # (S,P,N) f32 post-normalize, pre-weight


_STEP_CACHE: dict = {}

# Chunked-evaluation thresholds (see the memory-regime comment in step):
# chunk the filter/score stage when the (P,N) f32 matrix exceeds
# _CHUNK_WHEN_BYTES, targeting chunks of ~_CHUNK_TARGET_BYTES. Module-level
# so tests can force the chunked path at small shapes.
_CHUNK_WHEN_BYTES = 1 << 30
# 768M chunks measured 13% faster than 256M on the config-4 step at
# 50k x 10k (fewer lax.map iterations → less per-chunk launch overhead);
# 1.5G OOMs (22.3G > 15.75G HBM) — the per-chunk topology temps are ~6
# (C,N) f32 arrays, so the target must keep 6x target + the (P,N) score
# matrix + features inside HBM.
_CHUNK_TARGET_BYTES = 768 << 20
_CHUNK_MIN_PODS = 128


def _select_node_sample(nf, key, k: int) -> jnp.ndarray:
    """Pick K candidate node rows for a sampled step: top-K by a cheap
    LeastAllocated-flavored proxy (mean free fraction over resource axes)
    plus small random jitter, restricted to schedulable nodes. The proxy
    biases the sample toward nodes the default scorers would rank high;
    random jitter keeps the sample diverse so repeated batches don't
    hammer one node set. One (N,)-shaped pass + top_k — O(N log K)
    against the O(P×N×plugins) it saves."""
    alloc = jnp.maximum(nf.allocatable, 1e-9)
    frac = jnp.clip(nf.free, 0.0, None) / alloc
    score = frac.mean(axis=1)
    noise = jax.random.uniform(key, score.shape, maxval=0.05)
    ok = nf.valid & ~nf.unschedulable
    return jax.lax.top_k(jnp.where(ok, score + noise, -jnp.inf), k)[1]


def _gather_nodes(nf, idx):
    """NodeFeatures restricted to rows ``idx`` (topo_domains' node axis is
    axis 1; every other leaf leads with N). Domain ids are NOT remapped —
    they stay global so counts, minima and anti-forbid comparisons agree
    with state computed on the full cluster."""
    return nf._replace(
        topo_domains=nf.topo_domains[:, idx],
        **{f: getattr(nf, f)[idx]
           for f in nf._fields if f != "topo_domains"})


def build_step(plugin_set: PluginSet, *, explain: bool = False,
               cfg: EncodingConfig = DEFAULT_ENCODING,
               pallas: Optional[bool] = None,
               assignment: str = "greedy",
               assign_fn=None, assign_key=None,
               sample_nodes: Optional[int] = None,
               shortlist: Optional[int] = None,
               _raw: bool = False):
    """Compile the scheduling step for a plugin profile.

    Returns jitted ``step(eb, nf, af, key) -> Decision`` where eb is an
    encode.EncodedBatch (pod features + constraint groups), nf the node
    features, af the assigned-pod corpus. Shapes must be bucketed by the
    caller — each distinct bucket combination compiles once. Steps are
    memoized on the profile's traced behavior (plugin trace keys + weights +
    explain) so scheduler restarts and equivalent profiles reuse compiles.

    ``pallas``: use the pallas greedy-assignment kernel (ops/pallas_select).
    None = auto: on TPU when the node axis is lane-tiled. True off the TPU
    runs the same kernel interpreted (pallas_select.greedy_assign_kernel).
    The sharded builder passes False — a Mosaic kernel can't be GSPMD-
    partitioned.

    ``assignment``: "greedy" (default; priority-faithful sequential
    semantics, scan or pallas) or "auction" (ops/auction.py — parallel
    bidding rounds, aggregate-score-seeking, GSPMD-friendly; see its
    module docstring for the semantic deviations).

    ``assign_fn(masked_total, requests, free, group, min_count, key) ->
    GangResult`` overrides the whole assignment stage (the sharded builder
    supplies the shard_map chunked-gather scan,
    parallel/sharded_assign.py); ``assign_key`` is its hashable identity
    for the step cache.

    ``sample_nodes``: the percentage_of_nodes_to_score analog (upstream
    adaptive node sampling, surfaced ignored at the reference's
    scheduler_test.go:79). When set to K < N, a cheap device-side
    pre-pass picks the top-K candidate nodes (free-capacity proxy +
    random jitter over schedulable nodes) and the full filter/score/
    assign pipeline runs on the gathered (P, K) problem — the step cost
    is N-dominated, so a small batch stops paying the whole-cluster
    price. Topology/affinity state is computed on the FULL node set
    first (global domain ids, counts and minima stay exact) and only the
    per-node tables are gathered. Outputs are remapped to global node
    rows; ``free_after`` is returned full-size. A pod with zero feasible
    nodes IN THE SAMPLE must be re-evaluated by the caller against the
    full axis before being declared unschedulable (the engine's residual
    pass). Not supported with explain mode (per-node annotation columns
    would misalign) or a custom assign_fn.

    ``shortlist``: run the assignment SHORTLIST-COMPRESSED with this
    top-K width. Greedy takes the compressed scan
    (ops/select.greedy_assign_shortlist): the sequential P-step scan
    consults per-pod top-K candidate columns instead of the full node
    axis, with an exactness certificate per step and a counted full-row
    repair rescan where it fails. Auction takes the bid shortlist
    (ops/bid_select.auction_assign_shortlist): the bidding rounds'
    value reductions run over the same per-pod top-K candidates with a
    price-plateau certificate, and an uncertified bid reruns the full
    row under lax.cond, counted through the same repaired plane. Both
    are bit-identical to their full-row step for any K. Composes with
    node sampling (the shortlist then compresses the sampled axis), and
    yields to the full caps-scan at run time when enforced domain caps
    are present (lax.cond on ``caps.any_enforced``, like the pallas
    gate). An EXPLICIT ``pallas=True`` wins over the shortlist (the
    bench's kernel-vs-scan comparison depends on it); the auto-selected
    pallas kernel is gated off — the shortlist scan is the narrower
    sequential path the kernel existed to accelerate.

    ``_raw``: return the UN-JITTED trace function (and skip the step
    cache) — the tenant-fused builder vmaps it over a tenant axis and
    jits the vmapped program itself (build_tenant_step). The raw step
    additionally accepts ``w_vec``, an optional (S,) traced scorer
    weight vector replacing the python-float weights baked at build
    time; ``None`` (every existing caller) yields an identical jaxpr.
    """
    if assignment not in ("greedy", "auction"):
        raise ValueError(
            f"unknown assignment strategy {assignment!r}; "
            "expected 'greedy' or 'auction'")
    if sample_nodes is not None and (explain or assign_fn is not None):
        raise ValueError(
            "sample_nodes is incompatible with explain mode / assign_fn")
    if shortlist is not None and shortlist < 1:
        shortlist = None
    if shortlist is not None and assign_fn is not None:
        # A custom assign_fn keeps full (P,N) rows — a silently ignored
        # knob would let a config claim shortlist numbers it never ran.
        # (greedy takes ops/select.greedy_assign_shortlist; auction
        # takes the bid shortlist, ops/bid_select — both certified.)
        raise ValueError(
            "shortlist compression applies to the built-in assignments "
            "only (a custom assign_fn keeps full rows)")
    if assign_fn is not None and assign_key is None:
        # Without an explicit identity the cache would collide with the
        # default-assignment step and silently drop the custom stage.
        assign_key = assign_fn
    cache_key = (
        tuple(p.trace_key() for p in plugin_set.filter_plugins),
        tuple((p.trace_key(), plugin_set.weight_of(p))
              for p in plugin_set.score_plugins),
        explain, cfg, pallas, assignment, assign_key, sample_nodes,
        shortlist,
    )
    if not _raw:
        cached = _STEP_CACHE.get(cache_key)
        if cached is not None:
            return cached
    filters = plugin_set.filter_plugins
    scorers = plugin_set.score_plugins
    weights = [plugin_set.weight_of(p) for p in scorers]
    active = filters + scorers
    needs_topology = any(p.needs_topology for p in active)
    needs_node_affinity = any(p.needs_node_affinity for p in active)

    def step(eb, nf, af, key, w_vec=None) -> Decision:
        pf = eb.pf
        P = pf.valid.shape[0]
        N = nf.valid.shape[0]

        # Shared cycle state (reference CycleState / RunPreScorePlugins):
        # computed once, consumed by any plugin that declared a need.
        # ALWAYS computed on the full node set — topology domain ids,
        # counts and minima must stay global even under node sampling
        # (a subset min would let DoNotSchedule skew fail open).
        ctx = {"af": af, "gf": eb.gf, "naf": eb.naf}
        if needs_topology:
            num_domains = max(N, cfg.domain_buckets)
            ctx.update(group_topology_state(nf, af, eb.gf, num_domains))
        if needs_node_affinity:
            from ..plugins.nodeaffinity import (group_preferred_score,
                                               group_required_match)

            ctx["na_req_match"] = group_required_match(eb.naf, nf)
            ctx["na_pref_score"] = group_preferred_score(eb.naf, nf)

        sample_idx = None
        free_full = nf.free
        if sample_nodes is not None and sample_nodes < N:
            key, skey = jax.random.split(key)
            sample_idx = _select_node_sample(nf, skey, sample_nodes)
            # Inverse map for row-identity inputs: a claim pinned to a
            # node OUTSIDE the sample maps to row K (out of range), which
            # matches no sampled node — the pod then reads 0-feasible and
            # the caller's residual full-axis pass decides it.
            inv = jnp.full((N,), sample_nodes, dtype=jnp.int32)
            inv = inv.at[sample_idx].set(
                jnp.arange(sample_nodes, dtype=jnp.int32))
            cr = pf.claim_rows
            pf = pf._replace(claim_rows=jnp.where(
                cr >= 0, inv[jnp.clip(cr, 0, N - 1)], cr))
            eb = eb._replace(pf=pf)
            nf = _gather_nodes(nf, sample_idx)
            for k2 in ("counts_node", "dom_valid",
                       "na_req_match", "na_pref_score"):
                if k2 in ctx:
                    ctx[k2] = ctx[k2][:, sample_idx]
            N = sample_nodes

        # In-scan hard-spread enforcement (ops/spreadcap.py): only the
        # default greedy scan can carry the running domain counts — the
        # auction's parallel rounds and the sharded chunked-gather scan
        # keep the static filter verdict (+ host arbitration/repair).
        # Explain mode keeps it OFF too: the recorded per-node filter
        # verdicts must reflect upstream's static skew reasoning, not a
        # deferred always-pass. And SAMPLED steps keep it off: the
        # running min would cover only the sampled nodes' domains while
        # the filter's global-min check stands down — hard DoNotSchedule
        # would fail open device-side (the host arbitration would catch
        # it, but as revocation churn). The engine disables sampling for
        # gang batches already; hard-spread batches simply keep the
        # static filter + exact-arbitration/repair backstop when
        # sampled.
        caps = None
        if (needs_topology and "counts_dom" in ctx and not explain
                and sample_idx is None
                and assignment == "greedy" and assign_fn is None):
            from .spreadcap import build_domain_caps

            caps = build_domain_caps(eb.pf, eb.gf, nf,
                                     ctx["counts_dom"], ctx["dom_exists"])
            ctx["spread_scan_groups"] = caps.scan_groups
        spread_plugin = next(
            (f for f in filters if f.name == "PodTopologySpread"), None)

        def evaluate(pf_sub):
            """Filters + scores for a pod sub-batch against the full node
            axis → (masked_total, feasible_counts, reject_counts (F,C),
            explain lists). Every plugin op is pod-row-wise (normalize
            reduces over axis=1 only), so a sub-batch result equals the
            corresponding rows of the full-batch result."""
            valid_pair = pf_sub.valid[:, None] & nf.valid[None, :]
            # One pass over filters: each (C,N) mask contributes its
            # reject count and the running AND, then dies — outside
            # explain mode no list holds all F masks live at once.
            feasible = valid_pair
            rc: List[jnp.ndarray] = []
            masks: List[jnp.ndarray] = []
            for p in filters:
                # named_scope: pure metadata — labels the pass in an XLA
                # profile so a TPU capture lines up with the engine's
                # flight-recorder spans (obs) by name.
                with jax.named_scope(f"minisched.filter.{p.name}"):
                    m = p.filter(pf_sub, nf, ctx)
                rc.append((valid_pair & ~m).sum(axis=1).astype(jnp.int32))
                feasible = feasible & m
                if explain:
                    masks.append(m)
            feasible_counts = feasible.sum(axis=1).astype(jnp.int32)
            feasible_static = feasible_counts
            if caps is not None and spread_plugin is not None:
                # Undeferred spread verdict for terminal-vs-contention
                # classification (Decision.feasible_static): one extra
                # spread-filter pass — and only when a hard slot is
                # actually enforced this batch (lax.cond), so the
                # common all-soft topology batch never pays it (the
                # filter deferred nothing; static == deferred there).
                def _static_pass(args):
                    feas, pf_c = args
                    ctx_static = dict(ctx)
                    ctx_static.pop("spread_scan_groups", None)
                    m_static = spread_plugin.filter(pf_c, nf, ctx_static)
                    return (feas & m_static).sum(axis=1).astype(jnp.int32)

                feasible_static = jax.lax.cond(
                    caps.any_enforced, _static_pass,
                    lambda args: args[0].sum(axis=1).astype(jnp.int32),
                    (feasible, pf_sub))
            reject_counts = (jnp.stack(rc) if rc else
                             jnp.zeros((0, pf_sub.valid.shape[0]),
                                       dtype=jnp.int32))

            total = jnp.zeros_like(valid_pair, dtype=jnp.float32)
            raws, norms = [], []
            for i, (p, w) in enumerate(zip(scorers, weights)):
                with jax.named_scope(f"minisched.score.{p.name}"):
                    raw = p.score(pf_sub, nf, ctx).astype(jnp.float32)
                    norm = p.normalize(raw, feasible).astype(jnp.float32)
                # Traced per-lane weight (tenant fusion) or the baked
                # python float — multiplying equal f32 values is
                # deterministic, so the two paths stay bit-identical.
                wv = w if w_vec is None else w_vec[i]
                total = total + wv * norm
                if explain:
                    raws.append(raw)
                    norms.append(norm)
            return (jnp.where(feasible, total, NEG), feasible_counts,
                    feasible_static, reject_counts, masks, raws, norms)

        # Memory regime: the per-slot topology/affinity math materializes
        # several (P,N) f32 temps at once; at config-4 shapes (16k pods ×
        # 65k nodes) that blows HBM (measured 26.5G vs 15.75G). Above a
        # size threshold, evaluate pod CHUNKS under lax.map so only one
        # chunk's temps are live while the (P,N) score matrix accumulates
        # — semantics are unchanged (row-wise ops), the assignment stage
        # still sees the full matrix. Explain mode needs the full stacks
        # (and is host-bound anyway); the sharded builder manages memory
        # by partitioning instead.
        chunkable = (assign_fn is None and not explain
                     and P * N * 4 > _CHUNK_WHEN_BYTES)
        if chunkable:
            # Halve only through even values: C = P / 2^k always divides P
            # exactly (an odd division step would break the reshape below
            # for non-power-of-two pod pads).
            C = P
            while (C > _CHUNK_MIN_PODS and C % 2 == 0
                   and C * N * 4 > _CHUNK_TARGET_BYTES):
                C //= 2
            pf_chunks = jax.tree_util.tree_map(
                lambda a: a.reshape((P // C, C) + a.shape[1:]), pf)
            mt, fc, fs, rcs, _, _, _ = jax.lax.map(evaluate, pf_chunks)
            masked_total = mt.reshape(P, N)
            feasible_counts = fc.reshape(P)
            feasible_static = fs.reshape(P)
            reject_counts = rcs.transpose(1, 0, 2).reshape(-1, P)
            masks, raws, norms = [], [], []
        else:
            (masked_total, feasible_counts, feasible_static, reject_counts,
             masks, raws, norms) = evaluate(pf)
        if assign_fn is not None:
            # Externally-supplied assignment stage (sharded chunked-gather
            # scan; identical results to the default path).
            assign: GangResult = assign_fn(
                masked_total, pf.requests, nf.free,
                eb.gang.group, eb.gang.min_count, key)
        else:
            # Trace-time choice of the inner assignment: auction mode if
            # configured; else pallas kernel on TPU (identical results to
            # the scan, tests/test_pallas_select.py), lax.scan elsewhere.
            # Re-evaluated per shape bucket at retrace.
            greedy_fn = None
            if assignment == "auction":
                import functools

                from .auction import auction_assign

                # Priority-tiered bidding: the batch rows carry real
                # priorities; banded rounds keep the greedy contract's
                # cross-priority faithfulness (ops/auction.py docstring).
                if shortlist is not None:
                    # Bid shortlist (ops/bid_select): per-pod top-K
                    # compression of the bidding rounds' value rows
                    # under the same certify-or-repair contract as the
                    # greedy shortlist scan — bit-identical to the
                    # full-row auction for any K, repairs counted
                    # through the shared ShortlistAssignResult plane.
                    from .bid_select import auction_assign_shortlist

                    greedy_fn = functools.partial(
                        auction_assign_shortlist, priority=pf.priority,
                        k=min(shortlist, N))
                else:
                    greedy_fn = functools.partial(auction_assign,
                                                  priority=pf.priority)
            else:
                use_pallas = pallas
                if use_pallas is None:
                    from .pallas_select import pallas_supported

                    use_pallas = pallas_supported(N)
                if shortlist is not None and pallas is not True:
                    # Shortlist-compressed arbitration: the parallel
                    # top-K selection + K-wide certified scan
                    # (ops/select.greedy_assign_shortlist). It REPLACES
                    # the auto-selected pallas kernel — both attack the
                    # same sequential critical path, and the shortlist
                    # scan's per-step work is ~N/K smaller than the
                    # kernel's full-width argmax; an explicit
                    # pallas=True keeps the kernel (bench comparison).
                    # The counted trade is visible: the engine exposes
                    # shortlist_width/shortlist_repairs in metrics().
                    import functools

                    from .select import greedy_assign_shortlist

                    k_eff = min(shortlist, N)
                    sl_fn = functools.partial(greedy_assign_shortlist,
                                              k=k_eff)
                    if caps is not None:
                        # Enforced domain caps need the N-wide running
                        # cap mask every step — decided at RUN time
                        # (lax.cond), so a topology profile pays the
                        # full caps-scan only when a hard constraint is
                        # really present; everything else keeps the
                        # compressed scan.
                        from .select import (ShortlistAssignResult,
                                             greedy_assign as _ga)

                        def greedy_fn(sc, rq, fr, kk, _caps=caps,
                                      _sl=sl_fn):
                            def full(a):
                                r = _ga(*a, caps=_caps)
                                return ShortlistAssignResult(
                                    r.chosen, r.assigned, r.free_after,
                                    jnp.zeros_like(r.assigned))

                            return jax.lax.cond(
                                _caps.any_enforced, full,
                                lambda a: _sl(*a), (sc, rq, fr, kk))
                    else:
                        greedy_fn = sl_fn
                elif use_pallas:
                    from .pallas_select import greedy_assign_kernel

                    if caps is not None:
                        # The kernel can't carry domain counts; batches
                        # that actually contain enforceable hard-spread
                        # slots take the caps-scan, everything else the
                        # kernel — decided at RUN time (lax.cond), so a
                        # topology profile only pays the scan when a
                        # hard constraint is really present.
                        from .select import greedy_assign as _ga

                        def greedy_fn(sc, rq, fr, k, _caps=caps):
                            return jax.lax.cond(
                                _caps.any_enforced,
                                lambda a: _ga(*a, caps=_caps),
                                lambda a: greedy_assign_kernel(*a),
                                (sc, rq, fr, k))
                    else:
                        greedy_fn = greedy_assign_kernel
                elif caps is not None:
                    import functools

                    from .select import greedy_assign as _ga

                    greedy_fn = functools.partial(_ga, caps=caps)
            # Gang-aware joint assignment (ops/gang.py); with no gangs in
            # the batch this reduces to plain capacity-aware greedy
            # assignment.
            with jax.named_scope("minisched.assign"):
                assign = gang_assign(
                    masked_total, pf.requests, nf.free,
                    eb.gang.group, eb.gang.min_count, key,
                    greedy_fn=greedy_fn)

        # Spread-arbitration inputs: per (pod, GROUP), gathered at the
        # ASSIGNED node, so they must come after the assignment stage.
        # Cheap — (P,G) gathers with G = distinct selector groups (small).
        G = eb.gf.valid.shape[0]
        scan_groups = (caps.scan_groups & caps.any_enforced
                       if caps is not None
                       else jnp.zeros((G,), dtype=bool))
        if needs_topology and "counts_node" in ctx:
            safe_row = jnp.clip(assign.chosen, 0, N - 1)         # (P,)
            live = assign.assigned[:, None] & eb.gf.valid[None, :]
            spread_pre = jnp.where(
                live, ctx["counts_node"][:, safe_row].T, 0.0)    # (P,G)
            gkey = jnp.clip(eb.gf.key_idx, 0,
                            nf.topo_domains.shape[0] - 1)        # (G,)
            spread_dom = jnp.where(
                live, nf.topo_domains[gkey][:, safe_row].T, -1)  # (P,G)
            spread_min = ctx["min_count"]                        # (G,)
            spread_cdom = ctx["counts_dom"]                      # (G,D)
            spread_dexist = ctx["dom_exists"]                    # (G,D)
        else:
            spread_pre = jnp.zeros((0, G), dtype=jnp.float32)
            spread_dom = jnp.full((0, G), -1, dtype=jnp.int32)
            spread_min = jnp.zeros((0,), dtype=jnp.float32)
            spread_cdom = jnp.zeros((0, 0), dtype=jnp.float32)
            spread_dexist = jnp.zeros((0, 0), dtype=bool)

        if explain:
            filter_stack = (jnp.stack(masks) if masks
                            else jnp.zeros((0, P, N), dtype=bool))
            raw_stack = (jnp.stack(raws) if raws
                         else jnp.zeros((0, P, N), dtype=jnp.float32))
            norm_stack = (jnp.stack(norms) if norms
                          else jnp.zeros((0, P, N), dtype=jnp.float32))
        else:
            filter_stack = jnp.zeros((0, P, N), dtype=bool)
            raw_stack = jnp.zeros((0, P, N), dtype=jnp.float32)
            norm_stack = jnp.zeros((0, P, N), dtype=jnp.float32)

        chosen = assign.chosen
        free_after = assign.free_after
        # Repair ledger (a GangResult field since the shortlist stage;
        # getattr keeps external assign_fn suppliers returning the old
        # 5-field shape working — they have no shortlist to account).
        sl_repaired = getattr(assign, "repaired", None)
        if sl_repaired is None:
            sl_repaired = jnp.zeros((P,), dtype=bool)
        if sample_idx is not None:
            # Remap subset rows back to GLOBAL node rows; free_after is
            # scattered into the full-size table so downstream consumers
            # (the engine's residual pass) see cluster-wide capacity.
            safe = jnp.clip(chosen, 0, sample_nodes - 1)
            chosen = jnp.where(assign.assigned, sample_idx[safe], chosen)
            free_after = free_full.at[sample_idx].set(assign.free_after)

        return Decision(
            chosen=chosen,
            assigned=assign.assigned,
            gang_rejected=assign.gang_rejected,
            feasible_counts=feasible_counts,
            feasible_static=feasible_static,
            reject_counts=reject_counts,
            # The (P,N) score matrix is an explain-mode output: nothing on
            # the scheduling path reads it back, and materializing it as a
            # program output costs a P×N f32 buffer (4.3GB at 16k×65k).
            total_scores=(masked_total if explain
                          else jnp.zeros((0, N), dtype=jnp.float32)),
            free_after=free_after,
            spread_pre=spread_pre,
            spread_min=spread_min,
            spread_dom=spread_dom,
            spread_cdom=spread_cdom,
            spread_dexist=spread_dexist,
            scan_groups=scan_groups,
            shortlist_repaired=sl_repaired,
            filter_masks=filter_stack,
            raw_scores=raw_stack,
            norm_scores=norm_stack,
        )

    if _raw:
        return step
    # A step that fails to lower raises at its first call: no slower
    # path may stand in for it unseen.
    jitted = jax.jit(step)
    _STEP_CACHE[cache_key] = jitted
    return jitted


_LOOP_CACHE: dict = {}


def build_loop_step(plugin_set: PluginSet, *,
                    cfg: EncodingConfig = DEFAULT_ENCODING,
                    assignment: str = "greedy",
                    shortlist: Optional[int] = None,
                    slim: bool = True):
    """Compile the PERSISTENT DEVICE LOOP: one jitted program that
    consumes a depth-D work ring of pre-encoded, fixed-shape batches and
    runs the whole tranche without returning to Python between batches.

    Returns ``loop(eb_stack, nf, af, counters, base_key) ->
    (packed_stack, free_final)`` where every leaf of ``eb_stack`` is the
    per-batch EncodedBatch leaf stacked along a leading depth axis,
    ``counters`` is the (D,) u32 step-counter value each slot would have
    drawn on the per-batch path (the loop folds it into ``base_key``
    exactly like the engine's per-batch ``fold_in``, so tie-break
    streams are bit-identical), and ``nf``/``af`` are shared across the
    tranche. The body is THE SAME compiled step the per-batch path runs
    (ops/pipeline.build_step — nested jit inlines at trace time, so the
    op sequence is identical); ``lax.scan`` carries ``free`` across
    iterations — slot k+1's input IS slot k's ``free_after``, the
    residency chain fused on device — and emits one packed slim/i32
    decision buffer per slot, stacked so the host fetches the whole
    tranche in a SINGLE device→host transfer.

    Sharding-pinning rule (the pjit guidance of SNIPPETS.md [2]/[3]):
    the carry's output sharding must equal its input sharding or XLA
    inserts a reshard between iterations. Here the carry is the step's
    own ``free_after``, produced by the identical program that consumed
    ``free`` — same shape, same layout, and on one device the identity
    placement — so nothing moves between slots. The mesh path keeps its
    per-batch dispatch (the engine gates the loop off there) until the
    multi-host loop follow-up pins the carry to
    ``parallel.mesh.leaf_sharding`` explicitly.

    Constraints mirror the engine's loop gates: no explain (per-batch
    matrices would have to stack D-deep), and ``used_ports`` rides
    along un-carried — the engine stages only port-free batches into
    the ring, so the tranche's port table is invariant by construction.
    Both built-in assignments are ring-eligible: the greedy scan
    carries its sequential free chain, and the auction's banded bidding
    starts slot k+1's prices fresh while its ``free`` input IS slot k's
    ``free_after`` — exactly the per-batch residency carry, fused. The
    between-slot validator replays debits with the order-free per-node
    aggregate (_DeviceResidency I1), which both assignment orders equal
    bitwise under the exact-integer resource grammar.
    """
    if assignment not in ("greedy", "auction"):
        raise ValueError(
            f"unknown assignment strategy {assignment!r}; "
            "expected 'greedy' or 'auction'")
    if shortlist is not None and shortlist < 1:
        shortlist = None
    cache_key = (
        tuple(p.trace_key() for p in plugin_set.filter_plugins),
        tuple((p.trace_key(), plugin_set.weight_of(p))
              for p in plugin_set.score_plugins),
        cfg, assignment, shortlist, slim, "device_loop",
    )
    cached = _LOOP_CACHE.get(cache_key)
    if cached is not None:
        return cached
    # The loop body IS the per-batch step (process-wide memo — a tuner
    # revisit of the shortlist width reuses the compiled body).
    step = build_step(plugin_set, explain=False, cfg=cfg,
                      assignment=assignment, shortlist=shortlist)
    from .residency import pack_decision_i32, pack_decision_slim

    pack = pack_decision_slim if slim else pack_decision_i32

    def loop(eb_stack, nf, af, counters, base_key):
        def body(free, slot):
            eb_s, counter = slot
            # Identical key derivation to the per-batch path: fold the
            # slot's pre-assigned step-counter value into the engine's
            # base key. fold_in is value-deterministic, so a traced u32
            # draws the same stream as the host's python int.
            key = jax.random.fold_in(base_key, counter)
            d = step(eb_s, nf._replace(free=free), af, key)
            packed = pack(d.chosen, d.assigned, d.gang_rejected,
                          d.feasible_counts, d.feasible_static,
                          d.reject_counts, d.shortlist_repaired)
            return d.free_after, packed

        with jax.named_scope("minisched.device_loop"):
            free_final, packs = jax.lax.scan(
                body, nf.free, (eb_stack, counters))
        return packs, free_final

    jitted = jax.jit(loop)
    _LOOP_CACHE[cache_key] = jitted
    return jitted


_TENANT_CACHE: dict = {}


def build_tenant_step(plugin_set: PluginSet, *,
                      cfg: EncodingConfig = DEFAULT_ENCODING,
                      shortlist: Optional[int] = None):
    """Compile the FUSED MULTI-TENANT step: one jitted program that
    vmaps the per-batch step over a leading tenant axis, so one
    dispatch serves T independent virtual clusters at the cost of one
    big one.

    Returns ``tenant_step(eb_stack, nf_stack, af_stack, keys, w_stack)
    -> (packed_stack, free_stack)`` where every leaf of ``eb_stack`` /
    ``af_stack`` carries a leading (T,) axis, ``keys`` is the (T, ...)
    stack of each tenant's per-batch PRNG key, and ``w_stack`` is the
    (T, S) per-tenant scorer weight matrix (threaded through the raw
    step's ``w_vec`` seam — weight-differing tenants share this one
    compile, the cache below keys WITHOUT weights). ``nf_stack`` maps
    only the DYNAMIC node leaves (free / used_ports) over the tenant
    axis; every static leaf is passed ONCE and broadcast — the fusion
    coordinator only groups tenants whose static node encodings are
    content-identical, which is the whole point: T tenants, one static
    upload.

    Per-lane outputs are bit-identical to the solo step on the same
    (inputs, key): the body is the SAME trace (vmap of elementwise /
    scan / gather ops on CPU preserves per-lane values; ``lax.cond``
    becomes a select of two deterministically-computed branches), and
    each lane's decision is packed with the i32 layout so the host
    fetches the whole tranche in one (T, 6+F, P) transfer. Greedy
    scan only (pallas=False — a Mosaic kernel can't be vmapped), no
    explain, no node sampling (the only in-step key split would break
    lane purity).
    """
    if shortlist is not None and shortlist < 1:
        shortlist = None
    cache_key = (
        tuple(p.trace_key() for p in plugin_set.filter_plugins),
        tuple(p.trace_key() for p in plugin_set.score_plugins),
        cfg, shortlist, "tenant_step",
    )
    cached = _TENANT_CACHE.get(cache_key)
    if cached is not None:
        return cached
    inner = build_step(plugin_set, explain=False, cfg=cfg, pallas=False,
                       assignment="greedy", shortlist=shortlist, _raw=True)
    from ..encode.cache import NodeFeatureCache
    from ..encode.features import NodeFeatures
    from .residency import pack_decision_i32

    def lane(eb, nf, af, key, w_vec):
        d = inner(eb, nf, af, key, w_vec)
        packed = pack_decision_i32(
            d.chosen, d.assigned, d.gang_rejected, d.feasible_counts,
            d.feasible_static, d.reject_counts, d.shortlist_repaired)
        return packed, d.free_after

    dyn = NodeFeatureCache.DYNAMIC_NF_FIELDS
    nf_axes = NodeFeatures(**{f: (0 if f in dyn else None)
                              for f in NodeFeatures._fields})
    fused = jax.jit(jax.vmap(lane, in_axes=(0, nf_axes, 0, 0, 0)))
    _TENANT_CACHE[cache_key] = fused
    return fused


#: Where the persistent compilation cache lives when
#: JAX_COMPILATION_CACHE_DIR is unset: one fixed path inside the checkout
#: (listed in .gitignore). The path is part of each entry's key, so a
#: directory that moved between runs would never hit.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")
_ARM_LOCK = threading.Lock()


def arm_compile_cache() -> str:
    """Arm jax's persistent compilation cache and return its directory.

    The one rule: JAX_COMPILATION_CACHE_DIR when it is set (jax reads it
    at import; it is set here only if the variable came later), else
    DEFAULT_COMPILE_CACHE_DIR.
    Child processes share the cache by inheriting the variable or the
    fixed path. Raises RuntimeError when the cache cannot be armed — a
    run never continues silently without it."""
    from jax._src import compilation_cache as cc

    path = os.path.abspath(os.environ.get("JAX_COMPILATION_CACHE_DIR")
                           or DEFAULT_COMPILE_CACHE_DIR)
    with _ARM_LOCK:
        if jax.config.jax_compilation_cache_dir != path:
            jax.config.update("jax_compilation_cache_dir", path)
        if cc._cache is not None and str(cc._cache.path) == path:
            return path  # already armed here
        if not jax.config.jax_enable_compilation_cache:
            raise RuntimeError(
                "persistent compilation cache is disabled "
                "(jax_enable_compilation_cache=False)")
        # jax latches "no cache" at its first consult, which an import-
        # time backend probe can trigger before the directory is known.
        cc.reset_cache()
        cc._initialize_cache()
        if cc._cache is None:
            raise RuntimeError(
                f"persistent compilation cache could not be armed at {path}")
    return path


def max_normalize_100(scores: jnp.ndarray, feasible: jnp.ndarray) -> jnp.ndarray:
    """Standard k8s NormalizeScore: scale so the best feasible node gets 100.
    Rows with all-zero max pass through unchanged (upstream behavior)."""
    masked = jnp.where(feasible, scores, 0.0)
    row_max = masked.max(axis=1, keepdims=True)
    return jnp.where(row_max > 0, masked * (100.0 / jnp.maximum(row_max, 1e-30)),
                     masked)
