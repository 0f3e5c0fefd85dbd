"""In-scan domain capacity for hard topology spread.

The batched step evaluates DoNotSchedule skew against PRE-batch counts
(plugins/podtopologyspread.py filter): every pod of a batch sees the same
frozen feasibility, so a skew-constrained burst can only raise the
currently-minimal domains by ~max_skew per step — the engine's exact host
arbitration + in-cycle repair then drain it tranche by tranche (round-3
verdict weak #1 measured ~(domains x max_skew) admissions per cycle).
A sequential scheduler has no such ceiling: each placement re-evaluates
skew against RUNNING counts, so balanced rotation fills every domain in
one pass.

This module moves that running-count evaluation INTO the greedy scan
(ops/select.py): the scan carries a per-(group, domain) count table, the
per-pod feasibility mask is computed against the running counts and the
running min, and each assignment updates the counts of every group the
pod MATCHES (membership, not just its own constraints) — the exact math
of the host arbitration's _SpreadGroupState, executed at choice time, so
the choice itself respects skew and a skew-bound burst assigns maximally
in ONE device pass.

Bounded compaction: the carry must be small, so up to ``max_groups``
hard-referenced selector groups are enforced, each with up to
``max_domains`` distinct topology domains (zones/racks compact fine;
kubernetes.io/hostname has N domains and overflows). Slots whose group
is not selected or not compactable are NOT enforced in-scan — the
pipeline keeps the static filter verdict for them and the engine's exact
arbitration + repair remain the (correct, slower) backstop. The
PodTopologySpread filter skips its static skew rejection exactly for the
GROUPS the scan enforces (ctx["spread_scan_groups"] — per-group, which
is what lets the chunked evaluate index it with any pod sub-batch), so
the scan's
running-count feasibility — which can legally ADMIT nodes the frozen
pre-count check would reject — is authoritative for them.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .topology import ns_in_set

# Python literals, NOT module-level jnp constants: a device-resident
# const captured by the trace becomes a hoisted executable parameter,
# and this module's consts proved to tickle a jax-0.9 cpp-pjit dispatch
# anomaly (see tests/test_spreadcap.py::test_dispatch_cache_stability).
BIG_GID = 2 ** 30
BIG_DOM = 2 ** 30
BIG_F = 3.0e38


class DomainCaps(NamedTuple):
    """Inputs for in-scan hard-spread enforcement (H selected groups,
    K compact domains; all shapes static)."""

    slot_h: jnp.ndarray      # (P,C) i32 — constraint slot → selected-group
    #                          index, -1 = slot not enforced in-scan
    slot_skew: jnp.ndarray   # (P,C) f32 — max_skew per slot
    domc: jnp.ndarray        # (H,N) i32 — compact domain per node, -1 none
    counts0: jnp.ndarray     # (H,K) f32 — pre-batch matching counts
    dexist: jnp.ndarray      # (H,K) bool — domain exists on some node
    match: jnp.ndarray       # (P,H) bool — batch pod matches group
    any_enforced: jnp.ndarray  # () bool — any slot enforced this batch
    scan_groups: jnp.ndarray  # (G,) bool — global group enforced in-scan
    #                           (the filter's skew opt-out,
    #                           ctx["spread_scan_groups"]; per-GROUP so
    #                           the chunked evaluate can index it with
    #                           any pod sub-batch)


def _pod_group_match(pf, gf, gsel: jnp.ndarray) -> jnp.ndarray:
    """(P,H) bool: batch pod p matches selected group gsel[h] — the
    batch-pod twin of ops.topology.group_assigned_match (same all-zero
    selector = match-all and namespace-set semantics), using the pod's
    own encoded ns_hash/label_pairs."""
    gsafe = jnp.clip(gsel, 0, gf.valid.shape[0] - 1)
    sel = gf.sel_pairs[gsafe]                       # (H,QT)
    gvalid = gf.valid[gsafe] & (gsel < BIG_GID)
    ns_ok = ns_in_set(gf.ns_set[gsafe], pf.ns_hash).T   # (P,H)
    # (P,H,QT): each non-empty selector pair present among the pod's
    # label pairs (reduced over the pod's L label slots)
    present = (sel[None, :, :, None]
               == pf.label_pairs[:, None, None, :]).any(-1)
    sel_ok = jnp.where(sel[None, :, :] != 0, present, True).all(axis=2)
    return pf.valid[:, None] & gvalid[None, :] & ns_ok & sel_ok


def build_domain_caps(pf, gf, nf, counts_dom, dom_exists, *,
                      max_groups: int = 8,
                      max_domains: int = 128) -> DomainCaps:
    """Traced builder: select up to H hard-referenced groups, compact
    their domain ids to K slots, and gather pre-batch counts from the
    step's (G,D) topology tables."""
    from ..encode import features as F

    H, K = max_groups, max_domains
    P, C = pf.spread_group.shape
    N = nf.valid.shape[0]

    hard_slot = ((pf.spread_group >= 0)
                 & (pf.spread_mode == F.SPREAD_DO_NOT_SCHEDULE)
                 & pf.valid[:, None])                           # (P,C)
    hard_gids = jnp.where(hard_slot, pf.spread_group, BIG_GID)
    gsel = jnp.unique(hard_gids, size=H, fill_value=BIG_GID)    # (H,) sorted

    gsafe = jnp.clip(gsel, 0, gf.valid.shape[0] - 1)
    key_h = gf.key_idx[gsafe]                                   # (H,)
    node_dom = nf.topo_domains[
        jnp.clip(key_h, 0, nf.topo_domains.shape[0] - 1)]       # (H,N)
    node_dom = jnp.where((gsel < BIG_GID)[:, None], node_dom, -1)

    # Compact each group's domain ids into K slots via sort + dense rank
    # (plain sort/cumsum/compare — no jnp.unique/searchsorted, whose
    # fancier lowerings proved fragile on this toolchain). Overflow
    # (more than K distinct domains) disables enforcement for the group.
    dom_or_big = jnp.where(node_dom >= 0, node_dom, BIG_DOM)
    sorted_dom = jnp.sort(dom_or_big, axis=1)                   # (H,N)
    is_new = jnp.concatenate(
        [jnp.ones((H, 1), dtype=bool),
         sorted_dom[:, 1:] != sorted_dom[:, :-1]], axis=1)
    is_new = is_new & (sorted_dom < BIG_DOM)
    rank = jnp.cumsum(is_new, axis=1) - 1                       # (H,N)
    n_distinct = jnp.max(jnp.where(is_new, rank + 1, 0), axis=1)  # (H,)
    compactable = n_distinct <= K
    # Unique values by rank, one (H,N) scatter: every position of a
    # sorted equal-run shares its rank AND its value, so duplicate
    # writes to a slot are value-identical (deterministic in effect);
    # positions that must not write (BIG padding, rank >= K) are routed
    # to the out-of-range column K and dropped.
    # Unwanted writes land in an in-bounds spill column K that is sliced
    # off (no drop-mode scatter; its lowering proved fragile here).
    write_col = jnp.where((sorted_dom < BIG_DOM) & (rank < K), rank, K)
    uniq_k = jnp.full((H, K + 1), BIG_DOM, dtype=sorted_dom.dtype).at[
        jnp.arange(H)[:, None], write_col].set(sorted_dom)[:, :K]

    pos = jnp.sum(uniq_k[:, :, None] <= dom_or_big[:, None, :],
                  axis=1) - 1                                    # (H,N)
    pos_safe = jnp.clip(pos, 0, K - 1)
    hit = (jnp.take_along_axis(uniq_k, pos_safe, axis=1) == dom_or_big)
    domc = jnp.where((node_dom >= 0) & hit & compactable[:, None],
                     pos_safe, -1).astype(jnp.int32)            # (H,N)

    # Pre-batch counts/existence for the compact domains from the step's
    # global tables (already computed by group_topology_state).
    D = counts_dom.shape[1]
    uniq_safe = jnp.clip(uniq_k, 0, D - 1)
    counts0 = jnp.take_along_axis(counts_dom[gsafe], uniq_safe, axis=1)
    dexist = (jnp.take_along_axis(dom_exists[gsafe], uniq_safe, axis=1)
              & (uniq_k < BIG_DOM))
    counts0 = jnp.where(dexist, counts0, 0.0)

    enforce_h = (gsel < BIG_GID) & compactable                  # (H,)

    # Constraint slot → selected-group index (searchsorted over the
    # sorted gsel), enforced only when the group is.
    spos = jnp.searchsorted(gsel, hard_gids.reshape(-1)).reshape(P, C)
    spos_safe = jnp.clip(spos, 0, H - 1)
    slot_ok = (hard_slot & (gsel[spos_safe] == hard_gids)
               & enforce_h[spos_safe])
    slot_h = jnp.where(slot_ok, spos_safe, -1).astype(jnp.int32)

    match = _pod_group_match(pf, gf, gsel) & enforce_h[None, :]
    G = gf.valid.shape[0]
    # Dense (G,H) compare instead of a bool scatter-max: H is tiny and
    # the dense form avoids an exotic scatter lowering.
    scan_groups = ((jnp.arange(G, dtype=gsel.dtype)[:, None]
                    == gsel[None, :]) & enforce_h[None, :]).any(axis=1)
    return DomainCaps(
        slot_h=slot_h,
        slot_skew=pf.spread_max_skew.astype(jnp.float32),
        domc=domc, counts0=counts0, dexist=dexist, match=match,
        any_enforced=slot_ok.any(), scan_groups=scan_groups)


def caps_mask(caps: DomainCaps, counts: jnp.ndarray,
              i: jnp.ndarray) -> jnp.ndarray:
    """(N,) bool: nodes pod row ``i`` may take under the RUNNING counts.
    Mirrors the filter's formula — count(node's domain) + 1 - min over
    existing domains <= max_skew — with the scan-carried state. Nodes
    whose domain is uncompacted/missing pass here (the static filter
    still owns them)."""
    mins = jnp.min(jnp.where(caps.dexist, counts, BIG_F), axis=1)   # (H,)
    N = caps.domc.shape[1]
    ok = jnp.ones((N,), dtype=bool)
    C = caps.slot_h.shape[1]
    for c in range(C):  # static tiny loop (max_spread_constraints)
        h = caps.slot_h[i, c]
        hs = jnp.clip(h, 0, caps.domc.shape[0] - 1)
        dom_n = caps.domc[hs]                                       # (N,)
        cnt_n = counts[hs][jnp.clip(dom_n, 0, counts.shape[1] - 1)]
        okc = (cnt_n + 1.0 - mins[hs]) <= caps.slot_skew[i, c]
        okc = okc | (dom_n < 0)
        ok = ok & jnp.where(h >= 0, okc, True)
    return ok


def caps_update(caps: DomainCaps, counts: jnp.ndarray, i: jnp.ndarray,
                chosen: jnp.ndarray, ok: jnp.ndarray) -> jnp.ndarray:
    """New (H,K) counts after pod row ``i`` takes node ``chosen`` —
    every group the pod MATCHES gains one in the chosen node's domain
    (membership semantics: unconstrained matching pods move counts for
    later constrained pods, exactly like the host arbitration)."""
    dj = caps.domc[:, chosen]                                       # (H,)
    upd = caps.match[i] & ok & (dj >= 0)                            # (H,)
    one = jax.nn.one_hot(jnp.clip(dj, 0, counts.shape[1] - 1),
                         counts.shape[1], dtype=counts.dtype)       # (H,K)
    return counts + one * upd[:, None].astype(counts.dtype)
