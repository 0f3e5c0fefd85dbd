"""Arithmetic the metric readers share: per-batch deltas of the engine's
counters, span self time, histogram quantiles and bind latencies."""
from __future__ import annotations

import math
from typing import Iterable, List, Optional


def per_batch(run, counter: str) -> Optional[float]:
    """Delta of an engine counter per batch committed meanwhile (the
    traced part of the window in a traced run)."""
    batches = run.layer_delta("batches")
    return run.layer_delta(counter) / batches if batches > 0 else None


def self_seconds(run, names: Iterable[str], child_prefix: str) -> float:
    """Summed self time of the flight-recorder spans named `names`,
    without the time of their children whose name starts with
    `child_prefix` (children: later spans on the same thread that end
    inside the parent)."""
    names = set(names)
    by_tid = {}
    for e in run.spans:
        if e["ph"] == "X":
            by_tid.setdefault(e["tid"], []).append(e)
    total = 0
    for evs in by_tid.values():
        evs.sort(key=lambda e: e["ts_ns"])
        for i, e in enumerate(evs):
            if e["name"] not in names:
                continue
            end = e["ts_ns"] + e["dur_ns"]
            child = 0
            for c in evs[i + 1:]:
                if c["ts_ns"] >= end:
                    break
                if (c["name"].startswith(child_prefix)
                        and c["ts_ns"] + c["dur_ns"] <= end):
                    child += c["dur_ns"]
            total += e["dur_ns"] - child
    return total / 1e9


def hist_quantile(snap: dict, q: float) -> float:
    """Prometheus-style quantile of a fixed-bucket histogram: the bucket
    holding the q-th observation, interpolated linearly inside it; the
    +Inf bucket reports the last finite bound."""
    counts, bounds, n = snap["counts"], snap["bounds"], snap["count"]
    if n <= 0:
        return math.nan
    rank, cum = q * n, 0.0
    for i, c in enumerate(counts):
        cum += c
        if cum >= rank:
            if i >= len(bounds):
                return float(bounds[-1])
            lo = float(bounds[i - 1]) if i else 0.0
            hi = float(bounds[i])
            return hi if c <= 0 else lo + (hi - lo) * (rank - (cum - c)) / c
    return float(bounds[-1])


def bind_latencies(run) -> List[float]:
    """Bind stamp minus due time of every pod due in the window; a pod
    never bound counts as waiting until the grace ran out."""
    out = []
    for k in run.window_keys:
        due = run.created[k][1]
        b = run.binds.get(k)
        out.append((b[1] if b else run.deadline) - due)
    return out


def nearest_rank(values: List[float], q: float) -> Optional[float]:
    if not values:
        return None
    s = sorted(values)
    return float(s[max(0, math.ceil(q * len(s)) - 1)])


def per_traced_batch(run, seconds: float) -> Optional[float]:
    """Seconds read from the trace (or the spans recorded with it) per
    batch committed while it recorded."""
    batches = run.layer_delta("batches")
    return seconds / batches if batches > 0 else None
