"""Reduce a profiler trace of the measured window to what the metrics read.

    python benchmark/trace_reduce.py <trace dir or .xplane.pb> [window span]

reads the `.xplane.pb` that `jax.profiler` wrote through
`jax.profiler.ProfileData` (nothing else is needed) and keeps, inside the
host annotation that marks the window (`bench.window`):

- per device plane (`/device:...`), the union of the intervals in which an
  operation of its `XLA Ops` line ran (busy), and so its idle share;
- device time by XLA module (one jitted program, `XLA Modules` line) and
  by operation (named by its HLO instruction, e.g. `%fusion.61`);
- each idle gap of the first device, labelled with the engine span open
  at its middle: the innermost one on the host thread that records most
  engine spans (the scheduling loop), else the innermost open on any
  thread, else "-". Engine spans are the names given (the flight
  recorder's); without names every host event counts.

Times are seconds. A device plane with no operation in the window does not
count as a device used. Without a window span argument the command lists
the trace's planes and lines instead.
"""
from __future__ import annotations

import bisect
import collections
import glob
import json
import os
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class Trace:
    window_s: float
    busy_s: float                      # mean over the devices used
    devices: int
    module_s: Dict[str, float] = field(default_factory=dict)
    op_s: Dict[str, float] = field(default_factory=dict)
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_time(self, prefix: str) -> float:
        """Device seconds of the modules whose name starts with `prefix`,
        summed over devices."""
        return sum(v for k, v in self.module_s.items()
                   if k.startswith(prefix))

    def gaps_by_label(self) -> List[Tuple[str, float]]:
        acc: Dict[str, float] = collections.defaultdict(float)
        for label, s in self.gaps:
            acc[label] += s
        return sorted(acc.items(), key=lambda kv: -kv[1])

    def breakdown(self) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps_by_label()[:10]]}


class _Spans:
    """Host spans sorted by start, for 'which span was open at t'."""

    def __init__(self, spans: List[Tuple[int, int, str]]):
        self.spans = sorted(spans)
        self.starts = [s for s, _e, _n in self.spans]

    def open_at(self, t: int, look: int = 256) -> Optional[str]:
        """The latest-starting span open at t (the innermost, for spans
        nested on one thread) among the `look` that started last."""
        i = bisect.bisect_right(self.starts, t)
        for _s, e, n in reversed(self.spans[max(0, i - look):i]):
            if e > t:
                return n
        return None


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _events(line) -> List[Tuple[str, int, int]]:
    return [(e.name, int(e.start_ns), int(e.start_ns) + int(e.duration_ns))
            for e in line.events]


def op_name(hlo: str) -> str:
    """`%fusion.61 = f32[...] fusion(...)` -> `%fusion.61`."""
    return hlo.split(" = ", 1)[0]


def find_file(path: str) -> str:
    if path.endswith(".xplane.pb"):
        return path
    files = glob.glob(os.path.join(path, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return max(files, key=os.path.getmtime)


def reduce_file(path: str, window: str,
                span_names: Optional[set] = None) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_file(path))
    host_lines: List[List[Tuple[str, int, int]]] = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            host_lines.extend(_events(line) for line in plane.lines)
        elif plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE in lines:
                devices.append((plane.name, lines))
    marks = [(s, e) for evs in host_lines for n, s, e in evs if n == window]
    if not marks:
        raise ValueError(f"no host span {window!r} in the trace")
    w0, w1 = min(s for s, _ in marks), max(e for _, e in marks)
    module_s: Dict[str, float] = collections.defaultdict(float)
    op_s: Dict[str, float] = collections.defaultdict(float)
    busy = []
    first_busy: List[Tuple[int, int]] = []
    for _name, lines in sorted(devices, key=lambda d: d[0]):
        ivs = []
        for n, s, e in _events(lines[OPS_LINE]):
            s, e = max(s, w0), min(e, w1)
            if e > s:
                ivs.append((s, e))
                op_s[op_name(n)] += (e - s) / 1e9
        if MODULES_LINE in lines:
            for n, s, e in _events(lines[MODULES_LINE]):
                s, e = max(s, w0), min(e, w1)
                if e > s:
                    module_s[n] += (e - s) / 1e9
        if not ivs:
            continue
        u = _union(ivs)
        busy.append(sum(b - a for a, b in u) / 1e9)
        if not first_busy:
            first_busy = u
    if not busy:
        raise ValueError("no device operation ran in the traced window")
    engine = [[(s, e, n) for n, s, e in evs if n != window
               and (span_names is None or n in span_names)]
              for evs in host_lines]
    main = _Spans(max(engine, key=len) if engine else [])
    every = _Spans([x for evs in engine for x in evs])
    gaps = []
    edges = [w0] + [x for iv in first_busy for x in iv] + [w1]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            mid = (a + b) // 2
            label = main.open_at(mid) or every.open_at(mid) or "-"
            gaps.append((label, (b - a) / 1e9))
    return Trace(window_s=(w1 - w0) / 1e9, busy_s=sum(busy) / len(busy),
                 devices=len(busy), module_s=dict(module_s),
                 op_s=dict(op_s), gaps=gaps)


def describe(path: str) -> dict:
    """Plane and line names with event counts, and a few event names per
    line: what to look at before writing code against a new trace."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_file(path))
    return {plane.name: [[ln.name, len(list(ln.events)),
                          sorted({e.name[:80] for e in ln.events})[:8]]
                         for ln in plane.lines]
            for plane in pd.planes}


if __name__ == "__main__":
    target = sys.argv[1]
    if len(sys.argv) > 2:
        t = reduce_file(target, sys.argv[2])
        print(json.dumps({"window_s": t.window_s, "busy_s": t.busy_s,
                          "devices": t.devices, **t.breakdown(),
                          "modules": t.module_s}, indent=1))
    else:
        print(json.dumps(describe(target), indent=1))
