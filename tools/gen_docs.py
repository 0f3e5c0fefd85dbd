"""Generate README's measured-numbers block from the committed benchmark
artifact — the round-2/round-3 verdicts flagged hand-edited numbers
drifting from the authoritative JSON three rounds running; this makes
the drift class impossible: the block between the BEGIN/END markers is
machine-written (``make docs``) and tests/test_docs_numbers.py fails the
suite whenever the committed README disagrees with a regeneration.

Also regenerates the plugin-count claim in the component table from the
live plugin registry (the count drifted 17 vs 20 vs 22 across rounds).
"""
from __future__ import annotations

import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BEGIN = "<!-- BEGIN GENERATED NUMBERS (make docs; source BENCH_TPU.json) -->"
END = "<!-- END GENERATED NUMBERS -->"


def _k(v) -> str:
    return f"{v / 1000:.1f}k"


def headline_block(bench: dict, n_plugins: int) -> str:
    d = bench["detail"]
    parts = []
    # device_kind is recorded by round-4+ artifacts; older ones only have
    # the device string ("TPU v5 lite0") — normalize rather than falling
    # back to a hardcoded chip name the artifact might contradict.
    device = (d.get("device_kind")
              or re.sub(r"\d+$", "", d.get("device", "unknown device")))
    parts.append(
        f"**Round-4 chip numbers** (measured on one {device} core in "
        "round 4, on code that predates PR 1 — not a measurement of this "
        "tree; ~±15% run-to-run variance then; this block is GENERATED "
        "from the committed `BENCH_TPU.json` by `make docs` — edit the "
        "artifact, not the prose): "
        f"{d['nodes']:,} nodes × {d['pods']:,} pending pods scored, "
        f"assigned, and committed at **~{_k(bench['value'])} pods/s** "
        f"({d['total_s']} s end-to-end) — ~{bench['vs_baseline']:.0f}× the "
        ">60 s sequential-loop anchor.")
    if d.get("engine_sched_s"):
        parts.append(
            "Through the full product path (store → watch → queue → "
            "batched cycle → bulk bind), the same burst lands "
            f"**create-to-bound in {d['engine_sched_s']} s "
            f"({_k(d['engine_pods_per_sec'])} pods/s), p50 schedule-one "
            f"latency {d['engine_p50_latency_s']} s**.")
    if d.get("engine_c4_sched_s"):
        parts.append(
            "On the topology-heavy BASELINE config-4 profile "
            "(PodTopologySpread + InterPodAffinity + fit, preemption "
            f"enabled) THROUGH the engine: create-to-bound {d['engine_c4_sched_s']} s, "
            f"p50 {d['engine_c4_p50']} s"
            + (f"; streamed, {_k(d['stream_c4_pods_per_sec'])} pods/s "
               f"(p99 {d['stream_c4_p99_latency_s']} s)"
               if d.get("stream_c4_pods_per_sec") else "") + ".")
    if d.get("skew_stream_pods_per_sec"):
        parts.append(
            "The skew-convergence worst case (hard DoNotSchedule, "
            "max_skew=1, every placement gated by intra-batch "
            f"arbitration) drains at {_k(d['skew_stream_pods_per_sec'])} "
            f"pods/s in {d.get('skew_stream_cycles')} queue cycles "
            f"({d.get('skew_stream_failed_attempts')} revoked attempts) "
            "via exact sequential-semantics arbitration plus in-cycle "
            "repair.")
    if d.get("stream_pods_per_sec"):
        parts.append(
            f"Sustained multi-batch streaming serves "
            f"**{_k(d['stream_pods_per_sec'])} pods/s with p99 latency "
            f"{d['stream_p99_latency_s']} s** via the "
            "`percentageOfNodesToScore` analog (device-side top-K "
            "candidate sampling with a same-cycle full-axis residual "
            "pass).")
    cfgs = []
    if d.get("config2_device_s") is not None:
        cfgs.append(f"config 2 (1k × 100) {d['config2_device_s']} s")
    if d.get("config3_device_s") is not None:
        cfgs.append(f"config 3 (10k × 1k) {d['config3_device_s']} s")
    if d.get("config4_device_s") is not None:
        cfgs.append(
            f"config 4 (50k × 10k, spread + affinity) "
            f"{d['config4_device_s']} s device-side")
    if d.get("config5_device_s") is not None:
        cfgs.append(f"config 5 (gang admission) {d['config5_device_s']} s")
    if cfgs:
        parts.append("**Every BASELINE config runs at full shape on one "
                     "chip**: " + "; ".join(cfgs) + ".")
    if d.get("device_s_pallas") and d.get("device_s_scan"):
        ratio = d["device_s_scan"] / d["device_s_pallas"]
        shapes = d.get("pallas_shapes", {})
        n_eq = sum(1 for v in shapes.values() if v == "equal")
        parts.append(
            f"The Pallas assignment kernel beats the `lax.scan` path "
            f"~{ratio:.1f}× on the full step ({d['device_s_pallas']} s vs "
            f"{d['device_s_scan']} s), bitwise-identical across "
            f"{n_eq}/{len(shapes)} shapes of the tiling-edge sweep "
            "asserted on hardware every benchmark run.")
    rl = d.get("roofline_headline")
    if rl:
        parts.append(
            f"Roofline accounting: the headline step moves ~{rl['bytes_gb']} GB "
            f"({rl['achieved_gbps']} GB/s achieved, {rl['pct_hbm_peak']}% of "
            f"the {rl['hbm_peak_gbps']} GB/s HBM peak) — {rl['regime']}.")
    if d.get("explain_overhead_pct") is not None:
        parts.append(
            f"Explain-mode observability costs ~{d['explain_overhead_pct']}% "
            "on the engine cycle.")
    parts.append(
        f"The plugin registry ships {n_plugins} batched plugins.")
    return "\n\n".join(parts)


def regenerate(readme: str, bench: dict, n_plugins: int) -> str:
    block = f"{BEGIN}\n{headline_block(bench, n_plugins)}\n{END}"
    pattern = re.escape(BEGIN) + r".*?" + re.escape(END)
    if not re.search(pattern, readme, flags=re.S):
        raise SystemExit(
            "README.md lacks the GENERATED NUMBERS markers; re-add them")
    out = re.sub(pattern, lambda _m: block, readme, flags=re.S)
    out = re.sub(r"— \d+ batched plugins",
                 f"— {n_plugins} batched plugins", out)
    return out


def main() -> None:
    sys.path.insert(0, REPO)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from minisched_tpu.service.defaultconfig import _REGISTRY

    bench = json.load(open(os.path.join(REPO, "BENCH_TPU.json")))
    path = os.path.join(REPO, "README.md")
    readme = open(path, encoding="utf-8").read()
    out = regenerate(readme, bench, len(_REGISTRY))
    if "--check" in sys.argv:
        if out != readme:
            sys.stderr.write(
                "README.md numbers drifted from BENCH_TPU.json / the "
                "plugin registry — run `make docs`\n")
            raise SystemExit(1)
        print("README numbers match the committed artifact")
        return
    open(path, "w", encoding="utf-8").write(out)
    print("README.md regenerated from BENCH_TPU.json")


if __name__ == "__main__":
    main()
