"""Incident-bundle validator + causal-narrative printer — read a bundle
captured by ``minisched_tpu/obs/bundle.py`` (or a bare journal JSONL)
without leaving the terminal.

    python tools/postmortem.py BUNDLE_DIR
    python tools/postmortem.py journal.jsonl

Validates the bundle schema (manifest, journal JSONL, config/metrics
JSON, the trace export via trace_view's validator), then prints the
journal's event timeline and the CAUSAL CHAINS it contains: for every
``fault.<gate>`` fire, the ladder moves it provoked — escalations, retry
outcomes, breaks, desyncs, quarantine — down to the recovery that closed
it. The chain summary is the artifact's headline: an incident reads as

    fault.step -> supervisor.escalate(upload) -> supervisor.retry(failed)
      -> supervisor.escalate(sync) -> ... -> supervisor.recover(resident)

CI-gating exit codes (the trace_view contract): 0 = valid (an
EMPTY/unarmed journal is valid and reported as such), 1 = unreadable
input, 2 = schema violation.

Importable pieces (tests/test_journal.py and tools/bench_journal.py):

    load_bundle(path)       -> dict with manifest/journal/... payloads
    validate_bundle(doc)    raise ValueError on any schema offense
    validate_journal(events)  seq-monotonicity + required-key check
    causal_chains(events)   [[event, ...], ...] — one chain per
                            fault fire, ordered, recovery-terminated
    narrative(events)       printable chain-summary lines
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

#: Event keys every journal record must carry (obs/journal.note).
REQUIRED_KEYS = ("seq", "t", "unix", "kind", "thread")

#: Kinds that CLOSE a causal chain (the system returned to a calmer
#: posture).
_RECOVERY_KINDS = ("supervisor.recover", "overload.recover",
                   "slo.clear", "steward.respawn", "store.reattach")

#: Kinds that belong to a chain between its fault root and recovery.
_CHAIN_PREFIXES = ("supervisor.", "overload.", "shortlist.",
                   "residency.", "loop.", "watchdog.", "slo.",
                   "queue.", "bundle.", "invariant.", "lease.",
                   "fleet.", "proc.", "engine.", "steward.",
                   "store.", "rebalance.")


def validate_journal(events: List[dict]) -> None:
    """Raise ValueError unless ``events`` is a schema-valid journal
    stream: every record an object with the required keys, and the seq
    fields monotonically increasing — EXCEPT records whose seq the
    ``journal:corrupt`` fault gate scribbled, which are detected (and
    reported by the caller) precisely because they break the order."""
    last = 0
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            raise ValueError(f"journal event {i} is not an object")
        for k in REQUIRED_KEYS:
            if k not in ev:
                raise ValueError(f"journal event {i} lacks {k!r}")
        if not isinstance(ev["seq"], int):
            raise ValueError(f"journal event {i}: seq is not an int")
        if not isinstance(ev["kind"], str) or not ev["kind"]:
            raise ValueError(f"journal event {i}: bad kind")
        if ev["seq"] <= last and not _is_scribbled(ev["seq"], last):
            raise ValueError(
                f"journal event {i}: seq {ev['seq']} not monotonic "
                f"(prev {last}) and not a recognized corrupt scribble")
        if not _is_scribbled(ev["seq"], last):
            last = ev["seq"]


def _is_scribbled(seq: int, last: int) -> bool:
    """The journal:corrupt gate scribbles seq by XOR-ing bit 30 — a
    scribbled value is either huge (bit set) or, de-scribbled, the next
    expected seq."""
    return seq >= (1 << 30) or (seq ^ 0x40000000) == last + 1


def scribbled_count(events: List[dict]) -> int:
    last = 0
    n = 0
    for ev in events:
        if isinstance(ev.get("seq"), int) and _is_scribbled(ev["seq"],
                                                            last):
            n += 1
        elif isinstance(ev.get("seq"), int):
            last = ev["seq"]
    return n


def load_bundle(path: str) -> Dict:
    """Read a bundle dir into {name: payload}. Raises OSError /
    json.JSONDecodeError on unreadable input (exit code 1 territory)."""
    out: Dict[str, object] = {}
    for name in sorted(os.listdir(path)):
        full = os.path.join(path, name)
        if not os.path.isfile(full):
            continue
        with open(full, encoding="utf-8") as f:
            if name.endswith(".jsonl"):
                out[name] = [json.loads(line) for line in f
                             if line.strip()]
            elif name.endswith(".json"):
                out[name] = json.load(f)
    return out


def validate_bundle(doc: Dict) -> None:
    """Raise ValueError on any schema offense in a loaded bundle."""
    man = doc.get("manifest.json")
    if not isinstance(man, dict):
        raise ValueError("bundle lacks manifest.json")
    if man.get("schema") != 1:
        raise ValueError(f"unknown bundle schema {man.get('schema')!r}")
    for k in ("incident_class", "unix", "pid", "journal_next_seq",
              "files"):
        if k not in man:
            raise ValueError(f"manifest lacks {k!r}")
    for name in man["files"]:
        if name != "manifest.json" and name not in doc:
            raise ValueError(f"manifest names missing file {name!r}")
    journal = doc.get("journal.jsonl")
    if not isinstance(journal, list):
        raise ValueError("bundle lacks journal.jsonl")
    validate_journal(journal)
    cfg = doc.get("config.json")
    if not isinstance(cfg, dict) or "env" not in cfg:
        raise ValueError("bundle lacks a config.json with env")
    for name in ("metrics.json", "timeline.json"):
        if name in doc and not isinstance(doc[name], dict):
            raise ValueError(f"{name} is not an object")
    if "trace.json" in doc:
        import trace_view

        trace_view.validate(doc["trace.json"])


def causal_chains(events: List[dict]) -> List[List[dict]]:
    """One chain per ``fault.<gate>`` root: the fault fire plus every
    subsequent control-machinery event up to and including the recovery
    that closed it. Overlapping faults share their containment tail —
    each chain independently reads root → ... → recovery, which is the
    question a postmortem asks per fault."""
    chains: List[List[dict]] = []
    open_chains: List[List[dict]] = []
    for ev in events:
        kind = ev.get("kind", "")
        if kind.startswith("fault."):
            chain = [ev]
            chains.append(chain)
            open_chains.append(chain)
            continue
        if not open_chains:
            continue
        if kind.startswith(_CHAIN_PREFIXES):
            for chain in open_chains:
                chain.append(ev)
            if kind in _RECOVERY_KINDS:
                # supervisor.recover steps one rung; a chain closes
                # only at the calm end (level 0 / the "to" of the
                # shallowest rung).
                if ev.get("level", 0) == 0 or kind == "slo.clear":
                    open_chains = [c for c in open_chains
                                   if c[-1] is not ev]
    return chains


def _fmt_event(ev: dict) -> str:
    kind = ev.get("kind", "?")
    detail = ev.get("to") or ev.get("outcome") or ev.get("reason") \
        or ev.get("slo") or ev.get("gate") or ev.get("cause") or ""
    if kind.startswith(("lease.", "fleet.", "proc.", "steward.",
                        "store.", "rebalance.")):
        # Fleet events read as WHO did WHAT: takeover names the dead
        # peer and the claiming epoch; others name the acting replica.
        who = ev.get("replica", "")
        frm = ev.get("frm", "")
        if kind == "lease.takeover" and frm:
            detail = f"{who}<-{frm}@e{ev.get('epoch', '?')}"
        elif kind == "proc.death":
            detail = (f"{who} exit={ev.get('exit_code', '?')}"
                      f" up={ev.get('uptime_s', '?')}s")
        elif kind in ("steward.claim", "steward.handoff") and frm:
            # Succession reads crown-passing: new steward <- predecessor
            # at the freshly fenced epoch.
            detail = f"{who}<-{frm}@e{ev.get('epoch', '?')}"
        elif kind in ("steward.mourn", "steward.respawn",
                      "steward.orphan_adopt"):
            detail = (f"{who} tends {ev.get('target', '?')}"
                      f" inc={ev.get('incarnation', '?')}")
        elif kind == "rebalance.burn_nominate":
            detail = (f"shard {ev.get('shard', '?')}: "
                      f"{ev.get('donor', '?')}->"
                      f"{ev.get('recipient', '?')}"
                      f" burn={ev.get('level', '?')}")
        elif kind == "store.reattach":
            detail = (f"{who} after {ev.get('outage_s', '?')}s"
                      if who else f"after {ev.get('outage_s', '?')}s")
        elif who:
            detail = f"{who}" + (f": {detail}" if detail else "")
    # A merged cross-process journal tags each record with the replica
    # process it came from; keep that attribution in the narrative.
    src = ev.get("source", "")
    line = f"{kind}({detail})" if detail else kind
    return f"{src}|{line}" if src else line


def narrative(events: List[dict]) -> List[str]:
    """Chain-summary lines, one per fault root."""
    out = []
    for chain in causal_chains(events):
        root = chain[0]
        arrow = " -> ".join(_fmt_event(ev) for ev in chain[:12])
        if len(chain) > 12:
            arrow += f" -> ... ({len(chain) - 12} more)"
        closed = chain[-1].get("kind") in _RECOVERY_KINDS
        out.append(f"[{root.get('kind')}] {arrow}"
                   + ("" if closed else "   [unresolved]"))
    return out


def _print_timeline(events: List[dict]) -> None:
    print(f"journal: {len(events)} events")
    for ev in events:
        tags = {k: v for k, v in ev.items()
                if k not in ("seq", "t", "unix", "kind", "thread")}
        print(f"  #{ev['seq']:<6d} {ev['t']:>10.3f}s  "
              f"{ev['kind']:<28s} {tags if tags else ''}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("bundle", help="incident bundle directory "
                                   "(obs/bundle.py) or a journal JSONL")
    ap.add_argument("--quiet", action="store_true",
                    help="validate only; print just the verdict")
    args = ap.parse_args()
    path = args.bundle
    try:
        if os.path.isdir(path):
            doc = load_bundle(path)
            events = doc.get("journal.jsonl") or []
        else:
            with open(path, encoding="utf-8") as f:
                events = [json.loads(line) for line in f
                          if line.strip()]
            doc = None
    except (OSError, json.JSONDecodeError) as e:
        print(f"postmortem: cannot read {path}: {e}", file=sys.stderr)
        return 1
    try:
        if doc is not None:
            validate_bundle(doc)
        else:
            validate_journal(events)
    except ValueError as e:
        print(f"postmortem: schema violation in {path}: {e}",
              file=sys.stderr)
        return 2
    if doc is not None:
        man = doc["manifest.json"]
        print(f"{path}: schema-valid bundle — "
              f"incident class {man['incident_class']!r}"
              + (f", reason: {man.get('reason')}"
                 if man.get("reason") else ""))
    else:
        print(f"{path}: schema-valid journal")
    n_scrib = scribbled_count(events)
    if n_scrib:
        print(f"  NOTE: {n_scrib} event(s) carry a corrupt-scribbled "
              "seq (journal:corrupt fault gate)")
    if not events:
        # An empty journal is a normal artifact (recorder unarmed or a
        # quiet run) — validated, reported, exit 0.
        print("  empty journal (recorder unarmed or no transitions "
              "recorded)")
        return 0
    if not args.quiet:
        _print_timeline(events)
    lines = narrative(events)
    if lines:
        print("causal chains (one per fault fire):")
        for line in lines:
            print(f"  {line}")
    else:
        print("no fault fires recorded — no causal chains to trace")
    return 0


if __name__ == "__main__":
    sys.exit(main())
