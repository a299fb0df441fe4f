#!/usr/bin/env python
"""Align flight-recorder black boxes across ranks and name the rank(s)
that never arrived.

``common/flightrec.py`` gives every process a ring of its last N
collective events, each stamped with a process-wide sequence number —
under SPMD every rank issues collectives from the same program line,
so seq ``k`` is the SAME collective on every rank. When a job hangs or
dies, every rank dumps its ring as ``blackbox.rank<r>.json``; this
tool merges them and turns "the job hung" into "rank 5 never submitted
allreduce for bucket 12 at step 4812":

* per rank: the last submitted seq, the last COMPLETED seq, and every
  pending/stalled/error event;
* per divergent seq: which ranks submitted it, which completed it,
  which never saw it — with the event's op, tensor signature (name),
  step, bytes and wire dtype from the ranks that did;
* a verdict line per finding, machine-checkable (the tier-1 stall
  chaos test asserts on it).

Usage:
    python tools/flight_diff.py DIR_OR_GLOB [--json]

``DIR_OR_GLOB`` is a directory containing ``blackbox.rank*.json`` (the
``HVD_TPU_FLIGHTREC_DIR`` of the dead job) or an explicit glob.
Prints a human-readable report (or one JSON object with ``--json``);
exits 0 with findings, 2 when no black boxes were found.

Stdlib-only — must run on a machine with nothing but the boxes.
"""

from __future__ import annotations

import argparse
import glob as glob_lib
import json
import os
import sys
from typing import Any, Dict, List, Optional

# Black-box schema contract with common/flightrec.py — check_parity
# asserts these tuples match the writer's byte for byte, so the schema
# cannot drift between writer and reader. v2 adds ``role``: the rank's
# (dp,pp,tp) coordinate label under a hybrid ParallelSpec ("" when
# role-blind) — verdicts then name the stage, not just the rank. v3
# adds ``trace``: the serve engine's request-id CSV per decode event
# ("" for training collectives), the analyze_serve --flight join key.
BLACKBOX_SCHEMA_VERSION = 3
BLACKBOX_KEYS = ("schema", "rank", "host", "role", "pid", "trigger",
                 "reason", "t_unix", "step", "seq_head", "events",
                 "stacks", "stall_inflight", "recovery")
EVENT_KEYS = ("seq", "op", "name", "step", "bytes", "wire",
              "t_submit", "t_complete", "outcome", "trace")


def load_blackbox(path: str) -> Dict[str, Any]:
    """Load + validate one black box. Raises ValueError naming the
    missing key — a truncated box must not silently produce an empty
    analysis."""
    with open(path) as f:
        box = json.load(f)
    if not isinstance(box, dict):
        raise ValueError(f"{path}: black box must be a JSON object")
    if box.get("schema", 1) < 2:
        box.setdefault("role", "")   # v1 boxes predate role labels
    if box.get("schema", 1) < 3:
        for ev in box.get("events", ()):
            ev.setdefault("trace", "")   # v2 events predate trace ids
    missing = [k for k in BLACKBOX_KEYS if k not in box]
    if missing:
        raise ValueError(f"{path}: black box missing keys {missing} "
                         f"(schema v{BLACKBOX_SCHEMA_VERSION})")
    for ev in box.get("events", ()):
        ev_missing = [k for k in EVENT_KEYS if k not in ev]
        if ev_missing:
            raise ValueError(
                f"{path}: event missing keys {ev_missing}")
    return box


def find_boxes(target: str) -> List[str]:
    if os.path.isdir(target):
        return sorted(glob_lib.glob(
            os.path.join(target, "blackbox.rank*.json")))
    return sorted(glob_lib.glob(target))


def analyze(boxes: Dict[int, Dict[str, Any]]) -> Dict[str, Any]:
    """The cross-rank alignment. ``boxes``: rank -> loaded black box."""
    per_rank: Dict[int, Dict[str, Any]] = {}
    events_by_seq: Dict[int, Dict[int, Dict[str, Any]]] = {}
    for rank, box in boxes.items():
        completed = [e for e in box["events"]
                     if e["outcome"] == "ok" and e["t_complete"]]
        incomplete = [e for e in box["events"] if e["outcome"] != "ok"]
        per_rank[rank] = {
            "host": box.get("host", ""),
            "role": box.get("role", ""),
            "trigger": box.get("trigger", ""),
            "reason": box.get("reason", ""),
            "step": box.get("step", 0),
            "last_submitted_seq": box.get("seq_head", 0),
            "last_completed_seq": max(
                (e["seq"] for e in completed), default=0),
            "incomplete": incomplete,
            "ring_span": (min((e["seq"] for e in box["events"]),
                              default=0),
                          max((e["seq"] for e in box["events"]),
                              default=0)),
        }
        for e in box["events"]:
            events_by_seq.setdefault(e["seq"], {})[rank] = e

    ranks = sorted(boxes)
    findings: List[Dict[str, Any]] = []

    # The frontier: the highest seq EVERY rank completed. Divergence
    # starts one past it — but only seqs inside every ring's span are
    # judged (a seq that scrolled out of a small ring is unknown, not
    # missing).
    frontier = min((per_rank[r]["last_completed_seq"] for r in ranks),
                   default=0)
    max_seq = max((per_rank[r]["last_submitted_seq"] for r in ranks),
                  default=0)
    ring_floor = max((per_rank[r]["ring_span"][0] for r in ranks
                      if per_rank[r]["ring_span"][1]), default=0)

    for seq in range(max(frontier + 1, ring_floor), max_seq + 1):
        seen = events_by_seq.get(seq, {})
        if not seen:
            continue
        submitted = sorted(seen)
        not_submitted = [r for r in ranks if r not in seen]
        not_completed = sorted(r for r, e in seen.items()
                               if e["outcome"] != "ok")
        if not not_submitted and not not_completed:
            continue
        # Describe the collective from any rank that saw it.
        ref = seen[submitted[0]]
        desc = {"seq": seq, "op": ref["op"], "name": ref["name"],
                "step": ref["step"], "bytes": ref["bytes"],
                "wire": ref["wire"]}
        # Role-tagged rank naming (schema v2): under a hybrid
        # ParallelSpec the verdict reads "rank 3 = dp0/pp1/tp1 never
        # completed ppermute..." — the stage is the unit an operator
        # reasons about, not the bare rank number.
        def who(r):
            role = per_rank[r]["role"] if r in per_rank else ""
            return f"rank {r} = {role}" if role else f"rank {r}"

        verdicts = []
        for r in not_submitted:
            verdicts.append(
                f"{who(r)} never submitted {ref['name']} "
                f"(op={ref['op']}, seq {seq}, step {ref['step']})")
        for r in not_completed:
            out = seen[r]["outcome"]
            verdicts.append(
                f"{who(r)} never completed {ref['name']} "
                f"(op={ref['op']}, seq {seq}, step {ref['step']}, "
                f"outcome={out})")
        findings.append({**desc, "submitted_ranks": submitted,
                         "missing_ranks": not_submitted,
                         "incomplete_ranks": not_completed,
                         "outcomes": {str(r): e["outcome"]
                                      for r, e in seen.items()},
                         "verdicts": verdicts})

    # Rank-level attribution: the rank whose completion frontier is
    # LOWEST is where the pod-wide barrier wedged.
    laggard: Optional[int] = None
    if ranks:
        laggard = min(ranks,
                      key=lambda r: per_rank[r]["last_completed_seq"])
    return {
        "ranks": ranks,
        "per_rank": {str(r): {k: v for k, v in per_rank[r].items()
                              if k != "incomplete"}
                     for r in ranks},
        "incomplete": {str(r): per_rank[r]["incomplete"] for r in ranks
                       if per_rank[r]["incomplete"]},
        "common_completed_seq": frontier,
        "laggard_rank": laggard,
        "findings": findings,
    }


def duration_skew(boxes: Dict[int, Dict[str, Any]]) -> Dict[str, Any]:
    """Per-seq submit→complete duration spread across ranks (monotonic
    clocks are per-host, so absolute timestamps never cross ranks —
    durations do). Consumed by ``analyze_metrics.py --flight``."""
    by_seq: Dict[int, Dict[int, float]] = {}
    meta: Dict[int, Dict[str, Any]] = {}
    for rank, box in boxes.items():
        for e in box["events"]:
            if e["outcome"] == "ok" and e["t_complete"] is not None:
                by_seq.setdefault(e["seq"], {})[rank] = \
                    e["t_complete"] - e["t_submit"]
                meta.setdefault(e["seq"], {"name": e["name"],
                                           "step": e["step"]})
    rows = []
    for seq in sorted(by_seq):
        durs = by_seq[seq]
        if len(durs) < 2:
            continue
        rows.append({
            "seq": seq, "name": meta[seq]["name"],
            "step": meta[seq]["step"],
            "ranks": len(durs),
            "min_ms": round(1000 * min(durs.values()), 3),
            "max_ms": round(1000 * max(durs.values()), 3),
            "skew_ms": round(
                1000 * (max(durs.values()) - min(durs.values())), 3),
            "slowest_rank": max(durs, key=durs.get),
        })
    rows.sort(key=lambda r: -r["skew_ms"])
    return {
        "aligned_events": len(rows),
        "max_skew_ms": rows[0]["skew_ms"] if rows else 0.0,
        "top_skew": rows[:10],
    }


def load_all(target: str) -> Dict[int, Dict[str, Any]]:
    boxes: Dict[int, Dict[str, Any]] = {}
    for path in find_boxes(target):
        try:
            box = load_blackbox(path)
        except (ValueError, OSError, json.JSONDecodeError) as e:
            print(f"flight_diff: skipping {path}: {e}", file=sys.stderr)
            continue
        boxes[int(box["rank"])] = box
    return boxes


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("target",
                    help="HVD_TPU_FLIGHTREC_DIR (contains "
                         "blackbox.rank*.json) or an explicit glob")
    ap.add_argument("--json", action="store_true",
                    help="print one JSON object instead of the "
                         "human-readable report")
    args = ap.parse_args()

    boxes = load_all(args.target)
    if not boxes:
        print(f"flight_diff: no black boxes under {args.target}",
              file=sys.stderr)
        return 2
    report = analyze(boxes)
    report["skew"] = duration_skew(boxes)

    if args.json:
        print(json.dumps(report, indent=2))
        return 0

    print(f"flight_diff: {len(boxes)} black box(es), ranks "
          f"{report['ranks']}")
    for r in report["ranks"]:
        pr = report["per_rank"][str(r)]
        role = f" role={pr['role']}" if pr.get("role") else ""
        print(f"  rank {r} host={pr['host'] or '?'}{role} "
              f"trigger={pr['trigger']} step={pr['step']} "
              f"submitted≤{pr['last_submitted_seq']} "
              f"completed≤{pr['last_completed_seq']}")
        if pr["reason"]:
            print(f"    reason: {pr['reason']}")
    print(f"  common completed seq: {report['common_completed_seq']}"
          f" (laggard: rank {report['laggard_rank']})")
    if not report["findings"]:
        print("  no divergent collectives — every rank completed the "
              "same frontier")
    for f in report["findings"]:
        for v in f["verdicts"]:
            print(f"  !! {v}")
    if report["skew"]["aligned_events"]:
        print(f"  duration skew over {report['skew']['aligned_events']} "
              f"aligned events: max {report['skew']['max_skew_ms']} ms")
    return 0


if __name__ == "__main__":
    sys.exit(main())
