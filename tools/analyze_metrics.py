#!/usr/bin/env python
"""Summarize what a run left on the host: a metrics JSON-lines dump
(``--metrics``) and the flight-recorder black boxes (``--flight``).
It reads no profiler trace. For device time (busy share, time per
class of op, step statistics) run
``python3 benchmark/run.py --workload <cell> --trace 1``.

``--metrics FILE`` condenses the last snapshot of a metrics JSON-lines
dump (``HVD_TPU_METRICS_FILE`` — the unified-telemetry registry,
docs/metrics.md): the step-time histogram, the step phases, the
wire-byte mix, cache hit rate, fusion fill and infeed wait.

Multi-rank dumps: ``hvdtpurun --metrics-file base.jsonl`` writes one
``base.jsonl.rank<k>`` per worker; ``--metrics base.jsonl`` GLOBS the
suffixed siblings (``.rank<k>`` and the legacy bare ``.<k>``) and
reports BOTH a per-rank view (``metrics_per_rank``) and a merged pod
view (summed bytes/recovery, per-rank step means + the step skew) —
instead of silently reading rank 0 only.

``--flight DIR`` overlays the flight-recorder black boxes
(``HVD_TPU_FLIGHTREC_DIR`` — docs/podmon.md): cross-rank alignment by
collective seq (which rank never arrived where, via
``tools/flight_diff.py``) plus per-collective duration skew. Usage:

    python tools/analyze_metrics.py [--metrics results/metrics.jsonl] \
        [--flight results/blackbox]

Prints ONE JSON object.
"""

import argparse
import json
import os
import re
import sys


def load_metrics_snapshot(path: str):
    """Last snapshot from a metrics JSON-lines dump ({"t":..,
    "metrics": {...}} per line; malformed lines skipped)."""
    last = None
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(rec, dict) and "metrics" in rec:
                    last = rec
    except OSError:
        return None
    return last


def load_rank_dumps(path: str) -> dict:
    """{rank: last-snapshot record} for a --metrics argument. A bare
    file with no suffixed siblings is rank 0 alone (the historical
    single-dump behavior); ``hvdtpurun --metrics-file`` writes
    ``<path>.rank<k>`` per worker (legacy launches wrote ``<path>.<k>``)
    and all of them are merged here — the report used to silently read
    rank 0's file only."""
    out = {}
    suffixed = re.compile(re.escape(os.path.basename(path))
                          + r"\.(?:rank)?(\d+)$")
    directory = os.path.dirname(path) or "."
    try:
        names = os.listdir(directory)
    except OSError:
        names = []
    for name in names:
        m = suffixed.match(name)
        if not m:
            continue
        rec = load_metrics_snapshot(os.path.join(directory, name))
        if rec is not None:
            out[int(m.group(1))] = rec
    if os.path.exists(path):
        rec = load_metrics_snapshot(path)
        if rec is not None:
            # The bare file is rank 0's (single-proc runs write it
            # unsuffixed); an explicit .rank0 sibling wins.
            out.setdefault(0, rec)
    return out


def merge_rank_summaries(per_rank: dict) -> dict:
    """One pod view from per-rank summaries: extensive quantities
    (bytes, counts, recovery events) sum; step time reports per-rank
    means plus the pod skew — the number a single-rank report cannot
    show (docs/podmon.md)."""
    ranks = sorted(per_rank)
    out = {"ranks": ranks}
    by_rank_mean = {}
    total_count = 0
    total_sum_ms = 0.0
    for r in ranks:
        s = per_rank[r].get("step_seconds")
        if s:
            by_rank_mean[str(r)] = s["mean_ms"]
            total_count += s["count"]
            total_sum_ms += s["mean_ms"] * s["count"]
    if by_rank_mean:
        out["step_mean_ms_by_rank"] = by_rank_mean
        out["step_seconds"] = {
            "count": total_count,
            "mean_ms": round(total_sum_ms / max(total_count, 1), 3),
        }
        if len(by_rank_mean) >= 2:
            vals = list(by_rank_mean.values())
            out["step_skew_ms"] = round(max(vals) - min(vals), 3)
            out["slowest_rank"] = int(max(by_rank_mean,
                                          key=by_rank_mean.get))
    wire = {}
    recovery = {}
    infeed_total_s = 0.0
    for r in ranks:
        for w, v in per_rank[r].get("allreduce_bytes_on_wire",
                                    {}).items():
            wire[w] = wire.get(w, 0) + v
        for k, v in per_rank[r].get("recovery", {}).items():
            recovery[k] = recovery.get(k, 0) + v
        iw = per_rank[r].get("infeed_wait")
        if iw:
            infeed_total_s += iw.get("total_s", 0.0)
    if wire:
        out["allreduce_bytes_on_wire"] = wire
    if recovery:
        out["recovery"] = recovery
    if infeed_total_s:
        out["infeed_wait_total_s"] = round(infeed_total_s, 3)
    rates = [per_rank[r]["cache_hit_rate"] for r in ranks
             if "cache_hit_rate" in per_rank[r]]
    if rates:
        out["cache_hit_rate"] = round(sum(rates) / len(rates), 3)
    return out


def summarize_flight(flight_dir: str) -> dict:
    """Black-box overlay (tools/flight_diff.py): cross-rank divergence
    verdicts + per-collective duration skew."""
    try:
        import flight_diff
    except ImportError:
        from tools import flight_diff  # imported as a package module
    boxes = flight_diff.load_all(flight_dir)
    if not boxes:
        return {"note": f"no blackbox.rank*.json under {flight_dir}"}
    report = flight_diff.analyze(boxes)
    skew = flight_diff.duration_skew(boxes)
    return {
        "ranks": report["ranks"],
        "common_completed_seq": report["common_completed_seq"],
        "laggard_rank": report["laggard_rank"],
        "verdicts": [v for f in report["findings"]
                     for v in f["verdicts"]],
        "max_duration_skew_ms": skew["max_skew_ms"],
        "top_skew": skew["top_skew"][:5],
    }


def summarize_metrics(rec: dict) -> dict:
    """Condense one registry snapshot to the numbers an operator reads."""
    snap = rec.get("metrics", {})

    def samples(name):
        return snap.get(name, {}).get("samples", [])

    out = {"snapshot_unix": rec.get("t")}
    hist = next(iter(samples("hvd_tpu_step_seconds")), None)
    if hist and isinstance(hist.get("value"), dict) \
            and hist["value"].get("count"):
        v = hist["value"]
        out["step_seconds"] = {
            "count": v["count"],
            "mean_ms": round(1000.0 * v["sum"] / v["count"], 3),
        }
    phases = {}
    for s in samples("hvd_tpu_step_phase_seconds"):
        v = s.get("value")
        if isinstance(v, dict) and v.get("count"):
            phases[s["labels"].get("phase", "?")] = round(
                1000.0 * v["sum"] / v["count"], 3)
    if phases:
        out["step_phase_mean_ms"] = phases
    # Sum across the `axis` label (eager flat + per-mesh-axis samples
    # share a wire format — a dict comprehension would keep only one).
    wire = {}
    for s in samples("hvd_tpu_allreduce_bytes_total"):
        if s["value"]:
            w = s["labels"].get("wire", "?")
            wire[w] = wire.get(w, 0) + s["value"]
    if wire:
        out["allreduce_bytes_on_wire"] = wire
    cache = {s["labels"].get("result", "?"): s["value"]
             for s in samples("hvd_tpu_eager_cache_total")}
    if sum(cache.values()):
        out["cache_hit_rate"] = round(
            cache.get("hit", 0) / sum(cache.values()), 3)
    fill = samples("hvd_tpu_fusion_fill_efficiency")
    if fill:
        out["fusion_fill_efficiency"] = fill[0]["value"]
    # Infeed starvation (docs/performance.md MFU playbook): how long
    # the step loop blocked on the next device batch. High infeed-wait
    # with a low comm phase = input-bound — reach for the prefetch
    # lever, not accumulation.
    iw = next(iter(samples("hvd_tpu_infeed_wait_seconds")), None)
    if iw and isinstance(iw.get("value"), dict) \
            and iw["value"].get("count"):
        v = iw["value"]
        out["infeed_wait"] = {
            "count": v["count"],
            "mean_ms": round(1000.0 * v["sum"] / v["count"], 3),
            "total_s": round(v["sum"], 3),
        }
    depth = samples("hvd_tpu_infeed_queue_depth")
    if depth:
        out["infeed_queue_depth"] = depth[0]["value"]
    rec_counts = {s["labels"].get("counter", "?"): int(s["value"])
                  for s in samples("hvd_tpu_recovery_total")
                  if s["value"]}
    if rec_counts:
        out["recovery"] = rec_counts
    return out


def main(metrics_path: str = None, flight_dir: str = None) -> int:
    out = {}
    if metrics_path:
        per_rank = {r: summarize_metrics(rec)
                    for r, rec in load_rank_dumps(metrics_path).items()}
        if len(per_rank) > 1:
            out["metrics"] = merge_rank_summaries(per_rank)
            out["metrics_per_rank"] = {str(r): per_rank[r]
                                       for r in sorted(per_rank)}
        elif per_rank:
            out["metrics"] = next(iter(per_rank.values()))
        else:
            # A missing or empty dump is a message, not a crash: the
            # flight overlay may still answer "who never arrived".
            out["note"] = f"no metrics snapshot at {metrics_path}"
    if flight_dir:
        out["flight"] = summarize_flight(flight_dir)
    print(json.dumps(out, indent=2))
    return 0


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--metrics", default=None,
                   help="metrics JSON-lines file (HVD_TPU_METRICS_FILE)"
                        "; per-rank .rank<k>-suffixed siblings are "
                        "globbed into a per-rank + merged view")
    p.add_argument("--flight", default=None,
                   help="flight-recorder black-box dir "
                        "(HVD_TPU_FLIGHTREC_DIR) to overlay: cross-rank "
                        "divergence verdicts + collective duration skew "
                        "(tools/flight_diff.py)")
    args = p.parse_args()
    if not (args.metrics or args.flight):
        p.error("pass --metrics FILE and/or --flight DIR")
    sys.exit(main(metrics_path=args.metrics, flight_dir=args.flight))
