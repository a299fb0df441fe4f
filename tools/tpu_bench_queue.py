#!/usr/bin/env python
"""TPU bench queue (VERDICT r2 #1): probes for a TPU in a loop and,
once one answers, drains a queued measurement list. Due for deletion
with tools/round_dirs.py once the benchmark exists (ROADMAP queue 3
item 1); nothing on bench.py's or chip_smoke.py's path touches it.

Queue (each job = one subprocess, strictly serialized and never
overlapping the probe — a chip belongs to one process at a time, and
this parent stays off JAX):
  model benches : bench.py --model M
                  (resnet50 s2d/nos2d + bert_large + gpt_small +
                  vit_base + inception3 + tuned-batch legs, each with
                  both MFU bases)
  micro benches : tools/tpu_microbench.py {flash, striped, kernels,
                  overlap, fusion} + tools/tpu_elastic_reset.py

A job's JSON is recorded ONLY if it reports platform == "tpu"; results
land in results/<round_dirs.CURRENT>/<job>.json (this round:
results/tpu_r05/) plus a combined results.json. State
survives restarts (done jobs are skipped). Methodology matches the
reference's examples/tensorflow2/tensorflow2_synthetic_benchmark.py
(synthetic data, timed batches after warmup).

Usage: python tools/tpu_bench_queue.py [--max-hours H] [--once]
"""

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from tools.round_dirs import CURRENT as _ROUND  # noqa: E402
from tools.round_dirs import SEARCH_ORDER as _SEARCH_ORDER  # noqa: E402

OUTDIR = os.path.join(REPO, "results", _ROUND)

PROBE_TIMEOUT = 90
PROBE_SLEEP = 420          # between failed probes
MAX_FAILS_PER_JOB = 3

# Ordered by ROUND VALUE, not model family: whatever time there is,
# the first jobs eat it. r05 order: headline ResNet legs → rest of the
# model matrix → resnet profile → flash/striped microbenches →
# tuned-batch GPT legs → overlap/fusion → tuned ResNet/BERT extras →
# bert profile → elastic reset.
# (name, argv tail, timeout_s). bench.py is one process that fails
# where it finds no TPU, so a down backend costs ONE timeout and never
# records a CPU number.
JOBS = [
    ("resnet50", ["bench.py",
                  "--model", "resnet50", "--batch-size", "256"], 1500),
    ("resnet50_nos2d", ["bench.py",
                        "--model", "resnet50", "--batch-size", "256",
                        "--no-s2d"], 1500),
    # Landed in the 15:41 window (2026-08-02); kept in the list so a
    # wiped state file re-captures them, but BELOW the headline legs.
    ("gpt_small", ["bench.py",
                   "--model", "gpt_small"], 1200),
    ("gpt_2k", ["bench.py",
                "--model", "gpt_small", "--seq-len", "2048",
                "--batch-size", "4"], 1500),
    ("vit_base", ["bench.py",
                  "--model", "vit_base", "--batch-size", "128"], 1200),
    ("bert_large", ["bench.py",
                    "--model", "bert_large"], 1200),
    ("inception3", ["bench.py",
                    "--model", "inception3", "--batch-size", "128"],
     1200),
    # Profiled runs: device-vs-wall gap (the r03 14% host tax — the
    # window timing fix should close it to <5%) + device-basis scaling.
    ("resnet50_profile", ["bench.py",
                          "--model", "resnet50", "--batch-size", "256",
                          "--num-iters", "3", "--profile-dir",
                          f"results/{_ROUND}/trace_resnet50"], 1500),
    ("flash", ["tools/tpu_microbench.py", "flash"], 1200),
    ("striped", ["tools/tpu_microbench.py", "striped"], 900),
    # Chip-proof for the kernel families no model bench exercises
    # (adasum VHDD math, int8 block quant): the CPU interpreter does
    # not catch TPU tiling violations, so these stay "believed
    # working" until they compile AND match their oracles on chip.
    ("kernels", ["tools/tpu_microbench.py", "kernels"], 900),
    # Tuned-batch GPT legs (r05): the first chip run measured gb=8 at
    # 13.4% model-MFU — batch-starved, not kernel-bound. These
    # quantify the batch lever on the same causal-flash path.
    ("gpt_small_b32", ["bench.py",
                       "--model", "gpt_small", "--batch-size", "32"],
     1200),
    ("gpt_small_b64", ["bench.py",
                       "--model", "gpt_small", "--batch-size", "64"],
     1200),
    ("gpt_2k_b16_remat", ["bench.py",
                          "--model", "gpt_small", "--seq-len", "2048",
                          "--batch-size", "16", "--remat"], 1500),
    ("overlap", ["tools/tpu_microbench.py", "overlap"], 900),
    ("fusion", ["tools/tpu_microbench.py", "fusion"], 900),
    ("resnet50_b512", ["bench.py",
                       "--model", "resnet50", "--batch-size", "512"],
     1500),
    ("bert_large_b32", ["bench.py",
                        "--model", "bert_large", "--batch-size", "32"],
     1500),
    ("bert_profile", ["bench.py",
                      "--model", "bert_large", "--num-iters", "3",
                      "--profile-dir", f"results/{_ROUND}/trace_bert"],
     1200),
    # The serving workload (docs/serve.md): multi-replica continuous
    # batching + KV-cache decode on the chip; its record is gated on
    # tokens/s + p99 latency instead of MFU (workload="serve").
    ("serve_gpt_small", ["bench.py",
                         "--serve", "--model", "gpt_small",
                         "--serve-requests", "200"], 1200),
    # Hybrid dp x pp parallelism (docs/pipeline.md): gpt_small split
    # into 2 pipeline stages under the scan-based 1F1B schedule, int8
    # stage-boundary sends, ZeRO-3 shards per stage — the record
    # carries the per-axis byte mix (activation bytes on pp, gradient
    # bytes on dp) and the per-stage memory block; gated on the same
    # train value/MFU bases (>2% worse than banked = regression).
    ("train_gpt_pp", ["bench.py",
                      "--model", "gpt_small", "--pipeline-stages", "2",
                      "--pp-wire", "int8", "--accum", "4",
                      "--zero-stage", "3", "--batch-size", "32"],
     1500),
    # Sequence parallelism (docs/sequence.md): gpt_small's 2k context
    # striped over 2 sp ranks, K/V ring hops in int8 — the record
    # carries hvd_tpu_seq_kv_bytes_total (seq_kv_bytes_by_axis) and
    # the memory block's per-rank vs dense activation accounting;
    # gated on the same train value/MFU bases (>2% worse than banked
    # = regression).
    ("train_gpt_seq", ["bench.py",
                       "--model", "gpt_small", "--seq-parallel", "2",
                       "--seq-impl", "ring", "--seq-wire", "int8",
                       "--seq-len", "2048", "--batch-size", "16"],
     1500),
    # Elastic reset under fire (VERDICT r3 #6): train → SIGKILL →
    # orbax restore + persistent-compile-cache warm start, all on the
    # real chip.
    ("elastic_reset", ["tools/tpu_elastic_reset.py"], 1800),
]

# Regression gate (ROADMAP item 5 seed, extended per-workload by ISSUE
# 11): a fresh capture is diffed against the best banked record for the
# same job across the round dirs, on the metric basis its workload
# defines. >GATE_PCT worse on any basis marks the record
# regression=true and the gate LOGS LOUDLY — the ratchet that turns
# banked chip numbers from anecdotes into a floor.
GATE_PCT = 2.0

# workload -> [(field, direction)]: direction +1 = higher is better
# (throughput/MFU), -1 = lower is better (latency).
GATE_BASES = {
    "train": [("value", +1), ("mfu", +1)],
    "serve": [("value", +1), ("latency_p99_s", -1)],
}


def _log(msg):
    print(f"[{time.strftime('%H:%M:%S')}] queue: {msg}",
          file=sys.stderr, flush=True)


def _state_path():
    return os.path.join(OUTDIR, "state.json")


def load_state():
    try:
        with open(_state_path()) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return {"done": {}, "fails": {}}


def save_state(state):
    os.makedirs(OUTDIR, exist_ok=True)
    tmp = _state_path() + ".tmp"
    with open(tmp, "w") as f:
        json.dump(state, f, indent=2)
    os.replace(tmp, _state_path())


def probe():
    """True iff the TPU backend answers within PROBE_TIMEOUT."""
    code = ("import jax; d = jax.devices(); "
            "assert d[0].platform == 'tpu', d; print(d[0].device_kind)")
    try:
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True,
                              timeout=PROBE_TIMEOUT, cwd=REPO)
    except subprocess.TimeoutExpired:
        _log("probe: hung (timeout) — backend down")
        return False
    if proc.returncode != 0:
        _log(f"probe: rc={proc.returncode} "
             f"{(proc.stderr or '').strip().splitlines()[-1:]}")
        return False
    _log(f"probe: serving ({proc.stdout.strip()})")
    return True


def run_job(name, argv, timeout_s):
    cmd = [sys.executable] + [
        a if a.startswith("-") or not a.endswith(".py")
        else os.path.join(REPO, a) for a in argv]
    _log(f"job {name}: starting (timeout {timeout_s}s)")
    # Every job's hvd.init() places the one compile cache
    # (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache), so a
    # retry or a same-config sibling (resnet50 vs resnet50_profile,
    # bert_large vs bert_profile) skips its 20-40s compile.
    t0 = time.time()
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=timeout_s, cwd=REPO)
    except subprocess.TimeoutExpired as e:
        # The partial stderr says WHERE it hung (backend init vs compile
        # vs mid-iteration) — the difference between "no chip" and
        # "this model's program is slow".
        partial = e.stderr or b""
        if isinstance(partial, bytes):
            partial = partial.decode("utf-8", "replace")
        _log(f"job {name}: TIMED OUT after {timeout_s}s; stderr tail:\n"
             f"{partial[-800:]}")
        return None
    dt = time.time() - t0
    tail = (proc.stderr or "")[-1500:]
    if proc.returncode != 0:
        _log(f"job {name}: rc={proc.returncode} after {dt:.0f}s\n{tail}")
        return None
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    try:
        payload = json.loads(lines[-1])
    except (IndexError, json.JSONDecodeError):
        _log(f"job {name}: unparseable stdout tail: {lines[-1:]}")
        return None
    if payload.get("platform") != "tpu":
        _log(f"job {name}: refused non-TPU record "
             f"(platform={payload.get('platform')})")
        return None
    payload["wall_s"] = round(dt, 1)
    payload["captured_unix"] = int(time.time())
    _log(f"job {name}: OK in {dt:.0f}s -> {json.dumps(payload)[:300]}")
    return payload


# profile job -> (trace dir, analyzer summary filename): the summary
# feeds perf_evidence.py's device-basis scaling rows.
PROFILE_TRACES = {
    "resnet50_profile": ("trace_resnet50", "trace_summary.json"),
    "bert_profile": ("trace_bert", "trace_bert_summary.json"),
}


def _summarize_trace(job_name):
    trace_dir, summary = PROFILE_TRACES.get(job_name, (None, None))
    if trace_dir is None:
        return
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "analyze_trace.py"),
             os.path.join(OUTDIR, trace_dir)],
            capture_output=True, text=True, timeout=300)
        if proc.returncode == 0:
            with open(os.path.join(OUTDIR, summary), "w") as f:
                f.write(proc.stdout)
            _log(f"job {job_name}: trace summarized -> {summary}")
        else:
            _log(f"job {job_name}: trace analysis rc={proc.returncode}")
    except Exception as e:  # noqa: BLE001 — post-processing only
        _log(f"job {job_name}: trace analysis failed ({e})")


def best_banked(name, skip_current=True):
    """The BEST prior record for job ``name`` across the round dirs
    (``skip_current`` excludes the dir a fresh capture is about to land
    in, so a record is never gated against itself). 'Best' = highest
    primary-basis ``value`` (throughput for both workloads) among valid
    TPU records — NOT the newest: gating against the newest would let
    the floor decay ~GATE_PCT per round (each capture 2% worse than
    the last, none ever flagged); gating against the max makes the
    banked number an actual ratchet."""
    best, best_dir = None, None
    for rdir in _SEARCH_ORDER:
        if skip_current and rdir == _ROUND:
            continue
        path = os.path.join(REPO, "results", rdir, f"{name}.json")
        try:
            with open(path) as f:
                rec = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        if not isinstance(rec, dict) or rec.get("platform") != "tpu" \
                or not isinstance(rec.get("value"), (int, float)):
            continue
        if best is None or rec["value"] > best["value"]:
            best, best_dir = rec, rdir
    return best, best_dir


def gate_record(name, payload, banked=None):
    """Per-workload regression gate: diff ``payload`` against the best
    banked record on its workload's bases (GATE_BASES — training diffs
    value/MFU, serve diffs tokens/s + p99 latency). Returns the diff
    dict (also annotated onto the payload) or None when there is
    nothing comparable; regressions past GATE_PCT set
    ``payload["regression"] = True`` and log loudly."""
    if banked is None:
        banked, rdir = best_banked(name)
    else:
        rdir = "given"
    if banked is None:
        return None
    workload = payload.get("workload", "train")
    if banked.get("workload", "train") != workload:
        return None  # a job that changed workload is not comparable
    diffs, regressed = {}, []
    for field, direction in GATE_BASES.get(workload, GATE_BASES["train"]):
        new, old = payload.get(field), banked.get(field)
        if not isinstance(new, (int, float)) \
                or not isinstance(old, (int, float)) or not old:
            continue
        delta_pct = (new - old) / abs(old) * 100.0
        diffs[field] = {"new": new, "banked": old,
                        "delta_pct": round(delta_pct, 2)}
        if direction * delta_pct < -GATE_PCT:
            regressed.append(field)
    if not diffs:
        return None
    # Memory block (docs/zero.md): diff the sharding-derived per-rank
    # state bytes. Same-zero-stage growth past the gate is a REGRESSION
    # (the state got fatter at the same sharding); across stages the
    # delta is the A/B evidence and stays informational.
    new_mem, old_mem = payload.get("memory"), banked.get("memory")
    if isinstance(new_mem, dict) and isinstance(old_mem, dict):
        mem = {}
        for field in ("per_rank_at_rest_bytes", "per_rank_peak_bytes"):
            nv, ov = new_mem.get(field), old_mem.get(field)
            if isinstance(nv, (int, float)) and ov:
                mem[field] = {"new": nv, "banked": ov,
                              "delta_pct": round(
                                  (nv - ov) / abs(ov) * 100.0, 2)}
        if mem:
            mem["zero_stage"] = {"new": new_mem.get("zero_stage"),
                                 "banked": old_mem.get("zero_stage")}
            diffs["memory"] = mem
            same_stage = (new_mem.get("zero_stage")
                          == old_mem.get("zero_stage"))
            at_rest = mem.get("per_rank_at_rest_bytes", {})
            if same_stage and at_rest.get("delta_pct", 0) > GATE_PCT:
                regressed.append("memory.per_rank_at_rest_bytes")
    gate = {"vs": rdir, "workload": workload, "diffs": diffs,
            "regressed": regressed}
    payload["gate"] = gate
    def _pct(f):
        d = diffs
        for part in f.split("."):
            d = d.get(part, {}) if isinstance(d, dict) else {}
        v = d.get("delta_pct") if isinstance(d, dict) else None
        return f"{v:+.1f}%" if isinstance(v, (int, float)) else "?"

    if regressed:
        payload["regression"] = True
        _log(f"job {name}: REGRESSION vs banked {rdir} record on "
             + ", ".join(f"{f} ({_pct(f)})" for f in regressed))
    else:
        _log(f"job {name}: gate ok vs {rdir} ("
             + ", ".join(f"{f} {_pct(f)}" for f in diffs) + ")")
    return gate


def write_result(name, payload):
    os.makedirs(OUTDIR, exist_ok=True)
    gate_record(name, payload)
    with open(os.path.join(OUTDIR, f"{name}.json"), "w") as f:
        json.dump(payload, f, indent=2)
    _summarize_trace(name)
    combined = {}
    for n, _, _ in JOBS:
        p = os.path.join(OUTDIR, f"{n}.json")
        if os.path.exists(p):
            with open(p) as f:
                combined[n] = json.load(f)
    with open(os.path.join(OUTDIR, "results.json"), "w") as f:
        json.dump(combined, f, indent=2)
    _write_summary_md(combined)


def _write_summary_md(combined):
    """Digest the captures into a human-readable table after every job,
    so a window served while nobody is watching still leaves curated
    evidence (not just raw JSON) for the round record."""
    lines = [
        "# TPU capture summary (auto-generated by tpu_bench_queue)",
        "",
        "One row per captured job; raw records sit beside this file.",
        "",
        "| job | metric | value | unit | model-MFU % | exec-MFU % | "
        "vs_baseline | captured (unix) |",
        "|---|---|---|---|---|---|---|---|",
    ]
    def cell(v):
        # Raw record strings must not break the table structure.
        return str(v).replace("|", "\\|").replace("\n", " ")

    for name, rec in sorted(combined.items()):
        if not isinstance(rec, dict):
            continue
        row = [cell(name)] + [
            cell(rec.get(k, "—"))
            for k in ("metric", "value", "unit", "mfu_model_pct",
                      "mfu_exec_pct", "vs_baseline", "captured_unix")]
        lines.append("| " + " | ".join(row) + " |")
    lines += [
        "",
        "Microbench jobs (flash/striped/overlap/fusion/elastic_reset) "
        "carry structured payloads — see their JSON.",
    ]
    try:
        # utf-8 explicitly: the em-dash placeholders are this script's
        # only non-ASCII output, and a LANG=C queue host must not die
        # mid-serving-window on an encoding error.
        with open(os.path.join(OUTDIR, "SUMMARY.md"), "w",
                  encoding="utf-8") as f:
            f.write("\n".join(lines) + "\n")
    except (OSError, ValueError) as e:
        _log(f"summary write failed ({e})")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--max-hours", type=float, default=11.0)
    ap.add_argument("--once", action="store_true",
                    help="single probe+drain pass, no sleep loop")
    args = ap.parse_args()

    deadline = time.time() + args.max_hours * 3600
    state = load_state()
    _log(f"starting; done={sorted(state['done'])}")

    while time.time() < deadline:
        pending = [(n, a, t) for n, a, t in JOBS
                   if n not in state["done"]
                   and state["fails"].get(n, 0) < MAX_FAILS_PER_JOB]
        if not pending:
            _log("queue drained (or all jobs exhausted retries); exiting")
            break
        if probe():
            name, argv, timeout_s = pending[0]
            payload = run_job(name, argv, timeout_s)
            if payload is not None:
                write_result(name, payload)
                state["done"][name] = payload.get("captured_unix")
            else:
                state["fails"][name] = state["fails"].get(name, 0) + 1
            save_state(state)
            # No sleep on success — drain the window while it lasts.
            continue
        if args.once:
            break
        time.sleep(PROBE_SLEEP)

    remaining = [n for n, _, _ in JOBS if n not in state["done"]]
    _log(f"exiting; captured={sorted(state['done'])} missing={remaining}")
    return 0 if not remaining else 1


if __name__ == "__main__":
    sys.exit(main())
