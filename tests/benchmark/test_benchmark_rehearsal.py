"""Each cell end to end on the CPU at its tiny preset, as a user would
start it: the job runs, the system's step agrees with the plain
reference, and the last line carries exactly the contract's keys and no
metric. And without the rehearsal flag a machine with no TPU gets no
result at all."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.catalog import ROOT

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    INDEX = json.load(_f)
CELLS = [w["name"] for w in INDEX["workloads"]]
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(cell, trace, *extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "HVD_TPU_FORCE_CPU_DEVICES")}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, INDEX["command"][1]),
         "--workload", cell, "--seed", "5", "--seconds", "1",
         "--trace", str(trace), *extra],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_of_each_cell(cell):
    # Odd cells traced, even cells not: both paths of every job kind.
    trace = CELLS.index(cell) % 2
    out = _run(cell, trace, "--rehearse-cpu")
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(line) for line in out.stdout.splitlines()]
    last = lines[-1]
    assert set(last) == KEYS            # no breakdown: no device trace
    assert last["correct"] is True, lines
    assert last["failed"] == 0 and last["attempted"] > 0
    assert last["metrics"] == {}        # a CPU gives no metric
    chips = next(w["chips"] for w in INDEX["workloads"] if w["name"] == cell)
    assert last["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": chips, "memory_peak_bytes": 0}
    notes = {line["note"]: line for line in lines[:-1]}
    assert notes["setup"]["rehearsal"] is True
    assert notes["window"]["compiles"] == 0
    # No note carries a metric's name: those are for chip runs.
    metric_names = {m["name"] for key in ("end_to_end", "per_layer")
                    for m in INDEX[key]}
    assert not any(metric_names & set(line) for line in lines)
    assert all(notes["window"]["checks"].values())
    would = set(notes["rehearsal"]["would_report"])
    if trace:
        # Host-clock and counter readers find something; the readers of
        # the device trace find nothing on a CPU and report nothing.
        assert {"step_ms_p50", "compiles_in_window", "init_s"} <= would
        assert not would & {"dense_ms", "flash_ms", "device_idle_pct",
                            "collective_ms", "mfu_pct"}
    else:
        assert would == {"train_tokens_per_s", "step_hbm_gib", "setup_s"}
    if chips > 1:
        assert notes["hlo"]["collectives"]["all-reduce"]["ops"] >= 1
        assert "parameters_equal_on_all_chips" in notes["window"]["checks"]
    else:
        assert notes["hlo"]["collectives"] == {}


def test_without_a_tpu_there_is_no_result():
    out = _run(CELLS[0], 0)
    assert out.returncode != 0
    assert "needs 1 tpu device" in out.stderr
    for line in out.stdout.splitlines():
        assert "correct" not in line and "metrics" not in line
