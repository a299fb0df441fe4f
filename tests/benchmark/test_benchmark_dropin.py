"""The harness is driven by data: a configuration, a traffic mix, a cell,
a job kind and a per-layer metric dropped into a copy of the benchmark's
directories are found by name, with no harness file edited."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.catalog import ROOT, Catalog, CatalogError

JOB = '''
import time


def run(run):
    config = run.catalog.config(run.cell["config"])
    traffic = run.catalog.traffic(run.cell["traffic"])
    run.say("dropped", widgets=config["widgets"], rate=traffic["rate"])
    return {"correct": True, "attempted": 5, "failed": 0,
            "end_to_end": {"setup_s": time.perf_counter() - run.t_start,
                           "widgets_per_s": 7.5},
            "device": {"platform": "cpu", "kind": "cpu", "count": 1,
                       "memory_peak_bytes": 0},
            "widget": {"polish": 0.25},
            "breakdown": {"device_ops": [], "idle_gaps": []}}
'''

METRIC = '''
def read(record):
    return record.get("widget", {}).get("polish")
'''


@pytest.fixture()
def copy(tmp_path):
    """A copy of BENCHMARK.json and the harness with one of each kind of
    thing added: files dropped in, entries appended, nothing edited."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        index = json.load(f)
    home = index["paths"][0]
    shutil.copytree(os.path.join(ROOT, home), tmp_path / home,
                    ignore=shutil.ignore_patterns("__pycache__"))
    drop = {
        "configs/widget-9000.json": json.dumps(
            {"family": "none", "source": "https://example.org/widget",
             "widgets": 9000, "reduced": [], "assumed": {}}),
        "traffic/steady.json": json.dumps({"rate": 3}),
        "workloads/widget.steady.json": json.dumps(
            {"config": "widget-9000", "traffic": "steady", "chips": 1,
             "job": "count_widgets"}),
        "jobs/count_widgets.py": JOB,
        "layer_metrics/widget_polish.py": METRIC,
    }
    for rel, text in drop.items():
        (tmp_path / home / rel).write_text(text)
    index["configs"].append(
        {"name": "widget-9000", "source": "https://example.org/widget",
         "file": f"{home}/configs/widget-9000.json", "reduced": [],
         "why": "dropped in"})
    index["workloads"].append(
        {"name": "widget.steady", "config": "widget-9000",
         "traffic": "steady", "chips": 1, "why": "dropped in"})
    index["end_to_end"].append(
        {"name": "widgets_per_s", "unit": "widgets/s", "better": "higher",
         "bound": 0.05, "source": "host_clock",
         "workloads": ["widget.steady"]})
    index["per_layer"].append(
        {"name": "widget_polish", "unit": "%", "better": "higher",
         "source": "program_counter", "layer": "widgets",
         "moves": "widgets_per_s", "workloads": ["widget.steady"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(index))
    return tmp_path, index


def _run(root, index, *args):
    env = dict(os.environ, PYTHONPATH=ROOT)
    out = subprocess.run(
        [sys.executable, os.path.join(root, index["command"][1]),
         "--workload", "widget.steady", "--seed", "3", "--seconds", "1",
         "--rehearse-cpu", *args],
        capture_output=True, text=True, timeout=120, env=env, cwd=root)
    assert out.returncode == 0, out.stderr
    return [json.loads(line) for line in out.stdout.splitlines()]


def test_dropped_in_files_are_found_by_name(copy):
    root, _ = copy
    cat = Catalog(str(root))
    assert cat.config("widget-9000")["widgets"] == 9000
    assert cat.traffic("steady") == {"rate": 3}
    assert cat.cell("widget.steady")["job"] == "count_widgets"
    assert callable(cat.module("jobs", "count_widgets").run)
    assert callable(cat.module("layer_metrics", "widget_polish").read)
    assert [m["name"] for m in cat.metrics("end_to_end", "widget.steady")] \
        == ["train_tokens_per_s", "step_hbm_gib", "setup_s",
            "widgets_per_s"]
    # ... and the cells that were there do not report the new metrics.
    assert "widgets_per_s" not in [
        m["name"] for m in cat.metrics("end_to_end", "gpt2s-s512")]


@pytest.mark.parametrize("trace, would_report", [
    ("0", ["setup_s", "widgets_per_s"]),
    ("1", ["widget_polish"]),
])
def test_a_dropped_in_job_kind_runs_through_the_unedited_harness(
        copy, trace, would_report):
    root, index = copy
    lines = _run(str(root), index, "--trace", trace)
    assert lines[0] == {"note": "dropped", "widgets": 9000, "rate": 3}
    assert lines[-2]["would_report"] == would_report
    want = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(lines[-1]) == (want | {"breakdown"} if trace == "1"
                              else want)


def test_names_that_lead_nowhere_are_errors(copy):
    cat = Catalog(str(copy[0]))
    with pytest.raises(CatalogError):
        cat.cell("no-such-cell")
    with pytest.raises(CatalogError):
        cat.module("jobs", "no_such_kind")
    with pytest.raises(CatalogError):
        cat.module("jobs", "../run")
    with pytest.raises(CatalogError):
        cat.traffic("../../BENCHMARK")
