#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is found by name in ``BENCHMARK.json``; its job kind, family,
reference and per-layer metrics are found by the names its files give.
This file knows none of them.

The LAST line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, in a traced run, ``breakdown``.
With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics. Earlier lines are notes, one JSON
object each. A run that finds no TPU, or not the number of chips the cell
asks for, exits non-zero and prints no result.

``--rehearse-cpu`` is for finding faults without a chip: the cell's tiny
preset on (virtual) CPU devices. Its last line has the same keys and an
empty ``metrics``: a CPU gives no time, rate, utilization or idle share.
"""

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Any, Callable  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.catalog import Catalog  # noqa: E402


@dataclasses.dataclass
class Run:
    """What a job kind is given."""

    catalog: Catalog
    cell: dict
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    t_start: float
    say: Callable[..., None]
    scratch: str          # a directory inside the checkout for traces


def say(note: str, **fields: Any) -> None:
    print(json.dumps({"note": note, **fields}), flush=True)


def _rehearsal_environment(chips: int) -> None:
    """Before JAX is imported: the CPU, with one virtual device a chip."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={chips}")
    os.environ["XLA_FLAGS"] = " ".join(flags)
    os.environ["HVD_TPU_FORCE_CPU_DEVICES"] = str(chips)
    # A rehearsal leaves nothing behind and reads nothing a chip run wrote.
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    parser.add_argument("--rehearse-cpu", action="store_true",
                        help="tiny preset on the CPU; prints no metric")
    args = parser.parse_args(argv)

    catalog = Catalog(ROOT)
    cell = catalog.cell(args.workload)
    if args.rehearse_cpu:
        _rehearsal_environment(cell["chips"])
    job = catalog.module("jobs", cell["job"])
    notes = say
    if args.rehearse_cpu:
        # A CPU's times and rates never appear under a metric's name.
        metric_names = {m["name"] for key in ("end_to_end", "per_layer")
                        for m in catalog.index[key]}

        def notes(note, **fields):
            say(note, **{k: v for k, v in fields.items()
                         if k not in metric_names})

    run = Run(catalog=catalog, cell=cell, seed=args.seed,
              seconds=args.seconds, trace=bool(args.trace),
              rehearse=args.rehearse_cpu, t_start=T_START, say=notes,
              scratch=os.path.join(ROOT, ".bench_scratch", cell["name"]))
    record = job.run(run)

    metrics = {}
    if args.trace:
        reported = {m["name"] for m in
                    catalog.metrics("end_to_end", cell["name"])}
        for m in catalog.metrics("per_layer", cell["name"]):
            if m["moves"] not in reported:
                continue
            value = catalog.module("layer_metrics", m["name"]).read(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in catalog.metrics("end_to_end", cell["name"]):
            value = record["end_to_end"].get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if args.rehearse_cpu:
        say("rehearsal", would_report=sorted(metrics),
            platform=record["device"]["platform"])
        metrics = {}

    last = {"correct": bool(record["correct"]),
            "attempted": int(record["attempted"]),
            "failed": int(record["failed"]),
            "metrics": metrics, "device": record["device"]}
    if args.trace and record.get("breakdown"):
        last["breakdown"] = record["breakdown"]
    print(json.dumps(last), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
