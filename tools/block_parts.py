#!/usr/bin/env python3
"""The parts of a model's blocks, ms a step, from a cell's newest trace.

    python3 tools/block_parts.py --workload <cell> --steps <n>

Reads the newest ``.xplane.pb`` under ``.bench_scratch/<cell>`` (left by
``python3 benchmark/run.py --workload <cell> ... --trace 1``; ``--steps``
is the ``steps`` of that run's ``trace`` note) with the benchmark's own
readers and prints one JSON line: the six readings of a block's parts
(docs/timeline.md: ``hvd_mixer_proj``, ``hvd_rope`` inside it,
``hvd_mlp`` with ``hvd_moe_shared``, ``hvd_norm``, ``hvd_embed``,
``hvd_loss``) and an expert layer's three, ``null`` where no event
carries the name. For a cell whose ``BENCHMARK.json`` lists do not hold
the readings yet; a cell that lists them prints them itself.
"""

import argparse
import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import hlo_counts, of_which, phase_reduce  # noqa: E402
from benchmark.catalog import Catalog  # noqa: E402

READERS = ("mixer_proj_ms", "rope_ms", "mlp_ms", "norm_ms", "embed_ms",
           "loss_ms", "moe_route_ms", "moe_expert_ms")
SHARED = "hvd_moe_shared"   # no reader's alone: ``mlp_ms`` holds it


def read(cell: str, steps: int, root: str = ROOT) -> dict:
    traces = glob.glob(os.path.join(root, ".bench_scratch", cell, "plugins",
                                    "profile", "*", "*.xplane.pb"))
    if not traces:
        raise SystemExit(f"no trace under .bench_scratch/{cell}")
    trace = phase_reduce.read_trace(max(traces, key=os.path.getmtime),
                                    hlo_counts.load_names())
    record = {"trace": {"steps": steps},
              "of_which_trace": of_which._without_loops(trace)}
    catalog = Catalog()     # the readers are this checkout's own
    out = {name: catalog.module("layer_metrics", name).read(record)
           for name in READERS}
    out["moe_shared_ms"] = of_which.per_step_ms(record, SHARED)
    return out


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--steps", type=int, required=True)
    args = parser.parse_args()
    print(json.dumps(read(args.workload, args.steps)))
