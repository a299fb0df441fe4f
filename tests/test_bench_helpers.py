"""bench.py as a user runs it: what it refuses, what its one JSON line
holds, and its at-rest memory block. It is no yardstick: utilisation
and device time are benchmark/run.py's (tests/benchmark/)."""

import sys

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import bench  # noqa: E402


def _bench(*argv):
    """`python bench.py ...` as a user runs it, on a host whose JAX has
    one CPU device and nothing else."""
    import os
    import subprocess

    repo = os.path.dirname(os.path.abspath(bench.__file__))
    env = {k: v for k, v in os.environ.items()
           if k != "HVD_TPU_FORCE_CPU_DEVICES"}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    return subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py"), *argv],
        capture_output=True, text=True, timeout=300, env=env)


def test_default_run_without_a_tpu_fails_and_prints_no_metric():
    """No CPU rung, no cached re-emit: where JAX finds no TPU the
    default run is one process that exits non-zero with empty stdout."""
    proc = _bench()
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_cpu_record_only_by_name_and_without_mfu():
    import json

    proc = _bench("--_platform=cpu", "--smoke", "--model", "gpt_tiny",
                  "--num-warmup", "1", "--num-iters", "1",
                  "--batches-per-iter", "1")
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["platform"] == "cpu" and rec["value"] > 0
    assert not [k for k in rec if k.startswith("mfu")
                or k in ("peak_flops_basis", "vs_baseline",
                         "baseline_variant")], rec.keys()
    assert "mfu" not in rec["config_note"]


def test_serve_arm_tp_with_one_device_is_an_error():
    proc = _bench("--_platform=cpu", "--smoke", "--serve", "--serve-arm",
                  "tp")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "--serve-arm tp" in proc.stderr and "JAX has 1" in proc.stderr


def test_bench_memory_block_shows_zero3_win():
    """bench._memory_block: stage-3 per-rank at-rest state bytes drop
    >=3x vs stage 1 on an 8-rank world (the acceptance number)."""
    import optax

    params = {"w": np.zeros((1024, 64), np.float32),
              "b": np.zeros((64,), np.float32)}
    inner = optax.adamw(1e-3)
    m1 = bench._memory_block(params, inner, 1, 8, accum=2)
    m3 = bench._memory_block(params, inner, 3, 8, accum=2)
    assert m1["per_rank_at_rest_bytes"] >= \
        3 * m3["per_rank_at_rest_bytes"]
    assert m3["per_rank_at_rest"]["params"] * 8 == \
        m1["per_rank_at_rest"]["params"]
