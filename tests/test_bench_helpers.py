"""Pins for bench.py's model-basis MFU helpers (VERDICT r3 #2): the
analytic FLOP counts must stay on the textbook bases the records claim,
or mfu_model_pct silently changes meaning across rounds."""

import sys

import numpy as np
import pytest

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import bench  # noqa: E402


def test_cnn_model_flops_textbook_basis():
    # ResNet-50 at native 224: 3 x 4.1 GFLOP/img.
    got = bench._cnn_model_flops("resnet50", 224)
    assert abs(got - 3 * 4.1e9) / got < 1e-6
    # Resolution scaling is quadratic (the conv-FLOPs law).
    assert abs(bench._cnn_model_flops("resnet50", 112) - got / 4) < 1.0
    # Inception's native size is 299, not 224.
    inc = 3 * 5.73e9
    assert abs(bench._cnn_model_flops("inception3", 299) - inc) / inc \
        < 1e-6
    assert bench._cnn_model_flops("unknown_model", 224) is None


def test_transformer_model_flops_formula():
    # Tiny fake params: P = 1000 total elements.
    params = {"a": np.zeros((10, 50)), "b": np.zeros((500,))}
    L, d, S = 2, 8, 16
    got = bench._transformer_model_flops(params, L, d, S)
    # 6*P*S + 12*L*S^2*d, exactly.
    assert got == 6.0 * 1000 * S + 12.0 * L * S * S * d


def test_transformer_model_flops_bert_large_magnitude():
    """BERT-large S=512 lands near the expected ~1.1 TFLOP/sample
    (6*335M*512 = 1.03T params term + 77G attention term) — the sanity
    band that keeps mfu_model_pct honest."""
    p_bert = 335e6  # ~BERT-large parameter count
    params = {"w": np.zeros((int(p_bert),), np.int8)}
    got = bench._transformer_model_flops(params, 24, 1024, 512)
    assert 0.9e12 < got < 1.4e12, got


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
                or k == "peak_flops_basis"], rec.keys()
    assert "mfu" not in rec["config_note"]


def test_peak_flops_unknown_device_kind_is_an_error(monkeypatch):
    import types

    import jax

    def kind(k):
        monkeypatch.setattr(
            jax, "devices", lambda: [types.SimpleNamespace(device_kind=k)])

    kind("TPU v5 lite")
    assert bench._peak_flops() == 197e12
    kind("TPU v5p")
    assert bench._peak_flops() == 459e12
    for unknown in ("cpu", "TPU v9", "NVIDIA H100"):
        kind(unknown)
        with pytest.raises(ValueError, match="no published peak"):
            bench._peak_flops()


def test_serve_arm_tp_with_one_device_is_an_error():
    proc = _bench("--_platform=cpu", "--smoke", "--serve", "--serve-arm",
                  "tp")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "--serve-arm tp" in proc.stderr and "JAX has 1" in proc.stderr


def test_round_dirs_single_source():
    """bench, the queue, and the tools must agree on the round dirs
    (code-review r5: the r4->r5 bump missed two of four files)."""
    from tools.round_dirs import CURRENT, SEARCH_ORDER

    assert SEARCH_ORDER[0] == CURRENT
    import tools.tpu_bench_queue as q

    assert q.OUTDIR.endswith(CURRENT)
    import tools.tpu_elastic_reset as er

    assert er._ROUND == CURRENT
    import tools.perf_evidence as pe

    assert tuple(pe._round_search_order()) == tuple(SEARCH_ORDER)
