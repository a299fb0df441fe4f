"""Examples must stay runnable — each runs as a subprocess on the
8-virtual-device CPU mesh with tiny configs (the reference CI runs its
examples the same way, docker-compose.test.yml)."""

import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout=420, extra_env=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    if extra_env:
        env.update(extra_env)
    proc = subprocess.run([sys.executable] + args, env=env, cwd=_REPO,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    return proc.stdout


def test_mnist_example(tmp_path):
    out = _run(["examples/mnist_train.py", "--epochs", "1",
                "--batch-size", "64",
                "--ckpt-dir", str(tmp_path / "ckpt")])
    assert "loss" in out.lower()


def test_mnist_guard_example(tmp_path):
    """--guard: scale_backoff over the overflow-prone fp16 loss + one
    injected NaN batch, recovery visible in the metrics snapshot
    (docs/integrity.md)."""
    out = _run(["examples/mnist_train.py", "--epochs", "1",
                "--batch-size", "64", "--guard",
                "--ckpt-dir", str(tmp_path / "ckpt")])
    assert "guard summary" in out
    assert "hvd_tpu_nonfinite_steps_total" in out
    assert "'nonfinite_steps': 0" not in out  # the injection was seen


def test_keras_mnist_example(tmp_path):
    pytest.importorskip("keras")
    out = _run(["examples/keras_mnist.py", "--epochs", "1",
                "--ckpt", str(tmp_path / "m.keras")])
    assert "checkpoint reloaded with DistributedAdam" in out


def test_join_example():
    _run(["examples/join_uneven_data.py"])


def test_estimator_example():
    _run(["examples/estimator_fit.py", "--epochs", "3"])


def test_ray_example():
    out = _run(["examples/ray_train.py"],
               extra_env={"HVD_TPU_EXAMPLE_FAKE_RAY": "1"})
    assert "ray_train: OK" in out


def test_spark_elastic_example():
    out = _run(["examples/spark_elastic_train.py"],
               extra_env={"HVD_TPU_EXAMPLE_FAKE_SPARK": "1"})
    assert "spark elastic OK: 3 workers" in out


def test_adasum_example():
    _run(["examples/adasum_resnet.py", "--tiny", "--steps", "2",
          "--batch-size", "16"])


def test_torch_mnist_example():
    pytest.importorskip("torch")
    out = _run(["examples/torch_mnist.py", "--epochs", "1",
                "--batch-size", "32"])
    assert "done" in out


def test_gpt_long_context_example():
    out = _run(["examples/gpt_long_context.py", "--steps", "6",
                "--seq-len", "32"])
    assert "done: dp=2 sp=4 seq=32" in out


def test_gpt_long_context_zero1_example():
    out = _run(["examples/gpt_long_context.py", "--steps", "6",
                "--seq-len", "32", "--zero1"])
    assert "done: dp=2 sp=4 seq=32 zero1" in out


def test_parity_doc_references_resolve():
    """tools/check_parity.py whole, once: its surface checks (docs
    against code) pass. The reference resolver's own cases, one a
    document, are tier-1 in tests/test_check_parity.py."""
    out = _run(["tools/check_parity.py"], timeout=60)
    assert "all file/test/module references resolve" in out


def test_tf2_mnist_example():
    pytest.importorskip("tensorflow")
    out = _run(["examples/tf2_mnist.py", "--epochs", "3"])
    assert "allreduce-averaged over 8 ranks" in out


def test_gpt_long_context_fsdp_example():
    out = _run(["examples/gpt_long_context.py", "--steps", "6",
                "--seq-len", "32", "--fsdp"])
    assert "done: dp=2 sp=4 seq=32 fsdp" in out and "loss" in out


def test_fsdp_example():
    out = _run(["examples/fsdp_train.py", "--steps", "12"])
    assert "FSDP OK" in out


def test_moe_example():
    out = _run(["examples/moe_train.py", "--steps", "10"])
    assert "MoE OK" in out


def test_gpt_long_context_striped_example():
    out = _run(["examples/gpt_long_context.py", "--steps", "6",
                "--striped"])
    assert "done: dp=2 sp=4 seq=64 striped" in out and "loss" in out
