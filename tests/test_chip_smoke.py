"""chip_smoke.py rehearsed on the CPU mesh: the same phase functions the
chip runs at gpt_small width, here on a tiny model with the Pallas
kernels in interpret mode. Finds wrong paths, arguments and control flow
before a chip call does; says nothing about the chip."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from horovod_tpu.models.gpt import gpt_tiny  # noqa: E402


def _last_json(text):
    return json.loads(text.strip().splitlines()[-1])


def test_kernels_phase_tiny(capsys):
    # 18 quantization blocks: more than one grid step, last one ragged.
    # One dtype: tests/test_pallas_kernels.py has both, and every case
    # here is a compile.
    worst = chip_smoke.phase_kernels(
        bucket=18 * 4096 - 5, attn_shapes=(((1, 128, 2, 64), True),),
        dtypes=("float32",))
    assert set(worst) == {"bucket.float32", "flash.float32.1x128x2x64",
                          "rope.float32.1x128x2x64"}
    assert set(worst["rope.float32.1x128x2x64"]) == {"fwd", "dx"}
    assert worst["bucket.float32"][
        "quantize_int8_stochastic.q_mismatches"] == 0
    assert _last_json(capsys.readouterr().out)["phase"] == "kernels"


def test_kernels_phase_fails_on_any_excess(monkeypatch):
    """One check over its tolerance (or NaN) fails the phase by name."""
    for bad in (1e-9, float("nan")):
        monkeypatch.setattr(
            chip_smoke, "_bucket_kernels",
            lambda dtype, n: {"scale_buffer": -1.0, "dequantize_int8": bad})
        with pytest.raises(chip_smoke.SmokeFailure,
                           match="dequantize_int8"):
            chip_smoke.phase_kernels(bucket=4096, attn_shapes=())


def test_train_phase_tiny(hvd, capsys):
    losses = chip_smoke.phase_train(hvd, gpt_tiny(), batch=2, seq_len=32,
                                    steps=5, warmup=1, require_flash=False)
    assert len(losses) == 6 and losses[-1] < losses[0]
    line = _last_json(capsys.readouterr().out)
    assert line["phase"] == "train" and line["flash_custom_calls"] == 0
    assert len(line["step_wall_s"]) == 6


def test_train_phase_requires_the_flash_kernels(hvd):
    """On the CPU flash_attention gives way to the reference, which is
    exactly what the chip run must refuse."""
    with pytest.raises(chip_smoke.SmokeFailure, match="gave way"):
        chip_smoke.phase_train(hvd, gpt_tiny(), batch=2, seq_len=32,
                               steps=5, warmup=1)


def test_serve_phase_tiny(hvd, capsys):
    report = chip_smoke.phase_serve(
        hvd, gpt_tiny(), max_len=64, max_prompt_len=16, slots=2,
        n_requests=4, prompt_lens=(4, 9, 16), output_lens=(3, 6))
    assert report["completed"] == 4
    parity = _last_json(capsys.readouterr().out)["parity_request"]
    assert parity["argmax_matches"] == parity["tokens"] > 0


def test_serve_phase_fails_on_wrong_tokens(hvd, monkeypatch):
    from horovod_tpu.serve import engine

    real = engine._sample_token
    monkeypatch.setattr(engine, "_sample_token",
                        lambda row, *a: (real(row, *a) + 1) % row.shape[-1])
    with pytest.raises(chip_smoke.SmokeFailure, match="greedy tokens"):
        chip_smoke.phase_serve(
            hvd, gpt_tiny(), max_len=64, max_prompt_len=16, slots=2,
            n_requests=2, prompt_lens=(4,), output_lens=(3,))


def test_dp_phase_on_four_virtual_devices(hvd, capsys):
    """--chips 4 on four of the eight virtual devices: spmd_step +
    DistributedOptimizer against the one-device step, then int8_ef."""
    hvd.shutdown()
    try:
        hvd.init(comm=[0, 1, 2, 3])
        assert hvd.size() == 4
        losses = chip_smoke.phase_dp(hvd, gpt_tiny(), batch=8, seq_len=32,
                                     steps=4, loss_rtol=1e-4)
    finally:
        hvd.shutdown()
        hvd.init()
    assert len(losses) == 4
    line = _last_json(capsys.readouterr().out)
    assert line["devices"] == 4 and line["per_device_batch"] == 2
    assert line["all_reduce_ops"] >= 1 and line["int8_ef_all_to_all"]


def test_main_refuses_any_platform_but_tpu():
    """`python chip_smoke.py` where JAX has only the CPU: non-zero, last
    line ok=false with the device as JAX reports it, no phase line."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    lines = proc.stdout.strip().splitlines()
    assert len(lines) == 1, lines
    last = json.loads(lines[0])
    assert last["ok"] is False and last["device"]["platform"] == "cpu"
    assert "need 1 TPU chip" in proc.stderr
