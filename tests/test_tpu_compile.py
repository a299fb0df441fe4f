"""The main path's Pallas kernels, compiled for a described TPU v5e at
the widths chip_smoke.py runs them.

Interpret mode (every other kernel test) never meets the Mosaic
lowering: tiling, block-shape and VMEM limits only show when the chip's
compiler is asked. libtpu compiles for a topology that is described, not
attached, so these cases cost no chip time; nothing executes, so they
say nothing about results — chip_smoke.py's ``kernels`` phase does that.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops import pallas_kernels as pk

BUCKET = 25_000_000  # one 100 MB fp32 gradient bucket
FLASH_SHAPES = [(8, 512, 12, 64), (4, 2048, 12, 64)]  # gpt_small b/S/h/d


@pytest.fixture(scope="module")
def v5e():
    """SingleDeviceSharding on one chip of a described v5e:2x2."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, no topology
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    # A compile for a described device is written to the persistent
    # cache but cannot be read back without the chip; the next run would
    # warn on every case.
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


@pytest.fixture()
def on_tpu(monkeypatch):
    """The kernels pick Pallas-vs-jnp from jax.default_backend(), which
    is the CPU here: steer them onto the compiled (non-interpret) kernel
    path, as they would decide on the chip."""
    monkeypatch.setattr(pk, "_on_tpu", lambda: True)


def _compile(fn, sharding, *specs):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in specs]
    return jax.jit(fn).lower(*args).compile().as_text()


def _flat(dtype):
    return ((BUCKET,), dtype)


def _quantized():
    rows = -(-BUCKET // (pk._Q_ROWS * pk._LANES)) * pk._Q_ROWS
    return ((rows, pk._LANES), jnp.int8), ((rows // pk._Q_ROWS,),
                                           jnp.float32)


def _stochastic(x):
    return pk.quantize_int8_stochastic(x, jax.random.PRNGKey(0))[:2]


def _dequantize(dtype, q, s):
    return pk.dequantize_int8(q, s, BUCKET, (BUCKET,), dtype)


KERNELS = {
    "scale_buffer": lambda dt: (lambda x: pk.scale_buffer(x, 0.5),
                                [_flat(dt)]),
    "adasum_dot_norms": lambda dt: (pk.adasum_dot_norms,
                                    [_flat(dt), _flat(dt)]),
    "adasum_combine": lambda dt: (pk.adasum_combine,
                                  [_flat(dt), _flat(dt),
                                   ((3,), jnp.float32)]),
    "quantize_int8": lambda dt: (lambda x: pk.quantize_int8(x)[:2],
                                 [_flat(dt)]),
    "quantize_int8_stochastic": lambda dt: (_stochastic, [_flat(dt)]),
    "dequantize_int8": lambda dt: (functools.partial(_dequantize, dt),
                                   list(_quantized())),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_bucket_kernel_compiles_for_v5e(v5e, on_tpu, kernel, dtype):
    fn, specs = KERNELS[kernel](dtype)
    assert "tpu_custom_call" in _compile(fn, v5e, *specs)


@pytest.mark.parametrize("shape", FLASH_SHAPES,
                         ids=["b8_s512", "b4_s2048"])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_attention_compiles_for_v5e(v5e, on_tpu, direction, shape):
    def fwd(q, k, v):
        return fa.flash_attention(q, k, v, causal=True)

    def bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    hlo = _compile(fwd if direction == "fwd" else bwd, v5e,
                   *[(shape, jnp.bfloat16)] * 3)
    # bwd recomputes nothing: fwd kernel for the residuals, then dq
    # and dk/dv.
    assert hlo.count("tpu_custom_call") >= (1 if direction == "fwd" else 3)
