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
import re

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops import pallas_kernels as pk

BUCKET = 25_000_000  # one 100 MB fp32 gradient bucket
# (b, S, h, d), causal: gpt_small at three lengths (S4096 is what the
# dk/dv kernel could not hold in VMEM before it was tiled on its q side),
# bert_large's unmasked attention, the looped model's heads of 128.
FLASH_SHAPES = {"b8_s512": ((8, 512, 12, 64), True),
                "b4_s2048": ((4, 2048, 12, 64), True),
                "b4_s4096": ((4, 4096, 12, 64), True),
                "bert_b8_s512": ((8, 512, 16, 64), False),
                # an odd head count: one head a block, on (B, H, S, D)
                "b2_s512_h3": ((2, 512, 3, 64), True),
                # Ouro-2.6B: one head of 128 fills a 128-lane block, and
                # the softmax scale (no power of two) rides on the scores
                "ouro_b2_s2048_d128": ((2, 2048, 16, 128), True)}


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


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "fp32"])
@pytest.mark.parametrize("shape", sorted(FLASH_SHAPES))
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_attention_compiles_for_v5e(v5e, on_tpu, direction, shape,
                                          dtype):
    (b, s, h, d), causal = FLASH_SHAPES[shape]

    def fwd(q, k, v):
        return fa.flash_attention(q, k, v, causal=causal)

    def bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    hlo = _compile(fwd if direction == "fwd" else bwd, v5e,
                   *[((b, s, h, d), dtype)] * 3)
    # bwd recomputes nothing: fwd kernel for the residuals, then dq
    # and dk/dv — one Mosaic call each, under its own name.
    kernels = ["hvd_flash_fwd"] + ["hvd_flash_dq", "hvd_flash_dkv"] * (
        direction == "bwd")
    assert hlo.count("tpu_custom_call") >= len(kernels)
    for name in kernels:
        assert name in hlo
    # Operands cross at the caller's head width and dtype: nothing is
    # padded to the 128 lanes on the way in, and no per-row vector (lse,
    # delta, the lse cotangent) is broadcast to (B, H, S, 128).
    assert " pad(" not in hlo
    assert not re.search(rf"\[{b},{h},{s},128\]", hlo)
    if h % 2 == 0:
        # Two heads of 64 fill the 128 lanes: the kernels read the
        # caller's (B, S, H*D) as it lies, nothing is transposed.
        assert re.search(rf"custom-call\([^)]*\).*"
                         rf"operand_layout_constraints=\{{\w+\[{b},{s},"
                         rf"{h * d}\]", hlo)
        assert not re.search(rf"\[{b},{h},{s},{d}\]", hlo)


def test_flash_with_lse_backward_compiles_for_v5e(v5e, on_tpu):
    """Ring attention's interface: a key mask operand, the lse as an
    output and its cotangent folded into the one row operand — on a
    short local block."""
    b, s, h, d = 2, 256, 4, 64

    def loss(q, k, v, mask):
        o, lse = fa.flash_attention_with_lse(q, k, v, mask=mask,
                                             causal=True)
        return o.astype(jnp.float32).sum() + lse.sum()

    hlo = _compile(jax.grad(loss, argnums=(0, 1, 2)), v5e,
                   *[((b, s, h, d), jnp.bfloat16)] * 3,
                   ((b, s), jnp.float32))
    assert hlo.count("tpu_custom_call") >= 3
    assert not re.search(rf"\[{b},{h},{s},128\]", hlo)
