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
import numpy as np
import pytest

from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops import pallas_kernels as pk

BUCKET = 25_000_000  # one 100 MB fp32 gradient bucket
# (b, S, h, d), causal or the mask kind: gpt_small at three lengths (S4096
# is what the dk/dv kernel could not hold in VMEM before it was tiled on
# its q side), bert_large's unmasked attention, the looped model's heads
# of 128.
FLASH_SHAPES = {"b8_s512": ((8, 512, 12, 64), True),
                "b4_s2048": ((4, 2048, 12, 64), True),
                "b4_s4096": ((4, 4096, 12, 64), True),
                "bert_b8_s512": ((8, 512, 16, 64), False),
                # an odd head count: one head a block, on (B, H, S, D)
                "b2_s512_h3": ((2, 512, 3, 64), True),
                # Ouro-2.6B: one head of 128 fills a 128-lane block, and
                # the softmax scale (no power of two) rides on the scores
                "ouro_b2_s2048_d128": ((2, 2048, 16, 128), True),
                # the gated-convolution cell's attention: S 8192, two
                # packed heads of 64 a tile, K and V of 8 heads repeated
                "lfm2_b2_s8192_kv8": ((2, 8192, 32, 64), True),
                # the block-diffusion cell's: [noisy ; clean] of 2 x 4096,
                # heads of 128 on 4 K/V heads through the index maps
                "sdar_b2_s8192_d128_kv4": ((2, 8192, 32, 128),
                                           fa.BlockDiffusionMask(4))}
# K/V heads where they are fewer than q's
FLASH_KV_HEADS = {"lfm2_b2_s8192_kv8": 8, "sdar_b2_s8192_d128_kv4": 4}


@pytest.fixture(scope="module")
def v5e_2x2():
    """A described v5e:2x2: four chips, none attached."""
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
    yield topo
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def v5e(v5e_2x2):
    """SingleDeviceSharding on one chip of the described v5e:2x2."""
    return jax.sharding.SingleDeviceSharding(v5e_2x2.devices[0])


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


def _mosaic_calls(hlo):
    """Mosaic calls in a compiled program's text: the instructions, not
    the attribute every one of them carries."""
    return len(re.findall(r" custom-call\([^\n]*custom_call_target="
                          r'"tpu_custom_call"', hlo))


def _hlo_type(dtype, b, s, h, d):
    """How the compiled text writes a (B, S, H, D) operand of the flash
    kernels: (B, S, H*D) where the heads pack the lanes, else per head."""
    name = {jnp.bfloat16: "bf16", jnp.float32: "f32"}[dtype]
    dims = (b, s, h * d) if fa._Layout(h, d).packed else (b, h, s, d)
    shape = ",".join(map(str, dims))
    return rf"{name}\[{shape}\]\{{[\d,]*(?::[^}}]*)?\}}"


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


def _with_residual(x, r, key=None):
    """The error-feedback form: (x + r) / 2 quantised, its residual the
    kernel's third result."""
    quantize = pk.quantize_int8 if key is None else functools.partial(
        pk.quantize_int8_stochastic, key=key)
    q, s, _, residual = quantize(x, plus=r, prescale=0.5,
                                 return_residual=True)
    return q, s, residual


def _stacked(ranks):
    """Four ranks' gathered chunks as one (ranks x rows, 128) view."""
    ((rows, lanes), _), ((blocks,), _) = _quantized()
    return (((ranks * rows, lanes), jnp.int8),
            ((ranks * blocks,), jnp.float32))


def _dequantize_stacked(dtype, q, s):
    n = q.shape[0] * q.shape[1]
    return pk.dequantize_int8(q, s * 0.25, n, (n,), dtype)


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
    "quantize_int8_residual": lambda dt: (
        _with_residual, [_flat(dt), _flat(jnp.float32)]),
    "quantize_int8_stochastic_residual": lambda dt: (
        functools.partial(_with_residual, key=jax.random.PRNGKey(0)),
        [_flat(dt), _flat(jnp.float32)]),
    "dequantize_int8_stacked": lambda dt: (
        functools.partial(_dequantize_stacked, dt), list(_stacked(4))),
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
    (b, s, h, d), mask = FLASH_SHAPES[shape]
    hkv = FLASH_KV_HEADS.get(shape, h)

    def fwd(q, k, v):
        return fa.flash_attention(q, k, v, mask_kind=fa._as_kind(mask))

    def bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    hlo = _compile(fwd if direction == "fwd" else bwd, v5e,
                   ((b, s, h, d), dtype), *[((b, s, hkv, d), dtype)] * 2)
    # bwd recomputes nothing: the fwd kernel for the residuals, then ONE
    # Mosaic call that gives dq, dk and dv, under the dk/dv call's name;
    # no call carries the dq kernel's name since the two were fused.
    kernels = ["hvd_flash_fwd"] + ["hvd_flash_dkv"] * (direction == "bwd")
    assert _mosaic_calls(hlo) == len(kernels)
    for name in kernels:
        assert name in hlo
    assert "hvd_flash_dq" not in hlo
    if direction == "bwd":
        qkv = _hlo_type(dtype, b, s, h, d)
        assert re.search(rf"%[\w.]*hvd_flash_dkv[\w.]* = "
                         rf"\({qkv}, {qkv}, {qkv}\) custom-call\(", hlo)
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


def _reductions(jaxpr):
    """``(primitive, operand shape, axes)`` of every reduction in a jaxpr
    and in the jaxprs its equations carry (loop bodies, branches)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith(("reduce_", "argm")):
            yield (eqn.primitive.name, eqn.invars[0].aval.shape,
                   tuple(eqn.params["axes"]))
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _reductions(sub)


@pytest.mark.parametrize("with_mask", [False, True], ids=["bare", "keys"])
@pytest.mark.parametrize("kind", [fa.NO_MASK, fa.CAUSAL,
                                  fa.BlockDiffusionMask(4)],
                         ids=lambda k: k.name)
@pytest.mark.parametrize("h, d", [(4, 64), (2, 128), (3, 64)],
                         ids=["packed2x64", "d128", "perhead_h3"])
def test_the_forward_reduces_over_keys_along_sublanes(h, d, kind, with_mask):
    """The forward kernel's jaxpr: every reduction over a score tile runs
    down the tile's first axis, the sublanes (the scores are key-major,
    (block_k, block_q): the max and the sum over keys are element-wise
    over the tile's sublane groups and one 8-sublane fold), and none
    along its minor axis, the lanes (128 rotate-and-combine reductions a
    block, with the per-query vectors as one-lane columns broadcast back
    across lanes: what the query-major form paid). Traced, not compiled:
    the orientation cannot drift back unnoticed."""
    b, s, bq, bk = 2, 1024, 256, 512
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16)
    mask = jax.ShapeDtypeStruct((b, s), jnp.float32) if with_mask else None
    jaxpr = jax.make_jaxpr(
        lambda q, k, v, m: fa._forward(q, k, v, m, kind, bq, bk, False)[:2]
    )(q, q, q, mask)
    kernel, = [eqn for eqn in jaxpr.jaxpr.eqns
               if eqn.primitive.name == "pallas_call"]
    assert kernel.params["name"] == "hvd_flash_fwd"
    found = list(_reductions(kernel.params["jaxpr"]))
    over_scores = [r for r in found if r[1] == (bk, bq)]
    # a max and a sum a head of the tile, in every range of k blocks the
    # kind's loop runs over
    assert {r[0] for r in over_scores} == {"reduce_max", "reduce_sum"}
    assert len(over_scores) >= 2 * fa._Layout(h, d).heads
    for name, shape, axes in found:
        if len(shape) == 2 and min(shape) >= 128:
            assert axes == (0,), (name, shape, axes)


def test_grouped_query_flash_compiles_for_v5e(v5e, on_tpu):
    """The expert, linear-attention cell's softmax layer: 8 query heads of
    128 on ONE K/V head, S4096. K and V cross as (B, S, 128), nothing is
    repeated; the backward writes dk and dv a query head ((B, S, 1024))
    and XLA sums them over the group."""
    b, s, h, hkv, d = 2, 4096, 8, 1, 128

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True) \
            .astype(jnp.float32).sum()

    hlo = _compile(jax.grad(loss, argnums=(0, 1, 2)), v5e,
                   ((b, s, h, d), jnp.bfloat16),
                   *[((b, s, hkv, d), jnp.bfloat16)] * 2)
    assert _mosaic_calls(hlo) == 2
    wide, narrow = f"bf16[{b},{s},{h * d}]", f"bf16[{b},{s},{d}]"
    fwd, = re.findall(r"%[\w.]*hvd_flash_fwd[\w.]* = [^\n]*", hlo)
    operands = fwd.split("operand_layout_constraints=")[1]
    assert operands.count(wide) == 1 and operands.count(narrow) == 2
    bwd, = re.findall(r"%[\w.]*hvd_flash_dkv[\w.]* = [^\n]*", hlo)
    assert bwd.split(" custom-call(")[0].count(wide) == 3



def test_the_block_diffusion_mask_compiles_for_v5e(v5e, on_tpu):
    """The block-diffusion cell's attention call: 32 query heads of 128
    on 4 K/V heads over [noisy ; clean] of 2 x 4096 positions under
    ``BlockDiffusionMask(4)``. One forward and one backward Mosaic call,
    K and V as they lie (the group is the index maps'), blocks of 512
    that tile the 4096 of one copy; the select of the partial tiles (a
    shift, two compares against scalars) and the clamped index maps are
    what interpret mode cannot vouch for."""
    b, s, h, hkv, d = 2, 8192, 32, 4, 128
    kind = fa.BlockDiffusionMask(4)
    assert fa._resolve_blocks(s, d, jnp.bfloat16, None, None, False,
                              kind.span(s)) == (512, 512)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, mask_kind=kind) \
            .astype(jnp.float32).sum()

    hlo = _compile(jax.grad(loss, argnums=(0, 1, 2)), v5e,
                   ((b, s, h, d), jnp.bfloat16),
                   *[((b, s, hkv, d), jnp.bfloat16)] * 2)
    assert _mosaic_calls(hlo) == 2
    wide, narrow = f"bf16[{b},{s},{h * d}]", f"bf16[{b},{s},{hkv * d}]"
    fwd, = re.findall(r"%[\w.]*hvd_flash_fwd[\w.]* = [^\n]*", hlo)
    operands = fwd.split("operand_layout_constraints=")[1]
    assert operands.count(wide) == 1 and operands.count(narrow) == 2
    assert len(re.findall(r"%[\w.]*hvd_flash_dkv[\w.]* = ", hlo)) == 1


def test_the_window_compiles_for_v5e(v5e, on_tpu):
    """The window-and-full cell's window layers' attention call, forward
    and backward: 72 query heads of 128 on 8 K/V heads (groups of 9
    through the index maps: K and V cross as they lie), S 8192, bf16,
    under ``SlidingWindowMask(512)``. The two Mosaic calls carry the
    window's own names and none of the flash kernels'; the backward's
    grid is the band (two q blocks a k block at blocks of 512), which
    interpret mode cannot vouch for the index maps of; and a window that
    is no multiple of the block, narrower than one, or past S compiles
    too."""
    b, s, h, hkv, d = 1, 8192, 72, 8, 128
    kind = fa.SlidingWindowMask(512)
    assert fa._resolve_blocks(s, d, jnp.bfloat16, None, None, False,
                              kind.span(s)) == (512, 512)
    assert kind.query_steps(s, 512, 512) == 2

    def loss(kind):
        def f(q, k, v):
            return fa.flash_attention(q, k, v, mask_kind=kind) \
                .astype(jnp.float32).sum()
        return jax.grad(f, argnums=(0, 1, 2))

    specs = (((b, s, h, d), jnp.bfloat16),
             *[((b, s, hkv, d), jnp.bfloat16)] * 2)
    hlo = _compile(loss(kind), v5e, *specs)
    assert _mosaic_calls(hlo) == 2
    assert "hvd_flash" not in hlo
    wide, narrow = f"bf16[{b},{s},{h * d}]", f"bf16[{b},{s},{hkv * d}]"
    fwd, = re.findall(r"%[\w.]*hvd_swa_fwd[\w.]* = [^\n]*", hlo)
    operands = fwd.split("operand_layout_constraints=")[1]
    assert operands.count(wide) == 1 and operands.count(narrow) == 2
    bwd, = re.findall(r"%[\w.]*hvd_swa_bwd[\w.]* = [^\n]*", hlo)
    assert bwd.split(" custom-call(")[0].count(wide) == 3
    for window in (500, 100, 1300, 3 * s):
        hlo = _compile(loss(fa.SlidingWindowMask(window)), v5e, *specs)
        assert _mosaic_calls(hlo) == 2 and "hvd_swa_bwd" in hlo


def test_the_window_and_full_cells_step_fits(v5e, on_tpu):
    """The whole training step of the window-and-full cell (``LagunaLM``'s
    defaults: layers 0-4 of the published stack, 48 and 72 query heads on
    8, 8 of 256 experts, 1 x S8192, AdamW with bf16 first moments,
    donated) compiled for a described v5e: 9.94 GiB, on the chip to the
    digit (PERF.md, PR 42), under the 15.75 the issue set, so the heads
    held did not have to be halved. Its flash calls are two kinds under
    two names: two full layers (forward, forward again, one backward
    each) and three window layers; the rotation is on packed rows (two
    kernels a layer each way); one block of routes a layer and no
    ``conditional``."""
    import optax

    from horovod_tpu.models import laguna

    model = laguna.LagunaLM()
    tokens = jax.ShapeDtypeStruct((1, 8193), jnp.int32, sharding=v5e)
    tx = optax.adamw(1e-4, mu_dtype=jnp.bfloat16)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=v5e), tree)

    params = jax.eval_shape(
        lambda key: model.init(key, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(params)) == 811_017_216
    state = jax.eval_shape(tx.init, params)

    def step(params, state, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: laguna.laguna_loss(model, p, tokens))(params)
        updates, state = tx.update(grads, state, params)
        return optax.apply_updates(params, updates), state, loss

    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        on_chip(params), on_chip(state), tokens).compile()
    memory = compiled.memory_analysis()
    held = (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.output_size_in_bytes - memory.alias_size_in_bytes)
    assert 9.5 < held / 2 ** 30 < 10.4
    hlo = compiled.as_text()
    assert len(re.findall(r" conditional\(", hlo)) == 0
    calls = re.findall(r"%([\w.\-]+) = [^\n]* custom-call\([^\n]*"
                       r'custom_call_target="tpu_custom_call"', hlo)
    ours = sorted(re.sub(r"[.\d]+$", "", c) for c in calls
                  if c.startswith("hvd_"))
    assert ours == (["hvd_flash_dkv"] * 2 + ["hvd_flash_fwd"] * 4
                    + ["hvd_rope_bwd"] * 10 + ["hvd_rope_fwd"] * 20
                    + ["hvd_swa_bwd"] * 3 + ["hvd_swa_fwd"] * 6)


def test_the_state_space_cells_step_fits(v5e, on_tpu):
    """The whole training step of the state-space cell
    (``GraniteHybridLM``'s defaults: layers 0-9 of the published stack,
    nine Mamba-2 layers and one attention layer, a quarter of the
    vocabulary, 1 x S8192, AdamW with bf16 first moments, donated)
    compiled for a described v5e: 9.45 GiB (9.71 while the scan was XLA
    code: its fp32 pair matrices are gone), under the 15.75 of the chip,
    over its quarter and inside the 1% the benchmark allows over the
    accepted 9.4466. Every layer's scan and the convolution before it are
    Mosaic calls (``ops/ssd.py``, ``ops/short_conv.py`` ``conv_act``: the
    forward, the forward run again under rematerialisation, the backward:
    18 + 9 each) beside the one attention layer's three flash calls (no
    rotation: no rope kernel), on every compile: the path is picked from
    the platform and the shapes as the step is traced. No ``while``; what
    XLA is left under ``hvd_ssd`` is A's sign, the stack of the heads'
    parameters, dt's cast and transposition (and back) and the sums' last
    additions; under ``hvd_short_conv`` the bias as a row and the sums'
    last additions, and no fp32 tensor of the activation's size
    anywhere."""
    import optax

    from horovod_tpu.common import scopes
    from horovod_tpu.models import granite

    model = granite.GraniteHybridLM()
    tokens = jax.ShapeDtypeStruct((1, 8193), jnp.int32, sharding=v5e)
    tx = optax.adamw(1e-4, mu_dtype=jnp.bfloat16)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=v5e), tree)

    params = jax.eval_shape(
        lambda key: model.init(key, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(params)) == 797_850_560
    state = jax.eval_shape(tx.init, params)

    def step(params, state, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: granite.granite_loss(model, p, tokens))(params)
        updates, state = tx.update(grads, state, params)
        return optax.apply_updates(params, updates), state, loss

    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        on_chip(params), on_chip(state), tokens).compile()
    memory = compiled.memory_analysis()
    held = (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.output_size_in_bytes - memory.alias_size_in_bytes)
    assert 9.40 < held / 2 ** 30 < 9.50 < 9.4466 * 1.01
    hlo = compiled.as_text()
    assert len(re.findall(r" while\(", hlo)) == 0
    calls = re.findall(r"%([\w.\-]+) = [^\n]* custom-call\([^\n]*"
                       r'custom_call_target="tpu_custom_call"', hlo)
    assert sorted(re.sub(r"[.\d]+$", "", c) for c in calls) == (
        ["hvd_flash_dkv"] + ["hvd_flash_fwd"] * 2
        + [scopes.SHORT_CONV_BWD] * 9 + [scopes.SHORT_CONV_FWD] * 18
        + [scopes.SSD_BWD] * 9 + [scopes.SSD_FWD] * 18)
    # the scope's name survives in every layer, forward and backward, and
    # no pair matrix (1 x 32 chunks x 64 heads x 256 x 256) is XLA's
    under = re.findall(r'op_name="([^"]*hvd_ssd[^"]*)"',
                       hlo[hlo.index("ENTRY"):])
    assert any("transpose(" in n for n in under)
    assert any("transpose(" not in n for n in under)
    assert len({m for n in under for m in re.findall(r"layer\d", n)}) == 9
    assert not re.search(r"\[1,32,(64|1,64),256,256\]", hlo)
    # and the kernels read x, B and C as the layer has them: what XLA lays
    # out anew around the calls is dt and its gradient (a row a head, 2
    # MiB) and the heads' parameters
    moved = {re.match(r"\s*%[\w.\-]+ = (\w+\[[\d,]*\])", line).group(1)
             for line in hlo[hlo.index("ENTRY"):].splitlines()
             if "hvd_ssd" in line
             and re.search(r" (copy|transpose)\(", line)}
    assert moved <= {"f32[64,3]", "f32[1,64,8192]", "bf16[1,64,8192]"}
    # the convolution's kernels take the projection's slice and give the
    # scan's operand in bf16: the padded fp32 rows and their four shifted
    # slices are no instruction of the program any more
    entry = hlo[hlo.index("ENTRY"):]
    assert not re.search(r"= f32\[1,8192,4352\]", entry)
    assert not re.search(r"= f32\[1,8195,4352\]", hlo)
    assert {re.sub(r".*/", "", n) for n in re.findall(
        r'op_name="([^"]*hvd_short_conv[^"]*)"', entry)} <= {
            "pallas_call", "broadcast_in_dim", "jit(_kernel_forward)",
            "jit(_kernel_backward)", "reduce_sum"}


@pytest.mark.parametrize("shape, with_bias", [
    ((1, 8192, 4352), True),     # the state-space cell's x, B and C
    ((2, 4096, 1024), False),    # a linear-attention layer's keys' kind
], ids=["granite", "no_bias"])
def test_the_convolutions_kernels_compile_alone_for_v5e(v5e, on_tpu, shape,
                                                        with_bias):
    """``hvd_short_conv_fwd`` and ``hvd_short_conv_bwd`` alone: the Mosaic
    compile fits VMEM under the default scoped limit (the calls set
    none), x is the only operand of the activation's size the backward
    reads beside dy, and it gives dx like x and one batch row's sums for
    the taps and the bias."""
    from horovod_tpu.common import scopes
    from horovod_tpu.ops import short_conv

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    batch, length, channels = shape
    args = (spec(shape, jnp.bfloat16), spec((4, channels))) \
        + ((spec((channels,)),) if with_bias else ())
    assert short_conv._kernels_take(*args[:2])

    def loss(*ops):
        return (short_conv.conv_act(*ops).astype(jnp.float32) ** 2).sum()

    hlo = jax.jit(jax.grad(loss, argnums=tuple(range(len(args))))).lower(
        *args).compile().as_text()
    assert "vmem_limit_bytes" not in hlo
    calls = {re.sub(r"[.\d]+$", "", name): line for name, line in re.findall(
        r"%([\w.\-]+) = ([^\n]*) custom-call\([^\n]*"
        r'custom_call_target="tpu_custom_call"', hlo)}
    assert sorted(calls) == [scopes.SHORT_CONV_BWD, scopes.SHORT_CONV_FWD]
    shapes = {name: re.findall(r"(?:bf16|f32)\[[\d,]+\]", line)
              for name, line in calls.items()}
    whole = f"bf16[{batch},{length},{channels}]"
    assert shapes[scopes.SHORT_CONV_FWD] == [whole]
    assert shapes[scopes.SHORT_CONV_BWD] == [
        whole, f"f32[{batch},4,{channels}]", f"f32[{batch},1,{channels}]"]


def test_the_state_space_kernels_compile_alone_for_v5e(v5e, on_tpu):
    """``hvd_ssd_fwd`` and ``hvd_ssd_bwd`` at the cell's shape (1 x S8192,
    64 heads of 64, a state of 128, one group, chunks of 256), alone: the
    Mosaic compile fits VMEM under the default scoped limit (the calls
    set none), the forward keeps the chunk-start states for the backward
    (32 chunks x 128 x 4096 fp32) and the backward gives dx, d dt (a row
    a head), dB, dC and the chunks' sums for d dt_bias, dA and dD."""
    from horovod_tpu.common import scopes
    from horovod_tpu.ops import ssd

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    heads = spec((64,), jnp.float32)
    args = (spec((1, 8192, 64, 64)), spec((1, 8192, 64)), heads,
            spec((1, 8192, 1, 128)), spec((1, 8192, 1, 128)), heads, heads)
    assert ssd._kernels_take(args[0], args[3], ssd.CHUNK)

    def loss(*ops):
        return ssd.ssd_scan(*ops).astype(jnp.float32).sum()

    hlo = jax.jit(jax.grad(loss, argnums=tuple(range(7)))).lower(
        *args).compile().as_text()
    assert "vmem_limit_bytes" not in hlo
    calls = {re.sub(r"[.\d]+$", "", name): line for name, line in re.findall(
        r"%([\w.\-]+) = ([^\n]*) custom-call\([^\n]*"
        r'custom_call_target="tpu_custom_call"', hlo)}
    assert sorted(calls) == [scopes.SSD_BWD, scopes.SSD_FWD]
    shapes = {name: re.findall(r"(?:bf16|f32)\[[\d,]+\]", line)
              for name, line in calls.items()}
    assert shapes[scopes.SSD_FWD] == ["bf16[1,8192,4096]",
                                      "f32[1,32,128,4096]"]
    assert shapes[scopes.SSD_BWD] == [
        "bf16[1,8192,4096]", "f32[1,64,8192]", "f32[1,8192,128]",
        "f32[1,8192,128]", "f32[1,32,64,3]"]


# (B, S, H, D), dtype: q of the three GPT cells' kind, of the looped cell
# (one head a tile), of the gated-convolution cell and its eight K/V
# heads, and the narrow and wide widths no cell runs.
ROPE_SHAPES = {"gpt_b32_s512": ((32, 512, 12, 64), jnp.bfloat16),
               "gpt_b6_s4096": ((6, 4096, 12, 64), jnp.bfloat16),
               "ouro_b2_s2048_d128": ((2, 2048, 16, 128), jnp.bfloat16),
               "lfm2_q_b2_s8192": ((2, 8192, 32, 64), jnp.bfloat16),
               "lfm2_k_b2_s8192": ((2, 8192, 8, 64), jnp.bfloat16),
               "d16_fp32": ((4, 256, 8, 16), jnp.float32),
               "d32": ((4, 256, 4, 32), jnp.bfloat16),
               "d256": ((2, 256, 2, 256), jnp.bfloat16),
               "gpt_fp32": ((2, 512, 12, 64), jnp.float32)}


def _under_rope(hlo):
    """The opcode of every instruction of the entry computation that lies
    under ``hvd_rope`` and moves a whole operand: fusions by their kind's
    name (``pad_maximum_fusion``), the rest by opcode."""
    found = []
    for line in hlo[hlo.index("ENTRY"):].splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = \S+ ([\w\-]+)\(", line)
        if m and "hvd_rope" in line and "[1," not in line.split(" = ")[1][:40]:
            found.append(re.sub(r"[.\d]+$", "", m.group(1))
                         if m.group(2) == "fusion" else m.group(2))
    return found


@pytest.mark.parametrize("positions", [False, True],
                         ids=["arange", "positions"])
@pytest.mark.parametrize("shape", sorted(ROPE_SHAPES))
def test_the_rotation_compiles_for_v5e(v5e, on_tpu, shape, positions):
    """``pltpu.roll`` inside a tile, the lane mask, the tile loop and the
    blocks ``_block_rows`` picks are met by Mosaic only here. Forward and
    backward are one kernel call each on a projection's (B, S, H·D) rows
    as they lie: the reshapes to (B, S, H, D) and back at ``rope``'s edge
    are views, and no copy, pad, concatenate or transpose of the rows or
    of their cotangent stands beside the calls."""
    from horovod_tpu.models.gpt import rope

    (b, s, h, d), dtype = ROPE_SHAPES[shape]
    specs = [((b, s, h * d), dtype)] + [((b, s), jnp.int32)] * positions

    def loss(rows, *pos):
        rotated = rope(rows.reshape(b, s, h, d), *pos).reshape(rows.shape)
        return (rotated.astype(jnp.float32) ** 2).sum()

    hlo = _compile(jax.grad(loss), v5e, *specs)
    assert _mosaic_calls(hlo) == 2
    assert "hvd_rope_fwd" in hlo and "hvd_rope_bwd" in hlo
    kind = _hlo_type(dtype, b, s, h, d)
    assert len(re.findall(rf"%hvd_rope_(?:fwd|bwd)[\w.]* = {kind} "
                          r"custom-call\(", hlo)) == 2
    assert not re.search(rf"\[{b},{s},{h},{d // 2}\]", hlo)
    entry = hlo[hlo.index("ENTRY"):]
    for opcode in (" pad(", " concatenate(", " transpose(", " copy("):
        assert opcode not in entry, opcode


def test_a_rotary_attention_layer_hands_its_rows_to_the_kernels_as_they_lie(
        v5e, on_tpu):
    """gpt2-small's attention at 32 x S512, forward and backward: q's and
    k's thirds of the fused projection's rows go through the rotation's
    kernels and into the flash kernels as (B, S, 768) rows, and back; under
    ``hvd_rope`` nothing but the four kernel calls and the small tables: no
    copy, pad or concatenate of a (B, S, ...) operand (the (B, S, H, D)
    formula left eight copies and four pad fusions a layer there, and
    ``jnp.roll`` on the packed rows ten and four: PERF.md, PR 39)."""
    from horovod_tpu.models.gpt import CausalSelfAttention

    b, s, hidden, heads = 32, 512, 768, 12
    layer = CausalSelfAttention(num_heads=heads)
    params = jax.eval_shape(
        lambda: layer.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8, hidden), jnp.bfloat16)))

    def loss(params, x):
        return (layer.apply(params, x).astype(jnp.float32) ** 2).sum()

    args = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=v5e),
        (params, jax.ShapeDtypeStruct((b, s, hidden), jnp.bfloat16)))
    hlo = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        *args).compile().as_text()
    assert _mosaic_calls(hlo) == 6
    rows = re.escape(f"bf16[{b},{s},{hidden}]")
    for name in ("hvd_rope_fwd", "hvd_rope_bwd"):
        assert len(re.findall(rf"%{name}[\w.]* = {rows}", hlo)) == 2
    moved = [op for op in _under_rope(hlo) if op != "custom-call"]
    assert not [op for op in moved if "copy" in op or "pad" in op
                or "concatenate" in op or op == "transpose"], moved
    assert not re.search(rf"\[{b},{s},{heads},{hidden // heads // 2}\]", hlo)
    assert "dynamic-update-slice" not in hlo[hlo.index("ENTRY"):]


def test_the_grouped_expert_matmuls_compile_for_v5e(v5e):
    """``jax.lax.ragged_dot`` at the cell's shapes (a block of 4096 routes to 8
    experts of 4096 x 1280), forward and both transposes: XLA's own
    Mosaic kernels, no dense fallback over the groups."""
    from horovod_tpu.parallel import moe

    def loss(xg, weights, gate, up, down, sizes):
        valid = jnp.arange(xg.shape[0]) < sizes.sum()
        return moe._expert_block(xg, weights, valid, gate, up, down,
                                 sizes).sum()

    bank = ((8, 4096, 1280), jnp.bfloat16)
    hlo = _compile(jax.grad(loss, argnums=(0, 2, 3, 4)), v5e,
                   ((4096, 4096), jnp.bfloat16), ((4096,), jnp.float32),
                   bank, bank, ((8, 1280, 4096), jnp.bfloat16),
                   ((8,), jnp.int32))
    kernels = re.findall(r"%ragged-dot-none[\w.]* = (\w+\[[\d,]*\])", hlo)
    # two forward (the third's result feeds no gradient of a sum), three
    # for the rows' gradients, three for the banks'
    assert len(kernels) == 8
    assert sum(k.startswith("f32[8,") or k.startswith("bf16[8,")
               for k in kernels) == 3


def test_the_linear_attention_block_runs_its_recurrence_as_kernels(v5e,
                                                                   on_tpu):
    """The expert cell's KDA block (8 heads of 128 on 2 x S4096, under
    ``nn.remat`` as the model has it), differentiated: the recurrence is
    three Mosaic calls (the forward, the forward run again, the backward)
    whose instruction names carry the scope, which is how the benchmark's
    readers find them, and XLA is left no triangular solve and no loop
    under the scope."""
    import flax.linen as nn

    from horovod_tpu.common import scopes
    from horovod_tpu.models import solar

    block = nn.remat(solar.KDA)(num_heads=8, head_dim=128)
    x = jax.ShapeDtypeStruct((2, 4096, 4096), jnp.bfloat16, sharding=v5e)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
        jax.eval_shape(block.init, jax.random.PRNGKey(0), x))

    def loss(p, x):         # its cotangent needs the block's output
        return (block.apply(p, x).astype(jnp.float32) ** 2).sum()

    hlo = jax.jit(jax.grad(loss)).lower(params, x).compile().as_text()
    calls = re.findall(r"%([\w.\-]+) = [^\n]* custom-call\([^\n]*"
                       r'custom_call_target="tpu_custom_call"', hlo)
    # (and the model's own call that does nothing: the scheduler's ballast)
    assert sorted(re.sub(r"[.\d]+$", "", c) for c in calls) == sorted(
        [scopes.KDA_FWD] * 2 + [scopes.KDA_BWD, "solar_scheduler_ballast"])
    under = [line for line in hlo.splitlines() if scopes.KDA in line]
    assert under and not any(
        re.search(r"triangular.solve| while\(", line) for line in under)
    assert "triangular-solve" not in hlo
    # the backward call gives its five gradients and nothing else
    backward, = [line.split(" custom-call(")[0] for line in hlo.splitlines()
                 if re.match(r"\s*%hvd_kda_bwd[\w.]* = ", line)]
    assert len(re.findall(r"(?:bf16|f32)\[[\d,]+\]", backward)) == 5


def test_the_expert_cells_step_fits_where_the_scheduler_is_told(v5e, on_tpu):
    """``models/solar.py`` tells XLA:TPU's scheduler two things (the loss
    before the backward, the weight of the XLA recurrence's temporaries at
    each KDA layer's backward) whose effect is the compiler's to give: the
    whole training step of the expert cell (the model's defaults, 2 x
    S4096, AdamW with bf16 first moments, donated), compiled for a
    described v5e, took 11.57 GiB with the recurrence as XLA code, 13.03
    with the kernels and nothing said, 11.98 with the loss alone, and takes
    10.45 with both (on the chip to the digit: PERF.md, PR 34). A compiler
    that stops listening fails here."""
    import optax

    from horovod_tpu.models import solar

    model = solar.SolarLM()
    tokens = jax.ShapeDtypeStruct((2, 4097), jnp.int32, sharding=v5e)
    tx = optax.adamw(1e-4, mu_dtype=jnp.bfloat16)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=v5e), tree)

    params = jax.eval_shape(
        lambda key: model.init(key, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    state = jax.eval_shape(tx.init, params)

    def step(params, state, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: solar.solar_loss(model, p, tokens))(params)
        updates, state = tx.update(grads, state, params)
        return optax.apply_updates(params, updates), state, loss

    memory = jax.jit(step, donate_argnums=(0, 1)).lower(
        on_chip(params), on_chip(state), tokens).compile().memory_analysis()
    held = (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.output_size_in_bytes - memory.alias_size_in_bytes)
    assert held / 2 ** 30 < 10.8


def test_the_gated_convolution_cells_step_fits_with_two_rows(v5e, on_tpu):
    """The whole training step of the gated-convolution, sigmoid-routed
    cell (``Lfm2LM``'s defaults, 2 x S8192, AdamW with bf16 first moments,
    donated) compiled for a described v5e: 11.602 GiB with every layer
    under one ``nn.remat`` and the experts' first block a switch over
    19,456 / 40,960 rows (11.588 with the one block of 40,960, both on
    the chip to the digit: PERF.md, PRs 35 and 37), so two rows fit and
    nothing had to be split. The switch is in the forward and the backward
    of each of the six expert layers and not in the forward ``nn.remat``
    runs again, which keeps no expert block. Its two attention layers are
    the first flash calls at S 8192 (K and V of two packed heads whole in
    VMEM) and the first on the packed width-64 layout with a K/V group:
    K and V reach the kernels repeated to the 32 query heads."""
    import optax

    from horovod_tpu.models import lfm2

    model = lfm2.Lfm2LM()
    tokens = jax.ShapeDtypeStruct((2, 8193), jnp.int32, sharding=v5e)
    tx = optax.adamw(1e-4, mu_dtype=jnp.bfloat16)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=v5e), tree)

    params = jax.eval_shape(
        lambda key: model.init(key, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    state = jax.eval_shape(tx.init, params)

    def step(params, state, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: lfm2.lfm2_loss(model, p, tokens))(params)
        updates, state = tx.update(grads, state, params)
        return optax.apply_updates(params, updates), state, loss

    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        on_chip(params), on_chip(state), tokens).compile()
    memory = compiled.memory_analysis()
    held = (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.output_size_in_bytes - memory.alias_size_in_bytes)
    assert 11.1 < held / 2 ** 30 < 11.8
    hlo = compiled.as_text()
    assert len(re.findall(r" conditional\(", hlo)) == 12
    calls = re.findall(r"%([\w.\-]+) = [^\n]* custom-call\([^\n]*"
                       r'custom_call_target="tpu_custom_call"', hlo)
    flash = sorted(re.sub(r"[.\d]+$", "", c) for c in calls
                   if "hvd_flash" in c)
    # two attention layers: forward, forward again, one backward each
    assert flash == ["hvd_flash_dkv"] * 2 + ["hvd_flash_fwd"] * 4
    forward = next(line for line in hlo.splitlines()
                   if re.match(r"\s*%hvd_flash_fwd[\w.]* = ", line))
    operands = forward.split("operand_layout_constraints=")[1]
    assert operands.count("bf16[2,8192,2048]") == 3     # q, and K/V repeated


@pytest.mark.parametrize("chunk", [32, 128])
def test_the_recurrence_kernels_compile_at_every_chunk_they_take(v5e, on_tpu,
                                                                 chunk):
    """``_kernels_take`` sends a chunk to the kernels only where Mosaic
    compiles them for it (64 is the block's test above)."""
    from horovod_tpu.ops import linear_attention as la

    assert la._KERNEL_CHUNKS == (32, 64, 128)
    x = jax.ShapeDtypeStruct((1, 256, 2, 128), jnp.bfloat16, sharding=v5e)
    decay = jax.ShapeDtypeStruct(x.shape, jnp.float32, sharding=v5e)
    beta = jax.ShapeDtypeStruct(x.shape[:3], jnp.float32, sharding=v5e)

    def loss(*a):
        return la.kda_attention(*a, chunk=chunk).astype(jnp.float32).sum()

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        x, x, x, decay, beta).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2


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
    assert _mosaic_calls(hlo) == 2 and "hvd_flash_dq" not in hlo
    assert not re.search(rf"\[{b},{h},{s},128\]", hlo)


EF_BUCKET = 3272 * 4096   # 13.4 M elements on four chips' block grid


def _entry_instructions(hlo):
    """``(name, result type, opcode)`` of the entry computation's
    instructions."""
    found = []
    for line in hlo[hlo.index("ENTRY"):].splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (\(.*?\)|\S+) "
                     r"([\w\-]+)\(", line)
        if m:
            found.append(m.groups())
    return found


def _elements(result_type):
    return max([1] + [int(np.prod([int(d) for d in dims.split(",")]))
                      for dims in re.findall(r"\[([\d,]+)\]", result_type)])


def test_an_error_feedback_bucket_moves_its_bytes_once(v5e_2x2, on_tpu):
    """One int8 error-feedback bucket as ``optim._reduce_tree_ef`` hands
    it to ``quantized_allreduce`` (13.4 M fp32 on the block grid, the
    residual a second operand, the hop keys derived outside), compiled
    for the four described chips. Three Mosaic calls: the bucket's
    quantise, the owned chunk's, the gathered result's dequantise.
    Between them nothing the size of the bucket or of a rank's chunk is
    converted, broadcast, reshaped other than as a bitcast, padded,
    sliced or copied: before PR 45 the residual and the result were
    dequantised by XLA (a convert, a broadcast of the scales and a
    relayout to the flat order each, all materialised: 1.613 GB accessed
    for this 53.6 MB bucket by ``cost_analysis``, which counts a Mosaic
    call's operands at nothing, against 0.229). And a step's keys for 25
    buckets are a hundred-odd instructions, not 8,951 (PERF.md, PR 45)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from horovod_tpu import optim
    from horovod_tpu.ops import collectives as C

    mesh = Mesh(np.array(v5e_2x2.devices), ("hvd",))
    rows, whole = NamedSharding(mesh, P("hvd")), NamedSharding(mesh, P())

    def bucket(g, r, step):
        keys, hop_keys = optim._ef_keys(step[0], 25, 2)
        y, residual = C.quantized_allreduce(
            g[0], C.ReduceOp.AVERAGE, "hvd", key=keys[3],
            return_residual=True, _hop_keys=hop_keys[3], _plus=r[0])
        return y[None], residual[None]

    compiled = jax.jit(jax.shard_map(
        bucket, mesh=mesh, in_specs=(P("hvd"), P("hvd"), P()),
        out_specs=(P("hvd"), P("hvd")), check_vma=False)).lower(
        *[jax.ShapeDtypeStruct((4, EF_BUCKET), jnp.float32,
                               sharding=rows)] * 2,
        jax.ShapeDtypeStruct((1,), jnp.int32, sharding=whole)).compile()
    assert compiled.cost_analysis()["bytes accessed"] < 0.6e9
    entry = _entry_instructions(compiled.as_text())
    calls = sorted(re.sub(r"[.\d]+$", "", name) for name, _, opcode in entry
                   if opcode == "custom-call" and name.startswith("hvd_"))
    assert calls == ["hvd_int8_dequantize"] + ["hvd_int8_quantize_sr"] * 2
    moved = {opcode for _, result, opcode in entry
             if _elements(result) >= EF_BUCKET // 4}
    assert not moved & {"convert", "broadcast", "reshape", "pad", "slice",
                        "copy", "transpose", "concatenate"}, moved
    # the thresholds, the sum over ranks, the owned chunk's error added in
    big_fusions = sorted(re.sub(r"[.\d]+$", "", name)
                         for name, result, opcode in entry
                         if opcode == "fusion"
                         and _elements(result) >= EF_BUCKET // 4)
    assert big_fusions == ["add_maximum_fusion", "add_maximum_fusion",
                           "dynamic-slice_add_fusion",
                           "multiply_reduce_fusion"], big_fusions
    assert len(entry) < 300     # 599 with a threefry a bucket and a hop

    keys = jax.jit(lambda step: optim._ef_keys(step[0], 25, 2)).lower(
        jax.ShapeDtypeStruct((1,), jnp.int32, sharding=whole)).compile()
    assert len(_entry_instructions(keys.as_text())) < 200


def test_a_fused_qkv_projections_gradient_is_not_joined_by_copies(v5e,
                                                                 on_tpu):
    """BERT's attention splits one fused qkv projection and so joins dq,
    dk and dv straight back. Three outputs of ONE Mosaic call
    concatenated, XLA:TPU builds as three update-slice copies into a
    zero buffer (1.7 ms a step of ``bert-large-s512``, measured); the
    backward hands dq over behind an optimization barrier, and the join
    is fused into its consumers as it was with two calls."""
    b, s, h, d = 8, 512, 16, 64

    def loss(x, w, bias):
        qkv = jnp.einsum("bsd,de->bse", x, w.astype(x.dtype)) \
            + bias.astype(x.dtype)
        q, k, v = (t.reshape(b, s, h, d) for t in jnp.split(qkv, 3, -1))
        return (fa.flash_attention(q, k, v).astype(jnp.float32) ** 2).sum()

    hlo = _compile(jax.grad(loss, argnums=(0, 1, 2)), v5e,
                   ((b, s, h * d), jnp.bfloat16),
                   ((h * d, 3 * h * d), jnp.float32),
                   ((3 * h * d,), jnp.float32))
    assert _mosaic_calls(hlo) == 2
    assert "dynamic-update-slice" not in hlo[hlo.index("ENTRY"):]


def test_flash_backward_holds_dq_of_a_16k_sequence_in_vmem(v5e, on_tpu):
    """The backward keeps dq of a whole (batch, head group) in VMEM: fp32
    rows and a double-buffered output, 16 MB at S16384 in bf16, the size
    class of the K and V the forward holds whole. It has to compile
    inside the ``vmem_limit_bytes`` the plan asks for (Mosaic refuses a
    kernel that overruns it), at 512-class blocks."""
    b, s, h, d = 1, 16384, 12, 64
    assert fa._choose_blocks(s, d, jnp.bfloat16) == (512, 512)

    def bwd(q, k, v):
        return jax.grad(lambda *a: fa.flash_attention(*a, causal=True)
                        .astype(jnp.float32).sum(), argnums=(0, 1, 2))(
                            q, k, v)

    hlo = _compile(bwd, v5e, *[((b, s, h, d), jnp.bfloat16)] * 3)
    assert _mosaic_calls(hlo) == 2 and "hvd_flash_dkv" in hlo
    # Each Mosaic call says the scoped VMEM it was allowed, then what it
    # used: the forward, then the backward.
    sizes = [int(n) for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             for n in re.findall(
                 r'scoped_memory_configs":\[\{"memory_space":"1",'
                 r'"offset":"0","size":"(\d+)"', line)]
    plan = fa._vmem_estimate(s, d, 2, 512, 512)
    assert plan >= s * 128 * (4 + 2 * 2)
    limit = min(max(2 * plan, fa._VMEM_FLOOR), fa._VMEM_CEIL)
    assert len(sizes) == 4 and sizes[0::2] == [limit, limit]
    assert all(used <= plan for used in sizes[1::2]), (sizes, plan)
