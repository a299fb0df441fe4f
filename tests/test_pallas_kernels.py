"""Pallas kernel tests — run the real kernel bodies in interpret mode on
CPU (use_pallas=True off-TPU => interpret) and check numerics against the
pure-jnp fallbacks / NumPy.

Reference analogs being covered: ScaleBuffer (collective_operations.h:
97-125), Adasum's fused dot/norm + combine loops (adasum/adasum.h:195-400),
and the quantization capability extension.
"""

import numpy as np
import pytest
import jax.numpy as jnp

from horovod_tpu.ops import pallas_kernels as pk


@pytest.mark.parametrize("n", [7, 1024, 5000])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_scale_buffer_matches_jnp(rng, n, dtype):
    x = jnp.asarray(rng.standard_normal(n), dtype)
    got = pk.scale_buffer(x, 2.5, use_pallas=True)
    want = pk.scale_buffer(x, 2.5, use_pallas=False)
    assert got.shape == x.shape and got.dtype == x.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=1e-2)


def test_scale_buffer_cast(rng):
    x = jnp.asarray(rng.standard_normal(100), jnp.float32)
    got = pk.scale_buffer(x, 0.5, out_dtype=jnp.bfloat16, use_pallas=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(x) * 0.5, rtol=1e-2)


@pytest.mark.parametrize("n", [64, 2048, 3333])
def test_adasum_dot_norms(rng, n):
    a = jnp.asarray(rng.standard_normal(n), jnp.float32)
    b = jnp.asarray(rng.standard_normal(n), jnp.float32)
    got = np.asarray(pk.adasum_dot_norms(a, b, use_pallas=True))
    an, bn = np.asarray(a, np.float64), np.asarray(b, np.float64)
    want = np.array([(an * bn).sum(), (an * an).sum(), (bn * bn).sum()])
    np.testing.assert_allclose(got, want, rtol=1e-4)


def test_adasum_dot_norms_multiblock(rng):
    # > _BLOCK_ROWS rows forces multi-step grid accumulation.
    n = (pk._BLOCK_ROWS + 17) * pk._LANES
    a = jnp.asarray(rng.standard_normal(n), jnp.float32)
    b = jnp.asarray(rng.standard_normal(n), jnp.float32)
    got = np.asarray(pk.adasum_dot_norms(a, b, use_pallas=True))
    want = np.asarray(pk.adasum_dot_norms(a, b, use_pallas=False))
    np.testing.assert_allclose(got, want, rtol=1e-4)


def test_adasum_combine_matches_formula(rng):
    a = jnp.asarray(rng.standard_normal(500), jnp.float32)
    b = jnp.asarray(rng.standard_normal(500), jnp.float32)
    dn = pk.adasum_dot_norms(a, b, use_pallas=False)
    got = np.asarray(pk.adasum_combine(a, b, dn, use_pallas=True))
    an, bn = np.asarray(a, np.float64), np.asarray(b, np.float64)
    dot, na2, nb2 = (an * bn).sum(), (an * an).sum(), (bn * bn).sum()
    want = an * (1 - dot / (2 * na2)) + bn * (1 - dot / (2 * nb2))
    np.testing.assert_allclose(got, want, rtol=1e-4)


def test_adasum_combine_zero_side(rng):
    # All-zero operand => plain sum (coef 1.0), adasum.h:380-388 parity.
    a = jnp.zeros(128, jnp.float32)
    b = jnp.asarray(rng.standard_normal(128), jnp.float32)
    dn = pk.adasum_dot_norms(a, b, use_pallas=True)
    got = np.asarray(pk.adasum_combine(a, b, dn, use_pallas=True))
    np.testing.assert_allclose(got, np.asarray(b), rtol=1e-5)


@pytest.mark.parametrize("n", [100, 4096, 9001])
def test_quantize_roundtrip(rng, n):
    x = jnp.asarray(rng.standard_normal(n) * 10, jnp.float32)
    q, scales, cnt = pk.quantize_int8(x, use_pallas=True)
    assert q.dtype == jnp.int8 and cnt == n
    out = pk.dequantize_int8(q, scales, cnt, x.shape,
                             use_pallas=True)
    # absmax/127 per 4096-block => error bounded by scale/2 per element.
    err = np.abs(np.asarray(out) - np.asarray(x))
    bound = np.asarray(scales).max() / 2 + 1e-6
    assert err.max() <= bound


def test_quantize_pallas_matches_fallback(rng):
    x = jnp.asarray(rng.standard_normal(8192), jnp.float32)
    q1, s1, _ = pk.quantize_int8(x, use_pallas=True)
    q0, s0, _ = pk.quantize_int8(x, use_pallas=False)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q0))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quantize_ragged_last_group_matches_fallback(rng, dtype):
    """More blocks than one grid step takes, and not a multiple of it:
    the last step's out-of-range blocks must not leak into the result."""
    import jax

    n = (pk._Q_GROUP + 5) * pk._Q_ROWS * pk._LANES - 7
    x = jnp.asarray(rng.standard_normal(n) * 5, dtype)
    key = jax.random.PRNGKey(3)
    for quantize in (pk.quantize_int8,
                     lambda v, use_pallas: pk.quantize_int8_stochastic(
                         v, key, use_pallas=use_pallas)):
        q1, s1, _ = quantize(x, use_pallas=True)
        q0, s0, _ = quantize(x, use_pallas=False)
        assert s1.shape == (pk._Q_GROUP + 5,)
        np.testing.assert_array_equal(np.asarray(q1), np.asarray(q0))
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s0))
    d1 = pk.dequantize_int8(q1, s1, n, x.shape, dtype, use_pallas=True)
    d0 = pk.dequantize_int8(q1, s1, n, x.shape, dtype, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(d1, np.float32),
                                  np.asarray(d0, np.float32))


# -- stochastic-rounding quantize kernel (the int8_ef reduce path) ---------

def test_stochastic_quantize_pallas_matches_fallback(rng):
    """The rounding thresholds are drawn OUTSIDE the kernel from the
    jax.random key, so the Pallas body (interpret mode on CPU) and the
    jnp fallback must agree BITWISE — q and scales both."""
    import jax

    x = jnp.asarray(rng.standard_normal(8192) * 7, jnp.float32)
    key = jax.random.PRNGKey(11)
    q1, s1, n1 = pk.quantize_int8_stochastic(x, key, use_pallas=True)
    q0, s0, n0 = pk.quantize_int8_stochastic(x, key, use_pallas=False)
    assert n1 == n0 == 8192
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q0))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s0))


@pytest.mark.parametrize("ragged", [False, True],
                         ids=["whole_groups", "ragged_last_group"])
@pytest.mark.parametrize("operands", [1, 2],
                         ids=["one_operand", "two_operands"])
@pytest.mark.parametrize("keyed", [False, True],
                         ids=["nearest", "stochastic"])
def test_quantize_residual_matches_twin(rng, keyed, operands, ragged):
    """The residual a caller asks the quantise kernels for, ``(x + plus)
    * prescale`` less its dequantised int8, formed and written inside the
    kernel, against the jnp twin: with and without a key, one operand
    and two, and where the last grid step reads past the end. q and the
    scales are bitwise the twin's and the ones the same call gives
    without the residual. The residual is the twin's to one rounding of
    ``q * scale``: XLA:CPU contracts ``x - q * scale`` into a fused
    multiply-add in some of the programs it compiles and not in others
    (as for ``adasum_combine`` below), so two CPU programs cannot be held
    to the same bits; a TPU v5e has no such instruction, and there
    ``chip_smoke.py`` counts the kernel's mismatches with XLA's fusion."""
    import jax

    blocks = pk._Q_GROUP + 5 if ragged else 2 * pk._Q_GROUP
    n = blocks * pk._Q_ROWS * pk._LANES - (7 if ragged else 0)
    x = jnp.asarray(rng.standard_normal(n) * 5, jnp.float32)
    extra = {} if operands == 1 else {
        "plus": jnp.asarray(rng.standard_normal(n) * 0.05, jnp.float32),
        "prescale": 0.25}
    key = jax.random.PRNGKey(3)

    def quantize(use_pallas, **kwargs):
        if keyed:
            return pk.quantize_int8_stochastic(x, key, use_pallas=use_pallas,
                                               **extra, **kwargs)
        return pk.quantize_int8(x, use_pallas=use_pallas, **extra, **kwargs)

    q1, s1, _, r1 = quantize(True, return_residual=True)
    q0, s0, _, r0 = quantize(False, return_residual=True)
    assert r1.shape == x.shape and r1.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q0))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s0))
    for use_pallas in (True, False):
        q, s, _ = quantize(use_pallas)
        np.testing.assert_array_equal(np.asarray(q), np.asarray(q0))
        np.testing.assert_array_equal(np.asarray(s), np.asarray(s0))
    # Op by op the twin rounds the product before it subtracts, as the
    # same arithmetic in numpy does: those two are the same bits.
    xf = np.asarray(x)
    if operands == 2:
        xf = (xf + np.asarray(extra["plus"])) * np.float32(0.25)
    deq = np.asarray(pk.dequantize_int8(q0, s0, n, x.shape,
                                        use_pallas=False))
    np.testing.assert_array_equal(np.asarray(r0), xf - deq)
    one_rounding = 2.0 ** -23 * np.abs(xf).max()
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r0), rtol=0,
                               atol=one_rounding)
    # The error of this quantisation: within a step of the block's grid
    # (half a step, rounded to nearest).
    bound = np.repeat(np.asarray(s0), pk._Q_ROWS * pk._LANES)[:n]
    assert (np.abs(np.asarray(r1))
            <= bound * (1.0 if keyed else 0.5) + one_rounding).all()


@pytest.mark.parametrize("use_pallas", [True, False],
                         ids=["kernel", "twin"])
@pytest.mark.parametrize("operands", [1, 2],
                         ids=["one_operand", "two_operands"])
@pytest.mark.parametrize("keyed", [False, True],
                         ids=["nearest", "stochastic"])
def test_quantize_is_the_plain_arithmetic(rng, keyed, operands, use_pallas):
    """q, the scales and the residual against the quantisation written
    out in numpy on the (blocks, 4096) view, sharing no line with the
    kernel's body: the kernel and its twin are one function
    (``_quantize_blocks``), so their agreeing says nothing of a slip in
    it. One scale a block, absmax / 127 (as a product, see
    ``_scale_of``); nearest rounds half to even; with a key, up where
    the threshold ``jax.random.uniform(key, (rows, 128))`` lies under
    the fractional part; clipped to +-127."""
    import jax

    f32 = np.float32
    blocks = pk._Q_GROUP + 5
    n = blocks * 4096 - 7
    x = (rng.standard_normal(n) * np.exp(rng.standard_normal(n))).astype(f32)
    plus = (rng.standard_normal(n) * 0.05).astype(f32)
    x[4096:2 * 4096] = 0                    # a block of zeros: scale 1e-30/127
    plus[4096:2 * 4096] = 0
    extra = {} if operands == 1 else {"plus": jnp.asarray(plus),
                                      "prescale": 0.25}
    key = jax.random.PRNGKey(11)
    if keyed:
        q, s, _, res = pk.quantize_int8_stochastic(
            jnp.asarray(x), key, use_pallas=use_pallas, return_residual=True,
            **extra)
    else:
        q, s, _, res = pk.quantize_int8(
            jnp.asarray(x), use_pallas=use_pallas, return_residual=True,
            **extra)

    xf = x if operands == 1 else (x + plus) * f32(0.25)
    xb = np.concatenate([xf, np.zeros(7, f32)]).reshape(blocks, 4096)
    scale = np.maximum(np.abs(xb).max(axis=1), f32(1e-30)) * f32(1.0 / 127.0)
    scaled = xb / scale[:, None]
    if keyed:
        u = np.asarray(jax.random.uniform(
            key, (blocks * 32, 128), jnp.float32)).reshape(blocks, 4096)
        low = np.floor(scaled)
        want = low + (u < scaled - low).astype(f32)
    else:
        want = np.round(scaled)
    want = np.clip(want, -127, 127)
    assert scale.dtype == f32 and want.dtype == f32
    np.testing.assert_array_equal(np.asarray(s), scale)
    np.testing.assert_array_equal(np.asarray(q).reshape(blocks, 4096),
                                  want.astype(np.int8))
    np.testing.assert_allclose(
        np.asarray(res), (xb - want * scale[:, None]).reshape(-1)[:n],
        rtol=0, atol=2.0 ** -23 * np.abs(xf).max())


@pytest.mark.parametrize("use_pallas", [True, False],
                         ids=["kernel", "twin"])
@pytest.mark.parametrize("ranks", [2, 4, 8])
def test_dequantize_stacked_view_folds_the_mean_into_the_scales(
        rng, ranks, use_pallas):
    """The gathered result of the quantised allreduce, (ranks, rows, 128)
    int8 with (ranks, blocks) scales, dequantised in ONE call on the
    (ranks * blocks, 32, 128) view with 1/ranks on the scales: bitwise
    ``_deq(q, s) / ranks`` for a power of two, the flat buffer's order."""
    from horovod_tpu.ops.collectives import _deq

    blocks = pk._Q_GROUP + 3
    q = jnp.asarray(rng.integers(-127, 128, (ranks, blocks * pk._Q_ROWS,
                                             pk._LANES)), jnp.int8)
    s = jnp.asarray(np.exp(rng.standard_normal((ranks, blocks)) * 4),
                    jnp.float32)
    size = ranks * blocks * pk._Q_ROWS * pk._LANES
    got = pk.dequantize_int8(
        q.reshape(-1, pk._LANES), (s * jnp.float32(1.0 / ranks)).reshape(-1),
        size, (size,), use_pallas=use_pallas)
    want = _deq(q, s).reshape(-1) / jnp.float32(ranks)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_stochastic_quantize_deterministic_per_key(rng):
    import jax

    x = jnp.asarray(rng.standard_normal(5000), jnp.float32)
    key = jax.random.PRNGKey(5)
    q1, _, _ = pk.quantize_int8_stochastic(x, key, use_pallas=True)
    q2, _, _ = pk.quantize_int8_stochastic(x, key, use_pallas=True)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    q3, _, _ = pk.quantize_int8_stochastic(x, jax.random.PRNGKey(6),
                                           use_pallas=True)
    assert not np.array_equal(np.asarray(q3), np.asarray(q1)), \
        "different keys must draw different roundings"


@pytest.mark.parametrize("n", [100, 4096, 9001])
def test_stochastic_quantize_rounds_to_neighbor(rng, n):
    """Every element rounds to an adjacent int8 level: |deq - x| < scale
    (one full step — stochastic rounding may go either way, unlike
    nearest's half step)."""
    import jax

    x = jnp.asarray(rng.standard_normal(n) * 10, jnp.float32)
    q, scales, cnt = pk.quantize_int8_stochastic(
        x, jax.random.PRNGKey(0), use_pallas=True)
    assert q.dtype == jnp.int8 and cnt == n
    out = pk.dequantize_int8(q, scales, cnt, x.shape, use_pallas=True)
    err = np.abs(np.asarray(out) - np.asarray(x))
    assert err.max() <= np.asarray(scales).max() + 1e-6


def test_stochastic_quantize_unbiased(rng):
    """E[dequant(quant(x))] = x: averaging the roundtrip over many keys
    must beat any single draw's error by ~sqrt(K) — the property that
    makes quantization error cancel instead of accumulate across ranks
    and steps."""
    import jax

    x = jnp.asarray(rng.standard_normal(4096) * 3, jnp.float32)
    K = 64
    acc = np.zeros(4096, np.float64)
    for k in range(K):
        q, s, n = pk.quantize_int8_stochastic(
            x, jax.random.PRNGKey(k), use_pallas=False)
        acc += np.asarray(pk.dequantize_int8(q, s, n, x.shape,
                                             use_pallas=False),
                          np.float64)
    mean_err = acc / K - np.asarray(x, np.float64)
    scale = float(np.asarray(s).max())
    # per-element stderr <= scale/2/sqrt(K); 5 sigma over 4096 elements.
    assert np.abs(mean_err).max() < 5 * 0.5 * scale / np.sqrt(K)
    # ...and the MEAN bias across elements is far tighter.
    assert abs(mean_err.mean()) < scale / np.sqrt(K)


def test_int8_compressor_roundtrip(rng):
    from horovod_tpu.ops.compression import Compression

    x = jnp.asarray(rng.standard_normal((33, 17)), jnp.float32)
    wire, ctx = Compression.int8.compress(x)
    out = Compression.int8.decompress(wire, ctx)
    assert out.shape == x.shape and out.dtype == x.dtype
    assert np.abs(np.asarray(out) - np.asarray(x)).max() < 0.05


def test_int8_rejected_for_reduction():
    import optax
    import horovod_tpu as hvd
    from horovod_tpu.ops.compression import Compression

    with pytest.raises(ValueError, match="wire-format"):
        hvd.DistributedOptimizer(optax.sgd(0.1),
                                 compression=Compression.int8)


def test_int8_ef_compressor_surface():
    """int8_ef is the reduce-safe int8: accepted by the optimizer, wire
    format inherited from the block-scale machinery."""
    from horovod_tpu.ops.compression import Compression, Int8EFCompressor

    assert Compression.by_name("int8_ef") is Int8EFCompressor
    assert Int8EFCompressor.reduce_safe
    assert Int8EFCompressor.quantized_reduce
    assert Int8EFCompressor.error_feedback
    assert Int8EFCompressor.wire == "int8"
    # compress/decompress stay the plain wire format (broadcast/
    # allgather) — same roundtrip contract as Compression.int8.
    x = jnp.asarray(np.linspace(-2, 2, 512, dtype=np.float32))
    wire, ctx = Int8EFCompressor.compress(x)
    out = Int8EFCompressor.decompress(wire, ctx)
    assert out.shape == x.shape and out.dtype == x.dtype
    assert np.abs(np.asarray(out) - np.asarray(x)).max() < 0.05


def test_pairwise_combine_uses_kernels(rng):
    from horovod_tpu.ops.adasum import _pairwise_combine

    a = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
    got = np.asarray(_pairwise_combine(a, b))
    an = np.asarray(a, np.float64).ravel()
    bn = np.asarray(b, np.float64).ravel()
    dot, na2, nb2 = (an * bn).sum(), (an * an).sum(), (bn * bn).sum()
    want = (an * (1 - dot / (2 * na2)) +
            bn * (1 - dot / (2 * nb2))).reshape(a.shape)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


def test_adasum_combine_pallas_jnp_parity(rng):
    """The combine kernel is ELEMENTWISE given the (3,) scalar vector,
    so the Pallas body (run under the CPU interpreter) and the jnp
    fallback perform the same multiplies and adds — parity is pinned at
    one rounding of the OPERAND scale (XLA may contract `a*ca + b*cb`
    into an FMA in one separately-compiled program and not the other,
    so bit equality across programs is not guaranteed; where the sum
    cancels toward zero that single contraction is the whole absolute
    difference). The ISSUE-6 satellite: these kernels had never run
    outside the interpreter, so this parity is the contract a future
    chip run is checked against."""
    for n in (64, 4096, 70000):  # sub-block, one block, multi-block
        a = jnp.asarray(rng.standard_normal(n), jnp.float32)
        b = jnp.asarray(rng.standard_normal(n) * 3, jnp.float32)
        dn = pk.adasum_dot_norms(a, b, use_pallas=False)
        got = np.asarray(pk.adasum_combine(a, b, dn, use_pallas=True))
        want = np.asarray(pk.adasum_combine(a, b, dn, use_pallas=False))
        scale = max(float(np.abs(np.asarray(a)).max()),
                    float(np.abs(np.asarray(b)).max()))
        np.testing.assert_allclose(got, want, rtol=1e-6,
                                   atol=2 ** -23 * scale * 4)


def test_adasum_dot_norms_edge_cases_parity(rng):
    """Zero-norm / orthogonal / parallel inputs through BOTH kernel
    paths: the degenerate coefficients (adasum.h:380-388) must agree
    between the Pallas interpreter and the jnp fallback, and match the
    analytic values."""
    n = 2048
    base = rng.standard_normal(n).astype(np.float32)
    zeros = np.zeros(n, np.float32)
    # orthogonal pair: disjoint support
    oa, ob = zeros.copy(), zeros.copy()
    oa[: n // 2] = base[: n // 2]
    ob[n // 2:] = base[n // 2:]
    cases = {
        "zero_a": (zeros, base),
        "zero_b": (base, zeros),
        "zero_both": (zeros, zeros),
        "orthogonal": (oa, ob),
        "parallel": (base, 2.0 * base),
    }
    for name, (a, b) in cases.items():
        a, b = jnp.asarray(a), jnp.asarray(b)
        dn_p = np.asarray(pk.adasum_dot_norms(a, b, use_pallas=True))
        dn_j = np.asarray(pk.adasum_dot_norms(a, b, use_pallas=False))
        np.testing.assert_allclose(dn_p, dn_j, rtol=1e-5, atol=1e-5,
                                   err_msg=name)
        out_p = np.asarray(pk.adasum_combine(a, b, jnp.asarray(dn_p),
                                             use_pallas=True))
        out_j = np.asarray(pk.adasum_combine(a, b, jnp.asarray(dn_p),
                                             use_pallas=False))
        # One-contraction parity (see test_adasum_combine_pallas_jnp_
        # parity for why not bit-exact across compiled programs).
        np.testing.assert_allclose(out_p, out_j, rtol=1e-6, atol=1e-6,
                                   err_msg=name)
        if name.startswith("zero") or name == "orthogonal":
            # dot = 0 (or zero-norm side): plain sum, coefs 1.0.
            np.testing.assert_allclose(out_p, np.asarray(a) +
                                       np.asarray(b), rtol=1e-5,
                                       atol=1e-6, err_msg=name)
        elif name == "parallel":
            # adasum(a, 2a): dot=2||a||^2 -> ca=1-1=0, cb=1-1/4=3/4
            # -> result (3/4)*2a = 1.5a (equal-norm parallel inputs
            # would average; the general parallel case interpolates).
            np.testing.assert_allclose(out_p, 1.5 * np.asarray(a),
                                       rtol=1e-4, atol=1e-5,
                                       err_msg=name)


def test_pairwise_combine_scalar_axes_sharded_vhdd(rng):
    """_pairwise_combine(scalar_axes=) — the vector-halving VHDD form
    the mesh router uses: combining SHARDS with fast-axis-psum-med
    scalars must reproduce the FULL-vector combine exactly."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    from horovod_tpu.ops.adasum import _pairwise_combine

    a = rng.standard_normal((8, 128)).astype(np.float32)
    b = rng.standard_normal((8, 128)).astype(np.float32)
    mesh = Mesh(np.array(jax.devices()), ("hvd",))
    f = jax.jit(jax.shard_map(
        lambda av, bv: _pairwise_combine(av, bv, scalar_axes=("hvd",)),
        mesh=mesh, in_specs=(P("hvd"), P("hvd")),
        out_specs=P("hvd")))
    got = np.asarray(f(a.reshape(8, 1, 128), b.reshape(8, 1, 128)))
    full = np.asarray(_pairwise_combine(jnp.asarray(a.ravel()),
                                        jnp.asarray(b.ravel())))
    np.testing.assert_allclose(got.reshape(-1), full, rtol=1e-4,
                               atol=1e-5)


def test_flash_block_specs_obey_mosaic_tiling_rule():
    """Static pin of the Mosaic constraint that cost a round-3 chip
    window: every BlockSpec's minor-two dims must be (multiple of 8,
    multiple of 128) OR equal the array dims. CPU interpret mode never
    checks this, so the rule is asserted statically here for every
    benchmark shape (BERT/GPT S=512, GPT-2k and 4k, microbench S in
    {1k, 2k, 4k}, the S=512 block sweep, short ring-attention blocks)
    against the exact spec/array pairs each pallas_call binds, at the
    blocks a COMPILED call resolves (tests/test_tpu_compile.py asks the
    chip's compiler itself for the main shapes)."""
    from horovod_tpu.ops.flash_attention import (_k_major_specs, _Layout,
                                                 _q_major_specs,
                                                 _resolve_blocks)

    def ok(block, array):
        # A squeezed (None) dimension is not one of the block's minor two.
        dims = [(b, a) for b, a in zip(block, array) if b is not None]
        (sub, sub_a), (last, last_a) = dims[-2:]
        return (last == last_a or last % 128 == 0) \
            and (sub == sub_a or sub % 8 == 0)

    configs = [
        # (b, s, h, d, block_q, block_k)
        (8, 512, 16, 64, None, None),  # bert_large bench
        (32, 512, 12, 64, None, None),  # gpt_small bench
        (8, 2048, 12, 64, None, None),  # gpt_2k long-context leg
        (4, 4096, 12, 64, None, None),
        (4, 1024, 8, 64, 128, 128),    # microbench
        (4, 4096, 8, 64, 128, 128),
        (4, 512, 8, 64, 256, 128),     # S=512 block sweep entries
        (4, 512, 8, 64, 256, 256),
        (4, 512, 8, 64, 512, 512),
        (2, 200, 4, 64, None, None),   # short S no 128 divides: whole
        (2, 384, 4, 128, None, None),  # a head fills the lanes
        (2, 640, 3, 64, None, None),   # odd head count: one head a block
        (2, 512, 25, 64, None, None),  # gpt2-xl's 25 heads of 64
        (2, 256, 8, 32, None, None),   # four heads a block
        (2, 256, 4, 80, None, None),   # a width that does not divide 128
    ]
    for b, s, h, d, cbq, cbk in configs:
        blocks = _resolve_blocks(s, d, jnp.bfloat16, cbq, cbk,
                                 interpret=False)
        assert blocks, (s, cbq, cbk)
        bq, bk = blocks
        assert s % bq == 0 and s % bk == 0
        layout = _Layout(h, d)
        assert layout.packed == (h % (128 // d) == 0 if 128 % d == 0
                                 else d % 128 == 0)
        assert layout.groups * layout.heads == h
        qkv = (b, s, h * d) if layout.packed else (b, h, s, d)
        rows, mask = (b, h, 1, s), (b, 1, s)
        # (spec, array) pairs exactly as the two pallas_calls bind
        # them: the forward (q blocks outermost), then the backward,
        # whose last spec is dq's whole sequence of a head group.
        pairs = list(zip(_q_major_specs(layout, s, bq),
                         (qkv, qkv, mask, rows)))
        pairs += zip(_k_major_specs(layout, s, bq, bk, True),
                     (qkv, qkv, mask, rows, qkv))
        for spec, array in pairs:
            assert ok(spec.block_shape, array), (
                f"Mosaic-untileable block {spec.block_shape} over "
                f"{array} at config {(b, s, h, d, cbq, cbk)}")
