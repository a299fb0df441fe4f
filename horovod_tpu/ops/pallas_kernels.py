"""Pallas TPU kernels for the hot collective pre/post-processing ops.

The reference keeps these paths native: ``ScaleBuffer`` has AVX fp16 and
CUDA implementations (reference: horovod/common/ops/collective_operations.h
:97-125, cuda/cuda_kernels.cu), and Adasum's scalar reductions are
hand-vectorised AVX (adasum/adasum.h:427-530). On TPU the equivalents are
Pallas kernels feeding the VPU directly from VMEM:

- ``scale_buffer``          — fused multiply(+cast), the pre/postscale path.
- ``adasum_dot_norms``      — ONE pass over (a, b) producing
                              [dot(a,b), ||a||^2, ||b||^2] in fp32; the
                              bandwidth-bound core of the Adasum combine.
- ``adasum_combine``        — fused a*ca + b*cb with the adaptive
                              coefficients computed in-kernel from scalars.
- ``quantize_int8`` / ``dequantize_int8`` — block-scaled int8 wire
                              compression (4x over fp32) for DCN-bound
                              gradient exchange.

Every kernel flattens to a (rows, 128) lane layout, pads to the dtype's
sublane tile, and has a pure-jnp fallback used off-TPU (``use_pallas=None``
auto-selects; ``True`` forces Pallas in interpret mode on CPU — used by the
test suite to exercise the real kernel bodies).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import scopes

_LANES = 128
# Rows per grid step: 512x128 f32 = 256 KiB per operand block in VMEM —
# deep enough to amortise grid overhead, small enough to double-buffer.
_BLOCK_ROWS = 512


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _decide(use_pallas: Optional[bool]) -> Tuple[bool, bool]:
    """Returns (use_pallas_kernel, interpret_mode)."""
    if use_pallas is None:
        return _on_tpu(), False
    return use_pallas, not _on_tpu()


def _sublane(dtype) -> int:
    """Native sublane tile for a dtype (pallas_guide: tiling constraints)."""
    size = jnp.dtype(dtype).itemsize
    return {4: 8, 2: 16, 1: 32}.get(size, 8)


def _to_rows(x, sublane: int = 0):
    """Flatten to (rows, 128), zero-padded to a sublane-aligned row count."""
    sublane = sublane or _sublane(x.dtype)
    flat = x.ravel()
    n = flat.size
    rows = -(-n // _LANES)
    rows = -(-rows // sublane) * sublane
    pad = rows * _LANES - n
    flat = jnp.pad(flat, (0, pad))
    return flat.reshape(rows, _LANES), n


def _tile(x, sublane: int = 0):
    """Flatten+pad so the row count divides evenly into whole blocks —
    out-of-bounds block rows would read undefined memory, which matters
    for the reduction kernels (zero padding contributes 0; garbage
    doesn't). Returns (x2d, n, block_rows, nblocks)."""
    x2, n = _to_rows(x, sublane or _sublane(x.dtype))
    rows = x2.shape[0]
    if rows <= _BLOCK_ROWS:
        return x2, n, rows, 1
    full = -(-rows // _BLOCK_ROWS) * _BLOCK_ROWS
    if full != rows:
        x2 = jnp.pad(x2, ((0, full - rows), (0, 0)))
    return x2, n, _BLOCK_ROWS, full // _BLOCK_ROWS


# -- scale_buffer ----------------------------------------------------------

def _scale_kernel(s_ref, x_ref, o_ref):
    o_ref[:] = (x_ref[:].astype(jnp.float32) * s_ref[0]).astype(o_ref.dtype)


def scale_buffer(x, scale, out_dtype=None, use_pallas: Optional[bool] = None):
    """``x * scale`` (optionally casting) — standalone scale kernel.

    Reference analog: ScaleBuffer / ScaleBufferCudaImpl
    (collective_operations.h:97-125, cuda/cuda_kernels.cu). Inside jit the
    pre/postscale path stays as plain ``x * scale`` (collectives.py
    ``_apply_scale``) so XLA can fuse it into the surrounding collective;
    this kernel is the host-staged equivalent for eager buffer prep and
    for callers that want the scale+cast off the XLA fusion path.
    """
    out_dtype = out_dtype or x.dtype
    use, interpret = _decide(use_pallas)
    if not use:
        return (x.astype(jnp.float32) * scale).astype(out_dtype)
    rows2d, n, br, nblocks = _tile(x)
    scale_arr = jnp.asarray([scale], jnp.float32)
    out = pl.pallas_call(
        _scale_kernel,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((br, _LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((br, _LANES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(rows2d.shape, out_dtype),
        interpret=interpret,
        name=scopes.SCALE,
    )(scale_arr, rows2d)
    return out.ravel()[:n].reshape(x.shape)


# -- adasum: fused dot/norm reduction --------------------------------------

def _dot_norms_kernel(a_ref, b_ref, o_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[0] = 0.0
        o_ref[1] = 0.0
        o_ref[2] = 0.0

    af = a_ref[:].astype(jnp.float32)
    bf = b_ref[:].astype(jnp.float32)
    o_ref[0] += jnp.sum(af * bf)
    o_ref[1] += jnp.sum(af * af)
    o_ref[2] += jnp.sum(bf * bf)


def adasum_dot_norms(a, b, use_pallas: Optional[bool] = None):
    """Single-pass [dot(a,b), ||a||^2, ||b||^2] in fp32.

    The reference computes these three reductions in one AVX loop
    (adasum.h:195-337 ComputeDotAndNormSqrds); this is the VPU version —
    both operands stream from HBM exactly once. Zero padding is harmless
    (contributes 0 to every sum).
    """
    use, interpret = _decide(use_pallas)
    if not use:
        af = a.astype(jnp.float32).ravel()
        bf = b.astype(jnp.float32).ravel()
        return jnp.stack([jnp.dot(af, bf), jnp.dot(af, af),
                          jnp.dot(bf, bf)])
    sub = max(_sublane(a.dtype), _sublane(b.dtype))
    a2, _, br, nblocks = _tile(a, sub)
    b2, _, _, _ = _tile(b, sub)
    return pl.pallas_call(
        _dot_norms_kernel,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((br, _LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((br, _LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((3,), jnp.float32),
        interpret=interpret,
        name=scopes.ADASUM_DOT_NORMS,
    )(a2, b2)


# -- adasum: fused combine -------------------------------------------------

def _combine_kernel(s_ref, a_ref, b_ref, o_ref, *, eps=1e-30):
    dot, na2, nb2 = s_ref[0], s_ref[1], s_ref[2]
    ca = jnp.where(na2 > 0, 1.0 - dot / jnp.maximum(2.0 * na2, eps), 1.0)
    cb = jnp.where(nb2 > 0, 1.0 - dot / jnp.maximum(2.0 * nb2, eps), 1.0)
    af = a_ref[:].astype(jnp.float32)
    bf = b_ref[:].astype(jnp.float32)
    o_ref[:] = (af * ca + bf * cb).astype(o_ref.dtype)


def adasum_combine(a, b, dot_norms, use_pallas: Optional[bool] = None,
                   eps: float = 1e-30):
    """Fused ``a*(1-dot/2||a||^2) + b*(1-dot/2||b||^2)`` (adasum.h:371-390).

    ``dot_norms`` is the (3,) fp32 vector from :func:`adasum_dot_norms`;
    the coefficients are derived in-kernel from SMEM scalars so the
    elementwise pass reads each operand exactly once.
    """
    use, interpret = _decide(use_pallas)
    if not use:
        dot, na2, nb2 = dot_norms[0], dot_norms[1], dot_norms[2]
        ca = jnp.where(na2 > 0, 1.0 - dot / jnp.maximum(2.0 * na2, eps), 1.0)
        cb = jnp.where(nb2 > 0, 1.0 - dot / jnp.maximum(2.0 * nb2, eps), 1.0)
        return (ca.astype(a.dtype) * a + cb.astype(b.dtype) * b)
    sub = max(_sublane(a.dtype), _sublane(b.dtype))
    a2, n, br, nblocks = _tile(a, sub)
    b2, _, _, _ = _tile(b, sub)
    out = pl.pallas_call(
        functools.partial(_combine_kernel, eps=eps),
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((br, _LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((br, _LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((br, _LANES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(a2.shape, a.dtype),
        interpret=interpret,
        name=scopes.ADASUM_COMBINE,
    )(dot_norms.astype(jnp.float32), a2, b2)
    return out.ravel()[:n].reshape(a.shape)


# -- int8 block quantization ----------------------------------------------

# int8 sublane tile is 32; one scale per (32, 128) = 4096-element block.
_Q_ROWS = 32
# Quantization blocks per grid step: the kernels see the (rows, 128)
# buffer as (nblocks, 32, 128) — a free view, each block is whole
# (8, 128)/(32, 128) tiles — and take _Q_GROUP blocks at a time, so one
# step moves the same 512 rows as the other kernels. The per-block
# scales stay in the vector domain as (group, 1, 1): the TPU lowering
# refuses a one-element rank-1 SMEM block, and a keepdims reduction over
# the minor two dims needs no relayout. A ragged last group reads
# undefined blocks past the end; blocks are independent and the
# out-of-range writes are dropped.
_Q_GROUP = _BLOCK_ROWS // _Q_ROWS


def _scale_of(absmax):
    # A product, not ``/ 127.0``: XLA rewrites division by a constant
    # into this product under jit and Mosaic does not, which would leave
    # kernel and jnp twin one ulp apart on some blocks.
    return jnp.maximum(absmax, 1e-30) * (1.0 / 127.0)


def _block_scale(xf):
    return _scale_of(jnp.max(jnp.max(jnp.abs(xf), axis=2, keepdims=True),
                             axis=1, keepdims=True))


def _quantize_blocks(xf, u, want_residual):
    """The quantisation of fp32 blocks ``xf`` (blocks, 32, 128), the body
    of the kernels and of their jnp twin alike, so the two are equal to
    the bit: one absmax scale a block, round to nearest, or up where the
    threshold ``u`` lies under the fractional part. Returns ``(q, scale,
    residual)``: q as fp32 whole numbers in [-127, 127], the scales
    (blocks, 1, 1) and ``xf - q * scale`` (None unless wanted)."""
    scale = _block_scale(xf)
    scaled = xf / scale
    if u is None:
        q = jnp.round(scaled)
    else:
        fl = jnp.floor(scaled)
        q = fl + (u < (scaled - fl)).astype(jnp.float32)
    q = jnp.clip(q, -127, 127)
    return q, scale, (xf - q * scale) if want_residual else None


def _corrected(x, plus, prescale):
    """``(x + plus) * prescale`` in fp32, either of the two left out where
    it is None: what the quantisers quantise."""
    xf = x.astype(jnp.float32)
    if plus is not None:
        xf = xf + plus
    if prescale is not None:
        xf = xf * prescale
    return xf


def _quant_kernel(*refs, plus, stochastic, prescale, want_residual):
    """Operands ``x[, plus][, u]``, results ``q, scales[, residual]``. The
    block quantised is ``(x + plus) * prescale`` in fp32: the error-
    feedback path's corrected gradient, formed here so that neither the
    sum nor the residual ``x - q * scale`` is a pass over HBM of its own
    (optim._reduce_tree_ef). No PRNG in here: the thresholds are an
    operand."""
    refs = list(refs)
    x = refs.pop(0)[...]
    xf = _corrected(x, refs.pop(0)[...] if plus else None, prescale)
    u = refs.pop(0)[...] if stochastic else None
    q, scale, residual = _quantize_blocks(xf, u, want_residual)
    refs[0][...] = q.astype(jnp.int8)
    refs[1][...] = scale
    if want_residual:
        refs[2][...] = residual


def _dequant_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = (q_ref[...].astype(jnp.float32)
                  * s_ref[...]).astype(o_ref.dtype)


def _q_specs(nblocks):
    """(grid, data BlockSpec, scale BlockSpec) over the
    (nblocks, 32, 128) / (nblocks, 1, 1) views."""
    group = min(nblocks, _Q_GROUP)
    data = pl.BlockSpec((group, _Q_ROWS, _LANES), lambda i: (i, 0, 0),
                        memory_space=pltpu.VMEM)
    scale = pl.BlockSpec((group, 1, 1), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM)
    return (pl.cdiv(nblocks, group),), data, scale


def _quantize(x, key, use_pallas, plus, prescale, return_residual):
    """Both quantisers: ``(x + plus) * prescale`` to int8 by 4,096-element
    blocks, by the kernel or by its twin. See :func:`quantize_int8`."""
    use, interpret = _decide(use_pallas)
    x2, n = _to_rows(x, sublane=_Q_ROWS)
    nblocks = x2.shape[0] // _Q_ROWS
    view = (nblocks, _Q_ROWS, _LANES)   # the flat buffer's own order
    operands = [x2.reshape(view)]
    if plus is not None:
        operands.append(_to_rows(plus.astype(jnp.float32),
                                 sublane=_Q_ROWS)[0].reshape(view))
    if key is not None:
        operands.append(jax.random.uniform(key, x2.shape,
                                           jnp.float32).reshape(view))
    if not use:
        q, scales, residual = _quantize_blocks(
            _corrected(operands[0],
                       operands[1] if plus is not None else None, prescale),
            operands[-1] if key is not None else None, return_residual)
        q = q.astype(jnp.int8)
    else:
        grid, data, scale = _q_specs(nblocks)
        results = pl.pallas_call(
            functools.partial(_quant_kernel, plus=plus is not None,
                              stochastic=key is not None, prescale=prescale,
                              want_residual=return_residual),
            grid=grid,
            in_specs=[data] * len(operands),
            out_specs=[data, scale] + [data] * return_residual,
            out_shape=[jax.ShapeDtypeStruct(view, jnp.int8),
                       jax.ShapeDtypeStruct((nblocks, 1, 1), jnp.float32)]
            + [jax.ShapeDtypeStruct(view, jnp.float32)] * return_residual,
            interpret=interpret,
            name=(scopes.INT8_QUANTIZE if key is None
                  else scopes.INT8_QUANTIZE_SR),
        )(*operands)
        q, scales = results[:2]
        residual = results[2] if return_residual else None
    out = (q.reshape(x2.shape), scales.reshape(nblocks), n)
    if return_residual:
        out += (residual.ravel()[:n].reshape(x.shape),)
    return out


def quantize_int8(x, use_pallas: Optional[bool] = None, *, plus=None,
                  prescale=None, return_residual: bool = False):
    """Block-scaled int8 quantization: 4x wire compression over fp32.

    Returns ``(q, scales, n)`` where ``q`` is (rows, 128) int8, ``scales``
    holds one fp32 absmax-scale per 32x128 block, and ``n`` is the original
    element count. This is the capability extension of the reference's
    cast-only ``Compression.fp16`` (compression.py) for DCN-bound traffic,
    built as a Pallas quantization kernel (pallas_guide: quantization
    pattern).

    What is quantised is ``(x + plus) * prescale`` in fp32, both formed
    inside the kernel (``plus``: an fp32 array of ``x``'s shape, the
    error-feedback residual; ``prescale``: a Python number). With
    ``return_residual=True`` a fourth result is that value less its
    dequantised int8, fp32 in ``x``'s shape, written by the same kernel:
    the error-feedback path asks for it, and no dequantise of the whole
    buffer stands beside the quantise (docs/compression.md).
    """
    return _quantize(x, None, use_pallas, plus, prescale, return_residual)


def quantize_int8_stochastic(x, key, use_pallas: Optional[bool] = None, *,
                             plus=None, prescale=None,
                             return_residual: bool = False):
    """Block-scaled int8 quantization with UNBIASED stochastic rounding —
    the reduce-path variant of :func:`quantize_int8`.

    Round-to-nearest has a deterministic per-element bias of up to
    scale/2, which SUMS coherently across ranks in a quantized allreduce
    and across steps in training; stochastic rounding (round up with
    probability equal to the fractional part) makes the expected wire
    value exactly the input, so quantization error averages out instead
    of accumulating (the EQuARX/error-feedback convergence requirement —
    PAPERS.md).

    ``key`` is a ``jax.random`` PRNGKey; the rounding thresholds are
    ``jax.random.uniform(key, ...)`` drawn OUTSIDE the kernel and fed in
    as an operand, so (a) the result is a deterministic function of
    ``(x, key)`` on every backend, and (b) the Pallas body and the jnp
    fallback are bitwise-identical (the parity tests rely on this).
    Fold the step counter / bucket index into ``key`` for per-step
    determinism (optim.py does).

    Returns ``(q, scales, n)`` — same contract as :func:`quantize_int8`
    (one fp32 absmax scale per 32x128 block; ``plus``, ``prescale`` and
    ``return_residual`` as there); invert with :func:`dequantize_int8`.
    """
    return _quantize(x, key, use_pallas, plus, prescale, return_residual)


def dequantize_int8(q, scales, n, shape, dtype=jnp.float32,
                    use_pallas: Optional[bool] = None):
    """Inverse of :func:`quantize_int8`."""
    use, interpret = _decide(use_pallas)
    nblocks = q.shape[0] // _Q_ROWS
    if not use:
        blocks = q.reshape(nblocks, _Q_ROWS * _LANES).astype(jnp.float32)
        out = (blocks * scales[:, None]).astype(dtype)
        return out.ravel()[:n].reshape(shape)
    grid, data, scale = _q_specs(nblocks)
    out = pl.pallas_call(
        _dequant_kernel,
        grid=grid,
        in_specs=[data, scale],
        out_specs=data,
        out_shape=jax.ShapeDtypeStruct((nblocks, _Q_ROWS, _LANES), dtype),
        interpret=interpret,
        name=scopes.INT8_DEQUANTIZE,
    )(q.reshape(nblocks, _Q_ROWS, _LANES), scales.reshape(nblocks, 1, 1))
    return out.ravel()[:n].reshape(shape)
