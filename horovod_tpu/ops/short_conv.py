"""Short causal depthwise convolutions along the sequence: the plain one
(the taps of a linear-attention layer's q, k and v, ``models/solar.py``),
the gated one that is a whole token mixer (``models/lfm2.py``):

    y = C * conv(B * x),    conv(z)_t = sum_j taps[j] z_{t - (n - 1) + j}

(B, C and x three slices of one projection of the layer's input; no
activation, no norm), and the one with a bias and a SiLU that stands
before a state-space scan (``models/granite.py``):

    y = silu(conv(x) + bias)                                    ``conv_act``

There is no matmul in any of them: ``n`` taps a channel (and two gates, or
a bias and an activation), bound by the bytes of the operands and of y
(``benchmark/lfm2_cost.py`` counts the gated one's). Every chain carries
the scope ``hvd_short_conv`` (``common/scopes.py``), and a kernel is named
with the scope as its prefix, so that the readers of a trace find either.

``causal_conv`` and ``gated_short_conv`` are XLA code. ``conv_act`` is one
piece of mathematics under two compilers; which runs is a function of the
platform and of the shapes it sees in its operands, decided as the call is
traced (``ops/pallas_kernels._decide``, ``_kernels_take``), with no option,
nothing read from the environment and no way from one to the other at run
time; ``hvd_tpu_short_conv_calls_total{path}`` counts the pick:

- **On a TPU, where the kernels take the shapes** (channels in whole tiles
  of 128 lanes, a sequence of whole tiles of ``_ROWS`` tokens, no more
  taps than the 8 sublanes): two Pallas kernels under one ``custom_vjp``,
  ``hvd_short_conv_fwd`` and ``hvd_short_conv_bwd``, each reading its
  operands once and writing its results once. x is the only residual: the
  backward forms the pre-activation again, and no fp32 tensor of the
  activation's size crosses HBM.
- **Elsewhere** (a CPU, the tests, the tiny preset, other shapes):
  ``silu(causal_conv(x, taps) + bias)`` as XLA code, which pads the rows
  in fp32 and adds ``n`` shifted slices. ``use_pallas=True`` runs the
  kernels' bodies in interpret mode there (the tests' twin check).
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_kernels import _decide
from ..common import metrics as metrics_lib
from ..common import scopes

_LANE = 128
# What the kernels were compiled and run for on the chip (PERF.md): a tile
# of 1024 tokens of a block of 256 lanes (128 where 256 does not divide the
# channels) a grid step, worked on 64 tokens at a time; the 16 tokens before
# a tile (one sublane tile of bf16) as a block of their own.
_ROWS = 1024
_CHUNK = 64
_HALO = 16
_SUBLANES = 8

_M_CALLS = metrics_lib.counter(
    "hvd_tpu_short_conv_calls_total",
    "conv_act calls traced, by the path picked for them: the Pallas "
    "kernels (a TPU, channels a multiple of 128, a sequence of whole "
    "tiles of 1024 tokens, at most 8 taps) or the XLA code",
    labels=("path",))


def causal_conv(x, taps):
    """Depthwise causal convolution along S: ``y_t = sum_j taps[j]
    x_{t - (n - 1) + j}``, fp32. x: (B, S, C); taps: (n, C)."""
    n = taps.shape[0]
    x = jnp.pad(x.astype(jnp.float32), ((0, 0), (n - 1, 0), (0, 0)))
    s = x.shape[1] - (n - 1)
    return sum(x[:, j:j + s] * taps[j] for j in range(n))


def gated_short_conv(b, c, x, taps):
    """``c * causal_conv(b * x, taps)`` in x's dtype, the arithmetic in
    fp32. b, c, x: (B, S, C); taps: (n, C)."""
    with jax.named_scope(scopes.SHORT_CONV):
        z = b.astype(jnp.float32) * x.astype(jnp.float32)
        return (c.astype(jnp.float32) * causal_conv(z, taps)).astype(x.dtype)


def conv_act(x, taps, bias=None, use_pallas: Optional[bool] = None):
    """``silu(causal_conv(x, taps) + bias)`` in x's dtype, the arithmetic
    in fp32. x: (B, S, C); taps: (n, C); bias: (C,) or None.

    ``use_pallas=None`` runs the Pallas kernels on a TPU where they take
    the shapes (``_kernels_take``) and the XLA code elsewhere; ``True``
    forces the kernels where they take the shapes (interpret mode off-TPU:
    the test path), ``False`` the XLA code. The path is a function of the
    platform and the shapes, picked as the call is traced."""
    use, interpret = _decide(use_pallas)
    use = use and _kernels_take(x, taps)
    _M_CALLS.labels(path="pallas" if use else "xla").inc()
    with jax.named_scope(scopes.SHORT_CONV):
        if use:
            if bias is None:
                bias = jnp.zeros(x.shape[-1:], jnp.float32)
            return _conv_kernels(x, taps.astype(jnp.float32),
                                 bias.astype(jnp.float32), interpret)
        pre = causal_conv(x, taps)
        if bias is not None:
            pre = pre + bias
        return jax.nn.silu(pre).astype(x.dtype)


def _kernels_take(x, taps):
    """Shapes the kernels are written for, from what ``conv_act`` sees in
    its operands: channels in whole tiles of 128 lanes, whole tiles of
    ``_ROWS`` tokens, the taps within one sublane tile. Anything else runs
    the XLA code."""
    return (x.ndim == 3 and x.shape[2] % _LANE == 0
            and x.shape[1] % _ROWS == 0 and 1 <= taps.shape[0] <= _SUBLANES)


# -- the Pallas kernels ------------------------------------------------------
#
# A tile of ``_ROWS`` tokens of a block of lanes a grid step, grid (batch,
# blocks of lanes, tiles), tokens on sublanes and channels on lanes, as x
# lies in HBM. The body walks the tile ``_CHUNK`` tokens at a time, so that
# a chunk's fp32 values stay in vector registers. The n - 1 tokens before a
# chunk are the tile's own rows or, at the tile's first chunk, the last
# rows of a second, 16-row block of x that ends where the tile starts
# (zero at the start of a sequence: nothing crosses from one batch row to
# the next). The backward walks tiles and chunks from the last: the n - 1
# rows of g = dy silu'(pre) after a chunk are the later chunk's first,
# carried in registers inside a tile and in VMEM scratch from one grid step
# to the next.

def _down(rows, k):
    """Row t of the result is row t - k of ``rows``, k of either sign (the
    rows that wrap round are the ones the callers cut off). A sublane
    rotation and a select a vector register, and the result lies on whole
    registers: a slice at an offset would leave every later operation on
    a register more a column."""
    return pltpu.roll(rows, k % rows.shape[0], 0) if k else rows


def _pre_activation(before, x, taps_ref, bias_ref):
    """``conv(x) + bias`` for a chunk x (rows, lanes) fp32 after the 8 rows
    ``before``, and the n shifted views of x the taps met (the backward's
    tap gradients meet them again)."""
    n = taps_ref.shape[0]
    rows = jnp.concatenate([before, x], 0)
    views = [_down(rows, n - 1 - j)[_SUBLANES:] for j in range(n)]
    pre = sum(v * taps_ref[j:j + 1, :] for j, v in enumerate(views))
    return pre + bias_ref[...], views


def _rows_before(before_ref, x_ref, tile, start):
    """The 8 rows before row ``start`` of the tile, fp32."""
    f32 = jnp.float32
    inside = x_ref[pl.ds(pl.multiple_of(jnp.maximum(start - _HALO, 0),
                                        _HALO), _HALO), :]
    outside = jnp.where(tile > 0, before_ref[...].astype(f32), 0.0)
    return jnp.where(start > 0, inside.astype(f32), outside)[_SUBLANES:]


def _fwd_kernel(before_ref, x_ref, taps_ref, bias_ref, y_ref):
    tile = pl.program_id(2)

    def chunk(c, carry):
        start = pl.multiple_of(c * _CHUNK, _CHUNK)
        rows = pl.ds(start, _CHUNK)
        pre, _ = _pre_activation(
            _rows_before(before_ref, x_ref, tile, start),
            x_ref[rows, :].astype(jnp.float32), taps_ref, bias_ref)
        y_ref[rows, :] = (pre * jax.nn.sigmoid(pre)).astype(y_ref.dtype)
        return carry

    jax.lax.fori_loop(0, x_ref.shape[0] // _CHUNK, chunk, 0)


def _bwd_kernel(before_ref, x_ref, dy_ref, taps_ref, bias_ref, dx_ref,
                dtaps_ref, dbias_ref, after_ref, sums_ref):
    """``after_ref`` (8, lanes): the first rows of g of the tile after this
    one; ``sums_ref`` (n + 1, 8, lanes): the tap gradients and the bias
    gradient, a sublane tile of partial sums each, over the tiles so far."""
    f32 = jnp.float32
    n = taps_ref.shape[0]
    step = pl.program_id(2)
    tile = pl.num_programs(2) - 1 - step
    chunks = x_ref.shape[0] // _CHUNK

    @pl.when(step == 0)
    def _():
        after_ref[...] = jnp.zeros_like(after_ref)
        sums_ref[...] = jnp.zeros_like(sums_ref)

    def by_sublane(v):
        return sum(v[k:k + _SUBLANES] for k in range(0, _CHUNK, _SUBLANES))

    def chunk(k, after):
        start = pl.multiple_of((chunks - 1 - k) * _CHUNK, _CHUNK)
        rows = pl.ds(start, _CHUNK)
        pre, views = _pre_activation(
            _rows_before(before_ref, x_ref, tile, start),
            x_ref[rows, :].astype(f32), taps_ref, bias_ref)
        s = jax.nn.sigmoid(pre)
        g = dy_ref[rows, :].astype(f32) * (s * (1.0 + pre * (1.0 - s)))
        later = jnp.concatenate([g, after], 0)
        dx = sum(_down(later, j - (n - 1))[:_CHUNK] * taps_ref[j:j + 1, :]
                 for j in range(n))
        dx_ref[rows, :] = dx.astype(dx_ref.dtype)
        for j, v in enumerate(views):
            sums_ref[j] += by_sublane(g * v)
        sums_ref[n] += by_sublane(g)
        return g[:_SUBLANES]

    after_ref[...] = jax.lax.fori_loop(0, chunks, chunk, after_ref[...])

    @pl.when(step == pl.num_programs(2) - 1)
    def _():
        dtaps_ref[...] = sums_ref[:n].sum(1)
        dbias_ref[...] = sums_ref[n].sum(0, keepdims=True)


def _block_lanes(channels):
    return 2 * _LANE if channels % (2 * _LANE) == 0 else _LANE


def _kernel_call(kernel, name, x, operands, outs, scratch, reverse,
                 interpret):
    """Grid (batch, blocks of lanes, tiles), the tiles in sequence
    (``reverse``: from the last). ``operands`` / ``outs``: (array or
    ShapeDtypeStruct, kind), the kind naming the layout: "tile" (B, S, C:
    the tile's rows, the block's lanes), "before" (the same array: the 16
    rows that end where the tile starts), "channels" (rows, C: whole rows,
    the block's lanes), "sums" (B, rows, C: one batch row's)."""
    batch, length, channels = x.shape
    lanes = _block_lanes(channels)
    tiles = length // _ROWS

    def at(i):
        return tiles - 1 - i if reverse else i

    def spec(v, kind):
        if kind == "tile":
            return pl.BlockSpec((None, _ROWS, lanes),
                                lambda b, j, i: (b, at(i), j))
        if kind == "before":
            return pl.BlockSpec(
                (None, _HALO, lanes), lambda b, j, i: (
                    b, jnp.maximum(at(i) * (_ROWS // _HALO) - 1, 0), j))
        if kind == "channels":
            return pl.BlockSpec((v.shape[0], lanes), lambda b, j, i: (0, j))
        return pl.BlockSpec((None, v.shape[1], lanes),
                            lambda b, j, i: (b, 0, j))

    return pl.pallas_call(
        kernel, grid=(batch, channels // lanes, tiles),
        in_specs=[spec(v, kind) for v, kind in operands],
        out_specs=[spec(v, kind) for v, kind in outs],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype) for v, _ in outs],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name=name,
    )(*(v for v, _ in operands))


# (a jit of their own, as the scan's: a model binds the convolution once a
# layer, and each kernel is traced and lowered once a shape)
@functools.partial(jax.jit, static_argnums=(3,))
def _kernel_forward(x, taps, bias, interpret):
    return _kernel_call(
        _fwd_kernel, scopes.SHORT_CONV_FWD, x,
        [(x, "before"), (x, "tile"), (taps, "channels"),
         (bias[None], "channels")], [(x, "tile")], [], False, interpret)[0]


@functools.partial(jax.jit, static_argnums=(4,))
def _kernel_backward(x, taps, bias, dy, interpret):
    """dx like x, and one batch row's sums for the taps' gradient (B, n,
    C) and the bias's (B, 1, C), fp32."""
    batch, _, channels = x.shape
    n, lanes = taps.shape[0], _block_lanes(channels)
    f32 = jnp.float32
    return _kernel_call(
        _bwd_kernel, scopes.SHORT_CONV_BWD, x,
        [(x, "before"), (x, "tile"), (dy, "tile"), (taps, "channels"),
         (bias[None], "channels")],
        [(x, "tile"),
         (jax.ShapeDtypeStruct((batch, n, channels), f32), "sums"),
         (jax.ShapeDtypeStruct((batch, 1, channels), f32), "sums")],
        [pltpu.VMEM((_SUBLANES, lanes), f32),
         pltpu.VMEM((n + 1, _SUBLANES, lanes), f32)],
        True, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _conv_kernels(x, taps, bias, interpret):
    """x (B, S, C), S whole tiles; taps (n, C) and bias (C,) fp32."""
    return _kernel_forward(x, taps, bias, interpret)


def _conv_kernels_fwd(x, taps, bias, interpret):
    return _kernel_forward(x, taps, bias, interpret), (x, taps, bias)


def _conv_kernels_bwd(interpret, residuals, dy):
    dx, dtaps, dbias = _kernel_backward(*residuals, dy, interpret)
    return dx, dtaps.sum(0), dbias.sum((0, 1))


_conv_kernels.defvjp(_conv_kernels_fwd, _conv_kernels_bwd)
