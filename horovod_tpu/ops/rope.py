"""Rotary position embedding on the rows the flash kernels read.

The models' q and k leave their projections as (B, S, H·D) rows and the
flash kernels read them so (``flash_attention._Layout``: a block is
``rows`` x 128 lanes, two heads of 64 side by side, or one head of 128).
The rotation pairs lane i of a head with lane i ± D/2 of the same head.
Written on (B, S, H, D) with half-width slices and a concatenate, it makes
XLA:TPU lay a 4-D array out with a minor axis of 64 and then 32 under a
128-lane tile: pads, copies of the halves, and a copy back to packed rows
for the kernel. Here it is computed on the packed rows themselves, where
the widths pack (the very test ``_Layout`` makes: D a multiple of 128, or
D dividing 128 with H a multiple of 128 / D):

    out = x * cos + rotate_half(x) * sin_signed

``rotate_half`` swaps the halves of each head and never leaves a tile of
``lanes`` = max(D, 128) lanes: for D = 128 one lane rotation by 64; for
D = 64 a tile is ``[a1 a2 b1 b2]``, a rotation by -32 gives
``[a2 b1 b2 a1]``, by +32 ``[b2 a1 a2 b1]``, lanes with ``lane % 64 < 32``
take the first and the others the second: ``[a2 a1 b2 b1]``. The sign of
the first half rides in the sine table, so the pass has the products and
the one sum per element of ``x1 * cos - x2 * sin`` / ``x1 * sin + x2 *
cos``, in fp32, cast once. The tables are lane-periodic, (1 or B, S,
lanes), and serve every tile of a row. The backward is the same pass on
the cotangent with the sine negated (the rotation's transpose is its
inverse); its only residual is the positions.

On a TPU the pass is a Pallas kernel (``hvd_rope_fwd`` / ``hvd_rope_bwd``,
``pltpu.roll`` on (rows, lanes) tiles: XLA lowers ``jnp.roll`` of the
minor axis to the slices, pads and copies this module exists to remove);
off a TPU its jnp twin. Any other width (D = 96, an odd count of narrow
heads), and on a TPU any S no block of whole sublane tiles divides (a
decode step's one row), keeps the (B, S, H, D) formula, which
:func:`rotate_heads` gives a caller whatever the widths.

Which rotation is one static description, :class:`Rotation`, that
:func:`rotate` and :func:`rotate_heads` both take (``base=`` alone says
the plain one): the rotary width (the first ``width`` channels of a head
turn in half-split pairs, the rest pass through), the inverse frequencies
(``base ** (-2 i / width)``, or YaRN's blend of them with their ``factor``-th
part), and a scale on cos and sin. On packed rows a narrower rotary width
changes the swap's period and nothing else: lanes of a head pair with the
lane ``width / 2`` away inside its first ``width`` lanes (for 64 of 128: a
rotation by 32 either way and the same select), and the tables carry the
identity, cos = 1 and sin = 0, on the lanes that pass through, so the pass
stays inside the 128-lane tile and the backward stays the same pass with
the sine negated, times the same scale.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _Layout
from .pallas_kernels import _decide, _sublane
from ..common import metrics as metrics_lib
from ..common import scopes

logger = logging.getLogger("horovod_tpu")

_BLOCK_ROWS = 512
_BLOCK_BYTES = 1 << 20     # of x in a block; as much again leaves it

_M_PATHS = metrics_lib.counter(
    "hvd_tpu_rope_paths",
    "rope calls traced, by head width and by the layout the rotation "
    "ran on: the packed (B, S, H*D) rows the flash kernels read, or "
    "(B, S, H, D) a head at a time where the widths do not pack; and by "
    "the rotation: plain (the whole head width at one base), partial (a "
    "rotary width under the head's), yarn (YaRN's frequencies)",
    labels=("head_dim", "layout", "rotation"))


@dataclasses.dataclass(frozen=True)
class Rotation:
    """Which rotation: static, hashable, compared by value.

    ``width``: the rotary channels of a head, its first ``width`` (None:
    the whole head); channel i < width / 2 pairs with channel i + width /
    2. ``base``: inverse frequency ``f_i = base ** (-2 i / width)``.
    ``factor`` other than 1 makes them YaRN's (Peng et al. 2023) over a
    trained length of ``original_length``: ``corr(r) = width ln(
    original_length / (2 pi r)) / (2 ln base)``, ``low = max(floor(corr(
    beta_fast)), 0)``, ``high = min(ceil(corr(beta_slow)), width - 1)``,
    ``ramp_i = clip((i - low) / (high - low), 0, 1)``, ``f_i (1 - ramp_i)
    + f_i / factor * ramp_i``.
    ``scale`` multiplies cos and sin (YaRN's attention factor)."""

    base: float = 10000.0
    width: Optional[int] = None
    factor: float = 1.0
    original_length: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    scale: float = 1.0

    @property
    def name(self):
        return "yarn" if self.factor != 1.0 else \
            "plain" if self.width is None else "partial"

    def rotary(self, d):
        """The rotary width under a head of ``d`` channels."""
        width = d if self.width is None else self.width
        if not 0 < width <= d or width % 2:
            raise ValueError(f"a rotary width is even and at most the "
                             f"head's {d}; got {width}")
        return width

    def inv_freq(self, pairs, width):
        """The inverse frequencies of the pairs ``pairs`` (fp32 pair
        numbers under ``width / 2``), fp32. The plain ones are computed
        where they are used, as they were; YaRN's are constants of the
        description."""
        half = width // 2
        if self.factor == 1.0:
            return self.base ** (-pairs / half)
        i = np.arange(half, dtype=np.float64)
        plain = self.base ** (-i / half)

        def corr(turns):
            return width * math.log(self.original_length / (
                2 * math.pi * turns)) / (2 * math.log(self.base))

        low = max(math.floor(corr(self.beta_fast)), 0)
        high = min(math.ceil(corr(self.beta_slow)), width - 1)
        ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
        table = plain * (1.0 - ramp) + plain / self.factor * ramp
        return jnp.asarray(table, jnp.float32)[pairs.astype(jnp.int32)]


def _tables(positions, rotation, d, lanes, backward):
    """cos and the signed sine of (1 or B, S) fp32 positions, (1 or B, S,
    lanes) in fp32: lane l holds frequency ``l % (w/2)`` of its head, w
    the rotary width, the sine negative in a rotary half's first lanes
    (positive there for the backward, negative in the second); lanes past
    the rotary width hold the identity."""
    width = rotation.rotary(d)
    half = width // 2
    lane = jnp.arange(lanes) % d
    freqs = rotation.inv_freq((lane % half).astype(jnp.float32), width)
    angles = positions[:, :, None] * freqs[None, None, :]
    sign = jnp.where((lane < half) != backward, -1.0, 1.0)
    cos, sin = jnp.cos(angles), jnp.sin(angles) * sign.astype(jnp.float32)
    if rotation.scale != 1.0:
        cos, sin = cos * rotation.scale, sin * rotation.scale
    if width < d:
        cos = jnp.where(lane < width, cos, 1.0)
        sin = jnp.where(lane < width, sin, 0.0)
    return cos, sin


def _rotate_twin(x, cos, sin, d):
    """The pass in jnp, on (B, S, H·D) rows; ``d`` is the swap's period,
    the rotary width."""
    lanes, half = cos.shape[-1], d // 2
    y = x.reshape(x.shape[:2] + (-1, lanes)).astype(jnp.float32)
    swapped = jnp.roll(y, half, axis=-1)
    if d < lanes:
        first = (jnp.arange(lanes) % d) < half
        swapped = jnp.where(first, jnp.roll(y, -half, axis=-1), swapped)
    out = y * cos[:, :, None] + swapped * sin[:, :, None]
    return out.astype(x.dtype).reshape(x.shape)


def _kernel(x_ref, cos_ref, sin_ref, o_ref, *, d):
    """One (rows, H·D) block against its (rows, lanes) tables, a tile of
    ``lanes`` at a time; ``d`` is the swap's period, the rotary width."""
    lanes, half = cos_ref.shape[-1], d // 2
    cos, sin = cos_ref[...], sin_ref[...]
    if d < lanes:       # d divides 128: a power of two
        lane = jax.lax.broadcasted_iota(jnp.int32, cos.shape, 1)
        first = (lane & (d - 1)) < half
    for t in range(x_ref.shape[-1] // lanes):
        tile = pl.ds(t * lanes, lanes)
        y = x_ref[:, tile].astype(jnp.float32)
        swapped = pltpu.roll(y, half, 1)
        if d < lanes:
            swapped = jnp.where(first, pltpu.roll(y, lanes - half, 1),
                                swapped)
        o_ref[:, tile] = (y * cos + swapped * sin).astype(o_ref.dtype)


def _block_rows(s, width, dtype):
    """Rows of a block: the most that divide S in whole sublane tiles
    within ``_BLOCK_ROWS`` and ``_BLOCK_BYTES``; None where none does."""
    sub = _sublane(dtype)
    most = min(_BLOCK_ROWS, _BLOCK_BYTES // (width * dtype.itemsize), s)
    return next((r for r in range(most - most % sub, 0, -sub)
                 if s % r == 0), None)


def _rotate_kernel(x, cos, sin, d, rows, interpret, name):
    b, s, width = x.shape
    lanes = cos.shape[-1]
    shared = cos.shape[0] == 1
    # the batch innermost: a shared table's block stays where it is
    x_spec = pl.BlockSpec((None, rows, width), lambda i, j: (j, i, 0))
    t_spec = pl.BlockSpec((None, rows, lanes),
                          lambda i, j: (0 if shared else j, i, 0))
    return pl.pallas_call(
        functools.partial(_kernel, d=d),
        grid=(s // rows, b),
        in_specs=[x_spec, t_spec, t_spec],
        out_specs=x_spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret, name=name,
    )(x, cos, sin)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _pass(x, positions, rotation, d, lanes, block, backward):
    """``block``: (rows, interpret) of the kernel, or None for the twin.
    A ``jit`` of its own, so that the layers of a model share one trace
    and one lowering of it: a ``pallas_call`` is traced and lowered anew
    wherever it is bound, and 48 of them cost gpt2-small's step 3.5 s of
    set-up with its program read from the cache (PERF.md PR 39); XLA
    inlines the calls, and the tables are still built once a step."""
    cos, sin = _tables(positions, rotation, d, lanes, backward)
    width = rotation.rotary(d)
    if block is None:
        return _rotate_twin(x, cos, sin, width)
    return _rotate_kernel(x, cos, sin, width, *block,
                          scopes.ROPE_BWD if backward else scopes.ROPE_FWD)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _rotate_rows(x, positions, rotation, d, lanes, block):
    return _pass(x, positions, rotation, d, lanes, block, False)


def _rotate_rows_fwd(x, positions, rotation, d, lanes, block):
    return _pass(x, positions, rotation, d, lanes, block, False), positions


def _rotate_rows_bwd(rotation, d, lanes, block, positions, g):
    return (_pass(g, positions, rotation, d, lanes, block, True),
            jnp.zeros_like(positions))


_rotate_rows.defvjp(_rotate_rows_fwd, _rotate_rows_bwd)


@functools.lru_cache(maxsize=None)
def _say_path(shape, dtype, how):
    logger.info("rope: x%s %s rotates %s", shape, dtype, how)


def _positions(positions, s):
    if positions is None:
        positions = jnp.arange(s)[None, :]
    return positions.astype(jnp.float32)


def rotate_heads(x, positions=None, base: float = 10000.0,
                 rotation: Optional[Rotation] = None):
    """x (B, S, H, D) rotated by its (1 or B, S) ``positions`` (None:
    0 … S-1) a head at a time, with half-width slices and a concatenate,
    under the scope ``hvd_rope``: what :func:`rotate` does with widths
    that do not pack, for a caller that wants it whatever the widths.
    ``rotation``: a :class:`Rotation`; None is the plain one at ``base``."""
    rotation = rotation or Rotation(base)
    s, d = x.shape[1], x.shape[3]
    width = rotation.rotary(d)
    half = width // 2
    _M_PATHS.labels(head_dim=str(d), layout="per_head",
                    rotation=rotation.name).inc()
    _say_path(x.shape, x.dtype.name, "a head at a time")
    with jax.named_scope(scopes.ROPE):
        freqs = rotation.inv_freq(
            jnp.arange(0, half, dtype=jnp.float32), width)
        angles = _positions(positions, s)[:, :, None] * freqs[None, None, :]
        cos = jnp.cos(angles)[:, :, None, :]                # (B, S, 1, w/2)
        sin = jnp.sin(angles)[:, :, None, :]
        if rotation.scale != 1.0:
            cos, sin = cos * rotation.scale, sin * rotation.scale
        x1, x2 = x[..., :half], x[..., half:width]
        passed = [x[..., width:]] if width < d else []
        rotated = jnp.concatenate(
            [x1 * cos - x2 * sin, x1 * sin + x2 * cos] + passed, axis=-1)
        return rotated.astype(x.dtype)


def rotate(x, positions=None, base: float = 10000.0,
           use_pallas: Optional[bool] = None,
           rotation: Optional[Rotation] = None):
    """x (B, S, H, D) rotated by its (1 or B, S) ``positions`` (None:
    0 … S-1), under the scope ``hvd_rope``: on the packed rows where H and
    D pack, and a head at a time elsewhere. ``rotation``: a
    :class:`Rotation`; None is the plain one at ``base``.
    ``use_pallas=None`` runs the packed pass as the Pallas kernels on a
    TPU and as their jnp twin elsewhere; ``True`` forces the kernels
    (interpret mode off-TPU: the test path), ``False`` the twin."""
    rotation = rotation or Rotation(float(base))
    b, s, h, d = x.shape
    layout = _Layout(h, d)
    use, interpret = _decide(use_pallas)
    rows = _block_rows(s, h * d, x.dtype) if use else None
    width = rotation.rotary(d)
    # the swap's period inside a tile of lanes: the tile, or a power of
    # two under it
    swaps = width == layout.lanes or (
        layout.lanes % width == 0 and width & (width - 1) == 0)
    # On a TPU the twin is no fallback: XLA:TPU lowers its rolls to the
    # very slices and pads (30 ms a step of gpt2-small for 5.6, PERF.md
    # PR 39), and its fusion emitter has aborted on them at D = 128.
    if not layout.packed or not swaps \
            or (use and not interpret and rows is None):
        return rotate_heads(x, positions, rotation=rotation)
    _M_PATHS.labels(head_dim=str(d), layout="packed",
                    rotation=rotation.name).inc()
    _say_path(x.shape, x.dtype.name,
              f"on packed (B, S, {h * d}) rows in tiles of {layout.lanes} "
              "lanes, " + (f"the Pallas kernels on blocks of {rows} rows"
                           if rows else "in jnp"))
    block = None if rows is None else (rows, interpret)
    with jax.named_scope(scopes.ROPE):
        return _rotate_rows(x.reshape(b, s, h * d), _positions(positions, s),
                            rotation, d, layout.lanes,
                            block).reshape(x.shape)
