"""Flash attention — Pallas TPU kernels for the attention hot op.

The reference has no attention kernels (it is a collectives framework);
this belongs to the TPU rebuild's perf mandate: attention is where the
BERT benchmark's FLOPs and HBM traffic live, and the blockwise
online-softmax formulation (Dao et al.; same math as ring attention's
per-block combine in horovod_tpu/parallel/ring_attention.py) keeps the
(S, S) logits matrix out of HBM entirely — O(S) memory instead of O(S²),
with every block matmul MXU-shaped.

Layout (``_Layout``): the public API takes (B, S, H, D) as produced by
the models' fused QKV projection, and where the widths allow — D a
multiple of 128, or D dividing 128 with H a multiple of G = 128 / D, which
is every model here (two heads of 64) — the kernels read exactly that
memory, seen as (B, S, H·D): a block is (rows, 128 lanes) = G whole heads
side by side. Nothing is transposed, padded or widened on the way in or
out, and HBM holds no lane padding. A head's matmuls run on the whole
128-lane tile with the other heads' lanes zeroed in one operand: the MXU
does for 128 lanes what it did for 64, the products are exact, and each
result lands in its own head's lanes, so the heads' results add up to
the tile (the forward's second product alone works on a head's own D
rows of the transposed tile instead). Other shapes (an odd head count, a
width like 80) go one head a block on the transposed (B, H, S, D), D
being that array's own minor size. Mosaic wants a block's last two dims (8k, 128k) or equal to the
array's; both layouts give it that. Operands cross at the caller's own
dtype, and o, dq, dk, dv come back in it. The MXU is fed that dtype
(bf16 tiles straight from the refs; probabilities and ds cast to it
before their second matmul) and accumulates in fp32; the running max /
sum, ``exp`` and the saved logsumexp are fp32 whatever the input.

Per-row vectors (logsumexp, and ``delta - dlse`` of the backward) cross
HBM as (B, H, 1, S) rows and the key mask as (B, 1, S): a block of them
is a (1, block) lane slice, which is why a compiled block is a multiple
of 128 or the whole sequence. Both kernels read a block of the key mask
as a (block_k, 1) column of their key-major scores. ``mask=None`` builds
no mask operand and no ``where``; under ``causal`` only the blocks the
diagonal crosses pay for the iota / compare / select, blocks under it
run bare and blocks above it are skipped.

Two kernels, named in common/scopes.py. Forward: grid (B, H/G,
S/block_q), K/V whole in VMEM per (batch, head group), an in-kernel loop
over their blocks with carried fp32 state. It computes the scores of a
block transposed (k·qᵀ: keys down the sublanes, queries along the
lanes), which makes the online softmax's per-query vectors (running max,
sum, rescale) (1, block_q) lane rows that broadcast along sublanes, and
the max and the sum over keys element-wise over the tile's sublane
groups with one 8-sublane fold: no reduction across lanes, no one-lane
column broadcast back across them, and the logsumexp is already the row
it is stored as. A head's accumulator is held transposed, (D, block_q) =
its own rows of vᵀ times the probabilities (v's tile is turned once a
block, an eighth of the score tile; packed heads each stream their own D
rows through the MXU, not the tile's 128), and the heads' accumulators,
laid under one another, are turned back to (block_q, lanes) once a q
block, after the cast. Backward, one call for dq, dk
and dv: grid (B, H/G, S/block_k, S/block_q) with the q blocks innermost.
It computes the scores of a block once, transposed (k·qᵀ), which makes
dv = pᵀ·dO and dk = dsᵀ·q plain matmuls and lets the row vectors
broadcast along sublanes; dq's share, (dsᵀ)ᵀ·k, is the one transposed
product: five S×S×d products and one ``exp`` a block. dk and dv wait in
fp32 VMEM scratch while the q blocks pass; dq waits in an fp32 scratch
over the whole sequence of the (batch, head group), (S, lanes), added to
in ascending k order, and leaves through an output block of the same
extent, so it goes to HBM once and no partial dq ever does: S × lanes ×
(4 + 2 × itemsize) bytes, the size class of the K and V the forward
holds. ``block_q`` / ``block_k`` default to a choice from S, D, the
dtype and a VMEM budget (``_choose_blocks``); passing them caps the
choice.

Grouped-query attention: k and v may hold fewer heads than q (H a
multiple of Hkv, q head h reads K/V head h // (H / Hkv)). Where a block is
one head (width 128, or the per-head layout) that is the K/V index maps
alone: nothing is repeated in HBM, and the backward writes each q head's
dk and dv, which are then summed over the group. Where a 128-lane block
packs several narrow heads, K and V are repeated before the call.

Which (query, key) pairs exist is a static **mask kind** (``MaskKind``),
carried through both kernels the way the blocks are: it says of a (query
tile, key tile) pair whether it is empty, full or partial, from the
tiles' indices alone, and gives the elementwise predicate of the partial
ones. ``NO_MASK`` (every tile full) and ``CAUSAL`` (the text above) are
two instances, what ``causal=False`` / ``True`` mean; the third is
``BlockDiffusionMask(block)`` over a noisy and a clean copy of one
sequence laid end to end, ``[noisy ; clean]`` of L positions each in
blocks of ``block`` tokens: a noisy query sees its own noisy block both
ways and the clean blocks strictly before it, a clean query the clean
blocks up to its own, nobody a noisy key of another block. Three quarters
of that 2L square are empty tiles, which neither kernel visits: the
forward's loop runs over the two key ranges a q block can see, the
backward's grid steps of an empty pair do nothing and fetch nothing new
(their block indices are clamped to the nearest visited pair's). The
blocks tile L, so that no tile straddles the two copies. The fourth is
``SlidingWindowMask(window)``, causal attention over the last ``window``
keys: the visible pairs are a band under the diagonal, the forward loops
over the band's k blocks (partial tiles at the window's far edge, bare
tiles, partial tiles on the diagonal) and the backward's grid is the band
itself, a k block's visible q blocks and no more, with dq's rows zeroed
at the first k block that sees them; its two calls carry names of their
own (``scopes.SWA_KERNELS``). How many tiles
a call visits of how many is counted where the call is traced
(``tiles_visited``, the gauge ``hvd_tpu_flash_attention_tiles``).

``flash_attention`` (what the models call) has a backward with no lse
cotangent at all; ``flash_attention_with_lse`` (ring attention's
blockwise-combine interface) returns the logsumexp as a differentiable
output and folds its cotangent into the same kernels. Off-TPU (or
sequences no block tiles) falls back to the plain jnp reference —
numerically identical, used by the CPU test suite which also runs the
real kernel bodies in interpret mode.
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

from .pallas_kernels import _decide
from ..common import metrics as metrics_lib
from ..common import scopes
from ..common.config import runtime_env

logger = logging.getLogger("horovod_tpu")

_NEG = -1e30  # mask value; NOT -inf (exp(-inf - -inf) = nan)
_LANE = 128
_BLOCK_TARGET = 512        # swept on the v5e (PERF.md §6, PR 25)
_WHOLE_SEQ_MAX = 1024      # an S no 128-multiple divides runs as one block
_VMEM_BUDGET = 40 << 20    # what _choose_blocks lets one call plan for
_VMEM_FLOOR = 32 << 20     # vmem_limit_bytes is never set below this
_VMEM_CEIL = 96 << 20

_NT = (((1,), (1,)), ((), ()))   # a · bᵀ
_NN = (((1,), (0,)), ((), ()))   # a · b

_M_PATHS = metrics_lib.counter(
    "hvd_tpu_flash_attention_traces_total",
    "flash-attention kernel paths traced, by what engaged: sequence "
    "length, head width, dtype, the blocks chosen, key mask operand, "
    "causal, the mask kind, and whether the lse cotangent is a backward "
    "operand",
    labels=("seq_len", "head_dim", "dtype", "block_q", "block_k",
            "has_mask", "causal", "dlse", "mask_kind"))
_M_TILES = metrics_lib.gauge(
    "hvd_tpu_flash_attention_tiles",
    "(query tile, key tile) pairs of one flash-attention call's S x S "
    "square, a batch row and head: tiles=\"visited\" those the kernels "
    "work on under the call's mask kind, tiles=\"square\" all of them; "
    "static, set where the call is traced",
    labels=("mask_kind", "seq_len", "block_q", "block_k", "tiles"))


def _pick_block(s: int, target: int = 128) -> Optional[int]:
    """Largest multiple-of-8 divisor of s that is <= target."""
    for b in range(min(target, s), 7, -1):
        if s % b == 0 and b % 8 == 0:
            return b
    return None


def _lane_block(s: int, target: int) -> Optional[int]:
    """The block a COMPILED call uses: the largest multiple-of-128
    divisor of s that is <= target, else s whole where s is short (a
    block of the lse / mask rows is a (1, block) lane slice, which
    Mosaic takes at multiples of 128 or at the array's own size)."""
    for b in range(min(target, s) // _LANE * _LANE, 0, -_LANE):
        if s % b == 0:
            return b
    if s % 8 == 0 and s <= _WHOLE_SEQ_MAX:
        return s
    return None


def _vmem_estimate(s: int, d: int, itemsize: int, bq: int, bk: int) -> int:
    """Bytes of VMEM the hungrier of the two calls plans for. What stays
    for a whole sequence: K and V, double buffered (forward), or dq as an
    fp32 accumulator and its double-buffered output (backward). Beside
    it the q-side / k-side / output blocks, each double buffered and
    lane-padded, and the fp32 (bq, bk) temporaries."""
    lanes = -(-d // _LANE) * _LANE
    resident = s * lanes * max(2 * 2 * itemsize, 4 + 2 * itemsize)
    blocks = 2 * 4 * max(bq, bk) * lanes * itemsize
    scores = 6 * bq * bk * 4
    return resident + blocks + scores


def _choose_blocks(s: int, d: int, dtype) -> tuple:
    """(block_q, block_k) targets from the shape: 512-class blocks, cut
    down while the plan overruns the VMEM budget."""
    itemsize = jnp.dtype(dtype).itemsize
    tq = tk = _BLOCK_TARGET
    while max(tq, tk) > _LANE \
            and _vmem_estimate(s, d, itemsize, tq, tk) > _VMEM_BUDGET:
        if tk >= tq:
            tk //= 2
        else:
            tq //= 2
    return tq, tk


def _resolve_blocks(s, d, dtype, block_q, block_k, interpret, span=None):
    """The (bq, bk) a call runs with, or None where no block tiles s.
    ``block_q`` / ``block_k`` of None are chosen from the shape; given,
    they cap the block. ``span``: the length the blocks must tile where
    that is not s itself (``MaskKind.span``)."""
    tq, tk = _choose_blocks(s, d, dtype)
    pick = _pick_block if interpret else _lane_block
    span = s if span is None else span
    bq = pick(span, tq if block_q is None else block_q)
    bk = pick(span, tk if block_k is None else block_k)
    return (bq, bk) if bq and bk else None


def _repeat_heads(x, group):
    """(B, S, Hkv, D) -> (B, S, Hkv * group, D), each head ``group``
    times in a row."""
    return x if group == 1 else jnp.repeat(x, group, axis=2)


def reference_attention(q, k, v, mask=None, causal=False, mask_kind=None):
    """Plain softmax attention on (B, S, H, D); ``mask`` is a (B, S) key
    mask (1 = attend); k and v may hold H / group heads; ``mask_kind`` as
    :func:`flash_attention` takes it, written out as its (S, S) boolean
    matrix (``MaskKind.dense``). The jnp fallback and the numerics
    oracle."""
    d = q.shape[-1]
    kind = _as_kind(mask_kind or causal)
    k, v = (_repeat_heads(x, q.shape[2] // x.shape[2]) for x in (k, v))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / np.sqrt(d)
    if mask is not None:
        logits = jnp.where(mask[:, None, None, :] > 0, logits, _NEG)
    if kind == CAUSAL:
        s = q.shape[1]
        rows = jax.lax.broadcasted_iota(jnp.int32, (s, s), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (s, s), 1)
        logits = jnp.where((rows >= cols)[None, None], logits, _NEG)
    elif kind != NO_MASK:
        logits = jnp.where(kind.dense(q.shape[1])[None, None], logits, _NEG)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs,
                      v.astype(jnp.float32)).astype(q.dtype)


# -- kernels ----------------------------------------------------------------
#
# Every kernel sees 2-D tiles (rows, lanes): ``lanes`` holds one head of
# width D, or — packed, see _Layout — G = 128 / D heads side by side. A
# head's matmuls then run on the whole 128-lane tile with the other
# heads' lanes zeroed in ONE operand: the contraction (or the output)
# over 128 lanes costs the MXU what 64 did, the products are exact, and a
# result lands in its own head's lanes, so the heads' partial results
# add up to the tile. (The forward's p·v is the exception: on the
# transposed v tile a head is D whole sublane rows, sliced, not zeroed.)
# The row vectors come as (G, 1, rows).

def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _scale_folds_into_q(scale: float) -> bool:
    """Whether ``q * scale`` is exact in q's own dtype: a power of two
    (head widths 64 and 16). Width 128's 2^-3.5 is none, and would round
    a bf16 q a second time."""
    return math.frexp(scale)[0] == 0.5


def _scaled(q_ref, scale):
    """``(q tile, on_scores)``: the softmax scale goes into the q tile
    where that is exact, and ``on_scores`` is None; else the tile is as
    it came and the fp32 scores take the scale (``_scores``)."""
    q = q_ref[...]
    if _scale_folds_into_q(scale):
        return (q.astype(jnp.float32) * scale).astype(q.dtype), None
    return q, scale


def _scores(a, b, on_scores):
    """a · bᵀ in fp32, times the scale where ``_scaled`` left it out."""
    s = _dot(a, b, _NT)
    return s if on_scores is None else s * on_scores


def _head(x, g, heads):
    """Tile ``x`` (rows, lanes) with every lane outside head g zeroed."""
    if heads == 1:
        return x
    width = x.shape[-1] // heads
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, x.shape[-1]), 1)
    return jnp.where((lane >= g * width) & (lane < (g + 1) * width), x,
                     jnp.zeros_like(x))


def _below_diagonal(row0, col0, shape, rows_dim):
    """rows >= cols over a block whose first row / column are row0 /
    col0; ``rows_dim`` is the block dimension the q rows run along."""
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, rows_dim)
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - rows_dim)
    return rows >= cols


def _masked(s, kmask, keep):
    """Scores with the key mask and the causal select applied, each only
    where there is one."""
    if kmask is not None:
        s = jnp.where(kmask > 0, s, _NEG)
    if keep is not None:
        s = jnp.where(keep, s, _NEG)
    return s


# -- mask kinds ---------------------------------------------------------------
#
# A mask kind is static (hashable, compared by value: it rides where
# ``causal`` rode, a non-differentiable argument of the ``custom_vjp``) and
# speaks in tile indices: the kernels ask it which key blocks a q block
# loops over and which of them need the elementwise select (forward),
# whether a (q block, k block) grid step is bare, partial or empty and
# which q block a skipped step should pretend to be (backward). The
# scalars it is asked with are the kernels' own (program ids, loop
# counters); ``tiles_visited`` asks the same methods with numbers.

@dataclasses.dataclass(frozen=True)
class MaskKind:
    """No structure: every (query, key) pair exists, every tile is full.
    The base of the others, and what ``causal=False`` means."""

    name = "none"

    def span(self, s):
        """The length the blocks must tile: no tile may straddle a seam
        of the mask."""
        return s

    def dense(self, s):
        """The (S, S) boolean matrix, rows the queries (numpy)."""
        return np.ones((s, s), bool)

    def keep(self, s, row0, col0, shape, rows_dim):
        """The predicate over a partial tile whose first row / column are
        row0 / col0; ``rows_dim`` is the tile dimension the q rows run
        along."""
        raise NotImplementedError("no tile of this kind is partial")

    def key_segments(self, s, qi, block_q, block_k, nk):
        """``((first, end, partial), ...)``: the ranges of k blocks q
        block ``qi`` visits, in the order the forward loops over them."""
        return ((0, nk, False),)

    def tile(self, s, qi, ki, block_q, block_k):
        """``(bare, visible)`` of one grid step of the backward; a
        Python ``True`` for ``bare`` says every step is."""
        return True, True

    def last_key_block(self, s, qi, ki, block_q, block_k, nk, visible):
        """Whether q block ``qi`` meets no k block after ``ki``: dq's
        rows are written out then."""
        return ki == nk - 1

    def first_query_block(self, s, j, i, block_q, block_k):
        """The q block whose tiles the backward's grid step (k block j,
        step i) fetches: the step's own (``query_block``) where the pair
        is visited, else a neighbour that is, so that a skipped step
        fetches nothing new."""
        return i

    # What follows is asked by a kind whose visible pairs lie in a band:
    # the three standing kinds keep the answers below, and their kernels
    # the jaxprs they had.

    kernel_names = (scopes.FLASH_FWD, scopes.FLASH_DKV)

    def query_steps(self, s, block_q, block_k):
        """The innermost extent of the backward's grid: how many q blocks
        a k block is given steps for."""
        return s // block_q

    def query_block(self, s, j, i, block_q, block_k):
        """The q block of the backward's grid step (k block j, step i);
        past the last one where the step has none."""
        return i

    def first_key_block(self, s, qi, ki, block_q, block_k):
        """Whether q block ``qi`` meets no k block before ``ki``: dq's
        rows start from zero then."""
        return ki == 0


@dataclasses.dataclass(frozen=True)
class _Causal(MaskKind):
    """rows >= cols: the blocks wholly under the diagonal run bare,
    those it crosses pay for the select, those above it are skipped."""

    name = "causal"

    def dense(self, s):
        return np.tril(np.ones((s, s), bool))

    def keep(self, s, row0, col0, shape, rows_dim):
        return _below_diagonal(row0, col0, shape, rows_dim)

    def key_segments(self, s, qi, block_q, block_k, nk):
        n_bare = jax.lax.div(qi * block_q + 1, block_k)
        n_visible = jnp.minimum(
            jax.lax.div((qi + 1) * block_q + block_k - 1, block_k), nk)
        return (0, n_bare, False), (n_bare, n_visible, True)

    def tile(self, s, qi, ki, block_q, block_k):
        bare = qi * block_q >= (ki + 1) * block_k - 1
        visible = (qi + 1) * block_q > ki * block_k
        return bare, visible

    def last_key_block(self, s, qi, ki, block_q, block_k, nk, visible):
        # the next k block starts past this q block's last row
        return jnp.logical_and(
            visible, (ki + 1) * block_k >= (qi + 1) * block_q)

    def first_query_block(self, s, j, i, block_q, block_k):
        return jnp.maximum(i, jax.lax.div(j * block_k, block_q))


def _block_of(x, block):
    """x // block of non-negative int32s: a shift where that says it."""
    if block & (block - 1) == 0:
        return jnp.right_shift(x, block.bit_length() - 1)
    return jax.lax.div(x, jnp.int32(block))


@dataclasses.dataclass(frozen=True)
class BlockDiffusionMask(MaskKind):
    """Block-diffusion training's mask over ``[noisy ; clean]``: two
    copies of one sequence of L = S / 2 positions laid end to end, cut
    into blocks of ``block`` tokens, n(i) = (i mod L) // block. Query u
    sees key w iff

    - both noisy and n(u) == n(w): a block sees itself, both ways;
    - u noisy, w clean and n(w) < n(u): the clean past, strictly;
    - both clean and n(w) <= n(u): block-causal;

    and a clean query sees no noisy key. Of the 2L square's tiles the
    upper right quarter and all of the upper left but its diagonal are
    empty, the noisy diagonal and the two clean diagonals are partial,
    and what lies under the clean diagonals is full."""

    block: int = 4
    name = "block_diffusion"

    def span(self, s):
        if s % 2 or (s // 2) % self.block:
            raise ValueError(
                f"a block-diffusion mask lies over two copies of a "
                f"sequence in blocks of {self.block}; got S = {s}")
        return s // 2

    def dense(self, s):
        half = self.span(s)
        at = np.arange(s)
        noisy, n = at < half, (at % half) // self.block
        un, wn, nu, nw = noisy[:, None], noisy[None], n[:, None], n[None]
        return (un & wn & (nu == nw)) | (un & ~wn & (nw < nu)) \
            | (~un & ~wn & (nw <= nu))

    def _local(self, s, at):
        """``(in the noisy copy, position within its copy)`` of a tile's
        first row or column: a tile lies within one copy."""
        half = s // 2
        noisy = at < half
        return noisy, at - jnp.where(noisy, 0, half)

    def keep(self, s, row0, col0, shape, rows_dim):
        # one predicate for the three partial kinds of tile, told apart
        # by two scalars: n(w) <= n(u) - a and n(w) >= n(u) - c, with
        # (a, c) = (0, 0) noisy on noisy, (1, all) noisy on clean, (0,
        # all) clean on clean
        rn, r0 = self._local(s, row0)
        cn, c0 = self._local(s, col0)
        nu = _block_of(r0 + jax.lax.broadcasted_iota(
            jnp.int32, shape, rows_dim), self.block)
        nw = _block_of(c0 + jax.lax.broadcasted_iota(
            jnp.int32, shape, 1 - rows_dim), self.block)
        a = jnp.where(jnp.logical_and(rn, jnp.logical_not(cn)), 1, 0)
        c = jnp.where(cn, 0, s)
        return (nw <= nu - a) & (nw >= nu - c)

    def key_segments(self, s, qi, block_q, block_k, nk):
        # the noisy copy's keys of the q block's own rows (a noisy q block
        # alone: no clean one sees them), then the clean copy's keys up to
        # them, the last of those partial
        noisy, r0 = self._local(s, qi * block_q)
        lo = jax.lax.div(r0, block_k)
        hi = jax.lax.div(r0 + block_q + block_k - 1, block_k)
        clean = nk // 2
        return ((lo, jnp.where(noisy, hi, lo), True),
                (clean, clean + lo, False),
                (clean + lo, clean + hi, True))

    def tile(self, s, qi, ki, block_q, block_k):
        rn, r0 = self._local(s, qi * block_q)
        cn, c0 = self._local(s, ki * block_k)
        overlap = jnp.logical_and(c0 < r0 + block_q, r0 < c0 + block_k)
        before = c0 + block_k <= r0
        clean_key = jnp.logical_not(cn)
        bare = jnp.logical_and(clean_key, before)
        visible = jnp.logical_or(
            bare, jnp.logical_and(overlap, jnp.logical_or(clean_key, rn)))
        return bare, visible

    def first_query_block(self, s, j, i, block_q, block_k):
        half = s // 2
        noisy_key, c0 = self._local(s, j * block_k)
        lo = jax.lax.div(c0, block_q)
        hi = jax.lax.div(c0 + block_k + block_q - 1, block_q)
        clean = half // block_q             # the first clean q block
        return jnp.where(
            noisy_key, jnp.clip(i, lo, hi - 1),
            jnp.where(i < clean, jnp.maximum(i, lo),
                      jnp.maximum(i, clean + lo)))


@dataclasses.dataclass(frozen=True)
class SlidingWindowMask(MaskKind):
    """Causal attention over the last ``window`` keys: query u sees key w
    iff 0 <= u - w < window. The visible pairs are a band under the
    diagonal, ``window`` wide: a q block's key range has two partial ends
    (the window's far edge and the diagonal; one tile may be both) and,
    where the window is wider than a block, bare tiles between them.
    Nothing outside the band is visited. The forward loops over the
    band's k blocks; the backward's grid is the band itself, (k block,
    step) with ``query_steps`` steps a k block and q block =
    ``query_block`` = the k block's first visible q block + step, so that
    a call costs what its band costs and not its square's grid steps (at S
    8192, blocks of 256 and a window of 512 a head has 32 x 3 steps, not
    32 x 32). ``window >= S`` is ``CAUSAL``: the same tiles in the same
    order, every select's second half true.

    The blocks are ``_choose_blocks``'s own. A q block of b rows works on
    b + window columns for b x window visible pairs, window / (window + b)
    of what it computes: half at b = window = 512, two thirds at 256, four
    fifths at 128; but on the v5e at S 8192, 72 heads on 8, a forward and
    a backward took 13.2 ms at 512 x 512, 16.4 at 256 x 256 and 28.8 at
    128 x 128 (14.6 / 15.9 with one side 256): a tile's fixed cost
    outweighs what a thinner rim saves (PERF.md section 6, PR 42). The
    two calls carry names of their own, so that a trace tells a window
    call from a full one."""

    window: int = 512
    name = "sliding_window"
    kernel_names = scopes.SWA_KERNELS

    def __post_init__(self):
        if self.window < 1:
            raise ValueError(f"a window holds at least the query's own "
                             f"key; got {self.window}")

    def dense(self, s):
        gap = np.arange(s)[:, None] - np.arange(s)[None]
        return (gap >= 0) & (gap < self.window)

    def keep(self, s, row0, col0, shape, rows_dim):
        gap = (row0 - col0) \
            + jax.lax.broadcasted_iota(jnp.int32, shape, rows_dim) \
            - jax.lax.broadcasted_iota(jnp.int32, shape, 1 - rows_dim)
        return (gap >= 0) & (gap < self.window)

    def _first_key_block(self, r0, block_k):
        """The first k block the q rows from ``r0`` on see."""
        return jax.lax.div(jnp.maximum(r0 - (self.window - 1), 0), block_k)

    def key_segments(self, s, qi, block_q, block_k, nk):
        # far-edge partial tiles, bare tiles, diagonal partial tiles; a
        # tile that is both (a window narrower than a block) falls to the
        # last range
        r0 = qi * block_q
        hi = jnp.minimum(
            jax.lax.div(r0 + block_q + block_k - 1, block_k), nk)
        bare_end = jax.lax.div(r0 + 1, block_k)
        bare_first = jnp.minimum(bare_end, jax.lax.div(
            jnp.maximum(r0 + block_q - self.window, 0) + block_k - 1,
            block_k))
        return ((self._first_key_block(r0, block_k), bare_first, True),
                (bare_first, bare_end, False), (bare_end, hi, True))

    def tile(self, s, qi, ki, block_q, block_k):
        # the gaps row - col over a tile are the whole range below
        r0, c0 = qi * block_q, ki * block_k
        least, most = r0 - c0 - (block_k - 1), r0 - c0 + (block_q - 1)
        visible = jnp.logical_and(
            jnp.logical_and(most >= 0, least < self.window), r0 < s)
        bare = jnp.logical_and(
            jnp.logical_and(least >= 0, most < self.window), r0 < s)
        return bare, visible

    def last_key_block(self, s, qi, ki, block_q, block_k, nk, visible):
        return jnp.logical_and(
            visible, (ki + 1) * block_k >= (qi + 1) * block_q)

    def first_key_block(self, s, qi, ki, block_q, block_k):
        r0 = qi * block_q
        return jnp.logical_and(
            r0 < s, ki == self._first_key_block(r0, block_k))

    def query_steps(self, s, block_q, block_k):
        # the most q blocks one k block's rows and their window reach
        return min(s // block_q, max(
            (j * block_k + block_k + self.window - 2) // block_q
            - j * block_k // block_q + 1 for j in range(s // block_k)))

    def query_block(self, s, j, i, block_q, block_k):
        return jax.lax.div(j * block_k, block_q) + i

    def first_query_block(self, s, j, i, block_q, block_k):
        # clamped to the last q block k block j's last row is visible to
        last = jax.lax.div(j * block_k + block_k + self.window - 2, block_q)
        return jnp.minimum(self.query_block(s, j, i, block_q, block_k),
                           jnp.minimum(last, s // block_q - 1))


NO_MASK = MaskKind()
CAUSAL = _Causal()


def _as_kind(kind) -> MaskKind:
    """``causal``'s two values as the kinds they name."""
    if isinstance(kind, MaskKind):
        return kind
    return CAUSAL if kind else NO_MASK


@functools.lru_cache(maxsize=None)
def _forward_tiles(kind: MaskKind, s: int, block_q: int, block_k: int):
    """``(visited, square)``: the (q block, k block) pairs of one S x S
    square the forward's loops run over, and all there are: the kernel's
    own arithmetic (``key_segments``) asked with numbers."""
    nq, nk = s // block_q, s // block_k
    with jax.ensure_compile_time_eval():
        visited = sum(
            max(0, int(end) - int(first)) for qi in range(nq)
            for first, end, _ in kind.key_segments(
                s, jnp.int32(qi), block_q, block_k, nk))
    return visited, nq * nk


def tiles_visited(kind: MaskKind, s: int, block_q: int, block_k: int):
    """``(forward, backward, square)``: :func:`_forward_tiles` with, between
    them, the pairs the backward's grid steps work on (``tile`` asked
    with numbers, a step at a time: the tests' reading, not a trace's)."""
    forward, square = _forward_tiles(kind, s, block_q, block_k)
    with jax.ensure_compile_time_eval():
        backward = sum(bool(kind.tile(
            s, jnp.int32(qi), jnp.int32(ki), block_q, block_k)[1])
            for qi in range(s // block_q) for ki in range(s // block_k))
    return forward, backward, square


def _loop_key_blocks(step, init, qi, block_q, block_k, nk, kind):
    """Run ``step(j, carry, partial)`` over the k blocks q block ``qi``
    sees under ``kind`` (a mask kind, or ``causal``'s boolean): range by
    range, the full ones bare and the partial ones with the select;
    blocks in no range hold nothing visible and are skipped."""
    carry = init
    for first, end, partial in _as_kind(kind).key_segments(
            nk * block_k, qi, block_q, block_k, nk):
        carry = jax.lax.fori_loop(
            first, end, lambda j, c, p=partial: step(j, c, p), carry)
    return carry


def _key_block(k_ref, v_ref, m_ref, qi, j, block_q, block_k, partial,
               kind=True):
    """What a step of the forward's loop reads for k block j: the K
    and V tiles (bk, lanes), the key mask as a (bk, 1) column or None,
    and the mask kind's select (bk, bq), key-major, or None."""
    ks = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
    kmask = None if m_ref is None else m_ref[0, ks][:, None]
    keep = _as_kind(kind).keep(
        k_ref.shape[0], qi * block_q, j * block_k, (block_k, block_q),
        1) if partial else None
    return k_ref[ks, :], v_ref[ks, :], kmask, keep


def _fwd_kernel(*refs, block_k, kind, scale, has_mask):
    """o and lse of one q block of a head group. The scores of a block
    are built key-major (k·qᵀ), as the backward builds them: a query's
    running max, sum and rescale are (1, bq) lane rows, the max and the
    sum over the keys run down the sublanes, and the rows broadcast
    along them. A head's accumulator is held transposed, (D, bq) = its
    own rows of vᵀ times the probabilities, so packed heads do not pay
    for each other's lanes; the heads' accumulators are laid under one
    another and turned back to (bq, lanes) once a q block."""
    q_ref, k_ref, v_ref = refs[:3]
    m_ref = refs[3] if has_mask else None
    o_ref, lse_ref = refs[-2:]
    block_q, lanes = q_ref.shape
    heads = lse_ref.shape[0]
    width = lanes // heads
    nk = k_ref.shape[0] // block_k
    qi = pl.program_id(2)
    q, on_scores = _scaled(q_ref, scale)                    # (bq, lanes)

    def step(j, carry, partial):
        k, v, kmask, keep = _key_block(k_ref, v_ref, m_ref, qi, j, block_q,
                                       block_k, partial, kind)
        vt = v.T                                            # (lanes, bk)
        out = []
        for g, (m, l, acc) in enumerate(carry):
            st = _masked(_scores(_head(k, g, heads), q, on_scores), kmask,
                         keep)                              # (bk, bq)
            m_new = jnp.maximum(m, st.max(axis=0, keepdims=True))
            pt = jnp.exp(st - m_new)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + pt.sum(axis=0, keepdims=True)
            acc = acc * alpha + _dot(vt[g * width:(g + 1) * width],
                                     pt.astype(v.dtype), _NN)
            out.append((m_new, l, acc))
        return tuple(out)

    init = (jnp.full((1, block_q), _NEG, jnp.float32),
            jnp.zeros((1, block_q), jnp.float32),
            jnp.zeros((width, block_q), jnp.float32))
    carry = _loop_key_blocks(step, (init,) * heads, qi, block_q, block_k,
                             nk, kind)
    outs = []
    for g, (m, l, acc) in enumerate(carry):
        l = jnp.maximum(l, 1e-30)
        outs.append(acc / l)                                # (D, bq)
        lse_ref[g] = m + jnp.log(l)
    o_ref[...] = jnp.concatenate(outs, axis=0).astype(o_ref.dtype).T


def _pt_dst(qg, dog, k, v, lse, dd, on_scores, kmask, keep):
    """(pᵀ, dsᵀ) of one head over a (bk, bq) block, in fp32: the scores
    transposed (k·qᵀ), so that the rows ``lse`` and ``dd`` (1, bq)
    broadcast along sublanes and dv = pᵀ·dO, dk = dsᵀ·q are plain
    products. dd = delta - dlse, delta_i = rowsum(dO_i * o_i): lse =
    logsumexp(s) and dlse/ds = p, so an lse cotangent folds into ds as
    p * dlse. ``kmask`` (bk, 1) and ``keep`` (bk, bq) or None."""
    pt = jnp.exp(_masked(_scores(k, qg, on_scores), kmask, keep) - lse)
    return pt, pt * (_dot(v, dog, _NT) - dd)


def _bwd_kernel(*refs, kind, scale, has_mask):
    """dq, dk and dv of one (q block, k block) of a head group: the
    scores are built once (transposed, k·qᵀ) and feed all three."""
    q_ref, k_ref, v_ref = refs[:3]
    m_ref = refs[3] if has_mask else None
    (do_ref, lse_ref, dd_ref, dq_ref, dk_ref, dv_ref,
     dq_acc, dk_acc, dv_acc) = refs[-9:]
    block_q = q_ref.shape[0]
    block_k = k_ref.shape[0]
    heads = lse_ref.shape[0]
    s = dq_acc.shape[0]
    ki, step = pl.program_id(2), pl.program_id(3)
    # the step's q block: the step itself, but in a banded grid
    qi = kind.query_block(s, ki, step, block_q, block_k)
    # This q block's rows of dq, which VMEM holds for the whole sequence:
    # zeroed where the first k block meets them, added to at every visible
    # block (so in ascending k order, in fp32), written out after the last.
    rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)

    @pl.when(kind.first_key_block(s, qi, ki, block_q, block_k))
    def _():
        dq_acc[rows, :] = jnp.zeros((block_q, dq_acc.shape[1]), jnp.float32)

    @pl.when(step == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def block(on_diagonal):
        q, on_scores = _scaled(q_ref, scale)                # (bq, lanes)
        do = do_ref[...]
        k = k_ref[...]                                      # (bk, lanes)
        v = v_ref[...]
        kmask = m_ref[0, :][:, None] if has_mask else None  # (bk, 1)
        keep = kind.keep(s, qi * block_q, ki * block_k,
                         (block_k, block_q), 1) if on_diagonal else None
        for g in range(heads):
            qg, dog = _head(q, g, heads), _head(do, g, heads)
            pt, dst = _pt_dst(qg, dog, k, v, lse_ref[g], dd_ref[g],
                              on_scores, kmask, keep)
            dv_acc[...] += _dot(pt.astype(do.dtype), dog, _NN)
            dst = dst.astype(q.dtype)
            # dk = scale * dsᵀ·q: q carries the scale already, or the
            # accumulator takes it when it is written out
            dk_acc[...] += _dot(dst, qg, _NN)
            # dq's share, ds·k: the one transposed product, of the tile
            # already cast for the MXU (half the vregs through the XLU)
            dq_acc[rows, :] += _dot(dst.T, _head(k, g, heads), _NN)

    bare, visible = kind.tile(s, qi, ki, block_q, block_k)
    if bare is True:
        block(False)
    else:
        pl.when(bare)(lambda: block(False))
        pl.when(jnp.logical_and(visible, jnp.logical_not(bare)))(
            lambda: block(True))
    last_k = kind.last_key_block(s, qi, ki, block_q, block_k,
                                 pl.num_programs(2), visible)

    @pl.when(last_k)
    def _():
        dq_ref[rows, :] = (dq_acc[rows, :] * scale).astype(dq_ref.dtype)

    @pl.when(step == pl.num_programs(3) - 1)
    def _():
        dk = dk_acc[...]
        if not _scale_folds_into_q(scale):
            dk = dk * scale
        dk_ref[...] = dk.astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


# -- pallas_call plumbing ---------------------------------------------------

class _Layout:
    """How (B, S, H, D) operands reach the kernels as (rows, lanes) tiles.

    Packed, where the widths allow (D a multiple of 128; or D dividing
    128 with H a multiple of G = 128 / D): the operands stay where the
    caller has them, seen as (B, S, H·D), and a block is ``rows`` × 128
    lanes = G whole heads — nothing is transposed or padded on the way
    in or out, and HBM holds no lane padding. Otherwise per head: the
    operands are transposed to (B, H, S, D) (XLA's copies) and a block is
    ``rows`` × D of one head, D being the array's own minor size."""

    def __init__(self, h, d):
        if d % _LANE == 0:
            self.heads, self.packed = 1, True
        elif _LANE % d == 0 and h % (_LANE // d) == 0:
            self.heads, self.packed = _LANE // d, True
        else:
            self.heads, self.packed = 1, False
        self.h, self.d = h, d
        self.lanes = self.heads * d
        self.groups = h // self.heads      # grid size over the heads

    def to_kernel(self, x):
        b, s = x.shape[:2]
        return x.reshape(b, s, x.shape[2] * self.d) if self.packed \
            else jnp.swapaxes(x, 1, 2)

    def from_kernel(self, x):
        if self.packed:
            return x.reshape(x.shape[:2] + (self.h, self.d))
        return jnp.swapaxes(x, 1, 2)

    def rowsum(self, x, y):
        """sum over D of x * y, in fp32, as (B, H, S)."""
        prod = x.astype(jnp.float32) * y.astype(jnp.float32)
        if self.packed:
            prod = prod.reshape(prod.shape[:2] + (self.h, self.d))
            return jnp.swapaxes(prod.sum(axis=-1), 1, 2)
        return prod.sum(axis=-1)

    def tile(self, rows, index):
        """BlockSpec of a (rows, lanes) tile; ``index(*grid ids)`` gives
        (batch, head group, row block)."""
        if self.packed:
            def at(*ids):
                b, g, i = index(*ids)
                return b, i, g
            return pl.BlockSpec((None, rows, self.lanes), at)

        def at(*ids):
            b, g, i = index(*ids)
            return b, g, i, 0
        return pl.BlockSpec((None, None, rows, self.lanes), at)

    def row(self, rows, index):
        """BlockSpec of a (G, 1, rows) slice of a (B, H, 1, S) row
        vector."""
        def at(*ids):
            b, g, i = index(*ids)
            return b, g, 0, i
        return pl.BlockSpec((None, self.heads, 1, rows), at)


def _kv_head(group):
    """The K/V head (group) a q head (group) reads. The grids run the
    heads in order, the block dimensions inside them, so consecutive grid
    steps stay in a group until its last head is done and the K/V block
    index changes once a group: the forward, which holds K and V whole a
    (batch, K/V head), fetches them once a GROUP and not once a head
    (Pallas skips the copy of a block whose index did not change). Read
    on the v5e at S 8192 under a window of 512, 72 query heads: on 8 K/V
    heads (groups of 9) the forward took 4.38 ms, on 72 (every head its
    own K/V) 5.53: the 64 fetches of 4 MiB the grouping saves (PERF.md
    section 6, PR 42)."""
    return (lambda g: g) if group == 1 else (lambda g: g // group)


def _q_major_specs(layout, s, bq, group=1):
    """Block specs of the forward call, grid (B, H/G, S/bq): a q-side
    tile, K / V whole, the (B, 1, S) key mask whole, and a row slice."""
    kv = _kv_head(group)
    q_spec = layout.tile(bq, lambda b, g, i: (b, g, i))
    kv_spec = layout.tile(s, lambda b, g, i: (b, kv(g), 0))
    m_spec = pl.BlockSpec((None, 1, s), lambda b, g, i: (b, 0, 0))
    row_spec = layout.row(bq, lambda b, g, i: (b, g, i))
    return q_spec, kv_spec, m_spec, row_spec


def _k_major_specs(layout, s, bq, bk, kind, group=1):
    """Block specs of the backward call, grid (B, H/G, S/bk, S/bq), q
    blocks innermost: a q-side tile, a k-side tile, a block of the key
    mask, a row slice, and dq's tile. ``kind`` is the mask kind (or
    ``causal``'s boolean): the q blocks of a k block's empty pairs are
    skipped; their index is clamped to a visited one's
    (``MaskKind.first_query_block``) so that a skipped step fetches
    nothing new. dq's tile is
    the whole sequence of a (batch, head group): it stays in VMEM over
    both block dimensions and goes to HBM once. With ``group`` > 1 the
    k-side tile is the group's one K/V head's."""
    kv = _kv_head(group)
    kind = _as_kind(kind)

    def qi(j, i):
        return kind.first_query_block(s, j, i, bq, bk)

    q_spec = layout.tile(bq, lambda b, g, j, i: (b, g, qi(j, i)))
    kv_spec = layout.tile(bk, lambda b, g, j, i: (b, kv(g), j))
    m_spec = pl.BlockSpec((None, 1, bk), lambda b, g, j, i: (b, 0, j))
    row_spec = layout.row(bq, lambda b, g, j, i: (b, g, qi(j, i)))
    dq_spec = layout.tile(s, lambda b, g, j, i: (b, g, 0))
    return q_spec, kv_spec, m_spec, row_spec, dq_spec


def _compiler_params(s, d, itemsize, bq, bk, backward=False):
    """Batch and head are independent, and so is the forward's q block
    dimension; the backward call's two block dimensions accumulate (dk
    and dv over the q blocks, dq over the k blocks). The scoped VMEM
    limit follows the plan instead of shrinking the blocks."""
    plan = _vmem_estimate(s, d, itemsize, bq, bk)
    blocks = ("arbitrary", "arbitrary") if backward else ("parallel",)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel") + blocks,
        vmem_limit_bytes=min(max(2 * plan, _VMEM_FLOOR), _VMEM_CEIL))


def _forward(q, k, v, mask, kind, bq, bk, interpret):
    """(o, lse, residuals): o (B, S, H, D) in q's dtype, lse (B, H, S)
    fp32. The residuals are kept in the kernels' layout so the backward
    moves nothing twice. ``kind``: the mask kind (or ``causal``'s
    boolean)."""
    b, s, h, d = q.shape
    kind = _as_kind(kind)
    layout = _Layout(h, d)
    group = h // k.shape[2]
    has_mask = mask is not None
    q_spec, kv_spec, m_spec, row_spec = _q_major_specs(layout, s, bq, group)
    qt, kt, vt = (layout.to_kernel(x) for x in (q, k, v))
    mask3 = mask.astype(jnp.float32)[:, None, :] if has_mask else None
    ot, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, block_k=bk, kind=kind,
                          scale=1.0 / np.sqrt(d), has_mask=has_mask),
        grid=(b, layout.groups, s // bq),
        in_specs=[q_spec, kv_spec, kv_spec] + [m_spec] * has_mask,
        out_specs=[q_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct(qt.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32)],
        compiler_params=_compiler_params(s, d, q.dtype.itemsize, bq, bk),
        interpret=interpret,
        name=kind.kernel_names[0],
    )(qt, kt, vt, *([mask3] * has_mask))
    return (layout.from_kernel(ot), lse[:, :, 0, :],
            (qt, kt, vt, mask3, ot, lse))


def _backward(kind, bq, bk, interpret, res, do, dlse):
    """(dq, dk, dv, None) on (B, S, H, D). ``dlse`` None: the caller has
    no lse output (flash_attention), so no cotangent of it exists."""
    qt, kt, vt, mask3, ot, lse = res
    b, h, _, s = lse.shape
    kind = _as_kind(kind)
    # Packed operands are (B, S, H·D), per-head ones (B, H, S, D).
    d = qt.shape[-1] // h if qt.ndim == 3 else qt.shape[-1]
    layout = _Layout(h, d)
    group = qt.shape[-1] // kt.shape[-1] if qt.ndim == 3 \
        else h // kt.shape[1]
    has_mask = mask3 is not None
    dot = layout.to_kernel(do)
    # delta_i = rowsum(dO_i * o_i) — one fused elementwise pass in-graph;
    # with the lse cotangent folded in it is the one row operand the
    # kernel reads beside lse.
    dd = layout.rowsum(dot, ot)
    if dlse is not None:
        dd = dd - dlse.astype(jnp.float32)
    dd = dd[:, :, None, :]
    masks = [mask3] * has_mask
    q_spec, kv_spec, m_spec, row_spec, dq_spec = _k_major_specs(
        layout, s, bq, bk, kind, group)
    # dk and dv leave a query head at a time: the k-side tile of a call
    # with no grouping, whatever this one's is
    dkv_spec = _k_major_specs(layout, s, bq, bk, kind)[1]
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, kind=kind,
                          scale=1.0 / np.sqrt(d), has_mask=has_mask),
        grid=(b, layout.groups, s // bk, kind.query_steps(s, bq, bk)),
        in_specs=[q_spec, kv_spec, kv_spec] + [m_spec] * has_mask
        + [q_spec, row_spec, row_spec],
        out_specs=[dq_spec, dkv_spec, dkv_spec],
        out_shape=[jax.ShapeDtypeStruct(qt.shape, x.dtype)
                   for x in (qt, kt, vt)],
        scratch_shapes=[pltpu.VMEM((s, layout.lanes), jnp.float32)]
        + [pltpu.VMEM((bk, layout.lanes), jnp.float32)] * 2,
        compiler_params=_compiler_params(s, d, qt.dtype.itemsize, bq, bk,
                                         backward=True),
        interpret=interpret,
        name=kind.kernel_names[1],
    )(qt, kt, vt, *masks, dot, lse, dd)
    # Where a model joins the three straight back into the gradient of a
    # fused qkv projection (BERT's), XLA:TPU sees one call's outputs
    # concatenated and builds the join as three update-slice copies into
    # a zero buffer, 70 us a layer at (8, 512, 3 x 1024), in place of
    # fusing it into its consumers as it does for operands of separate
    # origin. The barrier costs nothing (a Mosaic call fuses with nothing
    # anyway) and gives dq a separate origin.
    dq = jax.lax.optimization_barrier(dq)
    dq, dk, dv = (layout.from_kernel(x) for x in (dq, dk, dv))
    if group > 1:       # a K/V head's gradient: its q heads' shares, summed
        dk, dv = (x.reshape(b, s, h // group, group, d)
                  .astype(jnp.float32).sum(3).astype(x.dtype)
                  for x in (dk, dv))
    return dq, dk, dv, None


# Two interfaces over the same kernels: the need differs by caller (is
# the logsumexp an output?), not by a knob. ``kind`` is the mask kind.

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash(q, k, v, mask, kind, bq, bk, interpret):
    """o alone: the backward has no lse cotangent to carry."""
    return _forward(q, k, v, mask, kind, bq, bk, interpret)[0]


def _flash_fwd(q, k, v, mask, kind, bq, bk, interpret):
    o, _, res = _forward(q, k, v, mask, kind, bq, bk, interpret)
    return o, res


def _flash_bwd(kind, bq, bk, interpret, res, do):
    return _backward(kind, bq, bk, interpret, res, do, None)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_lse(q, k, v, mask, kind, bq, bk, interpret):
    """(o, lse). lse (B, H, S) is a first-class differentiable output so
    blockwise callers (ring attention) can combine partial results; its
    cotangent folds into the backward kernels' ds."""
    return _forward(q, k, v, mask, kind, bq, bk, interpret)[:2]


def _flash_lse_fwd(q, k, v, mask, kind, bq, bk, interpret):
    o, lse, res = _forward(q, k, v, mask, kind, bq, bk, interpret)
    return (o, lse), res


def _flash_lse_bwd(kind, bq, bk, interpret, res, cotangents):
    return _backward(kind, bq, bk, interpret, res, *cotangents)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


# -- public surface ---------------------------------------------------------

def flash_available(seq_len: int, use_pallas: Optional[bool] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    mask_kind: Optional[MaskKind] = None) -> bool:
    """THE availability predicate — single source of truth for every
    reason the kernel path can decline (off-TPU without forcing,
    HVD_TPU_FLASH_ATTENTION=0 escape hatch, un-tileable sequence).
    flash_attention_with_lse consults exactly this, so callers (ring
    attention) pre-checking it can rely on a non-None result."""
    use, interpret = _decide(use_pallas)
    if runtime_env("FLASH_ATTENTION", "1") == "0":
        return False
    # Whether SOME block tiles seq_len does not depend on D or the dtype
    # (they only shrink the choice, never below one lane tile).
    span = None if mask_kind is None else mask_kind.span(seq_len)
    return bool(use) and _resolve_blocks(
        seq_len, _LANE, jnp.float32, block_q, block_k,
        interpret, span) is not None


@functools.lru_cache(maxsize=None)  # once per shape, not per trace
def _warn_untileable(shape, block_q, block_k):
    logger.warning(
        "flash_attention: sequence length %d of q%s has no block the "
        "kernels can tile (a multiple of 128, or the whole of a short "
        "sequence a multiple of 8; block_q=%s, block_k=%s); this call "
        "runs the O(S^2) reference attention on the TPU instead of the "
        "Pallas kernel", shape[1], tuple(shape), block_q, block_k)


@functools.lru_cache(maxsize=None)  # once per distinct call shape
def _say_path(shape, dtype, bq, bk, has_mask, kind, dlse, tiles):
    logger.info(
        "flash_attention: q%s %s runs the Pallas kernels with "
        "block_q=%d block_k=%d has_mask=%s causal=%s dlse_operand=%s "
        "mask_kind=%s (%d of %d tiles visited)",
        tuple(shape), dtype, bq, bk, has_mask, kind == CAUSAL, dlse,
        kind.name, *tiles)


def _kv_heads_for_kernels(q, k, v):
    """K and V as the kernels take them: as they are where a block is one
    head (the index maps do the grouping), repeated to q's head count
    where a 128-lane block packs several narrow heads. Outside the
    ``custom_vjp``, so JAX sums the repeated heads' gradients."""
    h, d = q.shape[2:]
    group = h // k.shape[2]
    if group > 1 and _Layout(h, d).heads > 1:
        return _repeat_heads(k, group), _repeat_heads(v, group)
    return k, v


def _engage(q, mask, kind, use_pallas, block_q, block_k, dlse):
    """(kind, bq, bk, interpret) of the kernel path for this call, or
    None where flash_available declines; says which path engaged."""
    _, s, _, d = q.shape
    use, interpret = _decide(use_pallas)
    if not flash_available(s, use_pallas, block_q, block_k, kind):
        if use and not interpret \
                and runtime_env("FLASH_ATTENTION", "1") != "0":
            _warn_untileable(q.shape, block_q, block_k)
        return None
    bq, bk = _resolve_blocks(s, d, q.dtype, block_q, block_k, interpret,
                             kind.span(s))
    has_mask = mask is not None
    forward, square = _forward_tiles(kind, s, bq, bk)
    _say_path(q.shape, q.dtype.name, bq, bk, has_mask, kind, dlse,
              (forward, square))
    _M_PATHS.labels(seq_len=str(s), head_dim=str(d), dtype=q.dtype.name,
                    block_q=str(bq), block_k=str(bk),
                    has_mask=str(has_mask).lower(),
                    causal=str(kind == CAUSAL).lower(),
                    dlse=str(dlse).lower(), mask_kind=kind.name).inc()
    for tiles, n in (("visited", forward), ("square", square)):
        _M_TILES.labels(mask_kind=kind.name, seq_len=str(s),
                        block_q=str(bq), block_k=str(bk),
                        tiles=tiles).set(n)
    return kind, bq, bk, interpret


def flash_attention_with_lse(q, k, v, mask=None, causal: bool = False,
                             use_pallas: Optional[bool] = None,
                             block_q: Optional[int] = None,
                             block_k: Optional[int] = None,
                             mask_kind: Optional[MaskKind] = None):
    """Like :func:`flash_attention` but also returns the per-row
    logsumexp (B, H, S) — the blockwise-combination interface ring
    attention stitches partial results with. Both outputs are
    differentiable (the lse cotangent folds into the backward kernels).
    Returns None when :func:`flash_available` declines, so callers use
    their own reference path."""
    path = _engage(q, mask, _as_kind(mask_kind or causal), use_pallas,
                   block_q, block_k, True)
    if path is None:
        return None
    return _flash_lse(q, *_kv_heads_for_kernels(q, k, v), mask, *path)


def flash_attention(q, k, v, mask=None, causal: bool = False,
                    use_pallas: Optional[bool] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    mask_kind: Optional[MaskKind] = None):
    """Blockwise online-softmax attention on (B, S, H, D), returned in
    q's dtype. k and v may hold fewer heads, (B, S, Hkv, D) with H a
    multiple of Hkv (grouped-query attention).

    ``mask``: optional (B, S) key mask (1 = attend). ``mask_kind``: which
    (query, key) pairs exist, a static ``MaskKind``: ``NO_MASK`` and
    ``CAUSAL`` are what ``causal=False`` / ``True`` say (and what None
    leaves to ``causal``), ``BlockDiffusionMask(block)`` the mask of
    block-diffusion training over ``[noisy ; clean]``; tiles the kind
    leaves empty are not visited. ``use_pallas=None``
    auto-selects the Pallas kernel on TPU with a jnp fallback elsewhere;
    ``True`` forces the kernel (interpret mode off-TPU — the test path).
    ``block_q`` / ``block_k``: None lets the code choose from the shape;
    a number caps the block. Differentiable via the flash backward
    kernels."""
    kind = _as_kind(mask_kind or causal)
    path = _engage(q, mask, kind, use_pallas, block_q, block_k, False)
    if path is None:
        return reference_attention(q, k, v, mask, mask_kind=kind)
    return _flash(q, *_kv_heads_for_kernels(q, k, v), mask, *path)


def attend(q, k, v, mask=None):
    """Drop-in ``attend_fn`` for the models (SelfAttention): flash on
    TPU, reference jnp elsewhere."""
    return flash_attention(q, k, v, mask=mask)
