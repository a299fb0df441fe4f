"""The selective state-space recurrence with a scalar decay a head
(Mamba-2's "state-space dual", SSD), a head of P channels over a state of
N, B and C shared by the heads of a group:

    delta_t = softplus(dt_t + dt_bias)            a_t = exp(delta_t A),  A < 0
    H_t = a_t H_{t-1} + delta_t x_t B_t^T         H: (P, N), H_0 = 0
    y_t = H_t C_t + D x_t

``ssd_reference`` runs it token by token in fp32: the numerics oracle.
``ssd_scan`` is the chunked form, chunks of Q tokens, g the running sum
of ``delta A`` inside a chunk (every exponent below is <= 0 as written:
the difference is built, masked, then exponentiated, so a decay of any
strength neither overflows nor divides by a vanished factor):

    Y_diag = ((C B^T) * L) (delta x)         L[t, s] = exp(g_t - g_s), s <= t
    state_c = B^T (exp(g_Q - g) delta x)     a chunk's own contribution
    start_c = sum_{j < c} exp(sum_{j < i < c} g_Q,i) state_j
    Y_off  = exp(g) (C start_c)

Four matmul families (C B^T, the pair matrix times delta x, a chunk's
state, C times the state at the chunk's start) with operands in x's dtype
and fp32 accumulation; delta, the decays, the running sums and the states
in fp32. The carry across the S / Q chunks is one small lower-triangular
decay matrix a head times the chunks' states (fp32, full precision), so
there is no ``lax.scan``: JAX's own backward, and no ``while`` in the
program. The sums between chunks are built term by term (a masked
cumulative sum, not a difference of running totals: the totals grow with
the sequence and their difference would lose the small exponents that
matter); inside a chunk g is bounded by the chunk and the difference is
exact enough.

Everything carries the scope ``hvd_ssd`` (``common/scopes.py``). One
algorithm, two compilers; which runs is a function of the platform and of
the shapes ``ssd_scan`` sees in its operands, decided as the call is
traced (``ops/pallas_kernels._decide``, ``_kernels_take``), with no
option, nothing read from the environment and no way from one to the
other at run time; ``hvd_tpu_ssd_calls_total{path}`` counts the pick:

- **On a TPU, where the kernels take the shapes** (one group of B and C,
  heads of 64 channels in whole blocks of 8, a state that is a multiple of
  the 128 lanes, chunks of 256: the state-space cell's): two Pallas
  kernels, ``hvd_ssd_fwd`` and ``hvd_ssd_bwd`` (the scope as their prefix:
  how the readers of a trace find them), under one ``custom_vjp``. The
  pair matrices, the decays, the running sums and the states live in VMEM;
  x, dt, B, C, y, their gradients and the chunk-start states the backward
  reads (fp32, (B, S / Q, N, H P), alive inside a layer's rematerialised
  backward) are all that crosses HBM. The state crosses chunks as the
  recurrence itself, ``H <- exp(g_Q) H + B^T (exp(g_Q - g) delta x)``,
  every factor <= 1, so ``_sums_between`` has no counterpart there.
  Gradients by hand, held to ``ssd_reference``'s by the tests.
- **Elsewhere** (a CPU, the tests, the tiny preset, several groups, other
  widths): the XLA code above, unchanged; its pair matrices (B, S / Q, H,
  Q, Q) are its traffic, 0.5 GiB in fp32 at S 8192, 64 heads, Q 256.
  ``use_pallas=True`` runs the kernels' bodies in interpret mode there
  (the tests' twin check).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .linear_attention import (_NT, _TN, _cumsum_matrix, _dot, _iota,
                               _parts)
from .pallas_kernels import _decide
from ..common import metrics as metrics_lib
from ..common import scopes

CHUNK = 256
_LANE = 128
# What the kernels were compiled and run for on the chip (PERF.md): a chunk
# of 256 tokens, heads of 64 channels (two to a tile of 128 lanes), eight
# heads a grid step, a chunk's pair matrices in tiles of 128 x 128.
_KERNEL_CHUNK = 256
_KERNEL_WIDTH = 64
_HEADS_A_STEP = 8
_PAIR_TILE = 128
# A padded token's dt: a step size of exactly 0 after the softplus.
_NO_STEP = -1e30

_M_CALLS = metrics_lib.counter(
    "hvd_tpu_ssd_calls_total",
    "ssd_scan calls traced, by the path picked for them: the Pallas "
    "kernels (a TPU, one group of B and C, heads of 64 channels in "
    "blocks of 8, a state that is a multiple of 128, a chunk of 256) or "
    "the chunked XLA code",
    labels=("path",))


def _step_sizes(dt, dt_bias):
    dt = dt.astype(jnp.float32)
    if dt_bias is not None:
        dt = dt + dt_bias.astype(jnp.float32)
    return jax.nn.softplus(dt)


def ssd_reference(x, dt, a, b, c, d, dt_bias=None):
    """The recurrence token by token, fp32. x: (B, S, H, P); dt: (B, S,
    H), before the softplus; a, d, dt_bias: (H,); b, c: (B, S, G, N), H a
    multiple of G (head h reads group h // (H / G)). Returns y like x,
    fp32."""
    x, a, b, c, d = (v.astype(jnp.float32) for v in (x, a, b, c, d))
    batch, _, heads, width = x.shape
    per_group = heads // b.shape[2]
    b, c = (jnp.repeat(v, per_group, 2) for v in (b, c))    # (B, S, H, N)
    delta = _step_sizes(dt, dt_bias)

    def step(state, xs):
        xt, dt_t, bt, ct = xs               # (B, H, P), (B, H), (B, H, N)
        state = state * jnp.exp(dt_t * a)[..., None, None] \
            + (dt_t[..., None] * xt)[..., None] * bt[..., None, :]
        return state, (state * ct[..., None, :]).sum(-1)

    xs = tuple(jnp.moveaxis(v, 1, 0) for v in (x, delta, b, c))
    state = jnp.zeros((batch, heads, width, b.shape[-1]), jnp.float32)
    y = jnp.moveaxis(jax.lax.scan(step, state, xs)[1], 0, 1)
    return y + d[:, None] * x


def _sums_between(totals):
    """``out[..., z, j] = sum_{j < i <= z} totals[..., i]`` for j <= z and
    -inf above the diagonal, each sum built from its own terms."""
    n = totals.shape[-1]
    rows = jnp.arange(n)[:, None]
    cols = jnp.arange(n)[None, :]
    terms = jnp.where(rows > cols, totals[..., :, None], 0.0)
    return jnp.where(rows >= cols, jnp.cumsum(terms, -2), -jnp.inf)


def ssd_scan(x, dt, a, b, c, d, dt_bias=None, chunk: int = CHUNK,
             use_pallas: Optional[bool] = None):
    """The chunked form of ``ssd_reference``, same operands; returns y in
    x's dtype. A sequence that ``chunk`` does not divide is padded with
    tokens of step size 0, which neither decay nor write the state.

    ``use_pallas=None`` runs the Pallas kernels on a TPU where they take
    the shapes (``_kernels_take``) and the chunked XLA code elsewhere;
    ``True`` forces the kernels where they take the shapes (interpret
    mode off-TPU: the test path), ``False`` the XLA code. The path is a
    function of the platform and the shapes, picked as the call is
    traced."""
    use, interpret = _decide(use_pallas)
    use = use and _kernels_take(x, b, chunk)
    _M_CALLS.labels(path="pallas" if use else "xla").inc()
    with jax.named_scope(scopes.SSD):
        if use:
            return _scan_with_kernels(x, dt, a, b, c, d, dt_bias, chunk,
                                      interpret)
        return _ssd_scan(x, dt, a, b, c, d, dt_bias, chunk)


def _ssd_scan(x, dt, a, b, c, d, dt_bias, chunk):
    dtype = x.dtype
    batch, length, heads, width = x.shape
    groups, n = b.shape[2:]
    per_group = heads // groups
    q = min(chunk, length)
    chunks = -(-length // q)
    pad = chunks * q - length

    def in_chunks(v):
        v = jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
        return v.reshape(batch, chunks, q, *v.shape[2:])

    def mm(spec, lhs, rhs):
        return jnp.einsum(spec, lhs.astype(dtype), rhs.astype(dtype),
                          preferred_element_type=jnp.float32)

    delta = in_chunks(_step_sizes(dt, dt_bias))             # (B, Z, Q, H)
    x = in_chunks(x).reshape(batch, chunks, q, groups, per_group, width)
    b, c = in_chunks(b), in_chunks(c)                       # (B, Z, Q, G, N)
    g = jnp.cumsum(delta * a.astype(jnp.float32), 2)        # <= 0, falling
    g = g.reshape(batch, chunks, q, groups, per_group)
    x = x.astype(jnp.float32)
    xd = x * delta.reshape(g.shape)[..., None]

    # inside a chunk: the pair matrices
    gt = jnp.moveaxis(g, 2, -1)                             # (B, Z, G, R, Q)
    low = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]
    decay = jnp.exp(jnp.where(low, gt[..., :, None] - gt[..., None, :],
                              -jnp.inf))                    # L
    cb = mm("bzqgn,bzkgn->bzgqk", c, b)
    y = mm("bzgrqk,bzkgrp->bzqgrp", cb[:, :, :, None] * decay, xd)

    # a chunk's own state, the carry across chunks, what it adds
    to_end = jnp.exp(g[:, :, -1:] - g)                      # (B, Z, Q, G, R)
    states = mm("bzkgn,bzkgrp->bzgrpn", b, xd * to_end[..., None])
    totals = jnp.moveaxis(g[:, :, -1], 1, -1)               # (B, G, R, Z)
    # start of chunk z: the states of the chunks j < z, decayed by the
    # whole chunks strictly between
    carry = jnp.exp(_sums_between(totals))[..., :-1, :]     # rows z - 1
    carry = jnp.pad(carry, ((0, 0),) * 3 + ((1, 0), (0, 0)))
    starts = jnp.einsum("bgrzj,bjgrpn->bzgrpn", carry, states,
                        precision=jax.lax.Precision.HIGHEST)
    y = y + mm("bzqgn,bzgrpn->bzqgrp", c, starts) * jnp.exp(g)[..., None]

    y = y + d.astype(jnp.float32).reshape(groups, per_group, 1) * x
    y = y.reshape(batch, chunks * q, heads, width)[:, :length]
    return y.astype(dtype)


# -- the Pallas kernels ------------------------------------------------------
#
# The same chunked algorithm, a chunk of a block of heads a grid step, grid
# (batch, chunks, blocks of heads): the chunks in sequence, the blocks of a
# chunk one after another inside it, so that what the heads share (B, C,
# ``C B^T`` and, in the backward, the sums of dB and dC over the heads) is
# read, built and written once a chunk. Values are (Q, heads * P) tiles,
# tokens on sublanes and the block's channels on lanes, as x lies in HBM;
# the states are (N, heads * P), every head's side by side, so that the two
# products with a state are full-width matmuls. A scalar a head a token
# (dt, the step size, a decay) is a row a head with the tokens along the
# lanes, (heads, Q): two vector registers where the other way takes 32;
# it is spread down its head's lanes by a matmul with a matrix of ones, and
# summed back the same way. The state crosses chunks as the recurrence
# does, ``H <- exp(g_Q) H + B^T (exp(g_Q - g) delta x)``, in fp32 VMEM
# scratch: every factor <= 1.

def _three(ones, axis):
    return jnp.concatenate([ones.astype(jnp.bfloat16)] * 3, axis)


def _over_lanes(v, ones):
    """``v^T @ ones`` in fp32, bit for bit, for v (heads, Q) fp32 and a
    matrix of ones and zeros (heads, n) with one 1 a column: a head's row
    of v down each of its lanes, (Q, n). v's three bf16 parts (8 + 8 + 8
    bits of mantissa) against the matrix three times over: one bf16 pass
    where an fp32 matmul at full precision runs six."""
    return _dot(jnp.concatenate(_parts(v), 0), _three(ones, 0), _TN)


def _by_head(v, ones):
    """``ones @ v^T`` in fp32 for v (rows, n) fp32 and ones (heads, n):
    the sums over each head's lanes, a row a head, (heads, rows); exact
    products, fp32 sums."""
    return _dot(_three(ones, 1), jnp.concatenate(_parts(v), 1), _NT)


def _running_sum(v, reverse=False):
    """The running sum of v (heads, Q) along the tokens (``reverse``: from
    the last), fp32: a triangular matrix of ones a tile of ``_PAIR_TILE``
    tokens (exact products, fp32 sums), and each tile takes the total of
    the tiles before it."""
    tiles = [v[:, i:i + _PAIR_TILE] for i in range(0, v.shape[1], _PAIR_TILE)]
    ones = _three(_cumsum_matrix(_PAIR_TILE, not reverse), 0)
    sums, before = [], 0.0
    for tile in (reversed(tiles) if reverse else tiles):
        tile = _dot(jnp.concatenate(_parts(tile), 1), ones) + before
        before = tile[:, :1] if reverse else tile[:, -1:]
        sums.append(tile)
    return jnp.concatenate(sums[::-1] if reverse else sums, 1)


class _Decays:
    """What a chunk of a block of heads knows of its step sizes and
    decays, fp32, a row a head and the tokens along the lanes (heads, Q):
    the block's rows of dt (H, Q) and of the columns dt_bias, A and D (H,
    3) give ``pre`` (dt + dt_bias), ``delta`` and g; g once more with the
    tokens along the sublanes (Q, heads) for the pair matrices' rows; and
    the factors the (Q, heads * P) tiles meet, spread over each head's P
    lanes: delta, exp(g), exp(g_Q - g), (1, lanes) exp(g_Q) and D."""

    def __init__(self, dt_ref, heads_ref, block):
        heads, width = _HEADS_A_STEP, _KERNEL_WIDTH
        q = dt_ref.shape[1]
        # the block's rows among all heads'
        self.mine = mine = pl.ds(pl.multiple_of(block * heads, heads), heads)
        bias, self.a, skip = (heads_ref[mine, i:i + 1] for i in range(3))
        self.lanes_of = _iota((heads, heads * width), 1) // width \
            == _iota((heads, heads * width), 0)
        self.pre = dt_ref[mine, :] + bias
        self.delta = jax.nn.softplus(self.pre)
        self.g = _running_sum(self.delta * self.a)
        # bit for bit: the pair matrices' rows read the very g their
        # columns read, and the diagonal is exp(0)
        self.g_columns = _over_lanes(
            self.g, _iota((heads, heads), 0) == _iota((heads, heads), 1))
        self.delta_l = self.over_lanes(self.delta)
        self.from_start_l = self.over_lanes(jnp.exp(self.g))
        self.to_end_l = self.over_lanes(jnp.exp(self.g[:, q - 1:q] - self.g))
        self.gamma_l = self.from_start_l[q - 1:q]               # (1, lanes)
        self.skip_l = self.over_lanes(
            jnp.broadcast_to(skip, (heads, 8)))[:1]

    def over_lanes(self, v):
        """(heads, rows) -> (rows, heads * P), a head's value on each of
        its lanes."""
        return _over_lanes(v, self.lanes_of)

    def by_head(self, v):
        """(rows, heads * P) -> (heads, rows): the sums over each head's
        lanes. (One row is given as eight: Mosaic takes no matmul with a
        vector.)"""
        if v.shape[0] == 1:
            return _by_head(jnp.broadcast_to(v, (8, v.shape[1])),
                            self.lanes_of)[:, :1]
        return _by_head(v, self.lanes_of)

    def pair_tiles(self, h):
        """L of head h, exp(g_t - g_s) for s <= t, fp32, a tile of
        ``_PAIR_TILE`` rows and columns at a time, on and under the
        diagonal (nothing above it is built or multiplied): (row tile,
        column tile, rows, columns, L's tile). The exponent is clamped at
        0, so above the diagonal inside a diagonal tile, where ``C B^T``
        is masked, it reads 1 and nothing overflows."""
        for r in range(self.g.shape[1] // _PAIR_TILE):
            rows = slice(r * _PAIR_TILE, (r + 1) * _PAIR_TILE)
            for c in range(r + 1):
                cols = slice(c * _PAIR_TILE, (c + 1) * _PAIR_TILE)
                yield r, c, rows, cols, jnp.exp(jnp.minimum(
                    self.g_columns[rows, h:h + 1] - self.g[h:h + 1, cols],
                    0.0))

    def heads_of_tile(self, t):
        """The heads whose channels lie in tile t of 128 lanes, each with
        the mask of its lanes."""
        a_tile = _LANE // _KERNEL_WIDTH
        lane = _iota((self.g.shape[1], _LANE), 1) // _KERNEL_WIDTH
        return [(t * a_tile + i, lane == i) for i in range(a_tile)]


def _only(mask, tile):
    return jnp.where(mask, tile, 0)


def _tiles(v):
    return [v[:, i:i + _LANE] for i in range(0, v.shape[1], _LANE)]


def _masked_cb(c_ref, b_ref):
    """``C B^T`` (Q, Q) fp32, 0 above the diagonal."""
    q = c_ref.shape[0]
    return jnp.where(_cumsum_matrix(q), _dot(c_ref[...], b_ref[...], _NT), 0.0)


def _fwd_kernel(x_ref, dt_ref, b_ref, c_ref, heads_ref, y_ref, *rest):
    """One chunk of one block of heads: applies and advances the block's
    state (N, heads * P) held in ``state`` and, where the backward will
    need it, writes the state the chunk starts from."""
    state, cb = rest[-2:]
    states_ref = rest[0] if len(rest) == 3 else None
    z, j = pl.program_id(1), pl.program_id(2)
    dtype = x_ref.dtype

    @pl.when(z == 0)
    def _():
        state[j] = jnp.zeros(state.shape[1:], jnp.float32)

    @pl.when(j == 0)
    def _():
        cb[...] = _masked_cb(c_ref, b_ref)

    dec = _Decays(dt_ref, heads_ref, j)
    s0 = state[j]
    if states_ref is not None:
        states_ref[...] = s0
    xf = x_ref[...].astype(jnp.float32)
    xd = xf * dec.delta_l
    inside = []
    for t, tile in enumerate(_tiles(xd.astype(dtype))):
        y_t = [0.0] * (x_ref.shape[0] // _PAIR_TILE)
        for h, mask in dec.heads_of_tile(t):
            x_h = _only(mask, tile)
            for r, _, rows, cols, decay in dec.pair_tiles(h):
                y_t[r] += _dot((cb[rows, cols] * decay).astype(dtype),
                               x_h[cols])
        inside.append(jnp.concatenate(y_t, 0))
    y = jnp.concatenate(inside, 1) + dec.skip_l * xf \
        + dec.from_start_l * _dot(c_ref[...], s0.astype(dtype))
    y_ref[...] = y.astype(y_ref.dtype)
    state[j] = dec.gamma_l * s0 + _dot(
        b_ref[...], (xd * dec.to_end_l).astype(dtype), _TN)


def _bwd_kernel(x_ref, dt_ref, b_ref, c_ref, heads_ref, states_ref, dy_ref,
                dx_ref, ddt_ref, db_ref, dc_ref, sums_ref, dstate, cb, dcb):
    """The same chunk in the reverse pass: rebuilds g, L and the products
    from the operands and the kept chunk-start state, carries dH in
    ``dstate`` and writes dx, d dt, the block's rows of the sums for dA,
    dD and d dt_bias, and (summed over the blocks of the chunk, in the
    output's own tile) dB and dC.

    The decays are not differentiated apart: g_t multiplies everything
    token t reads (its row of y) and divides everything it writes (its
    row of delta x, into later tokens and into the state the chunk
    leaves), so dg_t = <dy_t, y_t - D x_t> - <d(delta x)_t, delta x_t>,
    and the chunk's last g also scales the state it leaves. What a token
    writes and a later one reads stands in both sums and has to leave
    their running sum exactly, so both are taken on the very operands the
    matmuls multiplied (rounded to the operands' dtype), not on the fp32
    values they were rounded from."""
    z, j = pl.program_id(1), pl.program_id(2)
    last = pl.num_programs(2) - 1
    q = x_ref.shape[0]
    dtype = x_ref.dtype
    f32 = jnp.float32

    @pl.when(z == 0)
    def _():
        dstate[j] = jnp.zeros(dstate.shape[1:], f32)

    @pl.when(j == 0)
    def _():
        cb[...] = _masked_cb(c_ref, b_ref)
        dcb[...] = jnp.zeros_like(dcb)

    dec = _Decays(dt_ref, heads_ref, j)
    s0f, ds1f = states_ref[...], dstate[j]
    s0, ds1 = s0f.astype(dtype), ds1f.astype(dtype)
    xf = x_ref[...].astype(f32)
    dyf = dy_ref[...].astype(f32)
    dy = dyf.astype(dtype)
    xd = (xf * dec.delta_l).astype(dtype)
    to_end = (xf * dec.delta_l * dec.to_end_l).astype(dtype)
    dy_from_start = (dyf * dec.from_start_l).astype(dtype)
    # through the state: what y reads of the chunk-start state, and what
    # the chunk writes into the state it leaves
    y = dec.from_start_l * _dot(c_ref[...], s0)
    dc = _dot(dy_from_start, s0, _NT)
    dstate[j] = dec.gamma_l * ds1f + _dot(c_ref[...], dy_from_start, _TN)
    dto_end = _dot(b_ref[...], ds1)
    db = _dot(to_end, ds1, _NT)
    # inside the chunk, a head at a time
    inside, dxd = [], []
    for t, (x_tile, dy_tile) in enumerate(zip(_tiles(xd), _tiles(dy))):
        y_t = [0.0] * (q // _PAIR_TILE)
        dx_t = [0.0] * (q // _PAIR_TILE)
        for h, mask in dec.heads_of_tile(t):
            x_h, dy_h = _only(mask, x_tile), _only(mask, dy_tile)
            for r, c, rows, cols, decay in dec.pair_tiles(h):
                pairs = (cb[rows, cols] * decay).astype(dtype)
                y_t[r] += _dot(pairs, x_h[cols])
                dx_t[c] += _dot(pairs, dy_h[rows], _TN)
                dcb[rows, cols] += _dot(dy_h[rows], x_tile[cols], _NT) * decay
        inside.append(jnp.concatenate(y_t, 0))
        dxd.append(jnp.concatenate(dx_t, 0))
    y = y + jnp.concatenate(inside, 1)
    dxd = jnp.concatenate(dxd, 1)
    written = dto_end * to_end.astype(f32)
    dg = dec.by_head(dyf * y - dxd * xd.astype(f32) - written)  # (heads, Q)
    at_end = dec.by_head(
        written.sum(0, keepdims=True)
        + (ds1f * s0f).sum(0, keepdims=True) * dec.gamma_l)     # (heads, 1)
    dg = jnp.where(_iota(dg.shape, 1) == q - 1, dg + at_end, dg)
    through_g = _running_sum(dg, reverse=True)
    dxd = dxd + dec.to_end_l * dto_end
    dx_ref[...] = (dec.delta_l * dxd + dec.skip_l * dyf).astype(
        dx_ref.dtype)
    through_x = dec.by_head(dxd * xf)
    ddt = (through_x + through_g * dec.a) * jax.nn.sigmoid(dec.pre)
    ddt_ref[dec.mine, :] = ddt
    # as the heads' columns: d dt_bias, dA, dD
    sums_ref[dec.mine, :] = jnp.concatenate([
        ddt.sum(1, keepdims=True),
        (through_g * dec.delta).sum(1, keepdims=True),
        dec.by_head((dyf * xf).sum(0, keepdims=True))], 1)

    @pl.when(j == 0)
    def _():
        db_ref[...] = db
        dc_ref[...] = dc

    @pl.when(j > 0)
    def _():
        db_ref[...] += db
        dc_ref[...] += dc

    @pl.when(j == last)
    def _():
        pairs = jnp.where(_cumsum_matrix(q), dcb[...], 0.0).astype(dtype)
        dc_ref[...] += _dot(pairs, b_ref[...])
        db_ref[...] += _dot(pairs, c_ref[...], _TN)


def _kernel_call(kernel, name, x, operands, outs, scratch, reverse, interpret):
    """Grid (batch, chunks, blocks of heads), the chunks in sequence
    (``reverse``: from the last) and a chunk's blocks inside it.
    ``operands`` / ``outs``: (array or ShapeDtypeStruct, kind), the kind
    naming the layout: "tile" (B, S, H P: the chunk's rows, the block's
    lanes), "chunk" (B, S, N: the chunk's rows), "tokens" (B, H, S: the
    chunk's lanes), "heads" (H, 3: whole), "state" (B, Z, N, H P), "sums"
    (B, Z, H, 3)."""
    batch, chunks, blocks = grid = (
        x.shape[0], x.shape[1] // _KERNEL_CHUNK,
        x.shape[2] // (_HEADS_A_STEP * _KERNEL_WIDTH))

    def at(i):
        return chunks - 1 - i if reverse else i

    def spec(v, kind):
        shape = v.shape
        if kind == "tile":
            return pl.BlockSpec((None, _KERNEL_CHUNK, shape[2] // blocks),
                                lambda b_, z, j: (b_, at(z), j))
        if kind == "chunk":
            return pl.BlockSpec((None, _KERNEL_CHUNK, shape[2]),
                                lambda b_, z, j: (b_, at(z), 0))
        if kind == "tokens":
            return pl.BlockSpec((None, shape[1], _KERNEL_CHUNK),
                                lambda b_, z, j: (b_, 0, at(z)))
        if kind == "heads":
            return pl.BlockSpec(shape, lambda b_, z, j: (0, 0))
        if kind == "state":
            return pl.BlockSpec((None, None, shape[2], shape[3] // blocks),
                                lambda b_, z, j: (b_, at(z), 0, j))
        return pl.BlockSpec((None, None) + shape[2:],
                            lambda b_, z, j: (b_, at(z), 0, 0))

    return pl.pallas_call(
        kernel, grid=grid,
        in_specs=[spec(v, kind) for v, kind in operands],
        out_specs=[spec(v, kind) for v, kind in outs],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype) for v, _ in outs],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret, name=name,
    )(*(v for v, _ in operands))


def _operands(x, dt, b, c, heads):
    return [(x, "tile"), (dt, "tokens"), (b, "chunk"), (c, "chunk"),
            (heads, "heads")]


# (a jit of their own, the forward's and the backward's: a model binds the
# scan once a layer, and each kernel is traced and lowered once a shape,
# not once a layer: nine layers' worth was 6 s of a warm set-up)
@functools.partial(jax.jit, static_argnums=(5, 6))
def _kernel_forward(x, dt, b, c, heads, interpret, keep_states):
    """y like x and, with ``keep_states``, the chunk-start states (B, Z,
    N, H P) fp32."""
    batch, length, lanes = x.shape
    n = b.shape[2]
    outs = [(x, "tile")]
    if keep_states:
        outs.append((jax.ShapeDtypeStruct(
            (batch, length // _KERNEL_CHUNK, n, lanes), jnp.float32),
            "state"))
    a_step = _HEADS_A_STEP * _KERNEL_WIDTH
    return _kernel_call(
        _fwd_kernel, scopes.SSD_FWD, x, _operands(x, dt, b, c, heads), outs,
        [pltpu.VMEM((lanes // a_step, n, a_step), jnp.float32),
         pltpu.VMEM((_KERNEL_CHUNK, _KERNEL_CHUNK), jnp.float32)],
        False, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _ssd_kernels(x, dt, b, c, heads, interpret):
    """x (B, S, H P), dt (B, H, S) fp32 before the softplus, b and c (B,
    S, N), heads (H, 3) fp32: the columns dt_bias, A and D; S whole
    chunks."""
    return _kernel_forward(x, dt, b, c, heads, interpret, False)[0]


def _ssd_kernels_fwd(x, dt, b, c, heads, interpret):
    y, states = _kernel_forward(x, dt, b, c, heads, interpret, True)
    return y, (x, dt, b, c, heads, states)


@functools.partial(jax.jit, static_argnums=(7,))
def _kernel_backward(x, dt, b, c, heads, states, dy, interpret):
    """dx, d dt (B, H, S), dB and dC (fp32) and the chunks' sums (B, Z, H,
    3) for d dt_bias, dA and dD."""
    batch, n_heads, length = dt.shape
    a_step = _HEADS_A_STEP * _KERNEL_WIDTH
    f32 = jnp.float32
    pairs = (_KERNEL_CHUNK, _KERNEL_CHUNK)
    return _kernel_call(
        _bwd_kernel, scopes.SSD_BWD, x,
        _operands(x, dt, b, c, heads) + [(states, "state"), (dy, "tile")],
        [(x, "tile"), (dt, "tokens"),
         (jax.ShapeDtypeStruct(b.shape, f32), "chunk"),
         (jax.ShapeDtypeStruct(b.shape, f32), "chunk"),
         (jax.ShapeDtypeStruct(
             (batch, length // _KERNEL_CHUNK, n_heads, 3), f32), "sums")],
        [pltpu.VMEM((n_heads // _HEADS_A_STEP, b.shape[2], a_step), f32),
         pltpu.VMEM(pairs, f32), pltpu.VMEM(pairs, f32)],
        True, interpret)


def _ssd_kernels_bwd(interpret, residuals, dy):
    *primals, _ = residuals
    dx, ddt, db, dc, sums = _kernel_backward(*residuals, dy, interpret)
    b, c = primals[2:4]
    return (dx, ddt, db.astype(b.dtype), dc.astype(c.dtype),
            sums.sum((0, 1)))


_ssd_kernels.defvjp(_ssd_kernels_fwd, _ssd_kernels_bwd)


def _kernels_take(x, b, chunk):
    """Shapes the kernels are written for, from what ``ssd_scan`` sees in
    its operands: heads of 64 channels in whole blocks of 8, one group of
    B and C over a state that is a multiple of the 128 lanes, the chunk
    they were compiled and run for. Anything else runs the XLA code."""
    heads, width = x.shape[2:]
    groups, n = b.shape[2:]
    return (width == _KERNEL_WIDTH and heads % _HEADS_A_STEP == 0
            and groups == 1 and n % _LANE == 0 and chunk == _KERNEL_CHUNK)


def _scan_with_kernels(x, dt, a, b, c, d, dt_bias, chunk, interpret):
    batch, length, heads, _ = x.shape
    pad = -length % chunk
    f32 = jnp.float32

    def flat(v, value=0):       # (B, S, ., .) -> (B, whole chunks, . x .)
        v = jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2),
                    constant_values=value)
        return v.reshape(batch, length + pad, -1)

    if dt_bias is None:
        dt_bias = jnp.zeros((heads,), f32)
    y = _ssd_kernels(
        flat(x), jnp.swapaxes(flat(dt.astype(f32), _NO_STEP), 1, 2), flat(b),
        flat(c), jnp.stack([v.astype(f32) for v in (dt_bias, a, d)], 1),
        interpret)
    return y[:, :length].reshape(x.shape)
