"""Gated delta-rule linear attention (KDA), chunked, with its own backward.

Per head, with a state S (Dk x Dv, fp32) and, a token, a query q, a key k
(both L2-normalised by the caller), a value v, a per-channel decay
alpha in (0, 1)^Dk and a write strength beta:

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``kda_reference`` is that recurrence token by token (the jnp twin and the
numerics oracle). ``kda_attention`` is the same function as a chunked
algorithm: the sequence is cut into chunks of C tokens, the work inside a
chunk is matmuls over all chunks at once, and only the state crosses
chunks, in a scan of S / C steps.

Inside a chunk, with g_t the running sum of log alpha from the chunk's
start and u_t = beta_t (v_t - (Diag(alpha_t) S_{t-1})^T k_t) the value the
delta rule really writes (so S_t = Diag(alpha_t) S_{t-1} + k_t u_t^T):

    A[t, s] = beta_t sum_c k_t[c] k_s[c] exp(g_t[c] - g_s[c])      (s <  t)
    P[t, s] =        sum_c q_t[c] k_s[c] exp(g_t[c] - g_s[c])      (s <= t)
    (I + A) [W | U0] = beta [k exp(g) | v]          (unit lower triangular)
    U = U0 - W S_0,   O = (q exp(g)) S_0 + P U
    S_C = Diag(exp(g_C)) S_0 + (k exp(g_C - g))^T U

Every exponent above is <= 0 as written, but the usual factoring of A and P
into (x exp(g)) (k exp(-g))^T is not: exp(-g) overflows fp32 once a
channel has decayed by e^88 inside a chunk. So the pair matrices are built
in sub-chunks of 16 tokens: a block between two sub-chunks is a matmul
of rows decayed from their sub-chunk's start with keys decayed up to that
start (both factors <= 1), and the 16 x 16 blocks on the diagonal are
summed directly over the channels.

Decays, running sums, the triangular solve and the state are fp32; matmul
operands are the caller's dtype (bf16 in the models) with fp32
accumulation. Everything runs under the scope ``hvd_kda``
(common/scopes.py).

One algorithm, two compilers; which runs is picked from what the code
sees (``ops/pallas_kernels._decide``, the flash kernels' convention), with
no option:

- **On a TPU, where the kernels take the shapes** (one head width for keys
  and values, a multiple of the 128 lanes; a chunk of 32, 64 or 128): two
  Pallas kernels, ``hvd_kda_fwd`` and ``hvd_kda_bwd``, under one
  ``custom_vjp``. Grid (batch, heads, chunks), the chunk axis sequential.
  A forward step reads one chunk of one head, q, k, v (bf16), log alpha
  and beta (fp32), once, builds g, A, P, T = (I + A)^-1
  (blocked forward substitution: the 16 x 16 diagonal blocks on the vector
  unit, joined by fp32 matmuls), W and U0 in VMEM, applies the state it
  carries in VMEM scratch, and writes o and, for the backward, the
  chunk-start state and T. The backward is a reverse pass over the chunks
  that carries dS in scratch, builds the chunk's A, P, W, U again from the
  operands, the kept state and T, and writes dq, dk, dv, d log alpha and
  d beta: gradients written by hand (the solve's through dR = T^T dX and
  dA = -dR X^T, both fp32 like the solve; the decays' through dg =
  operand x (gradient met as a row - gradient met as a column)), held to
  ``kda_reference``'s by the tests. None of A, P, W, U0, Q~, K- reaches
  HBM.
- **Elsewhere** (a CPU, the tests, other widths): XLA over all chunks at
  once. The work inside the chunks is differentiated by JAX (it is
  parallel over chunks and holds no long loop); the scan over chunks has a
  hand-written backward (``custom_vjp``): a reverse scan that reads the
  chunk-start states the forward kept (S / C states a head, not S) and
  carries dS. ``use_pallas=True`` runs the kernels' bodies in interpret
  mode there (the tests' twin check).

``gated_delta_attention`` is the recurrence with ONE decay scalar a head
(Gated DeltaNet: ``Diag(alpha_t) = alpha_t I``), keys and values of
widths of their own, under the scope ``hvd_gdn``. With a scalar,
``exp(g_t - g_s)`` leaves the sums over the channels above: A and P are
``K K^T`` and ``Q K^T``, one matmul a chunk each, times the chunk's (C, C)
decay mask ``exp(g_t - g_s)`` (s <= t, every exponent <= 0 as it stands:
no sub-chunks). The solve (``_solve``), the scan over chunks and its
hand-written backward are the ones above, gamma one value a head. XLA
code on every platform; no kernel is written for it yet.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.scipy.linalg import solve_triangular

from .pallas_kernels import _decide
from ..common import metrics as metrics_lib
from ..common import scopes

CHUNK = 64
SUB_CHUNK = 16
_LANE = 128
# Chunks the kernels were compiled and run for on the chip (PERF.md): one of
# 16 has no block under the diagonal blocks to join, one of 256 needs more
# VMEM than a core has.
_KERNEL_CHUNKS = (32, 64, 128)


def kda_reference(q, k, v, log_alpha, beta):
    """The recurrence token by token, fp32, on (B, S, H, D) operands
    (``log_alpha`` like k, ``beta`` (B, S, H)). Returns o like v, fp32."""
    q, k, v, log_alpha, beta = (x.astype(jnp.float32)
                                for x in (q, k, v, log_alpha, beta))
    b, _, h, dk = k.shape

    def step(state, xs):
        qt, kt, vt, at, bt = xs                       # (B, H, D), (B, H)
        state = state * jnp.exp(at)[..., None]
        written = bt[..., None] * (
            vt - jnp.einsum("bhkv,bhk->bhv", state, kt))
        state = state + kt[..., None] * written[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, log_alpha, beta))
    state = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    return jnp.moveaxis(jax.lax.scan(step, state, xs)[1], 0, 1)


def _mm(spec, a, b, dtype):
    """einsum with operands in ``dtype`` and fp32 accumulation."""
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32)


def _pair_matrices(rows, k, g, sub):
    """``M[r, t, s] = sum_c rows[r, t, c] k[s, c] exp(g[t, c] - g[s, c])``
    for s <= t, 0 above the diagonal, over chunks (..., C, D): ``rows``
    (R, ..., C, D) stacks the row operands (q and k), ``g`` is the
    running sum of log alpha in the chunk. Returns (R, ..., C, C) fp32."""
    dtype = k.dtype
    *lead, c, d = k.shape
    ns = c // sub
    g = g.reshape(*lead, ns, sub, d)
    k = k.reshape(*lead, ns, sub, d)
    rows = rows.reshape(rows.shape[0], *lead, ns, sub, d)
    # g just before each sub-chunk: the point both factors decay to
    start = jnp.concatenate(
        [jnp.zeros_like(g[..., :1, -1, :]), g[..., :-1, -1, :]], -2)
    rows_from_start = rows * jnp.exp(g - start[..., None, :])
    # keys of earlier sub-chunks decayed up to sub-chunk i's start; 0 for
    # the keys of sub-chunk i and later (a select, so no overflow there)
    gap = start[..., :, None, None, :] - g[..., None, :, :, :]
    earlier = (jnp.arange(ns)[:, None] > jnp.arange(ns)[None, :])
    keys_to_start = k[..., None, :, :, :] * jnp.exp(
        jnp.where(earlier[:, :, None, None], gap, -jnp.inf))
    off = _mm("r...itc,...ijsc->r...itjs", rows_from_start, keys_to_start,
              dtype)
    # the sub x sub blocks on the diagonal, channel by channel
    low = jnp.arange(sub)[:, None] >= jnp.arange(sub)[None, :]
    decay = jnp.exp(jnp.where(
        low[:, :, None], g[..., :, None, :] - g[..., None, :, :], -jnp.inf))
    diag = (rows.astype(jnp.float32)[..., :, None, :]
            * (k.astype(jnp.float32)[..., None, :, :] * decay)).sum(-1)
    full = off + diag[..., None, :] * jnp.eye(ns)[:, None, :, None]
    return full.reshape(rows.shape[0], *lead, c, c)


def _within_chunks(q, k, v, log_alpha, beta, sub):
    """Everything a chunk can know without the state, for all chunks at
    once: operands (..., C, D) -> (Q~, P, W, U0, K-, gamma) of the module
    docstring."""
    dtype = k.dtype
    g = jnp.cumsum(log_alpha, -2)
    c = k.shape[-2]
    pairs = _pair_matrices(jnp.stack([q, k]), k, g, sub)
    decayed = jnp.exp(g)
    w, u0 = _solve(pairs[1], beta, k.astype(jnp.float32) * decayed, v)
    k_to_end = (k * jnp.exp(g[..., -1:, :] - g)).astype(dtype)
    return ((q * decayed).astype(dtype), pairs[0], w, u0, k_to_end,
            decayed[..., -1, :])


def _solve(pairs, beta, k_decayed, v):
    """``(I + A) [W | U0] = beta [k e^g | v]`` with ``A = beta pairs``
    under the diagonal (unit lower triangular), fp32, for all chunks at
    once: ``pairs`` (..., C, C) the keys' pair matrix, ``k_decayed``
    (..., C, Dk) fp32, ``v`` (..., C, Dv)."""
    c = pairs.shape[-1]
    strict = jnp.arange(c)[:, None] > jnp.arange(c)[None, :]
    a = jnp.where(strict, pairs, 0.0) * beta[..., None]
    rhs = beta[..., None] * jnp.concatenate(
        [k_decayed, v.astype(jnp.float32)], -1)
    solved = solve_triangular(a + jnp.eye(c), rhs, lower=True,
                              unit_diagonal=True)
    return solved[..., :k_decayed.shape[-1]], solved[..., k_decayed.shape[-1]:]


def _within_chunks_scalar(q, k, v, log_decay, beta):
    """``_within_chunks`` where the decay is one scalar a head
    (``log_decay`` (..., C)): ``exp(g_t - g_s)`` leaves the sum over the
    channels, so a pair matrix is one ``rows K^T`` matmul a chunk times
    the (C, C) decay mask, every exponent <= 0 with no sub-chunks. gamma
    comes back (..., 1)."""
    dtype = k.dtype
    g = jnp.cumsum(log_decay, -1)
    c = k.shape[-2]
    low = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]
    decay = jnp.exp(jnp.where(low, g[..., :, None] - g[..., None, :],
                              -jnp.inf))
    pairs = _mm("r...tc,...sc->r...ts", jnp.stack([q, k]), k, dtype) * decay
    decayed = jnp.exp(g)[..., None]
    w, u0 = _solve(pairs[1], beta, k.astype(jnp.float32) * decayed, v)
    k_to_end = (k * jnp.exp(g[..., -1:] - g)[..., None]).astype(dtype)
    return ((q * decayed).astype(dtype), pairs[0], w, u0, k_to_end,
            decayed[..., -1, :])


def _chunk_forward(dtype, state, xs):
    qd, p, w, u0, kbar, gamma = xs
    u = u0 - _mm("bhck,bhkv->bhcv", w, state, dtype)
    o = _mm("bhck,bhkv->bhcv", qd, state, dtype) \
        + _mm("bhcs,bhsv->bhcv", p, u, dtype)
    new = gamma[..., None] * state + _mm("bhck,bhcv->bhkv", kbar, u, dtype)
    return new, (o, state)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _across_chunks(dtype, qd, p, w, u0, kbar, gamma):
    """The scan over chunks (leading axis), from a zero state: per chunk
    U = U0 - W S, O = Q~ S + P U, S <- Diag(gamma) S + K-^T U; ``gamma``
    (N, B, H, Dk), or (N, B, H, 1) where the decay is one scalar a head.
    Returns O (N, B, H, C, Dv) fp32."""
    return _across_chunks_fwd(dtype, qd, p, w, u0, kbar, gamma)[0]


def _across_chunks_fwd(dtype, qd, p, w, u0, kbar, gamma):
    state = jnp.zeros(kbar.shape[1:3] + (kbar.shape[-1], u0.shape[-1]),
                      jnp.float32)
    xs = (qd, p, w, u0, kbar, gamma)
    _, (o, states) = jax.lax.scan(
        functools.partial(_chunk_forward, dtype), state, xs)
    return o, (xs, states)


def _across_chunks_bwd(dtype, residuals, do):
    """Reverse scan carrying dS; a chunk's U is computed again from the
    chunk-start state the forward kept."""
    xs, states = residuals

    def step(ds, inputs):
        (qd, p, w, u0, kbar, gamma), state, do_n = inputs
        d_gamma = (ds * state).sum(-1)
        if gamma.shape[-1] == 1:    # one decay a head: over the keys too
            d_gamma = d_gamma.sum(-1, keepdims=True)
        u = u0 - _mm("bhck,bhkv->bhcv", w, state, dtype)
        du = _mm("bhcs,bhcv->bhsv", p, do_n, dtype) \
            + _mm("bhck,bhkv->bhcv", kbar, ds, dtype)
        grads = (
            _mm("bhcv,bhkv->bhck", do_n, state, dtype).astype(qd.dtype),
            _mm("bhcv,bhsv->bhcs", do_n, u, dtype),
            -_mm("bhcv,bhkv->bhck", du, state, dtype),
            du,
            _mm("bhcv,bhkv->bhck", u, ds, dtype).astype(kbar.dtype),
            d_gamma)
        ds = gamma[..., None] * ds \
            + _mm("bhck,bhcv->bhkv", qd, do_n, dtype) \
            - _mm("bhck,bhcv->bhkv", w, du, dtype)
        return ds, grads

    return jax.lax.scan(step, jnp.zeros_like(states[0]), (xs, states, do),
                        reverse=True)[1]


_across_chunks.defvjp(_across_chunks_fwd, _across_chunks_bwd)


# -- the Pallas kernels ------------------------------------------------------
#
# The same algorithm, a chunk of one head a grid step: everything the
# XLA path builds over all chunks at once (g, A, P, the solve, W, U0) is
# built here for one chunk in VMEM and never reaches HBM. Values are
# (C, D) tiles of one head: tokens on sublanes, channels on lanes. The
# state is kept transposed, (Dv, Dk), so that the decay gamma (a row over
# the key channels) scales its lanes and every product with it is a
# matmul Mosaic takes as it is.

_HI = jax.lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))   # a · b
_NT = (((1,), (1,)), ((), ()))   # a · bᵀ
_TN = (((0,), (0,)), ((), ()))   # aᵀ · b

_M_CALLS = metrics_lib.counter(
    "hvd_tpu_kda_calls_total",
    "kda_attention calls traced, by the path picked for them: the "
    "Pallas kernels (a TPU, a head width that is a multiple of 128 "
    "lanes, a chunk of whole sub-chunks) or the chunked XLA code",
    labels=("path",))


def _dot(a, b, dims=_NN, precision=None):
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=jnp.float32)


def _parts(x):
    """fp32 -> three bf16 parts that sum to it (8 + 8 + 8 bits of
    mantissa)."""
    parts = []
    for _ in range(3):
        parts.append(x.astype(jnp.bfloat16))
        x = x - parts[-1].astype(jnp.float32)
    return parts


def _mask_dot(mask, x):
    """``mask @ x`` in fp32 for a matrix of ones and zeros: exact in one
    bf16 pass a part of x, where an fp32 product would split the mask too
    and run six."""
    mask = mask.astype(jnp.bfloat16)
    return _dot(jnp.concatenate([mask] * 3, 1), jnp.concatenate(_parts(x), 0))


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _tile_of(j):
    """First row of the fp32 tile of 8 sublanes that holds row j."""
    return j // 8 * 8


def _from_row(top, x, below):
    """x with its rows from ``top`` on replaced."""
    return jnp.concatenate([x[:top], below], 0) if top else below


def _column(row):
    """(1, n) -> (n, 1), through the diagonal of an (n, n) tile."""
    n = row.shape[1]
    eye = _iota((n, n), 0) == _iota((n, n), 1)
    return jnp.where(eye, row, 0.0).sum(1, keepdims=True)


def _row(column):
    """(n, 1) -> (1, n)."""
    n = column.shape[0]
    eye = _iota((n, n), 0) == _iota((n, n), 1)
    return jnp.where(eye, column, 0.0).sum(0, keepdims=True)


def _sum_in_lane_groups(x, size):
    """Every lane of x (rows, 128) replaced by the sum over its group of
    ``size`` lanes, fp32: a matmul with the block-diagonal matrix of ones
    (exact in bf16, so x's three bf16 parts are added up exactly, a pass
    each). The cross-lane unit would do it in log2(size) rotations, each
    many times a matmul's latency, and this sum sits on the substitution's
    critical path."""
    ones = ((_iota((_LANE, _LANE), 0) // size)
            == (_iota((_LANE, _LANE), 1) // size)).astype(jnp.bfloat16)
    return _dot(jnp.concatenate(_parts(x), 1), jnp.concatenate([ones] * 3, 0))


def _diagonal_block_inverses(a, sub):
    """(I + N)^-1, transposed, for every sub x sub block N on the diagonal
    of the (C, C) strictly lower triangular ``a``, by forward substitution
    in fp32 on the vector unit. The blocks lie side by side along the
    lanes, (sub, C), so one step of the substitution (a column of each
    inverse's transpose: the row of N times what stands, summed over the
    block's lanes) serves them all: sub - 1 dependent steps a chunk.
    Returns blockdiag(inverses)ᵀ, (C, C)."""
    c = a.shape[0]
    ns = c // sub
    same = (_iota((c, c), 0) // sub) == (_iota((c, c), 1) // sub)
    a = jnp.where(same, a, 0.0)
    n = sum(a[i * sub:(i + 1) * sub] for i in range(ns))
    if c < _LANE:   # a whole tile of lanes: a step's matmul is 20% slower
        n = jnp.concatenate(                        # on 64 of them
            [n, jnp.zeros((sub, _LANE - c), jnp.float32)], 1)
    column = _iota(n.shape, 1) % sub
    inv_t = (column == _iota(n.shape, 0)).astype(jnp.float32)
    for t in range(1, sub):
        inv_t = inv_t - jnp.where(
            column == t, _sum_in_lane_groups(inv_t * n[t:t + 1], sub), 0.0)
    return jnp.where(same, jnp.concatenate([inv_t[:, :c]] * ns, 0), 0.0)


def _join_blocks(inv_t, a, sub):
    """(I + a)^-1 (C, C) from the transposed inverses of its diagonal
    blocks: with D the block-diagonal inverse and L the part of ``a``
    under the diagonal blocks, (I + a)^-1 = (I + D L)^-1 D, and D L is
    nilpotent over the C / sub blocks, so its inverse is the finite
    product (I - n)(I + n^2)(I + n^4)... The blocked forward substitution,
    as fp32 matmuls."""
    c = a.shape[0]
    rows, cols = _iota((c, c), 0), _iota((c, c), 1)
    eye = (rows == cols).astype(jnp.float32)
    below = jnp.where(rows // sub > cols // sub, a, 0.0)

    def under(lo, x):       # rows [lo, C) of a product whose others are 0
        return jnp.concatenate([jnp.zeros((lo, c), jnp.float32), x], 0) \
            if lo else x

    # n = D L has no first row of blocks, n^2 no first two, ...: the
    # products are made of the rows that can be other than 0
    power = under(sub, _dot(inv_t[:, sub:], below, _TN, precision=_HI))
    join = eye - power
    reach = 1
    while 2 * reach < c // sub:
        lo = 2 * reach * sub
        power = under(lo, _dot(power[lo:], power, precision=_HI))
        join = join + under(lo, _dot(join[lo:], power, precision=_HI))
        reach *= 2
    return _dot(join, inv_t, _NT, precision=_HI)


class _Chunk:
    """What one head's chunk knows without the state (the module
    docstring's g, P, A, T = (I + A)^-1, W, U0, Q~, K-), from q, k, v
    (C, D) in the caller's dtype, g (C, D) fp32 and beta (1, C); with the
    pieces the backward needs again. ``solve`` completes it, given T."""

    def __init__(self, q, k, v, g, beta_row, sub):
        c, d = k.shape
        dtype = k.dtype
        f32 = jnp.float32
        self.c, self.d, self.sub, self.ns, self.dtype = c, d, sub, c // sub, \
            dtype
        qf, kf, vf = (x.astype(f32) for x in (q, k, v))
        self.qf, self.kf, self.vf = qf, kf, vf
        self.g = g
        self.decayed = decayed = jnp.exp(g)
        self.gamma = decayed[c - 1:c]                        # (1, D)
        self.to_end = jnp.exp(g[c - 1:c] - g)
        self.lower = _iota((c, c), 0) >= _iota((c, c), 1)
        self.strict = _iota((c, c), 0) > _iota((c, c), 1)
        p, akk = self._pair_matrices()
        self.p = jnp.where(self.lower, p, 0.0)
        self.akk = jnp.where(self.strict, akk, 0.0)
        self.beta = _column(beta_row)                        # (C, 1)
        self.a = self.akk * self.beta
        self.kg = kf * decayed
        self.qd = (qf * decayed).astype(dtype)
        self.kbar = (kf * self.to_end).astype(dtype)

    def solve(self, t):
        """(I + A) [W | U0] = beta [k e^g | v], given T = (I + A)^-1."""
        self.t = t
        solved = _dot(self.t,
                      self.beta * jnp.concatenate([self.kg, self.vf], 1),
                      precision=_HI)
        self.w, self.u0 = solved[:, :self.d], solved[:, self.d:]

    def rows_of(self, x, i):
        return x[i * self.sub:(i + 1) * self.sub]

    def pair_decay(self, i, j):
        """(sub, D): exp(g[t] - g[s]) over the tokens t of sub-chunk i with
        s its token j, from the first tile of 8 rows that holds a t >= s;
        1 where t < s (the exponent is clamped at 0, so none is positive),
        which is above the diagonal, where the pair matrices and their
        cotangents are masked."""
        lo = i * self.sub
        return jnp.exp(jnp.minimum(
            self.g[lo + _tile_of(j):lo + self.sub]
            - self.g[lo + j:lo + j + 1], 0.0))

    def _pair_matrices(self):
        """P and A's pair matrix (C, C), s <= t, a sub-chunk's rows at a
        time. The blocks between two sub-chunks are a matmul: (rows of q
        and k decayed from their sub-chunk's start, g just before it) x
        (earlier keys decayed up to that start)ᵀ, both factors <= 1; the
        sub x sub block on the diagonal is summed over the channels in
        fp32, a column a step. Keeps the factors for the backward."""
        c, d, sub, dtype = self.c, self.d, self.sub, self.dtype
        self.from_start, self.to_start = [], []
        self.rows_from_start, self.keys_to_start = [], []
        ps, as_ = [], []
        for i in range(self.ns):
            lo = i * sub
            g, q, k = (self.rows_of(x, i)
                       for x in (self.g, self.qf, self.kf))
            if i:
                start = self.g[lo - 1:lo]
                from_start = jnp.exp(g - start)
                to_start = jnp.exp(start - self.g[:lo])
                rows = jnp.concatenate(
                    [q * from_start, k * from_start], 0).astype(dtype)
                keys = (self.kf[:lo] * to_start).astype(dtype)
                off = jnp.concatenate(
                    [_dot(rows, keys, _NT),
                     jnp.zeros((2 * sub, c - lo), jnp.float32)], 1)
                p, a = off[:sub], off[sub:]
            else:
                from_start = to_start = rows = keys = None
                p = a = jnp.zeros((sub, c), jnp.float32)
            for j in range(sub):
                top = _tile_of(j)
                keyed = self.pair_decay(i, j) * k[j:j + 1]
                # (an iota is built at its size: Mosaic cannot slice one)
                hit = _iota((sub - top, c), 1) == lo + j
                p = _from_row(top, p, jnp.where(
                    hit, (q[top:] * keyed).sum(1, keepdims=True), p[top:]))
                a = _from_row(top, a, jnp.where(
                    hit, (k[top:] * keyed).sum(1, keepdims=True), a[top:]))
            ps.append(p)
            as_.append(a)
            self.from_start.append(from_start)
            self.to_start.append(to_start)
            self.rows_from_start.append(rows)
            self.keys_to_start.append(keys)
        return jnp.concatenate(ps, 0), jnp.concatenate(as_, 0)


def _cumsum_matrix(c, reverse=False):
    rows, cols = _iota((c, c), 0), _iota((c, c), 1)
    return rows <= cols if reverse else rows >= cols


def _chunk(q_ref, k_ref, v_ref, la_ref, beta_ref, n, sub, t_ref=None):
    """Chunk ``n`` of the grid step's head; (I + A)^-1 built here, or read
    from ``t_ref`` where the forward kept it."""
    g = _mask_dot(_cumsum_matrix(q_ref.shape[0]), la_ref[...])
    ch = _Chunk(q_ref[...], k_ref[...], v_ref[...], g,
                beta_ref[pl.ds(n, 1), :], sub)
    ch.solve(_join_blocks(_diagonal_block_inverses(ch.a, sub), ch.a, sub)
             if t_ref is None else t_ref[...])
    return ch


def _fwd_kernel(q_ref, k_ref, v_ref, la_ref, beta_ref, o_ref, *rest, sub):
    """One chunk of one head: reads q, k, v, log alpha, beta once, applies
    and advances the state held in ``state`` (Dv, Dk) and, where the
    backward will need them, writes the chunk-start state and (I + A)^-1
    (C x C: a quarter of a state)."""
    state = rest[-1]
    states_ref, t_ref = rest[:2] if len(rest) == 3 else (None, None)
    n = pl.program_id(2)
    c = q_ref.shape[0]

    @pl.when(n == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    ch = _chunk(q_ref, k_ref, v_ref, la_ref, beta_ref, n, sub)
    s0 = state[...]
    if states_ref is not None:
        states_ref[...] = s0
        t_ref[...] = ch.t
    s0 = s0.astype(ch.dtype)
    both = _dot(jnp.concatenate([ch.w.astype(ch.dtype), ch.qd], 0), s0, _NT)
    u = (ch.u0 - both[:c]).astype(ch.dtype)
    o_ref[...] = (both[c:] + _dot(ch.p.astype(ch.dtype), u)).astype(
        o_ref.dtype)
    state[...] = ch.gamma * state[...] + _dot(u, ch.kbar, _TN)


def _bwd_kernel(q_ref, k_ref, v_ref, la_ref, beta_ref, states_ref, t_ref,
                do_ref, dq_ref, dk_ref, dv_ref, dla_ref, dbeta_ref, dstate,
                dk_diag, *, sub):
    """The same chunk in the reverse pass: rebuilds what the forward knew
    of it from the operands, the kept chunk-start state and the kept
    (I + A)^-1, carries dS in ``dstate`` (Dv, Dk) and writes the five
    gradients."""
    n = pl.num_programs(2) - 1 - pl.program_id(2)
    c, d = q_ref.shape

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    ch = _chunk(q_ref, k_ref, v_ref, la_ref, beta_ref, n, sub, t_ref)
    dtype = ch.dtype
    s0f = states_ref[...]
    s0 = s0f.astype(dtype)
    ds1f = dstate[...]
    ds1 = ds1f.astype(dtype)
    do = do_ref[...].astype(dtype)
    w = ch.w.astype(dtype)
    u = (ch.u0 - _dot(w, s0, _NT)).astype(dtype)
    # across chunks: the reverse of U = U0 - W S, O = Q~ S + P U,
    # S <- Diag(gamma) S + K-ᵀ U
    du = _dot(ch.p.astype(dtype), do, _TN) + _dot(ch.kbar, ds1, _NT)
    dub = du.astype(dtype)
    from_state = _dot(jnp.concatenate([do, dub], 0), s0)       # (2C, Dk)
    dqd, dw = from_state[:c], -from_state[c:]
    dp = jnp.where(ch.lower, _dot(do, u, _NT), 0.0)
    dkbar = _dot(u, ds1)
    dgamma = (ds1f * s0f).sum(0, keepdims=True)                 # (1, Dk)
    dstate[...] = ch.gamma * ds1f + _dot(do, ch.qd, _TN) - _dot(dub, w, _TN)
    # the solve, in fp32 like the forward's: [W | U0] = T R gives
    # dR = Tᵀ [dW | dU] and dA = -dR [W | U0]ᵀ under the diagonal
    dr = _dot(ch.t, jnp.concatenate([dw, du], 1), _TN, precision=_HI)
    da = jnp.where(ch.strict, -_dot(
        dr, jnp.concatenate([ch.w, ch.u0], 1), _NT, precision=_HI), 0.0)
    dbeta = (dr[:, :d] * ch.kg + dr[:, d:] * ch.vf).sum(
        -1, keepdims=True) + (da * ch.akk).sum(-1, keepdims=True)
    dbeta_ref[pl.ds(n, 1), :] = _row(dbeta)
    dv_ref[...] = (ch.beta * dr[:, d:]).astype(dv_ref.dtype)
    dakk = ch.beta * da
    # the pair matrices. A key meets the decays as a row (with q, in Q~,
    # K e^g, A's rows: e^{+g}) or as a column (in K-, P's and A's columns:
    # e^{-g}); dg is row part minus column part, times the operand, so the
    # exponentials are not differentiated apart.
    dq_rows, dk_rows, dk_cols = _pairs_backward(ch, dp, dakk, dk_diag)
    dq = dqd * ch.decayed + dq_rows
    dk_rows = ch.beta * dr[:, :d] * ch.decayed + dk_rows
    dk_cols = dkbar * ch.to_end + dk_cols
    dq_ref[...] = dq.astype(dq_ref.dtype)
    dk_ref[...] = (dk_rows + dk_cols).astype(dk_ref.dtype)
    dg = ch.qf * dq + ch.kf * (dk_rows - dk_cols)
    # g's last row is also the decay to the chunk's end, in K- and in gamma
    at_end = (dkbar * ch.kf * ch.to_end).sum(0, keepdims=True) \
        + dgamma * ch.gamma
    dla_ref[...] = _mask_dot(
        _cumsum_matrix(c, reverse=True),
        jnp.where(_iota((c, 1), 0) == c - 1, dg + at_end, dg))


def _pairs_backward(ch, dp, dakk, dk_diag):
    """Gradients through P (cotangent ``dp``, s <= t) and through A's
    pair matrix (``dakk``, s < t): what reaches q as P's rows, k as A's
    rows, and k as the columns of both, each (C, D) fp32. ``dk_diag``
    (C, D) is VMEM the diagonal blocks' column sums are put together in,
    a row a store."""
    c, d, sub, dtype = ch.c, ch.d, ch.sub, ch.dtype
    dq_rows, dk_rows = [], []
    dk_cols = jnp.zeros((c, d), jnp.float32)
    for i in range(ch.ns):
        lo = i * sub
        q, k = ch.rows_of(ch.qf, i), ch.rows_of(ch.kf, i)
        dp_i, da_i = ch.rows_of(dp, i), ch.rows_of(dakk, i)
        if i:       # the blocks between sub-chunks: the matmul's transposes
            off = jnp.concatenate([dp_i[:, :lo], da_i[:, :lo]],
                                  0).astype(dtype)
            rows = _dot(off, ch.keys_to_start[i]) \
                * jnp.concatenate([ch.from_start[i]] * 2, 0)
            to_q, to_k = rows[:sub], rows[sub:]
            cols = _dot(off, ch.rows_from_start[i], _TN) * ch.to_start[i]
            dk_cols = dk_cols + jnp.concatenate(
                [cols, jnp.zeros((c - lo, d), jnp.float32)], 0)
        else:
            to_q = to_k = jnp.zeros((sub, d), jnp.float32)
        for j in range(sub):
            # the block on the diagonal, a column a step: dp and dakk are
            # 0 above the diagonal, where the decay reads 1
            top = _tile_of(j)
            decay = ch.pair_decay(i, j)
            keyed = decay * k[j:j + 1]
            from_p = dp_i[top:, lo + j:lo + j + 1]
            from_a = da_i[top:, lo + j:lo + j + 1]
            to_q = _from_row(top, to_q, to_q[top:] + from_p * keyed)
            to_k = _from_row(top, to_k, to_k[top:] + from_a * keyed)
            dk_diag[lo + j:lo + j + 1, :] = (
                (from_p * q[top:] + from_a * k[top:]) * decay).sum(
                    0, keepdims=True)
        dq_rows.append(to_q)
        dk_rows.append(to_k)
    return (jnp.concatenate(dq_rows, 0), jnp.concatenate(dk_rows, 0),
            dk_cols + dk_diag[...])


def _kernel_call(kernel, name, grid, operands, outs, scratch, reverse,
                 interpret):
    """``grid`` (batch, heads, chunks), the chunk axis sequential
    (``reverse``: from the last). ``operands`` / ``outs``: (array or
    ShapeDtypeStruct, kind) with kind "tile" (B, S, H D: a chunk of the
    step's head), "beta" (B, H, N, C: the head's, whole) or "state"
    (B, H, N, rows, columns: the chunk's)."""
    _, h, n = grid

    def chunk(i):
        return n - 1 - i if reverse else i

    def spec(x, kind):
        if kind == "tile":
            return pl.BlockSpec((None, x.shape[1] // n, x.shape[2] // h),
                                lambda b_, h_, i: (b_, chunk(i), h_))
        if kind == "beta":
            return pl.BlockSpec((None, None) + x.shape[2:],
                                lambda b_, h_, i: (b_, h_, 0, 0))
        return pl.BlockSpec((None, None, None) + x.shape[3:],
                            lambda b_, h_, i: (b_, h_, chunk(i), 0, 0))

    return pl.pallas_call(
        kernel, grid=grid,
        in_specs=[spec(x, kind) for x, kind in operands],
        out_specs=[spec(x, kind) for x, kind in outs],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x, _ in outs],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name=name,
    )(*(x for x, _ in operands))


def _kernel_forward(q, k, v, log_alpha, beta, sub, interpret, keep_states):
    """o (B, S, H D) and, with ``keep_states``, the chunk-start states
    (B, H, N, Dv, Dk) and the chunks' (I + A)^-1 (B, H, N, C, C), on
    operands in the kernels' layout."""
    b, h, n, c = beta.shape
    d = q.shape[2] // h
    outs = [(jax.ShapeDtypeStruct(v.shape, v.dtype), "tile")]
    if keep_states:
        outs += [(jax.ShapeDtypeStruct((b, h, n, d, d), jnp.float32), "state"),
                 (jax.ShapeDtypeStruct((b, h, n, c, c), jnp.float32),
                  "state")]
    return _kernel_call(
        functools.partial(_fwd_kernel, sub=sub), scopes.KDA_FWD, (b, h, n),
        [(q, "tile"), (k, "tile"), (v, "tile"), (log_alpha, "tile"),
         (beta, "beta")],
        outs, [pltpu.VMEM((d, d), jnp.float32)], False, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kda_kernels(q, k, v, log_alpha, beta, sub, interpret):
    return _kernel_forward(q, k, v, log_alpha, beta, sub, interpret,
                           False)[0]


def _kda_kernels_fwd(q, k, v, log_alpha, beta, sub, interpret):
    o, states, inverses = _kernel_forward(q, k, v, log_alpha, beta, sub,
                                          interpret, True)
    return o, (q, k, v, log_alpha, beta, states, inverses)


def _kda_kernels_bwd(sub, interpret, residuals, do):
    q, k, v, log_alpha, beta, states, inverses = residuals
    c = beta.shape[3]
    d = q.shape[2] // beta.shape[1]
    tiles = [(x, "tile") for x in (q, k, v, log_alpha)]
    return tuple(_kernel_call(
        functools.partial(_bwd_kernel, sub=sub), scopes.KDA_BWD,
        beta.shape[:3],
        tiles + [(beta, "beta"), (states, "state"), (inverses, "state"),
                 (do, "tile")],
        tiles + [(beta, "beta")],
        [pltpu.VMEM((d, d), jnp.float32), pltpu.VMEM((c, d), jnp.float32)],
        True, interpret))


_kda_kernels.defvjp(_kda_kernels_fwd, _kda_kernels_bwd)


def _kernels_take(k, v, chunk):
    """Shapes the kernels are written for: one width for keys and values,
    a multiple of the 128 lanes; a chunk they were compiled for. Anything
    else runs the XLA code."""
    d = k.shape[-1]
    return d == v.shape[-1] and d % _LANE == 0 and chunk in _KERNEL_CHUNKS


def kda_attention(q, k, v, log_alpha, beta, chunk: int = CHUNK,
                  use_pallas: Optional[bool] = None):
    """Chunked gated delta-rule attention on (B, S, H, D) operands:
    ``q``, ``k`` (B, S, H, Dk), ``v`` (B, S, H, Dv), ``log_alpha`` like k
    (fp32, <= 0), ``beta`` (B, S, H). Returns o like v. Any S: the tail is
    padded with tokens that write nothing (beta 0, no decay).

    ``use_pallas=None`` runs the Pallas kernels on a TPU where they take
    the shapes and the chunked XLA code elsewhere; ``True`` forces the
    kernels (interpret mode off-TPU: the test path), ``False`` the XLA
    code."""
    use, interpret = _decide(use_pallas)
    use = use and _kernels_take(k, v, chunk)
    _M_CALLS.labels(path="pallas" if use else "xla").inc()
    with jax.named_scope(scopes.KDA):
        b, s, h, _ = k.shape
        pad = -s % chunk
        n = (s + pad) // chunk

        def padded(x):
            return jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))

        log_alpha = log_alpha.astype(jnp.float32)
        beta = beta.astype(jnp.float32)
        if use:
            def tiles(x):           # (B, S, H, D) -> (B, N C, H D)
                return padded(x).reshape(b, n * chunk, -1)

            by_head = jnp.moveaxis(
                padded(beta).reshape(b, n, chunk, h), 3, 1)
            o = _kda_kernels(tiles(q), tiles(k), tiles(v), tiles(log_alpha),
                             by_head, SUB_CHUNK, interpret)
            return o.reshape(b, n * chunk, h, -1)[:, :s]

        return _chunked_xla(
            functools.partial(_within_chunks, sub=min(SUB_CHUNK, chunk)),
            q, k, v, log_alpha, beta, chunk)


def _chunked_xla(within, q, k, v, log_decay, beta, chunk):
    """The XLA code of either recurrence on (B, S, H, ...) operands: the
    tail padded to whole chunks, ``within`` over all chunks at once, the
    scan across them."""
    b, s, h, _ = k.shape
    pad = -s % chunk
    n = (s + pad) // chunk

    def chunks(x):              # (B, S, H, ...) -> (B, H, N, C, ...)
        x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        return jnp.moveaxis(x.reshape((b, n, chunk) + x.shape[2:]), 3, 1)

    parts = within(*(chunks(x) for x in (q, k, v, log_decay, beta)))
    o = _across_chunks(k.dtype, *(jnp.moveaxis(x, 2, 0) for x in parts))
    o = jnp.moveaxis(o, (0, 3), (1, 2))     # (B, N, C, H, Dv)
    return o.reshape(b, n * chunk, h, -1)[:, :s].astype(v.dtype)


_M_GDN_CALLS = metrics_lib.counter(
    "hvd_tpu_gdn_calls_total",
    "gated_delta_attention calls traced, by the path picked for them: "
    "the chunked XLA code (no kernel is written for the scalar-decay "
    "form yet)",
    labels=("path",))


def gated_delta_attention(q, k, v, log_decay, beta, chunk: int = CHUNK):
    """The gated delta rule with ONE decay scalar a head (Gated DeltaNet),
    ``kda_reference``'s recurrence with ``Diag(alpha_t) = alpha_t I``:
    ``q``, ``k`` (B, S, H, Dk), ``v`` (B, S, H, Dv) with any Dv,
    ``log_decay`` (B, S, H) (fp32, <= 0), ``beta`` (B, S, H). Returns o
    like v. Any S: the tail is padded with tokens that write nothing.

    Chunked as ``kda_attention`` is, under the scope ``hvd_gdn``: the
    solve, the scan across chunks and its hand-written backward are
    KDA's; the pair matrices are one matmul a chunk times the chunk's
    decay mask (``_within_chunks_scalar``). XLA code on every platform."""
    _M_GDN_CALLS.labels(path="xla").inc()
    with jax.named_scope(scopes.GDN):
        return _chunked_xla(_within_chunks_scalar, q, k, v,
                            log_decay.astype(jnp.float32),
                            beta.astype(jnp.float32), chunk)
