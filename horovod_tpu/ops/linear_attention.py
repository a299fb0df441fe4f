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
accumulation. The work inside the chunks is differentiated by JAX (it is
parallel over chunks and holds no long loop); the scan over chunks has a
hand-written backward (``custom_vjp``): a reverse scan that reads the
chunk-start states the forward kept (S / C states a head, not S) and
carries dS. Everything runs under the scope ``hvd_kda``
(common/scopes.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

from ..common import scopes

CHUNK = 64
SUB_CHUNK = 16


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
    strict = jnp.arange(c)[:, None] > jnp.arange(c)[None, :]
    a = jnp.where(strict, pairs[1], 0.0) * beta[..., None]
    decayed = jnp.exp(g)
    rhs = beta[..., None] * jnp.concatenate(
        [k.astype(jnp.float32) * decayed, v.astype(jnp.float32)], -1)
    solved = solve_triangular(a + jnp.eye(c), rhs, lower=True,
                              unit_diagonal=True)
    w, u0 = solved[..., :k.shape[-1]], solved[..., k.shape[-1]:]
    k_to_end = (k * jnp.exp(g[..., -1:, :] - g)).astype(dtype)
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
    U = U0 - W S, O = Q~ S + P U, S <- Diag(gamma) S + K-^T U. Returns O
    (N, B, H, C, Dv) fp32."""
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
        u = u0 - _mm("bhck,bhkv->bhcv", w, state, dtype)
        du = _mm("bhcs,bhcv->bhsv", p, do_n, dtype) \
            + _mm("bhck,bhkv->bhcv", kbar, ds, dtype)
        grads = (
            _mm("bhcv,bhkv->bhck", do_n, state, dtype).astype(qd.dtype),
            _mm("bhcv,bhsv->bhcs", do_n, u, dtype),
            -_mm("bhcv,bhkv->bhck", du, state, dtype),
            du,
            _mm("bhcv,bhkv->bhck", u, ds, dtype).astype(kbar.dtype),
            (ds * state).sum(-1))
        ds = gamma[..., None] * ds \
            + _mm("bhck,bhcv->bhkv", qd, do_n, dtype) \
            - _mm("bhck,bhcv->bhkv", w, du, dtype)
        return ds, grads

    return jax.lax.scan(step, jnp.zeros_like(states[0]), (xs, states, do),
                        reverse=True)[1]


_across_chunks.defvjp(_across_chunks_fwd, _across_chunks_bwd)


def kda_attention(q, k, v, log_alpha, beta, chunk: int = CHUNK):
    """Chunked gated delta-rule attention on (B, S, H, D) operands:
    ``q``, ``k`` (B, S, H, Dk), ``v`` (B, S, H, Dv), ``log_alpha`` like k
    (fp32, <= 0), ``beta`` (B, S, H). Returns o like v. Any S: the tail is
    padded with tokens that write nothing (beta 0, no decay)."""
    with jax.named_scope(scopes.KDA):
        b, s, h, _ = k.shape
        sub = min(SUB_CHUNK, chunk)
        pad = -s % chunk
        n = (s + pad) // chunk

        def chunks(x):              # (B, S, H, ...) -> (B, H, N, C, ...)
            x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
            x = x.reshape((b, n, chunk) + x.shape[2:])
            return jnp.moveaxis(x, 3, 1)

        parts = _within_chunks(
            chunks(q), chunks(k), chunks(v),
            chunks(log_alpha.astype(jnp.float32)),
            chunks(beta.astype(jnp.float32)), sub)
        o = _across_chunks(k.dtype, *(jnp.moveaxis(x, 2, 0) for x in parts))
        o = jnp.moveaxis(o, (0, 3), (1, 2))     # (B, N, C, H, Dv)
        return o.reshape(b, n * chunk, h, -1)[:, :s].astype(v.dtype)
