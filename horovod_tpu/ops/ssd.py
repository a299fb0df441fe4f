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

XLA code; everything carries the scope ``hvd_ssd`` (``common/scopes.py``),
and a kernel that takes its place is named with the scope as its prefix
(``hvd_ssd_fwd``), so that the readers of a trace find either. The pair
matrices (B, S / Q, H, Q, Q) are the traffic: 0.5 GiB in fp32 at S 8192,
64 heads, Q 256.
"""

import jax
import jax.numpy as jnp

from ..common import scopes

CHUNK = 256


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


def ssd_scan(x, dt, a, b, c, d, dt_bias=None, chunk: int = CHUNK):
    """The chunked form of ``ssd_reference``, same operands; returns y in
    x's dtype. A sequence that ``chunk`` does not divide is padded with
    tokens of step size 0, which neither decay nor write the state."""
    with jax.named_scope(scopes.SSD):
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
