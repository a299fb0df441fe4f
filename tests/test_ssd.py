"""``ops/ssd.py``: the chunked state-space scan (Mamba-2's SSD) against
the recurrence run token by token, values and every gradient, at chunks
that do and do not divide the sequence, one chunk and several, one group
and several, under a decay strong enough that a factored ``exp(g_t) *
exp(-g_s)`` overflows, and with bf16 operands against fp32."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import ssd

B, S, H, P, N = 2, 50, 4, 8, 16
OPERANDS = ("x", "dt", "a", "b", "c", "d", "dt_bias")


def _operands(groups=1, seed=0, dtype=jnp.float32, decay=1.0):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(k[0], (B, S, H, P))
    dt = jax.random.normal(k[1], (B, S, H))
    a = -jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.7))
    b = jax.random.normal(k[3], (B, S, groups, N))
    c = jax.random.normal(k[4], (B, S, groups, N))
    d = jax.random.normal(k[5], (H,))
    dt_bias = jax.random.normal(k[6], (H,)) - 2.0
    return (x.astype(dtype), dt.astype(dtype), decay * a, b.astype(dtype),
            c.astype(dtype), d, dt_bias)


def _close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) <= tol * np.linalg.norm(want)


# chunks that divide S (25, 50, 1), that do not (16, 7), one chunk (50,
# 64: longer than the sequence) and a chunk a token; one group and four
@pytest.mark.parametrize("groups, chunk", [
    (1, 16), (1, 25), (1, 50), (1, 7), (1, 64), (1, 1), (4, 16), (4, 7)])
def test_the_chunked_scan_is_the_recurrence(chunk, groups):
    args = _operands(groups)
    want = jax.jit(ssd.ssd_reference)(*args)
    got = jax.jit(lambda *ops: ssd.ssd_scan(*ops, chunk=chunk))(*args)
    assert got.shape == want.shape == (B, S, H, P)
    assert got.dtype == jnp.float32
    assert _close(got, want, 2e-6)


@pytest.mark.parametrize("chunk", [16, 7])
def test_every_gradient_is_the_recurrences(chunk):
    args = _operands()
    weights = jnp.cos(jnp.arange(S * P, dtype=jnp.float32)).reshape(S, 1, P)

    def total(fn):
        return jax.grad(lambda *ops: (fn(*ops) * weights).sum(),
                        argnums=tuple(range(len(OPERANDS))))(*args)

    want = jax.jit(lambda: total(ssd.ssd_reference))()
    got = jax.jit(lambda: total(
        lambda *ops: ssd.ssd_scan(*ops, chunk=chunk)))()
    for name, g, w in zip(OPERANDS, got, want):
        assert float(jnp.abs(w).max()) > 0, name
        assert _close(g, w, 1e-5), name


def test_without_a_bias_dt_goes_through_the_softplus_alone():
    x, dt, a, b, c, d, dt_bias = _operands()
    want = ssd.ssd_reference(x, dt + dt_bias, a, b, c, d)
    assert _close(ssd.ssd_scan(x, dt + dt_bias, a, b, c, d, chunk=16), want,
                  2e-6)
    assert _close(ssd.ssd_scan(x, dt, a, b, c, d, dt_bias, 16), want, 2e-6)
    # the skip is D x, a head at a time
    skip = ssd.ssd_scan(x, dt, a, b, c, d, dt_bias, 16) \
        - ssd.ssd_scan(x, dt, a, b, c, jnp.zeros_like(d), dt_bias, 16)
    assert _close(skip, d[:, None] * x, 1e-5)


def test_a_strong_decay_does_not_overflow_and_the_factored_form_would():
    """``delta A`` of some -90 a token: ``exp(-g)`` leaves fp32 within a
    chunk (e^88 is its largest), the masked difference never does."""
    x, dt, a, b, c, d, dt_bias = _operands(decay=40.0)
    dt = dt + 8.0
    g = jnp.cumsum(jax.nn.softplus(dt + dt_bias) * a, 1)
    assert float(-g[:, 15].max()) > 200
    want = ssd.ssd_reference(x, dt, a, b, c, d, dt_bias)
    got = ssd.ssd_scan(x, dt, a, b, c, d, dt_bias, 16)
    assert bool(jnp.isfinite(got).all()) and _close(got, want, 1e-5)
    grads = jax.grad(lambda *ops: ssd.ssd_scan(*ops, chunk=16).sum(),
                     argnums=(0, 1, 2))(x, dt, a, b, c, d, dt_bias)
    assert all(bool(jnp.isfinite(v).all()) for v in grads)
    # the same strength through a factored pair matrix, exp(g_t) exp(-g_s)
    # inside one chunk of 16: not a number, where the masked difference
    # ssd_scan exponentiates is at most 1
    g = g[0, :16, 0]
    low = jnp.arange(16)[:, None] >= jnp.arange(16)[None, :]
    factored = jnp.where(low, jnp.exp(g)[:, None] * jnp.exp(-g)[None, :], 0.0)
    assert not bool(jnp.isfinite(factored).all())
    masked = jnp.exp(jnp.where(low, g[:, None] - g[None, :], -jnp.inf))
    assert bool(jnp.isfinite(masked).all()) and float(masked.max()) == 1.0


def test_the_carry_between_chunks_is_summed_term_by_term():
    """A long sequence of strongly decaying chunks: the chunks' totals run
    into the thousands, the exponent between neighbours stays small and
    exact."""
    totals = jnp.full((3, 200), -700.0).at[:, 100].set(-1e-3)
    sums = ssd._sums_between(totals)
    assert sums.shape == (3, 200, 200)
    assert float(sums[0, 100, 99]) == pytest.approx(-1e-3, rel=1e-6)
    assert float(sums[0, 101, 99]) == pytest.approx(-700.001, rel=1e-6)
    assert np.all(np.asarray(sums[0])[np.triu_indices(200, 1)] == -np.inf)
    assert np.all(np.diagonal(np.asarray(sums[0])) == 0.0)
    # a difference of running totals loses it
    run = jnp.cumsum(totals[0])
    assert abs(float(run[100] - run[99]) + 1e-3) > 1e-4


@pytest.mark.parametrize("chunk", [16, 7])
def test_bf16_operands_agree_with_fp32_to_bf16s_rounding(chunk):
    args = _operands()
    want = ssd.ssd_reference(*args)
    low = _operands(dtype=jnp.bfloat16)
    got = ssd.ssd_scan(*low, chunk=chunk)
    assert got.dtype == jnp.bfloat16
    assert _close(got, want, 2e-2) and not _close(got, want, 1e-4)
    # against the recurrence on the same rounded operands: the products'
    # rounding alone
    assert _close(got, ssd.ssd_reference(*low), 1e-2)


def test_the_scan_is_under_its_scope_and_has_no_loop():
    from horovod_tpu.common import scopes

    args = _operands()
    text = jax.jit(jax.grad(
        lambda x: ssd.ssd_scan(x, *args[1:], chunk=16).sum())).lower(
            args[0]).as_text(debug_info=True)
    names = set(re.findall(r'"(jit\([^"]*)"', text))
    assert scopes.SSD == "hvd_ssd"
    under = [n for n in names if scopes.SSD in n]
    assert any("transpose(" in n for n in under)
    assert any("transpose(" not in n for n in under)
    assert "while" not in text
