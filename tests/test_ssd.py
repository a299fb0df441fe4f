"""``ops/ssd.py``: the chunked state-space scan (Mamba-2's SSD) against
the recurrence run token by token, values and every gradient, at chunks
that do and do not divide the sequence, one chunk and several, one group
and several, under a decay strong enough that a factored ``exp(g_t) *
exp(-g_s)`` overflows, and with bf16 operands against fp32; and the two
Pallas kernels that run the chunked form on a TPU (interpret mode here),
at the state-space cell's shape cut in length, against both."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.common import metrics as metrics_lib
from horovod_tpu.common import scopes
from horovod_tpu.ops import ssd

B, S, H, P, N = 2, 50, 4, 8, 16
OPERANDS = ("x", "dt", "a", "b", "c", "d", "dt_bias")
# the state-space cell's scan (64 heads of 64 over a state of 128, one
# group, chunks of 256) cut to two chunks, and to two blocks of 8 heads
# where the recurrence token by token is differentiated as well
CELL = dict(batch=1, length=512, heads=64, width=64, state=128)
TWO_BLOCKS = dict(CELL, heads=16)


def _operands(groups=1, seed=0, dtype=jnp.float32, decay=1.0, batch=B,
              length=S, heads=H, width=P, state=N):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(k[0], (batch, length, heads, width))
    dt = jax.random.normal(k[1], (batch, length, heads))
    a = -jnp.exp(jax.random.uniform(k[2], (heads,), minval=0.0, maxval=2.7))
    b = jax.random.normal(k[3], (batch, length, groups, state))
    c = jax.random.normal(k[4], (batch, length, groups, state))
    d = jax.random.normal(k[5], (heads,))
    dt_bias = jax.random.normal(k[6], (heads,)) - 2.0
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


# -- the Pallas kernels (interpret mode off a TPU) ---------------------------

def _kernels(*ops):
    return ssd.ssd_scan(*ops, use_pallas=True)


def _xla(*ops):
    return ssd.ssd_scan(*ops, use_pallas=False)


def _gradients(fn, args, weights):
    return jax.jit(jax.grad(
        lambda *ops: (fn(*ops).astype(jnp.float32) * weights).sum(),
        argnums=tuple(range(len(OPERANDS)))))(*args)


# fp32 operands: the kernels are the recurrence to fp32's rounding (the
# sums over a head's tokens, dA and d dt_bias, to a few 1e-5: the XLA
# code's own read 1.4e-5 and 3.0e-5 there). bf16 operands: to the
# products' rounding, as the XLA code is.
@pytest.mark.parametrize("dtype, length, tol, sums_tol", [
    (jnp.float32, 512, 1e-5, 1e-4), (jnp.float32, 300, 1e-5, 1e-4),
    (jnp.bfloat16, 512, 1e-2, 1e-2), (jnp.bfloat16, 300, 1e-2, 1e-2)])
def test_the_kernels_are_the_recurrence_values_and_every_gradient(
        dtype, length, tol, sums_tol):
    """Two blocks of 8 heads over two chunks (300: the second one padded),
    against the recurrence token by token on the same operands."""
    shape = dict(TWO_BLOCKS, length=length)
    args = _operands(dtype=dtype, **shape)
    got = jax.jit(_kernels)(*args)
    assert got.dtype == dtype and got.shape == args[0].shape
    assert _close(got, jax.jit(ssd.ssd_reference)(*args), tol)
    weights = jnp.cos(jnp.arange(length * 64, dtype=jnp.float32)).reshape(
        length, 1, 64)
    want = _gradients(ssd.ssd_reference, args, weights)
    grads = _gradients(_kernels, args, weights)
    for name, g, w, operand in zip(OPERANDS, grads, want, args):
        assert g.dtype == operand.dtype and g.shape == operand.shape, name
        assert float(jnp.abs(w).max()) > 0, name
        assert _close(g, w, sums_tol if name in ("a", "dt_bias") else tol), \
            name


@pytest.mark.parametrize("dtype, length, tol", [
    (jnp.float32, 512, 1e-4), (jnp.bfloat16, 512, 1e-2),
    (jnp.bfloat16, 300, 1e-2)])
def test_the_kernels_and_the_xla_code_agree_at_the_cells_shape(dtype, length,
                                                                tol):
    """All 64 heads (eight blocks) of the cell's scan, values and every
    gradient against the chunked XLA code on the same operands; the
    values against the recurrence too."""
    args = _operands(dtype=dtype, **dict(CELL, length=length))
    got = jax.jit(_kernels)(*args)
    assert _close(got, jax.jit(_xla)(*args), tol)
    assert _close(got, jax.jit(ssd.ssd_reference)(*args), tol)
    weights = jnp.sin(jnp.arange(length * 64, dtype=jnp.float32)).reshape(
        length, 1, 64)
    want = _gradients(_xla, args, weights)
    for name, g, w in zip(OPERANDS, _gradients(_kernels, args, weights),
                          want):
        assert bool(jnp.isfinite(g.astype(jnp.float32)).all()), name
        assert _close(g, w, tol), name


def test_a_strong_decay_does_not_overflow_in_the_kernels():
    """``test_a_strong_decay_does_not_overflow...``'s operands (``delta
    A`` of some -90 a token, g past -200 within 16 tokens and past -20,000
    within a chunk) through the kernels: the exponent of a pair is
    clamped at 0 before ``exp``, the state is carried by factors <= 1."""
    x, dt, a, b, c, d, dt_bias = _operands(decay=40.0, **TWO_BLOCKS)
    dt = dt + 8.0
    g = jnp.cumsum(jax.nn.softplus(dt + dt_bias) * a, 1)
    assert float(-g[:, 15].max()) > 200
    want = ssd.ssd_reference(x, dt, a, b, c, d, dt_bias)
    got = _kernels(x, dt, a, b, c, d, dt_bias)
    assert bool(jnp.isfinite(got).all()) and _close(got, want, 1e-5)
    grads = jax.grad(lambda *ops: _kernels(*ops).sum(),
                     argnums=tuple(range(7)))(x, dt, a, b, c, d, dt_bias)
    assert all(bool(jnp.isfinite(v).all()) for v in grads)


def test_without_a_bias_the_kernels_read_dt_alone():
    x, dt, a, b, c, d, dt_bias = _operands(**dict(TWO_BLOCKS, length=256))
    want = _xla(x, dt + dt_bias, a, b, c, d)
    assert _close(_kernels(x, dt + dt_bias, a, b, c, d), want, 1e-5)
    assert _close(_kernels(x, dt, a, b, c, d, dt_bias), want, 1e-5)


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) \
                    else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


def _pallas_names(fn, *args):
    return [eqn.params["name"]
            for eqn in _equations(jax.make_jaxpr(fn)(*args).jaxpr)
            if eqn.primitive.name == "pallas_call"]


def _calls(path):
    samples = metrics_lib.snapshot()["hvd_tpu_ssd_calls_total"]["samples"]
    return sum(s["value"] for s in samples
               if s["labels"].get("path") == path)


@pytest.mark.parametrize("shape, groups, chunk, use_pallas, path", [
    (TWO_BLOCKS, 1, 256, True, "pallas"),   # the cell's kind, forced here
    (TWO_BLOCKS, 1, 256, None, "xla"),      # a CPU picks the XLA code
    (TWO_BLOCKS, 1, 256, False, "xla"),
    (TWO_BLOCKS, 1, 128, True, "xla"),      # a chunk never run on the chip
    (TWO_BLOCKS, 2, 256, True, "xla"),      # two groups of B and C
    (dict(TWO_BLOCKS, heads=12), 1, 256, True, "xla"),  # no whole blocks
    (dict(TWO_BLOCKS, width=32), 1, 256, True, "xla"),
    (dict(TWO_BLOCKS, state=64), 1, 256, True, "xla"),  # half a lane tile
    (dict(batch=B, length=S, heads=H, width=P, state=N), 1, 8, True,
     "xla"),                                # the tiny preset's kind
])
def test_the_path_follows_what_the_scan_sees_and_is_counted(
        shape, groups, chunk, use_pallas, path):
    args = _operands(groups, **dict(shape, length=64))
    before = {p: _calls(p) for p in ("pallas", "xla")}

    def loss(*ops):
        return ssd.ssd_scan(*ops, chunk=chunk, use_pallas=use_pallas).sum()

    assert _pallas_names(loss, *args) == (
        [scopes.SSD_FWD] if path == "pallas" else [])
    other = "xla" if path == "pallas" else "pallas"
    assert _calls(path) == before[path] + 1
    assert _calls(other) == before[other]
    # differentiated: the forward that keeps the states, and the backward
    assert _pallas_names(jax.grad(loss), *args) == (
        list(scopes.SSD_KERNELS) if path == "pallas" else [])


def test_the_kernels_carry_the_state_across_chunks():
    """A token of the second chunk reads what the first chunk wrote."""
    args = _operands(**TWO_BLOCKS)
    out = _kernels(*args)
    cut = _kernels(args[0].at[:, :256].set(0.0), *args[1:])
    assert float(jnp.abs(out[:, 256:] - cut[:, 256:]).max()) > 1e-4
    assert float(jnp.abs(out[:, :256] - cut[:, :256]).max()) > 1e-4
