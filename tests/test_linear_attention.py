"""Gated delta-rule linear attention (``ops/linear_attention.py``): the
chunked algorithm and its hand-written backward against the recurrence
token by token, on the CPU: the XLA code at a small width, and the Pallas
kernels' bodies in interpret mode at the width the kernels take (128
lanes a head)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.common import metrics as metrics_lib
from horovod_tpu.common import scopes
from horovod_tpu.ops import linear_attention as la

# path -> (use_pallas, head width): the XLA code is what a CPU runs by
# itself (and what a width the kernels do not take falls back to); the
# kernels are forced, so they run in interpret mode here.
PATHS = {"xla": (False, 32), "pallas": (True, 128)}


def _operands(seed, b, s, h, d, strong, dtype=jnp.float32):
    """q, k L2-normalised, v, log alpha <= 0, beta in (0, 2). ``strong``:
    decays down to e^-400 a token, which the factored form of the pair
    matrices would overflow on."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v = (jax.random.normal(key, (b, s, h, d)) for key in ks[:3])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    log_alpha = -jnp.exp(jax.random.normal(ks[3], (b, s, h, d))
                         + (2.0 if strong else -3.0))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), log_alpha,
            beta)


def _close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.isfinite(got).all()
    return np.linalg.norm(got - want) <= tol * np.linalg.norm(want)


def _value_and_grads(fn, args, weight):
    return jax.value_and_grad(
        lambda *a: (fn(*a).astype(jnp.float32) * weight).sum(),
        argnums=(0, 1, 2, 3, 4))(*args)


# One compile a call and a shape, shared by the cases that read it (the
# mild and the strong decays, the seeds): run op by op, every case paid
# for its own few hundred small programs.
@functools.partial(jax.jit, static_argnames=("chunk", "use_pallas"))
def _attention(args, weight, chunk=la.CHUNK, use_pallas=None):
    """``(o, (sum, five gradients))`` of ``kda_attention``."""
    def fn(*a):
        return la.kda_attention(*a, chunk=chunk, use_pallas=use_pallas)
    return fn(*args), _value_and_grads(fn, args, weight)


@jax.jit
def _recurrence(args, weight):
    """The same of the recurrence token by token."""
    return la.kda_reference(*args), _value_and_grads(la.kda_reference, args,
                                                     weight)


# S two whole chunks (of two sub-chunks each), a tail that is no whole
# chunk, shorter than one chunk, and a chunk that is a single sub-chunk.
@pytest.mark.parametrize("strong", [False, True], ids=["mild", "strong"])
@pytest.mark.parametrize("s, chunk", [(64, 32), (40, 32), (37, 64),
                                      (32, 16)])
@pytest.mark.parametrize("path", sorted(PATHS))
def test_chunked_forward_and_backward_equal_the_recurrence(path, s, chunk,
                                                           strong):
    use_pallas, width = PATHS[path]
    args = _operands(s, 2 if path == "xla" else 1, s, 2, width, strong)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    with jax.default_matmul_precision("highest"):
        out, (_, got_grads) = _attention(args, weight, chunk=chunk,
                                         use_pallas=use_pallas)
        want, (_, want_grads) = _recurrence(args, weight)
    assert out.shape == want.shape == args[2].shape
    assert _close(out, want, 1e-5)
    for got, wanted in zip(got_grads, want_grads):
        assert _close(got, wanted, 1e-4)


@pytest.mark.parametrize("path", sorted(PATHS))
def test_bf16_operands_stay_near_the_fp32_recurrence(path):
    use_pallas, width = PATHS[path]
    args = _operands(3, 1, 96, 2, max(width, 64), False, jnp.bfloat16)
    out = jax.jit(lambda *a: la.kda_attention(
        *a, use_pallas=use_pallas))(*args)
    assert out.dtype == jnp.bfloat16
    assert _close(out, jax.jit(la.kda_reference)(*args), 2e-2)


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 2e-2)],
                         ids=["fp32", "bf16"])
def test_the_kernels_and_the_xla_code_agree_on_the_same_operands(dtype, tol):
    """One algorithm, two compilers: the cell's chunk of 64 at its width,
    two heads, a chunk and a half, forward and the five gradients."""
    args = _operands(11, 1, 96, 2, 128, True, dtype)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    with jax.default_matmul_precision("highest"):
        (got, (_, got_grads)), (want, (_, want_grads)) = (
            _attention(args, weight, use_pallas=use)
            for use in (True, False))
    assert _close(got, want, tol)
    for g, w, like in zip(got_grads, want_grads, args):
        assert g.dtype == like.dtype and g.shape == like.shape
        assert _close(g, w, 5 * tol)


# d beta of the kernels against the XLA code's, both on bf16 operands. The
# two round the same matmul operands to bf16, so they differ by what a
# rounding flips: read 6.4e-7 and 3.3e-6 (strong decays), 2.3e-5 and
# 3.7e-5 (mild) over two seeds each at S 160. With the solve's gradient
# dA = -dR [W | U0]^T taken on bf16 operands (the XLA code's comes from
# JAX's rule for the triangular solve, fp32 at the highest precision) the
# same four read 2.1e-5, 2.8e-5, 5.0e-4 and 5.2e-4: each limit lies between.
@pytest.mark.parametrize("seed, strong, tol", [(11, True, 1e-5),
                                               (12, False, 1.5e-4)],
                         ids=["strong", "mild"])
def test_the_solves_gradient_keeps_fp32_under_bf16_operands(seed, strong,
                                                            tol):
    args = _operands(seed, 1, 96, 2, 128, strong, jnp.bfloat16)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    with jax.default_matmul_precision("highest"):
        got, want = (_attention(args, weight, use_pallas=use)[1][1][4]
                     for use in (True, False))
    assert got.dtype == jnp.float32
    assert _close(got, want, tol)


def test_a_head_of_two_lane_tiles_runs_the_kernels():
    args = _operands(13, 1, 48, 1, 256, False)
    weight = jnp.ones(args[2].shape)
    with jax.default_matmul_precision("highest"):
        got, (_, got_grads) = _attention(args, weight, chunk=32,
                                         use_pallas=True)
        want, (_, want_grads) = _recurrence(args, weight)
    assert _close(got, want, 1e-5)
    for g, w in zip(got_grads, want_grads):
        assert _close(g, w, 1e-4)


def _equations(jaxpr):
    """Every equation of a jaxpr, inner jaxprs included."""
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
    samples = metrics_lib.snapshot()["hvd_tpu_kda_calls_total"]["samples"]
    return sum(s["value"] for s in samples
               if s["labels"].get("path") == path)


@pytest.mark.parametrize("width, chunk, use_pallas, path", [
    (128, 64, True, "pallas"),      # what the cell runs, forced off a TPU
    (128, 64, None, "xla"),         # a CPU picks the XLA code by itself
    (32, 64, True, "xla"),          # a width that fills no lane tile
    (128, 48, True, "xla"),         # three sub-chunks: no power of two
    (128, 8, True, "xla"),          # a chunk shorter than a sub-chunk
    (128, 16, True, "xla"),         # one sub-chunk: nothing to join
    (128, 256, True, "xla"),        # more VMEM than a core has
])
def test_the_path_follows_what_the_code_sees_and_is_counted(
        width, chunk, use_pallas, path):
    args = _operands(1, 1, 96, 1, width, False)
    before = {p: _calls(p) for p in ("pallas", "xla")}
    names = _pallas_names(
        lambda *a: la.kda_attention(*a, chunk=chunk, use_pallas=use_pallas),
        *args)
    assert names == ([scopes.KDA_FWD] if path == "pallas" else [])
    other = "xla" if path == "pallas" else "pallas"
    assert _calls(path) == before[path] + 1
    assert _calls(other) == before[other]


@pytest.mark.parametrize("path", sorted(PATHS))
def test_the_state_crosses_chunks(path):
    """A token in the last chunk reads what the first chunk wrote: with
    the first chunk's values zeroed the last outputs change."""
    use_pallas, width = PATHS[path]
    q, k, v, log_alpha, beta = _operands(5, 1, 96, 1, width, False)
    attention = jax.jit(lambda *a: la.kda_attention(
        *a, chunk=32, use_pallas=use_pallas))
    out = attention(q, k, v, log_alpha, beta)
    cut = attention(q, k, v.at[:, :32].set(0.0), log_alpha, beta)
    assert float(jnp.abs(out[:, 64:] - cut[:, 64:]).max()) > 1e-4


def test_the_backward_is_a_scan_over_chunks_not_over_tokens():
    """The differentiated program holds scans of S / chunk steps (the
    forward's and the hand-written reverse one) and none of S."""
    args = _operands(7, 1, 256, 1, 16, False)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: la.kda_attention(*a, chunk=32).sum(),
        argnums=(0, 1, 2, 3, 4)))(*args)
    lengths = [(eqn.params["length"], eqn.params["reverse"])
               for eqn in _equations(jaxpr.jaxpr)
               if eqn.primitive.name == "scan"]
    assert (8, False) in lengths and (8, True) in lengths
    assert all(n == 8 for n, _ in lengths)


@pytest.mark.parametrize("path", sorted(PATHS))
def test_the_work_carries_the_scope(path):
    use_pallas, width = PATHS[path]
    args = _operands(1, 1, 64, 1, width, False)
    text = jax.jit(lambda *a: la.kda_attention(
        *a, use_pallas=use_pallas)).lower(*args).as_text(debug_info=True)
    assert scopes.KDA + "/" in text


def test_the_kernels_backward_is_one_call_and_differentiates_nothing_inside():
    """jax.grad of the kernel path: the forward call that keeps the
    chunk-start states and one backward call; no scan, no triangular
    solve and no while loop is left for XLA."""
    args = _operands(7, 1, 128, 2, 128, False)
    grad = jax.grad(lambda *a: la.kda_attention(*a, use_pallas=True).sum(),
                    argnums=(0, 1, 2, 3, 4))
    assert _pallas_names(grad, *args) == [scopes.KDA_FWD, scopes.KDA_BWD]
    text = str(jax.make_jaxpr(grad)(*args))
    outside = text.split("pallas_call")[0]
    assert "scan" not in outside and "triangular_solve" not in text
