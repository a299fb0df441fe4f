"""Gated delta-rule linear attention (``ops/linear_attention.py``): the
chunked algorithm and its hand-written backward against the recurrence
token by token, on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.common import scopes
from horovod_tpu.ops import linear_attention as la


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


# S a multiple of the chunk, not a multiple, shorter than one chunk, and
# a chunk that is a single sub-chunk.
@pytest.mark.parametrize("strong", [False, True], ids=["mild", "strong"])
@pytest.mark.parametrize("s, chunk", [(128, 32), (100, 32), (37, 64),
                                      (48, 16)])
def test_chunked_forward_and_backward_equal_the_recurrence(s, chunk, strong):
    args = _operands(s, 2, s, 2, 32, strong)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)

    def both(fn):
        return jax.value_and_grad(
            lambda *a: (fn(*a) * weight).sum(), argnums=(0, 1, 2, 3, 4))(
                *args)

    with jax.default_matmul_precision("highest"):
        out = la.kda_attention(*args, chunk=chunk)
        want = la.kda_reference(*args)
        (_, got_grads), (_, want_grads) = (
            both(lambda *a: la.kda_attention(*a, chunk=chunk)),
            both(la.kda_reference))
    assert out.shape == want.shape == args[2].shape
    assert _close(out, want, 1e-5)
    for got, wanted in zip(got_grads, want_grads):
        assert _close(got, wanted, 1e-4)


def test_bf16_operands_stay_near_the_fp32_recurrence():
    args = _operands(3, 1, 192, 2, 64, False, jnp.bfloat16)
    out = la.kda_attention(*args)
    assert out.dtype == jnp.bfloat16
    assert _close(out, la.kda_reference(*args), 2e-2)


def test_the_state_crosses_chunks():
    """A token in the last chunk reads what the first chunk wrote: with
    the first chunk's values zeroed the last outputs change."""
    q, k, v, log_alpha, beta = _operands(5, 1, 128, 1, 16, False)
    out = la.kda_attention(q, k, v, log_alpha, beta, chunk=32)
    cut = la.kda_attention(q, k, v.at[:, :32].set(0.0), log_alpha, beta,
                           chunk=32)
    assert float(jnp.abs(out[:, 96:] - cut[:, 96:]).max()) > 1e-4


def test_the_backward_is_a_scan_over_chunks_not_over_tokens():
    """The differentiated program holds scans of S / chunk steps (the
    forward's and the hand-written reverse one) and none of S."""
    args = _operands(7, 1, 256, 1, 16, False)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: la.kda_attention(*a, chunk=32).sum(),
        argnums=(0, 1, 2, 3, 4)))(*args)
    lengths = []

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "scan":
                lengths.append((eqn.params["length"],
                                eqn.params["reverse"]))
            for value in eqn.params.values():
                for sub in value if isinstance(value, (list, tuple)) \
                        else [value]:
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)

    walk(jaxpr.jaxpr)
    assert (8, False) in lengths and (8, True) in lengths
    assert all(n == 8 for n, _ in lengths)


def test_the_work_carries_the_scope():
    args = _operands(1, 1, 64, 1, 16, False)
    text = jax.jit(la.kda_attention).lower(*args).as_text(debug_info=True)
    assert scopes.KDA in text
