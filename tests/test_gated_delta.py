"""The scalar-gated delta rule (``ops/linear_attention.py``
``gated_delta_attention``: Gated DeltaNet, one decay a head, keys and
values of widths of their own) on the CPU: the chunked algorithm and the
backward it shares with KDA against ``kda_reference`` fed the scalar
broadcast over the key channels, an oracle the new code shares nothing
with; what it shares with ``kda_attention`` and what it leaves alone."""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.common import metrics as metrics_lib
from horovod_tpu.common import scopes
from horovod_tpu.ops import linear_attention as la

DK, DV = 8, 16      # the widths differ, as the published 96 and 192 do


def _operands(seed, s, strong=False, b=2, h=3, dtype=jnp.float32):
    """q, k L2-normalised, v, one log decay <= 0 a head, beta in (0, 2).
    ``strong``: decays down to e^-400 a token."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k = (jax.random.normal(key, (b, s, h, DK)) for key in ks[:2])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * DK ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, s, h, DV))
    log_decay = -jnp.exp(jax.random.normal(ks[3], (b, s, h))
                         + (2.0 if strong else -3.0))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), log_decay, beta


def _oracle(q, k, v, log_decay, beta):
    """The per-channel recurrence token by token, every channel of a head
    given the head's scalar."""
    return la.kda_reference(
        q, k, v, jnp.broadcast_to(log_decay[..., None], k.shape), beta)


def _close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.isfinite(got).all()
    return np.linalg.norm(got - want) <= tol * np.linalg.norm(want)


def _value_and_grads(fn, args, weight):
    return jax.value_and_grad(
        lambda *a: (fn(*a).astype(jnp.float32) * weight).sum(),
        argnums=(0, 1, 2, 3, 4))(*args)


# S whole chunks (the state crosses three edges), a tail that needs
# padding, shorter than one chunk; mild decays and ones the factored form
# exp(g) exp(-g) would overflow on.
@pytest.mark.parametrize("strong", [False, True], ids=["mild", "strong"])
@pytest.mark.parametrize("s, chunk", [(32, 8), (37, 8), (20, 32)])
def test_chunked_forward_and_backward_equal_the_recurrence(s, chunk, strong):
    args = _operands(s, s, strong)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)

    def attention(*a):
        return la.gated_delta_attention(*a, chunk=chunk)

    with jax.default_matmul_precision("highest"):
        (_, got_grads), (_, want_grads) = (
            jax.jit(lambda *a, fn=fn: _value_and_grads(fn, a, weight))(*args)
            for fn in (attention, _oracle))
        out, want = attention(*args), _oracle(*args)
    assert out.shape == want.shape == args[2].shape
    assert _close(out, want, 1e-5)
    assert len(got_grads) == 5
    for got, wanted, like in zip(got_grads, want_grads, args):
        assert got.shape == like.shape      # the decay's: one a head
        assert _close(got, wanted, 1e-4)


def test_bf16_operands_stay_near_the_fp32_recurrence():
    args = _operands(3, 48, dtype=jnp.bfloat16)
    out = la.gated_delta_attention(*args, chunk=16)
    assert out.dtype == jnp.bfloat16
    assert _close(out, _oracle(*args), 2e-2)


def test_the_state_crosses_chunks():
    q, k, v, log_decay, beta = _operands(5, 32, b=1, h=1)
    out = la.gated_delta_attention(q, k, v, log_decay, beta, chunk=8)
    cut = la.gated_delta_attention(q, k, v.at[:, :8].set(0.0), log_decay,
                                   beta, chunk=8)
    assert float(jnp.abs(out[:, 24:] - cut[:, 24:]).max()) > 1e-4


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) \
                    else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


def test_the_pair_matrices_are_one_matmul_a_chunk_and_the_rest_is_kdas():
    """No sub-chunks (no exponent is positive without them): the
    differentiated program holds no operand with a sub-chunk axis, its
    scans are the S / chunk steps of KDA's ``_across_chunks`` and its
    hand-written reverse, and one triangular solve serves W and U0."""
    args = _operands(7, 64, b=1, h=1)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: la.gated_delta_attention(*a, chunk=32).sum(),
        argnums=(0, 1, 2, 3, 4)))(*args)
    eqns = list(_equations(jaxpr.jaxpr))
    scans = [(e.params["length"], e.params["reverse"]) for e in eqns
             if e.primitive.name == "scan"]
    assert sorted(scans) == [(2, False), (2, True)]
    forward = list(_equations(jax.make_jaxpr(
        lambda *a: la.gated_delta_attention(*a, chunk=32))(*args).jaxpr))
    assert sum(e.primitive.name == "triangular_solve" for e in forward) == 1
    # (1, 1, 2, 32, 32): a chunk's square, never (.., sub, .., sub, D)
    assert max(v.aval.ndim for e in forward for v in e.outvars) <= 6
    source = inspect.getsource(la._within_chunks_scalar)
    assert "_solve(" in source and "_pair_matrices" not in source
    assert "_solve(" in inspect.getsource(la._within_chunks)
    assert "_across_chunks(" in inspect.getsource(la._chunked_xla)
    for entry in (la.kda_attention, la.gated_delta_attention):
        assert "_chunked_xla(" in inspect.getsource(entry)


def _calls(name, path):
    samples = metrics_lib.snapshot().get(name, {}).get("samples", [])
    return sum(s["value"] for s in samples
               if s["labels"].get("path") == path)


def test_the_call_is_counted_beside_kdas_and_carries_its_own_scope():
    args = _operands(1, 16, b=1, h=1)
    before = (_calls("hvd_tpu_gdn_calls_total", "xla"),
              _calls("hvd_tpu_kda_calls_total", "xla"))
    text = jax.jit(lambda *a: la.gated_delta_attention(
        *a, chunk=8)).lower(*args).as_text(debug_info=True)
    assert _calls("hvd_tpu_gdn_calls_total", "xla") == before[0] + 1
    assert _calls("hvd_tpu_kda_calls_total", "xla") == before[1]
    assert _calls("hvd_tpu_gdn_calls_total", "pallas") == 0
    assert scopes.GDN + "/" in text and scopes.KDA not in text
    assert scopes.GDN == "hvd_gdn" and scopes.KDA not in scopes.GDN
    assert "pallas_call" not in str(jax.make_jaxpr(
        lambda *a: la.gated_delta_attention(*a, chunk=8))(*args))
