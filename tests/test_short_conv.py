"""The short causal convolutions of ``ops/short_conv.py`` on the CPU: the
gated one against a loop token by token, forward and gradients; its
causality; and the plain one, which ``models/solar.py``'s linear
attention calls, against what that file computed before the function
moved."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.common import scopes
from horovod_tpu.models import solar
from horovod_tpu.ops import short_conv

B, S, C = 2, 19, 12


@pytest.fixture(scope="module", params=[3, 4], ids=["taps3", "taps4"])
def operands(request):
    ks = jax.random.split(jax.random.PRNGKey(request.param), 4)
    return tuple(jax.random.normal(k, (B, S, C)) for k in ks[:3]) \
        + (jax.random.normal(ks[3], (request.param, C)),)


def _token_by_token(b, c, x, taps):
    """``y_t = c_t * sum_j taps[j] (b x)_{t - (n - 1) + j}``, a token and
    a tap at a time, nothing before the sequence."""
    n = taps.shape[0]
    rows = []
    for t in range(x.shape[1]):
        acc = jnp.zeros_like(x[:, 0])
        for j in range(n):
            at = t - (n - 1) + j
            if at >= 0:
                acc = acc + taps[j] * b[:, at] * x[:, at]
        rows.append(c[:, t] * acc)
    return jnp.stack(rows, 1)


def test_the_gated_convolution_against_a_loop_over_tokens(operands):
    got = short_conv.gated_short_conv(*operands)
    assert got.shape == (B, S, C) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, _token_by_token(*operands), atol=1e-5)
    weight = jax.random.normal(jax.random.PRNGKey(9), (B, S, C))
    got_grads = jax.grad(lambda *a: (short_conv.gated_short_conv(*a)
                                     * weight).sum(), argnums=range(4))(
                                         *operands)
    want_grads = jax.grad(lambda *a: (_token_by_token(*a) * weight).sum(),
                          argnums=range(4))(*operands)
    for got, want in zip(got_grads, want_grads):
        np.testing.assert_allclose(got, want, atol=2e-5)


def test_a_change_at_t_leaves_every_output_before_t(operands):
    b, c, x, taps = operands
    t = 7
    y = short_conv.gated_short_conv(b, c, x, taps)
    for i, changed in enumerate((b, c, x)):
        args = [b, c, x]
        args[i] = changed.at[:, t].add(3.0)
        moved = short_conv.gated_short_conv(*args, taps)
        assert (np.asarray(moved[:, :t]) == np.asarray(y[:, :t])).all()
        assert not np.allclose(moved[:, t], y[:, t])
    # and the taps reach back n - 1 tokens, no further
    moved = short_conv.gated_short_conv(b, c, x.at[:, t].add(3.0), taps)
    reach = t + taps.shape[0]
    assert not np.allclose(moved[:, reach - 1], y[:, reach - 1])
    assert (np.asarray(moved[:, reach:]) == np.asarray(y[:, reach:])).all()


def test_bf16_operands_give_bf16_from_fp32_arithmetic(operands):
    b, c, x, taps = operands
    low = [a.astype(jnp.bfloat16) for a in (b, c, x)]
    got = short_conv.gated_short_conv(*low, taps)
    assert got.dtype == jnp.bfloat16
    want = _token_by_token(*(a.astype(jnp.float32) for a in low), taps)
    # one rounding, the result's: the chain itself ran in fp32
    assert (np.asarray(got) == np.asarray(want.astype(jnp.bfloat16))).mean() \
        > 0.98
    np.testing.assert_allclose(got.astype(jnp.float32), want, rtol=1e-2,
                               atol=1e-2)


def test_the_chain_carries_its_scope_and_the_projections_do_not():
    from horovod_tpu.models import lfm2

    mixer = lfm2.ShortConv(3, jnp.float32)
    u = jnp.ones((1, 8, 16))
    params = mixer.init(jax.random.PRNGKey(0), u)
    text = jax.jit(jax.grad(lambda p: mixer.apply(p, u).sum())).lower(
        params).as_text(debug_info=True)
    lines = [line for line in text.splitlines() if "loc(" in line]
    under = [line for line in lines if scopes.SHORT_CONV in line]
    assert any("transpose(" in line for line in under)
    assert any("transpose(" not in line for line in under)
    assert not any("dot_general" in line for line in under)
    assert any("dot_general" in line and "in_proj" in line for line in lines)


def _solars_convolution_before_it_moved(x, taps):
    n = taps.shape[0]
    x = jnp.pad(x.astype(jnp.float32), ((0, 0), (n - 1, 0), (0, 0)))
    s = x.shape[1] - (n - 1)
    return sum(x[:, j:j + s] * taps[j] for j in range(n))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_solars_convolution_is_the_one_function_and_gives_what_it_gave(
        operands, dtype):
    _, _, x, taps = operands
    assert solar.causal_conv is short_conv.causal_conv
    x = x.astype(dtype)
    got, got_grads = jax.value_and_grad(
        lambda *a: (short_conv.causal_conv(*a) ** 2).sum(), (0, 1))(x, taps)
    want, want_grads = jax.value_and_grad(
        lambda *a: (_solars_convolution_before_it_moved(*a) ** 2).sum(),
        (0, 1))(x, taps)
    assert np.asarray(got) == np.asarray(want)
    for g, w in zip(got_grads, want_grads):
        assert g.dtype == w.dtype
        assert (np.asarray(g, np.float32) == np.asarray(w, np.float32)).all()
    assert str(jax.make_jaxpr(short_conv.causal_conv)(x, taps)) == str(
        jax.make_jaxpr(_solars_convolution_before_it_moved)(x, taps))
