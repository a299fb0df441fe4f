"""The short causal convolutions of ``ops/short_conv.py`` on the CPU: the
gated one against a loop token by token, forward and gradients; its
causality; the plain one, which ``models/solar.py``'s linear attention
calls, against what that file computed before the function moved; and
``conv_act`` (the convolution with its bias and SiLU before a state-space
scan): its two Pallas kernels in interpret mode and its XLA code against
one loop over tokens that shares no body with either, the path each shape
takes and the counter that says so."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.common import metrics as metrics_lib
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


# -- conv_act: silu(conv(x) + bias), two compilers ---------------------------

ROWS = short_conv._ROWS             # a tile of the kernels' tokens
WIDE = (2, 2 * ROWS, 384)           # two tiles, three blocks of 128 lanes


def _silu_conv_token_by_token(x, taps, bias):
    """``y_t = z_t / (1 + exp(-z_t))``, ``z_t = bias + sum_j taps[j]
    x_{t - (n - 1) + j}``: a scan over the tokens that holds the n - 1
    before the one it is at (zeros before the sequence), a tap at a time,
    fp32; no call into ``ops/short_conv.py``."""
    n = taps.shape[0]
    tokens = jnp.moveaxis(x.astype(jnp.float32), 1, 0)       # (S, B, C)

    def token(held, now):                           # held: (n - 1, B, C)
        seen = jnp.concatenate([held, now[None]], 0)
        z = jnp.zeros_like(now) if bias is None \
            else jnp.broadcast_to(bias, now.shape)
        for j in range(n):
            z = z + taps[j] * seen[j]
        return seen[1:], z / (1.0 + jnp.exp(-z))

    held = jnp.zeros((n - 1,) + tokens.shape[1:], jnp.float32)
    return jnp.moveaxis(jax.lax.scan(token, held, tokens)[1], 0, 1)


def _weighted(fn, weight):
    return lambda *a: (fn(*a).astype(jnp.float32) * weight).sum()


@pytest.fixture(scope="module")
def wide():
    """x, taps, bias, a weight for the loss (all four exact in bf16, so
    that bf16 operands change the arithmetic's inputs by nothing), and the
    loop's y and gradients with and without the bias."""
    ks = jax.random.split(jax.random.PRNGKey(11), 4)

    def exact(key, shape, scale=1.0):
        return (jax.random.normal(key, shape) * scale).astype(
            jnp.bfloat16).astype(jnp.float32)

    x, weight = exact(ks[0], WIDE), exact(ks[3], WIDE)
    taps, bias = exact(ks[1], (4, WIDE[2]), 0.5), exact(ks[2], WIDE[2:], 0.5)
    want = {}
    for with_bias in (True, False):
        args = (x, taps, bias) if with_bias else (x, taps)
        loop = (lambda x, taps, bias=None:
                _silu_conv_token_by_token(x, taps, bias))
        y, back = jax.vjp(loop, *args)
        want[with_bias] = (y, back(weight))
    return x, taps, bias, weight, want


@pytest.mark.parametrize("with_bias", [True, False], ids=["bias", "no_bias"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("use_pallas", [True, False], ids=["kernels", "xla"])
def test_conv_act_against_a_loop_over_tokens(wide, use_pallas, dtype,
                                             with_bias):
    """y and the gradients of x, the taps and the bias, by either path:
    fp32 arithmetic whatever the operands' dtype, one rounding at the
    end (y's and dx's, to x's dtype)."""
    x, taps, bias, weight, want = wide
    want_y, want_grads = want[with_bias]
    args = (x.astype(dtype), taps) + ((bias,) if with_bias else ())

    def fn(x, taps, bias=None):
        return short_conv.conv_act(x, taps, bias, use_pallas=use_pallas)

    got_y = fn(*args)
    assert got_y.shape == WIDE and got_y.dtype == dtype
    got_grads = jax.grad(_weighted(fn, weight), range(len(args)))(*args)
    assert got_grads[0].dtype == dtype
    assert all(g.dtype == jnp.float32 for g in got_grads[1:])
    if dtype == jnp.float32:
        np.testing.assert_allclose(got_y, want_y, atol=1e-5)
        np.testing.assert_allclose(got_grads[0], want_grads[0], atol=2e-5)
    else:
        for got, wanted in ((got_y, want_y), (got_grads[0], want_grads[0])):
            assert (np.asarray(got) == np.asarray(
                wanted.astype(jnp.bfloat16))).mean() > 0.98
            np.testing.assert_allclose(got.astype(jnp.float32), wanted,
                                       rtol=1e-2, atol=1e-2)
    # sums over 4,096 tokens of products of order one
    for got, wanted in zip(got_grads[1:], want_grads[1:]):
        np.testing.assert_allclose(got, wanted, atol=2e-3, rtol=1e-4)


def test_conv_acts_xla_code_is_what_the_model_computed_before_it_moved():
    """Off a TPU, and at every shape the kernels refuse, ``conv_act`` is
    ``models/granite.py``'s old ``_conv_act`` to the bit."""
    import flax.linen as nn

    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(ks[0], (2, 19, 12)).astype(jnp.bfloat16)
    taps, bias = jax.random.normal(ks[1], (4, 12)), jax.random.normal(
        ks[2], (12,))
    got = short_conv.conv_act(x, taps, bias)
    want = nn.silu(short_conv.causal_conv(x, taps) + bias).astype(x.dtype)
    assert got.dtype == want.dtype
    assert (np.asarray(got, np.float32) == np.asarray(want, np.float32)).all()
    none = short_conv.conv_act(x, taps)
    want = nn.silu(short_conv.causal_conv(x, taps)).astype(x.dtype)
    assert (np.asarray(none, np.float32) == np.asarray(want, np.float32)).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_kernels_carry_nothing_from_one_batch_row_to_the_next(wide, dtype):
    x, taps, bias = (wide[0].astype(dtype),) + wide[1:3]
    y = short_conv.conv_act(x, taps, bias, use_pallas=True)
    moved = short_conv.conv_act(x.at[0, -3:].add(3.0), taps, bias,
                                use_pallas=True)
    assert (np.asarray(moved[1]) == np.asarray(y[1])).all()
    assert (np.asarray(moved[0, :-3]) == np.asarray(y[0, :-3])).all()
    assert not np.allclose(moved[0, -3:].astype(jnp.float32),
                           y[0, -3:].astype(jnp.float32))
    # nor the backward, which walks the rows from their last token: row
    # 0's first tokens meet nothing of row 1's
    weight = wide[3]

    def dx(weight):
        return jax.grad(_weighted(
            lambda x: short_conv.conv_act(x, taps, bias, use_pallas=True),
            weight))(x)

    moved = dx(weight.at[1, :3].add(3.0))
    assert (np.asarray(moved[0]) == np.asarray(dx(weight)[0])).all()


# a token at a tile's edge, at a chunk's edge inside a tile, and elsewhere
@pytest.mark.parametrize("t", [ROWS - 2, ROWS, short_conv._CHUNK - 1, 777])
def test_a_change_at_t_reaches_the_kernels_next_taps_and_no_further(wide, t):
    x, taps, bias = wide[:3]
    y = short_conv.conv_act(x, taps, bias, use_pallas=True)
    moved = short_conv.conv_act(x.at[:, t].add(3.0), taps, bias,
                                use_pallas=True)
    reach = t + taps.shape[0]
    assert (np.asarray(moved[:, :t]) == np.asarray(y[:, :t])).all()
    for at in range(t, reach):
        assert not np.allclose(moved[:, at], y[:, at])
    assert (np.asarray(moved[:, reach:]) == np.asarray(y[:, reach:])).all()
    # and backward: a cotangent at t reaches the n - 1 tokens before it
    weight = jnp.zeros(WIDE).at[:, t].set(1.0)
    dx = jax.grad(_weighted(lambda x: short_conv.conv_act(
        x, taps, bias, use_pallas=True), weight))(x)
    touched = np.abs(np.asarray(dx)).sum((0, 2)) > 0
    assert touched[t - taps.shape[0] + 1:t + 1].all()
    assert not touched[:t - taps.shape[0] + 1].any()
    assert not touched[t + 1:].any()


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
    samples = metrics_lib.snapshot()[
        "hvd_tpu_short_conv_calls_total"]["samples"]
    return sum(s["value"] for s in samples
               if s["labels"].get("path") == path)


@pytest.mark.parametrize("shape, n, use_pallas, path", [
    ((1, ROWS, 256), 4, True, "pallas"),     # whole tiles, forced here
    ((2, 2 * ROWS, 128), 3, True, "pallas"),
    ((1, ROWS, 4352), 4, True, "pallas"),    # the cell's 34 tiles of lanes
    ((1, ROWS, 256), 8, True, "pallas"),
    ((1, ROWS, 256), 4, None, "xla"),        # a CPU picks the XLA code
    ((1, ROWS, 256), 4, False, "xla"),
    ((1, ROWS + 8, 256), 4, True, "xla"),    # no whole tiles of tokens
    ((1, ROWS // 2, 256), 4, True, "xla"),
    ((1, ROWS, 192), 4, True, "xla"),        # no whole tiles of lanes
    ((1, ROWS, 256), 9, True, "xla"),        # more taps than sublanes
    ((2, 32, 160), 4, True, "xla"),          # the tiny preset's kind
])
def test_the_path_follows_what_conv_act_sees_and_is_counted(
        shape, n, use_pallas, path):
    x = jnp.ones(shape, jnp.bfloat16)
    args = (x, jnp.ones((n, shape[2])), jnp.ones(shape[2:]))
    before = {p: _calls(p) for p in ("pallas", "xla")}

    def loss(*ops):
        return short_conv.conv_act(*ops, use_pallas=use_pallas).astype(
            jnp.float32).sum()

    assert _pallas_names(loss, *args) == (
        [scopes.SHORT_CONV_FWD] if path == "pallas" else [])
    other = "xla" if path == "pallas" else "pallas"
    assert _calls(path) == before[path] + 1
    assert _calls(other) == before[other]
    # differentiated: x is the only residual, one kernel a direction
    assert _pallas_names(jax.grad(loss, (0, 1, 2)), *args) == (
        list(scopes.SHORT_CONV_KERNELS) if path == "pallas" else [])


def test_the_backward_keeps_x_and_no_tensor_of_the_activations_size():
    """What the forward leaves for the backward is its three operands:
    the pre-activation is formed again in the backward's kernel."""
    x = jnp.ones((1, ROWS, 256), jnp.bfloat16)
    taps, bias = jnp.ones((4, 256)), jnp.ones((256,))
    _, back = jax.vjp(lambda *a: short_conv.conv_act(*a, use_pallas=True),
                      x, taps, bias)
    kept = sorted((leaf.shape, str(leaf.dtype))
                  for leaf in jax.tree.leaves(back)
                  if hasattr(leaf, "shape") and leaf.size > 1)
    assert kept == sorted([((1, ROWS, 256), "bfloat16"),
                           ((4, 256), "float32"), ((256,), "float32")])
