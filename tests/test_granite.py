"""``models/granite.py`` and the data it asked of the shared classes:
``RotaryGQA`` without a rotation and at a softmax scale of its own,
``Lfm2Layer`` with a residual scale, ``head_losses`` with a logit scale,
each against a formula written out here and each leaving the traced
program alone at its default; ``Mamba2Mixer`` against the recurrence run
token by token."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import granite, lfm2, looplm
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops import short_conv, ssd


def _close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) <= tol * np.linalg.norm(want)


def _attention_by_hand(params, x, heads, kv_heads, width, scale):
    p = params["params"]
    b, s, _ = x.shape
    q = (x @ p["q"]["kernel"]).reshape(b, s, heads, width)
    k, v = ((x @ p[n]["kernel"]).reshape(b, s, kv_heads, width)
            for n in ("k", "v"))
    k, v = (jnp.repeat(t, heads // kv_heads, 2) for t in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    return o.reshape(b, s, -1) @ p["o"]["kernel"]


# (rotation, scale): no positions at the head's own scale, at Granite's
# 1/64 on heads of 16 (the ratio 1/16, a power of two) and at a scale
# whose ratio is none
@pytest.mark.parametrize("scale", [None, 0.015625, 0.3])
def test_attention_without_a_rotation_at_a_scale_of_its_own(scale):
    attention = lfm2.RotaryGQA(4, 2, 16, dtype=jnp.float32,
                               rotation=lfm2.NO_ROTATION, qk_norm=False,
                               scale=scale)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, 32))
    params = attention.init(jax.random.PRNGKey(3), x)
    assert set(params["params"]) == {"q", "k", "v", "o"}
    with jax.default_matmul_precision("highest"):
        got = attention.apply(params, x)
        want = _attention_by_hand(params, x, 4, 2, 16,
                                  16 ** -0.5 if scale is None else scale)
    assert _close(got, want, 1e-5)
    # no positions: the order of the earlier tokens is nothing to the last
    swapped = x.at[:, [3, 7]].set(x[:, [7, 3]])
    np.testing.assert_allclose(attention.apply(params, swapped)[:, -1],
                               got[:, -1], rtol=2e-5, atol=2e-6)
    # a rotation sees it
    rotated = attention.clone(rotation=None, rope_base=100.0)
    turned = rotated.apply(params, x)
    assert not np.allclose(rotated.apply(params, swapped)[:, -1],
                           turned[:, -1], rtol=1e-3, atol=1e-4)
    assert not _close(turned, got, 1e-3)


def test_the_scale_is_exact_in_bf16_where_its_ratio_is_a_power_of_two():
    """1/64 on heads of 64 is 1/8 of the kernels' own 1/8: q times 1/8
    loses no bit of a bf16 value."""
    q = jax.random.normal(jax.random.PRNGKey(0), (4096,)).astype(
        jnp.bfloat16)
    scaled = (q.astype(jnp.float32) * (0.015625 * 64 ** 0.5)).astype(
        jnp.bfloat16)
    assert np.array_equal(np.asarray(scaled.astype(jnp.float32)) * 8,
                          np.asarray(q.astype(jnp.float32)))


def _jaxpr(module, *args):
    params = jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0),
                                                *args))
    return str(jax.make_jaxpr(jax.grad(
        lambda p, *a: module.apply(p, *a).sum()))(params, *args))


def test_the_defaults_trace_to_what_they_traced_to():
    """``scale=None``, a rotation and ``residual_scale=1`` are no
    operation of the program: the shared classes with and without the new
    fields spelled out give one jaxpr, and the new data changes it."""
    x = jnp.zeros((1, 16, 32), jnp.bfloat16)
    plain = lfm2.RotaryGQA(4, 2, 16)
    assert _jaxpr(plain, x) == _jaxpr(lfm2.RotaryGQA(
        4, 2, 16, 1e6, 1e-5, jnp.bfloat16, fa.CAUSAL, None, True, False,
        False, None), x)
    assert _jaxpr(plain, x) != _jaxpr(plain.clone(scale=0.25), x)
    assert "mul" in _jaxpr(plain.clone(scale=0.25), x)
    assert _jaxpr(plain, x) != _jaxpr(
        plain.clone(rotation=lfm2.NO_ROTATION), x)
    parts = (lfm2.ShortConv, (3, jnp.bfloat16),
             lfm2.DenseFFN, (48, jnp.bfloat16))
    layer = lfm2.Lfm2Layer(*parts)
    assert _jaxpr(layer, x) == _jaxpr(
        lfm2.Lfm2Layer(*parts, 1e-5, jnp.bfloat16, 1.0), x)
    assert _jaxpr(layer, x) != _jaxpr(layer.clone(residual_scale=0.22), x)
    z, table = jnp.zeros((1, 8, 32)), jnp.zeros((64, 32))
    labels = jnp.zeros((1, 8), jnp.int32)
    head = functools.partial(looplm.head_losses, tied=True)
    assert str(jax.make_jaxpr(head)(z, table, labels)) == str(
        jax.make_jaxpr(functools.partial(head, logit_scale=1.0))(
            z, table, labels))


@pytest.mark.parametrize("scale", [1.0, 0.22])
def test_the_residual_scale_is_on_both_branches(scale):
    layer = lfm2.Lfm2Layer(lfm2.ShortConv, (3, jnp.float32), lfm2.DenseFFN,
                           (48, jnp.float32), 1e-5, jnp.float32, scale)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 32))
    params = layer.init(jax.random.PRNGKey(2), x)
    p = params["params"]
    norm = looplm.RMSNorm(1e-5, jnp.float32)
    mixer = lfm2.ShortConv(3, jnp.float32)
    ffn = lfm2.DenseFFN(48, jnp.float32)
    h = x + scale * mixer.apply(
        {"params": p["mixer"]}, norm.apply({"params": p["op_norm"]}, x))
    want = h + scale * ffn.apply(
        {"params": p["ffn"]}, norm.apply({"params": p["ffn_norm"]}, h))[0]
    assert _close(layer.apply(params, x), want, 1e-6)
    if scale != 1.0:
        assert not _close(layer.clone(residual_scale=1.0).apply(params, x),
                          want, 1e-2)


def test_the_logit_scale_divides_the_logits_and_so_the_loss_sees_it():
    z = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 32))
    table = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
    labels = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0, 64)
    head = functools.partial(looplm.head_losses, dtype=jnp.float32,
                             tied=True)
    np.testing.assert_allclose(head(z, table, logit_scale=0.125),
                               head(z, table) / 8, rtol=1e-6)
    want = -jnp.take_along_axis(jax.nn.log_softmax(head(z, table) / 8, -1),
                                labels[..., None], -1)[..., 0]
    np.testing.assert_allclose(head(z, table, labels, logit_scale=0.125),
                               want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("groups", [1, 2])
def test_the_mamba_mixer_is_the_recurrence_between_its_projections(groups):
    """``Mamba2Mixer`` against its equations written out, the scan as
    ``ssd_reference`` (token by token), at a chunk that does not divide
    the sequence."""
    mixer = granite.Mamba2Mixer(4, 8, 16, groups, 4, 7, 1e-5, jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(4), (2, 30, 24))
    params = mixer.init(jax.random.PRNGKey(5), u)
    p = params["params"]
    inner, bc = 32, groups * 16
    assert p["in_proj"]["kernel"].shape == (24, 2 * inner + 2 * bc + 4)
    assert p["conv"].shape == (4, inner + 2 * bc)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(mixer.apply)(params, u)
        z, xbc, dt = jnp.split(u @ p["in_proj"]["kernel"],
                               (inner, 2 * inner + 2 * bc), -1)
        padded = jnp.pad(xbc, ((0, 0), (3, 0), (0, 0)))
        conv = sum(p["conv"][j] * padded[:, j:j + 30] for j in range(4))
        xbc = jax.nn.silu(conv + p["conv_bias"])
        x, b, c = jnp.split(xbc, (inner, inner + bc), -1)
        y = ssd.ssd_reference(
            x.reshape(2, 30, 4, 8), dt, -jnp.exp(p["A_log"]),
            b.reshape(2, 30, groups, 16), c.reshape(2, 30, groups, 16),
            p["D"], p["dt_bias"]).reshape(2, 30, inner)
        y = (y * jax.nn.silu(z)).reshape(2, 30, groups, -1)
        y = y / jnp.sqrt((y ** 2).mean(-1, keepdims=True) + 1e-5)
        want = (y.reshape(2, 30, inner) * p["norm"]["scale"]) \
            @ p["out_proj"]["kernel"]
    assert _close(got, want, 1e-5)


def test_the_model_is_causal_and_its_multipliers_are_where_they_belong():
    model = granite.GraniteHybridLM(
        vocab_size=64, num_layers=3, hidden=32,
        layer_types=("mamba", "attention", "mamba"), num_heads=2,
        num_kv_heads=1, head_dim=16, mlp_dim=48, ssm_heads=4,
        ssm_head_dim=16, ssm_state=8, chunk=8, dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 20), 0, 64)
    params = model.init(jax.random.PRNGKey(1), tokens)
    apply = jax.jit(model.apply)
    logits = apply(params, tokens)
    assert logits.shape == (2, 20, 64) and logits.dtype == jnp.float32
    # a later token moves no earlier logit
    later = tokens.at[:, 13].set((tokens[:, 13] + 1) % 64)
    moved = apply(params, later)
    np.testing.assert_allclose(moved[:, :13], logits[:, :13], rtol=1e-5,
                               atol=1e-6)
    assert not np.allclose(moved[:, 13:], logits[:, 13:], atol=1e-4)
    # logits over logits_scaling, exactly
    np.testing.assert_allclose(
        jax.jit(model.clone(logits_scaling=1.0).apply)(params, tokens) / 8,
        logits,
        rtol=1e-6)
    assert isinstance(model.layer_parts(1)[1][7], str)     # no rotation
    assert model.layer_parts(1)[1][11] == model.attention_multiplier
    # every layer and the head are rematerialised
    text = str(jax.make_jaxpr(lambda p: granite.granite_loss(
        model, p["params"], tokens))(params))
    assert text.count("remat2[") == 4


def _conv_calls(path):
    from horovod_tpu.common import metrics as metrics_lib

    samples = metrics_lib.snapshot()[
        "hvd_tpu_short_conv_calls_total"]["samples"]
    return sum(s["value"] for s in samples
               if s["labels"].get("path") == path)


@pytest.mark.parametrize("dtype, loss_rtol, scale_rtol, leaf_rtol", [
    (jnp.float32, 1e-6, 1e-5, 1e-4),
    # the state-space cell's own limits on the loss and on sqrt(sum g^2)
    # (benchmark/workloads/granite-4.0-h-micro-l10-s8192.json)
    (jnp.bfloat16, 2e-5, 7.5e-4, 3e-2),
], ids=["float32", "bfloat16"])
def test_the_model_through_the_convolutions_kernels_is_the_model(
        monkeypatch, dtype, loss_rtol, scale_rtol, leaf_rtol):
    """Loss and gradients with ``conv_act``'s kernels forced (interpret
    mode) against the XLA code, at the smallest widths the kernels take:
    256 channels of x, B and C over one tile of tokens, two Mamba-2
    layers."""
    model = granite.GraniteHybridLM(
        vocab_size=64, num_layers=2, hidden=32,
        layer_types=("mamba", "mamba"), num_heads=2, num_kv_heads=1,
        head_dim=16, mlp_dim=48, ssm_heads=8, ssm_head_dim=16,
        ssm_state=64, dtype=dtype)
    tokens = jax.random.randint(jax.random.PRNGKey(0),
                                (1, short_conv._ROWS + 1), 0, 64)
    params = model.init(jax.random.PRNGKey(1), tokens[:, :8])["params"]

    def step(params):
        return jax.value_and_grad(
            lambda p: granite.granite_loss(model, p, tokens))(params)

    want_loss, want = step(params)
    before = _conv_calls("pallas"), _conv_calls("xla")
    monkeypatch.setattr(granite, "conv_act", functools.partial(
        short_conv.conv_act, use_pallas=True))
    got_loss, got = step(params)
    assert _conv_calls("pallas") > before[0]
    assert _conv_calls("xla") == before[1]

    def scale(tree):
        return float(jnp.sqrt(sum(
            (g.astype(jnp.float32) ** 2).sum()
            for g in jax.tree.leaves(tree))))

    assert abs(float(got_loss) - float(want_loss)) \
        <= loss_rtol * abs(float(want_loss))
    assert abs(scale(got) - scale(want)) <= scale_rtol * scale(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.isfinite(np.asarray(g, np.float32)).all()
        assert scale(jax.tree.map(jnp.subtract, g, w)) \
            <= leaf_rtol * scale(w) + 1e-8
