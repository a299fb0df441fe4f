"""``models/olmo_hybrid.py`` and the data it asked of the shared classes:
``RotaryGQA``'s QK norm over the whole projection, the block that norms a
branch's output, ``GatedDeltaNet`` against the recurrence run token by
token, each against a formula written out here; the shared classes at
their defaults trace to what they traced to; and what moved out of
``models/solar.py`` is what it was."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import delta_rule, lfm2, olmo_hybrid, solar
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops import linear_attention as la


def _close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) <= tol * np.linalg.norm(want)


def _rms(x, scale, eps):
    return x / jnp.sqrt((x ** 2).mean(-1, keepdims=True) + eps) * scale


def _softmax_attention(q, k, v):
    s = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


@pytest.fixture(scope="module")
def attention():
    """The full-attention mixer at a small size, with scale vectors that
    are not ones, and an input."""
    mixer = lfm2.RotaryGQA(4, 4, 16, 0.0, 1e-6, jnp.float32, fa.CAUSAL,
                           lfm2.NO_ROTATION, lfm2.WHOLE_PROJECTION)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, 64))
    params = mixer.init(jax.random.PRNGKey(3), x)["params"]
    for i, name in enumerate(("q_norm", "k_norm")):
        params[name]["scale"] = 1.0 + 0.5 * jax.random.normal(
            jax.random.PRNGKey(i), (64,))
    return mixer, params, x


def test_the_qk_norm_is_over_the_whole_projection(attention):
    mixer, p, x = attention
    assert set(p) == {"q", "k", "v", "o", "q_norm", "k_norm"}
    assert p["q_norm"]["scale"].shape == p["k_norm"]["scale"].shape == (64,)
    with jax.default_matmul_precision("highest"):
        got = mixer.apply({"params": p}, x)
        q, k = (_rms(x @ p[n]["kernel"], p[n + "_norm"]["scale"], 1e-6)
                .reshape(2, 24, 4, 16) for n in ("q", "k"))
        v = (x @ p["v"]["kernel"]).reshape(2, 24, 4, 16)
        want = _softmax_attention(q, k, v).reshape(2, 24, 64) \
            @ p["o"]["kernel"]
    assert _close(got, want, 1e-5)
    # no positions: the order of the earlier tokens is nothing to the last
    swapped = x.at[:, [3, 7]].set(x[:, [7, 3]])
    np.testing.assert_allclose(mixer.apply({"params": p}, swapped)[:, -1],
                               got[:, -1], rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("other", ["per_head", "none"])
def test_another_qk_norm_is_another_function(attention, other):
    """A norm a head at a time (16-wide scale vectors) and none at all
    both differ from the whole-projection norm on the same weights."""
    mixer, p, x = attention
    got = mixer.apply({"params": p}, x)
    if other == "none":
        wrong = mixer.clone(qk_norm=False).apply(
            {"params": {n: p[n] for n in "qkvo"}}, x)
    else:
        heads = {n: {"scale": p[n]["scale"][:16]}
                 for n in ("q_norm", "k_norm")}
        wrong = mixer.clone(qk_norm=True).apply({"params": {**p, **heads}}, x)
    assert wrong.shape == got.shape and not _close(wrong, got, 1e-2)


def _jaxpr(module, *args):
    params = jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0),
                                                *args))
    return str(jax.make_jaxpr(jax.grad(
        lambda p, *a: module.apply(p, *a).sum()))(params, *args))


def test_the_shared_attention_traces_to_what_it_traced_to():
    """``qk_norm`` True and False are the programs they were (the norm a
    head at a time, and none); the whole-projection norm is a third."""
    x = jnp.zeros((1, 16, 32), jnp.bfloat16)
    plain = lfm2.RotaryGQA(4, 2, 16)
    assert _jaxpr(plain, x) == _jaxpr(lfm2.RotaryGQA(
        4, 2, 16, 1e6, 1e-5, jnp.bfloat16, fa.CAUSAL, None, True), x)
    texts = {kind: _jaxpr(plain.clone(qk_norm=kind), x)
             for kind in (True, False, lfm2.WHOLE_PROJECTION)}
    assert len(set(texts.values())) == 3
    assert texts[False].count("rsqrt") == 0
    assert texts[True].count("rsqrt") == texts[lfm2.WHOLE_PROJECTION].count(
        "rsqrt") > 0


def test_the_block_norms_each_branchs_output():
    layer = olmo_hybrid.PostNormLayer(
        lfm2.ShortConv, (3, jnp.float32), lfm2.DenseFFN, (48, jnp.float32),
        1e-6, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 32))
    p = layer.init(jax.random.PRNGKey(2), x)["params"]
    for i, name in enumerate(("op_norm", "ffn_norm")):
        p[name]["scale"] = 1.0 + 0.5 * jax.random.normal(
            jax.random.PRNGKey(i), (32,))
    mixer = lfm2.ShortConv(3, jnp.float32)
    ffn = lfm2.DenseFFN(48, jnp.float32)
    h = x + _rms(mixer.apply({"params": p["mixer"]}, x),
                 p["op_norm"]["scale"], 1e-6)
    want = h + _rms(ffn.apply({"params": p["ffn"]}, h)[0],
                    p["ffn_norm"]["scale"], 1e-6)
    assert _close(layer.apply({"params": p}, x), want, 1e-6)
    # the same tree under the block that norms a branch's input
    pre = lfm2.Lfm2Layer(lfm2.ShortConv, (3, jnp.float32), lfm2.DenseFFN,
                         (48, jnp.float32), 1e-6, jnp.float32)
    assert jax.tree.map(jnp.shape, pre.init(jax.random.PRNGKey(2), x)[
        "params"]) == jax.tree.map(jnp.shape, p)
    assert not _close(pre.apply({"params": p}, x), want, 1e-2)


@pytest.mark.parametrize("neg_eigval", [True, False])
def test_the_mixer_is_the_recurrence_between_its_projections(neg_eigval):
    """``GatedDeltaNet`` against the equations written out, the recurrence
    token by token (``kda_reference`` with the head's scalar on every
    channel): d_k 8 beside d_v 16, a length that is no whole chunk."""
    heads, dk, dv, s = 3, 8, 16, 21
    mixer = olmo_hybrid.GatedDeltaNet(heads, dk, dv, 4, neg_eigval, 8, 1e-6,
                                      jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, s, 32))
    p = mixer.init(jax.random.PRNGKey(5), x)["params"]
    p["o_norm"] = 1.0 + 0.5 * jax.random.normal(jax.random.PRNGKey(6), (dv,))
    assert {k: v.shape for k, v in p.items() if hasattr(v, "shape")} == {
        "conv": (4, heads * (2 * dk + dv)), "A_log": (heads,),
        "dt_bias": (heads,), "o_norm": (dv,)}
    assert p["qkv"]["kernel"].shape == (32, heads * (2 * dk + dv))
    assert p["a"]["kernel"].shape == p["b"]["kernel"].shape == (32, heads)
    assert p["gate"]["kernel"].shape == (32, heads * dv)
    assert p["o"]["kernel"].shape == (heads * dv, 32)
    with jax.default_matmul_precision("highest"):
        got = mixer.apply({"params": p}, x)
        z = x @ p["qkv"]["kernel"]
        padded = jnp.pad(z, ((0, 0), (3, 0), (0, 0)))
        z = jax.nn.silu(sum(p["conv"][j] * padded[:, j:j + s]
                            for j in range(4)))
        q, k, v = jnp.split(z, (heads * dk, 2 * heads * dk), -1)
        q, k = (y.reshape(2, s, heads, dk) for y in (q, k))
        q, k = (y / jnp.sqrt((y ** 2).sum(-1, keepdims=True) + 1e-6)
                for y in (q, k))
        beta = (2.0 if neg_eigval else 1.0) * jax.nn.sigmoid(
            x @ p["b"]["kernel"])
        g = -jnp.exp(p["A_log"]) * jax.nn.softplus(
            x @ p["a"]["kernel"] + p["dt_bias"])
        o = la.kda_reference(q * dk ** -0.5, k, v.reshape(2, s, heads, dv),
                             jnp.broadcast_to(g[..., None], k.shape), beta)
        y = _rms(o, p["o_norm"], 1e-6) * jax.nn.silu(
            (x @ p["gate"]["kernel"]).reshape(o.shape))
        want = y.reshape(2, s, heads * dv) @ p["o"]["kernel"]
    assert _close(got, want, 1e-5)


def test_the_start_of_the_decay_is_the_one_solar_starts_from():
    """``A`` in [1, 16], the step in [1e-3, 0.1] through the softplus: the
    initialisers moved to ``models/delta_rule.py`` draw what they drew in
    ``models/solar.py``, and both mixers use them."""
    key = jax.random.PRNGKey(11)
    rate = np.exp(np.asarray(delta_rule.decay_rate_init(key, (256,))))
    assert rate.min() >= 1.0 and rate.max() <= 16.0 and rate.std() > 1.0
    step = np.asarray(jax.nn.softplus(delta_rule.decay_bias_init(key, (256,))))
    assert step.min() >= 1e-3 * 0.999 and step.max() <= 0.1 * 1.001
    # the draws the parent's ``solar._decay_*_init`` gave for this key
    assert np.asarray(delta_rule.decay_rate_init(key, (2,))).tolist() \
        == pytest.approx([1.4223227500915527, 0.2477923482656479], rel=1e-6)
    assert np.asarray(delta_rule.decay_bias_init(key, (2,))).tolist() \
        == pytest.approx([-5.940356254577637, -6.82088041305542], rel=1e-6)
    y = jax.random.normal(key, (3, 5, 8))
    np.testing.assert_allclose(
        delta_rule.unit(y), y / np.sqrt((np.asarray(y) ** 2).sum(
            -1, keepdims=True) + 1e-6), rtol=1e-6)
    assert solar.unit is olmo_hybrid.unit is delta_rule.unit
    assert solar.decay_rate_init is olmo_hybrid.decay_rate_init
    assert not hasattr(solar, "_decay_bias_init")
    for mixer, x in ((solar.KDA(2, 16), jnp.zeros((1, 8, 32), jnp.bfloat16)),
                     (olmo_hybrid.GatedDeltaNet(2, 8, 16),
                      jnp.zeros((1, 8, 32), jnp.bfloat16))):
        p = jax.jit(mixer.init)(key, x)["params"]
        assert 1.0 <= float(jnp.exp(p["A_log"]).min()) \
            and float(jnp.exp(p["A_log"]).max()) <= 16.0
        assert float(jax.nn.softplus(p["dt_bias"]).max()) <= 0.1 * 1.001


def test_the_model_reads_the_pattern_up_to_its_depth_and_unties_its_head():
    model = olmo_hybrid.OlmoHybridLM()
    assert len(model.layer_types) == 32 > model.num_layers == 4
    assert [model.layer_parts(i)[0].__name__ for i in range(4)] \
        == ["GatedDeltaNet"] * 3 + ["RotaryGQA"]
    assert [i for i, kind in enumerate(model.layer_types)
            if kind == olmo_hybrid.ATTENTION] == list(range(3, 32, 4))
    small = olmo_hybrid.OlmoHybridLM(
        vocab_size=64, num_layers=2, hidden=32,
        layer_types=("full_attention", "linear_attention"), num_heads=2,
        head_dim=16, mlp_dim=48, linear_heads=2, linear_key_dim=8,
        linear_value_dim=16, chunk=8)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 17), 0, 64)
    p = small.init(jax.random.PRNGKey(1), tokens[:, :-1])["params"]
    assert set(p) == {"tok_emb", "layer0", "layer1", "final_norm", "lm_head"}
    assert "q_norm" in p["layer0"]["mixer"] and "A_log" in p["layer1"]["mixer"]
    assert p["lm_head"]["kernel"].shape == (32, 64)
    logits = small.apply({"params": p}, tokens[:, :-1])
    assert logits.shape == (2, 16, 64) and logits.dtype == jnp.float32
    ce = small.apply({"params": p}, tokens[:, :-1], tokens[:, 1:])
    want = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                tokens[:, 1:, None], -1)[..., 0]
    np.testing.assert_allclose(ce, want, rtol=2e-2, atol=2e-2)
    assert float(olmo_hybrid.olmo_hybrid_loss(small, p, tokens)) \
        == pytest.approx(float(ce.mean()), rel=1e-6)
