"""``models/sdar.py``: the block-diffusion decoder and its loss, at toy
widths on the CPU. The comparison with the plain reference is the
benchmark's (``tests/benchmark/test_benchmark_sdar.py``); here: what the
model is built from, what its step names, and what the loss does with the
batch's seed column."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.common import scopes
from horovod_tpu.models import SdarLM, block_noise, lfm2, sdar_loss
from horovod_tpu.ops import flash_attention as fa

L = 64


def _model(**kwargs):
    return SdarLM(**{**dict(
        vocab_size=96, num_layers=2, hidden=32, num_heads=4, num_kv_heads=2,
        head_dim=16, num_experts=8, held_experts=(2, 4), top_k=2,
        expert_dim=16, mask_token=95, noise_seed=3), **kwargs})


@pytest.fixture(scope="module")
def setup():
    model = _model()
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, L), jnp.int32))["params"]
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, L + 1), 0, 96)
    return model, params, tokens


def test_the_model_is_a_plain_decoder_over_what_it_is_given(setup):
    model, params, tokens = setup
    assert set(params) == {"tok_emb", "layer0", "layer1", "final_norm",
                           "lm_head"}
    assert set(params["layer0"]) == {"op_norm", "mixer", "ffn_norm", "ffn"}
    assert set(params["layer0"]["mixer"]) == {"q", "k", "v", "o", "q_norm",
                                              "k_norm"}
    assert set(params["layer0"]["ffn"]) == {
        "router", "experts_gate", "experts_up", "experts_down"}
    x = tokens[:, :-1]
    logits = model.apply({"params": params}, x)
    assert logits.shape == (2, L, 96) and logits.dtype == jnp.float32
    # causal unless told: a later token does not move an earlier logit
    moved = model.apply({"params": params}, x.at[:, -1].add(1) % 96)
    assert np.array_equal(np.asarray(logits[:, :-1]),
                          np.asarray(moved[:, :-1]))
    # positions: 0 … S-1 is what None means
    again = model.apply({"params": params}, x, jnp.arange(L)[None])
    np.testing.assert_allclose(again, logits, rtol=1e-6, atol=1e-6)
    shifted = model.apply({"params": params}, x, jnp.arange(L)[None] + 5)
    assert not np.allclose(shifted, logits, atol=1e-3)
    # labels of R columns: the cross-entropy of the first R positions
    ce = model.apply({"params": params}, x, None, fa.CAUSAL, x[:, :16])
    assert ce.shape == (2, 16)
    want = jax.nn.logsumexp(logits[:, :16], -1) - jnp.take_along_axis(
        logits[:, :16], x[:, :16, None], -1)[..., 0]
    np.testing.assert_allclose(ce, want, rtol=2e-5, atol=2e-5)


def test_the_clean_copy_never_sees_the_noisy_one(setup):
    """Under the block-diffusion mask the clean half's states are those of
    a block-causal decoder over the clean tokens alone, whatever the noisy
    half holds; and a noisy block sees its own block and the clean past."""
    model, params, tokens = setup
    x = tokens[:, :-1]
    positions = jnp.tile(jnp.arange(L), 2)[None]
    kind = fa.BlockDiffusionMask(4)

    def run(noisy):
        return model.apply({"params": params},
                           jnp.concatenate([noisy, x], 1), positions, kind)

    a, b = run(jnp.full_like(x, 95)), run((x + 1) % 96)
    assert np.array_equal(np.asarray(a[:, L:]), np.asarray(b[:, L:]))
    assert not np.allclose(a[:, :L], b[:, :L], atol=1e-3)
    # the last clean block moves no noisy logit but nothing before it is
    # hidden from the last noisy block
    c = model.apply({"params": params}, jnp.concatenate(
        [jnp.full_like(x, 95), x.at[:, -4:].add(1) % 96], 1), positions, kind)
    assert np.array_equal(np.asarray(a[:, :L]), np.asarray(c[:, :L]))
    d = model.apply({"params": params}, jnp.concatenate(
        [jnp.full_like(x, 95), x.at[:, -8:-4].add(1) % 96], 1), positions,
        kind)
    assert np.array_equal(np.asarray(a[:, :L - 4]), np.asarray(d[:, :L - 4]))
    assert not np.allclose(a[:, L - 4:L], d[:, L - 4:L], atol=1e-4)


def test_the_loss_reads_the_noise_off_the_batchs_last_column(setup):
    model, params, tokens = setup
    loss = float(sdar_loss(model, params, tokens))
    assert np.isfinite(loss) and loss > 0
    # the same data under another seed column: another noise, another loss
    other = float(sdar_loss(model, params,
                            tokens.at[:, -1].set(tokens[:, -1] + 1)))
    assert other != loss
    # the same seeds under another ``noise_seed``
    assert float(sdar_loss(_model(noise_seed=4), params, tokens)) != loss
    masked, rates = block_noise(tokens[:, -1], L, 4, 3)
    assert masked.shape == (2, L) and rates.shape == (2, L // 4)
    # a token is masked with its block's probability
    many, rates = block_noise(jnp.arange(512), L, 4, 0)
    share = np.asarray(many, np.float64).reshape(512, L // 4, 4).mean()
    assert share == pytest.approx(float(rates.mean()), abs=0.01)
    assert float(rates.mean()) == pytest.approx(0.5, abs=0.02)


def test_whole_expert_blocks_cost_the_same_and_change_nothing(setup):
    """``whole_expert_blocks``: the held experts' blocks worked whole
    (``moe.held_experts_layer(whole_blocks=True)``, whose tests hold that
    the rows worked on do not follow the routes): the loss and every
    gradient are the ladder's."""
    model, params, tokens = setup
    whole = _model(whole_expert_blocks=True)
    got, want = (jax.value_and_grad(
        lambda p: sdar_loss(m, p, tokens))(params) for m in (whole, model))
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-6)
    for ours, theirs in zip(jax.tree.leaves(got[1]),
                            jax.tree.leaves(want[1])):
        np.testing.assert_allclose(ours, theirs, rtol=1e-4, atol=1e-6)


def test_the_step_names_its_parts(setup):
    """The noise under ``hvd_bd_noise``; the layer's parts under the names
    of ``models/lfm2.py``, whose layer it is; the expert layer's own."""
    model, params, tokens = setup
    text = jax.jit(jax.grad(lambda p: sdar_loss(model, p, tokens))).lower(
        params).as_text(debug_info=True)
    names = set(re.findall(r'"(jit\([^"]*)"', text))
    for scope in (scopes.BD_NOISE, scopes.MIXER_PROJ, scopes.ROPE,
                  scopes.NORM, scopes.EMBED, scopes.LM_HEAD, scopes.LOSS,
                  scopes.MOE_ROUTE, scopes.MOE_EXPERTS):
        assert any(re.search(rf"[/(]{scope}[/)]", n) for n in names), scope
    # the loss's first scope: JAX writes it ``jvp(hvd_bd_noise)``
    noise = [n for n in names if scopes.BD_NOISE in n]
    assert any("threefry" in n or "random" in n for n in noise)
    assert any("concatenate" in n for n in noise)
    # no gradient passes through the noise: nothing of it is transposed
    assert not any("transpose(" in n for n in noise)


def test_the_attention_body_is_the_gated_convolution_models():
    """``RotaryGQA`` takes the positions and the mask kind; causal and
    0 … S-1 unless told, which is what ``Lfm2LM`` leaves it at."""
    attention = lfm2.RotaryGQA(4, 2, 16)
    assert attention.mask_kind == fa.CAUSAL
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 32, 32))
    params = attention.init(jax.random.PRNGKey(3), x)
    plain = attention.apply(params, x)
    np.testing.assert_allclose(
        attention.apply(params, x, jnp.arange(32)[None]), plain,
        rtol=1e-6, atol=1e-6)
    diffusion = attention.clone(mask_kind=fa.BlockDiffusionMask(4))
    assert not np.allclose(diffusion.apply(params, x), plain, atol=1e-3)
