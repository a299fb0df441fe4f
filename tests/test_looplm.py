"""The looped decoder (``horovod_tpu/models/looplm.py``) on the CPU at a
tiny size: one stack run several times over ONE parameter tree, an exit at
every pass, the exit-weighted loss, the scope the exit work carries, and
the flash kernels at the head width it brings (128, whose softmax scale is
no power of two)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.common import scopes
from horovod_tpu.models import LoopLM, exit_log_distribution, looplm_loss
from horovod_tpu.ops import flash_attention as fa

TINY = dict(vocab_size=256, num_layers=2, hidden=64, num_heads=4,
            head_dim=16, mlp_dim=176, passes=3, dtype=jnp.float32)
STACK = ("layer0", "layer1", "final_norm")      # what a pass runs through


@pytest.fixture(scope="module")
def tiny():
    model = LoopLM(**TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, 256)
    params = model.init(jax.random.PRNGKey(0), tokens[:, :-1])["params"]
    return model, params, tokens


def test_one_parameter_tree_with_the_modules_at_the_top(tiny):
    model, params, tokens = tiny
    assert sorted(params) == ["exit_gate", "final_norm", "layer0", "layer1",
                              "lm_head", "tok_emb"]
    assert sorted(params["layer0"]) == [
        "attn_norm", "attn_out_norm", "down", "gate", "k", "mlp_norm",
        "mlp_out_norm", "o", "q", "up", "v"]
    # No bias but the gate's; the head is untied.
    assert all(set(leaf) == {"kernel"} for name, leaf in
               params["layer0"].items() if not name.endswith("norm"))
    assert set(params["exit_gate"]) == {"kernel", "bias"}
    assert params["lm_head"]["kernel"].shape == (64, 256)
    logits, gates = model.apply({"params": params}, tokens[:, :-1])
    assert logits.shape == (3, 2, 32, 256) and logits.dtype == jnp.float32
    assert gates.shape == (3, 2, 32) and gates.dtype == jnp.float32
    ce, _ = model.apply({"params": params}, tokens[:, :-1], tokens[:, 1:])
    want = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                tokens[None, :, 1:, None], -1)[..., 0]
    assert np.allclose(ce, want, atol=1e-5)


def test_the_published_model_counts_its_parameters():
    """Ouro-2.6B as published, and the cut the benchmark runs."""
    def count(**kw):
        shapes = jax.eval_shape(
            lambda k: LoopLM(**kw).init(k, jnp.zeros((1, 8), jnp.int32)),
            jax.random.PRNGKey(0))
        return sum(x.size for x in jax.tree.leaves(shapes))

    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 51_388_416
    rest = 2 * 49152 * 2048 + 2048 + 2049
    assert count(num_layers=8) == 8 * layer + rest == 612_438_017
    assert count() == 48 * layer + rest


def test_the_exit_distribution_sums_to_one_and_the_last_pass_takes_the_rest():
    gates = jax.random.normal(jax.random.PRNGKey(2), (4, 3, 5)) * 3
    p = jnp.exp(exit_log_distribution(gates))
    lam = jax.nn.sigmoid(gates)
    assert np.allclose(p.sum(0), 1.0, atol=1e-6)
    assert np.allclose(p[0], lam[0], atol=1e-6)
    assert np.allclose(p[2], lam[2] * (1 - lam[0]) * (1 - lam[1]), atol=1e-6)
    assert np.allclose(p[3], (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2]),
                       atol=1e-6)
    # A gate shut or wide open gives no NaN: 0 log 0 = 0.
    hard = jnp.array([[60.0], [-60.0], [0.0]])
    log_p = exit_log_distribution(hard)
    assert np.isfinite(log_p).all()
    assert np.allclose(jnp.exp(log_p)[:, 0], [1.0, 0.0, 0.0], atol=1e-6)


@pytest.mark.parametrize("weighted", [False, True])
def test_with_stats_the_exit_mass_sums_to_one(tiny, weighted):
    model, params, tokens = tiny
    weights = jax.random.uniform(jax.random.PRNGKey(3), (2, 32)) \
        if weighted else None
    loss, stats = looplm_loss(model, params, tokens, 0.05, weights,
                              with_stats=True)
    assert loss == looplm_loss(model, params, tokens, 0.05, weights)
    assert stats["exit_mass"].shape == (3,)
    assert float(stats["exit_mass"].sum()) == pytest.approx(1.0, abs=1e-5)
    assert 0.0 < float(stats["exit_entropy"]) <= np.log(3) + 1e-6
    # beta weighs the entropy, and nothing else.
    assert float(looplm_loss(model, params, tokens, 0.0, weights) - loss) \
        == pytest.approx(0.05 * float(stats["exit_entropy"]), rel=1e-4)


def test_one_pass_with_all_the_mass_is_a_plain_stack(tiny):
    """A gate bias that sends every position out at the first exit: the
    loss is the cross-entropy of the stack run once."""
    model, params, tokens = tiny
    forced = {**params, "exit_gate": {**params["exit_gate"],
                                      "bias": jnp.full((1,), 40.0)}}
    plain = LoopLM(**{**TINY, "passes": 1})
    logits, _ = plain.apply({"params": params}, tokens[:, :-1])
    want = -jnp.take_along_axis(jax.nn.log_softmax(logits[0], -1),
                                tokens[:, 1:, None], -1).mean()
    got, stats = looplm_loss(model, forced, tokens, 0.05, with_stats=True)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert np.allclose(stats["exit_mass"], [1.0, 0.0, 0.0], atol=1e-6)
    # ... and a single pass has nowhere else to exit, whatever its gate.
    assert float(looplm_loss(plain, params, tokens, 0.05)) \
        == pytest.approx(float(want), rel=1e-6)


def test_a_shared_weights_gradient_is_the_sum_over_its_uses(tiny):
    """The looped model against an unrolled copy with untied weights:
    pass t of the copy runs through its own copy of the stack, and the
    gradients of the copies add up to the looped one's."""
    model, params, tokens = tiny
    inputs, labels = tokens[:, :-1], tokens[:, 1:]

    def untied(stacks, rest):
        def apply(p, method, *args):
            return model.apply({"params": p}, *args, method=method)

        h = apply(rest, lambda m, x: m.tok_emb(x), inputs)
        ce, gates = [], []
        for stack in stacks:
            h = apply({**rest, **stack}, LoopLM.one_pass, h)
            ce.append(apply(rest, lambda m, z: m.lm_head(z, labels), h))
            gates.append(apply(rest, lambda m, z: m.exit_gate(z), h))
        log_p = exit_log_distribution(jnp.stack(gates))
        p = jnp.exp(log_p)
        return ((p * jnp.stack(ce)).sum(0) + 0.05 * (p * log_p).sum(0)).mean()

    stack = {k: params[k] for k in STACK}
    rest = {k: v for k, v in params.items() if k not in STACK}
    loss, (per_pass, of_rest) = jax.value_and_grad(untied, argnums=(0, 1))(
        [stack] * 3, rest)
    want, looped = jax.value_and_grad(
        lambda p: looplm_loss(model, p, tokens, 0.05))(params)
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    summed = jax.tree.map(lambda *g: sum(g), *per_pass)
    for a, b in zip(jax.tree.leaves({**of_rest, **summed}),
                    jax.tree.leaves(looped)):
        assert np.allclose(a, b, rtol=1e-4, atol=1e-7)
    # No pass's share is nothing: every use of a weight counts.
    for g in per_pass:
        assert float(jnp.abs(g["layer0"]["q"]["kernel"]).max()) > 0


def test_the_exit_scope_is_on_forward_and_backward_instructions(tiny):
    model, params, tokens = tiny
    text = jax.jit(jax.value_and_grad(
        lambda p: looplm_loss(model, p, tokens, 0.05))).lower(
            params).compile().as_text()
    names = [n for n in set(re.findall(r'op_name="([^"]*)"', text))
             if scopes.LOOP_EXIT in n]
    assert any("transpose(" not in n for n in names)
    assert any("transpose(jvp(" in n for n in names)
    # The gate's matvec inside the scan, and the weighting outside it.
    assert any("exit_gate/" + scopes.LOOP_EXIT in n for n in names)
    assert any("exit_gate" not in n for n in names)
    # The head keeps the scope the other families give it, once a pass;
    # the recomputation is marked by JAX itself.
    assert any(scopes.LM_HEAD in n and "rematted_computation" in n
               for n in re.findall(r'op_name="([^"]*)"', text))
    assert scopes.LOOP_SCOPES == (scopes.LOOP_EXIT,)
    assert not set(scopes.LOOP_SCOPES) & set(scopes.STEP_SCOPES)


# -- the flash kernels at head width 128 --------------------------------------

@pytest.mark.parametrize("width, folds", [(16, True), (64, True),
                                          (128, False), (256, True),
                                          (80, False)])
def test_the_scale_goes_into_q_only_where_that_is_exact(width, folds):
    assert fa._scale_folds_into_q(1.0 / np.sqrt(width)) is folds


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("width", [128, 64])
def test_flash_kernels_at_width_128_match_the_reference(width, dtype, tol):
    """Interpret mode, causal, (B, S, 4, D): forward and the three
    gradients against the jnp reference in fp32 on the same operands. At
    128 one head fills a 128-lane block and the scale rides on the fp32
    scores; 64 (two heads a block, the scale in q) is the control."""
    ks = jax.random.split(jax.random.PRNGKey(width), 4)
    q, k, v, w = (jax.random.normal(kk, (2, 256, 4, width),
                                    jnp.float32).astype(dtype) for kk in ks)

    def both(fn):
        def loss(q, k, v):
            o = fn(q, k, v).astype(jnp.float32)
            return (o * w.astype(jnp.float32)).sum(), o
        return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)

    (_, o), grads = both(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, use_pallas=True, block_q=128,
        block_k=128))(q, k, v)
    (_, o_ref), grads_ref = both(lambda q, k, v: fa.reference_attention(
        *(x.astype(jnp.float32) for x in (q, k, v)), causal=True))(q, k, v)
    assert np.allclose(o, o_ref, atol=tol, rtol=tol)
    for g, g_ref in zip(grads, grads_ref):
        assert g.dtype == dtype
        scale = float(jnp.abs(g_ref).max())
        assert float(jnp.abs(g.astype(jnp.float32)
                             - g_ref.astype(jnp.float32)).max()) \
            <= tol * max(scale, 1.0)
