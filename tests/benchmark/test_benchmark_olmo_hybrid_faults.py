"""The faults of family ``olmo_hybrid``'s mathematics (``FAULTS``: a scratch
script on the chip puts the same overrides under the timed path,
``.claude/skills/verify/SKILL.md``), each held against the plain reference
by the fp32 logits at the tiny preset. A file of its own beside
``test_benchmark_olmo_hybrid.py`` (whose tiny system and reference it
shares) so that neither is long under ``--dist loadfile``. Nothing here
touches a device."""

import contextlib
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_benchmark_olmo_hybrid import (FAMILY, REFERENCE, TINY, TOLERANCE,
                                        _close, system_and_reference)


def _patched(owner, name, new):
    @contextlib.contextmanager
    def patch():
        old = getattr(owner, name)
        setattr(owner, name, new(old))
        try:
            yield
        finally:
            setattr(owner, name, old)
    return patch


def _with_fields(**fields):
    """``OlmoHybridLM`` built with ``fields`` whatever it is given."""
    from horovod_tpu.models import olmo_hybrid

    def wrap(real):
        def post_init(self):
            for key, value in fields.items():
                object.__setattr__(self, key, value)
            real(self)
        return post_init
    return lambda config: _patched(olmo_hybrid.OlmoHybridLM,
                                   "__post_init__", wrap)()


# positions in ``RotaryGQA``'s arguments
ROPE_BASE, ROTATION = 3, 7


def _rotated(real):
    """``OlmoHybridLM.layer_parts`` with a plain rotation on the
    full-attention layer."""
    from horovod_tpu.models import olmo_hybrid

    def layer_parts(self, i):
        mixer, mixer_args, ffn, ffn_args = real(self, i)
        if mixer is olmo_hybrid.RotaryGQA:
            mixer_args = list(mixer_args)
            mixer_args[ROTATION], mixer_args[ROPE_BASE] = None, 10000.0
        return mixer, tuple(mixer_args), ffn, ffn_args
    return layer_parts


def _in_the_mixer(name, new):
    from horovod_tpu.models import olmo_hybrid

    return lambda config: _patched(olmo_hybrid, name, new)()


def _recurrence_given(change):
    """``gated_delta_attention`` called on ``change(q, k, v, log_decay,
    beta)``."""
    return _in_the_mixer("gated_delta_attention", lambda real: (
        lambda q, k, v, log_decay, beta, chunk: real(
            *change(q, k, v, log_decay, beta), chunk)))


def _gate_before_the_norm(real):
    def gate_then_norm(o, gate, scale, eps):
        ones = jnp.full(gate.shape, 1.2784645, jnp.float32)   # silu = 1
        gated = o.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
        return real(gated, ones, scale, eps) / jax.nn.silu(ones)
    return gate_then_norm


def _sigmoid_gate(real):
    def sigmoid_for_silu(o, gate, scale, eps):
        g = gate.astype(jnp.float32)
        return real(o, gate, scale, eps) / jax.nn.silu(g) * jax.nn.sigmoid(g)
    return sigmoid_for_silu


def _qk_norm(statistics):
    """``models/lfm2.py``'s ``RMSNorm`` with, where it is named for q or
    k, the statistics a head at a time (``"head"``) or no norm at all
    (``"none"``); the whole-projection scale vector stays in the tree."""
    from horovod_tpu.models import lfm2

    def patch(config):
        heads = config["num_attention_heads"]

        class OtherQKNorm(nn.Module):
            eps: float = 1e-6
            dtype: object = jnp.bfloat16

            @nn.compact
            def __call__(self, x):
                scale = self.param("scale", nn.initializers.ones,
                                   (x.shape[-1],), jnp.float32)
                x = x.astype(jnp.float32)
                if self.name not in ("q_norm", "k_norm"):
                    shaped = x
                elif statistics == "none":
                    return x.astype(self.dtype)
                else:
                    shaped = x.reshape(*x.shape[:-1], heads, -1)
                shaped = shaped * jax.lax.rsqrt(
                    jnp.mean(shaped * shaped, -1, keepdims=True) + self.eps)
                return (shaped.reshape(x.shape) * scale).astype(self.dtype)
        return _patched(lfm2, "RMSNorm", lambda real: OtherQKNorm)()
    return patch


# (``config -> context``): while the context is open, a model that is
# built and traced has the fault; the reference never does. Every one
# keeps the tree's shapes (the twelfth of the issue's list, layer 3 run as
# a linear layer, cannot: the test after these holds what it does).
FAULTS = {
    "beta_without_its_two": _with_fields(allow_neg_eigval=False),
    "alpha_set_to_one": _recurrence_given(lambda q, k, v, g, beta: (
        q, k, v, jnp.zeros_like(g), beta)),
    "l2_norms_left_out": _in_the_mixer("unit", lambda real: lambda y: y),
    "q_scale_left_out": _recurrence_given(lambda q, k, v, g, beta: (
        (q.astype(jnp.float32) * k.shape[-1] ** 0.5).astype(q.dtype), k, v,
        g, beta)),
    "gate_sigmoid_for_silu": _in_the_mixer("_gated_norm", _sigmoid_gate),
    "gate_before_the_norm": _in_the_mixer("_gated_norm",
                                          _gate_before_the_norm),
    "conv_silu_left_out": _in_the_mixer("_conv_act", lambda real: (
        lambda x, taps: sys.modules["horovod_tpu.models.olmo_hybrid"]
        .causal_conv(x, taps).astype(x.dtype))),
    "qk_norm_a_head_at_a_time": _qk_norm("head"),
    "qk_norm_left_out": _qk_norm("none"),
    "attention_rotated": lambda config: _patched(
        sys.modules["horovod_tpu.models.olmo_hybrid"].OlmoHybridLM,
        "layer_parts", _rotated)(),
    "norm_on_the_branchs_input": _in_the_mixer(
        "PostNormLayer", lambda real: sys.modules[
            "horovod_tpu.models.lfm2"].Lfm2Layer),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_moves_the_system_away_from_the_reference(fault):
    """Every one of the eleven, in fp32, where the sound system and the
    reference agree to rounding: the override is under the model, and the
    comparison of the logits sees it. Afterwards it is gone."""
    _, params, tokens, logits, want = system_and_reference("float32")
    assert _close(logits, want, TOLERANCE["float32"][1])

    def system():
        model = FAMILY.build(TINY).clone(dtype=jnp.float32)
        return model, jax.jit(model.apply)({"params": params},
                                           tokens[:, :-1])

    with FAULTS[fault](TINY):
        model, faulty = system()
        shapes = jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(5), tokens[:, :-1])["params"])
    assert jax.tree.map(jnp.shape, shapes) == jax.tree.map(jnp.shape, params)
    assert not _close(faulty, want, 50 * TOLERANCE["float32"][1])
    if fault == sorted(FAULTS)[-1]:     # once: a compile of the sound model
        assert np.array_equal(np.asarray(system()[1]), np.asarray(logits))


def test_layer_3_as_a_linear_layer_is_another_tree_and_the_reference_raises():
    """The twelfth fault changes the parameters themselves: a model whose
    layer 3 is linear holds a Gated DeltaNet's weights there, and the
    reference, which reads the published pattern, finds no ``q`` in them:
    the check cannot be run on such a program, let alone passed."""
    wrong = {**TINY, "layer_types": ["linear_attention"] * 8}
    tokens = system_and_reference("float32")[2]
    params = FAMILY.build(wrong).init(jax.random.PRNGKey(5),
                                      tokens[:, :-1])["params"]
    assert "A_log" in params["layer3"]["mixer"]
    with pytest.raises(KeyError, match="q"):
        REFERENCE.logits(params, tokens[:, :-1], TINY)


def test_the_gate_faults_are_what_they_say():
    """``RMSNorm(o * silu(g)) * w`` and ``RMSNorm(o) * w * sigmoid(g)``."""
    from horovod_tpu.models import olmo_hybrid

    k = jax.random.split(jax.random.PRNGKey(1), 3)
    o, gate = (jax.random.normal(k[i], (2, 5, 3, 16)) for i in (0, 1))
    scale = jax.random.normal(k[2], (16,))

    def normed(x):
        return x / jnp.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-6) * scale

    real = olmo_hybrid._gated_norm
    assert _close(real(o, gate, scale, 1e-6), normed(o) * jax.nn.silu(gate),
                  1e-6)
    assert _close(_gate_before_the_norm(real)(o, gate, scale, 1e-6),
                  normed(o * jax.nn.silu(gate)), 1e-5)
    assert _close(_sigmoid_gate(real)(o, gate, scale, 1e-6),
                  normed(o) * jax.nn.sigmoid(gate), 1e-5)
    for other in (_gate_before_the_norm, _sigmoid_gate):
        assert not _close(other(real)(o, gate, scale, 1e-6),
                          real(o, gate, scale, 1e-6), 0.1)
