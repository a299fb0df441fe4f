"""Family ``lfm2`` (``LiquidAI/LFM2-8B-A1B``: gated short convolutions and
QK-normed rotary GQA by a pattern, two dense feed-forward layers and then
a mixture of experts routed by a sigmoid with a selection bias, a tied
head) on the CPU at its tiny preset: the system against the plain
reference on seeded weights, the configuration's file against the
published widths, the family's and the cost file's counts by hand, the
cell's two readers on a hand-made trace, and the faults of the
mathematics that the cell's limits were held against on the chip
(``FAULTS``: a scratch script there puts the same overrides under the
timed path). Nothing here touches a device."""

import contextlib

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import lfm2_cost, moe_kda_cost, of_which
from benchmark.catalog import Catalog
from benchmark.jobs import train_lm
from benchmark.stream import token_stream

CAT = Catalog()
FAMILY = CAT.module("families", "lfm2")
REFERENCE = CAT.module("reference", "lfm2")
TINY = CAT.config("lfm2-tiny")
CONFIG = "lfm2-8b-a1b-l8-e8"
CELL = "lfm2-8b-a1b-l8-e8-s8192"
# Two layers of one kind, or the tiny preset's eight (c c A c c c A c, the
# first two dense).
STACKS = {"conv-dense": {"num_hidden_layers": 2},
          "attention-experts": {"num_hidden_layers": 2, "num_dense_layers": 0,
                                "layer_types": ["full_attention"] * 2},
          "stage": {}}
# (loss, logits, gradients): the arithmetic of the two agrees to fp32
# rounding; in bf16 the system's operands are rounded, and a router that
# reads rounded activations gives a few tokens another fourth expert.
TOLERANCE = {"float32": (1e-5, 2e-4, 3e-4), "bfloat16": (3e-3, 1e-1, 5e-1)}


def _close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) <= tol * np.linalg.norm(want)


@pytest.fixture(scope="module",
                params=[(s, d) for s in STACKS for d in sorted(TOLERANCE)],
                ids=lambda p: "-".join(p))
def pair(request):
    """The system's model of one stack in one compute dtype, its seeded
    parameters and a batch; the reference reads the same tree."""
    from horovod_tpu.models import Lfm2LM

    stack, dtype = request.param
    config = {**TINY, **STACKS[stack]}
    model = FAMILY.build(config)
    assert isinstance(model, Lfm2LM) and model.dtype == jnp.bfloat16
    model = model.clone(dtype=jnp.dtype(dtype))
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 81), 0,
                                config["vocab_size"])
    params = model.init(jax.random.PRNGKey(5), tokens[:, :-1])["params"]
    return model, params, tokens, config, TOLERANCE[dtype]


def test_the_logits(pair):
    model, params, tokens, config, (_, tol, _) = pair
    logits = model.apply({"params": params}, tokens[:, :-1])
    want = REFERENCE.logits(params, tokens[:, :-1], config)
    assert logits.shape == want.shape == (2, 80, config["vocab_size"])
    assert logits.dtype == jnp.float32
    assert _close(logits, want, tol)


def test_the_loss_a_position_and_its_mean(pair):
    from horovod_tpu.models import lfm2_loss

    model, params, tokens, config, (tol, _, _) = pair
    want = REFERENCE.token_losses(params, {"tokens": tokens}, config)
    assert want.shape == (2, 80) and want.dtype == jnp.float32
    assert float(lfm2_loss(model, params, tokens)) \
        == pytest.approx(float(want.mean()), rel=tol)
    assert float(FAMILY.loss(model, params, {"tokens": tokens})) \
        == pytest.approx(float(want.mean()), rel=tol)
    for b, s in ((0, 0), (1, 17), (1, 79)):
        one = jnp.zeros((2, 80)).at[b, s].set(2.0)
        assert float(lfm2_loss(model, params, tokens, one)) \
            == pytest.approx(float(want[b, s]), rel=30 * tol)


def test_every_gradient(pair):
    from horovod_tpu.models import lfm2_loss

    model, params, tokens, config, (_, _, tol) = pair
    got = jax.grad(lambda p: lfm2_loss(model, p, tokens))(params)
    want = jax.grad(lambda p: REFERENCE.token_losses(
        p, {"tokens": tokens}, config).mean())(params)
    flat = jax.tree_util.tree_leaves_with_path(got)
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    attention = sum(kind == "full_attention" for kind in kinds)
    dense = config["num_dense_layers"]
    # a layer: 2 norms; 3 of a convolution or 6 of attention; 3 of a dense
    # feed-forward or 5 of the experts; the embedding (= the head), a norm
    assert len(flat) == 2 * len(kinds) + 3 * (len(kinds) - attention) \
        + 6 * attention + 3 * dense + 5 * (len(kinds) - dense) + 2
    for (path, g), w in zip(flat, jax.tree.leaves(want)):
        if "select_bias" in jax.tree_util.keystr(path):
            # no gradient reaches the selection bias, in either
            assert float(jnp.abs(g).max()) == float(jnp.abs(w).max()) == 0
            continue
        assert float(jnp.abs(w).max()) > 0, path
        assert _close(g, w, tol), path


def test_the_head_is_the_embeddings_table():
    """Tied: no second vocabulary matrix in the tree, and the table's
    gradient is the gather's and the head's together."""
    from horovod_tpu.models import lfm2_loss

    model = FAMILY.build({**TINY, **STACKS["conv-dense"]}).clone(
        dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 33), 0, 256)
    params = model.init(jax.random.PRNGKey(5), tokens[:, :-1])["params"]
    assert set(params) == {"tok_emb", "layer0", "layer1", "final_norm"}
    grad = jax.grad(lambda p: lfm2_loss(model, p, tokens))(params)
    table = grad["tok_emb"]["embedding"]
    unseen = sorted(set(range(256)) - set(np.asarray(tokens[:, :-1]).ravel()))
    assert unseen and float(jnp.abs(table[jnp.asarray(unseen)]).min()) > 0


def test_the_step_holds_no_host_callback():
    """The model leaves the expert layers' stats behind: setting the
    program's gauges from inside the step takes a host callback, and a
    program that holds one is not kept in JAX's persistent compile cache
    (the expert layer by itself still sets them where it is asked to:
    ``tests/test_moe_held.py``)."""
    model = FAMILY.build(TINY)
    assert not hasattr(model, "publish_stats")
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 33), 0, 256)
    params = model.init(jax.random.PRNGKey(5), tokens[:, :-1])["params"]
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: model.apply({"params": p}, tokens[:, :-1],
                              tokens[:, 1:]).mean()))(params))
    assert "ragged_dot" in text and "callback" not in text


# -- the first step's three numbers, the control and the faults ---------------

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


def _faulty_route(change):
    """``moe.route_top_k`` with ``change(real, x, w, k, scale, score,
    bias)`` in its place."""
    from horovod_tpu.parallel import moe

    def wrap(real):
        def route(x, w, k, scale=1.0, score="softmax", select_bias=None):
            return change(real, x, w, k, scale, score, select_bias)
        return route
    return _patched(moe, "route_top_k", wrap)


def _weights_from_the_biased_scores(real, x, w, k, scale, score, bias):
    experts = real(x, w, k, scale, score, bias)[0]
    logits = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGH)
    chosen = jnp.take_along_axis(jax.nn.sigmoid(logits) + bias, experts, -1)
    return experts, chosen / (chosen.sum(-1, keepdims=True) + 1e-6) * scale


def _one_experts_routes_dropped(config):
    dropped = config["held_experts_first"] + 1

    def change(real, *args):
        experts, weights = real(*args)
        return experts, jnp.where(experts == dropped, 0.0, weights)
    return _faulty_route(change)()


def _faulty_conv(change):
    from horovod_tpu.models import lfm2

    return _patched(lfm2, "gated_short_conv",
                    lambda real: lambda b, c, x, taps: real(
                        *change(b, c, x, taps)))()


def _qk_norms_left_out():
    from horovod_tpu.models import lfm2

    class NoQKNorm(nn.Module):
        """``looplm.RMSNorm``, but the identity where it is named for q
        or k (the scale stays in the tree)."""

        eps: float = 1e-6
        dtype: object = jnp.bfloat16

        @nn.compact
        def __call__(self, x):
            scale = self.param("scale", nn.initializers.ones,
                               (x.shape[-1],), jnp.float32)
            if self.name in ("q_norm", "k_norm"):
                return x.astype(self.dtype)
            x = x.astype(jnp.float32)
            x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                  + self.eps)
            return (x * scale).astype(self.dtype)

    return _patched(lfm2, "RMSNorm", lambda real: NoQKNorm)()


# Each fault of the mathematics the cell's limits were held against
# (``config -> context``): while the context is open, a model that is
# built and traced has the fault; the reference never does.
FAULTS = {
    "bias_left_out_of_the_selection": lambda config: _faulty_route(
        lambda real, x, w, k, scale, score, bias:
        real(x, w, k, scale, score, None))(),
    "weights_from_the_biased_scores": lambda config: _faulty_route(
        _weights_from_the_biased_scores)(),
    "softmax_in_place_of_the_sigmoid": lambda config: _faulty_route(
        lambda real, x, w, k, scale, score, bias:
        real(x, w, k, scale, "softmax", bias))(),
    "gate_c_left_out": lambda config: _faulty_conv(
        lambda b, c, x, taps: (b, jnp.ones_like(c), x, taps)),
    "taps_in_reverse_order": lambda config: _faulty_conv(
        lambda b, c, x, taps: (b, c, x, taps[::-1])),
    "qk_norms_left_out": lambda config: _qk_norms_left_out(),
    "one_held_experts_routes_dropped": _one_experts_routes_dropped,
}


def _reference_numbers(params, batch):
    return train_lm._reference_first_step(REFERENCE, TINY, params, batch,
                                          1, 2, 1e-4)


def _system_numbers(params, batch):
    """What the job reads of the system's first step: the loss, the sum of
    Adam's second moments and each module's movement, through the
    family's model and loss and the cell's optimizer."""
    model = FAMILY.build(TINY)
    tx = optax.adamw(1e-4, mu_dtype=jnp.bfloat16)

    @jax.jit
    def step(params, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: FAMILY.loss(model, p, {"tokens": tokens}))(params)
        updates, state = tx.update(grads, tx.init(params), params)
        after = optax.apply_updates(params, updates)
        return (loss, train_lm._adam_nu_sum(state),
                train_lm._module_moves(after, params))

    loss, nu, moves = step(params, batch["tokens"])
    return [float(loss)], float(nu), {k: float(v) for k, v in moves.items()}


@pytest.fixture(scope="module")
def first_step():
    """Seeded weights and a batch at the rehearsal's size, and the plain
    reference's three numbers for them."""
    traffic = CAT.traffic(CAT.cell(CELL)["rehearsal"]["traffic"])
    params = FAMILY.build(TINY).init(
        jax.random.PRNGKey(3),
        jnp.zeros((1, traffic["seq_len"]), jnp.int32))["params"]
    batch = next(token_stream(3, traffic, TINY["vocab_size"]))
    return params, batch, _reference_numbers(params, batch)


def _limits(tolerance):
    return (tolerance["loss_rtol"], tolerance["grad_scale_rtol"],
            tolerance["module_move_rtol"])


def test_the_sound_system_is_correct_by_the_rehearsals_limits(first_step):
    params, batch, plain = first_step
    gaps = train_lm._gaps(*_system_numbers(params, batch), *plain)[:3]
    limits = _limits(CAT.cell(CELL)["rehearsal"]["tolerance"])
    assert all(gap <= limit for gap, limit in zip(gaps, limits)), gaps
    assert set(plain[2]) == set(params)     # every top-level module moved
    assert all(move > 0 for move in plain[2].values())


# At 128 tokens and 64 channels the bf16 step's own gaps (loss to 1.3e-3,
# sqrt(sum nu) to 7.6e-3, movement to 9.0e-5 over ten seeds) hide the three
# faults that change few routes or rescale what a norm follows; these four
# read over the rehearsal's limits on the seed used here (the first two on
# every seed tried).
SEEN_AT_THE_TINY_PRESET = ("gate_c_left_out", "one_held_experts_routes_dropped",
                           "softmax_in_place_of_the_sigmoid",
                           "taps_in_reverse_order")


@pytest.mark.parametrize("fault", SEEN_AT_THE_TINY_PRESET)
def test_a_fault_of_the_mathematics_is_not_correct(first_step, fault):
    """At the tiny preset, against the rehearsal's limits, by at least
    one of them. On the chip at the cell's size the same four read
    ``correct: false`` against the cell's limits and the other three of
    ``FAULTS`` (both faults of the selection bias, the QK norms) read
    ``correct: true``: the first step's three numbers do not see them
    (``PERF.md`` section 4); the test below holds all seven by the fp32
    logits."""
    params, batch, plain = first_step
    with FAULTS[fault](TINY):
        gaps = train_lm._gaps(*_system_numbers(params, batch), *plain)[:3]
    limits = _limits(CAT.cell(CELL)["rehearsal"]["tolerance"])
    assert any(gap > limit for gap, limit in zip(gaps, limits)), gaps


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_moves_the_logits_away_from_the_references(fault):
    """Every one of the seven, in fp32, where the sound system and the
    reference agree to rounding (two layers, one of each mixer, both with
    experts): the override is under the model, and the comparison sees
    it. Afterwards it is gone."""
    config = {**TINY, "num_hidden_layers": 2, "num_dense_layers": 0,
              "layer_types": ["conv", "full_attention"]}
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 81), 0, 256)

    def logits():
        model = FAMILY.build(config).clone(dtype=jnp.float32)
        return model.apply({"params": params}, tokens[:, :-1])

    params = FAMILY.build(config).init(jax.random.PRNGKey(5),
                                       tokens[:, :-1])["params"]
    with jax.default_matmul_precision("highest"):
        want = REFERENCE.logits(params, tokens[:, :-1], config)
        with FAULTS[fault](config):
            faulty = logits()
        assert not _close(faulty, want, 50 * TOLERANCE["float32"][1])
        assert _close(logits(), want, TOLERANCE["float32"][1])


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_the_reference_in_the_precision_below_is_not_correct_here_either(seed):
    """As for the other first-step cells: the plain reference with
    float8's mantissa in its matmul operands, in the program's place, on
    the cell's tiny preset against the cell's own limits: not correct."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from low_precision import matmul_operands_in

    cell = CAT.cell(CELL)
    traffic = CAT.traffic(cell["rehearsal"]["traffic"])
    params = FAMILY.build(TINY).init(
        jax.random.PRNGKey(seed),
        jnp.zeros((1, traffic["seq_len"]), jnp.int32))["params"]
    batch = next(token_stream(seed, traffic, TINY["vocab_size"]))
    plain = _reference_numbers(params, batch)
    with matmul_operands_in("float8_e4m3"):
        gaps = train_lm._gaps(*_reference_numbers(params, batch), *plain)[:3]
    limits = _limits(cell["tolerance"])
    assert limits == LIMITS
    assert any(gap > limit for gap, limit in zip(gaps, limits))


LIMITS = (4e-4, 8.5e-4, 1.5e-3)       # the cell's: loss, sqrt(sum nu), movement


# -- the configuration and the counts -----------------------------------------

def test_the_configuration_keeps_every_published_width():
    import json

    config = CAT.config(CONFIG)
    published = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
                 "intermediate_size": 7168, "max_position_embeddings": 128000,
                 "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
                 "norm_eps": 1e-5, "norm_topk_prob": True,
                 "num_attention_heads": 32, "num_dense_layers": 2,
                 "num_experts_per_tok": 4, "num_key_value_heads": 8,
                 "rope_theta": 1000000, "routed_scaling_factor": 1,
                 "use_expert_bias": True}
    assert {k: config[k] for k in published} == published
    attention = {2, 6, 10, 14, 18, 21}
    assert config["layer_types"] == [                   # copied whole
        "full_attention" if i in attention else "conv" for i in range(24)]
    assert config["router_width"] == 32     # the router keeps its width
    held = {"num_hidden_layers": 8, "num_experts": 8, "vocab_size": 16384,
            "held_experts_first": 0}
    assert {k: config[k] for k in held} == held
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 24,
                                   "num_experts": 32, "vocab_size": 65536}
    assert "three pipeline stages of 8" in config["deployment"]
    assert "4 chips share each layer" in config["deployment"]
    assert {"tie_word_embeddings", "norm_topk_eps", "expert_bias",
            "aux_loss", "initialization", "compute", "parameters"} \
        <= set(config["assumed"])
    assert "772,217,088" in config["assumed"]["parameters"]
    entry = next(c for c in CAT.index["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert json.dumps(config)       # plain data


def test_the_family_builds_the_share_of_the_published_model():
    from horovod_tpu.models import Lfm2LM

    model = FAMILY.build(CAT.config(CONFIG))
    assert model == Lfm2LM()        # the defaults ARE the cell: no callback
    assert (model.num_layers, model.hidden, model.num_dense_layers,
            model.mlp_dim) == (8, 2048, 2, 7168)
    assert model.layer_types[:8] == (
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv")
    assert (model.num_heads, model.num_kv_heads, model.head_dim,
            model.conv_taps) == (32, 8, 64, 3)
    assert (model.num_experts, model.held_experts, model.top_k,
            model.expert_dim) == (32, (0, 8), 4, 1792)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 128), jnp.int32))["params"])
    assert set(shapes) == {"tok_emb", "final_norm"} | {
        f"layer{i}" for i in range(8)}      # flat, and no second head
    count = {k: sum(x.size for x in jax.tree.leaves(v))
             for k, v in shapes.items()}
    conv = 2048 * 3 * 2048 + 2048 * 2048 + 3 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    norms, bias = 2 * 2048, 32
    dense = 3 * 2048 * 7168
    experts = 8 * 3 * 2048 * 1792 + 2048 * 32
    assert conv == 16_783_360 and attention == 10_485_888
    assert count["layer0"] == count["layer1"] == conv + norms + dense \
        == 60_827_648
    for i in (3, 4, 5, 7):
        assert count[f"layer{i}"] == conv + norms + experts + bias \
            == 104_933_376 + 32
    for i in (2, 6):
        assert count[f"layer{i}"] == attention + norms + experts + bias \
            == 98_635_904 + 32
    assert count["tok_emb"] == 16384 * 2048 and count["final_norm"] == 2048
    # the trained parameters, and six selection biases no gradient reaches
    assert sum(count.values()) - 6 * bias == 772_217_088


def test_train_flops_per_token_by_hand():
    config = CAT.config(CONFIG)
    # Matmul weights a token meets: a convolution's two projections 2048 x
    # 6144 + 2048 x 2048; attention's q and o 2048 x 2048 each, k and v
    # 2048 x 512 each; a dense feed-forward 3 x 2048 x 7168; an expert
    # layer the router 2048 x 32 and 4 x 8 / 32 = one expert of 3 x 2048 x
    # 1792; the tied head 16384 x 2048.
    conv, attention = 16_777_216, 10_485_760
    dense, expert = 44_040_192, 65_536 + 11_010_048
    weights = 2 * (conv + dense) + 4 * (conv + expert) \
        + 2 * (attention + expert) + 33_554_432
    assert weights == 309_723_136
    # Causal attention: 6 x S x 2048 in each of the two attention layers.
    assert FAMILY.train_flops_per_token(config, 8192) \
        == 6 * weights + 2 * 6 * 8192 * 2048 == 2_059_665_408
    assert FAMILY.attention_calls(config, 2, 8192) == {
        "calls": 2, "batch": 2, "heads": 32, "seq_len": 8192,
        "head_dim": 64, "causal": True}


def test_the_convolutions_cost_by_hand():
    config = CAT.config(CONFIG)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert lfm2_cost.conv_layers(config) == 6
    assert lfm2_cost.conv_layers(TINY) == 6
    flops, nbytes = lfm2_cost.short_conv_step_cost(config, 16384)
    # a token, a layer: the forward reads B, C, X and writes y (16,384 B in
    # bf16), the backward reads B, C, X, dy and writes dB, dC, dX (28,672)
    assert flops == 0.0
    assert nbytes == 6 * 16384 * (16_384 + 28_672) == 4_429_185_024
    ms, bound = moe_kda_cost.least_ms((flops, nbytes), peaks)
    assert bound == "memory" and ms == pytest.approx(5.408, rel=1e-3)
    assert moe_kda_cost.config_of_metric("short_conv_roofline_pct") == config
    assert moe_kda_cost.config_of_metric("short_conv_ms") == config


# -- the cell's two readers, on a hand-made trace -----------------------------

FWD = "jit(step)/jvp(Lfm2LM)/layer3/"
BACK = "jit(step)/transpose(jvp(Lfm2LM))/jvp(Lfm2LM)/checkpoint/layer3/"
FUSION = "%fusion.{} = f32[8]{{0}} fusion(%p.1), kind=kLoop"
CALL = (' = f32[8]{{0}} custom-call(%p.1), '
        'custom_call_target="tpu_custom_call"')
# A later PR's kernels: the forward called under the scope, the backward
# (a ``custom_vjp``'s, traced outside it) found by its name alone.
CONV_FWD = "%hvd_short_conv_fwd.{}" + CALL
CONV_BWD = "%hvd_short_conv_bwd.{}" + CALL
# (instruction, microseconds, op_name), one after the other on one device.
EVENTS = [
    (FUSION, 90, FWD + "mixer/in_proj/dot_general"),
    (FUSION, 21, FWD + "mixer/hvd_short_conv/mul"),
    (FUSION, 80, FWD + "mixer/out_proj/dot_general"),
    (FUSION, 12, FWD + "ffn/hvd_moe_route/top_k"),
    ("%ragged-dot-none.{}" + CALL, 50, "ragged-dot-none"),
    ("%hvd_flash_fwd.{}" + CALL, 20, "jit(step)/jvp(Lfm2LM)/layer2/mixer/"
     "hvd_flash_fwd/pallas_call"),
    (FUSION, 19, BACK + "rematted_computation/mixer/hvd_short_conv/mul"),
    (FUSION, 33, BACK + "mixer/hvd_short_conv/reduce_sum"),
    (FUSION, 70, BACK + "mixer/in_proj/dot_general"),
    (CONV_FWD, 7, FWD + "mixer/hvd_short_conv/pallas_call"),
    (CONV_BWD, 11, "jit(step)/transpose(jvp(Lfm2LM))/pallas_call"),
    (FUSION, 43, "jit(step)/hvd_update/mul"),
]
XLA_ONLY = [e for e in EVENTS if "tpu_custom_call" not in e[0]]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _record(events, steps=1):
    out, start = [], 0.0
    for i, (name, us, op_name) in enumerate(events):
        out.append([name.format(i), start, us * 1e3, "", op_name, 1])
        start += us * 1e3
    trace = {"devices": {"/device:TPU:0": out}, "hlo": {}}
    return {"trace": {"steps": steps},
            "of_which_trace": of_which._without_loops(trace),
            "cell": {"peaks": PEAKS, "tokens_per_step": 16384, "chips": 1}}


def test_short_conv_ms_sums_the_time_under_the_scope_and_its_kernels():
    read = CAT.module("layer_metrics", "short_conv_ms").read
    # forward, forward again, backward; not the projections beside them
    assert read(_record(XLA_ONLY)) == pytest.approx(0.073)
    assert read(_record(XLA_ONLY, steps=2)) == pytest.approx(0.0365)
    # a kernel under the scope, and one named for it alone
    assert read(_record(EVENTS)) == pytest.approx(0.091)
    kernels = [e for e in EVENTS if e[0] in (CONV_FWD, CONV_BWD)]
    assert read(_record(kernels)) == pytest.approx(0.018)
    # a program none of whose events carries the name (the parent's, or
    # another cell's): nothing to read, and nothing raised
    others = [e for e in EVENTS if "hvd_short_conv" not in e[0] + e[2]]
    assert len(others) == 7 and read(_record(others)) is None
    assert read({"trace": {}}) is None and read({}) is None
    assert read({"trace": {"steps": 3}, "of_which_trace": None}) is None


def test_the_roofline_divides_the_least_traffic_by_the_reading():
    read = CAT.module("layer_metrics", "short_conv_roofline_pct").read
    assert read(_record(EVENTS)) == pytest.approx(100 * 5.408 / 0.091,
                                                  rel=1e-3)
    assert read(_record(XLA_ONLY)) == pytest.approx(100 * 5.408 / 0.073,
                                                    rel=1e-3)
    assert read({**_record(EVENTS), "cell": {}}) is None
    assert read(_record(EVENTS[:1])) is None
    assert read({}) is None


def test_the_cell_reports_the_two_readings_and_no_other_cell_does():
    two = {"short_conv_ms", "short_conv_roofline_pct"}
    for entry in CAT.index["workloads"]:
        names = {m["name"] for m in CAT.metrics("per_layer", entry["name"])}
        assert (two <= names) == (entry["name"] == CELL)
        assert not (two & names) or two <= names
    for m in CAT.index["per_layer"]:
        if m["name"] in two:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "train_tokens_per_s"
            assert m["layer"] == "short convolution"
            assert m["source"] == "device_trace"
    # the 20 readings every cell reports, and the two of its new span
    common = {m["name"] for m in CAT.index["per_layer"]
              if "workloads" not in m}
    assert len(common) == 20
    assert {m["name"] for m in CAT.metrics("per_layer", CELL)} == common | two
    cell = CAT.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"], cell["job"],
            cell["check_steps"], cell["reference_microbatch"]) == (
                CONFIG, "lm-b2-s8192", 1, "train_lm", 1, 1)
    assert cell["optimizer"] == {"learning_rate": 1e-4,
                                 "mu_dtype": "bfloat16",
                                 "compression": "none"}
    assert cell["rehearsal"]["config"] == "lfm2-tiny"
    traffic = CAT.traffic("lm-b2-s8192")
    assert (traffic["batch"], traffic["seq_len"]) == (2, 8192)
    assert "names" not in cell
    assert CAT.index["workloads"][-1]["name"] == CELL    # added at the end
