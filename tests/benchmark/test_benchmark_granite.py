"""Family ``granite`` (``ibm-granite/granite-4.0-h-micro``: Mamba-2
state-space layers nine in ten beside grouped-query attention without
positions, a SwiGLU in every layer, Granite's four multipliers, a tied
head) on the CPU at its tiny preset: the system against the plain
reference on seeded weights (logits, the loss, every gradient), the
reference's token-by-token recurrence against ``ops/ssd.py``'s oracle,
the configuration's file against the published widths and the tree's
parameter count, the family's counts by hand, the cell's two readers and
the accepted readers the two new cells are bound to,
the faults of the mathematics (``FAULTS``: a scratch script on the chip
puts the same overrides under the timed path), the data-only four-chip
cell beside it, and the earlier PRs' positional tests run whole on the
lists as they stood before this PR. This file's own tests hold order and
membership, never the end of a list or its length. Nothing here touches a
device."""

import contextlib
import importlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import flops, granite_cost, of_which
from benchmark.catalog import Catalog
from benchmark.jobs import train_lm
from benchmark.stream import token_stream

CAT = Catalog()
FAMILY = CAT.module("families", "granite")
REFERENCE = CAT.module("reference", "granite")
TINY = CAT.config("granite-tiny")
CONFIG = "granite-4.0-h-micro-l10"
CELL = "granite-4.0-h-micro-l10-s8192"
DP4 = "gpt2s-s512-dp4"
READERS = ["ssd_ms", "ssd_roofline_pct"]
# the accepted readers whose scopes the new cells' steps hold, and which of
# the two cells each had appended to its list
BOUND = {"short_conv_ms": [CELL], "loss_ms": [CELL], "rope_ms": [DP4],
         **{name: [CELL, DP4] for name in (
             "mixer_proj_ms", "mlp_ms", "norm_ms", "embed_ms")}}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
LENGTH = 32
# (loss, logits, gradients): the arithmetic of the two agrees to fp32
# rounding (the logits to 2.3e-7 here; the weakest fault, a rotation on
# attention that is nearly flat at a scale of 1/64, moves them by 2.3e-3);
# in bf16 the system's operands are rounded.
TOLERANCE = {"float32": (1e-5, 2e-5, 3e-4), "bfloat16": (3e-3, 1e-1, 5e-1)}


def _close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) <= tol * np.linalg.norm(want)


def _tokens(seed=4, rows=2):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, LENGTH + 1),
                              0, TINY["vocab_size"])


@pytest.fixture(scope="module", params=sorted(TOLERANCE))
def pair(request):
    """The system's tiny model in one compute dtype, its seeded
    parameters and a batch; the reference reads the same tree."""
    from horovod_tpu.models import GraniteHybridLM

    model = FAMILY.build(TINY)
    assert isinstance(model, GraniteHybridLM) and model.dtype == jnp.bfloat16
    model = model.clone(dtype=jnp.dtype(request.param))
    tokens = _tokens()
    params = model.init(jax.random.PRNGKey(5), tokens[:, :-1])["params"]
    return model, params, tokens, TOLERANCE[request.param]


def test_the_tiny_preset_has_what_the_cell_has():
    """Both layer kinds over two periods, so that a second attention layer
    follows state-space layers; more than one chunk a sequence and a
    chunk that is not the sequence; grouped K/V heads; the four
    multipliers as published."""
    assert TINY["num_hidden_layers"] == 6
    held = TINY["layer_types"][:6]
    assert held == ["mamba", "attention", "mamba"] * 2
    assert 1 < LENGTH // TINY["mamba_chunk_size"] < LENGTH
    assert TINY["mamba_n_heads"] * TINY["mamba_d_head"] \
        == TINY["mamba_expand"] * TINY["hidden_size"]
    assert TINY["num_attention_heads"] > TINY["num_key_value_heads"] > 1
    published = CAT.config(CONFIG)
    for key in ("embedding_multiplier", "attention_multiplier",
                "residual_multiplier", "logits_scaling", "mamba_d_conv",
                "mamba_n_groups", "position_embedding_type", "rms_norm_eps",
                "mamba_conv_bias", "mamba_proj_bias", "tie_word_embeddings"):
        assert TINY[key] == published[key], key
    # the scale is not the head's default at either size
    for config in (TINY, published):
        width = config["hidden_size"] // config["num_attention_heads"]
        assert config["attention_multiplier"] != width ** -0.5


def test_the_logits(pair):
    model, params, tokens, (_, tol, _) = pair
    logits = jax.jit(model.apply)({"params": params}, tokens[:, :-1])
    want = REFERENCE.logits(params, tokens[:, :-1], TINY)
    assert logits.shape == want.shape == (2, LENGTH, TINY["vocab_size"])
    assert logits.dtype == jnp.float32
    assert _close(logits, want, tol)


def test_bf16_in_place_of_fp32_is_seen_by_the_fp32_limits(pair):
    """The comparison is tight enough that the precision below fails it:
    the bf16 system is not the fp32 reference by the fp32 limit, which the
    fp32 system meets."""
    model, params, tokens, _ = pair
    logits = jax.jit(model.apply)({"params": params}, tokens[:, :-1])
    want = REFERENCE.logits(params, tokens[:, :-1], TINY)
    assert _close(logits, want, TOLERANCE["float32"][1]) \
        == (model.dtype == jnp.float32)


def test_the_loss_is_the_mean_the_job_makes(pair):
    from horovod_tpu.models import granite_loss

    model, params, tokens, (tol, _, _) = pair
    want = REFERENCE.token_losses(params, {"tokens": tokens}, TINY)
    assert want.shape == (2, LENGTH) and want.dtype == jnp.float32
    assert float(granite_loss(model, params, tokens)) \
        == pytest.approx(float(want.mean()), rel=tol)
    assert float(FAMILY.loss(model, params, {"tokens": tokens})) \
        == pytest.approx(float(want.mean()), rel=tol)


def test_every_gradient(pair):
    model, params, tokens, (_, _, tol) = pair
    got = jax.jit(jax.grad(
        lambda p: FAMILY.loss(model, p, {"tokens": tokens})))(params)
    want = jax.jit(jax.grad(lambda p: REFERENCE.token_losses(
        p, {"tokens": tokens}, TINY).mean()))(params)
    flat = jax.tree_util.tree_leaves_with_path(got)
    # a layer: 2 norms and 3 of the feed-forward; a state-space mixer's 8
    # (in_proj, conv, conv_bias, dt_bias, A_log, D, norm, out_proj), an
    # attention mixer's 4; the embedding (tied) and the final norm
    assert len(flat) == 6 * 5 + 4 * 8 + 2 * 4 + 2
    for (path, g), w in zip(flat, jax.tree.leaves(want)):
        assert float(jnp.abs(w).max()) > 0, path
        assert _close(g, w, tol), path


def test_the_references_recurrence_is_the_oracles_and_not_the_scans():
    """Token by token, as ``ops/ssd.py`` ``ssd_reference`` runs it (the
    two were written apart), with blocks that do and do not divide the
    sequence; and no part of the chunked algorithm: one ``while`` over
    the blocks and no pair matrix."""
    from horovod_tpu.ops import ssd

    k = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(k[0], (2, 37, 4, 8))
    delta = jax.nn.softplus(jax.random.normal(k[1], (2, 37, 4)) - 1.0)
    a = -jnp.exp(jax.random.uniform(k[2], (4,), maxval=2.7))
    b = jax.random.normal(k[3], (2, 37, 4, 16))
    c = jax.random.normal(k[4], (2, 37, 4, 16))
    inverse = delta + jnp.log(-jnp.expm1(-delta))      # softplus^-1
    want = ssd.ssd_reference(x, inverse, a, b, c, jnp.zeros(4))
    for block in (128, 8, 37):
        REFERENCE._TOKEN_BLOCK = block
        try:
            got = REFERENCE._recurrence(x, delta, a, b, c)
        finally:
            REFERENCE._TOKEN_BLOCK = 128
        assert _close(got, want, 1e-6), block
    text = str(jax.make_jaxpr(REFERENCE._recurrence)(x, delta, a, b, c))
    assert "scan" in text and "cumsum" not in text
    source = open(REFERENCE.__file__).read()
    assert "ssd_scan" not in source.split('"""', 2)[2]
    assert "horovod_tpu" not in source.split('"""', 2)[2]


def test_layer_types_is_read_up_to_the_depth_held():
    """The published list of 40 is copied whole; the ten layers held read
    its first ten entries, and a depth of six reads six."""
    from horovod_tpu.models import granite

    config = CAT.config(CONFIG)
    assert len(config["layer_types"]) == 40 > config["num_hidden_layers"]
    model = FAMILY.build(config)
    kinds = [model.layer_parts(i)[0].__name__ for i in range(10)]
    assert kinds == ["Mamba2Mixer"] * 5 + ["RotaryGQA"] \
        + ["Mamba2Mixer"] * 4
    assert tuple(config["layer_types"]) == granite._PATTERN
    assert granite_cost.ssm_layers(config) == 9
    assert granite_cost.ssm_layers({**config, "num_hidden_layers": 6}) == 5
    assert granite_cost.ssm_layers({**config, "num_hidden_layers": 5}) == 5
    shallow = FAMILY.build({**TINY, "num_hidden_layers": 3})
    shapes = jax.eval_shape(lambda: shallow.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    assert set(shapes) == {"tok_emb", "layer0", "layer1", "layer2",
                           "final_norm"}
    assert "A_log" in shapes["layer2"]["mixer"]
    assert set(shapes["layer1"]["mixer"]) == {"q", "k", "v", "o"}


# -- faults of the mathematics --------------------------------------------

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
    """``GraniteHybridLM`` built with ``fields`` whatever it is given."""
    from horovod_tpu.models import granite

    def wrap(real):
        def post_init(self):
            for key, value in fields.items():
                object.__setattr__(self, key, value)
            real(self)
        return post_init
    return lambda config: _patched(granite.GraniteHybridLM, "__post_init__",
                                   wrap)()


# positions in ``RotaryGQA``'s arguments
ROPE_BASE, ROTATION, SCALE = 3, 7, 11


def _in_the_attention_layers(change):
    """``GraniteHybridLM.layer_parts`` with ``change(mixer_args)`` over
    an attention layer's arguments (as a list)."""
    from horovod_tpu.models import granite

    def wrap(real):
        def layer_parts(self, i):
            mixer, mixer_args, ffn, ffn_args = real(self, i)
            if mixer is granite.RotaryGQA:
                mixer_args = list(mixer_args)
                change(mixer_args)
            return mixer, tuple(mixer_args), ffn, ffn_args
        return layer_parts
    return lambda config: _patched(granite.GraniteHybridLM, "layer_parts",
                                   wrap)()


def _scale(value):
    def change(args):
        args[SCALE] = value
    return _in_the_attention_layers(change)


def _rotated(args):
    args[ROTATION], args[ROPE_BASE] = None, 10000.0


def _in_the_mixer(name, new):
    from horovod_tpu.models import granite

    return lambda config: _patched(granite, name, new)()


def _gate_after_the_norm(real):
    def norm_then_gate(y, z, scale, eps, groups):
        ones = jnp.full(z.shape, 1.2784645, z.dtype)    # silu(ones) = 1
        return real(y, ones, scale, eps, groups) / jax.nn.silu(
            ones.astype(jnp.float32)) * jax.nn.silu(z.astype(jnp.float32))
    return norm_then_gate


# (``config -> context``): while the context is open, a model that is
# built and traced has the fault; the reference never does. Every one
# keeps the tree's shapes.
FAULTS = {
    "embedding_multiplier_left_out": _with_fields(embedding_multiplier=1.0),
    "residual_multiplier_left_out": _with_fields(residual_multiplier=1.0),
    "logits_scaling_left_out": _with_fields(logits_scaling=1.0),
    # the head's default, head_dim ** -0.5: 1/8 at the published width
    "attention_multiplier_left_out": _scale(None),
    "scale_an_eighth_for_a_sixty_fourth": _scale(0.125),
    "attention_rotated": _in_the_attention_layers(_rotated),
    "d_skip_left_out": _in_the_mixer("ssd_scan", lambda real: (
        lambda x, dt, a, b, c, d, *rest: real(
            x, dt, a, b, c, jnp.zeros_like(d), *rest))),
    "dt_bias_left_out": _in_the_mixer("ssd_scan", lambda real: (
        lambda x, dt, a, b, c, d, dt_bias, chunk: real(
            x, dt, a, b, c, d, None, chunk))),
    "gate_after_the_norm": _in_the_mixer("_gated_norm",
                                         _gate_after_the_norm),
    "conv_bias_left_out": _in_the_mixer("_conv_act", lambda real: (
        lambda xbc, taps, bias: real(xbc, taps, jnp.zeros_like(bias)))),
    "conv_silu_left_out": _in_the_mixer("_conv_act", lambda real: (
        lambda xbc, taps, bias: (
            sys.modules["horovod_tpu.models.granite"].causal_conv(xbc, taps)
            + bias).astype(xbc.dtype))),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_moves_the_system_away_from_the_reference(fault):
    """Every one of the eleven, in fp32, where the sound system and the
    reference agree to rounding: the override is under the model, and the
    comparison of the logits sees it. Afterwards it is gone."""
    tokens = _tokens()

    def system(params=None):
        model = FAMILY.build(TINY).clone(dtype=jnp.float32)
        if params is None:
            params = model.init(jax.random.PRNGKey(5),
                                tokens[:, :-1])["params"]
        return params, jax.jit(model.apply)({"params": params},
                                             tokens[:, :-1])

    with jax.default_matmul_precision("highest"):
        params, sound = system()
        want = REFERENCE.logits(params, tokens[:, :-1], TINY)
        with FAULTS[fault](TINY):
            other, faulty = system(params)
            assert jax.tree.map(jnp.shape, system()[0]) \
                == jax.tree.map(jnp.shape, params)
        assert not _close(faulty, want, 50 * TOLERANCE["float32"][1])
        again = system(params)[1]
        assert _close(sound, want, TOLERANCE["float32"][1])
        assert np.array_equal(np.asarray(again), np.asarray(sound))


def test_the_gate_fault_is_the_norm_before_the_gate():
    """What the override computes is ``RMSNorm(y) * w * silu(z)``."""
    from horovod_tpu.models import granite

    k = jax.random.split(jax.random.PRNGKey(1), 3)
    y, z = (jax.random.normal(k[i], (2, 5, 32)) for i in (0, 1))
    scale = jax.random.normal(k[2], (32,))
    got = _gate_after_the_norm(granite._gated_norm)(y, z, scale, 1e-5, 2)
    yg = y.reshape(2, 5, 2, 16)
    normed = (yg / jnp.sqrt((yg ** 2).mean(-1, keepdims=True) + 1e-5)
              ).reshape(y.shape) * scale
    assert _close(got, normed * jax.nn.silu(z), 1e-5)
    assert not _close(got, granite._gated_norm(y, z, scale, 1e-5, 2), 0.1)


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
    assert traffic["seq_len"] == LENGTH
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
    assert set(plain[2]) == set(params) == {
        "tok_emb", *(f"layer{i}" for i in range(6)), "final_norm"}
    assert all(move > 0 for move in plain[2].values())


# What the rehearsal's three numbers see at 128 tokens (the cell's file
# says by how much): every fault but the rotation, which at a scale of
# 1/64 lies inside the bf16 system's own distance from the reference there
# and is held by the fp32 logits above. Five of the ten are run here, one
# of each kind: a multiplier, the attention's scale, the scan's skip, the
# gate's place, the convolution's activation.
SEEN_BY_THE_REHEARSAL = ["embedding_multiplier_left_out",
                         "scale_an_eighth_for_a_sixty_fourth",
                         "d_skip_left_out", "gate_after_the_norm",
                         "conv_silu_left_out"]
assert set(SEEN_BY_THE_REHEARSAL) < set(FAULTS) - {"attention_rotated"}


@pytest.mark.parametrize("fault", SEEN_BY_THE_REHEARSAL)
def test_a_fault_of_the_mathematics_is_not_correct(first_step, fault):
    params, batch, plain = first_step
    with FAULTS[fault](TINY):
        gaps = train_lm._gaps(*_system_numbers(params, batch), *plain)[:3]
    limits = _limits(CAT.cell(CELL)["rehearsal"]["tolerance"])
    assert any(gap > limit for gap, limit in zip(gaps, limits)), gaps


@pytest.mark.parametrize("seed", [1, 2])
def test_the_reference_in_the_precision_below_is_not_correct_here_either(seed):
    """As for the other first-step cells: the plain reference with
    float8's mantissa in its matmul operands, in the program's place, on
    the cell's tiny preset against the cell's own limits: not correct."""
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
    assert any(gap > limit for gap, limit in zip(gaps, limits))


# -- the configuration and the counts -------------------------------------

def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        with open(path) as f:
            rows = [json.loads(line) for line in f]
    except FileNotFoundError:
        pytest.skip("the catalog of public architectures is not here")
    return next(r for r in rows if r["name"] == "granite-4.0-h-micro")


def test_the_configuration_keeps_every_published_width():
    config = CAT.config(CONFIG)
    published = {
        "model_type": "granitemoehybrid", "hidden_size": 2048,
        "intermediate_size": 8192, "shared_intermediate_size": 8192,
        "num_attention_heads": 32, "num_key_value_heads": 8,
        "mamba_n_heads": 64, "mamba_d_head": 64, "mamba_d_state": 128,
        "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_chunk_size": 256,
        "mamba_expand": 2, "mamba_conv_bias": True,
        "mamba_proj_bias": False, "attention_bias": False,
        "embedding_multiplier": 12, "attention_multiplier": 0.015625,
        "residual_multiplier": 0.22, "logits_scaling": 8,
        "position_embedding_type": "nope", "rms_norm_eps": 1e-5,
        "num_local_experts": 0, "num_experts_per_tok": 0,
        "tie_word_embeddings": True, "hidden_act": "silu",
        "normalization_function": "rmsnorm",
        "max_position_embeddings": 131072}
    assert {k: config[k] for k in published} == published
    assert config["layer_types"] == [
        "attention" if i % 10 == 5 else "mamba" for i in range(40)]
    held = {"num_hidden_layers": 10, "vocab_size": 25088}
    assert {k: config[k] for k in held} == held
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 40,
                                   "vocab_size": 100352}
    assert config["vocab_size"] * 4 == config["published"]["vocab_size"]
    assert config["num_hidden_layers"] * 4 \
        == config["published"]["num_hidden_layers"]
    assert config["deployment"].startswith("16 chips")
    assert "four pipeline stages of ten" in config["deployment"]
    assert "The number stated is the 4 that share a layer" \
        in config["deployment"]
    assert {"mamba_in_proj_order", "mamba_conv", "mamba_dt",
            "mamba_gate_and_norm", "mamba_skip", "attention", "head_dim",
            "feed_forward", "multipliers", "layer_types", "initialization",
            "compute", "parameters"} <= set(config["assumed"])
    assert "797,850,560" in config["assumed"]["parameters"]
    entry = next(c for c in CAT.index["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert json.dumps(config)       # plain data


def test_the_configuration_is_the_catalogs_but_for_what_reduced_names():
    row = _catalog_row()
    config = CAT.config(CONFIG)
    assert config["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == set(config["reduced"])
    assert {k: row["config"][k] for k in differs} == config["published"]
    # the tiny preset changes sizes and nothing of the kind
    kept = {k for k, v in row["config"].items() if TINY.get(k) == v}
    assert {"embedding_multiplier", "attention_multiplier",
            "residual_multiplier", "logits_scaling", "mamba_d_conv",
            "mamba_n_groups", "mamba_expand", "model_type"} <= kept


def test_the_family_builds_the_share_of_the_published_model():
    """The parameter count of the configuration's file is the tree's,
    line by line."""
    from horovod_tpu.models import GraniteHybridLM

    config = CAT.config(CONFIG)
    model = FAMILY.build(config)
    assert model == GraniteHybridLM()       # the defaults are the cell's
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 512), jnp.int32))["params"])
    count = {k: sum(x.size for x in jax.tree.leaves(v))
             for k, v in shapes.items()}
    mamba = 2048 * 8512 + (4 * 4352 + 4352) + 3 * 64 + 4096 + 4096 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512
    swiglu = 3 * 2048 * 8192
    assert (mamba, attention, swiglu) == (25_847_232, 10_485_760,
                                          50_331_648)
    assert count == {
        **{f"layer{i}": mamba + swiglu + 4096 for i in range(10) if i != 5},
        "layer5": attention + swiglu + 4096,
        "tok_emb": 51_380_224, "final_norm": 2_048}
    assert count["layer0"] == 76_182_976 and count["layer5"] == 60_821_504
    assert sum(count.values()) == 797_850_560
    for part in ("76,182,976", "60,821,504", "51,380,224", "17,432,576",
                 "21,760", "8,388,608", "10,485,760", "50,331,648"):
        assert part in config["assumed"]["parameters"], part
    mixer = shapes["layer0"]["mixer"]
    assert mixer["in_proj"]["kernel"].shape == (2048, 8512)
    assert mixer["conv"].shape == (4, 4352)
    assert mixer["conv_bias"].shape == (4352,)
    assert mixer["out_proj"]["kernel"].shape == (4096, 2048)
    assert mixer["norm"]["scale"].shape == (4096,)
    assert {mixer[n].shape for n in ("dt_bias", "A_log", "D")} == {(64,)}
    assert set(shapes["layer5"]["mixer"]) == {"q", "k", "v", "o"}
    assert shapes["layer5"]["mixer"]["k"]["kernel"].shape == (2048, 512)
    assert "lm_head" not in shapes      # tied
    with pytest.raises(ValueError, match="no positions"):
        FAMILY.build({**config, "position_embedding_type": "rope"})


def test_the_start_is_mamba_2s():
    """``A`` in [-16, -1], the step size in [1e-3, 0.1] through the
    softplus, ``D`` 1, a convolution bias that is not zero."""
    from horovod_tpu.models import Mamba2Mixer

    mixer = jax.jit(Mamba2Mixer(64, 8, 16).init)(
        jax.random.PRNGKey(11), jnp.zeros((1, 8, 32), jnp.bfloat16))["params"]
    a = np.exp(np.asarray(mixer["A_log"]))
    assert a.min() >= 1.0 and a.max() <= 16.0 and a.std() > 1.0
    delta = np.asarray(jax.nn.softplus(mixer["dt_bias"]))
    assert delta.min() >= 1e-3 * 0.999 and delta.max() <= 0.1 * 1.001
    assert delta.max() > 10 * delta.min()
    assert np.all(np.asarray(mixer["D"]) == 1.0)
    bias = np.asarray(mixer["conv_bias"])
    assert np.abs(bias).max() <= 0.5 and bias.std() > 0.1


def test_train_flops_per_token_by_hand():
    config = CAT.config(CONFIG)
    weights = 9 * (17_432_576 + 8_388_608) + 10_485_760 \
        + 10 * 50_331_648 + 51_380_224
    assert weights == 797_573_120
    attention = 6 * 8192 * 2048
    scan = 128 * 256 + 64 * 64 * 256 + 4 * 64 * 128 * 64
    assert scan == 3_178_496
    assert granite_cost.ssd_flops_per_token_forward(config) == scan
    assert FAMILY.train_flops_per_token(config, 8192) \
        == 6 * weights + attention + 9 * 3 * scan == 4_971_921_408
    # a step: 8,192 tokens
    assert 8192 * 4_971_921_408 == pytest.approx(40.7e12, rel=1e-3)
    # the head's share is the whole model's
    assert 51_380_224 / weights == pytest.approx(0.0644, abs=2e-4)
    whole = 36 * (17_432_576 + 8_388_608) + 4 * 10_485_760 \
        + 40 * 50_331_648 + 4 * 51_380_224
    assert 4 * 51_380_224 / whole == pytest.approx(0.0644, abs=5e-4)
    # a depth without the attention layer counts none
    five = {**config, "num_hidden_layers": 5}
    assert FAMILY.train_flops_per_token(five, 8192) == 6 * (
        5 * (17_432_576 + 8_388_608 + 50_331_648) + 51_380_224) \
        + 5 * 3 * scan


def test_the_scans_cost_by_hand():
    """Operations with the in-chunk triangle counted half; bytes of the
    operands and results alone; the floor narrowly memory's."""
    config = CAT.config(CONFIG)
    ops, nbytes = granite_cost.ssd_step_cost(config, 8192)
    assert ops == 9 * 8192 * 3 * 3_178_496 == pytest.approx(0.703e12,
                                                            rel=1e-3)
    assert granite_cost.ssd_bytes_per_token(config) \
        == 2 * (5 * 4096 + 3 * 64 + 6 * 128) == 42_880
    assert nbytes == 9 * 8192 * 42_880
    seconds, bound = flops.roofline_seconds(ops, nbytes, PEAKS)
    assert bound == "memory"
    assert 1e3 * seconds == pytest.approx(3.86, abs=0.01)
    assert 1e3 * ops / PEAKS["bf16_flops_per_s"] == pytest.approx(3.57,
                                                                  abs=0.01)
    # the whole square in place of the triangle: a third more
    whole = 2 * 128 * 256 + 2 * 64 * 64 * 256 + 4 * 64 * 128 * 64
    assert whole == 4_259_840
    # more groups: more of B and C
    wide = {**config, "mamba_n_groups": 4}
    assert granite_cost.ssd_flops_per_token_forward(wide) \
        == 3_178_496 + 3 * 128 * 256
    assert granite_cost.ssd_bytes_per_token(wide) == 42_880 + 2 * 18 * 128


def test_the_attention_call_is_lfm2s_shape():
    config = CAT.config(CONFIG)
    calls = FAMILY.attention_calls(config, 1, 8192)
    assert calls == {"calls": 1, "batch": 1, "heads": 32, "seq_len": 8192,
                     "head_dim": 64, "causal": True}
    lfm2 = CAT.module("families", "lfm2").attention_calls(
        CAT.config("lfm2-8b-a1b-l8-e8"), 1, 8192)
    assert {**lfm2, "calls": 1} == calls


# -- the cell's readers -----------------------------------------------------

CALL = ' = custom-call(...), custom_call_target="tpu_custom_call"'
LAYER = "jit(step)/jvp(GraniteHybridLM)/layer0/mixer/"
BACK = "jit(step)/transpose(jvp(GraniteHybridLM))/layer0/mixer/"
# One state-space layer of a step as the trace of the compiled program
# names it: the in-projection, the convolution, the scan's fusions
# forward, the gated norm, and the backward of each; a flash call and the
# feed-forward beside them.
EVENTS = [
    ("%fusion.{}", 900, LAYER + "hvd_mixer_proj/in_proj/dot_general"),
    ("%fusion.{}", 120, LAYER + "hvd_short_conv/mul"),
    ("%fusion.{}", 400, LAYER + "hvd_ssd/exp"),
    ("%fusion.{}", 700, LAYER + "hvd_ssd/bzgrqk,bzkgrp->bzqgrp/dot_general"),
    ("%fusion.{}", 80, LAYER + "hvd_mixer_proj/norm/mul"),
    ("%hvd_flash_fwd.{}" + CALL, 500, "jit(step)/layer5/mixer/pallas_call"),
    ("%fusion.{}", 2000, "jit(step)/jvp(GraniteHybridLM)/layer0/ffn/hvd_mlp/"
                         "dot_general"),
    ("%fusion.{}", 1500, BACK + "hvd_ssd/bzgrqk,bzkgrp->bzqgrp/dot_general"),
    ("%fusion.{}", 300, BACK + "hvd_ssd/mul"),
    ("%fusion.{}", 260, BACK + "hvd_short_conv/mul"),
]
# the same step with the scan as a later PR's kernels, named by the
# contract: the scope's string as the prefix
KERNELS = [e for e in EVENTS if "hvd_ssd" not in e[2]] + [
    ("%hvd_ssd_fwd.{}" + CALL, 350, LAYER + "hvd_ssd/pallas_call"),
    ("%hvd_ssd_bwd.{}" + CALL, 650, "jit(step)/layer0/mixer/pallas_call")]


def _record(events, steps=1, **cell):
    out, start = [], 0.0
    for i, (name, us, op_name) in enumerate(events):
        out.append([name.format(i), start, us * 1e3, "", op_name, 1])
        start += us * 1e3
    trace = {"devices": {"/device:TPU:0": out}, "hlo": {}}
    return {"trace": {"steps": steps}, "cell": cell,
            "of_which_trace": of_which._without_loops(trace)}


def test_ssd_ms_sums_the_scans_events_forward_and_backward():
    read = CAT.module("layer_metrics", "ssd_ms").read
    assert read(_record(EVENTS)) == pytest.approx(2.9)
    assert read(_record(EVENTS, steps=2)) == pytest.approx(1.45)
    # a kernel named with the scope as its prefix is found by its own name
    assert read(_record(KERNELS)) == pytest.approx(1.0)
    # a loop is left out of the reading
    looped = EVENTS + [("%while.{}", 9000, "")]
    assert read(_record(looped)) == pytest.approx(2.9)


@pytest.mark.parametrize("reader, ms", [
    ("short_conv_ms", 0.38), ("mixer_proj_ms", 0.98), ("mlp_ms", 2.0)])
def test_an_accepted_reader_reads_its_scope_in_this_cells_step(reader, ms):
    """The convolution before the scan (its bias and SiLU with it) lies
    under ``hvd_short_conv`` and the projections, the gate and the gated
    norm under ``hvd_mixer_proj``: the readers that were there read them,
    and the cell brings no second name for either."""
    read = CAT.module("layer_metrics", reader).read
    assert read(_record(EVENTS)) == pytest.approx(ms)


def test_ssd_roofline_pct_is_the_scans_least_time_over_their_time():
    read = CAT.module("layer_metrics", "ssd_roofline_pct").read
    record = _record(EVENTS, peaks=PEAKS, tokens_per_step=8192, chips=1)
    # 3.16 GB over 819 GB/s against 2.9 ms
    assert read(record) == pytest.approx(100 * 3.8599 / 2.9, rel=1e-3)
    assert read(_record(EVENTS)) is None            # no peaks: a rehearsal


@pytest.mark.parametrize("reader", READERS)
def test_a_reader_gives_nothing_where_there_is_nothing_to_read(reader):
    """No trace, or the parent's program, which has no such scope: the
    reader returns nothing and does not raise, and the line leaves the
    metric out."""
    read = CAT.module("layer_metrics", reader).read
    assert read({"trace": {}}) is None and read({}) is None
    assert read({"trace": {"steps": 3}, "of_which_trace": None}) is None
    others = [e for e in EVENTS
              if "hvd_ssd" not in e[2] and "hvd_short_conv" not in e[2]]
    assert len(others) == 4
    assert read(_record(others, peaks=PEAKS, tokens_per_step=8192,
                        chips=1)) is None


def test_the_cells_report_the_common_readings_their_own_and_the_bound():
    per_layer = {m["name"]: m for m in CAT.index["per_layer"]}
    for name, unit, better in (("ssd_ms", "ms/step", "lower"),
                               ("ssd_roofline_pct", "%", "higher")):
        assert per_layer[name] == {
            "name": name, "unit": unit, "better": better,
            "source": "device_trace", "layer": "state-space scan",
            "moves": "train_tokens_per_s", "workloads": [CELL]}
    common = {m["name"] for m in CAT.index["per_layer"]
              if "workloads" not in m}
    assert {"flash_ms", "flash_fwd_ms", "flash_dkv_ms", "flash_roofline_pct",
            "mfu_pct", "lm_head_ms", "fwd_ms", "bwd_ms", "optimizer_ms",
            "collective_ms", "exposed_collective_ms", "bucket_copy_ms"} \
        <= common
    # each accepted reader whose scope a new cell's step holds has the cell
    # at the end of its list, after every cell that was there
    for name, cells in BOUND.items():
        assert per_layer[name]["workloads"][-len(cells):] == cells
        assert not set(cells) & set(per_layer[name]["workloads"][:-len(cells)])
    assert {m["name"] for m in CAT.metrics("per_layer", CELL)} \
        == common | set(READERS) | {n for n, c in BOUND.items() if CELL in c}
    # the four-chip cell adds no reader: the common readings and the block's
    # parts, the rotation among them (models/gpt.py), no loss scope
    assert {m["name"] for m in CAT.metrics("per_layer", DP4)} \
        == common | {n for n, c in BOUND.items() if DP4 in c}
    assert "ssm_conv_ms" not in per_layer
    for cell in (CELL, DP4):
        assert {m["name"] for m in CAT.metrics("end_to_end", cell)} == {
            "train_tokens_per_s", "step_hbm_gib", "setup_s"}
    # no other cell reports the three
    for other in CAT.index["workloads"]:
        if other["name"] != CELL:
            assert not set(READERS) & {
                m["name"] for m in CAT.metrics("per_layer", other["name"])}


def test_the_cells_files_say_what_the_issue_gave_them():
    cell = CAT.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"], cell["job"],
            cell["check_steps"], cell["reference_microbatch"]) == (
        CONFIG, "lm-b1-s8192", 1, "train_lm", 1, 1)
    assert cell["optimizer"] == {"learning_rate": 1e-4,
                                 "mu_dtype": "bfloat16",
                                 "compression": "none"}
    assert cell["rehearsal"]["config"] == "granite-tiny"
    assert cell["rehearsal"]["traffic"] == "lm-tiny"
    assert "names" not in cell      # the scope needs no file of names
    # the traffic file is the window cell's, comment and all
    assert "12,544" in CAT.traffic("lm-b1-s8192")["comment"]
    assert "25,088" in cell["why"] and "comment" in cell["why"]
    entry = next(w for w in CAT.index["workloads"] if w["name"] == CELL)
    assert "25,088" in entry["why"] and len(entry["why"]) <= 200


def test_the_four_chip_cell_is_data_alone():
    """``gpt2s-s512-dp4``: ``gpt2s-s512``'s files at four times the rows
    on four chips, under ``bert-large-s512-dp4``'s contract; it brings no
    module."""
    cell, one = CAT.cell(DP4), CAT.cell("gpt2s-s512")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "gpt2-small", "lm-b128-s512", 4)
    for key in ("job", "optimizer", "check_steps", "reference_microbatch",
                "trace_seconds", "rehearsal"):
        assert cell[key] == one[key], key
    # the limits are the cell's own: each between its sound runs' largest
    # on the four-chip machine and the least the float8 control or a fault
    # of the step read there (the file's reason has the readings), where
    # PR 22's, copied, lay 45 to 150 times over the first
    tolerance = cell["tolerance"]
    for limit, (sound, under) in zip(_limits(tolerance), (
            ("4.4e-6", "1.89e-4"), ("2.0e-4", "4.82e-3"),
            ("6.5e-4", "2.37e-3"))):
        assert 1.5 * float(sound) < limit < float(under) / 1.5
        assert sound in tolerance["reason"] and under in tolerance["reason"]
    assert all(mine < theirs for mine, theirs in zip(
        _limits(tolerance), _limits(one["tolerance"])))
    assert "correct: false" in tolerance["reason"]
    traffic, base = CAT.traffic("lm-b128-s512"), CAT.traffic("lm-b32-s512")
    assert traffic["batch"] == 4 * base["batch"] == 128
    assert traffic["seq_len"] == base["seq_len"] == 512
    assert set(traffic) == {"batch", "seq_len", "comment"}
    # the four-chip places: a quarter of the cells, rounded down
    four = [w["name"] for w in CAT.index["workloads"] if w["chips"] == 4]
    assert four == ["bert-large-s512-dp4", "bert-large-s512-dp4-int8ef",
                    DP4]
    assert len(four) <= len(CAT.index["workloads"]) // 4
    assert CAT.config("gpt2-small")["family"] == "gpt"


def test_my_entries_come_after_pr_45s_in_this_order():
    """Order and membership, never the end of a list or its length: the
    next PR that appends inherits nothing from this test."""
    def after(names, mine, theirs):
        at = [names.index(n) for n in theirs + mine]
        assert at == sorted(at) and len(set(at)) == len(at)

    after([m["name"] for m in CAT.index["per_layer"]], READERS,
          ["swa_tiles_visited_pct", "quantize_ms", "dequantize_ms"])
    after([w["name"] for w in CAT.index["workloads"]], [CELL, DP4],
          ["bert-large-s512-dp4-int8ef", "laguna-s2.1-l5-e8-s8192"])
    after([c["name"] for c in CAT.index["configs"]], [CONFIG],
          ["sdar-30b-a3b-l6-e16", "laguna-s2.1-l5-e8"])
    # a layer of its own, one name letter for letter
    layers = {m["layer"] for m in CAT.index["per_layer"]
              if m["name"] in READERS}
    assert layers == {"state-space scan"}


# -- the earlier PRs' positional tests, whole, on the lists before this PR --

INT8EF = importlib.import_module("test_benchmark_int8ef")
MINE = {"configs": {CONFIG}, "workloads": {CELL, DP4},
        "per_layer": set(READERS)}
# what tests/conftest.py marks since this PR, as (test, its arguments,
# whether it takes monkeypatch): PR 45's runner (six cases), and the two
# tests that hold an accepted reader's list of cells whole (seven)
MARKED = [
    pytest.param(INT8EF.test_the_marked_tests_hold_whole_before_this_pr,
                 case, True,
                 id=case[1] if case[2] is None else
                 f"{case[2][1]}-{case[2][2][1] if case[2][2] else 'whole'}")
    for case in INT8EF.MARKED] + [
    pytest.param(importlib.import_module("test_benchmark_block_parts")
                 .test_a_reading_has_its_entry_its_file_and_its_cells,
                 (metric,), False, id=metric)
    for metric in sorted(BOUND) if metric != "short_conv_ms"] + [
    pytest.param(importlib.import_module("test_benchmark_lfm2")
                 .test_the_convolutions_cost_by_hand, (), False,
                 id="short_conv_ms")]


def _index_before_this_pr():
    """``BENCHMARK.json``'s lists with this PR's entries taken out, and
    its two cells taken off the end of each accepted reader's list."""
    index = dict(CAT.index)
    for key, mine in MINE.items():
        kept = [e for e in index[key] if e["name"] not in mine]
        # this PR's entries lie after everything that was there
        assert index[key][:len(kept)] == kept
        assert {e["name"] for e in index[key][len(kept):]} == mine
        index[key] = kept
    index["per_layer"] = [
        {**m, "workloads": [c for c in m["workloads"]
                            if c not in MINE["workloads"]]}
        if "workloads" in m else m for m in index["per_layer"]]
    return index


@pytest.mark.parametrize("their, case, patches", MARKED)
def test_the_marked_tests_hold_whole_before_this_pr(their, case, patches,
                                                    monkeypatch):
    """The cases ``tests/conftest.py`` marks since this PR appended a
    configuration, two cells and two readers, and its two cells to seven
    accepted readers' lists: PR 45's runner (``test_benchmark_int8ef.py``),
    the test of each block reader's entry and the convolution's cost by
    hand (which asks which one configuration ``short_conv_ms`` belongs
    to), each case run whole on ``BENCHMARK.json``'s lists as they stood
    before this PR. PR 45's runner takes its own two readers out in turn
    and runs PR 42's runner and PR 40's inside it, so every assertion of
    the chain executes here, the positions and the lengths too: four
    runners deep."""
    index = _index_before_this_pr()
    for name in ("test_benchmark_int8ef", "test_benchmark_sdar",
                 "test_benchmark_laguna", "test_benchmark_lfm2",
                 "test_benchmark_block_parts"):
        monkeypatch.setattr(importlib.import_module(name).CAT, "index",
                            index)
    # a catalog made inside a test (``moe_kda_cost.config_of_metric``)
    # reads the same lists
    made = Catalog.__init__

    def catalog(self, *args, **kwargs):
        made(self, *args, **kwargs)
        self.index = index

    monkeypatch.setattr(Catalog, "__init__", catalog)
    their(*case, *([monkeypatch] if patches else []))


def test_the_limits_are_the_chips_and_still_see_the_two_faults_of_the_step():
    """The cell's limits as its file reasons them, and what each must
    still refuse: a module left out of the update reads 1.0 in the
    movement and a learning rate 5% off 0.05."""
    tolerance = CAT.cell(CELL)["tolerance"]
    assert _limits(tolerance) == (2e-5, 7.5e-4, 1.5e-3)
    assert _limits(tolerance)[2] < 0.05 < 1.0
    # each between its two readings on the chip (the file's reason has
    # them): the sound runs' largest, and the least the control or a fault
    # of the mathematics reads
    for limit, (sound, under) in zip(_limits(tolerance), (
            ("4.5e-6", "6.1e-5"), ("3.94e-4", "1.39e-3"),
            ("7.25e-4", "2.6e-3"))):
        assert 1.5 * float(sound) < limit < float(under) / 1.5
        assert sound in tolerance["reason"] and under in tolerance["reason"]
    # says what separates nothing, and what the check saw of the faults
    assert "NOT" in tolerance["reason"] and "float8" in tolerance["reason"]
    assert "correct: false on all ten" in tolerance["reason"]
    assert _limits(CAT.cell(CELL)["rehearsal"]["tolerance"]) \
        == (1.5e-4, 2e-3, 5e-4)
