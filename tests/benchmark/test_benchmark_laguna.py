"""Family ``laguna`` (``poolside/Laguna-S-2.1``: full and sliding-window
attention mixed in one stack at two query-head counts, a gate a head, a
YaRN rotation of half the head width beside a plain one, a leading dense
layer and a softmax-routed top-k mixture of experts with a shared expert)
on the CPU at its tiny preset: the system against the plain reference on
seeded weights (logits, the loss, every gradient), the reference's mask
and frequencies against the program's, the shares of the experts adding up
to the uncut layer, the configuration's file against the published
widths, the family's counts by hand and against pairs counted from
``dense()``, the cell's three readers, the faults of the mathematics
(``FAULTS``: a scratch script on the chip puts the same overrides under
the timed path), and the earlier PRs' positional tests run whole on the
lists as they stood before this PR. This file's own tests hold order and
membership, never the end of a list or its length. Nothing here touches a
device."""

import contextlib
import dataclasses
import importlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import flops, laguna_cost
from benchmark.catalog import Catalog
from benchmark.jobs import train_lm
from benchmark.stream import token_stream

CAT = Catalog()
FAMILY = CAT.module("families", "laguna")
REFERENCE = CAT.module("reference", "laguna")
TINY = CAT.config("laguna-tiny")
CONFIG = "laguna-s2.1-l5-e8"
CELL = "laguna-s2.1-l5-e8-s8192"
READERS = ["swa_flash_ms", "swa_flash_roofline_pct", "swa_tiles_visited_pct"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
LENGTH = 32
# (loss, logits, gradients): the arithmetic of the two agrees to fp32
# rounding; in bf16 the system's operands are rounded, and a router that
# reads rounded activations gives a few tokens another fourth expert.
TOLERANCE = {"float32": (1e-5, 2e-4, 3e-4), "bfloat16": (3e-3, 1e-1, 5e-1)}


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
    from horovod_tpu.models import LagunaLM

    model = FAMILY.build(TINY)
    assert isinstance(model, LagunaLM) and model.dtype == jnp.bfloat16
    model = model.clone(dtype=jnp.dtype(request.param))
    tokens = _tokens()
    params = model.init(jax.random.PRNGKey(5), tokens[:, :-1])["params"]
    return model, params, tokens, TOLERANCE[request.param]


def test_the_tiny_preset_has_what_the_cell_has():
    """Both head counts, a window shorter than the sequence, two periods
    so that a second full layer follows window layers, 4 of 16 experts
    held at top 4, a leading dense layer, both rotations."""
    assert TINY["num_hidden_layers"] == 8
    assert TINY["layer_types"] == (["full_attention"]
                                   + ["sliding_attention"] * 3) * 2
    assert TINY["num_attention_heads_per_layer"] == [4, 6, 6, 6] * 2
    assert TINY["sliding_window"] < LENGTH
    assert TINY["mlp_layer_types"] == ["dense"] + ["sparse"] * 7
    assert (TINY["num_experts"], TINY["router_width"],
            TINY["num_experts_per_tok"]) == (4, 16, 4)
    rope = TINY["rope_parameters"]
    assert rope["full_attention"]["rope_type"] == "yarn"
    assert rope["full_attention"]["partial_rotary_factor"] == 0.5
    assert rope["sliding_attention"]["rope_type"] == "default"
    # YaRN's ramp is not flat here: some pairs keep their frequency, some
    # take the factor's
    table = REFERENCE.inv_freq(rope["full_attention"], 8)
    plain = 100.0 ** (-np.arange(4) / 4)
    assert table[0] == plain[0] and table[-1] == plain[-1] / 8


def test_the_logits(pair):
    model, params, tokens, (_, tol, _) = pair
    logits = model.apply({"params": params}, tokens[:, :-1])
    want = REFERENCE.logits(params, tokens[:, :-1], TINY)
    assert logits.shape == want.shape == (2, LENGTH, TINY["vocab_size"])
    assert logits.dtype == jnp.float32
    assert _close(logits, want, tol)


def test_the_loss_is_the_mean_the_job_makes(pair):
    from horovod_tpu.models import laguna_loss

    model, params, tokens, (tol, _, _) = pair
    want = REFERENCE.token_losses(params, {"tokens": tokens}, TINY)
    assert want.shape == (2, LENGTH) and want.dtype == jnp.float32
    assert float(laguna_loss(model, params, tokens)) \
        == pytest.approx(float(want.mean()), rel=tol)
    assert float(FAMILY.loss(model, params, {"tokens": tokens})) \
        == pytest.approx(float(want.mean()), rel=tol)


def test_every_gradient(pair):
    model, params, tokens, (_, _, tol) = pair
    got = jax.grad(lambda p: FAMILY.loss(model, p, {"tokens": tokens}))(
        params)
    want = jax.grad(lambda p: REFERENCE.token_losses(
        p, {"tokens": tokens}, TINY).mean())(params)
    flat = jax.tree_util.tree_leaves_with_path(got)
    # a layer: 2 norms, 5 of attention (q, k, v, gate, o); the dense
    # layer's 3; a sparse layer's router, 3 banks and 3 of the shared
    # expert; the embedding, the final norm, the untied head
    assert len(flat) == 8 * 7 + 3 + 7 * 7 + 3
    for (path, g), w in zip(flat, jax.tree.leaves(want)):
        assert float(jnp.abs(w).max()) > 0, path
        assert _close(g, w, tol), path


def test_the_references_mask_and_frequencies_are_the_programs():
    from horovod_tpu.ops.flash_attention import CAUSAL, SlidingWindowMask

    at = jnp.arange(48)
    for window in (1, 5, 16, 48, 60):
        want = REFERENCE.visible(at[:, None], at[None, :], window)
        assert np.array_equal(np.asarray(want),
                              SlidingWindowMask(window).dense(48))
    assert np.array_equal(np.asarray(REFERENCE.visible(
        at[:, None], at[None, :], None)), CAUSAL.dense(48))
    for config in (TINY, CAT.config(CONFIG)):
        model = FAMILY.build(config)
        for kind, rotation in (("full_attention", model.full_rotation),
                               ("sliding_attention", model.window_rotation)):
            rope = config["rope_parameters"][kind]
            rot = int(rope["partial_rotary_factor"] * config["head_dim"])
            assert rotation.rotary(config["head_dim"]) == rot
            assert rotation.scale == rope.get("attention_factor", 1.0)
            got = rotation.inv_freq(
                jnp.arange(rot // 2, dtype=jnp.float32), rot)
            np.testing.assert_allclose(got, REFERENCE.inv_freq(rope, rot),
                                       rtol=1e-6)
    published = FAMILY.build(CAT.config(CONFIG))
    assert (published.full_rotation.name,
            published.window_rotation.name) == ("yarn", "plain")
    assert published.full_rotation.rotary(128) == 64


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The model-configs guide's test of the cut: the routed parts of an
    expert layer's output that the four shares of 4 of 16 experts give
    (the system's layer, told which experts it holds), with the shared
    expert, which every chip computes alike, counted once, add up to what
    the reference gives for the whole layer (all 16 held)."""
    from horovod_tpu.models.solar import SparseExperts

    key = jax.random.PRNGKey(9)
    x = jax.random.normal(key, (2, 32, TINY["hidden_size"]), jnp.float32)
    whole = {"router": jax.random.normal(
        jax.random.fold_in(key, 1), (64, 16)) * 0.5}
    for i, (name, shape) in enumerate((("experts_gate", (16, 64, 32)),
                                       ("experts_up", (16, 64, 32)),
                                       ("experts_down", (16, 32, 64)))):
        whole[name] = jax.random.normal(jax.random.fold_in(key, 2 + i),
                                        shape) * 0.2
    for i, (name, shape) in enumerate((("shared_gate", (64, 32)),
                                       ("shared_up", (64, 32)),
                                       ("shared_down", (32, 64)))):
        whole[name] = {"kernel": jax.random.normal(
            jax.random.fold_in(key, 5 + i), shape) * 0.2}
    shared = None
    total = 0.0
    for first in (0, 4, 8, 12):
        share = {k: v[first:first + 4] if k.startswith("experts_") else v
                 for k, v in whole.items()}
        both = SparseExperts(16, (first, 4), 4, 32, 32, 2.5, jnp.float32)
        routed = SparseExperts(16, (first, 4), 4, 32, 0, 2.5, jnp.float32)
        y, stats = both.apply({"params": share}, x)
        part, _ = routed.apply({"params": share}, x)
        assert float(stats["dropped_tokens"]) == 0
        # what every chip computes alike, once
        if shared is None:
            shared = y - part
        assert _close(y - part, shared, 1e-5)
        total = total + part
    with jax.default_matmul_precision("highest"):
        want = REFERENCE._experts(
            x, whole, {**TINY, "held_experts_first": 0})
    assert _close(total + shared, want, 2e-4)
    # and one share is a part, not the whole; nor is the sum with the
    # shared expert counted four times
    assert not _close(y, want, 0.1)
    assert not _close(total + 4 * shared, want, 0.1)


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


def _faulty_layers(change):
    """``LagunaLM.layer_parts`` with ``change(i, mixer_args, ffn_args,
    full)`` over a layer's two argument tuples (as lists)."""
    from horovod_tpu.models import laguna

    def wrap(real):
        def layer_parts(self, i):
            mixer, mixer_args, ffn, ffn_args = real(self, i)
            mixer_args, ffn_args = list(mixer_args), list(ffn_args)
            change(i, mixer_args, ffn_args,
                   self.layer_types[i] == laguna.ATTENTION)
            return mixer, tuple(mixer_args), ffn, tuple(ffn_args)
        return layer_parts
    return _patched(laguna.LagunaLM, "layer_parts", wrap)()


# positions in ``RotaryGQA``'s and ``SparseExperts``' arguments
HEADS, MASK, ROTATION = 0, 6, 7
SCALE = 5


@contextlib.contextmanager
def _window_off_by_one(config):
    """0 <= u - w <= window in place of <: in the matrix the jnp fallback
    reads and in the select of the kernels' partial tiles."""
    from horovod_tpu.ops import flash_attention as fa

    def dense(real):
        def wide(self, s):
            gap = np.arange(s)[:, None] - np.arange(s)[None]
            return (gap >= 0) & (gap <= self.window)
        return wide

    def keep(real):
        def wide(self, s, row0, col0, shape, rows_dim):
            gap = (row0 - col0) \
                + jax.lax.broadcasted_iota(jnp.int32, shape, rows_dim) \
                - jax.lax.broadcasted_iota(jnp.int32, shape, 1 - rows_dim)
            return (gap >= 0) & (gap <= self.window)
        return wide

    with _patched(fa.SlidingWindowMask, "dense", dense)(), \
            _patched(fa.SlidingWindowMask, "keep", keep)():
        yield


def _window_left_out_of_one_layer(config):
    from horovod_tpu.ops.flash_attention import CAUSAL

    def change(i, mixer, ffn, full):
        if i == 2:
            assert not full
            mixer[MASK] = CAUSAL
    return _faulty_layers(change)


def _head_counts_swapped(config):
    """72 heads in the full layers and 48 in the window layers (at the
    tiny preset 6 and 4): a tree of other shapes."""
    counts = sorted(set(config["num_attention_heads_per_layer"]))

    def change(i, mixer, ffn, full):
        mixer[HEADS] = counts[-1] if full else counts[0]
    return _faulty_layers(change)


def _in_full_layers(**fields):
    def change(i, mixer, ffn, full):
        if full:
            mixer[ROTATION] = dataclasses.replace(mixer[ROTATION], **fields)
    return lambda config: _faulty_layers(change)


def _gate_left_out(config):
    """Every head's gate 1: the sigmoid (this model's only one under
    ``flax.linen``) gives ones; ``W_g`` stays in the tree."""
    import flax.linen as nn

    return _patched(nn, "sigmoid", lambda real: jnp.ones_like)()


def _scale_left_out(config):
    def change(i, mixer, ffn, full):
        if len(ffn) > 2:
            ffn[SCALE] = 1.0
    return _faulty_layers(change)


def _shared_expert_left_out(config):
    """The shared expert adds nothing: its down-projection gives zeros;
    its three kernels stay in the tree."""
    import flax.linen as nn

    def wrap(real):
        def call(self, x):
            y = real(self, x)
            return jnp.zeros_like(y) if self.name == "shared_down" else y
        return call
    return _patched(nn.Dense, "__call__", wrap)()


def _one_expert_fewer_a_token(config):
    """top-(k - 1): the last (weakest) choice goes, the others share what
    the k shared."""
    from horovod_tpu.parallel import moe

    def wrap(real):
        def route(*args, **kwargs):
            experts, weights = real(*args, **kwargs)
            kept = weights.at[:, -1].set(0.0)
            return experts, kept * (weights.sum(-1, keepdims=True)
                                    / kept.sum(-1, keepdims=True))
        return route
    return _patched(moe, "route_top_k", wrap)()


# (``config -> context``): while the context is open, a model that is
# built and traced has the fault; the reference never does.
FAULTS = {
    "window_off_by_one": _window_off_by_one,
    "window_left_out_of_one_layer": _window_left_out_of_one_layer,
    "head_counts_swapped": _head_counts_swapped,
    "gate_left_out": _gate_left_out,
    "full_layers_rotated_whole": _in_full_layers(width=None),
    "yarn_attention_factor_left_out": _in_full_layers(scale=1.0),
    "yarn_ramp_left_out": _in_full_layers(factor=1.0),
    "routed_scale_left_out": _scale_left_out,
    "one_expert_fewer_a_token": _one_expert_fewer_a_token,
    "shared_expert_left_out": _shared_expert_left_out,
}
# the fault that changes the tree's shapes: the reference, which takes its
# head counts from the configuration, refuses the tree
RESHAPES = "head_counts_swapped"


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_moves_the_system_away_from_the_reference(fault):
    """Every one of the ten, in fp32, where the sound system and the
    reference agree to rounding: the override is under the model, and the
    comparison of the logits sees it. Afterwards it is gone."""
    tokens = _tokens()

    def system(params=None):
        model = FAMILY.build(TINY).clone(dtype=jnp.float32)
        if params is None:
            params = model.init(jax.random.PRNGKey(5),
                                tokens[:, :-1])["params"]
        return params, model.apply({"params": params}, tokens[:, :-1])

    with jax.default_matmul_precision("highest"):
        params, sound = system()
        want = REFERENCE.logits(params, tokens[:, :-1], TINY)
        if fault == RESHAPES:
            with FAULTS[fault](TINY):
                other, _ = system()
            assert jax.tree.map(jnp.shape, other) \
                != jax.tree.map(jnp.shape, params)
            with pytest.raises(TypeError, match="reshape"):
                REFERENCE.logits(other, tokens[:, :-1], TINY)
        else:
            with FAULTS[fault](TINY):
                faulty = system(params)[1]
            assert not _close(faulty, want, 50 * TOLERANCE["float32"][1])
        again = system(params)[1]
        assert _close(sound, want, TOLERANCE["float32"][1])
        assert np.array_equal(np.asarray(again), np.asarray(sound))


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
        "tok_emb", *(f"layer{i}" for i in range(8)), "final_norm",
        "lm_head"}
    assert all(move > 0 for move in plain[2].values())


# What the rehearsal's three numbers see at 128 tokens (the cell's file
# says which and by how much): the others lie inside the bf16 system's own
# distance from the reference there and are held by the fp32 logits above.
SEEN_BY_THE_REHEARSAL = ["full_layers_rotated_whole", "gate_left_out",
                         "routed_scale_left_out", "shared_expert_left_out",
                         "window_off_by_one",
                         "yarn_attention_factor_left_out"]


@pytest.mark.parametrize("fault", SEEN_BY_THE_REHEARSAL)
def test_a_fault_of_the_mathematics_is_not_correct(first_step, fault):
    params, batch, plain = first_step
    with FAULTS[fault](TINY):
        gaps = train_lm._gaps(*_system_numbers(params, batch), *plain)[:3]
    limits = _limits(CAT.cell(CELL)["rehearsal"]["tolerance"])
    assert any(gap > limit for gap, limit in zip(gaps, limits)), gaps


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
    assert any(gap > limit for gap, limit in zip(gaps, limits))


# -- the configuration and the counts -------------------------------------

def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        with open(path) as f:
            rows = [json.loads(line) for line in f]
    except FileNotFoundError:
        pytest.skip("the catalog of public architectures is not here")
    return next(r for r in rows if r["name"] == "Laguna-S-2.1")


def test_the_configuration_keeps_every_published_width():
    config = CAT.config(CONFIG)
    published = {
        "model_type": "laguna", "hidden_size": 3072,
        "intermediate_size": 12288, "num_attention_heads": 48,
        "num_key_value_heads": 8, "head_dim": 128,
        "max_position_embeddings": 1048576, "attention_bias": False,
        "rms_norm_eps": 1e-6, "num_experts_per_tok": 10,
        "moe_intermediate_size": 1024,
        "shared_expert_intermediate_size": 1024, "norm_topk_prob": True,
        "decoder_sparse_step": 1, "mlp_only_layers": [0],
        "tie_word_embeddings": False, "gating": "per-head",
        "sliding_window": 512, "moe_apply_router_weight_on_input": False,
        "moe_routed_scaling_factor": 2.5, "moe_router_logit_softcapping": 0}
    assert {k: config[k] for k in published} == published
    assert config["rope_parameters"] == {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}}
    # the per-layer lists are the published ones whole; the depth held
    # reads their first five entries
    assert config["layer_types"] == (["full_attention"]
                                     + ["sliding_attention"] * 3) * 12
    assert config["num_attention_heads_per_layer"] == [48, 72, 72, 72] * 12
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 47
    assert config["gating_types"] == ["per_head"] * 48
    assert laguna_cost.layers(config) == (
        (48, False), (72, True), (72, True), (72, True), (48, False))
    assert config["router_width"] == 256    # the router keeps its width
    held = {"num_hidden_layers": 5, "num_experts": 8, "vocab_size": 12544,
            "held_experts_first": 0}
    assert {k: config[k] for k in held} == held
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 256, "vocab_size": 100352}
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert config["num_experts"] * 32 == config["published"]["num_experts"]
    assert "twelve pipeline stages" in config["deployment"]
    assert "32 chips that share each layer" in config["deployment"]
    assert {"router", "shared_expert", "qk_norm", "gate", "rotation",
            "attention", "initialization", "compute", "parameters",
            "expert_blocks"} <= set(config["assumed"])
    assert "811,017,216" in config["assumed"]["parameters"]
    entry = next(c for c in CAT.index["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert json.dumps(config)       # plain data


def test_the_configuration_is_the_catalogs_but_for_what_reduced_names():
    row = _catalog_row()
    config = CAT.config(CONFIG)
    assert config["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == set(config["reduced"])
    assert {k: row["config"][k] for k in differs} == config["published"]


def test_the_family_builds_the_share_of_the_published_model():
    from horovod_tpu.models import LagunaLM
    from horovod_tpu.ops.flash_attention import CAUSAL, SlidingWindowMask

    config = CAT.config(CONFIG)
    model = FAMILY.build(config)
    defaults = LagunaLM()
    for field in ("vocab_size", "num_layers", "hidden", "num_kv_heads",
                  "head_dim", "window", "full_rotation", "window_rotation",
                  "mlp_dim", "num_experts", "held_experts", "top_k",
                  "expert_dim", "shared_dim", "routed_scale", "norm_eps"):
        assert getattr(model, field) == getattr(defaults, field), field
    # the step's cost does not follow the routes (PERF.md section 6)
    assert model.whole_expert_blocks and config["whole_expert_blocks"]
    assert "whole_expert_blocks true" in config["assumed"]["expert_blocks"]
    # the rehearsal runs the path the cell runs
    assert FAMILY.build(TINY).whole_expert_blocks
    assert model.layer_parts(1)[3][-1] is True
    masks = [model.layer_parts(i)[1][MASK] for i in range(5)]
    assert masks == [CAUSAL] + [SlidingWindowMask(512)] * 3 + [CAUSAL]
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 128), jnp.int32))["params"])
    count = {k: sum(x.size for x in jax.tree.leaves(v))
             for k, v in shapes.items()}
    full = 3072 * 6144 * 2 + 3072 * 1024 * 2 + 3072 * 48
    window = 3072 * 9216 * 2 + 3072 * 1024 * 2 + 3072 * 72
    sparse = 3072 * 256 + 3 * 3072 * 1024 + 8 * 3 * 3072 * 1024
    assert (full, window, sparse) == (44_187_648, 63_135_744, 85_721_088)
    assert count == {
        "layer0": full + 3 * 3072 * 12288 + 6144,
        **{f"layer{i}": window + sparse + 6144 for i in (1, 2, 3)},
        "layer4": full + sparse + 6144,
        "tok_emb": 38_535_168, "lm_head": 38_535_168, "final_norm": 3_072}
    assert sum(count.values()) == 811_017_216
    mixer = shapes["layer1"]["mixer"]
    assert mixer["q"]["kernel"].shape == (3072, 9216)
    assert mixer["k"]["kernel"].shape == (3072, 1024)
    assert mixer["gate"]["kernel"].shape == (3072, 72)
    assert set(mixer) == {"q", "k", "v", "gate", "o"}      # no QK norm
    assert shapes["layer0"]["mixer"]["q"]["kernel"].shape == (3072, 6144)
    assert shapes["layer0"]["ffn"]["gate"]["kernel"].shape == (3072, 12288)
    assert shapes["layer4"]["ffn"]["router"].shape == (3072, 256)
    assert shapes["layer4"]["ffn"]["experts_gate"].shape == (8, 3072, 1024)
    assert shapes["layer4"]["ffn"]["shared_up"]["kernel"].shape \
        == (3072, 1024)


def test_the_windows_pairs_are_counted_from_dense():
    from horovod_tpu.ops.flash_attention import SlidingWindowMask

    for s, window in ((64, 16), (64, 1), (96, 33), (64, 64), (64, 200)):
        assert laguna_cost.window_pairs(s, window) \
            == SlidingWindowMask(window).dense(s).sum()
    assert laguna_cost.window_pairs(8192, 512) == 4_063_488
    assert laguna_cost.window_pairs(8192, 512) / (8192 * 8192 / 2) \
        == pytest.approx(0.1211, rel=1e-3)


def test_train_flops_per_token_by_hand():
    config = CAT.config(CONFIG)
    weights = (44_187_648 + 113_246_208) \
        + 3 * (63_135_744 + 786_432 + 9_437_184 + 2_949_120) \
        + (44_187_648 + 786_432 + 9_437_184 + 2_949_120) + 38_535_168
    assert weights == 482_254_848
    attention = 2 * 6 * 8192 * 6144 + 3 * 12 * 4_063_488 * 9216 // 8192
    assert attention == 603_979_776 + 164_571_264
    assert FAMILY.train_flops_per_token(config, 8192) \
        == 6 * weights + attention == 3_662_080_128
    # a step: 8,192 tokens
    assert 8192 * 3_662_080_128 == pytest.approx(30.0e12, rel=1e-3)
    # the window's pairs and not the triangle's
    causal = {**config, "sliding_window": 8192}
    assert FAMILY.train_flops_per_token(causal, 8192) \
        == pytest.approx(6 * weights + 6 * 8192 * (2 * 6144 + 3 * 9216),
                         rel=2e-4)


def test_the_attention_is_counted_by_the_pairs_of_both_kinds():
    """``attention_calls`` is one causal shape whose operations equal the
    sum of both kinds' visible-pair operations and whose bytes are no more
    than the true bytes; each kind is compute-bound at ``peaks.json``'s
    numbers, so ``flash_roofline_pct`` can read low and never over."""
    from horovod_tpu.ops.flash_attention import SlidingWindowMask

    config = CAT.config(CONFIG)
    calls = FAMILY.attention_calls(config, 1, 8192)
    assert calls == {"calls": pytest.approx(2.545, abs=5e-4), "batch": 1,
                     "heads": 48, "seq_len": 8192, "head_dim": 128,
                     "causal": True}
    cost = flops.flash_attention_cost(1, 48, 8192, 128, True)
    window = laguna_cost.swa_step_cost(config, 1, 8192)
    full_pairs, window_pairs = 8192 * 8192 // 2, 4_063_488
    for part, products in (("fwd", 2), ("bwd", 5)):
        ops = calls["calls"] * cost[part][0]
        true_ops = products * 2 * 128 * (2 * 48 * full_pairs
                                         + 3 * 72 * window_pairs)
        assert ops == pytest.approx(true_ops, rel=1e-12)
        assert window[part][0] == products * 2 * 128 * 3 * 72 * window_pairs
        # bytes: 2.545 x 48 heads' tensors counted, 2 x 48 + 3 x 72 moved
        true_bytes = 2 * cost[part][1] + window[part][1]
        assert calls["calls"] * cost[part][1] < true_bytes
        assert window[part][1] == cost[part][1] * 3 * 72 / 48
        # each kind compute-bound
        assert flops.roofline_seconds(*cost[part], PEAKS)[1] == "compute"
        assert flops.roofline_seconds(*window[part], PEAKS)[1] == "compute"
    least, bound = flops.attention_step_roofline(calls, PEAKS)
    assert bound == {"fwd": "compute", "bwd": "compute"}
    assert least * 197e12 == pytest.approx(
        7 * 2 * 128 * (2 * 48 * full_pairs + 3 * 72 * window_pairs))
    # the pairs from the mask itself, at a size a test can write out
    small = {**config, "sliding_window": 16}
    counted = SlidingWindowMask(16).dense(128).sum()
    assert laguna_cost.swa_step_cost(small, 2, 128)["fwd"][0] \
        == 2 * 2.0 * (2 * 3 * 72) * counted * 128


# -- the cell's readers -----------------------------------------------------

def test_swa_tiles_visited_pct_divides_the_gauges_samples():
    """The gauge a window call sets where it is traced, read as a share; a
    causal call's samples are not in it."""
    import horovod_tpu as hvd
    from horovod_tpu.ops import flash_attention as fa

    reader = CAT.module("layer_metrics", "swa_tiles_visited_pct")
    q = jnp.zeros((1, 256, 2, 16))
    fa._say_path.cache_clear()
    jax.jit(lambda q: fa.flash_attention(
        q, q, q, mask_kind=fa.SlidingWindowMask(48), use_pallas=True,
        block_q=32, block_k=32)).lower(q)
    jax.jit(lambda q: fa.flash_attention(
        q, q, q, causal=True, use_pallas=True, block_q=32,
        block_k=32)).lower(q)
    samples = {(s["labels"]["mask_kind"], s["labels"]["tiles"]): s["value"]
               for s in hvd.metrics()[reader.GAUGE]["samples"]
               if s["labels"]["seq_len"] == "256"
               and s["labels"]["block_q"] == s["labels"]["block_k"] == "32"}
    # 8 x 8 tiles: 1 + 2 + 6 x 3 under a window of 48; causal 8 x 9 / 2
    assert samples[("sliding_window", "visited")] == 21
    assert samples[("sliding_window", "square")] == 64
    assert samples[("causal", "visited")] == 36
    # every window call this process has traced, other tests' too
    got = reader.read({})
    assert got is not None and 0.0 < got <= 100.0


def test_swa_flash_ms_reads_the_cells_own_phase():
    reader = CAT.module("layer_metrics", "swa_flash_ms")
    assert reader.read({"trace": {"steps": 3}, "names": {"phases": {}}}) \
        is None
    assert reader.read({}) is None
    record = {"trace": {"steps": 2}, "names": {"phases": {"swa_flash": []}},
              "phases": {"ms": {"swa_flash": 52.5, "flash_fwd": 17.0,
                                "flash_dkv": 34.0},
                         "named": {"flash": True, "dense": True},
                         "kind": {"swa_flash": "flash"}}}
    assert reader.read(record) == 52.5
    # a program without the kernels' names gives nothing, not 0
    record["phases"]["named"]["flash"] = False
    assert reader.read(record) is None


def test_swa_flash_roofline_pct_is_the_windows_least_time_over_its_time():
    reader = CAT.module("layer_metrics", "swa_flash_roofline_pct")
    config = CAT.config(CONFIG)
    record = {"trace": {"steps": 2}, "names": {"phases": {"swa_flash": []}},
              "phases": {"ms": {"swa_flash": 50.0},
                         "named": {"flash": True, "dense": True},
                         "kind": {"swa_flash": "flash"}},
              "cell": {"peaks": PEAKS, "attention": FAMILY.attention_calls(
                  config, 1, 8192)}}
    # 7 products of 2 P_w 128 a head over 3 x 72 heads, at the bf16 peak
    least_ms = 1e3 * 7 * 2 * 4_063_488 * 128 * 216 / 197e12
    assert least_ms == pytest.approx(7.98, abs=0.01)
    assert reader.read(record) == pytest.approx(100 * least_ms / 50.0)
    assert reader.read({**record, "cell": {}}) is None
    assert reader.read({"trace": {"steps": 2}, "names": {"phases": {}}}) \
        is None


def test_the_cells_file_of_names_adds_the_window_kernels_to_the_flash_ones():
    from benchmark import hlo_counts, trace_reduce
    from horovod_tpu.common import scopes

    cell = CAT.cell(CELL)
    assert cell["names"] == ["sliding-window"]
    names = hlo_counts.load_names(CAT.names(cell))
    kernels = dict(map(tuple, names["flash_kernels"]))
    assert kernels[scopes.SWA_FWD] == "swa_fwd"
    assert kernels[scopes.SWA_BWD] == "swa_bwd"
    assert [kernels[k] for k in scopes.FLASH_KERNELS] == [
        "flash_fwd", "flash_dq", "flash_dkv"]
    assert names["phases"]["swa_flash"] == ["swa_fwd", "swa_bwd"]
    assert names["phases"]["flash_fwd"] == ["flash_fwd"]
    plain = hlo_counts.load_names()
    assert "swa_flash" not in plain["phases"]
    # a window call is an attention kernel for this cell, dense work for a
    # cell that does not list the file; a full call is one for both
    marker = plain["mosaic_call_marker"]
    for kernel, ours, theirs in ((scopes.SWA_FWD, "flash", "dense"),
                                 (scopes.SWA_BWD, "flash", "dense"),
                                 (scopes.FLASH_FWD, "flash", "flash")):
        event = f"%{kernel}.3 = bf16[1] custom-call(), {marker}"
        assert trace_reduce.classify(event, "", names) == ours
        assert trace_reduce.classify(event, "", plain) == theirs
    hlo = "\n".join(f"  %{k}.1 = bf16[1] custom-call(), {marker}"
                    for k in (scopes.SWA_FWD, scopes.SWA_BWD,
                              scopes.FLASH_FWD, "hvd_rope_fwd"))
    assert hlo_counts.count(hlo, names)["flash_mosaic_calls"] == 3
    assert hlo_counts.count(hlo, plain)["flash_mosaic_calls"] == 1


def test_the_three_phases_sum_to_flash_ms_on_a_hand_made_trace():
    """``flash_fwd_ms`` + ``flash_dkv_ms`` + ``swa_flash_ms`` =
    ``flash_ms``: the reduction on events named as the chip names them."""
    from benchmark import hlo_counts, phase_reduce
    from horovod_tpu.common import scopes

    names = hlo_counts.load_names(CAT.names(CAT.cell(CELL)))
    marker = names["mosaic_call_marker"]

    def event(kernel, start, dur):
        return (f"%{kernel}.7 = bf16[8] custom-call(), {marker}", start, dur,
                "", "", 0)

    trace = {"devices": {0: [
        event(scopes.FLASH_FWD, 0.0, 4e6), event(scopes.SWA_FWD, 5e6, 1e6),
        event(scopes.SWA_BWD, 7e6, 2e6), event(scopes.FLASH_DKV, 10e6, 8e6),
        event(scopes.SWA_FWD, 20e6, 1e6)]}, "hlo": {}}
    reduced = phase_reduce.reduce_phases(trace, names)
    seconds = reduced["seconds"]
    assert reduced["named"]["flash"]
    phases = {phase: sum(seconds[p] for p in parts)
              for phase, parts in names["phases"].items()}
    assert phases["swa_flash"] == pytest.approx(4e-3)
    assert phases["flash_fwd"] == pytest.approx(4e-3)
    assert phases["flash_dkv"] == pytest.approx(8e-3)
    flash = sum(seconds[p] for _, p in names["flash_kernels"])
    assert flash == pytest.approx(
        phases["flash_fwd"] + phases["flash_dkv"] + phases["swa_flash"])


def test_the_cell_reports_the_common_readings_and_its_three():
    per_layer = {m["name"]: m for m in CAT.index["per_layer"]}
    for name in READERS:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["layer"] == "attention kernel"
        assert per_layer[name]["moves"] == "train_tokens_per_s"
    assert per_layer["swa_tiles_visited_pct"]["source"] == "program_counter"
    assert per_layer["swa_flash_ms"]["source"] == "device_trace"
    common = {m["name"] for m in CAT.index["per_layer"]
              if "workloads" not in m}
    assert {"flash_ms", "flash_fwd_ms", "flash_dkv_ms", "flash_roofline_pct",
            "mfu_pct", "lm_head_ms", "fwd_ms", "bwd_ms", "optimizer_ms"} \
        <= common
    got = {m["name"] for m in CAT.metrics("per_layer", CELL)}
    assert got == common | set(READERS)
    assert {m["name"] for m in CAT.metrics("end_to_end", CELL)} == {
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
    traffic = CAT.traffic("lm-b1-s8192")
    assert (traffic["batch"], traffic["seq_len"]) == (1, 8192)
    assert set(traffic) == {"batch", "seq_len", "comment"}
    assert cell["rehearsal"]["config"] == "laguna-tiny"
    assert "thirty-second" in cell["why"]
    # a one-chip cell: the four-chip places are the accepted benchmark's
    four = [w["name"] for w in CAT.index["workloads"] if w["chips"] == 4]
    assert CELL not in four and len(four) <= len(CAT.index["workloads"]) // 4


def test_my_entries_come_after_pr_40s_in_this_order():
    """Order and membership, never the end of a list or its length: the
    next PR that appends inherits nothing from this test."""
    def after(names, mine, theirs):
        at = [names.index(n) for n in theirs + mine]
        assert at == sorted(at) and len(set(at)) == len(at)

    after([m["name"] for m in CAT.index["per_layer"]], READERS,
          ["loss_ms", "bd_noise_ms", "bd_tiles_visited_pct"])
    after([w["name"] for w in CAT.index["workloads"]], [CELL],
          ["lfm2-8b-a1b-l8-e8-s8192", "sdar-30b-a3b-l6-e16-s4096",
           "bert-large-s512-dp4-int8ef"])
    after([c["name"] for c in CAT.index["configs"]], [CONFIG],
          ["lfm2-8b-a1b-l8-e8", "sdar-30b-a3b-l6-e16"])
    # the cells that list a file of names, each its own
    listed = {w["name"]: CAT.cell(w["name"])["names"]
              for w in CAT.index["workloads"]
              if "names" in CAT.cell(w["name"])}
    assert listed["sdar-30b-a3b-l6-e16-s4096"] == ["block-diffusion"]
    assert listed[CELL] == ["sliding-window"]


# -- the earlier PRs' positional tests, whole, on the lists before this PR --

MARKED = [
    ("test_benchmark_sdar",
     "test_the_cells_files_say_what_the_issue_gave_them", ()),
    ("test_benchmark_sdar",
     "test_the_earlier_entries_stand_where_they_stood", ()),
    ("test_benchmark_sdar",
     "test_the_cells_file_of_names_adds_the_scope_for_this_cell_alone", ()),
    ("test_benchmark_sdar",
     "test_the_two_marked_tests_hold_whole_before_this_pr",
     ("test_benchmark_lfm2",
      "test_the_cell_reports_the_two_readings_and_no_other_cell_does")),
    ("test_benchmark_sdar",
     "test_the_two_marked_tests_hold_whole_before_this_pr",
     ("test_benchmark_block_parts",
      "test_the_six_are_appended_and_none_is_reported_everywhere")),
]


@pytest.mark.parametrize("module, test, args", MARKED,
                         ids=[f"{t}-{a[1] if a else 'whole'}"
                              for _, t, a in MARKED])
def test_the_marked_tests_hold_whole_before_this_pr(module, test, args,
                                                    monkeypatch):
    """The cases ``tests/conftest.py`` marks, each run whole on
    ``BENCHMARK.json``'s lists with what this PR appended taken out and
    with this PR's cell's files out of sight: every assertion of theirs
    holds there, the positions and the lengths too. PR 40's own runner of
    the two older marked tests is one of them, so those two run whole
    here as well, on the lists as they stood before PR 40."""
    their = importlib.import_module(module)
    index = dict(their.CAT.index)
    before = {key: [e for e in index[key] if e["name"] not in mine]
              for key, mine in (("workloads", {CELL}),
                                ("per_layer", set(READERS)),
                                ("configs", {CONFIG}))}
    # this PR's entries lie after everything that was there: taking them
    # out leaves the earlier lists as they were, in their order
    for key, kept in before.items():
        assert index[key][:len(kept)] == kept
        assert len(index[key]) - len(kept) == {"workloads": 1,
                                                "per_layer": 3,
                                                "configs": 1}[key]
    index.update(before)
    catalogs = {id(their.CAT): their.CAT}
    if args:
        inner = importlib.import_module(args[0])
        catalogs[id(inner.CAT)] = inner.CAT
    for cat in catalogs.values():
        monkeypatch.setattr(cat, "index", index)
    if args:
        getattr(their, test)(*args, monkeypatch)
    else:
        getattr(their, test)()


def test_the_limits_are_the_chips_and_still_see_the_two_faults_of_the_step():
    """The cell's limits as its file reasons them, and what each must
    still refuse: a module left out of the update reads 1.0 in the
    movement and a learning rate 5% off 0.05."""
    tolerance = CAT.cell(CELL)["tolerance"]
    assert _limits(tolerance) == (2e-4, 5e-4, 1.5e-3)
    assert _limits(tolerance)[2] < 0.05 < 1.0
    # says what the check cannot see, and what separates nothing
    assert "NOT" in tolerance["reason"] and "float8" in tolerance["reason"]
    assert "the window off by one" in tolerance["reason"]
    assert _limits(CAT.cell(CELL)["rehearsal"]["tolerance"]) \
        == (6e-3, 3e-2, 2e-3)
