"""Family ``sdar`` (``JetLM/SDAR-30B-A3B-Chat``: QK-normed rotary GQA and a
softmax-routed top-k mixture of experts in every layer, an untied head,
trained by block diffusion: one pass over a noisy and a clean copy of
every sequence under a block-structured attention mask) on the CPU at its
tiny preset: the system against the plain reference on seeded weights
(the noisy copy's logits, the loss, every gradient), the noise of the two
bit for bit, the shares of the experts adding up to the uncut layer, the
configuration's file against the published widths, the family's counts by
hand, the cell's two readers, and the faults of the mathematics that the
cell's limits were held against on the chip (``FAULTS``: a scratch script
there puts the same overrides under the timed path). Nothing here touches
a device."""

import contextlib
import importlib
import json

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import flops
from benchmark.catalog import Catalog
from benchmark.jobs import train_lm
from benchmark.stream import token_stream

CAT = Catalog()
FAMILY = CAT.module("families", "sdar")
REFERENCE = CAT.module("reference", "sdar")
TINY = CAT.config("sdar-tiny")
CONFIG = "sdar-30b-a3b-l6-e16"
CELL = "sdar-30b-a3b-l6-e16-s4096"
INT8_CELL = "bert-large-s512-dp4-int8ef"
LENGTH = 128
# (loss, logits, gradients): the arithmetic of the two agrees to fp32
# rounding; in bf16 the system's operands are rounded, and a router that
# reads rounded activations gives a few tokens another fourth expert.
TOLERANCE = {"float32": (1e-5, 2e-4, 3e-4), "bfloat16": (3e-3, 1e-1, 5e-1)}


def _close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) <= tol * np.linalg.norm(want)


def _tokens(seed=4, rows=2, config=TINY):
    """(rows, L + 1) ids: L of data and the row's noise seed."""
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, LENGTH + 1),
                              0, config["vocab_size"])


@pytest.fixture(scope="module", params=sorted(TOLERANCE))
def pair(request):
    """The system's tiny model in one compute dtype, its seeded
    parameters and a batch; the reference reads the same tree."""
    from horovod_tpu.models import SdarLM

    model = FAMILY.build(TINY)
    assert isinstance(model, SdarLM) and model.dtype == jnp.bfloat16
    model = model.clone(dtype=jnp.dtype(request.param))
    tokens = _tokens()
    params = model.init(jax.random.PRNGKey(5), tokens[:, :-1])["params"]
    return model, params, tokens, TOLERANCE[request.param]


def _noisy_logits(model, params, tokens):
    """The system's fp32 logits of the noisy copy: the model over
    ``[noisy ; clean]`` as ``sdar_loss`` assembles it."""
    from horovod_tpu.models import block_noise
    from horovod_tpu.ops.flash_attention import BlockDiffusionMask

    x = tokens[:, :-1]
    masked, _ = block_noise(tokens[:, -1], LENGTH, model.block,
                            model.noise_seed)
    both = jnp.concatenate([jnp.where(masked, model.mask_token, x), x], 1)
    positions = jnp.tile(jnp.arange(LENGTH), 2)[None]
    return model.apply({"params": params}, both, positions,
                       BlockDiffusionMask(model.block))[:, :LENGTH]


def test_the_noisy_copys_logits(pair):
    model, params, tokens, (_, tol, _) = pair
    logits = _noisy_logits(model, params, tokens)
    want = REFERENCE.noisy_logits(params, tokens, TINY)
    assert logits.shape == want.shape == (2, LENGTH, TINY["vocab_size"])
    assert logits.dtype == jnp.float32
    assert _close(logits, want, tol)


def test_the_loss_is_the_weighted_sum_the_job_makes(pair):
    from horovod_tpu.models import sdar_loss

    model, params, tokens, (tol, _, _) = pair
    want = REFERENCE.token_losses(params, {"tokens": tokens}, TINY)
    assert want.shape == (2, LENGTH) and want.dtype == jnp.float32
    # the job's weights: 1 / (rows x L) at every position
    assert float(sdar_loss(model, params, tokens)) \
        == pytest.approx(float(want.mean()), rel=tol)
    assert float(FAMILY.loss(model, params, {"tokens": tokens})) \
        == pytest.approx(float(want.mean()), rel=tol)
    # unmasked positions carry no loss, masked ones m / t times theirs
    _, masked, rates = REFERENCE.noise(tokens, TINY)
    assert 0 < int(masked.sum()) < masked.size
    assert float(jnp.abs(jnp.where(masked, 0.0, want)).max()) == 0
    assert float(jnp.where(masked, want * rates, 1.0).min()) > 0


def test_every_gradient(pair):
    from horovod_tpu.models import sdar_loss

    model, params, tokens, (_, _, tol) = pair
    got = jax.grad(lambda p: sdar_loss(model, p, tokens))(params)
    want = jax.grad(lambda p: REFERENCE.token_losses(
        p, {"tokens": tokens}, TINY).mean())(params)
    flat = jax.tree_util.tree_leaves_with_path(got)
    # a layer: 2 norms, 6 of attention, 4 of the experts; the embedding,
    # the final norm, the untied head
    assert len(flat) == 12 * TINY["num_hidden_layers"] + 3
    for (path, g), w in zip(flat, jax.tree.leaves(want)):
        assert float(jnp.abs(w).max()) > 0, path
        assert _close(g, w, tol), path


def test_the_noise_of_the_two_is_equal_bit_for_bit():
    """The system's draw (``models/sdar.py`` ``block_noise``, vmapped over
    the rows) and the reference's (a row at a time, written from the
    configuration's ``assumed``) from the same batch: the same masks and
    the same rates, to the bit."""
    from horovod_tpu.models import block_noise

    for seed in (1, 2, 3):
        batch = next(token_stream(seed, CAT.traffic("bd-tiny"),
                                  TINY["vocab_size"]))
        tokens = jnp.asarray(batch["tokens"])
        masked, rates = jax.jit(
            lambda t: block_noise(t[:, -1], LENGTH, TINY["block_length"],
                                  TINY["noise_seed"]))(tokens)
        x, want_masked, want_rates = jax.jit(
            lambda t: REFERENCE.noise(t, TINY))(tokens)
        assert np.array_equal(np.asarray(x), batch["tokens"][:, :-1])
        assert np.array_equal(np.asarray(masked), np.asarray(want_masked))
        assert rates.shape == (2, LENGTH // 4)
        assert np.array_equal(
            np.asarray(jnp.repeat(rates, 4, axis=1)).view(np.uint32),
            np.asarray(want_rates).view(np.uint32))
        assert 1e-3 <= float(rates.min()) and float(rates.max()) < 1.0
        # two rows, two seeds: not the same draw twice
        assert not np.array_equal(np.asarray(masked[0]),
                                  np.asarray(masked[1]))


def test_the_references_mask_is_the_kernels_mask():
    from horovod_tpu.ops.flash_attention import BlockDiffusionMask

    at = jnp.arange(2 * 24)
    want = REFERENCE.visible(at[:, None], at[None, :], 24, 4)
    assert np.array_equal(np.asarray(want), BlockDiffusionMask(4).dense(48))


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The model-configs guide's test of the cut: the parts of an expert
    layer's output that the four shares of 4 of 16 experts give (the
    system's layer, told which experts it holds) add up to what the
    reference gives for the whole layer (all 16 held)."""
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
    total = 0.0
    for first in (0, 4, 8, 12):
        share = {k: v if k == "router" else v[first:first + 4]
                 for k, v in whole.items()}
        layer = SparseExperts(16, (first, 4), 4, 32, 0, 1.0, jnp.float32)
        y, stats = layer.apply({"params": share}, x)
        assert float(stats["dropped_tokens"]) == 0
        total = total + y
    with jax.default_matmul_precision("highest"):
        want = REFERENCE._experts(
            x, whole, {**TINY, "held_experts_first": 0})
    assert _close(total, want, 2e-4)
    # and one share is a part, not the whole
    assert not _close(y, want, 0.1)


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


def _faulty_route(change):
    """``moe.route_top_k`` with ``change(experts, weights)`` over what it
    returns."""
    from horovod_tpu.parallel import moe

    def wrap(real):
        def route(*args, **kwargs):
            return change(*real(*args, **kwargs))
        return route
    return _patched(moe, "route_top_k", wrap)()


def _one_expert_fewer(experts, weights):
    """top-(k - 1): the last (weakest) choice goes, the others share 1."""
    kept = weights.at[:, -1].set(0.0)
    return experts, kept / kept.sum(-1, keepdims=True)


def _one_experts_routes_dropped(config):
    dropped = config["held_experts_first"] + 1
    return _faulty_route(lambda experts, weights: (
        experts, jnp.where(experts == dropped, 0.0, weights)))


@contextlib.contextmanager
def _noisy_sees_its_own_clean_block(config):
    """n(w) <= n(u) where the noisy copy looks at the clean one, in place
    of <: in the matrix the jnp fallback reads and in the select of the
    kernels' partial tiles (which lie over the same tiles either way)."""
    from horovod_tpu.ops import flash_attention as fa

    def dense(real):
        def leaky(self, s):
            half = s // 2
            at = np.arange(s)
            own = (at[:, None] < half) & (at[None] >= half) & (
                (at[:, None] % half) // self.block
                == (at[None] % half) // self.block)
            return real(self, s) | own
        return leaky

    def keep(real):
        def leaky(self, s, row0, col0, shape, rows_dim):
            _, r0 = self._local(s, row0)
            cn, c0 = self._local(s, col0)
            nu = fa._block_of(r0 + jax.lax.broadcasted_iota(
                jnp.int32, shape, rows_dim), self.block)
            nw = fa._block_of(c0 + jax.lax.broadcasted_iota(
                jnp.int32, shape, 1 - rows_dim), self.block)
            return (nw <= nu) & (nw >= nu - jnp.where(cn, 0, s))
        return leaky

    with _patched(fa.BlockDiffusionMask, "dense", dense)(), \
            _patched(fa.BlockDiffusionMask, "keep", keep)():
        yield


def _weight_left_out(config):
    """The loss of a masked token unweighted: m in place of m / t."""
    from horovod_tpu.models import sdar

    return _patched(sdar, "block_noise", lambda real: lambda *args: (
        lambda masked, rates: (masked, jnp.ones_like(rates)))(
            *real(*args)))()


def _qk_norms_left_out(config):
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


# (``config -> context``): while the context is open, a model that is
# built and traced has the fault; the reference never does.
FAULTS = {
    "noisy_sees_its_own_clean_block": _noisy_sees_its_own_clean_block,
    "weight_left_out": _weight_left_out,
    "one_expert_fewer_a_token": lambda config: _faulty_route(
        _one_expert_fewer),
    "qk_norms_left_out": _qk_norms_left_out,
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
        "tok_emb", *(f"layer{i}" for i in range(6)), "final_norm",
        "lm_head"}
    assert all(move > 0 for move in plain[2].values())


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_of_the_mathematics_is_not_correct(first_step, fault):
    """Each of the five, at the tiny preset, against the rehearsal's
    limits, by at least one of them."""
    params, batch, plain = first_step
    with FAULTS[fault](TINY):
        gaps = train_lm._gaps(*_system_numbers(params, batch), *plain)[:3]
    limits = _limits(CAT.cell(CELL)["rehearsal"]["tolerance"])
    assert any(gap > limit for gap, limit in zip(gaps, limits)), gaps


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_moves_the_system_away_from_the_reference(fault):
    """Every one of the five, in fp32, where the sound system and the
    reference agree to rounding (two layers): the override is under the
    model, and the comparison (the noisy copy's logits; the loss for the
    fault that is the loss's own) sees it. Afterwards it is gone."""
    from horovod_tpu.models import sdar_loss

    config = {**TINY, "num_hidden_layers": 2}
    tokens = _tokens()
    model = FAMILY.build(config).clone(dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(5), tokens[:, :-1])["params"]

    def both():
        model = FAMILY.build(config).clone(dtype=jnp.float32)
        return (_noisy_logits(model, params, tokens),
                sdar_loss(model, params, tokens))

    with jax.default_matmul_precision("highest"):
        want = (REFERENCE.noisy_logits(params, tokens, config),
                REFERENCE.token_losses(params, {"tokens": tokens},
                                       config).mean())
        with FAULTS[fault](config):
            faulty = both()
        which = 1 if fault == "weight_left_out" else 0
        assert not _close(faulty[which], want[which],
                          50 * TOLERANCE["float32"][which])
        sound = both()
        assert _close(sound[0], want[0], TOLERANCE["float32"][1])
        assert _close(sound[1], want[1], TOLERANCE["float32"][0])


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

def test_the_configuration_keeps_every_published_width():
    config = CAT.config(CONFIG)
    published = {"attention_bias": False, "decoder_sparse_step": 1,
                 "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
                 "intermediate_size": 6144,
                 "max_position_embeddings": 32768, "max_window_layers": 48,
                 "mlp_only_layers": [], "model_type": "sdar_moe",
                 "moe_intermediate_size": 768, "norm_topk_prob": True,
                 "num_attention_heads": 32, "num_experts_per_tok": 8,
                 "num_key_value_heads": 4, "rms_norm_eps": 1e-6,
                 "rope_scaling": None, "rope_theta": 1000000,
                 "sliding_window": None, "tie_word_embeddings": False,
                 "use_sliding_window": False}
    assert {k: config[k] for k in published} == published
    assert config["router_width"] == 128    # the router keeps its width
    held = {"num_hidden_layers": 6, "num_experts": 16, "vocab_size": 18992,
            "held_experts_first": 0, "block_length": 4,
            "mask_token_id": 18991}
    assert {k: config[k] for k in held} == held
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 128, "vocab_size": 151936}
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert "eight pipeline stages of six" in config["deployment"]
    assert "eight chips that share each layer" in config["deployment"]
    assert {"block_length", "noise", "objective", "layout", "mask_token_id",
            "noise_seed", "router", "attention", "initialization",
            "compute", "parameters", "expert_blocks"} \
        <= set(config["assumed"])
    # the step's cost does not follow the routes (the driver's check)
    assert config["whole_expert_blocks"] is True
    assert "whole_expert_blocks true" in config["assumed"]["expert_blocks"]
    assert "645,623,296" in config["assumed"]["parameters"]
    entry = next(c for c in CAT.index["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert json.dumps(config)       # plain data


def test_the_family_builds_the_share_of_the_published_model():
    from horovod_tpu.models import SdarLM

    config = CAT.config(CONFIG)
    model = FAMILY.build(config)
    assert model == SdarLM(noise_seed=config["noise_seed"],
                           whole_expert_blocks=True)
    # the rehearsal runs the path the cell runs
    assert FAMILY.build(TINY).whole_expert_blocks
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 128), jnp.int32))["params"])
    count = {k: sum(x.size for x in jax.tree.leaves(v))
             for k, v in shapes.items()}
    layer = 18_874_368 + 256 + 4_096 + 262_144 + 16 * 4_718_592
    assert layer == 94_638_336
    assert count == {**{f"layer{i}": layer for i in range(6)},
                     "tok_emb": 38_895_616, "lm_head": 38_895_616,
                     "final_norm": 2_048}
    assert sum(count.values()) == 645_623_296
    assert shapes["layer0"]["mixer"]["q"]["kernel"].shape == (2048, 4096)
    assert shapes["layer0"]["mixer"]["k"]["kernel"].shape == (2048, 512)
    assert shapes["layer0"]["ffn"]["router"].shape == (2048, 128)
    assert shapes["layer0"]["ffn"]["experts_gate"].shape == (16, 2048, 768)


def test_train_flops_per_token_by_hand():
    config = CAT.config(CONFIG)
    layer = 18_874_368 + 262_144 + 4_718_592
    want = 6 * (2 * 6 * layer + 38_895_616) + 6 * 6 * 2 * 4096 * 4096
    assert want == 3_158_900_736
    assert FAMILY.train_flops_per_token(config, 4096) == want
    # a step: 8,192 tokens of data
    assert 8192 * want == pytest.approx(25.9e12, rel=2e-3)


def test_the_attention_is_counted_by_its_visible_pairs():
    """Two causal calls a layer at L, whatever the kernels' layout: what
    ``flash_roofline_pct`` divides is the work of the pairs the mask
    allows, under what the kernels' one call over 2L would be charged as
    a causal call and far under its square."""
    config = CAT.config(CONFIG)
    calls = FAMILY.attention_calls(config, 2, 4096)
    assert calls == {"calls": 12, "batch": 2, "heads": 32, "seq_len": 4096,
                     "head_dim": 128, "causal": True}
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least, bound = flops.attention_step_roofline(calls, peaks)
    assert bound == {"fwd": "compute", "bwd": "compute"}
    # 12 calls x 7 products x 2 x 32 heads x 4096^2 / 2 pairs x 128 x 2
    pairs = 4096 * 4096 / 2
    assert least * 197e12 == pytest.approx(12 * 7 * 2 * 2 * 32 * pairs * 128)
    square = flops.attention_step_roofline(
        {**calls, "calls": 6, "seq_len": 8192, "causal": False}, peaks)[0]
    assert least == pytest.approx(square / 4)
    # and what the mask allows over that count: the noisy diagonal
    from horovod_tpu.ops.flash_attention import BlockDiffusionMask
    allowed = BlockDiffusionMask(4).dense(2 * 256).sum()
    assert allowed == 256 * 256 + 256 * 4
    assert allowed / (2 * 256 * 257 / 2) == pytest.approx(1.0, abs=0.02)


# -- the cell's readers -----------------------------------------------------

def test_bd_tiles_visited_pct_divides_the_gauges_samples():
    """The gauge a block-diffusion call sets where it is traced, read as
    a share; a causal call's samples are not in it."""
    import horovod_tpu as hvd
    from horovod_tpu.ops import flash_attention as fa

    reader = CAT.module("layer_metrics", "bd_tiles_visited_pct")
    q = jnp.zeros((1, 256, 2, 16))
    fa._say_path.cache_clear()
    jax.jit(lambda q: fa.flash_attention(
        q, q, q, mask_kind=fa.BlockDiffusionMask(4), use_pallas=True,
        block_q=32, block_k=32)).lower(q)
    jax.jit(lambda q: fa.flash_attention(
        q, q, q, causal=True, use_pallas=True, block_q=32,
        block_k=32)).lower(q)
    samples = {(s["labels"]["mask_kind"], s["labels"]["tiles"]): s["value"]
               for s in hvd.metrics()[reader.GAUGE]["samples"]
               if s["labels"]["seq_len"] == "256"
               and s["labels"]["block_q"] == s["labels"]["block_k"] == "32"}
    # 8 x 8 tiles: 4 on the noisy diagonal, 4 + 3 + 2 + 1 twice in the
    # clean columns; causal 8 x 9 / 2
    assert samples[("block_diffusion", "visited")] == 24
    assert samples[("block_diffusion", "square")] == 64
    assert samples[("causal", "visited")] == 36
    # every block-diffusion call this process has traced, other tests' too
    got = reader.read({})
    assert got is not None and 25.0 < got <= 100.0


def test_the_cells_diagonals_stand_a_little_over_the_quarter():
    """At the cell's own shape and the kernels' own blocks: 16 x 16 tiles
    of 512, 8 + 2 x 36 visited: 31.25%."""
    from horovod_tpu.ops import flash_attention as fa

    blocks = fa._resolve_blocks(8192, 128, jnp.bfloat16, None, None, False,
                                fa.BlockDiffusionMask(4).span(8192))
    assert blocks == (512, 512)
    forward, backward, square = fa.tiles_visited(
        fa.BlockDiffusionMask(4), 8192, *blocks)
    assert (forward, backward, square) == (80, 80, 256)


def test_bd_noise_ms_reads_the_cells_own_phase():
    reader = CAT.module("layer_metrics", "bd_noise_ms")
    assert reader.read({"trace": {"steps": 3}, "names": {"phases": {}}}) \
        is None
    assert reader.read({}) is None
    record = {"trace": {"steps": 2}, "names": {"phases": {"bd_noise": []}},
              "phases": {"ms": {"bd_noise": 0.25}, "named": {"dense": True},
                         "kind": {"bd_noise": "dense"}}}
    assert reader.read(record) == 0.25


def test_the_cells_file_of_names_adds_the_scope_for_this_cell_alone():
    from benchmark import hlo_counts
    from horovod_tpu.common import scopes

    cell = CAT.cell(CELL)
    assert cell["names"] == ["block-diffusion"]
    names = hlo_counts.load_names(CAT.names(cell))
    assert names["dense_markers"][0] == [scopes.BD_NOISE, "bd_noise"]
    assert scopes.BD_NOISE in names["program_scopes"]
    assert names["phases"]["bd_noise"] == ["bd_noise"]
    assert "bd_noise" not in hlo_counts.load_names()["phases"]
    for other in CAT.index["workloads"]:
        if other["name"] != CELL:
            assert "names" not in CAT.cell(other["name"])


def test_the_cells_report_their_readings():
    per_layer = {m["name"]: m for m in CAT.index["per_layer"]}
    for name in ("bd_noise_ms", "bd_tiles_visited_pct"):
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["layer"] == "block diffusion"
        assert per_layer[name]["moves"] == "train_tokens_per_s"
    common = {m["name"] for m in CAT.index["per_layer"]
              if "workloads" not in m}
    assert len(common) == 20
    for cell, own in ((CELL, {"bd_noise_ms", "bd_tiles_visited_pct"}),
                      (INT8_CELL, set())):
        got = {m["name"] for m in CAT.metrics("per_layer", cell)}
        assert got == common | own, cell
        assert {m["name"] for m in CAT.metrics("end_to_end", cell)} == {
            "train_tokens_per_s", "step_hbm_gib", "setup_s"}


def test_the_cells_files_say_what_the_issue_gave_them():
    cell = CAT.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"], cell["job"],
            cell["check_steps"], cell["reference_microbatch"]) == (
        CONFIG, "bd-b2-s4096", 1, "train_lm", 1, 1)
    assert CAT.traffic("bd-b2-s4096")["batch"] == 2
    assert CAT.traffic("bd-b2-s4096")["seq_len"] == 4096
    assert set(CAT.traffic("bd-b2-s4096")) == {"batch", "seq_len", "comment"}
    int8, plain = CAT.cell(INT8_CELL), CAT.cell("bert-large-s512-dp4")
    assert (int8["config"], int8["traffic"], int8["chips"], int8["job"],
            int8["check_steps"], int8["reference_microbatch"]) == (
        "bert-large", "mlm-b32-s512-int8ef", 4, "train_lm", 2, 8)
    same = dict(CAT.traffic("mlm-b32-s512"), comment="")
    assert dict(CAT.traffic("mlm-b32-s512-int8ef"), comment="") == same
    assert int8["optimizer"] == {**plain["optimizer"],
                                 "compression": "int8_ef"}
    assert int8["rehearsal"]["config"] == plain["rehearsal"]["config"]
    four = [w["name"] for w in CAT.index["workloads"] if w["chips"] == 4]
    assert four == ["bert-large-s512-dp4", INT8_CELL]
    assert len(CAT.index["workloads"]) == 10    # a quarter of which is 2


def test_the_earlier_entries_stand_where_they_stood():
    """What two earlier tests held by position in lists that have grown
    since (``conftest.py``): the entries themselves, in their order,
    before what this PR appended."""
    lfm2 = "lfm2-8b-a1b-l8-e8-s8192"
    names = [m["name"] for m in CAT.index["per_layer"]]
    assert names[-8:] == ["mixer_proj_ms", "rope_ms", "mlp_ms", "norm_ms",
                          "embed_ms", "loss_ms", "bd_noise_ms",
                          "bd_tiles_visited_pct"]
    cells = [w["name"] for w in CAT.index["workloads"]]
    assert cells[-3:] == [lfm2, CELL, INT8_CELL]
    two = {"short_conv_ms", "short_conv_roofline_pct"}
    common = {m["name"] for m in CAT.index["per_layer"]
              if "workloads" not in m}
    for cell in cells:
        got = {m["name"] for m in CAT.metrics("per_layer", cell)}
        assert (two <= got) == (cell == lfm2)
    assert {m["name"] for m in CAT.metrics("per_layer", lfm2)} \
        == common | two
    cell = CAT.cell(lfm2)
    assert (cell["config"], cell["traffic"], cell["chips"], cell["job"],
            cell["check_steps"], cell["reference_microbatch"]) == (
        "lfm2-8b-a1b-l8-e8", "lm-b2-s8192", 1, "train_lm", 1, 1)
    assert "names" not in cell
    six = {"mixer_proj_ms", "rope_ms", "mlp_ms", "norm_ms", "embed_ms",
           "loss_ms"}
    assert not six & {m["name"] for m in CAT.metrics("per_layer", lfm2)}
    for cell, count in (("gpt2s-s512", 5), ("bert-large-s512-dp4", 4),
                        ("ouro-2.6b-l8-s2048", 6),
                        ("solar-open2-l4-e8-s4096", 5), (CELL, 0),
                        (INT8_CELL, 0)):
        assert len(six & {m["name"] for m in CAT.metrics(
            "per_layer", cell)}) == count


@pytest.mark.parametrize("module, test", [
    ("test_benchmark_lfm2",
     "test_the_cell_reports_the_two_readings_and_no_other_cell_does"),
    ("test_benchmark_block_parts",
     "test_the_six_are_appended_and_none_is_reported_everywhere")])
def test_the_two_marked_tests_hold_whole_before_this_pr(module, test,
                                                        monkeypatch):
    """The two tests ``conftest.py`` marks, run whole on the lists with
    what this PR appended taken off their ends: every assertion of
    theirs holds there, the position too."""
    their = importlib.import_module(module)
    index = dict(their.CAT.index)
    assert [w["name"] for w in index["workloads"][-2:]] == [CELL, INT8_CELL]
    assert [m["name"] for m in index["per_layer"][-2:]] == [
        "bd_noise_ms", "bd_tiles_visited_pct"]
    index["workloads"] = index["workloads"][:-2]
    index["per_layer"] = index["per_layer"][:-2]
    monkeypatch.setattr(their.CAT, "index", index)
    getattr(their, test)()


def test_the_limits_are_the_chips_and_still_see_the_two_faults_of_the_step():
    """The two new cells' limits as their files reason them, and what
    each must still refuse: a module left out of the update reads 1.0 in
    the movement and a missing 1/n reads n - 1 in sqrt(sum nu)."""
    assert _limits(CAT.cell(CELL)["tolerance"]) == (1e-4, 3e-3, 0.1)
    int8 = _limits(CAT.cell(INT8_CELL)["tolerance"])
    assert int8 == (2e-4, 2e-2, 0.3)
    plain = _limits(CAT.cell("bert-large-s512-dp4")["tolerance"])
    assert int8[:2] == plain[:2] and int8[2] > plain[2]
    for limits, chips in ((int8, 4), (_limits(CAT.cell(CELL)["tolerance"]),
                                      1)):
        assert limits[2] < 1.0
        assert chips == 1 or limits[1] < chips - 1
    for cell in (CELL, INT8_CELL):
        assert "NOT" in CAT.cell(cell)["tolerance"]["reason"]   # says so
