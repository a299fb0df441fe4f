"""Family ``olmo_hybrid`` (``allenai/Olmo-Hybrid-7B``: a scalar-gated
delta rule, Gated DeltaNet, three layers in four beside 30-head full
attention without positions, a SwiGLU in every layer, Olmo's norm on each
branch's output, an untied head) on the CPU at its tiny preset: the
system against the plain reference on seeded weights (logits, the loss,
every gradient), the reference's token-by-token recurrence against
``ops/linear_attention.py``'s oracle, the configuration's file against the
published widths and the tree's parameter count, the family's counts by
hand, the cell's two readers and the accepted readers the cell is bound
to, the faults of the mathematics (``FAULTS``: a scratch script on the
chip puts the same overrides under the timed path), and the earlier PRs'
positional tests run whole on the lists as they stood before this PR.
This file's own tests hold order and membership, never the end of a list
or its length. Nothing here touches a device."""

import functools
import importlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import flops, of_which, olmo_hybrid_cost
from benchmark.catalog import Catalog
from benchmark.jobs import train_lm
from benchmark.stream import token_stream

CAT = Catalog()
FAMILY = CAT.module("families", "olmo_hybrid")
REFERENCE = CAT.module("reference", "olmo_hybrid")
TINY = CAT.config("olmo-hybrid-tiny")
CONFIG = "olmo-hybrid-7b-l4"
CELL = "olmo-hybrid-7b-l4-s8192"
READERS = ["gdn_ms", "gdn_roofline_pct"]
# the accepted readers whose scopes the new cell's step holds
BOUND = ["short_conv_ms", "mixer_proj_ms", "mlp_ms", "norm_ms", "embed_ms",
         "loss_ms"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
LENGTH = 32
# (loss, logits, gradients): the arithmetic of the two agrees to fp32
# rounding (the logits to 2.7e-6 here; the weakest fault, the QK norm a
# head at a time, moves them by 8.5e-2); in bf16 the system's operands are
# rounded, and at this size that says nothing of a gradient (none held).
TOLERANCE = {"float32": (1e-5, 2e-5, 3e-4), "bfloat16": (3e-3, 1.5e-1, None)}


def _close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) <= tol * np.linalg.norm(want)


def _tokens(seed=4, rows=2):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, LENGTH + 1),
                              0, TINY["vocab_size"])


@jax.jit
def _reference_logits(params, tokens):
    return REFERENCE.logits(params, tokens, TINY)


@jax.jit
def _reference_loss_and_gradients(params, tokens):
    return jax.value_and_grad(lambda p: REFERENCE.token_losses(
        p, {"tokens": tokens}, TINY).mean())(params)


@functools.lru_cache(maxsize=None)
def system_and_reference(dtype):
    """The system's tiny model in one compute dtype, its seeded
    parameters, a batch, and the two sides' logits, each compiled once
    for the cases (of this file and of the faults' file) that read
    them."""
    from horovod_tpu.models import OlmoHybridLM

    model = FAMILY.build(TINY)
    assert isinstance(model, OlmoHybridLM) and model.dtype == jnp.bfloat16
    model = model.clone(dtype=jnp.dtype(dtype))
    tokens = _tokens()
    params = model.init(jax.random.PRNGKey(5), tokens[:, :-1])["params"]
    got = jax.jit(model.apply)({"params": params}, tokens[:, :-1])
    want = _reference_logits(params, tokens[:, :-1])
    return model, params, tokens, got, want


@pytest.fixture(scope="module", params=sorted(TOLERANCE))
def pair(request):
    return system_and_reference(request.param) + (TOLERANCE[request.param],)


def test_the_tiny_preset_has_what_the_cell_has():
    """One whole period L L L F; more than one chunk a sequence and a
    chunk that is not the sequence; keys and values of different widths;
    every kind of setting as published."""
    assert TINY["num_hidden_layers"] == 4
    assert TINY["layer_types"][:4] == ["linear_attention"] * 3 \
        + ["full_attention"]
    assert 1 < LENGTH // TINY["gdn_chunk_size"] < LENGTH
    assert TINY["linear_key_head_dim"] != TINY["linear_value_head_dim"]
    published = CAT.config(CONFIG)
    assert TINY["linear_value_head_dim"] // TINY["linear_key_head_dim"] \
        == published["linear_value_head_dim"] \
        // published["linear_key_head_dim"] == 2
    for key in ("model_type", "hidden_act", "attention_bias",
                "rms_norm_eps", "tie_word_embeddings",
                "linear_conv_kernel_dim", "linear_allow_neg_eigval",
                "rope_parameters"):
        assert TINY[key] == published[key], key
    for config in (TINY, published):
        assert config["linear_num_key_heads"] \
            == config["linear_num_value_heads"]
        assert config["num_key_value_heads"] == config["num_attention_heads"]
        assert config["layer_types"][:config["num_hidden_layers"]].count(
            "full_attention") == 1


def test_the_logits(pair):
    _, _, _, got, want, (_, tol, _) = pair
    assert got.shape == want.shape == (2, LENGTH, TINY["vocab_size"])
    assert got.dtype == jnp.float32
    assert _close(got, want, tol)


def test_bf16_in_place_of_fp32_is_seen_by_the_fp32_limits(pair):
    """The comparison is tight enough that the precision below fails it:
    the bf16 system is not the fp32 reference by the fp32 limit, which the
    fp32 system meets."""
    model, _, _, got, want, _ = pair
    assert _close(got, want, TOLERANCE["float32"][1]) \
        == (model.dtype == jnp.float32)


def test_the_loss_is_the_mean_the_job_makes(pair):
    from horovod_tpu.models import olmo_hybrid_loss

    model, params, tokens, _, _, (tol, _, _) = pair
    want = REFERENCE.token_losses(params, {"tokens": tokens}, TINY)
    assert want.shape == (2, LENGTH) and want.dtype == jnp.float32
    assert float(_reference_loss_and_gradients(params, tokens)[0]) \
        == pytest.approx(float(want.mean()), rel=1e-6)
    loss = jax.jit(lambda p: FAMILY.loss(model, p, {"tokens": tokens}))(
        params)
    assert float(loss) == pytest.approx(float(want.mean()), rel=tol)
    # (not compiled: a bf16 product may round another way)
    assert float(olmo_hybrid_loss(model, params, tokens)) \
        == pytest.approx(float(loss), rel=tol)


def test_every_gradient():
    """In fp32, where the two agree to rounding; what bf16 does to a
    gradient at this size says nothing of the arithmetic (a linear
    layer's convolution is 50% off there: PERF.md section 7)."""
    model, params, tokens, _, _ = system_and_reference("float32")
    system = jax.jit(jax.grad(
        lambda p: FAMILY.loss(model, p, {"tokens": tokens})))(params)
    plain = _reference_loss_and_gradients(params, tokens)[1]
    flat = jax.tree_util.tree_leaves_with_path(system)
    # a layer: 2 norms and 3 of the feed-forward; a linear mixer's 9 (qkv,
    # conv, a, b, A_log, dt_bias, gate, o_norm, o), the attention mixer's
    # 6 (q, k, v, o and the two norms); the embedding, the final norm and
    # the untied head
    assert len(flat) == 4 * 5 + 3 * 9 + 6 + 3
    for (path, g), w in zip(flat, jax.tree.leaves(plain)):
        assert float(jnp.abs(w).max()) > 0, path
        assert _close(g, w, TOLERANCE["float32"][2]), path


def test_the_references_recurrence_is_the_oracles_and_not_the_chunks():
    """Token by token, as ``ops/linear_attention.py`` ``kda_reference``
    runs it with the head's scalar on every channel (the two were written
    apart), with blocks that do and do not divide the sequence; and no
    part of the chunked algorithm: scans over tokens, no running sum, no
    triangular solve."""
    from horovod_tpu.ops import linear_attention as la

    k = jax.random.split(jax.random.PRNGKey(0), 5)
    q, key = (jax.random.normal(k[i], (2, 37, 3, 8)) for i in (0, 1))
    key = key / jnp.linalg.norm(key, axis=-1, keepdims=True)
    v = jax.random.normal(k[2], (2, 37, 3, 16))
    log_decay = -jax.nn.softplus(jax.random.normal(k[3], (2, 37, 3)))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(k[4], (2, 37, 3)))
    want = la.kda_reference(
        q, key, v, jnp.broadcast_to(log_decay[..., None], key.shape), beta)
    for block in (128, 8, 37):
        REFERENCE._TOKEN_BLOCK = block
        try:
            got = REFERENCE._recurrence(q, key, v, jnp.exp(log_decay), beta)
        finally:
            REFERENCE._TOKEN_BLOCK = 128
        assert got.shape == (2, 37, 3, 16)
        assert _close(got, want, 1e-6), block
    text = str(jax.make_jaxpr(REFERENCE._recurrence)(
        q, key, v, jnp.exp(log_decay), beta))
    assert "scan" in text and "cumsum" not in text
    assert "triangular_solve" not in text
    source = open(REFERENCE.__file__).read().split('"""', 2)[2]
    assert "gated_delta_attention" not in source
    assert "horovod_tpu" not in source and "import flax" not in source


def test_layer_types_is_read_up_to_the_depth_held():
    """The published list of 32 is copied whole; the four layers held read
    its first four entries, and a depth of three reads three."""
    from horovod_tpu.models import olmo_hybrid

    config = CAT.config(CONFIG)
    assert len(config["layer_types"]) == 32 > config["num_hidden_layers"]
    model = FAMILY.build(config)
    kinds = [model.layer_parts(i)[0].__name__ for i in range(4)]
    assert kinds == ["GatedDeltaNet"] * 3 + ["RotaryGQA"]
    assert tuple(config["layer_types"]) == olmo_hybrid._PATTERN
    assert olmo_hybrid_cost.linear_layers(config) == 3
    assert olmo_hybrid_cost.linear_layers(
        {**config, "num_hidden_layers": 8}) == 6
    assert olmo_hybrid_cost.linear_layers(
        {**config, "num_hidden_layers": 3}) == 3
    shallow = FAMILY.build({**TINY, "num_hidden_layers": 3})
    shapes = jax.eval_shape(lambda: shallow.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    assert set(shapes) == {"tok_emb", "layer0", "layer1", "layer2",
                           "final_norm", "lm_head"}
    assert "A_log" in shapes["layer2"]["mixer"]


# -- the first step's three numbers at the rehearsal's size --------------

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
        "tok_emb", *(f"layer{i}" for i in range(4)), "final_norm", "lm_head"}
    assert all(move > 0 for move in plain[2].values())


def test_the_reference_in_the_precision_below_is_not_correct_here_either(
        first_step):
    """As for the other first-step cells: the plain reference with
    float8's mantissa in its matmul operands, in the program's place, on
    the cell's tiny preset against the cell's own limits: not correct."""
    from low_precision import matmul_operands_in

    params, batch, plain = first_step
    with matmul_operands_in("float8_e4m3"):
        gaps = train_lm._gaps(*_reference_numbers(params, batch), *plain)[:3]
    limits = _limits(CAT.cell(CELL)["tolerance"])
    assert any(gap > limit for gap, limit in zip(gaps, limits))


# -- the configuration and the counts -------------------------------------

def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        with open(path) as f:
            rows = [json.loads(line) for line in f]
    except FileNotFoundError:
        pytest.skip("the catalog of public architectures is not here")
    return next(r for r in rows if r["name"] == "Olmo-Hybrid-7B")


def test_the_configuration_keeps_every_published_width():
    config = CAT.config(CONFIG)
    published = {
        "model_type": "olmo_hybrid", "hidden_size": 3840,
        "intermediate_size": 11008, "num_attention_heads": 30,
        "num_key_value_heads": 30, "linear_num_key_heads": 30,
        "linear_num_value_heads": 30, "linear_key_head_dim": 96,
        "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
        "linear_allow_neg_eigval": True, "attention_bias": False,
        "rope_parameters": {"rope_theta": None}, "rms_norm_eps": 1e-6,
        "tie_word_embeddings": False, "hidden_act": "silu",
        "max_position_embeddings": 65536}
    assert {k: config[k] for k in published} == published
    assert config["layer_types"] == [
        "full_attention" if i % 4 == 3 else "linear_attention"
        for i in range(32)]
    held = {"num_hidden_layers": 4, "vocab_size": 12544}
    assert {k: config[k] for k in held} == held
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 32,
                                   "vocab_size": 100352}
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert config["num_hidden_layers"] * 8 \
        == config["published"]["num_hidden_layers"]
    assert "eight pipeline stages of four" in config["deployment"]
    assert "The number stated is the 8 that share a layer's vocabulary" \
        in config["deployment"]
    assert {"gdn_projections", "gdn_conv", "gdn_qk_l2_norm", "gdn_decay",
            "gdn_output_gate_and_norm", "gdn_chunk_size", "attention",
            "head_dim", "block", "feed_forward", "layer_types", "positions",
            "aux_loss", "initialization", "compute", "parameters"} \
        <= set(config["assumed"])
    assert "928,862,196" in config["assumed"]["parameters"]
    entry = next(c for c in CAT.index["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert len(entry["why"]) <= 200
    assert json.dumps(config)       # plain data


def test_the_configuration_is_the_catalogs_but_for_what_reduced_names():
    row = _catalog_row()
    config = CAT.config(CONFIG)
    assert config["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == set(config["reduced"])
    assert {k: row["config"][k] for k in differs} == config["published"]
    # the one key the source does not have is the program's chunk
    assert set(config) - set(row["config"]) == {
        "family", "source", "gdn_chunk_size", "reduced", "published",
        "deployment", "assumed"}
    # the tiny preset changes sizes and nothing of the kind
    kept = {k for k, v in row["config"].items() if TINY.get(k) == v}
    assert {"model_type", "linear_conv_kernel_dim", "linear_allow_neg_eigval",
            "rope_parameters", "rms_norm_eps", "tie_word_embeddings"} <= kept


def test_the_family_builds_the_share_of_the_published_model():
    """The parameter count of the configuration's file is the tree's,
    line by line."""
    from horovod_tpu.models import OlmoHybridLM

    config = CAT.config(CONFIG)
    model = FAMILY.build(config)
    assert model == OlmoHybridLM()       # the defaults are the cell's
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 512), jnp.int32))["params"])
    count = {k: sum(x.size for x in jax.tree.leaves(v))
             for k, v in shapes.items()}
    linear = 3840 * 11520 + 4 * 11520 + 2 * 3840 * 30 + 2 * 30 + 192 \
        + 2 * 3840 * 5760
    attention = 4 * 3840 * 3840 + 2 * 3840
    swiglu = 3 * 3840 * 11008
    assert (linear, attention, swiglu) == (88_750_332, 58_990_080,
                                           126_812_160)
    assert count == {
        **{f"layer{i}": linear + swiglu + 7680 for i in range(3)},
        "layer3": attention + swiglu + 7680,
        "tok_emb": 48_168_960, "lm_head": 48_168_960, "final_norm": 3_840}
    assert count["layer0"] == 215_570_172 and count["layer3"] == 185_809_920
    assert sum(count.values()) == 928_862_196
    for part in ("215,570,172", "185,809,920", "48,168,960", "88,750,332",
                 "44,236,800", "46,080", "58,990,080", "126,812,160"):
        assert part in config["assumed"]["parameters"], part
    mixer = shapes["layer0"]["mixer"]
    assert mixer["qkv"]["kernel"].shape == (3840, 11520)
    assert mixer["conv"].shape == (4, 11520)
    assert mixer["gate"]["kernel"].shape == (3840, 5760)
    assert mixer["o"]["kernel"].shape == (5760, 3840)
    assert mixer["o_norm"].shape == (192,)
    assert {mixer[n].shape for n in ("dt_bias", "A_log")} == {(30,)}
    assert {mixer[n]["kernel"].shape for n in "ab"} == {(3840, 30)}
    full = shapes["layer3"]["mixer"]
    assert set(full) == {"q", "k", "v", "o", "q_norm", "k_norm"}
    assert full["k"]["kernel"].shape == (3840, 3840)
    assert full["q_norm"]["scale"].shape == (3840,)
    assert shapes["lm_head"]["kernel"].shape == (3840, 12544)    # untied
    # the convolution's channels are whole lane tiles side by side, which
    # one projection's are not
    assert 11520 % 128 == 0 and 2880 % 128 != 0
    for key, value, match in (
            ("rope_parameters", {"rope_theta": 10000.0}, "no positions"),
            ("num_key_value_heads", 10, "groups no heads"),
            ("linear_num_key_heads", 15, "groups no heads"),
            ("tie_word_embeddings", True, "untied")):
        with pytest.raises(ValueError, match=match):
            FAMILY.build({**config, key: value})


def test_train_flops_per_token_by_hand():
    config = CAT.config(CONFIG)
    linear = 3840 * (11520 + 60 + 2 * 5760)
    assert linear == 88_704_000
    weights = 3 * linear + 4 * 3840 * 3840 + 4 * 126_812_160 + 48_168_960
    assert weights == 880_512_000
    attention = 6 * 8192 * 3840
    recurrence = 30 * (64 * (3 * 96 + 2 * 192) + 6 * 96 * 192)
    assert recurrence == 4_608_000
    assert olmo_hybrid_cost.gdn_flops_per_token_forward(config) == recurrence
    assert FAMILY.train_flops_per_token(config, 8192) \
        == 6 * weights + attention + 3 * 3 * recurrence == 5_513_287_680
    # a step: 8,192 tokens
    assert 8192 * 5_513_287_680 == pytest.approx(45.2e12, rel=1e-3)
    # the head's share is the whole model's
    assert 48_168_960 / weights == pytest.approx(0.0547, abs=2e-4)
    whole = 24 * linear + 8 * 4 * 3840 * 3840 + 32 * 126_812_160 \
        + 8 * 48_168_960
    assert 8 * 48_168_960 / whole == pytest.approx(0.0547, abs=5e-4)
    # a depth without the attention layer counts none
    three = {**config, "num_hidden_layers": 3}
    assert FAMILY.train_flops_per_token(three, 8192) == 6 * (
        3 * (linear + 126_812_160) + 48_168_960) + 3 * 3 * recurrence


def test_the_recurrences_cost_by_hand():
    """Operations with the in-chunk triangle counted half, at the count's
    own chunk whatever the program's; bytes of the operands and results
    alone, nothing recomputed; the floor memory's."""
    config = CAT.config(CONFIG)
    ops, nbytes = olmo_hybrid_cost.gdn_step_cost(config, 8192)
    assert ops == 3 * 8192 * 3 * 4_608_000 == pytest.approx(0.340e12,
                                                            rel=1e-3)
    a_head = 2 * (2 * 96 + 2 * 192) + 8 + 2 * (4 * 96 + 3 * 192) + 16
    assert a_head == 3_096
    assert olmo_hybrid_cost.gdn_bytes_per_token(config) == 30 * a_head
    assert nbytes == 3 * 8192 * 30 * a_head
    seconds, bound = flops.roofline_seconds(ops, nbytes, PEAKS)
    assert bound == "memory"
    assert 1e3 * seconds == pytest.approx(2.79, abs=0.01)
    assert 1e3 * ops / PEAKS["bf16_flops_per_s"] == pytest.approx(1.72,
                                                                  abs=0.01)
    # the program's chunk is no part of the count
    assert olmo_hybrid_cost.gdn_step_cost(
        {**config, "gdn_chunk_size": 256}, 8192) == (ops, nbytes)
    assert olmo_hybrid_cost.CHUNK == 64
    # the whole square in place of the triangle: 28% more
    whole = 30 * (2 * 64 * (3 * 96 + 2 * 192) + 6 * 96 * 192)
    assert whole == 5_898_240
    # equal widths at KDA's 128: KDA's count but for the solve's second
    # set of columns counted as what it is (C (d + d), KDA's 2 C d)
    kda = {**config, "linear_key_head_dim": 128,
           "linear_value_head_dim": 128, "linear_num_value_heads": 1}
    assert olmo_hybrid_cost.gdn_flops_per_token_forward(kda) \
        == 5 * 64 * 128 + 6 * 128 * 128


def test_the_attention_call_is_thirty_heads_of_128():
    config = CAT.config(CONFIG)
    calls = FAMILY.attention_calls(config, 1, 8192)
    assert calls == {"calls": 1, "batch": 1, "heads": 30, "seq_len": 8192,
                     "head_dim": 128, "causal": True}
    assert calls["heads"] * calls["head_dim"] == config["hidden_size"]


# -- the cell's readers -----------------------------------------------------

CALL = ' = custom-call(...), custom_call_target="tpu_custom_call"'
LAYER = "jit(step)/jvp(OlmoHybridLM)/layer0/mixer/"
BACK = "jit(step)/transpose(jvp(OlmoHybridLM))/layer0/mixer/"
# One linear layer of a step as the trace of the compiled program names
# it: the projection, the convolution's kernel, the recurrence's
# operations forward, the gated norm, and the backward of each; a flash
# call and the feed-forward beside them.
EVENTS = [
    ("%fusion.{}", 900, LAYER + "hvd_mixer_proj/qkv/dot_general"),
    ("%hvd_short_conv_fwd.{}" + CALL, 120,
     LAYER + "hvd_short_conv/pallas_call"),
    ("%fusion.{}", 400, LAYER + "hvd_gdn/exp"),
    ("%fusion.{}", 700, LAYER + "hvd_gdn/triangular_solve"),
    ("%fusion.{}", 80, LAYER + "hvd_mixer_proj/mul"),
    ("%hvd_flash_fwd.{}" + CALL, 500, "jit(step)/layer3/mixer/pallas_call"),
    ("%fusion.{}", 2000, "jit(step)/jvp(OlmoHybridLM)/layer0/ffn/hvd_mlp/"
                         "dot_general"),
    ("%fusion.{}", 1500, BACK + "hvd_gdn/bhck,bhcv->bhkv/dot_general"),
    ("%fusion.{}", 300, BACK + "hvd_gdn/mul"),
    ("%hvd_short_conv_bwd.{}" + CALL, 260,
     "jit(step)/layer0/mixer/pallas_call"),
]
# the same step with the recurrence as a later PR's kernels, named by the
# contract: the scope's string as the prefix
KERNELS = [e for e in EVENTS if "hvd_gdn" not in e[2]] + [
    ("%hvd_gdn_fwd.{}" + CALL, 350, LAYER + "hvd_gdn/pallas_call"),
    ("%hvd_gdn_bwd.{}" + CALL, 650, "jit(step)/layer0/mixer/pallas_call")]


def _record(events, steps=1, **cell):
    out, start = [], 0.0
    for i, (name, us, op_name) in enumerate(events):
        out.append([name.format(i), start, us * 1e3, "", op_name, 1])
        start += us * 1e3
    trace = {"devices": {"/device:TPU:0": out}, "hlo": {}}
    return {"trace": {"steps": steps}, "cell": cell,
            "of_which_trace": of_which._without_loops(trace)}


def test_gdn_ms_sums_the_recurrences_events_forward_and_backward():
    read = CAT.module("layer_metrics", "gdn_ms").read
    assert read(_record(EVENTS)) == pytest.approx(2.9)
    assert read(_record(EVENTS, steps=2)) == pytest.approx(1.45)
    # a kernel named with the scope as its prefix is found by its own name
    assert read(_record(KERNELS)) == pytest.approx(1.0)
    # a loop is left out of the reading: its body's events carry the names
    looped = EVENTS + [("%while.{}", 9000, "")]
    assert read(_record(looped)) == pytest.approx(2.9)
    # KDA's reader finds nothing in this step, nor this one in KDA's
    assert CAT.module("layer_metrics", "kda_ms").read(
        _record(EVENTS)) is None
    kda = [(n, us, op.replace("hvd_gdn", "hvd_kda")) for n, us, op in EVENTS]
    assert read(_record(kda)) is None


@pytest.mark.parametrize("reader, ms", [
    ("short_conv_ms", 0.38), ("mixer_proj_ms", 0.98), ("mlp_ms", 2.0)])
def test_an_accepted_reader_reads_its_scope_in_this_cells_step(reader, ms):
    """The convolution before the recurrence lies under ``hvd_short_conv``
    (its kernels are named with it) and the projections, the norms, the
    decays and the gate under ``hvd_mixer_proj``: the readers that were
    there read them, and the cell brings no second name for either."""
    read = CAT.module("layer_metrics", reader).read
    assert read(_record(EVENTS)) == pytest.approx(ms)


def test_gdn_roofline_pct_is_the_recurrences_least_time_over_their_time():
    read = CAT.module("layer_metrics", "gdn_roofline_pct").read
    record = _record(EVENTS, peaks=PEAKS, tokens_per_step=8192, chips=1)
    # 2.28 GB over 819 GB/s against 2.9 ms
    assert read(record) == pytest.approx(100 * 2.7868 / 2.9, rel=1e-3)
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
              if "hvd_gdn" not in e[2] and "hvd_short_conv" not in e[0]
              and "hvd_short_conv" not in e[2]]
    assert len(others) == 4
    assert read(_record(others, peaks=PEAKS, tokens_per_step=8192,
                        chips=1)) is None


def test_the_cell_reports_the_common_readings_its_own_and_the_bound():
    per_layer = {m["name"]: m for m in CAT.index["per_layer"]}
    for name, unit, better in (("gdn_ms", "ms/step", "lower"),
                               ("gdn_roofline_pct", "%", "higher")):
        assert per_layer[name] == {
            "name": name, "unit": unit, "better": better,
            "source": "device_trace", "layer": "linear attention",
            "moves": "train_tokens_per_s", "workloads": [CELL]}
    # the layer's name is KDA's readers', letter for letter
    assert per_layer["kda_ms"]["layer"] == "linear attention"
    common = {m["name"] for m in CAT.index["per_layer"]
              if "workloads" not in m}
    assert {"flash_ms", "flash_fwd_ms", "flash_dkv_ms", "flash_roofline_pct",
            "mfu_pct", "lm_head_ms", "fwd_ms", "bwd_ms", "optimizer_ms",
            "collective_ms", "exposed_collective_ms", "bucket_copy_ms"} \
        <= common
    # each accepted reader whose scope the cell's step holds lists the cell
    # once, after the state-space cell PR 47 appended
    for name in BOUND:
        cells = per_layer[name]["workloads"]
        assert cells.count(CELL) == 1
        assert cells.index(CELL) > cells.index(
            "granite-4.0-h-micro-l10-s8192")
    assert {m["name"] for m in CAT.metrics("per_layer", CELL)} \
        == common | set(READERS) | set(BOUND)
    # no rotation, and the other recurrences' and the convolution's
    # counted share are their cells'
    for name in ("rope_ms", "kda_ms", "kda_roofline_pct", "ssd_ms",
                 "short_conv_roofline_pct"):
        assert CELL not in per_layer[name]["workloads"]
    assert {m["name"] for m in CAT.metrics("end_to_end", CELL)} == {
        "train_tokens_per_s", "step_hbm_gib", "setup_s"}
    # no other cell reports the two
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
    assert cell["rehearsal"]["config"] == "olmo-hybrid-tiny"
    assert cell["rehearsal"]["traffic"] == "lm-tiny"
    assert "names" not in cell      # the scope needs no file of names
    # the traffic file is the window cell's; its 12,544 rows are this
    # configuration's too
    assert "12,544" in CAT.traffic("lm-b1-s8192")["comment"]
    assert CAT.config(CONFIG)["vocab_size"] == 12544
    entry = next(w for w in CAT.index["workloads"] if w["name"] == CELL)
    assert "12,544" in entry["why"] and len(entry["why"]) <= 200


def test_my_entries_come_after_pr_47s_in_this_order():
    """Order and membership, never the end of a list or its length: the
    next PR that appends inherits nothing from this test."""
    def after(names, mine, theirs):
        at = [names.index(n) for n in theirs + mine]
        assert at == sorted(at) and len(set(at)) == len(at)

    after([m["name"] for m in CAT.index["per_layer"]], READERS,
          ["dequantize_ms", "ssd_ms", "ssd_roofline_pct"])
    after([w["name"] for w in CAT.index["workloads"]], [CELL],
          ["granite-4.0-h-micro-l10-s8192", "gpt2s-s512-dp4"])
    after([c["name"] for c in CAT.index["configs"]], [CONFIG],
          ["laguna-s2.1-l5-e8", "granite-4.0-h-micro-l10"])
    # the four-chip places are as they were: this cell takes one chip
    four = [w["name"] for w in CAT.index["workloads"] if w["chips"] == 4]
    assert CELL not in four and len(four) <= len(CAT.index["workloads"]) // 4


# -- the earlier PRs' positional tests, whole, on the lists before this PR --

GRANITE = importlib.import_module("test_benchmark_granite")
MINE = {"configs": {CONFIG}, "workloads": {CELL}, "per_layer": set(READERS)}
# what tests/conftest.py marks since this PR, as (test, its arguments,
# whether it takes monkeypatch): the thirteen cases of PR 47's runner, and
# its test of where its cells stand in the accepted readers' lists
MARKED = [
    pytest.param(GRANITE.test_the_marked_tests_hold_whole_before_this_pr,
                 case.values, True, id=case.id)
    for case in GRANITE.MARKED] + [pytest.param(getattr(
        GRANITE, "test_the_cells_report_the_common_readings_their_own_"
        "and_the_bound"), (), False, id="the_bound")]


def _index_before_this_pr():
    """``BENCHMARK.json``'s lists with this PR's entries taken out, and
    its cell taken out of each accepted reader's list."""
    index = dict(CAT.index)
    for key, mine in MINE.items():
        index[key] = [e for e in index[key] if e["name"] not in mine]
    index["per_layer"] = [
        {**m, "workloads": [c for c in m["workloads"]
                            if c not in MINE["workloads"]]}
        if "workloads" in m else m for m in index["per_layer"]]
    return index


@pytest.mark.parametrize("their, case, patches", MARKED)
def test_the_marked_tests_hold_whole_before_this_pr(their, case, patches,
                                                    monkeypatch):
    """The cases ``tests/conftest.py`` marks since this PR appended a
    configuration, a cell and two readers after PR 47's, and its cell to
    six accepted readers' lists: the thirteen cases of PR 47's runner
    (which asserts that PR 47's entries end the lists) and its test of the
    accepted readers' lists (which asserts that its cells end them), each
    run whole on ``BENCHMARK.json``'s lists as they stood before this PR.
    That runner takes PR 47's own entries out in turn and runs PR 45's, PR
    42's and PR 40's inside it, so every assertion of the chain executes
    here: five runners deep."""
    index = _index_before_this_pr()
    monkeypatch.setattr(GRANITE.CAT, "index", index)
    # a catalog made inside a test reads the same lists
    made = Catalog.__init__

    def catalog(self, *args, **kwargs):
        made(self, *args, **kwargs)
        self.index = index

    monkeypatch.setattr(Catalog, "__init__", catalog)
    their(*case, *([monkeypatch] if patches else []))


def test_the_lists_before_this_pr_are_the_lists_without_it():
    index = _index_before_this_pr()
    assert len(MARKED) == 14
    for key, mine in MINE.items():
        names = [e["name"] for e in index[key]]
        assert not mine & set(names)
        assert [e["name"] for e in CAT.index[key]
                if e["name"] not in mine] == names
    assert not any(CELL in m.get("workloads", ())
                   for m in index["per_layer"])


def test_the_limits_are_the_chips_and_still_see_the_two_faults_of_the_step():
    """The cell's limits as its file reasons them, and what each must
    still refuse: a module left out of the update reads 1.0 in the
    movement and a learning rate 5% off 0.05."""
    tolerance = CAT.cell(CELL)["tolerance"]
    assert _limits(tolerance) == (5e-4, 2e-3, 1.5e-3)
    assert _limits(tolerance)[2] < 0.05 < 1.0
    reason = tolerance["reason"]
    # sqrt(sum nu), the number precision moves, between its two readings
    # on the chip: the sound runs' largest, and the least the control or a
    # fault of the mathematics reads
    sound, under = "1.06e-3", "3.63e-3"
    assert 1.5 * float(sound) < tolerance["grad_scale_rtol"] \
        < float(under) / 1.5
    assert sound in reason and under in reason and "5.64e-3" in reason
    # the two others over their sound runs' largest with room, and the
    # file says that neither separates
    for limit, largest in ((tolerance["loss_rtol"], "1.63e-4"),
                           (tolerance["module_move_rtol"], "1.50e-4")):
        assert 3 * float(largest) <= limit and largest in reason
    assert "NOT" in reason and "float8" in reason
    # what the check saw of the faults, and what it did not
    assert "correct: false on these ten" in reason
    assert "UNSEEN, correct: true: the gate before the output norm" in reason
    assert "KeyError" in reason
    assert _limits(CAT.cell(CELL)["rehearsal"]["tolerance"]) \
        == (4e-3, 0.6, 1.5e-3)
