"""The names the training step gives its device work
(``horovod_tpu/common/scopes.py``): each is on the instructions it was
written for, and the benchmark's data file and the docs quote the same
strings."""

import contextlib
import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import PartitionSpec as P

from horovod_tpu.common import scopes
from horovod_tpu.ops import collectives as C
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops import pallas_kernels as pk

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

OPTIMIZERS = {
    "none": dict(compression="none"),
    "int8_ef": dict(compression="int8_ef", quantize_min_bucket_bytes=1024),
    "bf16": dict(compression="bf16"),
    "adasum": dict(compression="none", op=C.ReduceOp.ADASUM),
}
# A linear op with a per-element wire over the flat axis reduces each
# gradient where it lies: no flat bucket, so nothing under pack / unpack.
# Adasum's per-bucket dots keep the bucket.
IN_PLACE = {"none", "bf16"}


def _model(family):
    """The tiny model of one of the five families."""
    from horovod_tpu import models

    if family == "gpt":
        return models.gpt.gpt_tiny(vocab_size=128)
    if family == "bert":
        return models.bert.bert_tiny(vocab_size=128)
    if family == "ouro":
        return models.looplm.LoopLM(vocab_size=64, num_layers=2, hidden=32,
                                    num_heads=2, head_dim=16, mlp_dim=48,
                                    passes=3)
    if family == "solar":
        return models.SolarLM(
            vocab_size=64, num_layers=2, hidden=32, gqa_layers=(0,),
            num_heads=2, num_kv_heads=1, head_dim=16, kda_heads=2,
            kda_head_dim=16, gate_rank=8, num_experts=8, held_experts=(2, 4),
            top_k=2, expert_dim=16, shared_dim=16)
    return models.Lfm2LM(
        vocab_size=64, num_layers=3, hidden=32,
        layer_types=("conv", "full_attention", "conv"), num_heads=2,
        num_kv_heads=1, head_dim=16, num_dense_layers=1, mlp_dim=48,
        num_experts=8, held_experts=(2, 4), top_k=2, expert_dim=16)


@contextlib.contextmanager
def _compiled_here():
    """JAX's persistent compile cache off: an ``op_name`` is metadata,
    which is not in the cache's key, so a warm cache hands back the names
    of whichever program of this text was compiled first."""
    from jax.experimental.compilation_cache import compilation_cache

    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def op_names(hvd):
    """``op_names(family, optimizer)``: every ``op_name`` in the compiled
    text of a tiny data-parallel step over the 8 virtual ranks (on one
    device the reduction is the identity and XLA drops its copies)."""
    cache = {}

    def get(family, optimizer):
        if (family, optimizer) not in cache:
            ax = hvd.rank_axis()
            model = _model(family)
            tokens = jnp.zeros((8, 16), jnp.int32)
            params = model.init(jax.random.PRNGKey(0), tokens[:1])["params"]
            tx = hvd.DistributedOptimizer(
                optax.adamw(1e-3), axis_name=ax, fusion_threshold_bytes=4096,
                **OPTIMIZERS[optimizer])

            def step(params, opt_state, tokens):
                def loss(p):
                    logits = model.apply({"params": p}, tokens)
                    return optax.softmax_cross_entropy_with_integer_labels(
                        logits, tokens).mean()

                value, grads = jax.value_and_grad(loss)(params)
                updates, opt_state = tx.update(grads, opt_state, params)
                return (optax.apply_updates(params, updates), opt_state,
                        jax.lax.pmean(value, ax))

            jitted = hvd.spmd_step(step, in_specs=(P(), P(), P(ax)),
                                   out_specs=(P(), P(), P()))
            with _compiled_here():
                text = jitted.lower(params, tx.init(params),
                                    tokens).compile().as_text()
            cache[family, optimizer] = set(
                re.findall(r'op_name="([^"]*)"', text))
        return cache[family, optimizer]

    return get


def _under(scope):
    """Matches an ``op_name`` with ``scope`` as consecutive components."""
    return re.compile(r"(^|/)" + re.escape(scope) + r"(/|$)")


@pytest.mark.parametrize("optimizer", sorted(OPTIMIZERS))
@pytest.mark.parametrize("family", ["gpt", "bert"])
@pytest.mark.parametrize("scope", scopes.STEP_SCOPES)
def test_scope_is_on_the_compiled_step(op_names, family, optimizer, scope):
    found = any(_under(scope).search(n) for n in op_names(family, optimizer))
    copies = scope in (scopes.REDUCE_PACK, scopes.REDUCE_UNPACK)
    assert found == (not (copies and optimizer in IN_PLACE))


@pytest.mark.parametrize("optimizer", sorted(OPTIMIZERS))
@pytest.mark.parametrize("family", ["gpt", "bert"])
def test_lm_head_scope_is_on_forward_and_backward(op_names, family,
                                                  optimizer):
    head = [n for n in op_names(family, optimizer)
            if _under(scopes.LM_HEAD).search(n)]
    assert any("jvp(" in n and "transpose(" not in n for n in head)
    assert any("transpose(jvp(" in n for n in head)


@pytest.mark.parametrize("optimizer", sorted(OPTIMIZERS))
def test_the_reduction_and_the_update_do_not_share_an_instruction(
        op_names, optimizer):
    """The two scopes of ``core_update`` are siblings: no ``op_name``
    lies under both, and neither lies under the model's."""
    for n in op_names("gpt", optimizer):
        both = [s for s in (scopes.REDUCE, scopes.UPDATE, scopes.LM_HEAD)
                if _under(s).search(n)]
        assert len(both) <= 1, n


def _pallas_calls(jaxpr, found):
    """Every ``pallas_call`` equation of a jaxpr, inner jaxprs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _pallas_calls(sub, found)
    return found


def _pallas_names(jaxpr, found):
    return found + [eqn.params["name"] for eqn in _pallas_calls(jaxpr, [])]


def _flash_loss(with_lse):
    def loss(q, k, v):
        if with_lse:
            o, lse = fa.flash_attention_with_lse(q, k, v, causal=True,
                                                 use_pallas=True)
            return o.sum() + lse.sum()
        return fa.flash_attention(q, k, v, causal=True,
                                  use_pallas=True).sum()
    return loss


@pytest.mark.parametrize("with_lse", [False, True], ids=["o", "o_and_lse"])
def test_flash_kernels_carry_their_names(with_lse):
    """Two Mosaic calls a layer: the forward, and one backward that gives
    dq, dk and dv under the dk/dv call's name. ``FLASH_DQ`` stays a
    constant (the benchmark's data file quotes the tuple) that no call
    carries."""
    q = jnp.ones((1, 128, 2, 64), jnp.float32)
    loss = _flash_loss(with_lse)
    forward = _pallas_names(jax.make_jaxpr(loss)(q, q, q).jaxpr, [])
    assert forward == [scopes.FLASH_FWD]
    both = _pallas_names(
        jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q).jaxpr, [])
    assert both == [scopes.FLASH_FWD, scopes.FLASH_DKV]
    assert set(both) < set(scopes.FLASH_KERNELS)
    assert set(scopes.FLASH_KERNELS) - set(both) == {scopes.FLASH_DQ}


def test_kda_kernels_carry_their_names():
    """The recurrence's kernels meet the contract the benchmark's readers
    find a layer's kernel by (PERF.md, the ``kda_ms`` row): the scope
    string is each call's prefix (the instruction's own name holds it,
    which the backward's ``op_name`` under ``transpose(`` does not) and
    the call is made under the scope; no name is an attention kernel's or
    a bucket kernel's, so ``flash_ms`` cannot count it."""
    from horovod_tpu.ops import linear_attention as la

    x = jnp.ones((1, 64, 2, 128), jnp.float32)
    args = (x, x, x, -x, x[..., 0])

    def loss(*a):
        return la.kda_attention(*a, use_pallas=True).sum()

    forward = _pallas_calls(jax.make_jaxpr(loss)(*args).jaxpr, [])
    assert [e.params["name"] for e in forward] == [scopes.KDA_FWD]
    both = _pallas_calls(jax.make_jaxpr(
        jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(*args).jaxpr, [])
    names = [e.params["name"] for e in both]
    assert names == [scopes.KDA_FWD, scopes.KDA_BWD] \
        == list(scopes.KDA_KERNELS)
    # made under the scope: a path component of the forward's op_name
    # (the differentiated calls' wrap it: ``transpose(jvp(hvd_kda))``)
    text = jax.jit(loss).lower(*args).as_text(debug_info=True)
    assert f"{scopes.KDA}/{scopes.KDA_FWD}" in text
    for name in names:
        assert name.startswith(scopes.KDA + "_")
        assert "hvd_flash" not in name
    assert not set(names) & set(scopes.FLASH_KERNELS + scopes.BUCKET_KERNELS)


def test_rope_kernels_carry_their_names():
    """The rotation's kernels meet the same contract (PERF.md, the
    ``kda_ms`` row): made under ``hvd_rope`` and named with it as their
    prefix, so ``rope_ms`` finds them by either, and no attention
    kernel's or bucket kernel's name, so ``flash_ms`` cannot count them."""
    from horovod_tpu.ops import rope as rope_lib

    x = jnp.ones((1, 64, 2, 64), jnp.bfloat16)

    def loss(x):
        return rope_lib.rotate(x, use_pallas=True).astype(jnp.float32).sum()

    forward = _pallas_names(jax.make_jaxpr(loss)(x).jaxpr, [])
    assert forward == [scopes.ROPE_FWD]
    names = _pallas_names(jax.make_jaxpr(jax.grad(loss))(x).jaxpr, [])
    assert names == [scopes.ROPE_FWD, scopes.ROPE_BWD] \
        == list(scopes.ROPE_KERNELS)
    # made under the scope: a path component of the compiled op_names
    # before the kernel's own (here, where nothing encloses it, the
    # differentiated calls' wrap it), through the pass's own ``jit``,
    # which the layers of a model share and XLA inlines
    def op_names(fn):
        with _compiled_here():
            text = jax.jit(fn).lower(x).compile().as_text()
        return re.findall(r'op_name="([^"]*)"', text)

    assert [name for name in op_names(loss) if re.search(
        rf"/{scopes.ROPE}/(jit\(\w+\)/)?{scopes.ROPE_FWD}/", name)]
    assert [name for name in op_names(jax.grad(loss)) if re.search(
        rf"/transpose\(jvp\({scopes.ROPE}\)\)/(jit\(\w+\)/)?"
        rf"{scopes.ROPE_BWD}/", name)]
    for name in names:
        assert name.startswith(scopes.ROPE + "_")
    assert not set(names) & set(scopes.FLASH_KERNELS + scopes.BUCKET_KERNELS
                                + scopes.KDA_KERNELS)


def test_the_benchmarks_readers_count_the_rope_kernels_as_the_rotation():
    """On a hand-made trace of the two calls as the chip names them, the
    benchmark's readers (not this PR's to edit) give ``rope_ms`` and
    ``mixer_proj_ms`` their time, the flash kernels none of it, and the
    cell's own partition lays them in ``fwd`` and ``bwd``."""
    from benchmark import hlo_counts, of_which, phase_reduce
    from benchmark.catalog import Catalog

    call = ('%{}.{} = bf16[8]{{0}} custom-call(%p.1), '
            'custom_call_target="tpu_custom_call"')
    under = "layer0/attn/hvd_mixer_proj/hvd_rope/"
    events = [[call.format(scopes.ROPE_FWD, 4), 0.0, 3e3, "",
               f"jit(step)/jvp(GPT)/{under}{scopes.ROPE_FWD}/pallas_call", 1],
              [call.format(scopes.FLASH_FWD, 2), 4e3, 9e3, "",
               f"jit(step)/jvp(GPT)/layer0/attn/{scopes.FLASH_FWD}"
               "/pallas_call", 1],
              [call.format(scopes.ROPE_BWD, 6), 14e3, 5e3, "",
               f"jit(step)/transpose(jvp(GPT))/{under}{scopes.ROPE_BWD}"
               "/pallas_call", 1]]
    trace = {"devices": {"/device:TPU:0": events}, "hlo": {}}
    got = phase_reduce.reduce_phases(trace, hlo_counts.load_names())
    assert got["seconds"]["fwd"] == pytest.approx(3e-6)
    assert got["seconds"]["bwd"] == pytest.approx(5e-6)
    assert got["seconds"]["flash_fwd"] == pytest.approx(9e-6)
    assert got["seconds"]["other_kernel"] == 0.0
    record = {"trace": {"steps": 1},
              "of_which_trace": of_which._without_loops(trace)}
    for metric in ("rope_ms", "mixer_proj_ms"):
        read = Catalog().module("layer_metrics", metric).read
        assert read(record) == pytest.approx(8e-3)


def test_short_conv_kernels_carry_their_names():
    """``conv_act``'s kernels meet the contract of PERF.md's
    ``short_conv_ms`` row: made under ``hvd_short_conv`` and named with it
    as their prefix (the backward's ``op_name`` loses the scope under
    ``transpose(``; the instruction's own name keeps it), no other
    kernel's name."""
    from horovod_tpu.ops import short_conv

    args = (jnp.ones((1, short_conv._ROWS, 128), jnp.bfloat16),
            jnp.ones((4, 128)), jnp.ones((128,)))

    def loss(*a):
        return short_conv.conv_act(*a, use_pallas=True).astype(
            jnp.float32).sum()

    assert _pallas_names(jax.make_jaxpr(loss)(*args).jaxpr, []) \
        == [scopes.SHORT_CONV_FWD]
    names = _pallas_names(jax.make_jaxpr(
        jax.grad(loss, argnums=(0, 1, 2)))(*args).jaxpr, [])
    assert names == list(scopes.SHORT_CONV_KERNELS)
    with _compiled_here():
        text = jax.jit(loss).lower(*args).compile().as_text()
    assert [name for name in re.findall(r'op_name="([^"]*)"', text)
            if re.search(rf"/{scopes.SHORT_CONV}/(jit\(\w+\)/)?"
                         rf"{scopes.SHORT_CONV_FWD}/", name)]
    for name in names:
        assert name.startswith(scopes.SHORT_CONV + "_")
    assert not set(names) & set(
        scopes.FLASH_KERNELS + scopes.BUCKET_KERNELS + scopes.KDA_KERNELS
        + scopes.ROPE_KERNELS + scopes.SWA_KERNELS + scopes.SSD_KERNELS)


def test_ssd_kernels_carry_their_names():
    """The state-space scan's kernels meet the same contract (PERF.md,
    the ``ssd_ms`` row): made under ``hvd_ssd`` and named with it as their
    prefix, no other kernel's name."""
    from horovod_tpu.ops import ssd

    x = jnp.ones((1, 256, 8, 64), jnp.bfloat16)
    heads = jnp.ones((8,), jnp.float32)
    shared = jnp.ones((1, 256, 1, 128), jnp.bfloat16)
    args = (x, x[..., 0], -heads, shared, shared, heads, heads)

    def loss(*a):
        return ssd.ssd_scan(*a, use_pallas=True).astype(jnp.float32).sum()

    forward = _pallas_names(jax.make_jaxpr(loss)(*args).jaxpr, [])
    assert forward == [scopes.SSD_FWD]
    names = _pallas_names(jax.make_jaxpr(
        jax.grad(loss, argnums=tuple(range(7))))(*args).jaxpr, [])
    assert names == [scopes.SSD_FWD, scopes.SSD_BWD] \
        == list(scopes.SSD_KERNELS)
    # made under the scope: a path component of the compiled op_names
    # before the kernel's own, through the call's own ``jit``, which the
    # layers of a model share and XLA inlines (as the rotation's)
    with _compiled_here():
        text = jax.jit(loss).lower(*args).compile().as_text()
    assert [name for name in re.findall(r'op_name="([^"]*)"', text)
            if re.search(rf"/{scopes.SSD}/(jit\(\w+\)/)?{scopes.SSD_FWD}/",
                         name)]
    for name in names:
        assert name.startswith(scopes.SSD + "_")
    assert not set(names) & set(scopes.FLASH_KERNELS + scopes.BUCKET_KERNELS
                                + scopes.KDA_KERNELS + scopes.ROPE_KERNELS)


def test_the_benchmarks_readers_count_the_ssd_kernels_as_the_scan():
    """On a hand-made trace of the calls as the chip names them (the
    backward's ``op_name`` loses the scope under ``transpose(``; its own
    instruction name keeps it), the benchmark's readers (not this PR's to
    edit) give ``ssd_ms`` all three calls' time and the flash kernels,
    the convolution and the projections none of it."""
    from benchmark import of_which
    from benchmark.catalog import Catalog

    call = ('%{}.{} = bf16[8]{{0}} custom-call(%p.1), '
            'custom_call_target="tpu_custom_call"')
    under = f"layer0/mixer/{scopes.SSD}/"
    events = [[call.format(scopes.SSD_FWD, 4), 0.0, 3e3, "",
               f"jit(step)/jvp(G)/{under}{scopes.SSD_FWD}/pallas_call", 1],
              [call.format(scopes.SSD_FWD, 5), 4e3, 3e3, "",
               "jit(step)/transpose(jvp(G))/jvp(G)/checkpoint/"
               f"rematted_computation/{under}{scopes.SSD_FWD}/pallas_call",
               1],
              [call.format(scopes.SSD_BWD, 6), 8e3, 7e3, "",
               "jit(step)/transpose(jvp(G))/jvp(G)/checkpoint/layer0/mixer/"
               f"transpose(jvp({scopes.SSD}))/{scopes.SSD_BWD}/pallas_call",
               1]]
    trace = {"devices": {"/device:TPU:0": events}, "hlo": {}}
    record = {"trace": {"steps": 1},
              "of_which_trace": of_which._without_loops(trace)}

    def read(metric):
        return Catalog().module("layer_metrics", metric).read(record)

    assert read("ssd_ms") == pytest.approx(13e-3)
    for metric in ("short_conv_ms", "mixer_proj_ms", "kda_ms"):
        assert not read(metric)


def test_the_flash_backward_call_has_three_outputs():
    q = jnp.ones((1, 128, 2, 64), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(_flash_loss(False), argnums=(0, 1, 2)))(
        q, q, q).jaxpr
    backward, = [eqn for eqn in _pallas_calls(jaxpr, [])
                 if eqn.params["name"] == scopes.FLASH_DKV]
    # dq, dk, dv as the kernels see them: (B, S, H*D), the input's dtype
    assert [(v.aval.shape, v.aval.dtype) for v in backward.outvars] \
        == [((1, 128, 128), jnp.bfloat16)] * 3


@pytest.mark.parametrize("name, call", [
    (scopes.SCALE, lambda x: pk.scale_buffer(x, 0.5, use_pallas=True)),
    (scopes.ADASUM_DOT_NORMS,
     lambda x: pk.adasum_dot_norms(x, x, use_pallas=True)),
    (scopes.ADASUM_COMBINE,
     lambda x: pk.adasum_combine(x, x, jnp.ones((3,)), use_pallas=True)),
    (scopes.INT8_QUANTIZE,
     lambda x: pk.quantize_int8(x, use_pallas=True)[:2]),
    (scopes.INT8_QUANTIZE_SR,
     lambda x: pk.quantize_int8_stochastic(
         x, jax.random.PRNGKey(0), use_pallas=True)[:2]),
    (scopes.INT8_DEQUANTIZE,
     lambda x: pk.dequantize_int8(
         *pk.quantize_int8(x, use_pallas=False), x.shape, use_pallas=True)),
])
def test_bucket_kernels_carry_their_names(name, call):
    x = jnp.ones((8192,), jnp.float32)
    assert _pallas_names(jax.make_jaxpr(call)(x).jaxpr, []) == [name]


def test_the_benchmarks_reader_gives_the_unused_name_zero_not_nothing():
    """A step of this program leaves events named for the forward and
    for the dk/dv call only. The benchmark's reader (not this PR's to
    edit) then reads ``flash_dq`` as 0.0 — a number: ``named`` is about
    the flash kernels as a family — and the other two sum to the flash
    time."""
    from benchmark import hlo_counts, phase_reduce

    names = hlo_counts.load_names()
    call = ('%{}.{} = bf16[8]{{0}} custom-call(%p.1), '
            'custom_call_target="tpu_custom_call"')
    events = [[call.format(scopes.FLASH_FWD, 2), 0.0, 4e3, "",
               f"jit(step)/jvp(GPT)/{scopes.FLASH_FWD}/pallas_call", 1],
              [call.format(scopes.FLASH_DKV, 3), 5e3, 7e3, "",
               f"jit(step)/transpose(jvp(GPT))/{scopes.FLASH_DKV}"
               "/pallas_call", 1]]
    got = phase_reduce.reduce_phases(
        {"devices": {"/device:TPU:0": events}}, names)
    assert got["named"]["flash"] is True
    flash = {part: got["seconds"][part]
             for _, part in names["flash_kernels"]}
    assert flash == {"flash_fwd": pytest.approx(4e-6), "flash_dq": 0.0,
                     "flash_dkv": pytest.approx(7e-6)}
    assert got["seconds"][names["flash_default"]] == 0.0


def _constants():
    return {k: v for k, v in vars(scopes).items()
            if k.isupper() and isinstance(v, str)}


ALL_NAMES = (scopes.STEP_SCOPES + scopes.LOOP_SCOPES + scopes.MOE_SCOPES
             + scopes.LINEAR_ATTN_SCOPES + scopes.SHORT_CONV_SCOPES
             + scopes.STATE_SPACE_SCOPES
             + scopes.BLOCK_SCOPES + scopes.BLOCK_DIFFUSION_SCOPES
             + scopes.FLASH_KERNELS
             + scopes.BUCKET_KERNELS + scopes.KDA_KERNELS
             + scopes.ROPE_KERNELS + scopes.SWA_KERNELS
             + scopes.SSD_KERNELS + scopes.SHORT_CONV_KERNELS)


def test_each_name_is_written_once():
    values = list(_constants().values())
    assert len(values) == len(set(values)) == 41
    assert set(ALL_NAMES) <= set(values)
    # tuples of their own: a scope of one model's step is not one every
    # family carries
    assert scopes.MOE_SCOPES == ("hvd_moe_route", "hvd_moe_experts",
                                 "hvd_moe_shared")
    assert scopes.LINEAR_ATTN_SCOPES == ("hvd_kda", "hvd_gdn")
    assert scopes.SHORT_CONV_SCOPES == ("hvd_short_conv",)
    assert scopes.STATE_SPACE_SCOPES == ("hvd_ssd",)
    assert scopes.BLOCK_DIFFUSION_SCOPES == ("hvd_bd_noise",)
    assert scopes.BLOCK_SCOPES == ("hvd_mixer_proj", "hvd_rope", "hvd_mlp",
                                   "hvd_norm", "hvd_embed", "hvd_loss")
    assert not set(scopes.MOE_SCOPES + scopes.LINEAR_ATTN_SCOPES
                   + scopes.SHORT_CONV_SCOPES + scopes.STATE_SPACE_SCOPES
                   + scopes.BLOCK_SCOPES) \
        & set(scopes.STEP_SCOPES + scopes.LOOP_SCOPES)


@pytest.fixture(scope="module")
def solar_op_names():
    """Every ``op_name`` of a tiny expert / linear-attention model's
    differentiated step, as lowered."""
    from horovod_tpu.models import solar_loss

    model = _model("solar")
    tokens = jnp.zeros((2, 33), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens[:, :-1])["params"]
    text = jax.jit(jax.grad(lambda p: solar_loss(model, p, tokens))).lower(
        params).as_text(debug_info=True)
    return set(re.findall(r'"(jit\([^"]*)"', text))


@pytest.mark.parametrize("scope", scopes.MOE_SCOPES + (scopes.KDA,))
def test_an_expert_linear_attention_models_scopes_are_on_its_step(
        solar_op_names, scope):
    """Forward and backward, and only under the layers that have them:
    KDA's in the linear layer, none of it in the softmax one."""
    under = [n for n in solar_op_names if _under(scope).search(n)]
    assert any("transpose(" not in n for n in under)
    assert any("transpose(" in n for n in under)
    if scope == scopes.KDA:
        assert all("layer1" in n for n in under)
    for n in under:     # siblings: no instruction under two of them
        assert sum(bool(_under(s).search(n)) for s in scopes.MOE_SCOPES
                   + scopes.LINEAR_ATTN_SCOPES + (scopes.LM_HEAD,)) == 1, n


@pytest.fixture(scope="module")
def lfm2_op_names():
    """Every ``op_name`` of a tiny gated-convolution / attention model's
    differentiated step, as lowered: layers c A c, the first dense."""
    from horovod_tpu.models import lfm2_loss

    model = _model("lfm2")
    tokens = jnp.zeros((2, 33), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens[:, :-1])["params"]
    text = jax.jit(jax.value_and_grad(
        lambda p: lfm2_loss(model, p, tokens))).lower(params).as_text(
            debug_info=True)
    return set(re.findall(r'"(jit\([^"]*)"', text))


@pytest.mark.parametrize("scope", scopes.SHORT_CONV_SCOPES
                         + scopes.MOE_SCOPES[:2] + (scopes.LM_HEAD,))
def test_a_gated_convolution_models_scopes_are_on_its_step(lfm2_op_names,
                                                           scope):
    """Forward and backward, and only under the layers that have them:
    the convolution's in the two convolution layers, the experts' in the
    two layers past the dense one, the tied head's once."""
    under = [n for n in lfm2_op_names if _under(scope).search(n)]
    assert any("transpose(" not in n for n in under)
    assert any("transpose(" in n for n in under)
    layers = {m for n in under for m in re.findall(r"layer\d", n)}
    assert layers == {scopes.SHORT_CONV: {"layer0", "layer2"},
                      scopes.MOE_ROUTE: {"layer1", "layer2"},
                      scopes.MOE_EXPERTS: {"layer1", "layer2"},
                      scopes.LM_HEAD: set()}[scope]
    for n in under:     # siblings: no instruction under two of them
        assert sum(bool(_under(s).search(n)) for s in scopes.MOE_SCOPES
                   + scopes.SHORT_CONV_SCOPES + (scopes.LM_HEAD,)) == 1, n
    # this model has no shared expert
    assert not any(_under(scopes.MOE_SHARED).search(n)
                   for n in lfm2_op_names)


@pytest.fixture(scope="module")
def laguna_op_names():
    """Every ``op_name`` of a tiny window-and-full model's differentiated
    step, as lowered: layers F W W, the first dense."""
    from horovod_tpu.models import LagunaLM, laguna_loss
    from horovod_tpu.ops.rope import Rotation

    model = LagunaLM(
        vocab_size=64, num_layers=3, hidden=32,
        layer_types=("full_attention", "sliding_attention",
                     "sliding_attention"),
        heads_per_layer=(2, 3, 3), num_kv_heads=1, head_dim=16, window=8,
        full_rotation=Rotation(base=100.0, width=8, factor=8.0,
                               original_length=16, beta_fast=4.0,
                               scale=1.2),
        mlp_layer_types=("dense", "sparse", "sparse"), mlp_dim=48,
        num_experts=8, held_experts=(2, 4), top_k=2, expert_dim=16,
        shared_dim=16)
    tokens = jnp.zeros((2, 33), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens[:, :-1])["params"]
    text = jax.jit(jax.value_and_grad(
        lambda p: laguna_loss(model, p, tokens))).lower(params).as_text(
            debug_info=True)
    return set(re.findall(r'"(jit\([^"]*)"', text))


@pytest.mark.parametrize("scope", scopes.BLOCK_SCOPES + scopes.MOE_SCOPES
                         + (scopes.LM_HEAD,))
def test_a_window_and_full_models_scopes_are_on_its_step(laguna_op_names,
                                                         scope):
    """The gate and the projections under ``hvd_mixer_proj`` with the
    rotation inside it, the dense layer under ``hvd_mlp``, the expert
    layers under the three of ``MOE_SCOPES``, the norms, the embedding and
    the loss under theirs: forward and backward, and only in the layers
    that have them, so that ``tools/block_parts.py`` prints this family's
    parts as it prints the others'."""
    under = [n for n in laguna_op_names if _under(scope).search(n)]
    assert any("transpose(" not in n for n in under)
    assert any("transpose(" in n for n in under)
    layers = {m for n in under for m in re.findall(r"layer\d", n)}
    every, sparse = {"layer0", "layer1", "layer2"}, {"layer1", "layer2"}
    assert layers == {
        scopes.MIXER_PROJ: every, scopes.ROPE: every, scopes.NORM: every,
        scopes.MLP: {"layer0"}, scopes.MOE_ROUTE: sparse,
        scopes.MOE_EXPERTS: sparse, scopes.MOE_SHARED: sparse,
        scopes.EMBED: set(), scopes.LOSS: set(),
        scopes.LM_HEAD: set()}[scope]
    if scope == scopes.ROPE:    # nested in the projections, and only there
        assert all(_under(scopes.MIXER_PROJ).search(n) for n in under)
    if scope == scopes.MIXER_PROJ:      # the gate's map is among them
        assert any("gate" in n for n in under)
    siblings = (scopes.MIXER_PROJ, scopes.MLP, scopes.NORM, scopes.EMBED,
                scopes.LOSS, scopes.LM_HEAD) + scopes.MOE_SCOPES
    if scope != scopes.ROPE:
        for n in under:     # no instruction under two of them
            assert sum(bool(_under(s).search(n)) for s in siblings) == 1, n


@pytest.fixture(scope="module")
def granite_op_names():
    """Every ``op_name`` of a tiny state-space / attention model's
    differentiated step, as lowered: layers m A m."""
    from horovod_tpu.models import GraniteHybridLM, granite_loss

    model = GraniteHybridLM(
        vocab_size=64, num_layers=3, hidden=32,
        layer_types=("mamba", "attention", "mamba"), num_heads=2,
        num_kv_heads=1, head_dim=16, mlp_dim=48, ssm_heads=4,
        ssm_head_dim=16, ssm_state=8, chunk=8)
    tokens = jnp.zeros((2, 33), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens[:, :-1])["params"]
    text = jax.jit(jax.value_and_grad(
        lambda p: granite_loss(model, p, tokens))).lower(params).as_text(
            debug_info=True)
    assert " while" not in text     # the scan is no loop
    return set(re.findall(r'"(jit\([^"]*)"', text))


@pytest.mark.parametrize("scope", scopes.STATE_SPACE_SCOPES
                         + scopes.SHORT_CONV_SCOPES
                         + tuple(s for s in scopes.BLOCK_SCOPES
                                 if s != scopes.ROPE) + (scopes.LM_HEAD,))
def test_a_state_space_models_scopes_are_on_its_step(granite_op_names,
                                                     scope):
    """The scan under ``hvd_ssd`` and the convolution with its bias and
    SiLU under ``hvd_short_conv`` in the two state-space layers and not
    in the attention layer, the projections, the gate and the gated norm
    under ``hvd_mixer_proj`` in all three, the feed-forward, the norms,
    the embedding, the loss and the tied head under theirs: forward and
    backward, no instruction under two of them, and no rotation
    anywhere."""
    under = [n for n in granite_op_names if _under(scope).search(n)]
    assert any("transpose(" not in n for n in under)
    assert any("transpose(" in n for n in under)
    layers = {m for n in under for m in re.findall(r"layer\d", n)}
    every, ssm = {"layer0", "layer1", "layer2"}, {"layer0", "layer2"}
    assert layers == {
        scopes.SSD: ssm, scopes.SHORT_CONV: ssm, scopes.MIXER_PROJ: every,
        scopes.MLP: every, scopes.NORM: every, scopes.EMBED: set(),
        scopes.LOSS: set(), scopes.LM_HEAD: set()}[scope]
    if scope == scopes.SSD:     # the step size and the decays are inside
        assert any("softplus" in n for n in under)
        assert any("exp" in n for n in under)
    siblings = scopes.STATE_SPACE_SCOPES + scopes.SHORT_CONV_SCOPES + (
        scopes.MIXER_PROJ, scopes.MLP, scopes.NORM, scopes.EMBED,
        scopes.LOSS, scopes.LM_HEAD)
    for n in under:
        assert sum(bool(_under(s).search(n)) for s in siblings) == 1, n
    assert not any(_under(scopes.ROPE).search(n) for n in granite_op_names)


@pytest.fixture(scope="module")
def olmo_op_names():
    """Every ``op_name`` of a tiny scalar-gated delta-rule / attention
    model's differentiated step, as lowered: layers L F L."""
    from horovod_tpu.models import OlmoHybridLM, olmo_hybrid_loss

    model = OlmoHybridLM(
        vocab_size=64, num_layers=3, hidden=32,
        layer_types=("linear_attention", "full_attention",
                     "linear_attention"), num_heads=2, head_dim=16,
        mlp_dim=48, linear_heads=2, linear_key_dim=8, linear_value_dim=16,
        chunk=8)
    tokens = jnp.zeros((2, 33), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens[:, :-1])["params"]
    text = jax.jit(jax.value_and_grad(
        lambda p: olmo_hybrid_loss(model, p, tokens))).lower(params).as_text(
            debug_info=True)
    return set(re.findall(r'"(jit\([^"]*)"', text))


@pytest.mark.parametrize("scope", (scopes.GDN,) + scopes.SHORT_CONV_SCOPES
                         + tuple(s for s in scopes.BLOCK_SCOPES
                                 if s != scopes.ROPE) + (scopes.LM_HEAD,))
def test_a_scalar_gated_delta_rule_models_scopes_are_on_its_step(
        olmo_op_names, scope):
    """The recurrence under ``hvd_gdn`` and the convolution with its SiLU
    under ``hvd_short_conv`` in the two linear layers and not in the
    attention layer; the projections, the norms, the decays and the gate
    under ``hvd_mixer_proj`` in all three; the feed-forward, the
    post-branch norms, the embedding, the loss and the head under theirs:
    forward and backward, no instruction under two of them, no rotation
    and nothing of KDA's anywhere."""
    under = [n for n in olmo_op_names if _under(scope).search(n)]
    assert any("transpose(" not in n for n in under)
    assert any("transpose(" in n for n in under)
    layers = {m for n in under for m in re.findall(r"layer\d", n)}
    every, linear = {"layer0", "layer1", "layer2"}, {"layer0", "layer2"}
    assert layers == {
        scopes.GDN: linear, scopes.SHORT_CONV: linear,
        scopes.MIXER_PROJ: every, scopes.MLP: every, scopes.NORM: every,
        scopes.EMBED: set(), scopes.LOSS: set(),
        scopes.LM_HEAD: set()}[scope]
    siblings = (scopes.GDN,) + scopes.SHORT_CONV_SCOPES + (
        scopes.MIXER_PROJ, scopes.MLP, scopes.NORM, scopes.EMBED,
        scopes.LOSS, scopes.LM_HEAD)
    for n in under:
        assert sum(bool(_under(s).search(n)) for s in siblings) == 1, n
    assert not any(_under(scopes.ROPE).search(n) for n in olmo_op_names)


def test_every_event_of_the_recurrence_carries_its_scope_and_not_kdas(
        olmo_op_names):
    """What ``gdn_ms`` reads and what ``kda_ms`` must not: the solve, the
    scan over chunks and its hand-written reverse scan lie under
    ``hvd_gdn`` wherever they are in the step, and no ``op_name`` of the
    step holds ``kda_ms``'s marker, which is no part of ``hvd_gdn``."""
    from benchmark.layer_metrics import gdn_ms, kda_ms

    assert gdn_ms.MARKER == scopes.GDN and kda_ms.MARKER == scopes.KDA
    assert kda_ms.MARKER not in gdn_ms.MARKER
    assert not any(kda_ms.MARKER in n for n in olmo_op_names)
    recurrence = [n for n in olmo_op_names
                  if re.search(r"triangular_solve|/while|cumsum", n)]
    assert len(recurrence) >= 3
    assert all(_under(scopes.GDN).search(n) for n in recurrence)
    # a step of three events: the recurrence's is gdn_ms's alone
    events = [["%fusion.1", 0.0, 2e6, "",
               "jit(step)/layer0/mixer/hvd_gdn/triangular_solve", 1],
              ["%fusion.2", 2e6, 1e6, "",
               "jit(step)/layer0/mixer/hvd_mixer_proj/dot_general", 1]]
    record = {"trace": {"steps": 1}, "cell": {},
              "of_which_trace": {"devices": {"/device:TPU:0": events},
                                 "hlo": {}}}
    assert gdn_ms.read(dict(record)) == pytest.approx(2.0)
    assert kda_ms.read(dict(record)) is None


@pytest.fixture(scope="module")
def loop_op_names():
    """Every ``op_name`` of a tiny looped model's differentiated step, as
    compiled (the lowered text names a scan's body apart from its call)."""
    from horovod_tpu.models.looplm import looplm_loss

    model = _model("ouro")
    tokens = jnp.zeros((2, 33), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens[:, :-1])["params"]
    with _compiled_here():
        text = jax.jit(jax.value_and_grad(
            lambda p: looplm_loss(model, p, tokens, 0.05))).lower(
                params).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


# -- the block's parts, one vocabulary for the five families ------------------

# What each family's step has of ``BLOCK_SCOPES``: no rotation where the
# positions are learned (bert) or absent (solar), no dense feed-forward
# where every layer is an expert layer (solar: its shared expert keeps
# ``hvd_moe_shared``), the loss where it is the program's own and not the
# caller's (``looplm.head_losses``).
BLOCK_PARTS = {
    "gpt": (scopes.MIXER_PROJ, scopes.ROPE, scopes.MLP, scopes.NORM,
            scopes.EMBED),
    "bert": (scopes.MIXER_PROJ, scopes.MLP, scopes.NORM, scopes.EMBED),
    "ouro": scopes.BLOCK_SCOPES,
    "solar": (scopes.MIXER_PROJ, scopes.NORM, scopes.EMBED, scopes.LOSS),
    "lfm2": scopes.BLOCK_SCOPES,
}


@pytest.fixture(scope="module")
def block_op_names(request):
    """``block_op_names(family)``: the family's fixture above."""
    def get(family):
        if family in ("gpt", "bert"):
            return request.getfixturevalue("op_names")(family, "none")
        return request.getfixturevalue(
            {"ouro": "loop", "solar": "solar", "lfm2": "lfm2"}[family]
            + "_op_names")
    return get


@pytest.mark.parametrize("scope", scopes.BLOCK_SCOPES)
@pytest.mark.parametrize("family", sorted(BLOCK_PARTS))
def test_a_blocks_part_is_on_the_forward_and_the_backward(
        block_op_names, family, scope):
    """Each part a family has is named on its forward and (through the
    name stack JAX keeps for the transpose) on its backward; a part it
    has not is on nothing."""
    under = [n for n in block_op_names(family) if _under(scope).search(n)]
    has = scope in BLOCK_PARTS[family]
    assert any("transpose(" not in n for n in under) == has
    assert any("transpose(" in n for n in under) == has
    if scope == scopes.LOSS:    # beside the head's matmul, not inside it
        assert not any(_under(scopes.LM_HEAD).search(n) for n in under)


@pytest.mark.parametrize("family", sorted(BLOCK_PARTS))
def test_the_blocks_parts_are_siblings_but_the_rope(block_op_names, family):
    """No instruction lies under two parts, under a part and a kernel's
    scope, or under the dense feed-forward and an expert layer's scope;
    the rotation alone is nested, and only in the mixer's projections."""
    flat = tuple(s for s in scopes.BLOCK_SCOPES if s != scopes.ROPE) \
        + scopes.LINEAR_ATTN_SCOPES + scopes.SHORT_CONV_SCOPES \
        + scopes.MOE_SCOPES + (scopes.LM_HEAD,)
    for n in block_op_names(family):
        assert sum(bool(_under(s).search(n)) for s in flat) <= 1, n
        if _under(scopes.ROPE).search(n):
            assert re.search(f"(^|/){scopes.MIXER_PROJ}/{scopes.ROPE}(/|$)",
                             n), n


# sha256 over "path shape dtype" of every leaf, read from the commit
# before the block's parts were named: a ``named_scope`` is no module, so
# checkpoints, the benchmark's references and its ``correct`` see the
# same trees.
PARAMETER_TREES = {
    "gpt": (27, "a7f6131fef453c4b252fc4037da6a1f6"
                "b00ef35d079c3a2f53b3b54006f28fa9"),
    "bert": (28, "e41ed3f52d352426463a426558881ad3"
                 "77e8863f59f24e5f9896b1f6bfd83dc7"),
    "ouro": (27, "f20b9a5539ea02c166ba4fd695aad377"
                 "09d91d8b27fbfb23fca67015cf7fbbd5"),
    "solar": (41, "3d4fa473e8f9466514122007176114ea"
                  "97502b02123cc3b863565a8d468c7df1"),
    "lfm2": (33, "caaab0bbdd628848f8450920f0347935"
                 "ceb1781b832c174b008a1ae5be7ee606"),
}


def _tree_digest(params):
    leaves = jax.tree_util.tree_leaves_with_path(params)
    lines = [f"{jax.tree_util.keystr(path)} {leaf.shape} {leaf.dtype}"
             for path, leaf in leaves]
    return len(lines), hashlib.sha256("\n".join(lines).encode()).hexdigest()


@pytest.mark.parametrize("family", sorted(PARAMETER_TREES))
def test_naming_the_parts_left_the_parameter_trees_alone(family):
    model = _model(family)
    tokens = jnp.zeros((2, 16), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens))["params"]
    assert _tree_digest(params) == PARAMETER_TREES[family]


def test_the_benchmarks_data_file_quotes_the_same_names():
    with open(os.path.join(ROOT, "benchmark", "phase_names.json")) as f:
        names = json.load(f)
    assert [m for m, _ in names["flash_kernels"]] == list(scopes.FLASH_KERNELS)
    assert names["not_flash_kernels"] == list(scopes.BUCKET_KERNELS)
    markers = [m for m, _ in names["dense_markers"]]
    assert markers[:5] == [scopes.REDUCE_PACK, scopes.REDUCE_UNPACK,
                           scopes.REDUCE, scopes.LM_HEAD, scopes.UPDATE]
    assert sorted(names["program_scopes"]) == sorted(
        [scopes.REDUCE, scopes.LM_HEAD, scopes.UPDATE])


def test_the_docs_list_the_same_names():
    with open(os.path.join(ROOT, "docs", "timeline.md")) as f:
        docs = f.read()
    listed = set(re.findall(r"`(hvd_[a-z0-9_/]+)`", docs))
    want = set(ALL_NAMES)
    assert want <= listed
    assert {n for n in listed if not n.startswith("hvd_tpu")} <= want
