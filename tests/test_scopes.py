"""The names the training step gives its device work
(``horovod_tpu/common/scopes.py``): each is on the instructions it was
written for, and the benchmark's data file and the docs quote the same
strings."""

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
    if family == "gpt":
        from horovod_tpu.models.gpt import gpt_tiny

        return gpt_tiny(vocab_size=128)
    from horovod_tpu.models.bert import bert_tiny

    return bert_tiny(vocab_size=128)


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


def _pallas_names(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn.params["name"])
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _pallas_names(sub, found)
    return found


def test_flash_kernels_carry_their_names():
    q = jnp.ones((1, 128, 2, 64), jnp.float32)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True,
                                  use_pallas=True).sum()

    forward = _pallas_names(jax.make_jaxpr(loss)(q, q, q).jaxpr, [])
    assert forward == [scopes.FLASH_FWD]
    both = _pallas_names(
        jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q).jaxpr, [])
    assert sorted(both) == sorted(scopes.FLASH_KERNELS)


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


def _constants():
    return {k: v for k, v in vars(scopes).items()
            if k.isupper() and isinstance(v, str)}


def test_each_name_is_written_once():
    values = list(_constants().values())
    assert len(values) == len(set(values)) == 17
    assert set(scopes.STEP_SCOPES + scopes.LOOP_SCOPES + scopes.FLASH_KERNELS
               + scopes.BUCKET_KERNELS) <= set(values)


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
    want = set(scopes.STEP_SCOPES + scopes.LOOP_SCOPES + scopes.FLASH_KERNELS
               + scopes.BUCKET_KERNELS)
    assert want <= listed
    assert {n for n in listed if not n.startswith("hvd_tpu")} <= want
