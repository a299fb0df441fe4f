"""The loss-and-gradient program of every standing configuration at its
cell's own sizes, held to the one it was before ``models/lfm2.py``
(``RotaryGQA``: no rotation, a scale; ``Lfm2Layer``: a residual scale) and
``models/looplm.py`` (``head_losses``: a logit scale) took Granite's data
(PR 47): a sha256 of the jaxpr of ``value_and_grad(family.loss)``, traced
abstractly (``eval_shape`` parameters, no buffer of the model's size is
made) with the TPU's kernel paths chosen. The hashes are the parent
commit's (``ecd8c82``), read from its own checkout by the same lines; the
defaults of the shared classes trace to the same equations, so the
compiled steps of the standing cells are the parent's.

A PR that means to change a family's program replaces that family's hash
here and says so; one that does not and sees this fail has changed a
standing cell's step."""

import hashlib
import re

import jax
import jax.numpy as jnp
import pytest

from benchmark.catalog import Catalog
from benchmark.stream import token_stream
from horovod_tpu.ops import pallas_kernels

CAT = Catalog()
# cell: sha256 of its configuration's jaxpr at the parent commit, and the
# text's length
PARENTS = {
    "gpt2s-s512": (
        "451520a766e9be9b0b3c0702b3d7572aabb5783e9ac9b0e8547aeed357ea874b",
        790514),
    "bert-large-s512": (
        "d1e30f6de799654c172a68be1e7ba9158541380fc78b34eb9a3f050f612cd279",
        986369),
    "ouro-2.6b-l8-s2048": (
        "694ce45df1e056e87fbe1750c4350e3965dcf231fb70bb0c3c61dadd00d36f74",
        637979),
    "solar-open2-l4-e8-s4096": (
        "6d844acdfef45a555af92076c54400686b30b0463cd5e49b5a14ec531506635c",
        2129038),
    "lfm2-8b-a1b-l8-e8-s8192": (
        "723c511e48174564c8833aa5d656cb97b0cd64e892dd5003fac0c27e80a09e2e",
        760043),
    "sdar-30b-a3b-l6-e16-s4096": (
        "dfeab0224c31e0eb4b09f16f70b9bdd2e1d16a60e4ebb0f9ce95e3fc2bbe7305",
        824149),
    "laguna-s2.1-l5-e8-s8192": (
        "25435e1026bdfad3d1d999f80e53223495f27a882ffb61421371bee717cea2f3",
        923657),
}


def _program(cell_name):
    cell = CAT.cell(cell_name)
    config = CAT.config(cell["config"])
    traffic = CAT.traffic(cell["traffic"])
    family = CAT.module("families", config["family"])
    model = family.build(config)
    batch = next(token_stream(1, traffic, config["vocab_size"]))
    shapes = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
              for k, v in batch.items()}
    params = jax.eval_shape(
        lambda key: model.init(
            key, jnp.zeros((1, traffic["seq_len"]), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    text = str(jax.make_jaxpr(jax.value_and_grad(
        lambda p, b: family.loss(model, p, b)))(params, shapes))
    return re.sub(r"0x[0-9a-f]+", "0x", text)     # a function's address


@pytest.mark.parametrize("cell", sorted(PARENTS))
def test_a_standing_cells_program_is_the_parents(cell, monkeypatch):
    monkeypatch.setattr(pallas_kernels, "_on_tpu", lambda: True)
    text = _program(cell)
    assert (hashlib.sha256(text.encode()).hexdigest(), len(text)) \
        == PARENTS[cell]


def test_every_configuration_before_this_one_is_held():
    configs = [c["name"] for c in CAT.index["configs"]]
    held = {CAT.cell(cell)["config"] for cell in PARENTS}
    assert held == set(configs[:configs.index("granite-4.0-h-micro-l10")])
