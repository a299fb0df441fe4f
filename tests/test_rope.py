"""The rotary embedding on packed rows (``horovod_tpu/ops/rope.py``,
``models/gpt.py`` ``rope``) against the formula it replaced, kept here as
the plain reference: forward and gradient, every width that packs and two
that do not, the Pallas kernels' bodies in interpret mode against their
jnp twin, and the counter that says which layout a traced call took."""

import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.common import metrics as metrics_lib
from horovod_tpu.common import scopes
from horovod_tpu.models.gpt import rope
from horovod_tpu.ops import rope as rope_lib

S = 32


def reference(x, positions=None, base=10000.0):
    """``rope`` as it was before it moved to packed rows."""
    b, s, h, d = x.shape
    if positions is None:
        positions = jnp.arange(s)[None, :]
    positions = positions.astype(jnp.float32)
    half = d // 2
    freqs = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[:, :, None] * freqs[None, None, :]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate([x1 * cos - x2 * sin,
                               x1 * sin + x2 * cos], axis=-1)
    return rotated.astype(x.dtype)


def _packs(h, d):
    return d % 128 == 0 or (128 % d == 0 and h % (128 // d) == 0)


def _operands(h, d, dtype, positions, seed=0):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed + 131 * h + d))
    x = jax.random.normal(kx, (2, S, h, d), dtype)
    w = jax.random.normal(kw, x.shape, dtype)
    pos = None
    if positions:       # a row of its own for each batch row, not sorted
        pos = jnp.stack([jnp.arange(S) + 5, jnp.arange(S)[::-1] * 3])
    return x, w, pos


def _ulp(dtype):
    return float(jnp.finfo(dtype).eps)


def _scale(x):
    """|x| + |its partner across the halves|, element by element: what
    each element's one sum of two products cannot exceed."""
    a = np.abs(np.asarray(x, np.float32))
    half = a.shape[-1] // 2
    return a + np.concatenate([a[..., half:], a[..., :half]], axis=-1)


def _close(got, want, operands, ulps):
    """Within ``ulps`` units in the last place of the sum's bound: the
    products and the sum are the reference's, so all that may differ is
    whether a compiler contracts a product and the sum into one rounding."""
    assert got.dtype == want.dtype and got.shape == want.shape
    gap = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))
    bound = ulps * _ulp(got.dtype) * _scale(operands).reshape(gap.shape)
    assert (gap <= bound).all(), gap.max()


def _count(layout, d):
    samples = metrics_lib.snapshot()["hvd_tpu_rope_paths"]["samples"]
    return sum(s["value"] for s in samples
               if s["labels"].get("layout") == layout
               and s["labels"].get("head_dim") == str(d))


def _grad(fn, x, w, pos):
    return jax.grad(lambda x: (fn(x, pos).astype(jnp.float32)
                               * w.astype(jnp.float32)).sum())(x)


@pytest.mark.parametrize("positions", [False, True],
                         ids=["arange", "positions"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("h", [2, 8, 12])
@pytest.mark.parametrize("d", [16, 32, 64, 128])
def test_the_rotation_is_the_formula_it_replaced(d, h, dtype, positions):
    """Forward to one unit in the last place, the gradient to two (the
    reference rounds each of its two terms to the input's dtype before it
    adds them; the packed pass adds in fp32 and rounds once), whichever
    layout the widths give."""
    x, w, pos = _operands(h, d, dtype, positions)
    layout = "packed" if _packs(h, d) else "per_head"
    before = _count(layout, d)
    _close(rope(x, pos), reference(x, pos), x, 1)
    assert _count(layout, d) == before + 1
    _close(_grad(rope, x, w, pos), _grad(reference, x, w, pos), w, 2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("h, d", [(4, 96), (3, 64), (1, 32), (12, 16)])
def test_widths_that_do_not_pack_keep_the_formula(h, d, dtype):
    """A width no 128-lane tile holds whole, or a count of narrow heads
    that leaves a tile half full: a head at a time, bit for bit."""
    x, w, pos = _operands(h, d, dtype, True)
    before = _count("per_head", d), _count("packed", d)
    assert (rope(x, pos) == reference(x, pos)).all()
    assert (rope(x, base=1e6) == reference(x, base=1e6)).all()
    assert (_grad(rope, x, w, pos) == _grad(reference, x, w, pos)).all()
    assert _count("per_head", d) == before[0] + 3
    assert _count("packed", d) == before[1]


def _pallas_names(fn, *args):
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn.params["name"])
            for value in eqn.params.values():
                for sub in value if isinstance(value, (list, tuple)) \
                        else [value]:
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("positions", [False, True],
                         ids=["arange", "positions"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("h, d", [(8, 16), (4, 32), (2, 64), (12, 64),
                                  (3, 128), (2, 256)])
def test_the_kernels_are_their_twin(h, d, dtype, positions):
    """The kernels' bodies in interpret mode against the jnp pass, forward
    and backward, and against the reference: ``pltpu.roll`` inside a tile
    turns the way ``jnp.roll`` does."""
    x, w, pos = _operands(h, d, dtype, positions)
    def kernel(x, _):
        return rope_lib.rotate(x, pos, use_pallas=True)

    def twin(x, _):
        return rope_lib.rotate(x, pos, use_pallas=False)

    assert _pallas_names(kernel, x, None) == [scopes.ROPE_FWD]
    assert _pallas_names(twin, x, None) == []
    _close(kernel(x, None), twin(x, None), x, 1)
    _close(kernel(x, None), reference(x, pos), x, 1)
    _close(_grad(kernel, x, w, None), _grad(twin, x, w, None), w, 1)
    assert _pallas_names(lambda x: _grad(kernel, x, w, None), x) \
        == [scopes.ROPE_FWD, scopes.ROPE_BWD]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("h, d", [(2, 64), (32, 64), (2, 128), (4, 96)])
def test_a_caller_may_ask_for_a_head_at_a_time(h, d, dtype):
    """``rotate_heads`` is the formula whatever the widths (the
    gated-convolution model's attention asks for it: its rotation follows
    a per-head norm on (B, S, H, 64)), bit for bit, counted ``per_head``."""
    x, w, pos = _operands(h, d, dtype, True)
    before = _count("per_head", d), _count("packed", d)
    assert (rope_lib.rotate_heads(x, pos, 1e6)
            == reference(x, pos, 1e6)).all()
    assert (rope_lib.rotate_heads(x) == reference(x)).all()
    assert (_grad(rope_lib.rotate_heads, x, w, pos)
            == _grad(reference, x, w, pos)).all()
    assert _count("per_head", d) == before[0] + 3
    assert _count("packed", d) == before[1]


def test_the_backward_keeps_the_positions_and_nothing_else():
    """No residual but the positions: the tables are made again from them
    (XLA shares them between the layers of a step)."""
    x, _, pos = _operands(2, 64, jnp.bfloat16, True)
    _, vjp = jax.vjp(lambda x: rope_lib.rotate(x, pos), x)
    kept = [leaf for leaf in jax.tree.leaves(vjp) if hasattr(leaf, "shape")]
    assert [(leaf.shape, leaf.dtype) for leaf in kept] \
        == [(pos.shape, jnp.float32)]


@pytest.mark.parametrize("s, width, dtype, rows", [
    (512, 768, jnp.bfloat16, 512),      # gpt2-small: a batch row a block
    (4096, 768, jnp.bfloat16, 512),
    (2048, 2048, jnp.bfloat16, 256),    # the looped cell: 1 MiB a block
    (8192, 512, jnp.bfloat16, 512),     # eight K/V heads of 64
    (2048, 2048, jnp.float32, 128),
    (24, 128, jnp.float32, 24),
    (24, 128, jnp.bfloat16, None),      # no block of whole bf16 tiles
    (1, 768, jnp.bfloat16, None),       # a decode step's one row
    (7, 768, jnp.float32, None),
])
def test_a_block_is_whole_sublane_tiles_that_divide_the_sequence(
        s, width, dtype, rows):
    assert rope_lib._block_rows(s, width, jnp.dtype(dtype)) == rows


@pytest.mark.parametrize("on_tpu", [False, True], ids=["cpu", "tpu"])
def test_rows_no_block_divides_run_no_kernel(on_tpu, monkeypatch):
    """A decode step's single row (any S no block of whole sublane tiles
    divides): forced onto the kernels off a TPU it runs their twin; on a
    TPU, where the twin is XLA's slices and pads again, the formula a head
    at a time, bit for bit."""
    from horovod_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "_on_tpu", lambda: on_tpu)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 1, 2, 64), jnp.bfloat16)
    given = jnp.array([[7], [300]])
    force = None if on_tpu else True
    before = _count("per_head", 64)
    assert _pallas_names(
        lambda x: rope_lib.rotate(x, given, use_pallas=force), x) == []
    assert _count("per_head", 64) == before + on_tpu
    got = rope_lib.rotate(x, given, use_pallas=force)
    _close(got, reference(x, given), x, 1)
    assert not on_tpu or (got == reference(x, given)).all()


def test_a_models_step_counts_its_layout_and_says_it_once(caplog):
    """A tiny GPT's differentiated step counts ``packed`` once a ``rope``
    call traced (q and k of each layer) and says so once a shape; heads of
    96 count ``per_head``."""
    import optax

    from horovod_tpu.models import GPT

    def step(model, tokens):
        params = model.init(jax.random.PRNGKey(0), tokens)["params"]

        def loss(p):
            logits = model.apply({"params": p}, tokens)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, tokens).mean()

        return jax.jit(jax.value_and_grad(loss)).lower(params)

    tokens = jnp.zeros((2, 16), jnp.int32)
    rope_lib._say_path.cache_clear()
    before = {(layout, d): _count(layout, d)
              for layout in ("packed", "per_head") for d in (64, 96)}
    with caplog.at_level(logging.INFO, logger="horovod_tpu"):
        step(GPT(num_layers=2, hidden=128, num_heads=2, mlp_dim=64,
                 vocab_size=64), tokens)
    # init traces the forward, the step traces it again: q and k a layer
    assert _count("packed", 64) == before["packed", 64] + 2 * 2 * 2
    assert _count("per_head", 64) == before["per_head", 64]
    said = [r.getMessage() for r in caplog.records
            if r.getMessage().startswith("rope:")]
    assert len(said) == 1 and "packed (B, S, 128) rows" in said[0] \
        and "(2, 16, 2, 64)" in said[0], said
    step(GPT(num_layers=1, hidden=192, num_heads=2, mlp_dim=64,
             vocab_size=64), tokens)
    assert _count("per_head", 96) == before["per_head", 96] + 2 * 2
    assert _count("packed", 96) == before["packed", 96]


# -- one description of a rotation --------------------------------------------
#
# ``Rotation``: the rotary width (the first ``width`` channels of a head,
# half-split pairs inside them, the rest pass through), the inverse
# frequencies (a base's, or YaRN's blend) and a scale on cos and sin. The
# plain one (``base=`` alone) is every test above; here the others, each
# and together, packed against a head at a time against the formulas
# written out below in numpy.

YARN = dict(factor=8.0, original_length=16, beta_fast=4.0, beta_slow=1.0)
ROTATIONS = {
    "partial": rope_lib.Rotation(base=10000.0, width=None),     # set by d
    "yarn": rope_lib.Rotation(base=100.0, scale=1.25, **YARN),
    "partial_yarn": rope_lib.Rotation(base=100.0, scale=1.25, **YARN),
    "scaled": rope_lib.Rotation(base=1e4, scale=0.5),
}


def _rotation(which, d):
    rotation = ROTATIONS[which]
    if which.startswith("partial"):
        rotation = rope_lib.Rotation(**{**rotation.__dict__, "width": d // 2})
    return rotation


def _inv_freq(rotation, rot):
    """The formulas of the issue, in float64."""
    i = np.arange(rot // 2, dtype=np.float64)
    plain = rotation.base ** (-2.0 * i / rot)
    if rotation.factor == 1.0:
        return plain

    def corr(turns):
        return rot * np.log(rotation.original_length / (2 * np.pi * turns)) \
            / (2 * np.log(rotation.base))

    low = max(np.floor(corr(rotation.beta_fast)), 0)
    high = min(np.ceil(corr(rotation.beta_slow)), rot - 1)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return plain * (1 - ramp) + plain / rotation.factor * ramp


def described(x, positions, rotation):
    """The rotation a ``Rotation`` describes, a head at a time in fp32."""
    b, s, h, d = x.shape
    rot = d if rotation.width is None else rotation.width
    half = rot // 2
    if positions is None:
        positions = jnp.arange(s)[None, :]
    angles = positions.astype(jnp.float32)[:, :, None] \
        * jnp.asarray(_inv_freq(rotation, rot), jnp.float32)
    cos = (jnp.cos(angles) * rotation.scale)[:, :, None, :]
    sin = (jnp.sin(angles) * rotation.scale)[:, :, None, :]
    x = x.astype(jnp.float32)
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                            x[..., rot:]], -1)


def _near(got, want, dtype, ulps=4):
    assert got.shape == want.shape
    gap = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))
    bound = ulps * _ulp(dtype) * (np.abs(np.asarray(want, np.float32)) + 1.0)
    assert (gap <= bound).all(), gap.max()


@pytest.mark.parametrize("positions", [False, True],
                         ids=["arange", "positions"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("h, d", [(2, 128), (9, 128), (4, 64), (2, 32),
                                  (3, 64)],
                         ids=["d128", "h9_d128", "packed_d64", "packed_d32",
                              "perhead_h3_d64"])
@pytest.mark.parametrize("which", sorted(ROTATIONS))
def test_a_described_rotation_is_its_formulas(which, h, d, dtype, positions):
    """Partial and YaRN each and together, and a bare scale: the packed
    pass (or a head at a time where the widths do not pack), the kernels'
    bodies in interpret mode and ``rotate_heads`` against the formulas,
    values and gradients."""
    rotation = _rotation(which, d)
    x, w, pos = _operands(h, d, dtype, positions)
    want = described(x, pos, rotation)
    want_grad = jax.grad(lambda x: (described(x, pos, rotation)
                                    * w.astype(jnp.float32)).sum())(x)
    forms = {
        "rotate": lambda x, p: rope_lib.rotate(x, p, rotation=rotation),
        "kernels": lambda x, p: rope_lib.rotate(x, p, rotation=rotation,
                                                use_pallas=True),
        "heads": lambda x, p: rope_lib.rotate_heads(x, p, rotation=rotation),
    }
    layout = "packed" if _packs(h, d) else "per_head"
    for name, form in forms.items():
        got = form(x, pos)
        assert got.dtype == x.dtype
        _near(got, want, dtype)
        _near(_grad(form, x, w, pos), want_grad, dtype)
    if _packs(h, d):
        assert _pallas_names(lambda x: _grad(forms["kernels"], x, w, pos),
                             x) == [scopes.ROPE_FWD, scopes.ROPE_BWD]
    samples = metrics_lib.snapshot()["hvd_tpu_rope_paths"]["samples"]
    assert any(s["labels"] == {"head_dim": str(d), "layout": layout,
                               "rotation": rotation.name} and s["value"] > 0
               for s in samples)
    if which.startswith("partial"):
        # the channels past the rotary width pass through to the bit
        assert (forms["rotate"](x, pos)[..., d // 2:] == x[..., d // 2:]).all()
        assert (forms["kernels"](x, pos)[..., d // 2:]
                == x[..., d // 2:]).all()


@pytest.mark.parametrize("form", ["rotate", "kernels", "heads"])
@pytest.mark.parametrize("h, d", [(2, 128), (4, 64), (3, 64)])
def test_the_plain_rotation_is_unchanged_to_the_bit(h, d, form):
    """``base=`` is the short way to say ``Rotation(base)``: the same
    values and gradients bit for bit, and the formula's as before."""
    x, w, pos = _operands(h, d, jnp.bfloat16, True)
    rotate = {"rotate": rope_lib.rotate,
              "kernels": functools.partial(rope_lib.rotate, use_pallas=True),
              "heads": rope_lib.rotate_heads}[form]

    def short(x, p):
        return rotate(x, p, base=1e6)

    def long(x, p):
        return rotate(x, p, rotation=rope_lib.Rotation(base=1e6))

    assert (short(x, pos) == long(x, pos)).all()
    assert (_grad(short, x, w, pos) == _grad(long, x, w, pos)).all()
    _close(short(x, pos), reference(x, pos, 1e6), x, 1)
    assert rope_lib.Rotation(1e6).name == "plain"
    assert rope_lib.Rotation(1e6, width=32).name == "partial"
    assert rope_lib.Rotation(1e6, factor=2.0, original_length=64).name \
        == "yarn"


def test_the_published_yarn_table():
    """The full layers' rotation of the window-and-full cell, from its
    published numbers: low 9, high 18; the first nine frequencies are the
    plain ones, those from the eighteenth on the plain ones over 128."""
    rotation = rope_lib.Rotation(base=500000.0, width=64, factor=128.0,
                                 original_length=8192, beta_fast=32.0,
                                 beta_slow=1.0, scale=1.4852030263919618)
    table = np.asarray(rotation.inv_freq(
        jnp.arange(32, dtype=jnp.float32), 64), np.float64)
    plain = 500000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(table[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(table[18:], plain[18:] / 128, rtol=1e-6)
    assert (table[10:18] < plain[10:18]).all() \
        and (table[10:18] > plain[10:18] / 128).all()
    np.testing.assert_allclose(table, _inv_freq(rotation, 64), rtol=1e-6)
    with pytest.raises(ValueError, match="rotary width"):
        rope_lib.Rotation(width=130).rotary(128)
