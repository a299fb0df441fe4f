"""The gradient reduction's one decision (ISSUE 28, ISSUE 29): a
flat-axis step with a linear op and a per-element wire reduces each
gradient where it lies, bitwise what flat buckets give (the reference
here is built in the test: ``fusion.fused_apply`` over
``collectives.allreduce``, the program every path was before ISSUE 28);
everything that needs a flat buffer keeps one, in flatten order; nobody
can ask for another shape. ZeRO's stages 2 and 3 keep their
``optimization_barrier`` chain; only a TPU mesh of several devices gets
compiler options."""

import functools
import re

import numpy as np
import optax
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import horovod_tpu as hvd_mod
from horovod_tpu import optim
from horovod_tpu.common import fusion, scopes
from horovod_tpu.ops import collectives as C
from horovod_tpu.ops.compression import Compression


def _mlp_tree(rng, depth=6, width=16):
    return {
        f"layer{i:02d}": {
            "w": jnp.asarray(rng.standard_normal((width, width))
                             .astype(np.float32)),
            "b": jnp.asarray(rng.standard_normal((width,))
                             .astype(np.float32)),
        } for i in range(depth)}


# -- the flatten plan is a checkpoint layout -------------------------------

def test_flatten_plan_emits_buckets_in_opening_order_for_interleaved_dtypes():
    """The ZeRO-1/FSDP sharded-state layout indexes ``plan.buckets``
    positionally, so the plan must not reorder across releases: the
    float32 bucket opens first (id 0) and is emitted first, although the
    int32 bucket closes before it."""
    # Flatten order = sorted keys: a0(f32) b(int32) z1 z2 z3(f32).
    tree = {"a0": jnp.ones((4,), jnp.float32),
            "b": jnp.arange(3, dtype=jnp.int32),
            "z1": jnp.ones((4,), jnp.float32),
            "z2": jnp.ones((4,), jnp.float32),
            "z3": jnp.ones((4,), jnp.float32)}
    plan = fusion.plan_fusion(tree, 1 << 20)
    assert [str(b.dtype) for b in plan.buckets] == ["float32", "int32"]
    back = fusion.unfuse(fusion.fuse(tree, plan), plan)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- ZeRO's issue-order chain ------------------------------------------------

def test_chain_issue_order_is_identity_on_values(rng):
    flats = [jnp.asarray(rng.standard_normal((n,)).astype(np.float32))
             for n in (5, 7, 3)]
    outs = optim._chain_issue_order(flats, lambda f: f * 2.0)
    for f, o in zip(flats, outs):
        np.testing.assert_allclose(np.asarray(o), np.asarray(f) * 2.0,
                                   rtol=1e-6)


def _zero_step_jaxpr(stage, rng):
    """The traced step of one ZeRO stage on four ranks, two buckets."""
    params = {"b": jnp.zeros((2,), jnp.float32),
              "w": jnp.asarray(rng.standard_normal((8, 2))
                               .astype(np.float32))}
    X = jnp.asarray(rng.standard_normal((16, 8)).astype(np.float32))
    tx = hvd_mod.ZeroOptimizer(optax.sgd(0.1), zero_stage=stage,
                               axis_name="z", fusion_threshold_bytes=16)
    mesh = Mesh(np.array(jax.devices()[:4]), ("z",))

    def loss(p, xb):
        return ((xb @ p["w"] + p["b"]) ** 2).mean()

    if stage == 3:
        sspecs, stspecs = tx.shard_specs(params), tx.state_specs(params)
        assert len(sspecs) == 2

        def step(p, xb):
            sh = tx.shard_params(p)
            full = tx.gather_params(sh)
            g = tx.reduce_grads(jax.grad(loss)(full, xb), full)
            return tx.update(g, tx.init(sh), sh)[0]

        outs = sspecs
    else:
        def step(p, xb):
            g = jax.grad(loss)(p, xb)
            if stage == 2:
                g = tx.reduce_grads(g, p)
            return tx.update(g, tx.init(p), p)[0]

        outs = P()
    return str(jax.make_jaxpr(jax.shard_map(
        step, mesh=mesh, in_specs=(P(), P("z")), out_specs=outs,
        check_vma=False))(params, X))


@pytest.mark.parametrize("stage", [1, 2, 3])
def test_zero_stages_2_and_3_carry_the_barrier_chain(hvd, rng, stage):
    """ZeRO's own program is unchanged by ISSUE 29: the reverse-order
    chained reduce-scatter (stages 2 and 3) and the chained gather of
    parameter shards (stage 3) are in the traced step; stage 1 (the
    ShardedOptimizer) never had a chain."""
    barriers = _zero_step_jaxpr(stage, rng).count("optimization_barrier")
    # n buckets chain n-1 times: one chain at stage 2 (the scatter), two
    # at stage 3 (the gather as well).
    assert barriers == {1: 0, 2: 1, 3: 2}[stage]


# -- the references, built here and not in the package ----------------------

def _flat_bucket_reduce(axis_name, threshold, op=hvd_mod.Average,
                        compression=Compression.none, prescale=1.0,
                        postscale=1.0):
    """Flat buckets in flatten order, one ``collectives.allreduce`` each:
    the program of every path before ISSUE 28, spelled out."""
    def one(flat):
        w, ctx = compression.compress(flat)
        return compression.decompress(
            C.allreduce(w, op, axis_name, prescale, postscale), ctx)

    return lambda grads: fusion.fused_apply(grads, one, threshold)


def _reference_tx(inner, reduce):
    """``inner`` fed gradients that ``reduce`` has reduced."""
    return optax.GradientTransformation(
        inner.init,
        lambda g, s, p=None: inner.update(reduce(g), s, p))


def _train(hvd, tx, params, X, Y, steps=5):
    ax = hvd.rank_axis()

    def loss_fn(p, xb, yb):
        h = xb
        for k in sorted(p):
            h = jnp.tanh(h @ p[k]["w"] + p[k]["b"])
        return jnp.mean((h - yb) ** 2)

    @hvd.spmd_step(in_specs=(P(), P(), P(ax), P(ax)),
                   out_specs=(P(), P(), P()))
    def step(p, s, xb, yb):
        l, g = jax.value_and_grad(loss_fn)(p, xb, yb)
        u, s = tx.update(g, s, p)
        return optax.apply_updates(p, u), s, jax.lax.pmean(l, ax)

    p, s = params, tx.init(params)
    losses = []
    for _ in range(steps):
        p, s, l = step(p, s, X, Y)
        losses.append(float(np.asarray(l)))
    return p, losses


def _assert_trees_bitwise(want, got):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_overlap_equivalence_distributed_optimizer(hvd, rng):
    """The default step against SGD on flat-bucket gradients: the same
    numbers summed in the same precision, so bit-identical updates."""
    width = 8
    params = _mlp_tree(rng, depth=4, width=width)
    X = rng.standard_normal((16, width)).astype(np.float32)
    Y = rng.standard_normal((16, width)).astype(np.float32)
    thr = (width * width + width) * 4  # multiple buckets
    ax = hvd.rank_axis()

    tx = hvd_mod.DistributedOptimizer(
        optax.sgd(0.05), axis_name=ax, fusion_threshold_bytes=thr)
    ref = _reference_tx(optax.sgd(0.05), _flat_bucket_reduce(ax, thr))

    p_ref, l_ref = _train(hvd, ref, params, X, Y)
    p_def, l_def = _train(hvd, tx, params, X, Y)
    np.testing.assert_array_equal(np.asarray(l_ref), np.asarray(l_def))
    _assert_trees_bitwise(p_ref, p_def)


GRADFN_CASES = {
    "average_none": (hvd_mod.Average, "none"),
    "average_bf16": (hvd_mod.Average, "bf16"),
    "sum_none": (hvd_mod.Sum, "none"),
    "sum_bf16": (hvd_mod.Sum, "bf16"),
}


@pytest.mark.parametrize("case", sorted(GRADFN_CASES))
def test_overlap_equivalence_grad_fn(hvd, rng, case):
    """``DistributedGradFn`` shares ``_reduce_tree``: the same decision
    (no copy under the reduce scope) and the flat buckets' bits."""
    op, wire = GRADFN_CASES[case]
    width = 8
    params = _mlp_tree(rng, depth=3, width=width)
    X = rng.standard_normal((16, width)).astype(np.float32)
    ax = hvd.rank_axis()
    thr = (width * width + width) * 4

    def loss_fn(p, xb):
        h = xb
        for k in sorted(p):
            h = jnp.tanh(h @ p[k]["w"] + p[k]["b"])
        return jnp.mean(h ** 2)

    gfn = hvd_mod.DistributedGradFn(
        jax.grad(loss_fn), op=op, axis_name=ax, compression=wire,
        fusion_threshold_bytes=thr)
    reduce = _flat_bucket_reduce(ax, thr, op, Compression.by_name(wire))
    run = hvd.spmd_step(gfn, in_specs=(P(), P(ax)), out_specs=P())
    ref = hvd.spmd_step(lambda p, xb: reduce(jax.grad(loss_fn)(p, xb)),
                        in_specs=(P(), P(ax)), out_specs=P())
    _assert_trees_bitwise(ref(params, X), run(params, X))
    text = run.lower(params, X).compile().as_text()
    assert not _copies_under_reduce(text)


def test_overlap_composes_with_compression(hvd, rng):
    width = 8
    params = _mlp_tree(rng, depth=3, width=width)
    X = rng.standard_normal((16, width)).astype(np.float32)
    Y = rng.standard_normal((16, width)).astype(np.float32)
    thr = (width * width + width) * 4
    ax = hvd.rank_axis()

    tx = hvd_mod.DistributedOptimizer(
        optax.sgd(0.05), axis_name=ax, compression=Compression.fp16,
        fusion_threshold_bytes=thr)
    ref = _reference_tx(optax.sgd(0.05), _flat_bucket_reduce(
        ax, thr, compression=Compression.fp16))
    p_ref, _ = _train(hvd, ref, params, X, Y, steps=3)
    p_def, _ = _train(hvd, tx, params, X, Y, steps=3)
    _assert_trees_bitwise(p_ref, p_def)


# -- the default path: reduced where it lies (ISSUE 28) ---------------------

THR = (8 * 8 + 8) * 4  # one (w, b) pair of the small trees below


def _grad_tree(kind, rng, ranks=8):
    """A per-rank gradient tree (leading axis = rank), by shape of
    interest."""
    def f32(*shape):
        return jnp.asarray(rng.standard_normal((ranks,) + shape)
                           .astype(np.float32))

    tree = {f"layer{i}": {"w": f32(8, 8), "b": f32(8)} for i in range(3)}
    if kind == "mixed":
        tree["layer1"]["w"] = tree["layer1"]["w"].astype(jnp.bfloat16)
        tree["head"] = {"w": f32(8, 4).astype(jnp.bfloat16), "b": f32(4)}
    elif kind == "big_leaf":
        tree["emb"] = f32(32, 32)  # 4096 B, fourteen thresholds
    elif kind == "scalar":
        tree["temperature"] = f32()
    return tree


def _reduction_alone(**kwargs):
    """Per-rank gradients in, reduced gradients out: ``optax.identity``
    hands the reduced gradients back as the updates."""
    tx = hvd_mod.DistributedOptimizer(
        optax.identity(), fusion_threshold_bytes=THR, **kwargs)

    def run(per_rank):
        grads = jax.tree.map(lambda x: x[0], per_rank)
        return tx.update(grads, tx.init(grads), grads)[0]

    return run


def _reduce_step(hvd, **kwargs):
    ax = hvd.rank_axis()
    return hvd.spmd_step(_reduction_alone(axis_name=ax, **kwargs),
                         in_specs=P(ax), out_specs=P())


IN_PLACE_CASES = {
    "one_dtype": ("plain", {}),
    "mixed_fp32_bf16": ("mixed", {}),
    "leaf_over_threshold": ("big_leaf", {}),
    "scalar_leaf": ("scalar", {}),
    "sum": ("plain", dict(op=hvd_mod.Sum)),
    "average": ("plain", dict(op=hvd_mod.Average)),
    "pre_and_postscale": ("plain", dict(prescale_factor=0.5,
                                        postscale_factor=3.0)),
    "sum_prescaled_mixed": ("mixed", dict(op=hvd_mod.Sum,
                                          prescale_factor=0.25)),
    "bf16_wire": ("mixed", dict(compression="bf16")),
    "fp16_wire_scalar": ("scalar", dict(compression="fp16")),
}


def _flat_reference_step(hvd, op=hvd_mod.Average, compression="none",
                         prescale_factor=1.0, postscale_factor=1.0):
    """The flat-bucket twin of ``_reduce_step`` with the same scales and
    wire."""
    ax = hvd.rank_axis()
    reduce = _flat_bucket_reduce(ax, THR, op,
                                 Compression.by_name(compression),
                                 prescale_factor, postscale_factor)
    return hvd.spmd_step(
        lambda per_rank: reduce(jax.tree.map(lambda x: x[0], per_rank)),
        in_specs=P(ax), out_specs=P())


@pytest.mark.parametrize("case", sorted(IN_PLACE_CASES))
def test_default_reduction_is_bitwise_the_flat_buckets(hvd, rng, case):
    """Axis bound over eight ranks: each leaf reduced where it lies,
    against the flat buckets' sum with the same scales and wire."""
    kind, kwargs = IN_PLACE_CASES[case]
    grads = _grad_tree(kind, rng)
    flat = _flat_reference_step(hvd, **kwargs)(grads)
    _assert_trees_bitwise(flat, _reduce_step(hvd, **kwargs)(grads))


@pytest.mark.parametrize("inner", ["sgd", "adamw"])
def test_default_training_matches_overlap_false(hvd, rng, inner):
    """Through a real optimizer over several steps, against plain optax
    on ``lax.pmean``-ed gradients. The reduced gradients are bitwise the
    reference's (above; eight ranks, so the mean is exact either way); a
    linear update keeps that to the parameters. A stateful one XLA:CPU
    compiles next to another producer and may contract a multiply-add
    differently: an ulp, so ``adamw`` is held to a few of them."""
    params = _mlp_tree(rng, depth=4, width=8)
    X = rng.standard_normal((16, 8)).astype(np.float32)
    Y = rng.standard_normal((16, 8)).astype(np.float32)

    ax = hvd.rank_axis()

    def make():
        return {"sgd": optax.sgd(0.05), "adamw": optax.adamw(1e-2)}[inner]

    tx = hvd_mod.DistributedOptimizer(make(), axis_name=ax,
                                      fusion_threshold_bytes=THR)
    ref = _reference_tx(make(), lambda g: jax.lax.pmean(g, ax))
    p_flat, l_flat = _train(hvd, ref, params, X, Y, steps=4)
    p_def, l_def = _train(hvd, tx, params, X, Y, steps=4)
    same = np.testing.assert_array_equal if inner == "sgd" else \
        functools.partial(np.testing.assert_allclose, rtol=2e-6, atol=1e-7)
    same(np.asarray(l_flat), np.asarray(l_def))
    for a, b in zip(jax.tree.leaves(p_flat), jax.tree.leaves(p_def)):
        same(np.asarray(a), np.asarray(b))


def _op_names(compiled_text):
    return set(re.findall(r'op_name="([^"]*)"', compiled_text))


def _copies_under_reduce(compiled_text):
    """Instructions that concatenate under the ``hvd_reduce`` scope."""
    return [ln for ln in compiled_text.splitlines()
            if re.search(r"= \S+ concatenate\(", ln)
            and scopes.REDUCE in ln]


@pytest.mark.parametrize("case", sorted(IN_PLACE_CASES))
def test_default_step_has_no_concatenate_under_hvd_reduce(hvd, rng, case):
    """The structural half of the decision, for every input that takes
    it: no bucket is packed or unpacked."""
    kind, kwargs = IN_PLACE_CASES[case]
    grads = _grad_tree(kind, rng)
    default = _reduce_step(hvd, **kwargs).lower(grads).compile().as_text()
    assert not _copies_under_reduce(default)
    names = _op_names(default)
    assert not any(scopes.PACK in n.split("/") or scopes.UNPACK
                   in n.split("/") for n in names)
    # the reduction itself still carries the scope the trace is read by
    assert any(scopes.REDUCE in n.split("/") for n in names)


def _mesh_step(mesh, axes, **kwargs):
    return jax.jit(jax.shard_map(_reduction_alone(**kwargs), mesh=mesh,
                                 in_specs=P(axes), out_specs=P(),
                                 check_vma=False))


NEEDS_A_FLAT_BUFFER = {
    "int8_ef": dict(compression="int8_ef", quantize_min_bucket_bytes=64),
    "adasum": dict(op=hvd_mod.Adasum),
    "route": dict(route="local:none,cross:none"),
    "route_int8": dict(route="local:none,cross:int8"),
    "hierarchical": dict(hierarchical=True),
    "hierarchical_int8_cross": dict(hierarchical=True,
                                    quantized_cross=True),
}


@pytest.mark.parametrize("case", sorted(NEEDS_A_FLAT_BUFFER))
def test_paths_that_need_a_flat_buffer_keep_it(hvd, rng, case):
    """Block-scaled payloads, the router's and the staged pipeline's
    shards and Adasum's per-bucket dot products: these still pack."""
    kwargs = NEEDS_A_FLAT_BUFFER[case]
    grads = _grad_tree("plain", rng)
    if "route" in kwargs or "hierarchical" in kwargs:
        mesh = Mesh(np.array(jax.devices()).reshape(2, 4),
                    ("cross", "local"))
        step = _mesh_step(mesh, ("cross", "local"), **kwargs)
    else:
        step = _reduce_step(hvd, **kwargs)
    names = _op_names(step.lower(grads).compile().as_text())
    assert any(scopes.REDUCE_PACK in n for n in names), sorted(names)[:5]


# What the parent commit (f2ac18d) traced for this update with no axis
# bound: the five one-chip cells of the benchmark take this path, and it
# is theirs character for character.
UNBOUND_JAXPR = """\
{ lambda ; a:f32[3] b:f32[2,3] c:f32[] d:bf16[4]. let
    e:f32[6] = reshape[dimensions=None new_sizes=(6,) sharding=None] b
    f:f32[1] = reshape[dimensions=None new_sizes=(1,) sharding=None] c
    g:f32[3] = slice[limit_indices=(3,) start_indices=(0,) strides=(1,)] a
    h:f32[6] = slice[limit_indices=(6,) start_indices=(0,) strides=(1,)] e
    i:f32[2,3] = reshape[dimensions=None new_sizes=(2, 3) sharding=None] h
    j:f32[1] = slice[limit_indices=(1,) start_indices=(0,) strides=(1,)] f
    k:f32[] = reshape[dimensions=None new_sizes=() sharding=None] j
    l:bf16[4] = slice[limit_indices=(4,) start_indices=(0,) strides=(1,)] d
    m:f32[3] = mul -0.5:f32[] g
    n:f32[2,3] = mul -0.5:f32[] i
    o:f32[] = mul -0.5:f32[] k
    p:bf16[4] = mul -0.5:bf16[] l
  in (m, n, o, p) }"""


@pytest.mark.parametrize("case", ["default"])
def test_step_with_no_axis_bound_is_the_parents_jaxpr(case):
    tree = {"a": {"b": jnp.ones((3,), jnp.float32),
                  "w": jnp.ones((2, 3), jnp.float32)},
            "s": jnp.ones((), jnp.float32),
            "z": jnp.ones((4,), jnp.bfloat16)}
    tx = hvd_mod.DistributedOptimizer(
        optax.sgd(0.5), axis_name="hvd", compression="none",
        fusion_threshold_bytes=16)
    state = tx.init(tree)
    text = str(jax.make_jaxpr(lambda g: tx.update(g, state, tree)[0])(tree))
    assert text == UNBOUND_JAXPR


def test_spmd_step_gives_a_cpu_mesh_no_compiler_options(hvd, monkeypatch):
    seen = []
    real_jit = jax.jit

    def spy(fn, **kwargs):
        seen.append(kwargs)
        return real_jit(fn, **kwargs)

    monkeypatch.setattr(jax, "jit", spy)
    hvd.spmd_step(lambda x: x)
    assert [k.get("compiler_options") for k in seen] == [None]


def test_axis_bound_over_one_rank_takes_the_flat_path(rng):
    """``axis_size > 1`` is part of the decision: a one-device mesh is
    the one-chip cells' program with the axis bound, so it packs as they
    do, and what all-reduce XLA:CPU keeps spans that one device."""
    grads = jax.tree.map(lambda x: x[:1], _grad_tree("plain", rng))
    mesh = Mesh(np.array(jax.devices()[:1]), ("hvd",))
    text = _mesh_step(mesh, "hvd", axis_name="hvd").lower(
        grads).compile().as_text()
    assert any(scopes.REDUCE_PACK in n for n in _op_names(text))
    groups = re.findall(r" all-reduce\(.*replica_groups=(\{[^ ]*\}),", text)
    assert set(groups) <= {"{{0}}"}, groups


def test_defaulted_route_on_a_flat_mesh_reduces_in_place(hvd, rng,
                                                         monkeypatch):
    """A route that arrives as a default (``HVD_TPU_ROUTE``) and whose
    axes the step does not bind falls back to the live rank axis; the
    shape is then decided as if no route had been given."""
    monkeypatch.setattr(hvd_mod.common.basics.context().config, "route",
                        "staged")
    grads = _grad_tree("plain", rng)
    step = _reduce_step(hvd)
    monkeypatch.undo()
    _assert_trees_bitwise(_flat_reference_step(hvd)(grads), step(grads))
    assert not _copies_under_reduce(step.lower(grads).compile().as_text())


def test_error_feedback_plan_is_flatten_order_for_mixed_dtypes(
        hvd, monkeypatch):
    """``_reduce_tree_ef`` plans as every sharded surface does: bucket
    ``i`` of the plan is what ``_ef_key(step, i)`` seeds, so the order
    is part of the numerics. Mixed dtypes interleaved: float32 opens
    first and is bucket 0."""
    ax = hvd.rank_axis()
    tree = {"a0": jnp.ones((8, 64), jnp.float32),
            "b": jnp.ones((8, 64), jnp.bfloat16),
            "z1": jnp.ones((8, 64), jnp.float32)}
    seen = []
    real = fusion.assign_wire_dtypes

    def spy(plan, qmin, **kw):
        out = real(plan, qmin, **kw)
        seen.append(out)
        return out

    tx = hvd_mod.DistributedOptimizer(
        optax.identity(), axis_name=ax, compression="int8_ef",
        fusion_threshold_bytes=1 << 20, quantize_min_bucket_bytes=256)

    def run(per_rank):
        g = jax.tree.map(lambda x: x[0], per_rank)
        return tx.update(g, tx.init(g), g)[0]

    monkeypatch.setattr(fusion, "assign_wire_dtypes", spy)
    hvd.spmd_step(run, in_specs=P(ax), out_specs=P()).lower(tree)
    (plan,) = seen
    assert [str(b.dtype) for b in plan.buckets] == ["float32", "bfloat16"]
    assert [b.leaf_indices for b in plan.buckets] == [(0, 2), (1,)]
    assert plan.wire_dtypes == ("int8", "none")
