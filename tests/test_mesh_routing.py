"""Topology-aware collective router (docs/topology.md): per-axis
RS/AG phases with per-axis wire dtypes over simulated 2-D/3-D meshes,
Adasum as a first-class reduction mode, the int8_ef error-feedback
composition, and the grad-consistency acceptance gates — all on the
8-virtual-CPU-device loopback tier (2x4, 2x2, 2x2x2 factorizations).
"""

import numpy as np
import optax
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import horovod_tpu as hvd_mod
from horovod_tpu import optim
from horovod_tpu.ops import adasum as adasum_lib
from horovod_tpu.ops import collectives as C


@pytest.fixture(scope="module")
def mesh2d():
    devs = np.array(jax.devices()).reshape(2, 4)
    return Mesh(devs, ("cross", "local"))


@pytest.fixture(scope="module")
def mesh2x2():
    # 4-device 2x2 mesh over the first half of the world — the "other"
    # simulated pod shape of the grad-consistency gate.
    devs = np.array(jax.devices()[:4]).reshape(2, 2)
    return Mesh(devs, ("cross", "local"))


@pytest.fixture(scope="module")
def mesh3d():
    devs = np.array(jax.devices()).reshape(2, 2, 2)
    return Mesh(devs, ("cross", "middle", "local"))


def _spmd(mesh, axes, fn):
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P(axes),
                                 out_specs=P(axes)))


PLAN = C.WirePlan.parse("local:none,cross:none")
PLAN_Q = C.WirePlan.parse("local:none,cross:int8")
PLAN_QQ = C.WirePlan.parse("local:int8,cross:int8")


# -- WirePlan ---------------------------------------------------------------

def test_wireplan_parse_and_helpers():
    plan = C.WirePlan.parse("local:none,cross:int8")
    assert plan.axis_names == ("local", "cross")
    assert plan.wires == ("none", "int8")
    assert plan.describe() == "local:none,cross:int8"
    assert plan.with_wires("none").wires == ("none", "none")
    assert plan.reversed().axis_names == ("cross", "local")
    # fp32 is an alias of none; bare axis defaults to none.
    assert C.WirePlan.parse("a:fp32,b").wires == ("none", "none")
    assert C.WirePlan.hierarchical(cross_wire="int8") == PLAN_Q


def test_wireplan_resolve_named_routes():
    assert C.WirePlan.resolve(None) is None
    assert C.WirePlan.resolve("flat") is None
    assert C.WirePlan.resolve("staged") == PLAN
    assert C.WirePlan.resolve("staged_int8") == PLAN_Q
    assert C.WirePlan.resolve(PLAN_Q) is PLAN_Q
    assert C.WirePlan.resolve("local:int8,cross:int8") == PLAN_QQ
    with pytest.raises(ValueError, match="unknown route"):
        C.WirePlan.resolve("bogus")


def test_wireplan_validation():
    with pytest.raises(ValueError, match="wire"):
        C.WirePlan.parse("local:float8")
    with pytest.raises(ValueError, match="duplicate"):
        C.WirePlan.parse("local:none,local:int8")
    with pytest.raises(ValueError, match="at least one"):
        C.WirePlan(())


# -- router numerics --------------------------------------------------------

def test_mesh_allreduce_exact_matches_flat(mesh2d, rng):
    n = 5000  # deliberately not a multiple of the mesh grid
    x = rng.standard_normal((8, n)).astype(np.float32)
    f = _spmd(mesh2d, ("cross", "local"),
              lambda v: C.mesh_allreduce(v.reshape(n), C.ReduceOp.SUM,
                                         PLAN)[None])
    out = np.asarray(f(x))
    want = x.sum(axis=0)
    for r in range(8):
        np.testing.assert_allclose(out[r], want, rtol=1e-4, atol=1e-4)
    # AVERAGE divides by the full mesh size once.
    g = _spmd(mesh2d, ("cross", "local"),
              lambda v: C.mesh_allreduce(v.reshape(n),
                                         C.ReduceOp.AVERAGE, PLAN)[None])
    np.testing.assert_allclose(np.asarray(g(x))[0], want / 8.0,
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("plan", [PLAN_Q, PLAN_QQ],
                         ids=["int8_cross", "int8_both"])
def test_mesh_allreduce_quantized_within_bound(mesh2d, rng, plan):
    n = 6000
    x = rng.standard_normal((8, n)).astype(np.float32)
    f = _spmd(mesh2d, ("cross", "local"),
              lambda v: C.mesh_allreduce(v.reshape(n), C.ReduceOp.SUM,
                                         plan)[None])
    out = np.asarray(f(x))
    want = x.sum(axis=0)
    err = np.abs(out[0] - want)
    # Multi-hop bound: each int8 phase adds <= r*s per element (s =
    # block absmax/127 of THAT hop's payload — local sums on the cross
    # hop), so the routed error is a small multiple of the flat
    # quantized allreduce's; measured q99 is 0.054 (Q) / 0.070 (QQ).
    assert np.quantile(err / (np.abs(want) + 1.0), 0.99) < 0.12, err.max()
    # Every replica computes the IDENTICAL routed result — the int8
    # hops dequantize the same wire data everywhere.
    np.testing.assert_allclose(out, np.tile(out[0], (8, 1)), atol=1e-6)


def test_mesh_allreduce_int_average_promotes_like_flat(mesh2d):
    """Integer AVERAGE must match the flat allreduce's promotion: the
    true-divide yields float, and casting back to int would silently
    floor-truncate (7 ranks of 1 averaged to 0)."""
    x = np.arange(8 * 4, dtype=np.int32).reshape(8, 4)
    f = _spmd(mesh2d, ("cross", "local"),
              lambda v: C.mesh_allreduce(v.reshape(4),
                                         C.ReduceOp.AVERAGE, PLAN)[None])
    out = np.asarray(f(x))
    assert np.issubdtype(out.dtype, np.floating), out.dtype
    np.testing.assert_allclose(out[0], x.mean(axis=0), rtol=1e-6)
    # SUM keeps the integer dtype exactly.
    g = _spmd(mesh2d, ("cross", "local"),
              lambda v: C.mesh_allreduce(v.reshape(4),
                                         C.ReduceOp.SUM, PLAN)[None])
    outs = np.asarray(g(x))
    assert outs.dtype == np.int32
    np.testing.assert_array_equal(outs[0], x.sum(axis=0))


def test_mesh_allreduce_3d_mixed_wires(mesh3d, rng):
    n = 4096
    x = rng.standard_normal((8, n)).astype(np.float32)
    plan = C.WirePlan.parse("local:none,middle:bf16,cross:int8")
    f = _spmd(mesh3d, ("cross", "middle", "local"),
              lambda v: C.mesh_allreduce(v.reshape(n), C.ReduceOp.SUM,
                                         plan)[None])
    out = np.asarray(f(x))
    want = x.sum(axis=0)
    err = np.abs(out[0] - want)
    assert np.quantile(err / (np.abs(want) + 1.0), 0.99) < 0.06, err.max()


def test_mesh_allreduce_residual_sum_invariant(mesh2d, rng):
    """The error-feedback contract: exact_sum - routed_result equals
    the residual summed over ALL mesh ranks (descent errors land on
    their owning shard, ascent errors are owner-masked) — the same
    invariant the flat quantized_allreduce fuzz tests pin."""
    n = 5000
    x = (rng.standard_normal((8, n)) * 3).astype(np.float32)
    key = jax.random.PRNGKey(11)

    def fn(v):
        y, r = C.mesh_allreduce(v.reshape(n), C.ReduceOp.SUM, PLAN_QQ,
                                key=key, return_residual=True)
        return jnp.stack([y, jax.lax.psum(r, ("cross", "local"))])[None]

    out = np.asarray(_spmd(mesh2d, ("cross", "local"), fn)(x))
    y, rsum = out[0, 0], out[0, 1]
    want = x.sum(axis=0)
    raw_err = np.abs(want - y).max()
    closed = np.abs(want - y - rsum).max()
    # The residual closes the quantization error to fp32 roundoff.
    assert closed < 1e-4 * (np.abs(want).max() + 1), (closed, raw_err)
    assert raw_err > 10 * closed  # the invariant is non-vacuous


def test_mesh_reducescatter_allgather_roundtrip(mesh2d, rng):
    L = 8 * C._Q_BLOCK
    x = rng.standard_normal((8, L)).astype(np.float32)

    def fn(v):
        shard = C.mesh_reducescatter(v.reshape(L), C.ReduceOp.SUM, PLAN)
        return C.mesh_allgather(shard, PLAN.reversed())[None]

    out = np.asarray(_spmd(mesh2d, ("cross", "local"), fn)(x))
    np.testing.assert_allclose(out[0], x.sum(axis=0), rtol=1e-3,
                               atol=1e-3)


def test_mesh_allgather_flat_row_order(mesh2d, rng):
    x = rng.standard_normal((8, 3, 5)).astype(np.float32)
    f = _spmd(mesh2d, ("cross", "local"),
              lambda v: C.mesh_allgather(v.reshape(3, 5), PLAN)[None])
    out = np.asarray(f(x))
    np.testing.assert_array_equal(out[0], x.reshape(24, 5))


# -- Adasum on the router ---------------------------------------------------

def test_mesh_adasum_matches_hierarchical_reference(mesh2d, rng):
    """mesh_allreduce(ADASUM) = Adasum of the per-fast-group AVERAGES
    (the reference adasum_gpu_operations.cc scheme), computed on shards
    with fast-axis-psum-med scalars — must match the full-vector numpy
    recursion exactly (no quantization in this plan)."""
    x = rng.standard_normal((8, 300)).astype(np.float32)
    f = _spmd(mesh2d, ("cross", "local"),
              lambda v: C.mesh_allreduce(v.reshape(300),
                                         C.ReduceOp.ADASUM, PLAN)[None])
    out = np.asarray(f(x))
    expected = adasum_lib.adasum_allreduce_reference(
        [x[:4].mean(axis=0), x[4:].mean(axis=0)])
    for r in range(8):
        np.testing.assert_allclose(out[r], expected, rtol=1e-4,
                                   atol=1e-4)


def test_mesh_adasum_int8_wire_within_bound(mesh2d, rng):
    x = (rng.standard_normal((8, 5000)) * 2).astype(np.float32)
    key = jax.random.PRNGKey(5)
    f = _spmd(mesh2d, ("cross", "local"),
              lambda v: C.mesh_allreduce(v.reshape(5000),
                                         C.ReduceOp.ADASUM, PLAN_QQ,
                                         key=key)[None])
    out = np.asarray(f(x))
    expected = adasum_lib.adasum_allreduce_reference(
        [x[:4].mean(axis=0), x[4:].mean(axis=0)])
    err = np.abs(out[0] - expected)
    # Descent RS rounding + one quantized exchange level (nc=2);
    # measured q99 0.077 on 2-sigma data.
    assert np.quantile(err / (np.abs(expected) + 1.0), 0.99) < 0.12
    # Quantized exchange keeps replicas bitwise-consistent: both pair
    # partners combine the SAME dequantized views.
    np.testing.assert_allclose(out, np.tile(out[0], (8, 1)), atol=1e-6)


def test_adasum_quantized_exchange_flat_axis(hvd, rng):
    """adasum_allreduce(wire='int8') on the flat 8-rank axis stays
    within the per-level block-rounding bound of the exact recursion."""
    ctx = hvd_mod.init()
    x = rng.standard_normal((8, 4000)).astype(np.float32)
    f = jax.jit(jax.shard_map(
        lambda v: adasum_lib.adasum_allreduce(
            v, ctx.config.rank_axis, wire="int8",
            key=jax.random.PRNGKey(2)),
        mesh=ctx.mesh, in_specs=P(ctx.config.rank_axis),
        out_specs=P(ctx.config.rank_axis)))
    out = np.asarray(f(hvd.scatter(x)))
    expected = adasum_lib.adasum_allreduce_reference(
        [x[r] for r in range(8)])
    err = np.abs(out[0] - expected)
    # log2(8)=3 quantized exchange levels, and the adaptive combine
    # SHRINKS the result (near-average of sigma=1 inputs) while the
    # block scales come from the full-magnitude operands — the
    # relative error is the largest of the int8 family here (measured
    # q99 0.155).
    assert np.quantile(err / (np.abs(expected) + 1.0), 0.99) < 0.25


def test_adasum_combine_counter(mesh2d, rng):
    from horovod_tpu.common import metrics as metrics_lib

    if not metrics_lib.enabled():
        pytest.skip("metrics disabled")
    snap0 = metrics_lib.snapshot().get("hvd_tpu_adasum_combines_total",
                                       {"samples": []})
    before = sum(s["value"] for s in snap0["samples"])
    x = rng.standard_normal((8, 64)).astype(np.float32)
    f = _spmd(mesh2d, ("cross", "local"),
              lambda v: C.mesh_allreduce(v.reshape(64),
                                         C.ReduceOp.ADASUM, PLAN)[None])
    np.asarray(f(x))
    snap1 = metrics_lib.snapshot()["hvd_tpu_adasum_combines_total"]
    after = sum(s["value"] for s in snap1["samples"])
    assert after >= before + 1  # log2(cross=2) = 1 combine level traced


# -- wire-cost model --------------------------------------------------------

def test_mesh_wire_cost_slow_axis_strictly_fewer():
    """The acceptance inequality: the per-axis plan moves strictly
    fewer bytes on the slowest axis than the flat ring, at and above
    the fusion threshold."""
    for mib in (0.0625, 1, 64, 256):
        nelems = int(mib * 2**20 / 4)
        flat_slow = 2.0 * 7 / 8 * nelems * 4  # 8-rank ring, worst case
        staged = C.mesh_wire_cost(PLAN, nelems, (4, 2))
        quant = C.mesh_wire_cost(PLAN_Q, nelems, (4, 2))
        assert staged["cross"]["bytes"] < flat_slow
        assert quant["cross"]["bytes"] < staged["cross"]["bytes"]
        # int8 ≈ staged/4 (plus the 0.1% scale overhead).
        assert quant["cross"]["bytes"] == pytest.approx(
            staged["cross"]["bytes"] / 4, rel=0.01)
    # Adasum cost model: log2(nc) full-shard exchanges on the slow axis.
    ada = C.mesh_wire_cost(PLAN, 4096, (4, 4), op=C.ReduceOp.ADASUM)
    assert ada["cross"]["bytes"] == pytest.approx(2 * (4096 / 4) * 4)


def test_mesh_allreduce_publishes_per_axis_bytes(mesh2d, rng):
    from horovod_tpu.common import metrics as metrics_lib

    if not metrics_lib.enabled():
        pytest.skip("metrics disabled")
    x = rng.standard_normal((8, 4096)).astype(np.float32)
    f = _spmd(mesh2d, ("cross", "local"),
              lambda v: C.mesh_allreduce(v.reshape(4096),
                                         C.ReduceOp.SUM, PLAN_Q)[None])
    np.asarray(f(x))
    samples = metrics_lib.snapshot()[
        "hvd_tpu_allreduce_bytes_total"]["samples"]
    by = {(s["labels"].get("axis"), s["labels"].get("wire")): s["value"]
          for s in samples}
    assert by.get(("local", "none"), 0) > 0
    assert by.get(("cross", "int8"), 0) > 0


# -- optimizer composition --------------------------------------------------

def _train(mesh, axes, tx, steps=30, lr_probe=None):
    """Tiny shared regression: fixed target, losses (first, last)."""
    g = np.random.default_rng(17)
    Wt = g.standard_normal((24, 1)).astype(np.float32)
    X = g.standard_normal((8, 24)).astype(np.float32)
    Y = (X @ Wt).reshape(8)
    p = {"w": jnp.zeros((24, 1), jnp.float32)}
    s = tx.init(p)

    def stepfn(p, s, xb, yb):
        def loss_fn(p):
            return jnp.mean((xb @ p["w"] - yb.reshape(-1, 1)) ** 2)

        l, grad = jax.value_and_grad(loss_fn)(p)
        u, s2 = tx.update(grad, s, p)
        return optax.apply_updates(p, u), s2, jax.lax.pmean(l, axes)

    f = jax.jit(jax.shard_map(
        stepfn, mesh=mesh,
        in_specs=(P(), P(), P(axes), P(axes)),
        out_specs=(P(), P(), P()), check_vma=False))
    l0 = lN = None
    for _ in range(steps):
        p, s, l = f(p, s, X[:, None, :], Y[:, None])
        l0 = float(l) if l0 is None else l0
        lN = float(l)
    return l0, lN


def test_route_conflicts_with_legacy_flags():
    with pytest.raises(ValueError, match="mesh_allreduce|mesh router"):
        optim.DistributedOptimizer(optax.sgd(0.1), hierarchical=True,
                                   route="staged_int8")
    with pytest.raises(ValueError, match="route|mesh_allreduce"):
        optim.DistributedOptimizer(optax.sgd(0.1), quantized_cross=True,
                                   hierarchical=True, route=PLAN_Q)


def test_env_route_default_does_not_break_legacy_flags(monkeypatch):
    """HVD_TPU_ROUTE is a DEFAULT: an unchanged call site passing the
    legacy hierarchical/quantized_cross booleans must keep its legacy
    path (not raise, not silently re-route); only an EXPLICIT route=
    alongside the booleans conflicts."""
    monkeypatch.setenv("HVD_TPU_ROUTE", "staged_int8")
    assert optim.DistributedOptimizer(optax.sgd(0.1),
                                      hierarchical=True) is not None
    assert optim.DistributedOptimizer(optax.sgd(0.1), hierarchical=True,
                                      quantized_cross=True) is not None
    with pytest.raises(ValueError, match="route"):
        optim.DistributedOptimizer(optax.sgd(0.1), hierarchical=True,
                                   route="staged")


def test_route_default_falls_back_on_flat_mesh(monkeypatch, rng):
    """A route DEFAULT (HVD_TPU_ROUTE) reaching a step traced under the
    FLAT mesh must reduce over the live rank axis — silently taking the
    identity (no-reduction) path would diverge replicas."""
    monkeypatch.setenv("HVD_TPU_ROUTE", "staged")
    flat = Mesh(np.array(jax.devices()), ("hvd",))
    tx = optim.DistributedOptimizer(optax.sgd(1.0))
    p = {"w": jnp.zeros((4,), jnp.float32)}

    def fn(g):
        s = tx.init(p)
        u, _ = tx.update({"w": g.reshape(4)}, s, p)
        return u["w"][None]

    g_host = np.arange(32, dtype=np.float32).reshape(8, 4)
    out = np.asarray(_spmd(flat, ("hvd",), fn)(g_host))
    want = -g_host.mean(axis=0)
    np.testing.assert_allclose(out, np.tile(want, (8, 1)), rtol=1e-5)


def test_minmax_ops_reduce_jointly_under_route(mesh2d, rng):
    """MIN/MAX have no staged decomposition — under a route they reduce
    jointly over all plan axes instead of crashing in mesh_allreduce."""
    g_host = rng.standard_normal((8, 64)).astype(np.float32)
    for op, red in ((hvd_mod.Max, np.max), (hvd_mod.Min, np.min)):
        tx = optim.DistributedOptimizer(optax.sgd(1.0), op=op,
                                        route="staged")
        p = {"w": jnp.zeros((64,), jnp.float32)}

        def fn(g):
            s = tx.init(p)
            u, _ = tx.update({"w": g.reshape(64)}, s, p)
            return u["w"][None]

        out = np.asarray(_spmd(mesh2d, ("cross", "local"), fn)(g_host))
        np.testing.assert_allclose(out[0], -red(g_host, axis=0),
                                   rtol=1e-5)


def test_quantized_cross_error_points_at_router():
    # The legacy special case's guard rail now names its replacement.
    with pytest.raises(ValueError, match="route|mesh_allreduce"):
        optim.DistributedOptimizer(optax.sgd(0.1), quantized_cross=True)


def test_int8_ef_hierarchical_routes_through_wireplan(mesh2d, rng):
    """The former optim.py hard error: compression='int8_ef' +
    hierarchical=True now routes through the per-axis WirePlan (int8 on
    the cross hop) and reduces correctly on the 2x4 mesh."""
    tx = optim.DistributedOptimizer(optax.sgd(0.05),
                                    compression="int8_ef",
                                    hierarchical=True,
                                    quantize_min_bucket_bytes=0)
    n = 2048
    g_host = (rng.standard_normal((8, n)) * 2).astype(np.float32)
    p = {"w": jnp.zeros((n,), jnp.float32)}

    def fn(g):
        s = tx.init(p)
        u, _ = tx.update({"w": g.reshape(n)}, s, p)
        return u["w"][None]

    out = np.asarray(_spmd(mesh2d, ("cross", "local"), fn)(g_host))
    want = -0.05 * g_host.mean(axis=0)
    err = np.abs(out[0] - want)
    assert np.quantile(err / (np.abs(want) + 1e-2), 0.99) < 0.1
    np.testing.assert_allclose(out, np.tile(out[0], (8, 1)), atol=1e-6)


def test_adasum_int8_ef_overlap_acceptance(mesh2d):
    """THE acceptance gate: DistributedOptimizer(op=hvd.Adasum,
    compression='int8_ef') trains on the simulated 2D
    mesh to within the documented (2%, docs/compression.md) bound of
    the flat fp32 SUM run, and of the exact (fp32) routed Adasum."""
    flat_mesh = Mesh(np.array(jax.devices()), ("hvd",))
    tx_ada = optim.DistributedOptimizer(
        optax.adam(5e-2), op=hvd_mod.Adasum, compression="int8_ef",
        route=PLAN_QQ, quantize_min_bucket_bytes=0)
    tx_exact = optim.DistributedOptimizer(
        optax.adam(5e-2), op=hvd_mod.Adasum, route=PLAN)
    tx_flat = optim.DistributedOptimizer(optax.adam(5e-2),
                                         op=hvd_mod.Sum)
    l0a, lNa = _train(mesh2d, ("cross", "local"), tx_ada)
    l0e, lNe = _train(mesh2d, ("cross", "local"), tx_exact)
    l0f, lNf = _train(flat_mesh, ("hvd",), tx_flat)
    assert l0a == pytest.approx(l0f, abs=1e-4)  # identical start
    assert lNa < 0.05 * l0a                     # it trains
    assert abs(lNa - lNf) < 0.02 * l0f          # vs flat fp32 SUM
    assert abs(lNa - lNe) < 0.02 * l0e + 1e-3   # compression bound


def test_route_composes_with_nonfinite_guard(mesh2d):
    """The integrity guard's one-scalar agreement runs over the plan's
    axes when routed (the flat rank axis is not bound there)."""
    tx = optim.DistributedOptimizer(
        optax.sgd(0.05), route=PLAN_Q, compression="int8_ef",
        nonfinite_policy="skip_step", quantize_min_bucket_bytes=0)
    l0, lN = _train(mesh2d, ("cross", "local"), tx, steps=10)
    assert np.isfinite(lN) and lN < l0


# -- grad consistency across mesh shapes ------------------------------------

def _routed_grad(mesh, axes, route, nranks, g_host,
                 threshold=4096 * 4):
    """One int8_ef reduction of a two-leaf tree (two buckets at the
    default threshold); returns the reduced tree on rank 0's view."""
    tx = optim.DistributedOptimizer(
        optax.sgd(1.0), op=hvd_mod.Sum, compression="int8_ef",
        route=route, quantize_min_bucket_bytes=0,
        fusion_threshold_bytes=threshold)
    shapes = {"a": (3000,), "b": (2000,)}
    p = {k: jnp.zeros(v, jnp.float32) for k, v in shapes.items()}

    def fn(ga, gb):
        s = tx.init(p)
        u, _ = tx.update({"a": ga.reshape(3000), "b": gb.reshape(2000)},
                         s, p)
        return u["a"][None], u["b"][None]

    f = jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(P(axes), P(axes)),
        out_specs=(P(axes), P(axes)), check_vma=False))
    ua, ub = f(g_host["a"][:nranks], g_host["b"][:nranks])
    # sgd(1.0) => update = -reduced_grad
    return {"a": -np.asarray(ua)[0], "b": -np.asarray(ub)[0]}


@pytest.mark.parametrize("shape,threshold", [((2, 4), 4096 * 4),
                                             ((2, 4), 1 << 20),
                                             ((2, 2), 4096 * 4),
                                             ((2, 2), 1 << 20)],
                         ids=["2x4", "2x4_one_bucket", "2x2",
                              "2x2_one_bucket"])
def test_grad_consistency_mesh_sum_vs_flat(rng, shape, threshold,
                                           mesh2d, mesh2x2):
    """Mesh-routed int8 SUM on the 2x2 (4-device) and 2x4 (8-device)
    simulated meshes matches the flat-axis fp32 reference within the
    documented int8_ef bound, whether the 5000-float tree splits into
    two buckets (the 16 KiB threshold) or rides one whose block grid
    straddles the two leaves (1 MiB)."""
    nranks = int(np.prod(shape))
    mesh = mesh2d if nranks == 8 else mesh2x2
    g_host = {"a": (rng.standard_normal((8, 3000)) * 2).astype(
        np.float32), "b": rng.standard_normal((8, 2000)).astype(
        np.float32)}
    got = _routed_grad(mesh, ("cross", "local"), PLAN_Q, nranks,
                       g_host, threshold=threshold)
    for k in ("a", "b"):
        want = g_host[k][:nranks].sum(axis=0)
        err = np.abs(got[k] - want)
        # per-element bound: r*(Σ s_rank + s_red) per int8 hop, with
        # the cross hop quantizing LOCAL SUMS of the 2-sigma data;
        # measured q99 is ~0.10 on the 2x4 mesh.
        assert np.quantile(err / (np.abs(want) + 1.0), 0.99) < 0.15, \
            (k, err.max())


def test_grad_consistency_adasum_across_shapes(rng, mesh2d, mesh2x2):
    """Adasum routed on 2x4 and 2x2 meshes: each matches ITS OWN
    hierarchical numpy reference (different factorization => different
    local groups) within the int8 bound."""
    x = (rng.standard_normal((8, 4096)) * 1.5).astype(np.float32)
    for mesh, nranks, nl in ((mesh2d, 8, 4), (mesh2x2, 4, 2)):
        key = jax.random.PRNGKey(9)
        f = jax.jit(jax.shard_map(
            lambda v: C.mesh_allreduce(v.reshape(4096),
                                       C.ReduceOp.ADASUM, PLAN_Q,
                                       key=key)[None],
            mesh=mesh, in_specs=P(("cross", "local")),
            out_specs=P(("cross", "local"))))
        out = np.asarray(f(x[:nranks]))
        expected = adasum_lib.adasum_allreduce_reference(
            [x[:nranks][:nl].mean(axis=0), x[:nranks][nl:].mean(axis=0)])
        err = np.abs(out[0] - expected)
        assert np.quantile(err / (np.abs(expected) + 1.0), 0.99) < 0.05


def test_ef_residual_survives_elastic_reshard(mesh2d, mesh2x2, rng):
    """The elastic contract (ShardedOptimizer.gather_state's residual
    rule applied to the replicated surface): carry Σ_ranks residual
    across a mesh change, hand it to the new world's rank 0, and the
    pending correction is preserved — the next routed reduction in the
    NEW (2x2) world applies the OLD (2x4) world's accumulated
    quantization error."""
    n = C._Q_BLOCK  # one int8 block per rank chunk keeps shapes easy
    g_host = (rng.standard_normal((8, n)) * 3).astype(np.float32)
    key = jax.random.PRNGKey(21)

    # Old world: one quantized reduction, gather residual as its psum.
    def old_world(v):
        y, r = C.mesh_allreduce(v.reshape(n), C.ReduceOp.SUM, PLAN_Q,
                                key=key, return_residual=True)
        return y[None], jax.lax.psum(r, ("cross", "local"))[None]

    f_old = jax.jit(jax.shard_map(
        old_world, mesh=mesh2d, in_specs=P(("cross", "local")),
        out_specs=(P(("cross", "local")), P(("cross", "local")))))
    y_old, r_sum = f_old(g_host)
    y_old, r_sum = np.asarray(y_old)[0], np.asarray(r_sum)[0]
    want = g_host.sum(axis=0)
    pending = want - y_old
    np.testing.assert_allclose(r_sum, pending, atol=1e-3)

    # New world (2x2): rank 0 carries the old residual; reducing ZERO
    # gradients + the carried residual must reproduce the pending
    # correction within the new world's own quantization error.
    r0 = jnp.asarray(r_sum)

    def new_world(z):
        me = (jax.lax.axis_index("cross") == 0) & \
            (jax.lax.axis_index("local") == 0)
        corrected = z.reshape(n) + jnp.where(me, r0, jnp.zeros_like(r0))
        y, _ = C.mesh_allreduce(corrected, C.ReduceOp.SUM, PLAN_Q,
                                key=jax.random.fold_in(key, 1),
                                return_residual=True)
        return y[None]

    zeros = np.zeros((4, n), np.float32)
    f_new = jax.jit(jax.shard_map(
        new_world, mesh=mesh2x2, in_specs=P(("cross", "local")),
        out_specs=P(("cross", "local"))))
    got = np.asarray(f_new(zeros))[0]
    # The carried correction survives the reshard: reducing it in the
    # new world returns the old pending error (within one more int8
    # rounding of a residual-sized payload — far below the signal).
    np.testing.assert_allclose(got, pending, atol=np.abs(
        pending).max() * 0.1 + 1e-3)


# -- autotuner route dimension ----------------------------------------------

def test_autotuner_route_dimension():
    from horovod_tpu.common.autotune import Autotuner

    tuner = Autotuner(candidates_bytes=(1024,), warmup_samples=0,
                      steps_per_sample=1, tune_route=True,
                      route_candidates=("flat", "staged_int8"))
    assert tuner.current_route in ("flat", "staged_int8")
    seen = set()
    for _ in range(30):
        seen.add(tuner.feed_full(4096.0, 0.01).route)
        if tuner.done:
            break
    assert seen <= {"flat", "staged_int8"}
    assert len(seen) == 2  # both route candidates explored


def test_autotuner_route_logged_csv(tmp_path):
    from horovod_tpu.common.autotune import Autotuner

    log = tmp_path / "tune.csv"
    tuner = Autotuner(candidates_bytes=(1024,), warmup_samples=0,
                      steps_per_sample=1, tune_route=True,
                      log_file=str(log))
    for _ in range(3):
        tuner.feed(1024.0, 0.01)
    lines = log.read_text().splitlines()
    assert lines[0].split(",")[:2] == ["unix_time", "threshold_bytes"]
    assert "route" in lines[0]
    assert any(any(r in l for r in ("flat", "staged", "adasum"))
               for l in lines[1:])


def test_stepper_joint_route_rebuilds(hvd):
    from horovod_tpu.common.autotune import Autotuner

    tuner = Autotuner(candidates_bytes=(1024,), warmup_samples=0,
                      steps_per_sample=1, tune_route=True,
                      route_candidates=("flat", "staged"))
    built = []

    def build(threshold, hier, comp, route):
        built.append((threshold, hier, comp, route))

        def step(x):
            return x + 1
        return step

    stepper = optim.AutotunedStepper(build, grad_bytes=4096,
                                     tuner=tuner, block=False)
    for i in range(12):
        stepper(jnp.ones(()))
        if stepper.rebuilds >= 1:
            break
    assert stepper.rebuilds >= 1
    assert {b[3] for b in built} >= {"flat", "staged"}
    assert stepper.route in ("flat", "staged")


# -- route= on the sharded (ZeRO-1/FSDP) surfaces ---------------------------
#
# The PR 6 follow-up (ROADMAP item 1): staged mesh routing must not be
# flat-only on sharded state. The shard grid spans ALL plan axes
# (fast-axis-major — mesh_reducescatter's descent layout), the gradient
# RS rides the per-axis wires, and the update AG inverts it.

def _sm(mesh, f, ins, outs):
    return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=ins,
                                 out_specs=outs, check_vma=False))


@pytest.fixture()
def sharded_problem(rng):
    params = {"w": np.zeros((64, 4), np.float32),
              "b": np.zeros((4,), np.float32)}
    X = rng.standard_normal((8, 16, 64)).astype(np.float32)
    W = rng.standard_normal((64, 4)).astype(np.float32)
    Y = np.einsum("rbi,ij->rbj", X, W).astype(np.float32)
    return params, X, Y


def _sharded_loss(p, xb, yb):
    return jnp.mean((xb @ p["w"] + p["b"] - yb) ** 2)


def _run_sharded(mesh, axes, route, params, X, Y, steps=4,
                 compression=None):
    import optax

    tx = optim.ShardedOptimizer(optax.adamw(1e-2), axis_name="hvd",
                                route=route, compression=compression)
    sspec = tx.state_specs(params)

    def step(p, s, xb, yb):
        l, g = jax.value_and_grad(_sharded_loss)(p, xb[0], yb[0])
        u, s = tx.update(g, s, p)
        p = jax.tree.map(lambda a, b: a + b, p, u)
        return p, s, jax.lax.pmean(l, axes)

    stepf = _sm(mesh, step, (P(), sspec, P(axes), P(axes)),
                (P(), sspec, P()))
    initf = _sm(mesh, lambda p: tx.init(p), (P(),), sspec)
    p = jax.tree.map(jnp.asarray, params)
    s = initf(p)
    for _ in range(steps):
        p, s, loss = stepf(p, s, jnp.asarray(X), jnp.asarray(Y))
    return p, s, float(loss), tx, sspec


def _replicated_reference(params, X, Y, steps=4):
    import optax

    inner = optax.adamw(1e-2)
    p = jax.tree.map(jnp.asarray, params)
    s = inner.init(p)
    for _ in range(steps):
        g = jax.grad(lambda pp: jnp.mean(jnp.stack(
            [_sharded_loss(pp, jnp.asarray(X)[r], jnp.asarray(Y)[r])
             for r in range(8)])))(p)
        u, s = inner.update(g, s, p)
        p = jax.tree.map(lambda a, b: a + b, p, u)
    return p


def test_mesh_reducescatter_residual_sum_invariant(mesh2d, rng):
    """mesh_reducescatter(return_residual=True): reconstructed result +
    Σ_ranks residual == the exact fp32 sum (the error-feedback contract
    the routed ZeRO-1 path carries)."""
    L = 8 * C._Q_BLOCK
    x = (rng.standard_normal((8, L)) * 2).astype(np.float32)

    def f(v):
        shard, res = C.mesh_reducescatter(
            v.reshape(L), C.ReduceOp.SUM, PLAN_QQ, return_residual=True)
        full = C.mesh_allgather(shard,
                                PLAN_QQ.reversed().with_wires("none"))
        return full[None], jax.lax.psum(res, ("cross", "local"))[None]

    g = _sm(mesh2d, f, P(("cross", "local")),
            (P(("cross", "local")), P(("cross", "local"))))
    out, corr = g(x)
    approx = np.asarray(out)[0].astype(np.float64)
    corr = np.asarray(corr)[0].astype(np.float64)
    exact = x.astype(np.float64).sum(0)
    np.testing.assert_allclose(approx + corr, exact, atol=2e-2)
    # And the residual is genuinely nonzero (int8 wires did round).
    assert np.abs(corr).max() > 0


def test_sharded_optimizer_routed_matches_replicated(mesh2d,
                                                     sharded_problem):
    """ShardedOptimizer(route="staged" fp32) == replicated DP training
    step-for-step (exact wires, different schedule only)."""
    params, X, Y = sharded_problem
    p, _, _, _, _ = _run_sharded(mesh2d, ("cross", "local"), PLAN,
                                 params, X, Y)
    ref = _replicated_reference(params, X, Y)
    np.testing.assert_allclose(np.asarray(p["w"]),
                               np.asarray(ref["w"]), atol=1e-5)


def test_sharded_optimizer_routed_int8_ef_close_to_fp32(mesh2d,
                                                        sharded_problem):
    """route=staged_int8 + compression="int8_ef" on the sharded state:
    the staged quantized RS (residual carried through
    mesh_reducescatter) stays within int8_ef tolerance of the fp32
    trajectory.

    What the tolerance is: AdamW from zero moves every weight by about
    ``lr`` a step whatever its gradient's size, so after six steps
    ``scale`` is ~6 lr, and an element whose mean gradient is within the
    int8 rounding noise can take a step the other way: 2 lr, a third of
    ``scale``, each time. Over eight data seeds (PR 29) the largest
    deviation was 0.14-0.47 of ``scale`` routed and 0.23-0.58 on the
    unrouted ``int8_ef`` twin, with at most 2 of the 256 weights beyond
    one such step: the routed path drifts no more than its twin, and the
    old bound (0.35, one step) was thinner than either's spread. Two
    steps, and few elements beyond one."""
    params, X, Y = sharded_problem
    p, s, loss, _, _ = _run_sharded(mesh2d, ("cross", "local"), PLAN_Q,
                                    params, X, Y, steps=6,
                                    compression="int8_ef")
    ref = _replicated_reference(params, X, Y, steps=6)
    dev = np.abs(np.asarray(p["w"]) - np.asarray(ref["w"]))
    scale = max(np.abs(np.asarray(ref["w"])).max(), 1e-6)
    assert dev.max() <= 0.7 * scale, (dev.max(), scale)
    assert (dev > 0.35 * scale).sum() <= 4, (dev > 0.35 * scale).sum()
    assert np.isfinite(loss)
    # The EF state really is mesh-sharded: residual length is the
    # 8-rank padded grid, carried as P((cross, local)) shards.
    assert isinstance(s.residual, list) and s.residual[0].ndim == 1


def test_sharded_routed_gather_reshard_roundtrip(mesh2d,
                                                 sharded_problem):
    """gather_state/reshard_state under a route: the residual's psum
    (the pending correction) and the inner state survive the
    roundtrip."""
    params, X, Y = sharded_problem
    p, s, _, tx, sspec = _run_sharded(mesh2d, ("cross", "local"),
                                      PLAN_Q, params, X, Y, steps=2,
                                      compression="int8_ef")
    gather = _sm(mesh2d, lambda st, pp: tx.gather_state(st, pp),
                 (sspec, P()), P())
    reshard = _sm(mesh2d, lambda sf: tx.reshard_state(sf), (P(),),
                  sspec)
    full = gather(s, p)
    s2 = reshard(full)
    full2 = gather(s2, p)
    for a, b in zip(jax.tree.leaves(full.inner),
                    jax.tree.leaves(full2.inner)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6)
    r0 = sum(np.asarray(l).astype(np.float64).sum()
             for l in jax.tree.leaves(s.residual))
    r1 = sum(np.asarray(l).astype(np.float64).sum()
             for l in jax.tree.leaves(s2.residual))
    np.testing.assert_allclose(r0, r1, atol=1e-4)


def test_fsdp_routed_matches_replicated(mesh2d, sharded_problem):
    """FSDPOptimizer(route=): params at rest shard over both mesh axes;
    gather/update through the staged router reproduce replicated DP."""
    import optax

    params, X, Y = sharded_problem
    fs = optim.FSDPOptimizer(optax.adamw(1e-2), axis_name="hvd",
                             route=PLAN)
    sspecs = fs.shard_specs(params)
    stspecs = fs.state_specs(params)
    setup = _sm(mesh2d,
                lambda p: ((lambda sh: (sh, fs.init(sh)))
                           (fs.shard_params(p))),
                (P(),), (sspecs, stspecs))

    def step(shards, st, xb, yb):
        full = fs.gather_params(shards)
        l, g = jax.value_and_grad(_sharded_loss)(full, xb[0], yb[0])
        shards, st = fs.update(g, st, shards)
        return shards, st, jax.lax.pmean(l, ("cross", "local"))

    stepf = _sm(mesh2d, step,
                (sspecs, stspecs, P(("cross", "local")),
                 P(("cross", "local"))),
                (sspecs, stspecs, P()))
    shards, st = setup(jax.tree.map(jnp.asarray, params))
    # At-rest memory: each shard leaf holds 1/8 of its bucket.
    for sh in shards:
        local = np.asarray(sh.addressable_data(0)).shape[-1]
        assert local * 8 == sh.shape[0]
    for _ in range(4):
        shards, st, _ = stepf(shards, st, jnp.asarray(X),
                              jnp.asarray(Y))
    gp = _sm(mesh2d, lambda sh: fs.gather_params(sh), (sspecs,), P())
    full = gp(shards)
    ref = _replicated_reference(params, X, Y)
    np.testing.assert_allclose(np.asarray(full["w"]),
                               np.asarray(ref["w"]), atol=1e-5)


def test_sharded_route_falls_back_on_flat_mesh(sharded_problem, hvd):
    """A route whose axes are NOT bound in the live trace (e.g. an
    HVD_TPU_ROUTE default reaching a flat-axis step) falls back to the
    flat rank axis on the sharded surfaces — same contract as the
    reduction surfaces (a route must never break a flat-world
    program). The shards then follow the 1-D grid and training still
    reduces."""
    import optax

    params, X, Y = sharded_problem
    tx = optim.ShardedOptimizer(optax.sgd(0.1),
                                axis_name=hvd.rank_axis(),
                                route="staged")
    assert tx.route is not None  # pinned...
    ax = hvd.rank_axis()

    @hvd.spmd_step(in_specs=(P(), P(ax), P(ax)), out_specs=(P(), P()))
    def one_step(p, xb, yb):
        s = tx.init(p)  # ...but only the flat mesh is live
        l, g = jax.value_and_grad(_sharded_loss)(p, xb[0], yb[0])
        u, s = tx.update(g, s, p)
        p = jax.tree.map(lambda a, b: a + b, p, u)
        return p, jax.lax.pmean(l, ax)

    p, loss = one_step(jax.tree.map(jnp.asarray, params),
                       jnp.asarray(X), jnp.asarray(Y))
    assert np.isfinite(float(loss))
    # The update really reduced over the flat axis: matches a 1-step
    # replicated reference.
    ref = jax.tree.map(jnp.asarray, params)
    g = jax.grad(lambda pp: jnp.mean(jnp.stack(
        [_sharded_loss(pp, jnp.asarray(X)[r], jnp.asarray(Y)[r])
         for r in range(8)])))(ref)
    ref = jax.tree.map(lambda a, b: a - 0.1 * b, ref, g)
    np.testing.assert_allclose(np.asarray(p["w"]),
                               np.asarray(ref["w"]), atol=1e-5)
