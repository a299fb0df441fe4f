"""Hierarchical (cross×local) allreduce — the NCCLHierarchicalAllreduce
analog (reference nccl_operations.cc:190+): RS within the fast domain,
AR across, AG back. Simulated as a 2×4 mesh on 8 CPU devices."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.ops import collectives as C
from horovod_tpu.common import fusion


@pytest.fixture(scope="module")
def mesh2d():
    devs = np.array(jax.devices()).reshape(2, 4)
    return Mesh(devs, ("cross", "local"))


def test_hierarchical_allreduce_average(mesh2d, rng):
    x = rng.standard_normal((8, 6)).astype(np.float32)

    f = jax.jit(jax.shard_map(
        lambda v: C.hierarchical_allreduce(v, C.ReduceOp.AVERAGE,
                                           "local", "cross"),
        mesh=mesh2d, in_specs=P(("cross", "local")),
        out_specs=P(("cross", "local"))))
    out = np.asarray(f(x))
    for r in range(8):
        np.testing.assert_allclose(out[r], x.mean(axis=0), rtol=1e-5,
                                   atol=1e-6)


def test_hierarchical_staged_matches_flat(mesh2d, rng):
    # The explicitly staged RS→AR→AG path must equal a flat allreduce.
    n = 16  # divisible by local size 4
    x = rng.standard_normal((8, n)).astype(np.float32)

    f = jax.jit(jax.shard_map(
        lambda v: C.hierarchical_allreduce_staged(
            v.reshape(n), C.ReduceOp.SUM, "local", "cross")[None],
        mesh=mesh2d, in_specs=P(("cross", "local")),
        out_specs=P(("cross", "local"))))
    out = np.asarray(f(x))
    for r in range(8):
        np.testing.assert_allclose(out[r], x.sum(axis=0), rtol=1e-4,
                                   atol=1e-4)


def test_staged_with_padding(mesh2d, rng):
    # Fusion-buffer path pads to local-size multiple before RS staging.
    n = 13  # NOT divisible by 4
    x = rng.standard_normal((8, n)).astype(np.float32)

    def per_rank(v):
        flat, orig = fusion.pad_to_multiple(v.reshape(n), 4)
        red = C.hierarchical_allreduce_staged(flat, C.ReduceOp.SUM,
                                              "local", "cross")
        return jax.lax.slice_in_dim(red, 0, orig)[None]

    f = jax.jit(jax.shard_map(per_rank, mesh=mesh2d,
                              in_specs=P(("cross", "local")),
                              out_specs=P(("cross", "local"))))
    out = np.asarray(f(x))
    np.testing.assert_allclose(out[3], x.sum(axis=0), rtol=1e-4, atol=1e-4)


def test_engine_hierarchical_config(rng):
    # Engine-level: hierarchical_allreduce knob + hier mesh wired through.
    import horovod_tpu as hvd
    from horovod_tpu.ops.eager import EagerEngine
    from horovod_tpu.common.config import configure

    ctx = hvd.init()
    cfg = configure(hierarchical_allreduce=True)
    devs = np.array(jax.devices()).reshape(2, 4)
    hier = Mesh(devs, ("cross", "local"))
    eng = EagerEngine(ctx.mesh, cfg.rank_axis, cfg, hier_mesh=hier)
    x = rng.standard_normal((8, 10)).astype(np.float32)
    out = eng.gather(eng.allreduce(eng.scatter(x), C.ReduceOp.AVERAGE))
    for r in range(8):
        np.testing.assert_allclose(out[r], x.mean(axis=0), rtol=1e-5,
                                   atol=1e-6)


def test_hierarchical_allgather_matches_flat(mesh2d, rng):
    # MPIHierarchicalAllgather analog: AG(local/ICI) → AG(cross/DCN) must
    # reproduce the flat allgather's global row order exactly.
    x = rng.standard_normal((8, 3, 5)).astype(np.float32)

    f = jax.jit(jax.shard_map(
        lambda v: C.hierarchical_allgather(
            v.reshape(v.shape[1:]), "local", "cross")[None],
        mesh=mesh2d, in_specs=P(("cross", "local")),
        out_specs=P(("cross", "local"))))
    out = np.asarray(f(x))
    expected = x.reshape(24, 5)
    for r in range(8):
        np.testing.assert_array_equal(out[r], expected)


def test_engine_hierarchical_allgather_config(rng):
    # HVD_TPU_HIERARCHICAL_ALLGATHER knob wired through the engine.
    import horovod_tpu as hvd
    from horovod_tpu.common.config import configure
    from horovod_tpu.ops.eager import EagerEngine

    ctx = hvd.init()
    cfg = configure(hierarchical_allgather=True)
    devs = np.array(jax.devices()).reshape(2, 4)
    hier = Mesh(devs, ("cross", "local"))
    eng = EagerEngine(ctx.mesh, cfg.rank_axis, cfg, hier_mesh=hier)
    x = rng.standard_normal((8, 2, 3)).astype(np.float32)
    out = eng.gather(eng.allgather(eng.scatter(x)))
    expected = x.reshape(16, 3)
    for r in range(8):
        np.testing.assert_array_equal(out[r], expected)


def test_adasum_hierarchical(mesh2d, rng):
    # AdasumGpuAllreduceOp analog: average within local, adasum across.
    from horovod_tpu.ops import adasum

    x = rng.standard_normal((8, 12)).astype(np.float32)
    f = jax.jit(jax.shard_map(
        lambda v: adasum.adasum_hierarchical(v, "local", "cross"),
        mesh=mesh2d, in_specs=P(("cross", "local")),
        out_specs=P(("cross", "local"))))
    out = np.asarray(f(x))
    # local groups: ranks 0-3 (cross 0), 4-7 (cross 1)
    a = x[:4].mean(axis=0)
    b = x[4:].mean(axis=0)
    expected = adasum.adasum_allreduce_reference([a, b])
    for r in range(8):
        np.testing.assert_allclose(out[r], expected, rtol=1e-4, atol=1e-4)


def test_quantized_hierarchical_allreduce(mesh2d, rng):
    """EQuARX-style int8 DCN hop (PAPERS.md): matches the exact flat
    reduction within block-absmax quantization error."""
    n = 4096  # divisible by local size 4
    x = rng.standard_normal((8, n)).astype(np.float32)

    f = jax.jit(jax.shard_map(
        lambda v: C.quantized_hierarchical_allreduce(
            v.reshape(n), C.ReduceOp.SUM, "local", "cross")[None],
        mesh=mesh2d, in_specs=P(("cross", "local")),
        out_specs=P(("cross", "local"))))
    out = np.asarray(f(x))
    want = x.sum(axis=0)
    # int8 block quantization: error per cross-shard bounded by
    # absmax/127 per 32x128 block; the summed result stays within ~2%
    # relative on standard-normal data.
    for r in range(8):
        err = np.abs(out[r] - want)
        scale = np.abs(want) + 1.0
        assert np.quantile(err / scale, 0.99) < 0.05, (
            err.max(), np.abs(want).max())

    # AVERAGE variant divides by world size.
    g = jax.jit(jax.shard_map(
        lambda v: C.quantized_hierarchical_allreduce(
            v.reshape(n), C.ReduceOp.AVERAGE, "local", "cross")[None],
        mesh=mesh2d, in_specs=P(("cross", "local")),
        out_specs=P(("cross", "local"))))
    out = np.asarray(g(x))
    np.testing.assert_allclose(out[0], np.asarray(f(x))[0] / 8.0,
                               rtol=1e-5, atol=1e-5)


def test_optimizer_quantized_cross(mesh2d, rng):
    """DistributedOptimizer(hierarchical, quantized_cross): the int8 DCN
    hop trains a regression to (near) the same point as the exact path."""
    import optax

    from horovod_tpu import optim

    W = rng.standard_normal((16, 1)).astype(np.float32)
    X = rng.standard_normal((8, 16)).astype(np.float32)
    Y = (X @ W).reshape(8)

    def make_step(tx):
        def step(p, s, xb, yb):
            def loss_fn(p):
                return jnp.mean((xb @ p["w"] - yb) ** 2)

            l, g = jax.value_and_grad(loss_fn)(p)
            u, s2 = tx.update(g, s, p)
            import optax as _o

            return _o.apply_updates(p, u), s2, jax.lax.pmean(
                l, ("cross", "local"))

        return step

    results = {}
    for name, kw in (("exact", {}), ("quantized",
                                     {"quantized_cross": True})):
        tx = optim.DistributedOptimizer(
            optax.adam(5e-2), hierarchical=True, local_axis="local",
            cross_axis="cross", **kw)
        p = {"w": jnp.zeros((16, 1), jnp.float32)}
        s = tx.init(p)
        f = jax.jit(jax.shard_map(
            make_step(tx), mesh=mesh2d,
            in_specs=(P(), P(), P(("cross", "local")),
                      P(("cross", "local"))),
            out_specs=(P(), P(), P()), check_vma=False))
        l0 = None
        for _ in range(60):
            p, s, l = f(p, s, X[:, None, :], Y[:, None])
            # One step in flight at a time: dozens of queued 8-device
            # steps can starve XLA:CPU's in-process rendezvous of
            # threads, which hangs and then ABORTS the whole pytest
            # process (seen three times at this line).
            jax.block_until_ready(l)
            l0 = l0 if l0 is not None else float(l)
        results[name] = (l0, float(l))
    # Both paths train (big drop), and the int8 hop lands on the same
    # trajectory as the exact reduction.
    for l0, lN in results.values():
        assert lN < l0 * 0.05, results
    e, q = results["exact"][1], results["quantized"][1]
    assert abs(q - e) < 0.02 * e + 1e-4, results


def test_optimizer_quantized_cross_validation():
    import optax

    from horovod_tpu import optim
    from horovod_tpu.ops.collectives import ReduceOp

    with pytest.raises(ValueError, match="hierarchical"):
        optim.DistributedOptimizer(optax.sgd(0.1), quantized_cross=True)
    with pytest.raises(ValueError, match="SUM/AVERAGE"):
        optim.DistributedOptimizer(optax.sgd(0.1), hierarchical=True,
                                   op=ReduceOp.ADASUM,
                                   quantized_cross=True)
