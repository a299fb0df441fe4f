"""End-to-end smoke for the reduce-safe quantized allreduce
(compression="int8_ef"): the toy MLP trained 20 steps on CPU with int8
gradients + error feedback must reach a final loss within 2% of the
fp32 run — the tentpole's convergence claim as a tier-1 gate
(docs/compression.md). Plus fast sanity for the eager engine's
quantized path and the ZeRO-1 sharded variant.
"""

import numpy as np
import optax
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd_mod


def _mlp_data(rng, n_ranks=8, per_rank=16, dim=64, classes=10):
    X = rng.standard_normal((n_ranks, per_rank, dim)).astype(np.float32)
    W = rng.standard_normal((dim, classes)).astype(np.float32)
    y = (X.reshape(-1, dim) @ W).argmax(-1).reshape(n_ranks, per_rank)
    return X, y.astype(np.int32)


def _train_mlp(hvd, compression, steps=20, lr=0.1, seed=0):
    from horovod_tpu.models import MLP

    ctx = hvd_mod.init()
    ax = ctx.config.rank_axis
    rng = np.random.default_rng(seed)
    X, y = _mlp_data(rng)
    model = MLP(features=(64, 32), num_classes=10)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.asarray(X[0]))["params"]
    tx = hvd_mod.DistributedOptimizer(optax.sgd(lr), axis_name=ax,
                                      compression=compression,
                                      quantize_min_bucket_bytes=0)

    def loss_fn(p, xb, yb):
        logits = model.apply({"params": p}, xb)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, yb).mean()

    @hvd_mod.spmd_step(in_specs=(P(), P(), P(ax), P(ax)),
                       out_specs=(P(), P(), P()))
    def step(p, s, xb, yb):
        # per-rank block: (1, per_rank, dim) -> this rank's microbatch.
        l, g = jax.value_and_grad(loss_fn)(p, xb[0], yb[0])
        u, s = tx.update(g, s, p)
        return optax.apply_updates(p, u), s, jax.lax.pmean(l, ax)

    p, s = params, tx.init(params)
    l = None
    for _ in range(steps):
        p, s, l = step(p, s, jnp.asarray(X), jnp.asarray(y))
    return float(np.asarray(l))


def test_int8_ef_mlp_tracks_fp32_within_2pct(hvd):
    """THE acceptance gate: 20 SGD steps on the toy MLP classifier,
    int8_ef vs fp32, final loss within 2%."""
    l_fp32 = _train_mlp(hvd, compression=None)
    l_ef = _train_mlp(hvd, compression="int8_ef")
    assert l_ef == l_ef and l_fp32 == l_fp32  # no NaNs
    rel = abs(l_ef - l_fp32) / max(abs(l_fp32), 1e-9)
    assert rel < 0.02, (l_fp32, l_ef, rel)


def test_eager_quantized_allreduce_matches_sum(hvd):
    # >= HVD_TPU_QUANTIZE_MIN_BYTES (64 KiB) so the int8 path engages;
    # smaller eager payloads ride bf16 (tested below).
    rng = np.random.default_rng(3)
    x = (rng.standard_normal((8, 20000)) * 2).astype(np.float32)
    out = hvd.gather(hvd.allreduce(
        hvd.scatter(x), op=hvd.Sum,
        compression=hvd.Compression.int8_ef, name="e2e_q"))
    want = x.astype(np.float64).sum(0)
    bound = (0.5 * sum(np.abs(x[r]).max() for r in range(8))
             + 0.5 * np.abs(want).max()) / 127 + 1e-6
    assert np.abs(out[0] - want).max() <= bound
    for r in range(1, 8):
        np.testing.assert_array_equal(out[r], out[0])


def test_eager_small_payload_rides_bf16_not_int8(hvd):
    """Below the quantize-min threshold the eager path must NOT pad a
    tiny tensor onto the n*4096 int8 grid (more wire than fp32!) — it
    rides the bf16 cast instead, whose error is far below the int8
    bound for the same data."""
    rng = np.random.default_rng(7)
    x = (rng.standard_normal((8, 33)) * 2).astype(np.float32)
    out = hvd.gather(hvd.allreduce(
        hvd.scatter(x), op=hvd.Sum,
        compression=hvd.Compression.int8_ef, name="e2e_small"))
    want = x.astype(np.float64).sum(0)
    # bf16 cast error: ~2^-8 relative per summand.
    assert np.abs(out[0] - want).max() <= \
        8 * np.abs(x).max() * 2 ** -8 + 1e-6


def test_eager_quantized_skips_integer_payloads(hvd):
    """An int payload under the int8_ef default must ride uncompressed
    (exact), not through the float quantizer."""
    rng = np.random.default_rng(4)
    xi = rng.integers(-50, 50, (8, 31)).astype(np.int32)
    out = hvd.gather(hvd.allreduce(
        hvd.scatter(xi), op=hvd.Sum,
        compression=hvd.Compression.int8_ef, name="e2e_qi"))
    np.testing.assert_array_equal(out[0], xi.sum(0))


def test_eager_grouped_per_bucket_wires(hvd):
    """grouped_allreduce with int8_ef: the large bucket quantizes, the
    tiny bucket rides bf16 — both land within their format's bound."""
    rng = np.random.default_rng(5)
    tree = {"big": rng.standard_normal((8, 40000)).astype(np.float32),
            "small": rng.standard_normal((8, 16)).astype(np.float32)}
    out = hvd.grouped_allreduce(tree, op=hvd.Sum, name="e2e_tree",
                                compression=hvd.Compression.int8_ef)
    wb = tree["big"].astype(np.float64).sum(0)
    ws = tree["small"].astype(np.float64).sum(0)
    big_bound = (0.5 * sum(np.abs(tree["big"][r]).max()
                           for r in range(8))
                 + 0.5 * np.abs(wb).max()) / 127 + 1e-6
    assert np.abs(np.asarray(out["big"])[0] - wb).max() <= big_bound
    # bf16 wire: 8 ulps at bf16 precision of the summands' scale.
    assert np.abs(np.asarray(out["small"])[0] - ws).max() <= \
        np.abs(ws).max() * 2 ** -6 + 8 * 2 ** -8


def test_zero1_int8_ef_trains_and_shards(hvd):
    """ShardedOptimizer(compression="int8_ef"): loss decreases, the
    state carries residual + step, and vector inner-state leaves stay
    1/n-sharded."""
    from horovod_tpu.optim import _EFShardState

    ax = hvd.rank_axis()
    rng = np.random.default_rng(6)
    Xs = rng.standard_normal((16, 500)).astype(np.float32)
    Ys = (Xs @ rng.standard_normal((500, 3))).astype(np.float32)
    X = np.broadcast_to(Xs, (8,) + Xs.shape).reshape(8 * 16, 500)
    Y = np.broadcast_to(Ys, (8,) + Ys.shape).reshape(8 * 16, 3)
    p0 = {"w": jnp.zeros((500, 3), jnp.float32),
          "b": jnp.zeros((3,), jnp.float32)}

    tx = hvd.ShardedOptimizer(optax.adam(1e-2), axis_name=ax,
                              compression="int8_ef")
    specs = tx.state_specs(p0)

    def loss_fn(p, xb, yb):
        return jnp.mean((xb @ p["w"] + p["b"] - yb) ** 2)

    @hvd.spmd_step(in_specs=(P(),), out_specs=(specs,))
    def init_s(p):
        return (tx.init(p),)

    @hvd.spmd_step(in_specs=(P(), specs, P(ax), P(ax)),
                   out_specs=(P(), specs, P()))
    def step_s(p, s, xb, yb):
        l, g = jax.value_and_grad(loss_fn)(p, xb, yb)
        u, s = tx.update(g, s, p)
        return optax.apply_updates(p, u), s, jax.lax.pmean(l, ax)

    (s,) = init_s(p0)
    p = p0
    losses = []
    for _ in range(10):
        p, s, l = step_s(p, s, jnp.asarray(X), jnp.asarray(Y))
        losses.append(float(np.asarray(l)))
    assert all(b < a for a, b in zip(losses, losses[1:])), losses
    assert losses[-1] < 0.7 * losses[0], losses
    assert isinstance(s, _EFShardState)
    assert int(np.asarray(s.step).reshape(-1)[0]) == 10
    for leaf in jax.tree.leaves(s.inner):
        if hasattr(leaf, "ndim") and leaf.ndim:
            shard = leaf.addressable_shards[0].data
            assert shard.size * hvd.size() == leaf.size


def test_zero1_compression_state_mismatch_raises(hvd):
    """A state built without compression cannot be consumed by an
    int8_ef update (different shard grid + missing residual) — the
    mismatch must be a loud error, not silent corruption."""
    from horovod_tpu import sharded_init, sharded_update

    ax = hvd.rank_axis()
    p0 = {"w": jnp.zeros((100,), jnp.float32)}

    @hvd.spmd_step(in_specs=(P(),), out_specs=P())
    def go(xb):
        s = sharded_init(optax.sgd(0.1), p0, ax)  # no compression
        u, _ = sharded_update(optax.sgd(0.1), p0, s, p0, ax,
                              compression="int8_ef")
        return xb

    with pytest.raises(ValueError, match="must match the sharded_init"):
        go(jnp.zeros((8, 1), jnp.float32))


# -- the bucket moves its bytes once (ISSUE 45) ------------------------------
#
# The residual leaves the quantise kernel, the gathered int8 goes through
# the dequantise kernel with the mean on its scales, the buckets are packed
# onto the block grid and every bucket's keys are derived at once: the wire,
# the rounding and the state are what they were. The plain reference is the
# reduction as it stood before, kept here.

def _reduction_before(x, op, axis_name, key):
    """``collectives.quantized_allreduce(x, op, axis_name, key=key,
    return_residual=True)`` as it stood before PR 45: the pad and the
    slices its own, a ``fold_in`` a hop, the residual and the result by
    dequantising whole buffers in jnp, the mean divided after."""
    from jax import lax

    from horovod_tpu.ops import collectives as C
    from horovod_tpu.ops import pallas_kernels as pk

    def chunks(flat, n, hop):
        if key is None:
            q, s, _ = pk.quantize_int8(flat)
        else:
            q, s, _ = pk.quantize_int8_stochastic(
                flat, jax.random.fold_in(key, hop))
        chunk = flat.shape[0] // n
        return q.reshape(n, chunk // 128, 128), s.reshape(n, chunk // 4096)

    n = lax.axis_size(axis_name)
    size = int(x.size)
    chunk = -(-size // (n * 4096)) * 4096
    flat = jnp.pad(x.astype(jnp.float32).reshape(-1), (0, n * chunk - size))
    q, s = chunks(flat, n, 0)
    qx = lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0)
    sx = lax.all_to_all(s, axis_name, split_axis=0, concat_axis=0)
    own = jnp.sum(C._deq(qx, sx), axis=0)
    residual = flat - C._deq(q, s).reshape(flat.shape)
    qr, sr = chunks(own, 1, 1)
    qg = lax.all_gather(qr[0], axis_name)
    sg = lax.all_gather(sr[0], axis_name)
    y = C._deq(qg, sg).reshape(-1)[:size].reshape(x.shape)
    if op == C.ReduceOp.AVERAGE:
        y = y / jnp.asarray(n, y.dtype)
    me = lax.axis_index(axis_name)
    cur = lax.dynamic_slice_in_dim(residual, me * chunk, chunk)
    residual = lax.dynamic_update_slice_in_dim(
        residual, cur + (own - C._deq(qr[0], sr[0])), me * chunk, 0)
    return y.astype(x.dtype), residual[:size].reshape(x.shape)


def _one_rounding(x):
    """``x - q * scale`` is one fused multiply-add in some of the programs
    XLA:CPU compiles and two roundings in others
    (tests/test_pallas_kernels.py), so a residual is held to the
    reference's within one rounding of the product, not to its bits."""
    return 2.0 ** -22 * float(np.abs(np.asarray(x)).max())


def _per_rank(fn, ranks, *stacked):
    """``fn`` on each rank's row of the ``(ranks, ...)`` operands under a
    flat ``hvd`` mesh of ``ranks`` CPU devices; results stacked alike."""
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:ranks]), ("hvd",))

    def body(*rows):
        out = fn(*jax.tree.map(lambda v: v[0], rows))
        return jax.tree.map(lambda v: v[None], out)

    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("hvd"),
                                 out_specs=P("hvd"), check_vma=False))(
        *stacked)


def _check_against_the_reduction_before(ranks, op, keyed, size, operands,
                                        dtype=np.float32):
    from horovod_tpu.ops import collectives as C

    op = {"average": C.ReduceOp.AVERAGE, "sum": C.ReduceOp.SUM}[op]
    rng = np.random.default_rng(size + ranks)
    x = jnp.asarray(rng.standard_normal((ranks, size))
                    * np.exp(rng.standard_normal((ranks, size))), dtype)
    r = (rng.standard_normal((ranks, size)) * 0.02).astype(np.float32)
    key = jax.random.PRNGKey(7) if keyed else None

    def corrected(x, r):
        # One operand: the buffer in its own dtype, as any caller hands
        # it over; two: the fp32 sum the parent's optimizer formed.
        return x if operands == 1 else x.astype(jnp.float32) + r

    def now(x, r):
        if operands == 1:
            return C.quantized_allreduce(x, op, "hvd", key=key,
                                         return_residual=True)
        hops = None if key is None else (jax.random.fold_in(key, 0),
                                         jax.random.fold_in(key, 1))
        return C.quantized_allreduce(x, op, "hvd", key=key, _plus=r,
                                     _hop_keys=hops, return_residual=True)

    y, res = _per_rank(now, ranks, x, r)
    y_was, res_was = _per_rank(
        lambda x, r: _reduction_before(corrected(x, r), op, "hvd", key),
        ranks, x, r)
    assert y.dtype == y_was.dtype and res.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_was))
    np.testing.assert_allclose(np.asarray(res), np.asarray(res_was), rtol=0,
                               atol=_one_rounding(x))
    # every rank holds the same result
    assert (np.asarray(y) == np.asarray(y)[0]).all()


_SIZES = pytest.mark.parametrize("size", [50_000, 3 * 8 * 4096],
                                 ids=["ragged", "on_the_grid"])
_OPERANDS = pytest.mark.parametrize("operands", [1, 2],
                                    ids=["x", "x_plus_residual"])
_ROUNDINGS = pytest.mark.parametrize("keyed", [False, True],
                                     ids=["nearest", "stochastic"])
_OPS = pytest.mark.parametrize("op", ["average", "sum"])


@_OPERANDS
@_SIZES
@_ROUNDINGS
@_OPS
@pytest.mark.parametrize("ranks", [4, 8])
def test_quantized_allreduce_is_the_reduction_it_was(ranks, op, keyed, size,
                                                     operands):
    """``y`` to the bit (so q and the scales of both hops, the thresholds
    and the mean folded into the gathered scales), the residual to one
    rounding; with the corrected gradient handed in whole and as its two
    operands, formed in the kernel, under the hop keys derived outside."""
    _check_against_the_reduction_before(ranks, op, keyed, size, operands)


@_OPERANDS
@_SIZES
@_ROUNDINGS
@_OPS
def test_quantized_allreduce_of_bf16_is_the_reduction_it_was(op, keyed, size,
                                                             operands):
    """A bf16 buffer: what each hop quantises is fp32 all the way, as it
    was when the buffer was cast on entry. On the grid nothing pads the
    buffer, so nothing casts it either: the owned chunk's sum must not
    pass through bf16 before the second hop rounds it (it did, in this
    PR's first form; the case on the grid is the one that showed it)."""
    _check_against_the_reduction_before(4, op, keyed, size, operands,
                                        jnp.bfloat16)


@_OPERANDS
@_SIZES
@_ROUNDINGS
@_OPS
@pytest.mark.parametrize("ranks", [3, 6])
def test_quantized_allreduce_over_ranks_no_power_of_two(ranks, op, keyed,
                                                        size, operands):
    """Where 1/ranks is not exact the mean is divided after the
    dequantise, as it was, and not folded into the gathered scales."""
    _check_against_the_reduction_before(ranks, op, keyed, size, operands)


@pytest.mark.parametrize("step", [0, 7, 2 ** 31 - 5])
def test_a_steps_keys_derived_at_once_are_the_keys(step):
    """``_ef_keys``: every bucket's ``_ef_key(step, i)`` and the two hop
    keys ``quantized_allreduce`` folds from it, to the bit, for every
    bucket index of a 25-bucket step (and the router's five hops)."""
    from horovod_tpu import optim

    step = jnp.asarray(step, jnp.int32)
    keys, hop_keys = jax.jit(lambda s: optim._ef_keys(s, 25, 5))(step)
    assert keys.shape == (25, 2) and hop_keys.shape == (25, 5, 2)
    for i in range(25):
        key = optim._ef_key(step, i)
        np.testing.assert_array_equal(np.asarray(keys[i]), np.asarray(key))
        for hop in range(5):
            np.testing.assert_array_equal(
                np.asarray(hop_keys[i, hop]),
                np.asarray(jax.random.fold_in(key, hop)))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("prescale", [1.0, 0.5], ids=["plain", "prescaled"])
@pytest.mark.parametrize("ranks", [4, 8])
def test_three_int8_ef_steps_leave_the_state_they_left(ranks, prescale,
                                                       dtype):
    """``DistributedOptimizer(compression="int8_ef")`` over three steps
    against the reduction as it stood (each int8 bucket: ``(g + r) *
    prescale`` whole through :func:`_reduction_before` under
    ``_ef_key(step, i)``, the small bucket on bf16): the first step's
    updates to the bit, every later one and the ``_EFState`` residual but
    for what one rounding of a residual can move (an element whose
    threshold falls inside that rounding takes the other neighbour)."""
    from horovod_tpu import optim
    from horovod_tpu.common import fusion
    from horovod_tpu.ops import collectives as C

    rng = np.random.default_rng(ranks)
    params = {"a": jnp.zeros((300, 257), dtype),
              "b": jnp.zeros((1000, 130), dtype), "c": jnp.zeros((7,), dtype)}
    # bf16 gradients: the fp32 sum g + r is what is reduced, and the
    # result is rounded to the gradient's dtype once, after the postscale.
    threshold = 400_000 if dtype == "float32" else 200_000
    postscale = 1.0 if dtype == "float32" else 1.0 / 3.0
    tx = optim.DistributedOptimizer(
        optax.sgd(1.0), compression="int8_ef", axis_name="hvd",
        quantize_min_bucket_bytes=1024, fusion_threshold_bytes=threshold,
        prescale_factor=prescale, postscale_factor=postscale)

    def was(grads, residual, step):
        plan = fusion.assign_wire_dtypes(
            fusion.plan_fusion(grads, threshold), 1024)
        assert plan.wire_dtypes.count(fusion.WIRE_INT8) == 2
        ys, rs = [], []
        for i, (g, r) in enumerate(zip(fusion.fuse(grads, plan),
                                       fusion.fuse(residual, plan))):
            if plan.wire_dtypes[i] != fusion.WIRE_INT8:
                ys.append(C.allreduce(g.astype(jnp.bfloat16),
                                      C.ReduceOp.AVERAGE, "hvd", prescale,
                                      postscale).astype(g.dtype))
                rs.append(r)
                continue
            y, res = _reduction_before(
                (g.astype(jnp.float32) + r) * prescale, C.ReduceOp.AVERAGE,
                "hvd", optim._ef_key(step, i))
            ys.append((y * postscale).astype(g.dtype))
            rs.append(res / prescale)
        return fusion.unfuse(ys, plan), fusion.unfuse(rs, plan)

    def now(grads, state):
        updates, state = tx.update(grads, state, params)
        return updates, state

    state = jax.tree.map(
        lambda v: jnp.broadcast_to(v, (ranks,) + jnp.shape(v)),
        tx.init(params))
    residual_was = state.residual
    for step in range(3):
        grads = jax.tree.map(
            lambda p: jnp.asarray(rng.standard_normal((ranks,) + p.shape)
                                  * np.exp(rng.standard_normal(p.shape)),
                                  dtype), params)
        updates, state = _per_rank(now, ranks, grads, state)
        reduced_was, residual_was = _per_rank(
            lambda g, r: was(g, r, jnp.asarray(step, jnp.int32)), ranks,
            grads, residual_was)
        for name in params:
            got = -np.asarray(updates[name], np.float32)
            want = np.asarray(reduced_was[name], np.float32)
            if step == 0:
                np.testing.assert_array_equal(got, want)
            for got, want, within in (
                    (got, want, 1e-6 * np.abs(want).max()),
                    (np.asarray(state.residual[name]),
                     np.asarray(residual_was[name]),
                     _one_rounding(grads[name]))):
                assert (np.abs(got - want) > within).mean() < 1e-4, (
                    step, name)
    assert (np.asarray(state.step) == 3).all()
