"""Scan-based gradient accumulation (docs/performance.md §4c): the
accumulation-equivalence suite — ``accum_steps=k`` gradients match the
fused large batch within dtype tolerance across the
{int8_ef, route, guard} compositions, with exactly ONE
collective round and ONE guard agreement per effective step, and the
error-feedback / loss-scale state transitions bitwise-matching the
unaccumulated path."""

import numpy as np
import optax
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import horovod_tpu as hvd_mod
from horovod_tpu import optim
from horovod_tpu.ops import collectives as C


def _spmd(ctx, f, nouts=1, check_vma=False):
    spec = P(ctx.config.rank_axis)
    outs = spec if nouts == 1 else tuple([spec] * nouts)
    return jax.jit(jax.shard_map(f, mesh=ctx.mesh, in_specs=spec,
                                 out_specs=outs, check_vma=check_vma))


def _count(fn, args, *needles):
    """Occurrences of collective primitives in the traced program —
    nested jaxprs included (shard_map bodies print inline)."""
    text = str(jax.make_jaxpr(fn)(*args))
    return sum(text.count(n) for n in needles)


def _mse(w, xb, yb):
    return jnp.mean((xb @ w - yb) ** 2)


# -- the scan driver ---------------------------------------------------------

def test_accumulate_gradients_matches_large_batch(hvd, rng):
    w = jnp.asarray(rng.standard_normal((6, 3)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((16, 6)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal((16, 3)).astype(np.float32))
    v_ref, g_ref = jax.value_and_grad(_mse)(w, x, y)
    for k in (1, 2, 4, 8):
        v, g = jax.jit(hvd_mod.accumulate_gradients(_mse, k))(w, x, y)
        np.testing.assert_allclose(v, v_ref, rtol=1e-5)
        np.testing.assert_allclose(g, g_ref, rtol=1e-5, atol=1e-6)


def test_accumulate_gradients_remat_policies_identical(hvd, rng):
    """Remat is a memory/recompute trade — the gradients are the same
    program, so every policy must agree numerically."""
    w = jnp.asarray(rng.standard_normal((6, 3)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((8, 6)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal((8, 3)).astype(np.float32))
    _, g_ref = jax.jit(hvd_mod.accumulate_gradients(_mse, 2))(w, x, y)
    for policy in ("full", "dots", "dots_no_batch"):
        _, g = jax.jit(hvd_mod.accumulate_gradients(
            _mse, 2, remat_policy=policy))(w, x, y)
        np.testing.assert_allclose(g, g_ref, rtol=1e-6, atol=1e-7)


def test_accumulate_gradients_has_aux_mean(hvd):
    def loss(w, xb):
        per = (xb * w).sum(axis=1)
        return per.mean(), {"stat": per.mean() * 2.0}

    w = jnp.ones((3,), jnp.float32)
    x = jnp.arange(12, dtype=jnp.float32).reshape(4, 3)
    (v1, aux1), g1 = jax.value_and_grad(loss, has_aux=True)(w, x)
    (v2, aux2), g2 = jax.jit(hvd_mod.accumulate_gradients(
        loss, 2, has_aux=True))(w, x)
    np.testing.assert_allclose(v1, v2, rtol=1e-6)
    np.testing.assert_allclose(aux1["stat"], aux2["stat"], rtol=1e-6)
    np.testing.assert_allclose(g1, g2, rtol=1e-6)


def test_accumulate_gradients_errors(hvd):
    with pytest.raises(ValueError, match="does not divide"):
        jax.jit(hvd_mod.accumulate_gradients(_mse, 3))(
            jnp.ones((6, 3)), jnp.ones((8, 6)), jnp.ones((8, 3)))
    with pytest.raises(ValueError, match="unknown remat policy"):
        hvd_mod.resolve_remat_policy("bogus")
    with pytest.raises(ValueError, match="accum_steps"):
        optim._resolve_accum_steps(0)


# -- DistributedGradFn(accum_steps=) -----------------------------------------

def test_gradfn_accum_equals_large_batch(hvd, rng):
    """accum_steps=2 under SPMD == the unaccumulated reduced gradient
    of the same (fused) per-rank batch, within dtype tolerance."""
    ctx = hvd_mod.init()
    ax = ctx.config.rank_axis
    w0 = rng.standard_normal((5,)).astype(np.float32)
    X = rng.standard_normal((8, 4, 5)).astype(np.float32)
    Y = rng.standard_normal((8, 4)).astype(np.float32)

    def loss(w, xb, yb):
        return jnp.mean((xb @ w - yb) ** 2)

    ref_fn = hvd_mod.DistributedGradFn(jax.grad(loss), axis_name=ax)
    acc_fn = hvd_mod.DistributedGradFn(loss, axis_name=ax,
                                       accum_steps=2)

    def step(xb, yb):
        wl = C.to_local(jnp.asarray(w0), ax)
        return (ref_fn(wl, xb[0], yb[0])[None],
                acc_fn(wl, xb[0], yb[0])[None])

    ref, acc = _spmd(ctx, step, nouts=2)(hvd.scatter(X), hvd.scatter(Y))
    np.testing.assert_allclose(np.asarray(acc)[0], np.asarray(ref)[0],
                               rtol=1e-5, atol=1e-6)


def test_gradfn_accum_one_collective_round(hvd, rng):
    """THE cadence acceptance gate: the accumulated step traces exactly
    as many collective rounds as the unaccumulated one — the scan adds
    arithmetic, never collectives."""
    ctx = hvd_mod.init()
    ax = ctx.config.rank_axis
    w0 = jnp.zeros((5,), jnp.float32)
    X = np.ones((8, 4, 5), np.float32)
    Y = np.ones((8, 4), np.float32)

    def loss(w, xb, yb):
        return jnp.mean((xb @ w - yb) ** 2)

    def build(fn):
        def step(xb, yb):
            wl = C.to_local(w0, ax)
            return fn(wl, xb[0], yb[0])[None]

        return jax.shard_map(step, mesh=ctx.mesh,
                             in_specs=P(ax), out_specs=P(ax),
                             check_vma=False)

    args = (hvd.scatter(X), hvd.scatter(Y))
    n_ref = _count(build(hvd_mod.DistributedGradFn(
        jax.grad(loss), axis_name=ax)), args, "psum")
    n_acc = _count(build(hvd_mod.DistributedGradFn(
        loss, axis_name=ax, accum_steps=4)), args, "psum")
    assert n_ref == n_acc, (n_ref, n_acc)


def test_gradfn_accum_one_guard_agreement(hvd, rng):
    """One pmin guard agreement per EFFECTIVE step (not per
    microbatch), agreed on the ACCUMULATED gradient."""
    ctx = hvd_mod.init()
    ax = ctx.config.rank_axis
    w0 = jnp.zeros((5,), jnp.float32)
    X = np.ones((8, 4, 5), np.float32)
    Y = np.ones((8, 4), np.float32)

    def loss(w, xb, yb):
        return jnp.mean((xb @ w - yb) ** 2)

    def build(fn):
        def step(xb, yb):
            wl = C.to_local(w0, ax)
            g, guard = fn(wl, xb[0], yb[0])
            return g[None]

        return jax.shard_map(step, mesh=ctx.mesh, in_specs=P(ax),
                             out_specs=P(ax), check_vma=False)

    args = (hvd.scatter(X), hvd.scatter(Y))
    n_ref = _count(build(hvd_mod.DistributedGradFn(
        jax.grad(loss), axis_name=ax, nonfinite_policy="skip_step")),
        args, "pmin")
    n_acc = _count(build(hvd_mod.DistributedGradFn(
        loss, axis_name=ax, accum_steps=4,
        nonfinite_policy="skip_step")), args, "pmin")
    assert n_ref == n_acc, (n_ref, n_acc)


def test_gradfn_accum_guard_skips_poisoned_microbatch(hvd, rng):
    """A NaN in ONE microbatch poisons the accumulated gradient; the
    guard must skip the whole effective step (zero grads, nonfinite
    counted) on every rank."""
    ctx = hvd_mod.init()
    ax = ctx.config.rank_axis
    w0 = rng.standard_normal((5,)).astype(np.float32)
    X = rng.standard_normal((8, 4, 5)).astype(np.float32)
    X[:, 0, 0] = np.nan  # microbatch 0 of 2, every rank
    Y = rng.standard_normal((8, 4)).astype(np.float32)

    def loss(w, xb, yb):
        return jnp.mean((xb @ w - yb) ** 2)

    gfn = hvd_mod.DistributedGradFn(loss, axis_name=ax, accum_steps=2,
                                    nonfinite_policy="skip_step")

    def step(xb, yb):
        wl = C.to_local(jnp.asarray(w0), ax)
        g, guard = gfn(wl, xb[0], yb[0])
        return g[None], guard.nonfinite_steps[None], guard.last_ok[None]

    g, bad, ok = _spmd(ctx, step, nouts=3)(hvd.scatter(X),
                                           hvd.scatter(Y))
    assert np.all(np.asarray(g) == 0.0)
    assert np.all(np.asarray(bad) == 1)
    assert np.all(np.asarray(ok) == 0)


def test_gradfn_accum_reduces_in_place_bitwise_the_flat_buckets(hvd, rng):
    """The shape of the reduction is chosen after the accumulation as
    before it: the accumulated mean, reduced where it lies, is bitwise
    what one flat bucket over ``collectives.allreduce`` gives."""
    from horovod_tpu.common import fusion

    ctx = hvd_mod.init()
    ax = ctx.config.rank_axis
    w0 = rng.standard_normal((64,)).astype(np.float32)
    X = rng.standard_normal((8, 4, 64)).astype(np.float32)
    Y = rng.standard_normal((8, 4)).astype(np.float32)

    def loss(w, xb, yb):
        return jnp.mean((xb @ w - yb) ** 2)

    gfn = hvd_mod.DistributedGradFn(loss, axis_name=ax, accum_steps=2,
                                    fusion_threshold_bytes=64)
    local = optim.accumulate_gradients(loss, 2)

    def reference(w, xb, yb):
        return fusion.fused_apply(
            local(w, xb, yb)[1],
            lambda f: C.allreduce(f, C.ReduceOp.AVERAGE, ax), 64)

    outs = []
    for fn in (reference, gfn):
        def step(xb, yb):
            wl = C.to_local(jnp.asarray(w0), ax)
            return fn(wl, xb[0], yb[0])[None]

        outs.append(np.asarray(
            _spmd(ctx, step)(hvd.scatter(X), hvd.scatter(Y))))
    np.testing.assert_array_equal(outs[0], outs[1])


def test_gradfn_accum_int8_ef_bitwise_state_transitions(hvd, rng):
    """The EF-residual state transition is BITWISE identical between
    the accumulated and unaccumulated paths when the gradients they
    reduce are bitwise identical. A bilinear loss at microbatch size 1
    with two identical microbatches makes them so by construction
    (every per-element gradient is a 2-term sum — no reduction-order
    freedom for XLA to exploit; a matmul-mse loss would differ in ulps
    between the scan body and the straight-line program, which is a
    compiler property, not an accumulation one). Same corrected input
    + same stochastic-rounding key ⇒ same reduced gradient, residual,
    and step counter, bit for bit."""
    ctx = hvd_mod.init()
    ax = ctx.config.rank_axis
    w0 = rng.standard_normal((4096,)).astype(np.float32)
    x_mb = rng.standard_normal((8, 1, 4096)).astype(np.float32)
    y_mb = rng.standard_normal((8, 1)).astype(np.float32)
    X = np.tile(x_mb, (1, 2, 1))   # 2 identical microbatches
    Y = np.tile(y_mb, (1, 2))

    def loss(w, xb, yb):
        return jnp.mean((xb @ w) * yb)

    ref_fn = hvd_mod.DistributedGradFn(jax.grad(loss), axis_name=ax,
                                       compression="int8_ef",
                                       quantize_min_bucket_bytes=0)
    acc_fn = hvd_mod.DistributedGradFn(loss, axis_name=ax,
                                       accum_steps=2,
                                       compression="int8_ef",
                                       quantize_min_bucket_bytes=0)

    def step(xmb, ymb, xfull, yfull):
        wl = C.to_local(jnp.asarray(w0), ax)
        ef0 = ref_fn.init_ef_state(wl)
        g_ref, ef_ref = ref_fn(wl, xmb[0], ymb[0], ef_state=ef0)
        g_acc, ef_acc = acc_fn(wl, xfull[0], yfull[0], ef_state=ef0)
        return (g_ref[None], g_acc[None], ef_ref.residual[None],
                ef_acc.residual[None], ef_ref.step[None],
                ef_acc.step[None])

    g_ref, g_acc, r_ref, r_acc, s_ref, s_acc = _spmd(
        ctx, step, nouts=6)(hvd.scatter(x_mb), hvd.scatter(y_mb),
                            hvd.scatter(X), hvd.scatter(Y))
    np.testing.assert_array_equal(np.asarray(g_ref), np.asarray(g_acc))
    np.testing.assert_array_equal(np.asarray(r_ref), np.asarray(r_acc))
    np.testing.assert_array_equal(np.asarray(s_ref), np.asarray(s_acc))


def test_gradfn_accum_loss_scale_transitions_bitwise(hvd, rng):
    """scale_backoff under accumulation: the guard's loss-scale state
    machine sees the accumulated gradient once per effective step, so
    its transitions (backoff on the poisoned step, streak reset)
    bitwise-match the unaccumulated path fed the same gradients."""
    ctx = hvd_mod.init()
    ax = ctx.config.rank_axis
    w0 = rng.standard_normal((5,)).astype(np.float32)
    X = rng.standard_normal((8, 4, 5)).astype(np.float32)
    Xbad = X.copy()
    Xbad[:, 0, 0] = np.nan
    Y = rng.standard_normal((8, 4)).astype(np.float32)

    def loss(w, xb, yb):
        return jnp.mean((xb @ w - yb) ** 2)

    ref_fn = hvd_mod.DistributedGradFn(jax.grad(loss), axis_name=ax,
                                       nonfinite_policy="scale_backoff")
    acc_fn = hvd_mod.DistributedGradFn(loss, axis_name=ax,
                                       accum_steps=2,
                                       nonfinite_policy="scale_backoff")

    def one_path(fn, xb_ok, yb, xb_bad):
        guard = None
        _, guard = fn(C.to_local(jnp.asarray(w0), ax), xb_ok, yb,
                      guard_state=guard)
        _, guard = fn(C.to_local(jnp.asarray(w0), ax), xb_bad, yb,
                      guard_state=guard)
        return guard

    def step(x_ok, x_bad, yb):
        g_ref = one_path(ref_fn, x_ok[0], yb[0], x_bad[0])
        g_acc = one_path(acc_fn, x_ok[0], yb[0], x_bad[0])
        return (g_ref.loss_scale[None], g_acc.loss_scale[None],
                g_ref.nonfinite_steps[None], g_acc.nonfinite_steps[None],
                g_ref.good_steps[None], g_acc.good_steps[None])

    ls_r, ls_a, nf_r, nf_a, gs_r, gs_a = _spmd(ctx, step, nouts=6)(
        hvd.scatter(X), hvd.scatter(Xbad), hvd.scatter(Y))
    np.testing.assert_array_equal(np.asarray(ls_r), np.asarray(ls_a))
    np.testing.assert_array_equal(np.asarray(nf_r), np.asarray(nf_a))
    np.testing.assert_array_equal(np.asarray(gs_r), np.asarray(gs_a))


def test_gradfn_accum_route_composition(hvd, rng):
    """accum_steps composes with the mesh router: routed accumulated
    gradients over a 2x4 mesh match the flat unaccumulated reduction."""
    ctx = hvd_mod.init()
    devs = np.array(jax.devices()).reshape(2, 4)
    mesh = Mesh(devs, ("cross", "local"))
    plan = C.WirePlan.parse("local:none,cross:none")
    w0 = rng.standard_normal((5,)).astype(np.float32)
    X = rng.standard_normal((8, 4, 5)).astype(np.float32)
    Y = rng.standard_normal((8, 4)).astype(np.float32)

    def loss(w, xb, yb):
        return jnp.mean((xb @ w - yb) ** 2)

    gfn = hvd_mod.DistributedGradFn(loss, accum_steps=2, route=plan)

    def step(xb, yb):
        wl = C.to_local(jnp.asarray(w0), ("cross", "local"))
        return gfn(wl, xb[0, 0], yb[0, 0])[None, None]

    axes = ("cross", "local")
    out = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=P(*axes), out_specs=P(*axes),
        check_vma=False))(hvd.scatter(X).reshape(2, 4, 4, 5),
                          hvd.scatter(Y).reshape(2, 4, 4))

    def np_grad(w, xb, yb):
        e = xb @ w - yb
        return 2 * xb.T @ e / len(yb)

    gmean = np.mean([np_grad(w0, X[r], Y[r]) for r in range(8)], axis=0)
    np.testing.assert_allclose(np.asarray(out)[0, 0], gmean,
                               rtol=1e-4, atol=1e-5)


# -- the optimizer surfaces ---------------------------------------------------

def test_optimizer_accumulate_end_to_end(hvd, rng):
    """DistributedOptimizer(accum_steps=2): accumulate + ONE update
    per effective step == the fused large-batch SGD step."""
    ctx = hvd_mod.init()
    ax = ctx.config.rank_axis
    w0 = rng.standard_normal((5,)).astype(np.float32)
    X = rng.standard_normal((8, 4, 5)).astype(np.float32)
    Y = rng.standard_normal((8, 4)).astype(np.float32)
    tx = hvd_mod.DistributedOptimizer(optax.sgd(0.1), axis_name=ax,
                                      accum_steps=2)
    assert tx.accum_steps == 2

    def loss(w, xb, yb):
        return jnp.mean((xb @ w - yb) ** 2)

    vgrad = tx.accumulate(loss)

    def step(xb, yb):
        w = C.to_local(jnp.asarray(w0), ax)
        st = tx.init(w)
        _, g = vgrad(w, xb[0], yb[0])
        updates, _ = tx.update(g, st, w)
        return (w + updates)[None]

    out = np.asarray(_spmd(ctx, step)(hvd.scatter(X), hvd.scatter(Y)))

    def np_grad(w, xb, yb):
        e = xb @ w - yb
        return 2 * xb.T @ e / len(yb)

    gmean = np.mean([np_grad(w0, X[r], Y[r]) for r in range(8)], axis=0)
    np.testing.assert_allclose(out[0], w0 - 0.1 * gmean, rtol=1e-4,
                               atol=1e-5)


def test_sharded_optimizer_accumulate(hvd, rng):
    """ShardedOptimizer(accum_steps=2): the scan driver + the RS/AG
    update agree with the replicated large-batch step."""
    ctx = hvd_mod.init()
    ax = ctx.config.rank_axis
    w0 = rng.standard_normal((64,)).astype(np.float32)
    X = rng.standard_normal((8, 4, 64)).astype(np.float32)
    Y = rng.standard_normal((8, 4)).astype(np.float32)
    tx = hvd_mod.ShardedOptimizer(optax.sgd(0.1), axis_name=ax,
                                  accum_steps=2)

    def loss(w, xb, yb):
        return jnp.mean((xb @ w - yb) ** 2)

    vgrad = tx.accumulate(loss)

    def step(xb, yb):
        w = C.to_local(jnp.asarray(w0), ax)
        st = tx.init(w)
        _, g = vgrad(w, xb[0], yb[0])
        updates, _ = tx.update(g, st, w)
        return (w + updates)[None]

    out = np.asarray(_spmd(ctx, step)(hvd.scatter(X), hvd.scatter(Y)))

    def np_grad(w, xb, yb):
        e = xb @ w - yb
        return 2 * xb.T @ e / len(yb)

    gmean = np.mean([np_grad(w0, X[r], Y[r]) for r in range(8)], axis=0)
    np.testing.assert_allclose(out[0], w0 - 0.1 * gmean, rtol=1e-4,
                               atol=1e-5)


def test_accum_conflicts_and_validation(hvd):
    with pytest.raises(ValueError, match="two spellings"):
        hvd_mod.DistributedOptimizer(optax.sgd(0.1), accum_steps=2,
                                     backward_passes_per_step=2)
    with pytest.raises(ValueError, match="remat_policy"):
        hvd_mod.DistributedGradFn(lambda w: w, remat_policy="dots")
    # accum binding survives on the k>1 legacy aggregation too.
    tx = hvd_mod.DistributedOptimizer(optax.sgd(0.1),
                                      backward_passes_per_step=2)
    assert tx.accum_steps == 1 and callable(tx.accumulate)


# -- weight-update-sharding heuristic ----------------------------------------

def test_should_shard_update_heuristic(hvd):
    small = {"w": jnp.zeros((8, 8), jnp.float32)}          # 256 B
    big = jax.ShapeDtypeStruct((1 << 20,), jnp.float32)    # 4 MiB
    assert not hvd_mod.should_shard_update(small, size=8,
                                           threshold_bytes=1 << 20)
    assert hvd_mod.should_shard_update({"w": big}, size=8,
                                       threshold_bytes=1 << 20)
    # Single-rank worlds never shard, whatever the size.
    assert not hvd_mod.should_shard_update({"w": big}, size=1,
                                           threshold_bytes=1)
    assert hvd_mod.auto_shard_threshold(123) == 123
    assert hvd_mod.auto_shard_threshold() > 0
