"""Data layer: ElasticSampler (reference torch/elastic/sampler.py
semantics), rank sharding, device prefetch."""

import pickle

import numpy as np
import jax.numpy as jnp
import pytest

from horovod_tpu import data as data_lib


class TestElasticSampler:
    def test_partitions_cover_dataset(self, hvd):
        s = data_lib.ElasticSampler(64, shuffle=False)
        assert s.num_replicas == 8
        # All ranks' shards together cover the dataset exactly.
        all_idx = []
        for r in range(8):
            s.rank = r
            shard = s.local_indices()
            assert len(shard) == s.num_samples == 8
            all_idx += shard
        assert sorted(all_idx) == list(range(64))

    def test_shuffle_deterministic_per_epoch(self, hvd):
        a = data_lib.ElasticSampler(32, shuffle=True, seed=5)
        b = data_lib.ElasticSampler(32, shuffle=True, seed=5)
        assert a.local_indices() == b.local_indices()
        a.set_epoch(1)
        b.set_epoch(1)
        assert a.local_indices() == b.local_indices()
        e0 = data_lib.ElasticSampler(32, shuffle=True, seed=5)
        assert a.local_indices() != e0.local_indices()  # epoch reshuffles

    def test_processed_indices_excluded_after_reset(self, hvd):
        s = data_lib.ElasticSampler(40, shuffle=False)
        first_batch = s.local_indices()[:3]
        s.record_indices(first_batch)
        s.reset()  # elastic topology change mid-epoch
        rest = set(s.remaining_indices)
        assert rest.isdisjoint(first_batch)
        assert len(rest) == 40 - 3

    def test_record_batch_maps_to_local_shard(self, hvd):
        s = data_lib.ElasticSampler(64, shuffle=False)
        local = s.local_indices()
        s.record_batch(batch_idx=1, batch_size=2)
        assert set(local[2:4]) <= s.processed_indices

    def test_set_epoch_clears_processed(self, hvd):
        s = data_lib.ElasticSampler(16, shuffle=False)
        s.record_indices(s.local_indices())
        s.set_epoch(1)
        assert s.processed_indices == set()
        assert len(s.remaining_indices) == 16

    def test_padding_when_not_divisible(self, hvd):
        s = data_lib.ElasticSampler(10, shuffle=False)  # 10 over 8 ranks
        assert s.num_samples == 2 and s.total_size == 16
        counts = []
        for r in range(8):
            s.rank = r
            counts.append(len(s.local_indices()))
        assert counts == [2] * 8  # equal shards via padding

    def test_pickles_inside_state(self, hvd):
        s = data_lib.ElasticSampler(8)
        s.record_indices([1, 2])
        s2 = pickle.loads(pickle.dumps(s))
        assert s2.processed_indices == {1, 2}
        assert s2.local_indices() == s.local_indices()


def test_shard_batch(hvd):
    x = np.arange(16).reshape(16, 1)
    out = data_lib.shard_batch({"x": x}, rank=2, size=8)
    np.testing.assert_array_equal(np.asarray(out["x"]), [[4], [5]])
    with pytest.raises(ValueError, match="not divisible"):
        data_lib.shard_batch(np.ones((10, 2)), rank=0, size=8)


def test_prefetch_to_device_order_and_device(hvd):
    batches = [{"x": np.full((2,), i, np.float32)} for i in range(5)]
    out = list(data_lib.prefetch_to_device(iter(batches), size=2))
    assert len(out) == 5
    for i, b in enumerate(out):
        assert isinstance(b["x"], jnp.ndarray)
        np.testing.assert_allclose(np.asarray(b["x"]), i)


def test_background_prefetcher(hvd):
    batches = [np.full((2,), i, np.float32) for i in range(6)]
    out = list(data_lib.BackgroundPrefetcher(batches, size=3))
    assert [int(np.asarray(b)[0]) for b in out] == list(range(6))


def test_background_prefetcher_propagates_error(hvd):
    def gen():
        yield np.ones(2)
        raise RuntimeError("decode failed")

    it = data_lib.BackgroundPrefetcher(gen(), size=2)
    next(it)
    with pytest.raises(RuntimeError, match="decode failed"):
        list(it)


# -- DeviceInfeed: the double-buffered infeed pipeline (PR 8) ----------------

def test_device_infeed_order_under_slow_consumer(hvd):
    """A consumer slower than the producer must still see every batch
    exactly once, in source order (the queue bounds memory, never
    reorders or drops)."""
    import time

    batches = [np.full((2,), i, np.float32) for i in range(8)]
    got = []
    with data_lib.DeviceInfeed(iter(batches), depth=2) as infeed:
        for b in infeed:
            time.sleep(0.01)  # slow consumer
            got.append(int(np.asarray(b)[0]))
    assert got == list(range(8))


def test_device_infeed_raising_iterator(hvd):
    """A producer exception surfaces on the consumer AFTER the batches
    that preceded it (drain-on-exception), and the worker thread is
    joined afterwards."""
    def gen():
        yield np.ones(2)
        yield np.ones(2) * 2
        raise RuntimeError("decode failed")

    infeed = data_lib.DeviceInfeed(gen(), depth=2)
    assert int(np.asarray(next(infeed))[0]) == 1
    assert int(np.asarray(next(infeed))[0]) == 2
    with pytest.raises(RuntimeError, match="decode failed"):
        next(infeed)
    infeed._thread.join(timeout=5)
    assert not infeed._thread.is_alive()


def test_device_infeed_close_joins_thread(hvd):
    """Abandoning iteration early + close() must stop and JOIN the
    worker — the thread-leak fix (a blocked put() drains). Idempotent."""
    def endless():
        i = 0
        while True:
            yield np.full((2,), i, np.float32)
            i += 1

    infeed = data_lib.DeviceInfeed(endless(), depth=2)
    next(infeed)
    next(infeed)
    infeed.close()
    assert not infeed._thread.is_alive()
    infeed.close()  # idempotent
    with pytest.raises(StopIteration):
        next(infeed)  # closed = exhausted, never a hang


def test_device_infeed_context_manager_abandon(hvd):
    def endless():
        while True:
            yield np.ones(2)

    with data_lib.DeviceInfeed(endless(), depth=2) as infeed:
        next(infeed)
    assert not infeed._thread.is_alive()


def test_prefetch_generator_close_stops_thread(hvd):
    """Dropping the prefetch_to_device generator mid-iteration closes
    the backing infeed (GeneratorExit -> close) — no leak at exit."""
    def endless():
        while True:
            yield np.ones(2)

    before = [t for t in __import__("threading").enumerate()
              if t.name == "hvd-device-infeed"]
    gen = data_lib.prefetch_to_device(endless(), size=2)
    next(gen)
    gen.close()
    import time

    deadline = time.time() + 5
    while time.time() < deadline:
        after = [t for t in __import__("threading").enumerate()
                 if t.name == "hvd-device-infeed" and t.is_alive()]
        if len(after) <= len(before):
            break
        time.sleep(0.05)
    assert len(after) <= len(before)


def test_device_infeed_shard_fuses_rank_slice(hvd):
    """shard=True slices THIS rank's rows before placement — the
    transferred batch is 1/n of the global one (single-controller
    tests run as rank 0 of 8)."""
    global_batch = {"x": np.arange(32, dtype=np.float32).reshape(16, 2)}
    with data_lib.DeviceInfeed(iter([global_batch]), depth=1,
                               shard=True) as infeed:
        out = next(infeed)
    assert out["x"].shape == (2, 2)
    np.testing.assert_array_equal(np.asarray(out["x"]),
                                  global_batch["x"][:2])


def test_infeed_pipeline_modes_and_metrics(hvd):
    """All three modes deliver identical content in order; the wait
    histogram and batch counter move (the starvation signal
    ``tools/analyze_metrics.py --metrics`` reads)."""
    import horovod_tpu as hvd_mod

    def snap():
        m = hvd_mod.metrics().get("hvd_tpu_infeed_batches_total", {})
        s = m.get("samples", [])
        return s[0]["value"] if s else 0

    batches = [(np.full((2,), i, np.float32),) for i in range(4)]
    for mode in ("off", "single", "double"):
        before = snap()
        out = [int(np.asarray(b[0])[0])
               for b in data_lib.infeed_pipeline(iter(batches), mode)]
        assert out == list(range(4)), mode
        assert snap() >= before + 4, mode
    with pytest.raises(ValueError, match="unknown infeed mode"):
        list(data_lib.infeed_pipeline(iter(batches), "bogus"))
    wait = hvd_mod.metrics().get("hvd_tpu_infeed_wait_seconds", {})
    assert wait["samples"][0]["value"]["count"] > 0


def test_infeed_pipeline_honors_config_prefetch(hvd):
    """``mode=None`` resolves ``init(prefetch=)``'s Config field, not
    just the env var — the config value must be consumed, so a bad one
    raises exactly like an explicit bad mode."""
    from horovod_tpu.common import basics

    cfg = basics.context().config
    prev = cfg.prefetch
    try:
        cfg.prefetch = "off"
        batches = [(np.full((2,), i, np.float32),) for i in range(3)]
        out = [int(np.asarray(b[0])[0])
               for b in data_lib.infeed_pipeline(iter(batches))]
        assert out == [0, 1, 2]
        cfg.prefetch = "bogus"
        with pytest.raises(ValueError, match="unknown infeed mode"):
            list(data_lib.infeed_pipeline(iter(batches)))
    finally:
        cfg.prefetch = prev
