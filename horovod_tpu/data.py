"""Data layer: rank sharding, elastic sampling, device prefetch.

Reference equivalents:
- ``ElasticSampler`` — horovod/torch/elastic/sampler.py:24 (rank
  partitioning with processed-index tracking so an elastic reset
  repartitions only the *unprocessed* remainder of the epoch).
- The Spark data path (petastorm readers feeding per-rank shards).

TPU-native additions: :class:`DeviceInfeed` — a DOUBLE-BUFFERED device
infeed pipeline (docs/performance.md "MFU playbook"): a background
thread stages batch N+1 into HBM (``jax.device_put``, sharding-aware)
while the step consumes batch N, so the host→device transfer never sits
on the timed path; ``prefetch_to_device``/``BackgroundPrefetcher`` ride
it. ``shard_batch`` lays a global batch out rank-major for
``hvd.spmd_step``'s ``P(rank_axis)`` specs — and fuses into infeed
placement (``DeviceInfeed(shard=True)``) so only this rank's slice is
ever transferred. Consumer starvation is measurable:
``hvd_tpu_infeed_wait_seconds`` (how long the step blocked on the next
batch) + ``hvd_tpu_infeed_queue_depth`` feed ``analyze_metrics.py
--metrics`` (docs/metrics.md).
"""

from __future__ import annotations

import threading
import weakref
from typing import Callable, Iterable, Iterator, List, Optional, Sequence

import numpy as np

from .common import metrics as _metrics_lib

# Infeed telemetry (docs/metrics.md): starvation is attributable only
# when the wait is measured at the consumer edge — a fast device with a
# slow input pipeline shows up HERE, not in the device trace.
_M_WAIT = _metrics_lib.histogram(
    "hvd_tpu_infeed_wait_seconds",
    "time the consumer blocked waiting for the next device batch "
    "(DeviceInfeed/BackgroundPrefetcher)")
_M_DEPTH = _metrics_lib.gauge(
    "hvd_tpu_infeed_queue_depth",
    "ready device batches queued ahead of the consumer")
_M_BATCHES = _metrics_lib.counter(
    "hvd_tpu_infeed_batches_total",
    "batches delivered through the device-infeed pipelines")
_M_BYTES = _metrics_lib.counter(
    "hvd_tpu_infeed_bytes_total",
    "host bytes handed to device placement by the infeed pipelines")


class ElasticSampler:
    """Partitions dataset indices across ranks; repartitions the
    unprocessed remainder after elastic resets.

    Framework-agnostic (index-based) version of the reference sampler.
    Include it in a ``JaxState``/``ObjectState`` (its state is plain
    picklable attributes), call :meth:`record_batch` after each step and
    :meth:`set_epoch` at epoch end; after a topology change call
    :meth:`reset` (the elastic State's on_reset hook).
    """

    def __init__(self, dataset_size: int, shuffle: bool = True,
                 seed: int = 0):
        self.dataset_size = int(dataset_size)
        self.shuffle = shuffle
        self.seed = seed
        self.epoch = 0
        self.processed_indices: set = set()
        self.rank = 0
        self.num_replicas = 1
        self.remaining_indices: List[int] = []
        self.num_samples = 0
        self.total_size = 0
        self.reset()

    # -- lifecycle ---------------------------------------------------------

    def reset(self) -> None:
        """Re-read world topology and repartition the unprocessed indices
        (called at construction and after elastic resets)."""
        import horovod_tpu as hvd

        if hvd.is_initialized():
            self.rank = hvd.rank()
            self.num_replicas = hvd.size()
        else:
            self.rank, self.num_replicas = 0, 1
        self._repartition()

    def set_epoch(self, epoch: int) -> None:
        """New epoch: clear processed tracking, reshuffle deterministically
        from (seed, epoch) — identical ordering on every rank."""
        self.epoch = epoch
        self.processed_indices = set()
        self._repartition()

    def get_indices(self, batch_idx: int, batch_size: int) -> List[int]:
        """This rank's indices for batch ``batch_idx`` (reference
        get_indices)."""
        start = batch_idx * batch_size
        return self.local_indices()[start:start + batch_size]

    def record_batch(self, batch_idx: int, batch_size: int) -> None:
        """Mark the batch's indices processed (reference record_batch)."""
        self.record_indices(self.get_indices(batch_idx, batch_size))

    def record_indices(self, indices: Sequence[int]) -> None:
        self.processed_indices.update(int(i) for i in indices)

    # -- sampling ----------------------------------------------------------

    def _repartition(self) -> None:
        indices = [i for i in range(self.dataset_size)
                   if i not in self.processed_indices]
        if self.shuffle:
            rng = np.random.default_rng(self.seed + self.epoch)
            indices = list(rng.permutation(indices))
        self.remaining_indices = [int(i) for i in indices]
        # Pad to a multiple of num_replicas so every rank sees the same
        # number of samples (same trick as the reference / TF
        # DistributedSampler).
        n = len(self.remaining_indices)
        self.num_samples = -(-n // self.num_replicas) if n else 0
        self.total_size = self.num_samples * self.num_replicas
        if n and self.total_size > n:
            self.remaining_indices += self.remaining_indices[
                :self.total_size - n]

    def local_indices(self) -> List[int]:
        """This rank's shard (strided, reference-style)."""
        return self.remaining_indices[self.rank:self.total_size:
                                      self.num_replicas]

    def __iter__(self) -> Iterator[int]:
        return iter(self.local_indices())

    def __len__(self) -> int:
        return self.num_samples

    # -- pickling (lives inside elastic State objects) ---------------------

    def __getstate__(self):
        d = dict(self.__dict__)
        d["processed_indices"] = sorted(self.processed_indices)
        return d

    def __setstate__(self, d):
        d = dict(d)
        d["processed_indices"] = set(d["processed_indices"])
        self.__dict__.update(d)


def shard_batch(batch, rank: Optional[int] = None,
                size: Optional[int] = None):
    """Slice this rank's rows out of a global batch pytree (for
    multi-process mode; under single-controller SPMD pass the global
    batch straight to spmd_step with ``P(rank_axis)`` specs instead)."""
    import jax

    import horovod_tpu as hvd

    r = hvd.rank() if rank is None else rank
    n = hvd.size() if size is None else size

    def one(x):
        b = x.shape[0]
        if b % n:
            raise ValueError(f"batch dim {b} not divisible by size {n}")
        per = b // n
        return x[r * per:(r + 1) * per]

    return jax.tree.map(one, batch)


def _compose_shard_transform(transform: Optional[Callable]) -> Callable:
    """Fuse this rank's :func:`shard_batch` slice after ``transform`` —
    the shared ``shard=True`` path for :class:`DeviceInfeed` and
    :func:`infeed_pipeline`, so every mode slices identically and only
    1/n of the global batch ever reaches the placement path."""
    import horovod_tpu as hvd

    r = hvd.rank() if hvd.is_initialized() else 0
    n = hvd.size() if hvd.is_initialized() else 1
    base = transform
    return (lambda b: shard_batch(
        base(b) if base is not None else b, rank=r, size=n))


def _host_nbytes(batch) -> int:
    """Host-side bytes of a batch pytree — the
    ``hvd_tpu_infeed_bytes_total`` accounting, shared by every infeed
    mode so "what counts as host bytes" has one definition."""
    import jax

    return sum(getattr(leaf, "nbytes", 0)
               for leaf in jax.tree.leaves(batch))


def _place_batch(batch, sharding):
    """Sharding-aware device placement of a batch pytree (shared by
    every infeed mode: one definition of the transfer semantics)."""
    import jax

    if sharding is not None:
        return jax.tree.map(
            lambda x: jax.device_put(x, sharding), batch)
    return jax.tree.map(jax.device_put, batch)


# Live infeed instances, closed at interpreter exit: a daemon worker
# mid-device_put when the process tears down produces backend aborts
# (and an unjoined thread) — the atexit drain mirrors the
# timeline-writer pattern (common/timeline.py).
_LIVE_INFEEDS: "weakref.WeakSet" = weakref.WeakSet()
_ATEXIT_REGISTERED = False


def _close_live_infeeds() -> None:
    for infeed in list(_LIVE_INFEEDS):
        infeed.close()


class DeviceInfeed:
    """Double-buffered device infeed: a background thread keeps up to
    ``depth`` batches ALREADY PLACED on device (HBM) ahead of the
    consumer, so batch N+1's host→device transfer (and any host-side
    ``transform``) overlaps the step on batch N::

        with hvd.DeviceInfeed(host_batches, depth=2,
                              sharding=sharding) as infeed:
            for batch in infeed:
                state = train_step(state, *batch)

    ``sharding`` (a ``jax.sharding.Sharding``) places each leaf —
    under SPMD pass ``NamedSharding(mesh, P(rank_axis))`` so every
    device receives exactly its shard, with no gather/re-layout at
    dispatch. ``shard=True`` instead slices THIS RANK's rows
    (:func:`shard_batch`) on the worker thread before placement —
    multi-process mode transfers 1/n of the global batch and the full
    batch never exists on the device path. ``transform`` is an
    arbitrary host-side pre-placement hook (decode/augment), run on the
    worker thread.

    Lifecycle: iteration ends (StopIteration) after the source is
    exhausted; a worker-side exception is re-raised to the consumer
    AFTER the batches that preceded it. ``close()`` (also via context
    manager / ``with``) stops the worker, drains the queue, and JOINS
    the thread — abandoning iteration early without closing leaks
    nothing at interpreter exit (an atexit hook closes stragglers), but
    close deterministically when you can. Delivery order is the source
    order. Waits are measured into ``hvd_tpu_infeed_wait_seconds``."""

    _DONE = object()

    def __init__(self, iterator: Iterable, depth: int = 2, sharding=None,
                 transform: Optional[Callable] = None,
                 shard: bool = False):
        import queue as queue_mod

        global _ATEXIT_REGISTERED
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        if shard:
            transform = _compose_shard_transform(transform)
        self._transform = transform
        self._q: "queue_mod.Queue" = queue_mod.Queue(maxsize=depth)
        self._sharding = sharding
        self._error: Optional[BaseException] = None
        self._stop = threading.Event()
        self._closed = False
        self._thread = threading.Thread(
            target=self._run, args=(iter(iterator),), daemon=True,
            name="hvd-device-infeed")
        if not _ATEXIT_REGISTERED:
            # Through the ONE ordered shutdown sequence (hvdlint
            # atexit-order): infeed workers stop before the Context
            # drains metrics, so their final byte counters land in the
            # drain-on-stop snapshot instead of racing it.
            from .common import shutdown as shutdown_lib

            shutdown_lib.register("data-infeeds", _close_live_infeeds,
                                  priority=15)
            _ATEXIT_REGISTERED = True
        _LIVE_INFEEDS.add(self)
        self._thread.start()

    # -- worker -------------------------------------------------------------

    def _put(self, item) -> bool:
        """Bounded put that stays responsive to close(): returns False
        when the consumer is gone."""
        import queue as queue_mod

        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return True
            except queue_mod.Full:
                continue
        return False

    def _run(self, it):
        try:
            for batch in it:
                if self._stop.is_set():
                    return
                if self._transform is not None:
                    batch = self._transform(batch)
                _M_BYTES.inc(_host_nbytes(batch))
                batch = _place_batch(batch, self._sharding)
                if not self._put(batch):
                    return
                _M_DEPTH.set(self._q.qsize())
        except BaseException as e:  # surfaced on the consumer's next()
            self._error = e
        finally:
            self._put(self._DONE)

    # -- consumer -----------------------------------------------------------

    def __iter__(self):
        return self

    def __next__(self):
        if self._closed:
            raise StopIteration
        with _M_WAIT.time():
            item = self._q.get()
        _M_DEPTH.set(self._q.qsize())
        if item is self._DONE:
            self.close()
            if self._error is not None:
                raise self._error
            raise StopIteration
        _M_BATCHES.inc()
        return item

    def close(self) -> None:
        """Stop the worker, drain queued batches, join the thread.
        Idempotent; called by the context manager, by exhaustion, and
        (as a last resort) by the atexit hook."""
        import queue as queue_mod

        if self._closed:
            return
        self._closed = True
        self._stop.set()
        while True:  # drain so a blocked worker put() unblocks
            try:
                self._q.get_nowait()
            except queue_mod.Empty:
                break
        self._thread.join(timeout=5.0)
        _M_DEPTH.set(0)
        _LIVE_INFEEDS.discard(self)

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        # Drain-on-exception included: a raising consumer must not
        # leave the worker blocked on a full queue forever.
        self.close()
        return False


class BackgroundPrefetcher(DeviceInfeed):
    """Thread-backed prefetcher (historical name; now the
    :class:`DeviceInfeed` double-buffered pipeline with the original
    ``size=`` spelling): a worker thread stays ``size`` batches ahead,
    so host preprocessing overlaps both the transfer and the step.
    Supports ``close()`` and ``with`` — see :class:`DeviceInfeed`."""

    def __init__(self, iterator: Iterable, size: int = 2, sharding=None):
        super().__init__(iterator, depth=size, sharding=sharding)


def prefetch_to_device(iterator: Iterable, size: int = 2,
                       sharding=None) -> Iterator:
    """Wrap a host batch iterator so up to ``size`` batches are already
    transferred to device (HBM) ahead of consumption — the TPU analog
    of pinned-memory prefetch, now backed by the double-buffered
    :class:`DeviceInfeed` (transfers happen on a background thread and
    genuinely overlap the step). ``sharding`` (optional
    jax.sharding.Sharding) places each batch; default = committed to
    the default device. The generator form closes the infeed when
    dropped mid-iteration (GeneratorExit → ``close()``)."""
    with DeviceInfeed(iterator, depth=size, sharding=sharding) as infeed:
        yield from infeed


def infeed_pipeline(iterator: Iterable, mode: Optional[str] = None,
                    sharding=None, transform: Optional[Callable] = None,
                    shard: bool = False) -> Iterator:
    """The bench/ablation surface over the infeed modes
    (``HVD_TPU_PREFETCH`` / ``bench.py --prefetch``; docs/performance.md):

    - ``"off"`` — place each batch on demand ON the consumer thread and
      BLOCK until it is device-resident (the full host tax on the timed
      path; the A/B baseline).
    - ``"single"`` — single-buffered: one batch staged ahead, placed on
      the consumer thread between steps (async dispatch may partially
      overlap; no worker thread).
    - ``"double"`` — the real thing: :class:`DeviceInfeed` with
      ``depth=2``, background-thread placement.

    ``mode=None`` resolves the configured default —
    ``init(prefetch=)`` / ``HVD_TPU_PREFETCH`` — falling back to
    ``double``."""
    if mode is None:
        from .common import basics

        if basics.is_initialized():
            mode = basics.context().config.prefetch
        if mode is None:
            from .common.config import _env

            mode = _env("PREFETCH")
        mode = mode or "double"
    if mode not in ("off", "single", "double"):
        raise ValueError(
            f"unknown infeed mode {mode!r}: off | single | double")
    if mode == "double":
        with DeviceInfeed(iterator, depth=2, sharding=sharding,
                          transform=transform, shard=shard) as infeed:
            yield from infeed
        return

    import jax

    if shard:
        transform = _compose_shard_transform(transform)

    def place(batch):
        if transform is not None:
            batch = transform(batch)
        _M_BYTES.inc(_host_nbytes(batch))
        return _place_batch(batch, sharding)

    it = iter(iterator)
    if mode == "off":
        for batch in it:
            with _M_WAIT.time():
                out = place(batch)
                out = jax.block_until_ready(out)
            _M_BATCHES.inc()
            yield out
        return
    # "single": one batch staged ahead on this thread.
    staged = None
    try:
        staged = place(next(it))
    except StopIteration:
        return
    while staged is not None:
        out = staged
        try:
            with _M_WAIT.time():
                staged = place(next(it))
        except StopIteration:
            staged = None
        _M_BATCHES.inc()
        yield out
