"""Chrome-trace timeline profiler.

Reference: horovod/common/timeline.cc:205-290 — a writer thread fed by a
lock-free SPSC queue emits chrome://tracing JSON of per-tensor collective
lifecycle events (NEGOTIATE_*, QUEUE, MEMCPY_IN_FUSION_BUFFER,
NCCL_ALLREDUCE — activity names common.h:31-62), toggleable at runtime via
horovod_start/stop_timeline (operations.cc:720-746).

TPU-native version: the same chrome-trace JSON surface (so existing
tooling/habits carry over) with phases named for the XLA pipeline
(COMPILE_CACHE_MISS, DISPATCH, XLA_ALLREDUCE...), a plain worker thread +
queue.Queue as the writer (CPython has no boost::lockfree; the queue is off
the hot path), and an optional bridge into ``jax.profiler`` traces for
device-side detail.
"""

from __future__ import annotations

import json
import os
import queue
import threading
import time
from typing import Optional

from . import lockdep
from .config import runtime_env

# Canonical activity names (subset of reference common.h:31-62, renamed for
# the XLA pipeline).
NEGOTIATE = "NEGOTIATE"          # eager compile-cache miss / controller round
QUEUE = "QUEUE"
FUSE = "MEMCPY_IN_FUSION_BUFFER"
XLA_ALLREDUCE = "XLA_ALLREDUCE"
XLA_ALLGATHER = "XLA_ALLGATHER"
XLA_BROADCAST = "XLA_BROADCAST"
XLA_ALLTOALL = "XLA_ALLTOALL"
UNFUSE = "MEMCPY_OUT_FUSION_BUFFER"
# Recovery lifecycle markers (no reference analog by name — the reference
# logs resets/blacklists as text; here each recovery-counter bump lands in
# the trace as an instant event RECOVERY:<counter> so downtime and retry
# storms are visible next to the collectives they interrupt).
RECOVERY = "RECOVERY"


class Timeline:
    """Writes chrome-trace JSON events; safe to call from any thread.

    Uses the native ring-buffer writer (horovod_tpu/native/timeline.cc —
    the reference's lock-free-queue + writer-thread design) when the
    native library is available; falls back to a Python queue+thread."""

    def __init__(self, filename: Optional[str] = None,
                 mark_cycles: bool = False, use_native: bool = True):
        self._filename = filename
        self._mark_cycles = mark_cycles
        self._queue: "queue.Queue" = queue.Queue()
        self._thread: Optional[threading.Thread] = None
        self._active = False
        self._start_ts = time.perf_counter()
        self._pending_starts = {}
        self._lock = lockdep.lock("timeline.writer")
        self._native = None
        self._xprof_active = False
        self._use_native = (use_native and
                            runtime_env("DISABLE_NATIVE") != "1")
        if filename:
            self.start(filename)

    def _load_native(self):
        # Deferred to start(): loading may trigger a one-time C++ build,
        # which must not tax every hvd.init() that never enables tracing.
        if not self._use_native:
            return None
        try:
            from ..native import NativeTimelineWriter

            w = NativeTimelineWriter()
            return w if w.available else None
        except Exception:  # pragma: no cover - native is optional
            return None

    # -- runtime start/stop (reference operations.cc:720-746) -------------

    def start(self, filename: str,
              xprof_dir: Optional[str] = None) -> None:
        """``xprof_dir`` additionally starts a jax.profiler trace there
        for device-side detail (the GPU-event layer the reference gets
        from CUDA events, gpu_operations.h:110-118) — owned HERE so
        every stop path (incl. Context.shutdown) flushes it."""
        with self._lock:
            if xprof_dir and not self._xprof_active:
                import jax

                jax.profiler.start_trace(xprof_dir)
                self._xprof_active = True
            if self._active:
                # Timeline already running (e.g. HVD_TPU_TIMELINE env
                # auto-start): the xprof request above still took effect.
                return
            self._filename = filename
            self._native = self._load_native()
            if self._native is not None and self._native.start(filename):
                self._active = True
                return
            self._native = None
            self._active = True
            self._thread = threading.Thread(target=self._writer, daemon=True)
            self._thread.start()

    def stop(self) -> None:
        with self._lock:
            # Claim the flag atomically so concurrent stop() calls (user
            # thread + Context.shutdown) can't double-stop the profiler.
            flush_xprof = self._xprof_active
            self._xprof_active = False
        try:
            if flush_xprof:
                import jax

                jax.profiler.stop_trace()
        finally:
            with self._lock:
                if not self._active:
                    return
                self._active = False
                if self._native is not None:
                    self._native.stop()
                    return
            self._queue.put(None)
            if self._thread:
                self._thread.join(timeout=5)
                self._thread = None

    @property
    def active(self) -> bool:
        return self._active

    def _now_us(self) -> float:
        return (time.perf_counter() - self._start_ts) * 1e6

    # -- event surface -----------------------------------------------------

    def begin(self, tensor_name: str, activity: str) -> None:
        if not self._active:
            return
        if self._native is not None:
            self._native.event(tensor_name, activity, "B", self._now_us())
            return
        self._queue.put({"name": activity, "cat": tensor_name, "ph": "B",
                         "ts": self._now_us(), "pid": os.getpid(),
                         "tid": tensor_name})

    def end(self, tensor_name: str, activity: Optional[str] = None) -> None:
        if not self._active:
            return
        if self._native is not None:
            self._native.event(tensor_name, activity or "", "E",
                               self._now_us())
            return
        self._queue.put({"name": activity or "", "cat": tensor_name,
                         "ph": "E", "ts": self._now_us(),
                         "pid": os.getpid(), "tid": tensor_name})

    def instant(self, name: str) -> None:
        if not self._active:
            return
        if self._native is not None:
            self._native.event("marker", name, "i", self._now_us())
            return
        self._queue.put({"name": name, "ph": "i", "ts": self._now_us(),
                         "pid": os.getpid(), "tid": "marker", "s": "g"})

    def mark_cycle(self) -> None:
        """Cycle markers (reference HOROVOD_TIMELINE_MARK_CYCLES)."""
        if self._mark_cycles:
            self.instant("CYCLE")

    def recovery(self, counter: str) -> None:
        """Recovery-counter bump as an instant event (fed by
        common.faults.RecoveryStats)."""
        self.instant(f"{RECOVERY}:{counter}")

    # -- writer thread (reference timeline.cc TimelineWriter) --------------

    def _writer(self) -> None:
        # STREAMS each event to disk as it arrives (the native writer and
        # the reference's TimelineWriter both do) — buffering everything
        # until stop() would grow without bound on a long traced run.
        try:
            f = open(self._filename, "w")
        except OSError:
            while self._queue.get() is not None:
                pass
            return
        try:
            f.write('{"displayTimeUnit": "ms", "traceEvents": [\n')
            first = True
            while True:
                ev = self._queue.get()
                if ev is None:
                    break
                if not first:
                    f.write(",\n")
                json.dump(ev, f)
                first = False
                if self._queue.empty():
                    f.flush()
            f.write("\n]}\n")
        except OSError:
            pass
        finally:
            try:
                f.close()
            except OSError:
                pass
