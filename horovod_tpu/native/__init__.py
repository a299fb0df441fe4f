"""Native runtime bindings (ctypes over libhvdtpu_native.so).

The reference keeps its runtime core in C++ (SURVEY.md §2.1: operations,
timeline, wire format, fusion — ~18.5k LoC); this package is the
TPU-native counterpart for the pieces that remain host-side under XLA:
the timeline writer (lock-free ring + writer thread), the controller wire
format, and the fusion planner. Built on first import with the system
toolchain; every consumer has a pure-Python fallback, so the framework
works (slower) without a compiler.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import List, Optional, Sequence, Tuple
from ..common.config import runtime_env

logger = logging.getLogger("horovod_tpu")

_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_DIR, "libhvdtpu_native.so")
_lib: Optional[ctypes.CDLL] = None
_lib_lock = threading.Lock()
_build_attempted = False
_status = "absent"


def _build() -> bool:
    try:
        r = subprocess.run(["make", "-C", _DIR, "-s"],
                           capture_output=True, text=True, timeout=120)
        if r.returncode != 0:
            logger.warning("native build failed:\n%s", r.stderr[-2000:])
            return False
        return os.path.exists(_LIB_PATH)
    except (OSError, subprocess.TimeoutExpired) as e:
        logger.warning("native build unavailable: %s", e)
        return False


def _stale() -> bool:
    """No binary, or one older than a source it is built from. The .so
    is git-ignored, so a checkout carries none and a copied tree may
    carry one from other sources."""
    try:
        built = os.path.getmtime(_LIB_PATH)
    except OSError:
        return True
    return any(os.path.getmtime(os.path.join(_DIR, f)) > built
               for f in os.listdir(_DIR)
               if f.endswith(".cc") or f == "Makefile")


def load() -> Optional[ctypes.CDLL]:
    """Load the native library, (re)building it first when it is missing
    or stale; None if unavailable. One build per process at most."""
    global _lib, _build_attempted, _status
    with _lib_lock:
        if _lib is not None:
            return _lib
        # Second pass: a binary that looked fresh but lacks a symbol
        # this tree binds was built from other sources after all.
        for rebuild in (_stale(), True):
            if rebuild:
                if _build_attempted:
                    return None
                _build_attempted = True
                if runtime_env("DISABLE_NATIVE") == "1" or not _build():
                    return None
            try:
                lib = ctypes.CDLL(_LIB_PATH)
                _bind_signatures(lib)
            except OSError as e:
                logger.warning("native library load failed: %s", e)
                return None
            except AttributeError:
                continue
            _lib = lib
            _status = "rebuilt" if _build_attempted else "loaded"
            return _lib
        logger.warning("native library unusable after rebuild; using "
                       "Python fallbacks")
        return None


def status() -> str:
    """How the library came into this process: ``"loaded"`` (a binary
    newer than its sources was found), ``"rebuilt"`` (``make`` ran
    first) or ``"absent"`` (Python fallbacks in use)."""
    load()
    return _status


def _bind_signatures(lib: ctypes.CDLL) -> None:
        # Signatures.
        lib.hvt_timeline_start.argtypes = [ctypes.c_char_p]
        lib.hvt_timeline_start.restype = ctypes.c_int
        lib.hvt_timeline_event.argtypes = [ctypes.c_char_p, ctypes.c_char_p,
                                           ctypes.c_char, ctypes.c_double]
        lib.hvt_timeline_event.restype = None
        lib.hvt_timeline_stop.restype = ctypes.c_int
        lib.hvt_timeline_dropped.restype = ctypes.c_uint64
        lib.hvt_plan_fusion.argtypes = [
            ctypes.c_int64, ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int64, ctypes.POINTER(ctypes.c_int32)]
        lib.hvt_plan_fusion.restype = ctypes.c_int64
        lib.hvt_encode_request.restype = ctypes.c_int64
        lib.hvt_encode_request.argtypes = [
            ctypes.c_int32, ctypes.c_uint8, ctypes.c_uint8, ctypes.c_int32,
            ctypes.c_uint8, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_uint8,
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64]
        lib.hvt_decode_request.restype = ctypes.c_int64
        lib.hvt_decode_request.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_char_p,
            ctypes.c_int64, ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_uint8]
        lib.hvt_encode_response.restype = ctypes.c_int64
        lib.hvt_encode_response.argtypes = [
            ctypes.c_uint8, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64]
        lib.hvt_decode_response.restype = ctypes.c_int64
        lib.hvt_decode_response.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_char_p,
            ctypes.c_int64, ctypes.c_char_p, ctypes.c_int64]
        # controller core
        lib.hvd_nt_new.argtypes = [ctypes.c_int]
        lib.hvd_nt_new.restype = ctypes.c_void_p
        lib.hvd_nt_free.argtypes = [ctypes.c_void_p]
        lib.hvd_nt_increment.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                         ctypes.c_int]
        lib.hvd_nt_increment.restype = ctypes.c_int
        lib.hvd_nt_pending.argtypes = [ctypes.c_void_p]
        lib.hvd_nt_pending.restype = ctypes.c_int64
        lib.hvd_nt_missing.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                       ctypes.POINTER(ctypes.c_uint8),
                                       ctypes.c_int]
        lib.hvd_nt_missing.restype = ctypes.c_int
        lib.hvd_lru_new.argtypes = [ctypes.c_int64]
        lib.hvd_lru_new.restype = ctypes.c_void_p
        lib.hvd_lru_free.argtypes = [ctypes.c_void_p]
        lib.hvd_lru_lookup.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.hvd_lru_lookup.restype = ctypes.c_int
        lib.hvd_lru_put.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                    ctypes.c_char_p, ctypes.c_int]
        lib.hvd_lru_put.restype = ctypes.c_int
        lib.hvd_lru_size.argtypes = [ctypes.c_void_p]
        lib.hvd_lru_size.restype = ctypes.c_int64
        lib.hvd_lru_erase.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        # GP/EI autotuner core
        lib.hvd_gp_ei.argtypes = [
            ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_double),
            ctypes.c_int64, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_double), ctypes.c_int64,
            ctypes.c_double, ctypes.c_double, ctypes.c_double,
            ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_double)]
        lib.hvd_gp_ei.restype = ctypes.c_int64


def available() -> bool:
    return load() is not None


# -- fusion planner --------------------------------------------------------

def plan_fusion_native(elem_counts: Sequence[int],
                       dtype_codes: Sequence[int],
                       itemsizes: Sequence[int],
                       threshold_bytes: int) -> Optional[List[int]]:
    """Bucket ids per leaf, or None if native is unavailable."""
    lib = load()
    if lib is None:
        return None
    n = len(elem_counts)
    ec = (ctypes.c_int64 * n)(*elem_counts)
    dc = (ctypes.c_int32 * n)(*dtype_codes)
    it = (ctypes.c_int32 * n)(*itemsizes)
    out = (ctypes.c_int32 * n)()
    lib.hvt_plan_fusion(n, ec, dc, it, threshold_bytes, out)
    return list(out)


# -- wire format -----------------------------------------------------------

OP_CODES = {"allreduce": 0, "allgather": 1, "broadcast": 2, "alltoall": 3,
            "reducescatter": 4, "barrier": 5, "join": 6}
DTYPE_CODES = {"float32": 0, "bfloat16": 1, "float16": 2, "float64": 3,
               "int32": 4, "int64": 5, "int8": 6, "uint8": 7, "bool": 8}


def encode_request(rank: int, op_type: str, reduce_op: int, root_rank: int,
                   dtype: str, name: str,
                   shape: Sequence[int]) -> Optional[bytes]:
    lib = load()
    if lib is None:
        return None
    ndim = len(shape)
    shp = (ctypes.c_int64 * max(ndim, 1))(*shape) if ndim else \
        (ctypes.c_int64 * 1)()
    cap = 64 + len(name) + 8 * ndim
    buf = (ctypes.c_uint8 * cap)()
    n = lib.hvt_encode_request(
        rank, OP_CODES[op_type], reduce_op, root_rank,
        DTYPE_CODES.get(dtype, 0), name.encode(), shp, ndim, buf, cap)
    if n < 0:
        return None
    return bytes(buf[:n])


def decode_request(data: bytes) -> Optional[Tuple]:
    lib = load()
    if lib is None:
        return None
    buf = (ctypes.c_uint8 * len(data)).from_buffer_copy(data)
    rank = ctypes.c_int32()
    op = ctypes.c_uint8()
    rop = ctypes.c_uint8()
    root = ctypes.c_int32()
    dt = ctypes.c_uint8()
    name = ctypes.create_string_buffer(65536)
    shape = (ctypes.c_int64 * 32)()
    ndim = ctypes.c_uint8()
    rc = lib.hvt_decode_request(
        buf, len(data), ctypes.byref(rank), ctypes.byref(op),
        ctypes.byref(rop), ctypes.byref(root), ctypes.byref(dt),
        name, 65536, shape, ctypes.byref(ndim), 32)
    if rc != 0:
        return None
    op_names = {v: k for k, v in OP_CODES.items()}
    dt_names = {v: k for k, v in DTYPE_CODES.items()}
    op_name = op_names.get(op.value)
    dt_name = dt_names.get(dt.value)
    if op_name is None or dt_name is None:
        return None  # unknown code = malformed/version-skewed message
    return (rank.value, op_name, rop.value, root.value,
            dt_name, name.value.decode(),
            tuple(shape[i] for i in range(ndim.value)))


def encode_response(ok: bool, name: str, error: str) -> Optional[bytes]:
    lib = load()
    if lib is None:
        return None
    cap = 16 + len(name) + len(error)
    buf = (ctypes.c_uint8 * cap)()
    n = lib.hvt_encode_response(1 if ok else 0, name.encode(),
                                error.encode(), buf, cap)
    return bytes(buf[:n]) if n >= 0 else None


def decode_response(data: bytes) -> Optional[Tuple[bool, str, str]]:
    lib = load()
    if lib is None:
        return None
    buf = (ctypes.c_uint8 * len(data)).from_buffer_copy(data)
    ok = ctypes.c_uint8()
    name = ctypes.create_string_buffer(65536)
    err = ctypes.create_string_buffer(65536)
    rc = lib.hvt_decode_response(buf, len(data), ctypes.byref(ok),
                                 name, 65536, err, 65536)
    if rc != 0:
        return None
    return bool(ok.value), name.value.decode(), err.value.decode()


# -- controller negotiation core -------------------------------------------

class NegotiationTable:
    """Native tensor-readiness table (reference IncrementTensorCount,
    controller.cc:837-860). Falls back to a dict when the native library
    is unavailable."""

    def __init__(self, world_size: int):
        self.world_size = world_size
        self._lib = load()
        if self._lib is not None:
            self._h = self._lib.hvd_nt_new(world_size)
        else:
            self._h = None
            self._pending = {}
            self._py_lock = threading.Lock()

    def increment(self, name: str, rank: int) -> int:
        """1 = just became ready (all ranks in), 0 = pending,
        -1 = duplicate/invalid."""
        if self._h is not None:
            return self._lib.hvd_nt_increment(self._h, name.encode(), rank)
        with self._py_lock:
            if not 0 <= rank < self.world_size:
                return -1
            ranks = self._pending.setdefault(name, set())
            if rank in ranks:
                return -1
            ranks.add(rank)
            if len(ranks) == self.world_size:
                del self._pending[name]
                return 1
            return 0

    def pending_count(self) -> int:
        if self._h is not None:
            return int(self._lib.hvd_nt_pending(self._h))
        with self._py_lock:
            return len(self._pending)

    def missing_ranks(self, name: str) -> Optional[List[int]]:
        """Ranks that have not yet reported `name` (StallInspector input);
        None if the name is unknown/complete."""
        if self._h is not None:
            out = (ctypes.c_uint8 * self.world_size)()
            n = self._lib.hvd_nt_missing(self._h, name.encode(), out,
                                         self.world_size)
            if n < 0:
                return None
            return [i for i in range(self.world_size) if out[i]]
        with self._py_lock:
            if name not in self._pending:
                return None
            got = self._pending[name]
            return [r for r in range(self.world_size) if r not in got]

    def __del__(self):
        if getattr(self, "_h", None) is not None:
            self._lib.hvd_nt_free(self._h)
            self._h = None


class ResponseCacheNative:
    """Bounded LRU signature cache (reference response_cache.cc LRU bits).
    Falls back to an ordered-dict LRU without the native library."""

    def __init__(self, capacity: int):
        self.capacity = max(int(capacity), 1)
        self._lib = load()
        if self._lib is not None:
            self._h = self._lib.hvd_lru_new(self.capacity)
            # One reusable out-buffer per cache (not per put call).
            self._evict_buf = ctypes.create_string_buffer(65536)
        else:
            self._h = None
            import collections

            self._od = collections.OrderedDict()
            self._py_lock = threading.Lock()

    def lookup(self, key: str) -> bool:
        if self._h is not None:
            return bool(self._lib.hvd_lru_lookup(self._h, key.encode()))
        with self._py_lock:
            if key in self._od:
                self._od.move_to_end(key)
                return True
            return False

    def put(self, key: str, want_evicted: bool = True) -> Optional[str]:
        """Insert; returns the evicted key if capacity forced one out.
        Pass ``want_evicted=False`` on hot paths to skip the out-buffer
        (the native side accepts NULL)."""
        if self._h is not None:
            if not want_evicted:
                self._lib.hvd_lru_put(self._h, key.encode(), None, 0)
                return None
            buf = self._evict_buf
            if self._lib.hvd_lru_put(self._h, key.encode(), buf,
                                     len(buf)):
                return buf.value.decode()
            return None
        with self._py_lock:
            if key in self._od:
                self._od.move_to_end(key)
                return None
            self._od[key] = True
            if len(self._od) > self.capacity:
                victim, _ = self._od.popitem(last=False)
                return victim
            return None

    def erase(self, key: str) -> None:
        if self._h is not None:
            self._lib.hvd_lru_erase(self._h, key.encode())
            return
        with self._py_lock:
            self._od.pop(key, None)

    def __len__(self) -> int:
        if self._h is not None:
            return int(self._lib.hvd_lru_size(self._h))
        with self._py_lock:
            return len(self._od)

    def __del__(self):
        if getattr(self, "_h", None) is not None:
            self._lib.hvd_lru_free(self._h)
            self._h = None


# -- GP / expected-improvement core ----------------------------------------

def gp_ei_native(x, y, candidates, length_scale: float = 1.0,
                 noise: float = 1e-4, xi: float = 0.01
                 ) -> Optional[Tuple[int, List[float]]]:
    """(argmax index, EI per candidate) via the native GP core, or None if
    unavailable/numerically failed (caller uses the numpy path)."""
    lib = load()
    if lib is None:
        return None
    import numpy as np

    x = np.ascontiguousarray(np.atleast_2d(np.asarray(x, dtype=np.float64)))
    y = np.ascontiguousarray(np.asarray(y, dtype=np.float64))
    c = np.ascontiguousarray(np.atleast_2d(
        np.asarray(candidates, dtype=np.float64)))
    if x.shape[0] != y.shape[0] or x.shape[1] != c.shape[1]:
        return None
    n, d = x.shape
    m = c.shape[0]
    ei = np.empty(m, dtype=np.float64)
    dp = ctypes.POINTER(ctypes.c_double)
    idx = lib.hvd_gp_ei(
        x.ctypes.data_as(dp), y.ctypes.data_as(dp), n, d,
        c.ctypes.data_as(dp), m, length_scale, noise, xi,
        ei.ctypes.data_as(dp), None)
    if idx < 0:
        return None
    return int(idx), ei.tolist()


# -- timeline --------------------------------------------------------------

class NativeTimelineWriter:
    """Thin wrapper used by horovod_tpu.common.timeline.Timeline."""

    def __init__(self):
        self._lib = load()

    @property
    def available(self) -> bool:
        return self._lib is not None

    def start(self, path: str) -> bool:
        return self._lib is not None and \
            self._lib.hvt_timeline_start(path.encode()) == 0

    def event(self, tid: str, name: str, phase: str, ts_us: float) -> None:
        self._lib.hvt_timeline_event(tid.encode(), name.encode(),
                                     phase.encode()[0], ts_us)

    def stop(self) -> None:
        if self._lib is not None:
            self._lib.hvt_timeline_stop()

    def dropped(self) -> int:
        return int(self._lib.hvt_timeline_dropped()) if self._lib else 0
