"""Controller — cross-rank coordination & validation for eager collectives.

Reference: horovod/common/controller.cc:63-358 (ComputeResponseList) — a
rank-0 coordinator gathers per-rank Requests, waits until every rank has
submitted a tensor, validates shape/dtype/op consistency, fuses, and
broadcasts Responses. It exists because TF/PyTorch processes issue
gradients asynchronously in nondeterministic order.

TPU-native role: under single-controller JAX the submitting program is
SPMD, so ordering is deterministic and negotiation is vacuous — the
compile cache (eager.py) plays the ResponseCache role. In *multi-process*
mode (one Python process per host), XLA collectives still require every
process to issue the same program in the same order; a mismatch deadlocks
the ICI/DCN collective with no diagnostics. This controller is the guard
rail: before dispatching a new eager collective signature, ranks publish a
Request to the coordination KV store, rank 0 validates that all ranks
submitted a *matching* signature (same op, shape, dtype — the reference's
ConstructResponse checks, controller.cc:380-657) and publishes a Response;
mismatches produce a clear error on every rank instead of a hang. Repeat
signatures skip the round entirely (the ResponseCache fast path,
response_cache.h:45-100).

The transport is pluggable so the protocol is unit-testable with an
in-memory store (the reference tests Controller with mocked comms the same
way — SURVEY.md §4).
"""

from __future__ import annotations

import base64
import dataclasses
import json
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

from .config import runtime_env
from .exceptions import (HorovodInternalError, MismatchError,
                         TensorShapeMismatchError)


@dataclasses.dataclass(frozen=True)
class Request:
    """Reference: message.h:48-113 (Request: rank, type, dtype, shape,
    name, root_rank, ...). ``wire_dtype`` and ``process_set`` extend
    the reference contract for this framework's integrity layer
    (docs/integrity.md): two ranks agreeing on shape/dtype/op but
    configured with different reduction compressions (or submitting
    against different process sets) would compile different XLA
    programs and hang just the same — so they negotiate too."""

    rank: int
    op_type: str          # "allreduce" | "allgather" | ...
    tensor_name: str
    dtype: str
    shape: Tuple[int, ...]
    reduce_op: int = 0
    root_rank: int = -1
    wire_dtype: str = ""   # reduction compression / wire decision tag
    process_set: str = ""  # engine scope ("" == world)

    def signature(self) -> str:
        return json.dumps([self.op_type, self.tensor_name, self.dtype,
                           list(self.shape), self.reduce_op,
                           self.root_rank, self.wire_dtype,
                           self.process_set])

    def encode(self) -> str:
        """Wire format for the KV round: the native codec (wire.cc) when
        built and the dtype/op are in its tables, else JSON. A one-char
        prefix tags the format so mixed availability across ranks still
        interops (the decoder dispatches on it). The integrity-contract
        extension fields (wire_dtype / process_set) are not in the
        native tables, so a request carrying them rides JSON."""
        import os

        from .. import native

        if (runtime_env("WIRE_FORMAT") != "json"
                and not self.wire_dtype and not self.process_set
                and native.available() and self.op_type in native.OP_CODES
                and self.dtype in native.DTYPE_CODES):
            data = native.encode_request(
                self.rank, self.op_type, self.reduce_op, self.root_rank,
                self.dtype, self.tensor_name, self.shape)
            if data is not None:
                return "w:" + base64.b64encode(data).decode()
        return "j:" + json.dumps(dataclasses.asdict(self))

    @classmethod
    def decode(cls, raw: str) -> "Request":
        from .. import native

        if raw.startswith("w:"):
            if not native.available():
                raise HorovodInternalError(
                    "peer encoded its request with the native wire codec "
                    "but this rank's libhvdtpu_native.so failed to "
                    "build/load — check the native build log, or set "
                    "HVD_TPU_WIRE_FORMAT=json on ALL ranks")
            tup = native.decode_request(base64.b64decode(raw[2:]))
            if tup is None:
                raise HorovodInternalError(
                    f"undecodable wire request: {raw[:80]!r}")
            rank, op_type, reduce_op, root_rank, dtype, name, shape = tup
            return cls(rank, op_type, name, dtype, tuple(shape),
                       reduce_op, root_rank)
        d = json.loads(raw[2:])
        d["shape"] = tuple(d["shape"])
        return cls(**d)


@dataclasses.dataclass
class Response:
    """Reference: message.h:145-244 (Response: type, names, error).
    ``kind`` distinguishes the failure family ("mismatch" vs "timeout")
    and ``ranks`` names the offending global ranks for mismatches —
    both ride the JSON wire form only (the native codec carries the
    reference triple; a response using them skips it)."""

    ok: bool
    tensor_name: str
    error: str = ""
    kind: str = ""
    ranks: Tuple[int, ...] = ()

    def encode(self) -> str:
        import os

        from .. import native

        if (runtime_env("WIRE_FORMAT") != "json"
                and not self.kind and not self.ranks
                and native.available()):
            data = native.encode_response(self.ok, self.tensor_name,
                                          self.error)
            if data is not None:
                return "w:" + base64.b64encode(data).decode()
        d = dataclasses.asdict(self)
        d["ranks"] = list(self.ranks)
        return "j:" + json.dumps(d)

    @classmethod
    def decode(cls, raw: str) -> "Response":
        from .. import native

        if raw.startswith("w:"):
            if not native.available():
                raise HorovodInternalError(
                    "peer encoded its response with the native wire codec "
                    "but this rank's libhvdtpu_native.so failed to "
                    "build/load — check the native build log, or set "
                    "HVD_TPU_WIRE_FORMAT=json on ALL ranks")
            tup = native.decode_response(base64.b64decode(raw[2:]))
            if tup is None:
                raise HorovodInternalError(
                    f"undecodable wire response: {raw[:80]!r}")
            return cls(*tup)
        d = json.loads(raw[2:])
        return cls(d["ok"], d["tensor_name"], d.get("error", ""),
                   d.get("kind", ""), tuple(d.get("ranks", ())))


class KVTransport:
    """Abstract blocking KV store used for the negotiation round."""

    def set(self, key: str, value: str) -> None:
        raise NotImplementedError

    def get(self, key: str, timeout_s: float) -> Optional[str]:
        raise NotImplementedError


class InMemoryTransport(KVTransport):
    """Single-process/loopback transport for tests: all ranks share a dict
    (the Gloo-rendezvous role in the reference test tier)."""

    def __init__(self):
        self._data: Dict[str, str] = {}
        self._cond = threading.Condition()

    def set(self, key: str, value: str) -> None:
        with self._cond:
            self._data[key] = value
            self._cond.notify_all()

    def get(self, key: str, timeout_s: float) -> Optional[str]:
        deadline = time.monotonic() + timeout_s
        with self._cond:
            while key not in self._data:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return None
                self._cond.wait(remaining)
            return self._data[key]


class JaxKVTransport(KVTransport):
    """Production transport over the JAX coordination-service KV store
    (the HTTP-KV/gloo-rendezvous replacement — SURVEY.md §5 'Distributed
    communication backend')."""

    def set(self, key: str, value: str) -> None:
        from jax._src import distributed as jdist

        jdist.global_state.client.key_value_set(key, value,
                                                allow_overwrite=True)

    def get(self, key: str, timeout_s: float) -> Optional[str]:
        from jax._src import distributed as jdist

        try:
            return jdist.global_state.client.blocking_key_value_get(
                key, int(timeout_s * 1000))
        except Exception as e:
            # Only a KV timeout means "rank didn't submit"; any other
            # failure (dead coordinator, connection loss) must surface as
            # itself, not masquerade as a program-order divergence.
            msg = str(e).upper()
            if "DEADLINE" in msg or "TIMEOUT" in msg or "NOT_FOUND" in msg:
                return None
            raise HorovodInternalError(
                f"coordination-service KV failure for {key}: {e}") from e


class Controller:
    """Negotiates one eager-collective signature across processes."""

    def __init__(self, rank: int, size: int, transport: KVTransport,
                 timeout_s: float = 60.0, namespace: str = "hvd_tpu/ctl",
                 incarnation: int = 0):
        """``incarnation`` scopes the KV namespace per init()-cycle: the
        JAX coordination KV outlives shutdown()/re-init (elastic restarts,
        tests), and a fresh controller must not read a prior incarnation's
        rounds — a stale ok=True response would wave a now-mismatched
        collective straight into the deadlock this class exists to
        prevent. Every rank of a world must pass the same value (the
        per-process Context counter in basics.py); if ranks disagree —
        itself a divergence — rounds simply time out."""
        self.rank = rank
        self.size = size
        self.transport = transport
        self.timeout_s = timeout_s
        self.ns = f"{namespace}/i{incarnation}"
        # Unbounded, order-independent membership set — deliberately NOT
        # the bounded LRU (native ResponseCacheNative): every rank must
        # agree on cache membership or fast paths desynchronize (rank A
        # hits, rank B posts a request nobody answers). The reference
        # keeps its bounded cache coherent with per-cycle cross-rank
        # bitwise AND/OR sync (response_cache.cc CacheCoordinator); with
        # signatures being ~100-byte strings, unbounded is the simpler
        # safe choice here. The native LRU serves single-process caches
        # (e.g. compiled-fn eviction), where coherence is not a concern.
        self._cache: set = set()
        self._name_seq: Dict[str, int] = {}
        self._lock = threading.Lock()
        # Rank 0's gather bookkeeping rides the native NegotiationTable
        # (controller_core.cc, the IncrementTensorCount analog —
        # reference controller.cc:837-860); Python dict fallback inside.
        from .. import native

        self._table = native.NegotiationTable(size) if rank == 0 else None

    def negotiate(self, req: Request) -> Response:
        """Validate that every rank submitted a matching request.

        Fast path: a signature seen before returns immediately (cache hit —
        no KV round; reference response_cache fast path controller.cc:133-203).
        """
        sig = req.signature()
        with self._lock:
            if sig in self._cache:
                return Response(True, req.tensor_name)

        if self.size == 1:
            with self._lock:
                self._cache.add(sig)
            return Response(True, req.tensor_name)

        # Round key: (tensor name, per-name sequence) — NOT the full
        # signature. The reference negotiates by name (controller.cc
        # IncrementTensorCount keys on tensor name), which is what lets
        # the coordinator *see* a mismatched shape/dtype for the same
        # tensor and report it; signature-keyed rounds would send diverged
        # ranks to different keys and reduce every mismatch to a timeout.
        # Not a shared global counter either: concurrent negotiations of
        # different names may interleave differently per process, and a
        # global counter would then pair mismatched KV keys across ranks.
        # The per-name sequence keeps a renegotiated name (cache eviction)
        # from reading a stale prior response out of the KV store.
        import hashlib

        with self._lock:
            seq = self._name_seq.get(req.tensor_name, 0)
            self._name_seq[req.tensor_name] = seq + 1
        name_h = hashlib.sha1(req.tensor_name.encode()).hexdigest()[:16]
        key_base = f"{self.ns}/{name_h}/{seq}"
        self.transport.set(f"{key_base}/req/{self.rank}", req.encode())

        if self.rank == 0:
            # Coordinator: gather all requests (MPI_Gatherv analog,
            # mpi_controller.cc:134), track arrivals in the NegotiationTable
            # (IncrementTensorCount analog), validate field-by-field,
            # publish the response (MPI_Bcast analog, :158). The gather
            # runs to COMPLETION before validating so the report names
            # EVERY offending rank, not just the first — at pod scale
            # "which workers diverged" is the actionable bit.
            mine = dataclasses.replace(req, rank=0)
            error, kind = "", ""
            offenders: List[int] = []
            first_bad: Optional[Request] = None
            for r in range(self.size):
                raw = self.transport.get(f"{key_base}/req/{r}",
                                         self.timeout_s)
                if raw is None:
                    # Zero-timeout poll of the not-yet-gathered ranks so
                    # the report names only genuinely missing ranks
                    # (reference stall_inspector.cc report style), not
                    # every rank after the first straggler.
                    for r2 in range(r + 1, self.size):
                        if self.transport.get(f"{key_base}/req/{r2}",
                                              0.0) is not None:
                            self._table.increment(key_base, r2)
                    missing = self._table.missing_ranks(key_base)
                    if missing is None:
                        missing = [r]
                    error = (f"ranks {missing} did not submit a collective "
                             f"within {self.timeout_s}s (stalled or "
                             "diverged program order)")
                    kind = "timeout"
                    offenders = list(missing)
                    break
                self._table.increment(key_base, r)
                other = Request.decode(raw)
                if dataclasses.replace(other, rank=0) != mine:
                    offenders.append(r)
                    if first_bad is None:
                        first_bad = other
            if not error and offenders:
                kind = "mismatch"
                error = (f"ranks {offenders} submitted a mismatched "
                         f"collective: expected {mine}, e.g. rank "
                         f"{offenders[0]} sent {first_bad} (reference: "
                         "controller.cc:390-621 validation)")
            resp = Response(not error, req.tensor_name, error, kind,
                            tuple(offenders))
            self.transport.set(f"{key_base}/resp", resp.encode())
        else:
            raw = self.transport.get(f"{key_base}/resp", self.timeout_s)
            if raw is None:
                raise HorovodInternalError(
                    f"controller response timeout after {self.timeout_s}s "
                    f"for {req.tensor_name}")
            resp = Response.decode(raw)

        if resp.ok:
            with self._lock:
                self._cache.add(sig)
        elif resp.kind == "mismatch":
            # Typed, named-rank contract failure (docs/integrity.md) —
            # same exception on every rank instead of a deadlocked
            # collective.
            raise MismatchError(resp.error, ranks=resp.ranks)
        elif resp.kind == "timeout":
            # A missing rank is a RUNTIME failure (dead/hung peer), not
            # a program bug: HorovodInternalError so elastic recovery
            # retries it — same classification as the join-round path.
            raise HorovodInternalError(resp.error)
        else:
            raise TensorShapeMismatchError(resp.error)
        return resp

    def exchange(self, tag: str, value: str) -> List[str]:
        """Symmetric all-gather of small per-rank strings through the KV
        store — the AlltoallGetRecvSplits transport (reference:
        controller.h:56-58 gathers every rank's send-split vector so each
        rank learns its recv splits). Returns the values rank-ordered.

        Unlike negotiate(), the payload is data, not a signature, so
        every call is a fresh round (per-tag sequence key)."""
        import hashlib

        with self._lock:
            seq = self._name_seq.get("exch:" + tag, 0)
            self._name_seq["exch:" + tag] = seq + 1
        tag_h = hashlib.sha1(tag.encode()).hexdigest()[:16]
        base = f"{self.ns}/exch/{tag_h}/{seq}"
        self.transport.set(f"{base}/{self.rank}", value)
        out: List[str] = []
        for r in range(self.size):
            raw = self.transport.get(f"{base}/{r}", self.timeout_s)
            if raw is None:
                raise HorovodInternalError(
                    f"rank {r} did not publish its value for exchange "
                    f"{tag!r} within {self.timeout_s}s")
            out.append(raw)
        return out

    def cache_size(self) -> int:
        with self._lock:
            return len(self._cache)
