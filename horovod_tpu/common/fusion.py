"""Tensor fusion — bucketing small tensors into flat buffers.

TPU-native re-design of the reference's FusionBufferManager + FuseResponses
(horovod/common/fusion_buffer_manager.cc; controller.cc:686-809). The
reference memcpys tensors into a persistent 64 MiB device buffer so one
NCCL call covers many small gradients. Under XLA we express the same thing
functionally: flatten a pytree, group leaves into ≤threshold same-dtype
buckets, ``concatenate`` each bucket into one flat array, run ONE collective
per bucket, then split/reshape back. Inside ``jit`` the concat/split are
pure data-movement that XLA fuses/elides where possible, and each bucket
becomes a single large AllReduce on the wire — the exact latency win fusion
buys the reference, with no hand-managed buffer.

Bucket *plans* are deterministic functions of (shapes, dtypes, threshold)
so every rank computes the identical plan without negotiation — the
property the reference's coordinator exists to enforce (controller.cc:63-358)
falls out for free in SPMD.

Since ISSUE 28 the default gradient reduction (linear op, flat rank axis,
per-element wire) does NOT come through here: it reduces each leaf where
it lies (``optim._reduce_tree``), which a chip measured 8% faster a step
than these buckets' copies in and out (PERF.md, PR 28). The buckets stay
for what needs a flat buffer by construction: block-scaled int8, the mesh
router's and the staged pipeline's per-axis shards, Adasum's per-bucket
dot products, ZeRO's positional shards, the eager engine.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import metrics as metrics_lib
from . import scopes

# Telemetry (docs/metrics.md): plan/assign run at trace time (host
# Python), so these record per compiled program, not per step. Guarded
# by one module-level bool so the disabled path costs a single check.
_METRICS_ON = metrics_lib.enabled()
_M_PLANS = metrics_lib.counter(
    "hvd_tpu_fusion_plans_total", "fusion bucket plans computed")
_M_BUCKETS = metrics_lib.gauge(
    "hvd_tpu_fusion_buckets", "bucket count of the most recent plan")
_M_FILL = metrics_lib.gauge(
    "hvd_tpu_fusion_fill_efficiency",
    "mean bucket fill fraction (bucket bytes / threshold) of the most "
    "recent plan")
_M_WIRE_BUCKETS = metrics_lib.counter(
    "hvd_tpu_fusion_bucket_wire_total",
    "fusion buckets by the wire format assign_wire_dtypes stamped",
    labels=("wire",))
_M_WIRE_BYTES = metrics_lib.counter(
    "hvd_tpu_fusion_wire_bytes_total",
    "bytes planned onto each wire format (per compiled plan, raw-dtype "
    "bytes of the buckets routed there)",
    labels=("wire",))


@dataclasses.dataclass(frozen=True)
class Bucket:
    """One fusion bucket: indices of the leaves it covers (in flatten order),
    their shapes, and the flat element count."""

    leaf_indices: Tuple[int, ...]
    shapes: Tuple[Tuple[int, ...], ...]
    dtype: Any
    total_elems: int


@dataclasses.dataclass(frozen=True)
class FusionPlan:
    buckets: Tuple[Bucket, ...]
    treedef: Any
    num_leaves: int
    # Per-bucket wire format for quantized reduction, parallel to
    # ``buckets`` ("int8"/"bf16"/"none"); None until
    # :func:`assign_wire_dtypes` stamps the plan. Part of the plan (not
    # recomputed at the call site) so every rank's compiled program
    # carries the identical bucket->wire mapping.
    wire_dtypes: Optional[Tuple[str, ...]] = None


def plan_fusion(tree, threshold_bytes: int,
                _telemetry: bool = True) -> FusionPlan:
    """Greedy same-dtype bucketing in flatten order (reference fuses in
    response order up to the threshold, controller.cc:686-809).

    The bucket-id assignment runs in the native planner
    (native/fusion_planner.cc hvt_plan_fusion) when the library is built —
    for 100k-leaf LLM trees the O(n) pass stays off the Python profile.
    The Python fallback implements byte-identical semantics (same
    per-dtype running bucket, same byte threshold) so plans never diverge
    across ranks with mixed availability.

    Buckets are emitted in bucket-id (opening) order: sharded optimizer
    state (ZeRO-1/FSDP) is positionally indexed by ``plan.buckets``, so
    the plan is a checkpoint layout and must stay stable across
    releases.
    """
    leaves, treedef = jax.tree.flatten(tree)
    leaves = [l if hasattr(l, "dtype") else jnp.asarray(l) for l in leaves]
    elem_counts = [int(np.prod(l.shape)) if l.shape else 1 for l in leaves]
    itemsizes = [np.dtype(l.dtype).itemsize for l in leaves]
    dtype_strs = [str(l.dtype) for l in leaves]
    dtype_codes = {}
    for d in dtype_strs:
        dtype_codes.setdefault(d, len(dtype_codes))

    from ..native import plan_fusion_native

    bucket_ids = plan_fusion_native(
        elem_counts, [dtype_codes[d] for d in dtype_strs], itemsizes,
        threshold_bytes)
    if bucket_ids is None:
        # Python fallback — mirror of fusion_planner.cc.
        open_buckets = {}  # dtype -> [bucket_id, bytes_used]
        next_bucket = 0
        bucket_ids = []
        for i in range(len(leaves)):
            nbytes = elem_counts[i] * itemsizes[i]
            o = open_buckets.get(dtype_strs[i])
            if o is None:
                open_buckets[dtype_strs[i]] = [next_bucket, nbytes]
                bucket_ids.append(next_bucket)
                next_bucket += 1
                continue
            if o[1] > 0 and o[1] + nbytes > threshold_bytes:
                o[0] = next_bucket
                next_bucket += 1
                o[1] = 0
            o[1] += nbytes
            bucket_ids.append(o[0])

    by_bucket = {}
    for i, b in enumerate(bucket_ids):
        by_bucket.setdefault(b, []).append(i)
    buckets = [
        Bucket(tuple(idxs),
               tuple(tuple(leaves[i].shape) for i in idxs),
               leaves[idxs[0]].dtype,
               sum(elem_counts[i] for i in idxs))
        for b, idxs in sorted(by_bucket.items())
    ]
    # ``_telemetry=False`` suppresses the metric bumps for plans built
    # purely to PRICE an already-planned program (the eager engine's
    # byte accounting) — otherwise every grouped signature counts twice.
    if _METRICS_ON and _telemetry:
        _M_PLANS.inc()
        _M_BUCKETS.set(len(buckets))
        if buckets and threshold_bytes > 0:
            fills = [min(1.0, b.total_elems
                         * np.dtype(b.dtype).itemsize / threshold_bytes)
                     for b in buckets]
            _M_FILL.set(sum(fills) / len(fills))
    return FusionPlan(tuple(buckets), treedef, len(leaves))


# Wire formats a bucket can ride in a quantized reduction.
WIRE_NONE = "none"    # native dtype (ints, half-precision small buckets)
WIRE_BF16 = "bf16"    # cast to bf16 around the collective (2x over fp32)
WIRE_INT8 = "int8"    # block-scaled int8 quantized allreduce (4x)


def assign_wire_dtypes(plan: FusionPlan, quantize_min_bytes: int,
                       small_wire: str = WIRE_BF16,
                       _telemetry: bool = True) -> FusionPlan:
    """Stamp per-bucket compression decisions onto a plan.

    Quantization has fixed per-bucket costs (quantize/dequant kernels,
    one fp32 scale per 4096-element block, chunk padding to n*4096) that
    only amortize on large buckets, and the bandwidth win only matters
    where the bytes are. So: float buckets of at least
    ``quantize_min_bytes`` ride int8 (the quantized allreduce); smaller
    fp32/fp64 buckets ride ``small_wire`` (bf16 cast — free, still 2x);
    half-precision buckets below the threshold and integer buckets ride
    uncompressed. Deterministic in (plan, threshold) — every rank stamps
    the identical mapping without negotiation, the same property the
    bucket plan itself has.
    """
    wires = []
    for b in plan.buckets:
        dt = np.dtype(b.dtype)
        if not jnp.issubdtype(dt, jnp.floating):
            wires.append(WIRE_NONE)
            continue
        if b.total_elems * dt.itemsize >= quantize_min_bytes:
            wires.append(WIRE_INT8)
        elif dt.itemsize > 2 and small_wire:
            wires.append(small_wire)
        else:
            wires.append(WIRE_NONE)
    if _METRICS_ON and _telemetry:
        for b, w in zip(plan.buckets, wires):
            _M_WIRE_BUCKETS.labels(wire=w).inc()
            _M_WIRE_BYTES.labels(wire=w).inc(
                b.total_elems * np.dtype(b.dtype).itemsize)
    return dataclasses.replace(plan, wire_dtypes=tuple(wires))


# Default size threshold for quantizing an alltoall payload — the same
# amortization argument as assign_wire_dtypes' bucket threshold
# (quantize/dequant kernels + per-4096-block scales + block padding only
# pay off on large slabs), applied to the dispatch/combine exchange.
A2A_QUANTIZE_MIN_BYTES = 64 * 1024


def assign_alltoall_wire(nbytes: int,
                         quantize_min_bytes: int = A2A_QUANTIZE_MIN_BYTES,
                         small_wire: str = WIRE_BF16) -> str:
    """Wire format for one alltoall payload of ``nbytes`` raw bytes —
    the :func:`assign_wire_dtypes` size-threshold rule lifted to the
    dispatch path (``wire="auto"`` on ``parallel.moe.moe_layer`` and
    the eager ``alltoall``): int8 at or above the threshold, the cheap
    ``small_wire`` cast below it. Deterministic in (nbytes, threshold),
    so every rank picks the identical format without negotiation."""
    if nbytes >= quantize_min_bytes:
        return WIRE_INT8
    return small_wire or WIRE_NONE


def fuse(tree, plan: FusionPlan,
         lengths: Optional[Sequence[int]] = None) -> List[jnp.ndarray]:
    """Concatenate each bucket's leaves into one flat array
    (the MemcpyInFusionBuffer analog, collective_operations.h:97-110).

    ``lengths[i]``, where it is more than bucket ``i`` holds, is the
    length its flat array is made: zeros join the same concatenate, so a
    reduction on a block grid (the int8 wire's n x 4096) finds its buffer
    aligned and pads nothing itself, which would be one more pass over
    it. :func:`unfuse` does not look at the tail."""
    leaves = jax.tree.leaves(tree)
    flats = []
    with jax.named_scope(scopes.PACK):
        for i, b in enumerate(plan.buckets):
            parts = [jnp.ravel(leaves[j]) for j in b.leaf_indices]
            tail = lengths[i] - b.total_elems if lengths else 0
            if tail > 0:
                parts.append(jnp.zeros((tail,), parts[0].dtype))
            flats.append(parts[0] if len(parts) == 1
                         else jnp.concatenate(parts))
    return flats


def unfuse(flats: Sequence[jnp.ndarray], plan: FusionPlan):
    """Split flat buffers back into the original pytree
    (the MemcpyOutFusionBuffer analog). A flat buffer may be longer than
    its bucket (:func:`fuse`'s ``lengths``): the tail is left behind."""
    leaves: List[Any] = [None] * plan.num_leaves
    with jax.named_scope(scopes.UNPACK):
        for flat, b in zip(flats, plan.buckets):
            off = 0
            for i, shape in zip(b.leaf_indices, b.shapes):
                n = int(np.prod(shape)) if shape else 1
                leaves[i] = jax.lax.slice_in_dim(
                    flat, off, off + n).reshape(shape)
                off += n
    return jax.tree.unflatten(plan.treedef, leaves)


def fused_apply(tree, fn: Callable, threshold_bytes: int = 64 * 1024 * 1024):
    """Apply ``fn`` (e.g. an allreduce lambda) to fusion buckets of ``tree``
    and restore the tree. This is the whole fusion pipeline of the reference
    — memcpy-in, collective, memcpy-out — as three pure functions."""
    plan = plan_fusion(tree, threshold_bytes)
    flats = fuse(tree, plan)
    out = [fn(f) for f in flats]
    return unfuse(out, plan)


def pad_to_multiple(flat: jnp.ndarray, multiple: int):
    """Pad a flat buffer so reduce-scatter staging divides evenly (the
    hierarchical path needs dim0 % local_size == 0). Returns (padded, n)."""
    n = flat.shape[0]
    rem = (-n) % multiple
    if rem:
        flat = jnp.concatenate([flat, jnp.zeros((rem,), dtype=flat.dtype)])
    return flat, n
