"""DistributedOptimizer — the gradient-averaging wrapper.

Reference equivalents: horovod/tensorflow/__init__.py:465-561
(DistributedOptimizer), :564-629 (DistributedGradientTape),
horovod/torch/optimizer.py:103-207 (per-grad async allreduce hooks), and the
local-gradient-aggregation helpers (tensorflow/gradient_aggregation.py:16)
for ``backward_passes_per_step > 1``.

TPU-native design: the optimizer is an ``optax.GradientTransformation``
wrapper meant to run *inside* the jitted SPMD step function, where the
reference's whole async machinery (hooks, handles, background thread) is
unnecessary — the gradients of every rank are produced by the same traced
program, so the wrapper simply inserts the allreduces between ``grad()``
and ``update()``, each gradient reduced where it lies, and XLA runs them
beside the remaining backprop compute where ``hvd.spmd_step`` asked it to
(asynchronous all-reduces under its latency-hiding scheduler play the role
of Horovod's background thread; common/xla_tuning.py).

Also provides ``DistributedGradFn`` (the DistributedGradientTape analog):
wraps ``jax.grad``/``jax.value_and_grad`` results with the same reduction.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from .common import fusion as fusion_lib
from .common import integrity as integrity_lib
from .common import metrics as metrics_lib
from .common import scopes
from .common.integrity import (current_loss_scale, observe_guard)  # noqa: F401 — re-exported API
from .ops import collectives as C
from .ops.compression import NoneCompressor

# Unified telemetry (docs/metrics.md): host-side step timing. The
# grad/comm/apply split cannot be observed from inside one jitted step
# (XLA owns the schedule) — StepTimer below times phases at dispatch
# boundaries and bridges them into jax.profiler traces; AutotunedStepper
# records the end-to-end step wall time it already measures for tuning.
_METRICS_ON = metrics_lib.enabled()
_M_STEP = metrics_lib.histogram(
    "hvd_tpu_step_seconds",
    "end-to-end training step wall time (AutotunedStepper, blocked)")
_M_PHASE = metrics_lib.histogram(
    "hvd_tpu_step_phase_seconds",
    "per-phase step wall time from StepTimer (grad/comm/apply/...)",
    labels=("phase",))
_M_EF_NORM = metrics_lib.gauge(
    "hvd_tpu_ef_residual_norm",
    "global L2 norm of the error-feedback quantization residual "
    "(observe_ef_residual)")
_M_REBUILDS = metrics_lib.counter(
    "hvd_tpu_autotune_rebuilds_total",
    "step-function rebuilds triggered by autotuner point moves")
_M_ZERO_GATHER = metrics_lib.counter(
    "hvd_tpu_zero_gather_bytes_total",
    "bytes moved by the ZeRO sharded-training collectives, ring-"
    "accounted per device at trace time (docs/zero.md): kind=param "
    "is the stage-3 on-demand parameter all-gather, kind=grad the "
    "gradient reduce-scatter descent, kind=update the stage-1/2 "
    "update all-gather; wire/axis show which hop carried them",
    labels=("kind", "wire", "axis"))
_M_ZERO_RESIDENT = metrics_lib.gauge(
    "hvd_tpu_zero_param_bytes_resident",
    "at-rest parameter bytes resident per rank under the current "
    "ZeRO stage (stage 3 = 1/N bucket shards; stages 0-2 = full "
    "replica) — the memory-model number docs/zero.md derives",
    labels=("stage",))


class StepTimer:
    """Host-side step-phase breakdown — the grad/comm/apply split of
    docs/metrics.md. Each phase records into the
    ``hvd_tpu_step_phase_seconds`` histogram and, when the
    metrics↔timeline bridge is on (``HVD_TPU_METRICS_TRACE=1``), the
    same span is emitted as a ``jax.profiler.TraceAnnotation`` so it
    lines up with the device-side XLA trace.

    Because JAX dispatch is async, a phase only measures real work if
    its outputs are forced before the block exits — use :meth:`timed`
    (which blocks on the result) or block yourself inside ``phase``::

        st = hvd.StepTimer()
        grads = st.timed("grad", grad_fn, params, batch)
        reduced = st.timed("comm", hvd.grouped_allreduce, grads)
        with st.phase("apply"):
            params = optax.apply_updates(params, updates)
            jax.block_until_ready(params)

    Zero-cost when metrics are disabled (every call lands on the no-op
    singleton)."""

    def __init__(self, name: str = "hvd_step"):
        self.name = name

    def phase(self, phase: str):
        """Context manager timing one named phase."""
        return _M_PHASE.labels(phase=phase).time(
            annotation=f"{self.name}/{phase}"
            if metrics_lib.registry().trace_bridge else None)

    def timed(self, phase: str, fn, *args, **kwargs):
        """Run ``fn`` and block until its outputs are ready, recording
        the elapsed wall time under ``phase``."""
        with self.phase(phase):
            out = fn(*args, **kwargs)
            jax.block_until_ready(out)
        return out


def observe_ef_residual(state) -> Optional[float]:
    """Global L2 norm of an error-feedback residual (the ``_EFState`` /
    ``_EFShardState`` carried by the ``int8_ef`` surfaces), published as
    the ``hvd_tpu_ef_residual_norm`` gauge. Host-side — fetches the
    residual leaves, so call it at checkpoint/eval cadence, not every
    step. Walks ``.inner`` wrappers (the integrity ``_GuardedState``,
    the k>1 ``_AggState``) so arming the non-finite guard does not make
    the gauge go dark. Returns the norm, or None if ``state`` carries
    no residual."""
    residual, probe, hops = None, state, 0
    while probe is not None and hops < 8:
        residual = getattr(probe, "residual", None)
        if residual is not None:
            break
        probe = getattr(probe, "inner", None)
        hops += 1
    if residual is None:
        return None
    import numpy as np

    total = 0.0
    for leaf in jax.tree.leaves(residual):
        a = np.asarray(jax.device_get(leaf)).astype(np.float64)
        total += float((a * a).sum())
    norm = float(total ** 0.5)
    _M_EF_NORM.set(norm)
    return norm


def _check_reduce_safe(compression) -> None:
    if not getattr(compression, "reduce_safe", True):
        raise ValueError(
            f"{compression.__name__} is a wire-format compressor (per-block "
            "scales don't commute with summation) and cannot ride the "
            "gradient reduction directly; use a reduce-safe compression "
            "instead — Compression.int8_ef (quantized allreduce with error "
            "feedback, same 4x wire win) or Compression.fp16 / bf16 (cast)")


def _resolve_compression(compression):
    """Accept a Compressor class, a name ("bf16"/"int8_ef"/...), or None
    (=> the configured default, HVD_TPU_COMPRESSION / init(compression=),
    falling back to no compression). Pre-init, the env knob is read
    directly — an optimizer built at module scope before hvd.init()
    must not silently discard HVD_TPU_COMPRESSION (an init(compression=)
    override can only be seen after init, by construction)."""
    from .ops.compression import Compression

    if compression is None:
        from .common import basics

        if basics.is_initialized():
            name = basics.context().config.compression
        else:
            from .common.config import _env

            name = _env("COMPRESSION")
        if name:
            return Compression.by_name(name)
        return NoneCompressor
    if isinstance(compression, str):
        return Compression.by_name(compression)
    return compression


def _resolve_quantize_min_bytes(explicit: Optional[int] = None) -> int:
    if explicit is not None:
        return explicit
    from .common import basics

    if basics.is_initialized():
        return basics.context().config.quantize_min_bucket_bytes
    from .common.config import Config, _env_int

    return _env_int("QUANTIZE_MIN_BYTES", Config.quantize_min_bucket_bytes)


def _resolve_route(route, local_axis: str = "local",
                   cross_axis: str = "cross"):
    """Resolve a route value to a :class:`~.ops.collectives.WirePlan`
    (or None = flat axis). ``None`` consults the configured default
    (``HVD_TPU_ROUTE`` / ``init(route=)``); explicit values — a
    WirePlan, a spec string like ``"local:none,cross:int8"``, or a
    named route (``"flat"``/``"staged"``/``"staged_int8"``) — win."""
    if route is None:
        from .common import basics

        if basics.is_initialized():
            route = basics.context().config.route
        else:
            from .common.config import _env

            route = _env("ROUTE")
        if route is None:
            return None
    return C.WirePlan.resolve(route, local_axis, cross_axis)


def _resolve_parallel(parallel):
    """Coerce a ``parallel=`` value (ParallelSpec / dict / spec string)
    — EXPLICIT-ONLY, deliberately no ``HVD_TPU_PARALLEL`` consult here:
    the spec renames the reduction axes (``hvd`` -> ``dp``) and an env
    knob must never re-route existing call sites' collectives onto
    axes their mesh does not bind (the same contract as ``route=`` on
    the sharded surfaces). ``HVD_TPU_PARALLEL`` / ``init(parallel=)``
    feed the Context's mesh (``hvd.parallel_spec()``) and the tools,
    which pass the spec explicitly."""
    if parallel is None:
        return None
    from .parallel.spec import ParallelSpec

    return ParallelSpec.resolve(parallel)


def _combine_tp(grads, tp_axis):
    """pmean-combine slice gradients over one axis name or a tuple of
    them (tensor_parallel.combine_slice_grads) ahead of the dp
    reduction — ``tp`` reassembles tensor-parallel slices, ``sp``
    averages the per-sequence-shard gradients of replicated params
    (docs/sequence.md): identical math, one combiner. Resolved at
    TRACE time: when an axis is not bound, the model necessarily ran
    unsharded over it in this trace, the grads are already exact, and
    that combine is correctly skipped (the single-device debug
    path)."""
    from .parallel.tensor_parallel import combine_slice_grads

    axes = (tp_axis,) if isinstance(tp_axis, str) else tuple(tp_axis)
    for a in axes:
        if _axes_bound(a):
            grads = combine_slice_grads(grads, a)
    return grads


def _axes_bound(*axes) -> bool:
    """True iff all mesh axis names are bound in the current trace (i.e. we
    are inside shard_map/pmap over them). Probed once, narrowly, so a
    genuine NameError inside user compressors/optimizers still raises."""
    try:
        for a in axes:
            jax.lax.axis_size(a)
        return True
    except NameError:
        return False


def _reduce_tree(grads, op: C.ReduceOp, axis_name: str, compression,
                 fusion_threshold: int, prescale: float = 1.0,
                 postscale: float = 1.0, hierarchical: bool = False,
                 local_axis: str = "local", cross_axis: str = "cross",
                 quantized_cross: bool = False, route=None):
    """Allreduce of a gradient pytree over the mesh axis, in one of two
    shapes chosen from what the trace shows.

    Where the flat rank axis is bound over more than one rank, the op is
    SUM/AVERAGE and the compressor acts on each element alone (identity,
    a cast), every leaf is reduced WHERE IT LIES: no leaf waits for a
    ``concatenate`` of its bucket's last leaf, and the compiler may run
    each all-reduce under the rest of the backward
    (common/xla_tuning.py). The same numbers are summed in the same
    precision, so this is bitwise the flat buckets' result. Every other
    path goes through flat buckets in flatten order
    (``fusion.fused_apply``), because bucket composition is part of its
    numerics: block-scaled payloads, the router's and the staged
    pipeline's per-axis shards, Adasum's per-bucket dots.

    ``route`` (a :class:`~.ops.collectives.WirePlan`) sends every bucket
    through the topology-aware router (``collectives.mesh_allreduce``):
    per-axis RS/AG phases with per-axis wire dtypes, SUM/AVERAGE/ADASUM
    (docs/topology.md). It supersedes ``hierarchical``/``quantized_cross``
    — those flags are the legacy 2-axis fp32/int8-cross special cases.

    Outside an SPMD region (axis names unbound) the reduction degenerates
    to size-1 reference semantics: no cross-rank sum, but pre/post scaling
    still applies (the reference applies ScaleBuffer regardless of world
    size). Under jit/pjit auto-sharding XLA already inserts the
    cross-device reduction itself — a manual psum there would
    double-reduce.
    """
    if route is not None and not _axes_bound(*route.axis_names) \
            and _axes_bound(axis_name):
        # The program is tracing under the FLAT mesh (rank axis bound,
        # plan axes not) — e.g. an HVD_TPU_ROUTE default reaching a
        # flat-axis step. Reduce over the live axis; the identity
        # (size-1) path below is only for fully-unbound traces, and
        # silently NOT reducing would diverge replicas.
        route = None
    needed_axes = (route.axis_names if route is not None
                   else (local_axis, cross_axis) if hierarchical
                   else (axis_name,))
    bound = _axes_bound(*needed_axes)

    def one(flat):
        w, ctx = compression.compress(flat)
        if route is not None:
            if op not in (C.ReduceOp.SUM, C.ReduceOp.AVERAGE,
                          C.ReduceOp.ADASUM):
                # MIN/MAX/PRODUCT have no staged decomposition (and no
                # wire win to stage for) — reduce jointly over ALL plan
                # axes, which lax accepts as an axis tuple.
                return compression.decompress(
                    C.allreduce(w, op, tuple(route.axis_names),
                                prescale, postscale), ctx)
            # Integer buckets must not ride lossy wires: same axes,
            # native payload (psum of ints is exact on every phase).
            rp = route if jnp.issubdtype(w.dtype, jnp.floating) \
                else route.with_wires("none")
            if op != C.ReduceOp.ADASUM:
                w = C._apply_scale(w, prescale)
            w = C.mesh_allreduce(w, op, rp)
            w = C._apply_scale(w, postscale)
        elif op == C.ReduceOp.ADASUM:
            from .ops import adasum as adasum_lib

            if hierarchical:
                w = adasum_lib.adasum_hierarchical(w, local_axis, cross_axis)
            else:
                w = adasum_lib.adasum_allreduce(w, axis_name)
            w = C._apply_scale(w, postscale)
        elif hierarchical:
            w = C._apply_scale(w, prescale)
            nl = jax.lax.axis_size(local_axis)
            w, n = fusion_lib.pad_to_multiple(w, nl)
            if quantized_cross:
                # EQuARX path: int8 payload on the DCN hop
                # (collectives.quantized_hierarchical_allreduce).
                w = C.quantized_hierarchical_allreduce(
                    w, op, local_axis, cross_axis)
            else:
                w = C.hierarchical_allreduce_staged(w, op, local_axis,
                                                    cross_axis)
            w = jax.lax.slice_in_dim(w, 0, n)
            w = C._apply_scale(w, postscale)
        else:
            w = C.allreduce(w, op, axis_name, prescale, postscale)
        return compression.decompress(w, ctx)

    def identity_with_scales(flat):
        w, ctx = compression.compress(flat)
        w = C._apply_scale(w, prescale)
        w = C._apply_scale(w, postscale)
        return compression.decompress(w, ctx)

    fn = one if bound else identity_with_scales
    in_place = (route is None and not hierarchical
                and op in (C.ReduceOp.SUM, C.ReduceOp.AVERAGE)
                and getattr(compression, "per_element", False))
    if in_place and bound and jax.lax.axis_size(axis_name) > 1:
        # Nothing ties a leaf to a concatenate that waits for the last
        # leaf of a bucket, and its 1/n fuses into the update that reads
        # it. When each all-reduce starts is the compiler's business
        # (xla_tuning.TPU_OVERLAP_FLAGS); a barrier chain between groups
        # bought nothing on the chip and cost memory under asynchronous
        # all-reduces (PERF.md, PR 28).
        return jax.tree.map(lambda g: fn(jnp.asarray(g)), grads)
    return fusion_lib.fused_apply(grads, fn, fusion_threshold)


class _AggState(NamedTuple):
    inner: Any
    acc: Any          # local gradient accumulator
    counter: jnp.ndarray


try:
    # The class needs the optax base at definition time; the rest of
    # this module must keep importing without optax installed.
    import optax as _optax
except Exception:  # pragma: no cover — exercised only without optax
    _optax = None


if _optax is not None:

    class AccumGradientTransformation(_optax.GradientTransformation):
        """The optax pair plus the scan-based accumulation driver the
        factory bound it to (docs/performance.md):
        ``accumulate(loss_fn, has_aux=False)`` returns the microbatched
        ``value_and_grad`` for the bound
        ``accum_steps``/``remat_policy`` — feed its gradients to
        ``update`` ONCE per effective step, so the collective round,
        non-finite guard agreement, and error-feedback advance all run
        once per effective step by construction.

        A module-level SUBCLASS of ``optax.GradientTransformation``
        with defaulted extras (not a wider NamedTuple): the 2-tuple
        shape, ``init, update = tx`` destructuring, isinstance checks,
        pickle/copy, and pytree flatten/unflatten all keep working.
        A pytree unflatten rebuilds ``cls(init, update)`` — the
        accumulation config resets to the ``1``/``"none"`` defaults,
        matching the pre-accumulation return type, which carried
        none."""

        def __new__(cls, init, update, accum_steps: int = 1,
                    remat_policy: str = "none"):
            self = super().__new__(cls, init, update)
            self.accum_steps = accum_steps
            self.remat_policy = remat_policy
            return self

        def accumulate(self, loss_fn: Callable, has_aux: bool = False):
            return accumulate_gradients(loss_fn, self.accum_steps,
                                        self.remat_policy,
                                        has_aux=has_aux)

else:  # pragma: no cover — optax-less installs have no optax surface
    AccumGradientTransformation = None


class _GuardedState(NamedTuple):
    """Optimizer-state wrapper carried when a non-finite policy is
    active (docs/integrity.md): the wrapped surface's state (possibly
    itself an :class:`_EFState` / :class:`_EFShardState`) plus the
    integrity :class:`~.common.integrity.GuardState` — policy code,
    non-finite step count, good-step streak, dynamic loss scale."""

    inner: Any
    guard: integrity_lib.GuardState


# -- error-feedback quantized reduction (compression="int8_ef") -------------

class _EFState(NamedTuple):
    """Optimizer-state wrapper carried by the error-feedback compressors:
    the inner transform's state, the fp32 residual pytree (this rank's
    accumulated quantization error — LOCAL, like the reference's per-rank
    gradient state), and the step counter that seeds the deterministic
    per-step stochastic rounding."""

    inner: Any
    residual: Any
    step: jnp.ndarray


# Base seed for the stochastic-rounding PRNG. The effective key is
# fold_in(fold_in(PRNGKey(_EF_SEED), step), bucket_index): deterministic
# per (step, bucket) — identical across ranks (SPMD traces one program)
# and across reruns, so elastic replays and bitwise-repro debugging hold.
_EF_SEED = 0x5EED


def _zeros_residual(tree):
    return jax.tree.map(
        lambda p: jnp.zeros(jnp.shape(p), jnp.float32), tree)


def _ef_key(step, bucket_index: int):
    return jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(_EF_SEED), step),
        bucket_index)


def _ef_keys(step, buckets: int, hops: int):
    """``_ef_key(step, i)`` for every bucket ``i`` of a step, (buckets, 2),
    and ``fold_in`` of each with every hop number below ``hops``,
    (buckets, hops, 2): the keys the reductions would derive themselves,
    to the bit, in three vectorised threefries instead of one on scalars
    for every bucket and hop (some 120 instructions of the compiled step
    each: 8,951 against 124 for 25 buckets, PERF.md PR 45)."""
    at_step = jax.random.fold_in(jax.random.PRNGKey(_EF_SEED), step)
    keys = jax.vmap(lambda i: jax.random.fold_in(at_step, i))(
        jnp.arange(buckets))
    hop_keys = jax.vmap(lambda key: jax.vmap(
        lambda hop: jax.random.fold_in(key, hop))(jnp.arange(hops)))(keys)
    return keys, hop_keys


def _reduce_tree_ef(grads, residual, step, op: C.ReduceOp, axis_name: str,
                    fusion_threshold: int, prescale: float = 1.0,
                    postscale: float = 1.0,
                    quantize_min_bytes: Optional[int] = None,
                    route=None):
    """Fused QUANTIZED allreduce of a gradient pytree with error
    feedback. Returns ``(reduced_tree, new_residual_tree)``.

    Buckets are planned like :func:`_reduce_tree`'s flat path (same
    threshold, flatten order) and then stamped with per-bucket wire
    decisions (``fusion.assign_wire_dtypes``): large float buckets go
    through
    ``collectives.quantized_allreduce`` with this step's corrected
    gradient ``g + residual`` and a per-(step, bucket) stochastic-
    rounding key; their returned local quantization error becomes the
    next residual. Small float buckets ride a bf16 cast (no residual —
    bf16 keeps fp32's exponent range and the cast error is far below the
    int8 rounding floor); integer buckets ride untouched.

    ``route`` (a WirePlan) sends each bucket through the mesh router
    instead of the flat axis: int8-eligible buckets run
    ``collectives.mesh_allreduce`` with the plan's PER-AXIS wires and
    carry its residual; small buckets ride the same axes bf16/native
    (docs/topology.md). With ``op=ADASUM`` the router runs the
    hierarchical Adasum scheme — the error-feedback residual corrects
    the LINEAR fast-axis phases (the local sums the Adasum recursion
    consumes); on a flat (1-phase) axis Adasum has no linear phase, so
    the residual is consumed once and zeroed rather than telescoped.

    Outside an SPMD region the reduction degenerates to size-1 semantics
    (scales applied, residual unchanged) — matching :func:`_reduce_tree`.
    """
    qmin = _resolve_quantize_min_bytes(quantize_min_bytes)
    if route is not None and not _axes_bound(*route.axis_names) \
            and _axes_bound(axis_name):
        route = None  # flat mesh is live — reduce flat (see _reduce_tree)
    bound = _axes_bound(*(route.axis_names if route is not None
                          else (axis_name,)))
    plan = fusion_lib.assign_wire_dtypes(
        fusion_lib.plan_fusion(grads, fusion_threshold), qmin)
    reducible = (C.ReduceOp.SUM, C.ReduceOp.AVERAGE, C.ReduceOp.ADASUM)
    adasum = op == C.ReduceOp.ADASUM
    lengths = None
    if bound and op in reducible:
        # The keys of every bucket and hop are derived at once, and an
        # int8 bucket is packed onto the grid its reduction works on (a
        # whole 4,096-element block a rank), so nothing pads or slices it
        # there. Flat Adasum keeps its buffers as they were.
        keys, hop_keys = _ef_keys(
            step, len(plan.buckets),
            2 if route is None else 2 * len(route.phases) - 1)
        if route is not None or not adasum:
            ranks = (jax.lax.axis_size(axis_name) if route is None
                     else _route_total(route))
            lengths = [_qpad_len(b.total_elems, ranks)
                       if w == fusion_lib.WIRE_INT8 else 0
                       for b, w in zip(plan.buckets, plan.wire_dtypes)]
    g_flats = fusion_lib.fuse(grads, plan, lengths)
    r_flats = fusion_lib.fuse(residual, plan, lengths)
    scaled = None if adasum or prescale in (None, 1.0) else prescale

    def one(i, g, r):
        wire = plan.wire_dtypes[i]
        if not bound:
            w = C._apply_scale(g, prescale)
            return C._apply_scale(w, postscale), r
        if wire == fusion_lib.WIRE_INT8 and op in reducible:
            if route is not None:
                corrected = g.astype(jnp.float32) + r
                if scaled is not None:
                    corrected = corrected * scaled
                y, res = C.mesh_allreduce(
                    corrected, op, route, key=keys[i],
                    return_residual=True, _hop_keys=hop_keys[i])
            elif adasum:
                # Flat-axis Adasum: quantized distance-doubling exchange
                # (unbiased with the stochastic key); no linear phase, so
                # the consumed residual zeroes instead of telescoping.
                from .ops import adasum as adasum_lib

                y = adasum_lib.adasum_allreduce(
                    g.astype(jnp.float32) + r, axis_name, wire="int8",
                    key=keys[i])
                res = jnp.zeros_like(r)
            else:
                # The corrected gradient (g + r) * prescale is formed in
                # the quantise kernel, which writes the new residual too.
                y, res = C.quantized_allreduce(
                    g, op, axis_name, key=keys[i], return_residual=True,
                    _hop_keys=hop_keys[i], _plus=r, _prescale=scaled)
            if scaled is not None:
                # Residual lives in UNSCALED gradient units (it is added
                # to raw grads next step, before this prescale reapplies).
                res = res / prescale
            y = C._apply_scale(y, postscale)
            return y.astype(g.dtype), res
        if wire == fusion_lib.WIRE_BF16 and op in reducible:
            gb = g.astype(jnp.bfloat16)
            if route is not None:
                if not adasum:
                    gb = C._apply_scale(gb, prescale)
                w = C.mesh_allreduce(gb, op, route.with_wires("none"))
                w = C._apply_scale(w, postscale)
            else:
                w = C.allreduce(gb, op, axis_name, prescale, postscale)
            return w.astype(g.dtype), r
        if route is not None and op in reducible:
            gg = g if adasum else C._apply_scale(g, prescale)
            w = C.mesh_allreduce(gg, op, route.with_wires("none"))
            return C._apply_scale(w, postscale), r
        return C.allreduce(g, op, axis_name, prescale, postscale), r

    outs = [one(i, g, r) for i, (g, r) in enumerate(zip(g_flats, r_flats))]
    reduced = fusion_lib.unfuse([y for y, _ in outs], plan)
    new_residual = fusion_lib.unfuse([res for _, res in outs], plan)
    return reduced, new_residual


def _resolve_fusion_threshold(explicit: Optional[int]) -> int:
    """None → the live runtime value (autotuner's current suggestion when
    tuning, else the configured knob); an explicit value always wins."""
    if explicit is not None:
        return explicit
    from .common import basics

    if basics.is_initialized():
        return basics.context().fusion_threshold()
    return 64 * 1024 * 1024


# -- scan-based gradient accumulation (accum_steps=) -------------------------
#
# The MFU lever for batch-starved and memory-bound steps (ROADMAP item 2,
# docs/performance.md "MFU playbook"): instead of paying one dispatch +
# one traced cond per microbatch (the reference-style
# ``backward_passes_per_step`` aggregation above), ONE jitted step scans
# the loss/grad over k microbatches, carrying an fp32 gradient
# accumulator, and pays the collective round, the non-finite guard
# agreement, and the error-feedback state advance exactly once per
# EFFECTIVE step. Activation memory peaks at one microbatch (1/k of the
# fused batch), which is what lets remat + bigger per-chip batches trade
# against each other.

_REMAT_POLICY_NAMES = ("none", "full", "dots", "dots_no_batch")


def resolve_remat_policy(policy: Optional[str] = None):
    """Resolve a remat-policy name to ``(name, wrap, jax_policy)``.

    ``None`` consults the configured default (``HVD_TPU_REMAT_POLICY``
    / ``init(remat_policy=)``). Names map to ``jax.checkpoint``
    policies: ``"none"`` = no remat; ``"full"`` = recompute everything
    in backward (``jax.checkpoint`` default); ``"dots"`` = save matmul
    outputs, recompute elementwise (``dots_saveable``);
    ``"dots_no_batch"`` = save only non-batch-dim matmuls
    (``dots_with_no_batch_dims_saveable`` — the TPU-recommended policy
    for transformer blocks)."""
    if policy is None:
        from .common import basics

        if basics.is_initialized():
            policy = basics.context().config.remat_policy
        else:
            from .common.config import _env

            policy = _env("REMAT_POLICY")
    if policy is None or policy in ("none", "off", ""):
        return "none", False, None
    if policy == "full":
        return "full", True, None
    cp = jax.checkpoint_policies
    if policy == "dots":
        return "dots", True, cp.dots_saveable
    if policy == "dots_no_batch":
        return "dots_no_batch", True, cp.dots_with_no_batch_dims_saveable
    raise ValueError(
        f"unknown remat policy {policy!r}; choose from "
        f"{_REMAT_POLICY_NAMES}")


def _resolve_accum_steps(explicit: Optional[int] = None) -> int:
    """None → the configured default (``HVD_TPU_ACCUM_STEPS`` /
    ``init(accum_steps=)``, falling back to 1); an explicit value always
    wins."""
    if explicit is not None:
        k = int(explicit)
    else:
        from .common import basics

        if basics.is_initialized():
            k = int(basics.context().config.accum_steps)
        else:
            from .common.config import _env_int

            k = _env_int("ACCUM_STEPS", 1)
    if k < 1:
        raise ValueError(f"accum_steps must be >= 1, got {k}")
    return k


def _split_microbatches(batch_args, k: int):
    """Each array leaf of the batch pytrees gains a leading microbatch
    axis: ``(b, ...) -> (k, b//k, ...)``. Raises (naming the leaf shape)
    when a leading dim does not divide."""
    def one(x):
        x = jnp.asarray(x)
        if x.ndim == 0 or x.shape[0] % k:
            raise ValueError(
                f"accum_steps={k} does not divide the leading batch dim "
                f"of a batch leaf with shape {jnp.shape(x)}; every batch "
                "array must carry b = k * microbatch rows")
        return x.reshape((k, x.shape[0] // k) + x.shape[1:])

    return jax.tree.map(one, batch_args)


def accumulate_gradients(loss_fn: Callable,
                         accum_steps: Optional[int] = None,
                         remat_policy: Optional[str] = None,
                         has_aux: bool = False):
    """Scan-based gradient accumulation: wrap a LOSS function into a
    microbatched ``value_and_grad``.

    Returns ``fn(params, *batch) -> (value, grads)`` (or
    ``((value, aux), grads)`` with ``has_aux``): the batch args are
    split into ``accum_steps`` microbatches along their leading dim and
    a ``lax.scan`` runs ``jax.value_and_grad(loss_fn)`` per microbatch,
    accumulating gradients (and the loss) in fp32 — activation memory
    peaks at ONE microbatch instead of the fused batch. The returned
    gradients are the MEAN over microbatches, so a loss that is a mean
    over its batch rows yields gradients equivalent to the fused large
    batch (the accumulation-equivalence contract, tests/test_accum.py).

    ``remat_policy`` wraps the microbatch loss in ``jax.checkpoint``
    (:func:`resolve_remat_policy` names), trading recompute for a
    further activation-memory cut INSIDE each microbatch — the two
    levers tune jointly (docs/performance.md).

    Float ``aux`` leaves are averaged across microbatches (e.g. batch
    stats); integer leaves keep the LAST microbatch's value. There are
    no collectives in here: reduce the returned gradients once per
    effective step (DistributedOptimizer/DistributedGradFn compose this
    for you via their own ``accum_steps=``)."""
    k = _resolve_accum_steps(accum_steps)
    _, wrap, jax_policy = resolve_remat_policy(remat_policy)
    inner = jax.checkpoint(loss_fn, policy=jax_policy) if wrap else loss_fn
    vgrad = jax.value_and_grad(inner, has_aux=has_aux)
    if k == 1:
        return vgrad

    def accum_fn(params, *batch):
        mbs = _split_microbatches(batch, k)
        mb0 = jax.tree.map(lambda x: x[0], mbs)
        # Every microbatch runs through the SAME compiled scan body —
        # unrolling the first iteration would let XLA compile it
        # differently, and ulp-level drift between "identical"
        # microbatches breaks the bitwise state-transition contract
        # (tests/test_accum.py). eval_shape gives the accumulator
        # structure without spending a FLOP.
        shapes = jax.eval_shape(vgrad, params, *mb0)
        out_s, g_s = shapes
        v_s, aux_s = out_s if has_aux else (out_s, None)

        def zeros_acc(t):
            return jax.tree.map(
                lambda s: jnp.zeros(
                    s.shape, jnp.float32
                    if jnp.issubdtype(s.dtype, jnp.floating)
                    else s.dtype), t)

        def acc_add(acc, new):
            return jax.tree.map(
                lambda a, x: a + x.astype(jnp.float32)
                if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)
                else x,  # non-float aux: keep the latest microbatch's
                acc, new)

        carry0 = (zeros_acc(g_s), jnp.zeros((), jnp.float32),
                  zeros_acc(aux_s))

        def body(carry, mb):
            g_acc, v_acc, aux_acc = carry
            out, g = vgrad(params, *mb)
            v, aux = out if has_aux else (out, None)
            return (acc_add(g_acc, g), v_acc + v.astype(jnp.float32),
                    acc_add(aux_acc, aux)), None

        (g_acc, v_acc, aux_acc), _ = jax.lax.scan(body, carry0, mbs)

        def mean_like(acc, template):
            return jax.tree.map(
                lambda a, s: (a / k).astype(s.dtype)
                if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)
                else a, acc, template)

        grads = mean_like(g_acc, g_s)
        value = (v_acc / k).astype(v_s.dtype)
        if has_aux:
            return (value, mean_like(aux_acc, aux_s)), grads
        return value, grads

    return accum_fn


def auto_shard_threshold(explicit: Optional[int] = None) -> int:
    """The weight-update-sharding threshold in bytes
    (``HVD_TPU_AUTO_SHARD_THRESHOLD`` / ``init(auto_shard_threshold_
    bytes=)``, default 256 MiB): replicated params at least this large
    make ZeRO-1's sharded update the default candidate."""
    if explicit is not None:
        return int(explicit)
    from .common import basics

    if basics.is_initialized():
        return int(basics.context().config.auto_shard_threshold_bytes)
    from .common.config import Config, _env_int

    return _env_int("AUTO_SHARD_THRESHOLD",
                    Config.auto_shard_threshold_bytes)


def should_shard_update(params, size: Optional[int] = None,
                        threshold_bytes: Optional[int] = None) -> bool:
    """Heuristic (arXiv:1909.09756, docs/performance.md): True when
    weight-update sharding (ZeRO-1, :class:`ShardedOptimizer`) should
    be the default candidate for this model — the world has more than
    one rank and the replicated params are at least
    :func:`auto_shard_threshold` bytes (the regime where the replicated
    optimizer state + update compute dominate the RS+AG latency the
    sharded path adds). Accepts real arrays or ShapeDtypeStructs."""
    if size is None:
        from .common import basics

        size = basics.context().size() if basics.is_initialized() else 1
    if size <= 1:
        return False
    import numpy as np

    nbytes = 0
    for leaf in jax.tree.leaves(params):
        shape = getattr(leaf, "shape", ())
        dtype = jnp.asarray(leaf).dtype if not hasattr(leaf, "dtype") \
            else leaf.dtype
        nbytes += int(np.prod(shape)) * jnp.dtype(dtype).itemsize
    return nbytes >= auto_shard_threshold(threshold_bytes)


def DistributedOptimizer(optimizer,
                         op: C.ReduceOp = C.ReduceOp.AVERAGE,
                         axis_name: str = "hvd",
                         compression=None,
                         backward_passes_per_step: int = 1,
                         average_aggregated_gradients: bool = True,
                         prescale_factor: float = 1.0,
                         postscale_factor: float = 1.0,
                         fusion_threshold_bytes: Optional[int] = None,
                         hierarchical: bool = False,
                         local_axis: str = "local",
                         cross_axis: str = "cross",
                         quantized_cross: bool = False,
                         quantize_min_bucket_bytes: Optional[int] = None,
                         nonfinite_policy: Optional[str] = None,
                         route=None,
                         accum_steps: Optional[int] = None,
                         remat_policy: Optional[str] = None,
                         zero_stage: int = 0,
                         parallel=None):
    """Wrap an optax optimizer so ``update()`` allreduces gradients first.

    Use inside the jitted step function running under
    shard_map/pjit over the rank axis::

        tx = hvd.DistributedOptimizer(optax.sgd(0.1), axis_name="hvd")

    ``backward_passes_per_step`` accumulates k local microbatch gradients
    before one fused allreduce + inner update (reference
    gradient_aggregation.py semantics: allreduce every k-th call, identity
    updates in between). Prefer ``accum_steps`` (below) for new code —
    the scan-based form pays one dispatch per EFFECTIVE step instead of
    one per microbatch.

    ``accum_steps`` (None → ``HVD_TPU_ACCUM_STEPS`` /
    ``init(accum_steps=)``) + ``remat_policy`` select SCAN-BASED
    gradient accumulation (docs/performance.md "MFU playbook"): the
    returned transformation carries an ``accumulate(loss_fn,
    has_aux=False)`` driver (:func:`accumulate_gradients` bound to the
    pinned knobs) that microbatches the loss under ``lax.scan`` —
    activation memory peaks at 1/k of the fused batch, and
    ``remat_policy`` ("full"/"dots"/"dots_no_batch") further remats
    inside each microbatch via ``jax.checkpoint``. Feed its MEAN
    gradient to ``update()`` once per effective step::

        tx = hvd.DistributedOptimizer(optax.adamw(1e-3), accum_steps=4,
                                      remat_policy="dots_no_batch")
        vgrad = tx.accumulate(loss_fn)         # scans 4 microbatches
        loss, grads = vgrad(params, batch)     # batch rows = 4 * mb
        updates, state = tx.update(grads, state, params)

    The collective round, the non-finite guard agreement, and the
    int8_ef error-feedback/stochastic-rounding advance then all run
    exactly ONCE per effective step by construction — accumulation
    composes with ``compression``/``route``/``nonfinite_policy``
    unchanged. Mutually exclusive with the legacy
    ``backward_passes_per_step`` aggregation.

    ``quantized_cross`` (requires ``hierarchical``) carries the DCN hop
    of each fused bucket as block-scaled int8 — the EQuARX-style
    quantized allreduce (collectives.quantized_hierarchical_allreduce);
    gradients land within block-absmax rounding error of the exact sum.

    The reduction's shape is chosen from what the trace shows, not by
    an option. Under ``hvd.spmd_step`` over more than one rank, with a
    SUM/AVERAGE op and ``compression`` none or a cast, each gradient is
    reduced where it lies (no flat buckets, no copies in or out), and
    across TPU chips ``spmd_step`` compiles the step with asynchronous
    all-reduces, so part of the reduction runs under the backward
    (common/xla_tuning.py; measured in PERF.md, PR 28: 107.6 -> 95.1 ms
    a step for BERT-large on four v5e chips). Bitwise the flat buckets'
    gradients. The paths whose numerics depend on the bucket
    (``route``/``hierarchical``/``quantized_cross``, Adasum,
    ``int8_ef``) reduce flat buckets of ``fusion_threshold_bytes`` in
    flatten order (docs/overlap.md).

    ``compression`` accepts a Compressor class, a name
    (``"bf16"``/``"int8_ef"``/...), or None — the configured default
    (``HVD_TPU_COMPRESSION`` / ``init(compression=)``). With
    ``compression="int8_ef"`` the reduction runs as a REDUCE-SAFE
    QUANTIZED ALLREDUCE (collectives.quantized_allreduce: int8 payload
    on every hop, ~4x fewer wire bytes) with an ERROR-FEEDBACK residual
    carried in the optimizer state: each step reduces ``grad +
    residual``, and the local quantization error becomes the next
    residual, so training converges like fp32 (docs/compression.md).
    Only fused buckets of at least ``quantize_min_bucket_bytes``
    (default: the HVD_TPU_QUANTIZE_MIN_BYTES knob, 64 KiB) are
    quantized — smaller float buckets ride bf16. Requires a SUM/AVERAGE
    op; does not compose with ``hierarchical`` (use
    ``quantized_cross`` for the int8 DCN hop of the staged pipeline).

    ``nonfinite_policy`` (None → ``HVD_TPU_NONFINITE_POLICY`` /
    ``init(nonfinite_policy=)``; docs/integrity.md) arms the
    training-integrity guard: an all-finite flag over the gradients is
    globally agreed via a one-scalar min-allreduce and a jit-safe
    ``lax.cond`` reacts identically on every rank — ``warn`` |
    ``skip_step`` (zero updates, optimizer state AND the int8_ef
    error-feedback residual untouched) | ``zero`` | ``scale_backoff``
    (dynamic loss scaling: multiply your loss by
    ``hvd.current_loss_scale(opt_state)``) | ``abort`` (skip in-trace,
    ``hvd.observe_guard(opt_state)`` raises host-side). The state is
    wrapped in :class:`_GuardedState`; observe with
    ``hvd.observe_guard``.

    ``route`` (None → ``HVD_TPU_ROUTE`` / ``init(route=)``) selects the
    TOPOLOGY-AWARE ROUTER (docs/topology.md): a
    :class:`~.ops.collectives.WirePlan`, a spec string like
    ``"local:none,cross:int8"`` (fast axis first), or a named route
    (``"flat"`` / ``"staged"`` / ``"staged_int8"``). Each fused bucket
    then reduces via per-axis phases with PER-AXIS WIRE DTYPES —
    fp32/bf16 on fast ICI axes, int8 on the slow cross hop — so wire
    cost scales with the slowest link, not the world size. Composes
    with ``compression="int8_ef"`` (the residual rides the linear
    phases), with ``op=hvd.Adasum`` (hierarchical Adasum: fast axes
    averaged, the adaptive recursion runs on shards over the slow axis
    with fast-axis-psum-med scalars). Supersedes the legacy
    ``hierarchical``/``quantized_cross`` booleans — passing both
    raises.

    ``parallel`` (EXPLICIT-ONLY — a :class:`~.parallel.spec.
    ParallelSpec`, role dict, or spec string like ``"dp=2,pp=2,tp=2"``;
    docs/pipeline.md) declares HYBRID dp x pp x tp parallelism on one
    mesh: gradients then reduce over the ``dp`` axis ONLY (pipeline
    stages own disjoint params — their activation sends ride the pp
    axis, not the gradient reduction; tensor-parallel slice gradients
    are pmean-combined over ``tp`` first via
    ``tensor_parallel.combine_slice_grads``), and the non-finite guard
    agrees over the ``dp`` axis only (each stage guards its own
    params). Feed it the gradients of
    ``parallel.pipeline.pipeline_accumulate_gradients`` (the 1F1B
    schedule with the same ``(value, grads)`` contract as
    ``accumulate``). Composes with ``compression``/
    ``zero_stage`` (ZeRO shard grids then span the dp axis, so
    stage-2/3 shards live PER PIPELINE STAGE); supersedes
    ``axis_name``/``route`` — passing an explicit route alongside
    raises unless its axes are exactly the spec's dp axes.
    """
    try:
        import optax
    except ImportError as e:  # pragma: no cover
        raise ImportError("DistributedOptimizer requires optax") from e

    pspec = _resolve_parallel(parallel)
    tp_combine_axis = None
    if pspec is not None:
        if hierarchical or quantized_cross:
            raise ValueError(
                "parallel= supersedes the hierarchical/quantized_cross "
                "booleans — wires on the dp reduction come from route= "
                "(a WirePlan over the spec's dp axes)")
        if route is not None:
            rt = C.WirePlan.resolve(route)
            if rt is not None and set(rt.axis_names) != set(
                    pspec.dp_axes):
                raise ValueError(
                    f"route axes {rt.axis_names} must be exactly the "
                    f"parallel spec's dp axes {pspec.dp_axes} — "
                    "gradients reduce over dp only (activation traffic "
                    "rides the pp axis; tp combines via pmean)")
        if not pspec.dp_axes:
            raise ValueError(
                f"parallel spec {pspec.describe()!r} has no dp axis — "
                "with nothing to reduce over, wrap the optimizer "
                "directly (pure pp x tp runs need no "
                "DistributedOptimizer)")
        axis_name = pspec.dp_axes[0]
        if route is None:
            # Reduce through the mesh router over the dp axis (not the
            # flat psum): the router stamps the per-axis byte counters
            # (hvd_tpu_allreduce_bytes_total{axis="dp"}) that prove the
            # schedule's wire mix, and it pins the plan so the
            # HVD_TPU_ROUTE default (which names local/cross axes this
            # mesh does not bind) can never apply.
            route = pspec.grad_route()
        tp_combine_axis = tuple(
            a for a in (pspec.tp_axis, pspec.sp_axis)
            if a is not None) or None

    if zero_stage:
        # The one-line ZeRO surface (docs/zero.md): stage 1 = sharded
        # optimizer state, 2 = + sharded gradient accumulation, 3 =
        # + sharded parameters with gather-on-demand. EXPLICIT-ONLY
        # (no HVD_TPU_ZERO_STAGE consult here): the stage changes the
        # update() call contract — it must run inside the SPMD region
        # and takes params/shards — and an env knob must never break
        # existing call sites; bench/tools read the config knob and
        # pass the stage explicitly.
        if int(zero_stage) not in (1, 2, 3):
            raise ValueError(
                f"zero_stage must be 0 (off), 1, 2 or 3 — got "
                f"{zero_stage!r}")
        if backward_passes_per_step != 1 or hierarchical \
                or quantized_cross:
            raise ValueError(
                "zero_stage composes with accum_steps / route / "
                "compression / nonfinite_policy, not with the legacy "
                "backward_passes_per_step aggregation or the "
                "hierarchical/quantized_cross booleans (express the "
                "staged reduction as a WirePlan route instead)")
        if prescale_factor != 1.0 or postscale_factor != 1.0:
            raise ValueError(
                "pre/postscale_factor are not supported on the ZeRO "
                "sharded surfaces — fold the scale into your loss")
        return ZeroOptimizer(
            optimizer, zero_stage=int(zero_stage),
            axis_name=axis_name, grad_op=op,
            fusion_threshold_bytes=fusion_threshold_bytes,
            compression=compression, nonfinite_policy=nonfinite_policy,
            route=route, accum_steps=accum_steps,
            remat_policy=remat_policy, parallel=pspec)

    compression = _resolve_compression(compression)
    _check_reduce_safe(compression)
    ef = getattr(compression, "error_feedback", False)
    route_explicit = route is not None
    route = _resolve_route(route, local_axis, cross_axis)
    if route_explicit and route is not None and (hierarchical
                                                or quantized_cross):
        raise ValueError(
            "route= supersedes the hierarchical/quantized_cross "
            "booleans: express the staged reduction as WirePlan phases "
            "on the mesh router instead (collectives.mesh_allreduce, "
            "docs/topology.md) — e.g. route='staged_int8' or "
            "WirePlan.hierarchical(cross_wire='int8') for the old "
            "hierarchical+quantized_cross pair")
    if not route_explicit and (hierarchical or quantized_cross):
        # Call-site legacy flags beat the HVD_TPU_ROUTE / init(route=)
        # DEFAULT — an env knob must never make existing hierarchical
        # call sites raise (or silently re-route them).
        route = None
    if quantized_cross and (not hierarchical or op not in (
            C.ReduceOp.SUM, C.ReduceOp.AVERAGE)):
        raise ValueError("quantized_cross requires hierarchical=True and "
                         "a SUM/AVERAGE op (the int8 hop rides the "
                         "staged RS->AR->AG pipeline); for Adasum or "
                         "deeper meshes use the router — route= / "
                         "collectives.mesh_allreduce (docs/topology.md)")
    if ef and op not in (C.ReduceOp.SUM, C.ReduceOp.AVERAGE,
                         C.ReduceOp.ADASUM):
        raise ValueError(
            f"compression={compression.__name__} needs a SUM/AVERAGE/"
            "ADASUM op (block-scaled payloads compose with linear "
            "reductions, plus the routed hierarchical Adasum)")
    if ef and hierarchical:
        # Formerly a hard error: int8_ef now composes with the ICI/DCN
        # split THROUGH the mesh router — the per-axis WirePlan carries
        # the slow cross hop as int8 and the error-feedback residual
        # rides the linear phases (docs/topology.md).
        route = C.WirePlan.hierarchical(local_axis, cross_axis,
                                        cross_wire="int8")
        hierarchical = quantized_cross = False

    k = int(backward_passes_per_step)
    accum_k = _resolve_accum_steps(accum_steps)
    # Resolve (and validate) the remat policy ONCE at factory time — a
    # later env-knob change must not re-shape the accumulate driver.
    remat_name, _, _ = resolve_remat_policy(remat_policy)
    if accum_k > 1 and k > 1:
        raise ValueError(
            "accum_steps and backward_passes_per_step are two spellings "
            "of gradient accumulation — pick one (accum_steps is the "
            "scan-based form; backward_passes_per_step the legacy "
            "call-per-microbatch aggregation)")
    fusion_threshold_bytes = _resolve_fusion_threshold(fusion_threshold_bytes)
    quantize_min_bucket_bytes = _resolve_quantize_min_bytes(
        quantize_min_bucket_bytes)
    nonfinite_policy = integrity_lib.resolve_nonfinite_policy(
        nonfinite_policy)
    scale_cfg = integrity_lib.ScaleConfig.from_env()

    def reduce_grads(grads):
        return _reduce_tree(grads, op, axis_name, compression,
                            fusion_threshold_bytes, prescale_factor,
                            postscale_factor, hierarchical, local_axis,
                            cross_axis, quantized_cross, route)

    # Core transformation: reduce + inner update (+ the error-feedback
    # residual/step state when the compressor declares it). The k>1
    # aggregation below wraps THIS, so backward_passes_per_step composes
    # with error feedback unchanged.
    def core_init(params):
        inner = optimizer.init(params)
        if not ef:
            return inner
        return _EFState(inner=inner, residual=_zeros_residual(params),
                        step=jnp.zeros((), jnp.int32))

    def core_update(grads, state, params=None, **extra):
        # The two scopes are what a device trace of the step is read by
        # (common/scopes.py): trace-time names, nothing on the hot path.
        if not ef:
            with jax.named_scope(scopes.REDUCE):
                reduced = reduce_grads(grads)
            with jax.named_scope(scopes.UPDATE):
                return optimizer.update(reduced, state, params, **extra)
        with jax.named_scope(scopes.REDUCE):
            reduced, new_res = _reduce_tree_ef(
                grads, state.residual, state.step, op, axis_name,
                fusion_threshold_bytes, prescale_factor, postscale_factor,
                quantize_min_bucket_bytes, route)
        with jax.named_scope(scopes.UPDATE):
            updates, new_inner = optimizer.update(reduced, state.inner,
                                                  params, **extra)
        return updates, _EFState(new_inner, new_res, state.step + 1)

    # Non-finite guard (docs/integrity.md): wraps the WHOLE core —
    # reduction + inner update — in the globally-agreed lax.cond, so a
    # skipped step leaves inner state, EF residual, and EF step counter
    # untouched. The k>1 aggregation below wraps THIS, so each
    # effective (post-accumulation) step is what gets guarded. Under a
    # mesh route the one-scalar agreement runs over the PLAN's axes
    # (the flat rank axis is not bound there); resolved at TRACE time
    # so a defaulted route reaching a flat-axis step still agrees over
    # the live axis (matching _reduce_tree's fallback).
    def _guard_axes():
        if route is not None and _axes_bound(*route.axis_names):
            return tuple(route.axis_names)
        if hierarchical and _axes_bound(local_axis, cross_axis):
            return (local_axis, cross_axis)
        return axis_name

    if nonfinite_policy is None:
        u_init, u_update = core_init, core_update
    else:
        def u_init(params):
            return _GuardedState(
                inner=core_init(params),
                guard=integrity_lib.init_guard_state(nonfinite_policy,
                                                     scale_cfg))

        def u_update(grads, state, params=None, **extra):
            def fn(g, c):
                return core_update(g, c, params, **extra)

            updates, new_inner, new_guard = integrity_lib.guarded_apply(
                nonfinite_policy, fn, grads, state.inner, state.guard,
                _guard_axes(), scale_cfg)
            return updates, _GuardedState(new_inner, new_guard)

    def _finish(init_f, update_f):
        if tp_combine_axis is not None:
            # Tensor/sequence-parallel slice grads reassemble (pmean
            # over tp, then sp) BEFORE everything downstream — the dp
            # reduction, the guard's finite check, and the legacy k>1
            # accumulator all see exact gradients (pmean is linear, so
            # combining ahead of accumulation is equivalent).
            inner_update_f = update_f

            def update_f(grads, state, params=None, **extra):  # noqa: F811
                return inner_update_f(_combine_tp(grads,
                                                  tp_combine_axis),
                                      state, params, **extra)

        return AccumGradientTransformation(
            init_f, update_f, accum_k, remat_name)

    if k <= 1:
        return _finish(u_init, u_update)

    def init_fn(params):
        acc = jax.tree.map(jnp.zeros_like, params)
        return _AggState(inner=u_init(params), acc=acc,
                         counter=jnp.zeros((), jnp.int32))

    def update_fn(grads, state, params=None, **extra):
        acc = jax.tree.map(jnp.add, state.acc, grads)
        counter = state.counter + 1
        do_step = counter >= k

        def take_step(args):
            acc, inner = args
            scale = (1.0 / k) if average_aggregated_gradients else 1.0
            scaled = jax.tree.map(lambda g: g * scale, acc) \
                if scale != 1.0 else acc
            updates, new_inner = u_update(scaled, inner, params,
                                          **extra)
            zeroed = jax.tree.map(jnp.zeros_like, acc)
            return updates, new_inner, zeroed

        def skip_step(args):
            acc, inner = args
            updates = jax.tree.map(jnp.zeros_like, acc)
            return updates, inner, acc

        updates, new_inner, new_acc = jax.lax.cond(
            do_step, take_step, skip_step, (acc, state.inner))
        new_counter = jnp.where(do_step, 0, counter)
        return updates, _AggState(new_inner, new_acc, new_counter)

    return _finish(init_fn, update_fn)


def DistributedGradFn(grad_fn: Callable,
                      op: C.ReduceOp = C.ReduceOp.AVERAGE,
                      axis_name: str = "hvd",
                      compression=None,
                      fusion_threshold_bytes: Optional[int] = None,
                      has_value: bool = False,
                      reduce_value: bool = True,
                      quantize_min_bucket_bytes: Optional[int] = None,
                      nonfinite_policy: Optional[str] = None,
                      route=None,
                      accum_steps: Optional[int] = None,
                      remat_policy: Optional[str] = None):
    """DistributedGradientTape analog (reference
    tensorflow/__init__.py:564-629): wraps a function returning gradients
    (e.g. ``jax.grad(loss)``) so the result is allreduced across ranks.

    ``has_value=True`` declares the wrapped function follows the
    ``jax.value_and_grad`` convention ``(value, grads)``; the value is
    additionally averaged across ranks when ``reduce_value``. Explicit flag
    instead of tuple-sniffing so ``jax.grad(loss, argnums=(0, 1))`` (a
    tuple of gradients) is never misclassified.

    The reduction's shape is chosen as on :func:`DistributedOptimizer`:
    each gradient reduced where it lies wherever that sums the same
    numbers, flat buckets elsewhere.

    ``accum_steps`` (EXPLICIT-ONLY on this surface: it changes how the
    first argument is interpreted, so the ``HVD_TPU_ACCUM_STEPS`` env
    default is deliberately not consulted) selects SCAN-BASED gradient
    accumulation: pass the LOSS function instead of ``jax.grad(loss)``
    — the wrapper owns the grad computation (it must: the microbatch
    scan and the ``remat_policy`` ``jax.checkpoint`` wrap live between
    loss and gradients, :func:`accumulate_gradients`)::

        gfn = hvd.DistributedGradFn(loss_fn, accum_steps=4,
                                    remat_policy="dots", has_value=True)
        (loss, grads) = gfn(params, batch)   # batch rows = 4 * mb

    The batch args are split into k microbatches along their leading
    dim, gradients accumulate in fp32 under ``lax.scan``, and the
    REDUCTION (with int8_ef error feedback / route / the
    non-finite guard agreement) runs exactly once on the accumulated
    mean — one collective round and one guard agreement per effective
    step. ``has_value=False`` simply drops the (already computed) loss
    from the returns.

    With an error-feedback compression (``"int8_ef"``) the wrapper is
    STATEFUL in the functional style: the wrapped function grows an
    ``ef_state`` keyword and returns ``(result, new_ef_state)`` — thread
    the state through your training loop like optimizer state::

        gfn = hvd.DistributedGradFn(jax.grad(loss), compression="int8_ef")
        ef = gfn.init_ef_state(params)        # zeros residual + step 0
        grads, ef = gfn(params, batch, ef_state=ef)

    ``ef_state=None`` starts from a zero residual (valid, but the
    residual is then discarded each call — quantization error no longer
    cancels across steps; thread the state for fp32-like convergence).

    ``nonfinite_policy`` (docs/integrity.md) arms the non-finite guard:
    the wrapped function grows a ``guard_state`` keyword and APPENDS
    the new guard state to its returns — ``(grads, guard)``, or
    ``(grads, ef_state, guard)`` with an error-feedback compression.
    On a globally-agreed non-finite step the returned gradients are
    zeros and (under ``skip_step``/``scale_backoff``/``abort``) the EF
    residual is NOT updated; gate your own optimizer update on
    ``guard.last_ok`` if zero gradients are not a no-op for it. Seed
    with ``wrapped.init_guard_state()``. EXPLICIT-ONLY on this surface:
    the ``HVD_TPU_NONFINITE_POLICY`` env default is deliberately NOT
    consulted here — the guard changes the wrapped function's return
    arity, and an env knob must never silently break existing call
    sites (the optimizer surfaces, whose state is opaque, do honor it).
    """
    compression = _resolve_compression(compression)
    _check_reduce_safe(compression)
    ef = getattr(compression, "error_feedback", False)
    route = _resolve_route(route)
    accum_k = int(accum_steps) if accum_steps is not None else 1
    if accum_k > 1:
        # grad_fn is the LOSS here; the scan driver produces
        # (value, grads) — has_value only controls the caller-visible
        # return arity below.
        grad_fn = accumulate_gradients(grad_fn, accum_k, remat_policy)
        produces_value = True
    else:
        if accum_k < 1:
            raise ValueError(f"accum_steps must be >= 1, got {accum_k}")
        if remat_policy is not None:
            raise ValueError(
                "remat_policy on DistributedGradFn requires "
                "accum_steps > 1 — remat wraps the LOSS before "
                "value_and_grad, which this surface only owns under "
                "the microbatch scan (use jax.checkpoint on your loss "
                "directly otherwise)")
        produces_value = has_value
    if ef and op not in (C.ReduceOp.SUM, C.ReduceOp.AVERAGE,
                         C.ReduceOp.ADASUM):
        raise ValueError(
            f"compression={compression.__name__} needs a SUM/AVERAGE/"
            "ADASUM op")
    fusion_threshold_bytes = _resolve_fusion_threshold(fusion_threshold_bytes)
    quantize_min_bucket_bytes = _resolve_quantize_min_bytes(
        quantize_min_bucket_bytes)
    nonfinite_policy = integrity_lib.resolve_nonfinite_policy(
        nonfinite_policy) if nonfinite_policy is not None else None
    scale_cfg = integrity_lib.ScaleConfig.from_env()
    def _guard_axes():
        """Resolved at TRACE time: the plan's axes when they are bound,
        else the flat rank axis (a defaulted route must not push the
        guard's agreement onto unbound axes — see _reduce_tree)."""
        if route is not None and _axes_bound(*route.axis_names):
            return tuple(route.axis_names)
        return axis_name

    def reduce_grads(grads):
        return _reduce_tree(grads, op, axis_name, compression,
                            fusion_threshold_bytes, route=route)

    def _reduce_value(val):
        if not reduce_value:
            return val
        if route is not None and _axes_bound(*route.axis_names):
            return jax.tree.map(
                lambda v: C.mesh_allreduce(
                    v, C.ReduceOp.AVERAGE, route.with_wires("none")),
                val)
        if _axes_bound(axis_name):
            return jax.tree.map(
                lambda v: C.allreduce(v, C.ReduceOp.AVERAGE, axis_name),
                val)
        return val

    def _guard_or_init(guard_state):
        if guard_state is not None:
            return guard_state
        return integrity_lib.init_guard_state(nonfinite_policy, scale_cfg)

    if ef:
        def wrapped(*args, ef_state=None, guard_state=None, **kwargs):
            out = grad_fn(*args, **kwargs)
            val, grads = out if produces_value else (None, out)
            if ef_state is None:
                residual = _zeros_residual(grads)
                step = jnp.zeros((), jnp.int32)
            else:
                residual, step = ef_state.residual, ef_state.step

            def reduce_ef(g, carry):
                res, stp = carry
                red, new_res = _reduce_tree_ef(
                    g, res, stp, op, axis_name,
                    fusion_threshold_bytes,
                    quantize_min_bytes=quantize_min_bucket_bytes,
                    route=route)
                return red, (new_res, stp + 1)

            if nonfinite_policy is None:
                reduced, (new_res, new_step) = reduce_ef(
                    grads, (residual, step))
                new_state = _EFState(inner=None, residual=new_res,
                                     step=new_step)
                if has_value:
                    return (_reduce_value(val), reduced), new_state
                return reduced, new_state
            # Guarded: the cond wraps the whole quantized reduction, so
            # a skipped step leaves residual AND step counter untouched
            # (the error-feedback telescoping stays exact).
            reduced, (new_res, new_step), new_guard = \
                integrity_lib.guarded_apply(
                    nonfinite_policy, reduce_ef, grads, (residual, step),
                    _guard_or_init(guard_state), _guard_axes(),
                    scale_cfg)
            new_state = _EFState(inner=None, residual=new_res,
                                 step=new_step)
            if has_value:
                return (_reduce_value(val), reduced), new_state, new_guard
            return reduced, new_state, new_guard

        wrapped.init_ef_state = lambda grads_template: _EFState(
            inner=None, residual=_zeros_residual(grads_template),
            step=jnp.zeros((), jnp.int32))
        if nonfinite_policy is not None:
            wrapped.init_guard_state = lambda: integrity_lib. \
                init_guard_state(nonfinite_policy, scale_cfg)
        return wrapped

    def wrapped(*args, guard_state=None, **kwargs):
        out = grad_fn(*args, **kwargs)
        if produces_value:
            val, grads = out
        else:
            val, grads = None, out
        if nonfinite_policy is None:
            if has_value:
                return _reduce_value(val), reduce_grads(grads)
            return reduce_grads(grads)
        reduced, _, new_guard = integrity_lib.guarded_apply(
            nonfinite_policy, lambda g, c: (reduce_grads(g), c), grads,
            (), _guard_or_init(guard_state), _guard_axes(), scale_cfg)
        if has_value:
            return (_reduce_value(val), reduced), new_guard
        return reduced, new_guard

    if nonfinite_policy is not None:
        wrapped.init_guard_state = lambda: integrity_lib. \
            init_guard_state(nonfinite_policy, scale_cfg)
    return wrapped


class AutotunedStepper:
    """Drives the runtime Autotuner from real step timings and rebuilds the
    jitted step function whenever the suggested fusion threshold moves.

    This is the in-jit analog of the reference's live ParameterManager
    tuning (parameter_manager.cc: each cycle scores bytes/sec and may
    change the fusion threshold; subsequent cycles fuse differently).
    Under XLA a threshold change means a different bucket plan, i.e. a
    retrace — so the stepper owns the (re)build. The plan exists only on
    the paths that reduce flat buckets (``route``, ``hierarchical``,
    Adasum, ``int8_ef``, ZeRO); the default step reduces each gradient
    in place and builds the same program at every threshold::

        def build(threshold_bytes):
            tx = hvd.DistributedOptimizer(optax.sgd(0.01),
                                          fusion_threshold_bytes=threshold_bytes)
            ... return jitted_step               # closes over tx
        stepper = hvd.AutotunedStepper(build, grad_bytes=nbytes)
        while training:
            out = stepper(*step_args)

    ``grad_bytes`` is the bytes reduced per step (the score numerator,
    matching the reference's bytes/sec score, parameter_manager.h:42).
    """

    def __init__(self, build_step: Callable[[int], Callable],
                 grad_bytes: int, tuner=None, block: bool = True,
                 controller=None):
        from .common import basics

        if tuner is None:
            tuner = basics.context().autotuner
            if tuner is None:
                raise ValueError(
                    "runtime autotuner not enabled — init(autotune=True) "
                    "or set HVD_TPU_AUTOTUNE=1, or pass tuner= explicitly")
        if controller is None and basics.is_initialized():
            controller = basics.context().controller
        self.tuner = tuner
        self.grad_bytes = int(grad_bytes)
        self.block = block
        self._build = build_step
        # Multi-process: rank 0 alone scores samples and decides; every
        # process adopts the decision at the SAME call index via a
        # synchronous controller exchange — per-process decisions would
        # compile diverged bucket plans and deadlock the collectives
        # (reference: SynchronizeParameters broadcasts rank-0's
        # ParameterManager state, controller.cc:34-48).
        self._controller = controller
        self._period = tuner.warmup + tuner.steps_per_sample
        self._calls = 0
        self._tuner_done = False  # set when rank 0 broadcasts :done
        self._threshold = tuner.current
        # Joint tuning (reference ParameterManager's hierarchical toggle):
        # build_step then takes (threshold, hierarchical). With a
        # tune_compression tuner the signature widens to
        # (threshold, hierarchical, compression), and with
        # tune_route to (..., route) — route is the axis-order/
        # reduction-mode candidate ("flat"/"staged"/"staged_int8"/
        # "adasum"; docs/topology.md) — the full point the (re)built
        # step must agree on across ranks.
        self._joint = getattr(tuner, "tune_hierarchical", False)
        self._joint_comp = getattr(tuner, "tune_compression", False)
        self._joint_route = getattr(tuner, "tune_route", False)
        # MFU dimensions (docs/performance.md): accumulation microbatch
        # count, remat policy, weight-update sharding. When ANY of them
        # is tuned, build_step receives the whole
        # :class:`~.common.autotune.TunedPoint` instead of the
        # positional cascade — seven positional args would be
        # unreadable at every call site.
        self._joint_accum = getattr(tuner, "tune_accum", False)
        self._joint_remat = getattr(tuner, "tune_remat", False)
        self._joint_shard = getattr(tuner, "tune_shard", False)
        # MoE dispatch-wire axis (docs/moe.md): like the MFU axes it
        # rides the whole-TunedPoint build signature — the build fn
        # threads pt.moe_wire into its moe_layer/MoeMlp construction.
        self._joint_moe_wire = getattr(tuner, "tune_moe_wire", False)
        # Pipeline stage-boundary wire axis (docs/pipeline.md): same
        # whole-TunedPoint contract — the build fn threads pt.pp_wire
        # into its pipeline_accumulate_gradients(wire=) construction.
        self._joint_pp_wire = getattr(tuner, "tune_pp_wire", False)
        self._hier = (tuner.current_hierarchical if self._joint else False)
        self._comp = (tuner.current_compression if self._joint_comp
                      else "none")
        self._route = (tuner.current_route if self._joint_route
                       else "flat")
        self._accum = (tuner.current_accum if self._joint_accum else 1)
        self._remat = (tuner.current_remat if self._joint_remat
                       else "none")
        self._shard = (tuner.current_shard if self._joint_shard
                       else 0)  # ZeRO stage, 0 = replicated
        self._moe_wire = (tuner.current_moe_wire
                          if self._joint_moe_wire else "none")
        self._pp_wire = (tuner.current_pp_wire
                         if self._joint_pp_wire else "none")
        self._step = self._rebuild()
        self.rebuilds = 0
        self._step_count = 0  # metrics/profiler step numbering

    @property
    def _mfu_joint(self) -> bool:
        return (self._joint_accum or self._joint_remat
                or self._joint_shard or self._joint_moe_wire
                or self._joint_pp_wire)

    def _rebuild(self):
        if self._mfu_joint:
            from .common.autotune import TunedPoint

            return self._build(TunedPoint(
                threshold=self._threshold, hierarchical=self._hier,
                compression=self._comp, route=self._route,
                accum=self._accum, remat=self._remat, shard=self._shard,
                moe_wire=self._moe_wire, pp_wire=self._pp_wire))
        if self._joint_route:
            return self._build(self._threshold, self._hier, self._comp,
                               self._route)
        if self._joint_comp:
            return self._build(self._threshold, self._hier, self._comp)
        if self._joint:
            return self._build(self._threshold, self._hier)
        return self._build(self._threshold)

    @property
    def fusion_threshold(self) -> int:
        return self._threshold

    @property
    def hierarchical(self) -> bool:
        return self._hier

    @property
    def compression(self) -> str:
        return self._comp

    @property
    def route(self) -> str:
        return self._route

    @property
    def accum(self) -> int:
        return self._accum

    @property
    def remat(self) -> str:
        return self._remat

    @property
    def shard(self) -> int:
        """The tuned ZeRO stage (0 = replicated; docs/zero.md)."""
        return self._shard

    @property
    def moe_wire(self) -> str:
        return self._moe_wire

    @property
    def pp_wire(self) -> str:
        return self._pp_wire

    def __call__(self, *args, **kwargs):
        import time

        self._step_count += 1
        t0 = time.perf_counter()
        # metrics<->timeline bridge: a StepTraceAnnotation per step when
        # HVD_TPU_METRICS_TRACE=1, so device-side traces group by step.
        with metrics_lib.step_annotation(self._step_count):
            out = self._step(*args, **kwargs)
            if self.block:
                jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        if _METRICS_ON:
            _M_STEP.observe(dt)
        c = self._controller
        if c is None or c.size == 1:
            pt = self.tuner.feed_full(self.grad_bytes, dt)
            new = pt.threshold
            new_h = pt.hierarchical if self._joint else self._hier
            new_c = pt.compression if self._joint_comp else self._comp
            new_r = pt.route if self._joint_route else self._route
            new_a = pt.accum if self._joint_accum else self._accum
            new_m = pt.remat if self._joint_remat else self._remat
            new_s = pt.shard if self._joint_shard else self._shard
            new_w = pt.moe_wire if self._joint_moe_wire \
                else self._moe_wire
            new_pw = pt.pp_wire if self._joint_pp_wire \
                else self._pp_wire
        else:
            if c.rank == 0:
                self.tuner.record(self.grad_bytes, dt)
            self._calls += 1
            (new, new_h, new_c, new_r, new_a, new_m, new_s,
             new_w, new_pw) = (
                self._threshold, self._hier, self._comp, self._route,
                self._accum, self._remat, self._shard, self._moe_wire,
                self._pp_wire)
            if self._calls % self._period == 0 and not self._tuner_done:
                # Sample boundary — same call index on every process
                # (SPMD lockstep), so the exchange is synchronous. After
                # rank 0 broadcasts convergence (:done) the rounds stop —
                # no point paying a KV round per period forever.
                if c.rank == 0 and self.tuner.ready():
                    self.tuner.suggest()
                cur = self.tuner.current_full  # atomic
                mine = (f"{cur.threshold}"
                        f"|{int(cur.hierarchical) if self._joint else 0}"
                        f"|{cur.compression if self._joint_comp else 'none'}"
                        f"|{cur.route if self._joint_route else 'flat'}"
                        f"|{cur.accum if self._joint_accum else 1}"
                        f"|{cur.remat if self._joint_remat else 'none'}"
                        f"|{int(cur.shard) if self._joint_shard else 0}"
                        f"|{cur.moe_wire if self._joint_moe_wire else 'none'}"
                        f"|{cur.pp_wire if self._joint_pp_wire else 'none'}"
                        + (":done" if c.rank == 0 and self.tuner.done
                           else ""))
                vals = c.exchange("autotune_threshold", mine)
                v0 = vals[0]  # rank 0's decision wins
                if v0.endswith(":done"):
                    self._tuner_done = True
                    v0 = v0[:-5]
                (t_str, h_str, c_str, r_str, a_str, m_str,
                 s_str, w_str, pw_str) = v0.split("|")
                new = int(t_str)
                new_h = bool(int(h_str)) if self._joint else self._hier
                new_c = c_str if self._joint_comp else self._comp
                new_r = r_str if self._joint_route else self._route
                new_a = int(a_str) if self._joint_accum else self._accum
                new_m = m_str if self._joint_remat else self._remat
                new_s = int(s_str) if self._joint_shard \
                    else self._shard
                new_w = w_str if self._joint_moe_wire \
                    else self._moe_wire
                new_pw = pw_str if self._joint_pp_wire \
                    else self._pp_wire
        if (new != self._threshold or new_h != self._hier
                or new_c != self._comp or new_r != self._route
                or new_a != self._accum or new_m != self._remat
                or new_s != self._shard
                or new_w != self._moe_wire or new_pw != self._pp_wire):
            (self._threshold, self._hier, self._comp, self._route,
             self._accum, self._remat, self._shard,
             self._moe_wire, self._pp_wire) = (
                new, new_h, new_c, new_r, new_a, new_m, new_s,
                new_w, new_pw)
            self._step = self._rebuild()
            self.rebuilds += 1
            _M_REBUILDS.inc()
        return out


def broadcast_parameters(params, root_rank: int = 0,
                         axis_name: str = "hvd"):
    """Broadcast a parameter pytree from root to all ranks — for use inside
    the jitted init path (reference: torch/functions.py:30
    broadcast_parameters / tensorflow broadcast_variables)."""
    return jax.tree.map(
        lambda p: C.broadcast(p, root_rank, axis_name), params)


# -- ZeRO-1 sharded optimizer state (beyond the reference) ------------------
#
# The reference replicates optimizer state on every rank (its
# DistributedOptimizer wraps a local optimizer; state is per-rank,
# memory = full). On TPU the idiomatic win is to SHARD the state over
# the rank axis: reduce-scatter the gradients, update only this rank's
# 1/n slice of each parameter with the inner optax transform, and
# all-gather the resulting updates — optimizer memory drops to 1/n (the
# ZeRO-1 / Megatron "distributed optimizer" recipe) while the wire cost
# stays the allreduce-equivalent RS+AG pair.
#
# Works for ELEMENTWISE inner transforms (sgd/momentum/adam/adamw/...).
# Transforms that couple elements across the tree (global-norm clipping)
# would compute shard-local statistics — compose those OUTSIDE.

def _sharded_state_specs(inner, plan, axes):
    """PartitionSpecs for an inner transform's state over bucket shards:
    vector leaves P(axes) — a single axis name, or the plan's axis
    tuple under a route (fast-major) — scalar leaves (step counters)
    replicated. A length-1 probe per bucket suffices — only leaf rank
    matters."""
    from jax.sharding import PartitionSpec as P

    probe = [jax.ShapeDtypeStruct((1,), b.dtype) for b in plan.buckets]
    shapes = jax.eval_shape(inner.init, probe)
    return jax.tree.map(
        lambda s: P(axes) if s.ndim else P(), shapes)


def _gather_sharded_state(inner, plan, state, axis_name: str):
    """Sharded inner state -> WORLD-SIZE-INDEPENDENT full state: every
    vector (bucket-shard) leaf all-gathers and drops the shard-split
    padding; scalar leaves pass through. The inverse of
    :func:`_reshard_state` — together they carry ZeRO-1/FSDP state
    across an elastic WORLD-SIZE CHANGE, where the 1/n shard shapes
    (and their pad-to-multiple) differ between the old and new worlds
    so a sharded checkpoint cannot be restored directly."""
    full_probe = [jax.ShapeDtypeStruct((b.total_elems,), b.dtype)
                  for b in plan.buckets]
    full_shapes = jax.eval_shape(inner.init, full_probe)

    def one(leaf, shp):
        if shp.ndim:
            return C.allgather(leaf, axis_name)[:shp.shape[0]]
        return leaf

    return jax.tree.map(one, state, full_shapes)


def _gather_sharded_state_routed(inner, plan, state, route):
    """Mesh analog of :func:`_gather_sharded_state`: vector (bucket-
    shard) leaves all-gather over the plan in REVERSE with wires forced
    native — state carry must be lossless — and drop the grid padding;
    scalar leaves pass through. Serves both the ZeRO-1 and FSDP routed
    gathers (one derivation to maintain)."""
    exact = route.reversed().with_wires("none")
    full_probe = [jax.ShapeDtypeStruct((b.total_elems,), b.dtype)
                  for b in plan.buckets]
    full_shapes = jax.eval_shape(inner.init, full_probe)
    return jax.tree.map(
        lambda leaf, shp: (C.mesh_allgather(leaf, exact)[:shp.shape[0]]
                           if shp.ndim else leaf),
        state, full_shapes)


def _reshard_state(state_full, axis_name: str):
    """Full (gathered) inner state -> this world's shards: vector
    leaves re-split 1/n under the CURRENTLY BOUND axis (whatever its
    size), scalars pass through."""
    return jax.tree.map(
        lambda v: _shard_flat(v, axis_name) if v.ndim else v,
        state_full)


def _require_axis(axis_name: str, what: str) -> None:
    if not _axes_bound(axis_name):
        raise ValueError(
            f"{what} must run inside the jitted SPMD region (shard_map/"
            f"pjit binding axis {axis_name!r}) — the shard shapes and "
            f"slices depend on the bound axis. Wrap the call in your "
            f"spmd_step (see ShardedOptimizer docstring).")


def _shard_flat(flat, axis_name: str, align: int = 1):
    """(1-D bucket) -> this rank's padded 1/n slice. ``align`` rounds the
    per-rank chunk up to a multiple (the quantized RS path needs whole
    32x128 int8 blocks per chunk, align=4096); align=1 is the historical
    layout and MUST stay the default — sharded state is positionally
    indexed by these shapes."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    # pad-to-multiple-of(n*align) == per-rank chunks of ceil-aligned
    # size: ceil(ceil(L/n)/a)*a == ceil(L/(n*a))*a.
    flat, _ = fusion_lib.pad_to_multiple(flat, n * align)
    chunk = flat.shape[0] // n
    return jax.lax.dynamic_slice_in_dim(flat, idx * chunk, chunk)


# -- mesh-routed sharding (route= on the ZeRO-1/FSDP surfaces) ---------------
#
# With a WirePlan the shard grid spans ALL plan axes (N = prod of axis
# sizes) and chunk ownership is fast-axis-MAJOR — exactly the layout
# collectives.mesh_reducescatter's descent produces, so the gradient RS
# can ride the staged per-axis wires (int8 on the slow hop) and the
# update all-gather inverts it with plan.reversed() (docs/topology.md).

def _route_total(route) -> int:
    n = 1
    for a in route.axis_names:
        n *= jax.lax.axis_size(a)
    return n


def _route_align(compression, route) -> int:
    """Per-rank chunk alignment: whole 32x128 int8 blocks whenever ANY
    hop is quantized — by the error-feedback compression or by an int8
    wire on the plan itself (a stateless staged_int8 route quantizes the
    RS the same way)."""
    from .ops.collectives import _Q_BLOCK

    ef = getattr(compression, "error_feedback", False)
    if ef or (route is not None and "int8" in route.wires):
        return _Q_BLOCK
    return 1


def _mesh_shard_flat(flat, route, align: int = 1):
    """(1-D bucket) -> this rank's padded 1/N mesh slice, N = prod of
    the plan's axis sizes, fast-axis-major chunk ownership (the static
    twin of mesh_reducescatter's descent: each phase keeps this rank's
    chunk of the previous phase's chunk)."""
    N = _route_total(route)
    flat, _ = fusion_lib.pad_to_multiple(flat, N * align)
    for a in route.axis_names:
        n = jax.lax.axis_size(a)
        idx = jax.lax.axis_index(a)
        chunk = flat.shape[0] // n
        flat = jax.lax.dynamic_slice_in_dim(flat, idx * chunk, chunk)
    return flat


def _sharded_route(route, axis_name: str):
    """Resolve + trace-time fallback for the sharded surfaces.

    EXPLICIT-ONLY: unlike the reduction surfaces, ``route=None`` does
    NOT consult the ``HVD_TPU_ROUTE`` / ``init(route=)`` default here —
    the route decides the sharded STATE LAYOUT (and the PartitionSpecs
    built OUTSIDE any trace, where no fallback can be probed), and an
    env knob must never change a state layout out from under a
    flat-world program. An explicit route traced under the flat mesh
    still falls back to the flat axis (safety net — same contract as
    _reduce_tree)."""
    route = C.WirePlan.resolve(route)
    if route is not None and not _axes_bound(*route.axis_names) \
            and _axes_bound(axis_name):
        return None
    return route


class _EFShardState(NamedTuple):
    """ZeRO-1 (sharded_update) analog of :class:`_EFState`: the inner
    state over bucket shards, plus this rank's full-length fp32
    quantization residual per bucket (padded to the quantized chunk
    grid) and the stochastic-rounding step counter."""

    inner: Any
    residual: Any            # list of (n*chunk,) fp32 arrays per bucket
    step: jnp.ndarray


def _qpad_len(total_elems: int, n: int) -> int:
    """Padded bucket length on the quantized-RS chunk grid — the static
    twin of ``_shard_flat(..., align=_Q_BLOCK)``'s padding."""
    from .ops.collectives import _Q_BLOCK

    grid = n * _Q_BLOCK
    return -(-total_elems // grid) * grid


def sharded_init(tx, params, axis_name: str = "hvd",
                 fusion_threshold_bytes: Optional[int] = None,
                 compression=None, nonfinite_policy: Optional[str] = None,
                 route=None):
    """Inner-optimizer state over FUSED-BUCKET SHARDS — call inside the
    same shard_map/jit region as :func:`sharded_update` (the shard
    shapes depend on the bound axis). State structure = the inner
    transform's state over a list of per-bucket shard arrays.

    With ``compression="int8_ef"`` the gradient reduce-scatter runs
    quantized (collectives.quantized_reducescatter) and the state gains
    the error-feedback residual + step counter (:class:`_EFShardState`);
    shard chunks align to the 4096-element int8 block grid, so a state
    built with compression can only be consumed by an update using the
    SAME compression (and vice versa). ``nonfinite_policy`` likewise
    wraps the state in :class:`_GuardedState` (docs/integrity.md) —
    init and update must agree on it.

    ``route`` (EXPLICIT-ONLY — the ``HVD_TPU_ROUTE`` env default
    applies to the reduction surfaces, never to a sharded state
    layout) shards over ALL the WirePlan's mesh axes (fast-axis-major,
    1/prod(sizes) per rank — docs/topology.md): the gradient
    reduce-scatter then descends the staged per-axis wires instead of
    the flat axis. Init, update, gather and reshard must all agree on
    the route — it decides the shard grid."""
    route = _sharded_route(route, axis_name)
    if route is not None:
        for a in route.axis_names:
            _require_axis(a, "sharded_init(route=)")
    else:
        _require_axis(axis_name, "sharded_init")
    compression = _resolve_compression(compression)
    _check_reduce_safe(compression)
    ef = getattr(compression, "error_feedback", False)
    nonfinite_policy = integrity_lib.resolve_nonfinite_policy(
        nonfinite_policy)
    threshold = _resolve_fusion_threshold(fusion_threshold_bytes)
    plan = fusion_lib.plan_fusion(params, threshold)
    flats = fusion_lib.fuse(params, plan)
    from .ops.collectives import _Q_BLOCK

    if route is not None:
        align = _route_align(compression, route)
        n = _route_total(route)
        inner = tx.init([_mesh_shard_flat(f, route, align)
                         for f in flats])
    else:
        align = _Q_BLOCK if ef else 1
        n = jax.lax.axis_size(axis_name)
        inner = tx.init([_shard_flat(f, axis_name, align)
                         for f in flats])
    if ef:
        residual = [jnp.zeros((_qpad_len(b.total_elems, n),), jnp.float32)
                    for b in plan.buckets]
        inner = _EFShardState(inner=inner, residual=residual,
                              step=jnp.zeros((), jnp.int32))
    if nonfinite_policy is None:
        return inner
    return _GuardedState(
        inner=inner,
        guard=integrity_lib.init_guard_state(nonfinite_policy))


def sharded_update(tx, grads, state, params, axis_name: str = "hvd",
                   grad_op: C.ReduceOp = C.ReduceOp.AVERAGE,
                   fusion_threshold_bytes: Optional[int] = None,
                   compression=None,
                   nonfinite_policy: Optional[str] = None,
                   route=None, **extra):
    """ZeRO-1 step over fused buckets: RS(bucket grads) -> inner update
    on this rank's shards -> AG(bucket updates). A few large collectives
    instead of one pair per leaf (same bucketing as the replicated
    path). Returns ``(updates, new_state)`` with ``updates`` shaped like
    ``params`` (apply with ``optax.apply_updates``).

    ``compression="int8_ef"`` (state from ``sharded_init`` with the same
    compression) carries the gradient reduce-scatter — the hop that
    moves (n-1)/n of every gradient byte — as block-scaled int8 with
    stochastic rounding, folding each step's quantization error into the
    carried residual. The update all-gather stays in the params' dtype:
    updates are small relative to gradients' dynamic range and have no
    residual state to absorb a second rounding.

    ``route`` (state from a ``sharded_init`` with the SAME route) runs
    the gradient reduce-scatter as the staged per-axis descent
    (``collectives.mesh_reducescatter``) and the update all-gather as
    the inverse ascent — each hop in its axis's wire format, so a
    ``staged_int8`` plan puts int8 only where the slow bytes are
    (docs/topology.md). With ``int8_ef`` the descent's quantization
    error feeds the carried residual (the ``mesh_reducescatter``
    Σ-over-ranks contract) and the update ascent stays in the params'
    dtype, exactly like the flat path."""
    if grad_op not in (C.ReduceOp.SUM, C.ReduceOp.AVERAGE):
        raise ValueError("sharded_update supports SUM/AVERAGE")
    route = _sharded_route(route, axis_name)
    if route is not None:
        for a in route.axis_names:
            _require_axis(a, "sharded_update(route=)")
    else:
        _require_axis(axis_name, "sharded_update")
    compression = _resolve_compression(compression)
    ef = getattr(compression, "error_feedback", False)
    nonfinite_policy = integrity_lib.resolve_nonfinite_policy(
        nonfinite_policy)
    guarded = isinstance(state, _GuardedState)
    if (nonfinite_policy is not None) != guarded:
        raise ValueError(
            "sharded_update nonfinite_policy= must match the "
            "sharded_init that built this state: policy="
            f"{nonfinite_policy}, state "
            f"{'carries' if guarded else 'lacks'} a guard")
    inner_state = state.inner if guarded else state
    if ef != isinstance(inner_state, _EFShardState):
        raise ValueError(
            "sharded_update compression= must match the sharded_init that "
            "built this state (error-feedback state and shard alignment "
            f"differ): compression={compression.__name__}, state "
            f"{'has' if isinstance(inner_state, _EFShardState) else 'lacks'} "
            "an error-feedback residual")
    n = (_route_total(route) if route is not None
         else jax.lax.axis_size(axis_name))
    threshold = _resolve_fusion_threshold(fusion_threshold_bytes)
    # Plan over PARAMS (grads share the treedef): the state was built
    # over the params plan, and a grad leaf cast to another dtype must
    # not change the bucket structure out from under the carried state.
    plan = fusion_lib.plan_fusion(params, threshold)
    p_flats = fusion_lib.fuse(params, plan)

    def core(g, st):
        """RS -> shard-local inner update -> AG; returns
        (updates_tree ≅ grads, new_inner_state)."""
        g_flats = fusion_lib.fuse(
            jax.tree.map(lambda gg, p: gg.astype(p.dtype), g, params),
            plan)
        if not ef:
            if route is not None:
                align = _route_align(compression, route)

                def rs(f):
                    padded, _ = fusion_lib.pad_to_multiple(f, n * align)
                    return C.mesh_reducescatter(padded, grad_op, route)

                g_shards = [rs(f) for f in g_flats]
                p_shards = [_mesh_shard_flat(f, route, align)
                            for f in p_flats]
                u_shards, new_st = tx.update(g_shards, st, p_shards,
                                             **extra)
                # Ascent inverts the fast-major descent: slow axis
                # first, each hop in its axis's wire format (stateless —
                # same bounded-error contract as mesh_allreduce).
                u_flats = [C.mesh_allgather(u, route.reversed())
                           [:f.shape[0]]
                           for u, f in zip(u_shards, g_flats)]
                return fusion_lib.unfuse(u_flats, plan), new_st

            def rs(f):
                padded, _ = fusion_lib.pad_to_multiple(f, n)
                return C.reducescatter(padded, grad_op, axis_name)

            g_shards = [rs(f) for f in g_flats]
            p_shards = [_shard_flat(f, axis_name) for f in p_flats]
            u_shards, new_st = tx.update(g_shards, st, p_shards, **extra)
            u_flats = [C.allgather(u, axis_name)[:f.shape[0]]
                       for u, f in zip(u_shards, g_flats)]
            return fusion_lib.unfuse(u_flats, plan), new_st

        from .ops.collectives import _Q_BLOCK

        g_shards, new_residual = [], []
        for i, (f, res) in enumerate(zip(g_flats, st.residual)):
            pad = res.shape[0] - f.shape[0]
            corrected = jnp.pad(f.astype(jnp.float32), (0, pad)) + res
            if route is not None:
                shard, r = C.mesh_reducescatter(
                    corrected, grad_op, route,
                    key=_ef_key(st.step, i), return_residual=True)
            else:
                shard, r = C.quantized_reducescatter(
                    corrected, grad_op, axis_name,
                    key=_ef_key(st.step, i), return_residual=True)
            g_shards.append(shard.astype(f.dtype))
            new_residual.append(r)
        if route is not None:
            p_shards = [_mesh_shard_flat(f, route, _Q_BLOCK)
                        for f in p_flats]
            # Update ascent stays in the params' dtype (wires
            # downgraded): updates have no residual state to absorb a
            # second rounding — the flat int8_ef contract.
            u_gather = route.reversed().with_wires("none")
        else:
            p_shards = [_shard_flat(f, axis_name, _Q_BLOCK)
                        for f in p_flats]
            u_gather = None
        u_shards, new_inner = tx.update(g_shards, st.inner, p_shards,
                                        **extra)
        u_flats = [(C.mesh_allgather(u, u_gather)
                    if u_gather is not None
                    else C.allgather(u, axis_name))[:f.shape[0]]
                   for u, f in zip(u_shards, g_flats)]
        new_st = _EFShardState(inner=new_inner, residual=new_residual,
                               step=st.step + 1)
        return fusion_lib.unfuse(u_flats, plan), new_st

    if not guarded:
        return core(grads, inner_state)
    # Guarded (docs/integrity.md): the cond wraps RS + update + AG, so
    # a skipped step leaves shards, EF residual and step untouched.
    # Under a route the one-scalar agreement runs over the PLAN's axes
    # (every mesh rank must take the same branch).
    updates, new_inner, new_guard = integrity_lib.guarded_apply(
        nonfinite_policy, core, grads, inner_state, state.guard,
        tuple(route.axis_names) if route is not None else axis_name)
    return updates, _GuardedState(new_inner, new_guard)


class ShardedOptimizer:
    """Object wrapper over :func:`sharded_init`/:func:`sharded_update`
    mirroring the optax GradientTransformation shape::

        tx = hvd.ShardedOptimizer(optax.adamw(1e-3), axis_name=ax)
        # inside the jitted step (axis bound):
        state = tx.init(params)                  # 1/n-sized state
        updates, state = tx.update(grads, state, params)
    """

    def __init__(self, inner, axis_name: str = "hvd",
                 grad_op: C.ReduceOp = C.ReduceOp.AVERAGE,
                 fusion_threshold_bytes: Optional[int] = None,
                 compression=None, nonfinite_policy: Optional[str] = None,
                 route=None, accum_steps: Optional[int] = None,
                 remat_policy: Optional[str] = None):
        self.inner = inner
        self.axis_name = axis_name
        self.grad_op = grad_op
        # Scan-based accumulation (docs/performance.md): pinned once
        # like the threshold; consumed by accumulate() — update() runs
        # once per EFFECTIVE step either way, so the RS+AG pair, the
        # guard agreement, and the EF advance stay once-per-step.
        self.accum_steps = _resolve_accum_steps(accum_steps)
        self.remat_policy = resolve_remat_policy(remat_policy)[0]
        # Pinned ONCE (like the DistributedOptimizer factory): the state
        # layout is one shard per bucket, so a live autotuner moving the
        # threshold between traces must not replan the buckets out from
        # under the carried state. Same for the compression: it decides
        # the shard alignment and the state structure (_EFShardState).
        # And the non-finite policy: it decides whether the state is
        # _GuardedState-wrapped (docs/integrity.md). And the route: it
        # decides the SHARD GRID (1/prod(mesh sizes), fast-axis-major)
        # — docs/topology.md.
        self.fusion_threshold_bytes = _resolve_fusion_threshold(
            fusion_threshold_bytes)
        self.compression = _resolve_compression(compression)
        _check_reduce_safe(self.compression)
        self._ef = getattr(self.compression, "error_feedback", False)
        self.nonfinite_policy = integrity_lib.resolve_nonfinite_policy(
            nonfinite_policy)
        # Explicit-only (no HVD_TPU_ROUTE default): the route decides
        # the state layout AND the state_specs built outside any trace.
        self.route = C.WirePlan.resolve(route)

    def _live_route(self):
        """The pinned route with the trace-time flat-mesh fallback
        applied (a defaulted route under the flat mesh must not change
        the shard grid — same contract as the reduction surfaces)."""
        return _sharded_route(self.route, self.axis_name)

    def accumulate(self, loss_fn, has_aux: bool = False):
        """The scan-based microbatch ``value_and_grad`` for the pinned
        ``accum_steps``/``remat_policy`` (:func:`accumulate_gradients`)
        — feed its mean gradient to :meth:`update` once per effective
        step."""
        return accumulate_gradients(loss_fn, self.accum_steps,
                                    self.remat_policy, has_aux=has_aux)

    def init(self, params):
        return sharded_init(self.inner, params, self.axis_name,
                            self.fusion_threshold_bytes,
                            compression=self.compression,
                            nonfinite_policy=self.nonfinite_policy,
                            route=self.route)

    def update(self, grads, state, params=None, **extra):
        if params is None:
            raise ValueError("ShardedOptimizer.update requires params "
                             "(the shard slices come from them)")
        return sharded_update(self.inner, grads, state, params,
                              self.axis_name, self.grad_op,
                              self.fusion_threshold_bytes,
                              compression=self.compression,
                              nonfinite_policy=self.nonfinite_policy,
                              route=self.route,
                              **extra)

    def state_specs(self, params):
        """PartitionSpecs for carrying the sharded state through
        shard_map: vector leaves are P(axis) (each rank owns its slice;
        the global array is the shard concatenation), scalar leaves
        (step counters) replicate. The probe uses the same fusion plan
        as init/update so the state STRUCTURE (one shard per bucket)
        matches — callable before init(). With an error-feedback
        compression the residual leaves are per-rank LOCAL (each rank's
        own quantization error), carried as P(axis) shards of the
        rank-stacked global view; the step counter replicates. Under a
        route the shard dim spans ALL plan axes fast-axis-major —
        ``P((fast, ..., slow))``."""
        from jax.sharding import PartitionSpec as P

        axes = (tuple(self.route.axis_names) if self.route is not None
                else self.axis_name)
        threshold = _resolve_fusion_threshold(self.fusion_threshold_bytes)
        plan = fusion_lib.plan_fusion(params, threshold)
        inner_specs = _sharded_state_specs(self.inner, plan, axes)
        if self._ef:
            inner_specs = _EFShardState(
                inner=inner_specs,
                residual=[P(axes)] * len(plan.buckets),
                step=P())
        if self.nonfinite_policy is None:
            return inner_specs
        # Guard scalars are globally agreed -> replicated.
        return _GuardedState(inner=inner_specs,
                             guard=integrity_lib.guard_state_specs())

    def gather_state(self, state, params):
        """Sharded state -> world-size-independent full state (inside
        the OLD world's SPMD region) — checkpoint this across an
        elastic resize; restore with :meth:`reshard_state` in the new
        world.

        The layout is still FUSION-PLAN-dependent: the new world's
        optimizer must resolve the SAME fusion threshold (pass
        ``fusion_threshold_bytes`` explicitly in elastic jobs — a
        live autotuner or changed env knob in the restarted process
        would re-bucket and silently misalign the per-bucket mu/nu
        vectors).

        Error-feedback states carry the residual across the resize as
        its PSUM: Σ_r residual_r is the total pending correction and is
        world-size-independent; :meth:`reshard_state` hands it to the
        new world's rank 0 (zeros elsewhere) — the next reduction sums
        residuals across ranks anyway, so placement is arbitrary.

        Routed states gather/psum over ALL the plan's axes (wires
        forced native — state carry must be exact); the gathered form
        is identical to the flat one, so a checkpoint written under a
        route restores into a flat world and vice versa (the residual's
        psum is grid-padding-independent: pads carry zeros)."""
        route = self._live_route()
        if route is not None:
            for a in route.axis_names:
                _require_axis(a, "ShardedOptimizer.gather_state")
        else:
            _require_axis(self.axis_name, "ShardedOptimizer.gather_state")
        threshold = _resolve_fusion_threshold(self.fusion_threshold_bytes)
        plan = fusion_lib.plan_fusion(params, threshold)
        guard = state.guard if isinstance(state, _GuardedState) else None
        if guard is not None:
            state = state.inner
        if route is not None:
            axes = tuple(route.axis_names)
            if not self._ef:
                full = _gather_sharded_state_routed(self.inner, plan,
                                                    state, route)
            else:
                inner_full = _gather_sharded_state_routed(
                    self.inner, plan, state.inner, route)
                residual_full = [
                    jax.lax.psum(r, axes)[:b.total_elems]
                    for r, b in zip(state.residual, plan.buckets)]
                full = _EFShardState(inner=inner_full,
                                     residual=residual_full,
                                     step=state.step)
        elif not self._ef:
            full = _gather_sharded_state(self.inner, plan, state,
                                         self.axis_name)
        else:
            inner_full = _gather_sharded_state(self.inner, plan,
                                               state.inner,
                                               self.axis_name)
            residual_full = [
                jax.lax.psum(r, self.axis_name)[:b.total_elems]
                for r, b in zip(state.residual, plan.buckets)]
            full = _EFShardState(inner=inner_full, residual=residual_full,
                                 step=state.step)
        if guard is None:
            return full
        # Guard scalars are replicated/world-size-independent — carried
        # across the resize verbatim.
        return _GuardedState(inner=full, guard=guard)

    def reshard_state(self, state_full):
        """Full (gathered) state -> this world's shards (inside the
        NEW world's SPMD region, whatever its size — or its ROUTE: a
        flat checkpoint reshards onto a mesh-routed world and back)."""
        route = self._live_route()
        if route is not None:
            for a in route.axis_names:
                _require_axis(a, "ShardedOptimizer.reshard_state")
        else:
            _require_axis(self.axis_name, "ShardedOptimizer.reshard_state")
        guard = state_full.guard \
            if isinstance(state_full, _GuardedState) else None
        if guard is not None:
            state_full = state_full.inner
        from .ops.collectives import _Q_BLOCK

        if route is not None:
            align = _route_align(self.compression, route)
            n = _route_total(route)
            # "Am I mesh rank 0" = every plan axis index is 0.
            me0 = jnp.asarray(True)
            for a in route.axis_names:
                me0 = jnp.logical_and(me0, jax.lax.axis_index(a) == 0)

            def shard_leaf(v):
                return _mesh_shard_flat(v, route, align) if v.ndim else v
        else:
            align = _Q_BLOCK
            n = jax.lax.axis_size(self.axis_name)
            me0 = jax.lax.axis_index(self.axis_name) == 0

            def shard_leaf(v):
                return (_shard_flat(v, self.axis_name, align)
                        if v.ndim else v)

        if not self._ef:
            if route is None:
                sharded = _reshard_state(state_full, self.axis_name)
            else:
                sharded = jax.tree.map(shard_leaf, state_full)
            return sharded if guard is None else \
                _GuardedState(inner=sharded, guard=guard)
        inner = jax.tree.map(shard_leaf, state_full.inner)
        residual = []
        for r in state_full.residual:
            pad = _qpad_len(r.shape[0], n) - r.shape[0]
            r = jnp.pad(r, (0, pad))
            residual.append(jnp.where(me0, r, jnp.zeros_like(r)))
        sharded = _EFShardState(inner=inner, residual=residual,
                                step=state_full.step)
        return sharded if guard is None else \
            _GuardedState(inner=sharded, guard=guard)


# -- FSDP / ZeRO-3: fully-sharded parameters (beyond the reference) ---------
#
# ZeRO-1 (above) shards the OPTIMIZER STATE; FSDP additionally keeps the
# PARAMETERS at rest as 1/n bucket shards. Per step: all-gather shards ->
# full params for compute, reduce-scatter grads -> shard-local inner
# update -> new shards. At-rest memory for params + Adam state drops to
# 1/n; the transient peak is full params + activations during the step
# (fusion-bucket granularity — XLA's scheduler overlaps the per-bucket
# allgathers with the first layers' compute the same way it overlaps the
# grad reduction with backprop). Wire cost per step: AG(params) +
# RS(grads) — the same bytes as ZeRO-1's RS+AG pair plus the param
# gather that replicated storage gets for free.

class FSDPOptimizer:
    """Fully-sharded (ZeRO-3-style) training helper over fused buckets::

        tx = hvd.FSDPOptimizer(optax.adamw(1e-3), axis_name=ax)
        # inside the jitted SPMD region (axis bound):
        shards = tx.shard_params(params)    # full -> 1/n bucket shards
        state  = tx.init(shards)            # inner state on shards (1/n)
        # each step:
        full   = tx.gather_params(shards)   # AG per bucket -> pytree
        loss, grads = jax.value_and_grad(loss_fn)(full, batch)
        shards, state = tx.update(grads, state, shards)  # RS + update

    Carry ``shards``/``state`` through shard_map with
    :meth:`shard_specs` / :meth:`state_specs` (leaves are P(axis)).
    Elementwise inner transforms only — same contract as
    :class:`ShardedOptimizer`."""

    def __init__(self, inner, axis_name: str = "hvd",
                 grad_op: C.ReduceOp = C.ReduceOp.AVERAGE,
                 fusion_threshold_bytes: Optional[int] = None,
                 route=None):
        if grad_op not in (C.ReduceOp.SUM, C.ReduceOp.AVERAGE):
            raise ValueError("FSDPOptimizer supports SUM/AVERAGE")
        self.inner = inner
        self.axis_name = axis_name
        self.grad_op = grad_op
        self.fusion_threshold_bytes = _resolve_fusion_threshold(
            fusion_threshold_bytes)
        # Route (docs/topology.md): params at rest shard over ALL plan
        # axes (fast-axis-major); the per-step param all-gather ascends
        # and the grad reduce-scatter descends the staged per-axis
        # wires. Pinned like the threshold — it decides the shard grid.
        # Explicit-only: the HVD_TPU_ROUTE default never reshapes a
        # sharded state layout (shard_specs are built outside traces).
        self.route = C.WirePlan.resolve(route)
        self._plan = None
        self._flat_lens = None
        self._sig = None

    def _live_route(self):
        return _sharded_route(self.route, self.axis_name)

    def _require_route_axes(self, route, what: str) -> None:
        if route is not None:
            for a in route.axis_names:
                _require_axis(a, what)
        else:
            _require_axis(self.axis_name, what)

    def bind(self, params_template):
        """Pin the bucket plan from a params pytree (real arrays or
        ShapeDtypeStructs). Called implicitly by shard_params; explicit
        bind() lets gather/update trace in a separate jit region.

        The instance is stateful: the first bind pins the tree
        structure, and a later bind with a STRUCTURALLY DIFFERENT
        template raises — silently replacing the plan would misalign
        any shards already produced under the old one. Use unbind() (or
        a fresh instance) to retarget deliberately."""
        sig = (str(jax.tree.structure(params_template)),
               tuple((tuple(x.shape), str(x.dtype))
                     for x in jax.tree.leaves(params_template)))
        if self._sig is not None and sig != self._sig:
            raise ValueError(
                "FSDPOptimizer is already bound to a different param "
                "tree (structure or leaf shapes changed); shards from "
                "the old plan would silently misalign. Use a fresh "
                "FSDPOptimizer per param tree, or call unbind() first")
        self._sig = sig
        self._plan = fusion_lib.plan_fusion(params_template,
                                            self.fusion_threshold_bytes)
        self._flat_lens = [b.total_elems for b in self._plan.buckets]
        return self

    def unbind(self):
        """Drop the bound plan so the instance can be re-bound to a new
        param tree (any shards/state from the old plan become invalid)."""
        self._plan = self._flat_lens = self._sig = None
        return self

    def _require_bound(self, what: str):
        if self._plan is None:
            raise ValueError(
                f"{what} needs the bucket plan — call shard_params "
                f"(or bind(params_template)) first")

    def _check_shards(self, shards, what: str):
        if len(shards) != len(self._flat_lens):
            raise ValueError(
                f"{what}: got {len(shards)} bucket shards but the bound "
                f"plan has {len(self._flat_lens)} buckets — these shards "
                f"come from a different plan/template")

    def shard_params(self, params):
        """Full params -> list of this rank's 1/n bucket shards (1/N
        over all plan axes under a route)."""
        route = self._live_route()
        self._require_route_axes(route, "FSDPOptimizer.shard_params")
        self.bind(params)
        flats = fusion_lib.fuse(params, self._plan)
        if route is not None:
            align = _route_align(NoneCompressor, route)
            return [_mesh_shard_flat(f, route, align) for f in flats]
        return [_shard_flat(f, self.axis_name) for f in flats]

    def gather_params(self, shards):
        """Bucket shards -> full params pytree (one all-gather per
        bucket; padding from the shard split sliced back off). Under a
        route the gather ascends the plan in reverse, each hop in its
        axis's wire format — a staged_int8 plan moves the slow-axis
        param bytes as block-scaled int8 (stateless, bounded like
        mesh_allreduce's ascent)."""
        self._require_bound("gather_params")
        self._check_shards(shards, "gather_params")
        route = self._live_route()
        self._require_route_axes(route, "FSDPOptimizer.gather_params")
        if route is not None:
            inv = route.reversed()
            flats = [C.mesh_allgather(s, inv)[:length]
                     for s, length in zip(shards, self._flat_lens)]
        else:
            flats = [C.allgather(s, self.axis_name)[:length]
                     for s, length in zip(shards, self._flat_lens)]
        return fusion_lib.unfuse(flats, self._plan)

    def init(self, shards):
        return self.inner.init(shards)

    def update(self, grads, state, shards, **extra):
        """RS(full grads) -> inner update on this rank's shards ->
        apply. Returns (new_shards, new_state). Under a route the RS
        descends the staged per-axis wires (docs/topology.md)."""
        self._require_bound("update")
        self._check_shards(shards, "update")
        route = self._live_route()
        self._require_route_axes(route, "FSDPOptimizer.update")
        g_flats = fusion_lib.fuse(grads, self._plan)

        if route is not None:
            n = _route_total(route)
            align = _route_align(NoneCompressor, route)

            def rs(f):
                padded, _ = fusion_lib.pad_to_multiple(f, n * align)
                return C.mesh_reducescatter(padded, self.grad_op, route)
        else:
            n = jax.lax.axis_size(self.axis_name)

            def rs(f):
                padded, _ = fusion_lib.pad_to_multiple(f, n)
                return C.reducescatter(padded, self.grad_op,
                                       self.axis_name)

        g_shards = [rs(f).astype(s.dtype)
                    for f, s in zip(g_flats, shards)]
        u_shards, new_state = self.inner.update(g_shards, state, shards,
                                                **extra)
        new_shards = [(s + u).astype(s.dtype)
                      for s, u in zip(shards, u_shards)]
        return new_shards, new_state

    def shard_specs(self, params_template):
        """P(axis) per bucket shard — for carrying shards through
        shard_map (P((fast, ..., slow)) over all plan axes under a
        route). Binds the plan from the template."""
        from jax.sharding import PartitionSpec as P

        self.bind(params_template)
        axes = (tuple(self.route.axis_names) if self.route is not None
                else self.axis_name)
        return [P(axes)] * len(self._flat_lens)

    def state_specs(self, params_template):
        """Specs for the inner state over bucket shards (vector leaves
        P(axis) — or the plan's axis tuple under a route; scalars
        replicated)."""
        self.bind(params_template)
        axes = (tuple(self.route.axis_names) if self.route is not None
                else self.axis_name)
        return _sharded_state_specs(self.inner, self._plan, axes)

    def gather_state(self, state):
        """Sharded state -> world-size-independent full state (inside
        the OLD world's SPMD region); pair with :meth:`reshard_state`
        (and gather_params/shard_params for the params themselves) to
        carry FSDP training across an elastic resize.

        Same caveat as ShardedOptimizer.gather_state: the layout is
        fusion-plan-dependent — pin ``fusion_threshold_bytes``
        explicitly across the resize so the new world re-buckets
        identically."""
        self._require_bound("gather_state")
        route = self._live_route()
        self._require_route_axes(route, "FSDPOptimizer.gather_state")
        if route is None:
            return _gather_sharded_state(self.inner, self._plan, state,
                                         self.axis_name)
        return _gather_sharded_state_routed(self.inner, self._plan,
                                            state, route)

    def reshard_state(self, state_full):
        """Full (gathered) state -> this world's 1/n shards (inside the
        NEW world's SPMD region, whatever its size or route)."""
        route = self._live_route()
        self._require_route_axes(route, "FSDPOptimizer.reshard_state")
        if route is None:
            return _reshard_state(state_full, self.axis_name)
        align = _route_align(NoneCompressor, route)
        return jax.tree.map(
            lambda v: (_mesh_shard_flat(v, route, align)
                       if v.ndim else v),
            state_full)


# -- ZeRO-2/3: gradient- and parameter-sharded training (docs/zero.md) -------
#
# ZeRO-1 (ShardedOptimizer, above) shards the OPTIMIZER STATE over the
# rank axis (or the WirePlan grid). ZeRO-2 additionally keeps the
# GRADIENT accumulator as 1/N shards: each microbatch's gradients are
# reduce-scattered straight into the owner's shard, so no full-size
# accumulated gradient ever materializes. ZeRO-3 additionally keeps the
# PARAMETERS at rest as 1/N bucket shards, all-gathered ON DEMAND per
# readiness-ordered bucket for the step's compute and freed after use
# (XLA liveness): the gather chain pins bucket order with the
# optimization-barrier pattern (_chain_issue_order, parallel/moe.py), so
# the async-collective scheduler may prefetch bucket k+1's params under
# bucket k's compute. Readiness order IS the gather schedule — forward
# (flatten) order for the param gathers, reverse for the gradient
# reduce-scatters. No chip has run a ZeRO step yet: whether the chain
# earns its place is for the first cell that does (ROADMAP.md queue 3).
#
# Wire model per effective step (docs/zero.md): stage 1/2 pay
# RS(grads) + AG(updates); stage 3 pays AG(params) + RS(grads) — the
# same ring bytes, with the update AG traded for the on-demand param
# gather. All hops ride the route's per-axis wires; int8_ef keeps its
# Σ-residual contract on the quantized descent (mesh_reducescatter).

def _zero_count_bytes(kind: str, nelems: int, itemsize: int, route,
                      axis_name: str, wire: Optional[str] = None) -> None:
    """Trace-time ring accounting of one sharded-collective descent or
    ascent into ``hvd_tpu_zero_gather_bytes_total``: ``(n-1)/n`` of the
    live buffer per device per axis, each hop priced at its wire format
    (``collectives.mesh_wire_cost``'s recipe). Axis sizes are trace-time
    constants, so the increments are static per compile. ``wire``
    overrides the flat-axis payload name (the quantized flat RS)."""
    if not _METRICS_ON:
        return
    length = float(nelems)
    if route is None:
        if not _axes_bound(axis_name):
            return
        n = jax.lax.axis_size(axis_name)
        w = wire or "none"
        _M_ZERO_GATHER.labels(kind=kind, wire=w, axis=axis_name).inc(
            (n - 1) / n * length * C._wire_elem_bytes(w, itemsize))
        return
    if not _axes_bound(*route.axis_names):
        return
    for p in route.phases:
        n = jax.lax.axis_size(p.axis)
        w = wire or p.wire
        _M_ZERO_GATHER.labels(kind=kind, wire=w, axis=p.axis).inc(
            (n - 1) / n * length * C._wire_elem_bytes(w, itemsize))
        length /= n


def _is_shard_grads(grads, like=None) -> bool:
    """True when ``grads`` is a list/tuple of 1-D bucket-shard arrays
    (the output of the ZeRO-2/3 shard accumulators) rather than a
    params-shaped pytree. ``like`` (params, or the stage-3 shard list)
    disambiguates the pathological case where the params tree is
    ITSELF a flat list of 1-D vectors: a tree with ``like``'s
    structure AND leaf shapes is a full-gradient tree, never shards —
    while stage-3 shard grads must match the shard list's shapes
    exactly."""
    if not isinstance(grads, (list, tuple)) or not grads:
        return False
    if not all(getattr(jnp.asarray(g), "ndim", None) == 1
               for g in grads):
        return False
    if like is None:
        return True
    g_shapes = [tuple(jnp.shape(g)) for g in grads]
    if isinstance(like, (list, tuple)) and like \
            and all(getattr(jnp.asarray(s), "ndim", None) == 1
                    for s in like):
        # Stage-3 form: ``like`` is the param-shard list — shard grads
        # mirror it one-to-one.
        return g_shapes == [tuple(jnp.shape(s)) for s in like]
    if jax.tree.structure(grads) != jax.tree.structure(like):
        return True
    return g_shapes != [tuple(jnp.shape(p))
                        for p in jax.tree.leaves(like)]


def _chain_issue_order(flats, fn: Callable) -> list:
    """Apply ``fn`` (the per-bucket collective) to each flat bucket,
    pinning the ISSUE ORDER with an ``optimization_barrier`` chain:
    bucket ``i+1``'s input is tied to bucket ``i``'s collective, so the
    scheduler cannot start them out of the order given. The collectives
    serialize against each other — they share one wire and would anyway
    — while each stays free to run beside the compute that produces or
    consumes LATER buckets. The barrier is a scheduling fence, not a
    math op: outputs equal inputs exactly."""
    outs = []
    token = None
    for f in flats:
        if token is not None:
            f, token = jax.lax.optimization_barrier((f, token))
        token = fn(f)
        outs.append(token)
    return outs


class ZeroOptimizer:
    """One surface over the ZeRO stages (docs/zero.md)::

        tx = hvd.DistributedOptimizer(optax.adamw(1e-3), zero_stage=3,
                                      axis_name=ax)           # == this
        tx = hvd.ZeroOptimizer(optax.adamw(1e-3), zero_stage=3,
                               axis_name=ax)

    Stage semantics (all inside the jitted SPMD region — the shard
    shapes come from the bound axes):

    * ``zero_stage=1`` — optimizer state sharded; full grads in,
      RS -> shard update -> AG(updates) out. Exactly
      :class:`ShardedOptimizer` (delegated; same state layout,
      checkpoint-compatible).
    * ``zero_stage=2`` — plus gradient sharding: :meth:`accumulate`
      carries a 1/N-shard fp32 accumulator (reduce-scatter per
      microbatch, exact native wires), and :meth:`update` accepts the
      resulting shard-gradient list directly (no RS inside). Full-grad
      ``update()`` calls keep stage-1 semantics, so the two stages are
      state-compatible.
    * ``zero_stage=3`` — plus parameter sharding: params live as 1/N
      fast-major bucket shards (:meth:`shard_params`), are gathered on
      demand (:meth:`gather_params` — per-bucket all-gathers chained in
      readiness order so bucket k+1's gather can fly under bucket k's
      compute), and :meth:`update` returns NEW SHARDS (the update never
      all-gathers; the next step's param gather is the inverse hop).

    Composition contracts:

    * ``route=`` (explicit-only, like every sharded surface): the shard
      grid spans ALL plan axes fast-major and every RS/AG hop rides the
      plan's per-axis wires (int8 on the slow hop under
      ``staged_int8``).
    * ``compression="int8_ef"``: the quantized gradient descent keeps
      the Σ-over-ranks residual contract (``mesh_reducescatter``); the
      residual advances once per quantized descent — under the stage-2/3
      shard accumulator the per-microbatch RS is EXACT (native wires),
      so the EF residual advances only on full-grad ``update()`` calls
      (accum_steps=1) and never drifts silently.
    * ``nonfinite_policy``: one globally-agreed flag over the plan's
      axes; a skipped step leaves shards, inner state, EF residual and
      step counter untouched (stage 3 adds zeros to the param shards).
    * ``accum_steps``/``remat_policy``: :meth:`accumulate` gathers
      params ONCE per effective step (stage 3) and accumulates
      shard-sized gradients (stages 2/3) — the gather count is
      trace-verified (tests/test_zero.py).

    Elementwise inner transforms only — the ShardedOptimizer contract.
    """

    def __init__(self, inner, zero_stage: int = 2,
                 axis_name: str = "hvd",
                 grad_op: C.ReduceOp = C.ReduceOp.AVERAGE,
                 fusion_threshold_bytes: Optional[int] = None,
                 compression=None,
                 nonfinite_policy: Optional[str] = None,
                 route=None, accum_steps: Optional[int] = None,
                 remat_policy: Optional[str] = None,
                 parallel=None):
        stage = int(zero_stage)
        if stage not in (1, 2, 3):
            raise ValueError(
                f"zero_stage must be 1, 2 or 3, got {zero_stage!r} "
                "(0/off = the replicated DistributedOptimizer)")
        if grad_op not in (C.ReduceOp.SUM, C.ReduceOp.AVERAGE):
            raise ValueError("ZeroOptimizer supports SUM/AVERAGE")
        # Hybrid parallelism (docs/pipeline.md): the spec pins the shard
        # grid + reduction to the dp axis ONLY, so ZeRO shards live PER
        # PIPELINE STAGE (each pp/tp coordinate forms its own dp shard
        # group) and the guard agrees over dp only; tp slice grads are
        # pmean-combined before every reduce-scatter.
        self._tp_axis = None
        pspec = _resolve_parallel(parallel)
        if pspec is not None:
            if not pspec.dp_axes:
                raise ValueError(
                    f"parallel spec {pspec.describe()!r} has no dp axis "
                    "— ZeRO shards gradient/optimizer/param state over "
                    "the data-parallel replicas; a pure pp x tp spec "
                    "has nothing to shard over")
            if route is not None:
                rt = C.WirePlan.resolve(route)
                if rt is not None and set(rt.axis_names) != set(
                        pspec.dp_axes):
                    raise ValueError(
                        f"route axes {rt.axis_names} must be exactly "
                        f"the parallel spec's dp axes {pspec.dp_axes}")
            else:
                # Shard and reduce through the mesh router over the dp
                # axis (mirroring DistributedOptimizer's parallel=
                # default): the router stamps the per-axis byte
                # counters (hvd_tpu_zero_gather_bytes_total /
                # hvd_tpu_allreduce_bytes_total axis="dp") that prove
                # the hybrid schedule's wire mix.
                route = pspec.grad_route()
            axis_name = pspec.dp_axes[0]
            self._tp_axis = tuple(
                a for a in (pspec.tp_axis, pspec.sp_axis)
                if a is not None) or None
        self.zero_stage = stage
        self.inner = inner
        self.axis_name = axis_name
        self.grad_op = grad_op
        self.fusion_threshold_bytes = _resolve_fusion_threshold(
            fusion_threshold_bytes)
        self.compression = _resolve_compression(compression)
        _check_reduce_safe(self.compression)
        self._ef = getattr(self.compression, "error_feedback", False)
        self.nonfinite_policy = integrity_lib.resolve_nonfinite_policy(
            nonfinite_policy)
        # Explicit-only (no HVD_TPU_ROUTE default): the route decides
        # the shard grid and the PartitionSpecs built outside traces.
        self.route = C.WirePlan.resolve(route)
        self.accum_steps = _resolve_accum_steps(accum_steps)
        self.remat_policy = resolve_remat_policy(remat_policy)[0]
        # Stages 1/2 ride the ZeRO-1 substrate unchanged: same state
        # layout, EF/guard wrapping, gather/reshard — checkpoint- and
        # elastic-compatible by construction.
        self._z1 = ShardedOptimizer(
            inner, axis_name=axis_name, grad_op=grad_op,
            fusion_threshold_bytes=self.fusion_threshold_bytes,
            compression=self.compression,
            nonfinite_policy=self.nonfinite_policy, route=self.route,
            accum_steps=self.accum_steps,
            remat_policy=self.remat_policy)
        # Stage-3 bound plan (the FSDPOptimizer binding contract).
        self._plan = None
        self._flat_lens = None
        self._sig = None

    # -- shared plumbing -----------------------------------------------------

    def _live_route(self):
        return _sharded_route(self.route, self.axis_name)

    def _maybe_combine_tp(self, grads):
        """Reassemble tensor/sequence-parallel slice gradients (pmean
        over tp, then sp) before a full-gradient tree enters any
        reduce-scatter — no-op without a parallel spec, or when an axis
        is unbound in this trace (the model then ran unsharded over it
        and grads are exact)."""
        if self._tp_axis is None:
            return grads
        return _combine_tp(grads, self._tp_axis)

    def _require_route_axes(self, route, what: str) -> None:
        if route is not None:
            for a in route.axis_names:
                _require_axis(a, what)
        else:
            _require_axis(self.axis_name, what)

    def _axes(self, route):
        return tuple(route.axis_names) if route is not None \
            else self.axis_name

    def _n(self, route) -> int:
        return (_route_total(route) if route is not None
                else jax.lax.axis_size(self.axis_name))

    def _plan_z12(self, params):
        """Stages 1/2 plan the buckets from the live params each call
        (the sharded_update contract — state carries one shard per
        bucket of THIS plan)."""
        return fusion_lib.plan_fusion(params, self.fusion_threshold_bytes)

    # -- stage-3 plan binding (the FSDPOptimizer contract) -------------------

    def bind(self, params_template):
        """Pin the stage-3 bucket plan from a params pytree (arrays or
        ShapeDtypeStructs). A later bind with a structurally different
        template raises — shards from the old plan would silently
        misalign; unbind() (or a fresh instance) retargets."""
        sig = (str(jax.tree.structure(params_template)),
               tuple((tuple(x.shape), str(x.dtype))
                     for x in jax.tree.leaves(params_template)))
        if self._sig is not None and sig != self._sig:
            raise ValueError(
                "ZeroOptimizer is already bound to a different param "
                "tree (structure or leaf shapes changed); use a fresh "
                "instance per param tree, or call unbind() first")
        self._sig = sig
        self._plan = fusion_lib.plan_fusion(
            params_template, self.fusion_threshold_bytes)
        self._flat_lens = [b.total_elems for b in self._plan.buckets]
        return self

    def unbind(self):
        self._plan = self._flat_lens = self._sig = None
        return self

    def _require_bound(self, what: str):
        if self._plan is None:
            raise ValueError(
                f"{what} needs the stage-3 bucket plan — call "
                f"shard_params (or bind(params_template)) first")

    def _check_shards(self, shards, what: str):
        if len(shards) != len(self._flat_lens):
            raise ValueError(
                f"{what}: got {len(shards)} bucket shards but the bound "
                f"plan has {len(self._flat_lens)} buckets — these "
                f"shards come from a different plan/template")

    # -- stage-3 parameter residency -----------------------------------------

    def shard_params(self, params):
        """Full params -> this rank's 1/N bucket shards (stage 3; the
        at-rest layout — fast-axis-major over all plan axes under a
        route). Publishes the per-rank resident-byte gauge."""
        if self.zero_stage < 3:
            raise ValueError(
                "shard_params is the stage-3 surface (params stay "
                f"replicated under zero_stage={self.zero_stage})")
        route = self._live_route()
        self._require_route_axes(route, "ZeroOptimizer.shard_params")
        self.bind(params)
        flats = fusion_lib.fuse(params, self._plan)
        align = _route_align(self.compression, route)
        if route is not None:
            shards = [_mesh_shard_flat(f, route, align) for f in flats]
        else:
            shards = [_shard_flat(f, self.axis_name, align)
                      for f in flats]
        if _METRICS_ON:
            resident = sum(int(s.shape[0]) * jnp.dtype(s.dtype).itemsize
                           for s in shards)
            _M_ZERO_RESIDENT.labels(stage="3").set(resident)
        return shards

    def gather_params(self, shards):
        """Bucket shards -> full params pytree: ONE all-gather per
        readiness-ordered bucket, chained through an
        ``optimization_barrier`` so the issue order is pinned (bucket
        k+1's gather may then fly under bucket k's compute — the
        prefetch schedule; inert on CPU, numerics unchanged). Under a
        route the gather ascends the plan in reverse, each hop in its
        axis's wire format, and the moved bytes land in
        ``hvd_tpu_zero_gather_bytes_total{kind="param"}``."""
        self._require_bound("gather_params")
        self._check_shards(shards, "gather_params")
        route = self._live_route()
        self._require_route_axes(route, "ZeroOptimizer.gather_params")
        if route is not None:
            inv = route.reversed()

            def ag(s):
                return C.mesh_allgather(s, inv)
        else:
            def ag(s):
                return C.allgather(s, self.axis_name)

        outs = _chain_issue_order(shards, ag)
        flats = [o[:length]
                 for o, length in zip(outs, self._flat_lens)]
        for b in self._plan.buckets:
            _zero_count_bytes("param", b.total_elems,
                              jnp.dtype(b.dtype).itemsize, route,
                              self.axis_name)
        return fusion_lib.unfuse(flats, self._plan)

    def shard_specs(self, params_template):
        """P(axes) per stage-3 bucket shard, for carrying the shards
        through shard_map. Binds the plan."""
        from jax.sharding import PartitionSpec as P

        self.bind(params_template)
        axes = (tuple(self.route.axis_names) if self.route is not None
                else self.axis_name)
        return [P(axes)] * len(self._flat_lens)

    # -- state ---------------------------------------------------------------

    def init(self, params_or_shards):
        """Stage 1/2: ``init(params)`` (sharded_init). Stage 3:
        ``init(shards)`` — inner state over the param shards, plus the
        EF residual / guard wrappers when configured."""
        if self.zero_stage < 3:
            return self._z1.init(params_or_shards)
        shards = params_or_shards
        self._require_bound("ZeroOptimizer.init")
        self._check_shards(shards, "init")
        inner = self.inner.init(list(shards))
        if self._ef:
            n = self._n(self._live_route())
            residual = [jnp.zeros((_qpad_len(b.total_elems, n),),
                                  jnp.float32)
                        for b in self._plan.buckets]
            inner = _EFShardState(inner=inner, residual=residual,
                                  step=jnp.zeros((), jnp.int32))
        if self.nonfinite_policy is None:
            return inner
        return _GuardedState(
            inner=inner,
            guard=integrity_lib.init_guard_state(self.nonfinite_policy))

    def state_specs(self, params_template):
        if self.zero_stage < 3:
            return self._z1.state_specs(params_template)
        from jax.sharding import PartitionSpec as P

        self.bind(params_template)
        axes = (tuple(self.route.axis_names) if self.route is not None
                else self.axis_name)
        inner_specs = _sharded_state_specs(self.inner, self._plan, axes)
        if self._ef:
            inner_specs = _EFShardState(
                inner=inner_specs,
                residual=[P(axes)] * len(self._plan.buckets),
                step=P())
        if self.nonfinite_policy is None:
            return inner_specs
        return _GuardedState(inner=inner_specs,
                             guard=integrity_lib.guard_state_specs())

    # -- the exact (native-wire) shard reduce-scatter ------------------------

    def _rs_exact(self, f, route, n, align):
        padded, _ = fusion_lib.pad_to_multiple(f, n * align)
        if route is not None:
            return C.mesh_reducescatter(padded, self.grad_op,
                                        route.with_wires("none"))
        return C.reducescatter(padded, self.grad_op, self.axis_name)

    def _rs_tree_exact(self, grads, params_like, plan, route, n, align):
        """Full gradient pytree -> fp32 bucket shards via the EXACT
        reduce-scatter descent (native wires on every hop — the shard
        accumulator must sum losslessly across microbatches), chained
        in REVERSE (backward-readiness) order."""
        g_flats = fusion_lib.fuse(
            jax.tree.map(lambda g, p: g.astype(p.dtype), grads,
                         params_like), plan)
        outs = [s.astype(jnp.float32) for s in _chain_issue_order(
            g_flats[::-1],
            lambda f: self._rs_exact(f, route, n, align))][::-1]
        for b in plan.buckets:
            _zero_count_bytes("grad", b.total_elems,
                              jnp.dtype(b.dtype).itemsize, route,
                              self.axis_name, wire="none")
        return outs

    # -- update --------------------------------------------------------------

    def update(self, grads, state, params=None, **extra):
        """Stage 1/2 with a params-shaped ``grads``: stage-1 semantics
        (sharded_update — RS inside, EF descent quantized, full updates
        out). Stage 1/2 with a SHARD-GRADIENT list (from
        :meth:`accumulate` / :meth:`reduce_grads`): shard-local inner
        update + AG(updates) — no second reduction. Stage 3:
        ``update(grads, state, shards) -> (new_shards, new_state)``."""
        if self.zero_stage < 3:
            if _is_shard_grads(grads, like=params):
                return self._update_from_shards_z12(grads, state, params,
                                                    **extra)
            return self._z1.update(self._maybe_combine_tp(grads), state,
                                   params, **extra)
        if not _is_shard_grads(grads, like=list(params)
                               if params is not None else None):
            grads = self._maybe_combine_tp(grads)
        return self._update_z3(grads, state, params, **extra)

    def reduce_grads(self, grads, params):
        """Full gradient pytree -> fp32 bucket-shard list via the exact
        reduce-scatter (the ZeRO-2 descent without accumulation); feed
        to :meth:`update`. One RS round, no full-gradient copy beyond
        backprop's own transient output."""
        route = self._live_route()
        self._require_route_axes(route, "ZeroOptimizer.reduce_grads")
        n = self._n(route)
        align = _route_align(self.compression, route)
        plan = (self._plan if self.zero_stage >= 3
                else self._plan_z12(params))
        if self.zero_stage >= 3:
            self._require_bound("reduce_grads")
        return self._rs_tree_exact(self._maybe_combine_tp(grads),
                                   params, plan, route, n, align)

    def _update_from_shards_z12(self, g_shards, state, params, **extra):
        if params is None:
            raise ValueError("ZeroOptimizer.update requires params")
        route = self._live_route()
        self._require_route_axes(route, "ZeroOptimizer.update")
        axes = self._axes(route)
        guarded = isinstance(state, _GuardedState)
        if (self.nonfinite_policy is not None) != guarded:
            raise ValueError(
                "ZeroOptimizer.update nonfinite_policy must match the "
                "init that built this state")
        inner_state = state.inner if guarded else state
        if self._ef != isinstance(inner_state, _EFShardState):
            raise ValueError(
                "ZeroOptimizer.update compression= must match the init "
                "that built this state (EF state/shard alignment)")
        plan = self._plan_z12(params)
        if len(g_shards) != len(plan.buckets):
            raise ValueError(
                f"got {len(g_shards)} gradient shards for a plan of "
                f"{len(plan.buckets)} buckets")
        align = _route_align(self.compression, route)
        p_flats = fusion_lib.fuse(params, plan)
        if route is not None:
            p_shards = [_mesh_shard_flat(f, route, align)
                        for f in p_flats]
            u_gather = route.reversed().with_wires("none")
        else:
            p_shards = [_shard_flat(f, self.axis_name, align)
                        for f in p_flats]
            u_gather = None

        def core(gs, st):
            ist = st.inner if self._ef else st
            gs = [g.astype(p.dtype) for g, p in zip(gs, p_shards)]
            u_shards, new_inner = self.inner.update(gs, ist, p_shards,
                                                    **extra)
            u_shards = [u.astype(jnp.float32) for u in u_shards]
            if self._ef:
                # No quantized hop ran: residual and step carry over
                # untouched (the EF telescope only advances on a lossy
                # descent).
                new_st = _EFShardState(inner=new_inner,
                                       residual=st.residual,
                                       step=st.step)
            else:
                new_st = new_inner
            return u_shards, new_st

        if not guarded:
            u_shards, new_inner = core(g_shards, inner_state)
            new_guard = None
        else:
            u_shards, new_inner, new_guard = integrity_lib.guarded_apply(
                self.nonfinite_policy, core, list(g_shards), inner_state,
                state.guard, axes)
        # Update all-gather OUTSIDE the guard: a skipped step gathers
        # zeros (harmless), and the guard's skip branch stays
        # structure-matched to the shard gradients.
        u_flats = [(C.mesh_allgather(u, u_gather)
                    if u_gather is not None
                    else C.allgather(u, self.axis_name))[:f.shape[0]]
                   .astype(f.dtype)
                   for u, f in zip(u_shards, p_flats)]
        for b in plan.buckets:
            _zero_count_bytes("update", b.total_elems,
                              jnp.dtype(b.dtype).itemsize, route,
                              self.axis_name, wire="none")
        updates = fusion_lib.unfuse(u_flats, plan)
        if new_guard is None:
            return updates, new_inner
        return updates, _GuardedState(new_inner, new_guard)

    def _update_z3(self, grads, state, shards, **extra):
        if shards is None:
            raise ValueError(
                "stage-3 update requires the param shards as the third "
                "argument: update(grads, state, shards)")
        self._require_bound("update")
        self._check_shards(shards, "update")
        route = self._live_route()
        self._require_route_axes(route, "ZeroOptimizer.update")
        axes = self._axes(route)
        guarded = isinstance(state, _GuardedState)
        if (self.nonfinite_policy is not None) != guarded:
            raise ValueError(
                "ZeroOptimizer.update nonfinite_policy must match the "
                "init that built this state")
        inner_state = state.inner if guarded else state
        if self._ef != isinstance(inner_state, _EFShardState):
            raise ValueError(
                "ZeroOptimizer.update compression= must match the init "
                "that built this state (EF state/shard alignment)")
        n = self._n(route)
        align = _route_align(self.compression, route)
        plan = self._plan
        from_shards = _is_shard_grads(grads, like=list(shards))

        def core(g, st):
            """-> (u_shards ≅ param shards, new inner state). The whole
            descent — EF residual advance included — sits inside the
            guard's cond."""
            if from_shards:
                g_shards = [gg.astype(s.dtype)
                            for gg, s in zip(g, shards)]
                new_res, new_step = ((st.residual, st.step)
                                     if self._ef else (None, None))
            elif not self._ef:
                g_flats = fusion_lib.fuse(g, plan)
                g_shards = []
                for f, s in zip(g_flats, shards):
                    padded, _ = fusion_lib.pad_to_multiple(
                        f.astype(s.dtype), n * align)
                    if route is not None:
                        # The descent rides the PLAN's wires (int8 on
                        # the slow hop under staged_int8 — stateless,
                        # bounded, the FSDP contract).
                        g_shards.append(C.mesh_reducescatter(
                            padded, self.grad_op, route))
                    else:
                        g_shards.append(C.reducescatter(
                            padded, self.grad_op, self.axis_name))
                for b in plan.buckets:
                    _zero_count_bytes("grad", b.total_elems,
                                      jnp.dtype(b.dtype).itemsize,
                                      route, self.axis_name)
                new_res = new_step = None
            else:
                # Quantized descent with error feedback: corrected
                # gradient g + residual rides the int8 wires; the local
                # rounding error becomes the next residual
                # (Σ-over-ranks contract, mesh_reducescatter).
                g_flats = fusion_lib.fuse(g, plan)
                g_shards, new_res = [], []
                for i, (f, res) in enumerate(zip(g_flats, st.residual)):
                    pad = res.shape[0] - f.shape[0]
                    corrected = jnp.pad(f.astype(jnp.float32),
                                        (0, pad)) + res
                    if route is not None:
                        shard, r = C.mesh_reducescatter(
                            corrected, self.grad_op, route,
                            key=_ef_key(st.step, i),
                            return_residual=True)
                    else:
                        shard, r = C.quantized_reducescatter(
                            corrected, self.grad_op, self.axis_name,
                            key=_ef_key(st.step, i),
                            return_residual=True)
                    g_shards.append(shard.astype(shards[i].dtype))
                    new_res.append(r)
                for b in plan.buckets:
                    _zero_count_bytes("grad", b.total_elems,
                                      jnp.dtype(b.dtype).itemsize,
                                      route, self.axis_name,
                                      wire=None if route is not None
                                      else "int8")
                new_step = st.step + 1
            ist = st.inner if self._ef else st
            u_shards, new_inner = self.inner.update(g_shards, ist,
                                                    list(shards),
                                                    **extra)
            u_shards = [u.astype(s.dtype)
                        for u, s in zip(u_shards, shards)]
            if self._ef:
                new_st = _EFShardState(inner=new_inner,
                                       residual=new_res, step=new_step)
            else:
                new_st = new_inner
            return u_shards, new_st

        if not guarded:
            u_shards, new_inner = core(grads, inner_state)
            new_guard = None
        else:
            u_shards, new_inner, new_guard = integrity_lib.guarded_apply(
                self.nonfinite_policy, core,
                list(grads) if from_shards else grads, inner_state,
                state.guard, axes, skip_like=list(shards))
        new_shards = [(s + u).astype(s.dtype)
                      for s, u in zip(shards, u_shards)]
        if new_guard is None:
            return new_shards, new_inner
        return new_shards, _GuardedState(new_inner, new_guard)

    # -- scan-based shard accumulation ---------------------------------------

    def accumulate(self, loss_fn: Callable, has_aux: bool = False):
        """The microbatched ``value_and_grad`` for the pinned
        ``accum_steps``/``remat_policy``. Stage 1 delegates to the
        full-accumulator scan (:func:`accumulate_gradients`). Stages
        2/3 return ``fn(params_or_shards, *batch) -> (value,
        shard_grads)``: the carried accumulator is the 1/N gradient
        SHARD list — each microbatch's full gradients exist only
        transiently inside its own backward before the exact
        reduce-scatter folds them into the owner's shard. Stage 3
        gathers the params ONCE per effective step, outside the scan
        (trace-count-verified, tests/test_zero.py), so k microbatches
        share one chained param gather."""
        if self.zero_stage == 1:
            return self._z1.accumulate(loss_fn, has_aux=has_aux)
        k = self.accum_steps
        _, wrap, jax_policy = resolve_remat_policy(self.remat_policy)
        inner_loss = jax.checkpoint(loss_fn, policy=jax_policy) \
            if wrap else loss_fn
        vgrad = jax.value_and_grad(inner_loss, has_aux=has_aux)
        stage3 = self.zero_stage >= 3

        def fn(params_or_shards, *batch):
            route = self._live_route()
            n = self._n(route)
            align = _route_align(self.compression, route)
            if stage3:
                self._require_bound("ZeroOptimizer.accumulate")
                plan = self._plan
                full = self.gather_params(params_or_shards)
            else:
                full = params_or_shards
                plan = self._plan_z12(full)

            def rs(g):
                return self._rs_tree_exact(self._maybe_combine_tp(g),
                                           full, plan, route, n, align)

            if k == 1:
                out, g = vgrad(full, *batch)
                return out, rs(g)

            mbs = _split_microbatches(batch, k)
            mb0 = jax.tree.map(lambda x: x[0], mbs)
            shapes = jax.eval_shape(vgrad, full, *mb0)
            out_s, _g_s = shapes
            v_s, aux_s = out_s if has_aux else (out_s, None)

            def zeros_acc(t):
                return jax.tree.map(
                    lambda s: jnp.zeros(
                        s.shape, jnp.float32
                        if jnp.issubdtype(s.dtype, jnp.floating)
                        else s.dtype), t)

            def acc_add(acc, new):
                return jax.tree.map(
                    lambda a, x: a + x.astype(jnp.float32)
                    if jnp.issubdtype(jnp.asarray(a).dtype,
                                      jnp.floating)
                    else x, acc, new)

            def chunk_len(total_elems: int) -> int:
                grid = n * align
                return (-(-total_elems // grid) * grid) // n

            g_acc0 = [jnp.zeros((chunk_len(b.total_elems),),
                                jnp.float32) for b in plan.buckets]
            carry0 = (g_acc0, jnp.zeros((), jnp.float32),
                      zeros_acc(aux_s))

            def body(carry, mb):
                g_acc, v_acc, aux_acc = carry
                out, g = vgrad(full, *mb)
                v, aux = out if has_aux else (out, None)
                g_sh = rs(g)
                g_acc = [a + s for a, s in zip(g_acc, g_sh)]
                return (g_acc, v_acc + v.astype(jnp.float32),
                        acc_add(aux_acc, aux)), None

            (g_acc, v_acc, aux_acc), _ = jax.lax.scan(body, carry0,
                                                      mbs)
            g_shards = [a / k for a in g_acc]
            value = (v_acc / k).astype(v_s.dtype)
            if has_aux:
                aux = jax.tree.map(
                    lambda a, s: (a / k).astype(s.dtype)
                    if jnp.issubdtype(jnp.asarray(a).dtype,
                                      jnp.floating)
                    else a, aux_acc, aux_s)
                return (value, aux), g_shards
            return value, g_shards

        return fn

    # -- elastic resize ------------------------------------------------------

    def gather_state(self, state, params=None):
        """Sharded state -> world-size-independent full state (inside
        the OLD world's SPMD region). Stage 3 needs no ``params`` (the
        bound plan carries the bucket layout); the param SHARDS
        themselves travel via :meth:`gather_params` /
        :meth:`shard_params`. EF residuals carry as their psum (the
        world-size-independent pending correction; the new world's
        mesh-rank 0 receives it)."""
        if self.zero_stage < 3:
            return self._z1.gather_state(state, params)
        self._require_bound("gather_state")
        route = self._live_route()
        self._require_route_axes(route, "ZeroOptimizer.gather_state")
        guard = state.guard if isinstance(state, _GuardedState) else None
        if guard is not None:
            state = state.inner
        inner = state.inner if self._ef else state
        if route is not None:
            inner_full = _gather_sharded_state_routed(
                self.inner, self._plan, inner, route)
        else:
            inner_full = _gather_sharded_state(
                self.inner, self._plan, inner, self.axis_name)
        if self._ef:
            axes = self._axes(route)
            residual_full = [
                jax.lax.psum(r, axes)[:b.total_elems]
                for r, b in zip(state.residual, self._plan.buckets)]
            full = _EFShardState(inner=inner_full,
                                 residual=residual_full,
                                 step=state.step)
        else:
            full = inner_full
        return full if guard is None else _GuardedState(inner=full,
                                                        guard=guard)

    def reshard_state(self, state_full):
        """Full (gathered) state -> this world's shards (inside the NEW
        world's SPMD region, whatever its size or route). This is the
        gather-then-reshard leg of the elastic journey; when only the
        SHARD GRID changed (an elastic respec — docs/elastic.md
        "hybrid worlds") ``checkpoint.restore_sharded`` remaps the
        saved pieces directly instead, with no gather at all."""
        if self.zero_stage < 3:
            return self._z1.reshard_state(state_full)
        self._require_bound("reshard_state")
        route = self._live_route()
        self._require_route_axes(route, "ZeroOptimizer.reshard_state")
        guard = state_full.guard \
            if isinstance(state_full, _GuardedState) else None
        if guard is not None:
            state_full = state_full.inner
        align = _route_align(self.compression, route)
        n = self._n(route)
        if route is not None:
            me0 = jnp.asarray(True)
            for a in route.axis_names:
                me0 = jnp.logical_and(me0, jax.lax.axis_index(a) == 0)

            def shard_leaf(v):
                return _mesh_shard_flat(v, route, align) if v.ndim \
                    else v
        else:
            me0 = jax.lax.axis_index(self.axis_name) == 0

            def shard_leaf(v):
                return (_shard_flat(v, self.axis_name, align)
                        if v.ndim else v)

        if not self._ef:
            sharded = jax.tree.map(shard_leaf, state_full)
            return sharded if guard is None else \
                _GuardedState(inner=sharded, guard=guard)
        inner = jax.tree.map(shard_leaf, state_full.inner)
        residual = []
        for r in state_full.residual:
            pad = _qpad_len(r.shape[0], n) - r.shape[0]
            r = jnp.pad(r, (0, pad))
            residual.append(jnp.where(me0, r, jnp.zeros_like(r)))
        sharded = _EFShardState(inner=inner, residual=residual,
                                step=state_full.step)
        return sharded if guard is None else \
            _GuardedState(inner=sharded, guard=guard)
