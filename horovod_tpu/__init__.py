"""horovod_tpu — a TPU-native distributed training framework.

A ground-up JAX/XLA/Pallas re-design with the capabilities of the reference
Horovod (data-parallel collectives + fusion + Adasum + elastic + launcher +
timeline), built for TPU hardware: SPMD over ``jax.sharding.Mesh``, XLA
collectives over ICI/DCN, compiled-step fusion instead of a background
thread, and sequence/expert parallel building blocks over the same
primitive set.

Top-level API mirrors the reference's ``hvd.*`` surface
(reference: horovod/tensorflow/__init__.py, horovod/torch/__init__.py,
horovod/common/basics.py) with JAX-idiomatic semantics documented per
function.

Quick start (single-controller SPMD, the idiomatic TPU path)::

    import horovod_tpu as hvd
    hvd.init()                     # or init(compression="int8_ef") to put
                                   # int8 gradients on every reduce hop
                                   # (HVD_TPU_COMPRESSION; docs/compression.md)
    tx = hvd.DistributedOptimizer(optax.adam(1e-3), axis_name=hvd.rank_axis())

    @hvd.spmd_step                       # shard_map over the rank mesh
    def train_step(params, opt_state, batch):
        ...

Eager collectives operate on rank-major distributed tensors
(``hvd.scatter`` / ``hvd.gather``) — see horovod_tpu/ops/eager.py.
"""

from __future__ import annotations

import os
from typing import Optional

import jax

from .common import basics as _basics
from .common.basics import (ccl_built, cuda_built, ddl_built, gloo_built,
                            gloo_enabled, init, is_initialized, mpi_built,
                            mpi_enabled, mpi_threads_supported, nccl_built,
                            rocm_built, shutdown, tpu_available, xla_built)
from .common.exceptions import (CheckpointCorruptError, DivergenceError,
                                HorovodInternalError, HostsUpdatedInterrupt,
                                MismatchError, NonFiniteError,
                                NotInitializedError, StallError,
                                StallTimeoutError,
                                TensorShapeMismatchError)
from .ops import collectives as collective_ops
from .ops.collectives import AxisPhase, WirePlan
from .ops.collectives import (Adasum, Average, Max, Min, Product, ReduceOp,
                              Sum)
from .ops.compression import Compression
from .optim import (AutotunedStepper, DistributedGradFn,
                    DistributedOptimizer, FSDPOptimizer, ShardedOptimizer,
                    StepTimer, ZeroOptimizer, accumulate_gradients,
                    auto_shard_threshold, broadcast_parameters,
                    observe_ef_residual, resolve_remat_policy,
                    sharded_init, sharded_update, should_shard_update)
from .common import integrity
from .common import metrics as _metrics_lib
from .common.faults import recovery_stats
from .common.integrity import (DivergenceDetector, current_loss_scale,
                               observe_guard)
from .data import (BackgroundPrefetcher, DeviceInfeed, infeed_pipeline,
                   prefetch_to_device, shard_batch)
from .functions import allgather_object, broadcast_object, broadcast_variables
from .parallel.pipeline import (pipeline_accumulate_gradients,
                                pipeline_apply, pipeline_train_step_1f1b,
                                select_last_stage)
from .parallel.respec import RespecDecision, solve_respec
from .parallel.spec import ParallelSpec
from .parallel.tensor_parallel import (column_parallel,
                                       combine_slice_grads, row_parallel,
                                       shard_column, shard_head_rows,
                                       shard_heads, shard_row,
                                       tp_attention_qkv, tp_mlp)
from .process_set import ProcessSet

__version__ = "0.1.0"

_ctx = _basics.context


def __getattr__(name):
    # Lazy submodules with heavy deps (orbax, TF) — imported on first use.
    if name == "run":
        # Reference horovod/__init__.py: `from horovod.runner import run`
        # — lazily here (runner pulls cloudpickle).
        from .runner import run as _run

        globals()["run"] = _run
        return _run
    if name in ("checkpoint", "callbacks", "elastic", "executor",
                "tensorflow", "torch", "mxnet", "store", "estimator",
                "spark", "serve"):
        import importlib

        mod = importlib.import_module(f".{name}", __name__)
        globals()[name] = mod
        return mod
    raise AttributeError(f"module 'horovod_tpu' has no attribute {name!r}")


# -- basics (reference common/basics.py surface) ---------------------------

def rank() -> int:
    return _ctx().rank()


def size() -> int:
    return _ctx().size()


def local_rank() -> int:
    return _ctx().local_rank()


def local_size() -> int:
    return _ctx().local_size()


def cross_rank() -> int:
    return _ctx().cross_rank()


def cross_size() -> int:
    return _ctx().cross_size()


def is_homogeneous() -> bool:
    return _ctx().is_homogeneous()


def mesh():
    """The global 1-D rank mesh (jax.sharding.Mesh)."""
    return _ctx().mesh


def hierarchical_mesh():
    """The 2-D (cross, local) mesh, if multi-host; else None."""
    return _ctx().hier_mesh


def mesh_axes():
    """Routing-axis factorization of the topology (fast axis first) —
    pod metadata or the HVD_TPU_MESH_SHAPE / init(mesh_shape=) override;
    the per-axis model the collective router keys on
    (docs/topology.md). None when discovery failed."""
    return _ctx().mesh_axes


def route_mesh():
    """The N-D jax Mesh matching :func:`mesh_axes` when the
    factorization is multi-axis (shard over it to use route= plans);
    else None."""
    return _ctx().route_mesh


def parallel_spec():
    """The resolved hybrid :class:`ParallelSpec` from
    ``HVD_TPU_PARALLEL`` / ``init(parallel=)`` (docs/pipeline.md) —
    pass it EXPLICITLY to ``DistributedOptimizer(parallel=...)``; else
    None."""
    return _ctx().parallel_spec


def parallel_mesh():
    """The role-named (dp/pp/tp/ep) jax Mesh matching
    :func:`parallel_spec` — shard_map your hybrid step over it; else
    None."""
    return _ctx().parallel_mesh


def rank_axis() -> str:
    return _ctx().config.rank_axis


def add_process_set(process_set) -> ProcessSet:
    """Register a ProcessSet (or rank list) and build its sub-mesh
    engine. See process_set.py."""
    return _ctx().add_process_set(process_set)


def remove_process_set(process_set) -> None:
    _ctx().remove_process_set(process_set)


# -- eager collectives (rank-major distributed tensors) --------------------

def _engine(process_set=None):
    """Route to the world engine or a registered process set's sub-mesh
    engine; non-member processes fail loudly (the set's XLA program
    spans member devices only — see process_set.py)."""
    if process_set is None:
        return _ctx().engine
    if not process_set.included():
        raise ValueError(
            f"this process drives none of {process_set!r}; only member "
            f"processes may call set-scoped collectives")
    return process_set.engine


def _communicator_size(process_set=None) -> int:
    """Size of the communicator a collective runs over: the SET's when
    one is given, else the world's — the denominator every averaging/
    predivide split must use (one definition; the shims share it)."""
    if process_set is not None:
        return process_set.size()
    return size()


def scatter(stacked, process_set=None):
    """Host-stacked (size, *shape) -> rank-sharded distributed tensor."""
    return _engine(process_set).scatter(stacked)


def gather(dt, process_set=None):
    """Distributed tensor -> host numpy (size, *shape)."""
    return _engine(process_set).gather(dt)


def allreduce(x, op: ReduceOp = ReduceOp.AVERAGE, name: Optional[str] = None,
              prescale_factor: float = 1.0, postscale_factor: float = 1.0,
              compression=None, process_set=None):
    """``compression=None`` uses the configured default
    (``HVD_TPU_COMPRESSION`` / ``init(compression=)``, falling back to
    the legacy ``HVD_TPU_COMPRESSION_DTYPE`` wire knob).
    ``Compression.int8_ef`` runs the reduction as a reduce-safe
    quantized allreduce — int8 payload on every hop, error bounded per
    block (docs/compression.md); stateless here, so rounding is
    round-to-nearest (the error-feedback residual lives on the
    DistributedOptimizer surfaces)."""
    return _engine(process_set).allreduce(x, op, name, prescale_factor,
                                          postscale_factor, compression)


def grouped_allreduce(tensors, op: ReduceOp = ReduceOp.AVERAGE,
                      name: Optional[str] = None,
                      compression=None,
                      prescale_factor: float = 1.0,
                      postscale_factor: float = 1.0,
                      process_set=None):
    return _engine(process_set).allreduce_tree(
        tensors, op, name, compression,
        prescale_factor=prescale_factor,
        postscale_factor=postscale_factor)


def allgather(x, name: Optional[str] = None, process_set=None):
    return _engine(process_set).allgather(x, name)


def grouped_allgather(tensors, name: Optional[str] = None,
                      process_set=None):
    """Allgather every leaf of a list/dict (the later-Horovod grouped
    surface): per-leaf dispatch (XLA's async dispatch pipelines the
    copies; unlike allreduce there is no flat-buffer win to fuse, so
    leaves stay separate executables). Unnamed calls pass None through
    so each leaf gets the engine's unique auto-naming — a constant
    default prefix would collide across distinct unnamed calls."""
    e = _engine(process_set)
    leaves, treedef = jax.tree.flatten(tensors)
    outs = [e.allgather(v, f"{name}.{i}" if name else None)
            for i, v in enumerate(leaves)]
    return jax.tree.unflatten(treedef, outs)


def broadcast(x, root_rank: int = 0, name: Optional[str] = None,
              process_set=None):
    """With ``process_set``, ``root_rank`` is the GLOBAL rank of the
    root (it must be a member); position within the set is resolved
    here."""
    if process_set is not None:
        if root_rank not in process_set.ranks:
            raise ValueError(f"root_rank {root_rank} is not a member of "
                             f"{process_set!r}")
        root_rank = process_set.ranks.index(root_rank)
    return _engine(process_set).broadcast(x, root_rank, name)


def alltoall(x, name: Optional[str] = None, splits=None, process_set=None,
             chunked: Optional[bool] = None, wire=None):
    """Even all-to-all, or — with ``splits`` — the dynamic uneven variant
    where recv splits are negotiated through the controller (reference:
    operations.cc:1020-1081, controller.h:56-58 AlltoallGetRecvSplits).
    See EagerEngine.alltoallv for the two call conventions. ``chunked``
    (extension) selects the uneven wire form: None auto-routes skewed
    tables through the bounded per-hop exchange, True/False forces it.
    ``wire`` (extension, docs/moe.md) compresses the exchanged payload:
    ``"bf16"``/``"int8"``/``"auto"`` or a ``Compression`` class — part
    of the compile-cache signature and the cross-rank contract; with
    ``splits`` it requires the chunked form."""
    return _engine(process_set).alltoall(x, name, splits=splits,
                                         chunked=chunked, wire=wire)


_rs_default_warned = False


def _reducescatter_default_op() -> ReduceOp:
    """One-release transition warning (ADVICE r4): the eager-surface
    default flipped SUM -> AVERAGE in r4 for upstream parity — a silent
    1/n scaling change for callers relying on the old default. Warns
    once per process when ``op`` is left defaulted."""
    global _rs_default_warned
    if not _rs_default_warned:
        _rs_default_warned = True
        import sys
        import warnings

        # Attribute the once-per-process warning to the USER's call
        # site: the depth to it varies by surface (core vs torch vs the
        # TF shim's autograph wrappers vs grouped_*), so walk out of
        # this package instead of hard-coding a stacklevel.
        pkg = os.path.dirname(os.path.abspath(__file__))
        level = 2
        f = sys._getframe(1)
        while (f.f_back is not None
               and f.f_code.co_filename.startswith(pkg)):
            f = f.f_back
            level += 1
        warnings.warn(
            "reducescatter's default op is AVERAGE as of round 4 "
            "(upstream parity; previously SUM on this surface). Pass "
            "op=hvd.Sum explicitly for the unscaled reduction. Note the "
            "in-jit horovod_tpu.ops.collectives.reducescatter still "
            "defaults to SUM.", UserWarning, stacklevel=level)
    return ReduceOp.AVERAGE


def reducescatter(x, op: Optional[ReduceOp] = None,
                  name: Optional[str] = None, process_set=None):
    """This rank's 1/n slice of the elementwise reduction over dim 0.
    Default op is AVERAGE on every surface (core + torch + TF),
    matching upstream's reducescatter default — pass op=Sum for the
    unscaled reduction. (The in-jit ``ops.collectives.reducescatter``
    keeps the SUM default; see docs/api.md.)"""
    if op is None:
        op = _reducescatter_default_op()
    return _engine(process_set).reducescatter(x, op, name)


def grouped_reducescatter(tensors, op: Optional[ReduceOp] = None,
                          name: Optional[str] = None, process_set=None):
    """Reducescatter every leaf of a list/dict (later-Horovod grouped
    surface; per-leaf dispatch — same naming contract as
    :func:`grouped_allgather`). Defaulted ``op`` is AVERAGE (see
    :func:`reducescatter` for the SUM->AVERAGE transition note)."""
    if op is None:
        op = _reducescatter_default_op()
    e = _engine(process_set)
    leaves, treedef = jax.tree.flatten(tensors)
    outs = [e.reducescatter(v, op, f"{name}.{i}" if name else None)
            for i, v in enumerate(leaves)]
    return jax.tree.unflatten(treedef, outs)


def barrier(process_set=None):
    _engine(process_set).barrier()


def join() -> int:
    """Mark this process as done; block until every process has joined,
    meanwhile participating in the remaining processes' allreduces with
    zero tensors. Returns the last-joined rank.

    Reference: operations.cc:1085-1109 EnqueueJoin + JoinOp
    (collective_operations.h:259-267) + torch/mpi_ops.py:631-644.
    Multi-process worlds must ``init(join_mode=True)`` (or set
    HVD_TPU_JOIN_MODE=1) so every collective runs a coordination round —
    the cost the reference pays on every background cycle. In
    single-controller SPMD every rank reaches join() at the same program
    point, so the call is vacuous and returns ``size - 1``."""
    return _ctx().engine.join()


# -- async handle surface (reference torch/mpi_ops.py) ---------------------

def allreduce_async(x, op: ReduceOp = ReduceOp.AVERAGE,
                    name: Optional[str] = None) -> int:
    e = _ctx().engine
    return e.async_call(e.allreduce, x, op, name)


def allgather_async(x, name: Optional[str] = None) -> int:
    e = _ctx().engine
    return e.async_call(e.allgather, x, name)


def broadcast_async(x, root_rank: int = 0, name: Optional[str] = None) -> int:
    e = _ctx().engine
    return e.async_call(e.broadcast, x, root_rank, name)


def poll(handle: int) -> bool:
    return _ctx().engine.poll(handle)


def synchronize(handle: int):
    return _ctx().engine.synchronize(handle)


# -- unified telemetry (docs/metrics.md) -----------------------------------

def metrics() -> dict:
    """Snapshot of the process-wide metrics registry: every counter,
    gauge, and histogram each layer reports (dispatch latency, raw-vs-
    wire bytes, cache hits, fusion fill, autotune state, recovery
    counters...). Empty when disabled via ``HVD_TPU_METRICS=0``. The
    same data is exportable as a JSON-lines file
    (``HVD_TPU_METRICS_FILE``) and a Prometheus ``/metrics`` endpoint
    (``HVD_TPU_METRICS_PORT`` / :func:`start_metrics_server`)."""
    return _metrics_lib.snapshot()


def start_metrics_server(port: int = 0) -> int:
    """Start (or return) the Prometheus ``/metrics`` endpoint on a
    stdlib HTTP background thread; returns the bound port (``port=0``
    binds an ephemeral one). Also serves the raw snapshot at
    ``/metrics.json``. Samples carry ``rank=``/``size=`` labels once
    ``init()`` has run, so rank 0 (or any scraper) can aggregate a pod
    view across workers."""
    return _metrics_lib.serve(port)


def stop_metrics_server() -> None:
    _metrics_lib.stop_serving()


def flight_recorder():
    """The process-wide flight recorder (docs/podmon.md): the ring of
    the last N collective events plus the black-box dump surface.
    ``flight_recorder().events()`` is the live ring;
    ``flight_recorder().dump("manual")`` writes a black box on demand
    (the same payload SIGUSR2 or a fatal stall produces). Usable before
    ``init()`` — the env-configured recorder is created on first use."""
    from .common import flightrec as _flightrec_lib

    return _flightrec_lib.recorder()


# -- timeline (reference operations.cc:720-746) ----------------------------

def start_timeline(filename: str, mark_cycles: bool = False,
                   xprof_dir: Optional[str] = None) -> None:
    """Start the chrome-trace collective timeline; ``xprof_dir``
    additionally starts a ``jax.profiler`` trace there for device-side
    detail (view with TensorBoard/xprof). Both lifecycles live on the
    Timeline, so every stop path — including shutdown() — flushes the
    device trace."""
    t = _ctx().timeline
    t._mark_cycles = mark_cycles
    t.start(filename, xprof_dir=xprof_dir)


def stop_timeline() -> None:
    _ctx().timeline.stop()


# -- SPMD helpers ----------------------------------------------------------

def spmd_step(fn=None, *, in_specs=None, out_specs=None, check_vma=False,
              donate_argnums=()):
    """Decorator: run ``fn`` as a jitted shard_map over the rank mesh with
    per-rank collectives available under ``rank_axis()``. Default specs
    shard the leading axis of every argument over ranks.

    ``check_vma=False`` (default) restores the reference's mental model
    exactly: every value inside the step is rank-local, ``jax.grad`` of a
    replicated parameter yields the LOCAL gradient (no auto-psum), and the
    framework's explicit allreduce is the only cross-rank reduction —
    matching how N reference processes behave (torch/optimizer.py hook
    model). With ``check_vma=True`` JAX's varying-manual-axes type system
    is enforced instead; use ``collective_ops.to_local`` on replicated
    params before ``jax.grad`` in that mode.

    ``donate_argnums``: positions of carry-state arguments (params,
    opt_state, ...) whose HBM buffers may be reused for the outputs —
    halves peak memory for the update and avoids a copy. Donated inputs
    are invalidated; only pass state you immediately overwrite with the
    step's outputs.
    """
    from jax.sharding import PartitionSpec as P

    def deco(f):
        ctx = _ctx()
        spec = P(ctx.config.rank_axis)
        ins = in_specs if in_specs is not None else spec
        outs = out_specs if out_specs is not None else spec
        return jax.jit(jax.shard_map(f, mesh=ctx.mesh, in_specs=ins,
                                     out_specs=outs, check_vma=check_vma),
                       donate_argnums=donate_argnums)
    return deco(fn) if fn is not None else deco


__all__ = [
    "init", "shutdown", "is_initialized", "rank", "size", "local_rank",
    "local_size", "cross_rank", "cross_size", "is_homogeneous", "mesh",
    "hierarchical_mesh", "mesh_axes", "route_mesh", "WirePlan",
    "AxisPhase", "rank_axis", "scatter", "gather", "allreduce",
    "grouped_allreduce", "allgather", "grouped_allgather", "broadcast",
    "alltoall", "reducescatter", "grouped_reducescatter", "barrier",
    "join", "allreduce_async",
    "allgather_async",
    "broadcast_async", "poll", "synchronize", "start_timeline",
    "stop_timeline", "spmd_step", "ReduceOp", "Average", "Sum", "Adasum",
    "Min", "Max", "Product", "Compression", "DistributedOptimizer",
    "DistributedGradFn", "AutotunedStepper", "ShardedOptimizer",
    "FSDPOptimizer", "ZeroOptimizer", "sharded_init", "sharded_update",
    "broadcast_parameters", "broadcast_object",
    "allgather_object", "broadcast_variables", "collective_ops",
    "HorovodInternalError", "HostsUpdatedInterrupt", "NotInitializedError",
    "StallError", "TensorShapeMismatchError", "__version__",
    "mpi_built", "mpi_enabled", "mpi_threads_supported", "gloo_built",
    "gloo_enabled", "nccl_built", "ddl_built", "ccl_built", "cuda_built",
    "rocm_built", "xla_built", "tpu_available",
    "ProcessSet", "add_process_set", "remove_process_set", "run",
    "recovery_stats", "metrics", "start_metrics_server",
    "stop_metrics_server", "flight_recorder",
    "StepTimer", "observe_ef_residual",
    "integrity", "observe_guard", "current_loss_scale",
    "DivergenceDetector", "MismatchError", "NonFiniteError",
    "DivergenceError", "CheckpointCorruptError", "StallTimeoutError",
    "accumulate_gradients", "resolve_remat_policy",
    "auto_shard_threshold", "should_shard_update", "DeviceInfeed",
    "prefetch_to_device", "BackgroundPrefetcher", "shard_batch",
    "infeed_pipeline", "serve",
    "ParallelSpec", "parallel_spec", "parallel_mesh",
    "pipeline_accumulate_gradients", "pipeline_apply",
    "pipeline_train_step_1f1b", "select_last_stage",
    "column_parallel", "row_parallel", "tp_mlp", "tp_attention_qkv",
    "shard_column", "shard_row", "shard_heads", "shard_head_rows",
    "combine_slice_grads",
]
