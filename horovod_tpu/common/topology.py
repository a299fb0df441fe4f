"""Device/process topology discovery — the MPIContext/GlooContext analog.

Reference equivalents: horovod/common/mpi/mpi_context.cc:147-156 (splitting
global/local/cross communicators) and horovod/common/gloo/gloo_context.cc:80-232
(rendezvous + 3-context construction). On TPU there is no MPI: the global
"communicator" is the JAX device mesh; the LOCAL/CROSS split falls out of the
(process, local-device) factorization of the device list; multi-host
bootstrap is ``jax.distributed.initialize`` + the TPU pod metadata instead of
an HTTP KV rendezvous.

Rank semantics: **one rank per device** (the reference runs one process per
GPU; under single-controller JAX the SPMD program has ``size = device_count``
participants regardless of process layout). ``local_*`` refers to devices on
this host/process; ``cross_*`` indexes the host.
"""

from __future__ import annotations

import dataclasses
import os
from typing import List, Optional, Sequence

import numpy as np

from . import config as config_lib


@dataclasses.dataclass(frozen=True)
class Topology:
    """Immutable snapshot of the device topology backing a Context.

    The reference's equivalent state lives in HorovodGlobalState /
    Controller (rank_, local_rank_, cross_rank_, sizes, is_homogeneous_ —
    horovod/common/global_state.h:42-122).
    """

    devices: tuple                 # global device list, mesh order
    process_index: int             # this process (reference: cross_rank)
    process_count: int             # number of processes (hosts)
    local_device_count: int        # devices addressable by this process
    platform: str                  # "tpu" | "cpu" | ...
    is_homogeneous: bool           # same local size on every process

    @property
    def size(self) -> int:
        return len(self.devices)

    @property
    def local_size(self) -> int:
        return self.local_device_count

    @property
    def cross_size(self) -> int:
        return self.process_count

    @property
    def cross_rank(self) -> int:
        return self.process_index

    def local_ranks(self) -> List[int]:
        """Global rank ids of this process's devices."""
        import jax

        local = set(id(d) for d in jax.local_devices())
        return [i for i, d in enumerate(self.devices) if id(d) in local]


def _cpu_platform_selected() -> bool:
    """True when this process will run on the CPU backend — the loopback
    test tier (JAX_PLATFORMS=cpu / jax_platforms config /
    HVD_TPU_FORCE_CPU_DEVICES), not a real TPU pod."""
    import jax

    if config_lib.runtime_env("FORCE_CPU_DEVICES"):
        return True
    for raw in (os.environ.get("JAX_PLATFORMS", ""),
                getattr(jax.config, "jax_platforms", None) or ""):
        if raw.split(",")[0].strip().lower() == "cpu":
            return True
    return False


def _maybe_enable_cpu_collectives() -> None:
    """Configure a cross-process collectives implementation for
    multi-process CPU worlds.

    XLA's CPU client refuses to compile multiprocess computations
    ("Multiprocess computations aren't implemented on the CPU backend")
    unless it was created with a collectives implementation, and the
    default is none: the config knob must be set in-process BEFORE the
    backend client exists. Without this, every `runner.run(..., np=2)`
    world on CPU (tests/test_run_api.py) dies at its first allreduce.
    """
    import jax

    jax.config.update(
        "jax_cpu_collectives_implementation",
        os.environ.get("JAX_CPU_COLLECTIVES_IMPLEMENTATION", "gloo"))


def _maybe_init_distributed() -> None:
    """Initialize jax.distributed when launched multi-process.

    The launcher (horovod_tpu/runner) exports HVD_TPU_COORDINATOR /
    HVD_TPU_NUM_PROC / HVD_TPU_PROC_ID — the analog of the reference's
    HOROVOD_GLOO_RENDEZVOUS_ADDR/PORT + HOROVOD_RANK env wiring
    (gloo_run.py:65-99). On Cloud TPU pods jax.distributed can also
    self-discover from the pod metadata server.
    """
    import jax

    coord = config_lib.runtime_env("COORDINATOR")
    if coord and config_lib.runtime_env("NUM_PROC"):
        nproc = int(config_lib.runtime_env("NUM_PROC", required=True))
        pid = int(config_lib.runtime_env("PROC_ID", "0"))
        if nproc > 1:
            if _cpu_platform_selected():
                _maybe_enable_cpu_collectives()
            try:
                jax.distributed.initialize(
                    coordinator_address=coord,
                    num_processes=nproc,
                    process_id=pid,
                )
            except RuntimeError:
                pass  # already initialized (elastic re-init path)


def discover(force_cpu_devices: int = 0,
             devices: Optional[Sequence] = None) -> Topology:
    """Build a Topology from the live JAX backend.

    ``force_cpu_devices > 0`` builds an N-virtual-device CPU topology (the
    loopback/"Gloo role" backend used by the test suite — SURVEY.md §4).
    """
    import jax

    if force_cpu_devices > 0 and devices is None:
        os.environ.setdefault("XLA_FLAGS", "")
        flag = f"--xla_force_host_platform_device_count={force_cpu_devices}"
        if "xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
            os.environ["XLA_FLAGS"] += " " + flag
        jax.config.update("jax_platforms", "cpu")

    _maybe_init_distributed()

    devs = tuple(devices) if devices is not None else tuple(jax.devices())
    local_count = len([d for d in devs if d in set(jax.local_devices())]) \
        if jax.process_count() > 1 else len(devs)
    # Homogeneity: all processes own the same number of devices.
    counts = {}
    for d in devs:
        counts[d.process_index] = counts.get(d.process_index, 0) + 1
    homo = len(set(counts.values())) <= 1
    return Topology(
        devices=devs,
        process_index=jax.process_index(),
        process_count=jax.process_count(),
        local_device_count=local_count,
        platform=devs[0].platform if devs else "cpu",
        is_homogeneous=homo,
    )


def build_mesh(topology: Topology, axis_name: str):
    """1-D mesh over all ranks — the GLOBAL communicator."""
    import jax

    return jax.sharding.Mesh(np.array(topology.devices), (axis_name,))


# ---------------------------------------------------------------------------
# Mesh-axis model — the topology the per-axis collective router consumes
# (ops/collectives.py mesh_allreduce; docs/topology.md).
#
# A TPU pod is a 2-D/3-D torus of links with very different bandwidths:
# intra-host ICI is an order of magnitude faster than the cross-host hop
# (DCN between slices; the slowest ICI dimension inside one slice). The
# MLPerf TPU-v3 pod work (arXiv:1909.09756, PAPERS.md) scales allreduce
# by staging it per torus axis — reduce-scatter along the fast axis
# first so the slow axis only ever carries 1/fast_size of the bytes.
# MeshAxis is the static per-axis record that routing decisions key on.
# ---------------------------------------------------------------------------

# Axis kinds, fastest first. "ici" = intra-host/slice torus links;
# "dcn" = the cross-host/slice hop (data-center network between slices,
# or the slowest torus dimension of a multi-host pod).
AXIS_ICI = "ici"
AXIS_DCN = "dcn"


@dataclasses.dataclass(frozen=True)
class MeshAxis:
    """One routing axis of the device mesh: its shard_map axis name, the
    number of ranks along it, and the link tier it maps onto. Ordered
    fast -> slow in :func:`mesh_axes` output — the router reduces-
    scatters along earlier (fast) axes first so later (slow) axes carry
    the fewest bytes."""

    name: str
    size: int
    kind: str = AXIS_ICI


def parse_mesh_shape(raw: Optional[str]) -> Optional[tuple]:
    """``"2x4"`` / ``"2,2,2"`` -> dim tuple (slow axis first, fast axis
    LAST — row-major device order, matching
    ``build_hierarchical_mesh``'s (cross, local) layout); None when
    unset/invalid."""
    if not raw:
        return None
    try:
        dims = tuple(int(d) for d in str(raw).replace("x", ",").split(",")
                     if d.strip())
    except ValueError:
        return None
    if not dims or any(d < 1 for d in dims):
        return None
    return dims


def mesh_shape_from_env() -> Optional[tuple]:
    """The ``HVD_TPU_MESH_SHAPE`` override that simulates a multi-axis
    mesh on any backend (the test suite's 8 virtual CPU devices stand in
    for a 2x4 pod slice)."""
    return parse_mesh_shape(config_lib._env("MESH_SHAPE"))


# Default axis names, slow -> fast, matching the historical
# (cross, local) hierarchical mesh; 3-D meshes insert "middle".
_AXIS_NAMES = {1: ("hvd",), 2: ("cross", "local"),
               3: ("cross", "middle", "local")}


def mesh_axes(topology: Topology,
              shape: Optional[Sequence[int]] = None) -> tuple:
    """The routing-axis factorization of a topology, FAST axis first.

    Resolution order: an explicit ``shape`` argument, then the
    ``HVD_TPU_MESH_SHAPE`` env override (simulated meshes), then the
    pod metadata the Topology already carries (cross_size x local_size
    when multi-host), else the flat 1-D axis. Shapes are given slow ->
    fast (row-major device order, ``"2x4"`` = 2 hosts x 4 chips); the
    returned tuple is reversed to fast -> slow because that is the
    order the router stages phases in.
    """
    dims = tuple(shape) if shape is not None else mesh_shape_from_env()
    if dims is None:
        if topology.is_homogeneous and topology.cross_size > 1:
            dims = (topology.cross_size,
                    topology.size // topology.cross_size)
        else:
            dims = (topology.size,)
    total = 1
    for d in dims:
        total *= d
    if total != topology.size:
        raise ValueError(
            f"mesh shape {dims} covers {total} devices but the topology "
            f"has {topology.size} (HVD_TPU_MESH_SHAPE must factor the "
            "world size exactly)")
    names = _AXIS_NAMES.get(len(dims))
    if names is None:
        raise ValueError(
            f"mesh shapes of rank {len(dims)} are not supported "
            "(1-D flat, 2-D cross x local, 3-D cross x middle x local)")
    # Slow -> fast in `dims`/`names`; emit fast-first. The LAST (fastest)
    # axis is the intra-host ICI dimension; every other axis is priced
    # as a cross/DCN hop.
    axes = []
    for i, (n, d) in enumerate(zip(names, dims)):
        kind = AXIS_ICI if i == len(dims) - 1 else AXIS_DCN
        axes.append(MeshAxis(name=n, size=d, kind=kind))
    return tuple(reversed(axes))


def build_mesh_from_axes(topology: Topology, axes: Sequence[MeshAxis]):
    """N-D jax Mesh over the topology's devices for a mesh_axes()
    factorization (axes given fast -> slow; the device array is
    reshaped slow-major, so the fastest axis is contiguous — matching
    the (cross, local) hierarchical mesh layout and, on a real pod,
    jax's device enumeration order within a host)."""
    import jax

    slow_first = list(reversed(list(axes)))
    arr = np.array(topology.devices).reshape(
        tuple(a.size for a in slow_first))
    return jax.sharding.Mesh(arr, tuple(a.name for a in slow_first))


def build_hierarchical_mesh(topology: Topology, cross_axis: str,
                            local_axis: str):
    """2-D (cross=hosts, local=per-host devices) mesh — the LOCAL/CROSS
    communicator split (reference common.h:113-117) for hierarchical
    allreduce (nccl_operations.cc:190+ analog: ICI within host/slice,
    DCN across).
    """
    import jax

    if not topology.is_homogeneous:
        raise ValueError(
            "hierarchical mesh requires homogeneous per-process device counts")
    local = topology.size // topology.cross_size
    arr = np.array(topology.devices).reshape(topology.cross_size, local)
    return jax.sharding.Mesh(arr, (cross_axis, local_axis))
