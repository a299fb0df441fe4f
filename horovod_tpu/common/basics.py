"""Process/context lifecycle — the HorovodBasics + global-state analog.

Reference: horovod/common/basics.py:22-258 (ctypes wrapper over the C ABI:
init/shutdown/rank/size/local_rank/local_size/is_homogeneous...) backed by
horovod/common/operations.cc:633-878 (InitializeHorovodOnce + extern "C").

TPU-native: there is no background C++ thread to spin up — ``init()``
discovers the topology (JAX devices / distributed processes), builds the
global 1-D rank mesh (and the 2-D cross×local mesh for hierarchical paths),
and instantiates the eager engine, timeline, and stall inspector. A subset
``init(comm=[ranks])`` builds the context over a device subset, mirroring
the reference's subset-communicator path (basics.py:33-65,
operations.cc:692-700).
"""

from __future__ import annotations

import logging
import os
import threading
from typing import List, Optional, Sequence

from . import shutdown as shutdown_lib
from . import topology as topo_lib
from . import config as config_lib
from .config import Config, configure
from .exceptions import NotInitializedError
from .stall import StallInspector
from .timeline import Timeline

logger = logging.getLogger("horovod_tpu")


# <checkout>/.jax_cache: derived from where this package sits, because
# the directory is part of every cache key — a path that moves never hits.
DEFAULT_COMPILATION_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def _place_compilation_cache() -> None:
    """The one rule for JAX's persistent compilation cache: where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own reading of it stands
    and nothing here names a directory; where it is not, the cache is
    :data:`DEFAULT_COMPILATION_CACHE_DIR`. An elastic reset or relaunch
    re-traces the same programs and TPU compiles run tens of seconds —
    the cache turns them into reads."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    if jax.config.jax_compilation_cache_dir \
            == DEFAULT_COMPILATION_CACHE_DIR:
        return
    jax.config.update("jax_compilation_cache_dir",
                      DEFAULT_COMPILATION_CACHE_DIR)
    # jax decides whether the cache is in use at the first compile of
    # the process; anything compiled before init() has already said no.
    from jax.experimental.compilation_cache import compilation_cache

    compilation_cache.reset_cache()


class Context:
    """The live runtime: topology + meshes + eager engine + profiling."""

    def __init__(self, config: Config, comm: Optional[Sequence[int]] = None):
        self.config = config
        logging.basicConfig()
        logger.setLevel(getattr(logging, config.log_level.upper(),
                                logging.WARNING))

        # Chaos: (re)install the fault plan if HVD_TPU_FAULT_PLAN changed
        # since import — any entrypoint that reaches init() runs under
        # the plan unchanged.
        from . import faults as faults_lib

        faults_lib.refresh_from_env()

        topo = topo_lib.discover(force_cpu_devices=config.force_cpu_devices)
        # JAX falls to the CPU without a word when the TPU fails to
        # initialise; say what this process really runs on.
        logger.info("devices: platform=%s device_kind=%s count=%d",
                    topo.platform, topo.devices[0].device_kind,
                    len(topo.devices))
        if comm is not None:
            # Subset communicator: restrict to the given global rank ids.
            devices = [topo.devices[r] for r in comm]
            topo = topo_lib.discover(devices=devices)
        self.topology = topo
        # Host-core pinning before any worker threads spawn (reference
        # common.cc:140-203 parse_and_set_affinity; input pipelines and
        # the finalizer pool inherit the pin).
        from .affinity import parse_and_set_affinity

        parse_and_set_affinity(
            config.thread_affinity,
            int(config_lib.runtime_env("LOCAL_SIZE", "1")),
            int(config_lib.runtime_env("LOCAL_RANK", "0")))
        _place_compilation_cache()
        self.mesh = topo_lib.build_mesh(topo, config.rank_axis)
        self.hier_mesh = None
        if topo.is_homogeneous and topo.cross_size > 1:
            self.hier_mesh = topo_lib.build_hierarchical_mesh(
                topo, "cross", "local")
        # Routing-axis model (docs/topology.md): the per-axis
        # factorization the collective router keys on — pod metadata,
        # or the HVD_TPU_MESH_SHAPE / init(mesh_shape=) override for
        # simulated meshes. route_mesh is the matching N-D jax Mesh
        # when the factorization is multi-axis (else the flat mesh
        # already covers it).
        self.mesh_axes = None
        self.route_mesh = None
        try:
            shape = topo_lib.parse_mesh_shape(config.mesh_shape)
            self.mesh_axes = topo_lib.mesh_axes(topo, shape)
            if len(self.mesh_axes) > 1:
                self.route_mesh = topo_lib.build_mesh_from_axes(
                    topo, self.mesh_axes)
        except ValueError as e:
            logger.warning(
                "mesh shape invalid for this topology (%s); routing "
                "falls back to the flat axis", e)
        # Hybrid parallelism spec (docs/pipeline.md): role-named mesh
        # (dp/pp/tp/ep) from HVD_TPU_PARALLEL / init(parallel=). The
        # spec itself is consumed EXPLICITLY by the optimizer surfaces
        # (parallel=) and the tools — the Context only resolves and
        # publishes it (hvd.parallel_spec()/hvd.parallel_mesh()).
        self.parallel_spec = None
        self.parallel_mesh = None
        if config.parallel:
            from ..parallel.spec import ParallelSpec

            try:
                spec = ParallelSpec.resolve(config.parallel)
                self.parallel_mesh = spec.mesh(topo.devices)
                self.parallel_spec = spec
            except ValueError as e:
                logger.warning(
                    "parallel spec invalid for this topology (%s); "
                    "hybrid parallelism disabled", e)

        self.timeline = Timeline(config.timeline_filename,
                                 config.timeline_mark_cycles)
        self.stall = StallInspector(config.stall_check_time_seconds,
                                    config.stall_shutdown_time_seconds,
                                    config.stall_check_disable,
                                    fatal_mode=config.stall_fatal)
        # Reference polls CheckForStalledTensors each background cycle
        # (stall_inspector.cc:28+); here a daemon watchdog thread polls.
        self.stall.start_watchdog()
        # Flight recorder (docs/podmon.md): the per-process black box.
        # Built from config and installed as the process singleton so
        # the eager engine's submit/complete path and the stall
        # inspector's dump trigger all feed one ring; SIGUSR2 arms the
        # on-demand dump (best-effort — main thread only, like the
        # preemption latch).
        from . import flightrec as flightrec_lib

        # rank= is the context fallback; HVD_TPU_PROC_ID (the virtual
        # identity) wins inside the constructor — same precedence as
        # the metrics rank= label below, so a direct multi-controller
        # launch (no hvdtpurun) still writes blackbox.rank<k>.json per
        # process instead of N colliding rank-0 boxes.
        self.flightrec = flightrec_lib.install(flightrec_lib.FlightRecorder(
            size=config.flightrec_size,
            directory=config.flightrec_dir,
            enabled=config.flightrec,
            rank=self.rank()))
        self.flightrec._stall_inspector = self.stall
        flightrec_lib.install_signal_handler()
        # Autotuner (reference ParameterManager, parameter_manager.cc):
        # constructed when HOROVOD_AUTOTUNE is set; the eager engine feeds
        # it grouped-allreduce timings and reads the live fusion threshold
        # from it; jitted step loops drive it via optim.AutotunedStepper.
        self.autotuner = None
        if config.autotune:
            from .autotune import Autotuner

            self.autotuner = Autotuner(
                warmup_samples=config.autotune_warmup_samples,
                steps_per_sample=config.autotune_steps_per_sample,
                log_file=config.autotune_log)
        from ..ops.eager import EagerEngine

        if config.hierarchical_allreduce and self.hier_mesh is None:
            logger.warning(
                "HIERARCHICAL_ALLREDUCE requested but topology is "
                "single-host/non-homogeneous; using flat allreduce "
                "(reference falls back the same way, operations.cc:470+)")
        # Multi-process guard rail: in one-process-per-host worlds a
        # program-order divergence would deadlock the XLA collective with
        # no diagnostics; the Controller validates each new eager
        # signature across processes first (reference controller.cc:63-358;
        # vacuous — and skipped — under single-controller SPMD).
        self.controller = None
        if topo.process_count > 1:
            from .controller import Controller, JaxKVTransport

            global _init_count
            self.controller = Controller(
                topo.process_index, topo.process_count, JaxKVTransport(),
                timeout_s=config.stall_check_time_seconds,
                incarnation=_init_count)
        self.engine = EagerEngine(self.mesh, config.rank_axis, config,
                                  timeline=self.timeline,
                                  stall_inspector=self.stall,
                                  hier_mesh=self.hier_mesh,
                                  controller=self.controller,
                                  autotuner=self.autotuner)
        # Unified telemetry (docs/metrics.md): stamp the rank identity
        # onto every exported sample (rank 0 aggregates a pod view by
        # scraping each worker's /metrics), then wire the export
        # surfaces the config asks for. Registry enable/disable itself
        # is env-only (HVD_TPU_METRICS — bound at import by the
        # instrumented modules).
        from . import metrics as metrics_lib

        self.metrics_port: Optional[int] = None
        self._owns_metrics_server = False
        self._owns_metrics_dump = False
        if metrics_lib.enabled():
            # host= rides along with rank=/size= (docs/podmon.md): the
            # pod aggregator attributes a scraped series to a host
            # without a reverse lookup, and the scrape-path autoscale
            # reports need the same host key the KV reports carry.
            labels = {"rank": str(self.rank()), "size": str(self.size())}
            virtual_np = config_lib.runtime_env("VIRTUAL_NUM_PROC")
            if virtual_np:
                # FORCE_LOCAL virtual hosts: every worker is an
                # independent 1-proc jax world that believes it is
                # rank 0 of 1 — the VIRTUAL identity (the same one the
                # autoscale KV publisher and podmon endpoint
                # registration key on) is what pod-scope scrapes must
                # see, or N workers collapse to one series.
                labels["rank"] = config_lib.runtime_env("PROC_ID",
                                                labels["rank"])
                labels["size"] = virtual_np
            host_label = config_lib.runtime_env("HOSTNAME")
            if host_label:
                labels["host"] = host_label
            metrics_lib.set_global_labels(**labels)
            if config.metrics_trace_bridge:
                metrics_lib.enable_trace_bridge(True)
            if config.metrics_file:
                # Ownership like the server below: a dump the user
                # started explicitly outlives this context's shutdown.
                self._owns_metrics_dump = \
                    metrics_lib.dumping_path() is None
                metrics_lib.start_file_dump(config.metrics_file,
                                            config.metrics_interval_s)
            if config.metrics_port >= 0:
                already = metrics_lib.serving_port()
                try:
                    self.metrics_port = metrics_lib.serve(
                        config.metrics_port)
                except OSError as e:
                    # Telemetry is best-effort, never fatal to init: a
                    # fixed-port collision (several workers per host)
                    # falls back to an ephemeral port.
                    logger.warning(
                        "metrics: port %d unavailable (%s); binding an "
                        "ephemeral port instead — pass --metrics-port 0 "
                        "with multiple workers per host",
                        config.metrics_port, e)
                    try:
                        self.metrics_port = metrics_lib.serve(0)
                    except OSError as e2:
                        logger.warning(
                            "metrics: /metrics endpoint disabled (%s)",
                            e2)
                if self.metrics_port is not None:
                    self._owns_metrics_server = already is None
                    logger.info("metrics: Prometheus /metrics endpoint "
                                "on port %d", self.metrics_port)
                    # Pod-scope discovery (docs/podmon.md): advertise
                    # this worker's endpoint over the controller KV so
                    # the driver-side aggregator can scrape it without
                    # knowing ephemeral ports. Best-effort; no-op
                    # without HVD_TPU_RENDEZVOUS.
                    from . import podmon as podmon_lib

                    podmon_lib.register_endpoint(self.metrics_port,
                                                 rank=self.rank())
        # Elastic host-update channel: poll the driver's rendezvous KV
        # topology version (reference: WorkerNotificationClient,
        # elastic/worker.py). Consumed by State.check_host_updates().
        self.host_update_notifier = None
        rdv = config_lib.runtime_env("RENDEZVOUS")
        if config.elastic and rdv:
            self.host_update_notifier = self._make_host_update_notifier(rdv)
        self._process_sets = []
        self._shutdown = False

    @staticmethod
    def _make_host_update_notifier(rdv_addr: str):
        from ..runner.rendezvous import RendezvousClient

        host, port = rdv_addr.rsplit(":", 1)
        client = RendezvousClient(host, int(port), timeout_s=5.0)
        last_seen = {"v": None}

        warned = {"auth": False}

        def notifier() -> bool:
            import urllib.error

            try:
                raw = client.get("elastic", "topology_version")
            except urllib.error.HTTPError as e:
                if e.code == 403 and not warned["auth"]:
                    # A silent False would permanently disable topology
                    # notification — a wrong/missing
                    # HVD_TPU_RENDEZVOUS_SECRET must be loud.
                    warned["auth"] = True
                    logger.warning(
                        "elastic host-update polling rejected (403): "
                        "HVD_TPU_RENDEZVOUS_SECRET missing or mismatched"
                        " — topology changes will NOT be observed")
                return False
            except OSError:
                return False
            if raw is None:
                return False
            v = raw.decode()
            if last_seen["v"] is None:
                last_seen["v"] = v
                return False
            if v != last_seen["v"]:
                last_seen["v"] = v
                return True
            return False

        return notifier

    # -- reference C-ABI query surface (operations.cc:690-878) -------------

    def rank(self) -> int:
        """Global rank of this controller process's first device. In
        single-controller SPMD the Python program acts for all ranks; this
        returns the canonical rank for rank-0-only work (checkpointing
        etc.), i.e. the smallest global rank this process drives."""
        ranks = self.topology.local_ranks()
        return ranks[0] if ranks else 0

    def size(self) -> int:
        return self.topology.size

    def local_rank(self) -> int:
        """Local rank of this controller process on its host. One process
        per host (the launcher's model) → 0. In one-process-per-chip
        layouts the launcher exports HVD_TPU_LOCAL_RANK (the reference's
        HOROVOD_LOCAL_RANK, gloo_run.py:65-99); per-device code inside jit
        uses axis_index instead."""
        env = config_lib.runtime_env("LOCAL_RANK")
        if env is not None:
            return int(env)
        return 0

    def local_size(self) -> int:
        """Paired with local_rank(): the launcher's HVD_TPU_LOCAL_SIZE
        wins in one-process-per-chip layouts so 0 <= local_rank <
        local_size always holds."""
        env = config_lib.runtime_env("LOCAL_SIZE")
        if env is not None:
            return int(env)
        return self.topology.local_size

    def cross_rank(self) -> int:
        return self.topology.cross_rank

    def cross_size(self) -> int:
        return self.topology.cross_size

    def is_homogeneous(self) -> bool:
        return self.topology.is_homogeneous

    def fusion_threshold(self) -> int:
        """Live fusion threshold (reference: ParameterManager owns the
        live value, parameter_manager.h:42). Single source of truth is
        the engine's resolver."""
        return self.engine.fusion_threshold()

    def add_process_set(self, process_set):
        """Register a ProcessSet (or plain rank list): builds its
        sub-mesh eager engine over the member ranks' devices. Beyond the
        reference era (general process sets arrived in later Horovod);
        see process_set.py for the TPU-native design."""
        from ..process_set import ProcessSet, _build_engine

        if not isinstance(process_set, ProcessSet):
            process_set = ProcessSet(process_set)
        _build_engine(self, process_set)
        self._process_sets.append(process_set)
        return process_set

    def remove_process_set(self, process_set) -> None:
        from ..process_set import ProcessSet

        if isinstance(process_set, ProcessSet) and \
                process_set in self._process_sets:
            resolved = process_set
        else:
            # Resolve by member ranks — covers the rank-list shorthand
            # AND a fresh ProcessSet instance equal to a registered one
            # (silently no-op'ing on those would leave the real set and
            # its engine alive).
            ranks = tuple(sorted({int(r) for r in (
                process_set.ranks if isinstance(process_set, ProcessSet)
                else process_set)}))
            matches = [ps for ps in self._process_sets
                       if ps.ranks == ranks]
            if not matches:
                raise ValueError(f"no registered process set with ranks "
                                 f"{list(ranks)}")
            resolved = matches[0]
        resolved._engine = None
        if isinstance(process_set, ProcessSet) and \
                resolved is not process_set:
            process_set._engine = None  # the caller's handle too
        self._process_sets = [ps for ps in self._process_sets
                              if ps is not resolved]

    def shutdown(self) -> None:
        if self._shutdown:
            return
        for ps in self._process_sets:
            ps._engine = None
        self._process_sets = []
        self.stall.stop_watchdog()
        self.timeline.stop()
        from . import metrics as metrics_lib

        # Stop only what THIS context started (ownership-checked for
        # both surfaces): a dump/server the user started explicitly
        # outlives re-init cycles. Stopping the dump drains a final
        # snapshot line.
        if self._owns_metrics_dump:
            metrics_lib.stop_file_dump()
        if self._owns_metrics_server:
            metrics_lib.stop_serving()
        self._shutdown = True


_context: Optional[Context] = None
_context_lock = threading.Lock()
# Count of Context constructions in this process — the controller's KV
# incarnation (identical across ranks when program order is identical).
_init_count = 0


def init(comm: Optional[Sequence[int]] = None, process_sets=None,
         **config_overrides) -> Context:
    """Initialize the runtime (idempotent, like InitializeHorovodOnce).

    ``comm``: optional list of global rank ids forming a subset communicator
    (reference basics.py:33-65). ``process_sets``: optional list of
    ProcessSet objects (or rank lists) to register at startup. Config
    overrides win over env vars.
    """
    global _context
    with _context_lock:
        if _context is not None and not _context._shutdown:
            if comm is not None or process_sets or config_overrides:
                # Silently returning the old context would make e.g. a
                # subset communicator request produce full-world collectives
                # — fail loudly instead (a bare init() stays idempotent).
                raise ValueError(
                    "init() called with comm/config overrides but the "
                    "runtime is already initialized; call shutdown() first "
                    "to re-initialize with different settings")
            return _context
        global _init_count
        _init_count += 1
        _context = Context(configure(**config_overrides), comm=comm)
        for ps in process_sets or ():
            _context.add_process_set(ps)
        # One ordered teardown sequence (common/shutdown.py): the
        # context stops its export surfaces AFTER the flight recorder
        # finalizes and BEFORE the recovery-stats dump — independent
        # atexit hooks used to race these.
        shutdown_lib.register("context", shutdown,
                              shutdown_lib.CONTEXT_PRIORITY)
        return _context


def shutdown() -> None:
    """Tear down (reference: horovod_shutdown, operations.cc:706-712)."""
    global _context
    with _context_lock:
        if _context is not None:
            _context.shutdown()
            _context = None


def is_initialized() -> bool:
    return _context is not None and not _context._shutdown


def context() -> Context:
    if _context is None or _context._shutdown:
        raise NotInitializedError()
    return _context


# -- capability queries (reference basics.py:160-258) -----------------------
#
# The reference answers "what was compiled in" so scripts can pick code
# paths (mpi_built/gloo_built/nccl_built/...). This framework has exactly
# one data plane — XLA collectives over ICI/DCN — so the vendor-backend
# queries honestly return False/0 and two TPU-native queries answer the
# question migrating scripts are actually asking. All callable pre-init,
# like the reference's.

def mpi_built() -> bool:
    return False


def mpi_enabled() -> bool:
    return False


def mpi_threads_supported() -> bool:
    """Reference basics.py:160-178 raises when MPI isn't enabled — same
    contract here, where it never is."""
    raise ValueError("MPI is not part of the TPU data plane; collectives "
                     "run on XLA over ICI/DCN (xla_built() == True)")


def gloo_built() -> bool:
    return False


def gloo_enabled() -> bool:
    return False


def nccl_built() -> int:
    return 0  # reference returns NCCL_VERSION_CODE or 0 (basics.py:218)


def ddl_built() -> bool:
    return False


def ccl_built() -> bool:
    return False


def cuda_built() -> bool:
    return False


def rocm_built() -> bool:
    return False


def xla_built() -> bool:
    """Always True: XLA collectives are the (only) data plane."""
    return True


def tpu_available() -> bool:
    """True when this process's JAX backend is a TPU. Answers from
    ``jax.devices()`` itself: a chip belongs to one process, so a probe
    in a child would take it from the caller — or hang on it. Before
    ``init()`` this starts the backend, as any first JAX call does."""
    import jax

    try:
        return any(d.platform == "tpu" for d in jax.devices())
    except RuntimeError:
        return False


# Single source of truth for the query surface the framework shims
# re-export (tensorflow/torch/mxnet/keras all loop over this).
CAPABILITY_QUERY_NAMES = (
    "mpi_built", "mpi_enabled", "mpi_threads_supported", "gloo_built",
    "gloo_enabled", "nccl_built", "ddl_built", "ccl_built", "cuda_built",
    "rocm_built", "xla_built", "tpu_available",
)


def export_capability_queries(namespace: dict) -> None:
    """Copy every capability query into a shim's module globals."""
    for _name in CAPABILITY_QUERY_NAMES:
        namespace[_name] = globals()[_name]
