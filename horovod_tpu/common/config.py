"""Runtime configuration knobs.

TPU-native analog of the reference's env-var config surface
(reference: horovod/common/common.h:64-90 canonical HOROVOD_* list, parsed
in horovod/common/operations.cc:441-523 and horovod/common/utils/env_parser.cc).

Same three-layer convergence as the reference: (1) env vars read here,
(2) launcher CLI flags that *set* those envs (see horovod_tpu/runner/launch.py),
(3) programmatic overrides via :func:`configure`.

We honor both a native ``HVD_TPU_*`` prefix and the reference-compatible
``HOROVOD_*`` names so scripts written against the reference keep working.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional

_MB = 1024 * 1024


def _env(name: str, default: Optional[str] = None) -> Optional[str]:
    """Look up `name` under both prefixes: HVD_TPU_X wins over HOROVOD_X."""
    for key in ("HVD_TPU_" + name, "HOROVOD_" + name):
        val = os.environ.get(key)
        if val is not None:
            return val
    return default


def _env_int(name: str, default: int) -> int:
    val = _env(name)
    try:
        return int(val) if val is not None else default
    except ValueError:
        return default


def _env_float(name: str, default: float) -> float:
    val = _env(name)
    try:
        return float(val) if val is not None else default
    except ValueError:
        return default


def _env_bool(name: str, default: bool) -> bool:
    val = _env(name)
    if val is None:
        return default
    return val.strip().lower() in ("1", "true", "yes", "on")


@dataclasses.dataclass
class Config:
    """All runtime knobs, resolved once at ``init()`` (re-resolved on re-init).

    Mirrors the knob inventory of the reference (SURVEY.md §5 "Config"):
    fusion threshold, cycle time, cache, autotune, stall, timeline, plus
    TPU-specific additions (donation, compression dtype, mesh axis names).
    """

    # Tensor fusion: bucket small tensors into flat buffers before the
    # collective (reference: 64 MiB default, operations.cc:442).
    # NOTE: the reference's HOROVOD_CYCLE_TIME (5 ms background-thread
    # cycle, operations.cc:451) has no TPU analog — there is no background
    # negotiation loop; eager dispatch rides XLA's async stream directly —
    # so that knob intentionally does not exist here.
    fusion_threshold_bytes: int = 64 * _MB
    # Response-cache capacity (reference: 1024, operations.cc:476).
    cache_capacity: int = 1024
    # Hierarchical (ICI intra-slice + DCN cross-slice) reduction.
    hierarchical_allreduce: bool = False
    hierarchical_allgather: bool = False
    # Stall inspector (reference defaults stall_inspector.h:75-80).
    stall_check_time_seconds: float = 60.0
    stall_shutdown_time_seconds: float = 0.0
    stall_check_disable: bool = False
    # Fatal-stall escalation (docs/integrity.md): "raise" promotes a
    # tripped shutdown threshold from the latched StallError to a typed
    # StallTimeoutError that the elastic loop classifies as a comm
    # failure — a hung collective aborts into elastic reset instead of
    # wedging the run. Default None keeps the historical behavior.
    stall_fatal: Optional[str] = None
    # Training-integrity guard (common/integrity.py; docs/integrity.md).
    # Non-finite gradient policy on the optimizer surfaces: None/"off"
    # disables; "warn" | "skip_step" | "zero" | "scale_backoff" |
    # "abort" select the globally-agreed reaction to a NaN/Inf gradient.
    nonfinite_policy: Optional[str] = None
    # Divergence detector cadence: check parameter fingerprints across
    # ranks every N steps (0 = off).
    diverge_check_steps: int = 0
    # Divergence policy: "warn" | "abort" | "resync" (resync =
    # broadcast params from rank 0, counted in RecoveryStats).
    diverge_policy: str = "warn"
    # Verified checkpoints: CRC+size sidecar written at save, verified
    # at restore with walk-back through the last-good chain.
    checkpoint_verify: bool = True
    # Timeline profiler (reference: HOROVOD_TIMELINE env).
    timeline_filename: Optional[str] = None
    timeline_mark_cycles: bool = False
    # Autotune (reference: HOROVOD_AUTOTUNE*).
    autotune: bool = False
    autotune_log: Optional[str] = None
    autotune_warmup_samples: int = 3
    autotune_steps_per_sample: int = 10
    # Topology-aware collective routing (docs/topology.md). `route`
    # names the default WirePlan for the optimizer surfaces: "flat"
    # (1-D axis), "staged" (RS local -> reduce cross -> AG local),
    # "staged_int8" (int8 on the slow cross hop), or a full spec like
    # "local:none,cross:int8" (fast axis first). None keeps the flat
    # axis unless the call site passes route= explicitly.
    route: Optional[str] = None
    # Simulated/override mesh factorization, slow axis first (e.g.
    # "2x4" = 2 hosts x 4 chips; also read pre-init by
    # topology.mesh_shape_from_env so tools can consume it directly).
    mesh_shape: Optional[str] = None
    # Hybrid dp x pp x tp parallelism on one mesh (docs/pipeline.md).
    # `parallel` is the ParallelSpec form ("dp=2,pp=2,tp=2", slow axis
    # first; init(parallel=) also takes a role dict) the Context
    # resolves into hvd.parallel_spec()/hvd.parallel_mesh(). The
    # optimizer surfaces take the spec EXPLICITLY (parallel=) — an env
    # knob must never rename the reduction axes of existing call
    # sites; bench/tools read this and pass it through.
    parallel: Optional[str] = None
    # Stage-boundary activation/cotangent wire format for the pipeline
    # schedule (parallel/pipeline.py): "none" | "bf16" | "int8"
    # (block-scaled, straight-through VJP — the MoE-dispatch pattern).
    pp_wire: Optional[str] = None
    # Tool defaults for the hybrid mesh shape (bench --pipeline-stages
    # / --tp consult these when the flags are unset; 1 = off).
    pp_stages: int = 1
    tp: int = 1
    # Sequence parallelism (docs/sequence.md). `seq_wire` is the K/V
    # exchange format for ring/Ulysses attention ("none" | "bf16" |
    # "int8", block-scaled STE — parallel/ring_attention.py resolves
    # it); `seq_parallel` is the tool default sp degree (bench
    # --seq-parallel consults it when the flag is unset; 1 = off);
    # `seq_impl` picks "ring" (striped causal ring) or "ulysses".
    seq_wire: Optional[str] = None
    seq_parallel: int = 1
    seq_impl: str = "ring"
    # Adasum scalar precision (reference keeps fp64 scalars, adasum.h).
    adasum_scalar_dtype: str = "float32"
    # Compression for the wire format of eager collectives.
    compression_dtype: Optional[str] = None  # e.g. "bfloat16"/"float16"
    # Default REDUCTION compression (HVD_TPU_COMPRESSION): the compressor
    # DistributedOptimizer/DistributedGradFn and the eager engine use when
    # none is passed explicitly. Must be reduce-safe: "bf16"/"fp16"
    # (cast) or "int8_ef" (reduce-safe quantized allreduce with error
    # feedback — ops/compression.Int8EFCompressor). Wins over
    # compression_dtype for the engine default when both are set.
    compression: Optional[str] = None
    # Smallest fused-bucket byte size the quantized (int8) reduce path
    # quantizes; smaller float buckets ride bf16 (common/fusion.py
    # assign_wire_dtypes — the per-bucket overhead of quantize/dequant +
    # scales only amortizes on large buckets).
    quantize_min_bucket_bytes: int = 64 * 1024
    # Expert-parallel MoE dispatch (docs/moe.md). `moe_wire` is the
    # default payload format for the dispatch/combine alltoall on the
    # MoE surfaces (parallel/moe.moe_layer via bench --moe, models.gpt
    # MoeMlp): "none" | "bf16" | "int8" | "auto" (int8 at or above the
    # fusion.assign_alltoall_wire size threshold, bf16 below).
    moe_wire: Optional[str] = None
    # Capacity-dim pipelining depth: dispatch-alltoall of chunk k+1
    # overlaps expert-FFN compute of chunk k (1 = off).
    moe_overlap_chunks: int = 1
    # Default expert capacity factor (GShard: tokens*2/num_experts *
    # this; overflow routes are dropped and re-weighted).
    moe_capacity_factor: float = 1.25
    # Scan-based gradient accumulation (docs/performance.md "MFU
    # playbook"): default microbatch count for the accumulate()
    # surfaces — hvd.accumulate_gradients and the accum_steps= knob on
    # DistributedOptimizer/ShardedOptimizer. 1 = off. One collective
    # round, one guard agreement, and one error-feedback advance per
    # EFFECTIVE (post-accumulation) step.
    accum_steps: int = 1
    # Remat policy for the microbatch loss under accumulation — maps to
    # jax.checkpoint policies: "none" | "full" (recompute everything) |
    # "dots" (save matmul outputs) | "dots_no_batch" (save only
    # non-batch-dim matmuls — the TPU-recommended default for
    # transformers).
    remat_policy: Optional[str] = None
    # Device-infeed mode default for the data pipeline helpers and the
    # bench --prefetch arm: "off" (place each batch on demand, blocked)
    # | "single" (one batch staged ahead on the consumer thread) |
    # "double" (background-thread double-buffered DeviceInfeed).
    prefetch: Optional[str] = None
    # Weight-update sharding heuristic (hvd.should_shard_update): when
    # the replicated params are at least this many bytes and the world
    # has >1 rank, ZeRO-1's sharded update is the default candidate.
    auto_shard_threshold_bytes: int = 256 * _MB
    # Default ZeRO stage for the TOOLS (bench --zero-stage auto,
    # docs/zero.md): 0 = replicated update, 1 = sharded optimizer
    # state, 2 = + sharded gradient accumulation, 3 = + sharded
    # parameters with gather-on-demand. Deliberately NOT consulted by
    # DistributedOptimizer itself — the stage changes the update() call
    # contract (SPMD region, params/shards argument), and an env knob
    # must never break existing call sites; pass zero_stage= there.
    zero_stage: int = 0
    # Elastic mode (reference: HOROVOD_ELASTIC).
    elastic: bool = False
    # Telemetry-driven autoscaling (docs/autoscale.md — no reference
    # analog: the reference's elastic layer only survives membership
    # change, it never decides). `autoscale` arms the control loop in
    # the elastic driver; `autoscale_policy` is a JSON policy file path
    # or inline JSON (every threshold/window/hysteresis knob is DATA —
    # see common/autoscale.AutoscalePolicy; individual fields override
    # via HVD_TPU_AUTOSCALE_<FIELD>); `autoscale_log` is the
    # driver-side JSON-lines decision log (deterministic under a seeded
    # fault plan — tools/chaos_soak.py --family autoscale).
    autoscale: bool = False
    autoscale_policy: Optional[str] = None
    autoscale_log: Optional[str] = None
    # Join mode: multi-process programs that call hvd.join() must enable
    # this so every eager collective runs a coordination round in which a
    # joined process can answer "JOIN" (the reference is ALWAYS in this
    # mode — every tensor negotiates every background cycle,
    # controller.cc:63-358; here it is opt-in because the negotiation-free
    # cached fast path is the default). Single-process SPMD needs no knob.
    join_mode: bool = False
    # Host-core pinning: one core id per local rank, comma-separated
    # (reference: HOROVOD_THREAD_AFFINITY, common.cc:140-203).
    thread_affinity: Optional[str] = None
    # Unified telemetry (docs/metrics.md). Registry enable/disable is
    # env-only (HVD_TPU_METRICS=0 — read at import so instrumented hot
    # paths can bind no-op singletons before init() ever runs); these
    # knobs wire the EXPORT surfaces at init():
    # JSON-lines snapshot dump path (the timeline-writer-thread pattern).
    metrics_file: Optional[str] = None
    # Dump interval in seconds.
    metrics_interval_s: float = 10.0
    # Prometheus /metrics endpoint port: -1 = off, 0 = ephemeral.
    metrics_port: int = -1
    # metrics<->timeline bridge: histogram spans + step annotations also
    # emit jax.profiler Trace/StepTraceAnnotations.
    metrics_trace_bridge: bool = False
    # Flight recorder (docs/podmon.md): fixed-size ring of the last N
    # collective events per process, dumped with all-thread stacks as a
    # JSON "black box" on StallTimeoutError / MismatchError / fatal
    # non-finite abort / SIGUSR2 (and pushed to the controller KV when
    # reachable — HVD_TPU_FLIGHTREC_PUSH). The ring write is one lock +
    # dict store; disable only when that is too much.
    flightrec: bool = True
    flightrec_size: int = 256
    flightrec_dir: Optional[str] = None  # black-box dir (default ".")
    # Logging level.
    log_level: str = "warning"
    # Mesh axis name used for the data-parallel "ranks" axis.
    rank_axis: str = "hvd"
    # Force a CPU mesh of this many virtual devices (testing).
    force_cpu_devices: int = 0

    @classmethod
    def from_env(cls) -> "Config":
        c = cls()
        c.fusion_threshold_bytes = _env_int(
            "FUSION_THRESHOLD", cls.fusion_threshold_bytes)
        c.cache_capacity = _env_int("CACHE_CAPACITY", cls.cache_capacity)
        c.hierarchical_allreduce = _env_bool("HIERARCHICAL_ALLREDUCE", False)
        c.hierarchical_allgather = _env_bool("HIERARCHICAL_ALLGATHER", False)
        c.stall_check_time_seconds = _env_float(
            "STALL_CHECK_TIME_SECONDS", cls.stall_check_time_seconds)
        c.stall_shutdown_time_seconds = _env_float(
            "STALL_SHUTDOWN_TIME_SECONDS", cls.stall_shutdown_time_seconds)
        c.stall_check_disable = _env_bool("STALL_CHECK_DISABLE", False)
        c.stall_fatal = _env("STALL_FATAL")
        c.nonfinite_policy = _env("NONFINITE_POLICY")
        c.diverge_check_steps = _env_int("DIVERGE_CHECK_STEPS", 0)
        c.diverge_policy = _env("DIVERGE_POLICY", "warn") or "warn"
        c.checkpoint_verify = _env_bool("CHECKPOINT_VERIFY", True)
        c.timeline_filename = _env("TIMELINE")
        c.timeline_mark_cycles = _env_bool("TIMELINE_MARK_CYCLES", False)
        c.autotune = _env_bool("AUTOTUNE", False)
        c.autotune_log = _env("AUTOTUNE_LOG")
        c.autotune_warmup_samples = _env_int(
            "AUTOTUNE_WARMUP_SAMPLES", cls.autotune_warmup_samples)
        c.autotune_steps_per_sample = _env_int(
            "AUTOTUNE_STEPS_PER_SAMPLE", cls.autotune_steps_per_sample)
        c.route = _env("ROUTE")
        c.mesh_shape = _env("MESH_SHAPE")
        c.parallel = _env("PARALLEL")
        c.pp_wire = _env("PP_WIRE")
        c.pp_stages = _env_int("PP_STAGES", cls.pp_stages)
        c.tp = _env_int("TP", cls.tp)
        c.seq_wire = _env("SEQ_WIRE")
        c.seq_parallel = _env_int("SEQ_PARALLEL", cls.seq_parallel)
        c.seq_impl = _env("SEQ_IMPL", cls.seq_impl) or cls.seq_impl
        c.adasum_scalar_dtype = _env(
            "ADASUM_SCALAR_DTYPE", cls.adasum_scalar_dtype) or "float32"
        c.compression_dtype = _env("COMPRESSION_DTYPE")
        c.compression = _env("COMPRESSION")
        c.quantize_min_bucket_bytes = _env_int(
            "QUANTIZE_MIN_BYTES", cls.quantize_min_bucket_bytes)
        c.moe_wire = _env("MOE_WIRE")
        c.moe_overlap_chunks = _env_int("MOE_OVERLAP_CHUNKS",
                                        cls.moe_overlap_chunks)
        c.moe_capacity_factor = _env_float("MOE_CAPACITY_FACTOR",
                                           cls.moe_capacity_factor)
        c.accum_steps = _env_int("ACCUM_STEPS", cls.accum_steps)
        c.remat_policy = _env("REMAT_POLICY")
        c.prefetch = _env("PREFETCH")
        c.auto_shard_threshold_bytes = _env_int(
            "AUTO_SHARD_THRESHOLD", cls.auto_shard_threshold_bytes)
        c.zero_stage = _env_int("ZERO_STAGE", cls.zero_stage)
        c.elastic = _env_bool("ELASTIC", False)
        c.autoscale = _env_bool("AUTOSCALE", False)
        c.autoscale_policy = _env("AUTOSCALE_POLICY")
        c.autoscale_log = _env("AUTOSCALE_LOG")
        c.join_mode = _env_bool("JOIN_MODE", False)
        c.thread_affinity = _env("THREAD_AFFINITY")
        c.metrics_file = _env("METRICS_FILE")
        c.metrics_interval_s = _env_float("METRICS_INTERVAL_S",
                                          cls.metrics_interval_s)
        c.metrics_port = _env_int("METRICS_PORT", cls.metrics_port)
        c.metrics_trace_bridge = _env_bool("METRICS_TRACE", False)
        c.flightrec = _env_bool("FLIGHTREC", True)
        c.flightrec_size = _env_int("FLIGHTREC_SIZE", cls.flightrec_size)
        c.flightrec_dir = _env("FLIGHTREC_DIR")
        c.log_level = _env("LOG_LEVEL", "warning") or "warning"
        c.rank_axis = _env("RANK_AXIS", cls.rank_axis) or cls.rank_axis
        c.force_cpu_devices = _env_int("FORCE_CPU_DEVICES", 0)
        return c


# -- runtime knob registry ---------------------------------------------------
#
# Knobs read at CALL time rather than resolved once into Config at
# init(): process identity the launcher exports per slot (PROC_ID,
# HOSTNAME), rendezvous wiring that must work before init, debug
# switches consulted lazily. Every name a `runtime_env()` read may
# serve is declared here EXACTLY once, so the registry stays auditable
# (tools/hvdlint rule `env-knob` forbids direct os.environ reads of
# HVD_TPU_* keys outside this module; rule `knob-doc` and
# check_parity cross-reference this table against docs/). A few names
# are ALSO Config fields — tools read them pre-init (mesh shape,
# compile cache), the Config field remains the init()-resolved form.
RUNTIME_KNOBS = {
    # Process identity (exported per slot by the launchers; the
    # virtual-identity convention for FORCE_LOCAL simulated worlds).
    "PROC_ID": "this process's rank identity",
    "NUM_PROC": "world size as launched",
    "LOCAL_RANK": "rank within the host",
    "LOCAL_SIZE": "processes on this host",
    "HOSTNAME": "host label for telemetry/attribution",
    "VIRTUAL_NUM_PROC": "simulated world size for FORCE_LOCAL workers",
    "COORDINATOR": "jax.distributed coordinator address",
    "SPARK_EPOCH": "elastic epoch the spark worker joined",
    # Rendezvous / elastic wiring (pre-init by construction).
    "RENDEZVOUS": "controller KV address host:port",
    "RENDEZVOUS_SECRET": "shared secret for the KV server",
    "RENDEZVOUS_RETRIES": "client retry budget for 5xx/conn errors",
    "RENDEZVOUS_WAIT_MAX_POLL_S": "wait() poll backoff cap",
    "ELASTIC_FORCE_LOCAL": "virtual multi-host elastic simulation",
    "ELASTIC_GRACE_SECS": "graceful-exit window before terminate",
    "ELASTIC_RESET_LIMIT": "max elastic resets before giving up",
    "DISCOVERY_DEBOUNCE": "identical scrapes before a host-set change",
    "BLACKLIST_TTL_S": "host blacklist TTL (strike-doubled)",
    "NIC_DISCOVERY": "probe NICs for the data-plane interface",
    # Telemetry switches read lazily by their subsystems.
    "METRICS": "registry enable (0 = shared NOOP singletons)",
    "METRICS_TRACE": "metrics<->jax.profiler trace bridge",
    "METRICS_DEBUG": "/debug/stacks + /debug/profile endpoints",
    "METRICS_ADVERTISE": "endpoint advertised to the pod aggregator",
    "POD_METRICS_ENDPOINTS": "static scrape endpoints for podmon",
    "POD_METRICS_INTERVAL_S": "driver-side scrape interval",
    "POD_REPLICA_SKEW_RATIO": "replica-stall gauge skew threshold",
    "FLIGHTREC": "flight-recorder enable",
    "FLIGHTREC_SIZE": "ring capacity (events)",
    "FLIGHTREC_DIR": "black-box dump directory",
    "FLIGHTREC_PUSH": "push black boxes to the controller KV",
    "FLIGHTREC_SIGNAL_GRACE_S": "driver wait after SIGUSR2 fan-out",
    "LOCKDEP": "runtime lock-order watchdog (common/lockdep.py)",
    # Fault injection / recovery bookkeeping.
    "FAULT_PLAN": "seeded fault-injection plan (JSON)",
    "FAULT_LOG": "JSON-lines injection log path",
    "RECOVERY_STATS_FILE": "at-exit recovery-counter dump path",
    # Subsystem toggles.
    "WIRE_FORMAT": "controller codec override (json = skip native)",
    "DISABLE_NATIVE": "skip the native acceleration library",
    "FLASH_ATTENTION": "pallas flash-attention kernel enable",
    "MAX_RETAINED_HANDLES": "eager-engine completed-handle cap",
    # Fleet digital twin (common/fleetsim.py, tools/fleetsim.py).
    "FLEETSIM_BASELINE_DIR": "banked decision-log baseline directory",
    "FLEETSIM_SEED": "default scenario seed for the fleetsim CLI",
    "FLEETSIM_TICK_CAP": "runaway guard: max virtual ticks per run",
    # Decision logs read by their subsystems at construction.
    "AUTOSCALE_LOG": "autoscale decision log (also a Config field)",
    "SERVE_LOG": "serve-controller decision log",
    "SERVE_PREFIX_CAP": "shared-prefix KV cache entry cap (0 disables)",
    "SERVE_SPEC_K": "speculative-decoding draft depth (0 disables)",
    "SERVE_TRACE": "request-span tracer enable (0 = shared no-op)",
    "SERVE_TRACE_DIR": "trace JSONL dump directory (unset = no dump)",
    "SERVE_TRACE_SIZE": "retained completed request-trace cap",
    "SERVE_BROWNOUT": "pin the brownout ladder level (operator lever)",
    "SERVE_CLASS_MIX": "bench overload-arm SLO class mix override",
    # Config-field twins read PRE-INIT by tools (bench/microbench):
    # the Config field stays the init()-resolved source of truth.
    "MESH_SHAPE": "mesh factorization override (also a Config field)",
    "FORCE_CPU_DEVICES": "virtual CPU mesh size (also a Config field)",
    "PP_STAGES": "pipeline stages for tools (also a Config field)",
    "TP": "tensor-parallel degree for tools (also a Config field)",
    "SEQ_WIRE": "sequence K/V exchange wire (also a Config field)",
    "SEQ_PARALLEL":
        "sequence-parallel degree for tools (also a Config field)",
    "SEQ_IMPL": "ring | ulysses attention impl (also a Config field)",
    "METRICS_PORT": "Prometheus endpoint port (also a Config field)",
}


def runtime_env(name: str, default: Optional[str] = None, *,
                required: bool = False) -> Optional[str]:
    """Read a registered call-time knob (raw string; call sites own
    their int()/float()/truthiness parsing so migration from direct
    ``os.environ`` reads is behavior-preserving). ``required=True``
    mirrors ``os.environ[...]`` — KeyError with the full name when
    unset. Unregistered names raise: a knob nobody declared is a knob
    the audits cannot see."""
    if name not in RUNTIME_KNOBS:
        raise KeyError(
            f"unregistered runtime knob {name!r}; declare it in "
            "config.RUNTIME_KNOBS (tools/hvdlint env-knob discipline)")
    key = "HVD_TPU_" + name
    if required:
        return os.environ[key]
    return os.environ.get(key, default)


def configure(**kwargs) -> Config:
    """Build a Config from env then apply keyword overrides."""
    c = Config.from_env()
    for k, v in kwargs.items():
        if not hasattr(c, k):
            raise ValueError(f"unknown config knob: {k}")
        setattr(c, k, v)
    return c
