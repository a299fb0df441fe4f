#!/usr/bin/env python
"""A driver for the levers no benchmark cell runs yet (mesh routes, ZeRO
stages, MoE, pipeline, sequence parallelism, serving), on synthetic data.
How fast the system is, is benchmark/run.py's to say, not this file's.

One process on the TPU; where JAX finds none it exits non-zero and
prints no metric. Prints ONE JSON line, e.g.:
  {"metric": "resnet50_images_per_sec_per_chip", "value": N,
   "unit": "img/s", "platform": "tpu"}
with its wall-clock rate, its counts (memory, wire bytes, metrics) and
its configuration.
"""

import argparse
import functools
import json
import os
import sys
import time

import numpy as np


def _log(msg):
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def _emit(payload):
    """The ONE JSON line the driver parses — always the last stdout line."""
    print(json.dumps(payload), flush=True)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch-size", type=int, default=0,
                   help="0 = per-model default (256 CNN, 8 BERT/GPT)")
    p.add_argument("--image-size", type=int, default=0,
                   help="0 = model's native size (224; 299 for inception3)")
    p.add_argument("--seq-len", type=int, default=512)
    p.add_argument("--num-warmup", type=int, default=3)
    p.add_argument("--num-iters", type=int, default=10)
    p.add_argument("--batches-per-iter", type=int, default=5)
    p.add_argument("--model", default="resnet50",
                   choices=["resnet50", "resnet101", "resnet152",
                            "vgg16", "vgg19", "inception3",
                            "vit_base", "bert_large", "bert_base",
                            "gpt_small", "gpt_medium", "gpt_tiny"])
    p.add_argument("--mesh-shape", default="",
                   help="train over a simulated RxC (or RxMxC) device "
                        "mesh with the topology-aware collective router "
                        "(docs/topology.md), e.g. 2x4. On a CPU run "
                        "the mesh is simulated via "
                        "--xla_force_host_platform_device_count. "
                        "Routing mode + per-axis wire mix land in the "
                        "BENCH json")
    p.add_argument("--route", default="staged_int8",
                   choices=["staged", "staged_int8", "adasum",
                            "adasum_int8"],
                   help="routing/reduction mode for --mesh-shape: "
                        "staged (fp32 per-axis RS/AG), staged_int8 "
                        "(int8 on the slow cross hop), adasum "
                        "(hierarchical Adasum across the cross axis), "
                        "adasum_int8 (Adasum with int8 exchange)")
    p.add_argument("--compression", default="none",
                   choices=["none", "bf16", "int8_ef"],
                   help="gradient-reduction wire format on the "
                        "DistributedOptimizer: bf16 cast (2x fewer "
                        "bytes) or the reduce-safe int8 quantized "
                        "allreduce with error feedback (4x; "
                        "docs/compression.md)")
    p.add_argument("--guard", choices=["off", "on"], default="off",
                   help="training-integrity guard A/B "
                        "(docs/integrity.md): 'on' arms the non-finite "
                        "gradient guard (nonfinite_policy=skip_step — "
                        "one extra scalar min-allreduce + lax.cond per "
                        "step) on the DistributedOptimizer and records "
                        "the measured overhead vs an unguarded arm "
                        "into the BENCH json (expected <2%%)")
    p.add_argument("--remat", action="store_true",
                   help="per-layer activation recomputation on the GPT "
                        "models (long-context HBM relief)")
    p.add_argument("--moe", default="",
                   help="GPT-MoE arm (docs/moe.md): "
                        "'num_experts[,capacity_factor]' (e.g. 8,1.25) "
                        "swaps every decoder layer's dense MLP for the "
                        "expert-parallel MoE FFN — GShard top-2 gating "
                        "+ alltoall dispatch over the rank axis (or "
                        "the --mesh-shape route mesh). Drop-rate / "
                        "expert-load / dispatch-byte fields land in "
                        "the BENCH json. GPT models only")
    p.add_argument("--moe-wire", default="",
                   choices=["", "none", "bf16", "int8", "auto"],
                   help="dispatch/combine alltoall payload format for "
                        "--moe ('' = HVD_TPU_MOE_WIRE or none): bf16 "
                        "cast (2x fewer bytes), block-scaled int8 "
                        "(~4x), or auto (size-thresholded). Under "
                        "--mesh-shape the format applies to the SLOW "
                        "cross axis of the per-axis mesh_alltoall "
                        "plan; fast axes stay exact")
    p.add_argument("--moe-overlap", type=int, default=0,
                   help="capacity-dim pipelining depth for --moe "
                        "(0 = HVD_TPU_MOE_OVERLAP_CHUNKS or 1): "
                        "dispatch-alltoall of chunk k+1 overlaps "
                        "expert-FFN compute of chunk k via "
                        "optimization_barrier chaining")
    p.add_argument("--moe-router-noise", type=float, default=1.0,
                   help="noisy-gating jitter std for --moe (Shazeer et "
                        "al. 2017): an UNTRAINED router's init bias "
                        "otherwise overflows capacity from step 0 "
                        "(~13%% drops measured at capacity 1.25), "
                        "charging the bench's drop-rate to init "
                        "artifacts instead of real load. 0 disables "
                        "(docs/moe.md runbook)")
    p.add_argument("--accum", type=int, default=1,
                   help="scan-based gradient accumulation: split the "
                        "per-rank batch into this many microbatches "
                        "under lax.scan (hvd accum_steps=; one "
                        "collective round per EFFECTIVE step; "
                        "docs/performance.md MFU playbook)")
    p.add_argument("--remat-policy", default="none",
                   choices=["none", "full", "dots", "dots_no_batch"],
                   help="jax.checkpoint policy for the microbatch loss "
                        "under --accum (tuned jointly with it: remat "
                        "frees the activation memory accumulation "
                        "needs)")
    p.add_argument("--prefetch", default="",
                   choices=["", "off", "single", "double"],
                   help="feed the step through the device-infeed "
                        "pipeline instead of static device-resident "
                        "args: off = per-step blocking host->device "
                        "placement (the host tax on the timed path), "
                        "single = one batch staged ahead, double = "
                        "background-thread double-buffered "
                        "hvd.DeviceInfeed. Infeed wait lands in the "
                        "BENCH json. Default '' keeps the legacy "
                        "static-args loop ('' != off: off measures the "
                        "transfer, '' excludes it)")
    p.add_argument("--pipeline-stages", type=int, default=0,
                   help="pipeline-parallel stages for the gpt_* models "
                        "(docs/pipeline.md): decoder layers split into "
                        "N stages on a pp mesh axis, trained under the "
                        "scan-based 1F1B schedule; 0 consults "
                        "HVD_TPU_PP_STAGES (1 = off)")
    p.add_argument("--tp", type=int, default=0,
                   help="tensor-parallel width for the gpt_* models: "
                        "sharded-head attention + column/row-parallel "
                        "MLP over a tp mesh axis; 0 consults "
                        "HVD_TPU_TP (1 = off)")
    p.add_argument("--pp-wire", default="",
                   choices=["", "none", "bf16", "int8"],
                   help="stage-boundary activation/cotangent wire "
                        "format for the pipeline schedule (int8 = "
                        "block-scaled with straight-through VJP); "
                        "empty consults HVD_TPU_PP_WIRE")
    p.add_argument("--seq-parallel", type=int, default=0,
                   help="sequence-parallel width for the gpt_* models "
                        "(docs/sequence.md): the context is sharded "
                        "over an sp mesh axis (per-rank activation "
                        "bytes shrink ~linearly with the width) and "
                        "attention exchanges K/V over wired ring hops "
                        "or Ulysses head-scatter alltoalls; 0 consults "
                        "HVD_TPU_SEQ_PARALLEL (1 = off)")
    p.add_argument("--seq-impl", default="",
                   choices=["", "ring", "ulysses"],
                   help="attention exchange for --seq-parallel: ring = "
                        "striped causal ring over wired ppermute K/V "
                        "hops, ulysses = head-scatter alltoall (needs "
                        "heads %% sp == 0); empty consults "
                        "HVD_TPU_SEQ_IMPL (default ring)")
    p.add_argument("--seq-wire", default="",
                   choices=["", "none", "bf16", "int8"],
                   help="sp-axis exchange wire format for "
                        "--seq-parallel (int8 = block-scaled with "
                        "straight-through VJP, ~4x fewer K/V bytes; "
                        "hvd_tpu_seq_kv_bytes_total records the mix); "
                        "empty consults HVD_TPU_SEQ_WIRE")
    p.add_argument("--ep", type=int, default=0,
                   help="expert-parallel width for the --moe arm under "
                        "--pipeline-stages (docs/moe.md): the expert "
                        "bank dispatches over a dedicated ep mesh axis "
                        "INSIDE each pipeline stage (pp x ep on one "
                        "mesh); 0 = no ep axis (flat --moe dispatches "
                        "over the whole rank axis)")
    p.add_argument("--zero-stage", default="auto",
                   choices=["auto", "0", "1", "2", "3"],
                   help="ZeRO stage for the optimizer (docs/zero.md): "
                        "0 replicated, 1 sharded optimizer state, 2 + "
                        "sharded gradient accumulation, 3 + sharded "
                        "params with gather-on-demand. 'auto' consults "
                        "HVD_TPU_ZERO_STAGE, then the legacy "
                        "--shard-update heuristic (stage 1). Stages "
                        "2/3 are gpt_* models only. Every record "
                        "carries a 'memory' block with the per-rank "
                        "at-rest/peak state bytes the stage implies")
    p.add_argument("--shard-update", default="auto",
                   choices=["auto", "on", "off"],
                   help="weight-update sharding (ZeRO-1, "
                        "hvd.ShardedOptimizer): 'auto' shards when "
                        "hvd.should_shard_update says the replicated "
                        "params cross HVD_TPU_AUTO_SHARD_THRESHOLD "
                        "(arXiv:1909.09756), 'on' forces it (n>1), "
                        "'off' keeps the replicated update")
    p.add_argument("--no-s2d", action="store_true",
                   help="disable the space-to-depth ResNet stem "
                        "(measures the lever's value; default stem is "
                        "the MLPerf-style s2d form)")
    p.add_argument("--sync-per-iter", action="store_true",
                   help="legacy timing: force a host fetch of the loss "
                        "every batches-per-iter batches instead of once "
                        "at window end (serializes host and device)")
    p.add_argument("--serve", action="store_true",
                   help="inference-serving workload (docs/serve.md): "
                        "drive a multi-replica continuously-batched "
                        "GPT decode service over a seeded open-loop "
                        "Poisson trace; records workload='serve' with "
                        "p50/p99 latency, token throughput, batch "
                        "occupancy, and a repeat-identity event digest "
                        "into the BENCH json. GPT models only "
                        "(non-GPT --model falls back to gpt_tiny)")
    p.add_argument("--serve-replicas", type=int, default=2,
                   help="initial replica count for --serve (the SLO "
                        "controller may grow/drain from here)")
    p.add_argument("--serve-slots", type=int, default=4,
                   help="decode slots per replica for --serve "
                        "(HVD_TPU_SERVE_SLOTS overrides)")
    p.add_argument("--serve-kv", default="",
                   choices=["", "fp32", "int8"],
                   help="KV-cache storage for --serve ('' = "
                        "HVD_TPU_SERVE_KV_DTYPE or fp32): int8 is the "
                        "block-scaled ~4x-smaller cache; the record "
                        "carries kv_cache_bytes either way")
    p.add_argument("--serve-requests", type=int, default=80,
                   help="trace length for --serve")
    p.add_argument("--serve-rate", type=float, default=25.0,
                   help="open-loop arrival rate (requests/s, virtual "
                        "time) for --serve")
    p.add_argument("--serve-seed", type=int, default=42,
                   help="traffic seed for --serve (same seed => "
                        "byte-identical event sequence)")
    p.add_argument("--serve-arm", default="",
                   choices=["", "tp", "disagg", "prefix", "spec",
                            "overload"],
                   help="serving A/B arm for --serve (docs/serve.md): "
                        "'tp' shards each replica's decode over 2 "
                        "devices (Megatron head grid; an error with "
                        "fewer), 'disagg' splits the replicas into "
                        "prefill/decode pools with warm-KV handoffs, "
                        "'prefix' serves shared-system-prompt traffic "
                        "through the cross-request prefix cache, "
                        "'spec' adds speculative decoding "
                        "(HVD_TPU_SERVE_SPEC_K tokens/round, "
                        "self-draft), 'overload' drives a mixed-"
                        "tenancy ~2x-capacity storm through BOTH the "
                        "overload controls and an uncontrolled "
                        "baseline in one run and records the ON-vs-"
                        "OFF SLO/goodput deltas. The record carries "
                        "arm= either way")
    p.add_argument("--smoke", action="store_true",
                   help="tiny-model config (seconds on a CPU)")
    # The one way to a run off the chip: asked for by name. Its record
    # says platform "cpu".
    p.add_argument("--_platform", default="", choices=["", "cpu"],
                   help=argparse.SUPPRESS)
    args, _ = p.parse_known_args()

    if args.num_iters < 1 or args.batches_per_iter < 1:
        # ADVICE r4: zero iterations left the window-timing loop with no
        # batch to force (NameError) and the legacy path with mean([]).
        p.error("--num-iters and --batches-per-iter must be >= 1")
    if args.accum < 1:
        p.error("--accum must be >= 1")
    if args.moe and not args.model.startswith("gpt"):
        p.error("--moe requires a gpt_* model")
    if args.ep > 1 and not args.moe:
        p.error("--ep is the --moe expert-bank mesh axis; pass --moe")
    if args.moe:
        try:
            _parse_moe_spec(args.moe)
        except ValueError as e:
            p.error(str(e))

    import jax
    if args._platform == "cpu":
        # Before any backend init.
        jax.config.update("jax_platforms", "cpu")

    if args.mesh_shape:
        # Routing arm (docs/topology.md): export the shape so the
        # runtime's mesh_axes discovery agrees, and on a CPU run
        # force enough virtual devices to factor the mesh BEFORE the
        # backend initializes (init() appends
        # --xla_force_host_platform_device_count from this knob).
        os.environ["HVD_TPU_MESH_SHAPE"] = args.mesh_shape
        if args._platform == "cpu":
            from horovod_tpu.common.topology import parse_mesh_shape

            dims = parse_mesh_shape(args.mesh_shape)
            if dims:
                os.environ.setdefault(
                    "HVD_TPU_FORCE_CPU_DEVICES",
                    str(int(np.prod(dims))))
    from horovod_tpu.common.config import runtime_env

    pp_req = args.pipeline_stages \
        or int(runtime_env("PP_STAGES", "1") or 1)
    tp_req = args.tp or int(runtime_env("TP", "1") or 1)
    sp_req = args.seq_parallel \
        or int(runtime_env("SEQ_PARALLEL", "1") or 1)
    ep_req = args.ep if args.moe else 0
    per = max(pp_req, 1) * max(tp_req, 1) * max(sp_req, 1) \
        * max(ep_req, 1)
    if per > 1 and args._platform == "cpu":
        # Hybrid pp/tp/sp/ep arm on a CPU run (flags or the
        # HVD_TPU_PP_STAGES/HVD_TPU_TP/HVD_TPU_SEQ_PARALLEL knobs):
        # force enough virtual devices that dp x pp x ep x sp x tp
        # factors the world — the test tier's 8 when the block fits,
        # else exactly the block (dp=1).
        os.environ.setdefault("HVD_TPU_FORCE_CPU_DEVICES",
                              str(per * max(1, 8 // per)))

    import horovod_tpu as hvd

    hvd.init()
    platform = jax.devices()[0].platform
    n = hvd.size()
    _log(f"initialized: platform={platform} "
         f"device_kind={jax.devices()[0].device_kind} n={n}")
    if platform != "tpu" and args._platform != "cpu":
        # JAX falls to the CPU without a word when the TPU does not
        # come up. A benchmark number from there is not a slower
        # number, it is a different one.
        _log(f"no TPU: JAX runs on {platform!r}; nothing measured")
        return 1
    if args.serve and args.serve_arm == "tp" and jax.device_count() < 2:
        p.error("--serve-arm tp shards each replica's decode over 2 "
                f"devices; JAX has {jax.device_count()}")

    if args.serve:
        # Serving workload (docs/serve.md): scheduling + latency, its
        # own record shape.
        result = _run_serve_benchmark(args)
        result["platform"] = platform
        if args.smoke:
            result["smoke"] = True
        _emit(result)
        return

    if args.smoke:
        args.batch_size = args.batch_size or 4 * n
        args.image_size = args.image_size or 64
        args.seq_len = min(args.seq_len, 128)
        args.num_iters = min(args.num_iters, 3)
        args.batches_per_iter = min(args.batches_per_iter, 2)

    result = _run_benchmark(args, n)
    result["platform"] = platform
    if args.smoke:
        result["smoke"] = True
    _emit(result)


def _parse_moe_spec(spec):
    """'num_experts[,capacity_factor]' -> (int, float | None); raises
    ValueError with the offending text (argparse-friendly)."""
    parts = [s.strip() for s in str(spec).split(",") if s.strip()]
    if not parts or len(parts) > 2:
        raise ValueError(f"--moe expects 'experts[,capacity]', got "
                         f"{spec!r}")
    try:
        experts = int(parts[0])
        cf = float(parts[1]) if len(parts) == 2 else None
    except ValueError:
        raise ValueError(f"--moe expects 'experts[,capacity]', got "
                         f"{spec!r}") from None
    if experts < 1 or (cf is not None and cf <= 0):
        raise ValueError(f"--moe values must be positive, got {spec!r}")
    return experts, cf


def _moe_config(args, n):
    """Resolved GPT-MoE arm config (model kwargs + record fields) or
    None. Defaults fall back to the HVD_TPU_MOE_* knobs; under
    --mesh-shape the dispatch rides a mesh_alltoall plan over the
    routing mesh's axes with the --moe-wire format on the SLOW axis."""
    if not args.moe:
        return None
    cached = getattr(args, "_moe_cfg", "unset")
    if cached != "unset":
        return cached
    from horovod_tpu.common import basics

    cfg = basics.context().config
    experts, cf = _parse_moe_spec(args.moe)
    if cf is None:
        cf = cfg.moe_capacity_factor
    wire = args.moe_wire or cfg.moe_wire or "none"
    overlap = args.moe_overlap or cfg.moe_overlap_chunks or 1
    rt = _routing(args)
    axis, route = None, None
    if rt is not None:
        axes = list(rt["plan"].axis_names)  # fast first
        # Slow-axis wire of the mesh_alltoall plan; "auto" means
        # compress-where-the-slow-bytes-are, i.e. int8 on the cross hop
        # (the bench slabs sit far above the size threshold).
        slow = {"bf16": "bf16", "int8": "int8",
                "auto": "int8"}.get(wire, "none")
        route = ",".join([f"{a}:none" for a in axes[:-1]]
                         + [f"{axes[-1]}:{slow}"])
    elif n > 1:
        import horovod_tpu as hvd

        axis = hvd.rank_axis()
    if experts % max(n, 1):
        _log(f"--moe {experts} experts do not divide over {n} ranks; "
             f"raising to {-(-experts // n) * n}")
        experts = -(-experts // n) * n
    out = {"experts": experts, "capacity_factor": cf, "wire": wire,
           "overlap_chunks": int(overlap), "axis": axis, "route": route,
           "router_noise": float(args.moe_router_noise)}
    args._moe_cfg = out
    return out


def _routing(args):
    """--mesh-shape routing config: {"mesh", "axes", "plan", "op",
    "describe"} or None (flat axis). The mesh itself comes from the
    RUNTIME's own discovery (hvd.route_mesh()/mesh_axes() — the worker
    exports HVD_TPU_MESH_SHAPE before init), so bench can never drift
    from the axis names the router expects; a shape that doesn't factor
    the live device count falls back to flat with a log line rather
    than failing the run. Memoized on the args namespace: the config is
    consulted by both the model setup and the JSON record, and
    rebuilding would double-log the fallback."""
    if not args.mesh_shape:
        return None
    cached = getattr(args, "_routing_cfg", "unset")
    if cached != "unset":
        return cached
    import horovod_tpu as hvd
    from horovod_tpu.ops.collectives import WirePlan

    rmesh = hvd.route_mesh()
    axes = hvd.mesh_axes()
    if rmesh is None or axes is None or len(axes) < 2:
        _log(f"mesh shape {args.mesh_shape!r} does not factor the live "
             "device count into a supported multi-axis mesh; using the "
             "flat axis")
        args._routing_cfg = None
        return None
    fast_first = [a.name for a in axes]  # mesh_axes is fast-first
    cross_wire = "int8" if args.route.endswith("int8") else "none"
    plan = WirePlan.parse(
        ",".join([f"{a}:none" for a in fast_first[:-1]]
                 + [f"{fast_first[-1]}:{cross_wire}"]))
    op = hvd.Adasum if args.route.startswith("adasum") else hvd.Average
    args._routing_cfg = {
        "mesh": rmesh, "axes": tuple(rmesh.axis_names),
        "plan": plan, "op": op,
        "describe": f"{args.route}[{plan.describe()}]"}
    return args._routing_cfg


def _route_kwargs(rt):
    """DistributedOptimizer kwargs for a _routing() config (one place
    to extend when the route grows more optimizer knobs)."""
    return {"route": rt["plan"], "op": rt["op"]} if rt else {}


def _parallel_config(args, n):
    """--pipeline-stages/--tp/--seq-parallel/--ep hybrid-mesh config
    (docs/pipeline.md, docs/sequence.md): {"spec", "mesh", "dp", "pp",
    "tp", "sp", "ep", "wire", "seq_impl", "seq_wire"} or None (flat
    arm). Flags win; unset flags consult the HVD_TPU_PP_STAGES /
    HVD_TPU_TP / HVD_TPU_SEQ_* / HVD_TPU_PP_WIRE config knobs. A shape
    that does not factor the live device count (or a non-gpt model)
    falls back to the flat arm with a log line rather than failing the
    run. Memoized on the args namespace — consulted by the model setup
    AND the JSON record."""
    cached = getattr(args, "_parallel_cfg", "unset")
    if cached != "unset":
        return cached
    from horovod_tpu.common import basics

    cfg = basics.context().config if basics.is_initialized() else None
    pp = args.pipeline_stages or (cfg.pp_stages if cfg else 1)
    tp = args.tp or (cfg.tp if cfg else 1)
    sp = args.seq_parallel or (cfg.seq_parallel if cfg else 1)
    ep = (args.ep or 1) if args.moe else 1
    wire = args.pp_wire or (cfg.pp_wire if cfg else None) or "none"
    seq_impl = args.seq_impl or (cfg.seq_impl if cfg else None) \
        or "ring"
    seq_wire = args.seq_wire or (cfg.seq_wire if cfg else None) \
        or "none"
    if pp <= 1 and tp <= 1 and sp <= 1 and ep <= 1:
        args._parallel_cfg = None
        return None
    layers, heads = None, None
    if args.model.startswith("gpt"):
        from horovod_tpu.models import gpt_medium, gpt_small, gpt_tiny

        factory = {"gpt_tiny": gpt_tiny, "gpt_small": gpt_small,
                   "gpt_medium": gpt_medium}.get(args.model)
        if factory is not None:
            # Module construction is a dataclass build (no params) —
            # the geometry stays single-sourced in models/gpt.py.
            layers = factory().num_layers
            heads = factory().num_heads
    block = max(pp, 1) * max(tp, 1) * max(sp, 1) * max(ep, 1)
    why = None
    if not args.model.startswith("gpt"):
        why = "hybrid pp/tp/sp/ep arms are wired for the gpt_* models"
    elif n % block:
        why = (f"pp={pp} x tp={tp} x sp={sp} x ep={ep} does not "
               f"factor the {n}-device world")
    elif layers is not None and pp > 1 and layers % pp:
        why = (f"{args.model}'s {layers} decoder layers do not divide "
               f"into pp={pp} stages")
    elif sp > 1 and args.seq_len % sp:
        why = (f"seq_len {args.seq_len} does not divide over sp={sp} "
               "sequence shards")
    elif sp > 1 and seq_impl == "ulysses" and heads is not None \
            and heads % sp:
        why = (f"{args.model}'s {heads} heads do not scatter over "
               f"sp={sp} (ulysses needs heads %% sp == 0; ring has no "
               "head constraint — docs/sequence.md)")
    elif args.mesh_shape:
        why = ("--mesh-shape routing and the hybrid parallel flags "
               "are separate arms (the hybrid mesh carries its own dp "
               "route)")
    if why is not None:
        _log(f"--pipeline-stages/--tp/--seq-parallel/--ep ignored: "
             f"{why}; using the flat arm")
        args._parallel_cfg = None
        return None
    from horovod_tpu.parallel.spec import ParallelSpec

    # Slow -> fast placement (parallel/mesh.AXIS_ORDER): dp outermost,
    # then pp / ep, with sp and tp innermost on the fastest links.
    dims = {"dp": n // block}
    if pp > 1:
        dims["pp"] = pp
    if ep > 1:
        dims["ep"] = ep
    if sp > 1:
        dims["sp"] = sp
    if tp > 1:
        dims["tp"] = tp
    spec = ParallelSpec.resolve(dims)
    args._parallel_cfg = {
        "spec": spec, "mesh": spec.mesh(), "dp": dims["dp"], "pp": pp,
        "tp": tp, "sp": sp, "ep": ep, "wire": wire,
        "seq_impl": seq_impl, "seq_wire": seq_wire}
    return args._parallel_cfg


def _guard_policy(args):
    """--guard on → the skip_step non-finite guard on the optimizer
    (docs/integrity.md); off → explicit "off" so a stray
    HVD_TPU_NONFINITE_POLICY in the environment can't skew the A/B."""
    return "skip_step" if args.guard == "on" else "off"


def _shard_decision(args, params, n) -> bool:
    """Whether this run uses the ZeRO-1 sharded update
    (hvd.ShardedOptimizer; docs/performance.md). 'auto' consults the
    hvd.should_shard_update heuristic — replicated params at least
    HVD_TPU_AUTO_SHARD_THRESHOLD bytes and n > 1; incompatible arms
    (single rank, Adasum routing) log and fall back to replicated."""
    import horovod_tpu as hvd

    if args.shard_update == "off":
        return False
    why = None
    if n <= 1:
        why = "single-rank world"
    elif args.route.startswith("adasum") and args.mesh_shape:
        why = "Adasum routing (sharded update reduces SUM/AVERAGE only)"
    if why is not None:
        if args.shard_update == "on":
            _log(f"--shard-update on ignored: {why}")
        return False
    if args.shard_update == "on":
        return True
    return hvd.should_shard_update(params, size=n)


def _zero_stage_decision(args, params, n) -> int:
    """Which ZeRO stage this arm runs (docs/zero.md). Explicit
    --zero-stage wins; 'auto' consults the HVD_TPU_ZERO_STAGE config
    knob, then the legacy --shard-update heuristic (stage 1).
    Incompatible arms (single rank, Adasum routing; stages 2/3 on
    non-GPT models or --moe) log and fall back."""
    stage = None
    if args.zero_stage != "auto":
        stage = int(args.zero_stage)
    else:
        from horovod_tpu.common import basics

        cfg = basics.context().config.zero_stage \
            if basics.is_initialized() else 0
        if cfg:
            stage = int(cfg)
    if stage is None:
        return 1 if _shard_decision(args, params, n) else 0
    if stage == 0:
        return 0
    why = None
    if n <= 1:
        why = "single-rank world"
    elif args.route.startswith("adasum") and args.mesh_shape:
        why = "Adasum routing (sharded update reduces SUM/AVERAGE only)"
    elif stage >= 2 and not args.model.startswith("gpt"):
        why = f"stage {stage} is wired for gpt_* models only here"
    elif stage >= 3 and args.moe:
        why = "stage 3 + --moe (sharded expert storage is a named " \
              "follow-up)"
    if why is not None:
        _log(f"--zero-stage {stage} ignored: {why}; falling back to "
             "the replicated arm")
        return 0
    return stage


def _make_tx(args, params, n, inner):
    """The optimizer for a bench arm: replicated DistributedOptimizer
    (stage 0) or the ZeRO surface at the decided stage — stage 1 keeps
    the historical ShardedOptimizer (identical semantics), stages 2/3
    build hvd.ZeroOptimizer (docs/zero.md). Returns (tx, stage)."""
    import horovod_tpu as hvd

    rt = _routing(args)
    stage = _zero_stage_decision(args, params, n)
    _ARM["sharded"] = stage
    if stage >= 2:
        tx = hvd.ZeroOptimizer(
            inner, zero_stage=stage, axis_name=hvd.rank_axis(),
            compression=args.compression,
            nonfinite_policy=_guard_policy(args),
            accum_steps=args.accum, remat_policy=args.remat_policy,
            **({"route": rt["plan"]} if rt else {}))
    elif stage == 1:
        tx = hvd.ShardedOptimizer(
            inner, axis_name=hvd.rank_axis(),
            compression=args.compression,
            nonfinite_policy=_guard_policy(args),
            accum_steps=args.accum, remat_policy=args.remat_policy,
            **({"route": rt["plan"]} if rt else {}))
    else:
        tx = hvd.DistributedOptimizer(
            inner, axis_name=hvd.rank_axis(),
            compression=args.compression,
            nonfinite_policy=_guard_policy(args),
            accum_steps=args.accum, remat_policy=args.remat_policy,
            **_route_kwargs(rt))
    _ARM["memory"] = _memory_block(params, inner, stage, n, args.accum)
    return tx, stage


def _memory_block(params, inner, stage, n, accum):
    """The BENCH ``memory`` block (docs/zero.md): per-rank at-rest and
    peak state bytes COMPUTED FROM THE SHARDINGS the stage implies —
    params, gradient accumulator, inner optimizer state — so the
    ZeRO-2/3 win is a recorded number, not an anecdote. eval_shape
    only; no arrays are built."""
    import jax

    import numpy as np

    def tree_bytes(t):
        return int(sum(int(np.prod(l.shape)) * jnp_dtype_size(l)
                       for l in jax.tree.leaves(t)))

    def jnp_dtype_size(l):
        import jax.numpy as jnp

        return jnp.dtype(l.dtype).itemsize

    pb = tree_bytes(params)
    try:
        ob = tree_bytes(jax.eval_shape(inner.init, params))
    except Exception:  # noqa: BLE001 — memory block must never fail it
        ob = 0
    shard = n if (stage >= 1 and n > 1) else 1
    pshard = n if (stage >= 3 and n > 1) else 1
    gshard = n if (stage >= 2 and n > 1) else 1
    # Gradients: backprop's transient output is one full tree on every
    # stage; the ACCUMULATOR (what persists across microbatches) is
    # what the stages shard. accum==1 carries no accumulator.
    grad_accum = 0 if accum <= 1 else pb // gshard
    at_rest = {"params": pb // pshard, "grad_accum": grad_accum,
               "opt_state": ob // shard}
    peak = {"params": pb,  # stage 3's transient full gather
            "grads": pb,   # one microbatch's backprop output
            "opt_state": ob // shard}
    return {
        "zero_stage": stage, "n_ranks": n,
        "replicated_total_bytes": pb + ob,
        "per_rank_at_rest": at_rest,
        "per_rank_at_rest_bytes": sum(at_rest.values()),
        "per_rank_peak": peak,
        "per_rank_peak_bytes": sum(peak.values()) + grad_accum,
    }


def _init_opt_state(tx, sharded, params, n, routing):
    """Optimizer state + its shard_map PartitionSpecs. The sharded
    state MUST be built inside an SPMD region (the 1/n shard shapes
    come from the bound axis), so it gets a one-shot jitted shard_map
    init program; replicated state keeps the host-side init."""
    import jax

    import horovod_tpu as hvd
    from jax.sharding import PartitionSpec as P

    if not sharded:
        return tx.init(params), P()
    from horovod_tpu.common import basics

    specs = tx.state_specs(params)
    mesh = routing["mesh"] if routing else basics.context().mesh
    init_fn = jax.jit(jax.shard_map(
        tx.init, mesh=mesh, in_specs=P(), out_specs=specs,
        check_vma=False))
    return init_fn(params), specs


def _setup(args, batch_size, n):
    if args.model.startswith("bert"):
        return _setup_bert(args, batch_size, n)
    if args.model.startswith("gpt"):
        return _setup_gpt(args, batch_size, n)
    return _setup_cnn(args, batch_size, n)


# infeed_pipeline generators created by _make_stepper during this
# benchmark invocation: the stepper's feed (backed by an infinite host
# iterator) never self-exhausts, and the guard A/B builds a SECOND
# stepper while the first's worker still pins depth+1 device-resident
# batches — so each _run_benchmark closes every feed it opened.
_FEEDS = []


def _run_serve_benchmark(args):
    """The --serve workload: a CPU/TPU multi-replica continuously
    batched GPT decode service driven by a seeded open-loop Poisson
    trace (docs/serve.md). Emits workload="serve" with p50/p99 latency
    (virtual time — deterministic), real token throughput (wall time),
    mean batch occupancy, the KV-cache byte accounting, and an
    event-digest fingerprint: two runs of the same seed/config must
    produce the same digest (the repeat-identity acceptance check)."""
    import hashlib

    import jax

    from horovod_tpu.models import gpt, init_kv_cache
    from horovod_tpu.serve import kvcache as kv_lib
    from horovod_tpu.serve.controller import SLOPolicy, ServeCluster
    from horovod_tpu.serve.engine import (engine_defaults_from_env,
                                          make_engine_factory)
    from horovod_tpu.serve.traffic import poisson_trace

    model_name = args.model if args.model.startswith("gpt") \
        else "gpt_tiny"
    if args.smoke:
        model_name = "gpt_tiny"
    model_fn = {"gpt_tiny": gpt.gpt_tiny, "gpt_small": gpt.gpt_small,
                "gpt_medium": gpt.gpt_medium}[model_name]
    model = model_fn()

    geometry = {"slots": args.serve_slots, "max_len": 64,
                "max_prompt_len": 16}
    geometry.update(engine_defaults_from_env())
    if args.serve_kv:
        geometry["kv_kind"] = args.serve_kv
    kv_kind = geometry.setdefault("kv_kind", "fp32")
    geometry["max_prompt_len"] = min(geometry["max_prompt_len"],
                                     geometry["max_len"])

    # --serve-arm (docs/serve.md): each arm flips exactly one serving
    # lever so the A/B against the stock run isolates it.
    arm = args.serve_arm
    factory_kw, trace_kw, roles = dict(geometry), {}, None
    prefix_cache = None
    spec_k = 0
    init_model = model
    if arm == "tp":
        from horovod_tpu.parallel.spec import ParallelSpec
        # Params init on the dense twin (identical tree — the
        # _DenseMaster contract); the tp model slices them in-trace
        # under shard_map.
        model = model_fn(tp_axis="tp")
        factory_kw["parallel"] = ParallelSpec.resolve({"tp": 2})
    elif arm == "disagg":
        roles = {"prefill": 1,
                 "decode": max(1, args.serve_replicas - 1)}
    elif arm == "prefix":
        from horovod_tpu.serve.prefix import (PrefixCache,
                                              prefix_cap_from_env)
        prefix_cache = PrefixCache(prefix_cap_from_env())
        factory_kw["prefix_cache"] = prefix_cache
        # Shared-system-prompt traffic: every prompt opens with the
        # same 8 tokens; the drawn lengths size the unique tails.
        shared = min(8, geometry["max_prompt_len"] - 2)
        trace_kw["shared_prefix_len"] = shared
        trace_kw["prompt_lens"] = tuple(
            n for n in (2, 4, geometry["max_prompt_len"] - shared)
            if n >= 1)
    elif arm == "spec":
        from horovod_tpu.common.config import runtime_env
        spec_k = int(runtime_env("SERVE_SPEC_K") or "4")
    elif arm == "overload":
        # Mixed-tenancy storm (docs/serve.md "Overload & tenancy"):
        # the SAME class-tagged trace — deadlines are stamped at
        # generation so both arms measure the identical SLO — runs
        # through the overload controls (admission gate + brownout
        # ladder + EDF classes) and through an uncontrolled FIFO
        # baseline, and the record carries the ON-vs-OFF deltas.
        from horovod_tpu.common.config import runtime_env
        overload_mix = [("latency", 0.5), ("throughput", 0.3),
                        ("batch", 0.2)]
        mix_raw = runtime_env("SERVE_CLASS_MIX") or ""
        if mix_raw:
            # HVD_TPU_SERVE_CLASS_MIX=latency=0.6,batch=0.4 overrides
            # the default tenancy mix (weights normalize in traffic).
            overload_mix = [(k, float(v)) for k, v in
                            (p.split("=") for p in mix_raw.split(",")
                             if p)]
        overload_pol = {
            "tick_interval_s": 0.1, "window": 8,
            "min_replicas": args.serve_replicas,
            "max_replicas": args.serve_replicas,
            "overload": True,
            "latency_deadline_s": 3.0, "throughput_deadline_s": 5.0,
            "admission_safety": 1.2,
            "brownout_enter_depth": 10, "brownout_exit_depth": 2,
            "brownout_enter_ticks": 2, "brownout_exit_ticks": 2,
            "brownout_clamp_tokens": 4,
        }
        trace_kw["class_mix"] = overload_mix
        trace_kw["class_deadlines"] = {
            "latency": overload_pol["latency_deadline_s"],
            "throughput": overload_pol["throughput_deadline_s"]}

    params = init_model.init(jax.random.PRNGKey(0),
                             np.zeros((1, 4), np.int32))
    if arm == "spec":
        # Self-draft (draft = target): the acceptance-rate UPPER BOUND
        # arm — a randomly initialized small draft would accept ~0 and
        # measure nothing; a real deployment plugs a distilled draft
        # into the same two kwargs.
        factory_kw.update(draft_model=model, draft_params=params,
                          spec_k=spec_k)
    factory = make_engine_factory(model, params, **factory_kw)
    requests = min(args.serve_requests, 20) if args.smoke \
        else args.serve_requests
    trace_kw.setdefault("prompt_lens",
                        (4, 8, geometry["max_prompt_len"]))
    trace = poisson_trace(
        seed=args.serve_seed, n_requests=requests,
        rate_rps=args.serve_rate,
        output_lens=(4, 8, 16, 32),
        vocab_size=model.vocab_size, **trace_kw)
    # Policy from env (HVD_TPU_SERVE_POLICY / HVD_TPU_SERVE_*): the
    # DEFAULT policy has every grow/shrink trigger off, so the stock
    # bench measures a fixed replica set — controller activity is an
    # explicit arm. The overload arm pins its own policy so the A/B
    # is self-contained (replicas fixed: no autoscale confound).
    policy = SLOPolicy.from_dict(overload_pol) \
        if arm == "overload" else SLOPolicy.from_env()
    cluster = ServeCluster(factory, policy=policy,
                           replicas=args.serve_replicas, step_s=0.05,
                           log_path="", roles=roles)
    _log(f"serve: {model_name} arm={arm or 'stock'} "
         f"replicas={args.serve_replicas} "
         f"slots={geometry['slots']} kv={kv_kind} "
         f"requests={requests} rate={args.serve_rate}/s")
    report = cluster.run(trace)

    digest = hashlib.sha256(json.dumps(
        {"events": [list(e) for e in report["events"]],
         "decisions": report["decisions"]},
        sort_keys=True).encode()).hexdigest()[:16]
    cache_bytes = kv_lib.cache_nbytes(init_kv_cache(
        model, geometry["slots"], geometry["max_len"], kind=kv_kind))
    fp32_bytes = kv_lib.cache_nbytes(init_kv_cache(
        model, geometry["slots"], geometry["max_len"], kind="fp32"))
    arm_fields = {}
    if roles is not None:
        arm_fields["handoffs"] = report["handoffs"]
    if prefix_cache is not None:
        arm_fields["prefix"] = prefix_cache.stats()
    if spec_k:
        arm_fields["spec"] = {
            "k": spec_k,
            "acceptance_rate": report["spec_acceptance_rate"],
        }
    if arm == "overload":
        # OFF arm: same trace (regenerated — Requests mutate in
        # flight), same stamped deadlines, overload controls off
        # (FIFO queue, admit everything, no brownout). Goodput =
        # SLO-bearing completions that met their stamped deadline;
        # batch is best-effort (no deadline, the tier brownout
        # sacrifices first) so it is reported separately rather than
        # counted as goodput in either arm.
        def _goodput(completed):
            ok = [r for r in completed
                  if r.deadline_s > 0 and r.latency_s is not None
                  and r.latency_s <= r.deadline_s]
            return {"requests": len(ok),
                    "tokens": sum(len(r.tokens) for r in ok),
                    "best_effort_completed": sum(
                        1 for r in completed if r.deadline_s <= 0)}

        off_pol = dict(overload_pol)
        off_pol["overload"] = False
        trace_off = poisson_trace(
            seed=args.serve_seed, n_requests=requests,
            rate_rps=args.serve_rate,
            output_lens=(4, 8, 16, 32),
            vocab_size=model.vocab_size, **trace_kw)
        cluster_off = ServeCluster(
            factory, policy=SLOPolicy.from_dict(off_pol),
            replicas=args.serve_replicas, step_s=0.05, log_path="")
        report_off = cluster_off.run(trace_off)
        by_class_off = {}
        for r in cluster_off.completed:
            if r.latency_s is not None:
                by_class_off.setdefault(
                    r.slo_class or "latency", []).append(r.latency_s)
        off_class_p99 = {
            cls: round(float(np.percentile(np.asarray(v), 99)), 6)
            for cls, v in sorted(by_class_off.items())}
        on_good = _goodput(cluster.completed)
        off_good = _goodput(cluster_off.completed)
        slo = overload_pol["latency_deadline_s"]
        on_lat = report["class_latency_p99_s"].get("latency", 0.0)
        off_lat = off_class_p99.get("latency", 0.0)
        arm_fields["overload"] = {
            "class_mix": dict(overload_mix),
            "latency_deadline_s": slo,
            "throughput_deadline_s":
                overload_pol["throughput_deadline_s"],
            "admission_safety": overload_pol["admission_safety"],
            "on": {
                "completed": report["completed"],
                "shed": report["shed"],
                "rejected": report["rejected"],
                "brownout_max_level": report["brownout_max_level"],
                "class_latency_p99_s": report["class_latency_p99_s"],
                "deadline_misses": report["deadline_misses"],
                "goodput": on_good,
            },
            "off": {
                "completed": report_off["completed"],
                "class_latency_p99_s": off_class_p99,
                "deadline_misses": report_off["deadline_misses"],
                "goodput": off_good,
            },
            "latency_p99_within_slo_on": bool(on_lat <= slo),
            "latency_p99_within_slo_off": bool(off_lat <= slo),
            "goodput_gain_x": round(
                on_good["requests"] / max(1, off_good["requests"]),
                2),
        }
        _log(f"serve: overload A/B latency-tier p99 ON={on_lat}s "
             f"OFF={off_lat}s (SLO {slo}s) goodput "
             f"ON={on_good['requests']} OFF={off_good['requests']}")
    return {
        "metric": f"{model_name}_serve_tokens_per_sec",
        "value": report["tokens_per_wall_s"],
        "unit": "tok/s",
        "workload": "serve",
        "arm": args.serve_arm,
        **arm_fields,
        "latency_p50_s": report["latency_p50_s"],
        "latency_p99_s": report["latency_p99_s"],
        # Per-phase percentiles + the goodput ledger (docs/serve.md
        # "Tracing & goodput"; goodput is {} with HVD_TPU_SERVE_TRACE=0).
        "ttft_p50_s": report["ttft_p50_s"],
        "ttft_p99_s": report["ttft_p99_s"],
        "tpot_p50_s": report["tpot_p50_s"],
        "tpot_p99_s": report["tpot_p99_s"],
        "queue_wait_p50_s": report["queue_wait_p50_s"],
        "queue_wait_p99_s": report["queue_wait_p99_s"],
        "goodput": report["goodput"],
        "tokens_per_virtual_s": report["tokens_per_virtual_s"],
        "mean_occupancy": report["mean_occupancy"],
        "prefill_tokens": report["prefill_tokens"],
        "completed": report["completed"],
        "dropped": report["dropped"],
        "deadline_misses": report["deadline_misses"],
        "decisions": len(report["decisions"]),
        "event_digest": digest,
        "kv": {
            "kind": kv_kind,
            "cache_bytes_per_replica": cache_bytes,
            "reduction_vs_fp32_x": round(fp32_bytes / cache_bytes, 2),
        },
        "config": {
            "model": model_name,
            "replicas": args.serve_replicas,
            "slots": geometry["slots"],
            "max_len": geometry["max_len"],
            "max_prompt_len": geometry["max_prompt_len"],
            "requests": requests,
            "rate_rps": args.serve_rate,
            "seed": args.serve_seed,
            "step_s": 0.05,
            "arm": args.serve_arm,
        },
        "config_note": (
            f"serve {model_name} arm={args.serve_arm or 'stock'} "
            f"r={args.serve_replicas} "
            f"slots={geometry['slots']} kv={kv_kind} "
            f"p99={report['latency_p99_s']}s "
            f"occ={report['mean_occupancy']}"),
    }


def _run_benchmark(args, n):
    try:
        return _run_benchmark_inner(args, n)
    finally:
        while _FEEDS:
            feed = _FEEDS.pop()
            try:
                feed.close()
            except Exception:  # noqa: BLE001 — result already computed
                pass


def _run_benchmark_inner(args, n):
    is_bert = args.model.startswith("bert")
    is_gpt = args.model.startswith("gpt")
    batch_size = args.batch_size or (8 if (is_bert or is_gpt) else 256)

    run_batch = _setup(args, batch_size, n)

    # Warmup (includes any compile the AOT path didn't already pay).
    import jax

    t0 = time.perf_counter()
    for i in range(args.num_warmup):
        _log(f"warmup step {i + 1}/{args.num_warmup} dispatching")
        jax.block_until_ready(run_batch())
    warmup_s = time.perf_counter() - t0
    _log(f"warmup done in {warmup_s:.1f}s (compile was "
         f"{_TIMINGS['compile_s']}s)")

    total_batches = args.num_iters * args.batches_per_iter
    iw_count0, iw_sum0 = _infeed_wait_totals()
    if args.sync_per_iter:
        # Legacy mode: one host fetch per iteration group. Serializes
        # host and device.
        rates = []
        for _ in range(args.num_iters):
            t0 = time.perf_counter()
            for _ in range(args.batches_per_iter):
                l = run_batch()
            jax.block_until_ready(l)
            rates.append(batch_size * args.batches_per_iter
                         / (time.perf_counter() - t0))
        val = float(np.mean(rates)) / n
        window_s = None
    else:
        # Steady-state window: dispatch every step async, wait ONCE
        # at the end. Each step's donated state feeds the next, so
        # the last loss is not ready before the whole chain has
        # executed — same completion guarantee as the per-iter
        # wait, none of the per-dispatch serialization.
        t0 = time.perf_counter()
        for _ in range(total_batches):
            l = run_batch()
        jax.block_until_ready(l)
        window_s = time.perf_counter() - t0
        val = batch_size * total_batches / window_s / n
    iw_count1, iw_sum1 = _infeed_wait_totals()

    # batch_size is the GLOBAL batch (sharded over n chips in spmd mode);
    # the metric is per-chip, so divide the measured global rate by n.
    result = {
        "metric": f"{args.model}_"
                  f"{'samples' if (is_bert or is_gpt) else 'images'}"
                  f"_per_sec_per_chip",
        "value": round(val, 2),
        "unit": "samples/s" if (is_bert or is_gpt) else "img/s",
        # Workload tag: a train record and a serve record have
        # different shapes (docs/serve.md).
        "workload": "train",
    }
    # Mandatory config record (VERDICT r3 weak #7): every number
    # carries the exact configuration that produced it, so records
    # from different rounds/batches can never be silently compared.
    image_size = None if (is_bert or is_gpt) else (
        args.image_size or (299 if args.model == "inception3" else 224))
    config = {
        "model": args.model,
        "global_batch": batch_size,
        "n_chips": n,
        "seq_len": args.seq_len if (is_bert or is_gpt) else None,
        "image_size": image_size,
        "s2d_stem": (not args.no_s2d)
        if args.model.startswith("resnet") else None,
        "timing": "per_iter_sync" if args.sync_per_iter
        else "window_single_fetch",
        "steps_timed": total_batches,
        "remat": bool(args.remat) if is_gpt else None,
        "compression": args.compression,
        "guard": args.guard,
        "mesh_shape": args.mesh_shape or None,
        "route": ((_routing(args) or {}).get("describe")
                  if args.mesh_shape else None),
        "accum": args.accum,
        "remat_policy": args.remat_policy,
        "prefetch": args.prefetch or None,
        "shard_update": bool(_ARM["sharded"]),
        "zero_stage": _ARM["sharded"],
        "moe": args.moe or None,
        "moe_wire": (_moe_config(args, n) or {}).get("wire")
        if args.moe else None,
        "moe_overlap": (_moe_config(args, n) or {}).get("overlap_chunks")
        if args.moe else None,
        # Hybrid dp x pp x tp arm (docs/pipeline.md): the resolved
        # spec + stage-boundary wire, so the per-axis byte mix in
        # metrics.activation_bytes_by_axis is self-describing.
        "parallel": ((_parallel_config(args, n) or {}).get("spec")
                     .describe()
                     if is_gpt and _parallel_config(args, n) else None),
        "pipeline_stages": ((_parallel_config(args, n) or {}).get("pp")
                            if is_gpt else None),
        "tp": ((_parallel_config(args, n) or {}).get("tp")
               if is_gpt else None),
        "pp_wire": ((_parallel_config(args, n) or {}).get("wire")
                    if is_gpt else None),
        # Sequence-parallel arm (docs/sequence.md): the sp width plus
        # the exchange impl/wire, so hvd_tpu_seq_kv_bytes_total and
        # the memory block's activation accounting are self-describing.
        "seq_parallel": ((_parallel_config(args, n) or {}).get("sp")
                         if is_gpt else None),
        "seq_impl": ((_parallel_config(args, n) or {}).get("seq_impl")
                     if is_gpt and ((_parallel_config(args, n) or {})
                                    .get("sp") or 1) > 1 else None),
        "seq_wire": ((_parallel_config(args, n) or {}).get("seq_wire")
                     if is_gpt and ((_parallel_config(args, n) or {})
                                    .get("sp") or 1) > 1 else None),
        "ep": ((_parallel_config(args, n) or {}).get("ep")
               if is_gpt else None),
    }
    if _ARM.get("memory"):
        # Sharding-derived per-rank state bytes (docs/zero.md): the
        # ZeRO A/B's acceptance number — per-rank AT-REST state bytes
        # (params + grad accumulator + opt state) must drop ≥3x from
        # stage 1 to stage 3 on the same model/mesh. (Peak includes
        # the transients — stage 3's full gather and one microbatch's
        # grads — which no stage can shard away.)
        result["memory"] = _ARM["memory"]
    moe_cfg = _moe_config(args, n) if is_gpt else None
    if moe_cfg:
        # The step output vector is [loss, dropped, frac, routed,
        # load x E] (global — psum-ed in-layer); publish the drop/load
        # gauges host-side and record the arm's health numbers the
        # acceptance criteria read (drop-rate, load balance, dispatch
        # bytes by wire from the alltoall byte family).
        vec = np.asarray(jax.device_get(l)).reshape(-1)
        e = moe_cfg["experts"]
        if vec.size >= 4 + e:
            from horovod_tpu.parallel import moe as moe_lib

            load = vec[4:4 + e]
            rec = moe_lib.record_moe_stats(
                {"dropped_tokens": vec[1], "dropped_frac": vec[2],
                 "expert_load": load})
            result["moe"] = {
                "experts": e,
                "capacity_factor": moe_cfg["capacity_factor"],
                "wire": moe_cfg["wire"],
                "route": moe_cfg["route"],
                "overlap_chunks": moe_cfg["overlap_chunks"],
                "router_noise": moe_cfg["router_noise"],
                "final_loss": round(float(vec[0]), 4),
                "dropped_frac": round(rec["dropped_frac"], 6),
                "load_max_over_mean": round(
                    float(load.max() / max(load.mean(), 1e-9)), 3),
            }
        else:
            # pp x ep arm (docs/moe.md): the 1F1B step carries a
            # scalar loss (the in-layer stats vector does not ride
            # the pipeline); the dispatch-byte mix still lands in
            # metrics.alltoall_bytes_by_axis under axis="ep".
            result["moe"] = {
                "experts": e,
                "capacity_factor": moe_cfg["capacity_factor"],
                "wire": moe_cfg["wire"],
                "route": moe_cfg["route"],
                "overlap_chunks": moe_cfg["overlap_chunks"],
                "router_noise": 0.0,
                "final_loss": round(float(vec[0]), 4),
                "stats": "in_layer_stats_not_carried_under_pipeline",
            }
    if args.prefetch:
        # Infeed-wait delta over the TIMED window only (warmup waits
        # excluded): how long the step loop blocked on the next device
        # batch — the host-overhead number the --prefetch A/B exists
        # to move (docs/performance.md MFU playbook).
        waited = max(iw_sum1 - iw_sum0, 0.0)
        nbatch = max(iw_count1 - iw_count0, 0)
        result["infeed"] = {
            "mode": args.prefetch,
            "wait_s": round(waited, 4),
            "wait_ms_per_batch": round(1000.0 * waited / nbatch, 3)
            if nbatch else None,
            "batches": nbatch,
        }
        if window_s is not None and window_s > 0:
            result["infeed"]["wait_pct_of_window"] = round(
                100.0 * waited / window_s, 1)
    if args.guard == "on":
        # Guard-overhead A/B (docs/integrity.md): rebuild the SAME
        # config without the guard and time a short window — the delta
        # prices the one extra scalar min-allreduce + lax.cond per
        # step. Target: report it; expected <2% of step time.
        import copy as copy_mod

        base_args = copy_mod.copy(args)
        base_args.guard = "off"
        base_run = _setup(base_args, batch_size, n)
        for _ in range(args.num_warmup):
            jax.block_until_ready(base_run())
        # SAME timing loop as the guarded measurement — mixing the
        # per-iter-sync and async-window styles would charge the loop
        # delta to the guard.
        if args.sync_per_iter:
            base_rates = []
            for _ in range(args.num_iters):
                t0 = time.perf_counter()
                for _ in range(args.batches_per_iter):
                    bl = base_run()
                jax.block_until_ready(bl)
                base_rates.append(batch_size * args.batches_per_iter
                                  / (time.perf_counter() - t0))
            base_val = float(np.mean(base_rates)) / n
        else:
            t0 = time.perf_counter()
            for _ in range(total_batches):
                bl = base_run()
            jax.block_until_ready(bl)
            base_val = batch_size * total_batches \
                / (time.perf_counter() - t0) / n
        overhead = (base_val / val - 1.0) * 100.0 if val else None
        result["guard"] = {
            "policy": "skip_step",
            "guarded_rate": round(val, 2),
            "unguarded_rate": round(base_val, 2),
            "overhead_pct": round(overhead, 2)
            if overhead is not None else None,
        }
    # Separate JSON fields so the driver can tell a slow MODEL from a
    # slow COMPILE (and so persistent-cache hits are visible: a warm
    # second attempt shows compile_s collapsing while the rate holds).
    if _TIMINGS["compile_s"] is not None:
        result["compile_s"] = round(_TIMINGS["compile_s"], 3)
    result["warmup_s"] = round(warmup_s, 3)
    result["config"] = config
    result["config_note"] = (
        f"{config['model']} gb={config['global_batch']} "
        f"n={config['n_chips']} "
        + (f"S={config['seq_len']}" if (is_bert or is_gpt)
           else f"px={config['image_size']}"))
    if window_s is not None:
        result["window_s"] = round(window_s, 3)

    mx = _metrics_summary()
    if mx:
        # WHY a round got faster, not just how fast: the wire-byte mix,
        # cache behavior, and fusion fill that produced this step time
        # (docs/metrics.md; hvd.metrics() is the full registry).
        result["metrics"] = mx
    return result


def _metrics_summary():
    """Condensed hvd.metrics() snapshot for the BENCH_*.json record:
    bytes-on-wire mix, eager cache hit rate, fusion fill efficiency."""
    try:
        import horovod_tpu as hvd

        snap = hvd.metrics()
    except Exception:  # noqa: BLE001 — telemetry must never fail a bench
        return None
    if not snap:
        return None

    def samples(name):
        return snap.get(name, {}).get("samples", [])

    out = {}
    # The allreduce byte family carries (wire, axis) labels: eager calls
    # stamp axis=flat, the mesh router stamps its per-axis plan (at
    # trace time). Aggregate by wire for the headline mix and keep the
    # per-axis split — the routing arm's whole point is WHICH axis the
    # bytes crossed.
    wire, by_axis = {}, {}
    for s in samples("hvd_tpu_allreduce_bytes_total"):
        if not s["value"]:
            continue
        w = s["labels"].get("wire", "?")
        ax = s["labels"].get("axis", "flat")
        wire[w] = wire.get(w, 0) + s["value"]
        by_axis.setdefault(ax, {})
        by_axis[ax][w] = by_axis[ax].get(w, 0) + s["value"]
    planned = {s["labels"].get("wire", "?"): s["value"]
               for s in samples("hvd_tpu_fusion_wire_bytes_total")
               if s["value"]}
    if wire:
        # Eager-path truth when the eager engine ran; in-jit steps only
        # leave the trace-time plan, so fall back to the planned mix.
        out["bytes_on_wire"] = wire
        out["bytes_basis"] = ("mesh_planned_per_compile"
                              if set(by_axis) - {"flat"} else "eager")
        if set(by_axis) - {"flat"}:
            out["bytes_by_axis"] = by_axis
    elif planned:
        out["bytes_on_wire"] = planned
        out["bytes_basis"] = "planned_per_compile"
    # Alltoall (MoE dispatch/combine) byte mix, same basis note as the
    # allreduce family: in-jit exchanges stamp at trace time (planned
    # per compile), eager calls per call on axis=flat.
    a2a_wire, a2a_axis = {}, {}
    for s in samples("hvd_tpu_alltoall_bytes_total"):
        if not s["value"]:
            continue
        w = s["labels"].get("wire", "?")
        ax = s["labels"].get("axis", "flat")
        a2a_wire[w] = a2a_wire.get(w, 0) + s["value"]
        a2a_axis.setdefault(ax, {})
        a2a_axis[ax][w] = a2a_axis[ax].get(w, 0) + s["value"]
    if a2a_wire:
        out["alltoall_bytes_on_wire"] = a2a_wire
        out["alltoall_bytes_by_axis"] = a2a_axis
    # Sequence-parallel K/V exchange bytes (docs/sequence.md): ring
    # hops / Ulysses head-scatter stamped at trace time by wire and
    # axis — the --seq-wire A/B's acceptance evidence (int8 must
    # strictly cut the sp-axis bytes vs the fp32 run).
    seq_wire_b, seq_axis_b = {}, {}
    for s in samples("hvd_tpu_seq_kv_bytes_total"):
        if not s["value"]:
            continue
        w = s["labels"].get("wire", "?")
        ax = s["labels"].get("axis", "sp")
        seq_wire_b[w] = seq_wire_b.get(w, 0) + s["value"]
        seq_axis_b.setdefault(ax, {})
        seq_axis_b[ax][w] = seq_axis_b[ax].get(w, 0) + s["value"]
    if seq_wire_b:
        out["seq_kv_bytes_on_wire"] = seq_wire_b
        out["seq_kv_bytes_by_axis"] = seq_axis_b
    # Pipeline stage-boundary sends (docs/pipeline.md): trace-time
    # planned bytes (ticks x payload) by wire and axis — activation
    # bytes must land ONLY on the pp axis; the per-axis split next to
    # bytes_by_axis is the hybrid arm's wire-mix evidence.
    act_wire, act_axis = {}, {}
    for s in samples("hvd_tpu_pipeline_activation_bytes_total"):
        if not s["value"]:
            continue
        w = s["labels"].get("wire", "?")
        ax = s["labels"].get("axis", "pp")
        act_wire[w] = act_wire.get(w, 0) + s["value"]
        act_axis.setdefault(ax, {})
        act_axis[ax][w] = act_axis[ax].get(w, 0) + s["value"]
    if act_wire:
        out["activation_bytes_on_wire"] = act_wire
        out["activation_bytes_by_axis"] = act_axis
    # ZeRO sharded-collective bytes (docs/zero.md): the gradient
    # reduce-scatter / param+update all-gathers by kind, wire and axis
    # — under the hybrid arm this is the gradient half of the per-axis
    # wire-mix evidence (axis="dp" next to the pp activation bytes).
    zero_axis = {}
    for s in samples("hvd_tpu_zero_gather_bytes_total"):
        if not s["value"]:
            continue
        ax = s["labels"].get("axis", "?")
        key = (f"{s['labels'].get('kind', '?')}:"
               f"{s['labels'].get('wire', '?')}")
        zero_axis.setdefault(ax, {})
        zero_axis[ax][key] = zero_axis[ax].get(key, 0) + s["value"]
    if zero_axis:
        out["zero_bytes_by_axis"] = zero_axis
    cache = {s["labels"].get("result", "?"): s["value"]
             for s in samples("hvd_tpu_eager_cache_total")}
    lookups = sum(cache.values())
    if lookups:
        out["cache"] = {"hits": int(cache.get("hit", 0)),
                        "misses": int(cache.get("miss", 0)),
                        "hit_rate": round(cache.get("hit", 0) / lookups,
                                          3)}
    for key, name in (("fusion_fill_efficiency",
                       "hvd_tpu_fusion_fill_efficiency"),
                      ("fusion_buckets", "hvd_tpu_fusion_buckets")):
        vals = samples(name)
        if vals:
            out[key] = round(vals[0]["value"], 6)
    return out or None


_TIMINGS = {"compile_s": None}
# What _make_tx actually decided: "sharded" is the ZeRO stage (0 =
# replicated; truthy = sharded surfaces), "memory" the computed
# per-rank state-byte block for the BENCH record (docs/zero.md).
_ARM = {"sharded": None, "memory": None}


def _infeed_wait_totals():
    """(count, sum_seconds) of the infeed-wait histogram — deltas
    around the timed window attribute starvation to THAT window."""
    try:
        import horovod_tpu as hvd

        s = hvd.metrics().get("hvd_tpu_infeed_wait_seconds", {}) \
            .get("samples", [])
        if not s:
            return 0, 0.0
        v = s[0]["value"]
        return int(v.get("count", 0)), float(v.get("sum", 0.0))
    except Exception:  # noqa: BLE001 — telemetry must not fail a bench
        return 0, 0.0


def _make_stepper(model_apply_loss, params_and_state, n, extra_args,
                  routing=None, state_specs=None, prefetch=""):
    """Shared step-loop builder: jit (n=1) or spmd_step shard_map (n>1);
    with ``routing`` (--mesh-shape) the step shards over the N-D route
    mesh so the optimizer's WirePlan axes are bound.

    ``state_specs`` optionally overrides the per-state-item shard_map
    specs (the ZeRO-1 arm carries its 1/n optimizer state as
    ``ShardedOptimizer.state_specs``; everything else replicates).
    ``prefetch`` (off/single/double) switches the loop from static
    device-resident args to a HOST-FED pipeline: each step consumes the
    next batch from ``hvd.infeed_pipeline``, so the host->device
    transfer is on (off) or off (double) the timed path and the wait is
    measured into ``hvd_tpu_infeed_wait_seconds``."""
    import jax

    import horovod_tpu as hvd
    from jax.sharding import PartitionSpec as P

    nstate = len(params_and_state)
    donate = tuple(range(nstate))  # update state in place in HBM
    if state_specs is None:
        state_specs = [P()] * nstate
    state_specs = tuple(state_specs)
    data_sharding = None  # NamedSharding for infeed placement
    if routing is not None and n > 1:
        axes = routing["axes"]
        spec = P(axes)
        in_specs = state_specs + tuple([spec] * len(extra_args))
        out_specs = state_specs + (P(),)
        if prefetch:
            data_sharding = jax.sharding.NamedSharding(
                routing["mesh"], spec)

        def _step(*all_args):
            state, data = all_args[:nstate], all_args[nstate:]
            return model_apply_loss(state, data, pmean_axis=axes)

        train_step = jax.jit(
            jax.shard_map(_step, mesh=routing["mesh"],
                          in_specs=in_specs, out_specs=out_specs,
                          check_vma=False),
            donate_argnums=donate)
    elif n > 1:
        ax = hvd.rank_axis()
        in_specs = state_specs + tuple([P(ax)] * len(extra_args))
        out_specs = state_specs + (P(),)
        if prefetch:
            from horovod_tpu.common import basics

            data_sharding = jax.sharding.NamedSharding(
                basics.context().mesh, P(ax))

        @hvd.spmd_step(in_specs=in_specs, out_specs=out_specs,
                       donate_argnums=donate)
        def train_step(*all_args):
            state, data = all_args[:nstate], all_args[nstate:]
            out = model_apply_loss(state, data, pmean_axis=ax)
            return out
    else:
        @functools.partial(jax.jit, donate_argnums=donate)
        def train_step(*all_args):
            state, data = all_args[:nstate], all_args[nstate:]
            return model_apply_loss(state, data, pmean_axis=None)

    feed = None
    if prefetch:
        from horovod_tpu import data as data_lib

        host_batch = tuple(np.asarray(x) for x in extra_args)

        def host_iter():
            while True:  # infinite: warmup, window, and any A/B rebuild
                yield host_batch

        feed = data_lib.infeed_pipeline(host_iter(), prefetch,
                                        sharding=data_sharding)
        _FEEDS.append(feed)

    carry = list(params_and_state)

    # Fresh slate: the guard A/B builds a second stepper.
    _TIMINGS["compile_s"] = None

    # AOT-compile the step — one compile total, same as calling the jit
    # directly. Timed separately from warmup: compile_s is the
    # (cacheable) XLA cost, warmup_s the first executions' cost.
    fn = train_step
    if feed is not None:
        # Lower/compile against a FED batch: the executable pins its
        # input shardings, and the pipeline's NamedSharding-placed
        # batches must match what it was built for.
        extra_args = next(feed)
    try:
        t0 = time.perf_counter()
        fn = train_step.lower(*carry, *extra_args).compile()
        _TIMINGS["compile_s"] = time.perf_counter() - t0
    except Exception as e:  # noqa: BLE001 — diagnostics only
        _log(f"AOT compile failed ({e}); falling back to jit dispatch")

    def run_batch():
        data = next(feed) if feed is not None else extra_args
        out = fn(*carry, *data)
        carry[:] = out[:-1]
        return out[-1]

    return run_batch


def _setup_cnn(args, batch_size, n):
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.models import (InceptionV3, ResNet50, ResNet101,
                                    ResNet152, VGG16, VGG19, vit_base)

    kw = {"num_classes": 1000}
    if args.model.startswith("resnet"):
        kw["space_to_depth"] = not args.no_s2d
    model = {"resnet50": ResNet50, "resnet101": ResNet101,
             "resnet152": ResNet152, "vgg16": VGG16, "vgg19": VGG19,
             "inception3": InceptionV3,
             "vit_base": vit_base}[args.model](**kw)
    image_size = args.image_size or (
        299 if args.model == "inception3" else 224)
    rng = jax.random.PRNGKey(0)
    images = jax.random.normal(
        rng, (batch_size, image_size, image_size, 3), dtype=jnp.bfloat16)
    labels = jax.random.randint(rng, (batch_size,), 0, 1000)

    init_rngs = {"params": rng, "dropout": jax.random.PRNGKey(1)}
    # Jitted init: un-jitted Flax init dispatches op-by-op; one
    # compiled program keeps the intermediates on-device and makes the
    # init a single dispatch.
    variables = jax.jit(functools.partial(model.init, train=True))(
        init_rngs, images)
    _log("model.init done")
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})  # VGG has none
    dropout_rng = jax.random.PRNGKey(2)

    # Reference benchmark uses plain SGD lr=0.01 wrapped in
    # DistributedOptimizer; same here (fused allreduce over the rank
    # axis, or the mesh router's per-axis plan under --mesh-shape) —
    # or the ZeRO-1 sharded update when the --shard-update decision
    # fires (docs/performance.md).
    from jax.sharding import PartitionSpec as P

    rt = _routing(args)
    tx, sharded = _make_tx(args, params, n, optax.sgd(0.01))
    opt_state, opt_specs = _init_opt_state(tx, sharded, params, n, rt)

    def apply_loss(state, data, pmean_axis):
        p, bs, st = state
        x, y = data

        def loss_fn(p, bs, xb, yb):
            logits, new_state = model.apply(
                {"params": p, "batch_stats": bs}, xb, train=True,
                mutable=["batch_stats"], rngs={"dropout": dropout_rng})
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, yb).mean()
            return loss, new_state.get("batch_stats", {})

        if args.accum > 1 or args.remat_policy != "none":
            # Scan-based accumulation: k microbatches per effective
            # step, ONE reduction on the mean gradient (batch stats
            # averaged across microbatches). Also the ONLY place the
            # remat wrap happens — a requested --remat-policy must go
            # through it even at k=1, or the record would claim a remat
            # the step never ran.
            (l, new_bs), g = tx.accumulate(
                lambda pp, xb, yb: loss_fn(pp, bs, xb, yb),
                has_aux=True)(p, x, y)
        else:
            (l, new_bs), g = jax.value_and_grad(
                lambda pp: loss_fn(pp, bs, x, y), has_aux=True)(p)
        if pmean_axis is not None:
            # BatchNorm stats averaged across ranks (SyncBatchNorm-lite).
            new_bs = jax.tree.map(
                lambda v: jax.lax.pmean(v, pmean_axis), new_bs)
            l = jax.lax.pmean(l, pmean_axis)
        updates, st = tx.update(g, st, p)
        p = optax.apply_updates(p, updates)
        return p, new_bs, st, l

    run = _make_stepper(apply_loss, (params, batch_stats, opt_state),
                        n, (images, labels), routing=rt,
                        state_specs=[P(), P(), opt_specs],
                        prefetch=args.prefetch)
    return run


def _setup_bert(args, batch_size, n):
    """BERT-large MLM pretraining step (BASELINE.json configs[2] —
    'examples/pytorch BERT-large pretraining' re-built for TPU: bf16
    compute, Adam, 15% random masked positions on synthetic tokens)."""
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.models import bert_base, bert_large

    model = (bert_large if args.model == "bert_large" else bert_base)(
        max_len=args.seq_len)
    rng = jax.random.PRNGKey(0)
    S = args.seq_len
    tokens = jax.random.randint(rng, (batch_size, S), 0, model.vocab_size)
    mask_positions = jax.random.bernoulli(rng, 0.15, (batch_size, S))
    labels = tokens  # predict the original token at masked positions

    params = jax.jit(model.init)(rng, tokens)["params"]
    _log("model.init done")
    # bf16 first moment: halves the Adam mu HBM traffic per step (the
    # "bf16-dominant optimizer path" lever; nu stays fp32 — optax only
    # exposes mu_dtype, and the second moment is scale-sensitive).
    from jax.sharding import PartitionSpec as P

    rt = _routing(args)
    tx, sharded = _make_tx(args, params, n,
                           optax.adamw(1e-4, mu_dtype=jnp.bfloat16))
    opt_state, opt_specs = _init_opt_state(tx, sharded, params, n, rt)

    def apply_loss(state, data, pmean_axis):
        p, st = state
        toks, mask_pos, y = data

        def loss_fn(p, tb, mb, yb):
            logits = model.apply({"params": p}, tb)
            per_tok = optax.softmax_cross_entropy_with_integer_labels(
                logits, yb)
            return (per_tok * mb).sum() / jnp.maximum(mb.sum(), 1.0)

        if args.accum > 1 or args.remat_policy != "none":
            l, g = tx.accumulate(loss_fn)(p, toks, mask_pos, y)
        else:
            l, g = jax.value_and_grad(loss_fn)(p, toks, mask_pos, y)
        if pmean_axis is not None:
            l = jax.lax.pmean(l, pmean_axis)
        updates, st = tx.update(g, st, p)
        p = optax.apply_updates(p, updates)
        return p, st, l

    run = _make_stepper(apply_loss, (params, opt_state), n,
                        (tokens, mask_positions.astype(jnp.float32), labels),
                        routing=rt, state_specs=[P(), opt_specs],
                        prefetch=args.prefetch)
    return run


def _moe_collect(inter, num_experts):
    """Sum the sown MoE intermediates across layers: (aux_loss,
    stats_vec) where stats_vec = [dropped_tokens, dropped_frac, routed,
    expert_load x E] (fp32, already global — moe_layer psums over the
    ep world)."""
    import jax
    import jax.numpy as jnp

    aux = jnp.zeros((), jnp.float32)
    dropped = jnp.zeros((), jnp.float32)
    routed = jnp.zeros((), jnp.float32)
    load = jnp.zeros((num_experts,), jnp.float32)
    for path, leaf in jax.tree_util.tree_flatten_with_path(inter)[0]:
        ks = jax.tree_util.keystr(path)
        if "moe_aux" in ks:
            aux = aux + leaf
        elif "dropped_tokens" in ks:
            dropped = dropped + leaf
        elif "routed_tokens" in ks:
            routed = routed + leaf
        elif "expert_load" in ks:
            load = load + leaf
    frac = dropped / jnp.maximum(routed, 1.0)
    return aux, jnp.concatenate([dropped[None], frac[None],
                                 routed[None], load])


def _setup_gpt(args, batch_size, n):
    """Causal-LM pretraining step on the GPT decoder (next-token loss,
    AdamW, flash attention + RoPE) — the model family this framework
    adds beyond the reference's CNN + BERT benchmarks.
    ``--moe`` swaps the dense MLPs for the expert-parallel MoE FFN
    (docs/moe.md): the load-balancing aux loss joins the objective and
    the step output grows the drop/load stats vector recorded into the
    BENCH json."""
    import jax
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.models import gpt_medium, gpt_small, gpt_tiny

    par = _parallel_config(args, n)
    if par is not None:
        return _setup_gpt_hybrid(args, batch_size, n, par)

    moe = _moe_config(args, n)
    mkw = {}
    if moe:
        mkw = {"moe_experts": moe["experts"],
               "moe_capacity_factor": moe["capacity_factor"],
               "moe_axis": moe["axis"], "moe_route": moe["route"],
               "moe_wire": moe["wire"] if moe["route"] is None
               else "none",
               "moe_overlap_chunks": moe["overlap_chunks"],
               "moe_router_noise": moe["router_noise"]}
    # gpt_tiny: the CPU-scale A/B model (the simulated-mesh MoE and
    # routing arms need a decoder whose step fits a CPU budget; same
    # methodology, the delta's SIGN is the evidence — docs/moe.md).
    model = {"gpt_small": gpt_small, "gpt_medium": gpt_medium,
             "gpt_tiny": gpt_tiny}[args.model](remat=args.remat, **mkw)
    rng = jax.random.PRNGKey(0)
    S = args.seq_len
    tokens = jax.random.randint(rng, (batch_size, S + 1), 0,
                                model.vocab_size)

    # Init outside the SPMD region through a LOCAL clone (no bound ep
    # axis at init time): the expert bank is replicated, so the param
    # tree is identical to the sharded apply's.
    init_model = model.clone(moe_axis=None, moe_route=None) if moe \
        else model
    params = jax.jit(init_model.init)(rng, tokens[:, :-1])["params"]
    _log("model.init done")
    import jax.numpy as jnp

    from jax.sharding import PartitionSpec as P

    rt = _routing(args)
    tx, zstage = _make_tx(args, params, n,
                          optax.adamw(1e-4, mu_dtype=jnp.bfloat16))

    def loss_of(p, tb):
        if moe:
            logits, mods = model.apply(
                {"params": p}, tb[:, :-1],
                mutable=["intermediates"],
                rngs={"gating": jax.random.PRNGKey(17)})
            aux, stats = _moe_collect(mods["intermediates"],
                                      moe["experts"])
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits, tb[:, 1:]).mean()
            return ce + 0.01 * aux, stats
        logits = model.apply({"params": p}, tb[:, :-1])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, tb[:, 1:]).mean()


    if zstage >= 3:
        # Stage-3 arm (docs/zero.md): params live as 1/N bucket shards;
        # every step gathers them on demand (chained per-bucket AG) and
        # the update returns new shards — the state carried through the
        # step is (shards, opt_state), both 1/N.
        from horovod_tpu.common import basics

        sspecs = tx.shard_specs(params)
        opt_specs = tx.state_specs(params)
        mesh = rt["mesh"] if rt else basics.context().mesh

        def _setup_shards(p):
            sh = tx.shard_params(p)
            return sh, tx.init(sh)

        setup = jax.jit(jax.shard_map(
            _setup_shards, mesh=mesh, in_specs=(P(),),
            out_specs=(sspecs, opt_specs), check_vma=False))
        shards, opt_state = setup(params)

        def apply_loss(state, data, pmean_axis):
            sh, st = state
            (toks,) = data
            if args.accum > 1 or args.remat_policy != "none":
                out, g = tx.accumulate(loss_of,
                                       has_aux=bool(moe))(sh, toks)
            else:
                full = tx.gather_params(sh)
                out, g = jax.value_and_grad(
                    loss_of, has_aux=bool(moe))(full, toks)
            l, stats = out if moe else (out, None)
            if pmean_axis is not None:
                l = jax.lax.pmean(l, pmean_axis)
            sh, st = tx.update(g, st, sh)
            if moe:
                return sh, st, jnp.concatenate(
                    [l.astype(jnp.float32)[None], stats])
            return sh, st, l

        run = _make_stepper(apply_loss, (shards, opt_state), n,
                            (tokens,), routing=rt,
                            state_specs=[sspecs, opt_specs],
                            prefetch=args.prefetch)
        return run

    opt_state, opt_specs = _init_opt_state(tx, zstage, params, n, rt)

    def apply_loss(state, data, pmean_axis):
        p, st = state
        (toks,) = data

        if args.accum > 1 or args.remat_policy != "none":
            out = tx.accumulate(loss_of, has_aux=bool(moe))(p, toks)
        else:
            out = jax.value_and_grad(loss_of,
                                     has_aux=bool(moe))(p, toks)
        if moe:
            (l, stats), g = out
        else:
            l, g = out
        if pmean_axis is not None:
            l = jax.lax.pmean(l, pmean_axis)
        updates, st = tx.update(g, st, p)
        p = optax.apply_updates(p, updates)
        if moe:
            # Loss + the global drop/load stats ride one output vector
            # (the stats are already replicated — psum-ed in-layer).
            return p, st, jnp.concatenate(
                [l.astype(jnp.float32)[None], stats])
        return p, st, l

    run = _make_stepper(apply_loss, (params, opt_state), n, (tokens,),
                        routing=rt, state_specs=[P(), opt_specs],
                        prefetch=args.prefetch)
    return run


def _wrap_pp_spec(s, pp_axis="pp"):
    """Prepend the pp axis to a shard PartitionSpec's leading dim:
    ZeRO shard/state leaves differ across pipeline stages AND dp
    replicas, so the round-trip assembly must split over both (a bare
    P("dp") would broadcast stage 0's shard onto every stage)."""
    from jax.sharding import PartitionSpec as P

    parts = tuple(s)
    if not parts or parts[0] is None:
        return s
    first = parts[0]
    axes = (first,) if isinstance(first, str) else tuple(first)
    return P((pp_axis,) + axes, *parts[1:])


def _setup_gpt_hybrid(args, batch_size, n, par):
    """The hybrid dp x pp (x ep x sp x tp) GPT arm (docs/pipeline.md,
    docs/sequence.md): decoder layers stage-stacked over the pp axis
    and trained under the scan-based 1F1B schedule
    (pipeline_accumulate_gradients), heads/MLP sharded over tp inside
    each stage, the context sharded over sp (ring/Ulysses attention —
    the layers resolve their own global RoPE positions, so sp runs
    INSIDE a stage), the --moe expert bank dispatching over ep, and
    gradients reduced over dp ONLY via
    DistributedOptimizer(parallel=spec) — or ZeRO stage-3 shards PER
    PIPELINE STAGE under --zero-stage 3. The BENCH record's ``memory``
    block is computed from the per-rank resident tree (this rank's
    stage + the shared embedding/head); under sp it also carries the
    per-rank vs dense activation accounting (the long-context
    acceptance number)."""
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from jax.sharding import PartitionSpec as P
    from horovod_tpu.models import gpt_medium, gpt_small, gpt_tiny
    from horovod_tpu.models.gpt import (activation_bytes, param_bytes,
                                        pipeline_fns,
                                        stack_stage_params)
    from horovod_tpu.parallel.pipeline import (
        pipeline_accumulate_gradients)
    from horovod_tpu.parallel.spec import (hybrid_param_specs,
                                           hybrid_state_specs)

    spec, mesh = par["spec"], par["mesh"]
    pp, tp, dp = par["pp"], par["tp"], par["dp"]
    sp, ep = par.get("sp", 1), par.get("ep", 1)
    mkw = {"remat": args.remat}
    if tp > 1:
        mkw["tp_axis"] = "tp"
    if sp > 1:
        mkw.update(seq_parallel="sp", seq_impl=par["seq_impl"],
                   seq_wire=par["seq_wire"])
    # pp x ep (docs/moe.md): the expert bank lives INSIDE each
    # pipeline stage and dispatches over its own ep axis. Router noise
    # is forced off — the 1F1B closures recompute deterministically
    # and carry no rng stream.
    moe = _moe_config(args, ep) if ep > 1 else None
    if moe:
        if args.moe_router_noise:
            _log("--moe-router-noise disabled on the pp x ep arm: the "
                 "1F1B stage closures recompute deterministically and "
                 "carry no gating rng (docs/pipeline.md)")
        mkw.update(moe_experts=moe["experts"],
                   moe_capacity_factor=moe["capacity_factor"],
                   moe_axis="ep", moe_wire=moe["wire"],
                   moe_overlap_chunks=moe["overlap_chunks"],
                   moe_router_noise=0.0)
    model = {"gpt_small": gpt_small, "gpt_medium": gpt_medium,
             "gpt_tiny": gpt_tiny}[args.model](**mkw)
    rng = jax.random.PRNGKey(0)
    S = args.seq_len
    tokens = jax.random.randint(rng, (batch_size, S + 1), 0,
                                model.vocab_size)
    # Init through the replicated clone (no bound tp/sp/ep axes at
    # init time): the tp/sp param tree is byte-compatible with the
    # dense one (_DenseMaster; sp ranks hold the SAME replicated
    # params), so one init serves every twin.
    params = jax.jit(model.clone(tp_axis=None, seq_parallel=None,
                                 moe_axis=None).init)(
        rng, tokens[:, :-1])["params"]
    _log("model.init done")

    s_local = S // sp if sp > 1 else S

    def _sp_slice(toks):
        """This rank's sequence shard of the (B, S+1) token slab, in
        the layout the seq impl expects — striped for ring (balanced
        causal: local j holds global j*sp + rank), contiguous for
        ulysses — with the matching next-token targets. sp=1 is the
        plain full-sequence split."""
        if sp <= 1:
            return toks[:, :-1], toks[:, 1:]
        i = jax.lax.axis_index("sp")
        if par["seq_impl"] == "ring":
            gpos = jnp.arange(s_local) * sp + i
        else:
            gpos = i * s_local + jnp.arange(s_local)
        return (jnp.take(toks, gpos, axis=1),
                jnp.take(toks, gpos + 1, axis=1))

    def _sp_mean(loss):
        """Global loss: the per-rank CE means cover disjoint sequence
        shards of the SAME samples, so the dp-pmean'd loss averages
        once more over sp."""
        return jax.lax.pmean(loss, "sp") if sp > 1 else loss
    stages, shared = stack_stage_params(params, pp)
    stage_fn, pre_fn, loss_fn = pipeline_fns(model)
    accum = max(args.accum, 1)
    vg = pipeline_accumulate_gradients(
        stage_fn, loss_fn, accum_steps=accum, axis_name="pp",
        pre_fn=pre_fn, wire=par["wire"],
        remat_policy=args.remat_policy)
    inner = optax.adamw(1e-4, mu_dtype=jnp.bfloat16)
    rt = {"mesh": mesh, "axes": tuple(spec.dp_axes)}

    zstage = 0
    if args.zero_stage not in ("auto", "0"):
        zstage = int(args.zero_stage)
        if zstage in (1, 2) or pp <= 1:
            _log(f"--zero-stage {zstage} on the hybrid arm falls back "
                 "to 0 (per-stage sharding is wired for stage 3 under "
                 "--pipeline-stages; stages 1/2 ride the flat arm)")
            zstage = 0
    if args.guard == "on":
        _log("--guard on ignored on the hybrid arm: the carried guard "
             "state is per-stage (agreement over dp only) — A/B guard "
             "overhead on the flat arm")

    # Per-rank resident tree: this rank's stage slice + the shared
    # embedding/head (tp masters are replicated and sliced in-trace) —
    # the honest basis for the memory block.
    per_rank = ({"stages": jax.tree.map(lambda a: a[0:1], stages),
                 "shared": shared} if pp > 1 else params)
    mem = _memory_block(per_rank, inner, zstage, dp, accum)
    mem["parallel"] = spec.describe()
    mem["full_model_params_bytes"] = param_bytes(params)
    if sp > 1:
        # The long-context acceptance numbers (docs/sequence.md):
        # per-rank activation accounting at the LOCAL sequence length
        # vs what one dense replica would hold at the full length —
        # sp>=2 must show per_rank < 1/2 dense.
        lb = max(batch_size // max(dp, 1), 1)
        mem["activation"] = {
            "seq_len": S, "sp": sp, "seq_impl": par["seq_impl"],
            "seq_wire": par["seq_wire"],
            "per_rank_bytes": activation_bytes(model, lb, s_local),
            "dense_accounting_bytes": activation_bytes(model, lb, S),
        }
    _ARM["sharded"] = zstage
    _ARM["memory"] = mem

    if pp <= 1:
        # tp/sp/ep arm without a pipeline axis: the model trains under
        # the ordinary (optionally accumulated) step with the parallel
        # optimizer combining slice grads over tp AND sp (sp ranks
        # hold identical params over different sequence shards —
        # docs/sequence.md) and reducing over dp.
        tx = hvd.DistributedOptimizer(inner, parallel=spec,
                                      compression=args.compression,
                                      nonfinite_policy="off")
        opt = tx.init(params)

        def loss_of(p, tb):
            x, y = _sp_slice(tb)
            logits = model.apply({"params": p}, x)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()

        def apply_loss(state, data, pmean_axis):
            p, op = state
            (toks,) = data
            if accum > 1 or args.remat_policy != "none":
                loss, g = tx.accumulate(loss_of)(p, toks)
            else:
                loss, g = jax.value_and_grad(loss_of)(p, toks)
            loss = _sp_mean(jax.lax.pmean(loss, pmean_axis))
            updates, op = tx.update(g, op, p)
            return optax.apply_updates(p, updates), op, loss

        run = _make_stepper(apply_loss, (params, opt), n, (tokens,),
                            routing=rt, state_specs=[P(), P()],
                            prefetch=args.prefetch)
        return run

    pspecs = hybrid_param_specs()

    if zstage >= 3:
        tx = hvd.ZeroOptimizer(inner, zero_stage=3, parallel=spec,
                               compression=args.compression)
        sspecs = [_wrap_pp_spec(s) for s in tx.shard_specs(per_rank)]
        ospecs = jax.tree.map(_wrap_pp_spec, tx.state_specs(per_rank),
                              is_leaf=lambda x: isinstance(x, P))

        def _setup_shards(st_g, sh):
            shd = tx.shard_params({"stages": st_g, "shared": sh})
            return shd, tx.init(shd)

        setup = jax.jit(jax.shard_map(
            _setup_shards, mesh=mesh, in_specs=(P("pp"), P()),
            out_specs=(sspecs, ospecs), check_vma=False))
        shards, opt = setup(stages, shared)

        def apply_loss(state, data, pmean_axis):
            shd, op = state
            (toks,) = data
            full = tx.gather_params(shd)
            x, y = _sp_slice(toks)
            loss, g = vg(full, x, y)
            loss = _sp_mean(jax.lax.pmean(loss, pmean_axis))
            shd, op = tx.update(g, op, shd)
            return shd, op, loss

        run = _make_stepper(apply_loss, (shards, opt), n, (tokens,),
                            routing=rt, state_specs=[sspecs, ospecs],
                            prefetch=args.prefetch)
        return run

    tx = hvd.DistributedOptimizer(inner, parallel=spec,
                                  compression=args.compression,
                                  nonfinite_policy="off")
    opt = tx.init({"stages": stages, "shared": shared})
    ospecs = hybrid_state_specs(jax.eval_shape(lambda: opt))

    def apply_loss(state, data, pmean_axis):
        st, sh, op = state
        (toks,) = data
        p = {"stages": st, "shared": sh}
        x, y = _sp_slice(toks)
        loss, g = vg(p, x, y)
        loss = _sp_mean(jax.lax.pmean(loss, pmean_axis))
        updates, op = tx.update(g, op, p)
        p = optax.apply_updates(p, updates)
        return p["stages"], p["shared"], op, loss

    run = _make_stepper(
        apply_loss, (stages, shared, opt), n, (tokens,), routing=rt,
        state_specs=[pspecs["stages"], pspecs["shared"], ospecs],
        prefetch=args.prefetch)
    return run


if __name__ == "__main__":
    sys.exit(main())
