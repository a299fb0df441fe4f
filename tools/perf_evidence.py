#!/usr/bin/env python
"""Perf-hardening evidence (VERDICT r1 #10): measured numbers, not prose.

Runs on the 8-virtual-device CPU mesh (the dryrun topology; the driver's
BENCH runs on real TPU) and reports:

1. DONATION coverage of the flagship train step: compiled memory stats
   with and without donate_argnums — donated steps must not double-buffer
   the parameter/optimizer state.
2. Staged hierarchical allreduce (RS-local -> AR-cross -> AG-local) vs
   flat psum on the 2x4 (cross, local) mesh: per-step wall time and the
   DCN-bytes argument (staged moves 1/local_size of the buffer over the
   cross axis).
3. Eager fusion: grouped allreduce of many small tensors vs per-tensor
   dispatch.

Usage: XLA_FLAGS="--xla_force_host_platform_device_count=8" \
       python tools/perf_evidence.py
"""

import json
import os
import sys
import time

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import optax
from jax.sharding import Mesh, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.ops import collectives as C


def _round_search_order():
    """Newest-first results dirs, from the shared tools/round_dirs.py."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from round_dirs import SEARCH_ORDER

    return SEARCH_ORDER


def mib(nbytes):
    return round(nbytes / (1024 * 1024), 2)


def donation_evidence():
    """Memory-analysis proof that donated state is reused in place."""
    hvd.init()
    from horovod_tpu.models import MLP

    model = MLP(features=(512, 512), num_classes=10)
    rng = jax.random.PRNGKey(0)
    x = np.zeros((64, 32 * 32), np.float32)
    y = np.zeros((64,), np.int64)
    params = model.init(rng, x)["params"]
    tx = hvd.DistributedOptimizer(optax.adam(1e-3),
                                  axis_name=hvd.rank_axis())
    st = tx.init(params)

    def step(params, st, xb, yb):
        def loss(p):
            return optax.softmax_cross_entropy_with_integer_labels(
                model.apply({"params": p}, xb), yb).mean()

        l, g = jax.value_and_grad(loss)(params)
        up, st2 = tx.update(g, st, params)
        return optax.apply_updates(params, up), st2, l

    out = {}
    for tag, donate in (("no_donation", ()), ("donated", (0, 1))):
        jf = jax.jit(step, donate_argnums=donate)
        lowered = jf.lower(params, st, x, y)
        compiled = lowered.compile()
        ma = compiled.memory_analysis()
        out[tag] = {
            "output_bytes": mib(getattr(ma, "output_size_in_bytes", 0)),
            "temp_bytes": mib(getattr(ma, "temp_size_in_bytes", 0)),
            "argument_bytes": mib(getattr(ma, "argument_size_in_bytes", 0)),
            "alias_bytes": mib(getattr(ma, "alias_size_in_bytes", 0)),
        }
    return out


def hierarchical_evidence():
    """Staged RS->AR->AG vs flat psum on the 2x4 dryrun mesh."""
    devs = np.array(jax.devices()).reshape(2, 4)
    mesh = Mesh(devs, ("cross", "local"))
    n = 1 << 20  # 4 MiB fp32 per rank

    flat_f = jax.jit(jax.shard_map(
        lambda v: C.hierarchical_allreduce(v, C.ReduceOp.SUM,
                                           "local", "cross"),
        mesh=mesh, in_specs=P(("cross", "local")),
        out_specs=P(("cross", "local"))))
    staged_f = jax.jit(jax.shard_map(
        lambda v: C.hierarchical_allreduce_staged(
            v.reshape(n), C.ReduceOp.SUM, "local", "cross")[None],
        mesh=mesh, in_specs=P(("cross", "local")),
        out_specs=P(("cross", "local"))))

    x = np.ones((8, n), np.float32)

    def bench(f, iters=20):
        f(x).block_until_ready()  # compile
        t0 = time.perf_counter()
        for _ in range(iters):
            out = f(x)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters * 1000

    return {
        "buffer_mib_per_rank": mib(n * 4),
        "flat_ms": round(bench(flat_f), 2),
        "staged_ms": round(bench(staged_f), 2),
        "cross_axis_bytes_flat": mib(n * 4),
        "cross_axis_bytes_staged": mib(n * 4 // 4),
        "note": ("staged moves 1/local_size of the buffer over the "
                 "cross (DCN) axis — the reference's hierarchical win; "
                 "on CPU loopback the wall-clock difference is noise, "
                 "the bytes ratio is the structural claim"),
    }


def quantized_cross_evidence():
    """EQuARX int8 DCN hops: read the COMPILED HLO and account the
    cross-axis collective payloads by element type — evidence the s8
    wire format actually reaches the executable, not just the Python."""
    import re

    devs = np.array(jax.devices()).reshape(2, 4)
    mesh = Mesh(devs, ("cross", "local"))
    n = 1 << 20

    def compiled_text(fn):
        return jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=P(("cross", "local")),
            out_specs=P(("cross", "local")))).lower(
                np.ones((8, n), np.float32)).compile().as_text()

    def collective_bytes(text):
        """Sum result-payload bytes of collective DEFINITIONS by element
        type. Anchored to `= <shape> <op>(` so consumers that merely
        reference a collective's instruction name (get-tuple-element
        etc.) are not counted, and tuple-shaped results contribute every
        element."""
        sizes = {"s8": 1, "f32": 4, "bf16": 2, "f16": 2}
        out = {k: 0 for k in sizes}
        for m in re.finditer(
                r"= (\(?[^=\n]*?)\s*"
                r"(all-to-all|all-gather|all-reduce|"
                r"reduce-scatter|collective-permute)\(", text):
            for dt, shape in re.findall(r"(s8|f32|bf16|f16)\[([\d,]*)\]",
                                        m.group(1)):
                elems = 1
                for d in shape.split(","):
                    if d:
                        elems *= int(d)
                out[dt] += elems * sizes[dt]
        return {k: v for k, v in out.items() if v}

    exact = collective_bytes(compiled_text(
        lambda v: C.hierarchical_allreduce_staged(
            v.reshape(n), C.ReduceOp.SUM, "local", "cross")[None]))
    quant = collective_bytes(compiled_text(
        lambda v: C.quantized_hierarchical_allreduce(
            v.reshape(n), C.ReduceOp.SUM, "local", "cross")[None]))
    return {
        "buffer_mib_per_rank": mib(n * 4),
        "exact_collective_bytes": {k: mib(v) for k, v in exact.items()},
        "quantized_collective_bytes": {k: mib(v)
                                       for k, v in quant.items()},
        "note": ("compiled-HLO accounting: the quantized path's "
                 "collective payloads are s8 (plus small fp32 scale "
                 "vectors), the exact path's are f32 — the ~4x DCN "
                 "byte reduction is in the executable, not just "
                 "claimed"),
    }


def fusion_evidence():
    """Grouped (fused-bucket) vs per-tensor eager allreduce."""
    hvd.init()
    tensors = {f"g{i}": np.ones((256,), np.float32) for i in range(64)}

    def grouped():
        out = hvd.grouped_allreduce(tensors, op=hvd.Sum, name="fuse")
        jax.block_until_ready(jax.tree.leaves(out))

    def per_tensor():
        outs = [hvd.allreduce(v, op=hvd.Sum, name=f"pt{i}")
                for i, v in enumerate(tensors.values())]
        jax.block_until_ready(outs)

    grouped(), per_tensor()  # compile both
    t0 = time.perf_counter()
    for _ in range(10):
        grouped()
    tg = (time.perf_counter() - t0) / 10 * 1000
    t0 = time.perf_counter()
    for _ in range(10):
        per_tensor()
    tp = (time.perf_counter() - t0) / 10 * 1000
    return {"tensors": 64, "grouped_ms": round(tg, 2),
            "per_tensor_ms": round(tp, 2),
            "speedup": round(tp / tg, 1)}


def overlap_evidence():
    """The handle model's value (reference async-completion design,
    gpu_operations.h:107-119): N collectives dispatched async then
    synchronized once vs N blocking round-trips."""
    hvd.init()
    tensors = [np.ones((1 << 16,), np.float32) for _ in range(16)]

    def async_batch():
        handles = [hvd.allreduce_async(t, op=hvd.Sum, name=f"ov{i}")
                   for i, t in enumerate(tensors)]
        return [hvd.synchronize(h) for h in handles]

    def sync_each():
        outs = []
        for i, t in enumerate(tensors):
            o = hvd.allreduce(t, op=hvd.Sum, name=f"sv{i}")
            jax.block_until_ready(jax.tree.leaves(o))
            outs.append(o)
        return outs

    async_batch(), sync_each()  # compile
    t0 = time.perf_counter()
    for _ in range(10):
        async_batch()
    ta = (time.perf_counter() - t0) / 10 * 1000
    t0 = time.perf_counter()
    for _ in range(10):
        sync_each()
    ts = (time.perf_counter() - t0) / 10 * 1000
    return {"tensors": 16, "async_then_sync_ms": round(ta, 2),
            "blocking_each_ms": round(ts, 2),
            "speedup": round(ts / ta, 2)}


def pipeline_evidence():
    """1F1B's memory bound vs GPipe-autodiff, from the COMPILED
    executables' memory analysis: GPipe stores every microbatch's
    activations for the backward (temp grows with n_micro), 1F1B's
    n-slot ring + recomputation keeps temps flat. Same grads either
    way (test_parallel pins numerics); this is the structural claim
    measured, not asserted."""
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from horovod_tpu.parallel.pipeline import (pipeline_apply,
                                               pipeline_train_step_1f1b,
                                               select_last_stage)

    n, d, b = 8, 128, 4
    mesh = Mesh(np.array(jax.devices()), ("pp",))
    rng = np.random.default_rng(0)

    def stage_fn(w, x):
        return jnp.tanh(x @ w)

    def loss_fn(o, y):
        return ((o - y) ** 2).sum()

    out = {}
    for n_micro in (4, 16, 32):
        Ws = jnp.asarray(rng.standard_normal((n, d, d)), jnp.float32)
        xs = jnp.ones((n_micro, b, d), jnp.float32)
        ys = jnp.zeros((n_micro, b, d), jnp.float32)

        def gpipe(w, x, y):
            outs = select_last_stage(
                pipeline_apply(stage_fn, w[0], x, "pp"), "pp")
            return jax.grad(
                lambda w0: loss_fn(
                    select_last_stage(
                        pipeline_apply(stage_fn, w0[0], x, "pp"),
                        "pp"), y))(w), outs

        def f1b(w, x, y):
            g, l = pipeline_train_step_1f1b(stage_fn, loss_fn, w[0],
                                            x, y, "pp")
            return g[None], l[None]

        row = {}
        for tag, fn, out_specs in (
                ("gpipe_autodiff", gpipe, (P("pp"), P())),
                ("interleaved_1f1b", f1b, (P("pp"), P("pp")))):
            jf = jax.jit(jax.shard_map(
                fn, mesh=mesh, in_specs=(P("pp"), P(), P()),
                out_specs=out_specs, check_vma=False))
            ma = jf.lower(Ws, xs, ys).compile().memory_analysis()
            row[tag] = {"temp_mib": mib(
                getattr(ma, "temp_size_in_bytes", 0))}
        out[f"n_micro={n_micro}"] = row
    out["note"] = ("GPipe autodiff temps grow with n_micro (every "
                   "microbatch's activations live until backward); "
                   "the 1F1B ring holds n_stages slots regardless — "
                   "the memory bound the schedule exists for")
    return out


def alltoallv_skew_evidence():
    """Wire-byte accounting for uneven all-to-all under skewed splits
    (VERDICT r3 #7): the flat segment-padded form puts O(n*max) rows on
    the wire; alltoallv_chunked's per-hop padding is bounded by
    sum_k(hop max). Both counted from the COMPILED HLO's collective
    payloads, against the analytic O(sum) floor."""
    import re

    hvd.init()
    mesh = hvd._ctx().mesh
    n, D = 8, 128
    srng = np.random.default_rng(7)
    splits = srng.integers(0, 5, (n, n)).tolist()
    splits[0][3] = 500  # one overloaded expert — the MoE skew shape
    splits = [[int(v) for v in row] for row in splits]

    maxs = max(max(row) for row in splits)
    max_send = max(sum(row) for row in splits)
    wire_rows = sum(splits[s][d] for s in range(n) for d in range(n)
                    if s != d)  # self-segments never need the wire

    def collective_bytes(text):
        # Result-payload bytes of collective definitions. Group 1 must
        # admit '=' — long HLO tuples carry /*index=N*/ comments.
        sizes = {"s8": 1, "f32": 4, "bf16": 2, "f16": 2}
        total = 0
        for m in re.finditer(
                r"= ([^\n]*?)\s*"
                r"(all-to-all|all-gather|all-reduce|"
                r"reduce-scatter|collective-permute)\(", text):
            for dt, shape in re.findall(r"(s8|f32|bf16|f16)\[([\d,]*)\]",
                                        m.group(1)):
                elems = 1
                for d in shape.split(","):
                    if d:
                        elems *= int(d)
                total += elems * sizes[dt]
        return total

    def flat(v):
        return C.alltoallv(v[0], splits)[None]

    def chunked(v):
        out, _ = C.alltoallv_chunked(v[0], splits)
        return out[None]

    flat_text = jax.jit(jax.shard_map(
        flat, mesh=mesh, in_specs=P("hvd"), out_specs=P("hvd"))).lower(
            np.ones((n, n * maxs, D), np.float32)).compile().as_text()
    chunk_text = jax.jit(jax.shard_map(
        chunked, mesh=mesh, in_specs=P("hvd"),
        out_specs=P("hvd"))).lower(
            np.ones((n, max_send, D), np.float32)).compile().as_text()

    item = 4 * D
    return {
        "splits_note": f"8x8 random 0-4 splits + one 500-row segment "
                       f"(max={maxs}, off-diagonal rows={wire_rows})",
        "analytic_floor_mib_per_rank": mib(wire_rows * item / n),
        "flat_padded_hlo_mib_per_rank": mib(collective_bytes(flat_text)),
        "chunked_hlo_mib_per_rank": mib(collective_bytes(chunk_text)),
        "note": "flat pads every (src,dst) segment to the global max "
                "(n*max rows per rank); chunked pays only each ppermute "
                "hop's own max (sum_k hop-max rows) — bounded under "
                "skew. HLO payload bytes are per-rank (one SPMD "
                "program).",
    }


def striped_evidence():
    """Striped vs contiguous-block causal ring attention (VERDICT r4
    #7): back the balance claim with MEASURED step times on the CPU
    mesh, not structure alone.

    Work model: both forms run n ring hops in SPMD lockstep (every hop
    ends in a ppermute rendezvous, so a hop costs the MAX work over
    devices). Contiguous causal: at every hop some device attends a
    FULL visible block (device idx attends src<=idx), so the ring pays
    ~n full block-attends of critical path while doing only n(n+1)/2
    real ones — the drained-tail imbalance. Striped (interleaved
    layout): every device does the same ~half-block of triangular work
    on every hop — critical path ~n half-blocks, ideal ratio -> 2x at
    large n. With n=8 the model predicts contiguous/striped =
    n / ((n+1)/2) = 1.78x; the measured ratio below is the evidence
    (CPU-mesh caveat: 8 virtual devices share host cores, which
    under-reports lockstep stalls, so the measured ratio is a floor)."""
    import time as _time

    from jax.sharding import Mesh

    from horovod_tpu.parallel.ring_attention import (ring_attention,
                                                     striped_attention)

    hvd.init()
    mesh = Mesh(np.array(hvd._ctx().mesh.devices), ("sp",))
    n = 8
    b, s_total, h, d = 1, 2048, 4, 64
    rng = np.random.default_rng(3)
    q = rng.standard_normal((b, s_total, h, d)).astype(np.float32)

    def make(fn):
        return jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=P(None, "sp"),
            out_specs=P(None, "sp"), check_vma=False))

    import jax.numpy as jnp

    def grad_wrap(attend):
        def loss(q, k, v):
            return attend(q, k, v).astype(jnp.float32).sum()
        return jax.grad(loss, argnums=(0, 1, 2))

    ring_f = make(lambda q, k, v: ring_attention(q, k, v, "sp",
                                                 causal=True))
    striped_f = make(lambda q, k, v: striped_attention(q, k, v, "sp"))
    ring_g = make(grad_wrap(
        lambda q, k, v: ring_attention(q, k, v, "sp", causal=True)))
    striped_g = make(grad_wrap(
        lambda q, k, v: striped_attention(q, k, v, "sp")))

    def bench(f, iters=20):
        jax.block_until_ready(f(q, q, q))  # compile + warm
        t0 = _time.perf_counter()
        for _ in range(iters):
            out = f(q, q, q)
        jax.block_until_ready(out)
        return (_time.perf_counter() - t0) / iters * 1e3

    ring_ms = bench(ring_f)
    striped_ms = bench(striped_f)
    ring_bwd_ms = bench(ring_g, iters=10)
    striped_bwd_ms = bench(striped_g, iters=10)
    return {
        "shape": f"b={b} S={s_total} (S_local={s_total // n}) h={h} "
                 f"d={d}, n={n} ring hops",
        "contiguous_causal_ms": round(ring_ms, 2),
        "striped_ms": round(striped_ms, 2),
        "measured_ratio": round(ring_ms / striped_ms, 2),
        "contiguous_causal_grad_ms": round(ring_bwd_ms, 2),
        "striped_grad_ms": round(striped_bwd_ms, 2),
        "measured_grad_ratio": round(ring_bwd_ms / striped_bwd_ms, 2),
        "model_ratio_n8": round(n / ((n + 1) / 2), 2),
        "model_ratio_large_n": 2.0,
        "note": "lockstep hops cost max-over-devices work: contiguous "
                "causal always has one device attending a full block "
                "per hop (drained tail); striped gives every device the "
                "same triangular half-block. CAVEAT: the CPU mesh is "
                "nearly insensitive to this effect — the 8 virtual "
                "devices share host cores, so a device's idle lockstep "
                "slot is immediately reused by a sibling and the "
                "measured ratio lands ~1.0-1.2 depending on machine "
                "load. Treat it as a floor; the per-hop work model and "
                "the queued on-chip kernel row carry the claim.",
    }


def host_gap_evidence():
    """Wall-vs-device rate from the captured profiled runs (VERDICT r3
    #3: the r03 per-iteration loss fetch cost 14% of wall time; the
    round-4 single-fetch window should close the gap to <5%). Reads the
    newest profile record + its trace summary; skips rows that have not
    been captured yet."""
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    rdirs = _round_search_order()
    rows = {}
    for model, rec_names, trace in (
            ("resnet50", ["resnet50", "resnet50_b256"],
             "trace_summary.json"),
            ("bert_large", ["bert_large"], "trace_bert_summary.json")):
        # Record and trace must come from the SAME round: the metric
        # verifies that round's timing loop, so pairing an r04 rate with
        # an r03 device basis would measure nothing.
        rec = summary = None
        rec_src = trace_src = None
        for rdir in rdirs:
            cand_rec = cand_src = None
            for cand in rec_names:
                p = os.path.join(here, "results", rdir, f"{cand}.json")
                if cand_rec is None and os.path.exists(p):
                    try:
                        with open(p) as f:
                            cand_rec = json.load(f)
                        cand_src = f"{rdir}/{cand}.json"
                    except (OSError, json.JSONDecodeError):
                        cand_rec = None
            ts = os.path.join(here, "results", rdir, trace)
            if cand_rec is not None and os.path.exists(ts):
                try:
                    with open(ts) as f:
                        summary = json.load(f)
                except (OSError, json.JSONDecodeError):
                    continue
                rec, rec_src = cand_rec, cand_src
                trace_src = f"{rdir}/{trace}"
                break
        if rec is None or summary is None:
            rows[model] = {"skipped": "record + trace not both captured "
                                      "in any one round yet"}
            continue
        dev_ms = None
        for op in summary.get("device_top_ops", []):
            if op["name"].startswith("jit_train_step") and op["count"]:
                dev_ms = op["ms"] / op["count"]
                break
        # NO Steps-track fallback here: a Steps-track span includes
        # within-step device idle while waiting on host dispatch — the
        # very gap this metric exists to expose — so using it would
        # make wall_vs_device self-pass at ~100% (code-review r5).
        bsz = (rec.get("config") or {}).get("global_batch")
        if not dev_ms or not bsz:
            rows[model] = {"skipped": "no device step in trace "
                                      "or no config in record"}
            continue
        device_rate = bsz / (dev_ms / 1e3)
        wall_rate = rec["value"] * (rec.get("config") or {}).get(
            "n_chips", 1)
        rows[model] = {
            "wall_rate": round(wall_rate, 1),
            "device_rate": round(device_rate, 1),
            "wall_vs_device_pct": round(100 * wall_rate / device_rate,
                                        1),
            "timing_mode": (rec.get("config") or {}).get("timing"),
            "record_source": rec_src, "trace_source": trace_src,
        }
    rows["note"] = ("target: wall >= 95% of device rate with the "
                    "single-fetch window (r03 measured 86% under the "
                    "per-iteration fetch)")
    return rows


def scaling_projection():
    """DP scaling-efficiency roofline from MEASURED single-chip step
    times (results/tpu_r03/*.json) + per-step gradient bytes + v5e ICI
    bandwidth — the honest stand-in for the SURVEY §6 north star
    (>=85% scaling at 256 chips) that one chip cannot measure.

    Model: ring/bidirectional allreduce moves 2*B*(N-1)/N bytes per
    chip per step (B = gradient bytes). With XLA's latency-hiding
    scheduler overlapping the bucketed reduction with backprop (the
    measured fusion/overlap sections), the step time at N chips is
    max(compute, exposed_comm) with exposed_comm = comm_time -
    overlappable backprop span (conservatively: no overlap at all for
    the lower bound). Efficiency = compute / step_time.

    ICI figures are marked assumptions: v5e carries 4 ICI links/chip;
    we project at 45 GB/s/chip usable allreduce bandwidth
    (conservative, ~1/4 of aggregate spec) and 90 GB/s (typical
    achieved), for N in {8, 64, 256} within a slice/pod. DCN-crossing
    multi-slice jobs use hierarchical+quantized paths measured in the
    sections above.

    Compute basis per row: the DEVICE step time from the captured
    profiler trace where one exists (the wall step includes a ~14%
    host-dispatch gap in that capture and would bias efficiency
    optimistic); otherwise the wall step, with
    the bias direction stated in the row."""
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def device_step_ms(trace_summary):
        """Mean per-execution device time of the jitted train step.

        Returns ``(ms, basis)``; a Steps-track fallback is marked as
        such because its span includes within-step host-dispatch gaps
        and therefore upper-bounds the true device time (efficiency
        from it is conservative, not optimistic — comm is compared
        against a LONGER compute span)."""
        try:
            with open(trace_summary) as f:
                summary = json.load(f)
            for op in summary.get("device_top_ops", []):
                if op["name"].startswith("jit_train_step"):
                    return op["ms"] / op["count"], "modules_track"
            ms = (summary.get("steps") or {}).get("mean_ms")
            if ms:
                return ms, "steps_track_span_incl_host_gaps"
        except (OSError, json.JSONDecodeError, KeyError,
                ZeroDivisionError):
            pass
        return None, None

    rdirs = _round_search_order()  # newest round's captures win
    models = {
        # row -> (grad bytes/step/chip, per-chip batch,
        #         candidate record names newest-config-first,
        #         trace summary filename)
        "resnet50_b256": (25.6e6 * 4, 256,
                          ["resnet50", "resnet50_b256"],
                          "trace_summary.json"),
        "bert_large": (340e6 * 4, 8, ["bert_large"],
                       "trace_bert_summary.json"),
    }

    def find(filenames):
        for rdir in rdirs:
            for fn in filenames:
                p = os.path.join(here, "results", rdir, fn)
                if os.path.exists(p):
                    return p, f"{rdir}/{fn}"
        return None, None

    out = {}
    for name, (grad_bytes, bsz, cands, trace) in models.items():
        path, rec_src = find([f"{c}.json" for c in cands])
        try:
            with open(path) as f:
                rec = json.load(f)
        except (OSError, json.JSONDecodeError, TypeError):
            # Missing OR truncated (queue killed mid-write): skip the
            # row, never the section.
            out[name] = {"skipped": "no (complete) chip record yet"}
            continue
        trace_path, trace_src = find([trace]) if trace else (None, None)
        dev_ms, dev_basis = (device_step_ms(trace_path)
                             if trace_path else (None, None))
        if dev_ms:
            step_s = dev_ms / 1e3
            basis = f"device step from profiler trace ({dev_basis})"
        else:
            step_s = bsz / rec["value"]
            basis = ("wall step (includes host dispatch gaps; biases "
                     "efficiency optimistic by that share)")
        # Provenance: the rate and the compute basis can come from
        # DIFFERENT queue runs (the profile job is separate); name both
        # sources so a basis/rate mismatch is visible in the evidence.
        row = {"measured_rate": rec["value"], "basis": basis,
               "record_source": rec_src,
               "record_captured_unix": rec.get("captured_unix"),
               "trace_source": trace_src,
               "grad_mib": round(grad_bytes / 2 ** 20, 1),
               "compute_ms": round(step_s * 1e3, 2)}
        for bw_gbs, tag in ((45, "conservative"), (90, "typical")):
            effs = {}
            for n in (8, 64, 256):
                comm_s = 2 * grad_bytes * (n - 1) / n / (bw_gbs * 1e9)
                no_overlap = step_s / (step_s + comm_s)
                full_overlap = step_s / max(step_s, comm_s)
                effs[f"N={n}"] = {
                    "comm_ms": round(comm_s * 1e3, 2),
                    "eff_no_overlap": round(100 * no_overlap, 1),
                    "eff_full_overlap": round(100 * full_overlap, 1)}
            row[f"ici_{bw_gbs}GBps_{tag}"] = effs
        out[name] = row
    out["note"] = ("projection, not measurement: single-chip step time "
                   "is measured; ICI bandwidth is an assumption stated "
                   "per column; real multi-chip numbers require a pod")
    return out


if __name__ == "__main__":
    sections = {
        "donation": donation_evidence,
        "hierarchical": hierarchical_evidence,
        "quantized_cross": quantized_cross_evidence,
        "fusion": fusion_evidence,
        "overlap": overlap_evidence,
        "pipeline": pipeline_evidence,
        "alltoallv_skew": alltoallv_skew_evidence,
        "striped": striped_evidence,
        "host_gap": host_gap_evidence,
        "scaling": scaling_projection,
    }
    import sys

    wanted = sys.argv[1:] or list(sections)
    unknown = [w for w in wanted if w not in sections]
    if unknown:
        raise SystemExit(f"unknown section(s) {unknown}; "
                         f"choose from {list(sections)}")
    evidence = {name: sections[name]() for name in wanted}
    print(json.dumps(evidence, indent=2))
