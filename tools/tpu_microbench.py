#!/usr/bin/env python
"""On-chip micro-benchmarks (VERDICT r2 #3): the measurements
docs/performance.md §4b deferred. No section has run on a chip yet.

Sections:
  flash    — Pallas flash attention vs the jnp reference at
             S ∈ {1024, 2048, 4096}, fwd and fwd+bwd, bf16 causal.
  overlap  — the async-handle model's actual purpose (reference
             gpu_operations.h:107-119 async completion): N collectives
             dispatched then synchronized once vs N blocking host
             round-trips, plus compute-overlap (independent matmul chain
             issued while a large collective is in flight).
  fusion   — grouped (fused-bucket) vs per-tensor eager allreduce.

Unlike tools/perf_evidence.py this does NOT force the CPU backend — it
runs on whatever jax.devices() gives and records the platform so a CPU
record can't masquerade as chip evidence. Prints ONE JSON object.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SMALL = "--small" in sys.argv  # smoke-scale shapes (CPU CI only)
FORCE_CPU = "--cpu" in sys.argv  # same as JAX_PLATFORMS=cpu


def _log(msg):
    print(f"microbench: {msg}", file=sys.stderr, flush=True)


def _force(out):
    """Completion barrier: EVERY leaf, or sibling dispatches keep
    running past the timer (code-review r5)."""
    import jax

    return jax.block_until_ready(out)


def _time_ms(fn, iters=20, warmup=3):
    if SMALL:
        iters, warmup = 2, 1
    for _ in range(warmup):
        _force(fn())
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    _force(out)
    return (time.perf_counter() - t0) / iters * 1000


def flash_section():
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import flash_attention as fa

    rng = jax.random.PRNGKey(0)
    B, H, D = (1, 2, 64) if SMALL else (4, 8, 64)
    out = {}
    for S in (256,) if SMALL else (1024, 2048, 4096):
        q, k, v = (jax.random.normal(jax.random.fold_in(rng, i),
                                     (B, S, H, D), dtype=jnp.bfloat16)
                   for i in range(3))

        flash_f = jax.jit(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True))
        ref_f = jax.jit(lambda q, k, v: fa.reference_attention(
            q, k, v, causal=True))

        def grad_of(f):
            def loss(q, k, v):
                return f(q, k, v).astype(jnp.float32).sum()
            return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

        flash_g, ref_g = grad_of(flash_f), grad_of(ref_f)

        row = {}
        for key, fn in (("fwd_flash_ms", lambda: flash_f(q, k, v)),
                        ("fwd_ref_ms", lambda: ref_f(q, k, v)),
                        ("bwd_flash_ms", lambda: flash_g(q, k, v)),
                        ("bwd_ref_ms", lambda: ref_g(q, k, v))):
            # The O(S²) reference materializes (B,H,S,S) logits (+ saved
            # probs in backward): at S=4096 that is multi-GiB and may
            # OOM — exactly the contrast the flash kernel exists for.
            # Record the failure as a row entry, never kill the job.
            try:
                row[key] = round(_time_ms(fn), 3)
            except Exception as e:  # noqa: BLE001 — evidence collection
                msg = (str(e) or repr(e)).splitlines()[0]
                row[key] = f"failed: {msg[:120]}"
        for leg in ("fwd", "bwd"):
            a, b = row.get(f"{leg}_ref_ms"), row.get(f"{leg}_flash_ms")
            if isinstance(a, float) and isinstance(b, float) and b:
                row[f"{leg}_speedup"] = round(a / b, 2)
        out[f"S={S}"] = row
        _log(f"flash S={S}: {row}")

    # Block-size sweep at the benchmark sequence length (VERDICT r3 #2:
    # "flash block tuning at S=512"): the 128x128 default is tuned for
    # long sequences; at S=512 fewer, larger q blocks may amortize the
    # grid better. The best (bq, bk) feeds the model configs.
    S = 256 if SMALL else 512
    q, k, v = (jax.random.normal(jax.random.fold_in(rng, 7 + i),
                                 (B, S, H, D), dtype=jnp.bfloat16)
               for i in range(3))
    sweep = {}
    best = None
    for bq, bk in ((128, 128), (256, 128), (256, 256), (S, S)):
        if bq > S or bk > S:
            continue

        def make(bq=bq, bk=bk):
            f = jax.jit(lambda q, k, v: fa.flash_attention(
                q, k, v, causal=True, block_q=bq, block_k=bk))

            def loss(q, k, v):
                return f(q, k, v).astype(jnp.float32).sum()
            return f, jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

        try:
            ff, fg = make()
            fwd = round(_time_ms(lambda: ff(q, k, v)), 3)
            bwd = round(_time_ms(lambda: fg(q, k, v)), 3)
            sweep[f"bq{bq}_bk{bk}"] = {"fwd_ms": fwd, "bwd_ms": bwd}
            if best is None or fwd + bwd < best[1]:
                best = (f"bq{bq}_bk{bk}", fwd + bwd)
        except Exception as e:  # noqa: BLE001 — evidence collection
            sweep[f"bq{bq}_bk{bk}"] = (
                f"failed: {(str(e) or repr(e)).splitlines()[0][:120]}")
    if best is not None:
        sweep["best"] = best[0]
    out[f"S={S}_block_sweep"] = sweep
    _log(f"flash block sweep S={S}: {sweep}")
    return out


def striped_section():
    """Per-hop kernel costs of striped attention, single chip (VERDICT
    r4 #7's on-chip row). A single chip cannot host the n-device ring
    itself (the CPU-mesh ratio lives in perf_evidence.py striped); what
    it CAN prove is the piece the CPU interpreter can't: the three hop
    kernels striped/contiguous rings actually dispatch, on real MXU —

      full_block    — non-causal full SxS block (contiguous ring's
                      worst hop, the one that sets its critical path)
      causal_block  — triangular diagonal hop (both forms)
      strict_block  — striped's strict-diagonal fallback (roll-by-one +
                      key-mask, ring_attention.py kernel_block): must
                      cost ~the causal block, NOT the full one, or the
                      balance claim dies at the kernel level.

    ring hop cost = max over devices; striped's claim needs
    strict ~= causal << full-is-not-needed-every-hop."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import flash_attention as fa

    rng = jax.random.PRNGKey(5)
    B, H, D = (1, 2, 64) if SMALL else (4, 8, 64)
    out = {}
    for S in (256,) if SMALL else (1024, 2048):
        q, k, v = (jax.random.normal(jax.random.fold_in(rng, i),
                                     (B, S, H, D), dtype=jnp.bfloat16)
                   for i in range(3))
        kmask = jnp.ones((B, S), jnp.float32).at[:, 0].set(0.0)

        # flash_attention auto-selects the Pallas kernel on TPU (jnp
        # fallback keeps the CPU --small smoke meaningful). The strict
        # hop is exactly striped's kernel_block form: roll K/V one right
        # + mask the wrapped slot (ring_attention.py:250-261).
        full_f = jax.jit(
            lambda q, k, v: fa.flash_attention(q, k, v, causal=False))
        causal_f = jax.jit(
            lambda q, k, v: fa.flash_attention(q, k, v, causal=True))
        strict_f = jax.jit(
            lambda q, k, v: fa.flash_attention(
                q, jnp.roll(k, 1, axis=1), jnp.roll(v, 1, axis=1),
                mask=kmask, causal=True))

        row = {}
        for key, fn in (("full_block_ms", lambda: full_f(q, k, v)),
                        ("causal_block_ms", lambda: causal_f(q, k, v)),
                        ("strict_block_ms", lambda: strict_f(q, k, v))):
            try:
                row[key] = round(_time_ms(fn), 3)
            except Exception as e:  # noqa: BLE001 — evidence collection
                row[key] = (
                    f"failed: {(str(e) or repr(e)).splitlines()[0][:120]}")
        if all(isinstance(row.get(f"{p}_block_ms"), float)
               for p in ("full", "causal", "strict")):
            row["strict_vs_causal"] = round(
                row["strict_block_ms"] / row["causal_block_ms"], 2)
            row["full_vs_causal"] = round(
                row["full_block_ms"] / row["causal_block_ms"], 2)
        out[f"S={S}"] = row
        _log(f"striped hop kernels S={S}: {row}")
    return out


def overlap_section():
    import jax
    import jax.numpy as jnp

    import horovod_tpu as hvd

    hvd.init()
    nelem = 1 << 12 if SMALL else 1 << 20
    ntens = 4 if SMALL else 16  # each name costs one eager compile
    tensors = [np.ones((nelem,), np.float32) for _ in range(ntens)]

    def async_batch():
        handles = [hvd.allreduce_async(t, op=hvd.Sum, name=f"ov{i}")
                   for i, t in enumerate(tensors)]
        return [hvd.synchronize(h) for h in handles]

    def sync_each():
        outs = []
        for i, t in enumerate(tensors):
            o = hvd.allreduce(t, op=hvd.Sum, name=f"sv{i}")
            _force(o)  # a real host round trip per tensor
            outs.append(o)
        return outs

    dispatch = {
        "tensors": ntens,
        "mib_each": round(nelem * 4 / 2**20, 3),
        "async_then_sync_ms": round(_time_ms(async_batch, iters=10), 2),
        "blocking_each_ms": round(_time_ms(sync_each, iters=10), 2),
    }
    dispatch["speedup"] = round(
        dispatch["blocking_each_ms"] / dispatch["async_then_sync_ms"], 2)

    # Compute-overlap: a big collective in flight while an INDEPENDENT
    # matmul chain runs. Serial = sync the collective first, then the
    # matmuls; overlapped = dispatch async, run matmuls, sync last.
    big = np.ones((1 << 14 if SMALL else 1 << 22,), np.float32)  # 16 MiB
    dim = 256 if SMALL else 2048
    a = jax.device_put(np.random.default_rng(0)
                       .standard_normal((dim, dim))
                       .astype(np.float32))

    @jax.jit
    def matmul_chain(a):
        for _ in range(2 if SMALL else 8):
            a = jnp.tanh(a @ a) * 0.01
        return a

    def overlapped():
        h = hvd.allreduce_async(big, op=hvd.Sum, name="ovl_big")
        c = matmul_chain(a)
        return hvd.synchronize(h), c

    def serialized():
        o = hvd.allreduce(big, op=hvd.Sum, name="ser_big")
        _force(o)  # wait out the collective before starting compute
        c = matmul_chain(a)
        return o, c

    compute = {
        "collective_mib": round(big.nbytes / 2**20, 3),
        "overlapped_ms": round(_time_ms(overlapped, iters=10), 2),
        "serialized_ms": round(_time_ms(serialized, iters=10), 2),
    }
    compute["speedup"] = round(
        compute["serialized_ms"] / compute["overlapped_ms"], 2)
    return {"dispatch": dispatch, "compute_overlap": compute,
            "world_size": hvd.size()}


def fusion_section():
    import horovod_tpu as hvd

    hvd.init()
    ngrp = 8 if SMALL else 64
    tensors = {f"g{i}": np.ones((256,), np.float32) for i in range(ngrp)}

    def grouped():
        out = hvd.grouped_allreduce(tensors, op=hvd.Sum, name="fuse")
        _force(out)  # one barrier for the whole fused bucket
        return out

    def per_tensor():
        outs = []
        for i, v in enumerate(tensors.values()):
            o = hvd.allreduce(v, op=hvd.Sum, name=f"pt{i}")
            _force(o)  # one barrier per tensor, matching dispatches
            outs.append(o)
        return outs

    out = {"tensors": ngrp,
           "grouped_ms": round(_time_ms(grouped, iters=10), 2),
           "per_tensor_ms": round(_time_ms(per_tensor, iters=10), 2)}
    out["speedup"] = round(out["per_tensor_ms"] / out["grouped_ms"], 1)
    return out


def kernels_section():
    """Chip-proof for the Pallas kernel families no model bench
    exercises: adasum dot-norms/combine (ops/pallas_kernels.py:141,184
    — the VHDD math of reference adasum.h:195-390) and block-scaled
    int8 quantization (:237 — the wire-compression lever of the
    int8-DCN hierarchical path). The r3 Mosaic bug showed the CPU
    interpreter does NOT catch TPU tiling-rule violations, so until a
    kernel has compiled AND matched its jnp oracle on the real chip it
    is only believed working."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import pallas_kernels as pk

    n = 1 << 14 if SMALL else 1 << 22  # 4M elements (16 MiB fp32)
    key = jax.random.PRNGKey(7)
    a = jax.random.normal(key, (n,), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(8), (n,), jnp.float32) * 3

    out = {}

    # adasum: pallas vs jnp-oracle numerics + timing.
    dn_p = jax.jit(lambda a, b: pk.adasum_dot_norms(a, b,
                                                    use_pallas=True))
    dn_j = jax.jit(lambda a, b: pk.adasum_dot_norms(a, b,
                                                    use_pallas=False))
    got, ref = np.asarray(dn_p(a, b)), np.asarray(dn_j(a, b))
    dn_err = float(np.max(np.abs(got - ref) / np.maximum(np.abs(ref),
                                                         1e-6)))
    cb_p = jax.jit(lambda a, b, s: pk.adasum_combine(a, b, s,
                                                     use_pallas=True))
    cb_j = jax.jit(lambda a, b, s: pk.adasum_combine(a, b, s,
                                                     use_pallas=False))
    s = dn_j(a, b)
    cb_err = float(np.max(np.abs(np.asarray(cb_p(a, b, s))
                                 - np.asarray(cb_j(a, b, s)))))
    out["adasum"] = {
        "n_elements": n,
        "dot_norms_rel_err": round(dn_err, 8),
        "combine_abs_err": round(cb_err, 8),
        "dot_norms_pallas_ms": round(_time_ms(lambda: dn_p(a, b)), 3),
        "dot_norms_jnp_ms": round(_time_ms(lambda: dn_j(a, b)), 3),
        "combine_pallas_ms": round(_time_ms(lambda: cb_p(a, b, s)), 3),
        "combine_jnp_ms": round(_time_ms(lambda: cb_j(a, b, s)), 3),
    }
    _log(f"kernels adasum: {out['adasum']}")

    # int8 block quant: roundtrip error must be bounded by the absmax
    # step size; pallas and jnp paths must agree exactly on q.
    q_p = jax.jit(lambda x: pk.quantize_int8(x, use_pallas=True))
    q_j = jax.jit(lambda x: pk.quantize_int8(x, use_pallas=False))
    qp, sp, np_ = q_p(a)
    qj, sj, _ = q_j(a)
    q_agree = bool(np.array_equal(np.asarray(qp), np.asarray(qj)))
    deq = jax.jit(lambda q, s: pk.dequantize_int8(
        q, s, np_, a.shape, use_pallas=True))
    rt = np.asarray(deq(qp, sp))
    # per-block bound: |x - deq(x)| <= scale/2 per element.
    step = float(np.max(np.asarray(sp)))
    rt_err = float(np.max(np.abs(rt - np.asarray(a))))
    out["int8_quant"] = {
        "n_elements": n,
        "q_pallas_equals_jnp": q_agree,
        "roundtrip_max_abs_err": round(rt_err, 6),
        "max_block_scale": round(step, 6),
        "err_within_half_step": bool(rt_err <= step / 2 + 1e-6),
        "quant_pallas_ms": round(_time_ms(lambda: q_p(a)[0]), 3),
        "quant_jnp_ms": round(_time_ms(lambda: q_j(a)[0]), 3),
    }
    _log(f"kernels int8: {out['int8_quant']}")
    # The pass/fail bit IS this section's deliverable: an oracle
    # mismatch must fail the job (non-zero exit -> the queue records a
    # failure and retries) instead of landing as green-looking
    # evidence with a false buried in it.
    ok = (dn_err < 1e-3 and cb_err < 1e-3 and q_agree
          and out["int8_quant"]["err_within_half_step"])
    out["ok"] = bool(ok)
    if not ok:
        raise SystemExit(f"kernels section oracle mismatch: {out}")
    return out


def compression_section():
    """Ground truth for the autotuner's compression dimension (the
    ISSUE-3 tentpole): payload sizes × {fp32, bf16, int8, int8_ef}
    allreduce, reporting (a) analytic bytes-on-wire per device for a
    ring/ICI schedule, (b) quantize/dequantize kernel overhead in
    isolation, and (c) end-to-end in-jit allreduce latency. int8 is the
    round-to-nearest quantized allreduce (the eager/stateless form);
    int8_ef adds seeded stochastic rounding (the optimizer's
    error-feedback form — same wire bytes, slightly more VPU work).
    On CPU the collective is a memcpy, so the latency columns only
    prove dispatch correctness; the chip run gives the real curve."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.ops import collectives as C
    from horovod_tpu.ops import pallas_kernels as pk

    ctx = hvd.init()
    n = hvd.size()
    ax = hvd.rank_axis()
    mesh = ctx.mesh
    rng = jax.random.PRNGKey(13)
    sizes = (1 << 14,) if SMALL else (1 << 18, 1 << 20, 1 << 22)

    def spmd(fn):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P(ax),
                                     out_specs=P(ax)))

    key = jax.random.PRNGKey(99)
    out = {"world_size": n}
    for nelem in sizes:
        x = jax.random.normal(rng, (n, nelem), jnp.float32) * 3
        mib = nelem * 4 / 2**20

        forms = {
            "fp32": spmd(lambda v: jax.lax.psum(v, ax)),
            "bf16": spmd(lambda v: jax.lax.psum(
                v.astype(jnp.bfloat16), ax).astype(v.dtype)),
            "int8": spmd(lambda v: C.quantized_allreduce(
                v.reshape(v.shape[1:]), C.ReduceOp.SUM, ax)[None]),
            "int8_ef": spmd(lambda v: C.quantized_allreduce(
                v.reshape(v.shape[1:]), C.ReduceOp.SUM, ax,
                key=key)[None]),
        }
        # Ring allreduce moves 2*(n-1)/n of the buffer per device; the
        # quantized form carries int8 payload + one fp32 scale per 4096
        # elements on both hops.
        ring = 2 * (n - 1) / max(n, 1)
        wire = {
            "fp32": ring * nelem * 4,
            "bf16": ring * nelem * 2,
            "int8": ring * (nelem + 4 * nelem / 4096),
            "int8_ef": ring * (nelem + 4 * nelem / 4096),
        }

        row = {"mib": round(mib, 3)}
        for name, fn in forms.items():
            try:
                row[f"{name}_ms"] = round(_time_ms(lambda: fn(x)), 3)
            except Exception as e:  # noqa: BLE001 — evidence collection
                row[f"{name}_ms"] = (
                    f"failed: {(str(e) or repr(e)).splitlines()[0][:120]}")
            row[f"{name}_wire_mib"] = round(wire[name] / 2**20, 3)
        if isinstance(row.get("fp32_ms"), float):
            for name in ("bf16", "int8", "int8_ef"):
                v = row.get(f"{name}_ms")
                if isinstance(v, float) and v:
                    row[f"{name}_speedup"] = round(row["fp32_ms"] / v, 2)
        # The ring factor 2*(n-1)/n cancels in the ratio (and is 0 on a
        # single device, where nothing touches the wire) — report the
        # payload ratio, which holds at any world size.
        row["int8_wire_reduction_vs_fp32"] = round(
            (nelem * 4) / (nelem + 4 * nelem / 4096), 2)

        # Quantize/dequant overhead in isolation (the cost the wire win
        # must beat): one flat buffer, jitted kernel round trips.
        flat = x[0]
        qfn = jax.jit(lambda v: pk.quantize_int8(v)[0])
        qsr = jax.jit(lambda v: pk.quantize_int8_stochastic(v, key)[0])
        q, s, cnt = pk.quantize_int8(flat)
        dq = jax.jit(lambda q, s: pk.dequantize_int8(
            q, s, cnt, flat.shape))
        row["quantize_ms"] = round(_time_ms(lambda: qfn(flat)), 3)
        row["quantize_sr_ms"] = round(_time_ms(lambda: qsr(flat)), 3)
        row["dequantize_ms"] = round(_time_ms(lambda: dq(q, s)), 3)
        out[f"{round(mib, 2)}MiB"] = row
        _log(f"compression {mib:.2f}MiB: {row}")
    return out


def alltoall_section():
    """The MoE dispatch hot path (docs/moe.md): payload ×
    {fp32, bf16, int8} compressed_alltoall — analytic bytes-on-wire per
    device + measured e2e in-jit latency — plus the flat-vs-mesh-routed
    analytic bytes-per-link model (the `mesh_routing` treatment applied
    to the PERMUTE family). The analytic half runs everywhere, so the
    wire win is recorded even off-chip; the acceptance bits check int8
    cuts dispatch bytes ~4x vs fp32 and the mesh-routed plan's
    cross-axis bytes sit STRICTLY below flat at the fusion threshold.
    An exchange over n ranks keeps (n-1)/n of the buffer on the wire
    (the self chunk stays local); a permutation has nothing to reduce,
    so the slow-axis win is pure wire format."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from horovod_tpu.ops import collectives as C

    ndev = len(jax.devices())
    if ndev >= 4 and ndev % 2 == 0:
        nc, nl = 2, ndev // 2
    else:
        nc, nl = 2, 4
    n = nc * nl
    plan_flat = C.WirePlan.parse("local:none,cross:none")
    plan_quant = C.WirePlan.parse("local:none,cross:int8")
    threshold = 64 * 1024 * 1024
    out = {"modeled_mesh": f"{nc}x{nl}", "world_size": n,
           "fusion_threshold_mib": threshold // 2**20}

    # Analytic: per-device bytes on the wire, flat axis, by wire format.
    ok_int8 = True
    ok_mesh = True
    for mib in ((0.0625, 1, 16, 64) if SMALL else (0.0625, 1, 16, 64,
                                                   256)):
        nelems = int(mib * 2**20 / 4)
        ring = (n - 1) / n
        wires = {
            "fp32": ring * nelems * 4,
            "bf16": ring * nelems * 2,
            "int8": ring * (nelems + 4 * nelems / 4096),
        }
        row = {"payload_mib": mib}
        for wname, b in wires.items():
            row[f"{wname}_wire_mib"] = round(b / 2**20, 4)
        row["int8_reduction_vs_fp32"] = round(
            wires["fp32"] / wires["int8"], 2)
        ok_int8 = ok_int8 and wires["fp32"] / wires["int8"] > 3.9
        # Mesh-routed cross-axis bytes vs the flat exchange's slow-link
        # exposure ((nc-1)/nc of the buffer can cross hosts, at the
        # native dtype).
        flat_slow = (nc - 1) / nc * nelems * 4
        routed = C.alltoall_wire_cost(plan_quant, nelems, (nl, nc))
        row["flat_slow_axis_mib"] = round(flat_slow / 2**20, 4)
        row["routed_int8_slow_axis_mib"] = round(
            routed["cross"]["bytes"] / 2**20, 4)
        row["routed_slow_reduction"] = round(
            flat_slow / max(routed["cross"]["bytes"], 1e-9), 2)
        if mib * 2**20 >= threshold:
            ok_mesh = ok_mesh and routed["cross"]["bytes"] < flat_slow
        out[f"{mib}MiB"] = row
        _log(f"alltoall {mib}MiB: {row}")
    out["int8_cuts_bytes_4x"] = bool(ok_int8)
    out["routed_cross_bytes_below_flat_at_threshold"] = bool(ok_mesh)

    # Measured: in-jit exchange latency per wire over the live world
    # (single flat axis), plus the mesh-routed form when the backend
    # factors a 2xN mesh. On CPU the collective is a memcpy, so the
    # latency columns prove dispatch correctness; the chip run gives
    # the real curve.
    nlive = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()), ("hvd",))
    nelem = 1 << 12 if SMALL else 1 << 20
    x = np.random.default_rng(7).standard_normal(
        (nlive, nlive * nelem)).astype(np.float32)

    def spmd(fn):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P("hvd"),
                                     out_specs=P("hvd")))

    key = jax.random.PRNGKey(23)
    forms = {
        "fp32_ms": spmd(lambda v: C.alltoall(
            v.reshape(v.shape[1:]), "hvd")[None]),
        "bf16_ms": spmd(lambda v: C.compressed_alltoall(
            v.reshape(v.shape[1:]), "hvd", "bf16")[None]),
        "int8_ms": spmd(lambda v: C.compressed_alltoall(
            v.reshape(v.shape[1:]), "hvd", "int8", key=key)[None]),
    }
    timed = {"payload_mib": round(nlive * nelem * 4 / 2**20, 3),
             "world_size": nlive}
    for fname, fn in forms.items():
        try:
            timed[fname] = round(_time_ms(lambda: fn(x), iters=5), 3)
        except Exception as e:  # noqa: BLE001 — evidence collection
            timed[fname] = (
                f"failed: {(str(e) or repr(e)).splitlines()[0][:120]}")
    out["measured_flat"] = timed
    _log(f"alltoall measured flat: {timed}")

    if ndev >= 4 and ndev % 2 == 0:
        devs = np.array(jax.devices()).reshape(nc, nl)
        mesh2 = Mesh(devs, ("cross", "local"))
        spec = P(("cross", "local"))

        def spmd2(fn):
            return jax.jit(jax.shard_map(fn, mesh=mesh2, in_specs=spec,
                                         out_specs=spec))

        mforms = {
            "flat_ms": spmd2(lambda v: C.alltoall(
                v.reshape(v.shape[1:]), ("cross", "local"))[None]),
            "routed_ms": spmd2(lambda v: C.mesh_alltoall(
                v.reshape(v.shape[1:]), plan_flat)[None]),
            "routed_int8_ms": spmd2(lambda v: C.mesh_alltoall(
                v.reshape(v.shape[1:]), plan_quant, key=key)[None]),
        }
        mtimed = {"payload_mib": timed["payload_mib"]}
        for fname, fn in mforms.items():
            try:
                mtimed[fname] = round(_time_ms(lambda: fn(x), iters=5),
                                      3)
            except Exception as e:  # noqa: BLE001 — evidence collection
                mtimed[fname] = (
                    f"failed: "
                    f"{(str(e) or repr(e)).splitlines()[0][:120]}")
        out["measured_mesh"] = mtimed
        _log(f"alltoall measured mesh: {mtimed}")
    else:
        out["measured_mesh"] = (f"skipped: {ndev} device(s), need an "
                                "even count >= 4 to factor a 2xN mesh")
    if not (ok_int8 and ok_mesh):
        raise SystemExit(f"alltoall section acceptance failed: {out}")
    return out


def mesh_routing_section():
    """Bytes-per-link model + (when the backend serves >=4 devices)
    measured latency for the topology-aware router (docs/topology.md):
    flat ring allreduce vs 2D-staged (RS local -> AR cross -> AG local)
    vs per-axis-quantized (int8 on the cross hop) across payload sizes.

    The analytic half runs EVERYWHERE — pure arithmetic over
    collectives.mesh_wire_cost — so the wire-cost win is recorded in the
    evidence JSON even when the live-TPU bench times out. The model
    prices the SLOWEST axis: a topology-oblivious flat ring moves
    2(N-1)/N * B per device and every byte can transit the slow
    cross-host link; the staged plan's cross hop carries only the
    1/local_size shard, and the quantized plan carries that shard as
    int8 (+ fp32 block scales). The acceptance bit checks the per-axis
    plan moves STRICTLY fewer slow-axis bytes than flat for every
    payload at or above the fusion threshold."""
    import jax

    from horovod_tpu.ops import collectives as C

    ndev = len(jax.devices())
    # Modeled topology: the live device factorization when it exists,
    # else the canonical 2-host x 4-chip slice.
    if ndev >= 4 and ndev % 2 == 0:
        nc, nl = 2, ndev // 2
    else:
        nc, nl = 2, 4
    n = nc * nl
    plan_staged = C.WirePlan.parse("local:none,cross:none")
    plan_quant = C.WirePlan.parse("local:none,cross:int8")
    threshold = 64 * 1024 * 1024  # default fusion threshold
    sizes_mib = (0.0625, 1, 16, 64) if SMALL else (0.0625, 1, 16, 64,
                                                   256)
    out = {"modeled_mesh": f"{nc}x{nl}", "world_size": n,
           "fusion_threshold_mib": threshold // 2**20}
    ok = True
    for mib in sizes_mib:
        nelems = int(mib * 2**20 / 4)
        flat_slow = 2.0 * (n - 1) / n * nelems * 4  # every byte can
        # transit the slow link in a topology-oblivious ring
        staged = C.mesh_wire_cost(plan_staged, nelems, (nl, nc))
        quant = C.mesh_wire_cost(plan_quant, nelems, (nl, nc))
        row = {
            "payload_mib": mib,
            "flat_slow_axis_mib": round(flat_slow / 2**20, 4),
            "staged_slow_axis_mib": round(
                staged["cross"]["bytes"] / 2**20, 4),
            "quantized_slow_axis_mib": round(
                quant["cross"]["bytes"] / 2**20, 4),
            "staged_fast_axis_mib": round(
                staged["local"]["bytes"] / 2**20, 4),
        }
        row["staged_slow_reduction"] = round(
            flat_slow / max(staged["cross"]["bytes"], 1e-9), 2)
        row["quantized_slow_reduction"] = round(
            flat_slow / max(quant["cross"]["bytes"], 1e-9), 2)
        if mib * 2**20 >= threshold:
            ok = ok and quant["cross"]["bytes"] < flat_slow \
                and staged["cross"]["bytes"] < flat_slow
        out[f"{mib}MiB"] = row
        _log(f"mesh_routing {mib}MiB: {row}")
    out["slow_axis_strictly_fewer_bytes_at_threshold"] = bool(ok)

    # Measured arm: only meaningful when the backend actually serves a
    # multi-device mesh (the live chip run, or a CPU world forced to
    # >=4 virtual devices). Skipped — with the reason recorded — on a
    # single chip, so the analytic model above is never lost with it.
    if ndev >= 4 and ndev % 2 == 0:
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P

        devs = np.array(jax.devices()).reshape(nc, nl)
        mesh = Mesh(devs, ("cross", "local"))
        spec = P(("cross", "local"))
        nelem = 1 << 14 if SMALL else 1 << 22
        x = np.random.default_rng(3).standard_normal(
            (n, nelem)).astype(np.float32)

        def spmd(fn):
            return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=spec,
                                         out_specs=spec))

        forms = {
            "flat_ms": spmd(lambda v: jax.lax.psum(
                v, ("cross", "local"))),
            "staged_ms": spmd(lambda v: C.mesh_allreduce(
                v.reshape(nelem), C.ReduceOp.SUM, plan_staged)[None]),
            "quantized_ms": spmd(lambda v: C.mesh_allreduce(
                v.reshape(nelem), C.ReduceOp.SUM, plan_quant)[None]),
            "adasum_ms": spmd(lambda v: C.mesh_allreduce(
                v.reshape(nelem), C.ReduceOp.ADASUM, plan_staged)[None]),
        }
        timed = {"payload_mib": round(nelem * 4 / 2**20, 3)}
        for name, fn in forms.items():
            try:
                timed[name] = round(_time_ms(lambda: fn(x), iters=5), 3)
            except Exception as e:  # noqa: BLE001 — evidence collection
                timed[name] = (
                    f"failed: {(str(e) or repr(e)).splitlines()[0][:120]}")
        out["measured"] = timed
        _log(f"mesh_routing measured: {timed}")
    else:
        out["measured"] = (f"skipped: {ndev} device(s), need an even "
                           "count >= 4 to factor a 2xN mesh")
    return out


def infeed_section():
    """Host→device input path (docs/performance.md MFU playbook):
    (a) raw host→device bandwidth (``jax.device_put`` + completion
    fetch) across transfer sizes, and (b) the consumer-visible wait per
    batch for each infeed mode — blocking placement (off) vs one batch
    staged ahead (single) vs the background double-buffered
    ``hvd.DeviceInfeed`` (double) — under a producer with real host
    cost. The double buffer's wait collapses toward zero whenever the
    per-batch host cost fits inside the step; off pays it serially every
    step. Wall-clock timing, recorded not asserted (CI boxes jitter)."""
    import jax

    from horovod_tpu import data as data_lib

    out = {}
    # (a) host→device bandwidth by payload size.
    sizes_mb = (1, 16, 64) if not SMALL else (1, 4)
    bw = {}
    for mb in sizes_mb:
        host = np.random.default_rng(0).standard_normal(
            (mb * 1024 * 1024 // 4,)).astype(np.float32)

        def put():
            return jax.device_put(host)

        ms = _time_ms(put, iters=10, warmup=2)
        bw[f"{mb}MiB"] = {
            "ms": round(ms, 3),
            "gbps": round(host.nbytes * 8 / (ms / 1e3) / 1e9, 2),
        }
    out["host_to_device"] = bw

    # (b) per-batch consumer wait by infeed mode. Producer cost and
    # simulated step time are chosen so double-buffering CAN hide the
    # producer (host_cost < step) — the measured question is whether
    # it does on this host.
    host_cost_s, step_s, batches = 0.003, 0.005, 30
    if SMALL:
        batches = 10
    batch_np = np.zeros((256, 1024), np.float32)  # 1 MiB

    def producer():
        for _ in range(batches):
            time.sleep(host_cost_s)
            yield (batch_np,)

    modes = {}
    for mode in ("off", "single", "double"):
        t0 = time.perf_counter()
        waited = 0.0
        pipe = data_lib.infeed_pipeline(producer(), mode)
        try:
            it = iter(pipe)
            while True:
                tw0 = time.perf_counter()  # wait = fetch + residency
                try:
                    b = next(it)
                except StopIteration:
                    break
                _force(b)
                waited += time.perf_counter() - tw0
                time.sleep(step_s)  # the "step"
        finally:
            pipe.close()
        wall = time.perf_counter() - t0
        modes[mode] = {
            "wall_s": round(wall, 3),
            "consumer_wait_ms_per_batch": round(
                1000.0 * waited / batches, 3),
        }
    out["modes"] = modes
    out["double_hides_producer"] = bool(
        modes["double"]["wall_s"] <= modes["off"]["wall_s"])
    return out


def seq_attention_section():
    """Sequence-parallel exchange costs (docs/sequence.md): the striped
    ring's per-step K/V hop chain (wired ppermute) vs the Ulysses
    head-scatter (wired alltoall) over the live device axis, per wire
    format — wall ms per attention call next to the trace-time
    ``hvd_tpu_seq_kv_bytes_total`` accounting both paths stamp. The
    acceptance bit: int8 must cut the sp-axis bytes ~4x vs the fp32
    run (3.9x gate; the remainder is the block-scale sidecar). A
    single-device world cannot host the exchange — it records the
    analytic per-element byte model only, marked as such."""
    import jax
    import jax.numpy as jnp

    devs = jax.devices()
    n = len(devs)
    out = {"n_devices": n}
    B, S, H, D = (1, 256, 4, 16) if SMALL else (2, 2048, 8, 64)
    if n <= 1 or S % n or H % n:
        out["basis"] = "analytic_single_device"
        eb = {"none": 4.0, "bf16": 2.0, "int8": 1.0 + 4.0 / 4096}
        out["elem_bytes"] = eb
        out["int8_cuts_4x"] = bool(eb["none"] / eb["int8"] >= 3.9)
        return out

    from jax.sharding import Mesh, PartitionSpec as P

    from horovod_tpu.common import metrics as metrics_lib
    from horovod_tpu.parallel.ring_attention import striped_attention
    from horovod_tpu.parallel.ulysses import ulysses_attention

    mesh = Mesh(np.array(devs), ("sp",))
    rng = jax.random.PRNGKey(11)
    q, k, v = (jax.random.normal(jax.random.fold_in(rng, i),
                                 (B, S, H, D), dtype=jnp.float32)
               for i in range(3))

    def _seq_bytes():
        vals = {}
        fam = metrics_lib.snapshot().get("hvd_tpu_seq_kv_bytes_total",
                                         {})
        for s in fam.get("samples", []):
            w = s["labels"].get("wire", "?")
            vals[w] = vals.get(w, 0.0) + float(s["value"])
        return vals

    def _arm(fn, wire):
        """Compile + time one wired attention; returns (ms, planned
        bytes this compile stamped for its wire)."""
        jit = jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"), check_vma=False))
        b0 = _seq_bytes().get(wire, 0.0)
        ms = _time_ms(lambda: jit(q, k, v))
        return ms, _seq_bytes().get(wire, 0.0) - b0

    rows = {}
    for wire in ("none", "bf16", "int8"):
        row = {}
        try:
            ms, nbytes = _arm(
                lambda qq, kk, vv, w=wire: striped_attention(
                    qq, kk, vv, axis_name="sp", wire=w), wire)
            row["ring_ms"] = round(ms, 3)
            row["ring_kv_bytes"] = int(nbytes)
        except Exception as e:  # noqa: BLE001 — evidence collection
            row["ring_ms"] = (
                f"failed: {(str(e) or repr(e)).splitlines()[0][:120]}")
        try:
            ms, nbytes = _arm(
                lambda qq, kk, vv, w=wire: ulysses_attention(
                    qq, kk, vv, axis_name="sp", wire=w), wire)
            row["ulysses_ms"] = round(ms, 3)
            row["ulysses_scatter_bytes"] = int(nbytes)
        except Exception as e:  # noqa: BLE001 — evidence collection
            row["ulysses_ms"] = (
                f"failed: {(str(e) or repr(e)).splitlines()[0][:120]}")
        rows[wire] = row
        _log(f"seq_attention wire={wire}: {row}")
    out["wires"] = rows
    fp32 = rows.get("none", {}).get("ring_kv_bytes")
    i8 = rows.get("int8", {}).get("ring_kv_bytes")
    if isinstance(fp32, int) and isinstance(i8, int) and i8:
        out["ring_bytes_fp32_over_int8"] = round(fp32 / i8, 3)
        out["int8_cuts_4x"] = bool(fp32 / i8 >= 3.9)
    return out


SECTIONS = {"flash": flash_section, "striped": striped_section,
            "overlap": overlap_section,
            "fusion": fusion_section, "kernels": kernels_section,
            "compression": compression_section,
            "mesh_routing": mesh_routing_section,
            "alltoall": alltoall_section,
            "seq_attention": seq_attention_section,
            "infeed": infeed_section}


def main():
    import jax

    if FORCE_CPU:
        jax.config.update("jax_platforms", "cpu")
    wanted = [a for a in sys.argv[1:] if not a.startswith("-")] \
        or list(SECTIONS)
    unknown = [w for w in wanted if w not in SECTIONS]
    if unknown:
        raise SystemExit(f"unknown section(s) {unknown}; "
                         f"choose from {list(SECTIONS)}")
    dev = jax.devices()[0]
    result = {"platform": dev.platform, "device_kind": dev.device_kind}
    for name in wanted:
        _log(f"section {name} ...")
        result[name] = SECTIONS[name]()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
