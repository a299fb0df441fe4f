#!/usr/bin/env python3
"""The quickest proof that horovod_tpu's main path runs on a TPU.

    python chip_smoke.py             one chip: kernels, train, serve
    python chip_smoke.py --chips 4   four chips: the data-parallel step
                                     and what it is compared with, only

One process, no children. It drives the entry points a user calls —
``hvd.init()``, ``hvd.DistributedOptimizer``, the jitted step
(``hvd.spmd_step`` across chips), ``hvd.serve`` — on ``gpt_small`` as
published, weights and tokens from a seed, and checks what comes out by
the repo's own means: each Pallas kernel against its jnp twin, losses
finite and falling, the flash kernels present in the compiled step,
served tokens equal to a plain uncached forward.

Every phase prints one JSON line. The LAST line of stdout is
``{"ok": ..., "device": {"platform", "kind", "count"}}`` with the device
as JAX reports it. Exit code 0 only when every phase passed on a TPU of
the asked size; a phase that fails raises, and nothing catches it.

The phases are plain functions of the model and sizes, so
``tests/test_chip_smoke.py`` rehearses them on the CPU at tiny size.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

SEED = 0
BUCKET = 25_000_000                                   # one 100 MB fp32 bucket
# ((b, S, h, d), causal): gpt_small twice, bert_large's unmasked attention
ATTN_SHAPES = (((8, 512, 12, 64), True), ((4, 2048, 12, 64), True),
               ((8, 512, 16, 64), False))
MOSAIC_CALL = 'custom_call_target="tpu_custom_call"'


class SmokeFailure(RuntimeError):
    """A check of a phase did not hold."""


def _require(ok, what):
    if not ok:
        raise SmokeFailure(what)


def _say(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def device_record():
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# -- kernels ------------------------------------------------------------------

def _excess(got, want, rtol, atol=0.0):
    """max(|got - want| - (atol + rtol |want|)): <= 0 is np.allclose."""
    import jax.numpy as jnp

    g, w = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.max(jnp.abs(g - w) - (atol + rtol * jnp.abs(w))))


def _kernel_and_twin(fn, *args):
    """``fn(use_pallas, *args)`` as the Pallas kernel and as its jnp twin,
    both jitted. ``use_pallas=True`` is the compiled kernel on a TPU and
    the interpreter elsewhere; on a TPU the program must hold a Mosaic
    call, or the comparison would be the twin against itself."""
    import jax

    kernel = jax.jit(functools.partial(fn, True)).lower(*args).compile()
    if jax.default_backend() == "tpu":
        _require(MOSAIC_CALL in kernel.as_text(),
                 f"{fn.__name__}: no Mosaic kernel in the compiled program")
    return kernel(*args), jax.jit(functools.partial(fn, False))(*args)


def _bucket_kernels(dtype, n):
    """The six kernels of ops/pallas_kernels.py on an ``n``-element
    bucket, each held to the tolerance tests/test_pallas_kernels.py uses
    (fp32) or to bf16's own rounding (bf16). Returns worst excesses."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import pallas_kernels as pk

    fp32 = dtype == jnp.float32
    rtol = 1e-6 if fp32 else 2e-2
    ka, kb, ku = jax.random.split(jax.random.PRNGKey(SEED), 3)
    a32 = jax.random.normal(ka, (n,), jnp.float32) * 3
    # Correlated like two ranks' gradients, so dot(a, b) is well
    # conditioned and a tolerance on it means something.
    a, b = a32.astype(dtype), (0.5 * a32 + jax.random.normal(
        kb, (n,), jnp.float32)).astype(dtype)
    scale = float(jnp.max(jnp.abs(a32))) * 2
    out = {}

    def scale_buffer(use, x):
        return pk.scale_buffer(x, 2.5, use_pallas=use)

    out["scale_buffer"] = _excess(*_kernel_and_twin(scale_buffer, a), 1e-2)

    def dot_norms(use, x, y):
        return pk.adasum_dot_norms(x, y, use_pallas=use)

    dn, dn_twin = _kernel_and_twin(dot_norms, a, b)
    out["adasum_dot_norms"] = _excess(dn, dn_twin, 1e-4)

    def combine(use, x, y, d):
        return pk.adasum_combine(x, y, d, use_pallas=use)

    out["adasum_combine"] = _excess(
        *_kernel_and_twin(combine, a, b, dn_twin), rtol,
        atol=(2 ** -23 * 4 if fp32 else rtol) * scale)

    def quantize(use, x):
        return pk.quantize_int8(x, use_pallas=use)[:2]

    (q, s), (q_twin, s_twin) = _kernel_and_twin(quantize, a)
    out["quantize_int8.scales"] = _excess(s, s_twin, 1e-6)
    out["quantize_int8.q_mismatches"] = int(jnp.sum(q != q_twin))

    def stochastic(use, x):
        return pk.quantize_int8_stochastic(x, ku, use_pallas=use)[:2]

    (qs, ss), (qs_twin, ss_twin) = _kernel_and_twin(stochastic, a)
    # Bitwise, as quantize_int8_stochastic's docstring promises.
    out["quantize_int8_stochastic.q_mismatches"] = int(
        jnp.sum(qs != qs_twin))
    out["quantize_int8_stochastic.scale_mismatches"] = int(
        jnp.sum(ss != ss_twin))

    def with_residual(use, x, r):
        q, s, _, res = pk.quantize_int8_stochastic(
            x, ku, use_pallas=use, plus=r, return_residual=True)
        return res, q, s

    # The error-feedback form: (x + r) quantised and its residual written
    # by the same kernel. On a TPU the residual is held to the twin's
    # bits (no fused multiply-add on a v5e: kernel and XLA's fusion both
    # round q * scale before they subtract); XLA:CPU contracts the two in
    # some programs, so elsewhere to one rounding of the product.
    (res, qr, sr), (res_twin, qr_twin, sr_twin) = _kernel_and_twin(
        with_residual, a, b.astype(jnp.float32) * 0.01)
    out["quantize_int8_stochastic.residual_q_mismatches"] = int(
        jnp.sum(qr != qr_twin) + jnp.sum(sr != sr_twin))
    out["quantize_int8_stochastic.residual"] = _excess(
        res, res_twin, 0.0,
        atol=0.0 if jax.default_backend() == "tpu" else 2 ** -23 * scale)

    # The same against the forms these kernels took the place of
    # (collectives.quantized_allreduce before it handed the residual and
    # the gathered result to them), written out here in plain jnp and
    # sharing no line with the kernels' body: g + r formed outside, the
    # residual by dequantising the whole buffer on the (blocks, 4096)
    # view, and a four-rank mean divided after the dequantise. Counted,
    # not tolerated: the reduction is to be the same result on the chip.
    ranks = 4
    m = n // (ranks * 4096) * ranks * 4096      # the reduction's own grid
    r32 = b[:m].astype(jnp.float32) * 0.01

    def deq_before(qq, sc):
        blocks = qq.reshape(-1, 4096).astype(jnp.float32) * sc[:, None]
        return blocks.reshape(-1)

    def residual_before(x, r):
        flat = x.astype(jnp.float32) + r
        q, s, _ = pk.quantize_int8_stochastic(flat, ku)
        return flat - deq_before(q, s), q, s

    res_was, q_was, s_was = jax.jit(residual_before)(a[:m], r32)
    res_now, q_now, s_now = jax.jit(
        functools.partial(with_residual, True))(a[:m], r32)
    out["residual_as_it_was.q_and_scale_mismatches"] = int(
        jnp.sum(q_now != q_was) + jnp.sum(s_now != s_was))
    out["residual_as_it_was.mismatches"] = int(jnp.sum(res_now != res_was))

    def mean_now(qq, sc):
        return pk.dequantize_int8(qq, sc * jnp.float32(1.0 / ranks), m, (m,),
                                  use_pallas=True)

    def mean_before(qq, sc):
        return deq_before(qq, sc) / jnp.float32(ranks)

    out["mean_as_it_was.mismatches"] = int(jnp.sum(
        jax.jit(mean_now)(q_was, s_was) != jax.jit(mean_before)(q_was, s_was)))

    def dequantize(use, qq, sc):
        return pk.dequantize_int8(qq, sc, n, (n,), dtype, use_pallas=use)

    deq, deq_twin = _kernel_and_twin(dequantize, q_twin, s_twin)
    out["dequantize_int8"] = _excess(deq, deq_twin, rtol)
    # Round trip: nearest rounding loses at most half a step per element
    # (plus float rounding at an exact tie and the output dtype's own).
    out["int8_roundtrip"] = _excess(
        deq, a, 0.0 if fp32 else 2 ** -8,
        atol=float(jnp.max(s_twin)) / 2 * (1 + 1e-4) + 1e-6)
    return out


def _flash_kernels(shape, dtype, causal=True):
    """Flash forward and the backward kernel at one (b, S, h, d)
    against ``reference_attention`` at full matmul precision.

    Tolerances are tests/test_flash_attention.py's, taken at the scale
    of the array compared (the tests' arrays are O(1); gradients here
    reach ~10). The fp32 ones hold where matmuls are fp32, which is the
    interpreter only: on the chip the MXU multiplies in bf16 passes
    whatever the operand dtype — Mosaic's default precision, as XLA's —
    so there fp32 inputs are held to the bf16 tolerance."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.flash_attention import (_resolve_blocks,
                                                 flash_attention,
                                                 reference_attention)

    fp32_matmuls = dtype == jnp.float32 and jax.default_backend() != "tpu"
    fwd_tol, bwd_tol = (2e-4, 5e-3) if fp32_matmuls else (2e-2, 2e-2)
    q, k, v = (jax.random.normal(key, shape, jnp.float32).astype(dtype)
               for key in jax.random.split(jax.random.PRNGKey(SEED), 3))

    def forward(use, q, k, v):
        if use:
            return flash_attention(q, k, v, causal=causal, use_pallas=True)
        with jax.default_matmul_precision("highest"):
            return reference_attention(
                *(x.astype(jnp.float32) for x in (q, k, v)), causal=causal)

    def backward(use, q, k, v):
        return jax.grad(lambda *a: (forward(use, *a).astype(jnp.float32)
                                    ** 2).sum(), argnums=(0, 1, 2))(q, k, v)

    got = dict(zip(("dq", "dk", "dv"), zip(
        *_kernel_and_twin(backward, q, k, v))))
    got["fwd"] = _kernel_and_twin(forward, q, k, v)
    out, measured = {}, {}
    for name, (x, ref) in got.items():
        tol = fwd_tol if name == "fwd" else bwd_tol
        scale = float(jnp.max(jnp.abs(ref)))
        out[name] = _excess(x, ref, tol, tol * max(1.0, scale))
        measured[name] = {"max_abs_err": _excess(x, ref, 0.0),
                          "ref_max": scale}
    _say("flash", shape=list(shape), dtype=jnp.dtype(dtype).name,
         causal=causal,
         blocks=_resolve_blocks(shape[1], shape[3], dtype, None, None,
                                jax.default_backend() != "tpu"),
         fwd_tol=fwd_tol, bwd_tol=bwd_tol, measured=measured)
    return out


def _rope_kernels(shape, dtype):
    """The rotation's kernels at one (b, S, h, d) against the formula a
    head at a time (``rotate_heads``: on a TPU their jnp twin is XLA's
    slices and pads, no reference), forward and gradient, on positions of
    each row's own. The products and the one sum are the formula's, so the
    two may differ by how the sum is rounded: one unit in the last place
    of |x| + |its partner| forward, two backward (the formula rounds each
    term of a gradient to the dtype before it adds them)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import rope

    b, s = shape[:2]
    x, w = (jax.random.normal(key, shape, jnp.float32).astype(dtype)
            for key in jax.random.split(jax.random.PRNGKey(SEED), 2))
    positions = jnp.arange(s)[None] * 3 + jnp.arange(b)[:, None] * 7

    def forward(use, x):
        if use:
            return rope.rotate(x, positions, use_pallas=True)
        return rope.rotate_heads(x, positions)

    def backward(use, x):
        return jax.grad(lambda x: (forward(use, x).astype(jnp.float32)
                                   * w.astype(jnp.float32)).sum())(x)

    def bound(x):
        a, half = jnp.abs(x.astype(jnp.float32)), shape[3] // 2
        return a + jnp.concatenate([a[..., half:], a[..., :half]], axis=-1)

    ulp = float(jnp.finfo(dtype).eps)
    out = {}
    for name, fn, operand, ulps in (("fwd", forward, x, 1),
                                    ("dx", backward, w, 2)):
        got, want = (y.astype(jnp.float32) for y in _kernel_and_twin(fn, x))
        out[name] = float(jnp.max(jnp.abs(got - want)
                                  - ulps * ulp * bound(operand)))
    return out


def phase_kernels(bucket=BUCKET, attn_shapes=ATTN_SHAPES,
                  dtypes=("float32", "bfloat16")):
    """Every Pallas kernel of ops/pallas_kernels.py,
    ops/flash_attention.py and ops/rope.py at a real size against its jnp
    twin (the rotation's against the formula it replaced, on q of the
    causal attention shapes)."""
    import jax.numpy as jnp

    worst = {}
    for dtype in map(jnp.dtype, dtypes):
        worst[f"bucket.{dtype.name}"] = _bucket_kernels(dtype, bucket)
        for shape, causal in attn_shapes:
            name = "x".join(map(str, shape)) + ("" if causal else ".full")
            worst[f"flash.{dtype.name}.{name}"] = _flash_kernels(
                shape, dtype, causal)
            if causal:
                worst[f"rope.{dtype.name}.{name}"] = _rope_kernels(
                    shape, dtype)
    _say("kernels", bucket_elems=bucket, excess_over_tolerance=worst)
    bad = {f"{group}.{k}": v for group, checks in worst.items()
           for k, v in checks.items() if not v <= 0}
    _require(not bad, f"kernels differ from their jnp twins: {bad}")
    return worst


# -- train --------------------------------------------------------------------

def make_train_step(hvd, model, tx, data_parallel):
    """The jitted gpt step of the README quick start and bench.py: a
    plain ``jit`` on one device, ``hvd.spmd_step`` over the rank mesh
    across several. State is donated, as a trainer's is."""
    import jax
    import optax
    from jax.sharding import PartitionSpec as P

    ax = hvd.rank_axis()

    def loss_fn(params, tokens):
        logits = model.apply({"params": params}, tokens[:, :-1])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, tokens[:, 1:]).mean()

    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        if data_parallel:
            loss = jax.lax.pmean(loss, ax)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    if not data_parallel:
        return jax.jit(step, donate_argnums=(0, 1))
    return hvd.spmd_step(step, in_specs=(P(), P(), P(ax)),
                         out_specs=(P(), P(), P()), donate_argnums=(0, 1))


def _seeded_batch_and_params(model, batch, seq_len):
    import jax

    key = jax.random.PRNGKey(SEED)
    tokens = jax.random.randint(key, (batch, seq_len + 1), 0,
                                model.vocab_size)
    return tokens, jax.jit(model.init)(key, tokens[:, :-1])["params"]


def _optimizer(hvd, compression="none"):
    import jax.numpy as jnp
    import optax

    return hvd.DistributedOptimizer(
        optax.adamw(1e-4, mu_dtype=jnp.bfloat16),
        axis_name=hvd.rank_axis(), compression=compression)


def _run_steps(compiled, params, opt_state, tokens, n):
    """``n`` steps, each timed on the host clock to block_until_ready."""
    import jax

    losses, wall = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        params, opt_state, loss = compiled(params, opt_state, tokens)
        jax.block_until_ready(loss)
        wall.append(time.perf_counter() - t0)
        losses.append(float(loss))
    return params, opt_state, losses, wall


def _cache_entries():
    import jax

    path = jax.config.jax_compilation_cache_dir
    return len(os.listdir(path)) if path and os.path.isdir(path) else 0


def phase_train(hvd, model, batch=8, seq_len=512, steps=6, warmup=2,
                require_flash=True):
    """A trainer that takes a few steps: init -> DistributedOptimizer ->
    the jitted step on one device. With ``require_flash`` the compiled
    step must hold two flash kernels per layer — flash forward, and the
    backward that gives dq, dk and dv — i.e. no layer gave way to
    ``reference_attention`` (the rotation's four kernels a layer are
    Mosaic calls too, under names of their own)."""
    import jax
    import numpy as np

    tokens, params = _seeded_batch_and_params(model, batch, seq_len)
    tx = _optimizer(hvd)
    opt_state = tx.init(params)

    # The same program built and compiled twice: the first pays whatever
    # the persistent cache does not hold; the second is a new jit
    # object, so nothing in memory knows it, and it reads what the first
    # left on disk — as the next process will.
    entries = [_cache_entries()]
    compile_s = []
    for _ in range(2):
        step = make_train_step(hvd, model, tx, data_parallel=False)
        t0 = time.perf_counter()
        compiled = step.lower(params, opt_state, tokens).compile()
        compile_s.append(round(time.perf_counter() - t0, 3))
        entries.append(_cache_entries())
    flash_calls = sum(
        MOSAIC_CALL in line and "hvd_flash" in line.split(" = ")[0]
        for line in compiled.as_text().splitlines())
    if require_flash:
        _require(flash_calls == 2 * model.num_layers,
                 f"{flash_calls} flash kernels in the step, expected "
                 f"{2 * model.num_layers}: flash_attention gave way to "
                 "the reference")
    mem = compiled.memory_analysis()

    # The device's peak counter never resets: it is this phase's peak
    # only where it rose above what earlier phases left.
    peak_before = (jax.devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use")
    params, opt_state, losses, wall = _run_steps(
        compiled, params, opt_state, tokens, warmup + steps)
    stats = jax.devices()[0].memory_stats() or {}
    _say("train", model={"layers": model.num_layers,
                         "hidden": model.hidden,
                         "heads": model.num_heads,
                         "vocab": model.vocab_size},
         batch=batch, seq_len=seq_len, warmup=warmup, steps=steps,
         compile_first_s=compile_s[0], compile_again_s=compile_s[1],
         first_compile_was_cold=entries[1] > entries[0],
         cache_dir=jax.config.jax_compilation_cache_dir,
         cache_entries=entries, flash_custom_calls=flash_calls,
         losses=losses, step_wall_s=[round(w, 5) for w in wall],
         step_program_bytes={
             "arguments": mem.argument_size_in_bytes,
             "outputs": mem.output_size_in_bytes,
             "temporaries": mem.temp_size_in_bytes} if mem else None,
         peak_bytes_before_phase=peak_before,
         peak_bytes_in_use=stats.get("peak_bytes_in_use"))
    _require(np.isfinite(losses).all(), f"non-finite loss: {losses}")
    _require(losses[-1] < losses[0], f"loss did not fall: {losses}")
    return losses


# -- serve --------------------------------------------------------------------

def phase_serve(hvd, model, max_len=1024, max_prompt_len=64, slots=4,
                n_requests=6, prompt_lens=(16, 40, 64),
                output_lens=(8, 16, 24), tie_tol=0.0):
    """One ``hvd.serve`` engine answers a few seeded requests; the
    longest answer must be the greedy continuation a plain uncached
    forward of the same model gives.

    ``tie_tol``: the cache path and the full forward sum in different
    orders, and a random-weight model's top two logits can sit closer
    than that noise. A served token that is not the reference argmax
    passes only if the reference scores it within ``tie_tol`` of its
    maximum; mismatches are counted and printed either way."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.ops.flash_attention import reference_attention

    variables = jax.jit(model.init)(jax.random.PRNGKey(SEED),
                                    jnp.zeros((1, 8), jnp.int32))
    factory = hvd.serve.engine.make_engine_factory(
        model, variables, slots=slots, max_len=max_len,
        max_prompt_len=max_prompt_len, kv_kind="fp32")
    cluster = hvd.serve.ServeCluster(
        factory, policy=hvd.serve.SLOPolicy(), replicas=1, log_path="")
    trace = hvd.serve.poisson_trace(
        seed=SEED, n_requests=n_requests, rate_rps=20.0,
        prompt_lens=prompt_lens, output_lens=output_lens,
        vocab_size=model.vocab_size)
    report = cluster.run(trace)
    _require(report["completed"] == n_requests and report["dropped"] == 0,
             f"served {report['completed']}/{n_requests} requests, "
             f"{report['dropped']} dropped")

    req = max(cluster.completed, key=lambda r: len(r.tokens))
    _require(len(req.tokens) == req.max_new_tokens,
             f"request {req.rid}: {len(req.tokens)} tokens of "
             f"{req.max_new_tokens}")
    # Teacher-forced: position P-1+t of ONE forward over prompt +
    # answer[:-1] sees exactly the context the engine had for token t.
    plain = model.clone(attend_fn=lambda q, k, v, mask=None:
                        reference_attention(q, k, v, mask, causal=True))
    seq = jnp.asarray([list(req.prompt) + list(req.tokens[:-1])],
                      jnp.int32)
    logits = np.asarray(jax.jit(plain.apply)(variables, seq)[
        0, len(req.prompt) - 1:], np.float32)
    served = np.asarray(req.tokens)
    gap = logits.max(-1) - logits[np.arange(len(served)), served]
    _say("serve", max_len=max_len, max_prompt_len=max_prompt_len,
         slots=slots, requests=n_requests, completed=report["completed"],
         generated_tokens=report["generated_tokens"],
         rounds=report["rounds"], wall_s=report["wall_s"],
         parity_request={"rid": req.rid, "prompt_len": len(req.prompt),
                         "tokens": len(served),
                         "argmax_matches": int((gap == 0).sum()),
                         "max_logit_gap": float(gap.max())})
    _require((gap <= tie_tol).all(),
             f"request {req.rid}: served tokens are not the plain "
             f"forward's greedy tokens (logit gaps {gap.tolist()})")
    return report


# -- four chips: data parallel --------------------------------------------------

def phase_dp(hvd, model, batch=8, seq_len=512, steps=6, loss_rtol=2e-3,
             int8_ef_bound=0.02):
    """The ``gpt_small`` step under ``hvd.spmd_step`` over ``init()``'s
    mesh, against the one-device step on the same global batch in the
    same process; then the same step with ``compression="int8_ef"``
    (the int8 kernels inside a real reduce-scatter / all-gather), its
    final loss within the 2% docs/compression.md documents."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = hvd.size()
    tokens, params = _seeded_batch_and_params(model, batch, seq_len)
    tx = _optimizer(hvd)

    def run(tx, data_parallel, toks):
        p = jax.tree.map(lambda x: x.copy(), params)  # the step donates
        st = tx.init(p)
        compiled = make_train_step(hvd, model, tx, data_parallel).lower(
            p, st, toks).compile()
        return compiled, _run_steps(compiled, p, st, toks, steps)

    _, (_, _, ref_losses, _) = run(tx, False, tokens)

    sharded = jax.device_put(tokens, NamedSharding(hvd.mesh(),
                                                   P(hvd.rank_axis())))
    shards = sharded.addressable_shards
    _require(len({s.device for s in shards}) == n
             and all(s.data.shape[0] * n == batch for s in shards),
             f"batch not spread 1/{n} over {n} devices: "
             f"{[(str(s.device), s.data.shape) for s in shards]}")

    compiled, (dp_params, _, dp_losses, dp_wall) = run(tx, True, sharded)
    hlo = compiled.as_text()
    _require("all-reduce" in hlo, "no all-reduce in the data-parallel step")
    leaf = jax.tree.leaves(dp_params)[0]
    _require(len(leaf.sharding.device_set) == n
             and leaf.sharding.is_fully_replicated,
             f"updated params not replicated over {n} devices")
    _require(np.allclose(dp_losses, ref_losses, rtol=loss_rtol),
             f"data-parallel losses {dp_losses} differ from the "
             f"one-device step's {ref_losses}")

    compiled_q, (_, _, q_losses, _) = run(_optimizer(hvd, "int8_ef"),
                                          True, sharded)
    q_hlo = compiled_q.as_text()
    if jax.default_backend() == "tpu":
        _require(q_hlo.count(MOSAIC_CALL) > hlo.count(MOSAIC_CALL),
                 "int8_ef step holds no quantize kernel beyond the exact "
                 "step's flash calls")
    _say("dp", devices=n, batch=batch, per_device_batch=batch // n,
         seq_len=seq_len, one_device_losses=ref_losses,
         dp_losses=dp_losses, int8_ef_losses=q_losses,
         dp_step_wall_s=[round(w, 5) for w in dp_wall],
         all_reduce_ops=hlo.count(" all-reduce("),
         int8_ef_mosaic_calls=q_hlo.count(MOSAIC_CALL),
         int8_ef_all_to_all="all-to-all" in q_hlo)
    _require(np.isfinite(q_losses).all()
             and abs(q_losses[-1] - dp_losses[-1])
             <= int8_ef_bound * abs(dp_losses[-1]),
             f"int8_ef final loss {q_losses[-1]} is not within "
             f"{int8_ef_bound:.0%} of the exact reduction's "
             f"{dp_losses[-1]}")
    return dp_losses


# -- entry ----------------------------------------------------------------------

def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4 runs the data-parallel phase and nothing "
                             "else")
    args = parser.parse_args(argv)

    import horovod_tpu as hvd
    from horovod_tpu import native
    from horovod_tpu.models.gpt import gpt_small

    hvd.init()
    device = device_record()
    ok = False
    try:
        # JAX falls to the CPU without a word when the TPU does not come
        # up; nothing below may run anywhere else.
        _require(device["platform"] == "tpu"
                 and device["count"] == args.chips,
                 f"need {args.chips} TPU chip(s), JAX reports {device}")
        _say("start", device=device, native=native.status())
        model = gpt_small()
        if args.chips == 1:
            phase_kernels()
            phase_train(hvd, model)
            # bf16 logits over a 50k vocabulary: a near-tie may flip.
            phase_serve(hvd, model, tie_tol=0.05)
        else:
            phase_dp(hvd, model)
        ok = True
    finally:
        print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
