"""Job kind ``train_lm``: a trainer's loop on a language model.

The step is the one of the README quick start, reached through the
surface a user calls: ``hvd.init()``, ``hvd.DistributedOptimizer`` around
``optax.adamw``, a donated ``jax.jit`` step on one chip and
``hvd.spmd_step`` over ``hvd.init()``'s mesh on several (parameters
replicated, batch rows split over ``hvd.rank_axis()``), batches from the
benchmark's stream through ``hvd.infeed_pipeline(mode="double")``.

Set-up, in order: ``hvd.init``; the weights made on the device in one
jitted call from ``--seed``; the plain reference (now, while little else
is on the chip); the optimizer state; the cell's one step shape compiled
or read from the persistent cache (a loaded program reserves its
scratch); the system's first steps, which are compared with the
reference's and are the warm-up. Then the window: a closed loop with one step
in flight — dispatch step i, then block on step i-1's loss — that ends at
the first completion after ``--seconds``.

``correct``: the first ``check_steps`` losses, through the system's step
on the stream's first batches, agree with the plain reference
(``reference/<family>.py``; float32, full matmul precision, plain
``optax.adamw``, one device, microbatches) within the cell's
``tolerance``. So does the size of Adam's second moment after those
steps: Adam's update does not change when every gradient is scaled, so
the losses cannot see a missing 1/n in the gradient mean. And so does
the distance each top-level module of the parameters moved over those
steps: at a learning rate of 1e-4 a layer left out of the update moves
the loss by less than the bf16 step's own rounding (PR 22 tried it on
the chip). After the window: every loss finite; across chips, each device got
its share of the batch rows and the parameters are equal on all.

Two ways to get the reference's three numbers, and the cell's
``check_steps`` decides which (the ``check`` note's ``mode``). With 2 or
more, ``trainer``: a plain AdamW trainer takes ``check_steps`` steps from
a copy of the weights. With the system's weights, the copy, the
trainer's own start, its two moments, the gradient accumulator and one
microbatch's gradients that is 28 bytes a parameter on one chip, whatever
the number of chips: a ceiling of about 500M parameters on a 16 GB chip.
With 1, ``first_step``, which a model past that ceiling needs and any
model can take: one jitted call reads the system's own parameter buffers
in place, accumulates the first batch's gradients, and works out from
them, a leaf at a time, what the trainer's first step would leave: the
sum of Adam's second moments and each module's movement (``optax.adamw``
itself, ``init`` and ``update`` on one leaf; equal to the trainer's
after one step to 1e-6, ``tests/benchmark``). 12 bytes a parameter, and
the system's ``start`` is kept on the host. One step carries no optimizer
state to a next one, so this way does not see it; the cells with two
checked steps run the same ``optim.py`` and do.

The objective, for the reference as for the system: each chip's rows form
a group; the loss is the mean over groups of the group's mean
cross-entropy over its scored positions (all positions where the traffic
has no ``score_rate``).
"""

from __future__ import annotations

import functools
import glob
import itertools
import os
import shutil
import time

from benchmark import device as device_lib
from benchmark import flops, hlo_counts, trace_reduce
from benchmark.stream import token_stream, tokens_per_step

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_EVENTS = ("/jax/compilation_cache/cache_hits",
                "/jax/compilation_cache/cache_misses")
INFEED_WAIT = "hvd_tpu_infeed_wait_seconds"


class _Monitor:
    """Counts JAX's own compile and compile-cache events."""

    def __init__(self):
        import jax

        self.counts = {COMPILE_EVENT: 0, **{e: 0 for e in CACHE_EVENTS}}
        jax.monitoring.register_event_listener(self._count)
        jax.monitoring.register_event_duration_secs_listener(self._count)

    def _count(self, event, *_, **__):
        if event in self.counts:
            self.counts[event] += 1

    @property
    def compiles(self):
        return self.counts[COMPILE_EVENT]


def _infeed_wait_seconds(hvd):
    samples = hvd.metrics().get(INFEED_WAIT, {}).get("samples", [])
    return float(sum(s["value"]["sum"] for s in samples))


def _adam_nu_sum(opt_state):
    """Sum of Adam's second moments, wherever the optimizer's state keeps
    its ``ScaleByAdamState``."""
    import jax
    import jax.numpy as jnp
    import optax

    def is_adam(x):
        return isinstance(x, optax.ScaleByAdamState)

    adam = [x for x in jax.tree.leaves(opt_state, is_leaf=is_adam)
            if is_adam(x)]
    if len(adam) != 1:
        raise ValueError(f"expected one ScaleByAdamState in the optimizer "
                         f"state, found {len(adam)}")
    return sum(jnp.sum(x.astype(jnp.float32))
               for x in jax.tree.leaves(adam[0].nu))


def _module_moves(after, before):
    """How far each top-level module of the parameters moved: the norm
    of ``after - before``, a module."""
    import jax
    import jax.numpy as jnp

    return {name: jnp.sqrt(sum(
        jnp.sum((a - b) ** 2) for a, b in zip(
            jax.tree.leaves(after[name]), jax.tree.leaves(before[name]))))
        for name in after}


def _module_moves_from_host(after, before):
    """``_module_moves`` where ``before`` is on the host (numpy): a leaf
    at a time goes back to the device, and no more than one module's
    leaves are there at once."""
    import jax
    import jax.numpy as jnp

    squares = jax.jit(lambda a, b: jnp.sum((a - b) ** 2))
    return {name: float(jnp.sqrt(sum(
        squares(a, b) for a, b in zip(
            jax.tree.leaves(after[name]), jax.tree.leaves(before[name])))))
        for name in after}


def _microbatches(batch, groups, micro):
    """``(tokens, weights)`` of one batch in microbatches of ``micro``
    rows. The weights are the objective's: each chip's rows form a group,
    and a scored position weighs 1 / (groups x the group's scored
    positions)."""
    import numpy as np

    tokens = batch["tokens"]
    rows, seq_len = tokens.shape[0], tokens.shape[1] - 1
    scored = batch.get("scored", np.ones((rows, seq_len), np.float32))
    per_group = scored.reshape(groups, -1).sum(1)
    weights = (scored.reshape(groups, -1)
               / (groups * np.maximum(per_group, 1.0))[:, None])
    return (tokens.reshape(rows // micro, micro, -1),
            weights.astype(np.float32).reshape(rows // micro, micro, -1))


def _accumulated_gradients(reference, config):
    """``(p, tokens, weights) -> (loss, gradients)`` of the plain
    reference over one batch, a microbatch at a time under ``lax.scan``:
    the accumulator and one microbatch's gradients are all it holds."""
    import jax
    import jax.numpy as jnp

    def micro_loss(p, tokens, weights):
        with jax.default_matmul_precision("highest"):
            return (reference.token_losses(p, {"tokens": tokens}, config)
                    * weights).sum()

    def accumulate(p, tokens, weights):
        def body(carry, xs):
            loss, grads = jax.value_and_grad(micro_loss)(p, *xs)
            return (carry[0] + loss,
                    jax.tree.map(jnp.add, carry[1], grads)), None

        zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, p))
        return jax.lax.scan(body, zero, (tokens, weights))[0]

    return accumulate


def _reference_steps(reference, config, params, batches, groups, micro,
                     learning_rate):
    """``check_steps`` of 2 or more. The plain trainer: ``len(batches)``
    AdamW steps from ``params`` (a copy: the step donates it) on one
    device. Returns ``(losses, nu_sum, moves)``."""
    import jax
    import jax.numpy as jnp
    import optax

    tx = optax.adamw(learning_rate)
    accumulate = _accumulated_gradients(reference, config)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(p, state, tokens, weights):
        loss, grads = accumulate(p, tokens, weights)
        updates, state = tx.update(grads, state, p)
        return optax.apply_updates(p, updates), state, loss

    state = jax.jit(tx.init)(params)
    start = jax.tree.map(jnp.copy, params)      # the step donates
    losses = []
    for batch in batches:
        params, state, loss = step(params, state,
                                   *_microbatches(batch, groups, micro))
        losses.append(float(loss))
    moves = jax.jit(_module_moves)(params, start)
    return (losses, float(jax.jit(_adam_nu_sum)(state)),
            {k: float(v) for k, v in moves.items()})


def _reference_first_step(reference, config, params, batch, groups, micro,
                          learning_rate):
    """``check_steps`` of 1. What the plain trainer's first step from
    ``params`` on ``batch`` would leave, without taking it: ``params`` are
    the system's own buffers, read and neither copied nor donated. One
    jitted call accumulates the gradients and reads them a leaf at a
    time, so no second tree is held: the leaf's ``optax.adamw`` state
    after ``init`` and one ``update`` gives its part of the sum of Adam's
    second moments, and the leaf's update, applied, how far it moves.
    Only scalars leave the call. Returns ``(losses, nu_sum, moves)``."""
    import jax
    import jax.numpy as jnp
    import optax

    tx = optax.adamw(learning_rate)
    accumulate = _accumulated_gradients(reference, config)

    @jax.jit
    def first_step(p, tokens, weights):
        loss, grads = accumulate(p, tokens, weights)
        nu, moves = 0.0, {}
        for name in p:
            squares = 0.0
            for leaf, grad in zip(jax.tree.leaves(p[name]),
                                  jax.tree.leaves(grads[name])):
                updates, state = tx.update(grad, tx.init(leaf), leaf)
                nu += _adam_nu_sum(state)
                # As ``_module_moves`` reads it from a trainer: after
                # less before, rounded as the parameter is.
                squares += jnp.sum(
                    (optax.apply_updates(leaf, updates) - leaf) ** 2)
            moves[name] = jnp.sqrt(squares)
        return loss, nu, moves

    loss, nu, moves = first_step(params, *_microbatches(batch, groups, micro))
    return ([float(loss)], float(nu),
            {k: float(v) for k, v in moves.items()})


def _gaps(losses, nu, moves, ref_losses, ref_nu, ref_moves):
    """The three numbers ``correct`` compares: ``(loss, gradient scale,
    worst module's movement, that module)``, each gap a share of the
    reference's number."""
    loss_err = max(abs(a - b) / abs(b) for a, b in zip(losses, ref_losses))
    nu_err = abs((nu / ref_nu) ** 0.5 - 1.0)
    move_errs = {k: abs(float(v) / ref_moves[k] - 1.0)
                 for k, v in moves.items()}
    worst = max(move_errs, key=move_errs.get)
    return loss_err, nu_err, move_errs[worst], worst


def _replica_checksums(hvd, params):
    """(chips, 2): each device's own sum and absolute sum of its copy of
    the parameters. ``shard_map`` with a replicated in-spec hands every
    device its local buffer and checks nothing, which is the point."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    def local(p):
        leaves = [x.astype(jnp.float32) for x in jax.tree.leaves(p)]
        return jnp.stack([sum(jnp.sum(x) for x in leaves),
                          sum(jnp.sum(jnp.abs(x)) for x in leaves)])[None]

    return np.asarray(jax.jit(jax.shard_map(
        local, mesh=hvd.mesh(), in_specs=P(), out_specs=P(hvd.rank_axis()),
        check_vma=False))(params))


def _window(seconds, compiled, state, feed):
    """The closed loop, one step in flight, until the first completion
    after ``seconds``. The step then in flight is drained and not counted.
    Returns the new state and ``{"t0", "completions", "losses",
    "dispatched"}`` (host clock; ``losses`` include the drained step's)."""
    import jax

    params, opt_state = state
    completions, losses, dispatched, pending = [], [], 0, None
    t0 = time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation("infeed.next"):
            batch = next(feed)
        with jax.profiler.TraceAnnotation("step.dispatch"):
            params, opt_state, loss = compiled(params, opt_state, batch)
        dispatched += 1
        if pending is not None:
            with jax.profiler.TraceAnnotation("loss.fetch"):
                losses.append(float(pending))
            completions.append(time.perf_counter())
            if completions[-1] - t0 >= seconds:
                break
        pending = loss
    with jax.profiler.TraceAnnotation("loss.fetch"):
        losses.append(float(loss))
    return (params, opt_state), {"t0": t0, "completions": completions,
                                 "losses": losses, "dispatched": dispatched}


def run(run):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd

    cell = run.cell
    preset = cell["rehearsal"] if run.rehearse else cell
    config = run.catalog.config(preset["config"])
    traffic = run.catalog.traffic(preset["traffic"])
    family = run.catalog.module("families", config["family"])
    reference = run.catalog.module("reference", config["family"])
    names = hlo_counts.load_names(run.catalog.names(cell))
    chips, seed = cell["chips"], run.seed
    batch_rows, seq_len = traffic["batch"], traffic["seq_len"]
    if batch_rows % chips:
        raise ValueError(f"batch {batch_rows} does not split over {chips}")
    mode = "first_step" if cell["check_steps"] == 1 else "trainer"

    # Every program of a run, small ones too, is read from the persistent
    # cache by the next run.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    monitor = _Monitor()

    # -- set-up: init and weights --------------------------------------------
    t0 = time.perf_counter()
    hvd.init()
    device = device_lib.require_devices(chips, run.rehearse)
    hvd_init_s = time.perf_counter() - t0
    peaks = None if run.rehearse else device_lib.peaks(device["kind"])
    data_parallel = chips > 1
    ax = hvd.rank_axis()
    replicated = NamedSharding(hvd.mesh(), P()) if data_parallel else None
    rows = NamedSharding(hvd.mesh(), P(ax)) if data_parallel else None

    model = family.build(config)
    opt = cell["optimizer"]
    tx = hvd.DistributedOptimizer(
        optax.adamw(opt["learning_rate"],
                    mu_dtype=jnp.dtype(opt["mu_dtype"])),
        axis_name=ax, compression=opt["compression"])

    def make_params(key):
        return model.init(key, jnp.zeros((1, seq_len), jnp.int32))["params"]

    t0 = time.perf_counter()
    params = jax.block_until_ready(
        jax.jit(make_params, out_shardings=replicated)(
            jax.random.PRNGKey(seed)))
    weights_s = time.perf_counter() - t0

    # -- the reference, while only the weights are on the chip: the step
    # program reserves its scratch when it is loaded ------------------------
    def stream():
        return token_stream(seed, traffic, config["vocab_size"])

    first = list(itertools.islice(stream(), cell["check_steps"]))
    micro = min(cell["reference_microbatch"], batch_rows // chips)
    in_use_before = device_lib.memory_stats()[0].get("bytes_in_use", 0)
    t0 = time.perf_counter()
    if mode == "first_step":
        ref_losses, ref_nu, ref_moves = _reference_first_step(
            reference, config,
            jax.tree.map(lambda x: x.addressable_data(0), params),
            first[0], chips, micro, opt["learning_rate"])
    else:
        ref_losses, ref_nu, ref_moves = _reference_steps(
            reference, config,
            jax.tree.map(lambda x: jnp.copy(x.addressable_data(0)), params),
            first, chips, micro, opt["learning_rate"])
    reference_s = time.perf_counter() - t0
    # What the reference added to the chip, as the allocator saw it: live
    # buffers are "in use", a running program's temporaries "reserved" (PR
    # 26's chip runs: 3.3 GB reserved against 0.4 GB in use at 6 x S4096).
    # Two peaks of the process so far, which need not fall together.
    stats_after_reference = device_lib.memory_stats()[0]
    reference_peaks = {
        "reference_peak_in_use_bytes": max(
            0, stats_after_reference.get("peak_bytes_in_use", 0)
            - in_use_before),
        "reference_peak_reserved_bytes":
            stats_after_reference.get("peak_bytes_reserved", 0)}
    n_params = sum(x.size for x in jax.tree.leaves(params))

    # -- set-up: optimizer state, the step program ---------------------------
    t0 = time.perf_counter()
    opt_state = jax.block_until_ready(
        jax.jit(tx.init, out_shardings=replicated)(params))
    state = [params, opt_state]
    del params, opt_state
    init_s = hvd_init_s + weights_s + time.perf_counter() - t0

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: family.loss(model, p, batch))(params)
        if data_parallel:
            loss = jax.lax.pmean(loss, ax)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    if data_parallel:
        jitted = hvd.spmd_step(step, in_specs=(P(), P(), P(ax)),
                               out_specs=(P(), P(), P()),
                               donate_argnums=(0, 1))
    else:
        jitted = jax.jit(step, donate_argnums=(0, 1))

    batch_shape = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=rows)
                   for k, v in first[0].items()}
    misses = monitor.counts[CACHE_EVENTS[1]]
    t0 = time.perf_counter()
    compiled = jitted.lower(*state, batch_shape).compile()
    compile_s = time.perf_counter() - t0
    cold = monitor.counts[CACHE_EVENTS[1]] > misses
    mem = compiled.memory_analysis()
    step_hbm = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    counts = hlo_counts.count(compiled.as_text(), names)
    attention = family.attention_calls(config, batch_rows // chips, seq_len)
    run.say("setup", device=device, hvd_init_s=hvd_init_s,
            weights_s=weights_s, init_s=init_s, reference_s=reference_s,
            compile_s=compile_s, step_compile_was_cold=cold,
            cache_dir=jax.config.jax_compilation_cache_dir,
            step_program_bytes={
                "arguments": mem.argument_size_in_bytes,
                "outputs": mem.output_size_in_bytes,
                "aliased": mem.alias_size_in_bytes,
                "temporaries": mem.temp_size_in_bytes,
                "step_hbm": step_hbm},
            memory_stats_after_reference=stats_after_reference,
            config=preset["config"], traffic=preset["traffic"],
            rehearsal=run.rehearse)
    run.say("hlo", **counts,
            mosaic_calls_are_all_flash=(
                counts["mosaic_calls"] == counts["flash_mosaic_calls"]))

    # -- correctness: the system's first steps are also the warm-up ---------
    t_check = time.perf_counter()
    feed = hvd.infeed_pipeline(stream(), mode="double", sharding=rows)
    try:
        losses, shard_rows = [], None
        if mode == "first_step":    # no second copy on the chip
            start = jax.tree.map(np.asarray, state[0])
        else:
            start = jax.tree.map(jnp.copy, state[0])    # the step donates
        for _ in first:
            batch = next(feed)
            if shard_rows is None:
                shards = batch["tokens"].addressable_shards
                shard_rows = sorted((str(s.device), s.data.shape[0])
                                    for s in shards)
            *state, loss = compiled(*state, batch)
            losses.append(float(loss))
        nu = float(jax.jit(_adam_nu_sum)(state[1]))
        if mode == "first_step":
            moves = _module_moves_from_host(state[0], start)
        else:
            moves = jax.jit(_module_moves)(state[0], start)
        del start
        tol = preset["tolerance"]
        loss_err, nu_err, move_err, worst_module = _gaps(
            losses, nu, moves, ref_losses, ref_nu, ref_moves)
        spread = (len(shard_rows) == chips
                  and all(r * chips == batch_rows for _, r in shard_rows))
        checks = {"losses_match_reference": loss_err <= tol["loss_rtol"],
                  "gradient_scale_matches_reference":
                      nu_err <= tol["grad_scale_rtol"],
                  "every_module_moved_as_the_reference":
                      move_err <= tol["module_move_rtol"],
                  "batch_rows_spread_over_chips": spread}
        run.say("check", mode=mode, system_losses=losses,
                reference_losses=ref_losses,
                loss_rel_err=loss_err, loss_rtol=tol["loss_rtol"],
                grad_scale_rel_err=nu_err,
                grad_scale_rtol=tol["grad_scale_rtol"],
                module_move_rel_err=move_err,
                module_move_worst=worst_module,
                module_move_rtol=tol["module_move_rtol"],
                **reference_peaks, parameters=n_params,
                batch_shards=shard_rows,
                check_s=time.perf_counter() - t_check)

        # -- the window --------------------------------------------------
        traced = {}
        wait0, compiles0 = _infeed_wait_seconds(hvd), monitor.compiles
        trace_s = min(cell["trace_seconds"], run.seconds / 2) \
            if run.trace else 0.0
        state, main = _window(run.seconds - trace_s, compiled, state, feed)
        wait_s = _infeed_wait_seconds(hvd) - wait0
        compiles = monitor.compiles - compiles0
        all_losses = list(main["losses"])
        attempted = main["dispatched"]
        if run.trace:
            shutil.rmtree(run.scratch, ignore_errors=True)
            os.makedirs(run.scratch)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(run.scratch, profiler_options=options)
            try:
                state, tail = _window(trace_s, compiled, state, feed)
            finally:
                jax.profiler.stop_trace()
            all_losses += tail["losses"]
            attempted += tail["dispatched"]
            compiles = monitor.compiles - compiles0
            files = glob.glob(os.path.join(
                run.scratch, "plugins", "profile", "*", "*.xplane.pb"))
            if files:
                traced = trace_reduce.reduce_trace(
                    trace_reduce.load_xplane(files[0], names),
                    tail["dispatched"], names)
    finally:
        feed.close()

    # -- after the window ------------------------------------------------
    failed = int(sum(not np.isfinite(x) for x in all_losses))
    checks["every_loss_finite"] = failed == 0
    if data_parallel:
        sums = _replica_checksums(hvd, state[0])
        leaf = jax.tree.leaves(state[0])[0]
        checks["parameters_equal_on_all_chips"] = bool(
            leaf.sharding.is_fully_replicated
            and len(leaf.sharding.device_set) == chips
            and (sums == sums[0]).all())
    # The allocator's peak counts live buffers only; a loaded program's
    # scratch is under ``bytes_reserved`` (PR 22's chip runs: reserved is
    # within 1% of the step's ``temp_size_in_bytes``).
    stats = device_lib.memory_stats()
    alloc_peak = max((max(s.get("peak_bytes_in_use", 0),
                          s.get("bytes_in_use", 0)
                          + s.get("bytes_reserved", 0)) for s in stats),
                     default=0)
    done = main["completions"]
    intervals = np.diff([main["t0"]] + done)
    tokens = tokens_per_step(traffic)
    tokens_per_s = tokens * len(done) / (done[-1] - main["t0"])
    setup_s = main["t0"] - run.t_start
    run.say("window", steps=len(done), attempted=attempted,
            setup_s=setup_s, train_tokens_per_s=float(tokens_per_s),
            first_loss=all_losses[0], last_loss=all_losses[-1],
            compiles=compiles, infeed_wait_s=wait_s, checks=checks,
            compile_cache={"hits": monitor.counts[CACHE_EVENTS[0]],
                           "misses": monitor.counts[CACHE_EVENTS[1]]},
            memory_stats=stats[0])
    if traced:
        least, bound = flops.attention_step_roofline(attention, peaks)
        run.say("trace", steps=traced["steps"], window_s=traced["window_s"],
                mean=traced["mean"], per_device=traced["per_device"],
                worst_idle_device=traced["worst_idle_device"],
                attention_least_ms_a_step=1e3 * least,
                attention_roofline_bound=bound)

    device = dict(device, memory_peak_bytes=int(alloc_peak))
    if traced:
        device.update(busy_s=traced["mean"]["busy_s"],
                      window_s=traced["window_s"])
    return {
        "correct": all(checks.values()),
        "attempted": attempted,
        "failed": failed,
        "end_to_end": {"train_tokens_per_s": float(tokens_per_s),
                       "step_hbm_gib": step_hbm / 2 ** 30,
                       "setup_s": setup_s},
        "device": device,
        "host": {"init_s": init_s, "compile_s": compile_s,
                 "step_intervals_s": [float(x) for x in intervals],
                 "steps": len(done), "infeed_wait_s": wait_s,
                 "compiles_in_window": compiles,
                 "tokens_per_s": float(tokens_per_s)},
        "cell": {"chips": chips, "tokens_per_step": tokens,
                 "flops_per_token": family.train_flops_per_token(
                     config, seq_len),
                 "attention": attention,
                 "peaks": peaks},
        "memory": {"alloc_peak_bytes": int(alloc_peak),
                   "step_hbm_bytes": int(step_hbm)},
        "trace": traced,
        "names": names,
        "breakdown": traced.get("breakdown"),
    }
