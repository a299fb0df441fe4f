"""Expert parallelism — top-k gated MoE with all-to-all dispatch.

The reference exposes alltoall with negotiated uneven splits
(operations.cc:1020-1081) as the primitive "added for such use cases"
(SURVEY.md §2.7 EP); this module provides the actual capability: GShard
style top-2 gating with capacity, einsum-based dispatch/combine (one-hot
matmuls — MXU-friendly, no scatters), and all-to-all routing of token
blocks to the devices holding each expert. Static capacity keeps every
shape compile-time constant (the XLA analog of the reference's
recv-split negotiation: instead of negotiating sizes at runtime,
overflow tokens are dropped and weighted by the combine tensor).

The dispatch/combine exchange is a first-class hot path (docs/moe.md),
peer to the allreduce stack:

* **wire compression** — ``wire="bf16"/"int8"`` carries the token
  payloads block-scaled on the wire (``collectives.compressed_alltoall``;
  activations, not reduced gradients, so no error feedback is needed —
  the per-element error is bounded by one cast/quantization step).
* **mesh routing** — ``route=`` decomposes the exchange into per-axis
  phases over a ``WirePlan`` (``collectives.mesh_alltoall``), e.g. fp32
  on the fast ICI axis and int8 on the slow DCN hop.
* **overlap pipelining** — ``overlap_chunks=k`` splits the capacity dim
  into ``k`` chunks and chains their exchanges with
  ``optimization_barrier`` so the dispatch
  alltoall of chunk ``k+1`` is free to fly while the expert FFN of
  chunk ``k`` computes. Chunking along capacity is a pure reshape —
  numerics are unchanged (``expert_fn`` must therefore be token-wise:
  a map over token rows, like any MLP).
* **load telemetry** — ``return_stats=True`` adds a stats dict
  (dropped token-routes, demanded per-expert load); the host-side
  :func:`record_moe_stats` publishes it as the
  ``hvd_tpu_moe_{dropped_tokens,dropped_frac,expert_load}`` gauges.

Beside it, the dropless path for a rank that is TOLD WHICH EXPERTS IT
HOLDS (:func:`held_experts_layer`, docs/moe.md): the router scores all
``num_experts``, takes the top k, and this rank computes its own
experts' part of the result for the routes that reach them — every one
of them, whatever the imbalance. No (T, E, C) one-hot exists: the routes
are sorted by expert and the SwiGLU experts run as grouped matmuls over
ragged groups (``jax.lax.ragged_dot``), a block of routes at a time, in a
loop as long as the routes demand. It is what an expert-parallel
exchange would feed; it adds no exchange itself.
"""

from __future__ import annotations

import functools
import itertools
import math
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..common import metrics as metrics_lib
from ..common import scopes

_METRICS_ON = metrics_lib.enabled()
_M_DROPPED = metrics_lib.gauge(
    "hvd_tpu_moe_dropped_tokens",
    "token-routes dropped in the most recently recorded MoE step: by "
    "capacity overflow on the capacity path (global count across the ep "
    "world; record_moe_stats), routes to a held expert left uncomputed "
    "on the held-experts path, which must read 0 (record_held_stats)")
_M_DROP_FRAC = metrics_lib.gauge(
    "hvd_tpu_moe_dropped_frac",
    "dropped token-routes as a fraction of all top-k routes in the most "
    "recently recorded MoE step (the capacity-factor health number; "
    "docs/moe.md runbook)")
_M_LOAD = metrics_lib.gauge(
    "hvd_tpu_moe_expert_load",
    "demanded token-routes per expert (top-k assignments INCLUDING "
    "dropped ones — the skew signal) in the most recently recorded MoE "
    "step; on the held-experts path the experts held here, by their "
    "global index, summed over the layers", labels=("expert",))
_M_LOCAL = metrics_lib.gauge(
    "hvd_tpu_moe_local_routes",
    "top-k token-routes that reached an expert held on this rank in the "
    "most recently recorded step, summed over the layers (held-experts "
    "path; record_held_stats)")
_M_WORKED = metrics_lib.gauge(
    "hvd_tpu_moe_worked_rows",
    "rows the held experts' blocks worked on in the most recently "
    "recorded step, summed over the layers: the first block's rung and "
    "every further block whole; local_routes over it is the fill of the "
    "passes around the grouped matmuls (record_held_stats)")
_HELD_GAUGES = {"local_routes": _M_LOCAL, "dropped_tokens": _M_DROPPED,
                "worked_rows": _M_WORKED}


def top2_gating(logits, capacity: int, noise=None):
    """GShard top-2 gating.

    logits: (T, E) router outputs for T local tokens.
    ``noise`` (optional, same shape) is added to the logits before
    gating — the noisy-gating jitter (Shazeer et al. 2017, GShard's
    input jitter): it decorrelates an untrained router's systematically
    skewed argmax so capacity overflow reflects genuine load, not init
    bias (docs/moe.md runbook).
    Returns (dispatch (T, E, C) bool-ish, combine (T, E, C) weights,
    aux_loss scalar).
    """
    if noise is not None:
        logits = logits + noise
    return _top2_gating_with_demand(logits, capacity)[:3]


def _top2_gating_with_demand(logits, capacity: int):
    """top2_gating plus the per-expert DEMANDED route counts (top-2
    assignments before the capacity cut — derived from the same one-hot
    selections the dispatch uses, so the load gauges can never drift
    from the actual routing)."""
    t, e = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    g1_idx = jnp.argmax(probs, axis=-1)                       # (T,)
    g1 = jnp.take_along_axis(probs, g1_idx[:, None], -1)[:, 0]
    probs_wo1 = probs * (1.0 - jax.nn.one_hot(g1_idx, e))
    g2_idx = jnp.argmax(probs_wo1, axis=-1)
    g2 = jnp.take_along_axis(probs_wo1, g2_idx[:, None], -1)[:, 0]

    # Load-balancing auxiliary loss (GShard eq. 4 style).
    me = probs.mean(axis=0)
    ce = jax.nn.one_hot(g1_idx, e).mean(axis=0)
    aux = (me * ce).sum() * e

    def positions(idx):
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)      # (T, E)
        pos = jnp.cumsum(onehot, axis=0) - 1                  # pos in expert
        return onehot, (pos * onehot).sum(axis=-1)            # (T,E),(T,)

    oh1, pos1 = positions(g1_idx)
    # Second choice queues behind all first choices.
    count1 = oh1.sum(axis=0)                                  # (E,)
    oh2, pos2_raw = positions(g2_idx)
    pos2 = pos2_raw + jnp.take(count1, g2_idx)

    keep1 = pos1 < capacity
    keep2 = pos2 < capacity
    g1 = g1 * keep1
    g2 = g2 * keep2
    # Renormalize the surviving pair weights to sum to 1 (tokens whose
    # expert overflowed lose that share — the static-capacity analog of
    # dropped sends).
    denom = jnp.maximum(g1 + g2, 1e-9)
    g1, g2 = g1 / denom, g2 / denom

    def one_dispatch(gate, idx, pos, keep):
        oh_e = jax.nn.one_hot(idx, e)                         # (T, E)
        oh_c = jax.nn.one_hot(pos, capacity)                  # (T, C)
        d = oh_e[:, :, None] * oh_c[:, None, :] * keep[:, None, None]
        return d, d * gate[:, None, None]

    d1, c1 = one_dispatch(g1, g1_idx, pos1, keep1)
    d2, c2 = one_dispatch(g2, g2_idx, pos2, keep2)
    dispatch = jnp.clip(d1 + d2, 0.0, 1.0)
    combine = c1 + c2
    demand = (oh1 + oh2).sum(axis=0).astype(jnp.float32)
    return dispatch, combine, aux, demand


def _resolve_plan(route):
    if route is None:
        return None
    from ..ops.collectives import WirePlan

    return WirePlan.resolve(route)


def ep_size(axis_name: Optional[str] = "ep", route=None) -> int:
    """Expert-parallel world size: the product of the route plan's axis
    sizes when ``route`` is given, else the size of ``axis_name`` (1
    with neither — the local, exchange-free MoE)."""
    plan = _resolve_plan(route)
    if plan is not None:
        n = 1
        for p in plan.phases:
            n *= lax.axis_size(p.axis)
        return n
    if axis_name is None:
        return 1
    return lax.axis_size(axis_name)


def ep_index(axis_name: Optional[str] = "ep", route=None):
    """This rank's expert-parallel index, SLOW-AXIS-MAJOR under a route
    plan (matching ``collectives.mesh_alltoall``'s global order) — the
    index an ``expert_fn`` uses to find its global expert ids."""
    plan = _resolve_plan(route)
    if plan is not None:
        idx = jnp.zeros((), jnp.int32)
        for p in reversed(plan.phases):        # slow axis first
            idx = idx * lax.axis_size(p.axis) + lax.axis_index(p.axis)
        return idx
    if axis_name is None:
        return jnp.zeros((), jnp.int32)
    return lax.axis_index(axis_name)


@jax.custom_vjp
def _chain_barrier(x, token):
    """Differentiable ``optimization_barrier``: the lax primitive has no
    VJP rule (it sits INSIDE the differentiated MoE layer, unlike the
    gradient-side chain of ``optim.ZeroOptimizer``), so the custom rule
    barriers the cotangents too — the backward walk's exchanges get the
    same issue-order pinning as the forward's. Identity on values both
    ways; numerics untouched."""
    return lax.optimization_barrier((x, token))


def _chain_barrier_fwd(x, token):
    return lax.optimization_barrier((x, token)), None


def _chain_barrier_bwd(_, g):
    return lax.optimization_barrier(g)


_chain_barrier.defvjp(_chain_barrier_fwd, _chain_barrier_bwd)


def _capacity_bounds(capacity: int, chunks: int):
    """Static contiguous split of the capacity dim into ``chunks``
    segments (last may be shorter)."""
    chunks = max(1, min(int(chunks), capacity))
    step = -(-capacity // chunks)
    return [(lo, min(lo + step, capacity))
            for lo in range(0, capacity, step)]


def moe_layer(x, gate_w, expert_fn: Callable, num_experts: int,
              capacity_factor: float = 1.25,
              axis_name: Optional[str] = "ep",
              route=None, wire: str = "none", overlap_chunks: int = 1,
              key=None, use_pallas=None, return_stats: bool = False,
              router_noise_std: float = 0.0,
              quantize_min_bytes: Optional[int] = None):
    """One MoE layer with experts sharded over the expert-parallel world.

    x: (T, D) local tokens on each ep device; gate_w: (D, E) router;
    expert_fn(local_idx, tokens (rows, D)) -> same shape, applied to the
    LOCAL experts' token slabs (num_experts/n experts per device). With
    ``overlap_chunks > 1`` it is called once per capacity chunk, so it
    must be TOKEN-WISE (a pure map over token rows — any MLP is).

    Flow (GShard): gate -> dispatch einsum -> all_to_all (tokens to the
    device owning the expert) -> expert MLP -> all_to_all back ->
    combine. The exchanges ride the wire-compressed / mesh-routed
    alltoall family (module docstring; docs/moe.md):

    - ``axis_name`` — the flat ep axis; ``None`` (and no ``route``)
      selects the local, exchange-free layer (n = 1).
    - ``route`` — a ``WirePlan`` (or spec/name ``WirePlan.resolve``
      accepts): the exchange becomes ``mesh_alltoall`` over the plan's
      axes with PER-AXIS wire formats; the plan's wires win over
      ``wire``, and the ep world is the product of the plan's axes.
    - ``wire`` — flat-axis payload format: ``"none"``/``"bf16"``/
      ``"int8"``, or ``"auto"`` (int8 when the slab crosses the
      ``fusion.assign_alltoall_wire`` size threshold, bf16 below it;
      the threshold is ``quantize_min_bytes`` when given, else the
      configured ``quantize_min_bucket_bytes`` — the same
      HVD_TPU_QUANTIZE_MIN_BYTES knob the eager alltoall consults).
    - ``overlap_chunks`` — capacity-dim pipelining depth (1 = off).
    - ``key`` — stochastic rounding for int8 hops (folded per chunk
      and phase); ``return_stats`` — also return the load/drop stats
      dict for :func:`record_moe_stats`.
    - ``router_noise_std`` — noisy-gating jitter (needs ``key``): adds
      ``std * N(0, 1)`` to the router logits before top-2 selection;
      different ranks draw different noise (the key is folded with the
      ep index), so an untrained router's init bias stops masquerading
      as expert load (docs/moe.md).

    Returns ``(y, aux_loss)`` or ``(y, aux_loss, stats)``.
    """
    from ..ops import collectives as C

    plan = _resolve_plan(route)
    if plan is not None:
        psum_axes: Optional[Tuple[str, ...]] = plan.axis_names
        n = 1
        for p in plan.phases:
            n *= lax.axis_size(p.axis)
    elif axis_name is not None:
        n = lax.axis_size(axis_name)
        psum_axes = (axis_name,) if n > 1 else None
    else:
        n, psum_axes = 1, None
    if num_experts % n != 0:
        raise ValueError(f"{num_experts} experts not divisible by ep={n}")
    e_local = num_experts // n
    t, d = x.shape
    capacity = int(capacity_factor * t * 2 / num_experts) or 1

    if wire == "auto":
        from ..common import fusion as fusion_lib

        qmin = quantize_min_bytes
        if qmin is None:
            # Honor the configured threshold when the runtime is up —
            # the SAME knob the eager alltoall's "auto" consults
            # (HVD_TPU_QUANTIZE_MIN_BYTES); fall back to the module
            # default outside an initialized context.
            try:
                from ..common import basics

                if basics.is_initialized():
                    qmin = basics.context().config \
                        .quantize_min_bucket_bytes
            except Exception:  # noqa: BLE001 — default below
                qmin = None
        slab_bytes = (num_experts * capacity * d
                      * jnp.dtype(x.dtype).itemsize)
        wire = fusion_lib.assign_alltoall_wire(
            slab_bytes, qmin if qmin is not None
            else fusion_lib.A2A_QUANTIZE_MIN_BYTES)

    logits = x.astype(jnp.float32) @ gate_w.astype(jnp.float32)
    if router_noise_std > 0.0 and key is not None:
        nk = jax.random.fold_in(jax.random.fold_in(key, 999),
                                ep_index(axis_name, route))
        logits = logits + router_noise_std * jax.random.normal(
            nk, logits.shape, jnp.float32)
    dispatch, combine, aux, demand = _top2_gating_with_demand(logits,
                                                              capacity)

    def exchange(buf, fold):
        kk = None if key is None else jax.random.fold_in(key, fold)
        if plan is not None:
            return C.mesh_alltoall(buf, plan, key=kk,
                                   use_pallas=use_pallas)
        if n == 1:
            return buf
        return C.compressed_alltoall(buf, axis_name, wire, key=kk,
                                     use_pallas=use_pallas)

    # (T,D),(T,E,C) -> (E,C,D): expert-major slabs of dispatched tokens,
    # viewed as (n, e_local, C, D) destination-major (slow-axis-major
    # global device order under a route plan — mesh_alltoall's order).
    slabs = jnp.einsum("td,tec->ecd", x.astype(jnp.float32),
                       dispatch).astype(x.dtype)
    slabs = slabs.reshape(n, e_local, capacity, d)

    # Dispatch exchanges, capacity-chunked and issue-order chained: the
    # barrier pins alltoall k before k+1 on the shared wire while each
    # chunk's expert compute depends only on its OWN routed slab — the
    # async-collective scheduler may then fly exchange k+1 under FFN k
    # (docs/overlap.md; inert on CPU, numerics unchanged either way).
    bounds = _capacity_bounds(capacity, overlap_chunks)
    routed = []
    token = None
    for ci, (lo, hi) in enumerate(bounds):
        ck = slabs[:, :, lo:hi].reshape(n * e_local * (hi - lo), d)
        if token is not None:
            ck, token = _chain_barrier(ck, token)
        r = exchange(ck, ci)
        routed.append((r, hi - lo))
        token = r

    # Expert FFN per chunk: (n, e_l, ck, D) -> (e_l, n*ck, D) slabs.
    expert_out = []
    for r, ck in routed:
        rr = r.reshape(n, e_local, ck, d).transpose(1, 0, 2, 3)
        rr = rr.reshape(e_local, n * ck, d)
        expert_out.append(jnp.stack(
            [expert_fn(le, rr[le]) for le in range(e_local)]))

    # Inverse route back to the token owners, chained the same way.
    backs = []
    token = None
    for ci, ((_, ck), eo) in enumerate(zip(routed, expert_out)):
        b = eo.reshape(e_local, n, ck, d).transpose(1, 0, 2, 3)
        b = b.reshape(n * e_local * ck, d)
        if token is not None:
            b, token = _chain_barrier(b, token)
        g = exchange(b, 100 + ci)
        backs.append(g.reshape(n, e_local, ck, d))
        token = g
    back = jnp.concatenate(backs, axis=2) if len(backs) > 1 else backs[0]
    back = back.reshape(num_experts, capacity, d)

    y = jnp.einsum("ecd,tec->td", back.astype(jnp.float32), combine)
    y = y.astype(x.dtype)
    if not return_stats:
        return y, aux

    # Load/drop stats (fp32, globally psum-ed over the ep world):
    # demanded load counts top-2 assignments BEFORE the capacity cut —
    # the hot-expert signal, taken from the gating's OWN one-hot
    # selections (noisy jitter included — it decided the routes) so the
    # gauges can never drift from the dispatched routing; kept counts
    # surviving routes.
    demanded = demand
    kept = dispatch.sum()
    routes = jnp.asarray(2.0 * t, jnp.float32)
    if psum_axes is not None:
        demanded = lax.psum(demanded, psum_axes)
        kept = lax.psum(kept, psum_axes)
        routes = lax.psum(routes, psum_axes)
    dropped = jnp.maximum(routes - kept, 0.0)
    stats = {"dropped_tokens": dropped,
             "dropped_frac": dropped / jnp.maximum(routes, 1.0),
             "expert_load": demanded,
             "routed_tokens": routes}
    return y, aux, stats


def record_moe_stats(stats) -> dict:
    """Publish a ``moe_layer(return_stats=True)`` stats dict to the
    Prometheus/podmon surface (host-side, once per observed step):
    ``hvd_tpu_moe_dropped_tokens`` / ``hvd_tpu_moe_dropped_frac``
    gauges plus one ``hvd_tpu_moe_expert_load{expert=}`` gauge per
    expert. Returns the plain-float dict (handy for BENCH/soak
    records)."""
    load = np.asarray(stats["expert_load"], np.float64).reshape(-1)
    out = {"dropped_tokens": float(stats["dropped_tokens"]),
           "dropped_frac": float(stats["dropped_frac"]),
           "expert_load": [float(v) for v in load]}
    if _METRICS_ON:
        _M_DROPPED.set(out["dropped_tokens"])
        _M_DROP_FRAC.set(out["dropped_frac"])
        for e, v in enumerate(load):
            _M_LOAD.labels(expert=str(e)).set(float(v))
    return out


def chaos_skew_gate(gate_w):
    """Chaos site ``moe_skew`` (docs/moe.md): when the installed fault
    plan fires, bias the router weights toward one hot expert —
    ``spec.target`` names the expert column (default 0), ``spec.scale``
    the logit boost (default 10). Host-side, applied to the router
    weight between steps (the ``integrity.chaos_poison`` pattern), so
    the skewed logits flow through the REAL gating/capacity path and
    the drop/load gauges must react. One global load + None check when
    no plan is installed."""
    from ..common import faults as faults_lib

    spec = faults_lib.maybe_moe_skew()
    if spec is None:
        return gate_w
    target = int(spec.target or 0)
    scale = spec.scale if spec.scale else 10.0
    g = jnp.asarray(gate_w)
    return g.at[..., target].add(jnp.asarray(scale, g.dtype))


# -- the dropless path of a rank that holds some of the experts -------------

_SCORES = {"softmax": functools.partial(jax.nn.softmax, axis=-1),
           "sigmoid": jax.nn.sigmoid}


def route_top_k(x, router_w, top_k: int, scale: float = 1.0,
                score: str = "softmax", select_bias=None):
    """A router over ALL experts: ``(experts (T, k) int32, weights (T, k)
    fp32)``. ``score`` is what makes scores of the router's logits:
    ``"softmax"`` over the experts, or ``"sigmoid"`` of each logit by
    itself. The k experts are the top k of the scores, or of ``scores +
    select_bias`` where a bias (num_experts,) is given: the bias decides
    who is chosen and never what a choice weighs. The weights are the
    chosen experts' own scores normalised to sum to 1 over a token's k
    choices, times ``scale``; a sigmoid's sum can be near 0, so it is
    divided by ``sum + 1e-6``. The scores are computed from fp32 operands
    in three bf16 passes (``Precision.HIGH``): a route is a discrete
    choice, and a score rounded to bf16 flips one in ten of the
    eighth-against-ninth decisions among 320 experts."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=lax.Precision.HIGH)
    scores = _SCORES[score](logits)
    if select_bias is None:
        chosen, experts = lax.top_k(scores, top_k)
    else:
        experts = lax.top_k(scores + select_bias, top_k)[1]
        chosen = jnp.take_along_axis(scores, experts, -1)
    total = chosen.sum(-1, keepdims=True)
    if score == "sigmoid":
        total = total + 1e-6
    return experts, chosen / total * scale


def _block_group_sizes(group_sizes, block, block_rows):
    """How many rows of the sorted routes' block ``block`` (scalar or
    (n,)) belong to each group: the overlap of the group's span with
    [block * block_rows, (block + 1) * block_rows)."""
    ends = jnp.cumsum(group_sizes)
    lo = jnp.asarray(block)[..., None] * block_rows
    return jnp.clip(jnp.minimum(ends, lo + block_rows)
                    - jnp.maximum(ends - group_sizes, lo), 0)


def _expert_block(xg, weights, valid, w_gate, w_up, w_down, sizes):
    """The SwiGLU experts on one block of routes sorted by expert: rows
    ``xg`` (R, D), ``sizes`` (G,) rows a group. Each row's result times
    its route's weight, fp32; 0 for the rows past the groups. Those rows
    are masked on the way in and on the way out: what a grouped matmul
    leaves in rows no group owns is not defined on every backend, in the
    forward or in the transposes (the TPU's kernel leaves them
    unwritten)."""
    xg = jnp.where(valid[:, None], xg, jnp.zeros_like(xg))
    dot = functools.partial(lax.ragged_dot, group_sizes=sizes,
                            preferred_element_type=jnp.float32)
    hidden = jax.nn.silu(dot(xg, w_gate)) * dot(xg, w_up)
    y = dot(hidden.astype(xg.dtype), w_down)
    return jnp.where(valid[:, None], y * weights[:, None], 0.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 8))
def _grouped_experts(rungs, x, w_gate, w_up, w_down, weights, tokens,
                     group_sizes, loop_rows=None):
    """Sum over the routes of weight * expert(x[token]), as (T, D) fp32.
    ``tokens`` / ``weights``: the routes sorted by expert (a multiple of
    ``block_rows = rungs[-1]`` long; only the first ``group_sizes.sum()``
    count), ``w_*``: the (G, ., .) banks. The routes go through a block
    at a time in a loop of ceil(routes / block_rows) steps, the first
    outside it: the usual step fills one block and pays for no second.
    That first block works on the smallest of the static row counts
    ``rungs`` that holds the routes (:func:`first_block_rungs`): the rows
    past them are rows no route fills. With ``loop_rows`` the loop's
    blocks are that long and not ``block_rows`` (:func:`_worked_whole`:
    ``tokens`` is then ``block_rows`` and a multiple of ``loop_rows``
    long)."""
    return _grouped_experts_fwd(rungs, x, w_gate, w_up, w_down, weights,
                                tokens, group_sizes, loop_rows)[0]


def _route_block(rows, tokens, weights, group_sizes, block, offset=0):
    """Block ``block`` of ``rows`` rows, ``offset`` rows further on where
    the blocks before it were not all ``rows`` long."""
    if offset:
        start = block * rows + offset
        ends = jnp.cumsum(group_sizes)
        sizes = jnp.clip(jnp.minimum(ends, start + rows)
                         - jnp.maximum(ends - group_sizes, start), 0)
    else:
        start = block * rows
        sizes = _block_group_sizes(group_sizes, block, rows)
    valid = start + jnp.arange(rows) < group_sizes.sum()
    return (lax.dynamic_slice_in_dim(tokens, start, rows),
            lax.dynamic_slice_in_dim(weights, start, rows),
            valid, sizes)


def _rung_taken(rungs, routes):
    """Index of the smallest rung that holds ``routes`` (the last one
    where none does: the loop takes the routes past it)."""
    return sum((routes > r).astype(jnp.int32) for r in rungs[:-1])


def _first_block(rungs, rung, add_block, carry):
    """``add_block(rows, 0, carry)`` at the rung taken: a ``lax.switch``
    over the static row counts, or the one call where there is one."""
    if len(rungs) == 1:
        return add_block(rungs[0], 0, carry)
    return lax.switch(rung, [functools.partial(add_block, rows, 0)
                             for rows in rungs], carry)


def _loop_blocks(rungs, loop_rows, routes):
    """``(rows of a block of the loop, how far its blocks are offset,
    blocks in all)``: the loop's blocks follow a first block of
    ``rungs[-1]`` rows, which is their own length unless ``loop_rows``
    says another."""
    block_rows = rungs[-1]
    if loop_rows is None:
        return block_rows, 0, -(-routes // block_rows)
    return loop_rows, block_rows - loop_rows, \
        1 + -(-jnp.maximum(routes - block_rows, 0) // loop_rows)


def _grouped_experts_fwd(rungs, x, w_gate, w_up, w_down, weights, tokens,
                         group_sizes, loop_rows=None):
    banks = tuple(w.astype(x.dtype) for w in (w_gate, w_up, w_down))
    block_rows, offset, blocks = _loop_blocks(rungs, loop_rows,
                                              group_sizes.sum())
    rung = _rung_taken(rungs, group_sizes.sum())

    def add_block(rows, block, out, offset=0):
        idx, wts, valid, sizes = _route_block(
            rows, tokens, weights, group_sizes, block, offset)
        return out.at[idx].add(
            _expert_block(x[idx], wts, valid, *banks, sizes))

    out = _first_block(rungs, rung, add_block,
                       jnp.zeros(x.shape, jnp.float32))
    out = lax.while_loop(
        lambda c: c[0] < blocks,
        lambda c: (c[0] + 1, add_block(block_rows, *c, offset)),
        (1, out))[1]
    return out, (x, w_gate, w_up, w_down, weights, tokens, group_sizes,
                 rung)


def _grouped_experts_bwd(rungs, loop_rows, residuals, dout):
    """A block at a time, as the forward went (the first at the forward's
    rung): the block's forward again and its transpose (JAX's, of the
    three grouped matmuls); dx scattered back to the tokens, the banks'
    gradients summed in fp32."""
    x, w_gate, w_up, w_down, weights, tokens, group_sizes, rung = residuals
    banks = tuple(w.astype(x.dtype) for w in (w_gate, w_up, w_down))
    block_rows, offset, blocks = _loop_blocks(rungs, loop_rows,
                                              group_sizes.sum())

    def add_block(rows, block, carry, offset=0):
        dx, dweights, dbanks = carry
        idx, wts, valid, sizes = _route_block(
            rows, tokens, weights, group_sizes, block, offset)
        _, vjp = jax.vjp(
            lambda xg, w, *b: _expert_block(xg, w, valid, *b, sizes),
            x[idx], wts, *banks)
        dxg, dwts, *db = vjp(dout[idx].astype(jnp.float32))
        db = tuple(b.astype(jnp.float32) for b in db)
        return (dx.at[idx].add(dxg.astype(jnp.float32)),
                lax.dynamic_update_slice_in_dim(
                    dweights, dwts,
                    block * rows + offset if offset else block * rows, 0),
                # the first block's are the sum so far: nothing to add to
                db if dbanks is None else tuple(
                    a + b for a, b in zip(dbanks, db)))

    carry = _first_block(rungs, rung, add_block,
                         (jnp.zeros(x.shape, jnp.float32),
                          jnp.zeros_like(weights), None))
    dx, dweights, dbanks = lax.while_loop(
        lambda c: c[0] < blocks,
        lambda c: (c[0] + 1, add_block(block_rows, *c, offset)),
        (1, carry))[1]
    return (dx.astype(x.dtype),
            *(g.astype(w.dtype) for g, w in
              zip(dbanks, (w_gate, w_up, w_down))),
            dweights, None, None)


_grouped_experts.defvjp(_grouped_experts_fwd, _grouped_experts_bwd)


def default_block_rows(tokens: int, top_k: int, held: int,
                       num_experts: int) -> int:
    """Rows of a block of routes: two and a half times what a balanced
    router sends to ``held`` of ``num_experts`` experts, so that one block
    is the step of an untrained router too (on the chip a random softmax
    router sent a layer's 8 of 320 experts 0.58 to 1.84 times the balanced
    share, steadily by seed and layer, and a second block costs 6 ms of a
    330 ms step: the banks' gradients are summed a second time); a
    multiple of 512 (the grouped matmul's row tile on the TPU) where there
    are that many, else of 8."""
    expected = tokens * top_k * held / num_experts
    tile = 512 if expected >= 512 else 8
    most = -(-tokens * min(top_k, held) // 8) * 8
    return min(most, -(-math.ceil(2.5 * expected) // tile) * tile)


def first_block_rungs(expected: float, block_rows: int) -> Tuple[int, ...]:
    """The static row counts the first block of routes may work on,
    ascending, the last ``block_rows`` itself: the step takes the smallest
    that holds the routes it counted (``_grouped_experts``), because every
    pass around the grouped matmuls (gather, masks, SwiGLU, weighting,
    scatter-add) runs over a block's rows whether a route fills them or
    not. Each rung under the block keeps an eighth of the headroom of the
    rung above it over the ``expected`` routes of a balanced router,
    ``expected * (1 + 1.5 / 8**j)`` rounded up to the grouped matmul's
    row tile (512), and a rung exists only where it sits at least 4,096
    rows under the rung above. Both numbers are read off the chip
    (PERF.md section 6, PR 37). A padded row cost 0.40 to 0.46 us a layer
    a step (45.4 ms for 16,384 rows in 6 layers), so 4,096 rows are 1.8
    ms a layer; a copy of the block in the program costs 9 s of cold
    compile, 1.4 s of a cached set-up and 44 MiB, and a rung 1,024 rows
    under the next read no faster. An eighth and not a quarter because
    a layer's routes lie within 0.85 to 1.12 of the balanced share (48
    layers of an untrained sigmoid router): a quarter's lowest rung
    (1.094) sat inside that range and needed a third rung to catch every
    twelfth layer, a third copy that won 0.4% of the step; at 1.19 one
    rung under the block holds them all. 16,384 expected routes in a
    block of 40,960 give (19456, 40960); 1,638 in a block of 4,096 give
    the block alone, and no ``conditional`` in the program."""
    rungs = (block_rows,)
    for j in itertools.count(1):
        rows = -(-math.ceil(expected * (1 + 1.5 / 8 ** j)) // 512) * 512
        if rungs[0] - rows < 4096:
            return rungs
        rungs = (rows, *rungs)


def _spill_rows(block_rows: int) -> int:
    """Rows of the blocks that take the routes past a whole first block:
    a fifth of it (half a balanced share where the block is the default
    two and a half), a multiple of the grouped matmul's row tile."""
    tile = 512 if block_rows >= 5 * 512 else 8
    return -(-block_rows // (5 * tile)) * tile


def _worked_whole(block_rows, x, w_gate, w_up, w_down, weights, tokens,
                  group_sizes):
    """:func:`_grouped_experts` at a cost that does not follow the routes:
    the first ``block_rows`` rows of the sorted routes are ONE block, and
    the routes past it, where a step has any, go through the loop in
    blocks of :func:`_spill_rows`; the groups are made to fill every block
    that runs: the rows past the routes are the last group's, at weight 0
    (their products are exact zeros in the result, in dx and in the banks'
    gradients). Returns ``(y, rows worked on)``."""
    small = _spill_rows(block_rows)
    routes = group_sizes.sum()
    worked = block_rows \
        + -(-jnp.maximum(routes - block_rows, 0) // small) * small
    weights = jnp.where(jnp.arange(tokens.size) < routes, weights, 0.0)
    y = _grouped_experts((block_rows,), x, w_gate, w_up, w_down, weights,
                         tokens, group_sizes.at[-1].add(worked - routes),
                         small)
    return y, worked


def held_experts_layer(x, router_w, w_gate, w_up, w_down, num_experts: int,
                       held: Tuple[int, int], top_k: int,
                       scale: float = 1.0,
                       block_rows: Optional[int] = None,
                       score: str = "softmax", select_bias=None,
                       whole_blocks: bool = False):
    """The routed experts' part of an MoE layer that the experts held on
    this rank give: ``held = (first, count)`` of ``num_experts``, their
    SwiGLU banks ``w_gate``, ``w_up`` (count, D, F) and ``w_down``
    (count, F, D). x: (T, D) tokens; router_w: (D, num_experts).

    The router scores all ``num_experts`` and takes ``top_k``
    (:func:`route_top_k`, which ``score`` and ``select_bias`` go to); a
    route to an expert held elsewhere is that rank's to compute and adds
    nothing here. Every route to a held expert is computed, however many
    there are (no capacity, no drop): the routes are sorted by expert and
    run through the grouped matmuls ``block_rows`` at a time
    (:func:`default_block_rows`), in as many blocks as they fill, the
    first on the rows its routes fill (:func:`first_block_rungs`; an
    explicit ``block_rows`` is one rung). With ``whole_blocks`` the first
    ``block_rows`` routes go through one block worked whole: the rows no
    route fills are handed to the last held expert at weight 0 and add
    exact zeros to the result and to every gradient, so a step costs the
    same whatever its routes are while they fit the block; routes past
    it, where there are any, take blocks a fifth that size, whole too
    (:func:`_worked_whole`). The price is the grouped matmuls on the rows
    a balanced router would not have filled. Summed
    over the ranks that hold all the experts, the results are the whole
    routed layer; over an ep axis it is what the exchange would feed
    (``first = ep_index * count``), and it adds no exchange.

    Returns ``(y (T, D) in x's dtype, stats)``: ``expert_load`` (count,)
    routes demanded of each held expert, ``local_routes`` their sum,
    ``dropped_tokens`` routes to a held expert that no block computed
    (0), ``worked_rows`` the rows the blocks worked on (the first block's
    rung and every further block whole: ``local_routes`` over it is the
    fill of the passes), all fp32 (:func:`record_held_stats`)."""
    first, count = held
    t = x.shape[0]
    if block_rows is None:
        block_rows = default_block_rows(t, top_k, count, num_experts)
        rungs = first_block_rungs(t * top_k * count / num_experts,
                                  block_rows)
    else:
        rungs = (block_rows,)       # the caller's size is the caller's
    with jax.named_scope(scopes.MOE_ROUTE):
        experts, weights = route_top_k(x, router_w, top_k, scale, score,
                                       select_bias)
        local = (experts - first).reshape(-1)
        is_held = (local >= 0) & (local < count)
        key = jnp.where(is_held, local, count)      # the others sort last
        order = jnp.argsort(key, stable=True)
        pad = -order.size % block_rows
        if whole_blocks:
            spill = -(-max(order.size - block_rows, 0)
                      // _spill_rows(block_rows)) * _spill_rows(block_rows)
            pad = block_rows + spill - order.size
        tokens = jnp.pad(order // top_k, (0, pad)).astype(jnp.int32)
        sorted_weights = jnp.pad(weights.reshape(-1)[order], (0, pad))
        group_sizes = jnp.bincount(key, length=count + 1)[:count] \
            .astype(jnp.int32)
    with jax.named_scope(scopes.MOE_EXPERTS):
        if whole_blocks:
            y, worked = _worked_whole(block_rows, x, w_gate, w_up, w_down,
                                      sorted_weights, tokens, group_sizes)
        else:
            y = _grouped_experts(rungs, x, w_gate, w_up, w_down,
                                 sorted_weights, tokens, group_sizes)
    routes = group_sizes.sum()
    if whole_blocks:
        computed = routes           # every group is handed to a block
    else:
        n_blocks = tokens.size // block_rows
        ran = -(-routes // block_rows)
        done = _block_group_sizes(group_sizes, jnp.arange(n_blocks),
                                  block_rows).sum(-1)
        computed = jnp.where(jnp.arange(n_blocks) < ran, done, 0).sum()
        worked = jnp.asarray(rungs)[_rung_taken(rungs, routes)] \
            + block_rows * (jnp.maximum(ran, 1) - 1)
    stats = {"expert_load": group_sizes.astype(jnp.float32),
             "local_routes": routes.astype(jnp.float32),
             "dropped_tokens": (routes - computed).astype(jnp.float32),
             "worked_rows": worked.astype(jnp.float32)}
    return y.astype(x.dtype), stats


def record_held_stats(stats, first: int = 0) -> None:
    """Publish a step's :func:`held_experts_layer` stats (summed over the
    layers by the caller) from INSIDE the jitted step: one
    ``jax.debug.callback`` that sets ``hvd_tpu_moe_expert_load{expert=}``
    (the held experts, by global index from ``first``),
    ``hvd_tpu_moe_local_routes``, ``hvd_tpu_moe_worked_rows`` and
    ``hvd_tpu_moe_dropped_tokens``. A
    no-op, and no callback in the program, with metrics off. The price
    of a callback: JAX does not keep a program with a host callback in
    its persistent compile cache, so the step compiles in every process
    (docs/moe.md); a caller that can return the stats from its step
    publishes them on the host instead, with plain floats, through the
    same function outside ``jit``."""
    if not _METRICS_ON:
        return

    # a stats dict written out by hand may lack the newer counters
    scalars = {k: stats[k] for k in _HELD_GAUGES if k in stats}

    def publish(load, scalars):
        for k, v in scalars.items():
            _HELD_GAUGES[k].set(float(v))
        for e, v in enumerate(np.asarray(load).reshape(-1)):
            _M_LOAD.labels(expert=str(first + e)).set(float(v))

    values = (stats["expert_load"], scalars)
    if any(isinstance(v, jax.core.Tracer) for v in jax.tree.leaves(values)):
        jax.debug.callback(publish, *values)
    else:
        publish(*values)
