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
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..common import metrics as metrics_lib

_METRICS_ON = metrics_lib.enabled()
_M_DROPPED = metrics_lib.gauge(
    "hvd_tpu_moe_dropped_tokens",
    "token-routes dropped by capacity overflow in the most recently "
    "recorded MoE step (global count across the ep world; set by "
    "record_moe_stats from a moe_layer return_stats=True dict)")
_M_DROP_FRAC = metrics_lib.gauge(
    "hvd_tpu_moe_dropped_frac",
    "dropped token-routes as a fraction of all top-2 routes in the most "
    "recently recorded MoE step (the capacity-factor health number; "
    "docs/moe.md runbook)")
_M_LOAD = metrics_lib.gauge(
    "hvd_tpu_moe_expert_load",
    "demanded token-routes per expert (top-2 assignments INCLUDING "
    "dropped ones — the skew signal) in the most recently recorded MoE "
    "step", labels=("expert",))


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
