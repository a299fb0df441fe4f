"""Decoder-only causal LM (GPT-style) in Flax — third benchmark model
family beyond the reference's CNN + BERT set (the reference scales batch
only; a causal LM is where the sequence-parallel capabilities this
framework adds — ring attention / Ulysses — earn their keep).

TPU-first choices, same pattern as models/bert.py: bf16 compute / fp32
params, fused QKV (one MXU matmul), Pallas flash attention with
``causal=True`` as the default inner loop, rotary position embeddings
(no learned position table — RoPE composes with ring attention because
positions travel with the query/key blocks), weight-tied LM head, and a
pluggable ``attend_fn`` so ``parallel/ring_attention`` can slot in for
long sequences without touching the model.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..common import scopes
from ..ops import rope as rope_lib
from ..ops.flash_attention import flash_attention


def rope(x, positions=None, base: float = 10000.0):
    """Rotary position embedding on (B, S, H, D) — rotate each head-dim
    pair by a position-dependent angle. ``positions`` (B, S) overrides
    the default arange, which is how a sequence-parallel shard applies
    its GLOBAL positions to a LOCAL block. Computed under ``hvd_rope`` on
    the packed (B, S, H·D) rows the flash kernels read wherever H and D
    pack (``ops/rope.py``): the reshapes at this edge are views."""
    return rope_lib.rotate(x, positions, base)


def _causal_attend(q, k, v, mask=None):
    return flash_attention(q, k, v, mask=mask, causal=True)


# Sequence-parallel impl names (docs/sequence.md): "ring" = striped
# causal ring attention (balanced blockwise ring over wired ppermute
# hops; tokens must arrive in stripe_layout order), "ulysses" = head/
# sequence alltoall scatter (contiguous shards; needs H % n == 0).
SEQ_IMPLS = ("ring", "ulysses")


def seq_attend_fn(seq_axis: str, seq_impl: str = "ring",
                  seq_wire: Optional[str] = None) -> Callable:
    """The causal attend_fn a sequence-parallel GPT runs: striped ring
    attention or Ulysses head scatter over ``seq_axis``, K/V exchanges
    in ``seq_wire`` (None -> ``HVD_TPU_SEQ_WIRE``)."""
    if seq_impl == "ring":
        from ..parallel.ring_attention import striped_attend_fn

        return striped_attend_fn(seq_axis, wire=seq_wire)
    if seq_impl == "ulysses":
        from ..parallel.ulysses import ulysses_attend_fn

        return ulysses_attend_fn(seq_axis, inner=_causal_attend,
                                 wire=seq_wire)
    raise ValueError(
        f"unknown seq_impl {seq_impl!r}; choose from {SEQ_IMPLS}")


def seq_positions(seq_axis: str, seq_impl: str, s_local: int):
    """(1, S_local) GLOBAL position ids of this rank's sequence shard —
    stripe positions for the ring layout, contiguous block offsets for
    Ulysses — fed to RoPE so rotary angles see global positions."""
    if seq_impl == "ring":
        from ..parallel.ring_attention import striped_positions

        return striped_positions(s_local, seq_axis)[None, :]
    return (jax.lax.axis_index(seq_axis) * s_local
            + jnp.arange(s_local))[None, :]


def _cache_attend(q, k_all, v_all, q_pos, k_pos):
    """Attention of ``s_in`` new queries over a ring-buffer KV cache
    (docs/serve.md): q (B, S_in, H, D) at global positions ``q_pos``
    (B, S_in); k_all/v_all (B, S_max, H, D) cache slabs whose line j
    holds the token at global position ``k_pos[b, j]`` (-1 = empty).
    A line is attendable iff occupied AND causally visible — validity
    is data, so prefill (S_in = prompt), single-token decode, and
    ring-wrapped sequences all share this one program. fp32 softmax
    (the standard LM-head/attention stability recipe)."""
    d = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k_all.astype(jnp.float32)) / jnp.sqrt(float(d))
    visible = ((k_pos[:, None, :] >= 0)
               & (k_pos[:, None, :] <= q_pos[:, :, None]))  # (B,S_in,S_max)
    logits = jnp.where(visible[:, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w,
                      v_all.astype(jnp.float32)).astype(q.dtype)


class MoeMlp(nn.Module):
    """Expert-parallel FFN replacing the dense MLP when the GPT
    ``moe_experts`` knob is set (docs/moe.md): GShard top-2 gating +
    all-to-all dispatch over the ``moe_axis``/``moe_route`` ep world
    (``parallel/moe.py`` — wire-compressed, mesh-routed,
    overlap-pipelined). The expert bank is REPLICATED (each rank stores
    all experts, uses only its local slice): under SPMD the backward
    all-to-all returns every rank's cotangents to the expert owner, so
    the owner-only gradient averaged across ranks equals the mean-loss
    gradient exactly — no correction factor, and the one-line
    DistributedOptimizer keeps working unchanged (sharded expert
    storage is the ZeRO-3 roadmap item).

    The load-balancing aux loss and the drop/load stats are sown into
    the ``"intermediates"`` collection (``moe_aux`` / ``moe_stats``) —
    pass ``mutable=["intermediates"]`` to collect them; plain ``apply``
    calls still work (sow is a no-op when the collection is immutable).
    """

    num_experts: int
    mlp_dim: int
    capacity_factor: float = 1.25
    dtype: Any = jnp.bfloat16
    axis_name: Optional[str] = None      # flat ep axis (None = local)
    route: Optional[str] = None          # WirePlan spec (wins over axis)
    wire: str = "none"                   # none | bf16 | int8 | auto
    overlap_chunks: int = 1
    # Noisy-gating jitter std (active only when a "gating" rng is
    # passed to apply); an untrained router's init bias otherwise
    # overflows capacity from step 0 — docs/moe.md.
    router_noise: float = 0.0

    @nn.compact
    def __call__(self, x):
        from ..parallel import moe as moe_lib

        b, s, h = x.shape
        e = self.num_experts
        gate_w = self.param("gate", nn.initializers.normal(0.02), (h, e),
                            jnp.float32)
        w_in = self.param("w_in", nn.initializers.normal(0.02),
                          (e, h, self.mlp_dim), jnp.float32)
        b_in = self.param("b_in", nn.initializers.zeros,
                          (e, self.mlp_dim), jnp.float32)
        w_out = self.param("w_out", nn.initializers.normal(0.02),
                           (e, self.mlp_dim, h), jnp.float32)
        b_out = self.param("b_out", nn.initializers.zeros, (e, h),
                           jnp.float32)

        n = moe_lib.ep_size(self.axis_name, self.route)
        e_local = e // n
        my_base = moe_lib.ep_index(self.axis_name, self.route) * e_local

        def expert_fn(local_idx, tokens):
            ge = my_base + local_idx                 # global expert id
            wi = jnp.take(w_in, ge, axis=0).astype(self.dtype)
            wo = jnp.take(w_out, ge, axis=0).astype(self.dtype)
            bi = jnp.take(b_in, ge, axis=0).astype(self.dtype)
            bo = jnp.take(b_out, ge, axis=0).astype(self.dtype)
            y = nn.gelu(tokens @ wi + bi)
            return (y @ wo + bo).astype(tokens.dtype)

        tokens = x.reshape(b * s, h)
        gkey = self.make_rng("gating") \
            if self.router_noise > 0 and self.has_rng("gating") else None
        y, aux, stats = moe_lib.moe_layer(
            tokens, gate_w, expert_fn, e,
            capacity_factor=self.capacity_factor,
            axis_name=self.axis_name, route=self.route, wire=self.wire,
            overlap_chunks=self.overlap_chunks, return_stats=True,
            key=gkey,
            router_noise_std=self.router_noise if gkey is not None
            else 0.0)
        self.sow("intermediates", "moe_aux", aux)
        self.sow("intermediates", "moe_stats", stats)
        return y.reshape(b, s, h).astype(x.dtype)


class _DenseMaster(nn.Module):
    """Master (replicated, full-shape) kernel + bias with nn.Dense's
    param names, shapes, and initializers, returned RAW so the
    tensor-parallel path can slice them per rank (docs/pipeline.md):
    the param tree stays byte-compatible with the dense path, so one
    checkpoint (and one ``model.init``) serves both the replicated and
    the tp-sharded apply."""

    features: int

    @nn.compact
    def __call__(self, in_features: int):
        k = self.param("kernel", nn.initializers.lecun_normal(),
                       (in_features, self.features), jnp.float32)
        b = self.param("bias", nn.initializers.zeros, (self.features,),
                       jnp.float32)
        return k, b


class CausalSelfAttention(nn.Module):
    num_heads: int
    dtype: Any = jnp.bfloat16
    attend_fn: Optional[Callable] = None
    # Megatron-style sharded-head attention (docs/pipeline.md): heads
    # shard over this mesh axis — column-parallel fused QKV
    # (parallel/tensor_parallel.shard_heads), local attention on the
    # head subset, row-parallel output projection (ONE allreduce per
    # block). Params stay replicated masters sliced in-trace, so the
    # tree matches the dense path and DistributedOptimizer's tp
    # slice-grad combine (combine_slice_grads) reassembles exactly.
    # The incremental (serve cache) path shards the SAME way: the
    # caller hands each rank its head shard of the ring cache
    # (heads_local on the heads axis — DecodeEngine's shard_map specs,
    # docs/serve.md), writes/attends locally, and the row-parallel
    # output allreduce is the block's one collective. The per-head
    # int8 block quantization operates head-vector-wise, so shards
    # quantize bit-identically to the unsharded cache.
    tp_axis: Optional[str] = None
    # Sequence-parallel mesh axis (docs/sequence.md): activations are
    # sequence-sharded over ``seq_axis``; attention runs striped-ring
    # or Ulysses over the wired exchange, and RoPE positions resolve to
    # this rank's GLOBAL shard positions in-module — so the layer
    # composes inside a pipeline stage without the schedule having to
    # thread positions. Params stay replicated over sp (slice grads
    # pmean-combine in optim.py, same as tp).
    seq_axis: Optional[str] = None
    seq_impl: str = "ring"
    seq_wire: Optional[str] = None

    @nn.compact
    def __call__(self, x, positions=None, cache=None, cache_ctx=None):
        """The projections on either side of the attention call carry
        ``hvd_mixer_proj`` (the rotation ``hvd_rope`` inside it); the
        call itself lies outside, under its kernels' own names."""
        b, s, h = x.shape
        head_dim = h // self.num_heads
        if self.seq_axis and cache is None and positions is None:
            positions = seq_positions(self.seq_axis, self.seq_impl, s)
        if cache is not None:
            from ..serve import kvcache as kv_lib

            # Incremental (serve) path: RoPE with each token's GLOBAL
            # position, scatter the new K/V into their ring lines, and
            # attend over the cache slab (docs/serve.md). Keys are
            # stored ALREADY ROPED, so absolute positions survive the
            # ring wrap without re-rotation.
            idx, positions, k_pos = cache_ctx
        if self.tp_axis:
            from ..parallel import tensor_parallel as tp_lib

            ntp = jax.lax.axis_size(self.tp_axis)
            heads_l = self.num_heads // ntp
            qkv_k, qkv_b = _DenseMaster(3 * h, name="qkv")(h)
            out_k, out_b = _DenseMaster(h, name="out")(h)
            with jax.named_scope(scopes.MIXER_PROJ):
                w3 = tp_lib.shard_heads(qkv_k, self.num_heads,
                                        self.tp_axis, fused=3)
                b3 = tp_lib.shard_heads(qkv_b, self.num_heads,
                                        self.tp_axis, fused=3)
                xd = x.astype(self.dtype)

                def proj(i):
                    w = w3[:, i].reshape(h, heads_l * head_dim)
                    bb = b3[i].reshape(heads_l * head_dim)
                    y = xd @ w.astype(self.dtype) + bb.astype(self.dtype)
                    return y.reshape(b, s, heads_l, head_dim)

                w_loc = tp_lib.shard_head_rows(out_k, self.num_heads,
                                               self.tp_axis)
                q = rope(proj(0), positions)
                k = rope(proj(1), positions)
                v = proj(2)
        else:
            heads_l = self.num_heads
            with jax.named_scope(scopes.MIXER_PROJ):
                qkv = nn.Dense(3 * h, dtype=self.dtype,
                               param_dtype=jnp.float32, name="qkv")(x)
                q, k, v = jnp.split(qkv, 3, axis=-1)
                q = rope(q.reshape(b, s, heads_l, head_dim), positions)
                k = rope(k.reshape(b, s, heads_l, head_dim), positions)
                v = v.reshape(b, s, heads_l, head_dim)
        if cache is not None:
            cache = kv_lib.layer_write(cache, idx, k, v)
            k_all, v_all = kv_lib.layer_read(cache, jnp.float32)
            o = _cache_attend(q, k_all, v_all, positions, k_pos)
        else:
            o = (self.attend_fn or self._resolve_attend())(q, k, v)
        with jax.named_scope(scopes.MIXER_PROJ):
            o = o.reshape(b, s, heads_l * head_dim)
            if self.tp_axis:
                o = tp_lib.row_parallel(o, w_loc.astype(self.dtype),
                                        self.tp_axis,
                                        out_b.astype(self.dtype))
            else:
                o = nn.Dense(h, dtype=self.dtype, param_dtype=jnp.float32,
                             name="out")(o)
        return o if cache is None else (o, cache)

    def _resolve_attend(self) -> Callable:
        if self.seq_axis:
            return seq_attend_fn(self.seq_axis, self.seq_impl,
                                 self.seq_wire)
        return _causal_attend


class DecoderLayer(nn.Module):
    num_heads: int
    mlp_dim: int
    dtype: Any = jnp.bfloat16
    attend_fn: Optional[Callable] = None
    moe_experts: int = 0                 # 0 = dense FFN
    moe_capacity_factor: float = 1.25
    moe_axis: Optional[str] = None
    moe_route: Optional[str] = None
    moe_wire: str = "none"
    moe_overlap_chunks: int = 1
    moe_router_noise: float = 0.0
    # Tensor-parallel mesh axis (docs/pipeline.md): sharded-head
    # attention + the paired column/row-parallel dense MLP (one
    # allreduce per block). Composes with the MoE expert axis — tp
    # shards the attention while ep routes the FFN tokens.
    tp_axis: Optional[str] = None
    # Sequence-parallel fields (docs/sequence.md) — forwarded to the
    # attention block; the MLP is pointwise over positions, so it runs
    # on the local sequence shard unchanged.
    seq_axis: Optional[str] = None
    seq_impl: str = "ring"
    seq_wire: Optional[str] = None

    @nn.compact
    def __call__(self, x, positions=None, cache=None, cache_ctx=None):
        with jax.named_scope(scopes.NORM):
            y = nn.LayerNorm(dtype=self.dtype, param_dtype=jnp.float32)(x)
        if cache is not None:
            a, cache = CausalSelfAttention(
                self.num_heads, self.dtype, self.attend_fn,
                tp_axis=self.tp_axis,
                name="attn")(y, positions, cache, cache_ctx)
            x = x + a
        else:
            x = x + CausalSelfAttention(self.num_heads, self.dtype,
                                        self.attend_fn,
                                        tp_axis=self.tp_axis,
                                        seq_axis=self.seq_axis,
                                        seq_impl=self.seq_impl,
                                        seq_wire=self.seq_wire,
                                        name="attn")(y, positions)
        with jax.named_scope(scopes.NORM):
            y = nn.LayerNorm(dtype=self.dtype, param_dtype=jnp.float32)(x)
        if self.moe_experts:
            y = MoeMlp(self.moe_experts, self.mlp_dim,
                       self.moe_capacity_factor, self.dtype, self.moe_axis,
                       self.moe_route, self.moe_wire,
                       self.moe_overlap_chunks, self.moe_router_noise,
                       name="moe")(y)
        elif self.tp_axis:
            from ..parallel import tensor_parallel as tp_lib

            k1, b1 = _DenseMaster(self.mlp_dim,
                                  name="mlp_in")(x.shape[-1])
            k2, b2 = _DenseMaster(x.shape[-1],
                                  name="mlp_out")(self.mlp_dim)
            with jax.named_scope(scopes.MLP):
                y = tp_lib.tp_mlp(
                    y.astype(self.dtype),
                    tp_lib.shard_column(k1.astype(self.dtype),
                                        self.tp_axis),
                    tp_lib.shard_column(b1.astype(self.dtype),
                                        self.tp_axis),
                    tp_lib.shard_row(k2.astype(self.dtype), self.tp_axis),
                    b2.astype(self.dtype), self.tp_axis,
                    activation=nn.gelu)
        else:
            with jax.named_scope(scopes.MLP):
                y = nn.Dense(self.mlp_dim, dtype=self.dtype,
                             param_dtype=jnp.float32, name="mlp_in")(y)
                y = nn.gelu(y)
                y = nn.Dense(x.shape[-1], dtype=self.dtype,
                             param_dtype=jnp.float32, name="mlp_out")(y)
        out = x + y
        return out if cache is None else (out, cache)


class GPT(nn.Module):
    """Pre-LN decoder-only transformer with weight-tied LM head.

    ``remat=True`` wraps each decoder layer in ``nn.remat``
    (jax.checkpoint): activations are recomputed during backprop
    instead of stored, cutting long-context HBM from O(layers x S x
    hidden) to O(S x hidden) at ~1/3 extra FLOPs — the standard TPU
    memory/compute trade for sequence lengths past a few thousand.

    ``moe_experts > 0`` swaps each layer's dense MLP for the
    expert-parallel :class:`MoeMlp` (GPT-MoE, docs/moe.md) — the
    ``moe_*`` fields thread straight through to ``parallel/moe.py``
    (ep axis / WirePlan route spec / dispatch wire format / capacity
    chunking depth)."""

    vocab_size: int = 32000
    num_layers: int = 12
    hidden: int = 768
    num_heads: int = 12
    mlp_dim: int = 3072
    dtype: Any = jnp.bfloat16
    attend_fn: Optional[Callable] = None
    remat: bool = False
    moe_experts: int = 0
    moe_capacity_factor: float = 1.25
    moe_axis: Optional[str] = None
    moe_route: Optional[str] = None
    moe_wire: str = "none"
    moe_overlap_chunks: int = 1
    moe_router_noise: float = 0.0
    # Tensor-parallel mesh axis (docs/pipeline.md): heads + MLP width
    # shard over ``tp`` inside every decoder layer, params stay
    # replicated masters sliced in-trace — the tree matches the dense
    # model, so one init/checkpoint serves both and
    # ``DistributedOptimizer(parallel=...)`` reassembles slice grads.
    tp_axis: Optional[str] = None
    # Sequence-parallel mesh axis (docs/sequence.md): activations
    # sequence-shard over ``seq_parallel``; attention runs
    # ``seq_impl`` ("ring" = striped causal ring over wired ppermute —
    # feed stripe_layout'd tokens; "ulysses" = head/sequence alltoall —
    # contiguous shards, needs num_heads % n == 0) with K/V exchanges
    # in ``seq_wire``. Params stay the SAME replicated dense tree (one
    # checkpoint serves the dense and sp twins); slice grads
    # pmean-combine over sp in the optimizer, exactly like tp.
    seq_parallel: Optional[str] = None
    seq_impl: str = "ring"
    seq_wire: Optional[str] = None

    @nn.compact
    def __call__(self, tokens, positions=None, cache=None):
        emb = nn.Embed(self.vocab_size, self.hidden,
                       param_dtype=jnp.float32, name="tok_emb")
        with jax.named_scope(scopes.EMBED):
            x = emb(tokens).astype(self.dtype)
        layer_cls = nn.remat(DecoderLayer) if self.remat else DecoderLayer
        cache_ctx = None
        new_layers = []
        if cache is not None:
            # Incremental mode (docs/serve.md): the s_in new tokens of
            # every slot extend that slot's sequence at global
            # positions pos..pos+s_in, landing in ring lines
            # (pos + i) % max_len — prefill (s_in = prompt length) and
            # decode (s_in = 1) are the SAME program at different
            # shapes. Returns (logits, updated cache).
            b, s_in = tokens.shape
            s_max = cache["slot_pos"].shape[1]
            q_pos = (cache["pos"][:, None]
                     + jnp.arange(s_in, dtype=jnp.int32)[None, :])
            idx = q_pos % s_max
            slot_pos = cache["slot_pos"].at[
                jnp.arange(b)[:, None], idx].set(q_pos)
            cache_ctx = (idx, q_pos, slot_pos)
        for i in range(self.num_layers):
            layer = layer_cls(self.num_heads, self.mlp_dim, self.dtype,
                              self.attend_fn, self.moe_experts,
                              self.moe_capacity_factor, self.moe_axis,
                              self.moe_route, self.moe_wire,
                              self.moe_overlap_chunks,
                              self.moe_router_noise,
                              tp_axis=self.tp_axis,
                              seq_axis=self.seq_parallel,
                              seq_impl=self.seq_impl,
                              seq_wire=self.seq_wire,
                              name=f"layer{i}")
            if cache is not None:
                x, lc = layer(x, positions, cache["layers"][i],
                              cache_ctx)
                new_layers.append(lc)
            else:
                x = layer(x, positions)
        with jax.named_scope(scopes.NORM):
            x = nn.LayerNorm(dtype=self.dtype, param_dtype=jnp.float32,
                             name="final_ln")(x)
        # Weight-tied head: bf16 operands + fp32 accumulation — the
        # V x H matmul at fp32 runs ~4x off the MXU's bf16 peak, and
        # fp32 accumulation keeps the softmax stable (standard LM-head
        # recipe).
        with jax.named_scope(scopes.LM_HEAD):
            logits = jax.lax.dot_general(
                x.astype(self.dtype), emb.embedding.astype(self.dtype),
                (((x.ndim - 1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        if cache is not None:
            new_cache = {"layers": tuple(new_layers),
                         "pos": cache["pos"] + tokens.shape[1],
                         "slot_pos": cache_ctx[2]}
            return logits, new_cache
        return logits


def gpt_small(**kw):
    """~124M params (GPT-2 small geometry)."""
    return GPT(num_layers=12, hidden=768, num_heads=12, mlp_dim=3072,
               vocab_size=kw.pop("vocab_size", 50257), **kw)


def gpt_medium(**kw):
    """~350M params (GPT-2 medium geometry)."""
    return GPT(num_layers=24, hidden=1024, num_heads=16, mlp_dim=4096,
               vocab_size=kw.pop("vocab_size", 50257), **kw)


def gpt_tiny(**kw):
    """Test-sized decoder for the loopback tier (every field
    overridable)."""
    for k, v in (("num_layers", 2), ("hidden", 64), ("num_heads", 4),
                 ("mlp_dim", 128), ("vocab_size", 128),
                 ("dtype", jnp.float32)):
        kw.setdefault(k, v)
    return GPT(**kw)


def activation_bytes(model: "GPT", batch: int, seq_len: int,
                     dtype_bytes: int = 4) -> int:
    """Analytic per-rank activation accounting for ONE training step
    (saved-for-backward residuals, no remat): per decoder layer the
    two LN outputs, q/k/v, the attention output + projection, the two
    MLP matmul activations (~``10*hidden + 2*mlp_dim`` values per
    token), plus the embedding and the LM-head logits
    (``hidden + vocab`` per token). LINEAR in ``seq_len`` by
    construction — that is the point: sequence parallelism over
    ``nsp`` ranks hands each rank ``seq_len // nsp`` of the context,
    dividing this number by ``nsp`` while the params stay whole
    (docs/sequence.md). The long-context acceptance test budgets
    against this accounting, the bench records it into the BENCH
    ``memory`` block."""
    per_tok_layer = 10 * model.hidden + 2 * model.mlp_dim
    per_tok = (model.num_layers * per_tok_layer + model.hidden
               + model.vocab_size)
    return int(batch) * int(seq_len) * per_tok * int(dtype_bytes)


def param_bytes(params) -> int:
    """Total bytes of a param tree (real arrays or ShapeDtypeStructs) —
    the number the hybrid acceptance test compares against the
    single-replica budget (docs/pipeline.md)."""
    import numpy as np

    total = 0
    for leaf in jax.tree.leaves(params):
        total += int(np.prod(getattr(leaf, "shape", ()))) \
            * jnp.dtype(leaf.dtype).itemsize
    return total


def stack_stage_params(params, num_stages: int):
    """Split a GPT param tree (``init(...)["params"]``) into the hybrid
    pipeline layout (docs/pipeline.md):

    Returns ``(stages, shared)``: ``stages`` is the decoder layers
    stacked STAGE-MAJOR — every leaf gains a leading
    ``(num_stages, layers_per_stage)`` pair, so ``in_specs=P("pp")``
    shards stage ``s``'s layers onto pp rank ``s`` — and ``shared`` is
    the replicated remainder (``tok_emb`` + ``final_ln``), consumed by
    ``pipeline_fns``'s pre/loss closures at the two pipeline ends.
    Raises when the layer count does not divide into stages."""
    layer_keys = sorted((k for k in params if k.startswith("layer")),
                        key=lambda k: int(k[len("layer"):]))
    n_layers = len(layer_keys)
    if num_stages < 1 or n_layers % num_stages:
        raise ValueError(
            f"{n_layers} decoder layers do not divide into "
            f"{num_stages} pipeline stages")
    lps = n_layers // num_stages
    per_stage = []
    for s in range(num_stages):
        chunk = [params[layer_keys[s * lps + j]] for j in range(lps)]
        per_stage.append(jax.tree.map(lambda *a: jnp.stack(a), *chunk))
    stages = jax.tree.map(lambda *a: jnp.stack(a), *per_stage)
    shared = {k: v for k, v in params.items()
              if not k.startswith("layer")}
    return stages, shared


def pipeline_fns(model: GPT):
    """The ``(stage_fn, pre_fn, loss_fn)`` closures that plug a GPT
    into ``parallel.pipeline.pipeline_accumulate_gradients``
    (docs/pipeline.md):

    - ``stage_fn(stage_params, x)`` applies the owned decoder layers in
      sequence. Leaves carry the ``stack_stage_params`` layout
      ``(local_stages, layers_per_stage, ...)`` — under ``in_specs=
      P("pp")`` each pp rank holds ``(1, lps, ...)`` and runs its one
      stage; the SAME closure applied to the full stacked tree runs the
      whole chain (the single-program reference the bitwise test pins
      against). Carries the model's ``tp_axis``/MoE/``seq_parallel``
      fields, so tensor, expert, and sequence parallelism run INSIDE
      each stage (sp layers resolve their own global RoPE positions —
      docs/sequence.md).
    - ``pre_fn(shared, tokens)`` is the stage-0 input: the embedding
      lookup (same math as the model's ``tok_emb`` path).
    - ``loss_fn(shared, out, targets)`` is the last-stage loss: final
      LayerNorm + weight-tied LM head (bf16 operands, fp32
      accumulation — the model's own head recipe) + mean next-token
      cross-entropy.

    The closures recompute from stored inputs under 1F1B, so they must
    be deterministic — they are (no dropout in this decoder)."""
    layer = DecoderLayer(model.num_heads, model.mlp_dim, model.dtype,
                         model.attend_fn, model.moe_experts,
                         model.moe_capacity_factor, model.moe_axis,
                         model.moe_route, model.moe_wire,
                         model.moe_overlap_chunks,
                         model.moe_router_noise,
                         tp_axis=model.tp_axis,
                         seq_axis=model.seq_parallel,
                         seq_impl=model.seq_impl,
                         seq_wire=model.seq_wire)

    def stage_fn(stage_params, x):
        local_stages, lps = jax.tree.leaves(stage_params)[0].shape[:2]
        for i in range(local_stages):
            for j in range(lps):
                lp = jax.tree.map(lambda a: a[i, j], stage_params)
                x = layer.apply({"params": lp}, x)
        return x

    def pre_fn(shared, tokens):
        return shared["tok_emb"]["embedding"][tokens].astype(
            model.dtype)

    def loss_fn(shared, out, targets):
        ln = nn.LayerNorm(dtype=model.dtype, param_dtype=jnp.float32)
        x = ln.apply({"params": shared["final_ln"]}, out)
        emb = shared["tok_emb"]["embedding"]
        logits = jax.lax.dot_general(
            x.astype(model.dtype), emb.astype(model.dtype),
            (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        logp = jax.nn.log_softmax(logits)
        ll = jnp.take_along_axis(logp, targets[..., None],
                                 axis=-1)[..., 0]
        return -ll.mean()

    return stage_fn, pre_fn, loss_fn


def init_kv_cache(model: GPT, slots: int, max_len: int,
                  kind: str = "fp32"):
    """A fresh KV-cache pytree matching ``model``'s geometry — the
    ``cache=`` argument of the incremental ``model.apply`` path
    (docs/serve.md). ``kind`` is ``"fp32"`` (model-dtype storage) or
    ``"int8"`` (block-scaled, ~4x smaller)."""
    from ..serve import kvcache as kv_lib

    return kv_lib.init_cache(model.num_layers, slots, max_len,
                             model.num_heads,
                             model.hidden // model.num_heads,
                             kind=kind, dtype=model.dtype)
