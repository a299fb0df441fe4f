"""Flash attention — Pallas TPU kernel for the attention hot op.

The reference has no attention kernels (it is a collectives framework);
this belongs to the TPU rebuild's perf mandate: attention is where the
BERT benchmark's FLOPs and HBM traffic live, and the blockwise
online-softmax formulation (Dao et al.; same math as ring attention's
per-block combine in horovod_tpu/parallel/ring_attention.py) keeps the
(S, S) logits matrix out of HBM entirely — O(S) memory instead of O(S²),
with every block matmul MXU-shaped.

Layout: the public API takes (B, S, H, D) as produced by the models'
fused QKV projection; internally the kernels run on (B, H, S, D) so
every block's minor-two dims are MXU/VPU-tileable (block_q, D) tiles —
Mosaic requires the last two block dims be (8k, 128k) or match the
array, which a (…, H, D) layout with a size-1 head block violates for
H > 1. Rank-deficient operands ride the same rule via lane/sublane
broadcast: the key mask crosses as (B, 8, S) and the logsumexp as
(B, H, S, 128), the trick the stock jax.experimental TPU flash kernel
uses for l/m/segment-ids. The kernel grid is (B, H, S/block_q); K/V
live whole in VMEM per (batch, head) and the kernel loops their blocks
with a carried (m, l, acc) online softmax. Backward is the standard
two-kernel split (dq over q blocks; dk/dv over kv blocks) against the
saved logsumexp. Off-TPU (or shapes Pallas can't tile) falls back to
the plain jnp reference — numerically identical, used by the CPU test
suite which also runs the real kernel bodies in interpret mode.
"""

from __future__ import annotations

import functools
import logging
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .pallas_kernels import _decide
from ..common import scopes
from ..common.config import runtime_env

logger = logging.getLogger("horovod_tpu")

_NEG = -1e30  # mask value; NOT -inf (exp(-inf - -inf) = nan)
_LANE = 128
_SUBLANES = 8


def _pick_block(s: int, target: int = 128) -> Optional[int]:
    """Largest multiple-of-8 divisor of s that is <= target."""
    for b in range(min(target, s), 7, -1):
        if s % b == 0 and b % 8 == 0:
            return b
    return None


def reference_attention(q, k, v, mask=None, causal=False):
    """Plain softmax attention on (B, S, H, D); ``mask`` is a (B, S) key
    mask (1 = attend). The jnp fallback and the numerics oracle."""
    d = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / np.sqrt(d)
    if mask is not None:
        logits = jnp.where(mask[:, None, None, :] > 0, logits, _NEG)
    if causal:
        s = q.shape[1]
        rows = jax.lax.broadcasted_iota(jnp.int32, (s, s), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (s, s), 1)
        logits = jnp.where((rows >= cols)[None, None], logits, _NEG)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs,
                      v.astype(jnp.float32)).astype(q.dtype)


# -- forward kernel ---------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, m_ref, o_ref, lse_ref, *,
                block_q, block_k, seq_len, causal, scale):
    q = q_ref[0, 0, :, :].astype(jnp.float32) * scale      # (bq, D)
    qi = pl.program_id(2)
    nk = seq_len // block_k
    if causal:
        hi = jax.lax.div(qi * block_q + block_q + block_k - 1, block_k)
        hi = jnp.minimum(hi, nk)
    else:
        hi = nk

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, 0, pl.ds(j * block_k, block_k), :].astype(
            jnp.float32)                                    # (bk, D)
        v = v_ref[0, 0, pl.ds(j * block_k, block_k), :].astype(
            jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        kmask = m_ref[0, 0, pl.ds(j * block_k, block_k)] > 0  # (bk,)
        s = jnp.where(kmask[None, :], s, _NEG)
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    m0 = jnp.full((block_q, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    a0 = jnp.zeros((block_q, q.shape[-1]), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, hi, body, (m0, l0, a0))
    l = jnp.maximum(l, 1e-30)
    o_ref[0, 0, :, :] = (acc / l).astype(o_ref.dtype)
    lse_ref[0, 0, :, :] = jnp.broadcast_to(m + jnp.log(l),
                                           (block_q, _LANE))


# -- backward kernels -------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, m_ref, do_ref, lse_ref, delta_ref,
               dlse_ref, dq_ref, *, block_q, block_k, seq_len, causal,
               scale):
    q = q_ref[0, 0, :, :].astype(jnp.float32)
    do = do_ref[0, 0, :, :].astype(jnp.float32)
    # lse/delta/dlse blocks are lane-broadcast (bq, 128); every lane
    # holds the same value — read lane 0 as the (bq, 1) column.
    lse = lse_ref[0, 0, :, :][:, 0:1]                       # (bq, 1)
    delta = delta_ref[0, 0, :, :][:, 0:1]
    # Cotangent of the lse OUTPUT (nonzero when callers combine blocks —
    # ring attention): lse = logsumexp(s) and dlse/ds = p, so the term
    # folds into ds as p * dlse.
    dlse = dlse_ref[0, 0, :, :][:, 0:1]
    qi = pl.program_id(2)
    nk = seq_len // block_k
    if causal:
        hi = jnp.minimum(
            jax.lax.div(qi * block_q + block_q + block_k - 1, block_k),
            nk)
    else:
        hi = nk

    def body(j, dq):
        k = k_ref[0, 0, pl.ds(j * block_k, block_k), :].astype(
            jnp.float32)
        v = v_ref[0, 0, pl.ds(j * block_k, block_k), :].astype(
            jnp.float32)
        s = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        kmask = m_ref[0, 0, pl.ds(j * block_k, block_k)] > 0
        s = jnp.where(kmask[None, :], s, _NEG)
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG)
        p = jnp.exp(s - lse)                                # (bq, bk)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta + dlse)
        return dq + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    dq = jax.lax.fori_loop(
        0, hi, body, jnp.zeros((block_q, q.shape[-1]), jnp.float32))
    dq_ref[0, 0, :, :] = dq.astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, m_ref, do_ref, lse_ref, delta_ref,
                dlse_ref, dk_ref, dv_ref, *, block_q, block_k, seq_len,
                causal, scale):
    ki = pl.program_id(2)
    k = k_ref[0, 0, :, :].astype(jnp.float32)               # (bk, D)
    v = v_ref[0, 0, :, :].astype(jnp.float32)
    # m_ref is the FULL (8, S) sublane-broadcast key mask; this grid
    # step's K block is bk wide, so slice the matching window.
    kmask = m_ref[0, 0, pl.ds(ki * block_k, block_k)] > 0   # (bk,)
    nq = seq_len // block_q
    lo = jax.lax.div(ki * block_k, block_q) if causal else 0

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, 0, pl.ds(i * block_q, block_q), :].astype(
            jnp.float32)
        do = do_ref[0, 0, pl.ds(i * block_q, block_q), :].astype(
            jnp.float32)
        lse = lse_ref[0, 0, pl.ds(i * block_q, block_q), :][:, 0:1]
        delta = delta_ref[0, 0, pl.ds(i * block_q, block_q), :][:, 0:1]
        dlse = dlse_ref[0, 0, pl.ds(i * block_q, block_q), :][:, 0:1]
        s = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(kmask[None, :], s, _NEG)
        if causal:
            rows = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG)
        p = jnp.exp(s - lse)                                # (bq, bk)
        dv = dv + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # (bk, D)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta + dlse)                        # (bq, bk)
        dk = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        return dk, dv

    z = jnp.zeros((block_k, k.shape[-1]), jnp.float32)
    dk, dv = jax.lax.fori_loop(lo, nq, body, (z, z))
    dk_ref[0, 0, :, :] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0, :, :] = dv.astype(dv_ref.dtype)


# -- pallas_call plumbing ---------------------------------------------------

def _specs(b, s, h, d, bq, bk):
    """Block specs over the internal (B, H, S, D) layout: every block's
    minor-two dims are a Mosaic-tileable (rows, lanes) tile. The key
    mask rides as (B, 8, S) (full-S block, 8 identical sublanes) and
    lse/delta as (B, H, S, 128) (lane-broadcast)."""
    q_spec = pl.BlockSpec((1, 1, bq, d), lambda bi, hi, i: (bi, hi, i, 0))
    kv_spec = pl.BlockSpec((1, 1, s, d), lambda bi, hi, i: (bi, hi, 0, 0))
    m_spec = pl.BlockSpec((1, _SUBLANES, s), lambda bi, hi, i: (bi, 0, 0))
    lse_spec = pl.BlockSpec((1, 1, bq, _LANE),
                            lambda bi, hi, i: (bi, hi, i, 0))
    lse_full = pl.BlockSpec((1, 1, s, _LANE),
                            lambda bi, hi, i: (bi, hi, 0, 0))
    kv_block = pl.BlockSpec((1, 1, bk, d),
                            lambda bi, hi, j: (bi, hi, j, 0))
    return q_spec, kv_spec, m_spec, lse_spec, lse_full, kv_block


def _lanes(x):
    """(B, H, S) -> lane-broadcast (B, H, S, 128) fp32."""
    return jnp.broadcast_to(x.astype(jnp.float32)[..., None],
                            x.shape + (_LANE,))


def _sublanes(mask):
    """(B, S) key mask -> sublane-broadcast (B, 8, S) fp32 (the layout
    _specs' m_spec blocks over; fwd and bwd must agree)."""
    b, s = mask.shape
    return jnp.broadcast_to(mask.astype(jnp.float32)[:, None, :],
                            (b, _SUBLANES, s))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash(q, k, v, mask, causal, bq, bk, interpret):
    """Returns (o, lse). lse (B, H, S) is a first-class differentiable
    output so blockwise callers (ring attention) can combine partial
    results; its cotangent folds into the backward kernels' ds."""
    return _flash_fwd_impl(q, k, v, mask, causal, bq, bk, interpret)


def _flash_fwd_impl(q, k, v, mask, causal, bq, bk, interpret):
    b, s, h, d = q.shape
    scale = 1.0 / np.sqrt(d)
    q_spec, kv_spec, m_spec, lse_spec, _, _ = _specs(b, s, h, d, bq, bk)
    kern = functools.partial(_fwd_kernel, block_q=bq, block_k=bk,
                             seq_len=s, causal=causal, scale=scale)
    # (B, S, H, D) API layout -> (B, H, S, D) kernel layout; XLA fuses
    # these transposes into the surrounding projections.
    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    mask8 = _sublanes(mask)
    o, lse = pl.pallas_call(
        kern,
        grid=(b, h, s // bq),
        in_specs=[q_spec, kv_spec, kv_spec, m_spec],
        out_specs=[q_spec, lse_spec],
        out_shape=[jax.ShapeDtypeStruct(qt.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, h, s, _LANE), jnp.float32)],
        interpret=interpret,
        name=scopes.FLASH_FWD,
    )(qt, kt, vt, mask8)
    return jnp.swapaxes(o, 1, 2), lse[..., 0]


def _flash_fwd(q, k, v, mask, causal, bq, bk, interpret):
    o, lse = _flash_fwd_impl(q, k, v, mask, causal, bq, bk, interpret)
    return (o, lse), (q, k, v, mask, o, lse)


def _flash_bwd(causal, bq, bk, interpret, res, cotangents):
    do, dlse = cotangents
    q, k, v, mask, o, lse = res
    b, s, h, d = q.shape
    scale = 1.0 / np.sqrt(d)
    # delta_i = rowsum(do_i * o_i) — cheap elementwise, computed in-graph.
    delta = jnp.einsum("bshd,bshd->bhs", do.astype(jnp.float32),
                       o.astype(jnp.float32))
    q_spec, kv_spec, m_spec, lse_blk, lse_full, kv_block = _specs(
        b, s, h, d, bq, bk)

    qt, kt, vt, dot = (jnp.swapaxes(x, 1, 2) for x in (q, k, v, do))
    mask8 = _sublanes(mask)
    lse_l, delta_l, dlse_l = _lanes(lse), _lanes(delta), _lanes(dlse)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, block_q=bq, block_k=bk, seq_len=s,
                          causal=causal, scale=scale),
        grid=(b, h, s // bq),
        in_specs=[q_spec, kv_spec, kv_spec, m_spec, q_spec,
                  lse_blk, lse_blk, lse_blk],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        interpret=interpret,
        name=scopes.FLASH_DQ,
    )(qt, kt, vt, mask8, dot, lse_l, delta_l, dlse_l)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, block_q=bq, block_k=bk, seq_len=s,
                          causal=causal, scale=scale),
        grid=(b, h, s // bk),
        in_specs=[kv_spec, kv_block, kv_block, m_spec, kv_spec,
                  lse_full, lse_full, lse_full],
        out_specs=[kv_block, kv_block],
        out_shape=[jax.ShapeDtypeStruct(kt.shape, k.dtype),
                   jax.ShapeDtypeStruct(vt.shape, v.dtype)],
        interpret=interpret,
        name=scopes.FLASH_DKV,
    )(qt, kt, vt, mask8, dot, lse_l, delta_l, dlse_l)
    return (jnp.swapaxes(dq, 1, 2), jnp.swapaxes(dk, 1, 2),
            jnp.swapaxes(dv, 1, 2), None)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_available(seq_len: int, use_pallas: Optional[bool] = None,
                    block_q: int = 128, block_k: int = 128) -> bool:
    """THE availability predicate — single source of truth for every
    reason the kernel path can decline (off-TPU without forcing,
    HVD_TPU_FLASH_ATTENTION=0 escape hatch, un-tileable sequence).
    flash_attention_with_lse consults exactly this, so callers (ring
    attention) pre-checking it can rely on a non-None result."""
    use, _ = _decide(use_pallas)
    if runtime_env("FLASH_ATTENTION", "1") == "0":
        return False
    return bool(use) and _pick_block(seq_len, block_q) is not None \
        and _pick_block(seq_len, block_k) is not None


@functools.lru_cache(maxsize=None)  # once per shape, not per trace
def _warn_untileable(shape, block_q, block_k):
    logger.warning(
        "flash_attention: sequence length %d of q%s has no multiple-of-8 "
        "block <= (%d, %d); this call runs the O(S^2) reference "
        "attention on the TPU instead of the Pallas kernel",
        shape[1], tuple(shape), block_q, block_k)


def flash_attention_with_lse(q, k, v, mask=None, causal: bool = False,
                             use_pallas: Optional[bool] = None,
                             block_q: int = 128, block_k: int = 128):
    """Like :func:`flash_attention` but also returns the per-row
    logsumexp (B, H, S) — the blockwise-combination interface ring
    attention stitches partial results with. Both outputs are
    differentiable (the lse cotangent folds into the backward kernels).
    Returns None when :func:`flash_available` declines, so callers use
    their own reference path."""
    b, s, h, d = q.shape
    use, interpret = _decide(use_pallas)
    if not flash_available(s, use_pallas, block_q, block_k):
        if use and not interpret \
                and runtime_env("FLASH_ATTENTION", "1") != "0":
            _warn_untileable(q.shape, block_q, block_k)
        return None
    bq = _pick_block(s, block_q)
    bk = _pick_block(s, block_k)
    if mask is None:
        mask = jnp.ones((b, s), jnp.float32)
    mask = mask.astype(jnp.float32)
    if d % _LANE != 0:
        # Pad head_dim to the lane width; zero columns contribute zero
        # to every dot product and are sliced off the output. The
        # kernel derives its scale from the PADDED d, so fold the
        # correction into q.
        pad = _LANE - d % _LANE
        qp = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, pad)))
        kp = jnp.pad(k, ((0, 0), (0, 0), (0, 0), (0, pad)))
        vp = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, pad)))
        corr = np.sqrt((d + pad) / d).astype(np.float32)
        o, lse = _flash(qp * corr, kp, vp, mask, causal, bq, bk,
                        interpret)
        return o[..., :d], lse
    return _flash(q, k, v, mask, causal, bq, bk, interpret)


def flash_attention(q, k, v, mask=None, causal: bool = False,
                    use_pallas: Optional[bool] = None,
                    block_q: int = 128, block_k: int = 128):
    """Blockwise online-softmax attention on (B, S, H, D).

    ``mask``: optional (B, S) key mask (1 = attend). ``use_pallas=None``
    auto-selects the Pallas kernel on TPU with a jnp fallback elsewhere;
    ``True`` forces the kernel (interpret mode off-TPU — the test path).
    Differentiable via the standard flash backward kernels."""
    out = flash_attention_with_lse(q, k, v, mask, causal, use_pallas,
                                   block_q, block_k)
    if out is None:
        return reference_attention(q, k, v, mask, causal)
    return out[0]


def attend(q, k, v, mask=None):
    """Drop-in ``attend_fn`` for the models (SelfAttention): flash on
    TPU, reference jnp elsewhere."""
    return flash_attention(q, k, v, mask=mask)
