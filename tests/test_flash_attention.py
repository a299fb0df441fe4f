"""Flash-attention Pallas kernels vs the jnp reference — forward AND
backward (custom-VJP kernels), run in interpret mode on CPU so the real
kernel bodies execute (same tier as tests/test_pallas_kernels.py).

The parity cases run over what the kernels adapt to: the blocks (chosen
from the shape, or given — also with block_q != block_k, so that the
causal diagonal crosses blocks unevenly), the mask (none, all ones, a
real key mask), the dtype and the head width (64 as the models have it,
unpadded; 128)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops.flash_attention import (flash_attention,
                                             flash_attention_with_lse,
                                             reference_attention)

B, S, H, D = 2, 256, 2, 128

# None: the blocks the code chooses (one 256 block at S256); pairs: given,
# equal and unequal.
BLOCKS = [None, (64, 64), (64, 32), (32, 128)]
BLOCK_IDS = ["chosen", "bq64_bk64", "bq64_bk32", "bq32_bk128"]
MASKS = ["none", "ones", "keys"]


def _qkv(rng, d=D, s=S, dtype=np.float32, h=H):
    return tuple(jnp.asarray(rng.standard_normal((B, s, h, d)), dtype)
                 for _ in range(3))


def _mask(rng, kind, s=S, keep=0.7):
    if kind == "none":
        return None
    if kind == "ones":
        return np.ones((B, s), np.float32)
    mask = (rng.random((B, s)) < keep).astype(np.float32)
    mask[:, 0] = 1.0  # at least one visible key per batch
    return mask


def _kw(blocks):
    return {} if blocks is None else dict(block_q=blocks[0],
                                          block_k=blocks[1])


def _grads(fn, q, k, v):
    """Gradients of sum(fn(q, k, v) ** 2), taken in fp32."""
    return jax.grad(lambda *a: (fn(*a).astype(jnp.float32) ** 2).sum(),
                    argnums=(0, 1, 2))(q, k, v)


def _lse_loss(fn, w):
    """sum(o ** 2) + sum(w * lse) of ``fn``'s (o, lse): a loss with a
    live lse cotangent ``w``."""
    def f(q, k, v):
        o, lse = fn(q, k, v)
        return (o.astype(jnp.float32) ** 2).sum() + (w * lse).sum()
    return f


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("masking", MASKS)
@pytest.mark.parametrize("blocks", BLOCKS, ids=BLOCK_IDS)
def test_forward_matches_reference(rng, blocks, masking, causal):
    q, k, v = _qkv(rng)
    mask = _mask(rng, masking)
    out = flash_attention(q, k, v, mask=mask, causal=causal,
                          use_pallas=True, **_kw(blocks))
    ref = reference_attention(q, k, v, mask=mask, causal=causal)
    assert out.dtype == q.dtype and out.shape == q.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("masking", ["none", "keys"])
@pytest.mark.parametrize("blocks", BLOCKS, ids=BLOCK_IDS)
def test_backward_matches_reference(rng, blocks, masking, causal):
    q, k, v = _qkv(rng)
    mask = _mask(rng, masking, keep=0.8)
    g_flash = _grads(lambda *a: flash_attention(
        *a, mask=mask, causal=causal, use_pallas=True, **_kw(blocks)),
        q, k, v)
    g_ref = _grads(lambda *a: reference_attention(
        *a, mask=mask, causal=causal), q, k, v)
    for gf, gr, x, name in zip(g_flash, g_ref, (q, k, v), "qkv"):
        assert gf.dtype == x.dtype, f"d{name} came back {gf.dtype}"
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), rtol=5e-3, atol=5e-3,
            err_msg=f"d{name} mismatch (causal={causal})")


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("blocks", BLOCKS, ids=BLOCK_IDS)
def test_no_mask_is_an_all_ones_mask_bitwise(rng, blocks, causal):
    """``mask=None`` builds no mask operand and no select; it must give
    what a mask of ones gives, to the bit, forward and backward."""
    q, k, v = _qkv(rng, d=64)
    ones = _mask(rng, "ones")

    def run(mask):
        fn = lambda *a: flash_attention(  # noqa: E731
            *a, mask=mask, causal=causal, use_pallas=True, **_kw(blocks))
        return (fn(q, k, v),) + _grads(fn, q, k, v)

    for bare, masked in zip(run(None), run(ones)):
        np.testing.assert_array_equal(np.asarray(bare), np.asarray(masked))


# Head width: 64 is what every model of the benchmark has (it used to be
# padded to the 128 lanes inside the wrapper); 128 is a whole lane tile;
# 16 is narrower than anything the MXU likes. None is a multiple of 128
# but the second.
@pytest.mark.parametrize("d", [64, 128, 16])
def test_forward_padded_head_dim(rng, d):
    q, k, v = _qkv(rng, d=d)
    out = flash_attention(q, k, v, use_pallas=True)
    ref = reference_attention(q, k, v)
    assert out.shape == (B, S, H, d) and out.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("d", [64, 128, 16])
def test_backward_padded_head_dim(rng, d):
    q, k, v = _qkv(rng, d=d)
    g_flash = _grads(lambda *a: flash_attention(*a, causal=True,
                                                use_pallas=True), q, k, v)
    g_ref = _grads(lambda *a: reference_attention(*a, causal=True),
                   q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        assert gf.shape == (B, S, H, d) and gf.dtype == q.dtype
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=5e-3, atol=5e-3)


# (H, D) -> how the operands reach the kernels: whole heads side by side
# on the 128 lanes where the widths allow (nothing transposed), else one
# head a block on the transposed (B, H, S, D).
@pytest.mark.parametrize("h, d, packed, heads", [
    (2, 64, True, 2), (4, 32, True, 4), (1, 128, True, 1),
    (3, 64, False, 1), (2, 16, False, 1), (2, 80, False, 1)])
def test_heads_side_by_side_on_the_lanes(rng, h, d, packed, heads):
    layout = fa._Layout(h, d)
    assert (layout.packed, layout.heads) == (packed, heads)
    assert layout.groups * layout.heads == h
    q, k, v = _qkv(rng, d=d, h=h, s=128)
    mask = _mask(rng, "keys", s=128)
    w = jnp.asarray(rng.standard_normal((B, h, 128)), jnp.float32)

    flash = lambda *a: flash_attention_with_lse(  # noqa: E731
        *a, mask=mask, causal=True, use_pallas=True, block_q=64,
        block_k=32)
    ref = lambda *a: _reference_with_lse(*a, mask, True)  # noqa: E731
    for got, want in zip(flash(q, k, v), ref(q, k, v)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
    g_flash = jax.grad(_lse_loss(flash, w), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(_lse_loss(ref, w), argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=5e-3, atol=5e-3,
                                   err_msg=f"d{name} at H={h} D={d}")


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("d", [64, 128])
def test_bf16_inputs(rng, d, causal):
    """bf16 in: bf16 tiles feed the matmuls, fp32 accumulates, and the
    output and every gradient come back bf16 (q's path used to come back
    promoted to fp32)."""
    q, k, v = _qkv(rng, d=d)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    mask = _mask(rng, "keys")
    out = flash_attention(qb, kb, vb, mask=mask, causal=causal,
                          use_pallas=True, block_q=64, block_k=128)
    assert out.dtype == jnp.bfloat16
    # The reference sees the same bf16-rounded inputs, in fp32.
    qf, kf, vf = (x.astype(jnp.float32) for x in (qb, kb, vb))
    ref = reference_attention(qf, kf, vf, mask=mask, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=2e-2, atol=2e-2)
    g_flash = _grads(lambda *a: flash_attention(
        *a, mask=mask, causal=causal, use_pallas=True, block_q=64,
        block_k=128), qb, kb, vb)
    g_ref = _grads(lambda *a: reference_attention(
        *a, mask=mask, causal=causal), qf, kf, vf)
    for gf, gr in zip(g_flash, g_ref):
        assert gf.dtype == jnp.bfloat16
        scale = float(np.abs(np.asarray(gr)).max())
        np.testing.assert_allclose(np.asarray(gf, np.float32),
                                   np.asarray(gr), rtol=3e-2,
                                   atol=3e-2 * scale)


def _reference_with_lse(q, k, v, mask, causal):
    """reference_attention beside the logsumexp of its own logits."""
    d, s = q.shape[-1], q.shape[1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    if mask is not None:
        logits = jnp.where(mask[:, None, None, :] > 0, logits, fa._NEG)
    if causal:
        tri = np.tril(np.ones((s, s), bool))
        logits = jnp.where(tri[None, None], logits, fa._NEG)
    return (reference_attention(q, k, v, mask=mask, causal=causal),
            jax.scipy.special.logsumexp(logits, axis=-1))


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("masking", ["none", "keys"])
@pytest.mark.parametrize("blocks", [None, (64, 32)],
                         ids=["chosen", "bq64_bk32"])
def test_lse_output_and_its_cotangent(rng, blocks, masking, causal):
    """flash_attention_with_lse: the logsumexp is a differentiable
    output (ring attention combines blocks with it), so a loss that uses
    it — a non-zero lse cotangent — must match the reference's
    gradients, not only one through o."""
    q, k, v = _qkv(rng, d=64)
    mask = _mask(rng, masking)
    w = jnp.asarray(rng.standard_normal((B, H, S)), jnp.float32)

    flash = lambda *a: flash_attention_with_lse(  # noqa: E731
        *a, mask=mask, causal=causal, use_pallas=True, **_kw(blocks))
    ref = lambda *a: _reference_with_lse(*a, mask, causal)  # noqa: E731
    o, lse = flash(q, k, v)
    o_ref, lse_ref = ref(q, k, v)
    assert lse.shape == (B, H, S) and lse.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                               rtol=2e-4, atol=2e-4)
    g_flash = jax.grad(_lse_loss(flash, w), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(_lse_loss(ref, w), argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=5e-3, atol=5e-3,
                                   err_msg=f"d{name} with an lse cotangent")


# dq is gathered over the k blocks in VMEM and leaves the backward call
# once a (batch, head group): S = 4 x the smaller block, so every q block
# meets several k blocks and the other way round. (H, D): two heads
# packed on the lanes, one head of 128, one head a block at width 80.
GATHER_BLOCKS = [(32, 32), (32, 64), (64, 32)]
GATHER_LAYOUTS = [(2, 64), (1, 128), (2, 80)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("operands", ["bare", "keys_and_dlse"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("h, d", GATHER_LAYOUTS,
                         ids=["packed64", "d128", "perhead80"])
@pytest.mark.parametrize("block_q, block_k", GATHER_BLOCKS,
                         ids=["bq32_bk32", "bq32_bk64", "bq64_bk32"])
def test_dq_gathered_over_several_k_and_q_blocks(rng, block_q, block_k, h,
                                                 d, causal, operands,
                                                 dtype):
    """One backward call gives dq, dk and dv: dq's rows wait in VMEM
    while the k blocks pass, dk's and dv's while the q blocks do. With
    the key mask and a live lse cotangent, or with neither."""
    s = 128
    q, k, v = _qkv(rng, d=d, s=s, h=h, dtype=dtype)
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    dlse = operands == "keys_and_dlse"
    mask = _mask(rng, "keys" if dlse else "none", s=s, keep=0.8)
    w = jnp.asarray(rng.standard_normal((B, h, s)) * dlse, jnp.float32)
    flash = lambda *a: flash_attention_with_lse(  # noqa: E731
        *a, mask=mask, causal=causal, use_pallas=True, block_q=block_q,
        block_k=block_k)
    ref = lambda *a: _reference_with_lse(*a, mask, causal)  # noqa: E731
    g_flash = jax.grad(_lse_loss(flash, w), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(_lse_loss(ref, w), argnums=(0, 1, 2))(qf, kf, vf)
    tol = 5e-3 if dtype == jnp.float32 else 3e-2
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        assert gf.dtype == dtype and gf.shape == (B, s, h, d)
        scale = max(1.0, float(np.abs(np.asarray(gr)).max()))
        np.testing.assert_allclose(
            np.asarray(gf, np.float32), np.asarray(gr), rtol=tol,
            atol=tol * scale, err_msg=f"d{name} bq={block_q} bk={block_k}")


def _twin_dq_kernel(*refs, block_k, causal, scale, has_mask, transposed):
    """dq alone, as a kernel of its own: a q block against all the k
    blocks in an in-kernel loop, the carried fp32 dq added to in
    ascending k order. What the backward was before dq moved into the
    dk/dv call, built from that call's helpers; ``transposed`` takes ds
    from the same ``_pt_dst`` as the fused call, else the scores are
    built untransposed (q·kᵀ), the other way round the same products
    (``_key_block`` hands the key mask and the select key-major, as the
    forward and ``_pt_dst`` take them)."""
    q_ref, k_ref, v_ref = refs[:3]
    m_ref = refs[3] if has_mask else None
    do_ref, lse_ref, dd_ref, dq_ref = refs[-4:]
    block_q, lanes = q_ref.shape
    heads = lse_ref.shape[0]
    qi = fa.pl.program_id(2)
    q, on_scores = fa._scaled(q_ref, scale)
    do = do_ref[...]

    def step(j, dq, on_diagonal):
        k, v, kmask, keep = fa._key_block(k_ref, v_ref, m_ref, qi, j,
                                          block_q, block_k, on_diagonal)
        for g in range(heads):
            kg = fa._head(k, g, heads)
            if transposed:
                _, dst = fa._pt_dst(
                    fa._head(q, g, heads), fa._head(do, g, heads), k, v,
                    lse_ref[g], dd_ref[g], on_scores, kmask, keep)
                ds = dst.T
            else:
                p = jnp.exp(fa._masked(
                    fa._scores(q, kg, on_scores),
                    None if kmask is None else kmask.T,
                    None if keep is None else keep.T)
                    - lse_ref[g, 0, :][:, None])
                ds = p * (fa._dot(do, fa._head(v, g, heads), fa._NT)
                          - dd_ref[g, 0, :][:, None])
            dq = dq + fa._dot(ds.astype(k.dtype), kg, fa._NN)
        return dq

    dq = fa._loop_key_blocks(step, jnp.zeros((block_q, lanes), jnp.float32),
                             qi, block_q, block_k,
                             k_ref.shape[0] // block_k, causal)
    dq_ref[...] = (dq * scale).astype(dq_ref.dtype)


def _twin_dq(res, do, dlse, causal, bq, bk, transposed):
    """dq by the twin kernel, from the forward's residuals."""
    qt, kt, vt, mask3, ot, lse = res
    b, h, _, s = lse.shape
    d = qt.shape[-1] // h if qt.ndim == 3 else qt.shape[-1]
    layout = fa._Layout(h, d)
    dot = layout.to_kernel(do)
    dd = (layout.rowsum(dot, ot) - dlse)[:, :, None, :]
    has_mask = mask3 is not None
    q_spec, kv_spec, m_spec, row_spec = fa._q_major_specs(layout, s, bq)
    dq = fa.pl.pallas_call(
        lambda *refs: _twin_dq_kernel(
            *refs, block_k=bk, causal=causal, scale=1.0 / np.sqrt(d),
            has_mask=has_mask, transposed=transposed),
        grid=(b, layout.groups, s // bq),
        in_specs=[q_spec, kv_spec, kv_spec] + [m_spec] * has_mask
        + [q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(qt.shape, qt.dtype),
        interpret=True,
    )(qt, kt, vt, *([mask3] * has_mask), dot, lse, dd)
    return layout.from_kernel(dq)


@pytest.mark.parametrize("scores", ["transposed", "untransposed"])
@pytest.mark.parametrize("operands", ["bare", "keys_and_dlse"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("h, d", GATHER_LAYOUTS,
                         ids=["packed64", "d128", "perhead80"])
@pytest.mark.parametrize("block_q, block_k", GATHER_BLOCKS,
                         ids=["bq32_bk32", "bq32_bk64", "bq64_bk32"])
def test_dq_is_the_two_kernel_twins(rng, block_q, block_k, h, d, causal,
                                    operands, scores):
    """The fused call adds a q block's contributions in ascending k
    order, in fp32: from the same ds it is the dq of a kernel that does
    nothing else (a loop, a carried accumulator) bit for bit in fp32;
    with the twin's scores built the other way round (what the dq kernel
    did), to the last places, the CPU's two matmuls adding the 128 lanes
    in their own orders."""
    s = 128
    q, k, v = _qkv(rng, d=d, s=s, h=h)
    do = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)
    dlse = jnp.asarray(rng.standard_normal((B, h, s))
                       * (operands == "keys_and_dlse"), jnp.float32)
    mask = _mask(rng, "keys" if operands == "keys_and_dlse" else "none",
                 s=s, keep=0.8)
    mask = None if mask is None else jnp.asarray(mask)
    res = fa._forward(q, k, v, mask, causal, block_q, block_k, True)[2]
    dq = fa._backward(causal, block_q, block_k, True, res, do, dlse)[0]
    twin = _twin_dq(res, do, dlse, causal, block_q, block_k,
                    scores == "transposed")
    assert np.abs(np.asarray(twin)).max() > 0.1
    if scores == "transposed":
        np.testing.assert_array_equal(np.asarray(dq), np.asarray(twin))
    else:
        np.testing.assert_allclose(np.asarray(dq), np.asarray(twin),
                                   rtol=1e-5, atol=1e-5)


# (S, D, itemsize, bq, bk) -> bytes: what stays for a whole sequence (the
# forward's K and V, double buffered, or the backward's fp32 dq and its
# double-buffered output, whichever is more) + the blocks + the scores.
@pytest.mark.parametrize("case, resident", [
    ((4096, 64, 2, 512, 512), 4096 * 128 * 8),     # bf16: 2+2 MB either way
    ((16384, 64, 2, 512, 512), 16384 * 128 * 8),   # 16 MB
    ((4096, 64, 4, 512, 512), 4096 * 128 * 16),    # fp32: K and V are more
    ((4096, 128, 4, 512, 512), 4096 * 128 * 16),
    ((4096, 64, 1, 512, 512), 4096 * 128 * 6),     # 8-bit: dq is more
    ((2048, 80, 2, 512, 512), 2048 * 128 * 8),     # width 80: padded lanes
])
def test_vmem_plan_counts_the_resident_dq(case, resident):
    s, d, itemsize, bq, bk = case
    lanes = -(-d // 128) * 128
    assert resident >= s * lanes * (4 + 2 * itemsize)   # dq fits in it
    assert fa._vmem_estimate(*case) == resident \
        + 2 * 4 * max(bq, bk) * lanes * itemsize + 6 * bq * bk * 4


# (query heads, K/V heads, width): one head a 128-lane block (the index
# maps group), a group of the whole head count, two narrow heads a block
# (K/V repeated before the call), the per-head layout, no grouping, and
# LFM2's 32 query heads on 8 K/V heads of 64 (packed two a block, each K/V
# head repeated fourfold before the call).
GQA = [(8, 1, 128), (4, 2, 128), (4, 2, 64), (6, 2, 80), (4, 4, 128),
       (32, 8, 64)]


def _plain_grouped(q, k, v, mask, causal):
    """Plain attention with every K/V head written out a group's times."""
    group = q.shape[2] // k.shape[2]
    return reference_attention(q, jnp.repeat(k, group, 2),
                               jnp.repeat(v, group, 2), mask, causal)


@pytest.mark.parametrize("with_lse", [False, True], ids=["o", "o_and_lse"])
@pytest.mark.parametrize("masking", ["none", "keys"])
@pytest.mark.parametrize("h, hkv, d", GQA,
                         ids=[f"h{h}_kv{k}_d{d}" for h, k, d in GQA])
def test_grouped_query_attention(rng, h, hkv, d, masking, with_lse):
    """K/V heads < query heads: o, dq and the K/V heads' gradients, each
    the sum over its group of query heads, against plain attention; k and
    v keep their own shapes in and out."""
    s = 128
    q = jnp.asarray(rng.standard_normal((B, s, h, d)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((B, s, hkv, d)), jnp.float32)
            for _ in range(2))
    mask = _mask(rng, masking, s)
    w = jnp.asarray(rng.standard_normal((B, h, s)), jnp.float32)

    def ours(q, k, v):
        if with_lse:
            o, lse = flash_attention_with_lse(
                q, k, v, mask=mask, causal=True, use_pallas=True,
                block_q=64, block_k=32)
            return (o ** 2).sum() + (w * lse).sum()
        return (flash_attention(q, k, v, mask=mask, causal=True,
                                use_pallas=True, block_q=64,
                                block_k=32) ** 2).sum()

    def plain(q, k, v):
        o = _plain_grouped(q, k, v, mask, True)
        if not with_lse:
            return (o ** 2).sum()
        group = h // hkv
        logits = jnp.einsum("bqhd,bkhd->bhqk", q,
                            jnp.repeat(k, group, 2)) / np.sqrt(d)
        visible = jnp.tril(jnp.ones((s, s), bool))[None, None]
        if mask is not None:
            visible = visible & (mask[:, None, None, :] > 0)
        lse = jax.nn.logsumexp(jnp.where(visible, logits, -1e30), -1)
        return (o ** 2).sum() + (w * lse).sum()

    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(ours, argnums=(0, 1, 2))(q, k, v)
        want = jax.value_and_grad(plain, argnums=(0, 1, 2))(q, k, v)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for g, wanted, x in zip(got[1], want[1], (q, k, v)):
        assert g.shape == x.shape
        np.testing.assert_allclose(g, wanted, rtol=2e-4, atol=2e-4)


def test_grouping_repeats_nothing_where_a_block_is_one_head():
    """Width 128: the kernels read the one K/V head through their index
    maps (operands (B, S, Hkv * D) as they lie) and write each query
    head's dk and dv; two narrow heads a block: K/V reach the call
    repeated to the query heads' count."""
    def operands(h, hkv, d):
        q = jnp.ones((1, 128, h, d), jnp.float32)
        k = jnp.ones((1, 128, hkv, d), jnp.float32)
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda q, k, v: flash_attention(
                q, k, v, causal=True, use_pallas=True).sum(),
            argnums=(0, 1, 2)))(q, k, k)
        calls = []

        def walk(j):
            for eqn in j.eqns:
                if eqn.primitive.name == "pallas_call":
                    calls.append(eqn)
                for value in eqn.params.values():
                    for sub in value if isinstance(value, (list, tuple)) \
                            else [value]:
                        sub = getattr(sub, "jaxpr", sub)
                        if hasattr(sub, "eqns"):
                            walk(sub)

        walk(jaxpr.jaxpr)
        fwd, bwd = calls
        return ([v.aval.shape for v in fwd.invars[:3]],
                [v.aval.shape for v in bwd.outvars])

    ins, outs = operands(8, 1, 128)
    assert ins == [(1, 128, 1024), (1, 128, 128), (1, 128, 128)]
    assert outs == [(1, 128, 1024)] * 3      # dk, dv a query head
    ins, outs = operands(4, 2, 64)
    assert ins == [(1, 128, 256)] * 3 and outs == [(1, 128, 256)] * 3


def test_fallback_off_tpu_and_odd_seq(rng):
    # use_pallas=None off-TPU and an un-tileable sequence both fall back
    # to the reference path — identical result, no error.
    q, k, v = _qkv(rng, s=130)  # 130 has no multiple-of-8 divisor <= 128
    out = flash_attention(q, k, v)
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    assert flash_attention_with_lse(q, k, v) is None


# (S, D, dtype, block_q, block_k, compiled) -> (bq, bk) or None
@pytest.mark.parametrize("case, want", [
    ((512, 64, jnp.bfloat16, None, None, True), (512, 512)),
    ((2048, 64, jnp.bfloat16, None, None, True), (512, 512)),
    ((4096, 64, jnp.float32, None, None, True), (512, 512)),
    ((384, 64, jnp.bfloat16, None, None, True), (384, 384)),
    ((640, 64, jnp.bfloat16, None, None, True), (128, 128)),
    ((200, 64, jnp.bfloat16, None, None, True), (200, 200)),  # whole, short
    ((2056, 64, jnp.bfloat16, None, None, True), None),   # 8 x 257: no tile
    ((130, 128, jnp.float32, None, None, True), None),
    ((512, 64, jnp.bfloat16, 256, 128, True), (256, 128)),    # caps
    ((512, 64, jnp.bfloat16, 200, 1024, True), (128, 512)),
    ((32, 16, jnp.float32, 8, 8, False), (8, 8)),             # the tests'
    ((256, 128, jnp.float32, None, None, False), (256, 256)),
    ((24, 8, jnp.float32, 16, 16, False), (8, 8)),
])
def test_blocks_chosen_from_the_shape(case, want):
    """block_q / block_k of None are chosen by the code; numbers cap the
    choice. A compiled call takes multiples of 128 (or a short sequence
    whole): a block of the lse row is a lane slice."""
    s, d, dtype, block_q, block_k, compiled = case
    assert fa._resolve_blocks(s, d, dtype, block_q, block_k,
                              interpret=not compiled) == want


def test_blocks_shrink_to_the_vmem_budget():
    """The choice is 512-class until the plan (K and V whole, the
    blocks, the fp32 score temporaries) overruns the budget; then the
    score tile gives way, never below one lane tile."""
    assert fa._choose_blocks(2048, 64, jnp.bfloat16) == (512, 512)
    assert fa._choose_blocks(4096, 128, jnp.float32) == (512, 512)
    tq, tk = fa._choose_blocks(32768, 128, jnp.float32)
    assert 128 <= tq <= 512 and 128 <= tk <= 512
    assert fa._vmem_estimate(32768, 128, 4, tq, tk) \
        < fa._vmem_estimate(32768, 128, 4, 512, 512)
    assert fa._choose_blocks(1 << 20, 128, jnp.float32) == (128, 128)


def test_the_path_that_engaged_is_said_once_and_counted(rng, caplog):
    """One INFO line per distinct call shape at trace time, and the same
    fields as a labelled counter in hvd.metrics(): a run's record can
    say which path its step compiled."""
    import logging

    from horovod_tpu.common import metrics

    def count(**labels):
        fam = metrics.snapshot()["hvd_tpu_flash_attention_traces_total"]
        return sum(s["value"] for s in fam["samples"]
                   if all(s["labels"].get(k) == v
                          for k, v in labels.items()))

    fa._say_path.cache_clear()
    q, k, v = _qkv(rng, s=64, d=16)
    labels = dict(seq_len="64", head_dim="16", dtype="float32",
                  block_q="32", block_k="16", has_mask="false",
                  causal="true")
    before = count(**labels, dlse="false"), count(**labels, dlse="true")
    with caplog.at_level(logging.INFO, logger="horovod_tpu"):
        for _ in range(2):
            flash_attention(q, k, v, causal=True, use_pallas=True,
                            block_q=32, block_k=16)
        flash_attention_with_lse(q, k, v, causal=True, use_pallas=True,
                                 block_q=32, block_k=16)
    said = [r.getMessage() for r in caplog.records
            if "flash_attention" in r.getMessage()]
    assert len(said) == 2, said
    assert "block_q=32 block_k=16" in said[0] \
        and "(2, 64, 2, 16)" in said[0] and "float32" in said[0] \
        and "has_mask=False causal=True dlse_operand=False" in said[0]
    assert "dlse_operand=True" in said[1]
    assert count(**labels, dlse="false") == before[0] + 2
    assert count(**labels, dlse="true") == before[1] + 1


def test_declining_on_a_tpu_is_said_once(rng, monkeypatch, caplog):
    """Where the kernel is the path (a TPU), an un-tileable S runs the
    reference — correct, but O(S^2) on the device: one WARNING with the
    shape, not silence, and not one per trace."""
    import logging

    from horovod_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "_on_tpu", lambda: True)
    fa._warn_untileable.cache_clear()
    q, k, v = _qkv(rng, s=130)
    with caplog.at_level(logging.WARNING, logger="horovod_tpu"):
        for _ in range(2):
            out = flash_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(reference_attention(q, k, v)),
        rtol=1e-5, atol=1e-5)
    said = [r.getMessage() for r in caplog.records
            if "flash_attention" in r.getMessage()]
    assert len(said) == 1 and "130" in said[0] and "(2, 130, 2, 128)" \
        in said[0]
    # Off the TPU the reference IS the path: nothing to say.
    monkeypatch.setattr(pk, "_on_tpu", lambda: False)
    fa._warn_untileable.cache_clear()
    caplog.clear()
    with caplog.at_level(logging.WARNING, logger="horovod_tpu"):
        flash_attention(q, k, v)
    assert not caplog.records


@pytest.mark.slow
@pytest.mark.parametrize("seed", range(6))
def test_flash_fuzz_matches_reference(seed):
    """Seeded random (B,S,H,D) x causal x mask configs: kernel fwd AND
    grads track the jnp reference (interpret mode)."""
    rng = np.random.default_rng(4000 + seed)
    B = int(rng.integers(1, 3))
    S = int(rng.choice([16, 24, 32]))
    H = int(rng.integers(1, 4))
    D = int(rng.choice([8, 16]))
    causal = bool(seed % 2)
    key = jax.random.PRNGKey(seed)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i),
                                 (B, S, H, D), dtype=jnp.float32)
               for i in range(3))
    mask = None
    if seed % 3 == 0:
        mask = (rng.random((B, S)) > 0.3).astype(np.float32)
        mask[:, 0] = 1.0  # at least one attendable key per batch
        mask = jnp.asarray(mask)

    def flash_loss(q, k, v):
        return flash_attention(q, k, v, mask=mask, causal=causal,
                               use_pallas=True, block_q=8, block_k=8
                               ).astype(jnp.float32).sum()

    def ref_loss(q, k, v):
        return reference_attention(q, k, v, mask=mask, causal=causal
                                   ).astype(jnp.float32).sum()

    got = flash_attention(q, k, v, mask=mask, causal=causal,
                          use_pallas=True, block_q=8, block_k=8)
    want = reference_attention(q, k, v, mask=mask, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    gf = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4)


# -- mask kinds ---------------------------------------------------------------

# [noisy ; clean] of L = 128 in blocks of 4; the pairs of tile sizes put
# empty, full and partial tiles in one call (S256: 8 x 8 tiles of 32, 4 x 16
# of (64, 16), ...), and a tile as long as L itself.
BD = fa.BlockDiffusionMask(4)
BD_BLOCKS = [(32, 32), (64, 16), (16, 64), (128, 8), (8, 128)]


def _dense_reference(q, k, v, mask, kind):
    """(o, lse) from the kind's (S, S) boolean matrix and the key mask,
    written out: the oracle of the forward's two outputs."""
    d, s = q.shape[-1], q.shape[1]
    dense = jnp.asarray(kind.dense(s))[None]
    if mask is not None:
        dense = dense & (jnp.asarray(mask)[:, None, :] > 0)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    logits = jnp.where(dense[:, None], logits, -jnp.inf)
    return (jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1), v),
            jax.nn.logsumexp(logits, -1))


def _explicit(dense):
    """Plain softmax attention under an explicit (S, S) boolean matrix."""
    def attend(q, k, v):
        k, v = (fa._repeat_heads(x, q.shape[2] // x.shape[2])
                for x in (k, v))
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
        logits = jnp.where(jnp.asarray(dense)[None, None], logits, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1), v)
    return attend


def test_the_block_diffusion_mask_is_the_four_rules():
    """Written out for L = 8, block 4: rows the queries, [noisy ; clean]."""
    own = np.kron(np.eye(2, dtype=bool), np.ones((4, 4), bool))
    past = np.kron(np.tril(np.ones((2, 2), bool), -1), np.ones((4, 4), bool))
    want = np.block([[own, past], [np.zeros((8, 8), bool), own | past]])
    assert np.array_equal(BD.dense(16), want)
    assert np.array_equal(fa.CAUSAL.dense(5), np.tril(np.ones((5, 5), bool)))
    assert fa.NO_MASK.dense(3).all()
    with pytest.raises(ValueError, match="two copies"):
        BD.span(2 * 6)
    assert fa.BlockDiffusionMask(4) == BD != fa.BlockDiffusionMask(8)
    assert len({fa.NO_MASK, fa.CAUSAL, BD, fa.BlockDiffusionMask(4)}) == 3


@pytest.mark.parametrize("kind", [fa.NO_MASK, fa.CAUSAL, BD],
                         ids=lambda k: k.name)
def test_reference_attention_takes_the_mask_kind(rng, kind):
    q, k, v = _qkv(rng, d=16)
    got = reference_attention(q, k, v, mask_kind=kind)
    np.testing.assert_allclose(got, _explicit(kind.dense(S))(q, k, v),
                               rtol=1e-5, atol=1e-6)
    if kind != BD:      # the two kinds ``causal`` names
        assert np.array_equal(got, reference_attention(
            q, k, v, causal=kind == fa.CAUSAL))


@pytest.mark.parametrize("kv_heads", [2, 1], ids=["mha", "gqa"])
@pytest.mark.parametrize("blocks", BD_BLOCKS,
                         ids=[f"bq{a}_bk{b}" for a, b in BD_BLOCKS])
def test_the_kernels_under_the_block_diffusion_mask(rng, blocks, kv_heads):
    """Forward and every gradient, the kernels' bodies in interpret mode
    against an explicit boolean mask."""
    q, k, v = _qkv(rng, d=16)
    k, v = k[:, :, :kv_heads], v[:, :, :kv_heads]
    explicit = _explicit(BD.dense(S))

    def kernels(q, k, v):
        return flash_attention(q, k, v, mask_kind=BD, use_pallas=True,
                               **_kw(blocks))

    np.testing.assert_allclose(kernels(q, k, v), explicit(q, k, v),
                               rtol=2e-5, atol=2e-5)
    for got, want in zip(_grads(kernels, q, k, v),
                         _grads(explicit, q, k, v)):
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_the_block_diffusion_mask_with_a_key_mask_and_an_lse(rng):
    """The kind composes with the (B, S) key mask, and the lse interface
    carries it (a live lse cotangent)."""
    q, k, v = _qkv(rng, d=16)
    mask = _mask(rng, "keys")
    mask[:, S // 2:] = 1.0      # every row keeps a visible key
    w = jnp.asarray(rng.standard_normal((B, H, S)), jnp.float32)

    def explicit(q, k, v):
        return _dense_reference(q, k, v, mask, BD)

    def kernels(q, k, v):
        return flash_attention_with_lse(q, k, v, mask=mask, mask_kind=BD,
                                        use_pallas=True, block_q=32,
                                        block_k=64)

    for got, want in zip(kernels(q, k, v), explicit(q, k, v)):
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    got = jax.grad(_lse_loss(kernels, w), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(_lse_loss(explicit, w), argnums=(0, 1, 2))(q, k, v)
    for g, e in zip(got, want):
        np.testing.assert_allclose(g, e, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("blocks", BD_BLOCKS + [(8, 8)],
                         ids=lambda b: f"bq{b[0]}_bk{b[1]}")
@pytest.mark.parametrize("kind", [fa.NO_MASK, fa.CAUSAL, BD],
                         ids=lambda k: k.name)
def test_empty_tiles_are_not_visited(kind, blocks):
    """The kernels' own arithmetic asked with numbers (``tiles_visited``):
    the forward's loops and the backward's grid steps work on exactly the
    tiles that hold a visible pair, and know a tile that holds nothing
    but visible pairs from one that needs the select."""
    bq, bk = blocks
    dense = kind.dense(S)
    tiles = dense.reshape(S // bq, bq, S // bk, bk)
    some, every = tiles.any((1, 3)), tiles.all((1, 3))
    forward, backward, square = fa.tiles_visited(kind, S, bq, bk)
    assert square == some.size
    assert forward == backward == some.sum()
    with jax.ensure_compile_time_eval():
        for qi in range(S // bq):
            seen = np.zeros(S // bk, bool)
            for first, end, partial in kind.key_segments(
                    S, jnp.int32(qi), bq, bk, S // bk):
                ks = np.arange(int(first), int(end))
                assert not seen[ks].any()       # no tile twice
                seen[ks] = True
                # a bare tile holds visible pairs alone
                assert partial or every[qi, ks].all()
            assert np.array_equal(seen, some[qi])
            for ki in range(S // bk):
                bare, visible = kind.tile(S, jnp.int32(qi), jnp.int32(ki),
                                          bq, bk)
                assert bool(visible) == some[qi, ki]
                assert not bool(bare) or every[qi, ki]
                # a skipped step fetches a visited pair's tiles
                at = int(kind.first_query_block(S, jnp.int32(ki),
                                                jnp.int32(qi), bq, bk))
                assert some[at, ki] and (at == qi or not some[qi, ki])
    if kind == BD and bq == bk == 32:
        assert (forward, square) == (24, 64)    # 4 + 2 x (4 + 3 + 2 + 1)


def test_the_mask_kind_is_said_and_counted(rng, caplog):
    import logging

    from horovod_tpu.common import metrics

    fa._say_path.cache_clear()
    q, k, v = _qkv(rng, s=64, d=16)
    with caplog.at_level(logging.INFO, logger="horovod_tpu"):
        flash_attention(q, k, v, mask_kind=BD, use_pallas=True, block_q=16,
                        block_k=16)
    said = [r.getMessage() for r in caplog.records
            if "flash_attention" in r.getMessage()]
    assert len(said) == 1 and "mask_kind=block_diffusion" in said[0] \
        and "causal=False" in said[0] and "(8 of 16 tiles visited)" in said[0]
    snapshot = metrics.snapshot()
    assert any(s["labels"].get("mask_kind") == "block_diffusion"
               and s["labels"]["seq_len"] == "64"
               for s in snapshot["hvd_tpu_flash_attention_traces_total"][
                   "samples"])
    tiles = {s["labels"]["tiles"]: s["value"]
             for s in snapshot["hvd_tpu_flash_attention_tiles"]["samples"]
             if s["labels"]["mask_kind"] == "block_diffusion"
             and s["labels"]["seq_len"] == "64"
             and s["labels"]["block_q"] == "16"}
    assert tiles == {"visited": 8, "square": 16}   # 2 + 2 x (1 + 2)


# -- the forward's orientation ------------------------------------------------
#
# The forward builds a block's scores key-major (k·qᵀ, as the backward
# does): the running max / sum are lane rows reduced down the sublanes,
# the accumulator is held transposed and turned back once a q block. What
# that is new in: the three layouts' accumulators (two packed heads summed
# into one tile, one head of 128, one head a block on (B, H, S, D)), the
# three mask kinds' selects asked key-major, the key mask as a column, and
# a q block that meets 1, 2 or 4 k blocks of its own rows.

KEY_MAJOR_LAYOUTS = [(2, 64), (2, 128), (3, 64)]
KEY_MAJOR_IDS = ["packed2x64", "d128", "perhead_h3"]
KEY_MAJOR_KINDS = [fa.NO_MASK, fa.CAUSAL, BD]


def _keys_every_row_sees(rng, s):
    """A key mask that keeps the first key of every block of 4: under
    each of the three kinds every query keeps a visible key."""
    mask = _mask(rng, "keys", s=s)
    mask[:, ::4] = 1.0
    return mask


@pytest.mark.parametrize("k_blocks", [1, 2, 4])
@pytest.mark.parametrize("masking", ["none", "keys"])
@pytest.mark.parametrize("kind", KEY_MAJOR_KINDS, ids=lambda k: k.name)
@pytest.mark.parametrize("h, d", KEY_MAJOR_LAYOUTS, ids=KEY_MAJOR_IDS)
def test_forward_and_lse_key_major(rng, h, d, kind, masking, k_blocks):
    """o and lse of the forward kernel against the written-out softmax:
    every layout under every mask kind, with and without a key mask, a q
    block of 64 rows over k blocks of 64, 32 and 16."""
    s, block_q = 128, 64
    q, k, v = _qkv(rng, d=d, s=s, h=h)
    mask = None if masking == "none" else _keys_every_row_sees(rng, s)
    o, lse = flash_attention_with_lse(
        q, k, v, mask=mask, mask_kind=kind, use_pallas=True,
        block_q=block_q, block_k=block_q // k_blocks)
    want_o, want_lse = _dense_reference(q, k, v, mask, kind)
    assert o.shape == (B, s, h, d) and lse.shape == (B, h, s)
    assert lse.dtype == jnp.float32
    np.testing.assert_allclose(o, want_o, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(lse, want_lse, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kind", KEY_MAJOR_KINDS, ids=lambda k: k.name)
@pytest.mark.parametrize("h, d", KEY_MAJOR_LAYOUTS, ids=KEY_MAJOR_IDS)
def test_no_mask_is_a_mask_of_ones_in_every_layout(rng, h, d, kind):
    """``mask=None`` reads no mask column and builds no select in the
    key-major body either: o and lse are a mask of ones' to the bit."""
    s = 128
    q, k, v = _qkv(rng, d=d, s=s, h=h)

    def run(mask):
        return flash_attention_with_lse(q, k, v, mask=mask, mask_kind=kind,
                                        use_pallas=True, block_q=64,
                                        block_k=32)

    for bare, masked in zip(run(None), run(np.ones((B, s), np.float32))):
        np.testing.assert_array_equal(np.asarray(bare), np.asarray(masked))


@pytest.mark.parametrize("kind", [fa.NO_MASK, fa.CAUSAL],
                         ids=lambda k: k.name)
@pytest.mark.parametrize("h, d", KEY_MAJOR_LAYOUTS, ids=KEY_MAJOR_IDS)
def test_a_row_with_every_key_masked_stays_finite(rng, h, d, kind):
    """A batch row whose key mask is all zeros: every score is ``_NEG``
    (not -inf), so the running max is ``_NEG``, every probability exp(0)
    and the row the plain mean of the values its tiles visit: all S of
    them with no mask kind, what ``reference_attention`` gives too; the
    lse is ``_NEG`` + log(count), finite. Nothing is NaN or inf, and the
    other batch row is untouched by it."""
    s = 128
    q, k, v = _qkv(rng, d=d, s=s, h=h)
    mask = np.ones((B, s), np.float32)
    mask[0] = 0.0
    o, lse = flash_attention_with_lse(q, k, v, mask=mask, mask_kind=kind,
                                      use_pallas=True, block_q=64,
                                      block_k=32)
    assert np.isfinite(np.asarray(o)).all()
    assert np.isfinite(np.asarray(lse)).all()
    np.testing.assert_array_equal(np.asarray(lse[0]),
                                  np.full((h, s), np.float32(fa._NEG)))
    if kind == fa.NO_MASK:
        np.testing.assert_allclose(
            o[0], jnp.broadcast_to(v[0].mean(0), (s, h, d)), rtol=1e-5,
            atol=1e-6)
        np.testing.assert_allclose(
            o, reference_attention(q, k, v, mask=mask), rtol=2e-5,
            atol=2e-5)
    want_o, want_lse = _dense_reference(q[1:], k[1:], v[1:], None, kind)
    np.testing.assert_allclose(o[1:], want_o, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(lse[1:], want_lse, rtol=2e-5, atol=2e-5)


# -- the sliding window -------------------------------------------------------
#
# ``SlidingWindowMask(w)``: query u sees key v iff 0 <= u - v < w. What is
# new in it: a q block's key range has two partial ends and bare tiles
# between them; a tile can be both ends at once (a window narrower than a
# block); the backward's grid is the band itself, so a grid step's q block
# is the k block's first visible one plus the step, dq's rows start at
# the first k block that sees them (not block 0) and skipped steps lie at
# the band's end; groups of 6 and 9 query heads share a K/V head of 128.

SWA_S = 256
# (window, block_q, block_k): a multiple of the block, not one, narrower
# than a block, unequal blocks both ways, and one past S
SWA_CASES = [(64, 32, 32), (96, 32, 32), (50, 32, 32), (7, 32, 32),
             (100, 32, 64), (100, 64, 32), (33, 64, 16), (1, 32, 32),
             (200, 128, 32), (SWA_S, 32, 32), (SWA_S + 44, 64, 32)]
SWA_IDS = [f"w{w}_bq{a}_bk{b}" for w, a, b in SWA_CASES]


def test_the_window_is_the_band_under_the_diagonal():
    want = np.array([[u - v in (0, 1, 2) for v in range(6)]
                     for u in range(6)])
    assert np.array_equal(fa.SlidingWindowMask(3).dense(6), want)
    assert np.array_equal(fa.SlidingWindowMask(6).dense(6),
                          fa.CAUSAL.dense(6))
    assert np.array_equal(fa.SlidingWindowMask(1).dense(4), np.eye(4, dtype=bool))
    assert fa.SlidingWindowMask(3) == fa.SlidingWindowMask(3) \
        != fa.SlidingWindowMask(4)
    assert isinstance(fa.SlidingWindowMask(3), fa.MaskKind)
    assert fa.SlidingWindowMask(3).name == "sliding_window"
    with pytest.raises(ValueError, match="at least the query's own"):
        fa.SlidingWindowMask(0)
    # the pairs a window allows: w S - w (w - 1) / 2
    assert fa.SlidingWindowMask(512).dense(2048).sum() \
        == 512 * 2048 - 512 * 511 // 2


@pytest.mark.parametrize("window, bq, bk", SWA_CASES, ids=SWA_IDS)
def test_the_kernels_under_the_window(rng, window, bq, bk):
    """Outputs, lse and all three gradients (a live lse cotangent), the
    kernels' bodies in interpret mode against ``reference_attention``
    under ``dense()``, two query heads on one K/V head."""
    kind = fa.SlidingWindowMask(window)
    q, k, v = _qkv(rng, d=16, s=SWA_S)
    k, v = k[:, :, :1], v[:, :, :1]
    w = jnp.asarray(rng.standard_normal((B, H, SWA_S)), jnp.float32)

    def kernels(q, k, v):
        return flash_attention_with_lse(q, k, v, mask_kind=kind,
                                        use_pallas=True, block_q=bq,
                                        block_k=bk)

    def explicit(q, k, v):
        k, v = (fa._repeat_heads(x, H) for x in (k, v))
        return _dense_reference(q, k, v, None, kind)

    o, lse = kernels(q, k, v)
    np.testing.assert_allclose(
        o, reference_attention(q, k, v, mask_kind=kind), rtol=2e-5,
        atol=2e-5)
    np.testing.assert_allclose(lse, explicit(q, k, v)[1], rtol=2e-5,
                               atol=2e-5)
    got = jax.grad(_lse_loss(kernels, w), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(_lse_loss(explicit, w), argnums=(0, 1, 2))(q, k, v)
    for g, e in zip(got, want):
        np.testing.assert_allclose(g, e, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("h, hkv, d", [(2, 2, 128), (6, 1, 128),
                                       (9, 1, 128), (18, 2, 128),
                                       (4, 2, 64)],
                         ids=["group1_d128", "group6_d128", "group9_d128",
                              "two_groups_of_9", "packed_d64_group2"])
def test_the_window_at_the_models_groups(rng, h, hkv, d):
    """Groups of 1, 6 and 9 query heads on a K/V head of 128 (the K/V
    index maps), and one packed width-64 case (K/V repeated): forward and
    every gradient, a window that is no multiple of the block."""
    kind = fa.SlidingWindowMask(72)
    s = 128
    q = jnp.asarray(rng.standard_normal((1, s, h, d)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((1, s, hkv, d)), jnp.float32)
            for _ in range(2))

    def kernels(q, k, v):
        return flash_attention(q, k, v, mask_kind=kind, use_pallas=True,
                               block_q=32, block_k=32)

    def plain(q, k, v):
        return reference_attention(q, k, v, mask_kind=kind)

    np.testing.assert_allclose(kernels(q, k, v), plain(q, k, v), rtol=2e-5,
                               atol=2e-5)
    for got, want in zip(_grads(kernels, q, k, v), _grads(plain, q, k, v)):
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("blocks", [(32, 32), (64, 32), (32, 128)],
                         ids=lambda b: f"bq{b[0]}_bk{b[1]}")
@pytest.mark.parametrize("window", [SWA_S, SWA_S + 1, 4 * SWA_S])
def test_a_window_past_the_sequence_is_causal_to_the_bit(rng, window, blocks):
    """The same tiles in the same order: outputs, lse and gradients equal
    ``CAUSAL``'s bit for bit."""
    q, k, v = _qkv(rng, d=16, s=SWA_S)
    w = jnp.asarray(rng.standard_normal((B, H, SWA_S)), jnp.float32)

    def under(kind):
        def kernels(q, k, v):
            return flash_attention_with_lse(q, k, v, mask_kind=kind,
                                            use_pallas=True, **_kw(blocks))
        return kernels(q, k, v) + jax.grad(
            _lse_loss(kernels, w), argnums=(0, 1, 2))(q, k, v)

    for got, want in zip(under(fa.SlidingWindowMask(window)),
                         under(fa.CAUSAL)):
        assert np.array_equal(np.asarray(got), np.asarray(want))
    assert fa.tiles_visited(fa.SlidingWindowMask(window), SWA_S, *blocks) \
        == fa.tiles_visited(fa.CAUSAL, SWA_S, *blocks)


@pytest.mark.parametrize("window, bq, bk", SWA_CASES, ids=SWA_IDS)
def test_the_window_visits_its_band_and_nothing_else(window, bq, bk):
    """``tiles_visited``'s three counts against a count made from
    ``dense()``; the forward's ranges (far-edge partial, bare, diagonal
    partial: no tile twice, a bare tile holds visible pairs alone); the
    backward's banded grid: every visible pair of a k block is one of its
    steps, in ascending q order, a step past the band fetches a visited
    pair's tiles, and every q block's dq rows start from zero at the
    first k block that sees them and are written out exactly once."""
    s = SWA_S
    kind = fa.SlidingWindowMask(window)
    nq, nk = s // bq, s // bk
    tiles = kind.dense(s).reshape(nq, bq, nk, bk)
    some, every = tiles.any((1, 3)), tiles.all((1, 3))
    forward, backward, square = fa.tiles_visited(kind, s, bq, bk)
    assert (forward, backward, square) == (some.sum(), some.sum(), nq * nk)
    steps = kind.query_steps(s, bq, bk)
    assert steps == min(nq, some.sum(0).max())      # the band, not the square
    with jax.ensure_compile_time_eval():
        for qi in range(nq):
            seen = np.zeros(nk, bool)
            for first, end, partial in kind.key_segments(
                    s, jnp.int32(qi), bq, bk, nk):
                ks = np.arange(int(first), int(end))
                assert not seen[ks].any()
                seen[ks] = True
                assert partial or every[qi, ks].all()
            assert np.array_equal(seen, some[qi])
        zeroed, written = np.zeros(nq, int), np.zeros(nq, int)
        for ki in range(nk):
            worked = []
            for step in range(steps):
                qi = int(kind.query_block(s, jnp.int32(ki), jnp.int32(step),
                                          bq, bk))
                at = int(kind.first_query_block(
                    s, jnp.int32(ki), jnp.int32(step), bq, bk))
                bare, visible = kind.tile(s, jnp.int32(qi), jnp.int32(ki),
                                          bq, bk)
                inside = qi < nq and some[qi, ki]
                assert bool(visible) == inside
                assert some[at, ki] and (at == qi or not inside)
                assert not bool(bare) or every[qi, ki]
                first = bool(kind.first_key_block(
                    s, jnp.int32(qi), jnp.int32(ki), bq, bk))
                last = bool(kind.last_key_block(
                    s, jnp.int32(qi), jnp.int32(ki), bq, bk, nk, visible))
                assert not (first or last) or inside
                if inside:
                    worked.append(qi)
                    # zeroed before anything is added, written after all is
                    assert first == (not some[qi, :ki].any())
                    assert last == (not some[qi, ki + 1:].any())
                    zeroed[qi] += first
                    written[qi] += last
            assert worked == list(np.flatnonzero(some[:, ki]))
        assert (zeroed == 1).all() and (written == 1).all()
    if window < s and bk < s:
        # the case the issue names: a first visible k block that is not 0
        assert some[-1, 0] == (window + bq > s)


def test_the_cells_window_visits_31_of_256_tiles():
    """At the cell's own shape and the kernels' own blocks: 16 x 16 tiles
    of 512, two a q block but the first; and the blocks are the chooser's
    own, the window asks for none (PERF.md section 6, PR 42)."""
    kind = fa.SlidingWindowMask(512)
    blocks = fa._resolve_blocks(8192, 128, jnp.bfloat16, None, None, False,
                                kind.span(8192))
    assert blocks == (512, 512)
    assert fa.tiles_visited(kind, 8192, *blocks) == (31, 31, 256)
    assert kind.query_steps(8192, *blocks) == 2
    assert fa.tiles_visited(fa.CAUSAL, 8192, *blocks)[0] == 136


def test_the_windows_calls_carry_names_of_their_own(rng, caplog):
    """A trace tells a window call from a full one: the two ``pallas_call``
    names hold none of the flash kernels' names; the standing kinds keep
    theirs; the mask kind is said and counted."""
    import logging

    from horovod_tpu.common import metrics, scopes

    assert not any(flash in swa for swa in scopes.SWA_KERNELS
                   for flash in scopes.FLASH_KERNELS)
    q, k, v = _qkv(rng, s=64, d=16)

    def names(kind):
        text = str(jax.make_jaxpr(jax.grad(lambda q: flash_attention(
            q, k, v, mask_kind=kind, use_pallas=True, block_q=16,
            block_k=16).sum()))(q))
        return {n for n in scopes.SWA_KERNELS + scopes.FLASH_KERNELS
                if f"name={n}" in text or n in text}

    fa._say_path.cache_clear()
    with caplog.at_level(logging.INFO, logger="horovod_tpu"):
        assert names(fa.SlidingWindowMask(24)) == set(scopes.SWA_KERNELS)
    assert names(fa.CAUSAL) == {scopes.FLASH_FWD, scopes.FLASH_DKV}
    assert names(BD) == {scopes.FLASH_FWD, scopes.FLASH_DKV}
    said = [r.getMessage() for r in caplog.records
            if "flash_attention" in r.getMessage()]
    assert len(said) == 1 and "mask_kind=sliding_window" in said[0] \
        and "causal=False" in said[0] and "(9 of 16 tiles visited)" in said[0]
    snapshot = metrics.snapshot()
    assert any(s["labels"].get("mask_kind") == "sliding_window"
               and s["labels"]["seq_len"] == "64"
               for s in snapshot["hvd_tpu_flash_attention_traces_total"][
                   "samples"])
    tiles = {s["labels"]["tiles"]: s["value"]
             for s in snapshot["hvd_tpu_flash_attention_tiles"]["samples"]
             if s["labels"]["mask_kind"] == "sliding_window"
             and s["labels"]["seq_len"] == "64"
             and s["labels"]["block_q"] == "16"}
    assert tiles == {"visited": 9, "square": 16}    # 1 + 2 + 3 + 3
