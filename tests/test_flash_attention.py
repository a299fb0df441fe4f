"""Flash-attention Pallas kernel vs the jnp reference — forward AND
backward (custom-VJP kernels), run in interpret mode on CPU so the real
kernel bodies execute (same tier as tests/test_pallas_kernels.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.flash_attention import (flash_attention,
                                             reference_attention)

B, S, H, D = 2, 256, 2, 128


def _qkv(rng, d=D, s=S, dtype=np.float32):
    return (rng.standard_normal((B, s, H, d)).astype(dtype),
            rng.standard_normal((B, s, H, d)).astype(dtype),
            rng.standard_normal((B, s, H, d)).astype(dtype))


def test_forward_matches_reference(rng):
    q, k, v = _qkv(rng)
    out = flash_attention(q, k, v, use_pallas=True)
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_forward_causal(rng):
    q, k, v = _qkv(rng)
    out = flash_attention(q, k, v, causal=True, use_pallas=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_forward_key_mask(rng):
    q, k, v = _qkv(rng)
    mask = (rng.random((B, S)) > 0.3).astype(np.float32)
    mask[:, 0] = 1.0  # at least one visible key per batch
    out = flash_attention(q, k, v, mask=mask, use_pallas=True)
    ref = reference_attention(q, k, v, mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_forward_padded_head_dim(rng):
    # D=64 (BERT-large) pads to the 128-lane width inside the wrapper.
    q, k, v = _qkv(rng, d=64)
    out = flash_attention(q, k, v, use_pallas=True)
    ref = reference_attention(q, k, v)
    assert out.shape == (B, S, H, 64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_backward_matches_reference(rng, causal):
    q, k, v = _qkv(rng)
    mask = (rng.random((B, S)) > 0.2).astype(np.float32)
    mask[:, 0] = 1.0

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, mask=mask, causal=causal,
                                use_pallas=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (reference_attention(q, k, v, mask=mask,
                                    causal=causal) ** 2).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), rtol=5e-3, atol=5e-3,
            err_msg=f"d{name} mismatch (causal={causal})")


def test_backward_padded_head_dim(rng):
    q, k, v = _qkv(rng, d=64)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    g_flash = jax.grad(
        loss(lambda q, k, v: flash_attention(q, k, v, use_pallas=True)),
        argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(reference_attention),
                     argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=5e-3, atol=5e-3)


def test_bf16_inputs(rng):
    q, k, v = _qkv(rng, dtype=np.float32)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = flash_attention(qb, kb, vb, use_pallas=True)
    assert out.dtype == jnp.bfloat16
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=2e-2, atol=2e-2)


def test_fallback_off_tpu_and_odd_seq(rng):
    # use_pallas=None off-TPU and an un-tileable sequence both fall back
    # to the reference path — identical result, no error.
    q, k, v = _qkv(rng, s=130)  # 130 has no multiple-of-8 divisor <= 128
    out = flash_attention(q, k, v)
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_declining_on_a_tpu_is_said_once(rng, monkeypatch, caplog):
    """Where the kernel is the path (a TPU), an un-tileable S runs the
    reference — correct, but O(S^2) on the device: one WARNING with the
    shape, not silence, and not one per trace."""
    import logging

    from horovod_tpu.ops import flash_attention as fa
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
