"""BERT encoder in Flax — the second benchmark workload (BERT-large
pretraining, BASELINE.json config #3; reference exercises BERT via
examples/pytorch scripts).

TPU-first choices: bf16 compute / fp32 params, fused QKV projection (one
big matmul for the MXU instead of three), no dropout on the benchmark path
(matching synthetic-benchmark methodology), and a masked-LM head reusing
the embedding matrix. Attention accepts an optional ``attend_fn`` so the
sequence-parallel implementations (ring attention / Ulysses, in
horovod_tpu/parallel/) can slot in without touching the model.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp


# Default attention: the Pallas flash kernel on TPU (O(S) memory,
# MXU-blocked), the numerically identical jnp reference elsewhere.
from ..common import scopes  # noqa: E402
from ..ops.flash_attention import attend as default_attend  # noqa: E402


class SelfAttention(nn.Module):
    num_heads: int
    dtype: Any = jnp.bfloat16
    attend_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, mask=None):
        b, s, h = x.shape
        head_dim = h // self.num_heads
        with jax.named_scope(scopes.MIXER_PROJ):
            qkv = nn.Dense(3 * h, dtype=self.dtype,
                           param_dtype=jnp.float32, name="qkv")(x)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(b, s, self.num_heads, head_dim)
            k = k.reshape(b, s, self.num_heads, head_dim)
            v = v.reshape(b, s, self.num_heads, head_dim)
        attend = self.attend_fn or default_attend
        o = attend(q, k, v, mask)   # the kernels' own names, no scope's
        with jax.named_scope(scopes.MIXER_PROJ):
            o = o.reshape(b, s, h)
            return nn.Dense(h, dtype=self.dtype, param_dtype=jnp.float32,
                            name="out")(o)


class TransformerLayer(nn.Module):
    num_heads: int
    mlp_dim: int
    dtype: Any = jnp.bfloat16
    attend_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, mask=None):
        with jax.named_scope(scopes.NORM):
            y = nn.LayerNorm(dtype=self.dtype, param_dtype=jnp.float32)(x)
        y = SelfAttention(self.num_heads, self.dtype,
                          self.attend_fn, name="attn")(y, mask)
        x = x + y
        with jax.named_scope(scopes.NORM):
            y = nn.LayerNorm(dtype=self.dtype, param_dtype=jnp.float32)(x)
        with jax.named_scope(scopes.MLP):
            y = nn.Dense(self.mlp_dim, dtype=self.dtype,
                         param_dtype=jnp.float32)(y)
            y = nn.gelu(y)
            y = nn.Dense(x.shape[-1], dtype=self.dtype,
                         param_dtype=jnp.float32)(y)
        return x + y


class Bert(nn.Module):
    vocab_size: int = 30522
    hidden_size: int = 1024
    num_layers: int = 24
    num_heads: int = 16
    mlp_dim: int = 4096
    max_len: int = 512
    dtype: Any = jnp.bfloat16
    attend_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, input_ids, mask=None, positions=None):
        """``positions``: optional (B, S) global position ids — REQUIRED
        under sequence parallelism, where each device holds a seq shard
        and local indices 0..S_local-1 would select the wrong embeddings
        (pass ``idx*S_local + arange(S_local)``)."""
        emb = nn.Embed(self.vocab_size, self.hidden_size,
                       param_dtype=jnp.float32, dtype=self.dtype,
                       name="tok_emb")
        with jax.named_scope(scopes.EMBED):
            x = emb(input_ids)
            pos = self.param("pos_emb", nn.initializers.normal(0.02),
                             (self.max_len, self.hidden_size), jnp.float32)
            if positions is None:
                pe = pos[None, :x.shape[1]]
            else:
                pe = jnp.take(pos, positions, axis=0)
            x = x + pe.astype(self.dtype)
        for i in range(self.num_layers):
            x = TransformerLayer(self.num_heads, self.mlp_dim, self.dtype,
                                 self.attend_fn, name=f"layer_{i}")(x, mask)
        with jax.named_scope(scopes.NORM):
            x = nn.LayerNorm(dtype=self.dtype, param_dtype=jnp.float32,
                             name="final_ln")(x)
        # Masked-LM logits via embedding tie (standard BERT pretraining).
        # bf16 operands + fp32 accumulation: the V x H head matmul at
        # fp32 runs ~4x off the MXU's bf16 peak; accumulating in fp32
        # keeps the softmax stable (the standard LM-head recipe).
        with jax.named_scope(scopes.LM_HEAD):
            logits = jax.lax.dot_general(
                x.astype(self.dtype), emb.embedding.astype(self.dtype),
                (((x.ndim - 1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        return logits


def bert_large(**kw) -> Bert:
    return Bert(hidden_size=1024, num_layers=24, num_heads=16,
                mlp_dim=4096, **kw)


def bert_base(**kw) -> Bert:
    return Bert(hidden_size=768, num_layers=12, num_heads=12,
                mlp_dim=3072, **kw)


def bert_tiny(**kw) -> Bert:
    """For tests/dry-runs. Any field (incl. max_len) is overridable."""
    cfg = dict(vocab_size=1024, hidden_size=64, num_layers=2, num_heads=4,
               mlp_dim=128, max_len=128)
    cfg.update(kw)
    return Bert(**cfg)
