"""Plain reference for family ``gpt``: the forward pass and per-token
loss of the decoder the configuration describes, in straightforward
``jax.numpy`` and float32. No kernels, no ``hvd``, no flax: it reads the
parameter tree the system initialised and nothing else of the program.

Departures from the published GPT-2 are the configuration's ``assumed``
list (rotary positions, LayerNorm epsilon 1e-6); the mathematics below is
otherwise the textbook pre-LN decoder with a tied head. The caller sets
``jax.default_matmul_precision("highest")``.
"""

import jax
import jax.numpy as jnp

LN_EPS = 1e-6
ROPE_BASE = 10000.0


def _layer_norm(x, p):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _dense(x, p):
    return x @ p["kernel"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def _rope(x):
    """Rotate pairs (i, i + d/2) of each head by position * base^(-i/(d/2))."""
    s, half = x.shape[1], x.shape[-1] // 2
    freqs = ROPE_BASE ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, causal):
    """Plain softmax attention on (B, S, H, D): the S x S matrix in full."""
    s, d = q.shape[1], q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(d))
    if causal:
        visible = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
        scores = jnp.where(visible[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


def _layer(x, p, heads):
    b, s, h = x.shape
    y = _layer_norm(x, p["LayerNorm_0"])
    q, k, v = (t.reshape(b, s, heads, h // heads)
               for t in jnp.split(_dense(y, p["attn"]["qkv"]), 3, -1))
    o = _attention(_rope(q), _rope(k), v, causal=True).reshape(b, s, h)
    x = x + _dense(o, p["attn"]["out"])
    y = _layer_norm(x, p["LayerNorm_1"])
    return x + _dense(_gelu_tanh(_dense(y, p["mlp_in"])), p["mlp_out"])


def token_losses(params, batch, config):
    """float32 (B, S): cross-entropy of the next token at every position."""
    tokens = batch["tokens"]
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    emb = params["tok_emb"]["embedding"].astype(jnp.float32)
    x = emb[inputs]
    layer = jax.checkpoint(_layer, static_argnums=(2,))  # so long sequences fit
    for i in range(config["n_layer"]):
        x = layer(x, params[f"layer{i}"], config["n_head"])
    logits = _layer_norm(x, params["final_ln"]) @ emb.T
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
