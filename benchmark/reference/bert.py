"""Plain reference for family ``bert``: the forward pass and per-token
loss of the encoder the configuration describes, in straightforward
``jax.numpy`` and float32. No kernels, no ``hvd``, no flax: it reads the
parameter tree the system initialised and nothing else of the program.

It follows the repo's model where that departs from the published BERT
(the configuration's ``assumed`` list: pre-LN blocks with a final
LayerNorm, tanh gelu, token + position embeddings only, the tied matrix
as the whole masked-LM head). The caller sets
``jax.default_matmul_precision("highest")``.
"""

import jax
import jax.numpy as jnp

LN_EPS = 1e-6


def _layer_norm(x, p):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _dense(x, p):
    return x @ p["kernel"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def _attention(q, k, v):
    """Plain unmasked softmax attention on (B, S, H, D)."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        float(q.shape[-1]))
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


def _layer(x, p, heads):
    b, s, h = x.shape
    y = _layer_norm(x, p["LayerNorm_0"])
    q, k, v = (t.reshape(b, s, heads, h // heads)
               for t in jnp.split(_dense(y, p["attn"]["qkv"]), 3, -1))
    x = x + _dense(_attention(q, k, v).reshape(b, s, h), p["attn"]["out"])
    y = _layer_norm(x, p["LayerNorm_1"])
    return x + _dense(_gelu_tanh(_dense(y, p["Dense_0"])), p["Dense_1"])


def token_losses(params, batch, config):
    """float32 (B, S): cross-entropy of the token AT every position (the
    job predicts the input token; which positions count is the
    caller's ``scored``)."""
    tokens = batch["tokens"][:, :-1]
    emb = params["tok_emb"]["embedding"].astype(jnp.float32)
    x = emb[tokens] + params["pos_emb"][None, :tokens.shape[1]]
    layer = jax.checkpoint(_layer, static_argnums=(2,))
    for i in range(config["num_hidden_layers"]):
        x = layer(x, params[f"layer_{i}"], config["num_attention_heads"])
    logits = _layer_norm(x, params["final_ln"]) @ emb.T
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp, tokens[..., None], -1)[..., 0]
