"""Plain reference for family ``ouro``: a looped decoder (Ouro / LoopLM)
and its exit-weighted training loss, in straightforward ``jax.numpy`` and
float32. No kernels, no ``hvd``, no flax: it reads the parameter tree the
system initialised and nothing else of the program.

The published model (``ByteDance/Ouro-2.6B``, ``config.json`` and model
card): a stack of ``num_hidden_layers`` sandwich-normed layers (RMSNorm
before and after attention and before and after the SwiGLU MLP, rotary
positions, no biases) run ``total_ut_steps`` times with the same weights;
after each pass the final norm, then the untied head and a sigmoid exit
gate. Per position, with ``l_t`` the gate of pass t:

    p_t = l_t prod_{j<t} (1 - l_j)  (t < T),   p_T = prod_{j<T} (1 - l_j)
    loss = sum_t p_t CE(W_head z_t, y) - beta H(p),   H(p) = -sum_t p_t log p_t

Departures from the published description, each also a line of the
configuration's ``assumed``:

- no bias anywhere but the gate's;
- the normed state z_t (after the final norm) is what the next pass
  starts from;
- ``beta`` is the configuration's ``exit_entropy_beta`` (0.05): the
  published config has no such key;
- ``early_exit_threshold`` is an inference threshold: not used here.

``jax.checkpoint`` around a layer application and around an exit's head,
so that one row of S2048 fits beside 12 bytes a parameter; the S x S
scores are kept in full; the passes are a ``lax.scan`` (``_passes``). The caller sets
``jax.default_matmul_precision("highest")``.
"""

import jax
import jax.numpy as jnp


def _rms_norm(x, p, eps):
    return x / jnp.sqrt((x ** 2).mean(-1, keepdims=True) + eps) * p["scale"]


def _rope_tables(s, width, base):
    """cos and sin (1, S, 1, D/2) of position * base^(-i/(D/2))."""
    half = width // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    return jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]


def _rope(x, cos, sin):
    """Rotate pairs (i, i + D/2) of each head by the position's angles."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _causal_attention(q, k, v, visible):
    """Plain softmax attention on (B, S, H, D): the S x S matrix in full."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(q.shape[-1]))
    scores = jnp.where(visible[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


def _layer(x, p, cos, sin, visible, heads, eps):
    b, s, _ = x.shape
    y = _rms_norm(x, p["attn_norm"], eps)
    q, k, v = ((y @ p[n]["kernel"]).reshape(b, s, heads, -1)
               for n in ("q", "k", "v"))
    o = _causal_attention(_rope(q, cos, sin), _rope(k, cos, sin), v, visible)
    o = o.reshape(b, s, -1) @ p["o"]["kernel"]
    x = x + _rms_norm(o, p["attn_out_norm"], eps)
    y = _rms_norm(x, p["mlp_norm"], eps)
    y = (jax.nn.silu(y @ p["gate"]["kernel"]) * (y @ p["up"]["kernel"])) \
        @ p["down"]["kernel"]
    return x + _rms_norm(y, p["mlp_out_norm"], eps)


def _passes(params, tokens, config, read):
    """Run ``tokens`` (B, S) through the stack ``total_ut_steps`` times.
    Returns ``(read(z_t), gate l_t)`` stacked over the passes, z_t the
    normed state after pass t. The loop over the passes is a
    ``lax.scan`` and not a Python loop for the compiler's sake alone: the
    body is the same at every pass, and unrolled four times the fp32
    program took 160 s to compile at the cell's size and was too large
    for the persistent cache (1.3 GB of code; PERF.md, PR 27)."""
    layer = jax.checkpoint(_layer, static_argnums=(5, 6))
    s = tokens.shape[1]
    # Made once, for every layer application: the rotary tables and who
    # sees whom.
    cos, sin = _rope_tables(s, config["head_dim"], float(config["rope_theta"]))
    visible = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def one_pass(h, _):
        for i in range(config["num_hidden_layers"]):
            h = layer(h, params[f"layer{i}"], cos, sin, visible,
                      config["num_attention_heads"], config["rms_norm_eps"])
        z = _rms_norm(h, params["final_norm"], config["rms_norm_eps"])
        gate = jax.nn.sigmoid((z @ params["exit_gate"]["kernel"])[..., 0]
                              + params["exit_gate"]["bias"][0])
        return z, (read(z), gate)

    return jax.lax.scan(one_pass, params["tok_emb"]["embedding"][tokens],
                        None, length=config["total_ut_steps"])[1]


def exits(params, tokens, config):
    """``(states, gates)``: the normed state z_t (T, B, S, hidden) after
    each pass over ``tokens`` (B, S), and the gate l_t (T, B, S)."""
    return _passes(params, tokens, config, lambda z: z)


def exit_distribution(gates):
    """p_t (T, B, S) from the gates l_t (T, B, S)."""
    stay, p = jnp.ones_like(gates[0]), []
    for gate in gates[:-1]:
        p.append(gate * stay)
        stay = stay * (1.0 - gate)
    return jnp.stack(p + [stay])


def exit_logits(params, tokens, config):
    """float32 (T, B, S, vocab): every exit's logits. For small sizes."""
    return exits(params, tokens, config)[0] @ params["lm_head"]["kernel"]


@jax.checkpoint
def _cross_entropy(z, head, labels):
    logp = jax.nn.log_softmax(z @ head, -1)
    return -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]


def token_losses(params, batch, config):
    """float32 (B, S): the exit-weighted loss of the next token at every
    position."""
    tokens = batch["tokens"]
    ce, gates = _passes(
        params, tokens[:, :-1], config,
        lambda z: _cross_entropy(z, params["lm_head"]["kernel"],
                                 tokens[:, 1:]))
    p = exit_distribution(gates)
    entropy = -jnp.where(p > 0, p * jnp.log(p), 0.0).sum(0)
    return (p * ce).sum(0) - config["exit_entropy_beta"] * entropy
