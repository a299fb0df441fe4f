"""Plain reference for family ``sdar``: a decoder of rotary grouped-query
attention (an RMSNorm over each head's channels on q and on k) and a
softmax-routed mixture of SwiGLU experts in every layer, trained by block
diffusion, in straightforward ``jax.numpy`` and float32. No kernels, no
``hvd``, no flax: it reads the parameter tree the system initialised and
nothing else of the program; the noise it writes out itself, from the
specification below.

The published model (``JetLM/SDAR-30B-A3B-Chat``, ``config.json``,
``model_type`` ``sdar_moe``), layer l:

    h <- h + Attn(RMSNorm_op(h));   h <- h + MoE(RMSNorm_ffn(h))

Attention: q = W_q u as heads of ``head_dim``, k = W_k u and v = W_v u as
``num_key_value_heads`` heads; RMSNorm over each head's channels on q and
on k; rotary positions over the whole head width in half-split pairs
(channel i with channel i + head_dim / 2), angle pos theta^(-2 i /
head_dim); softmax at scale head_dim^-0.5 under the mask below, q head h
on K/V head h // group; ``W_o``. MoE: ``p = softmax(W_r x)`` over the
router's width, the top ``num_experts_per_tok``, their weights ``p_i /
sum_chosen p``; a dense loop over the experts held here, each on every
token with the token's weight for it (0 where it was not chosen). A final
RMSNorm and an untied head.

Block-diffusion training (the configuration's ``assumed``): a row of the
batch is L tokens x and, in column L, its noise seed. The row's key is
``fold_in(PRNGKey(noise_seed), seed)``, split once: the first half draws a
rate a block of ``block_length`` tokens, ``t = 1e-3 + (1 - 1e-3) u`` with
u uniform on [0, 1); the second a uniform a token, and the token is
masked (replaced by ``mask_token_id``: m_i = 1) where its uniform lies
under its block's rate. The model runs over ``[noisy ; x]``, 2L
positions, both copies at positions 0 ... L-1, with n(i) = (i mod L) //
block_length, and query u sees key w iff

    u noisy, w noisy, n(u) == n(w)      (a block sees itself, both ways)
    u noisy, w clean, n(w) <  n(u)      (the clean past, strictly)
    u clean, w clean, n(w) <= n(u)      (block-causal)
    u clean, w noisy: never

``token_losses`` is ``m_i CE(head(norm(h_i)), x_i) / t_{n(i)}`` of the
noisy copy's position i (no shift), which the job's weights 1 / (rows L)
sum to the training loss. The scores are built a block of
``_QUERY_BLOCK`` queries at a time against the keys that block can see,
each block rematerialised (32 heads x 8192^2 fp32 scores are 8.6 GB a row
whole), the mask of a block from the four rules on the positions' own
indices.

The share of the deployment (the configuration's ``deployment``): the
parameter tree holds the experts and the vocabulary rows of ONE chip; the
reference computes that chip's part and leaves out, as the program does,
what the experts held elsewhere would have added. ``held_experts_first``
says which of the router's columns are the held experts'.

The caller sets ``jax.default_matmul_precision("highest")``.
"""

import jax
import jax.numpy as jnp

_QUERY_BLOCK = 1024
_RATE_FLOOR = 1e-3


def noise(tokens, config):
    """``(x (B, L), masked (B, L) bool, rates (B, L) fp32)``: the data,
    which of its tokens the noisy copy masks, and each token's block's
    rate, a row at a time from the row's own seed."""
    x, seeds = tokens[:, :-1], tokens[:, -1]
    length, block = x.shape[1], config["block_length"]
    root = jax.random.PRNGKey(config["noise_seed"])
    masked, rates = [], []
    for r in range(x.shape[0]):
        for_rates, for_masks = jax.random.split(
            jax.random.fold_in(root, seeds[r]))
        t = _RATE_FLOOR + (1.0 - _RATE_FLOOR) * jax.random.uniform(
            for_rates, (length // block,), jnp.float32)
        t = jnp.repeat(t, block)
        masked.append(jax.random.uniform(for_masks, (length,), jnp.float32)
                      < t)
        rates.append(t)
    return x, jnp.stack(masked), jnp.stack(rates)


def _rms_norm(x, p, eps):
    return x / jnp.sqrt((x ** 2).mean(-1, keepdims=True) + eps) * p["scale"]


def _rotary(x, positions, theta):
    """(B, S, H, D) at ``positions`` (S,): channel i and channel i + D / 2
    turned by the angle pos theta^(-2 i / D)."""
    half = x.shape[-1] // 2
    angle = positions.astype(jnp.float32)[:, None] \
        * theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def visible(u, w, length, block):
    """The four rules: whether query index u sees key index w, both
    indices into ``[noisy ; clean]`` of ``length`` positions each."""
    u_noisy, w_noisy = u < length, w < length
    nu, nw = (u % length) // block, (w % length) // block
    return (u_noisy & w_noisy & (nu == nw)) \
        | (u_noisy & ~w_noisy & (nw < nu)) \
        | (~u_noisy & ~w_noisy & (nw <= nu))


@jax.checkpoint
def _attend_block(q, k, v, u, w, length, block):
    """Queries with indices ``u`` against the keys with indices ``w``."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    seen = visible(u[:, None], w[None, :], length, block)
    scores = jnp.where(seen[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


def _attention(x, p, config):
    b, s, _ = x.shape
    length, block = s // 2, config["block_length"]
    width = config["head_dim"]
    eps, theta = config["rms_norm_eps"], float(config["rope_theta"])
    positions = jnp.arange(s) % length
    q, k, v = ((x @ p[n]["kernel"]).reshape(b, s, -1, width)
               for n in ("q", "k", "v"))
    q = _rotary(_rms_norm(q, p["q_norm"], eps), positions, theta)
    k = _rotary(_rms_norm(k, p["k_norm"], eps), positions, theta)
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, 2), jnp.repeat(v, group, 2)
    at = jnp.arange(s)
    step = min(_QUERY_BLOCK, length)
    outs = []
    for start in range(0, s, step):
        # the keys a block of queries can see at all: a noisy block its
        # own rows of the noisy copy and the clean copy up to them, a
        # clean block the clean copy up to its own rows
        end = start % length + step
        keys = at[length:length + end]
        if start < length:
            keys = jnp.concatenate([at[start:start + step], keys])
        outs.append(_attend_block(q[:, start:start + step], k[:, keys],
                                  v[:, keys], at[start:start + step], keys,
                                  length, block))
    return jnp.concatenate(outs, 1).reshape(b, s, -1) @ p["o"]["kernel"]


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _experts(x, p, config):
    scores = jax.nn.softmax(x @ p["router"], -1)
    chosen, experts = jax.lax.top_k(scores, config["num_experts_per_tok"])
    weights = chosen / chosen.sum(-1, keepdims=True)
    first = config["held_experts_first"]
    y = 0.0
    for e in range(p["experts_gate"].shape[0]):
        weight = (weights * (experts == first + e)).sum(-1)
        y = y + weight[..., None] * _swiglu(
            x, p["experts_gate"][e], p["experts_up"][e],
            p["experts_down"][e])
    return y


def _layer(h, p, config):
    eps = config["rms_norm_eps"]
    h = h + _attention(_rms_norm(h, p["op_norm"], eps), p["mixer"], config)
    return h + _experts(_rms_norm(h, p["ffn_norm"], eps), p["ffn"], config)


class _Frozen:
    """The configuration as a static argument: hashed by identity."""

    def __init__(self, config):
        self._config = config

    def __getitem__(self, key):
        return self._config[key]


def noisy_states(params, x, masked, config):
    """float32 (B, L, hidden): the normed states of the noisy copy, which
    the head reads, of the model run over ``[noisy ; x]``."""
    layer = jax.checkpoint(_layer, static_argnums=(2,))
    frozen = _Frozen(config)
    both = jnp.concatenate(
        [jnp.where(masked, config["mask_token_id"], x), x], 1)
    h = params["tok_emb"]["embedding"][both]
    for i in range(config["num_hidden_layers"]):
        h = layer(h, params[f"layer{i}"], frozen)
    return _rms_norm(h[:, :x.shape[1]], params["final_norm"],
                     config["rms_norm_eps"])


def noisy_logits(params, tokens, config):
    """float32 (B, L, vocab): the noisy copy's logits. For small sizes."""
    x, masked, _ = noise(tokens, config)
    return noisy_states(params, x, masked, config) \
        @ params["lm_head"]["kernel"]


@jax.checkpoint
def _cross_entropy(z, kernel, labels):
    logp = jax.nn.log_softmax(z @ kernel, -1)
    return -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]


def token_losses(params, batch, config):
    """float32 (B, L): ``m_i CE_i / t_{n(i)}`` at every position of the
    noisy copy; the job's weights 1 / (rows L) sum it to the loss."""
    x, masked, rates = noise(batch["tokens"], config)
    ce = _cross_entropy(noisy_states(params, x, masked, config),
                        params["lm_head"]["kernel"], x)
    return masked * ce / rates
