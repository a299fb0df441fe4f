"""Plain reference for family ``laguna``: a decoder whose attention is
full or over a sliding window by a pattern, the two kinds at different
query-head counts, each head's output gated, each kind with its own
rotation; a dense SwiGLU feed-forward in the layers ``mlp_layer_types``
calls ``"dense"`` and a softmax-routed mixture of SwiGLU experts with a
shared expert elsewhere; in straightforward ``jax.numpy`` and float32. No
kernels, no ``hvd``, no flax: it reads the parameter tree the system
initialised and nothing else of the program.

The published model (``poolside/Laguna-S-2.1``, ``config.json``,
``model_type`` ``laguna``), layer l:

    h <- h + Attn_l(RMSNorm(h));   h <- h + FF_l(RMSNorm(h))

``Attn_l``: ``H_l = num_attention_heads_per_layer[l]`` query heads on
``num_key_value_heads`` K/V heads of ``head_dim`` (q head h on K/V head h
// (H_l / Hkv)), no norm on q or k, the rotation below, softmax at scale
head_dim^-0.5 over the keys a query sees: ``w <= u`` in a layer
``layer_types`` calls ``"full_attention"``, ``0 <= u - w <
sliding_window`` in the others; then the gate, ``g = sigmoid(x W_g)`` in
R^{H_l} from the layer's normed input x, head h's output times ``g_h``;
then ``W_o``. The scores are built a block of ``_QUERY_BLOCK`` queries at
a time against the keys the block can see (a window layer's: the block's
own and the ``sliding_window - 1`` before it), each block rematerialised
(72 heads x 8192^2 fp32 scores are 19 GB whole).

Rotation, by ``rope_parameters[layer type]``: the first ``rot =
partial_rotary_factor x head_dim`` channels of a head turn in half-split
pairs (channel i with channel i + rot / 2), the rest pass through; angle
``t inv_freq_i``. ``rope_type`` ``"default"``: ``inv_freq_i = theta^(-2 i
/ rot)``. ``"yarn"``: with ``f_i = theta^(-2 i / rot)`` and L =
``original_max_position_embeddings``,

    corr(r) = rot ln(L / (2 pi r)) / (2 ln theta)
    low = max(floor(corr(beta_fast)), 0)
    high = min(ceil(corr(beta_slow)), rot - 1)
    ramp_i = clip((i - low) / (high - low), 0, 1)
    inv_freq_i = f_i (1 - ramp_i) + f_i / factor * ramp_i

and cos and sin both times ``attention_factor``.

``FF_l``, dense: ``W2(SiLU(W1 x) * W3 x)``. Sparse: ``p = softmax(W_r x)``
over the router's width, the top ``num_experts_per_tok``, their weights
``moe_routed_scaling_factor * p_i / sum_chosen p`` on the experts'
OUTPUTS; a dense loop over the experts held here, each on every token
with the token's weight for it (0 where it was not chosen); plus one
shared SwiGLU expert on every token, unweighted. A final RMSNorm and an
untied head.

The share of the deployment (the configuration's ``deployment``): the
parameter tree holds the experts and the vocabulary rows of ONE chip; the
reference computes that chip's part and leaves out, as the program does,
what the experts held elsewhere would have added. ``held_experts_first``
says which of the router's columns are the held experts'.

The caller sets ``jax.default_matmul_precision("highest")``.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

_QUERY_BLOCK = 512
FULL = "full_attention"


def _rms_norm(x, p, eps):
    return x / jnp.sqrt((x ** 2).mean(-1, keepdims=True) + eps) * p["scale"]


def inv_freq(rope, rot):
    """The ``rot / 2`` inverse frequencies of one ``rope_parameters``
    entry, float64 (numpy)."""
    i = np.arange(rot // 2, dtype=np.float64)
    theta = float(rope["rope_theta"])
    plain = theta ** (-2.0 * i / rot)
    if rope["rope_type"] == "default":
        return plain

    def corr(turns):
        return rot * math.log(rope["original_max_position_embeddings"] / (
            2 * math.pi * turns)) / (2 * math.log(theta))

    low = max(math.floor(corr(rope["beta_fast"])), 0)
    high = min(math.ceil(corr(rope["beta_slow"])), rot - 1)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return plain * (1.0 - ramp) + plain / rope["factor"] * ramp


def _rotary(x, rope):
    """(B, S, H, D): the first ``rot`` channels turned in half-split
    pairs, the others as they are."""
    s, d = x.shape[1], x.shape[-1]
    rot = int(rope["partial_rotary_factor"] * d)
    half = rot // 2
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq(rope, rot), jnp.float32)
    scale = rope.get("attention_factor", 1.0)
    cos = (jnp.cos(angle) * scale)[:, None, :]
    sin = (jnp.sin(angle) * scale)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., rot:]], -1)


def visible(rows, cols, window):
    """Whether query ``rows`` sees key ``cols``; ``window`` None: every
    key up to its own."""
    seen = rows >= cols
    return seen if window is None else seen & (rows - cols < window)


def _attend_block(q, k, v, start, first, window):
    """Queries ``start ..`` of a sequence against keys ``first ..``; k
    and v are cut to those keys by the caller."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    seen = visible((start + jnp.arange(q.shape[1]))[:, None],
                   (first + jnp.arange(k.shape[1]))[None, :], window)
    scores = jnp.where(seen[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


_attend_block = jax.checkpoint(_attend_block, static_argnums=(3, 4, 5))


def _attention(u, p, config, kind, heads):
    b, s, _ = u.shape
    width = config["head_dim"]
    rope = config["rope_parameters"][kind]
    window = None if kind == FULL else config["sliding_window"]
    # the head counts are the configuration's, not the tree's: a tree of
    # other shapes is an error here, not another model
    q, k, v = ((u @ p[n]["kernel"]).reshape(b, s, count, width)
               for n, count in (("q", heads),
                                ("k", config["num_key_value_heads"]),
                                ("v", config["num_key_value_heads"])))
    q, k = _rotary(q, rope), _rotary(k, rope)
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, 2), jnp.repeat(v, group, 2)
    block = min(_QUERY_BLOCK, s)
    outs = []
    for start in range(0, s, block):
        first = 0 if window is None else max(0, start - window + 1)
        outs.append(_attend_block(
            q[:, start:start + block], k[:, first:start + block],
            v[:, first:start + block], start, first, window))
    o = jnp.concatenate(outs, 1)                        # (B, S, H, D)
    gate = jax.nn.sigmoid(u @ p["gate"]["kernel"])      # (B, S, H)
    return (o * gate[..., None]).reshape(b, s, -1) @ p["o"]["kernel"]


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _experts(x, p, config):
    scores = jax.nn.softmax(x @ p["router"], -1)
    chosen, experts = jax.lax.top_k(scores, config["num_experts_per_tok"])
    weights = chosen / chosen.sum(-1, keepdims=True) \
        * config["moe_routed_scaling_factor"]
    first = config["held_experts_first"]
    y = _swiglu(x, *(p[f"shared_{n}"]["kernel"]
                     for n in ("gate", "up", "down")))
    for e in range(p["experts_gate"].shape[0]):
        weight = (weights * (experts == first + e)).sum(-1)
        y = y + weight[..., None] * _swiglu(
            x, p["experts_gate"][e], p["experts_up"][e],
            p["experts_down"][e])
    return y


def _layer(h, p, config, kind, heads, dense):
    eps = config["rms_norm_eps"]
    h = h + _attention(_rms_norm(h, p["op_norm"], eps), p["mixer"], config,
                       kind, heads)
    x = _rms_norm(h, p["ffn_norm"], eps)
    if dense:
        return h + _swiglu(x, *(p["ffn"][n]["kernel"]
                                for n in ("gate", "up", "down")))
    return h + _experts(x, p["ffn"], config)


class _Frozen:
    """The configuration as a static argument: hashed by identity."""

    def __init__(self, config):
        self._config = config

    def __getitem__(self, key):
        return self._config[key]


def states(params, tokens, config):
    """float32 (B, S, hidden): the normed state the head reads."""
    layer = jax.checkpoint(_layer, static_argnums=(2, 3, 4, 5))
    frozen = _Frozen(config)
    h = params["tok_emb"]["embedding"][tokens]
    for i in range(config["num_hidden_layers"]):
        h = layer(h, params[f"layer{i}"], frozen, config["layer_types"][i],
                  config["num_attention_heads_per_layer"][i],
                  config["mlp_layer_types"][i] == "dense")
    return _rms_norm(h, params["final_norm"], config["rms_norm_eps"])


def logits(params, tokens, config):
    """float32 (B, S, vocab). For small sizes."""
    return states(params, tokens, config) @ params["lm_head"]["kernel"]


@jax.checkpoint
def _cross_entropy(z, kernel, labels):
    logp = jax.nn.log_softmax(z @ kernel, -1)
    return -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]


def token_losses(params, batch, config):
    """float32 (B, S): the loss of the next token at every position."""
    tokens = batch["tokens"]
    return _cross_entropy(states(params, tokens[:, :-1], config),
                          params["lm_head"]["kernel"], tokens[:, 1:])
