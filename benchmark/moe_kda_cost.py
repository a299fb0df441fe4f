"""Operations and bytes of the two kernel families the expert,
linear-attention cells add, from shapes and from a step's counted routes:
the least a step's work could cost, for ``kda_roofline_pct`` and
``moe_expert_roofline_pct``.

Convention, as ``flops.py``'s for the flash kernels: what the algorithm
needs, forward once and backward once, nothing recomputed (the layers are
rematerialised: a forward run again is the program's cost, not the
mathematics'); a FLOP is a multiply or an add of a matmul; bytes are what
must cross HBM at least once, operands at the width the program stores
them (bf16 activations and matmul operands, fp32 decays, fp32 weight
gradients). A share over 100% is a wrong count here.

**KDA** (``horovod_tpu/ops/linear_attention.py``), a head, a token,
chunks of C, Dk = Dv = d. Forward products: the pair matrices A and P, 2
C d each; the unit triangular solve for W and U0 over 2 d columns, C d
multiply-adds = 2 C d FLOPs; W S, Q S and K^T U against the d x d state,
2 d d each; P U, 2 C d: ``8 C d + 6 d d``. The backward is twice that
(each product has two transposes). Bytes: forward reads q, k, v (bf16),
log alpha (fp32) and beta (fp32) and writes o (bf16): 12 d + 4; the
backward reads them and do again and writes dq, dk, dv (bf16), d log
alpha and d beta (fp32): 22 d + 8.

**The grouped SwiGLU experts** (``parallel/moe.py``
``held_experts_layer``), a layer with R routes to G held experts of width
w over hidden h: three matmuls a route, 2 h w each, forward; the backward
twice that: ``18 R h w`` FLOPs. Bytes: the three banks read as bf16 in
the forward and again in the backward, their gradients written in fp32
(8 bytes a weight, 3 G h w weights); a route's row read (bf16) and its
result written (fp32) in the forward, the row and the result's cotangent
read and the row's gradient written in the backward: 14 h bytes a route.
With 200 rows an expert the banks are the traffic, and the floor is
memory's.
"""

from benchmark.flops import roofline_seconds

KDA_CHUNK = 64      # horovod_tpu/ops/linear_attention.py CHUNK


def kda_layers(config):
    return config["num_hidden_layers"] - sum(
        1 for i in config["gqa_layers"] if i < config["num_hidden_layers"])


def kda_flops_per_token_forward(config, chunk=KDA_CHUNK):
    """Forward FLOPs a token of one KDA layer's recurrence, all held
    heads."""
    linear = config["linear_attn_config"]
    d = linear["head_dim"]
    return linear["num_heads"] * (8 * chunk * d + 6 * d * d)


def kda_step_cost(config, tokens, chunk=KDA_CHUNK):
    """``(FLOPs, bytes)`` of a step's KDA recurrences over ``tokens``
    tokens (all sequences together), every KDA layer, forward and
    backward."""
    linear = config["linear_attn_config"]
    d, heads = linear["head_dim"], linear["num_heads"]
    calls = kda_layers(config) * tokens
    return (3.0 * calls * kda_flops_per_token_forward(config, chunk),
            float(calls * heads * ((12 * d + 4) + (22 * d + 8))))


def expert_step_cost(config, routes):
    """``(FLOPs, bytes)`` of a step's grouped expert matmuls: ``routes``
    token-routes reached a held expert, all layers together; every
    layer's banks cross HBM whatever the routes."""
    hidden, width = config["hidden_size"], config["moe_intermediate_size"]
    weights = 3 * config["n_routed_experts"] * hidden * width
    return (18.0 * routes * hidden * width,
            float(config["num_hidden_layers"] * 8 * weights
                  + 14 * routes * hidden))


def least_ms(cost, peaks):
    """``(milliseconds, bound)`` of a ``(FLOPs, bytes)`` pair on one
    chip."""
    seconds, bound = roofline_seconds(*cost, peaks)
    return 1e3 * seconds, bound


def config_of_metric(name):
    """The configuration of the cells that report per-layer metric
    ``name`` (a reader is given the run's record, which does not say):
    the metric's ``workloads`` in ``BENCHMARK.json`` name them, and they
    share one configuration."""
    from benchmark.catalog import Catalog

    catalog = Catalog()
    cells = catalog._entry("per_layer", name)["workloads"]
    configs = {catalog._entry("workloads", cell)["config"] for cell in cells}
    if len(configs) != 1:
        raise ValueError(f"{name} is reported by cells of {sorted(configs)}")
    return catalog.config(configs.pop())


def counted(metric):
    """The value of the program's unlabelled gauge ``metric`` as
    ``horovod_tpu.metrics()`` has it, or ``None``."""
    import horovod_tpu as hvd

    samples = hvd.metrics().get(metric, {}).get("samples", [])
    return float(samples[0]["value"]) if samples else None
